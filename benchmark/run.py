"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which imports JAX itself and starts no child. It loads, warms
up, measures for `--seconds`, prints one JSON object as the last line of
its standard output (its last key, `compared`, and the last lines on standard
error: every number `correct` was decided from beside its limit) and exits
0. Without an accelerator of a kind the
peaks table knows, or with fewer chips than the cell asks for, it prints no
result and exits 3.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import stats  # noqa: E402  (first: stamps process start)

import argparse  # noqa: E402
import json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.lib import compared, harness, peaks
    _say("interpreter up")
    bench = harness.load_benchmark()
    try:
        res = harness.resolve_cell(bench, args.workload)
    except harness.Unresolved as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    _say("runner imported")
    cell = res["cell"]
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    # the compile cache: JAX_COMPILATION_CACHE_DIR where set, else the
    # program's fixed <checkout>/.jax_cache (config.enable_compile_cache)
    from distributed_pytorch_tpu import config as program_config
    cache_dir = program_config.enable_compile_cache()
    try:
        device = peaks.require_chips(cell["chips"])
    except peaks.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    _say("accelerator found")
    print(f"[bench] cell {cell['name']} seed {args.seed} seconds {seconds} "
          f"trace {args.trace} | device {device} | compile cache {cache_dir}",
          flush=True)

    ctx = {"cell": cell, "config": res["config"], "traffic": res["traffic"],
           "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
           "chips": cell["chips"],
           "work_dir": harness.work_dir(cell["name"]),
           "peaks": peaks.peaks_for(device["kind"]), "say": _say}
    out = res["runner"].run(ctx)

    if args.trace:
        metrics = harness.read_layer_metrics(bench, cell["name"],
                                             out["observations"])
    else:
        metrics = harness.end_to_end_metrics(bench, cell["name"],
                                             out["end_to_end"])
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]), "failed": int(out["failed"]),
            "metrics": metrics, "device": device}
    if args.trace:
        tr = out["observations"].get("trace")
        if tr is None:
            print("benchmark: the traced run produced no device trace",
                  file=sys.stderr)
            return 4
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = tr["breakdown"]
    # every number `correct` was decided from beside its limit: the last
    # key of the line and the last lines on standard error
    line["compared"] = compared.of_line(out["compared"])
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    print("\n".join(compared.lines(out["compared"])), file=sys.stderr,
          flush=True)
    return 0


def _say(msg: str) -> None:
    print(f"[bench +{stats.now() - stats.T_PROCESS_START:7.2f}s] {msg}",
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
