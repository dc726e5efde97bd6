"""Capture + analyze a TPU profile of the flagship train step (VERDICT #1a).

Runs a few steps of the flagship config under jax.profiler, then reduces the
capture with the benchmark's own code (benchmark/lib/trace_reduce.py and
trace_spans.py) and prints busy/idle, the op-level time breakdown, and the
breakdown by the program's named scopes and host phases — no TensorBoard
UI needed (this container has no browser). `--analyze_only --trace_dir D`
reduces any capture: the trainer's `--profile`, a replica's
`POST /admin/profile`, a benchmark run's `.bench_work/<cell>/trace`.

Usage: python scripts/profile_step.py [--batch 16] [--attn auto] [--remat]
"""

from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp


def capture(batch: int, attn_impl: str, remat: bool, loss_impl: str,
            trace_dir: str, iters: int = 6) -> None:
    from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
    from distributed_pytorch_tpu.train.state import create_train_state
    from distributed_pytorch_tpu.train.step import make_train_step

    from distributed_pytorch_tpu.config import flagship_gpt124m
    model_cfg = flagship_gpt124m(act_recomp=remat, act_recomp_policy="attn",
                                 loss_impl=loss_impl)
    train_cfg = TrainConfig(
        dataset="synthetic", total_batch_size=batch * 1024,
        batch_size=batch, max_iters=iters, parallelism="single",
        attn_impl=attn_impl, eval=False, save_model=False, save_stats=False,
        compute_dtype="bfloat16")

    model, tx, state, _ = create_train_state(model_cfg, train_cfg)
    step = make_train_step(model, tx, model_cfg, train_cfg, None, None)
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (1, batch, 1024), 0, 50304, jnp.int32)
    y = jax.random.randint(rng, (1, batch, 1024), 0, 50304, jnp.int32)
    state, m = step(state, x, y)
    jax.block_until_ready(m)           # compile outside the trace

    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            state, m = step(state, x, y)
        jax.block_until_ready(m)


def analyze(trace_dir: str, top: int = 25) -> None:
    """The benchmark's reduction on the newest capture under `trace_dir`
    (benchmark/lib/trace_reduce.py + trace_spans.py, so this table and the
    benchmark's are one arithmetic): busy and idle time of the devices,
    self time by op family, the longest idle gaps by host event; then by
    the PROGRAM's names (obs/trace.py): device self time a step by named
    scope, idle time by host phase, and each phase's median."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.lib import trace_reduce, trace_spans
    from benchmark.readers import trace_idle_owner, trace_scope_ms

    path = trace_reduce.find_xplane(trace_dir)
    planes = trace_reduce.load_planes(path)
    print(f"capture {path}")
    n_dev = len(trace_reduce.device_lines(planes))
    if n_dev:
        summary = trace_reduce.summarize(planes, n_dev)
        busy, window = summary["busy_s"], summary["window_s"]
        print(f"devices {summary['devices']}: busy {busy:.4f}s of "
              f"{window:.4f}s, idle {100 * (1 - busy / window):.2f}%")
        print("self time by op family, device 0:")
        for name, sec in trace_reduce.top_ops(summary["ops_dev0"], top=top,
                                              by_family=True):
            print(f"  {sec * 1e3:10.3f} ms  {100 * sec / busy:5.1f}%  "
                  f"{name[:100]}")
        print("longest idle gaps, by the host event that covers most:")
        for name, sec in summary["breakdown"]["idle_gaps"]:
            print(f"  {sec * 1e3:10.3f} ms  {name[:100]}")
    else:
        print("no device plane (a CPU capture): host phases only")
    sl = trace_spans.load(trace_dir)
    if sl["ops"]:
        scopes = trace_scope_ms.table(sl, [r"step\("])
        if scopes is not None:
            trace_scope_ms.say_table(scopes)
            # a patterned model's mixers have scopes of their own
            # (obs/trace.py MIXER_SCOPES): the same table over them too
            from benchmark.readers import trace_scope_named_ms
            from distributed_pytorch_tpu.obs.trace import (MIXER_MODULES,
                                                           MIXER_SCOPES)
            named = trace_scope_named_ms.table(
                (*MIXER_MODULES, *MIXER_SCOPES), [r"step\("], trace_dir)
            if named is not None and set(named["owners"]) & (
                    set(MIXER_SCOPES) | {"ssm", "conv"}):
                print("with the mixers' scopes (obs/trace.py MIXER_SCOPES):")
                trace_scope_ms.say_table(named)
    # host events that are no phase of a loop (obs/trace.py HOST_EVENTS: a
    # collector pause) lie inside the phases: they take the idle time they
    # cover before the phase around them does
    from distributed_pytorch_tpu.obs.trace import HOST_EVENTS
    space = trace_spans.read_xspace(
        path, lambda plane, name: plane == trace_reduce.HOST_PLANE
        and name in HOST_EVENTS)
    host = space.get(trace_reduce.HOST_PLANE, {"lines": {}, "meta": {}})
    events = [(host["meta"][mid][0], start, dur, st)
              for evs in host["lines"].values()
              for mid, start, dur, st in evs if st is not None]
    phases = [trace_spans.phase_events(sl, p)
              for p in trace_spans.PHASE_LAYERS]
    if sl["ops"] and (events or any(phases)):
        trace_idle_owner.say_table(trace_spans.split_idle(
            trace_spans.all_gaps(sl["ops"]), [events] + phases))
    by_name: dict = {}
    for name, _, dur, _ in events + [e for evs in phases for e in evs]:
        by_name.setdefault(name, []).append(dur / 1e6)
    if by_name:
        print("host phases and events (obs/trace.py PHASES, HOST_EVENTS): "
              "count, median ms")
    for name, ms in by_name.items():
        print(f"  {name:<16} {len(ms):6d} {trace_spans.median(ms):10.3f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--attn", default="auto")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--loss", default="fused")
    ap.add_argument("--trace_dir", default="",
                    help="default: the obs/profile.py convention "
                         "runs/profile_step/profile")
    ap.add_argument("--analyze_only", action="store_true")
    args = ap.parse_args()
    if not args.trace_dir:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from distributed_pytorch_tpu.obs.profile import profile_dir
        args.trace_dir = profile_dir("profile_step")

    if not args.analyze_only:
        print(f"device: {jax.devices()[0].device_kind}", flush=True)
        capture(args.batch, args.attn, args.remat, args.loss,
                args.trace_dir)
    analyze(args.trace_dir)


if __name__ == "__main__":
    main()
