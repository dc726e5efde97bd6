#!/usr/bin/env python
"""Fault-injection harness for ELASTIC TRAINING (the train-side mirror of
scripts/fault_inject.py): drive a seeded multi-process CPU training run
under the supervisor (train/supervisor.py), SIGKILL a victim worker
mid-run, and assert the ROADMAP's pod-scale exit criteria:

* **run completed** — the supervisor gang-restarts the workers and the
  run reaches max_iters (supervisor exit code 0);
* **zero lost run** — the restarted gang REJOINED from a verified
  checkpoint (it did not silently start over from step 0);
* **bitwise rejoin parity** (`--mode kill`) — the post-rejoin loss
  trajectory is bit-identical to an uninterrupted baseline on the same
  mesh: deterministic step math + the counter-based loader leave no
  trace of the fault in the training math;
* **rung-down re-mesh** (`--mode kill-hold`) — the victim's slot is
  additionally HELD (hold file = "this host is not coming back"), so
  past the deadline the supervisor re-meshes the survivors one dp rung
  down (2 hosts → 1), restores the SAME checkpoint onto the smaller
  mesh, and the leg must resume from the last verified step and
  converge. Bitwise parity is NOT asserted here: a different dp degree
  reorders reductions (tests/test_multihost.py pins that to ~rtol 2e-4).

`--mode none` is the fault-free control. `--json` prints one
machine-readable line (CI); artifacts (supervisor timeline,
worker logs, stats.json) stay under --log-dir.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--hosts", type=int, default=2)
    p.add_argument("--mode", choices=["kill", "kill-hold", "none"],
                   default="kill")
    p.add_argument("--recipe", choices=["fsdp", "pp"], default="fsdp",
                   help="worker parallelism: fsdp (dp over hosts) or pp "
                        "(interleaved-1F1B pipeline over hosts; kill-hold "
                        "is fsdp-only — a 1-host rung cannot hold a "
                        "2-stage pipe)")
    p.add_argument("--max-iters", type=int, default=40)
    p.add_argument("--ckpt-interval", type=int, default=5)
    p.add_argument("--seed", type=int, default=1729)
    p.add_argument("--remesh-deadline-s", type=float, default=2.0)
    p.add_argument("--timeout-s", type=float, default=600.0)
    p.add_argument("--json", action="store_true",
                   help="print one JSON line (for CI) instead of the "
                        "human log")
    p.add_argument("--log-dir", type=str, default="",
                   help="working dir for checkpoints/runs/logs "
                        "(default: runs/fault_inject_train_<ts>)")
    args = p.parse_args(argv)
    if args.recipe == "pp" and args.mode == "kill-hold":
        p.error("--recipe pp does not support --mode kill-hold (the "
                "rung-down re-mesh shrinks to 1 host, which cannot hold "
                "a 2-stage pipeline)")
    return args


# Tiny model, the tests/test_multihost.py experiment scaled for speed.
# total_batch_size 128 divides both meshes: 2 hosts × 1 device → dp=2,
# grad_accum 2; after the rung-down re-mesh dp=1 → grad_accum 4 — the
# GLOBAL batch (and the counter-based loader's coverage) is unchanged,
# which is exactly why the re-meshed leg continues the same experiment.
def _train_argv(args, run_name: str) -> list[str]:
    recipe = getattr(args, "recipe", "fsdp")
    extra = []
    if recipe == "pp":
        # 2 hosts x 1 device -> pipe=2 (pp_size carves the mesh, the
        # loop links pp_stages to it), one layer per stage, the
        # interleaved-1F1B schedule (models/pipeline.py) — the CI smoke
        # that the gang restart replays the SAME pipeline timeline
        extra = ["--pp_size", "2", "--pp_schedule", "1f1b"]
    return ["--dataset", "synthetic", "--platform", "cpu",
            "--parallelism", recipe, *extra,
            "--file_name", run_name,
            "--seed", str(args.seed),
            "--max_iters", str(args.max_iters),
            "--ckpt_interval", str(args.ckpt_interval),
            "--log_interval", "1",
            "--total_batch_size_str", "128", "--batch_size", "1",
            "--vocab_size", "256", "--block_size", "32",
            "--n_embd", "32", "--n_head", "4", "--n_kv_heads", "2",
            "--n_layer", "2", "--up_dim", "48"]


def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _read_jsonl(path: str) -> list[dict]:
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return []


def _inject_fault(proc: subprocess.Popen, workdir: str, run_name: str,
                  hold: bool, timeout_s: float) -> dict:
    """Wait until the run has a VERIFIED checkpoint (the supervisor's
    state file reports `resumed_from`), then SIGKILL the highest worker
    slot — mid-run, no goodbye. `hold` additionally marks the slot as
    unrestartable BEFORE the kill, forcing the rung-down path."""
    run_dir = os.path.join(workdir, "runs", run_name)
    state_path = os.path.join(run_dir, "supervisor_state.json")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"supervisor exited (rc={proc.returncode}) before the "
                f"fault could be injected — raise --max-iters")
        st = _read_json(state_path)
        if st and st.get("status") == "running" and st.get("resumed_from"):
            workers = [w for w in st.get("workers", []) if w.get("alive")]
            if workers:
                victim = max(workers, key=lambda w: w["slot"])
                if hold:
                    # hold BEFORE the kill: the supervisor must observe
                    # the slot as unrestartable when it handles the death
                    with open(os.path.join(
                            run_dir, f"hold_{victim['slot']}"), "w") as f:
                        f.write("fault_inject_train: host is gone\n")
                os.kill(victim["os_pid"], signal.SIGKILL)
                return {"victim_slot": victim["slot"],
                        "victim_pid": victim["os_pid"],
                        "killed_after_ckpt": st["resumed_from"],
                        "generation": st["generation"]}
        time.sleep(0.05)
    raise TimeoutError("no verified checkpoint appeared before the "
                       "injection deadline")


def _run_leg(args, workdir: str, run_name: str, hosts: int,
             inject: str) -> dict:
    """One supervised run; returns {rc, state, timeline, stats, fault}."""
    cmd = [sys.executable, "-m",
           "distributed_pytorch_tpu.train.supervisor",
           "--hosts", str(hosts), "--run-name", run_name,
           "--cpu-devices", "1", "--poll-s", "0.05",
           "--backoff-base-s", "0.2", "--backoff-cap-s", "1.0",
           "--remesh-deadline-s", str(args.remesh_deadline_s),
           "--", *_train_argv(args, run_name)]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    log_path = os.path.join(workdir, f"{run_name}_supervisor.log")
    with open(log_path, "w") as logf:
        proc = subprocess.Popen(cmd, cwd=workdir, env=env,
                                stdout=logf, stderr=subprocess.STDOUT)
    fault = None
    try:
        if inject != "none":
            fault = _inject_fault(proc, workdir, run_name,
                                  hold=(inject == "kill-hold"),
                                  timeout_s=args.timeout_s)
        rc = proc.wait(timeout=args.timeout_s)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    run_dir = os.path.join(workdir, "runs", run_name)
    return {
        "rc": rc,
        "fault": fault,
        "state": _read_json(os.path.join(run_dir,
                                         "supervisor_state.json")),
        "timeline": _read_jsonl(os.path.join(run_dir,
                                             "supervisor_timeline.jsonl")),
        "stats": _read_json(os.path.join(workdir, "checkpoints", run_name,
                                         "stats.json")),
        "supervisor_log": log_path,
    }


def _replay_report(run_dir: str) -> dict:
    """Deterministic timeline replay (obs/replay.py) over one leg's run
    dir — per-phase distributions + fitted cost model next to the
    timelines it came from. Best-effort: artifacts never fail the
    harness."""
    try:
        from distributed_pytorch_tpu.obs import replay
        rep = replay.write_report(run_dir)
        return {"report_md": rep["report_md"],
                "cost_model_json": rep["cost_model_json"]}
    except Exception:  # noqa: BLE001
        return {}


def main(argv=None) -> int:
    args = build_args(argv)
    workdir = args.log_dir or os.path.join(
        REPO, "runs", f"fault_inject_train_{int(time.time())}")
    os.makedirs(workdir, exist_ok=True)

    # Baseline: the SAME experiment (same mesh, same seed) uninterrupted.
    base = _run_leg(args, workdir, "baseline", args.hosts, inject="none")
    base_losses = (base["stats"] or {}).get("train_losses") or []

    out = {"mode": args.mode, "hosts": args.hosts,
           "recipe": args.recipe,
           "max_iters": args.max_iters,
           "ckpt_interval": args.ckpt_interval,
           "baseline_completed": base["rc"] == 0,
           "baseline_iters": len(base_losses),
           "log_dir": workdir}

    out["baseline_report"] = _replay_report(
        os.path.join(workdir, "runs", "baseline"))

    if args.mode == "none":
        out["run_completed"] = base["rc"] == 0
        out["ok"] = out["run_completed"] and len(base_losses) > 0
    else:
        leg = _run_leg(args, workdir, "faulted", args.hosts,
                       inject=args.mode)
        out["faulted_report"] = _replay_report(
            os.path.join(workdir, "runs", "faulted"))
        losses = (leg["stats"] or {}).get("train_losses") or []
        state = leg["state"] or {}
        events = {e.get("event") for e in leg["timeline"]}
        n = len(losses)
        out["fault"] = leg["fault"]
        out["supervisor_rc"] = leg["rc"]
        out["events"] = sorted(events)
        out["run_completed"] = leg["rc"] == 0 \
            and state.get("status") == "completed"
        # the final stats.json is written by the post-fault incarnation:
        # a non-empty loss list SHORTER than the baseline's proves the
        # gang rejoined mid-run from a checkpoint, not from step 0
        out["resume_iters"] = n
        out["zero_lost_run"] = (out["run_completed"] and 0 < n
                                and n < len(base_losses)
                                and state.get("resumed_from") is not None)
        if args.mode == "kill":
            # same mesh before/after the gang restart → the rejoined
            # trajectory must be BIT-IDENTICAL to the baseline's tail
            out["rejoin_loss_bitwise_parity"] = (
                out["zero_lost_run"] and base_losses[-n:] == losses)
            out["ok"] = (out["run_completed"] and out["zero_lost_run"]
                         and out["rejoin_loss_bitwise_parity"])
        else:  # kill-hold → rung-down re-mesh
            remesh = [e for e in leg["timeline"]
                      if e.get("event") == "remesh"]
            out["remeshed"] = (len(remesh) == 1
                               and state.get("n_hosts")
                               == remesh[0].get("new_n"))
            out["remesh"] = remesh[0] if remesh else None
            out["resumed_from_verified"] = bool(
                remesh and remesh[0].get("resumed_from"))
            final = losses[-1] if losses else None
            out["final_loss"] = final
            # a different dp degree reorders reductions — assert the leg
            # CONVERGES (finite, below the run's starting loss), not bits
            out["converged"] = (final is not None and final == final
                                and base_losses
                                and final < base_losses[0])
            out["ok"] = (out["run_completed"] and out["zero_lost_run"]
                         and out["remeshed"]
                         and out["resumed_from_verified"]
                         and out["converged"])

    try:
        out["host_cores"] = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover — non-Linux
        out["host_cores"] = os.cpu_count() or 1

    if args.json:
        print(json.dumps(out))
    else:
        keys = [k for k in ("run_completed", "zero_lost_run",
                            "rejoin_loss_bitwise_parity", "remeshed",
                            "resumed_from_verified", "converged")
                if k in out]
        flags = " ".join(f"{k}={out[k]}" for k in keys)
        print(f"[fault_inject_train] mode={args.mode} hosts={args.hosts} "
              f"iters={args.max_iters}: {flags} (artifacts: {workdir})")
        print(f"[fault_inject_train] {'OK' if out['ok'] else 'VIOLATION'}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
