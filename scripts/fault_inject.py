#!/usr/bin/env python
"""Fault-injection harness for the replicated serving tier: spawn N real
replica processes (demo model, greedy), drive seeded Poisson traffic
through the health-gated router, then KILL one replica mid-drive
(SIGKILL — no goodbye) and restart it on the same port. Asserts the
ROADMAP's scale-out exit criteria:

* **zero failed requests**: every submitted request either completes its
  FULL budget or is EXPLICITLY shed (`ShedError` with a cause) — no
  hangs, no truncated streams, no silent drops;
* **failover idempotency**: every completed stream — including the ones
  failed over mid-decode — is bit-identical to an offline greedy run of
  the same engine (gapless, duplicate-free);
* **~linear aggregate throughput** (with --baseline): delivered tok/s
  over N replicas vs the same drive against one.

Modes: `--mode kill` (default) SIGKILLs the victim mid-drive;
`--mode drain` performs a draining restart instead (stop admission, let
slots retire, then replace) and additionally asserts ZERO shed — a
drain must be lossless. `--mode none` is the fault-free control.

Used two ways: standalone (`python scripts/fault_inject.py`; `--json`
prints one machine-readable line) and as the 2-replica kill-and-replace
leg in scripts/serve_smoke.sh. This is a CORRECTNESS harness and it runs
on the CPU backend: the offline greedy reference runs in this process and
every replica is a
child process, so both pin `JAX_PLATFORMS=cpu` — a chip belongs to one
process at a time, and a parent that held it would starve its own
replicas. One process driving N one-chip replicas is ROADMAP R6.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--requests", type=int, default=48)
    p.add_argument("--prompt-lo", type=int, default=3)
    p.add_argument("--prompt-hi", type=int, default=24)
    p.add_argument("--budget-lo", type=int, default=8)
    p.add_argument("--budget-hi", type=int, default=24)
    p.add_argument("--load", type=float, default=1.2,
                   help="offered load vs the probed aggregate service "
                        "rate (>1 saturates: the queue genuinely fills)")
    p.add_argument("--mode", choices=["kill", "drain", "none"],
                   default="kill")
    p.add_argument("--kill-at-frac", type=float, default=0.3,
                   help="inject the fault after this fraction of "
                        "requests has been submitted")
    p.add_argument("--restart-after-s", type=float, default=1.0)
    p.add_argument("--retry-budget", type=int, default=4)
    p.add_argument("--baseline", action="store_true",
                   help="also drive a single replica (same per-slot "
                        "load) and report the scaling ratio")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout-s", type=float, default=420.0)
    p.add_argument("--json", action="store_true",
                   help="print one JSON line instead of the human log")
    p.add_argument("--log-dir", type=str, default="",
                   help="keep replica logs here (default: a tempdir)")
    return p.parse_args(argv)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ReplicaProc:
    """One replica subprocess on a fixed port (fixed so a replacement
    can take over the dead one's address — the router re-probes the
    same name)."""

    def __init__(self, port: int, slots: int, log_path: str):
        self.port = port
        self.slots = slots
        self.log_path = log_path
        self.proc: subprocess.Popen | None = None

    def spawn(self) -> "ReplicaProc":
        cmd = [sys.executable, "-m", "distributed_pytorch_tpu.serve",
               "--demo", "--temperature", "0.0", "--port", str(self.port),
               "--slots", str(self.slots), "--max-queue", "64"]
        self.log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, stdout=self.log, stderr=subprocess.STDOUT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        return self

    def kill(self) -> None:
        """SIGKILL: the replica gets no chance to flush, close, or shed
        — the failure the router must absorb."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait()

    def terminate(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        try:
            self.log.close()
        except Exception:
            pass

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.port}"


async def _healthz(port: int, timeout=2.0) -> tuple[int, dict]:
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection("127.0.0.1", port), timeout)
    try:
        writer.write(b"GET /healthz HTTP/1.1\r\nHost: h\r\n\r\n")
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout)
    finally:
        writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split(b" ")[1]), json.loads(body or b"{}")


async def _fetch_timeline(port: int, path: str, timeout=3.0) -> int:
    """Pull a replica's step-level flight recorder (`GET
    /debug/timeline`) and persist it as JSONL in the log dir — the
    post-hoc record of what the engine was doing around the injected
    fault. Best-effort: returns the entry count (0 on any failure)."""
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection("127.0.0.1", port), timeout)
        try:
            writer.write(b"GET /debug/timeline?n=4096 HTTP/1.1\r\n"
                         b"Host: h\r\n\r\n")
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout)
        finally:
            writer.close()
        body = json.loads(raw.partition(b"\r\n\r\n")[2] or b"{}")
        entries = body.get("entries", [])
        if entries:
            with open(path, "w") as f:
                for e in entries:
                    f.write(json.dumps(e) + "\n")
        return len(entries)
    except Exception:  # noqa: BLE001 — artifacts never fail the harness
        return 0


async def _wait_up(port: int, timeout_s: float = 120.0) -> None:
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        try:
            status, _ = await _healthz(port)
            if status == 200:
                return
        except Exception:
            pass
        await asyncio.sleep(0.25)
    raise TimeoutError(f"replica on :{port} never became healthy")


def _workload(args):
    import numpy as np
    npr = np.random.default_rng(args.seed)
    # demo model: vocab 1024, block 256 — keep prompt+budget well inside
    reqs = [(list(map(int, npr.integers(1, 1024,
                                        int(npr.integers(args.prompt_lo,
                                                         args.prompt_hi))))),
             int(npr.integers(args.budget_lo, args.budget_hi)))
            for _ in range(args.requests)]
    return npr, reqs


async def _probe_rate(router, reqs) -> float:
    """Warm every replica's compile cache and probe delivered tok/s for
    one request — the drive's offered-rate denominator."""
    from distributed_pytorch_tpu.serve.router import Router  # noqa: F401
    names = list(router.replicas)
    tok_s = []
    for name in names:
        # pin the dispatch by excluding everyone else
        exclude = {n for n in names if n != name}
        rep = router.pick(exclude=exclude)
        t0 = time.perf_counter()
        n = 0
        async for ev in router._stream_once(rep, reqs[0][0], 16, None):
            if "token" in ev:
                n += 1
        tok_s.append(n / (time.perf_counter() - t0))
    return sum(tok_s)


async def _drive(router, reqs, arrivals, timeout_s: float):
    """Poisson-submit every request through the router; classify each as
    completed / shed / failed. 'failed' is the criterion the harness
    exists to keep at zero: an exception that is not an explicit shed,
    or a stream that ended without its done event."""
    from distributed_pytorch_tpu.serve.scheduler import ShedError

    async def one(prompt, budget):
        tokens, done = [], None
        async for ev in router.stream(prompt, budget):
            if "token" in ev:
                tokens.append(ev["token"])
            else:
                done = ev
        return tokens, done

    start = time.perf_counter()
    tasks = []
    for (prompt, budget), at in zip(reqs, arrivals):
        delay = start + at - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(
            asyncio.wait_for(one(prompt, budget), timeout_s)))
    results = await asyncio.gather(*tasks, return_exceptions=True)
    dt = time.perf_counter() - start
    completed, shed, failed = [], [], []
    for i, r in enumerate(results):
        if isinstance(r, ShedError):
            shed.append((i, r.cause))
        elif isinstance(r, BaseException):
            failed.append((i, repr(r)))
        else:
            tokens, done = r
            if done is None or not done.get("done") \
                    or len(tokens) != reqs[i][1]:
                failed.append((i, f"truncated: {len(tokens)}/{reqs[i][1]}"
                                  f" done={done}"))
            else:
                completed.append((i, tokens, done))
    return completed, shed, failed, dt


def _offline_ref(reqs):
    """Bit-exact reference: the SAME demo model the replicas serve, run
    through the offline engine in this process."""
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.serve.__main__ import _demo_model
    model, variables, _, _ = _demo_model()
    eng = DecodeEngine(model, variables, n_slots=4, temperature=0.0)
    return eng.run([p for p, _ in reqs], [b for _, b in reqs])


async def _run_leg(args, n_replicas: int, inject: bool, log_dir: str,
                   tag: str) -> dict:
    from distributed_pytorch_tpu.serve.router import Router

    reps = [ReplicaProc(_free_port(), args.slots,
                        os.path.join(log_dir, f"{tag}_replica{i}.log"))
            .spawn()
            for i in range(n_replicas)]
    victim = reps[-1] if inject else None
    try:
        await asyncio.gather(*(_wait_up(r.port) for r in reps))
        router = Router([r.addr for r in reps],
                        retry_budget=args.retry_budget,
                        probe_interval_s=0.2, fail_threshold=2,
                        backoff_base_s=0.25, backoff_cap_s=2.0,
                        fleet_poll_interval_s=0.2)
        await router.start()

        npr, reqs = _workload(args)
        agg_tok_s = await _probe_rate(router, reqs)
        mean_budget = (args.budget_lo + args.budget_hi) / 2
        rate = args.load * agg_tok_s / mean_budget
        arrivals = list(npr.exponential(1.0 / rate,
                                        size=len(reqs)).cumsum())

        fault_task = None
        if inject:
            k = max(1, int(args.kill_at_frac * len(reqs)))
            fault_at = arrivals[k - 1]

            async def fault():
                await asyncio.sleep(fault_at)
                # land the fault while the victim is mid-stream (streams
                # at these sizes are short; killing between them would
                # test detection but never failover): wait until its own
                # healthz shows live slots, then strike
                deadline = time.perf_counter() + 30
                while time.perf_counter() < deadline:
                    try:
                        _, body = await _healthz(victim.port)
                        if body.get("live_slots", 0) >= 1:
                            break
                    except Exception:
                        break
                    await asyncio.sleep(0.02)
                if args.mode == "drain":
                    await router.drain(victim.addr)
                    # wait for quiescence (healthz reports drained)
                    while True:
                        try:
                            _, body = await _healthz(victim.port)
                            if body.get("drained"):
                                break
                        except Exception:
                            break
                        await asyncio.sleep(0.2)
                victim.kill()
                await asyncio.sleep(args.restart_after_s)
                victim.spawn()                # same port: rejoins by probe

            fault_task = asyncio.ensure_future(fault())

        completed, shed, failed, dt = await _drive(
            router, reqs, arrivals, args.timeout_s)
        if fault_task is not None:
            await fault_task
        snapshot = router.snapshot()
        metrics = router.metrics.summary()
        router._update_slo()   # fold the drive's final counts in before
        # reading the gauges (the probe loop stops with the router)
        slo = router.slo.snapshot()
        fleet_replicas = len(router.fleet_snapshots())
        await router.stop()
        # persist each live replica's step timeline before teardown —
        # the flight-recorder view of the drive (and, on the restarted
        # victim, of the post-rejoin traffic)
        artifacts = {}
        for i, r in enumerate(reps):
            p = os.path.join(log_dir, f"{tag}_replica{i}_timeline.jsonl")
            if await _fetch_timeline(r.port, p):
                artifacts[f"replica{i}_timeline"] = p
    finally:
        for r in reps:
            r.terminate()

    refs = _offline_ref(reqs)
    mismatches = [i for i, tokens, _ in completed
                  if tokens != refs[i][len(reqs[i][0]):]]
    toks_out = sum(len(t) for _, t, _ in completed)
    return {"replicas": n_replicas, "mode": args.mode if inject else
            "none", "requests": len(reqs),
            "completed": len(completed), "shed": len(shed),
            "failed": len(failed), "failed_detail": failed[:5],
            "shed_by_cause": metrics.get("shed_by_cause", {}),
            "parity_mismatches": len(mismatches),
            "failovers": metrics["failovers"],
            "retries": metrics["retries"],
            "replica_down": metrics["replica_down"],
            "replica_up": metrics["replica_up"],
            "tokens_per_sec": round(toks_out / dt, 1),
            "offered_rps": round(rate, 2),
            "probe_agg_tok_s": round(agg_tok_s, 1),
            "drive_s": round(dt, 2),
            "ttft_p50_ms": metrics["ttft"].get("p50_ms"),
            "ttft_p99_ms": metrics["ttft"].get("p99_ms"),
            "itl_p50_ms": metrics["itl"].get("p50_ms"),
            "itl_p99_ms": metrics["itl"].get("p99_ms"),
            "slo": slo,
            "fleet_metrics_replicas": fleet_replicas,
            "artifacts": artifacts,
            "replica_states": snapshot}


async def _amain(args) -> dict:
    log_dir = args.log_dir or os.path.join(
        REPO, "runs", f"fault_inject_{int(time.time())}")
    os.makedirs(log_dir, exist_ok=True)
    out = await _run_leg(args, args.replicas, args.mode != "none",
                         log_dir, "multi")
    if args.baseline:
        base = await _run_leg(args, 1, False, log_dir, "single")
        out["baseline_tokens_per_sec"] = base["tokens_per_sec"]
        out["baseline_shed"] = base["shed"]
        out["baseline_failed"] = base["failed"]
        if base["tokens_per_sec"]:
            out["scaling_x"] = round(
                out["tokens_per_sec"] / base["tokens_per_sec"], 2)
    # the exit criteria: nothing failed, every completed stream
    # bit-identical to offline greedy; a drain must additionally be
    # lossless (no shed at all — admission moved, nothing dropped)
    out["ok"] = (out["failed"] == 0 and out["parity_mismatches"] == 0
                 and (args.mode != "drain" or out["shed"] == 0))
    # SLO criterion (kill only): the mid-stream kill must BURN latency
    # budget — the failover gap is a client-visible >threshold sample —
    # without EXHAUSTING the availability budget (every request still
    # completed or was explicitly shed)
    if args.mode == "kill":
        slo = out.get("slo", {})
        out["slo_latency_burned"] = any(
            max(slo.get(n, {}).get("burn_rate", {"0": 0.0}).values()) > 0
            for n in ("ttft_p99", "itl_p99"))
        out["slo_availability_budget_remaining"] = slo.get(
            "availability", {}).get("budget_remaining", 1.0)
        out["ok"] = (out["ok"] and out["slo_latency_burned"]
                     and out["slo_availability_budget_remaining"] > 0)
    # the router runs IN this process: its dispatch/failover spans (one
    # trace per request, failed-over streams stitched) dump here too
    try:
        from distributed_pytorch_tpu.obs import trace as obs_trace
        rec = obs_trace.get_recorder()
        if len(rec):
            out.setdefault("artifacts", {})["router_trace"] = \
                rec.dump_jsonl(os.path.join(log_dir, "router_trace.jsonl"))
    except Exception:  # noqa: BLE001 — artifacts never fail the harness
        pass
    # replay the drive's artifacts (replica timelines + router trace)
    # into the per-phase report + fitted cost model
    try:
        from distributed_pytorch_tpu.obs import replay
        rep = replay.write_report(log_dir)
        out.setdefault("artifacts", {})["report_md"] = rep["report_md"]
        out["artifacts"]["cost_model_json"] = rep["cost_model_json"]
    except Exception:  # noqa: BLE001 — artifacts never fail the harness
        pass
    # the ~linear-scaling criterion needs a core per replica process +
    # one for the driver; report the host honestly so a 1-core CI box's
    # ~1x never reads as a scaling failure of the router itself
    try:
        out["host_cores"] = len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover — non-Linux
        out["host_cores"] = os.cpu_count() or 1
    out["log_dir"] = log_dir
    return out


def main(argv=None) -> int:
    args = build_args(argv)
    # same pin the replicas get (the offline reference runs in THIS
    # process); set before anything imports jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    out = asyncio.run(_amain(args))
    if args.json:
        print(json.dumps(out))
    else:
        print(f"[fault_inject] mode={out['mode']} replicas="
              f"{out['replicas']} requests={out['requests']}: "
              f"{out['completed']} completed, {out['shed']} shed, "
              f"{out['failed']} FAILED, "
              f"{out['parity_mismatches']} parity mismatches, "
              f"{out['failovers']} failovers, "
              f"{out['tokens_per_sec']} tok/s "
              f"(logs: {out['log_dir']})")
        if "scaling_x" in out:
            print(f"[fault_inject] scaling vs 1 replica: "
                  f"{out['scaling_x']}x "
                  f"({out['baseline_tokens_per_sec']} tok/s single)")
        print(f"[fault_inject] {'OK' if out['ok'] else 'VIOLATION'}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
