#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: kernels, train, serve
    python chip_smoke.py --multichip  # four chips: fsdp vs dp, nothing else

Drives the repo's main path ONCE, through the entry points a user calls,
at the full width and depth of the flagship `gpt2_124m` (random weights
from --seed, synthetic data from the loader, no network):

  kernels  every Pallas kernel the dispatchers can select, compiled, on
           the chip, against its plain jax.numpy reference (max abs error
           bounded) — interpret mode never ran the compiled arithmetic;
  train    `python -m distributed_pytorch_tpu --preset gpt2_124m ...`
           (9 steps with `--attn_impl xla` + fused CE, a verified
           checkpoint), then 2 steps with `--attn_impl pallas` whose first
           loss must agree with the XLA run's (the default `auto` takes
           the flash kernels here, so both are named);
  serve    `python -m distributed_pytorch_tpu.serve --ckpt <that one>`,
           six HTTP completions (three in flight), greedy determinism,
           prefix reuse, SIGTERM; then `python -m
           distributed_pytorch_tpu.sample` on the same prompt as oracle.

One process per chip: THIS process never imports jax. Each phase is a
child that has exited before the next starts (the server is the one child
that overlaps anything — the HTTP client, which lives here). Every phase
prints one JSON line; the first failed phase prints its child's output
and ends the run non-zero. There is no CPU mode: the children insist on a
TPU and a machine without one fails in the first phase. The CPU rehearsal
is tests/test_chip_smoke.py, which calls the same phase functions with a
tiny model shape and "cpu" — arguments only a caller in Python can pass.

The LAST line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import http.client
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model a phase runs: trainer CLI flags + the two numbers the
    phases themselves need. main() only ever passes FLAGSHIP."""

    args: tuple
    seq_len: int
    vocab: int


FLAGSHIP = Shape(("--preset", "gpt2_124m"), 1024, 50304)

# kernels the compiled programs must hold at flagship widths on a TPU —
# a gate that quietly declines is a failure of the smoke, not a slower pass
PALLAS_TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
ENGINE_KERNELS = {"engine.step": ("paged_flash_decode",),
                  "engine.fused_step": ("paged_flash_decode",
                                        "paged_flash_prefill")}

LOSS_TOL = 2e-2          # bf16: pallas vs XLA first loss, fsdp vs dp per step
# kernel numerics: max abs error over the reference's max abs value
# (bf16 operands, f32 accumulation, an all-f32 reference)
KERNEL_BOUNDS = {"out": 2e-2, "grad": 5e-2}


class PhaseFailed(RuntimeError):
    def __init__(self, phase: str, why: str, log: str = ""):
        super().__init__(f"{phase}: {why}")
        self.phase, self.why, self.log = phase, why, log


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _env(extra: dict | None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update(extra or {})
    return env


def _tail(path: str, n: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def run_child(phase: str, cmd: list, *, cwd: str, log: str,
              env: dict | None, timeout: int) -> float:
    """Run one child to its END (it holds the chip until it exits);
    returns its wall seconds. Non-zero exit or a timeout fails the phase
    with the child's output."""
    t0 = time.perf_counter()
    with open(log, "ab") as f:
        f.write(f"$ {' '.join(cmd)}\n".encode())
        f.flush()
        try:
            rc = subprocess.run(cmd, cwd=cwd, env=_env(env), stdout=f,
                                stderr=subprocess.STDOUT,
                                timeout=timeout).returncode
        except subprocess.TimeoutExpired:
            raise PhaseFailed(phase, f"child timed out after {timeout}s: "
                              f"{' '.join(cmd[:4])} ...", _tail(log))
    if rc != 0:
        raise PhaseFailed(phase, f"child exited {rc}: {' '.join(cmd[:4])} "
                          "...", _tail(log))
    return time.perf_counter() - t0


def _check(phase: str, cond: bool, why: str, log: str = "") -> None:
    if not cond:
        raise PhaseFailed(phase, why, _tail(log) if log else "")


def _load_json(phase: str, path: str, log: str) -> dict:
    _check(phase, os.path.exists(path), f"{path} was not written", log)
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# phase: train (A.1) — the trainer CLI, then the same model on the kernels
# ---------------------------------------------------------------------------

def _train_cmd(shape: Shape, platform: str, seed: int, *, name: str,
               recipe: str, batch: int, global_batch: int, max_iters: int,
               log_interval: int, extra: tuple = ()) -> list:
    return [sys.executable, "-m", "distributed_pytorch_tpu", *shape.args,
            "--dataset", "synthetic", "--data_dir", "data",
            "--parallelism", recipe, "--platform", platform,
            "--compute_dtype", "bfloat16", "--seed", str(seed),
            "--batch_size", str(batch),
            "--total_batch_size_str", f"{global_batch}*{shape.seq_len}",
            "--max_iters", str(max_iters),
            "--log_interval", str(log_interval),
            # the default 100-step warm-up would leave a 9-step run at a
            # few percent of its learning rate: "the loss falls" would
            # then be batch noise
            "--warmup_steps", "2",
            "--file_name", name, *extra]


def _verify_manifest(phase: str, step_dir: str) -> int:
    """Re-hash a step dir against its manifest.json (blake2b-128 per file,
    train/checkpoint.py) with nothing but hashlib; returns the file count."""
    mpath = os.path.join(step_dir, "manifest.json")
    _check(phase, os.path.exists(mpath), f"no manifest.json in {step_dir}")
    with open(mpath) as f:
        files = json.load(f)["files"]
    for rel, meta in files.items():
        h = hashlib.blake2b(digest_size=16)
        with open(os.path.join(step_dir, rel), "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        _check(phase, h.hexdigest() == meta["blake2b"],
               f"checkpoint file {rel} does not match its manifest digest")
    return len(files)


def _train_stats(phase: str, out: str, name: str, platform: str,
                 n_devices: int, log: str) -> dict:
    st = _load_json(phase, os.path.join(out, "checkpoints", name,
                                        "stats.json"), log)
    dev = st.get("device", {})
    _check(phase, dev.get("platform") == platform,
           f"{name} ran on {dev}, not on {platform!r}", log)
    _check(phase, dev.get("count") == n_devices,
           f"{name} saw {dev.get('count')} device(s), wanted {n_devices}",
           log)
    losses = st["train_losses"]
    _check(phase, bool(losses) and all(math.isfinite(x) for x in losses),
           f"{name}: non-finite or missing losses {losses}", log)
    _check(phase, st.get("step_traces") == 1 and st.get("step_retraces") == 0,
           f"{name}: train.step traced {st.get('step_traces')}x (retraces "
           f"after the first step: {st.get('step_retraces')})", log)
    return st


def _speed(st: dict) -> dict:
    """The step-time numbers of one trainer run (smoke, not a benchmark:
    a handful of steps, first window dropped as compile)."""
    med = st.get("median_step_time")
    n = st["device"]["count"]
    peaks = [d.get("measured_peak_gb") for d in st["memplan"]["devices"]]
    return {"step_ms": med * 1e3 if med else None,
            "tok_s_chip": (st["median_tokens_per_sec"] / n) if med else None,
            "mfu": st.get("median_mfu"),
            "peak_hbm_gib": peaks,
            "memplan_gib": st["memplan"]["predicted_gb"]}


def phase_train(out: str, *, seed: int, platform: str,
                shape: Shape = FLAGSHIP, batch: int = 16, iters: int = 8,
                kernel_iters: int = 1, env: dict | None = None) -> dict:
    """A.1. `kernel_iters` < 0 skips the pallas leg (a CPU cannot run a
    compiled kernel; only the test passes that)."""
    phase, log = "train", os.path.join(out, "train.log")
    t0 = time.perf_counter()
    run_child(phase, _train_cmd(shape, platform, seed, name="smoke_xla",
                                recipe="single", batch=batch,
                                global_batch=batch, max_iters=iters,
                                log_interval=2,
                                # by name: `auto` takes the flash kernels at
                                # this shape, and the leg below is compared
                                # with an XLA run
                                extra=("--save_model", "--attn_impl", "xla")),
              cwd=out, log=log, env=env, timeout=900)
    st = _train_stats(phase, out, "smoke_xla", platform, 1, log)
    losses = st["train_losses"]
    ln_v = math.log(shape.vocab)
    _check(phase, abs(losses[0] - ln_v) < 1.0,
           f"first loss {losses[0]:.4f} is not near ln(vocab) = {ln_v:.2f}",
           log)
    _check(phase, losses[-1] < losses[0],
           f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}", log)
    prog = st["programs"]["train.step"]
    ckpt = os.path.join(out, "checkpoints", "smoke_xla",
                        f"step_{len(losses)}")
    n_files = _verify_manifest(phase, ckpt)
    rec = {"phase": phase, "ok": True, "device": st["device"],
           "steps": len(losses), "loss_first": losses[0],
           "loss_last": losses[-1], "ln_vocab": ln_v, **_speed(st),
           "compiler_temp_gib": prog.get("temp_bytes", 0) / 2 ** 30,
           "compiler_args_gib": prog.get("argument_bytes", 0) / 2 ** 30,
           "retraces": st["step_retraces"],
           "checkpoint": ckpt, "checkpoint_files_verified": n_files,
           "kernels": {"xla": prog["kernels"]}, "paths": prog["paths"],
           "compile_seconds": prog["compile_s"]}
    if kernel_iters >= 0:
        # the same model and seed through flash attention forward AND
        # backward — executed, not just compiled
        run_child(phase, _train_cmd(
            shape, platform, seed, name="smoke_pallas", recipe="single",
            batch=batch, global_batch=batch, max_iters=kernel_iters,
            log_interval=1,
            extra=("--attn_impl", "pallas")),
            cwd=out, log=log, env=env, timeout=900)
        sp = _train_stats(phase, out, "smoke_pallas", platform, 1, log)
        pprog = sp["programs"]["train.step"]
        missing = [k for k in PALLAS_TRAIN_KERNELS
                   if not pprog["kernels"].get(k)]
        _check(phase, not missing,
               f"the pallas train step holds no tpu_custom_call for "
               f"{missing} (census {pprog['kernels']})", log)
        delta = abs(sp["train_losses"][0] - losses[0])
        _check(phase, delta <= LOSS_TOL,
               f"first-step loss pallas {sp['train_losses'][0]:.5f} vs XLA "
               f"{losses[0]:.5f}: |delta| {delta:.5f} > {LOSS_TOL}", log)
        rec["kernels"]["pallas"] = pprog["kernels"]
        rec["pallas"] = {"steps": len(sp["train_losses"]),
                         "loss_first": sp["train_losses"][0],
                         "loss_delta_vs_xla": delta, "tolerance": LOSS_TOL,
                         **_speed(sp), "paths": pprog["paths"],
                         "compiler_temp_gib":
                             pprog.get("temp_bytes", 0) / 2 ** 30,
                         "compile_seconds": pprog["compile_s"]}
        rec["compile_seconds"] += pprog["compile_s"]
    rec["seconds"] = time.perf_counter() - t0
    return rec


# ---------------------------------------------------------------------------
# phase: serve (A.2) — the serve CLI over HTTP, then the sample CLI as oracle
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(port: int, path: str, timeout: float = 5.0) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read().decode()
    finally:
        conn.close()


def _complete(port: int, prompt: list, max_tokens: int, result: dict) -> None:
    """One streamed /v1/completions call; token arrival times are taken
    HERE, on the client's clock, as each SSE event is read."""
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/completions",
                     body=json.dumps({"prompt": prompt,
                                      "max_tokens": max_tokens}),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        result["status"] = r.status
        toks, stamps = [], []
        while True:
            line = r.fp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                continue
            ev = json.loads(line[6:])
            if "token" in ev:
                toks.append(ev["token"])
                stamps.append(time.perf_counter() - t0)
            elif "error" in ev:
                result["error"] = ev
            elif ev.get("done"):
                result["reason"] = ev.get("reason")
        result["tokens"], result["stamps"] = toks, stamps
    except Exception as e:  # noqa: BLE001 — reported by the caller
        result["error"] = repr(e)
    finally:
        conn.close()


def _median(xs: list):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def _prompts(seed: int, vocab: int, lens: tuple, shared: int) -> list:
    """Six token-id prompts from the seed: lens[0] twice (identical),
    two of lens[1] sharing their first `shared` ids, two of lens[2]."""
    import random
    rng = random.Random(seed)
    ids = lambda n: [rng.randrange(vocab) for _ in range(n)]  # noqa: E731
    short, mid_a, long_a, long_b = (ids(lens[0]), ids(lens[1]),
                                    ids(lens[2]), ids(lens[2]))
    mid_b = mid_a[:shared] + ids(lens[1] - shared)
    # two waves of three in flight; the twin of each first-wave prompt
    # arrives after its sibling's blocks are registered
    return [short, mid_a, long_a, list(short), mid_b, long_b]


def phase_serve(out: str, ckpt: str, *, seed: int, platform: str,
                shape: Shape = FLAGSHIP, slots: int = 8, kv_block: int = 128,
                prefill_chunk: int = 256, prompt_lens: tuple = (16, 200, 700),
                new_tokens: int = 32, expect_kernels: dict | None = None,
                env: dict | None = None) -> dict:
    """A.2. `expect_kernels` {program: (kernel names)} is what the compiled
    engine programs must hold (main passes ENGINE_KERNELS; a CPU holds
    none, so the test passes nothing)."""
    phase, log = "serve", os.path.join(out, "serve.log")
    t0 = time.perf_counter()
    port = _free_port()
    spinup = os.path.join(out, "runs", "serve", "spinup.jsonl")
    if os.path.exists(spinup):
        os.remove(spinup)                 # this start-up's records only
    cmd = [sys.executable, "-m", "distributed_pytorch_tpu.serve",
           "--ckpt", ckpt, "--slots", str(slots), "--temperature", "0.0",
           "--kv-block", str(kv_block), "--prefill-chunk",
           str(prefill_chunk), "--port", str(port)]
    with open(log, "ab") as f:
        f.write(f"$ {' '.join(cmd)}\n".encode())
        f.flush()
        server = subprocess.Popen(cmd, cwd=out, env=_env(env), stdout=f,
                                  stderr=subprocess.STDOUT)
    try:
        # ---- wait for readiness (weights restored, programs compiled)
        t_up = time.perf_counter()
        while True:
            _check(phase, server.poll() is None,
                   f"server exited {server.returncode} before /healthz", log)
            _check(phase, time.perf_counter() - t_up < 600,
                   "server not healthy after 600s", log)
            try:
                status, body = _get(port, "/healthz")
                if status == 200 and json.loads(body).get("ok"):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        startup_s = time.perf_counter() - t_up
        with open(spinup) as f:
            programs = {r["program"]: r for r in map(json.loads, f)
                        if r.get("spinup") == "program"}
        _check(phase, bool(programs), "server described no program", log)
        device = next(iter(programs.values()))["device"]
        _check(phase, device["platform"] == platform,
               f"server runs on {device}, not on {platform!r}", log)
        for name, want in (expect_kernels or {}).items():
            have = programs.get(name, {}).get("kernels", {})
            missing = [k for k in want if not have.get(k)]
            _check(phase, not missing,
                   f"{name} holds no tpu_custom_call for {missing} (census "
                   f"{have}) — a gate declined at flagship widths", log)

        # ---- six completions, three in flight at once
        prompts = _prompts(seed, shape.vocab, prompt_lens, kv_block)
        results = [dict() for _ in prompts]
        for wave in (range(0, 3), range(3, 6)):
            threads = [threading.Thread(target=_complete, args=(
                port, prompts[i], new_tokens, results[i])) for i in wave]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        for i, r in enumerate(results):
            _check(phase, r.get("status") == 200 and "error" not in r
                   and len(r.get("tokens", ())) == new_tokens,
                   f"request {i} ({len(prompts[i])} ids): status "
                   f"{r.get('status')}, {len(r.get('tokens', ()))} tokens, "
                   f"error {r.get('error')}", log)
        _check(phase, results[0]["tokens"] == results[3]["tokens"],
               "the two identical greedy requests differ: "
               f"{results[0]['tokens']} vs {results[3]['tokens']}", log)
        _, metrics = _get(port, "/metrics", timeout=30)
        hit = miss = 0.0
        for line in metrics.splitlines():
            if line.startswith('serve_prefix_tokens_total{kind="hit"}'):
                hit = float(line.split()[-1])
            if line.startswith('serve_prefix_tokens_total{kind="miss"}'):
                miss = float(line.split()[-1])
        _check(phase, hit > 0, "no prefix hit on /metrics although two "
               f"prompts share {kv_block} ids (hit {hit}, miss {miss})", log)

        # ---- clean exit on SIGTERM
        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(phase, "server ignored SIGTERM for 60s",
                              _tail(log))
        _check(phase, rc == 0 and "server stopped cleanly" in _tail(log),
               f"server exit code {rc} on SIGTERM (want a clean 0)", log)
    finally:
        if server.poll() is None:         # any failure above: no orphan
            server.kill()
            server.wait()

    # ---- the repo's own oracle: models/generate.py through sample.py
    slog = os.path.join(out, "sample.log")
    sample_s = run_child(phase, [
        sys.executable, "-m", "distributed_pytorch_tpu.sample", "--ckpt",
        ckpt, "--temperature", "0", "--max_new_tokens", str(new_tokens),
        "--num_samples", "1", "--prompt",
        ",".join(map(str, prompts[0]))], cwd=out, log=slog, env=env,
        timeout=900)
    text = _tail(slog, 1 << 16)
    _check(phase, f"backend {platform}" in text,
           f"sample CLI did not report backend {platform!r}", slog)
    oracle = json.loads([l for l in text.splitlines()
                         if l.startswith("[")][-1])[len(prompts[0]):]
    got = results[0]["tokens"]
    _check(phase, len(oracle) == new_tokens and oracle[0] == got[0],
           f"engine's first token {got[0]} != generate's {oracle[:1]}", slog)
    diverge = next((i for i, (a, b) in enumerate(zip(got, oracle))
                    if a != b), None)

    ttft = [r["stamps"][0] for r in results]
    itl = [b - a for r in results
           for a, b in zip(r["stamps"], r["stamps"][1:])]
    return {"phase": phase, "ok": True, "device": device,
            "seconds": time.perf_counter() - t0,
            "compile_seconds": sum(p["compile_s"]
                                   for p in programs.values()),
            "startup_seconds": startup_s, "sample_cli_seconds": sample_s,
            "requests": len(results), "tokens_each": new_tokens,
            "prompt_lens": [len(p) for p in prompts],
            "identical_requests_bit_identical": True,
            "prefix_hit_tokens": hit, "prefix_miss_tokens": miss,
            "prefix_hit_rate": hit / max(hit + miss, 1.0),
            "ttft_s": ttft, "ttft_median_s": _median(ttft),
            "itl_median_s": _median(itl), "itl_max_s": max(itl),
            "kernels": {n: p["kernels"] for n, p in programs.items()},
            "paths": {n: p["paths"] for n, p in programs.items()},
            "engine_vs_generate": {
                "first_token_equal": True, "all_equal": diverge is None,
                "first_divergence_index": diverge},
            "sigterm_exit_code": 0}


# ---------------------------------------------------------------------------
# --multichip: fsdp on four chips, and dp as what it is compared with
# ---------------------------------------------------------------------------

def phase_multichip(out: str, *, seed: int, platform: str,
                    shape: Shape = FLAGSHIP, n_devices: int = 4,
                    batch: int = 16, iters: int = 5,
                    env: dict | None = None) -> dict:
    phase, log = "multichip", os.path.join(out, "multichip.log")
    t0 = time.perf_counter()
    runs = {}
    for recipe in ("fsdp", "dp"):
        run_child(phase, _train_cmd(
            shape, platform, seed, name=f"smoke_{recipe}", recipe=recipe,
            batch=batch, global_batch=batch * n_devices, max_iters=iters,
            log_interval=1), cwd=out, log=log, env=env, timeout=1200)
        runs[recipe] = _train_stats(phase, out, f"smoke_{recipe}", platform,
                                    n_devices, log)
    fsdp, dp = runs["fsdp"], runs["dp"]
    shards = [fsdp["state_bytes_per_device"][k]
              for k in sorted(fsdp["state_bytes_per_device"], key=int)]
    whole = max(dp["state_bytes_per_device"].values())
    _check(phase, len(shards) == n_devices and min(shards) > 0
           and max(shards) <= 1.05 * min(shards),
           f"fsdp state is not in {n_devices} near-equal non-empty shards: "
           f"{shards}", log)
    _check(phase, max(shards) <= 1.1 * whole / n_devices,
           f"an fsdp shard ({max(shards)} B) is more than 1/{n_devices} of "
           f"the dp state ({whole} B per device)", log)
    in_use = [d.get("bytes_in_use") for d in fsdp["hbm_after_init"]]
    if all(b is not None for b in in_use):   # the CPU reports none
        _check(phase, min(in_use) > 0 and max(in_use) <= 1.2 * min(in_use),
               f"per-device bytes_in_use after fsdp init not near-equal: "
               f"{in_use}", log)
    coll = fsdp["programs"]["train.step"]["collectives"]
    _check(phase, coll.get("all-gather", 0) > 0
           and (coll.get("reduce-scatter", 0) + coll.get("all-reduce", 0)) > 0,
           f"the fsdp step holds no all-gather / reduce-scatter: {coll}", log)
    deltas = [abs(a - b) for a, b in zip(fsdp["train_losses"],
                                         dp["train_losses"])]
    _check(phase, len(deltas) == iters + 1 and max(deltas) <= LOSS_TOL,
           f"fsdp and dp losses disagree step by step: {deltas} "
           f"(tolerance {LOSS_TOL})", log)
    peaks = {r: _speed(s)["peak_hbm_gib"] for r, s in runs.items()}
    if all(p is not None for ps in peaks.values() for p in ps):
        _check(phase, max(peaks["fsdp"]) < max(peaks["dp"]),
               f"peak HBM per chip fsdp {peaks['fsdp']} is not below dp "
               f"{peaks['dp']}", log)
    return {"phase": phase, "ok": True, "device": fsdp["device"],
            "seconds": time.perf_counter() - t0,
            "compile_seconds": sum(
                s["programs"]["train.step"]["compile_s"]
                for s in runs.values()),
            "steps": iters + 1,
            "fsdp_state_bytes_per_device": shards,
            "dp_state_bytes_per_device": whole,
            "fsdp_bytes_in_use_after_init": in_use,
            "fsdp_collectives": coll,
            "dp_collectives": dp["programs"]["train.step"]["collectives"],
            "losses": {r: s["train_losses"] for r, s in runs.items()},
            "max_loss_delta": max(deltas), "tolerance": LOSS_TOL,
            **{r: _speed(s) for r, s in runs.items()}}


# ---------------------------------------------------------------------------
# phase: kernels (A.4) — a child of its own; this is the only code here
# that imports jax, and only in that child
# ---------------------------------------------------------------------------

FLAGSHIP_WIDTHS = dict(nh=12, hs=64, C=768, bs=128, T=1024, B=16,
                       T_long=8192, slots=8, S=1024, chunk=256,
                       moe_tokens=16384, moe_E=8, moe_up=1024, fence_n=8192)


def kernel_numerics(seed: int, platform: str, w: dict = FLAGSHIP_WIDTHS,
                    say=print) -> dict:
    """Run every selectable Pallas kernel once, COMPILED, on the attached
    accelerator, against its plain jax.numpy reference (the ones the
    interpret-mode tests use). Returns {"device", "kernels": [...],
    "fence": {...}, "broken_bounds": [...]} — every row is measured and
    printed even when an earlier one broke its bound."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # NOT cached: these are some 25 one-off programs (each kernel beside an
    # all-f32 reference with its gradients). Cached, they push one smoke
    # run past the 192 MiB a chip machine caps its compile cache at, the
    # LRU then evicts in the order the next run asks, and a second run hit
    # NOTHING (PR 21: train.step 30.3 s cold, 33.9 s "warm"). The programs
    # users wait for — train step, engine steps, generate — are cached by
    # their own CLIs (config.enable_compile_cache).
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    assert dev.platform == platform, (
        f"kernel numerics want a {platform!r} device, jax found "
        f"{dev.platform!r} ({dev.device_kind})")
    from distributed_pytorch_tpu.ops import flash_attention as fa
    from distributed_pytorch_tpu.ops import flash_decode as fd
    from distributed_pytorch_tpu.ops import grouped_matmul as gm
    from distributed_pytorch_tpu.ops.attention_core import _naive_sdpa
    from distributed_pytorch_tpu.ops.block_pool import (kv_lanes, merge_heads,
                                                        paged_gather)
    from distributed_pytorch_tpu.ops.quant import dequantize_int8, quantize_kv

    bf16, f32 = jnp.bfloat16, jnp.float32
    nh, hs, bs = w["nh"], w["hs"], w["bs"]
    scale = hs ** -0.5
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    rows: list = []
    broken: list = []

    def normal(shape, dtype=bf16, std=1.0):
        return (jax.random.normal(next(keys), shape, f32) * std).astype(dtype)

    def record(name, shape, parts, seconds):
        """parts: {label: (got, ref, kind)}, kind in KERNEL_BOUNDS."""
        row = {"kernel": name, "shape": shape, "seconds": round(seconds, 2)}
        for label, (got, ref, kind) in parts.items():
            got = np.asarray(got, np.float32)
            ref = np.asarray(ref, np.float32)
            assert got.shape == ref.shape, (name, label, got.shape, ref.shape)
            assert np.isfinite(got).all(), f"{name} {label} is not finite"
            peak = float(np.abs(ref).max())
            e = float(np.abs(got - ref).max())
            rel = e / peak
            row[label] = {"max_abs_err": e, "ref_abs_max": peak,
                          "measured": rel, "bound": KERNEL_BOUNDS[kind],
                          "kind": kind}
            if rel > KERNEL_BOUNDS[kind]:   # keep going: show every row
                broken.append(
                    f"{name} {label}: max abs error {e:.4g} against a "
                    f"reference peaking at {peak:.4g} -> {rel:.4g} ({kind}),"
                    f" over the {KERNEL_BOUNDS[kind]} bound")
        rows.append(row)
        say(json.dumps(row))

    def ref_attention(q, k, v):
        """Causal softmax attention in f32, one head at a time (lax.map +
        remat) so the 8192-key reference fits beside the kernel."""
        def head(args):
            qh, kh, vh = args                       # (B, T, hs)
            return _naive_sdpa(qh[:, :, None], kh[:, :, None],
                               vh[:, :, None], scale=scale,
                               q_offset=0)[:, :, 0]
        hm = lambda a: jnp.moveaxis(a.astype(f32), 2, 0)  # noqa: E731
        out = jax.lax.map(jax.checkpoint(head), (hm(q), hm(k), hm(v)))
        return jnp.moveaxis(out, 0, 2)

    # ---- flash attention forward + backward (rows layout), two shapes
    for B, T in ((w["B"], w["T"]), (1, w["T_long"])):
        t0 = time.perf_counter()
        q, k, v, g = (normal((B, T, nh, hs)) for _ in range(4))

        def run(fn):
            loss = lambda q, k, v: (fn(q, k, v).astype(f32)  # noqa: E731
                                    * g.astype(f32)).sum()
            return jax.jit(lambda q, k, v: (
                fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))(
                    q, k, v)
        out, (dq, dk, dv) = run(lambda q, k, v: fa.flash_attention(
            q, k, v, scale=scale))
        ro, (rq, rk, rv) = run(ref_attention)
        record("flash_attention fwd+bwd", [B, T, nh, hs],
               {"out": (out, ro, "out"), "dq": (dq, rq, "grad"),
                "dk": (dk, rk, "grad"), "dv": (dv, rv, "grad")},
               time.perf_counter() - t0)
        del q, k, v, g, out, dq, dk, dv, ro, rq, rk, rv

    # ---- decode kernels: contiguous, paged, paged chunk prefill
    S, slots = w["S"], w["slots"]
    q1 = normal((slots, 1, nh, hs))
    cl = jnp.asarray(np.random.default_rng(seed).integers(
        1, S + 1, slots), jnp.int32).at[0].set(S).at[1].set(1)
    kc, vc = normal((slots, S, nh, hs)), normal((slots, S, nh, hs))
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    for name, (k_, v_, kw) in {
            "flash_decode": (kc, vc, {}),
            "flash_decode int8": (kq, vq, {"k_scale": ks, "v_scale": vs})
    }.items():
        t0 = time.perf_counter()
        got = jax.jit(lambda q, k, v, cl, kw=kw: fd.flash_decode(
            q[:, 0], k, v, cl, scale=scale, **kw))(q1, k_, v_, cl)
        kf = dequantize_int8(kq, ks, f32) if kw else kc.astype(f32)
        vf = dequantize_int8(vq, vs, f32) if kw else vc.astype(f32)
        ref = _naive_sdpa(q1.astype(f32), kf, vf, scale=scale,
                          q_offset=cl - 1)[:, 0]
        record(name, [slots, S, nh, hs], {"out": (got, ref, "out")},
               time.perf_counter() - t0)

    W = S // bs                                     # table width
    n_blocks = slots * W + 1
    perm = np.random.default_rng(seed + 1).permutation(n_blocks - 1) + 1
    bt = jnp.asarray(perm[:slots * W].reshape(slots, W), jnp.int32)
    kp, vp = (normal((n_blocks, bs, nh, hs)) for _ in range(2))
    kpq, kps = quantize_kv(kp)
    vpq, vps = quantize_kv(vp)
    # float pools as the engine declares them: heads merged into lanes
    kpm, vpm = (merge_heads(a, kv_lanes(nh, hs)) for a in (kp, vp))
    for name, (k_, v_, kw) in {
            "paged_flash_decode": (kpm, vpm, {"n_kv_heads": nh}),
            "paged_flash_decode int8": (kpq, vpq, {"k_scale": kps,
                                                   "v_scale": vps})
    }.items():
        t0 = time.perf_counter()
        got = jax.jit(lambda q, k, v, bt, cl, kw=kw: fd.paged_flash_decode(
            q[:, 0], k, v, bt, cl, scale=scale, **kw))(q1, k_, v_, bt, cl)
        q8 = "k_scale" in kw
        kf = dequantize_int8(kpq, kps, f32) if q8 else kp.astype(f32)
        vf = dequantize_int8(vpq, vps, f32) if q8 else vp.astype(f32)
        ref = _naive_sdpa(q1.astype(f32), paged_gather(kf, bt),
                          paged_gather(vf, bt), scale=scale,
                          q_offset=cl - 1)[:, 0]
        record(name, [slots, W, bs, nh, hs], {"out": (got, ref, "out")},
               time.perf_counter() - t0)

    t0 = time.perf_counter()
    chunk, off = w["chunk"], 2 * bs                 # two prior blocks
    qc = normal((1, chunk, nh, hs))
    got = jax.jit(lambda q, k, v, bt, o: fd.paged_flash_prefill(
        q, k, v, bt, o, scale=scale, n_kv_heads=nh))(
            qc, kpm, vpm, bt[:1], jnp.int32(off))
    ref = _naive_sdpa(qc.astype(f32), paged_gather(kp.astype(f32), bt[:1]),
                      paged_gather(vp.astype(f32), bt[:1]), scale=scale,
                      q_offset=off)
    record("paged_flash_prefill", [chunk, W, bs, nh, hs],
           {"out": (got, ref, "out")}, time.perf_counter() - t0)
    del kc, vc, kq, vq, kp, vp, kpm, vpm, kpq, vpq

    # ---- grouped matmul: the dropless MoE dispatch, forward + dx + dW
    t0 = time.perf_counter()
    N, E, U, C = w["moe_tokens"], w["moe_E"], w["moe_up"], w["C"]
    n_shared, topk = 1, 2
    xf = normal((N, C))
    fc, pj = normal((E, C, 2 * U), std=0.02), normal((E, U, C), std=0.02)
    idx = jax.random.randint(next(keys), (N, topk), 0, E - n_shared)
    gates = jax.nn.softmax(normal((N, topk), f32), axis=-1)
    gsum = normal((N, C))

    def ref_moe(x, fc, pj):
        """Every expert on every token, combined by the gates (f32)."""
        x, fc, pj = x.astype(f32), fc.astype(f32), pj.astype(f32)
        weight = jnp.zeros((N, E), f32).at[:, :n_shared].set(1.0)
        weight = weight.at[jnp.arange(N)[:, None], idx + n_shared].add(gates)

        def expert(carry, ew):
            fc_e, pj_e, w_e = ew
            h = x @ fc_e
            h = jax.nn.silu(h[:, :U]) * h[:, U:]
            return carry + w_e[:, None] * (h @ pj_e), None
        return jax.lax.scan(expert, jnp.zeros((N, C), f32),
                            (fc, pj, weight.T))[0]

    def run_moe(fn):
        loss = lambda x, fc, pj: (fn(x, fc, pj).astype(f32)  # noqa: E731
                                  * gsum.astype(f32)).sum()
        return jax.jit(lambda x, fc, pj: (
            fn(x, fc, pj), jax.grad(loss, argnums=(0, 1, 2))(x, fc, pj)))(
                xf, fc, pj)
    out, (dx, dfc, dpj) = run_moe(lambda x, fc, pj: gm.grouped_dispatch(
        x, idx, gates, fc, pj, non_linearity="swiglu", n_shared=n_shared))
    ro, (rx, rfc, rpj) = run_moe(ref_moe)
    record("grouped_matmul dispatch fwd+dx+dW", [N, C, E, U],
           {"out": (out, ro, "out"), "dx": (dx, rx, "grad"),
            "dW_fc": (dfc, rfc, "grad"), "dW_proj": (dpj, rpj, "grad")},
           time.perf_counter() - t0)

    # ---- is block_until_ready a fence? a dispatch-only loop must time
    # far below the same loop fenced (jax returns before the device ends)
    a = normal((w["fence_n"], w["fence_n"]))
    mm = jax.jit(lambda a: (a @ a) * 1e-2)
    mm(a).block_until_ready()
    n = 30
    t0 = time.perf_counter()
    y = a
    for _ in range(n):
        y = mm(y)
    dispatch_s = time.perf_counter() - t0
    y.block_until_ready()
    fenced_s = time.perf_counter() - t0
    fence = {"matmuls": n, "dispatch_only_s": dispatch_s,
             "fenced_s": fenced_s}
    if platform != "cpu":                 # the CPU backend runs inline
        assert fenced_s > 2 * dispatch_s, (
            f"block_until_ready did not wait: {fence}")
    from distributed_pytorch_tpu.obs.paths import device_record
    return {"device": device_record(), "kernels": rows, "fence": fence,
            "broken_bounds": broken}


def compile_cache_mib() -> dict:
    """Where the children's persistent compile cache lives (the package's
    one placement rule, imported lazily: config.py pulls in no jax) and
    how much it holds now — read beside each phase's compile seconds, it
    says whether a run started cold and whether it fits the machine's cap."""
    sys.path.insert(0, ROOT)
    from distributed_pytorch_tpu.config import COMPILE_CACHE_DIR
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    size = sum(os.path.getsize(os.path.join(r, f))
               for r, _, fs in os.walk(d) for f in fs)
    return {"dir": d, "mib": round(size / 2 ** 20, 1)}


def phase_kernels(out: str, *, seed: int, env: dict | None = None) -> dict:
    """A.4, as a child of this same file (the one phase with no CLI of its
    own in the package). The child insists on a TPU: a compiled Pallas
    kernel runs nowhere else, so this phase takes no platform."""
    phase, log = "kernels", os.path.join(out, "kernels.log")
    path = os.path.join(out, "kernels.json")
    if os.path.exists(path):
        os.remove(path)
    t0 = time.perf_counter()
    run_child(phase, [sys.executable, os.path.abspath(__file__), "--child",
                      "kernels", "--seed", str(seed), "--out", out],
              cwd=out, log=log, env=env, timeout=900)
    res = _load_json(phase, path, log)
    return {"phase": phase, "ok": True, "device": res["device"],
            "seconds": time.perf_counter() - t0,
            "compile_seconds": "not separated (each row's seconds are "
                               "compile + run of kernel and reference)",
            "kernels": res["kernels"], "block_until_ready": res["fence"]}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"),
                    help="data, checkpoints and logs land here (gitignored)")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: 6 steps of fsdp and the same 6 of dp, "
                         "and no other phase")
    ap.add_argument("--child", choices=["kernels"], help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    platform = "tpu"        # there is no other way to run this file

    if args.child:          # the kernels phase, inside its own process
        res = kernel_numerics(args.seed, platform)
        with open(os.path.join(out, "kernels.json"), "w") as f:
            json.dump(res, f)
        for line in res["broken_bounds"]:
            print(f"BOUND BROKEN: {line}")
        return 1 if res["broken_bounds"] else 0

    def run(phase_fn, *a, **kw) -> dict:
        rec = phase_fn(*a, seed=args.seed, **kw)
        rec["compile_cache_after"] = compile_cache_mib()
        emit(rec)
        return rec

    try:
        emit({"compile_cache_before": compile_cache_mib()})
        if args.multichip:
            rec = run(phase_multichip, out, platform=platform)
        else:
            run(phase_kernels, out)
            rec = run(phase_train, out, platform=platform)
            rec = run(phase_serve, out, rec["checkpoint"], platform=platform,
                      expect_kernels=ENGINE_KERNELS)
        device = rec["device"]
    except PhaseFailed as e:
        print(f"---- output of the failed phase ({e.phase}) ----")
        print(e.log)
        emit({"phase": e.phase, "ok": False, "error": e.why})
        return 1
    finally:
        # logs, stats.json and the run records stay; the 1.5 GB of
        # checkpoint state does not (the tool that copies --out back caps
        # what it carries)
        ckpts = os.path.join(out, "checkpoints")
        for run in os.listdir(ckpts) if os.path.isdir(ckpts) else ():
            for d in os.listdir(os.path.join(ckpts, run)):
                if d.startswith("step_"):
                    shutil.rmtree(os.path.join(ckpts, run, d),
                                  ignore_errors=True)
    emit({"claim": None, "note": "smoke, not a benchmark"})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
