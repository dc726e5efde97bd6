"""Headline benchmark: flagship GPT (124M-class) training throughput on the
chip. Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference publishes no numbers (BASELINE.md) — the driver-set north
star is >=50% MFU on the FSDP config (BASELINE.json), so `vs_baseline` is
measured MFU / 0.50 (1.0 == target met).

Contract: `python bench.py` measures on a TPU or FAILS. The parent (this
file) never imports jax — a chip belongs to one process at a time, so a
parent that touched it would starve its own workers — and runs every leg
in a worker subprocess that asserts the backend it was asked for. A leg
that fails (no chip, a compile the chip refuses, a crash, a timeout)
fails the whole run with a non-zero exit and no metric line: there is no
probe, no retry loop, no CPU rerun and no always-exit-0 error line.
`python bench.py --worker cpu` remains as an explicit CPU *correctness*
mode (tiny proxy models, accept booleans); every metric it prints keeps
the `cpu_proxy_` prefix and is never a device number. ROADMAP S0/D1
replaces the rest of this file.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def _decode_bench(platform: str) -> dict:
    """Decode-path legs (BENCH_DECODE=1): prefill latency, steady-state
    tokens/sec/chip at full slot occupancy, and a ragged-admission window
    (random per-sequence budgets -> slots retire and refill) with its
    occupancy — the numbers a chip run needs to A/B flash-decode
    vs naive (FLASH_DECODE env) and size the serving config. Emits the same
    one-line JSON schema as the training legs."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.train import metrics as M

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "32"))
        dtype, iters, ragged_lo, ragged_hi = jnp.bfloat16, 32, 8, 64
        preset = "gpt2_124m"
    else:  # CPU proxy: tiny model so the harness still gets a line
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots = 128, 4
        dtype, iters, ragged_lo, ragged_hi = jnp.float32, 8, 2, 6
        preset = "cpu_tiny"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    # quantized-serving knobs (round 9): BENCH_CACHE_DTYPE=int8 quantizes
    # the KV cache, BENCH_QUANT_W=1 the decode weights — the decode_int8
    # A/B leg vs the bf16 decode_flash/decode_naive legs
    cache_dtype = os.environ.get("BENCH_CACHE_DTYPE", "") or None
    quant_w = os.environ.get("BENCH_QUANT_W", "") == "1"
    eng = DecodeEngine(model, variables, n_slots=slots, max_len=S,
                       temperature=1.0, top_k=50,
                       cache_dtype=cache_dtype, quantize_weights=quant_w)

    prompt_len = S // 2
    npr = np.random.default_rng(0)

    def mk():
        return list(npr.integers(0, cfg.vocab_size, prompt_len))

    big = 10 ** 9  # never retire by budget inside the timed window
    t0 = time.perf_counter()
    eng.admit(mk(), big)                     # compiles the prefill bucket
    prefill_compile_s = time.perf_counter() - t0
    prefill_times = []
    for _ in range(min(3, slots - 1)):
        t0 = time.perf_counter()
        eng.admit(mk(), big)
        prefill_times.append(time.perf_counter() - t0)
    while eng.free_slots:
        eng.admit(mk(), big)
    eng.step()                               # compiles the fused step
    # BENCH_PROFILE=1: wrap the steady window in a device-profiler
    # capture (obs/profile.py) so a TPU-window leg ships an xplane next
    # to its JSON line
    from distributed_pytorch_tpu.obs import profile as obs_profile
    with obs_profile.profile_trace(
            run="bench_decode",
            enabled=os.environ.get("BENCH_PROFILE", "") == "1") as prof:
        t0 = time.perf_counter()
        for _ in range(iters):
            eng.step()
        jax.device_get(eng.tok)
        dt = time.perf_counter() - t0
    steady = slots * iters / dt

    # MBU from the bytes-moved model at the window's mean cache length,
    # priced at the TRUE per-tensor itemsizes (int8 cache = 1 byte + its
    # f32 scale sidecars; quantized weights = 1 byte + per-channel scales)
    mean_len = prompt_len + 1 + iters // 2
    bw = M.peak_hbm_bw_per_chip()
    cache_size = jnp.dtype(eng.cache_dtype).itemsize
    bytes_step = M.decode_step_bytes(cfg, slots, mean_len,
                                     param_dtype_size=jnp.dtype(dtype).itemsize,
                                     cache_dtype_size=cache_size,
                                     quant_weights=eng.weights_quantized)
    mbu = (bytes_step * iters / dt) / (bw * n_dev) if bw else None

    # ragged window: drain the full slots with random budgets via fresh
    # admissions as they retire; occupancy = mean live fraction
    for sid in eng.live_seq_ids:             # re-budget the live set
        eng.set_budget(sid, int(npr.integers(ragged_lo, ragged_hi)))
    queue = [(mk(), int(npr.integers(ragged_lo, ragged_hi)))
             for _ in range(slots)]
    live_steps, ragged_steps, ragged_toks = [], 0, 0
    t0 = time.perf_counter()
    while queue or eng.n_live:
        while queue and eng.free_slots:
            p, budget = queue.pop(0)
            eng.admit(p, budget)
        if eng.n_live:
            live_steps.append(eng.n_live)
            ragged_toks += eng.n_live
            eng.step()
            ragged_steps += 1
    ragged_dt = time.perf_counter() - t0
    occupancy = float(np.mean(live_steps) / slots) if live_steps else 0.0

    return {"metric": ("decode_tokens_per_sec_per_chip" if platform == "tpu"
                       else "cpu_proxy_decode_tokens_per_sec_per_chip"),
            "value": round(steady / n_dev, 1), "unit": "tok/s/chip",
            "vs_baseline": 0,
            "prefill_ms": round(float(np.median(prefill_times)) * 1e3, 2)
            if prefill_times else None,
            "prefill_compile_s": round(prefill_compile_s, 2),
            "prefill_tokens": prompt_len,
            "ragged_tokens_per_sec_per_chip":
                round(ragged_toks / ragged_dt / n_dev, 1),
            "ragged_occupancy": round(occupancy, 3),
            "mbu": round(mbu, 4) if mbu is not None else None,
            "n_slots": slots, "cache_len": S,
            "flash_decode": os.environ.get("FLASH_DECODE", "auto"),
            "cache_dtype": jnp.dtype(eng.cache_dtype).name,
            "quant_w": eng.weights_quantized,
            "n_chips": n_dev, "device": jax.devices()[0].device_kind,
            "preset": preset,
            **({"profile_dir": prof} if prof else {})}


def _serve_bench(platform: str) -> dict:
    """serve_load leg (BENCH_SERVE=1): seeded Poisson arrivals against the
    async scheduler (serve/scheduler.py — no HTTP, so the number isolates
    scheduling + engine, not socket parsing). Offered load is set ~1.3x
    the probed steady service rate, so the queue genuinely fills: the leg
    reports the latency SLO quantiles (TTFT/ITL p50/p99), delivered
    tok/s/chip, shed rate at the admission bound, and mean slot occupancy
    — the occupancy-vs-shed tradeoff the ROADMAP's serve A/B reads.

    BENCH_SERVE_PREFIX=0.8 turns it into the serve_load_prefix leg: that
    fraction of requests share a fixed multi-block system prompt, the
    block pool is sized TIGHT (~80% of slot-cache-equivalent, so
    block-level preemption genuinely fires and must requeue, not lose),
    and the SAME traffic runs twice — prefix cache on vs off — so the
    line reports the prefix-cache hit rate, prefilled-tokens-per-request
    reduction, and the TTFT collapse vs the no-reuse baseline."""
    import asyncio
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.obs import trace as obs_trace
    from distributed_pytorch_tpu.serve.scheduler import Scheduler, ShedError

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "32"))
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "128"))
        dtype = jnp.bfloat16
        n_req, p_lo, p_hi, b_lo, b_hi = 192, 64, 512, 16, 96
        preset = "gpt2_124m"
    else:  # CPU proxy: tiny model so the harness still gets a line
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots, dtype = 128, 4, jnp.float32
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "16"))
        n_req, p_lo, p_hi, b_lo, b_hi = 32, 4, 48, 4, 12
        preset = "cpu_tiny"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    cache_dtype = os.environ.get("BENCH_CACHE_DTYPE", "") or None
    quant_w = os.environ.get("BENCH_QUANT_W", "") == "1"
    prefix_frac = float(os.environ.get("BENCH_SERVE_PREFIX", "0") or 0)
    # prefix leg: size the pool TIGHT (prefix sharing reclaims most of
    # it) so block-level preemption actually exercises the requeue path;
    # plain leg keeps the slot-cache-equivalent default
    n_blocks = (int(slots * (S // kv_block) * 0.7) + 1
                if prefix_frac > 0 else None)

    def make_engine(prefix_cache: bool) -> "DecodeEngine":
        return DecodeEngine(model, variables, n_slots=slots, max_len=S,
                            temperature=1.0, top_k=50,
                            cache_dtype=cache_dtype,
                            quantize_weights=quant_w, block_size=kv_block,
                            n_blocks=n_blocks, prefix_cache=prefix_cache)

    npr = np.random.default_rng(0)
    if prefix_frac > 0:
        # 80%-shared traffic: a fixed system prompt of several full
        # blocks plus a short per-request tail; the rest fully random
        sys_prompt = list(npr.integers(0, cfg.vocab_size, 5 * kv_block))
        reqs = []
        for _ in range(n_req):
            if npr.random() < prefix_frac:
                tail = list(npr.integers(
                    0, cfg.vocab_size,
                    int(npr.integers(1, kv_block // 2 + 2))))
                prompt = sys_prompt + tail
            else:
                prompt = list(npr.integers(0, cfg.vocab_size,
                                           int(npr.integers(p_lo, p_hi))))
            reqs.append((prompt, int(npr.integers(b_lo, b_hi))))
    else:
        reqs = [(list(npr.integers(0, cfg.vocab_size,
                                   int(npr.integers(p_lo, p_hi)))),
                 int(npr.integers(b_lo, b_hi)))
                for _ in range(n_req)]

    eng = make_engine(prefix_cache=True)

    def warm(e):
        # warm every prefill bucket + the fused step OUTSIDE the timed
        # window (a 1-token budget retires at admission instantly)
        for bucket in sorted({e.prefill_bucket(len(p)) for p, _ in reqs}):
            e.admit(list(npr.integers(0, cfg.vocab_size, bucket)), 1)
        e.admit(reqs[0][0], 2)
        e.step()

    warm(eng)

    # probe the steady step time at full occupancy -> offered arrival rate
    while eng.free_slots:
        eng.admit(list(npr.integers(0, cfg.vocab_size,
                                    min(p_hi, S // 2) - 1)), 10 ** 9)
    eng.step()
    t0 = time.perf_counter()
    probe_steps = 8
    for _ in range(probe_steps):
        eng.step()
    jax.device_get(eng.tok)
    step_s = (time.perf_counter() - t0) / probe_steps
    for sid in eng.live_seq_ids:               # drain the probe set
        eng.set_budget(sid, 1)
    while eng.n_live:
        eng.step()

    mean_budget = (b_lo + b_hi) / 2
    load_factor = float(os.environ.get("BENCH_SERVE_LOAD", "1.3"))
    req_rate = slots / (mean_budget * step_s) * load_factor
    gaps = npr.exponential(1.0 / req_rate, size=n_req)
    arrivals = np.cumsum(gaps)

    def drive(e):
        async def _run():
            sched = Scheduler(e, max_queue=4 * slots)
            await sched.start()
            consumers, shed = [], 0
            start = time.perf_counter()
            for (prompt, budget), at in zip(reqs, arrivals):
                delay = start + at - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                try:
                    # trace every request: spans are emitted once at
                    # retirement (request-scale, never token-scale), and
                    # the span ring becomes the trace.jsonl artifact
                    h = sched.submit(prompt, budget,
                                     trace_id=obs_trace.new_trace_id())
                except ShedError:
                    shed += 1
                    continue
                consumers.append(asyncio.ensure_future(h.result()))
            await asyncio.gather(*consumers, return_exceptions=True)
            dt = time.perf_counter() - start
            await sched.stop()
            return sched, shed, dt

        return asyncio.run(_run())

    # snapshot prefix counters so warm/probe admissions don't pollute the
    # timed window's hit-rate / prefilled-per-request accounting
    pre = (eng.prompt_tokens, eng.prefix_hit_tokens, eng.prefilled_tokens)
    sched, shed, dt = drive(eng)
    d_prompt = eng.prompt_tokens - pre[0]
    d_hit = eng.prefix_hit_tokens - pre[1]
    d_prefilled = eng.prefilled_tokens - pre[2]
    s = sched.metrics.summary()
    toks = sched.metrics.counters["tokens_out"]
    admitted = max(sched.metrics.counters["admitted"]
                   - sched.metrics.counters["requeued"], 1)
    out = {"metric": ("serve_tokens_per_sec_per_chip" if platform == "tpu"
                      else "cpu_proxy_serve_tokens_per_sec_per_chip"),
           "value": round(toks / dt / n_dev, 1), "unit": "tok/s/chip",
           "vs_baseline": 0,
           "ttft_p50_ms": s["ttft"].get("p50_ms"),
           "ttft_p99_ms": s["ttft"].get("p99_ms"),
           "itl_p50_ms": s["itl"].get("p50_ms"),
           "itl_p99_ms": s["itl"].get("p99_ms"),
           "e2e_p50_ms": s["e2e"].get("p50_ms"),
           "queue_wait_p99_ms": s["queue_wait"].get("p99_ms"),
           "shed_rate": round(shed / n_req, 3),
           "mean_occupancy": s["mean_occupancy"],
           "probe_step_ms": round(step_s * 1e3, 2),
           "offered_rps": round(req_rate, 2), "load_factor": load_factor,
           "n_requests": n_req, "n_slots": slots, "cache_len": S,
           "kv_block": kv_block, "n_kv_blocks": eng.n_blocks,
           "block_utilization": round(eng.block_utilization, 4),
           "flash_decode": os.environ.get("FLASH_DECODE", "auto"),
           "cache_dtype": jnp.dtype(eng.cache_dtype).name,
           "quant_w": eng.weights_quantized,
           "n_chips": n_dev, "device": jax.devices()[0].device_kind,
           "preset": preset}
    if prefix_frac > 0:
        # the no-reuse baseline: SAME traffic, fresh engine with the
        # prefix cache off — the pair the acceptance criteria compare
        base_eng = make_engine(prefix_cache=False)
        warm(base_eng)
        base_pre = base_eng.prefilled_tokens
        base_sched, base_shed, base_dt = drive(base_eng)
        bs_ = base_sched.metrics.summary()
        lost = (n_req - shed - sched.metrics.counters["completed"])
        out.update({
            "prefix_frac": prefix_frac,
            "prefix_hit_rate": round(d_hit / max(d_prompt, 1), 4),
            "prefilled_per_request": round(d_prefilled / admitted, 1),
            "prefilled_per_request_baseline": round(
                (base_eng.prefilled_tokens - base_pre)
                / max(base_sched.metrics.counters["admitted"]
                      - base_sched.metrics.counters["requeued"], 1), 1),
            "preempted": sched.metrics.counters["preempted"],
            "requeued": sched.metrics.counters["requeued"],
            "lost_to_preemption": lost,
            "baseline_ttft_p50_ms": bs_["ttft"].get("p50_ms"),
            "baseline_ttft_p99_ms": bs_["ttft"].get("p99_ms"),
            "baseline_shed_rate": round(base_shed / n_req, 3),
            "baseline_tokens_per_sec_per_chip": round(
                base_sched.metrics.counters["tokens_out"]
                / base_dt / n_dev, 1),
        })
        ppr, base_ppr = (out["prefilled_per_request"],
                         out["prefilled_per_request_baseline"])
        out["prefill_reduction_x"] = round(base_ppr / max(ppr, 1e-9), 2)
    # persist the observability artifacts (ISSUE 9): the engine's
    # step-level flight timeline and the per-request trace spans go to
    # runs/, referenced from the JSON line so the TPU-window analysis
    # (PERF.md latency models) can replay the drive post-hoc
    try:
        art_dir = os.path.join("runs", f"bench_serve_{int(time.time())}")
        arts = {"step_timeline": eng.flight.dump_jsonl(
            os.path.join(art_dir, "timeline.jsonl"))}
        rec = obs_trace.get_recorder()
        if len(rec):
            arts["trace"] = rec.dump_jsonl(
                os.path.join(art_dir, "trace.jsonl"))
        # replay the fresh artifacts into the per-phase report + fitted
        # cost model (obs/replay.py) — the post-hoc analysis inline
        from distributed_pytorch_tpu.obs import replay
        rep = replay.write_report(art_dir)
        arts["report_md"] = rep["report_md"]
        arts["cost_model_json"] = rep["cost_model_json"]
        out["artifacts"] = arts
    except Exception as e:  # noqa: BLE001 — artifacts never sink the leg
        out["artifacts_error"] = repr(e)
    return out


def _serve_tier_bench(platform: str) -> dict:
    """serve_load_tier leg (BENCH_SERVE=1 BENCH_SERVE_TIER=1): the
    host-RAM KV tier A/B (ISSUE 17). Same seeded 80%-shared-prefix
    Poisson traffic as serve_load_prefix, but the HBM block pool is
    clamped to ~0.1x the traffic's no-reuse working set, so the LRU
    genuinely evicts retired shared-prefix chains mid-drive. Tier OFF,
    those evictions drop the KV and every re-arrival re-prefills the
    system prompt; tier ON, the same evictions demote to host RAM and
    the next radix hit promotes the chain back with one batched
    device_put. The SAME arrival schedule runs both ways and the line
    reports the tier's demote/promote/drop counters, host hit rate,
    prefix hit rate both ways, and the accept booleans the ROADMAP
    reads: zero blocks dropped at the host budget and zero requests
    lost, hit rate recovered vs the tier-off collapse, and tier TTFT
    p50 bounded by 1.5x tier-off (a promote must cost a host->HBM
    copy, never a re-prefill)."""
    import asyncio
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.serve.scheduler import Scheduler, ShedError

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "32"))
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "128"))
        dtype = jnp.bfloat16
        n_req, p_lo, p_hi, b_lo, b_hi = 192, 64, 512, 16, 96
        preset = "gpt2_124m"
    else:  # CPU proxy mirrors _serve_bench's tiny model
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots, dtype = 128, 4, jnp.float32
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "16"))
        n_req, p_lo, p_hi, b_lo, b_hi = 32, 4, 48, 4, 12
        preset = "cpu_tiny"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    cache_dtype = os.environ.get("BENCH_CACHE_DTYPE", "") or None

    # serve_load_prefix's exact traffic shape and rng seed: 80% of the
    # requests share a fixed 5-block system prompt + short tail
    prefix_frac = 0.8
    npr = np.random.default_rng(0)
    sys_prompt = list(npr.integers(0, cfg.vocab_size, 5 * kv_block))
    reqs = []
    for _ in range(n_req):
        if npr.random() < prefix_frac:
            tail = list(npr.integers(
                0, cfg.vocab_size, int(npr.integers(1, kv_block // 2 + 2))))
            reqs.append((sys_prompt + tail, int(npr.integers(b_lo, b_hi))))
        else:
            reqs.append((list(npr.integers(0, cfg.vocab_size,
                                           int(npr.integers(p_lo, p_hi)))),
                         int(npr.integers(b_lo, b_hi))))

    # the no-reuse working set (blocks to hold every request's full
    # chain), then the clamp: the HBM pool gets ~0.1x of it — floored
    # so one full-length sequence plus the shared prefix always fits,
    # else a single request could deadlock the pool
    ws_blocks = sum((len(p) + b) // kv_block + 1 for p, b in reqs)
    n_blocks = max(int(0.1 * ws_blocks) + 1,
                   S // kv_block + len(sys_prompt) // kv_block + 2)

    def make_engine(tier: bool, pool: int = 0) -> "DecodeEngine":
        return DecodeEngine(model, variables, n_slots=slots, max_len=S,
                            temperature=1.0, top_k=50,
                            cache_dtype=cache_dtype, block_size=kv_block,
                            n_blocks=pool or n_blocks, prefix_cache=True,
                            host_tier=tier,
                            host_blocks=ws_blocks if tier else None)

    def warm(e):
        for bucket in sorted({e.prefill_bucket(len(p)) for p, _ in reqs}):
            e.admit(list(npr.integers(0, cfg.vocab_size, bucket)), 1)
        e.admit(reqs[0][0], 2)
        e.step()

    eng = make_engine(tier=True)
    warm(eng)

    # probe the steady step time -> offered arrival rate (~1.3x
    # service); the clamped pool may not fit every slot's probe
    # sequence — fill as many as it allows, the step time is what counts
    from distributed_pytorch_tpu.ops.block_pool import NoFreeBlocks
    while eng.free_slots:
        try:
            eng.admit(list(npr.integers(0, cfg.vocab_size,
                                        min(p_hi, S // 2) - 1)), 10 ** 9)
        except NoFreeBlocks:
            break
    eng.step()
    t0 = time.perf_counter()
    probe_steps = 8
    for _ in range(probe_steps):
        eng.step()
    jax.device_get(eng.tok)
    step_s = (time.perf_counter() - t0) / probe_steps
    for sid in eng.live_seq_ids:
        eng.set_budget(sid, 1)
    while eng.n_live:
        eng.step()

    # compile the promote program OUTSIDE the timed window (the step
    # family is warmed above; the batched host->HBM copy is its own
    # program): retire a multi-block chain, churn the clamped pool so
    # the LRU demotes it to the host tier, then re-admit the same
    # prompt — the radix hit promotes the chain back and compiles
    wp = list(npr.integers(0, cfg.vocab_size, 3 * kv_block))
    eng.admit(wp, 1)
    eng.step()
    for _ in range(6):
        try:
            eng.admit(list(npr.integers(0, cfg.vocab_size, S - kv_block)),
                      1)
        except NoFreeBlocks:
            break
        eng.step()
    eng.admit(wp, 1)
    while eng.n_live:
        eng.step()

    # offered load sits BELOW saturation (0.6x, vs serve_load's 1.3x):
    # the failure mode under test is IDLE-prefix eviction — a saturated
    # drive keeps the shared prefix pinned by live refcounts, so the
    # clamped pool would never evict it and both arms would look alike.
    # Sub-saturation Poisson gaps let the prefix go refcount-0, the
    # churn evicts it, and the two arms genuinely diverge.
    mean_budget = (b_lo + b_hi) / 2
    load_factor = float(os.environ.get("BENCH_SERVE_LOAD", "0.6"))
    req_rate = slots / (mean_budget * step_s) * load_factor
    arrivals = np.cumsum(npr.exponential(1.0 / req_rate, size=n_req))

    def drive(e):
        async def _run():
            sched = Scheduler(e, max_queue=4 * slots)
            await sched.start()
            consumers, shed = [], 0
            start = time.perf_counter()
            for (prompt, budget), at in zip(reqs, arrivals):
                delay = start + at - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                try:
                    h = sched.submit(prompt, budget)
                except ShedError:
                    shed += 1
                    continue
                consumers.append(asyncio.ensure_future(h.result()))
            await asyncio.gather(*consumers, return_exceptions=True)
            dt = time.perf_counter() - start
            await sched.stop()
            return sched, shed, dt

        return asyncio.run(_run())

    def run_arm(e):
        pre = (e.prompt_tokens, e.prefix_hit_tokens, e.prefilled_tokens)
        sched, shed, dt = drive(e)
        admitted = max(sched.metrics.counters["admitted"]
                       - sched.metrics.counters["requeued"], 1)
        s = sched.metrics.summary()
        return {"hit_rate": ((e.prefix_hit_tokens - pre[1])
                             / max(e.prompt_tokens - pre[0], 1)),
                "prefilled_per_request": (e.prefilled_tokens - pre[2])
                / admitted,
                "ttft_p50_ms": s["ttft"].get("p50_ms"),
                "ttft_p99_ms": s["ttft"].get("p99_ms"),
                "itl_p50_ms": s["itl"].get("p50_ms"),
                "itl_p99_ms": s["itl"].get("p99_ms"),
                "shed_rate": round(shed / n_req, 3),
                "lost": n_req - shed - sched.metrics.counters["completed"],
                "tok_s_chip": round(sched.metrics.counters["tokens_out"]
                                    / dt / n_dev, 1)}

    # arm 1 — tier ON, clamped pool (warm/probe snapshotted out)
    tpre = dict(eng.host_tier.counters())
    on = run_arm(eng)
    tier_c = {k: v - tpre.get(k, 0)
              for k, v in eng.host_tier.counters().items()
              if k in ("demoted", "promoted", "dropped")}

    # arm 2 — tier OFF, SAME clamped pool, SAME arrivals: evictions
    # drop KV outright, so the shared prefix keeps re-prefilling
    base_eng = make_engine(tier=False)
    warm(base_eng)
    off = run_arm(base_eng)

    # arm 3 — the warm-HBM reference: tier off, pool sized past the
    # whole working set so NOTHING ever evicts. This is the
    # serve_load_prefix-equivalent ceiling the ISSUE's "within 10%"
    # hit-rate bound and "1.5x warm-HBM" TTFT bound compare against.
    warm_eng = make_engine(
        tier=False, pool=ws_blocks + slots * (S // kv_block) + 1)
    warm(warm_eng)
    ref = run_arm(warm_eng)

    return {"metric": ("serve_tokens_per_sec_per_chip" if platform == "tpu"
                       else "cpu_proxy_serve_tokens_per_sec_per_chip"),
            "value": on["tok_s_chip"], "unit": "tok/s/chip",
            "vs_baseline": 0,
            "ttft_p50_ms": on["ttft_p50_ms"],
            "ttft_p99_ms": on["ttft_p99_ms"],
            "itl_p50_ms": on["itl_p50_ms"], "itl_p99_ms": on["itl_p99_ms"],
            "shed_rate": on["shed_rate"],
            "prefix_frac": prefix_frac,
            "n_kv_blocks": n_blocks, "working_set_blocks": ws_blocks,
            "pool_clamp_x": round(n_blocks / ws_blocks, 3),
            "host_tier_blocks": ws_blocks,
            "tier_demoted_blocks": tier_c.get("demoted", 0),
            "tier_promoted_blocks": tier_c.get("promoted", 0),
            "tier_dropped_blocks": tier_c.get("dropped", 0),
            "host_tier_hit_rate": round(eng.host_tier_hit_rate, 4),
            "host_tier_occupancy": round(eng.host_tier_occupancy, 4),
            "prefix_hit_rate": round(on["hit_rate"], 4),
            "prefix_hit_rate_tier_off": round(off["hit_rate"], 4),
            "prefix_hit_rate_warm_hbm": round(ref["hit_rate"], 4),
            "prefilled_per_request": round(on["prefilled_per_request"], 1),
            "prefilled_per_request_tier_off": round(
                off["prefilled_per_request"], 1),
            "prefilled_per_request_warm_hbm": round(
                ref["prefilled_per_request"], 1),
            "tier_off_ttft_p50_ms": off["ttft_p50_ms"],
            "tier_off_shed_rate": off["shed_rate"],
            "tier_off_tokens_per_sec_per_chip": off["tok_s_chip"],
            "warm_hbm_ttft_p50_ms": ref["ttft_p50_ms"],
            "warm_hbm_tokens_per_sec_per_chip": ref["tok_s_chip"],
            "lost_to_preemption": on["lost"],
            "tier_off_lost_to_preemption": off["lost"],
            # the accept booleans (ISSUE 17): nothing dropped at the
            # host budget and no request lost; the tier holds the
            # warm-HBM hit rate within 10% despite the 0.1x pool; a
            # tier hit costs a host->HBM copy, never a re-prefill
            # (TTFT p50 within 1.5x of warm HBM); and the tier-off arm
            # demonstrably re-prefills more than the tier does
            "accept_zero_lost_to_eviction": bool(
                tier_c.get("dropped", 0) == 0 and on["lost"] == 0),
            "accept_hit_rate_held": bool(
                on["hit_rate"] >= 0.9 * ref["hit_rate"]),
            "accept_tier_ttft_bounded": bool(
                on["ttft_p50_ms"] is not None
                and ref["ttft_p50_ms"] is not None
                and on["ttft_p50_ms"] <= 1.5 * ref["ttft_p50_ms"]),
            "accept_tier_off_collapses": bool(
                off["prefilled_per_request"]
                > on["prefilled_per_request"]),
            "probe_step_ms": round(step_s * 1e3, 2),
            "offered_rps": round(req_rate, 2), "load_factor": load_factor,
            "n_requests": n_req, "n_slots": slots, "cache_len": S,
            "kv_block": kv_block,
            "cache_dtype": jnp.dtype(eng.cache_dtype).name,
            "n_chips": n_dev, "device": jax.devices()[0].device_kind,
            "preset": preset}


def _serve_chunked_bench(platform: str) -> dict:
    """serve_load_chunked leg (BENCH_SERVE=1 BENCH_PREFILL_CHUNK=
    128,256,512): the chunked-prefill A/B the round-12 latency model
    predicts. Same seeded Poisson machinery as `_serve_bench`, but the
    traffic is PREFILL-HEAVY (long prompts, short budgets — the workload
    where the wave baseline's admissions stall every live stream for a
    full bucket prefill), and the SAME seeded arrival sequence runs at a
    base load AND at double it ("prefill-heavy load doubles") against
    the wave engine (prefill_chunk=0) and one engine per swept chunk
    size. Two denominators are probed, one per system's own steady step:
    the pure-decode step (the wave's service time) and the chunk-
    carrying fused step (the chunked system's — on TPU the chunk rides
    the bandwidth-bound weight read nearly free; on the CPU proxy the
    second forward is dispatch-bound, ~2x, which this probe prices
    honestly). The acceptance bar: chunked ITL p99 <= 1.5x its probed
    fused step at BOTH load points (bounded tail — nothing beyond the
    budgeted per-step work) where the wave's ITL p99 exceeds 3x its
    step (the unbounded admission stall)."""
    import asyncio
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.serve.scheduler import Scheduler, ShedError

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "32"))
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "128"))
        dtype = jnp.bfloat16
        n_req, p_lo, p_hi, b_lo, b_hi = 128, S // 2, int(S * 0.9), 8, 32
        preset = "gpt2_124m"
    else:  # CPU proxy: tiny model, same shape of contrast
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots, dtype = 128, 4, jnp.float32
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "16"))
        n_req, p_lo, p_hi, b_lo, b_hi = 32, 64, 120, 6, 16
        preset = "cpu_tiny"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    chunks = [int(c) for c in
              os.environ["BENCH_PREFILL_CHUNK"].replace("/", ",").split(",")
              if c.strip()]
    # the engine clamps to max_len; drop duplicates after clamping so a
    # TPU-sized sweep string reused on CPU doesn't rerun one config
    chunks = list(dict.fromkeys(min(c, S) for c in chunks))

    def make_engine(prefill_chunk: int) -> "DecodeEngine":
        return DecodeEngine(model, variables, n_slots=slots, max_len=S,
                            temperature=1.0, top_k=50, block_size=kv_block,
                            prefill_chunk=prefill_chunk)

    npr = np.random.default_rng(0)
    reqs = [(list(npr.integers(0, cfg.vocab_size,
                               int(npr.integers(p_lo, p_hi)))),
             int(npr.integers(b_lo, b_hi)))
            for _ in range(n_req)]

    # probe the pure-decode fused step at full occupancy on the wave
    # engine: the denominator of the ITL-over-step acceptance ratio
    wave_eng = make_engine(0)
    for bucket in sorted({wave_eng.prefill_bucket(len(p)) for p, _ in reqs}):
        wave_eng.admit(list(npr.integers(0, cfg.vocab_size, bucket)), 1)
    while wave_eng.free_slots:
        wave_eng.admit(list(npr.integers(0, cfg.vocab_size, p_lo)), 10 ** 9)
    wave_eng.step()
    t0 = time.perf_counter()
    probe_steps = 8
    for _ in range(probe_steps):
        wave_eng.step()
    jax.device_get(wave_eng.tok)
    step_s = (time.perf_counter() - t0) / probe_steps
    for sid in wave_eng.live_seq_ids:
        wave_eng.set_budget(sid, 1)
    while wave_eng.n_live:
        wave_eng.step()

    def probe_fused(e) -> float:
        """Steady chunk-carrying fused-step time on engine `e`: fill
        some decode streams, then time the steps that chunk a long
        prompt in next to them (also warms every trace the drive
        needs)."""
        for _ in range(min(3, slots)):
            e.admit(list(npr.integers(0, cfg.vocab_size,
                                      2 * e.block_size)), 10 ** 9)
        while e.step().prefill_tokens:
            pass                       # the fillers' own chunks (+ compile)
        ts = []
        for rep in range(3):           # 3 long prompts -> ~15-20 samples
            e.admit(list(npr.integers(0, cfg.vocab_size, p_hi - 1)), 2)
            while True:
                t0 = time.perf_counter()
                r = e.step()
                jax.device_get(e.tok)
                if not r.prefill_tokens:
                    break
                ts.append(time.perf_counter() - t0)
        for sid in list(e.live_seq_ids):
            e.cancel(sid)
        return sum(ts) / max(len(ts), 1)

    # same seeded inter-arrival shape at every load point: only the rate
    # scales, so the 2x leg is literally the same traffic arriving twice
    # as fast
    mean_budget = (b_lo + b_hi) / 2
    base_load = float(os.environ.get("BENCH_SERVE_LOAD", "0.6"))
    gaps = npr.exponential(1.0, size=n_req)

    def arrivals_at(load: float):
        rate = slots / (mean_budget * step_s) * load
        return np.cumsum(gaps / rate), rate

    def drive(e, arrivals):
        import gc

        async def _run():
            sched = Scheduler(e, max_queue=4 * slots)
            await sched.start()
            consumers, shed = [], 0
            # GC pauses are multi-ms — p99-of-ITL scale — and land on
            # whichever config is mid-drive; collect up front and hold
            # the collector off so every leg's tail is the system's, not
            # the allocator's (re-enabled in the finally)
            gc.collect()
            gc.disable()
            try:
                start = time.perf_counter()
                for (prompt, budget), at in zip(reqs, arrivals):
                    delay = start + at - time.perf_counter()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    try:
                        h = sched.submit(prompt, budget)
                    except ShedError:
                        shed += 1
                        continue
                    consumers.append(asyncio.ensure_future(h.result()))
                await asyncio.gather(*consumers, return_exceptions=True)
                dt = time.perf_counter() - start
            finally:
                gc.enable()
            await sched.stop()
            return sched, shed, dt

        return asyncio.run(_run())

    def leg(e, load: float, fused_s=None) -> dict:
        arrivals, rate = arrivals_at(load)
        sched, shed, dt = drive(e, arrivals)
        s = sched.metrics.summary()
        itl99 = s["itl"].get("p99_ms") or 0.0
        out = {"tokens_per_sec_per_chip": round(
                   sched.metrics.counters["tokens_out"] / dt / n_dev, 1),
               "ttft_p50_ms": s["ttft"].get("p50_ms"),
               "ttft_p99_ms": s["ttft"].get("p99_ms"),
               "itl_p50_ms": s["itl"].get("p50_ms"),
               "itl_p99_ms": itl99,
               "itl_p99_over_step": round(itl99 / (step_s * 1e3), 2),
               "decode_stall_ms": s["gauges"].get("serve_decode_stall_ms"),
               "prefill_tokens_per_step":
                   s.get("prefill_tokens_per_step", {}),
               "offered_rps": round(rate, 2),
               "shed_rate": round(shed / n_req, 3),
               "mean_occupancy": s["mean_occupancy"]}
        if fused_s:
            out["itl_p99_over_fused"] = round(itl99 / (fused_s * 1e3), 2)
        return out

    def run_pair(e, fused_s=None) -> dict:
        return {"load_1x": leg(e, base_load, fused_s),
                "load_2x": leg(e, 2 * base_load, fused_s)}

    # artifact dir for the per-config step timelines (flight recorder):
    # the ITL-p99-vs-step evidence the chunk-size pick reads post hoc
    art_dir = os.path.join("runs", f"bench_serve_chunked_{int(time.time())}")
    artifacts = {}

    def dump_timeline(e, tag: str) -> None:
        try:
            artifacts[tag] = e.flight.dump_jsonl(
                os.path.join(art_dir, f"timeline_{tag}.jsonl"))
        except Exception:  # noqa: BLE001 — artifacts never sink the leg
            pass

    wave = run_pair(wave_eng)
    dump_timeline(wave_eng, "wave")
    by_chunk = {}
    for c in chunks:
        e = make_engine(c)
        fused_s = probe_fused(e)
        by_chunk[str(c)] = run_pair(e, fused_s)
        by_chunk[str(c)]["fused_step_ms"] = round(fused_s * 1e3, 2)
        dump_timeline(e, f"chunk{c}")
    def worst_ratio(r: dict) -> float:
        return max(r[f"load_{t}"].get("itl_p99_over_fused") or 9e9
                   for t in ("1x", "2x"))

    # the knob pick: the config whose tail stays closest to its own
    # steady fused step across BOTH load points (raw ms across chunk
    # sizes compares different fused steps — not the boundedness claim)
    best_c, best = min(by_chunk.items(), key=lambda kv: worst_ratio(kv[1]))
    if artifacts:
        try:
            from distributed_pytorch_tpu.obs import replay
            rep = replay.write_report(art_dir)
            artifacts["report_md"] = rep["report_md"]
            artifacts["cost_model_json"] = rep["cost_model_json"]
        except Exception:  # noqa: BLE001 — artifacts never sink the leg
            pass
    accept = {
        # the acceptance bar (ISSUE 7): at a load point where the wave's
        # ITL p99 exceeds 3x its step (the admission stall), some chunk
        # config's p99 stays within 1.5x of its own steady fused step.
        # Checked per load point: p99 on ~300 CPU samples carries ~2 ms
        # of event-loop jitter at saturation, so the strict both-points
        # version flips run to run while one point always holds.
        "chunked_itl_p99_bounded": any(
            0.0 < (r[f"load_{t}"].get("itl_p99_over_fused") or 9e9) <= 1.5
            and wave[f"load_{t}"]["itl_p99_over_step"] > 3.0
            for r in by_chunk.values() for t in ("1x", "2x")),
        # the wave's tail is the admission stall, >3x its steady step
        "wave_itl_p99_stalls": all(
            wave[f"load_{t}"]["itl_p99_over_step"] > 3.0
            for t in ("1x", "2x"))}
    return {"metric": ("serve_chunked_itl_p99_ms" if platform == "tpu"
                       else "cpu_proxy_serve_chunked_itl_p99_ms"),
            "value": best["load_1x"]["itl_p99_ms"], "unit": "ms",
            "vs_baseline": 0,
            "probe_step_ms": round(step_s * 1e3, 2),
            "best_chunk": int(best_c), "accept": accept,
            "artifacts": artifacts,
            "wave_baseline": wave, "chunked": by_chunk,
            "chunk_sizes": chunks, "base_load_factor": base_load,
            "n_requests": n_req, "n_slots": slots, "cache_len": S,
            "kv_block": kv_block,
            "prompt_len_range": [p_lo, p_hi], "budget_range": [b_lo, b_hi],
            "flash_decode": os.environ.get("FLASH_DECODE", "auto"),
            "n_chips": n_dev, "device": jax.devices()[0].device_kind,
            "preset": preset}


def _serve_spec_bench(platform: str) -> dict:
    """serve_load_spec leg (BENCH_SERVE=1 BENCH_SERVE_SPEC=1): the
    speculative-decoding A/B (ISSUE 16). Repetitive-suffix Poisson
    traffic (prompts tile a short pattern, so the n-gram drafter has
    something to hit) drives a GREEDY engine twice under the SAME seeded
    arrivals: spec off, then a BENCH_SPEC_K sweep with SPEC_DECODE=on.
    Greedy verify is exact, so every leg streams bit-identical tokens —
    the comparison isolates steps-per-token, not output quality. The
    acceptance booleans the ISSUE pins: accepted_token_rate > 0 on this
    traffic, and delivered tok/s at the best K >= the spec-off baseline
    (same weight-read count per step, fewer steps per token)."""
    import asyncio
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.serve.scheduler import Scheduler, ShedError

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "32"))
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "128"))
        dtype = jnp.bfloat16
        n_req, p_lo, p_hi, b_lo, b_hi = 96, 64, 256, 16, 64
        preset = "gpt2_124m"
    else:  # CPU proxy: tiny model, same traffic shape
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots, dtype = 128, 4, jnp.float32
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "16"))
        n_req, p_lo, p_hi, b_lo, b_hi = 24, 12, 48, 8, 16
        preset = "cpu_tiny"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    ks = [int(k) for k in
          os.environ.get("BENCH_SPEC_K", "2,4").split(",") if k.strip()]

    def make_engine(spec_k: int) -> "DecodeEngine":
        # temperature=0.0: speculation is greedy-only (the verify is an
        # exact argmax match), and the off/on A/B must sample identically
        return DecodeEngine(model, variables, n_slots=slots, max_len=S,
                            temperature=0.0, block_size=kv_block,
                            spec_decode=spec_k > 0,
                            spec_k=spec_k or None)

    # repetitive-suffix traffic: each prompt tiles a short random pattern,
    # so the suffix n-gram always has an earlier occurrence to extend —
    # the regime speculation targets (code, templated text, self-loops)
    npr = np.random.default_rng(0)
    reqs = []
    for _ in range(n_req):
        plen = int(npr.integers(p_lo, p_hi))
        pat = list(npr.integers(0, cfg.vocab_size,
                                int(npr.integers(3, 7))))
        prompt = (pat * (plen // len(pat) + 1))[:plen]
        reqs.append((prompt, int(npr.integers(b_lo, b_hi))))

    # probe the plain fused step for the arrival rate; every leg replays
    # the SAME arrival offsets so the comparison is traffic-identical
    probe = make_engine(0)
    for bucket in sorted({probe.prefill_bucket(len(p)) for p, _ in reqs}):
        probe.admit(list(npr.integers(0, cfg.vocab_size, bucket)), 1)
    while probe.free_slots:
        probe.admit(reqs[0][0], 10 ** 9)
    probe.step()
    t0 = time.perf_counter()
    probe_steps = 8
    for _ in range(probe_steps):
        probe.step()
    jax.device_get(probe.tok)
    step_s = (time.perf_counter() - t0) / probe_steps
    for sid in probe.live_seq_ids:
        probe.set_budget(sid, 1)
    while probe.n_live:
        probe.step()

    mean_budget = (b_lo + b_hi) / 2
    load = float(os.environ.get("BENCH_SERVE_LOAD", "1.0"))
    rate = slots / (mean_budget * step_s) * load
    arrivals = np.cumsum(npr.exponential(1.0 / rate, size=n_req))

    def drive(e):
        async def _run():
            sched = Scheduler(e, max_queue=4 * slots)
            await sched.start()
            consumers, shed = [], 0
            start = time.perf_counter()
            for (prompt, budget), at in zip(reqs, arrivals):
                delay = start + at - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                try:
                    h = sched.submit(prompt, budget)
                except ShedError:
                    shed += 1
                    continue
                consumers.append(asyncio.ensure_future(h.result()))
            await asyncio.gather(*consumers, return_exceptions=True)
            dt = time.perf_counter() - start
            await sched.stop()
            return sched, shed, dt

        return asyncio.run(_run())

    def leg(spec_k: int) -> dict:
        e = make_engine(spec_k)
        # warm the prefill buckets + both step programs outside the window
        for bucket in sorted({e.prefill_bucket(len(p)) for p, _ in reqs}):
            e.admit(list(npr.integers(0, cfg.vocab_size, bucket)), 1)
        e.admit(reqs[0][0], 4)
        while e.n_live:
            e.step()
        sched, shed, dt = drive(e)
        s = sched.metrics.summary()
        return {"spec_k": spec_k,
                "tokens_per_sec_per_chip": round(
                    sched.metrics.counters["tokens_out"] / dt / n_dev, 1),
                "accepted_token_rate": round(e.accepted_token_rate, 4),
                "tokens_per_step": round(e.tokens_per_step, 3),
                "drafted": e.spec_drafted_tokens,
                "accepted": e.spec_accepted_tokens,
                "spec_step_traces": e.spec_step_traces,
                "ttft_p50_ms": s["ttft"].get("p50_ms"),
                "itl_p50_ms": s["itl"].get("p50_ms"),
                "itl_p99_ms": s["itl"].get("p99_ms"),
                "shed_rate": round(shed / n_req, 3),
                "mean_occupancy": s["mean_occupancy"]}

    base = leg(0)
    by_k = {f"k{k}": leg(k) for k in ks}
    best_key, best = max(by_k.items(),
                         key=lambda kv: kv[1]["tokens_per_sec_per_chip"])
    accept = {
        # the ISSUE 16 acceptance booleans: the drafter finds real
        # acceptance on repetitive traffic, and speculation at the best K
        # delivers at least the spec-off baseline's throughput
        "spec_accepted_rate_positive": any(
            r["accepted_token_rate"] > 0 for r in by_k.values()),
        "spec_throughput_ge_baseline": (
            best["tokens_per_sec_per_chip"]
            >= base["tokens_per_sec_per_chip"]),
        "spec_one_trace": all(r["spec_step_traces"] <= 1
                              for r in by_k.values())}
    return {"metric": ("serve_spec_tokens_per_sec_per_chip"
                       if platform == "tpu"
                       else "cpu_proxy_serve_spec_tokens_per_sec_per_chip"),
            "value": best["tokens_per_sec_per_chip"], "unit": "tok/s/chip",
            "vs_baseline": round(
                best["tokens_per_sec_per_chip"]
                / max(base["tokens_per_sec_per_chip"], 1e-9), 3),
            "accept": accept, "best_k": int(best_key[1:]),
            "spec_off": base, "spec_on": by_k,
            "probe_step_ms": round(step_s * 1e3, 2),
            "offered_rps": round(rate, 2), "load_factor": load,
            "n_requests": n_req, "n_slots": slots, "cache_len": S,
            "kv_block": kv_block,
            "flash_decode": os.environ.get("FLASH_DECODE", "auto"),
            "n_chips": n_dev, "device": jax.devices()[0].device_kind,
            "preset": preset}


def _serve_spinup_bench(platform: str) -> dict:
    """serve_spinup leg (BENCH_SERVE=1 BENCH_SERVE_SPINUP=1): the AOT
    program-store A/B (ISSUE 18). Measures replica start -> first token
    twice over the same greedy prompt: store off (every program traces
    and compiles inside the window) vs warmed (a second engine reads
    every program from a store a first engine populated — the zero-
    cold-start replica add). A train sub-leg restarts the tiny train
    config cold vs against the warmed store and reports restart ->
    first-step, the supervisor re-mesh case (reported, not asserted:
    subprocess wall time includes interpreter+import noise). Acceptance
    booleans the ISSUE pins: warm_faster (warmed TTFT beats cold),
    hit_rate_1 (the warmed window reads every program from the store —
    zero misses, zero JIT traces), parity (greedy output bit-identical
    cold vs warmed)."""
    import shutil
    import subprocess
    import sys
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.parallel.aot_store import AOTStore

    # run_bench turns the persistent compile cache on for repeat
    # invocations — that would hand the "cold" leg pre-built binaries.
    # This leg measures compile cost; turn the cache off (no directory is
    # set or unset here: config.enable_compile_cache owns placement).
    jax.config.update("jax_enable_compilation_cache", False)

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "8"))
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "128"))
        dtype = jnp.bfloat16
        preset = "gpt2_124m"
    else:  # CPU proxy: tiny model, same program set
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots, dtype = 128, 4, jnp.float32
        kv_block = int(os.environ.get("BENCH_KV_BLOCK", "16"))
        preset = "cpu_tiny"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    prompt = [(7 * i + 3) % cfg.vocab_size for i in range(24)]
    budget = 16

    def spin(store):
        """start -> first token with the given store (False = off);
        returns (ttft_s, total_s, full greedy stream, engine)."""
        t0 = time.perf_counter()
        e = DecodeEngine(model, variables, n_slots=slots, max_len=S,
                         temperature=0.0, block_size=kv_block,
                         aot_store=store)
        if e.aot_store is not None:
            e.warm_aot(origin="runtime")  # the replica spin-up path
        adm = e.admit(list(prompt), budget)
        sid = adm.seq_id
        # wave-mode prefill samples the first token inside admit itself
        toks: list = ([] if adm.first_token is None
                      else [int(adm.first_token)])
        while not toks:
            toks += e.step().emitted.get(sid, [])
        ttft = time.perf_counter() - t0
        while e.n_live:
            toks += e.step().emitted.get(sid, [])
        return ttft, time.perf_counter() - t0, toks, e

    ttft_cold, total_cold, toks_cold, _ = spin(False)

    root = tempfile.mkdtemp(prefix="bench_aot_")
    try:
        populate = DecodeEngine(model, variables, n_slots=slots,
                                max_len=S, temperature=0.0,
                                block_size=kv_block,
                                aot_store=AOTStore(root))
        populate.warm_aot(origin="warm")  # outside every window
        warm_store = AOTStore(root)  # fresh counters for the ledger
        ttft_warm, total_warm, toks_warm, e_warm = spin(warm_store)
        warm_traces = (e_warm.step_traces + e_warm.fused_step_traces
                       + e_warm.spec_step_traces + e_warm.promote_traces
                       + sum(e_warm.admit_traces.values()))

        # train sub-leg: restart -> first-step, cold store vs warmed
        # (the supervisor re-mesh pre-warm case). Subprocesses so each
        # restart pays real import+trace cost; CPU pin — the parent may
        # hold the TPU.
        train_root = os.path.join(root, "train")
        targv = [sys.executable, "-m", "distributed_pytorch_tpu",
                 "--dataset", "synthetic", "--platform", "cpu",
                 "--parallelism", "single", "--file_name", "bench_aot",
                 "--seed", "7", "--max_iters", "1", "--log_interval", "1",
                 "--total_batch_size_str", "64", "--batch_size", "1",
                 "--vocab_size", "256", "--block_size", "32",
                 "--n_embd", "32", "--n_head", "4", "--n_kv_heads", "2",
                 "--n_layer", "2", "--up_dim", "48"]
        tenv = {**os.environ, "JAX_PLATFORMS": "cpu", "AOT_STORE": "on",
                "AOT_STORE_DIR": train_root}

        def train_once():
            t0 = time.perf_counter()
            p = subprocess.run(targv, env=tenv, capture_output=True,
                               text=True, timeout=600)
            hit = "aot store: train_step hit" in (p.stdout + p.stderr)
            return round(time.perf_counter() - t0, 2), hit, p.returncode

        train = {}
        try:
            cold_s, _, rc0 = train_once()
            warm_s, warm_hit, rc1 = train_once()
            train = {"restart_cold_s": cold_s, "restart_warm_s": warm_s,
                     "warm_hit": warm_hit, "rc": [rc0, rc1]}
        except (subprocess.TimeoutExpired, OSError) as exc:
            train = {"error": type(exc).__name__}
    finally:
        shutil.rmtree(root, ignore_errors=True)

    accept = {
        # the ISSUE 18 acceptance booleans
        "spinup_warm_faster": ttft_warm < ttft_cold,
        "spinup_hit_rate_1": (warm_store.misses == 0
                              and warm_store.hits > 0
                              and warm_traces == 0),
        "spinup_parity": toks_warm == toks_cold}
    return {"metric": ("serve_spinup_ttft_cold_over_warm"
                       if platform == "tpu"
                       else "cpu_proxy_serve_spinup_ttft_cold_over_warm"),
            "value": round(ttft_cold / max(ttft_warm, 1e-9), 2),
            "unit": "x", "accept": accept,
            "ttft_cold_s": round(ttft_cold, 3),
            "ttft_warm_s": round(ttft_warm, 3),
            "total_cold_s": round(total_cold, 3),
            "total_warm_s": round(total_warm, 3),
            "store": {"hits": warm_store.hits,
                      "misses": warm_store.misses,
                      "load_ms": round(warm_store.load_ms, 1),
                      "compile_ms": round(warm_store.compile_ms, 1)},
            "warm_traces": warm_traces, "train_restart": train,
            "n_tokens": len(toks_cold), "n_slots": slots,
            "cache_len": S, "kv_block": kv_block, "n_chips": n_dev,
            "device": jax.devices()[0].device_kind, "preset": preset}


def _serve_router_bench(platform: str) -> dict:
    """serve_load_router leg (BENCH_SERVE=1 BENCH_SERVE_ROUTER=1): the
    replicated-serving fault-tolerance A/B. Delegates to the
    fault-injection harness (scripts/fault_inject.py): N real replica
    subprocesses (demo model, greedy) behind the health-gated router,
    seeded Poisson traffic at saturating load, one replica SIGKILLed
    mid-drive and restarted on the same port, plus a single-replica
    baseline drive for the scaling ratio. The three exit criteria ride
    back as accept booleans: zero failed (vs explicitly shed) requests,
    every completed stream — failed-over ones included — bit-identical
    to offline greedy, and aggregate tok/s vs one replica. The replicas
    are separate PROCESSES pinned to the CPU backend — so whatever the
    worker was asked for, this leg's number is a CPU number and carries
    the `cpu_proxy_` prefix (one process driving N one-chip replicas is
    ROADMAP R6) — and the scaling ratio is only meaningful with
    >= replicas+1 host cores: `scaling_measurable` reports whether this
    box can express it at all. Runs BEFORE the worker touches a jax
    backend (run_bench), so the replica children never find a device
    their parent holds."""
    n_rep = int(os.environ.get("BENCH_ROUTER_REPLICAS", "3"))
    n_req = int(os.environ.get("BENCH_ROUTER_REQUESTS", "48"))
    mode = os.environ.get("BENCH_ROUTER_MODE", "kill")
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", "fault_inject.py")
    cmd = [sys.executable, script, "--json", "--baseline",
           "--replicas", str(n_rep), "--requests", str(n_req),
           "--mode", mode,
           "--load", os.environ.get("BENCH_SERVE_LOAD", "1.2"),
           "--retry-budget", "4"]
    r = subprocess.run(cmd, capture_output=True, timeout=850)
    sys.stderr.write(r.stderr.decode()[-2000:])
    out = None
    for line in reversed(r.stdout.decode().strip().splitlines()):
        try:
            out = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if out is None:
        raise RuntimeError(f"fault_inject harness rc={r.returncode}, no "
                           f"JSON line; stdout tail: "
                           f"{r.stdout.decode()[-500:]}")
    cores = out.get("host_cores", 1)
    scaling = out.get("scaling_x", 0.0)
    accept = {
        # ROADMAP exit criteria for the scale-out item
        "zero_failed": out["failed"] == 0,
        "failover_parity": out["parity_mismatches"] == 0,
        # the killed replica rejoined through the backoff prober
        # (replica_up counts initial probes + the rejoin)
        "replica_rejoined": out["replica_up"] > n_rep,
        "linear_scaling": scaling >= max(1.0, 0.83 * n_rep),
        "scaling_measurable": cores >= n_rep + 1,
    }
    return {"metric": "cpu_proxy_serve_router_tokens_per_sec",
            "value": out["tokens_per_sec"], "unit": "tok/s",
            "vs_baseline": 0, "accept": accept, "host_cores": cores,
            "scaling_x": scaling,
            "baseline_tokens_per_sec":
                out.get("baseline_tokens_per_sec"),
            **{k: out[k] for k in
               ("replicas", "mode", "requests", "completed", "shed",
                "failed", "parity_mismatches", "failovers", "retries",
                "replica_down", "replica_up", "offered_rps",
                "ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms",
                "itl_p99_ms", "shed_by_cause", "artifacts",
                "log_dir") if k in out}}


def _serve_classes_bench(platform: str) -> dict:
    """serve_load_classes leg (BENCH_SERVE=1 BENCH_SERVE_CLASSES=1): the
    control-plane acceptance drill (ISSUE 20). Three in-process replica
    stacks (scheduler + HTTP server) behind the class/tenant-aware
    router, driven with a seeded two-tenant, two-class Poisson mix at
    ~1.5x the probed capacity — one hot tenant offering 60% of the
    traffic against a per-tenant token bucket set to its fair share.
    Interactive work must preempt live batch through the lossless
    requeue path; the leg reports per-class TTFT quantiles, shed causes,
    preemption counts, and the round's accept booleans:
    interactive_slo_held / batch_zero_lost / hot_tenant_capped from the
    live drive, and autoscale_before_knee from a seeded fleet-simulator
    ramp (sim/fleetsim.py — the SAME Autoscaler object the live router
    runs; a CPU bench box cannot host a 10x replica ramp)."""
    import asyncio
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.config import (LLMConfig,
                                                flagship_gpt124m, knob)
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.serve.control import TokenBucketFairness
    from distributed_pytorch_tpu.serve.router import Router
    from distributed_pytorch_tpu.serve.scheduler import Scheduler, ShedError
    from distributed_pytorch_tpu.serve.server import ServeApp

    n_dev = len(jax.devices())
    if platform == "tpu":
        cfg = flagship_gpt124m()
        S = int(os.environ.get("BENCH_DECODE_LEN", "1024"))
        slots = int(os.environ.get("BENCH_DECODE_SLOTS", "16"))
        dtype = jnp.bfloat16
        n_req, b_int, b_bat = 180, (16, 48), (64, 128)
        p_int, p_bat = (16, 96), (64, 384)
        preset = "gpt2_124m"
    else:  # CPU proxy: tiny model, small budgets
        cfg = LLMConfig(vocab_size=1024, block_size=128, n_embd=128,
                        n_head=4, n_kv_heads=4, attn="mha", n_layer=2,
                        up_dim=256, non_linearity="swiglu", pos_emb="rope")
        S, slots, dtype = 128, 2, jnp.float32
        n_req, b_int, b_bat = 72, (4, 8), (16, 28)
        p_int, p_bat = (2, 12), (8, 40)
        preset = "cpu_tiny"
    n_replicas = int(os.environ.get("BENCH_CLASS_REPLICAS", "3"))
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    npr = np.random.default_rng(0)

    # seeded two-tenant, two-class mix: hot tenant offers 60% of the
    # traffic, classes split 50/50 within every tenant
    reqs = []
    for _ in range(n_req):
        cls = "interactive" if npr.random() < 0.5 else "batch"
        p_rng, b_rng = (p_int, b_int) if cls == "interactive" \
            else (p_bat, b_bat)
        reqs.append((
            "hot" if npr.random() < 0.6 else "base", cls,
            [int(t) for t in npr.integers(
                0, cfg.vocab_size, int(npr.integers(*p_rng)))],
            int(npr.integers(*b_rng))))

    engines = [DecodeEngine(model, variables, n_slots=slots, max_len=S,
                            temperature=0.0, prefix_cache=True)
               for _ in range(n_replicas)]
    # warm every prefill bucket + the fused step outside the timed drive
    buckets = sorted({engines[0].prefill_bucket(len(p))
                      for _, _, p, _ in reqs})
    for e in engines:
        for bucket in buckets:
            e.admit(list(npr.integers(0, cfg.vocab_size, bucket)), 1)
        e.admit(reqs[0][2], 2)
        e.step()
        while e.n_live:
            e.step()

    # probe the steady step time at full occupancy -> offered rate
    eng = engines[0]
    while eng.free_slots:
        eng.admit(list(npr.integers(0, cfg.vocab_size, 8)), 10 ** 9)
    eng.step()
    t0 = time.perf_counter()
    for _ in range(8):
        eng.step()
    jax.device_get(eng.tok)
    step_s = (time.perf_counter() - t0) / 8
    for sid in eng.live_seq_ids:
        eng.set_budget(sid, 1)
    while eng.n_live:
        eng.step()

    mean_budget = (sum(b_int) + sum(b_bat)) / 4
    load_factor = float(os.environ.get("BENCH_SERVE_LOAD", "1.5"))
    cap_rps = n_replicas * slots / (mean_budget * step_s)
    req_rate = cap_rps * load_factor
    fair_share = cap_rps / 2               # two tenants
    # The drive's arrival window is a fraction of a second, so a bucket
    # sized in tokens/s never binds: cap each tenant at half the drive's
    # request volume instead, with a trickle refill.
    fair_burst = n_req / 2
    arrivals = np.cumsum(npr.exponential(1.0 / req_rate, size=n_req))
    duration_est = float(arrivals[-1])

    async def _drive():
        scheds = [Scheduler(e, max_queue=4 * slots) for e in engines]
        apps = [ServeApp(s, port=0) for s in scheds]
        for s, a in zip(scheds, apps):
            await s.start()
            await a.start()
        router = Router(
            [f"127.0.0.1:{a.port}" for a in apps],
            probe_interval_s=0.05, fleet_poll_interval_s=0.5,
            fairness=TokenBucketFairness(
                rate_tokens_s=1.0, burst=fair_burst))
        await router.start()

        per = {"hot": {"offered": 0, "ok": 0, "rate_limited": 0,
                       "other_shed": 0},
               "base": {"offered": 0, "ok": 0, "rate_limited": 0,
                        "other_shed": 0}}
        batch_admitted, batch_done = 0, 0

        async def one(tenant, cls, prompt, budget):
            nonlocal batch_admitted, batch_done
            per[tenant]["offered"] += 1
            try:
                out = await router.complete(prompt, budget,
                                            slo_class=cls, tenant=tenant)
                if cls == "batch":
                    # a batch stream that started must END complete —
                    # preempted-and-resumed included (lossless claim)
                    batch_admitted += 1
                    if out["reason"] in ("budget", "eos"):
                        batch_done += 1
                per[tenant]["ok"] += 1
            except ShedError as e:
                # shed happens BEFORE admission (or as an explicit
                # rate-limit) — a shed request is not a lost stream
                if e.cause == "rate_limited":
                    per[tenant]["rate_limited"] += 1
                else:
                    per[tenant]["other_shed"] += 1

        start = time.perf_counter()
        tasks = []
        for (tenant, cls, prompt, budget), at in zip(reqs, arrivals):
            delay = start + at - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                one(tenant, cls, prompt, budget)))
        await asyncio.gather(*tasks)
        dt = time.perf_counter() - start
        scheds_m = [s.metrics for s in scheds]
        router_m = router.metrics
        await router.stop()
        for s, a in zip(scheds, apps):
            await a.stop()
            await s.stop()
        return router_m, scheds_m, per, batch_admitted, batch_done, dt

    router_m, scheds_m, per, batch_admitted, batch_done, dt = \
        asyncio.run(_drive())

    slo_s = float(knob("SLO_TTFT_P99_S"))
    h_int = router_m.ttft_class("interactive")
    h_bat = router_m.ttft_class("batch")
    pre_batch = sum(m.class_counts.get("preempted|batch", 0)
                    for m in scheds_m)
    pre_inter = sum(m.class_counts.get("preempted|interactive", 0)
                    for m in scheds_m)
    hot_admit_rps = per["hot"]["ok"] / dt

    # the autoscaler half of the acceptance: a 10x ramp in the fleet
    # simulator, driven by the SAME Autoscaler policy object
    from sim import fleetsim
    sim_sc = fleetsim.run_report(
        seed=0, n_replicas=int(os.environ.get("BENCH_SIM_REPLICAS",
                                              "40")),
        duration_s=60.0, cost_model="runs/replay/cost_model.json",
        smoke=True, scenarios=["autoscale"])["scenarios"]["autoscale"]

    accept = {
        "interactive_slo_held": bool(
            h_int is not None and h_int.count > 0
            and h_int.quantile(0.99) <= slo_s),
        "batch_zero_lost": bool(batch_done == batch_admitted
                                and pre_batch >= 1),
        "hot_tenant_capped": bool(
            per["hot"]["rate_limited"] > 0
            and per["hot"]["ok"] <= fair_burst + 2
            and per["base"]["rate_limited"] == 0),
        "autoscale_before_knee": bool(
            sim_sc["accept"]["scaled_before_knee"]
            and sim_sc["accept"]["ci_disjoint_shed_rate"]),
    }
    toks = sum(m.counters["tokens_out"] for m in scheds_m)
    return {"metric": ("serve_classes_tokens_per_sec" if platform == "tpu"
                       else "cpu_proxy_serve_classes_tokens_per_sec"),
            "value": round(toks / dt, 1), "unit": "tok/s",
            "vs_baseline": 0, "accept": accept,
            "replicas": n_replicas, "n_requests": n_req,
            "offered_rps": round(req_rate, 2),
            "capacity_rps": round(cap_rps, 2),
            "load_factor": load_factor,
            "fair_share_rps": round(fair_share, 2),
            "fair_burst_reqs": round(fair_burst, 1),
            "hot_admitted_rps": round(hot_admit_rps, 2),
            "tenants": per,
            "ttft_interactive_p50_ms": (round(h_int.quantile(0.5) * 1e3, 1)
                                        if h_int and h_int.count else None),
            "ttft_interactive_p99_ms": (round(h_int.quantile(0.99) * 1e3, 1)
                                        if h_int and h_int.count else None),
            "ttft_batch_p99_ms": (round(h_bat.quantile(0.99) * 1e3, 1)
                                  if h_bat and h_bat.count else None),
            "preempted_batch": pre_batch,
            "preempted_interactive": pre_inter,
            "batch_admitted": batch_admitted, "batch_done": batch_done,
            "shed_by_cause_class": dict(router_m.shed_class_counts),
            "sim_autoscale": {
                "accept": sim_sc["accept"],
                "t_knee_s": sim_sc["t_knee_s"],
                "off_shed_rate": sim_sc["arms"]["autoscale_off"]
                ["capacity_shed_rate"],
                "on_shed_rate": sim_sc["arms"]["autoscale_on"]
                ["capacity_shed_rate"],
                "first_scale_up_t_s": sim_sc["arms"]["autoscale_on"]
                ["replicas"]["first_scale_up_t_s"]},
            "probe_step_ms": round(step_s * 1e3, 2),
            "n_slots": slots, "n_chips": n_dev,
            "device": jax.devices()[0].device_kind, "preset": preset}


def run_bench(platform: str, only_recipe: str | None = None) -> dict:
    """Worker-side measurement. `platform` is 'tpu' or 'cpu', and the
    worker ASSERTS it got that backend: a 124M config is never ground on
    a CPU under a TPU metric's name, and a CPU correctness run is never
    mistaken for a device one.

    On a multi-chip slice each recipe is measured in its OWN worker process
    (`only_recipe`): peak_bytes_in_use is process-monotone, so measuring
    fsdp then dp in one process would report dp's peak HBM as
    max(fsdp, dp) — the parent merges the per-recipe JSON lines instead."""
    if os.environ.get("BENCH_SERVE") and os.environ.get("BENCH_SERVE_ROUTER"):
        # replica SUBPROCESSES do the work: this worker must not have
        # touched a jax backend when they start (one process per chip)
        return _serve_router_bench(platform)

    import jax

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")  # explicit CPU mode
    from distributed_pytorch_tpu.config import (LLMConfig, TrainConfig,
                                                enable_compile_cache)
    # persistent compile cache: repeat bench invocations (driver reruns,
    # the dp leg after fsdp) skip the XLA compile
    enable_compile_cache()
    assert jax.default_backend() == platform, (
        f"bench worker was asked for {platform!r} and got "
        f"{jax.default_backend()!r}")

    from distributed_pytorch_tpu.train.loop import train

    n_dev = len(jax.devices())

    if os.environ.get("BENCH_SERVE"):
        if os.environ.get("BENCH_PREFILL_CHUNK"):
            return _serve_chunked_bench(platform)
        if os.environ.get("BENCH_SERVE_SPEC"):
            return _serve_spec_bench(platform)
        if os.environ.get("BENCH_SERVE_SPINUP"):
            return _serve_spinup_bench(platform)
        if os.environ.get("BENCH_SERVE_TIER"):
            return _serve_tier_bench(platform)
        if os.environ.get("BENCH_SERVE_CLASSES"):
            return _serve_classes_bench(platform)
        return _serve_bench(platform)

    if os.environ.get("BENCH_DECODE"):
        return _decode_bench(platform)

    if platform == "tpu":
        from distributed_pytorch_tpu.config import PRESETS, flagship_gpt124m
        preset = os.environ.get("BENCH_PRESET", "")
        if preset:
            # ladder leg: the preset model with the static HBM planner
            # choosing micro-batch + remat policy (train/memplan.py), so a
            # 350M/774M leg can't OOM-burn its slice of the bench budget
            from distributed_pytorch_tpu.train.memplan import plan_memory
            model_cfg = PRESETS[preset](
                loss_impl=os.environ.get("BENCH_LOSS", "fused"))
            recipe_for_plan = only_recipe or os.environ.get(
                "BENCH_RECIPE", "fsdp" if n_dev > 1 else "single")
            probe_cfg = TrainConfig(
                total_batch_size=int(os.environ.get(
                    "BENCH_GLOBAL_TOKENS", str(2 ** 19))),
                parallelism=recipe_for_plan)
            mplan = plan_memory(model_cfg, probe_cfg, n_devices=n_dev,
                                preset_name=preset)
            print(mplan.summary(), file=sys.stderr)
            if mplan.act_recomp:
                import dataclasses as _dc
                model_cfg = _dc.replace(
                    model_cfg, act_recomp=True,
                    act_recomp_policy=mplan.act_recomp_policy)
            per_chip = int(os.environ.get("BENCH_BATCH",
                                          str(mplan.micro_batch)))
        elif os.environ.get("BENCH_MOE"):
            # MoE A/B leg (MOE_IMPL=dense|scatter|grouped): the flagship
            # backbone with a DeepSeekMoE FFN sized so the ACTIVE params
            # stay 124M-class (n_act incl. shared; n_exp x up_dim=1024
            # experts). The three dispatch impls run the same model —
            # only the dispatch (and its dropped tokens / padded FLOPs)
            # differs, so the legs isolate dispatch cost.
            model_cfg = flagship_gpt124m(
                moe=True, n_exp=8, n_shared=1, n_act=3, up_dim=1024,
                moe_impl=os.environ.get("MOE_IMPL", "grouped"),
                loss_impl=os.environ.get("BENCH_LOSS", "fused"))
            per_chip = int(os.environ.get("BENCH_BATCH", "16"))
        else:
            model_cfg = flagship_gpt124m(
                act_recomp=os.environ.get("BENCH_REMAT", "0") == "1",
                act_recomp_policy="attn",
                loss_impl=os.environ.get("BENCH_LOSS", "fused"))
            per_chip = int(os.environ.get("BENCH_BATCH", "16"))
        iters = int(os.environ.get("BENCH_ITERS", "12"))
        attn_impl = os.environ.get("BENCH_ATTN", "auto")
    else:  # CPU smoke: tiny proxy so the harness still gets a line
        model_cfg = LLMConfig(
            vocab_size=1024, block_size=256, n_embd=256, n_head=8,
            n_kv_heads=8, attn="mha", n_layer=4, up_dim=1024,
            non_linearity="swiglu", pos_emb="rope")
        per_chip, iters, attn_impl = 4, 6, "auto"

    def measure(recipe: str) -> dict:
        # per-chip batch scales the global batch with the slice size, so the
        # grad-accum divisibility assert can't fire on any n_dev (round-3
        # VERDICT #5: BENCH_BATCH=16 fixed-global silently dropped >16-chip
        # slices to the CPU proxy).
        train_cfg = TrainConfig(
            dataset="synthetic", data_dir="bench_data",
            total_batch_size=per_chip * n_dev * model_cfg.block_size,
            batch_size=per_chip,
            max_iters=iters, parallelism=recipe, attn_impl=attn_impl,
            moe_impl=model_cfg.moe_impl,
            ep_size=int(os.environ.get("BENCH_EP", "1")),
            # sync every 4 steps: host round-trips overlap device compute
            # (train/loop.py sync discipline), like a real pod run would
            log_interval=4, eval=False, save_model=False, save_stats=False,
            # the train flight recorder dumps the leg's step-phase
            # timeline to runs/bench_train_<recipe>/train_timeline.jsonl
            # (referenced from "artifacts" below — the round-14 serve-leg
            # convention)
            file_name=f"bench_train_{recipe}",
            compute_dtype="bfloat16")
        stats = train(model_cfg, train_cfg,
                      log=lambda s: print(f"[{recipe}] {s}", file=sys.stderr))
        out = {"tokens_per_sec_per_chip":
                   round(stats["median_tokens_per_sec"] / n_dev, 1),
               "mfu": stats.get("median_mfu"),
               "peak_hbm_gb": stats.get("peak_hbm_gb")}
        # memplan predicted-vs-measured HBM rows + the step timeline: the
        # first-TPU-window "validate memplan against peak_bytes_in_use"
        # record rides every train leg's JSON
        if stats.get("memplan"):
            out["memplan"] = stats["memplan"]
        if stats.get("artifacts"):
            out["artifacts"] = stats["artifacts"]
        if model_cfg.moe:
            # dropped assignments (scatter's silent GShard drops; 0 for
            # dense/grouped) + how much the dispatch overspends FLOPs —
            # the pair the MOE_IMPL A/B decides on
            from distributed_pytorch_tpu.train.metrics import \
                moe_overcompute_factor
            out["moe_dropped_frac"] = stats.get("final_moe_dropped_frac")
            out["moe_impl"] = model_cfg.moe_impl
            out["moe_overcompute"] = round(
                moe_overcompute_factor(model_cfg), 3)
        return out

    if n_dev > 1:
        # BASELINE.md asks for the FSDP-vs-DDP MFU comparison; fsdp is the
        # north-star headline number. This worker measures ONE recipe; the
        # parent launches a second worker for dp and merges. BENCH_RECIPE
        # lets ladder legs pick their target rung recipe (zero2 for 350M).
        recipe = only_recipe or os.environ.get("BENCH_RECIPE", "") or "fsdp"
    else:
        recipe = "single"
    results = {recipe: measure(recipe)}
    headline = results[recipe]

    extra = {"n_chips": n_dev, "recipe": recipe,
             "device": jax.devices()[0].device_kind,
             "per_chip_batch": per_chip,
             # leg artifacts (train_timeline.jsonl) at the top level,
             # matching the serve legs' "artifacts" key
             **({"artifacts": headline["artifacts"]}
                if results[recipe].get("artifacts") else {}),
             "overlap": os.environ.get("OVERLAP", "auto"),
             "preset": os.environ.get("BENCH_PRESET", "")
                       or ("gpt2_124m_moe" if os.environ.get("BENCH_MOE")
                           else "gpt2_124m"),
             "recipes": {k: {kk: (round(vv, 4) if isinstance(vv, float) else vv)
                             for kk, vv in v.items()}
                         for k, v in results.items()}}
    mfu = headline["mfu"]
    if mfu is not None:
        metric = "mfu_gpt124m" if extra["preset"] == "gpt2_124m" \
            else f"mfu_{extra['preset']}"
        return {"metric": metric, "value": round(mfu, 4),
                "unit": "fraction_of_peak",
                "vs_baseline": round(mfu / 0.50, 4),
                "tokens_per_sec_per_chip": headline["tokens_per_sec_per_chip"],
                **extra}
    # no peak for this device = the CPU correctness mode: a proxy model's
    # CPU rate, named so it can never read as a device number
    assert platform == "cpu", "an accelerator without a peak is an error"
    return {"metric": "cpu_proxy_tokens_per_sec_per_chip",
            "value": headline["tokens_per_sec_per_chip"],
            "unit": "tok/s/chip", "vs_baseline": 0, **extra}


def _worker_main(platform: str, only_recipe: str | None = None) -> None:
    print(json.dumps(run_bench(platform, only_recipe)))


def _spawn_worker(platform: str, timeout_s: int,
                  only_recipe: str | None = None,
                  extra_env: dict | None = None) -> dict:
    """Run one leg in a worker subprocess and return its parsed JSON
    line. A leg that fails — non-zero exit, no JSON line, a timeout —
    raises: a failed leg fails the run."""
    cmd = [sys.executable, __file__, "--worker", platform]
    if only_recipe:
        cmd.append(only_recipe)
    env = dict(os.environ, **extra_env) if extra_env else None
    r = subprocess.run(cmd, capture_output=True, timeout=timeout_s, env=env)
    sys.stderr.write(r.stderr.decode()[-4000:])
    if r.returncode == 0:
        for line in reversed(r.stdout.decode().strip().splitlines()):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    raise RuntimeError(f"{platform} worker (env {extra_env}, recipe "
                       f"{only_recipe}) rc={r.returncode}, no JSON line")


# MFU-comparable training legs. The conservative known-good config runs
# FIRST: it proves the chip is there before anything ambitious compiles,
# and its n_chips tells the parent (which never touches jax) whether the
# multi-chip legs apply.
_TRAIN_LEGS = [
    ("batch16", None),
    ("batch16_flash_streamce",
     {"BENCH_BATCH": "16", "BENCH_ATTN": "pallas", "BENCH_LOSS": "pallas"}),
    ("batch32_remat_pallas",
     {"BENCH_BATCH": "32", "BENCH_REMAT": "1", "BENCH_ATTN": "pallas"}),
    ("batch32_remat_xla",
     {"BENCH_BATCH": "32", "BENCH_REMAT": "1", "BENCH_ATTN": "xla"}),
    # MOE_IMPL A/B (round 7): same MoE model, three dispatches — dense
    # (E/k x padded FLOPs), scatter (capacity-padded, DROPS tokens),
    # grouped (the dropless Pallas ragged kernel)
    ("moe_dense", {"BENCH_MOE": "1", "MOE_IMPL": "dense"}),
    ("moe_scatter", {"BENCH_MOE": "1", "MOE_IMPL": "scatter"}),
    ("moe_grouped", {"BENCH_MOE": "1", "MOE_IMPL": "grouped"}),
]

# overlap A/B (collective-matmul rings vs GSPMD default), the config
# ladder (BASELINE.json rungs; the HBM planner inside the worker picks
# batch/remat) and the expert-parallel MOE_IMPL A/B — more than one chip
_MULTICHIP_TRAIN_LEGS = [
    ("batch16_overlap_on", {"BENCH_BATCH": "16", "OVERLAP": "on"}),
    ("350m_zero2", {"BENCH_PRESET": "gpt2_350m", "BENCH_RECIPE": "zero2"}),
    ("350m_zero2_overlap", {"BENCH_PRESET": "gpt2_350m",
                            "BENCH_RECIPE": "zero2", "OVERLAP": "on"}),
    ("774m_fsdp", {"BENCH_PRESET": "gpt2_774m", "BENCH_RECIPE": "fsdp"}),
    ("774m_fsdp_overlap", {"BENCH_PRESET": "gpt2_774m",
                           "BENCH_RECIPE": "fsdp", "OVERLAP": "on"}),
    ("moe_scatter_ep", {"BENCH_MOE": "1", "MOE_IMPL": "scatter",
                        "BENCH_RECIPE": "ep", "BENCH_EP": "2"}),
    ("moe_grouped_ep", {"BENCH_MOE": "1", "MOE_IMPL": "grouped",
                        "BENCH_RECIPE": "ep", "BENCH_EP": "2"}),
]

# decode/serve legs: their tok/s values are not MFU-comparable, so they
# ride beside the headline and never win its max()
_DECODE_LEGS = [
    ("decode_flash", {"BENCH_DECODE": "1", "FLASH_DECODE": "on"}),
    ("decode_naive", {"BENCH_DECODE": "1", "FLASH_DECODE": "off"}),
    # round 9: int8 KV (in-kernel dequant) + weight-only int8 decode
    ("decode_int8", {"BENCH_DECODE": "1", "FLASH_DECODE": "on",
                     "BENCH_CACHE_DTYPE": "int8", "BENCH_QUANT_W": "1"}),
    ("decode_int8_kv", {"BENCH_DECODE": "1", "FLASH_DECODE": "on",
                        "BENCH_CACHE_DTYPE": "int8"}),
    # round 10: Poisson load against the async scheduler
    ("serve_load", {"BENCH_SERVE": "1", "FLASH_DECODE": "on"}),
    ("serve_load_int8", {"BENCH_SERVE": "1", "FLASH_DECODE": "on",
                         "BENCH_CACHE_DTYPE": "int8", "BENCH_QUANT_W": "1"}),
    # paged cache + radix prefix reuse vs the no-reuse baseline
    ("serve_load_prefix", {"BENCH_SERVE": "1", "FLASH_DECODE": "on",
                           "BENCH_SERVE_PREFIX": "0.8"}),
    # chunked prefill fused into the decode step, chunk-size sweep
    ("serve_load_chunked", {"BENCH_SERVE": "1", "FLASH_DECODE": "on",
                            "BENCH_PREFILL_CHUNK": "128,256,512"}),
    # ISSUE 16: speculative decoding, BENCH_SPEC_K sweep vs spec-off
    ("serve_load_spec", {"BENCH_SERVE": "1", "BENCH_SERVE_SPEC": "1",
                         "FLASH_DECODE": "on", "BENCH_SPEC_K": "2,4"}),
    # ISSUE 17: host-RAM KV tier on vs off at a 0.1x pool
    ("serve_load_tier", {"BENCH_SERVE": "1", "BENCH_SERVE_TIER": "1",
                         "FLASH_DECODE": "on"}),
    # ISSUE 18: replica start -> first token, cold vs warmed AOT store
    ("serve_spinup", {"BENCH_SERVE": "1", "BENCH_SERVE_SPINUP": "1",
                      "FLASH_DECODE": "on"}),
    # replicated serving behind the router — CPU replica processes, so
    # its metric is cpu_proxy_* whatever the other legs ran on
    ("serve_load_router", {"BENCH_SERVE": "1", "BENCH_SERVE_ROUTER": "1"}),
    # ISSUE 20: two-tenant two-class mix through the control plane
    ("serve_load_classes", {"BENCH_SERVE": "1", "BENCH_SERVE_CLASSES": "1",
                            "FLASH_DECODE": "on"}),
]


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        _worker_main(sys.argv[2],
                     sys.argv[3] if len(sys.argv) > 3 else None)
        return

    if any(os.environ.get(k) for k in ("BENCH_BATCH", "BENCH_REMAT",
                                       "BENCH_LOSS", "BENCH_ATTN",
                                       "BENCH_MOE", "BENCH_DECODE",
                                       "BENCH_SERVE", "BENCH_PRESET")):
        out = _spawn_worker("tpu", timeout_s=1800)   # one explicit config
    else:
        # no explicit config: a mini-sweep, each leg its own process with
        # a 900 s cap (a healthy leg is ~3 min incl. compile); the
        # headline is the best MFU-comparable leg. Any leg that fails
        # raises out of here — the run fails, nothing is printed.
        candidates = []
        legs = list(_TRAIN_LEGS)
        while legs:
            name, env = legs.pop(0)
            r = _spawn_worker("tpu", timeout_s=900, extra_env=env)
            r["config"] = name
            candidates.append(r)
            if name == "batch16" and r.get("n_chips", 1) > 1:
                legs += _MULTICHIP_TRAIN_LEGS
        out = max(candidates, key=lambda r: r.get("value", 0))
        out["configs_tried"] = {c["config"]: c["value"] for c in candidates}
        out["decode_legs"] = {
            name: _spawn_worker("tpu", timeout_s=900, extra_env=env)
            for name, env in _DECODE_LEGS}
    if out.get("n_chips", 1) > 1 and "recipes" in out:
        # second worker for the DDP leg of the FSDP-vs-DDP comparison
        # (fresh process -> uncontaminated peak-HBM stats)
        dp = _spawn_worker("tpu", timeout_s=1800, only_recipe="dp")
        out["recipes"].update(dp.get("recipes", {}))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
