"""Serving CLI: `python -m distributed_pytorch_tpu.serve --ckpt <dir>`.

Loads a trainer checkpoint (same restore path as sample.py, including
`--shard` for mesh-sharded models and pp unstacking), builds a
`DecodeEngine` (+ the round-9 int8 knobs), wraps it in the async
scheduler, and serves `POST /v1/completions` (SSE streaming), `/healthz`
and `/metrics` until interrupted. `--demo` starts a tiny random-init
model instead — no checkpoint needed, for smoke tests
(scripts/serve_smoke.sh) and CI.
"""

from __future__ import annotations

import argparse
import asyncio
import signal

import jax


def build_args(argv=None):
    p = argparse.ArgumentParser(
        description="Streaming HTTP serving over the DecodeEngine")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--ckpt", type=str,
                     help="checkpoint dir (trainer layout; the newest "
                          "step is used when given the run root)")
    src.add_argument("--demo", action="store_true",
                     help="serve a tiny random-init model (no checkpoint; "
                          "token-id prompts only) — smoke tests")
    p.add_argument("--host", type=str, default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="0 picks an ephemeral port (printed at startup)")
    p.add_argument("--slots", type=int, default=8,
                   help="decode slots (size with "
                        "train.memplan.plan_decode_slots)")
    p.add_argument("--max-queue", type=int, default=128,
                   help="admission queue bound; overflow is shed as 429")
    p.add_argument("--deadline-s", type=float, default=None,
                   help="default per-request queue-wait deadline")
    p.add_argument("--max-tokens-default", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top_k", "--top-k", dest="top_k", type=int, default=50)
    p.add_argument("--eos-id", type=int, default=None,
                   help="retire sequences on this token (GPT-2: 50256)")
    p.add_argument("--seed", type=int, default=1729)
    p.add_argument("--shard", action="store_true",
                   help="sharded restore in the training recipe's layout")
    p.add_argument("--cache-dtype", "--cache_dtype", dest="cache_dtype",
                   default="", choices=["", "int8", "bfloat16", "float32"])
    p.add_argument("--quant-weights", "--quant_weights",
                   dest="quant_weights", action="store_true")
    p.add_argument("--kv-block", "--kv_block", dest="kv_block", type=int,
                   default=None,
                   help="paged-cache block size in KV rows (pow2; default "
                        "16 — TPU serving wants 128+ so the paged flash "
                        "kernel engages)")
    p.add_argument("--kv-blocks", "--kv_blocks", dest="kv_blocks", type=int,
                   default=None,
                   help="block-pool size (train.memplan.plan_decode_blocks;"
                        " default: slots x max_len worth of blocks)")
    p.add_argument("--no-prefix-cache", dest="prefix_cache",
                   action="store_false",
                   help="disable radix prefix reuse (A/B baseline)")
    p.add_argument("--kv-host-gb", "--kv_host_gb", dest="kv_host_gb",
                   type=float, default=None,
                   help="host-RAM KV tier budget in GiB — priced into "
                        "whole blocks via train.memplan (scale sidecars "
                        "included for an int8 cache) and enables the "
                        "tier; overrides the KV_HOST_BLOCKS knob")
    p.add_argument("--request-timeout-s", "--request_timeout_s",
                   dest="request_timeout_s", type=float, default=30.0,
                   help="per-connection read timeout while parsing a "
                        "request (stalled clients get 408)")
    p.add_argument("--no-trace", dest="trace", action="store_false",
                   help="disable the request-trace recorder (obs/trace.py"
                        "; spans cost ~µs per REQUEST, so default on — "
                        "this is the A/B-overhead escape hatch)")
    p.add_argument("--profile-dir", "--profile_dir", dest="profile_dir",
                   type=str, default="",
                   help="output dir for POST /admin/profile captures "
                        "(default runs/serve/profile)")
    p.add_argument("--prefill-chunk", "--prefill_chunk",
                   dest="prefill_chunk", type=int, default=0,
                   help="fuse Sarathi-style chunked prefill into the "
                        "decode step: <=N prefill tokens ride each fused "
                        "step so live streams never stall on a prompt "
                        "(multiple of --kv-block; a chunk-carrying step "
                        "costs N rows however full). 0 = legacy "
                        "all-or-nothing wave "
                        "prefill (the A/B baseline)")
    p.add_argument("--aot-store", "--aot_store", dest="aot_store",
                   type=str, default="",
                   help="AOT program store dir (parallel/aot_store.py): "
                        "spin-up loads serialized executables instead "
                        "of JIT-compiling (misses compile + write "
                        "back); empty defers to the AOT_STORE/"
                        "AOT_STORE_DIR knobs")
    p.add_argument("--aot-strict", "--aot_strict", dest="aot_strict",
                   choices=["off", "warn", "require"], default=None,
                   help="store-miss handling (default: the AOT_STRICT "
                        "knob); require raises — the zero-cold-start "
                        "CI proof")
    return p.parse_args(argv)


def _demo_model():
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.models.gpt import LLM
    import jax.numpy as jnp
    cfg = LLMConfig(vocab_size=1024, block_size=256, n_embd=128, n_head=4,
                    n_kv_heads=4, attn="mha", n_layer=2, up_dim=256,
                    non_linearity="swiglu", pos_emb="rope")
    model = LLM(cfg, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    return model, dict(variables), None, "single"


def build_engine(args, *, warm: bool = True):
    """Engine spin-up shared by this CLI and scripts/aot_warm.py (the
    warming CLI MUST build through the same code path so its store keys
    equal a serving replica's by construction). Returns (engine,
    encoder, weights_version, spinup) where `spinup` is the phase
    record list {phase: load|warm, ms} the TTFT-split report reads."""
    import time

    from distributed_pytorch_tpu.engine import DecodeEngine

    spinup = []
    t0 = time.perf_counter()
    if args.demo:
        model, variables, mesh, recipe = _demo_model()
        encoder = None
        weights_version = "demo"
        print("demo mode: tiny random-init model, token-id prompts only")
    else:
        from distributed_pytorch_tpu.sample import LazyEncoder, \
            load_for_inference
        (model, variables, _, train_cfg, mesh, _,
         weights_version) = load_for_inference(args.ckpt, shard=args.shard)
        recipe = train_cfg.parallelism if mesh is not None else "single"
        # resolved on the first TEXT prompt, never here: start-up must
        # not wait on tiktoken's vocabulary download (sample.LazyEncoder)
        encoder = LazyEncoder()
    spinup.append({"spinup": "weights", "phase": "load",
                   "ms": round((time.perf_counter() - t0) * 1e3, 3)})

    # --kv-host-gb prices a host-RAM tier budget into whole KV blocks
    # with the planner's bytes-per-token model (train/memplan.py) and
    # turns the tier on; None falls through to the KV_HOST_TIER /
    # KV_HOST_BLOCKS knobs inside the engine
    host_tier = None
    host_blocks = None
    if args.kv_host_gb is not None:
        from distributed_pytorch_tpu.train.memplan import \
            host_tier_blocks_for_gb
        host_blocks = host_tier_blocks_for_gb(
            model.config, args.kv_host_gb,
            block_size=args.kv_block or 16,
            cache_dtype_size=1 if args.cache_dtype == "int8" else 2)
        host_tier = host_blocks > 0

    aot_store = None
    if args.aot_store:
        from distributed_pytorch_tpu.parallel.aot_store import AOTStore
        aot_store = AOTStore(args.aot_store, strict=args.aot_strict)
    eng = DecodeEngine(model, variables, n_slots=args.slots,
                       cache_dtype=args.cache_dtype or None,
                       quantize_weights=args.quant_weights,
                       temperature=args.temperature, top_k=args.top_k,
                       eos_id=args.eos_id,
                       rng=jax.random.PRNGKey(args.seed),
                       mesh=mesh, recipe=recipe,
                       block_size=args.kv_block, n_blocks=args.kv_blocks,
                       prefix_cache=args.prefix_cache,
                       prefill_chunk=args.prefill_chunk,
                       host_tier=host_tier, host_blocks=host_blocks,
                       aot_store=aot_store)
    if warm and eng.aot_store is not None:
        # eager spin-up: every program this config can request is built
        # NOW (hit = deserialize, miss = compile + write back), so
        # first-token latency is weight load + prefill, never compile
        t0 = time.perf_counter()
        stats = eng.warm_aot(origin="runtime")
        spinup.append({"spinup": "aot_warm", "phase": "warm",
                       "ms": round((time.perf_counter() - t0) * 1e3, 3)})
        spinup.extend(dict(ev, spinup="aot")
                      for ev in eng.aot_store.events)
        print(f"aot store: {stats['hits']} hit(s), "
              f"{stats['misses']} miss(es), "
              f"compile {stats['compile_ms']:.0f}ms, "
              f"load {stats['load_ms']:.0f}ms ({eng.aot_store.root})")
    return eng, encoder, weights_version, spinup


def _dump_spinup(spinup) -> None:
    """Append this spin-up's phase records to runs/serve/spinup.jsonl —
    the obs/replay 'spinup' section's source (TTFT split into
    {load, compile, prefill})."""
    import json
    import os
    path = os.path.join("runs", "serve", "spinup.jsonl")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        for rec in spinup:
            f.write(json.dumps(rec) + "\n")


async def _amain(args) -> None:
    from distributed_pytorch_tpu.obs import trace as obs_trace
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    from distributed_pytorch_tpu.serve.server import ServeApp

    if not args.trace:
        obs_trace.get_recorder().enabled = False

    eng, encoder, weights_version, spinup = build_engine(args)
    # compile the step programs BEFORE /healthz answers ok, and say what
    # is in them: the device, each program's Pallas kernels by name (read
    # off the compiled text), and which way the dispatchers went
    from distributed_pytorch_tpu.obs.paths import device_record
    device = device_record()
    print(f"backend {device['platform']}: {device['count']} device(s) of "
          f"kind {device['kind']!r}")
    for name, prog in eng.describe_programs().items():
        print(f"[program] {name}: compiled in {prog['compile_s']:.1f}s | "
              f"kernels {prog['kernels'] or 'none (XLA only)'} | paths "
              f"{prog['paths']} | temp "
              f"{prog.get('temp_bytes', 0) / 2 ** 20:.0f} MiB")
        spinup.append({"spinup": "program", "phase": "compile",
                       "ms": round(prog["compile_s"] * 1e3, 3),
                       "program": name, "device": device, **prog})
    _dump_spinup(spinup)
    sched = Scheduler(eng, max_queue=args.max_queue,
                      default_deadline_s=args.deadline_s)
    # provenance labels for /metrics scrapes and bench JSON (the engine
    # half is set by the Scheduler; add what only the CLI knows)
    sched.metrics.set_build_info(
        preset="demo" if args.demo else (args.ckpt or ""),
        trace=args.trace)
    # weights identity (ckpt step dir + manifest digest prefix, or
    # "demo"): an info gauge on /metrics and a field on every
    # completion payload — the live-weight-delivery seed
    sched.metrics.set_weights_version(weights_version)
    app = ServeApp(sched, host=args.host, port=args.port, encoder=encoder,
                   default_max_tokens=args.max_tokens_default,
                   request_timeout_s=args.request_timeout_s,
                   profile_dir=args.profile_dir or None)
    await sched.start()
    await app.start()
    print(f"serving on http://{args.host}:{app.port} "
          f"(slots={args.slots}, queue<={args.max_queue}, "
          f"cache={'int8' if eng.kv_quantized else 'native'}, "
          f"quant_w={eng.weights_quantized}, "
          f"blocks={eng.n_blocks}x{eng.block_size}, "
          f"prefix_cache={eng.prefix_cache}, "
          f"prefill_chunk={eng.prefill_chunk or 'wave'})")
    print(f"  curl -N -X POST http://{args.host}:{app.port}/v1/completions "
          "-d '{\"prompt\": [1, 2, 3], \"max_tokens\": 16}'")
    # SIGTERM (what an orchestrator sends) ends the process the same way
    # Ctrl-C does: through the finally below, exit code 0 — not killed
    # mid-write with streams and the step loop still up
    serving = asyncio.ensure_future(app.serve_forever())
    asyncio.get_running_loop().add_signal_handler(signal.SIGTERM,
                                                  serving.cancel)
    try:
        await serving
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await app.stop()
        await sched.stop()
        print("server stopped cleanly", flush=True)


def main(argv=None) -> None:
    args = build_args(argv)
    from distributed_pytorch_tpu.config import enable_compile_cache
    enable_compile_cache()
    try:
        asyncio.run(_amain(args))
    except KeyboardInterrupt:
        print("\nshutting down")


if __name__ == "__main__":
    main()
