"""MFU ablation sweep on the flagship bench config (round-4 VERDICT #1).

Times the jitted train_step in isolation (device-resident data, no host
loop) across the tuning axes the verdict names: batch size, attention
implementation, activation recomputation, loss path. Prints one line per
variant: ms/step, tokens/s, MFU, peak HBM.

Usage:  python scripts/mfu_sweep.py [--iters 8] [--variants all|quick]
"""

from __future__ import annotations

import argparse
import itertools
import os
import sys
import time

# runnable as `python scripts/mfu_sweep.py` without an installed package or
# PYTHONPATH: the repo root owns `distributed_pytorch_tpu`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.train import metrics as M
from distributed_pytorch_tpu.train.state import create_train_state
from distributed_pytorch_tpu.train.step import make_train_step


def _time_decode(slots: int, iters: int) -> dict:
    """Isolated fused decode step (round 8): `slots` sequences advance one
    token against a half-full slot cache. Decode is memory-bound, so the
    utilization column is MBU — bytes-moved model (params read once per
    step + valid KV rows, train/metrics.decode_step_bytes) over the chip's
    peak HBM bandwidth — printed where the train variants print MFU.
    FLASH_DECODE / FLASH_DECODE_BLOCK env knobs A/B the split-KV kernel
    against the naive einsum path per subprocess; SWEEP_CACHE_DTYPE=int8 /
    SWEEP_QUANT_W=1 add the round-9 quantized columns (int8 KV cache with
    in-kernel dequant, weight-only int8 matmuls) with the MBU bytes priced
    at the true itemsizes."""
    import contextlib

    import jax.numpy as jnp

    from distributed_pytorch_tpu.config import PRESETS
    from distributed_pytorch_tpu.models.gpt import LLM, init_cache
    from distributed_pytorch_tpu.ops.quant import (quantize_params,
                                                   use_quantized_params)

    preset = os.environ.get("SWEEP_PRESET", "gpt2_124m")
    cfg = PRESETS[preset]()
    dtype = jnp.bfloat16
    cache_dtype = jnp.int8 \
        if os.environ.get("SWEEP_CACHE_DTYPE", "") == "int8" else dtype
    quant_w = os.environ.get("SWEEP_QUANT_W", "") == "1"
    model = LLM(cfg, compute_dtype=dtype, attn_impl="auto")
    rng = jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = jax.jit(model.init)({"params": rng, "dropout": rng},
                                    dummy, dummy)
    qparams = jax.jit(quantize_params)(variables["params"]) \
        if quant_w else None
    S = cfg.block_size
    cache_len = S // 2
    caches = init_cache(cfg, slots, S, dtype=cache_dtype)
    pos = jnp.full((slots,), cache_len, jnp.int32)
    tok = jnp.zeros((slots,), jnp.int32)

    @jax.jit
    def step(variables, caches, tok, pos, qparams):
        ctx = use_quantized_params(qparams) if qparams is not None \
            else contextlib.nullcontext()
        with ctx:
            logits, _, caches = model.apply(variables, tok[:, None], None,
                                            caches, pos, deterministic=True)
        nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
        return caches, nxt, pos + 1

    caches, tok, pos = step(variables, caches, tok, pos, qparams)  # compile
    jax.device_get(tok)
    t0 = time.perf_counter()
    for _ in range(iters):
        caches, tok, pos = step(variables, caches, tok, pos, qparams)
    jax.device_get(tok)  # metrics-fetch sync (see time_variant note)
    dt = (time.perf_counter() - t0) / iters
    dsz = jnp.dtype(dtype).itemsize
    bts = M.decode_step_bytes(cfg, slots, cache_len + iters // 2, dsz,
                              jnp.dtype(cache_dtype).itemsize,
                              quant_weights=quant_w)
    bw = M.peak_hbm_bw_per_chip()
    mbu = bts / dt / bw if bw else float("nan")
    flash = os.environ.get("FLASH_DECODE", "auto")
    blk = os.environ.get("FLASH_DECODE_BLOCK", "512")
    cd = jnp.dtype(cache_dtype).name
    print(f"decode slots={slots:4d} cache={cache_len:5d} flash={flash:4s} "
          f"block={blk:>4s} kv={cd:8s} qw={quant_w!s:5s} | "
          f"{dt * 1e3:7.2f} ms/step | "
          f"{slots / dt:9.0f} tok/s | mbu {mbu:6.2%} | "
          f"{bts / 2 ** 20:6.0f} MiB/step [{preset}]", flush=True)
    return {"decode": True, "slots": slots, "ms": dt * 1e3, "mbu": mbu,
            "flash_decode": flash, "block": blk, "preset": preset,
            "cache_dtype": cd, "quant_w": quant_w}


def time_variant(batch: int, attn_impl: str, act_recomp: bool,
                 loss_impl: str, iters: int) -> dict | None:
    import os as _os
    if _os.environ.get("SWEEP_DECODE"):
        # decode leg: `batch` is the slot count; attn/remat/loss unused
        try:
            return _time_decode(batch, iters)
        except Exception as e:  # noqa: BLE001 — report like train variants
            print(f"decode slots={batch} FAILED: {type(e).__name__}: "
                  f"{str(e)[:120]}", flush=True)
            if any(s in str(e) for s in ("Out of memory", "VMEM", "vmem",
                                         "exceeds available")):
                sys.exit(3)
            return None

    from distributed_pytorch_tpu.config import PRESETS
    # per-subprocess env knobs (like FLASH_BLOCK_*): SWEEP_PRESET picks the
    # ladder rung, SWEEP_RECIPE the parallelism, SWEEP_MOE the MoE dispatch
    # impl (dense|scatter|grouped — swaps the FFN for the bench MoE),
    # SWEEP_EP the 'expert' mesh-axis size (OVERLAP/OVERLAP_RING/GMM_BLOCK_*
    # are read by the ops modules directly)
    preset = _os.environ.get("SWEEP_PRESET", "gpt2_124m")
    recipe = _os.environ.get("SWEEP_RECIPE", "single")
    moe_impl = _os.environ.get("SWEEP_MOE", "")
    ep_size = int(_os.environ.get("SWEEP_EP", "1"))
    pp_size = int(_os.environ.get("SWEEP_PP", "1"))
    cpu_devs = int(_os.environ.get("SWEEP_CPU_DEVICES", "0"))
    if cpu_devs:
        # pipeline legs on a dev box: carve virtual CPU devices so the
        # pipe axis is a real mesh axis (must precede any jax device op)
        from distributed_pytorch_tpu.compat import request_cpu_devices
        request_cpu_devices(cpu_devs)
    moe_kw = {}
    if moe_impl:
        # same MoE shape as bench.py's moe_* legs so the two measure the
        # same model (active params stay 124M-class)
        moe_kw = dict(moe=True, n_exp=8, n_shared=1, n_act=3, up_dim=1024,
                      moe_impl=moe_impl)
    if pp_size > 1:
        # the pipe mesh axis and the model's stacked-stage count are one
        # decision (train/loop.py links them the same way)
        moe_kw["pp_stages"] = pp_size
    if _os.environ.get("SWEEP_TINY") == "1":
        # CPU-provable shape for the pipeline legs: a 124M step takes
        # minutes per iteration on a dev box; the schedule A/B only
        # needs enough layers for vpp=2 chunks, not the real width
        moe_kw.update(n_layer=4, n_embd=256, n_head=4, n_kv_heads=4,
                      up_dim=512)
    model_cfg = PRESETS[preset](act_recomp=act_recomp,
                                act_recomp_policy="attn",
                                loss_impl=loss_impl, **moe_kw)
    n_dev = len(jax.devices()) if recipe != "single" else 1
    train_cfg = TrainConfig(
        dataset="synthetic", total_batch_size=batch * n_dev * 1024,
        batch_size=batch, max_iters=iters, parallelism=recipe,
        attn_impl=attn_impl, ep_size=ep_size, pp_size=pp_size,
        eval=False, save_model=False, save_stats=False,
        compute_dtype="bfloat16")

    try:
        mesh = None
        if recipe != "single":
            from distributed_pytorch_tpu.parallel.mesh import mesh_for
            mesh = mesh_for(recipe, ep_size=ep_size, pp_size=pp_size)
        model, tx, state, state_sh = create_train_state(model_cfg,
                                                        train_cfg, mesh)
        # the sweep honors the OFFLOAD knob the same way the loop's gate
        # does for an explicit 'on' — the 1f1b+offload A/B leg
        from distributed_pytorch_tpu.config import knob
        step = make_train_step(model, tx, model_cfg, train_cfg, mesh,
                               state_sh, offload=knob("OFFLOAD") == "on")
        rng = jax.random.PRNGKey(0)
        x = jax.random.randint(rng, (1, batch * n_dev, 1024), 0, 50304,
                               jnp.int32)
        y = jax.random.randint(rng, (1, batch * n_dev, 1024), 0, 50304,
                               jnp.int32)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from distributed_pytorch_tpu.parallel import sharding as shd
            bsh = NamedSharding(mesh, shd.batch_pspec(recipe, mesh,
                                                      leading_accum=True))
            x = jax.device_put(x, bsh)
            y = jax.device_put(y, bsh)
        state, m = step(state, x, y)       # compile + warmup
        jax.device_get(m)
        # Sync via device_get of the step metrics, exactly like the trainer's
        # log-boundary sync (train/loop.py): it fetches a value that depends
        # on every queued step (block_until_ready on it is an equal fence).
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, x, y)
        jax.device_get(m)
        times = [(time.perf_counter() - t0) / iters]
    except Exception as e:  # OOM etc.
        print(f"batch={batch:3d} attn={attn_impl:6s} remat={act_recomp!s:5s} "
              f"loss={loss_impl:9s} FAILED: {type(e).__name__}: "
              f"{str(e)[:120]}", flush=True)
        # a variant the chip refuses (OOM, a Mosaic compile over the VMEM
        # limit) gets a distinct exit code: the sweep records it and goes on
        msg = str(e)
        if any(s in msg for s in ("Out of memory", "VMEM", "vmem",
                                  "exceeds available", "RESOURCE_EXHAUSTED")):
            sys.exit(3)
        return None

    dt = float(np.median(times))
    tokens = batch * n_dev * 1024
    flops = M.step_flops(model_cfg, tokens, 1024)
    peak = M.peak_flops_per_chip()
    mfu = flops / dt / (peak * n_dev) if peak else float("nan")
    hbm = M.device_memory_gb()
    # memplan predicted-vs-measured (ISSUE 10): price this exact variant
    # (batch/remat/recipe) and put the peak_bytes_in_use delta next to
    # the MFU column — the ladder sweep IS the ROADMAP's "validate
    # train/memplan.py against peak_bytes_in_use" instrument
    from distributed_pytorch_tpu.train import memplan
    try:
        mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
            if mesh is not None else {}
        predicted, _ = memplan.predicted_train_peak_gb(
            model_cfg, train_cfg, mesh_sizes)
        predicted = round(predicted, 3)
    except Exception:  # noqa: BLE001 — the plan must never sink a variant
        predicted = None
    plan_delta = round(hbm - predicted, 3) \
        if (hbm is not None and predicted is not None) else None
    tag = "" if (preset, recipe) == ("gpt2_124m", "single") \
        else f" [{preset}/{recipe}]"
    if plan_delta is not None:
        tag += f" [plan {predicted:.2f}GB Δ{plan_delta:+.2f}]"
    if moe_impl:
        # MFU counts active-expert FLOPs; the overcompute factor says how
        # much the dispatch overspends delivering them (dense E/k x,
        # scatter ~cf x, grouped ~1 x — train/metrics.py)
        tag += (f" [moe={moe_impl} "
                f"overcompute={M.moe_overcompute_factor(model_cfg):.2f}x]")
    # each variant names the device it ran on (the parent never asks)
    tag += f" [{jax.default_backend()}: {jax.devices()[0].device_kind}]"
    print(f"batch={batch:3d} attn={attn_impl:6s} remat={act_recomp!s:5s} "
          f"loss={loss_impl:9s} | {dt * 1e3:7.1f} ms | "
          f"{tokens / dt:9.0f} tok/s | mfu {mfu:6.2%} | "
          f"hbm {hbm or 0:5.2f}GB{tag}",
          flush=True)
    out = {"batch": batch, "attn": attn_impl, "remat": act_recomp,
           "loss": loss_impl, "ms": dt * 1e3, "mfu": mfu,
           "preset": preset, "recipe": recipe,
           "moe_impl": moe_impl or None,
           "memplan_predicted_gb": predicted, "measured_peak_gb": hbm,
           "memplan_delta_gb": plan_delta}
    # persist the variant as one train_timeline.jsonl record under
    # runs/ (the round-14 artifact convention: every leg's JSON points
    # at its on-disk timeline via "artifacts")
    try:
        from distributed_pytorch_tpu.obs.flight import FlightRecorder
        leg = (f"mfu_sweep/{preset}_{recipe}_b{batch}_{attn_impl}"
               f"_{'remat' if act_recomp else 'norem'}_{loss_impl}")
        fl = FlightRecorder(capacity=8)
        fl.record(**{k: v for k, v in out.items() if v is not None})
        out["artifacts"] = {"train_timeline": fl.dump_jsonl(
            os.path.join("runs", leg, "train_timeline.jsonl"))}
    except Exception:  # noqa: BLE001 — artifacts never sink the variant
        pass
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--variants", default="quick")
    ap.add_argument("--one", default=None,
                    help="internal: run ONE variant 'batch,attn,remat,loss' "
                         "in this process and exit")
    args = ap.parse_args()

    if args.one:
        b, a, r, l = args.one.split(",")
        ok = time_variant(int(b), a, r == "True", l, args.iters)
        sys.exit(0 if ok else 1)

    # the parent stays OFF every jax backend: a chip belongs to one process
    # at a time and each variant below is a child that needs it (the child
    # prints the device it saw on its own result line)

    if args.variants == "quick":
        grid = [
            (16, "xla", False, "fused"),      # round-3 bench config + fused CE
            (16, "xla", False, "unchunked"),  # round-3 baseline
            (16, "pallas", False, "fused"),
            (32, "xla", False, "fused"),
            (32, "pallas", False, "fused"),
            (32, "xla", True, "fused"),
            (64, "pallas", True, "fused"),
            (64, "xla", True, "fused"),
        ]
    elif args.variants == "blocks":
        # flash-kernel block-size ablation inside the REAL train step (the
        # profile shows XLA attention burns ~150ms/step materializing f32
        # scores; this decides whether the in-house kernel replaces it and
        # at which tile size). FLASH_BLOCK_* is read by ops/flash_attention
        # at import, so each subprocess gets its own value.
        grid = [
            (16, "xla", False, "fused"),
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "128",
                                            "FLASH_BLOCK_K": "128"}),
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "256"}),
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "512"}),
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "512",
                                            "FLASH_BLOCK_K": "512"}),
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "512",
                                            "FLASH_BLOCK_K": "1024"}),
            (32, "pallas", False, "fused", {"FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "512"}),
            (32, "pallas", False, "fused", {"FLASH_BLOCK_Q": "512",
                                            "FLASH_BLOCK_K": "512"}),
            # row-group (B*H flattened) blocking: grid steps / block_h
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "512",
                                            "FLASH_BLOCK_H": "1"}),
            (16, "pallas", False, "fused", {"FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "512",
                                            "FLASH_BLOCK_H": "24"}),
            # slab kernel layout (round 5): zero HBM transposes — A/B vs
            # the rows layout at the same tiles
            (16, "pallas", False, "fused", {"FLASH_LAYOUT": "slab",
                                            "FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "512"}),
            (16, "pallas", False, "fused", {"FLASH_LAYOUT": "slab",
                                            "FLASH_BLOCK_Q": "512",
                                            "FLASH_BLOCK_K": "512"}),
            (16, "pallas", False, "fused", {"FLASH_LAYOUT": "slab",
                                            "FLASH_BLOCK_Q": "256",
                                            "FLASH_BLOCK_K": "256"}),
            # streaming pallas CE (ops/fused_ce.py) vs the chunked scan
            (16, "xla", False, "pallas"),
            (16, "xla", False, "pallas", {"CE_BLOCK_N": "1024"}),
            (16, "xla", False, "pallas", {"CE_BLOCK_N": "256",
                                          "CE_BLOCK_V": "4096"}),
            (16, "pallas", False, "pallas", {"FLASH_BLOCK_Q": "256",
                                             "FLASH_BLOCK_K": "512"}),
        ]
    elif args.variants == "overlap":
        # collective-matmul A/B on the real sharded train step
        # (ops/collective_matmul.py): GSPMD baseline vs uni/bidir rings vs
        # hoisted gathers is decided by OVERLAP/OVERLAP_RING env, per
        # subprocess. fsdp on every available chip.
        grid = [
            (8, "xla", False, "fused", {"SWEEP_RECIPE": "fsdp"}),
            (8, "xla", False, "fused", {"SWEEP_RECIPE": "fsdp",
                                        "OVERLAP": "on"}),
            (8, "xla", False, "fused", {"SWEEP_RECIPE": "fsdp",
                                        "OVERLAP": "on",
                                        "OVERLAP_RING": "uni"}),
            (16, "pallas", False, "fused", {"SWEEP_RECIPE": "fsdp"}),
            (16, "pallas", False, "fused", {"SWEEP_RECIPE": "fsdp",
                                            "OVERLAP": "on"}),
        ]
    elif args.variants == "moe":
        # MOE_IMPL A/B inside the real train step (ISSUE round 7): dense
        # combine vs capacity-scatter vs the dropless grouped kernel, on
        # one chip and under expert parallelism. A chip run runs
        # this to self-select the MoE dispatch default (the bench
        # mini-sweep's moe_* legs measure the same matrix end-to-end).
        grid = [
            (16, "xla", False, "fused", {"SWEEP_MOE": "dense"}),
            (16, "xla", False, "fused", {"SWEEP_MOE": "scatter"}),
            (16, "xla", False, "fused", {"SWEEP_MOE": "grouped"}),
            (16, "xla", False, "fused", {"SWEEP_MOE": "grouped",
                                         "GMM_BLOCK_M": "256"}),
            (16, "xla", False, "fused", {"SWEEP_MOE": "grouped",
                                         "GMM_BLOCK_N": "1024"}),
            (16, "xla", False, "fused", {"SWEEP_MOE": "scatter",
                                         "SWEEP_RECIPE": "ep",
                                         "SWEEP_EP": "2"}),
            (16, "xla", False, "fused", {"SWEEP_MOE": "grouped",
                                         "SWEEP_RECIPE": "ep",
                                         "SWEEP_EP": "2"}),
        ]
    elif args.variants == "decode":
        # flash-decode vs naive A/B inside the isolated fused decode step
        # (round 8): slot-count scaling (decode amortizes the weight read
        # over slots), split-KV tile ablation, and a ladder rung. The
        # printed column is MBU (memory-bandwidth utilization), not MFU.
        # Round 9 adds the int8 column next to each bf16 leg: int8 KV
        # (in-kernel dequant), weight-only int8, and both — the
        # quantized-serving A/B that decides the QUANT_* auto defaults.
        D = {"SWEEP_DECODE": "1"}
        I8 = {"SWEEP_CACHE_DTYPE": "int8"}
        grid = [
            (8, "auto", False, "fused", {**D, "FLASH_DECODE": "off"}),
            (8, "auto", False, "fused", {**D, "FLASH_DECODE": "on"}),
            (8, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "on"}),
            (32, "auto", False, "fused", {**D, "FLASH_DECODE": "off"}),
            (32, "auto", False, "fused", {**D, "FLASH_DECODE": "on"}),
            (32, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "off"}),
            (32, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "on"}),
            (32, "auto", False, "fused", {**D, "FLASH_DECODE": "on",
                                          "SWEEP_QUANT_W": "1"}),
            (32, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "on",
                                          "SWEEP_QUANT_W": "1"}),
            (32, "auto", False, "fused", {**D, "FLASH_DECODE": "on",
                                          "FLASH_DECODE_BLOCK": "256"}),
            (32, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "on",
                                          "FLASH_DECODE_BLOCK": "256"}),
            (32, "auto", False, "fused", {**D, "FLASH_DECODE": "on",
                                          "FLASH_DECODE_BLOCK": "1024"}),
            (128, "auto", False, "fused", {**D, "FLASH_DECODE": "on"}),
            (128, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "on",
                                           "SWEEP_QUANT_W": "1"}),
            (8, "auto", False, "fused", {**D, "FLASH_DECODE": "on",
                                         "SWEEP_PRESET": "gpt2_350m"}),
            (8, "auto", False, "fused", {**D, **I8, "FLASH_DECODE": "on",
                                         "SWEEP_QUANT_W": "1",
                                         "SWEEP_PRESET": "gpt2_350m"}),
        ]
    elif args.variants == "pipeline":
        # interleaved-1F1B vs carry vs 1f1b+offload inside the real pp
        # train step (ISSUE 19), on CPU-provable shapes: 2 virtual CPU
        # devices carve a pipe=2 mesh (on a TPU slice the same legs run
        # on real chips and SWEEP_CPU_DEVICES is ignored by the backend).
        # The bubble win itself needs silicon; what this proves anywhere
        # is schedule parity at equal config, the plan-delta column, and
        # the offload split-step cost (PCIe legs on hardware, host
        # round-trip on CPU).
        PP = {"SWEEP_RECIPE": "pp", "SWEEP_PP": "2",
              "SWEEP_CPU_DEVICES": "2", "SWEEP_TINY": "1"}
        grid = [
            (4, "xla", False, "fused", {**PP, "PP_SCHEDULE": "carry"}),
            (4, "xla", False, "fused", {**PP, "PP_SCHEDULE": "1f1b"}),
            (4, "xla", False, "fused", {**PP, "PP_SCHEDULE": "1f1b",
                                        "OFFLOAD": "on"}),
            (8, "xla", True, "fused", {**PP, "PP_SCHEDULE": "carry"}),
            (8, "xla", True, "fused", {**PP, "PP_SCHEDULE": "1f1b"}),
            (8, "xla", True, "fused", {**PP, "PP_SCHEDULE": "1f1b",
                                       "PP_VPP": "2"}),
            (8, "xla", True, "fused", {**PP, "PP_SCHEDULE": "1f1b",
                                       "OFFLOAD": "on"}),
        ]
    elif args.variants == "ladder":
        # the 350M-1.5B rungs (BASELINE.json): batch/remat per the static
        # HBM plan printed by --dryrun; OVERLAP on/off legs for each rung
        grid = [
            (16, "xla", True, "fused", {"SWEEP_PRESET": "gpt2_350m",
                                        "SWEEP_RECIPE": "zero2"}),
            (16, "xla", True, "fused", {"SWEEP_PRESET": "gpt2_350m",
                                        "SWEEP_RECIPE": "zero2",
                                        "OVERLAP": "on"}),
            (8, "xla", True, "fused", {"SWEEP_PRESET": "gpt2_774m",
                                       "SWEEP_RECIPE": "fsdp"}),
            (8, "xla", True, "fused", {"SWEEP_PRESET": "gpt2_774m",
                                       "SWEEP_RECIPE": "fsdp",
                                       "OVERLAP": "on"}),
            (2, "xla", True, "fused", {"SWEEP_PRESET": "gpt2_1p5b",
                                       "SWEEP_RECIPE": "fsdp"}),
            (2, "xla", True, "fused", {"SWEEP_PRESET": "gpt2_1p5b",
                                       "SWEEP_RECIPE": "fsdp",
                                       "OVERLAP": "on"}),
        ]
    else:
        grid = list(itertools.product((16, 32, 64), ("xla", "pallas"),
                                      (False, True), ("fused",)))

    # one subprocess per variant: peak_bytes_in_use is process-monotone, so
    # an in-process loop would report every variant's 'peak HBM' as the max
    # over all PRIOR variants (hiding exactly the remat/batch savings this
    # sweep measures); a variant that OOMs also can't take down the rest
    import subprocess
    failed = []
    for variant in grid:
        batch, attn, remat, loss = variant[:4]
        extra_env = variant[4] if len(variant) > 4 else {}
        cmd = [sys.executable, __file__, "--iters", str(args.iters),
               "--one", f"{batch},{attn},{remat},{loss}"]
        env = dict(os.environ, **extra_env)
        tag = ",".join(f"{k}={v}" for k, v in extra_env.items())
        if tag:
            print(f"[{tag}]", flush=True)
        try:
            r = subprocess.run(cmd, timeout=1200, env=env)
            if r.returncode != 0:
                failed.append(f"{batch},{attn},{remat},{loss}"
                              f"{' [' + tag + ']' if tag else ''}: "
                              f"rc={r.returncode}"
                              f"{' (chip refused it)' if r.returncode == 3 else ''}")
                print(f"variant {failed[-1]}", flush=True)
        except subprocess.TimeoutExpired:
            failed.append(f"{batch},{attn},{remat},{loss}: TIMEOUT")
            print(f"variant {failed[-1]}", flush=True)
    if failed:
        print(f"{len(failed)} variant(s) failed:\n  " + "\n  ".join(failed),
              flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
