"""Static HBM budget estimator + micro-batch / remat planner.

Opens the 350M-1.5B config ladder (BASELINE.json) without burning a
chip time on OOM bisection: given a model config, a recipe, and a
per-chip HBM budget, `plan_memory` estimates the resident bytes of every
tensor class the recipe implies (fp32 params / AdamW moments / grad
accumulator — each divided by dp exactly when the recipe's sharding tables
shard it — plus per-micro-batch activations under each remat policy and
the fused-CE logits chunk) and picks the largest micro-batch x cheapest
remat policy that fits, with the grad-accum arithmetic
(global batch tokens / devices / micro-batch) solved at the same time.

Everything here is closed-form or jax.eval_shape (trace-only): no compile,
no allocation — `--dryrun` prints a 1.5B plan from a laptop CPU in
seconds. The estimate is deliberately conservative (activation bytes use a
per-token-per-layer formula derived from what the backward actually keeps
alive, times a 15% fragmentation/XLA-temp fudge); a chip run
validates the constants against `peak_bytes_in_use` and PERF.md records
the delta.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.parallel.sharding import (_GRAD_SHARDED,
                                                       _OPT_SHARDED,
                                                       _PARAM_SHARDED)

# The device-free planner (`--dryrun`, CPU rehearsals) has no chip to ask,
# so it plans for this one — by name, through the same table an attached
# chip is looked up in (train/metrics.CHIP_SPECS).
PLANNING_DEVICE_KIND = "TPU v5 lite"    # v5e, 16 GiB

# optimizer moment multiplier (x param bytes, fp32)
_OPT_MULT = {"adamw": 2.0, "lion": 1.0, "adafactor": 0.1}

_FUDGE = 1.15  # fragmentation + XLA temporaries

# HBM the runtime itself holds (program binaries, infeed buffers, XLA
# runtime scratch) — spec-sheet GiB minus this is what an allocation can
# actually get. Applied to plan_memory's fit check only: a plan within
# 0.9 GiB of the spec number OOMs in practice, and the 7B rung's
# "in-HBM moments DO NOT FIT / offload fits" decision depends on not
# pretending that margin exists.
_RUNTIME_RESERVE_GB = 0.9


def device_hbm_gb() -> float:
    """Per-chip HBM (GiB) to plan against: the attached accelerator's —
    an unknown device_kind is an error (metrics.chip_spec), never a
    silent 16 — or, on the CPU backend, the v5e the device-free planner
    names above."""
    from distributed_pytorch_tpu.train.metrics import CHIP_SPECS, chip_spec
    spec = chip_spec() or CHIP_SPECS[PLANNING_DEVICE_KIND]
    return spec.hbm_gib


def param_count(cfg: LLMConfig) -> int:
    """Exact parameter count via jax.eval_shape of the real model init —
    trace-only, so a 1.5B count costs milliseconds and cannot drift from
    the model code the way a hand-maintained formula would."""
    from distributed_pytorch_tpu.models.gpt import LLM
    import jax.numpy as jnp

    model = LLM(cfg)
    dummy = jax.ShapeDtypeStruct((1, cfg.block_size), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    variables = jax.eval_shape(
        lambda r, x: model.init(
            {"params": r, "dropout": r}, x, x), rng, dummy)
    return sum(int(np.prod(l.shape))
               for l in jax.tree_util.tree_leaves(variables["params"]))


def _act_bytes_per_token_layer(cfg: LLMConfig, policy: str,
                               dtype_bytes: int = 2) -> float:
    """Backward-live activation bytes per token per layer under a remat
    policy ('none' | 'attn' | 'block').

    'none' keeps every matmul input: ln1 out (C), fused qkv
    (C + 2*nkv*hs), sdpa out (C), proj out (C), ln2 out (C), fc out
    (fc_out), gated hidden (up), mlp proj out (C) — the flash kernel keeps
    no O(T^2) probabilities, only the per-row lse (nh).  'attn' drops the
    attention internals (recomputed blockwise), keeping the block input +
    the MLP side. 'block' keeps only the block input; one layer's full set
    stays as the recompute peak (added by the caller once, not x L).

    MoE layers replace the single MLP's hidden activations with one set
    per expert actually COMPUTED per token: shared + top-k for
    scatter/grouped, shared + all routed for 'dense' (which evaluates
    every expert and masks) — plus the router logits. The dispatch
    gather/scatter buffers are a separate, batch-shaped term
    (_moe_dispatch_bytes)."""
    C, up = cfg.n_embd, cfg.up_dim
    nkv, hs, nh = cfg.n_kv_heads, cfg.head_size, cfg.n_head
    fc_out = 2 * up if cfg.non_linearity.lower() in ("swiglu", "glu") else up
    attn_part = C + (C + 2 * nkv * hs) + C + nh / dtype_bytes
    if cfg.moe:
        n_eff = cfg.n_shared + (cfg.n_routed if cfg.moe_impl == "dense"
                                else cfg.n_act_routed)
        mlp_part = C + n_eff * (fc_out + up + C) + cfg.n_routed
    else:
        mlp_part = C + fc_out + up + C
    full = C + attn_part + mlp_part
    if policy == "none":
        return full * dtype_bytes
    if policy == "attn":
        return (2 * C + mlp_part) * dtype_bytes
    return C * dtype_bytes  # 'block': residual stream input only


def _moe_dispatch_bytes(cfg: LLMConfig, tokens: int, ep: int,
                        dtype_bytes: int = 2) -> float:
    """Per-device bytes of the MoE dispatch buffers per layer (the token
    gather on the way in + the combined output on the way out, both live
    for backward).

    'scatter': the (E, cap, C) buffers shard (expert, data) over the mesh
    (models/mlp._expert_constraint), so each device holds
    capacity_factor * k * tokens / ep rows per side.
    'grouped': the tile-aligned packed buffer is per-DATA-shard tokens x
    (k + n_shared) rows (ops/grouped_matmul.py; its static size cannot
    shrink with ep — any shard could receive every assignment), one
    (P, C) gather + one (P, C) output. 'dense' dispatches via the combine
    einsum — no buffers."""
    if not cfg.moe or cfg.moe_impl == "dense":
        return 0.0
    C = cfg.n_embd
    if cfg.moe_impl == "scatter":
        rows = cfg.capacity_factor * cfg.n_act_routed * tokens / max(ep, 1)
    else:  # grouped
        rows = (cfg.n_act_routed + cfg.n_shared) * tokens
    return rows * 2 * C * dtype_bytes * cfg.n_layer


@dataclasses.dataclass(frozen=True)
class HBMPlan:
    preset: str
    recipe: str
    micro_batch: int          # per-data-shard sequences (TrainConfig.batch_size)
    grad_accum: int
    act_recomp: bool
    act_recomp_policy: str    # 'block' | 'attn' (meaningful when act_recomp)
    est_peak_gb: float
    hbm_gb: float
    fits: bool
    breakdown_gb: dict

    def summary(self) -> str:
        pol = self.act_recomp_policy if self.act_recomp else "none"
        fit = "fits" if self.fits else "DOES NOT FIT"
        b = ", ".join(f"{k} {v:.2f}" for k, v in self.breakdown_gb.items())
        return (f"[hbm plan] {self.preset}/{self.recipe}: micro_batch="
                f"{self.micro_batch} grad_accum={self.grad_accum} "
                f"remat={pol} | est peak {self.est_peak_gb:.2f} GiB of "
                f"{self.hbm_gb:.0f} GiB ({fit}) | {b}")


def _expert_param_count(cfg: LLMConfig) -> int:
    """Parameters in the stacked (n_exp, ...) expert leaves — the slice of
    the model the 'expert' mesh axis shards (parallel/sharding.py expert
    rule), on top of whatever the recipe's data sharding does."""
    if not cfg.moe:
        return 0
    fc_out = 2 * cfg.up_dim \
        if cfg.non_linearity.lower() in ("swiglu", "glu") else cfg.up_dim
    per_expert = cfg.n_embd * fc_out + cfg.up_dim * cfg.n_embd
    return cfg.n_layer * cfg.n_exp * per_expert


# host<->device link bandwidth for the offload PCIe cost line (GiB/s per
# chip; v5e PCIe gen3 x16 effective — conservative, like _FUDGE)
_PCIE_GBPS = 16.0


def estimate_peak_gb(cfg: LLMConfig, recipe: str, micro_batch: int,
                     policy: str, dp: int, sp: int = 1, ep: int = 1,
                     optimizer: str = "adamw",
                     n_params: Optional[int] = None,
                     offload: bool = False,
                     pipe: int = 1, tp: int = 1) -> tuple[float, dict]:
    """(est peak GiB per device, breakdown dict). `policy` in
    'none'|'attn'|'block'. `micro_batch` is per-data-shard sequences.
    `ep`: 'expert' mesh-axis size — stacked (E, ...) expert leaves (and
    their moments/accumulators) divide by it on top of the recipe's data
    sharding.

    `pipe`: 'pipe' mesh-axis size — each stage holds n_layer/pipe of the
    block params (and their grads/moments), so those divide by `pipe`;
    the embedding table does NOT (the worst stage keeps it, and tied
    lm_head means the first stage is that stage). Activations do NOT
    divide: under 1F1B a stage holds up to `pipe` in-flight microbatches
    of its n_layer/pipe layers, which cancels back to one full model's
    worth of per-microbatch activations.

    `tp`: 'model' mesh-axis size — the matmul weights (qkv/proj, MLP
    up/down; the _TP_TABLE rows in parallel/sharding.py) column/row-split
    over 'model', so the block params divide by `tp` on top of any pipe
    and data sharding; the embedding stays whole per model-shard.

    `offload` (ZeRO-Offload, train/offload.py) moves the optimizer
    moments to host RAM: the 'opt' HBM row goes to zero and two
    NOT-summed rows appear after the total (the `host_kv_tier`
    precedent): 'host_opt' — host-RAM GiB the moments + fp32 master
    params occupy per process — and 'pcie_gb_per_step' — the 8P-bytes
    per-step transfer bill (4P grads down + 4P params up, per-device
    share) that buys the HBM back."""
    P = n_params if n_params is not None else param_count(cfg)
    p_div = dp if recipe in _PARAM_SHARDED else 1
    o_div = dp if recipe in _OPT_SHARDED else 1
    g_div = dp if recipe in _GRAD_SHARDED else 1
    Pe = _expert_param_count(cfg) if ep > 1 else 0
    Pd = P - Pe  # dense (non-expert-stacked) params
    mdl_div = max(pipe, 1) * max(tp, 1)
    if mdl_div > 1:
        emb = cfg.vocab_size * cfg.n_embd
        Pd = (Pd - emb) / mdl_div + emb  # worst shard keeps the embedding

    def _split(div):
        return Pd / div + Pe / (div * ep * max(pipe, 1))

    params_b = _split(p_div) * 4
    opt_b = _split(o_div) * 4 * _OPT_MULT.get(optimizer, 2.0)
    grads_b = _split(g_div) * 4  # fp32 accumulator (train/step.py)

    T_local = cfg.block_size // max(sp, 1)
    tokens = micro_batch * T_local
    act_b = tokens * cfg.n_layer * _act_bytes_per_token_layer(cfg, policy)
    if policy == "block":
        # recompute peak: one layer's full activation set lives during its
        # backward segment
        act_b += tokens * _act_bytes_per_token_layer(cfg, "none")
    # embedding output + final-LN + rope residuals, bf16
    act_b += tokens * cfg.n_embd * 2 * 3
    # fused-CE: ONE fp32 logits chunk (its gradients are taken in the scan
    # step that built it, ops/losses.py) beside the dW accumulator and the
    # per-chunk dW it adds, both in the compute dtype
    chunk = cfg.loss_chunk or min(128, cfg.block_size)
    loss_b = (micro_batch * chunk * cfg.vocab_size * 4
              + 2 * cfg.vocab_size * cfg.n_embd * 2)
    # the ZeRO-3 gather working set: with OVERLAP rings or GSPMD streaming
    # gathers, roughly the largest layer's full params in compute dtype
    # live at once; with hoisted gathers (grad accum) the whole model does.
    if recipe in _PARAM_SHARDED:
        per_layer = (P - cfg.vocab_size * cfg.n_embd) / max(cfg.n_layer, 1)
        gather_b = max(per_layer, cfg.vocab_size * cfg.n_embd) * 2 * 2
    else:
        gather_b = 0.0

    breakdown = {
        "params": params_b / 2 ** 30,
        "opt": 0.0 if offload else opt_b / 2 ** 30,
        "grads": grads_b / 2 ** 30,
        "acts": act_b / 2 ** 30,
        "loss": loss_b / 2 ** 30,
        "gather": gather_b / 2 ** 30,
    }
    if cfg.moe:
        breakdown["moe_dispatch"] = _moe_dispatch_bytes(
            cfg, tokens, ep) / 2 ** 30
    total = sum(breakdown.values()) * _FUDGE
    if offload:
        # host rows are reported AFTER total — host RAM and PCIe time,
        # never HBM (the estimate_serving_gb host_kv_tier precedent)
        breakdown["host_opt"] = (opt_b + _split(o_div) * 4) / 2 ** 30
        breakdown["pcie_gb_per_step"] = _split(g_div) * 8 / 2 ** 30
        breakdown["pcie_s_per_step"] = (
            breakdown["pcie_gb_per_step"] / _PCIE_GBPS)
    return total, {k: round(v, 3) for k, v in breakdown.items()}


def estimate_serving_gb(model_cfg: LLMConfig, n_slots: int, max_len: int, *,
                        cache_dtype_size: int = 2,
                        quantize_weights: bool = False,
                        compute_dtype_size: int = 2,
                        n_params: Optional[int] = None,
                        n_slots_acts: Optional[int] = None,
                        host_tier_blocks: int = 0,
                        host_tier_block_size: int = 16
                        ) -> tuple[float, dict]:
    """Serving-memory estimate for one chip running the DecodeEngine:
    the bf16 serving weights (prefill always needs them), the int8 decode
    copy + its per-output-channel f32 scales when `quantize_weights`, the
    (n_slots, max_len) KV cache at its true itemsize (+ the f32 scale
    sidecars for an int8 cache, cache_dtype_size=1), and a small
    activation term — so slot counts can be planned per chip instead of
    OOM-bisected on hardware. `host_tier_blocks` adds a 'host_kv_tier'
    breakdown row pricing the host-RAM KV tier (ops/kv_tier.py) at the
    same bytes-per-block as the pool — reported so the tier budget is
    sized from host RAM, but NEVER summed into the HBM total. Closed-form
    + jax.eval_shape only, like plan_memory."""
    from distributed_pytorch_tpu.train import metrics as M

    P = n_params if n_params is not None else param_count(model_cfg)
    weights_b = P * compute_dtype_size
    quant_b = 0.0
    if quantize_weights:
        quant_b = (M.quantized_matmul_params_per_token(model_cfg)
                   + M.quantized_matmul_out_channels(model_cfg) * 4)
    cache_b = n_slots * max_len * M.kv_bytes_per_token(
        model_cfg, cache_dtype_size, kv_scales=cache_dtype_size == 1)
    # decode activations: a few (n_slots, C) residual/qkv rows per layer
    # plus one (n_slots, vocab) logits buffer — tiny next to the above.
    # `n_slots_acts` decouples this from the cache term so the paged
    # block planner can price weights+acts with a zero-slot cache.
    ns = n_slots_acts if n_slots_acts is not None else n_slots
    act_b = (ns * model_cfg.n_embd * 8 * model_cfg.n_layer * 2
             + ns * model_cfg.vocab_size * 4)
    breakdown = {
        "weights": weights_b / 2 ** 30,
        "quant_weights": quant_b / 2 ** 30,
        "kv_cache": cache_b / 2 ** 30,
        "acts": act_b / 2 ** 30,
    }
    # total sums HBM terms only — the host tier row is added after
    total = sum(breakdown.values()) * _FUDGE
    if host_tier_blocks:
        breakdown["host_kv_tier"] = (
            host_tier_blocks * host_tier_block_size
            * M.kv_bytes_per_token(model_cfg, cache_dtype_size,
                                   kv_scales=cache_dtype_size == 1)
            / 2 ** 30)
    return total, {k: round(v, 3) for k, v in breakdown.items()}


def host_tier_blocks_for_gb(model_cfg: LLMConfig, gb: float, *,
                            block_size: int = 16,
                            cache_dtype_size: int = 2) -> int:
    """Price a `--kv-host-gb` budget into whole KV blocks with the same
    bytes-per-token model the HBM pool planner uses (f32 scale sidecars
    included for an int8 cache) — the number the serve CLI feeds the
    engine as its host-tier budget (KV_HOST_BLOCKS)."""
    from distributed_pytorch_tpu.train import metrics as M

    block_b = block_size * M.kv_bytes_per_token(
        model_cfg, cache_dtype_size, kv_scales=cache_dtype_size == 1)
    return max(0, int(gb * 2 ** 30 // block_b))


def plan_decode_blocks(model_cfg: LLMConfig, max_len: int, *,
                       block_size: int = 16,
                       hbm_gb: Optional[float] = None,
                       cache_dtype_size: int = 2,
                       quantize_weights: bool = False,
                       n_slots_hint: Optional[int] = None,
                       max_blocks: int = 2 ** 20,
                       host_tier_blocks: int = 0,
                       verbose: bool = False) -> int:
    """Block-budget planner for the PAGED decode engine: how many KV
    blocks of `block_size` rows fit the per-chip HBM after the serving
    weights (+ the int8 decode copy) and a slot-count-shaped activation
    term. The paged pool prices MEAN sequence length instead of the slot
    cache's worst case, so this is the number the engine's `n_blocks`
    knob should get; `n_slots_hint` (default: pool rows / max_len, i.e.
    worst-case sequences) only sizes the small activation estimate.
    Returns 0 when the weights alone don't fit — the model needs
    sharding. `verbose` prints the HBM-vs-host cache split when a
    host-RAM tier rides behind the pool (`host_tier_blocks`,
    ops/kv_tier.py), so an over-HBM bench pool is priced, not guessed.
    Closed-form + jax.eval_shape only, like plan_memory."""
    from distributed_pytorch_tpu.train import metrics as M

    budget_b = (hbm_gb if hbm_gb is not None else device_hbm_gb()) * 2 ** 30
    n_params = param_count(model_cfg)
    block_b = block_size * M.kv_bytes_per_token(
        model_cfg, cache_dtype_size, kv_scales=cache_dtype_size == 1)

    def fits(n_blocks: int) -> bool:
        slots = n_slots_hint or max(1, n_blocks * block_size // max_len)
        est, _ = estimate_serving_gb(
            model_cfg, 0, max_len, cache_dtype_size=cache_dtype_size,
            quantize_weights=quantize_weights, n_params=n_params,
            n_slots_acts=slots)
        return est * 2 ** 30 + block_b * n_blocks * _FUDGE <= budget_b

    if not fits(1):
        return 0
    lo, hi = 1, 2
    while hi <= max_blocks and fits(hi):
        lo, hi = hi, hi * 2
    hi = min(hi, max_blocks)
    while lo + 1 < hi:                      # bisect the last doubling
        mid = (lo + hi + 1) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    if verbose:
        hbm_cache_gb = block_b * lo / 2 ** 30
        host_gb = block_b * host_tier_blocks / 2 ** 30
        eff = (lo + host_tier_blocks) / lo
        print(f"[kv plan] pool {lo} blocks ({hbm_cache_gb:.2f} GiB HBM)"
              f" + host tier {host_tier_blocks} blocks"
              f" ({host_gb:.2f} GiB host RAM)"
              f" = {lo + host_tier_blocks} cacheable blocks"
              f" ({eff:.1f}x HBM)")
    return lo


def plan_decode_slots(model_cfg: LLMConfig, max_len: int, *,
                      hbm_gb: Optional[float] = None,
                      cache_dtype_size: int = 2,
                      quantize_weights: bool = False,
                      block_size: int = 16,
                      max_slots: int = 4096) -> int:
    """Largest power-of-two count of WORST-CASE (max_len) sequences the
    block budget covers (0 when even one doesn't fit — the model needs
    sharding). Since the paged rewrite this derives from
    `plan_decode_blocks`: slots x (max_len / block_size) blocks is the
    slot-cache-equivalent pool the engine defaults to; real traffic with
    shorter/shared sequences packs more concurrency into the same pool.
    int8 knobs roughly double the answer — the point of quantized
    serving."""
    n_blocks = plan_decode_blocks(
        model_cfg, max_len, block_size=block_size, hbm_gb=hbm_gb,
        cache_dtype_size=cache_dtype_size, quantize_weights=quantize_weights)
    per_seq = max_len // block_size
    best = 0
    n = 1
    while n <= max_slots and n * per_seq <= n_blocks:
        best = n
        n *= 2
    return best


def predicted_train_peak_gb(model_cfg: LLMConfig, train_cfg: TrainConfig,
                            mesh_sizes: Optional[dict] = None,
                            offload: bool = False) -> tuple[float, dict]:
    """Predicted per-device peak for the run configuration ACTUALLY in
    flight (not the planner's pick): the micro-batch / remat policy /
    recipe the loop is about to compile, priced by estimate_peak_gb.
    `mesh_sizes` is the loop's {axis: size} dict (data/seq/expert axes
    read, missing = 1). This is the "predicted" half of the
    watermark-vs-memplan delta the ROADMAP validation item needs."""
    sizes = mesh_sizes or {}
    policy = model_cfg.act_recomp_policy if model_cfg.act_recomp else "none"
    return estimate_peak_gb(
        model_cfg, train_cfg.parallelism, train_cfg.batch_size, policy,
        dp=sizes.get("data", 1), sp=sizes.get("seq", 1),
        ep=sizes.get("expert", 1), optimizer=train_cfg.optimizer,
        offload=offload)


def watermark_report(predicted_gb: Optional[float]) -> list[dict]:
    """Per-device `{device, memplan_predicted_gb, measured_peak_gb,
    delta}` rows from the live watermark (metrics.hbm_watermark's
    `peak_bytes`: in use + reserved) — the record stats.json carries so
    a chip run validates the planner constants without re-running
    anything.
    Keys are always present; values are None where the backend reports
    no memory stats (CPU) so the schema is stable across backends."""
    from distributed_pytorch_tpu.train.metrics import hbm_watermark

    rows = []
    for d in hbm_watermark():
        peak = d.get("peak_bytes")
        measured = round(peak / 2 ** 30, 3) if peak else None
        delta = round(measured - predicted_gb, 3) \
            if (measured is not None and predicted_gb is not None) else None
        rows.append({"device": d["device"],
                     "memplan_predicted_gb":
                         round(predicted_gb, 3)
                         if predicted_gb is not None else None,
                     "measured_peak_gb": measured,
                     "delta": delta})
    return rows


def plan_memory(model_cfg: LLMConfig, train_cfg: TrainConfig, *,
                n_devices: Optional[int] = None,
                hbm_gb: Optional[float] = None,
                preset_name: str = "custom",
                offload: bool = False) -> HBMPlan:
    """Pick (micro_batch, remat policy, grad_accum) for the config under
    the recipe's sharding and the per-chip HBM budget.

    Candidates are scored by a throughput proxy — micro-batch size divided
    by the policy's FLOP multiplier (none 1.0, attn ~1.1, block 4/3) — so
    a bigger batch only wins if its extra remat FLOPs don't eat the gain.
    Falls back to the smallest-batch/block-remat candidate (marked
    fits=False) when nothing fits, so callers always get arithmetic that
    satisfies the grad-accum divisibility contract (train/loop.py)."""
    from distributed_pytorch_tpu.parallel.mesh import resolve_plan

    recipe = train_cfg.parallelism
    if n_devices is None:
        n_devices = len(jax.devices())
    plan = resolve_plan(recipe, n_devices, tp_size=train_cfg.tp_size,
                        ep_size=train_cfg.ep_size, sp_size=train_cfg.sp_size,
                        pp_size=train_cfg.pp_size, dp_size=train_cfg.dp_size)
    dp, sp, ep = plan.data, plan.seq, plan.expert
    pipe, tp = plan.pipe, plan.model
    budget = hbm_gb if hbm_gb is not None else device_hbm_gb()
    n_params = param_count(model_cfg)
    T = model_cfg.block_size

    flop_mult = {"none": 1.0, "attn": 1.1, "block": 4.0 / 3.0}
    best = None       # (score, plan)
    fallback = None   # smallest candidate even if over budget
    for mb in (64, 32, 16, 8, 4, 2, 1):
        tokens_per_micro = mb * dp * T
        if train_cfg.total_batch_size % tokens_per_micro != 0:
            continue
        accum = train_cfg.total_batch_size // tokens_per_micro
        for policy in ("none", "attn", "block"):
            est, breakdown = estimate_peak_gb(
                model_cfg, recipe, mb, policy, dp, sp, ep,
                optimizer=train_cfg.optimizer, n_params=n_params,
                offload=offload, pipe=pipe, tp=tp)
            cand = HBMPlan(
                preset=preset_name, recipe=recipe, micro_batch=mb,
                grad_accum=accum, act_recomp=policy != "none",
                act_recomp_policy=policy if policy != "none" else "attn",
                est_peak_gb=round(est, 3), hbm_gb=budget,
                fits=est <= budget - _RUNTIME_RESERVE_GB,
                breakdown_gb=breakdown)
            if cand.fits:
                score = mb / flop_mult[policy]
                if best is None or score > best[0]:
                    best = (score, cand)
            fallback = cand  # last = smallest batch, heaviest remat
    if best is not None:
        return best[1]
    if fallback is None:
        raise ValueError(
            f"total_batch_size {train_cfg.total_batch_size} admits no "
            f"micro-batch with dp={dp}, T={T} (need divisibility by "
            f"micro_batch*dp*T)")
    return fallback


def _main(argv: Optional[list] = None) -> int:
    """`python -m distributed_pytorch_tpu.train.memplan --preset gpt2_7b
    --offload`: price a preset/recipe against a per-chip HBM budget,
    device-free. Exits non-zero when the plan does not fit — the loud
    failure the 7B rung relies on with offload off."""
    import argparse
    import json as _json

    from distributed_pytorch_tpu.config import PRESETS, TrainConfig as TC

    ap = argparse.ArgumentParser(
        description="static HBM planner (closed-form, no compile)")
    ap.add_argument("--preset", default="gpt2_7b", choices=sorted(PRESETS))
    ap.add_argument("--recipe", default="fsdp")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--pp-size", type=int, default=1,
                    help="pipe mesh-axis size (the pp recipe prices "
                         "pipe=1 — all params on every chip — without it)")
    ap.add_argument("--tp-size", type=int, default=1)
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="per-chip budget (default: detected, 16 on CPU)")
    ap.add_argument("--offload", action="store_true",
                    help="price with the optimizer moments in host RAM")
    ap.add_argument("--total-batch-size", type=int, default=None)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    cfg = PRESETS[args.preset]()
    tbs = args.total_batch_size or (args.devices * cfg.block_size * 8)
    tc = TC(batch_size=1, total_batch_size=tbs, max_iters=1,
            parallelism=args.recipe, warmup_steps=0,
            pp_size=args.pp_size, tp_size=args.tp_size)
    plan = plan_memory(cfg, tc, n_devices=args.devices,
                       hbm_gb=args.hbm_gb, preset_name=args.preset,
                       offload=args.offload)
    if args.json:
        print(_json.dumps({**dataclasses.asdict(plan),
                           "offload": args.offload}, indent=2))
    else:
        print(plan.summary())
        if args.offload:
            base = plan_memory(cfg, tc, n_devices=args.devices,
                               hbm_gb=args.hbm_gb, preset_name=args.preset,
                               offload=False)
            delta = base.est_peak_gb - plan.est_peak_gb
            bd = plan.breakdown_gb
            print(f"[offload] HBM delta vs in-HBM moments: "
                  f"{-delta:+.2f} GiB/chip (in-HBM plan "
                  f"{base.est_peak_gb:.2f} GiB, "
                  f"{'fits' if base.fits else 'DOES NOT FIT'}) | "
                  f"host_opt {bd.get('host_opt', 0.0):.2f} GiB RAM, "
                  f"pcie {bd.get('pcie_gb_per_step', 0.0):.2f} GiB/step "
                  f"(~{bd.get('pcie_s_per_step', 0.0):.3f} s at "
                  f"{_PCIE_GBPS:.0f} GiB/s)")
    if not plan.fits:
        print(f"[memplan] FAIL: {args.preset}/{args.recipe} does not fit "
              f"{plan.hbm_gb:.0f} GiB/chip"
              + ("" if args.offload else
                 " — retry with --offload (ZeRO-Offload host optimizer)"))
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
