"""Throughput + MFU accounting.

The reference instruments only wall-clock ms/step and reserved GPU memory
(single-gpu/train.py:354-359); BASELINE.json's metrics are tokens/sec/chip
and MFU, so this framework computes them natively. MFU is measured honestly
for MoE (only *active* experts count — SURVEY.md §7 hard part (e)) and MLA
(the latent down/up projections are counted as the matmuls actually run).

Model FLOPs: for every matmul with an (in, out) kernel touched by a token,
forward costs 2*in*out FLOPs/token; backward 2x forward; activation
recomputation adds one more forward (factor 4/3). Attention scores+values
add 4*T*C per token per layer, halved for causality. The weight-tied
lm_head matmul (vocab_size*n_embd) is counted; the embedding *lookup* is
not a matmul and is excluded.
"""

from __future__ import annotations

import dataclasses

import jax

from distributed_pytorch_tpu.config import LLMConfig

@dataclasses.dataclass(frozen=True)
class ChipSpec:
    peak_flops: float      # dense bf16 FLOP/s per chip
    hbm_bw: float          # HBM bytes/s per chip
    hbm_gib: float         # HBM capacity per chip


# Published per-chip peaks (Google Cloud TPU documentation, one page per
# generation), keyed by the EXACT `jax.devices()[0].device_kind` string —
# the strings below are what this installation's libtpu reports for each
# generation's topology. Exact keys, not substrings: the v5e chip reports
# "TPU v5 lite" and v5p reports "TPU v5", so substring order used to decide
# which row a device got, and a kind without its letter fell through to
# the v5e row. v2/v3 are absent on purpose: there a jax device is one CORE
# of a two-core chip, so a per-chip peak would be wrong per device.
CHIP_SPECS = {
    "TPU v5 lite": ChipSpec(197e12, 8.19e11, 16.0),     # v5e
    "TPU v5": ChipSpec(459e12, 2.765e12, 95.0),         # v5p
    "TPU v6 lite": ChipSpec(918e12, 1.64e12, 32.0),     # v6e (Trillium)
    "TPU v4": ChipSpec(275e12, 1.228e12, 32.0),
}


def chip_spec() -> ChipSpec | None:
    """The attached accelerator's published peaks. None on the CPU backend
    (no peak: MFU / MBU are not computed there). An accelerator whose
    device_kind is not in the table is an ERROR, not a default — a wrong
    peak makes every utilization downstream wrong without a sign of it."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in CHIP_SPECS:
        raise KeyError(
            f"device_kind {dev.device_kind!r} (platform {dev.platform!r}) "
            "is not in train/metrics.CHIP_SPECS; add its published peaks "
            f"there, keyed by that exact string (known: "
            f"{sorted(CHIP_SPECS)})")
    return CHIP_SPECS[dev.device_kind]


def peak_flops_per_chip() -> float | None:
    spec = chip_spec()
    return spec.peak_flops if spec else None


def peak_hbm_bw_per_chip() -> float | None:
    """Decode is memory-bound, so its utilization metric is MBU
    (memory-bandwidth utilization), not MFU."""
    spec = chip_spec()
    return spec.hbm_bw if spec else None


def kv_bytes_per_token(cfg: LLMConfig, cache_dtype_size: int = 2, *,
                       kv_scales: bool = False) -> int:
    """Bytes of KV cache one token occupies across all layers (GQA: 2
    (k+v) * n_kv heads * head_size; MLA: the compressed latent [+ the
    shared rotary key head]). `kv_scales` adds the int8 cache's f32
    per-(row, kv-head) scale sidecars (ops/quant.py) so the int8 bytes
    model is honest: ~ (hs + 4) / 2*hs of the bf16 bytes, not exactly
    half."""
    if cfg.attn in ("mha", "mqa", "gqa"):
        row = 2 * cfg.n_kv_heads * cfg.head_size * cache_dtype_size
        if kv_scales:
            row += 2 * cfg.n_kv_heads * 4
    else:
        row = (cfg.kv_latent_dim + (cfg.rope_head_dim
                                    if cfg.pos_emb == "rope" else 0)
               ) * cache_dtype_size
    return cfg.n_layer * row


def decode_step_bytes(cfg: LLMConfig, batch: int, cache_len: int,
                      param_dtype_size: int = 2,
                      cache_dtype_size: int = 2, *,
                      quant_weights: bool = False,
                      kv_scales: bool | None = None) -> int:
    """Bytes-moved model for ONE batched decode step: every matmul
    parameter is read once (decode is weight-bandwidth-bound; the batch
    amortizes this read — why the engine batches ragged slots), each
    sequence's valid KV rows are read once, and one new row is written.
    Activations (B rows of C floats) are noise and excluded. Divide by
    (step time x peak_hbm_bw_per_chip) for MBU.

    True per-tensor itemsizes for every dtype mix: `cache_dtype_size=1`
    defaults `kv_scales` on (the int8 cache always carries its f32 scale
    sidecars); `quant_weights` prices the weight-only-int8 store — the
    quantized matmuls read 1-byte codes plus their f32 per-output-channel
    scale vectors, anything the store excludes (MoE expert stacks, the
    router) stays at `param_dtype_size`."""
    if kv_scales is None:
        kv_scales = cache_dtype_size == 1
    if quant_weights:
        qp = quantized_matmul_params_per_token(cfg)
        rest = matmul_params_per_token(cfg) - qp
        params = (qp + quantized_matmul_out_channels(cfg) * 4
                  + rest * param_dtype_size)
    else:
        params = matmul_params_per_token(cfg) * param_dtype_size
    kv = batch * (cache_len + 1) * kv_bytes_per_token(
        cfg, cache_dtype_size, kv_scales=kv_scales)
    return params + kv


def attn_matmul_params_per_token(cfg: LLMConfig) -> int:
    """Matmul parameters of the attention sublayer per token (per ALL
    layers) — the recompute cost of the attention-only remat policy."""
    C, hs, nh, nkvh = cfg.n_embd, cfg.head_size, cfg.n_head, cfg.n_kv_heads
    if cfg.attn in ("mha", "mqa", "gqa"):
        attn = C * (C + 2 * nkvh * hs) + C * C          # c_attn + c_proj
    else:  # mla
        nlq, nlkv = cfg.q_latent_dim, cfg.kv_latent_dim
        attn = (C * nlq + nlq * C                        # W_dq, W_uq
                + C * nlkv + 2 * nlkv * C                # W_dkv, W_uk, W_uv
                + C * C)                                 # W_o
        if cfg.pos_emb == "rope":
            attn += nlq * nh * cfg.rope_head_dim + C * cfg.rope_head_dim
    return cfg.n_layer * attn


def matmul_params_per_token(cfg: LLMConfig) -> int:
    """Active matmul parameters touched per token (MoE: shared + n_act_routed
    routed experts only; cf. reference get_num_params 'active' count,
    single-gpu/model.py:588-617)."""
    C = cfg.n_embd

    fc_out = 2 * cfg.up_dim if cfg.non_linearity.lower() in ("swiglu", "glu") \
        else cfg.up_dim
    one_mlp = C * fc_out + cfg.up_dim * C
    if cfg.moe:
        ffn = one_mlp * (cfg.n_shared + cfg.n_act_routed) \
            + C * cfg.n_routed                           # router
    else:
        ffn = one_mlp

    lm_head = cfg.vocab_size * C                         # weight-tied matmul
    return attn_matmul_params_per_token(cfg) \
        + cfg.n_layer * ffn + lm_head


def quantized_matmul_params_per_token(cfg: LLMConfig) -> int:
    """Matmul parameters the weight-only-int8 store covers
    (ops/quant.py quantize_params): everything matmul_params_per_token
    counts EXCEPT the stacked MoE expert kernels and the router, which
    stay bf16."""
    C = cfg.n_embd
    qp = attn_matmul_params_per_token(cfg) + cfg.vocab_size * C  # + lm head
    if not cfg.moe:
        fc_out = 2 * cfg.up_dim \
            if cfg.non_linearity.lower() in ("swiglu", "glu") else cfg.up_dim
        qp += cfg.n_layer * (C * fc_out + cfg.up_dim * C)
    return qp


def quantized_matmul_out_channels(cfg: LLMConfig) -> int:
    """Output channels across the quantized matmuls — each carries one f32
    scale, the sidecar bytes a decode step reads on top of the int8
    codes."""
    C, hs, nh, nkvh = cfg.n_embd, cfg.head_size, cfg.n_head, cfg.n_kv_heads
    if cfg.attn in ("mha", "mqa", "gqa"):
        attn = (C + 2 * nkvh * hs) + C                   # c_attn + c_proj
    else:
        nlq, nlkv = cfg.q_latent_dim, cfg.kv_latent_dim
        attn = nlq + C + nlkv + 2 * C + C                # W_dq..W_uv, W_o
        if cfg.pos_emb == "rope":
            attn += nh * cfg.rope_head_dim + cfg.rope_head_dim
    ch = cfg.n_layer * attn + cfg.vocab_size             # + lm-head rows
    if not cfg.moe:
        fc_out = 2 * cfg.up_dim \
            if cfg.non_linearity.lower() in ("swiglu", "glu") else cfg.up_dim
        ch += cfg.n_layer * (fc_out + C)
    return ch


def moe_overcompute_factor(cfg: LLMConfig) -> float:
    """Executed / useful expert-FFN FLOPs for the configured dispatch.

    MFU here always counts ACTIVE-expert FLOPs (useful work); this factor
    says how much the dispatch overspends to deliver them: 'dense' runs
    every routed expert on every token (n_routed / k), 'scatter' pads each
    expert to capacity (~capacity_factor, load-dependent), 'grouped'
    streams packed tokens (~1.0, tile-rounding only). The bench/sweep MoE
    legs print it next to MFU so a dense-dispatch MFU number can't
    masquerade as kernel efficiency."""
    if not cfg.moe:
        return 1.0
    active = cfg.n_shared + cfg.n_act_routed
    if cfg.moe_impl == "dense":
        return (cfg.n_shared + cfg.n_routed) / active
    if cfg.moe_impl == "scatter":
        # capacity slots are computed whether filled or not; with a
        # balanced router utilization -> 1/capacity_factor
        return (cfg.n_shared + cfg.capacity_factor * cfg.n_act_routed) \
            / active
    return 1.0  # grouped: dropless AND packed


def step_flops(cfg: LLMConfig, tokens_per_step: int, seq_len: int) -> float:
    """Total train-step FLOPs (fwd + bwd [+ remat fwd]).

    Remat accounting is policy-aware: 'block' re-runs the whole forward
    (x4/3); 'attn' re-runs only attention projections + scores — counting
    the full forward there would flatter MFU."""
    score_flops = cfg.n_layer * 2 * cfg.n_embd * seq_len  # causal: 4*T*C/2
    per_tok_fwd = 2 * matmul_params_per_token(cfg) + score_flops
    recompute = 0.0
    if cfg.act_recomp:
        if cfg.act_recomp_policy == "attn":
            recompute = 2 * attn_matmul_params_per_token(cfg) + score_flops
        else:
            recompute = per_tok_fwd
    return (3 * per_tok_fwd + recompute) * tokens_per_step


def mfu(cfg: LLMConfig, tokens_per_step: int, seq_len: int,
        step_time_s: float, n_chips: int) -> float | None:
    peak = peak_flops_per_chip()
    if peak is None or step_time_s <= 0:
        return None
    achieved = step_flops(cfg, tokens_per_step, seq_len) / step_time_s
    return achieved / (peak * n_chips)


def _peak_bytes(st: dict) -> int | None:
    """Peak device memory out of one `memory_stats()` dict. This runtime
    (libtpu 0.0.34) keeps a compiled program's temporaries in a RESERVED
    region that `peak_bytes_in_use` does not count: the 124M step at
    16x1024 on a v5e read peak_bytes_in_use = 1.52 GB (the state and the
    batch) beside peak_bytes_reserved = 14.01 GB, and their sum is the
    compiler's own argument + temp bytes within 1% (PERF.md, PR 21). The
    sum is what the chip really held."""
    peak = st.get("peak_bytes_in_use") or st.get("bytes_in_use")
    if not peak:
        return None
    return peak + (st.get("peak_bytes_reserved") or 0)


def hbm_watermark() -> list[dict]:
    """Per-LOCAL-device memory watermark: one dict per device with
    `peak_bytes` (in use + reserved, `_peak_bytes`), the raw
    `peak_bytes_in_use` / `peak_bytes_reserved` it is made of, and
    `bytes_in_use` (None-valued where the backend doesn't report
    memory_stats — CPU). The sampling half of the "validate
    train/memplan.py against the chip" item: the train loop probes this
    after init and at log boundaries, and memplan.watermark_report turns
    it into the predicted-vs-measured delta."""
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}       # the CPU backend reports None
        out.append({"device": f"{d.platform}:{d.id}",
                    "peak_bytes": _peak_bytes(st),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    "peak_bytes_reserved": st.get("peak_bytes_reserved"),
                    "bytes_in_use": st.get("bytes_in_use")})
    return out


def device_memory_gb() -> float | None:
    """Peak device-memory use in GiB on the first local device
    (`_peak_bytes`: in use + reserved), or None when the backend doesn't
    report it (CPU). The TPU equivalent of the reference's per-step
    `torch.cuda.memory_reserved()` print (single-gpu/train.py:356) — the
    number that justifies batch-size choices when chasing MFU."""
    b = _peak_bytes(jax.local_devices()[0].memory_stats() or {})
    return b / 2 ** 30 if b else None
