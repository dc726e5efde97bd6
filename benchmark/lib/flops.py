"""Operations and bytes the algorithm needs, from shapes alone.

`cfg` is the `llm_config` mapping of a configuration file (the keyword
arguments of the program's LLMConfig), so these functions need nothing
from the program. Dense GPT-2-class models only (mha/gqa/mqa attention, one
ungated or gated FFN per layer, tied head); a configuration with experts or
latent attention brings its own functions in a file of its own.
"""

from __future__ import annotations

_GATED = ("swiglu", "glu")


def _n_kv_heads(cfg: dict) -> int:
    attn = cfg.get("attn", "gqa")
    if attn == "mha":
        return cfg["n_head"]
    if attn == "mqa":
        return 1
    if attn == "gqa":
        return cfg["n_kv_heads"]
    raise ValueError(f"no FLOPs model for attention kind {attn!r}")


def matmul_params_per_token(cfg: dict) -> int:
    """Matmul parameters one token touches in a forward pass: qkv and
    output projections, the FFN, and the tied head (the embedding lookup is
    not a matmul)."""
    if cfg.get("moe"):
        raise ValueError("no FLOPs model for expert layers here")
    C, nh = cfg["n_embd"], cfg["n_head"]
    hs = C // nh
    attn = C * (C + 2 * _n_kv_heads(cfg) * hs) + C * C
    up = cfg["up_dim"]
    fc_out = 2 * up if cfg["non_linearity"].lower() in _GATED else up
    ffn = C * fc_out + up * C
    return cfg["n_layer"] * (attn + ffn) + cfg["vocab_size"] * C


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token, forward + backward (3x forward), with NO
    recomputation counted: 2 per matmul parameter, plus causal attention
    scores and values, 4*T*C per layer halved for causality."""
    score = cfg["n_layer"] * 2 * cfg["n_embd"] * seq_len
    return 3.0 * (2 * matmul_params_per_token(cfg) + score)


def mfu(cfg: dict, seq_len: int, tokens_per_s_per_chip: float,
        peak_flops: float) -> float:
    """Model FLOP/s utilisation: a share of the chip's bf16 peak."""
    return model_flops_per_token(cfg, seq_len) * tokens_per_s_per_chip \
        / peak_flops


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of key+value cache one token holds over all layers."""
    hs = cfg["n_embd"] // cfg["n_head"]
    return cfg["n_layer"] * 2 * _n_kv_heads(cfg) * hs * itemsize


def paged_decode_bytes(cfg: dict, live_lengths, itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel has to read in ONE engine step,
    all layers: every live sequence's valid rows of keys and values, once.
    Queries, outputs and block tables are noise beside it and left out, so
    the roofline share this feeds is a lower bound on the bytes."""
    return int(sum(live_lengths)) * kv_bytes_per_token(cfg, itemsize)


def paged_decode_bytes_per_call(cfg: dict, live_lengths,
                                itemsize: int = 2) -> float:
    """The same for ONE call of the kernel, which serves one layer."""
    return paged_decode_bytes(cfg, live_lengths, itemsize) / cfg["n_layer"]
