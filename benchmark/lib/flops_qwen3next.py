"""Operations and bytes of the Qwen3-Next-80B-A3B-Instruct configuration,
from shapes alone: a patterned model that mixes the gated delta rule with a
decay a head ('G': a float32 state of (value heads, d, d) and a convolution
tail a SLOT, whatever the context) with gated full attention over block
pools ('*': heads of 256 lanes, a gate a channel from the query's own
projection), every mixer in front of softmax-routed gated experts beside one
GATED shared expert ('E'), zero-centred norms, a head of its own. `cfg` is
the configuration file's `llm_config`. The expert kernels' floors are the
accepted files' own functions."""

from __future__ import annotations

from benchmark.lib.flops_laguna import (_head, _held,  # noqa: F401
                                        expert_down_bytes_per_call,
                                        expert_down_elems,
                                        expert_up_bytes_per_call,
                                        expert_up_elems, kv_bytes_per_row,
                                        shared_params)


def _gdn(cfg: dict) -> tuple:
    """(value heads, key heads, head size, taps, key width, value width)."""
    H, Hk, d = cfg["gdn_heads"], cfg["gdn_key_heads"], cfg["gdn_head_dim"]
    return H, Hk, d, cfg.get("gdn_conv", 4), Hk * d, H * d


def gdn_params(cfg: dict) -> dict:
    """Parameters of a 'G' block's mixer, leaf by leaf."""
    C = cfg["n_embd"]
    H, _, d, K, Dk, Dv = _gdn(cfg)
    return {"W_qkvz": C * (2 * Dk + 2 * Dv), "W_ba": C * 2 * H,
            "conv_w": K * (2 * Dk + Dv), "A_log": H, "dt_bias": H,
            "o_norm": d, "W_o": Dv * C}


def attn_params(cfg: dict) -> dict:
    """Parameters of a '*' block's gated attention: the gate a channel
    doubles the query's columns of the one projection."""
    assert cfg.get("attn_gate") == "channel" and cfg.get("qk_norm")
    C = cfg["n_embd"]
    hs, kvw = _head(cfg)
    qw = cfg["n_head"] * hs
    return {"c_attn": C * (2 * qw + 2 * kvw), "c_proj": qw * C,
            "q_norm": hs, "k_norm": hs}


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind` as this chip holds it, the block's
    norm included."""
    C = cfg["n_embd"]
    if kind == "G":
        return sum(gdn_params(cfg).values()) + C
    if kind == "*":
        return sum(attn_params(cfg).values()) + C
    assert kind == "E", kind
    n_routed, held = _held(cfg)
    return (held * (expert_up_elems(cfg) + expert_down_elems(cfg))
            + shared_params(cfg) + C * n_routed
            + (C if cfg.get("shared_gate") else 0) + C)


def total_params(cfg: dict) -> int:
    assert not cfg.get("tie_head", True), "the head is a matrix of its own"
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + 2 * cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def gdn_state_bytes(cfg: dict) -> int:
    """ONE 'G' layer's float32 state of one slot: value heads x d x d x 4."""
    return cfg["gdn_heads"] * cfg["gdn_head_dim"] ** 2 * 4


def gdn_tail_bytes(cfg: dict, itemsize: int = 2) -> int:
    """ONE 'G' layer's convolution tail of one slot."""
    _, _, _, K, Dk, Dv = _gdn(cfg)
    return (K - 1) * (2 * Dk + Dv) * itemsize


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps, by kind."""
    n_g = cfg["layer_pattern"].count("G")
    out = {"weights": total_params(cfg) * itemsize,
           "gdn_state": n_g * n_slots * gdn_state_bytes(cfg),
           "gdn_tails": n_g * n_slots * gdn_tail_bytes(cfg, itemsize),
           "kv_pools": cfg["layer_pattern"].count("*") * n_blocks
           * block_size * kv_bytes_per_row(cfg, itemsize)}
    out["total"] = sum(out.values())
    return out


def gdn_step_bytes_per_call(cfg: dict, live_slots: float) -> float:
    """Bytes ONE call of `kda_state_step` in a 'G' layer moves and cannot
    avoid as the rule stands: every live slot's float32 state read once
    AND written once (a decay a head leaves no row of the state untouched),
    its float32 operands in (exp(g), k, beta k, q a key channel and value
    head; v) and its output out."""
    H, _, d, _, _, _ = _gdn(cfg)
    return live_slots * (2 * gdn_state_bytes(cfg) + (4 + 2) * H * d * 4)


def gdn_chunk_bytes_per_call(cfg: dict, rows: float,
                             itemsize: int = 2) -> float:
    """Bytes ONE chunk call of the gated delta rule (one 'G' layer) cannot
    avoid: the chunk's REAL rows of [q' | k' | v'] read once and its output
    rows written once (compute dtype), the rows' b and a (float32), the
    slot's float32 state read once and written once. The float32
    intermediates of the WY form and whatever XLA spills are not in the
    floor."""
    H, _, _, _, Dk, Dv = _gdn(cfg)
    return rows * ((2 * Dk + 2 * Dv) * itemsize + 2 * H * 4) \
        + 2 * gdn_state_bytes(cfg)


def paged_decode_bytes_per_call(cfg: dict, live_rows: float,
                                itemsize: int = 2) -> float:
    """Cache bytes ONE call of `paged_flash_decode` (one '*' layer) must
    read: every live sequence's valid rows of keys and values, once (2 x
    512 lanes: 2,048 B a row in bf16). The kernel fetches whole tiles of
    `block_size` rows, a sequence's last one part dead: a lower bound."""
    return live_rows * kv_bytes_per_row(cfg, itemsize)


def chunk_attention_ops(cfg: dict, pairs: float) -> float:
    """Multiply-adds x 2 ONE call of `paged_flash_prefill` must make: every
    (query row, key) pair the causal mask lets through, of a chunk's REAL
    rows, in each of the 16 heads of 256 lanes, scores and p @ v. The
    kernel computes whole tiles, pad rows and the masked half of the
    diagonal included: a lower bound."""
    return 2.0 * 2.0 * cfg["n_head"] * _head(cfg)[0] * pairs


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner. `experts_hit` is a
    layer's; `live_rows` the live sequences' rows."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "G*E"}
    n_routed, _ = _held(cfg)
    out = {"gdn_state": n["G"] * n_slots * 2 * gdn_state_bytes(cfg),
           "gdn_weights": n["G"] * sum(gdn_params(cfg).values()) * itemsize,
           "attention_rows": n["*"] * live_rows
           * kv_bytes_per_row(cfg, itemsize),
           "attention_weights": n["*"] * sum(attn_params(cfg).values())
           * itemsize,
           "experts": n["E"] * experts_hit * (expert_up_elems(cfg)
                                              + expert_down_elems(cfg))
           * itemsize,
           "routers_shared": n["E"] * (C * n_routed + shared_params(cfg))
           * itemsize,
           "head": cfg["vocab_size"] * C * itemsize}
    out["total"] = sum(out.values())
    return out
