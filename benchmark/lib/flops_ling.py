"""Operations and bytes of the Ling-3.0-flash-VL configuration's language
model, from shapes alone: the neighbour of `flops_joyai.py` for a patterned
model that mixes delta-rule linear attention ('K': a float32 state of
(heads, d, d) and a convolution tail a SLOT, whatever the context) with
latent attention WITHOUT a query latent ('L': a pool of latent rows), in
front of a dense gated FFN block ('F') or group-limited sigmoid-routed gated
experts beside one shared expert ('E'), under a head of its own. `cfg` is
the configuration file's `llm_config`. The latent kernels' floors and the
expert kernels' are the accepted files' own functions."""

from __future__ import annotations

from benchmark.lib.flops_joyai import (chunk_attention_ops,  # noqa: F401
                                       latent_decode_bytes_per_call,
                                       latent_row_bytes,
                                       paged_decode_bytes_per_call,
                                       pool_row_bytes)
from benchmark.lib.flops_laguna import (_held, expert_down_bytes_per_call,  # noqa: F401
                                        expert_down_elems,
                                        expert_up_bytes_per_call,
                                        expert_up_elems, shared_params)


def kda_params(cfg: dict) -> dict:
    """Parameters of a 'K' block's mixer, leaf by leaf."""
    C, H, d, K = (cfg["n_embd"], cfg["kda_heads"], cfg["kda_head_dim"],
                  cfg.get("kda_conv", 4))
    D = H * d
    return {"W_qkv": C * 3 * D, "W_a": C * D, "W_bg": C * 2 * H,
            "W_o": D * C, "conv_w": K * 3 * D, "A_log": H, "dt_bias": D,
            "o_norm": d}


def latent_params(cfg: dict) -> dict:
    """Parameters of an 'L' block's latent attention with no query latent."""
    assert not cfg.get("q_latent_dim"), "this model's q is h W_q"
    C, nh = cfg["n_embd"], cfg["n_head"]
    dn, dr, dv, lc = (cfg["qk_nope_head_dim"], cfg["rope_head_dim"],
                      cfg["v_head_dim"], cfg["kv_latent_dim"])
    return {"W_q": C * nh * (dn + dr), "W_kva": C * (lc + dr),
            "W_kvb": lc * nh * (dn + dv), "W_o": nh * dv * C, "kv_norm": lc}


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind` as this chip holds it, the block's
    norm included; the router's selection bias (a float32 buffer,
    `gate_bias`) is not counted."""
    C = cfg["n_embd"]
    if kind == "K":
        return sum(kda_params(cfg).values()) + C
    if kind == "L":
        return sum(latent_params(cfg).values()) + C
    if kind == "F":
        return 3 * C * cfg["dense_up_dim"] + C
    assert kind == "E", kind
    n_routed, held = _held(cfg)
    return (held * (expert_up_elems(cfg) + expert_down_elems(cfg))
            + shared_params(cfg) + C * n_routed + C)


def total_params(cfg: dict) -> int:
    assert not cfg.get("tie_head", True), "the head is a matrix of its own"
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + 2 * cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def kda_state_bytes(cfg: dict) -> int:
    """ONE 'K' layer's float32 state of one slot: heads x d_k x d_v x 4."""
    return cfg["kda_heads"] * cfg["kda_head_dim"] ** 2 * 4


def kda_tail_bytes(cfg: dict, itemsize: int = 2) -> int:
    """ONE 'K' layer's convolution tail of one slot."""
    return (cfg.get("kda_conv", 4) - 1) * 3 * cfg["kda_heads"] \
        * cfg["kda_head_dim"] * itemsize


def kv_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Pool bytes of one cached row over all latent layers."""
    return cfg["layer_pattern"].count("L") * pool_row_bytes(cfg, itemsize)


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps, by kind."""
    n_k = cfg["layer_pattern"].count("K")
    out = {"weights": total_params(cfg) * itemsize,
           "kda_state": n_k * n_slots * kda_state_bytes(cfg),
           "kda_tails": n_k * n_slots * kda_tail_bytes(cfg, itemsize),
           "latent_pools": n_blocks * block_size
           * kv_bytes_per_row(cfg, itemsize)}
    out["total"] = sum(out.values())
    return out


def kda_step_bytes_per_call(cfg: dict, live_slots: float) -> float:
    """State bytes ONE call of `kda_state_step` (one 'K' layer) must READ:
    every live slot's float32 state, ONCE. The write is NOT in the floor: a
    form that folds pending rows into the state every k-th token writes
    less, and a floor that priced both would let such a change read over
    100%. A kernel that reads and writes the state every call (today's)
    therefore reads at most half of what its DMAs reach (~46 of 92)."""
    return live_slots * kda_state_bytes(cfg)


def kda_chunk_bytes_per_call(cfg: dict, rows: float,
                             itemsize: int = 2) -> float:
    """Bytes ONE chunk call of the delta rule (one 'K' layer) cannot
    avoid: the chunk's REAL rows of q', k', v' and of the decay's
    projection read once, its output rows written once (compute dtype),
    the slot's float32 state read once. The write of the state at the
    chunk's end, the float32 intermediates of the WY form and whatever XLA
    spills are not in the floor."""
    D = cfg["kda_heads"] * cfg["kda_head_dim"]
    return rows * (4 * D + D) * itemsize + kda_state_bytes(cfg)


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner. `experts_hit` is a
    layer's; `live_rows` the live sequences' rows. The state is priced in
    AND out here (what today's step moves), unlike the roofline's floor."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "KLFE"}
    n_routed, _ = _held(cfg)
    out = {"kda_state": n["K"] * n_slots * 2 * kda_state_bytes(cfg),
           "kda_weights": n["K"] * sum(kda_params(cfg).values()) * itemsize,
           "latent_rows": n["L"] * live_rows * latent_row_bytes(cfg,
                                                                itemsize),
           "latent_weights": n["L"] * sum(latent_params(cfg).values())
           * itemsize,
           "experts": n["E"] * experts_hit * (expert_up_elems(cfg)
                                              + expert_down_elems(cfg))
           * itemsize,
           "routers_shared": n["E"] * (C * n_routed + shared_params(cfg))
           * itemsize,
           "dense_ffn": n["F"] * layer_params(cfg, "F") * itemsize,
           "head": cfg["vocab_size"] * C * itemsize}
    out["total"] = sum(out.values())
    return out
