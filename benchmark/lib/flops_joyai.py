"""Operations and bytes of the JoyAI-LLM-Flash configuration, from shapes
alone: the neighbour of `flops_laguna.py` for a patterned model whose
attention is latent ('L': a pool of latent rows with no head axis, one
shared rotated key head), in front of a dense gated FFN block ('F') or
sigmoid-routed gated experts beside one shared expert ('E'), under a head of
its own. `cfg` is the configuration file's `llm_config`.

Two widths of a cached row are told apart: what the mathematics needs,
`latent_row_bytes` (512 + 64 values: 1,152 B in bfloat16), the floor of the
decode kernel's roofline, and what the pool keeps, `pool_row_bytes` (whole
128-lane tiles: 640 values, 1,280 B), what is resident and what a fetch
moves."""

from __future__ import annotations

from benchmark.lib.flops_laguna import (_held, expert_down_bytes_per_call,  # noqa: F401
                                        expert_down_elems,
                                        expert_up_bytes_per_call,
                                        expert_up_elems, shared_params)


def _widths(cfg: dict) -> tuple:
    """(heads, nope, rope, value, q latent, kv latent)."""
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return (cfg["n_head"], cfg.get("qk_nope_head_dim") or hs,
            cfg["rope_head_dim"], cfg.get("v_head_dim") or hs,
            cfg["q_latent_dim"], cfg["kv_latent_dim"])


def attention_params(cfg: dict) -> dict:
    """Parameters of an 'L' block's latent attention, matrix by matrix,
    the two latent norms last."""
    C = cfg["n_embd"]
    nh, dn, dr, dv, nlq, lc = _widths(cfg)
    return {"W_qa": C * nlq, "W_qb": nlq * nh * (dn + dr),
            "W_kva": C * (lc + dr), "W_kvb": lc * nh * (dn + dv),
            "W_o": nh * dv * C, "norms": nlq + lc}


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind` as this chip holds it, the block's
    norm included; the router's selection bias (a float32 buffer,
    `gate_bias`) is not counted."""
    C = cfg["n_embd"]
    if kind == "L":
        return sum(attention_params(cfg).values()) + C
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


def latent_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What the mathematics needs of one cached row of ONE latent layer:
    the key/value latent and the shared rotated key."""
    return (cfg["kv_latent_dim"] + cfg["rope_head_dim"]) * itemsize


def pool_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What the pool keeps a row: the same in whole 128-lane tiles
    (`ops.latent_attention.row_lanes`)."""
    return -(-(cfg["kv_latent_dim"] + cfg["rope_head_dim"]) // 128) * 128 \
        * itemsize


def kv_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Pool bytes of one cached row over all latent layers."""
    return cfg["layer_pattern"].count("L") * pool_row_bytes(cfg, itemsize)


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps."""
    out = {"weights": total_params(cfg) * itemsize,
           "latent_pools": n_blocks * block_size
           * kv_bytes_per_row(cfg, itemsize)}
    out["total"] = sum(out.values())
    return out


def latent_decode_bytes_per_call(cfg: dict, live_rows: float,
                                 itemsize: int = 2) -> float:
    """Cache bytes ONE call of `latent_flash_decode` (one 'L' layer) must
    read: every live sequence's valid rows, once, at what the mathematics
    needs of a row. No form reads a live row less than once; the kernel
    fetches whole tiles of `block_size` rows of `pool_row_bytes`, so the
    share this feeds is a lower bound."""
    return live_rows * latent_row_bytes(cfg, itemsize)


def paged_decode_bytes_per_call(cfg: dict, live_rows: float,
                                itemsize: int = 2) -> float:
    """The accepted runner's name for the decode attention's bytes a call:
    this configuration's decode attention is `latent_flash_decode`."""
    return latent_decode_bytes_per_call(cfg, live_rows, itemsize)


def chunk_attention_ops(cfg: dict, pairs: float) -> float:
    """Multiply-adds x 2 ONE call of `latent_flash_prefill` must make at
    least: every (query row, key) pair its causal mask lets through, of
    the chunk's REAL rows, in each head, the scores over nope + rope lanes
    and p @ v over the value lanes. The up-projection of cached rows (the
    form the kernel runs) and the absorbed form's wider products are NOT
    counted: the least either form makes, so no later change of form can
    read over 100%."""
    nh, dn, dr, dv, _, _ = _widths(cfg)
    return 2.0 * nh * (dn + dr + dv) * pairs


def absorbed_decode_ops_per_row(cfg: dict) -> int:
    """Operations the absorbed decode makes a live row a layer: every
    head's score over latent + rope lanes and its `p c` over the latent."""
    nh, _, dr, _, _, lc = _widths(cfg)
    return 2 * nh * (2 * lc + dr)


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner. `experts_hit` is a
    layer's; `live_rows` the live sequences' rows."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "LFE"}
    n_routed, _ = _held(cfg)
    attn = sum(attention_params(cfg).values())
    out = {"latent_rows": n["L"] * live_rows * latent_row_bytes(cfg,
                                                                itemsize),
           "attention_weights": n["L"] * attn * itemsize,
           "experts": n["E"] * experts_hit * (expert_up_elems(cfg)
                                              + expert_down_elems(cfg))
           * itemsize,
           "routers_shared": n["E"] * (C * n_routed + shared_params(cfg))
           * itemsize,
           "dense_ffn": n["F"] * layer_params(cfg, "F") * itemsize,
           "head": cfg["vocab_size"] * C * itemsize}
    out["total"] = sum(out.values())
    return out
