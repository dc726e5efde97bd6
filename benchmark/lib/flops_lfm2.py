"""Operations and bytes of the LFM2 mixture-of-experts configurations, from
shapes alone: the neighbour of `flops_granite.py` for a patterned model with
gated short-convolution mixers ('C'), a dense gated FFN block of its own
width ('F'), QK-normed attention ('*') and sigmoid-routed gated experts with
no shared expert ('E'), under a tied head. `cfg` is the configuration file's
`llm_config`."""

from __future__ import annotations


def _head(cfg: dict) -> tuple:
    """(head size, q width, k or v width)."""
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return hs, cfg["n_head"] * hs, cfg["n_kv_heads"] * hs


def _held(cfg: dict) -> tuple:
    """(router width, experts held)."""
    n_routed = cfg["n_exp"] - cfg["n_shared"]
    return n_routed, (cfg.get("experts_held") or (0, n_routed))[1]


def expert_up_elems(cfg: dict) -> int:
    """Elements of a routed expert's up matrix, [W_1 ; W_3]: 2F x C."""
    return 2 * cfg["up_dim"] * cfg["n_embd"]


def expert_down_elems(cfg: dict) -> int:
    return cfg["up_dim"] * cfg["n_embd"]


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind` as this chip holds it, the
    block's norm included."""
    C = cfg["n_embd"]
    if kind == "C":
        return 3 * C * C + cfg["conv_len"] * C + C * C + C
    if kind == "*":
        hs, qw, kvw = _head(cfg)
        norms = 2 * hs if cfg.get("qk_norm") else 0
        return C * (qw + 2 * kvw) + qw * C + norms + C
    if kind == "F":
        return 3 * C * cfg["dense_up_dim"] + C
    n_routed, held = _held(cfg)
    assert not cfg["n_shared"], "an LFM2 expert layer has no shared expert"
    return (held * (expert_up_elems(cfg) + expert_down_elems(cfg))
            + C * n_routed + n_routed + C)


def total_params(cfg: dict) -> int:
    assert cfg.get("tie_head", True), "the head is the embedding"
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def state_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """What one slot carries over all 'C' layers: each convolution's last
    `conv_len` - 1 inputs."""
    return cfg["layer_pattern"].count("C") * (cfg["conv_len"] - 1) \
        * cfg["n_embd"] * itemsize


def kv_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Key + value bytes of one cached row over all '*' layers."""
    return cfg["layer_pattern"].count("*") * 2 * _head(cfg)[2] * itemsize


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps. The routers'
    expert bias is float32 in the tree and counted at `itemsize` like the
    rest (128 bytes an expert layer too few)."""
    out = {"weights": total_params(cfg) * itemsize,
           "state": n_slots * state_bytes_per_slot(cfg, itemsize),
           "kv_pools": n_blocks * block_size * kv_bytes_per_row(cfg,
                                                                itemsize)}
    out["total"] = sum(out.values())
    return out


def expert_up_bytes_per_call(cfg: dict, expert_tiles: float,
                             itemsize: int = 2) -> float:
    """Weight bytes ONE call of `expert_matmul_gated_up` must read: the
    [W_1 ; W_3] matrix of every expert TILE of the call (an expert hit is
    one tile, a second tile of one expert reads its matrix again: the
    kernel's block is the expert's whole slab). The packed activations
    beside them are left out, so the roofline share this feeds is a lower
    bound."""
    return expert_tiles * expert_up_elems(cfg) * itemsize


def expert_down_bytes_per_call(cfg: dict, expert_tiles: float,
                               itemsize: int = 2) -> float:
    """The same for `expert_matmul_down`: half the up kernel's."""
    return expert_tiles * expert_down_elems(cfg) * itemsize


def paged_decode_bytes_per_call(cfg: dict, live_rows: float,
                                itemsize: int = 2) -> float:
    """Cache bytes ONE call of `paged_flash_decode` (one '*' layer) must
    read: every live sequence's valid rows of keys and values, once. The
    kernel fetches whole tiles of `block_size` rows, a sequence's last one
    part dead, so the roofline share this feeds is a lower bound."""
    return live_rows * 2 * _head(cfg)[2] * itemsize


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner: ISSUE 45's planning
    reckoning from the tree's own shapes. `experts_hit` is a layer's."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "CF*E"}
    n_routed, _ = _held(cfg)
    out = {"experts": n["E"] * experts_hit * (expert_up_elems(cfg)
                                              + expert_down_elems(cfg))
           * itemsize,
           "routers": n["E"] * C * n_routed * itemsize,
           "conv_mixers": n["C"] * layer_params(cfg, "C") * itemsize
           + 2 * n_slots * state_bytes_per_slot(cfg, itemsize),  # in, out
           "attention": n["*"] * layer_params(cfg, "*") * itemsize
           + live_rows * kv_bytes_per_row(cfg, itemsize),
           "dense_ffn": n["F"] * layer_params(cfg, "F") * itemsize,
           "head": cfg["vocab_size"] * C * itemsize}
    out["total"] = sum(out.values())
    return out
