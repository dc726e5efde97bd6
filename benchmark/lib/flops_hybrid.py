"""Operations and bytes of the patterned (Nemotron-H) configurations, from
shapes alone: the neighbour of `flops.py` for models with state-space and
expert layers. `cfg` is the configuration file's `llm_config`."""

from __future__ import annotations


def _ssm_dims(cfg: dict) -> tuple:
    d_inner = cfg["ssm_heads"] * cfg["ssm_head_dim"]
    conv_dim = d_inner + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
    return d_inner, conv_dim


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one layer of `kind` as this chip holds it (its share
    of the experts), the block's norm included."""
    C = cfg["n_embd"]
    if kind == "M":
        d_inner, conv_dim = _ssm_dims(cfg)
        H = cfg["ssm_heads"]
        return (C * (d_inner + conv_dim + H) + d_inner * C
                + (cfg["ssm_conv"] + 1) * conv_dim + 3 * H + d_inner + C)
    if kind == "*":
        hs = cfg.get("head_dim") or C // cfg["n_head"]
        qkv = cfg["n_head"] * hs + 2 * cfg["n_kv_heads"] * hs
        return C * qkv + cfg["n_head"] * hs * C + C
    n_routed = cfg["n_exp"] - cfg["n_shared"]
    held = (cfg.get("experts_held") or (0, n_routed))[1]
    shared = cfg.get("shared_up_dim") or cfg["up_dim"]
    return (held * expert_matrix_elems(cfg) * 2 + 2 * C * shared
            + C * n_routed + n_routed + C)


def expert_matrix_elems(cfg: dict) -> int:
    """Elements of ONE of a routed expert's two matrices."""
    return cfg["n_embd"] * cfg["up_dim"]


def total_params(cfg: dict) -> int:
    head = 1 if cfg.get("tie_head", True) else 2
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + head * cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def state_bytes_per_slot(cfg: dict, tail_itemsize: int = 2) -> int:
    """Recurrent state one slot holds over all 'M' layers: the float32
    state and the convolution's tail."""
    d_inner, conv_dim = _ssm_dims(cfg)
    per = (d_inner * cfg["ssm_state"] * 4
           + (cfg["ssm_conv"] - 1) * conv_dim * tail_itemsize)
    return cfg["layer_pattern"].count("M") * per


def kv_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Key + value bytes of one cached row over all '*' layers."""
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return cfg["layer_pattern"].count("*") * 2 * cfg["n_kv_heads"] * hs \
        * itemsize


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps."""
    out = {"weights": total_params(cfg) * itemsize,
           "state": n_slots * state_bytes_per_slot(cfg, itemsize),
           "kv_pools": n_blocks * block_size * kv_bytes_per_row(cfg,
                                                                itemsize)}
    out["total"] = sum(out.values())
    return out


def expert_matmul_bytes_per_call(cfg: dict, experts_hit: float,
                                 itemsize: int = 2) -> float:
    """Weight bytes ONE call of an expert_matmul kernel (up or down: each
    reads one of the two matrices of every expert hit) must read. The
    packed activations beside them are left out (under 1% at 64 tokens),
    so the roofline share this feeds is a lower bound on the bytes."""
    return experts_hit * expert_matrix_elems(cfg) * itemsize


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner: the planning
    reckoning of ISSUE 33 from the tree's own shapes."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "ME*"}
    shared = cfg.get("shared_up_dim") or cfg["up_dim"]
    n_routed = cfg["n_exp"] - cfg["n_shared"]
    experts = n["E"] * (2 * experts_hit * expert_matrix_elems(cfg)
                        + 2 * C * shared + C * n_routed) * itemsize
    ssm_w = n["M"] * layer_params(cfg, "M") * itemsize
    state = 2 * n_slots * state_bytes_per_slot(cfg, itemsize)  # in and out
    attn = n["*"] * layer_params(cfg, "*") * itemsize \
        + live_rows * kv_bytes_per_row(cfg, itemsize)
    head = cfg["vocab_size"] * C * itemsize
    out = {"experts": experts, "ssm": ssm_w + state, "attention": attn,
           "head": head}
    out["total"] = sum(out.values())
    return out
