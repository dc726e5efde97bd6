"""Parameters and bytes of the Falcon-H1 configurations, from shapes alone:
the neighbour of `flops_lfm2.py` for a patterned model whose every published
layer is a 'P' block (a Mamba-2 mixer and GQA side by side on one normed
input: a slot's state AND blocks of the pool) and an 'F' block (a dense gated
FFN), under an untied head. `cfg` is the configuration file's `llm_config`."""

from __future__ import annotations


def _head(cfg: dict) -> tuple:
    """(head size, q width, k or v width)."""
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return hs, cfg["n_head"] * hs, cfg["n_kv_heads"] * hs


def ssm_dims(cfg: dict) -> tuple:
    """(d_inner, conv_dim, the in-projection's width)."""
    d_inner = cfg["ssm_heads"] * cfg["ssm_head_dim"]
    conv_dim = d_inner + 2 * cfg["ssm_groups"] * cfg["ssm_state"]
    return d_inner, conv_dim, d_inner + conv_dim + cfg["ssm_heads"]


def branch_params(cfg: dict) -> dict:
    """Parameters of a 'P' block's two branches, its norm apart."""
    C = cfg["n_embd"]
    _, qw, kvw = _head(cfg)
    d_inner, conv_dim, d_in = ssm_dims(cfg)
    return {"attention": C * (qw + 2 * kvw) + qw * C,
            "ssm": C * d_in + d_inner * C + (cfg["ssm_conv"] + 1) * conv_dim
            + d_inner + 3 * cfg["ssm_heads"]}


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind`, the block's norm included."""
    C = cfg["n_embd"]
    if kind == "F":
        return 3 * C * cfg["dense_up_dim"] + C
    assert kind == "P", kind
    return sum(branch_params(cfg).values()) + C


def total_params(cfg: dict) -> int:
    assert not cfg.get("tie_head", True), "the head is a matrix of its own"
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + 2 * cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def ssm_state_bytes(cfg: dict) -> int:
    """One slot's float32 state in ONE state-space layer."""
    return cfg["ssm_heads"] * cfg["ssm_head_dim"] * cfg["ssm_state"] * 4


def state_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """What one slot carries over all 'P' layers that is no block of the
    pool: the float32 state and the convolution's last K - 1 inputs."""
    tail = (cfg["ssm_conv"] - 1) * ssm_dims(cfg)[1] * itemsize
    return cfg["layer_pattern"].count("P") * (ssm_state_bytes(cfg) + tail)


def kv_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Key + value bytes of one cached row over all 'P' layers."""
    return cfg["layer_pattern"].count("P") * 2 * _head(cfg)[2] * itemsize


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps. A_log, D and
    dt_bias are float32 in the tree and counted at `itemsize` like the rest
    (192 bytes a layer too few)."""
    out = {"weights": total_params(cfg) * itemsize,
           "state": n_slots * state_bytes_per_slot(cfg, itemsize),
           "kv_pools": n_blocks * block_size * kv_bytes_per_row(cfg,
                                                                itemsize)}
    out["total"] = sum(out.values())
    return out


def paged_decode_bytes_per_call(cfg: dict, live_rows: float,
                                itemsize: int = 2) -> float:
    """Cache bytes ONE call of `paged_flash_decode` (one 'P' layer's
    attention branch) must read: every live sequence's valid rows of keys
    and values, once. The kernel fetches whole tiles of `block_size` rows,
    so the roofline share this feeds is a lower bound."""
    return live_rows * 2 * _head(cfg)[2] * itemsize


def ssm_step_bytes_per_call(cfg: dict, live_slots: float) -> float:
    """State bytes ONE one-token recurrence (one 'P' layer's `ssm_step`)
    must move: every live slot's float32 state in and out. The program's
    pass runs over ALL slots' rows, dead ones too (they keep their state
    through a select), and the token's x, B, C and dt beside the state are
    left out: the roofline share this feeds is a lower bound."""
    return 2 * live_slots * ssm_state_bytes(cfg)


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner: ISSUE 54's planning
    reckoning from the tree's own shapes (`experts_hit` is the interface's:
    the model has no experts)."""
    n_p = cfg["layer_pattern"].count("P")
    n_f = cfg["layer_pattern"].count("F")
    branches = branch_params(cfg)
    out = {"dense_ffn": n_f * layer_params(cfg, "F") * itemsize,
           "attention": n_p * branches["attention"] * itemsize
           + live_rows * kv_bytes_per_row(cfg, itemsize),
           "ssm_weights": n_p * branches["ssm"] * itemsize,
           "ssm_state": 2 * n_slots * state_bytes_per_slot(cfg, itemsize),
           "head": cfg["vocab_size"] * cfg["n_embd"] * itemsize}
    out["total"] = sum(out.values())
    return out
