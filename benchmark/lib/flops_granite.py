"""Operations and bytes of the Granite 4.0-H configurations, from shapes
alone: the neighbour of `flops_hybrid.py` for a patterned model whose
experts are gated (an up stack of 2 x the width), whose router has no
correction bias and whose head is the embedding. `cfg` is the configuration
file's `llm_config`. What is the same arithmetic (a state-space layer, an
attention layer, the state a slot holds, a cached row) is imported."""

from __future__ import annotations

from benchmark.lib import flops_hybrid
from benchmark.lib.flops_hybrid import kv_bytes_per_row, state_bytes_per_slot


def _experts(cfg: dict) -> tuple:
    """(router width, experts held, shared expert's width)."""
    n_routed = cfg["n_exp"] - cfg["n_shared"]
    held = (cfg.get("experts_held") or (0, n_routed))[1]
    return n_routed, held, cfg.get("shared_up_dim") or cfg["up_dim"]


def expert_up_elems(cfg: dict) -> int:
    """Elements of a routed expert's up matrix, [a | b]: 2F x C."""
    return 2 * cfg["up_dim"] * cfg["n_embd"]


def expert_down_elems(cfg: dict) -> int:
    return cfg["up_dim"] * cfg["n_embd"]


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind` as this chip holds it (its share
    of the experts), the block's norm included."""
    if kind in "M*":
        return flops_hybrid.layer_params(cfg, kind)
    C = cfg["n_embd"]
    n_routed, held, shared = _experts(cfg)
    return (held * (expert_up_elems(cfg) + expert_down_elems(cfg))
            + 3 * C * shared + C * n_routed + C)


def total_params(cfg: dict) -> int:
    assert cfg.get("tie_head", True), "Granite's head is its embedding"
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps. The 3 x heads
    float32 scalars of a state-space layer are counted at `itemsize` like
    the rest (768 bytes a layer too few)."""
    out = {"weights": total_params(cfg) * itemsize,
           "state": n_slots * state_bytes_per_slot(cfg, itemsize),
           "kv_pools": n_blocks * block_size * kv_bytes_per_row(cfg,
                                                                itemsize)}
    out["total"] = sum(out.values())
    return out


def expert_up_bytes_per_call(cfg: dict, expert_tiles: float,
                             itemsize: int = 2) -> float:
    """Weight bytes ONE call of `expert_matmul_gated_up` must read: the
    [a | b] matrix of every expert TILE of the call (an expert hit is one
    tile, a second tile of one expert reads its matrix again: the kernel's
    block is the expert's whole slab). The packed activations beside them
    are left out, so the roofline share this feeds is a lower bound."""
    return expert_tiles * expert_up_elems(cfg) * itemsize


def expert_down_bytes_per_call(cfg: dict, expert_tiles: float,
                               itemsize: int = 2) -> float:
    """The same for `expert_matmul_down`: half the up kernel's."""
    return expert_tiles * expert_down_elems(cfg) * itemsize


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2) -> dict:
    """Bytes a plain decode step must move, by owner: ISSUE 36's planning
    reckoning from the tree's own shapes."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "ME*"}
    n_routed, _, shared = _experts(cfg)
    held = n["E"] * experts_hit * (expert_up_elems(cfg)
                                   + expert_down_elems(cfg)) * itemsize
    shared_b = n["E"] * (3 * C * shared + C * n_routed) * itemsize
    mixers = (n["M"] * layer_params(cfg, "M")
              + n["*"] * layer_params(cfg, "*")) * itemsize \
        + live_rows * kv_bytes_per_row(cfg, itemsize)
    state = 2 * n_slots * state_bytes_per_slot(cfg, itemsize)  # in and out
    out = {"held_experts": held, "shared_and_router": shared_b,
           "mixers": mixers, "state": state,
           "head": cfg["vocab_size"] * C * itemsize}
    out["total"] = sum(out.values())
    return out
