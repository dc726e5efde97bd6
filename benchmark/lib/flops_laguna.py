"""Operations and bytes of the Laguna configurations, from shapes alone: the
neighbour of `flops_lfm2.py` for a patterned model that mixes attention over
the whole history ('*': block pools) with attention over a window ('W': a
ring a slot) at two head counts, each head's output gated, a dense gated FFN
block ('F') and sigmoid-routed gated experts beside one shared expert ('E'),
under a head of its own. `cfg` is the configuration file's `llm_config`."""

from __future__ import annotations


def _head(cfg: dict) -> tuple:
    """(head size, k or v width)."""
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return hs, cfg["n_kv_heads"] * hs


def _heads(cfg: dict, kind: str) -> int:
    return cfg["window_heads"] if kind == "W" else cfg["n_head"]


def _held(cfg: dict) -> tuple:
    """(router width, experts held)."""
    n_routed = cfg["n_exp"] - cfg["n_shared"]
    return n_routed, (cfg.get("experts_held") or (0, n_routed))[1]


def ring_rows(cfg: dict, block_size: int) -> int:
    """Rows of a slot's ring in a 'W' layer: the window in whole blocks."""
    return -(-cfg["window"] // block_size) * block_size


def expert_up_elems(cfg: dict) -> int:
    """Elements of a routed expert's up matrix, [W_1 ; W_3]: 2F x C."""
    return 2 * cfg["up_dim"] * cfg["n_embd"]


def expert_down_elems(cfg: dict) -> int:
    return cfg["up_dim"] * cfg["n_embd"]


def shared_params(cfg: dict) -> int:
    """Parameters of an 'E' block's shared gated experts."""
    return cfg["n_shared"] * 3 * cfg["n_embd"] * (cfg.get("shared_up_dim")
                                                  or cfg["up_dim"])


def layer_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind` as this chip holds it, the
    block's norm included; the router's selection bias (a float32 buffer,
    `gate_bias`) is not counted."""
    C = cfg["n_embd"]
    if kind in "*W":
        hs, kvw = _head(cfg)
        qw = _heads(cfg, kind) * hs
        gate = C * _heads(cfg, kind) if cfg.get("attn_gate") else 0
        return C * (qw + 2 * kvw) + qw * C + gate + C
    if kind == "F":
        return 3 * C * cfg["dense_up_dim"] + C
    n_routed, held = _held(cfg)
    return (held * (expert_up_elems(cfg) + expert_down_elems(cfg))
            + shared_params(cfg) + C * n_routed + C)


def total_params(cfg: dict) -> int:
    assert not cfg.get("tie_head", True), "the head is a matrix of its own"
    return (sum(layer_params(cfg, k) for k in cfg["layer_pattern"])
            + 2 * cfg["vocab_size"] * cfg["n_embd"] + cfg["n_embd"])


def kv_bytes_per_row(cfg: dict, itemsize: int = 2) -> int:
    """Key + value bytes of one cached row of ONE attention layer."""
    return 2 * _head(cfg)[1] * itemsize


def resident_bytes(cfg: dict, n_slots: int, n_blocks: int, block_size: int,
                   itemsize: int = 2) -> dict:
    """What a deployment holds on the chip between steps, by kind of state:
    the '*' layers' pools grow with `n_blocks` (= with `max_len`), the 'W'
    layers' rings with the window alone."""
    n = {k: cfg["layer_pattern"].count(k) for k in "*W"}
    out = {"weights": total_params(cfg) * itemsize,
           "kv_pools": n["*"] * n_blocks * block_size
           * kv_bytes_per_row(cfg, itemsize),
           "window_rings": n["W"] * n_slots * ring_rows(cfg, block_size)
           * kv_bytes_per_row(cfg, itemsize)}
    out["total"] = sum(out.values())
    return out


def expert_up_bytes_per_call(cfg: dict, expert_tiles: float,
                             itemsize: int = 2) -> float:
    """Weight bytes ONE call of `expert_matmul_gated_up` must read: the
    [W_1 ; W_3] matrix of every expert TILE of the call."""
    return expert_tiles * expert_up_elems(cfg) * itemsize


def expert_down_bytes_per_call(cfg: dict, expert_tiles: float,
                               itemsize: int = 2) -> float:
    return expert_tiles * expert_down_elems(cfg) * itemsize


def paged_decode_bytes_per_call(cfg: dict, live_rows: float,
                                itemsize: int = 2) -> float:
    """Cache bytes ONE call of `paged_flash_decode` (one '*' layer) must
    read: every live sequence's valid rows of keys and values, once. The
    kernel fetches whole tiles of `block_size` rows, a sequence's last one
    part dead, so the roofline share this feeds is a lower bound."""
    return live_rows * kv_bytes_per_row(cfg, itemsize)


def window_decode_bytes_per_call(cfg: dict, window_rows: float,
                                 itemsize: int = 2) -> float:
    """The same for ONE call of `window_flash_decode` (one 'W' layer):
    `window_rows` = the sum over the live sequences of min(length,
    window)."""
    return window_rows * kv_bytes_per_row(cfg, itemsize)


def chunk_attention_ops(cfg: dict, kind: str, pairs: float) -> float:
    """Multiply-adds x 2 ONE call of a chunk kernel must make: every
    (query row, key) pair its mask lets through, in each of the layer
    kind's heads, scores and p @ v. `pairs` counts a chunk's REAL rows
    alone (a prompt's last chunk is partial): the row at position p sees
    p + 1 keys in a '*' layer, min(p + 1, window) in a 'W' layer
    (`DecodeEngine.chunk_attn_pairs_by`). A kernel computes whole tiles,
    pad rows and masked parts included, so the share this feeds is a
    lower bound."""
    return 2.0 * 2.0 * _heads(cfg, kind) * _head(cfg)[0] * pairs


def chunk_attention_bytes(cfg: dict, key_rows: float, chunk: int, kind: str,
                          itemsize: int = 2) -> float:
    """Bytes ONE call of a chunk kernel must move: the keys and values it
    reads once, the queries in and the outputs out."""
    hs = _head(cfg)[0]
    return (key_rows * kv_bytes_per_row(cfg, itemsize)
            + 2 * chunk * _heads(cfg, kind) * hs * itemsize)


def decode_step_bytes(cfg: dict, n_slots: int, experts_hit: float,
                      live_rows: float, itemsize: int = 2,
                      window_rows: float = None) -> dict:
    """Bytes a plain decode step must move, by owner. `experts_hit` is a
    layer's; `live_rows` the live sequences' rows; `window_rows` what a
    window layer reads of them (default: every slot a full window)."""
    C = cfg["n_embd"]
    n = {k: cfg["layer_pattern"].count(k) for k in "*WFE"}
    n_routed, _ = _held(cfg)
    if window_rows is None:
        window_rows = min(live_rows, n_slots * cfg["window"])
    shared = shared_params(cfg)
    row = kv_bytes_per_row(cfg, itemsize)
    out = {"experts": n["E"] * experts_hit * (expert_up_elems(cfg)
                                              + expert_down_elems(cfg))
           * itemsize,
           "routers_shared": n["E"] * (C * n_routed + shared) * itemsize,
           "attention_full": n["*"] * (layer_params(cfg, "*") * itemsize
                                       + live_rows * row),
           "attention_window": n["W"] * (layer_params(cfg, "W") * itemsize
                                         + window_rows * row),
           "dense_ffn": n["F"] * layer_params(cfg, "F") * itemsize,
           "head": cfg["vocab_size"] * C * itemsize}
    out["total"] = sum(out.values())
    return out
