"""Attention over a window of the last `window` positions, and the ring a
slot keeps for it.

A query at position i of a window layer ('W', config.py) sees the keys j
with 0 <= i - j < window, its own included. What lies further back is
never read again, so such a layer's cache is no share of the block pool:
a slot keeps a RING of R = `ring_rows(window, block_size)` rows (the
window rounded up to whole tiles), position p at row p mod R, in the pool's
merged-lane row format (ops/block_pool.py `kv_lanes`): a leaf
(n_slots, R, L) beside the pools, whatever `max_len` is. Keys are cached
rotated, so their order in the ring does not matter to the softmax: only
which position a row holds does, and that follows from the query's own
position alone. Row r of the ring of a sequence whose newest row is
position p holds position p - ((p - r) mod R); it is visible to the query
at p iff that distance is under min(window, p + 1). A row the slot's last
occupant left stands for a negative position and is masked: a sequence
admitted into a used slot sees none of it, and nothing is zeroed.

Two forms, as the engine's programs have them:

* one token of every slot (`decode`): write the token's row (a dead or
  parked slot writes nothing: its index is sent out of bounds and
  dropped), then every query against its slot's ring: `window_flash_decode`
  reads the ring's live tiles, R / tile a sequence, never `cache_len / 128`.
* a chunk of T rows of ONE sequence at offset `off` (`chunk`): the keys are
  the slot's ring in position order (`ring_logical`: positions off - R ..
  off - 1) with the chunk's own rows behind them, one (R + T, L) array of
  CONSECUTIVE positions, so `window_flash_prefill` takes for a tile of
  queries the key tiles of its window by a static index map (a tile left
  of a query tile's window is neither fetched nor stepped over) and masks
  by position. The ring the chunk leaves is the last R REAL rows of that
  array (`ring_after`): pad rows of a part-filled chunk never reach it.

Both kernels have the XLA path beside them (`_masked`): what the CPU tests
run, and what carries a call a gate declines, aloud (obs/paths.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu.compat import tpu_compiler_params
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops.block_pool import merge_heads
from distributed_pytorch_tpu.ops.flash_decode import (_CHUNK_SCORE_BYTES,
                                                      _NEG_INF,
                                                      _budget_decline,
                                                      _chunk_vmem_bytes,
                                                      _common_decline,
                                                      _heads_of_lanes,
                                                      _lane_group_q,
                                                      _lane_head,
                                                      _pick_block,
                                                      _softmax_init,
                                                      _softmax_out,
                                                      _softmax_update,
                                                      _stack_tiles,
                                                      _zero_extend_q)

#: rows of a ring tile a decode grid step moves, and of a key view and a
#: query tile of the chunk kernel, on the chip (the CPU tests tile by 8)
_DECODE_TILE = 512
_CHUNK_TILE_K = 128
_CHUNK_TILE_Q = 128


def ring_rows(window: int, block_size: int) -> int:
    """Rows of a slot's ring: the window in whole blocks."""
    return -(-window // block_size) * block_size


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def ring_write_token(ring: jnp.ndarray, new: jnp.ndarray, pos,
                     live) -> jnp.ndarray:
    """Row b of `new` (B, 1, n_kv, hs) into slot b's ring at `pos[b]` mod
    R; a slot that is not `live` (dead, or parked while it prefills)
    writes nothing."""
    B, R, L = ring.shape
    rows = merge_heads(new.astype(ring.dtype), L)[:, 0]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    idx = jnp.where(live, p % R, R)             # out of bounds: dropped
    return ring.at[jnp.arange(B), idx].set(rows, mode="drop")


def ring_logical(ring_slot: jnp.ndarray, off) -> jnp.ndarray:
    """One slot's ring (R, L) in position order: row j is position
    off - R + j, for a sequence whose next row is `off`."""
    R, L = ring_slot.shape
    twice = jnp.concatenate([ring_slot, ring_slot])
    return jax.lax.dynamic_slice(twice, (jnp.asarray(off, jnp.int32) % R, 0),
                                 (R, L))


def ring_after(keys: jnp.ndarray, R: int, off, valid) -> jnp.ndarray:
    """The ring (R, L) a chunk leaves: `keys` (R + T, L) holds positions
    off - R .. off + T - 1 (`ring_logical` + the chunk's rows), of which
    the chunk's first `valid` are real; the last R real positions go back
    to their rows, position p at p mod R."""
    L = keys.shape[1]
    valid = jnp.asarray(valid, jnp.int32)
    last = jax.lax.dynamic_slice(keys, (valid, 0), (R, L))
    first = (jnp.asarray(off, jnp.int32) + valid) % R   # = position % R
    twice = jnp.concatenate([last, last])
    return jax.lax.dynamic_slice(twice, ((R - first) % R, 0), (R, L))


# ---------------------------------------------------------------------------
# the XLA path
# ---------------------------------------------------------------------------

def _masked(q, k, v, visible, scale: float) -> jnp.ndarray:
    """q (B, T, nh, hs) against k, v (B, S, n_kv, hs) under `visible`
    (B, T, S): scores in float32, every head of a group on its kv head."""
    B, T, nh, hs = q.shape
    nkv = k.shape[2]
    qg = q.reshape(B, T, nkv, nh // nkv, hs).astype(jnp.float32)
    s = jnp.einsum("btgrh,bsgh->bgrts", qg, k.astype(jnp.float32)) * scale
    s = jnp.where(visible[:, None, None], s, -jnp.inf)
    # a row that sees nothing (a dead slot's) reads zeros, not NaN
    p = jax.nn.softmax(jnp.where(visible.any(-1)[:, None, None, :, None],
                                 s, 0.0), axis=-1)
    p = jnp.where(visible[:, None, None], p, 0.0)
    out = jnp.einsum("bgrts,bsgh->btgrh", p.astype(v.dtype), v)
    return out.reshape(B, T, nh, hs).astype(q.dtype)


def window_attention(q, k, v, *, window: int, scale: float) -> jnp.ndarray:
    """No cache: T queries against their own T keys (B, T, n_kv, hs),
    causal inside the window."""
    paths.note("window_attention", "xla masked", "no cache: T keys of its "
               "own")
    T = q.shape[1]
    d = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    return _masked(q, k, v, ((d >= 0) & (d < window))[None], scale)


def _split(rows: jnp.ndarray, nkv: int, hs: int) -> jnp.ndarray:
    """Merged-lane rows (..., L) -> (..., n_kv, hs)."""
    return rows[..., :nkv * hs].reshape(rows.shape[:-1] + (nkv, hs))


# ---------------------------------------------------------------------------
# one token of every slot
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, tile: int, ring: int,
                   window: int, hs: int, rep: int):
    """`flash_decode`'s online softmax over the tiles of ONE slot's ring,
    on merged-lane tiles as `_paged_kernel` reads them (zero-extended
    query rows, one product for every head's scores, each row's own lanes
    picked at the end). Grid (slots, ring tiles)."""
    b, j = pl.program_id(0), pl.program_id(1)
    p = pos_ref[b]                      # the query's position; < 0: dead

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # a sequence shorter than the ring has written rows 0 .. p alone
    @pl.when(j * tile <= p)
    def _():
        q = q_ref[0]                                        # (Rq, L)
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (Rq, tile)
        r = j * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        back = jax.lax.rem(p - r + ring, ring)  # the row's distance behind
        visible = back < jnp.minimum(window, p + 1)
        s = jnp.where(visible, s, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a tile with nothing visible keeps exp(0) out of the sum
        pr = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        v = v_ref[0]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (Rq, L)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        g_pad = out.shape[0] // rep
        shape = (g_pad, out.shape[1])
        own = _lane_head(shape, hs) == jax.lax.broadcasted_iota(
            jnp.int32, shape, 0)
        for i in range(rep):
            slab = out[i * g_pad:(i + 1) * g_pad]
            o_ref[0, i:i + 1, :] = jnp.sum(
                jnp.where(own, slab, 0.0), axis=0,
                keepdims=True).astype(o_ref.dtype)


def _decode_tile(ring: int, interpret: bool) -> int:
    return _pick_block(ring, _DECODE_TILE, 8 if interpret else 128)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "n_kv_heads", "interpret"))
def window_flash_decode(q: jnp.ndarray, ring_k: jnp.ndarray,
                        ring_v: jnp.ndarray, pos: jnp.ndarray, *,
                        window: int, scale: float, n_kv_heads: int,
                        interpret: bool = False) -> jnp.ndarray:
    """q (B, nh, hs), one query a slot at position `pos[b]` (negative: a
    slot with nothing to read), against the slots' rings (B, R, L) that
    already hold the query's own row. Returns (B, nh, hs). A grid step
    moves one (tile, L) k/v pair of one ring; a tile past a short
    sequence's newest row is neither fetched nor computed."""
    B, nh, hs = q.shape
    R, L = ring_k.shape[1:]
    nkv = n_kv_heads
    rep = nh // nkv
    g_pad = -(-nkv // 8) * 8
    Rq = rep * g_pad
    tile = _decode_tile(R, interpret)
    assert tile, f"no tile split for a ring of {R} rows"
    p = jnp.asarray(pos, jnp.int32).reshape(B)

    def q_idx(b, j, pos_ref):
        return (b, 0, 0)

    def kv_idx(b, j, pos_ref):
        last = jax.lax.div(jnp.clip(pos_ref[b], 0, R - 1), tile)
        return (b, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, R // tile),
        in_specs=[pl.BlockSpec((1, Rq, L), q_idx),
                  pl.BlockSpec((1, tile, L), kv_idx),
                  pl.BlockSpec((1, tile, L), kv_idx)],
        out_specs=pl.BlockSpec((1, rep, L), q_idx),
        scratch_shapes=[pltpu.VMEM((Rq, L), jnp.float32),
                        pltpu.VMEM((Rq, 1), jnp.float32),
                        pltpu.VMEM((Rq, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), tile=tile,
                          ring=R, window=window, hs=hs, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rep, L), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        name="window_flash_decode",
        interpret=interpret,
    )(p, _zero_extend_q(q, nkv, g_pad, L), ring_k, ring_v)
    return out[:, :, :nkv * hs].reshape(B, rep, nkv, hs) \
        .transpose(0, 2, 1, 3).reshape(B, nh, hs)


def window_flash_decode_decline(q, ring_k, n_kv_heads: int):
    """Why the ring kernel cannot take this call (None = it can)."""
    if q.ndim != 4 or q.shape[1] != 1:
        return f"query shape {q.shape} is not decode-shaped (B, 1, nh, hs)"
    nh, hs = q.shape[-2:]
    R, L = ring_k.shape[1:]
    tile = _decode_tile(R, jax.default_backend() != "tpu")
    if not tile:
        return f"a ring of {R} rows has no tile split"
    why = _common_decline(q, ring_k, nh, n_kv_heads, hs, tile,
                          f"ring tile {tile}")
    if why is not None:
        return why
    Rq = (nh // n_kv_heads) * (-(-n_kv_heads // 8) * 8)
    item = jnp.dtype(ring_k.dtype).itemsize
    return _budget_decline(2 * 2 * tile * L * item + 2 * Rq * L * item
                           + 3 * Rq * L * 4 + 3 * Rq * tile * 4)


def window_flash_decode_usable(q, ring_k, n_kv_heads: int) -> bool:
    return window_flash_decode_decline(q, ring_k, n_kv_heads) is None


def window_decode(q, ring_k, ring_v, pos, live, *, window: int,
                  scale: float, n_kv_heads: int) -> jnp.ndarray:
    """One token of every slot: q (B, 1, nh, hs) at `pos` (B,) against the
    rings that hold the token's own row already. Returns (B, 1, nh, hs)."""
    from distributed_pytorch_tpu.ops.attention_core import (
        _decode_kernel_wanted, _on_tpu)
    B = q.shape[0]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    p = jnp.where(live, p, -1)
    if _decode_kernel_wanted(
            "window_flash_decode",
            window_flash_decode_decline(q, ring_k, n_kv_heads)):
        return window_flash_decode(
            q[:, 0], ring_k, ring_v, p, window=window, scale=scale,
            n_kv_heads=n_kv_heads, interpret=not _on_tpu())[:, None]
    R = ring_k.shape[1]
    back = (p[:, None] - jnp.arange(R)[None]) % R
    visible = back < jnp.minimum(window, p + 1)[:, None]
    hs = q.shape[-1]
    return _masked(q, _split(ring_k, n_kv_heads, hs),
                   _split(ring_v, n_kv_heads, hs), visible[:, None], scale)


# ---------------------------------------------------------------------------
# a chunk of one sequence
# ---------------------------------------------------------------------------

def _chunk_kernel(off_ref, q_ref, *refs, scale: float, tq: int, tk: int,
                  group: int, ring: int, window: int, hs: int, rep: int,
                  first_tile):
    """`_prefill_kernel`'s step over keys at CONSECUTIVE positions (row c
    of the keys is position off - ring + c). Grid (lane groups, query
    tiles, key tiles of a query tile's window): a key tile is `group`
    (tk, lanes) views of the keys, stacked, under ONE softmax update a
    head (`flash_decode._softmax_update`); `_chunk_tiles` opens as many
    views as a query tile's window spans where their score tile fits, and
    the third grid axis is then one step long: no running state is
    rescaled at all. Both of the band's edges (causal, and `window` keys
    back) run through such a tile for every query tile, so its one body
    masks: by a row and two columns of int32, no divide over the tile."""
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * group:]
    i, j = pl.program_id(1), pl.program_id(2)
    off = off_ref[0]
    hpg, n_rows = q_ref.shape[1], q_ref.shape[2]
    keys = group * tk

    pl.when(j == 0)(functools.partial(_softmax_init, acc_ref, m_ref, l_ref))

    k = _stack_tiles([r[...] for r in k_refs])              # (keys, lanes)
    v = _stack_tiles([r[...] for r in v_refs])
    # key c of the tile lies d = its position less the tile's first
    # query's ahead; packed row r (r // rep positions into the tile) sees
    # it iff 0 <= r // rep - d < window and its position is none a slot's
    # last occupant left: d * rep <= r < (d + window) * rep
    d = (first_tile(i) + j * group) * tk - ring - i * tq \
        + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
    lo = jnp.where(off + i * tq + d >= 0, d * rep, n_rows)
    visible = (lo <= row) & (row < (d + window) * rep)
    for h in range(hpg):
        # a row that has seen nothing yet keeps exp(0) out of its sum
        _softmax_update(q_ref[0, h], k, v, visible, acc_ref, m_ref, l_ref,
                        h, scale=scale, drop_masked=True)

    pl.when(j == pl.num_programs(2) - 1)(
        functools.partial(_softmax_out, o_ref, acc_ref, l_ref, hs))


def _chunk_tiles(T: int, ring: int, window: int, rep: int,
                 interpret: bool) -> tuple:
    """(query tile, key view, views a step, steps) of the chunk kernel, in
    rows, from the call's shapes: a view divides the ring, the chunk (the
    keys are the one behind the other) and the query tile (so that every
    query tile's window starts at the same view of its own); a step opens
    as many views as a query tile's window spans, fewer where the float32
    score tile of all of them would pass `flash_decode`'s budget for one.
    A 0 among the first two: no tile split."""
    step = 8 if interpret else 128
    tq = _pick_block(T, _CHUNK_TILE_Q, step)
    tk = _pick_block(math.gcd(ring, tq), _CHUNK_TILE_K, step) if tq else 0
    if not tq or not tk:
        return 0, 0, 0, 0
    # the views from the one that holds the key `window` - 1 behind a
    # tile's first query to the one that holds its last query's own
    n_k = (tq - 1 + ring) // tk - (ring - window + 1) // tk + 1
    group = max(1, min(n_k, _CHUNK_SCORE_BYTES // (tq * rep * tk * 4)))
    return tq, tk, group, -(-n_k // group)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "n_kv_heads", "interpret"))
def window_flash_prefill(q: jnp.ndarray, keys: jnp.ndarray,
                         values: jnp.ndarray, off, *, window: int,
                         scale: float, n_kv_heads: int,
                         interpret: bool = False) -> jnp.ndarray:
    """q (1, T, nh, hs), a chunk of one sequence at positions off ..
    off + T - 1, against `keys` / `values` (R + T, L): positions off - R
    .. off + T - 1 in order, merged lanes. Returns (1, T, nh, hs). A
    query tile i walks the key views from the one that holds the key
    `window` - 1 behind its first query to the one that holds its last
    query's own, several a grid step (`_chunk_tiles`): a static map,
    positions enter the mask alone."""
    _, T, nh, hs = q.shape
    S, L = keys.shape
    R = S - T
    nkv = n_kv_heads
    rep = nh // nkv
    tq, tk, group, n_steps = _chunk_tiles(T, R, window, rep, interpret)
    assert tq and tk, (T, R)
    rows = tq * rep

    def first_tile(i):                  # of query tile i, in key views
        return i * (tq // tk) + (R - window + 1) // tk

    last_tile = S // tk - 1

    # (t, rep)-packed query rows a kv head, zero-extended to their lane
    # group, as `paged_flash_prefill` lays them out
    qz, gl = _lane_group_q(q[0].reshape(T, nkv, rep, hs).transpose(
        1, 0, 2, 3).reshape(nkv, T * rep, hs), L)
    n_groups, hpg = qz.shape[:2]

    def q_idx(g, i, j, off_ref):
        return (g, 0, i, 0)

    def kv_idx(t):
        def idx(g, i, j, off_ref):
            return (jnp.minimum(first_tile(i) + j * group + t, last_tile),
                    g)
        return idx

    def o_idx(g, i, j, off_ref):
        return (i, g)

    kv_specs = [pl.BlockSpec((tk, gl), kv_idx(t)) for t in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups, T // tq, n_steps),
        in_specs=[pl.BlockSpec((1, hpg, rows, gl), q_idx)] + 2 * kv_specs,
        out_specs=pl.BlockSpec((rows, gl), o_idx),
        scratch_shapes=[pltpu.VMEM((hpg, rows, gl), jnp.float32),
                        pltpu.VMEM((hpg, rows, 1), jnp.float32),
                        pltpu.VMEM((hpg, rows, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=float(scale), tq=tq, tk=tk,
                          group=group, ring=R, window=window, hs=hs,
                          rep=rep, first_tile=first_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * rep, L), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="window_flash_prefill",
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(off, jnp.int32), (1,)), qz,
      *(group * [keys] + group * [values]))
    return _heads_of_lanes(out, T, nh, nkv, hs)


def window_flash_prefill_decline(q, keys, n_kv_heads: int, window: int):
    """Why the chunk kernel cannot take this call (None = it can)."""
    if q.ndim != 4 or q.shape[0] != 1 or q.shape[1] <= 1:
        return f"query shape {q.shape} is not one sequence's (1, T>1) chunk"
    _, T, nh, hs = q.shape
    S, L = keys.shape
    rep = nh // max(n_kv_heads, 1)
    tq, tk, group, _ = _chunk_tiles(T, S - T, window, rep,
                                    jax.default_backend() != "tpu")
    if not tq or not tk:
        return (f"a chunk of {T} rows behind a ring of {S - T} has no "
                "tile split")
    why = _common_decline(q, keys, nh, n_kv_heads, hs, tk,
                          f"key tile {tk}")
    if why is not None:
        return why
    if 128 % hs != 0 and hs % 128 != 0:
        return (f"head size {hs} neither divides nor is a multiple of the "
                "128 lanes a head group is cut by")
    gl = max(hs, 128)
    item = jnp.dtype(q.dtype).itemsize
    return _budget_decline(_chunk_vmem_bytes(
        tq * rep, group * tk, gl // hs, gl, item,
        jnp.dtype(keys.dtype).itemsize))


def window_flash_prefill_usable(q, keys, n_kv_heads: int,
                                window: int) -> bool:
    return window_flash_prefill_decline(q, keys, n_kv_heads, window) is None


def window_chunk(q, keys, values, off, *, window: int, scale: float,
                 n_kv_heads: int) -> jnp.ndarray:
    """A chunk's queries (1, T, nh, hs) at `off` against `keys` / `values`
    (R + T, L) at positions off - R on (`ring_logical` + the chunk's own
    rows). Returns (1, T, nh, hs)."""
    from distributed_pytorch_tpu.ops.attention_core import (
        _decode_kernel_wanted, _on_tpu)
    if _decode_kernel_wanted(
            "window_flash_prefill",
            window_flash_prefill_decline(q, keys, n_kv_heads, window)):
        return window_flash_prefill(
            q, keys, values, off, window=window, scale=scale,
            n_kv_heads=n_kv_heads, interpret=not _on_tpu())
    T, hs = q.shape[1], q.shape[-1]
    S = keys.shape[0]
    off = jnp.asarray(off, jnp.int32)
    qpos = off + jnp.arange(T)[:, None]
    kpos = off - (S - T) + jnp.arange(S)[None, :]
    back = qpos - kpos
    visible = (kpos >= 0) & (back >= 0) & (back < window)
    return _masked(q, _split(keys, n_kv_heads, hs)[None],
                   _split(values, n_kv_heads, hs)[None], visible[None],
                   scale)

