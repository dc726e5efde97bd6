"""Latent attention (MLA) over a paged cache of latent rows.

A latent layer ('L', config.py) caches ONE row a position, with no head
axis: `[c | k_r | 0]`, the normed key/value latent `c` (`kv_latent_dim`
lanes), the one rotated key head every query head shares (`rope_head_dim`
lanes), and zeros up to a whole number of 128-lane tiles (`row_lanes`: 512 +
64 -> 640; ops/block_pool.py has the reasoning: only a minor axis of whole
tiles is laid out, written in place and read by a kernel in one and the
same order). A pool leaf is (n_blocks, bs, L), block 0 the null block, and
`block_pool.paged_update` writes a row like any other whose trailing shape
is the pool's.

Head n's key and value at a cached position are `c W_kvb,n = [k_nope_n |
v_n]`: the latent is key and value both. Two forms of one mathematics, as
the engine's programs need them:

* one token of every slot (`latent_decode`), ABSORBED: the caller folds
  W_kvb,n^K into the query, q~_n = q_nope_n W_kvb,n^K^T (`lc` lanes), lays
  `[q~_n | q_rope_n | 0]` over the row's lanes, and a head's score against
  a cached row is ONE product over all L lanes; the output `sum p c` stays
  `lc` wide and the caller applies W_kvb,n^V. `latent_flash_decode` walks a
  sequence's live tiles as `flash_decode._paged_kernel` does (fetches of
  its own into a ring, running ahead into the next sequence, no fetch for
  a dead block) and reads every live row ONCE, for scores and values both.
  The walk's shape is the kernel's own (`_latent_walk`, from the tile's
  bytes and the table's width): a latent tile is key and value at once and
  a third to a fifth of a k/v pair's bytes, so the paged walk's (4, 8) put
  0.65 MB through a softmax update and paid the update's fixed costs three
  times as often a byte. Eight tiles an update, sixteen in the ring, the
  ring topped up by what an update freed, and a sequence's remainder in
  power-of-two updates: the fetches' own pace, 737 GB/s (PERF.md section 6,
  PR 60, has the grid).
* a chunk of T rows of ONE sequence at an offset (`latent_chunk`),
  UP-PROJECTED: a key tile's rows are taken through W_kvb,n inside the
  kernel, `[k_nope_n | v_n] = c W_kvb,n`, and a head attends as any head of
  192 / 128 lanes does. Per (query, key) pair that is 32 x (192 + 128) x 2
  operations against the absorbed form's 32 x (576 + 512) x 2, for (off +
  T) x 8.39 MFLOP of up-projection a call: fewer from the first chunk on
  (PERF.md section 6, PR 59, has both measured alone). `latent_flash_prefill`:
  grid (heads, query tiles, key tiles), a key tile `_CHUNK_GROUP` pool
  blocks under one masked softmax update, a key tile past the query tile's
  last row neither fetched nor computed. A grid step makes the FRONT of its
  key tile (up-projection, one contraction over `[k_nope | k_r | 0]` for
  the scores, mask, the rows' maxima, all left in VMEM) and the BACK of the
  tile before it (`exp`, rescale, `p @ v`): the two halves of one tile are
  a dependence chain, the halves of two tiles are not, and in one basic
  block the MXU works the one while the vector unit works the other
  (PERF.md section 6, PR 65: a grid step 5.21 -> 3.46 us by the device's
  clock; the chain was 7,500 bundles, the shared step is 4,950).

Each kernel has its decline function and its plain XLA twin, the same
lines in `jax.numpy` over a `paged_gather`ed view: what the CPU tests and
every declined call run, said aloud (obs/paths.py, kind `decode_attention`,
the kernel's name or `gather+naive`). `attend_rows` is the general twin (any
batch, any mask, up-projected): the layer's no-cache path, and what the
tests hold the absorbed form equal to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu.compat import tpu_compiler_params
from distributed_pytorch_tpu.ops.block_pool import paged_gather
from distributed_pytorch_tpu.ops.flash_decode import (_CHUNK_SCORE_BYTES,
                                                      _NEG_INF,
                                                      _budget_decline,
                                                      _common_decline,
                                                      _pick_block,
                                                      _softmax_init,
                                                      _stack_tiles)

#: pool blocks one softmax update of the chunk kernel takes side by side
_CHUNK_GROUP = 4
#: tiles one softmax update of the decode kernel takes at most, and the
#: bytes they may weigh together
_DECODE_GROUP = 8
_DECODE_UPDATE_BYTES = 2 * 2 ** 20


def row_lanes(lc: int, dr: int) -> int:
    """Lanes of a cached latent row: `[c (lc) | k_r (dr)]` rounded up to
    whole 128-lane tiles."""
    return -(-(lc + dr) // 128) * 128


def cache_rows(c: jnp.ndarray, k_r: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """(.., lc) normed latents and (.., dr) rotated shared keys -> the
    rows a latent pool keeps, (.., lanes): `[c | k_r | 0]`."""
    pad = lanes - c.shape[-1] - k_r.shape[-1]
    return jnp.concatenate(
        [c, k_r.astype(c.dtype), jnp.zeros(c.shape[:-1] + (pad,), c.dtype)],
        axis=-1)


def causal_visible(pos, T: int, S: int) -> jnp.ndarray:
    """(B | 1, T, S) bool: the query at position pos + t sees the keys at
    positions <= its own. `pos` a scalar or a per-sequence (B,) array."""
    qpos = jnp.reshape(jnp.asarray(pos, jnp.int32), (-1, 1, 1)) \
        + jnp.arange(T)[None, :, None]
    return jnp.arange(S)[None, None, :] <= qpos


# ---------------------------------------------------------------------------
# the XLA path
# ---------------------------------------------------------------------------

def _softmax_rows(s: jnp.ndarray, visible: jnp.ndarray) -> jnp.ndarray:
    """float32 scores (B, nh, T, S) under `visible` (B | 1, T, S): a row
    that sees nothing (a dead slot's) reads zeros, not NaN."""
    vis = visible[:, None]
    s = jnp.where(vis, s, -jnp.inf)
    p = jax.nn.softmax(jnp.where(vis.any(-1, keepdims=True), s, 0.0),
                       axis=-1)
    return jnp.where(vis, p, 0.0)


def attend_rows(q_nope, q_rope, rows, w_kvb, visible,
                scale: float) -> jnp.ndarray:
    """q_nope (B, T, nh, dn), q_rope (B, T, nh, dr) against cached rows
    (B, S, L) `[c | k_r | 0]` under `visible` (B | 1, T, S); w_kvb (lc,
    nh, dn + dv), a head's `[k_nope | v]` columns. Returns (B, T, nh, dv).
    Every row up-projected first (`latent_chunk`'s form). Operands in
    their own dtype, products accumulated and the softmax taken in
    float32, as the kernels do."""
    f32 = jnp.float32
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    lc = w_kvb.shape[0]
    c, k_r = rows[..., :lc], rows[..., lc:lc + dr]
    kv = jnp.einsum("bsl,lnd->bsnd", c, w_kvb,
                    preferred_element_type=f32).astype(c.dtype)
    s = jnp.einsum("btnd,bsnd->bnts", q_nope, kv[..., :dn],
                   preferred_element_type=f32) \
        + jnp.einsum("btnr,bsr->bnts", q_rope, k_r,
                     preferred_element_type=f32)
    p = _softmax_rows(s * scale, visible)
    return jnp.einsum("bnts,bsnv->btnv", p.astype(c.dtype), kv[..., dn:],
                      preferred_element_type=f32).astype(q_nope.dtype)


def latent_decode_xla(q: jnp.ndarray, pool: jnp.ndarray, block_tables,
                      cache_len, *, scale: float, lc: int) -> jnp.ndarray:
    """`latent_flash_decode`'s twin: q (B, nh, L) absorbed query rows
    against the gathered view of every sequence's blocks."""
    rows = paged_gather(pool, block_tables)                 # (B, S, L)
    s = jnp.einsum("bnl,bsl->bns", q, rows,
                   preferred_element_type=jnp.float32) * scale
    visible = jnp.arange(rows.shape[1])[None, None, :] \
        < jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1, 1, 1))
    p = _softmax_rows(s[:, :, None], visible)[:, :, 0]
    return jnp.einsum("bns,bsl->bnl", p.astype(rows.dtype), rows[..., :lc],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_chunk_xla(q_nope, q_rope, pool, w_kvb, block_tables, off, *,
                     scale: float) -> jnp.ndarray:
    """`latent_flash_prefill`'s twin: one sequence's chunk (1, T, nh, .)
    at `off` against the gathered view of its blocks, up-projected."""
    rows = paged_gather(pool, block_tables)                 # (1, S, L)
    return attend_rows(
        q_nope, q_rope, rows, w_kvb,
        causal_visible(off, q_nope.shape[1], rows.shape[1]), scale)


# ---------------------------------------------------------------------------
# one token of every slot
# ---------------------------------------------------------------------------

def _latent_walk(n_max: int, tile_bytes: int) -> tuple[int, int]:
    """(group, depth) of `_decode_kernel`'s walk, from the shapes of the
    call alone. One softmax update takes `group` tiles: a power of two (a
    sequence's remainder goes through the halves below it), as many as
    weigh `_DECODE_UPDATE_BYTES` together, `_DECODE_GROUP` at most and
    never more than a sequence can hold. The ring holds `depth` = two
    updates: one computed, one in flight behind it. 128 x 640 bf16 tiles
    under a table 136 wide give (8, 16): 1.3 MB an update, 2.6 MB in the
    ring, which is where the kernel alone reaches the pace of its fetches
    (PERF.md section 6, PR 60: (4, 8), `_walk_shape`'s answer, stood 30%
    under it, and (16, 32) is no faster)."""
    most = min(_DECODE_GROUP, n_max, _DECODE_UPDATE_BYTES // tile_bytes)
    group = 1 << (max(most, 1).bit_length() - 1)
    return group, 2 * group


def _decode_kernel(cl_ref, bt_ref, q_ref, pool_hbm, o_ref, buf, sem, cur,
                   acc_ref, m_ref, l_ref, *, scale: float, bs: int, lc: int,
                   group: int):
    """A grid step is ONE SEQUENCE and walks all its live tiles: the pool
    stays in HBM, the kernel starts the fetches itself into a ring of
    `depth` (bs, L) tiles, the cursor `cur` = (sequence, block, tiles
    issued, tiles done) running ahead over the live tiles of the whole
    batch. The batch's first step fills the ring; from then on an update
    starts as many fetches as it freed, so the ring stays full up to the
    batch's last tile and no turn asks whether there is room. `group`
    tiles of the sequence share one softmax update; what is left of the
    sequence, fewer than `group`, goes through updates of group / 2, ..,
    1 tiles, each at most once: a row's sum and its order are its own
    length's doing alone. A tile is key and value both: its L lanes
    against the absorbed query rows (nh, L) give every head's scores, its
    first `lc` lanes the values."""
    b = pl.program_id(0)
    depth, n_max = buf.shape[0], bt_ref.shape[1]

    def n_blocks(i):
        return jax.lax.min(
            jax.lax.div(jax.lax.max(cl_ref[i], 1) - 1, bs) + 1, n_max)

    def fetch(blk, slot):
        return pltpu.make_async_copy(pool_hbm.at[blk], buf.at[slot],
                                     sem.at[slot])

    def issue(_, carry):
        pb, pj, issued = cur[0], cur[1], cur[2]

        @pl.when(pb < pl.num_programs(0))
        def _():
            fetch(bt_ref[pb, pj], jax.lax.rem(issued, depth)).start()
            cur[2] = issued + 1
            end = pj + 1 >= n_blocks(pb)
            cur[0] = jax.lax.select(end, pb + 1, pb)
            cur[1] = jax.lax.select(end, 0, pj + 1)
        return carry

    @pl.when(b == 0)
    def _():
        for i in range(4):
            cur[i] = 0
        jax.lax.fori_loop(0, depth, issue, 0)

    _softmax_init(acc_ref, m_ref, l_ref)
    n, nb = cl_ref[b], n_blocks(b)

    def update(live, first):
        done = cur[3]
        slots = [jax.lax.rem(done + t, depth) for t in range(live)]
        for slot in slots:
            fetch(0, slot).wait()
        q = q_ref[0]                                         # (nh, L)
        scores = []
        for t, slot in enumerate(slots):
            s = jax.lax.dot_general(
                q, buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (nh, bs)
            kpos = (first + t) * bs + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            scores.append(jnp.where(kpos < n, s, _NEG_INF))
        m_prev = m_ref[:]
        m_new = m_prev
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new, pv = l_ref[:] * alpha, None
        for s, slot in zip(scores, slots):
            p = jnp.exp(s - m_new)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            c = buf[slot][:, :lc]
            d = jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (nh, lc)
            pv = d if pv is None else pv + d
        m_ref[:] = m_new
        l_ref[:] = l_new
        acc_ref[:] = acc_ref[:] * alpha + pv
        cur[3] = done + live
        jax.lax.fori_loop(0, live, issue, 0)

    def whole(g, carry):
        update(group, g * group)
        return carry

    jax.lax.fori_loop(0, jax.lax.div(nb, group), whole, 0)
    part = group // 2
    while part:
        left = jax.lax.rem(nb, 2 * part)     # tiles behind the larger updates
        pl.when(left >= part)(functools.partial(update, part, nb - left))
        part //= 2
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _tile_bytes(pool) -> int:
    return pool.shape[1] * pool.shape[2] * jnp.dtype(pool.dtype).itemsize


@functools.partial(jax.jit, static_argnames=("scale", "lc", "interpret"))
def latent_flash_decode(q: jnp.ndarray, pool: jnp.ndarray, block_tables,
                        cache_len, *, scale: float, lc: int,
                        interpret: bool = False) -> jnp.ndarray:
    """One token of every sequence over a paged latent cache: q (B, nh, L)
    absorbed query rows (`[q~ | q_rope | 0]`, laid out as `cache_rows` lays
    a cached row) against the pool (n_blocks, bs, L)
    through per-sequence block tables (B, max_blocks) and valid lengths
    `cache_len` (B,). Returns (B, nh, lc), `sum p c` a head: the caller
    applies W_kvb^V. Gate with `latent_flash_decode_decline`."""
    B, nh, L = q.shape
    bs = pool.shape[1]
    n_max = block_tables.shape[1]
    group, depth = _latent_walk(n_max, _tile_bytes(pool))

    def q_idx(b, cl_ref, bt_ref):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, nh, L), q_idx),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nh, lc), q_idx),
        scratch_shapes=[
            pltpu.VMEM((depth, bs, L), pool.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((nh, lc), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=float(scale), bs=bs, lc=lc,
                          group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, lc), q.dtype),
        # the cursor and the fetches in flight pass from a sequence to the
        # next: the grid runs in order
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        name="latent_flash_decode",
        interpret=interpret,
    )(jnp.asarray(cache_len, jnp.int32).reshape(B),
      jnp.asarray(block_tables, jnp.int32), q, pool)


def _shape_decline(q, pool):
    """Checks both gates share: a pool of latent rows in the queries'
    dtype and whole lane tiles, then `flash_decode._common_decline`'s
    (query dtype, a block the backend splits by, no live mesh)."""
    if pool.ndim != 3 or pool.dtype != q.dtype:
        return (f"pool {pool.dtype}{list(pool.shape)} is not (n_blocks, bs, "
                f"lanes) of the queries' {q.dtype}")
    bs, L = pool.shape[1:]
    step = 128 if jax.default_backend() == "tpu" else 8
    if L % step != 0:
        return f"rows of {L} lanes are no whole tiles of {step}"
    return _common_decline(q, pool, 1, 1, 8, bs, f"pool block size {bs}")


def _decode_vmem_bytes(q, pool, block_tables, lc: int) -> int:
    """VMEM one grid step of `latent_flash_decode` holds: the ring of
    `_latent_walk`'s depth, the double-buffered query and output blocks,
    the float32 softmax state, and an update's scores, probabilities and
    partial sums."""
    _, nh, L = q.shape
    group, depth = _latent_walk(block_tables.shape[1], _tile_bytes(pool))
    item = jnp.dtype(q.dtype).itemsize
    return (depth * _tile_bytes(pool) + 2 * nh * (L + lc) * item
            + nh * (lc + 2 * 128) * 4 + 2 * nh * lc * 4
            + 3 * group * nh * pool.shape[1] * 4)


def latent_flash_decode_decline(q, pool, block_tables, lc: int):
    """Why `latent_flash_decode` cannot take this call (None = it can)."""
    if q.ndim != 3:
        return f"query shape {q.shape} is not (B, nh, lanes)"
    why = _shape_decline(q, pool)
    if why is not None:
        return why
    _, nh, L = q.shape
    step = 128 if jax.default_backend() == "tpu" else 8
    if L != pool.shape[2] or lc % step != 0 or lc > L or nh % 8 != 0:
        return (f"{nh} query rows of {L} lanes, {lc} of them the latent, "
                f"against rows of {pool.shape[2]}")
    return _budget_decline(_decode_vmem_bytes(q, pool, block_tables, lc))


def latent_flash_decode_usable(q, pool, block_tables, lc: int) -> bool:
    return latent_flash_decode_decline(q, pool, block_tables, lc) is None


def latent_decode(q, pool, block_tables, cache_len, *, scale: float,
                  lc: int) -> jnp.ndarray:
    """q (B, nh, L) absorbed query rows, one token a sequence -> (B, nh,
    lc): the kernel where its gate allows, else its twin, said aloud."""
    from distributed_pytorch_tpu.ops.attention_core import (
        _decode_kernel_wanted, _on_tpu)
    if _decode_kernel_wanted(
            "latent_flash_decode",
            latent_flash_decode_decline(q, pool, block_tables, lc)):
        return latent_flash_decode(q, pool, block_tables, cache_len,
                                   scale=scale, lc=lc,
                                   interpret=not _on_tpu())
    return latent_decode_xla(q, pool, block_tables, cache_len, scale=scale,
                             lc=lc)


# ---------------------------------------------------------------------------
# a chunk of one sequence
# ---------------------------------------------------------------------------

def _chunk_tiles(T: int, n_max: int, bs: int) -> tuple[int, int]:
    """(tq, group) of the chunk kernel's grid step: a key tile is `group`
    pool blocks (`_CHUNK_GROUP`, or the whole table where it is narrower),
    a query tile the largest divisor of T in whole sublanes whose float32
    score tile against it stays inside `_CHUNK_SCORE_BYTES`: the whole
    chunk at 1,024 rows, so that a key tile is up-projected once a head."""
    group = max(1, min(n_max, _CHUNK_GROUP))
    rows = _CHUNK_SCORE_BYTES // (group * bs * 4)
    return _pick_block(T, max(rows, 8), 8) or T, group


def _prefill_kernel(meta_ref, bt_ref, q_ref, w_ref, *refs, scale: float,
                    bs: int, lc: int, dn: int, group: int):
    """Grid (heads, query tiles, key tiles). A key tile is worked in two
    halves a step apart, so that the MXU's half of one tile and the vector
    unit's half of the tile before it are ONE basic block and run side by
    side:

    * the front of key tile j: `group` pool blocks stacked into one tile,
      its latents through the head's W_kvb,n (lc, dn + dv) for its keys
      and values, ONE contraction of the query tile `[q_nope | q_rope | 0]`
      with `[k_nope | k_r | 0]` (the row's last lanes as cached) for the
      scores under the causal mask of the rows' global positions. Scores,
      values and the rows' maxima are left in VMEM (`s_ref`, `v_ref`,
      `top_ref`);
    * the back of key tile j - 1: ONE softmax update from what its front
      left: `exp` over the tile, the rescale, `p @ v`.

    Step 0 is a front alone, the query tile's last live step ends with the
    back of its own tile and writes the output. A key tile whose first key
    lies past the query tile's last row is neither fetched (views past the
    last needed block hold it again) nor computed; the grid's third axis
    ends with the chunk's last row."""
    c_refs = refs[:group]
    o_ref, acc_ref, m_ref, l_ref, s_ref, v_ref, top_ref = refs[group:]
    i, j = pl.program_id(1), pl.program_id(2)
    tq, keys = q_ref.shape[1], group * bs
    first = meta_ref[0] + i * tq
    # key tiles this query tile sees: those whose first key its last row sees
    n_live = jnp.minimum(jax.lax.div(first + tq - 1, keys) + 1,
                         pl.num_programs(2))

    def front():
        rows = _stack_tiles([r[0] for r in c_refs])          # (keys, L)
        kv = jax.lax.dot_general(
            rows[:, :lc], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(rows.dtype)
        s = jax.lax.dot_general(
            q_ref[0], jnp.concatenate([kv[:, :dn], rows[:, lc:]], axis=1),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (tq, keys)
        kpos = j * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        qpos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        s = jnp.where(kpos <= qpos, s, _NEG_INF)
        s_ref[:] = s
        v_ref[:] = kv[:, dn:]
        top_ref[:] = jnp.max(s, axis=-1, keepdims=True)

    def back():
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, top_ref[:])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_ref[:] - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        _softmax_init(acc_ref, m_ref, l_ref)
        front()

    @pl.when((j > 0) & (j < n_live))
    def _():
        # the back reads `s_ref` before the front writes it: said in this
        # order, scheduled side by side
        back()
        front()

    @pl.when(j == n_live - 1)
    def _():
        back()
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_flash_prefill(q_nope, q_rope, pool, w_kvb, block_tables, off, *,
                         scale: float, interpret: bool = False):
    """A chunk of ONE sequence over a paged latent cache: q_nope (1, T, nh,
    dn) and q_rope (1, T, nh, dr, rotated) at global positions [off, off +
    T) against the pool (n_blocks, bs, L) through the sequence's block
    table (1, max_blocks); w_kvb (lc, nh, dn + dv). The chunk's own rows
    must already be in the pool. Returns (1, T, nh, dv). Gate with
    `latent_flash_prefill_decline`."""
    _, T, nh, dn = q_nope.shape
    bs, L = pool.shape[1:]
    lc = w_kvb.shape[0]
    dv = w_kvb.shape[2] - dn
    n_max = block_tables.shape[1]
    tq, group = _chunk_tiles(T, n_max, bs)
    meta = jnp.reshape(jnp.asarray(off, jnp.int32), (1,))
    bt = jnp.asarray(block_tables, jnp.int32).reshape(n_max)
    # a head's query rows against a key's `[k_nope | k_r | 0]`: the rotated
    # part zero-extended to the row's last lanes, once a call
    q = jnp.concatenate([q_nope[0], q_rope[0]], axis=-1).transpose(1, 0, 2)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, dn + L - lc - q.shape[2])))
    w = w_kvb.transpose(1, 0, 2)                            # (nh, lc, dn+dv)

    def q_idx(n, i, j, meta_ref, bt_ref):
        return (n, i, 0)

    def w_idx(n, i, j, meta_ref, bt_ref):
        return (n, 0, 0)

    def c_idx(t):
        def idx(n, i, j, meta_ref, bt_ref):
            last = jax.lax.div(meta_ref[0] + (i + 1) * tq - 1, bs)
            return (bt_ref[jnp.minimum(j * group + t,
                                       jnp.minimum(last, n_max - 1))], 0, 0)
        return idx

    def o_idx(n, i, j, meta_ref, bt_ref):
        return (i, n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nh, T // tq, jnp.minimum(
            jax.lax.div(meta[0] + T - 1, group * bs) + 1,
            -(-n_max // group))),
        in_specs=[pl.BlockSpec((1, tq, dn + L - lc), q_idx),
                  pl.BlockSpec((1, lc, dn + dv), w_idx)]
        + [pl.BlockSpec((1, bs, L), c_idx(t)) for t in range(group)],
        out_specs=pl.BlockSpec((tq, dv), o_idx),
        scratch_shapes=[pltpu.VMEM((tq, dv), jnp.float32),
                        pltpu.VMEM((tq, 1), jnp.float32),
                        pltpu.VMEM((tq, 1), jnp.float32),
                        pltpu.VMEM((tq, group * bs), jnp.float32),
                        pltpu.VMEM((group * bs, dv), pool.dtype),
                        pltpu.VMEM((tq, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=float(scale), bs=bs, lc=lc,
                          dn=dn, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, nh * dv), q_nope.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="latent_flash_prefill",
        interpret=interpret,
    )(meta, bt, q, w, *(group * [pool]))
    return out.reshape(1, T, nh, dv)


def _prefill_vmem_bytes(q_nope, pool, w_kvb, block_tables) -> int:
    """VMEM one grid step of `latent_flash_prefill` holds: the
    double-buffered query, weight, output and pool blocks, the stacked key
    tile and what its front makes of it (`[k_nope | v]` in float32 and the
    pool's dtype, the scores' key operand), the float32 softmax state with
    the rows' maxima, the values and the score tile a front leaves for the
    next step's back, and a step's own score tile, `p` and its cast."""
    _, T, _, dn = q_nope.shape
    bs, L = pool.shape[1:]
    lc, dv = w_kvb.shape[0], w_kvb.shape[2] - dn
    tq, group = _chunk_tiles(T, block_tables.shape[1], bs)
    keys, item = group * bs, jnp.dtype(q_nope.dtype).itemsize
    return (2 * tq * (dn + L - lc + dv) * item + 2 * lc * (dn + dv) * item
            + 3 * keys * L * item + keys * (dn + dv) * (4 + item)
            + keys * (dn + L - lc + dv) * item
            + tq * (dv + 3 * 128) * 4 + 4 * tq * keys * 4)


def latent_flash_prefill_decline(q_nope, q_rope, pool, w_kvb, block_tables):
    """Why `latent_flash_prefill` cannot take this call (None = it can)."""
    if q_nope.ndim != 4 or q_nope.shape[0] != 1 or q_nope.shape[1] <= 1:
        return (f"query shape {q_nope.shape} is not one sequence's "
                "(1, T>1) chunk")
    _, T, nh, dn = q_nope.shape
    if T % 8 != 0:
        return f"chunk length {T} is not a sublane (8) multiple"
    why = _shape_decline(q_nope, pool)
    if why is not None:
        return why
    bs, L = pool.shape[1:]
    lc, dr = w_kvb.shape[0], q_rope.shape[-1]
    dv = w_kvb.shape[2] - dn
    step = 128 if jax.default_backend() == "tpu" else 8
    if any(d % step for d in (lc, dn, dv)) or lc + dr > L:
        return (f"a latent of {lc}, heads of {dn} + {dr} / {dv} against "
                f"rows of {L} lanes: not whole tiles of {step}")
    return _budget_decline(
        _prefill_vmem_bytes(q_nope, pool, w_kvb, block_tables))


def latent_flash_prefill_usable(q_nope, q_rope, pool, w_kvb,
                                block_tables) -> bool:
    return latent_flash_prefill_decline(q_nope, q_rope, pool, w_kvb,
                                        block_tables) is None


def latent_chunk(q_nope, q_rope, pool, w_kvb, block_tables, off, *,
                 scale: float) -> jnp.ndarray:
    """One sequence's chunk (1, T, nh, .) at `off` -> (1, T, nh, dv): the
    kernel where its gate allows, else its twin, said aloud."""
    from distributed_pytorch_tpu.ops.attention_core import (
        _decode_kernel_wanted, _on_tpu)
    if _decode_kernel_wanted(
            "latent_flash_prefill",
            latent_flash_prefill_decline(q_nope, q_rope, pool, w_kvb,
                                         block_tables)):
        return latent_flash_prefill(q_nope, q_rope, pool, w_kvb,
                                    block_tables, off, scale=scale,
                                    interpret=not _on_tpu())
    return latent_chunk_xla(q_nope, q_rope, pool, w_kvb, block_tables, off,
                            scale=scale)
