"""Pallas TPU split-KV flash-decode: single-query-per-sequence attention.

Decode attention is memory-bound: one query row per sequence against an
(S, n_kv, hs) cache — arithmetic intensity ~1 FLOP/byte, so the only
number that matters is how few bytes move and how well the move overlaps.
The naive einsum path (ops/attention_core.py `_naive_sdpa`, "Used for
decode steps") materializes repeated K/V per GQA query head, computes a
(B, nh, 1, S) f32 score tensor in HBM, and always streams the FULL cache
buffer even when a sequence occupies three rows of a 1024-slot cache.

This kernel is the flash-decode treatment (split-KV, cf. the
FlashAttention decoding variant and the TPU serving stacks' ragged
single-token attention):

* **Split-KV grid**: grid (B, S/block_s) with the KV length split across
  grid steps; the online-softmax state (running max m, normalizer l, f32
  accumulator) lives in VMEM scratch that persists across the kv
  dimension, exactly like the training kernel (ops/flash_attention.py) —
  attention probabilities never exist in HBM.
* **GQA head packing**: the query is reshaped (B, nh, hs) ->
  (B, n_kv, rep, hs), so each kv head's `rep = nh/n_kv` query heads sit
  in the SUBLANE dimension of one (rep, hs) x (hs, block_s) MXU tile —
  K/V are read once per kv head, never materialized per query head.
* **Per-sequence `cache_len` scalar-prefetch**
  (`pltpu.PrefetchScalarGridSpec`, same idiom as the grouped-matmul
  dispatch's tile->expert map): the (B,) valid-length vector is in SMEM
  before the body runs, so (contiguous and int8 paged kernels) grid steps
  past a sequence's last valid block are predicated off with `pl.when`
  AND their kv index map clamps to the last visible block — the
  revolving-buffer DMA sees an unchanged index and issues no fetch. A
  sequence three tokens into a 1024-slot cache costs one grid step, not
  eight: padded slots cost zero compute and zero HBM traffic.
* The last partial block masks `kpos >= cache_len` to a large negative
  (NaN-free) before the max/sum update.
* **The paged float kernel walks tiles, not a grid** (`paged_flash_decode`,
  `_paged_kernel`): grid (B,), a step is one sequence. The pools stay in
  HBM and the kernel starts the tile fetches itself into a ring of
  `depth` (bs, L) k/v pairs, a cursor in SMEM running ahead over the live
  tiles of the whole batch, so fetches are in flight across a sequence's
  end and no step exists for a dead block; up to `group` tiles of a
  sequence share ONE softmax update. `_walk_shape` reads both from a
  tile's bytes and the table's width: (4, 8) in every accepted cell. The
  latent kernel (ops/latent_attention.py) walks so with a shape of its
  own, `_latent_walk`: its 160 KB tiles want (8, 16), PR 60. (One BlockSpec
  tile a grid step (B, max_blocks) looked one step ahead and held on a
  sequence's last block: 1.9 us a live tile of 1.04 of DMA. PERF.md, PR 44.)
* **Chunked-prefill variant** (`paged_flash_prefill`, round 12): the
  paged decode kernel generalized from one query row per sequence to a
  (t, rep)-packed query tile of ONE sequence — a prefill chunk written
  at an arbitrary block-aligned offset attends the sequence's own prior
  blocks plus its in-chunk causal prefix, with per-row global positions
  in the mask. This is the device half of the engine's fused
  chunk+decode step (engine/decode.py `prefill_chunk`); bf16 and int8
  pools ride the same block-table index map. A grid step of the float
  body holds a query tile that fits (`_chunk_shape`) against a key tile
  of several pool blocks, each block a view of the pool with an index
  map of its own, under ONE masked softmax update; the key axis of the
  grid is as long as the call's offset needs (measurements: PERF.md
  section 6, PR 50).
* **The paged kernels read merged-lane pools** (ops/block_pool.py
  `kv_lanes`): a float k/v pool is (n_blocks, bs, L), every kv head's hs
  lanes side by side and L rounded up to a multiple of 128, because that
  is the one shape whose default device layout, XLA's in-place row write
  and a Pallas operand agree on — so the kernel's operand IS the buffer
  the step's write produced (a (.., 25, 64) pool was copied whole, twice
  a layer, every step). A (bs, L) tile holds no padding between heads
  (the head-major tile padded 25 -> 32 and 64 -> 128: 2.56x the bytes),
  and no body slices it by head or transposes it: the query rows are
  zero-extended to the tile's lanes instead (a row holds its head's hs
  values in its kv head's lanes, zeros elsewhere), one product yields
  every head's scores, one more accumulates p @ v over all lanes, and
  each row keeps its own head's lanes at the end. Decode does that over
  the whole L (memory-bound: the surplus multiplies are free); the
  chunk kernel, which is not, per 128-lane group of whole heads, where a
  zero-extended 64-wide head costs the same MXU pass as the bare one.
  The int8 pools keep the head axis and the head-major `_q8` bodies.

Contract: gate with `flash_decode_usable` (or its `*_decline` twin, which
says WHY) first. `FLASH_DECODE=auto|on|off` (read per call, so tests can
flip it): 'auto' uses the kernel on TPU only — where the gate declines
the naive path carries the call (identical semantics, more HBM traffic)
and the choice is recorded (obs/paths.py); 'on' asks for the kernel by
name — on a TPU backend a decline is then an error naming the gate
(ops/attention_core.py `_decode_kernel_wanted`), off-TPU it means
interpret mode for the CPU parity tests; 'off' pins the naive path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu import config
from distributed_pytorch_tpu.compat import (VMEM_LIMIT_BYTES,
                                            tpu_compiler_params)

# KV-length tile (lane dimension of the score tiles) of the CONTIGUOUS
# kernel `flash_decode`, read from the environment at import. No serving
# cell runs that kernel (the engine's caches are paged, and a paged tile is
# a pool block), so ROADMAP S5, which was settled on the paged kernel
# (PR 44), left it unmeasured and a knob.
DEFAULT_BLOCK_S = config.knob("FLASH_DECODE_BLOCK")

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free


def decode_mode() -> str:
    """'auto' | 'on' | 'off' — read per call (tests monkeypatch env)."""
    return config.knob("FLASH_DECODE")


def _pick_block(n: int, preferred: int, step: int) -> int:
    """Largest divisor of n that is <= preferred and a multiple of `step`;
    0 when none exists (gate then declines)."""
    b = min(preferred, n)
    b -= b % step
    while b > step and n % b != 0:
        b -= step
    return b if (b >= step and n % b == 0) else 0


def _kernel(cl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_s: int):
    b, j = pl.program_id(0), pl.program_id(1)
    n = cl_ref[b]
    last_j = jax.lax.div(jnp.maximum(n, 1) - 1, block_s)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j <= last_j)
    def _():
        q = q_ref[0]                            # (nkv, rep, hs)
        # cache tiles arrive in the model's natural (block_s, nkv, hs)
        # layout; relayout head-major in VMEM (the slab-kernel trick —
        # no HBM transpose of the big cache buffers)
        k = k_ref[0].transpose(1, 0, 2)         # (nkv, block_s, hs)
        v = v_ref[0].transpose(1, 0, 2)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (nkv, rep, bs) f32
        kpos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < n, s, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _kernel_q8(cl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
               acc_ref, m_ref, l_ref, *, scale: float, block_s: int):
    """int8-cache variant: the K/V tiles arrive as int8 codes (half the
    DMA bytes of bf16) with their per-(row, kv-head) scale rows riding the
    same index map — the cache_len block-skip logic is shared, so dead
    blocks skip compute AND the (now half-sized) DMA. Dequantization
    happens in VMEM registers: the codes cast to the compute dtype on the
    way into the MXU tile, and the row scales fold into the score /
    probability tiles (exact algebra — k's scale is constant along each
    score column, v's along each summed row), so a dequantized K/V buffer
    never exists anywhere."""
    b, j = pl.program_id(0), pl.program_id(1)
    n = cl_ref[b]
    last_j = jax.lax.div(jnp.maximum(n, 1) - 1, block_s)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j <= last_j)
    def _():
        q = q_ref[0]                            # (nkv, rep, hs) bf16/f32
        dt = q.dtype
        k = k_ref[0].transpose(1, 0, 2).astype(dt)   # (nkv, bs, hs) codes
        v = v_ref[0].transpose(1, 0, 2).astype(dt)
        # scale rows (block_s, nkv, 1) -> (nkv, 1, block_s): one scale per
        # key row, broadcast over the rep (query-head) sublane dim
        ks = ks_ref[0].transpose(1, 2, 0)
        vs = vs_ref[0].transpose(1, 2, 0)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = s * (ks * scale)                    # dequant k + softmax scale
        kpos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < n, s, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            (p * vs).astype(dt), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # dequant v folded into p

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 cache_len: jnp.ndarray, *, scale: float,
                 k_scale: jnp.ndarray = None, v_scale: jnp.ndarray = None,
                 block_s: int = 0, interpret: bool = False) -> jnp.ndarray:
    """Single-token cached attention: q (B, nh, hs) against k/v
    (B, S, n_kv, hs) cache buffers with per-sequence valid lengths
    `cache_len` (B,) int32 (rows [0, cache_len) are attended; the rest are
    dead slots). Returns (B, nh, hs). Gate with `flash_decode_usable`.

    With `k_scale`/`v_scale` (B, S, n_kv, 1) — the int8-cache scale
    sidecars (ops/quant.py) — k/v hold int8 codes and the `_kernel_q8`
    variant dequantizes in VMEM (half the cache DMA bytes; the block-skip
    logic is shared)."""
    B, nh, hs = q.shape
    S, nkv = k.shape[1], k.shape[2]
    rep = nh // nkv
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "int8 cache needs both k_scale and v_scale"
    block_s = block_s or _pick_block(S, DEFAULT_BLOCK_S,
                                     8 if interpret else 128)
    assert block_s and S % block_s == 0, (
        f"no usable KV split for S={S} — gate with flash_decode_usable")

    cl = jnp.asarray(cache_len, jnp.int32).reshape(B)
    q4 = q.reshape(B, nkv, rep, hs)

    def q_idx(b, j, cl_ref):
        return (b, 0, 0, 0)

    def kv_idx(b, j, cl_ref):
        # clamp skipped blocks to the sequence's last visible one: the
        # revolving buffer sees an unchanged index -> no DMA for dead slots
        last = jax.lax.div(jnp.maximum(cl_ref[b], 1) - 1, block_s)
        return (b, jnp.minimum(j, last), 0, 0)

    in_specs = [pl.BlockSpec((1, nkv, rep, hs), q_idx)]
    operands = [q4]
    if quantized:
        # scale rows share the kv index map, so skipped blocks skip their
        # (tiny) DMA too
        in_specs += [
            pl.BlockSpec((1, block_s, nkv, hs), kv_idx),
            pl.BlockSpec((1, block_s, nkv, 1), kv_idx),
            pl.BlockSpec((1, block_s, nkv, hs), kv_idx),
            pl.BlockSpec((1, block_s, nkv, 1), kv_idx),
        ]
        operands += [k, k_scale.astype(jnp.float32),
                     v, v_scale.astype(jnp.float32)]
        body = _kernel_q8
    else:
        in_specs += [
            pl.BlockSpec((1, block_s, nkv, hs), kv_idx),
            pl.BlockSpec((1, block_s, nkv, hs), kv_idx),
        ]
        operands += [k, v]
        body = _kernel

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // block_s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, rep, hs), q_idx),
        scratch_shapes=[
            pltpu.VMEM((nkv, rep, hs), jnp.float32),
            pltpu.VMEM((nkv, rep, 1), jnp.float32),
            pltpu.VMEM((nkv, rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(body, scale=float(scale), block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, rep, hs), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        name="flash_decode" + ("_q8" if quantized else ""),
        interpret=interpret,
    )(cl, *operands)
    return out.reshape(B, nh, hs)


def _lane_head(shape, hs: int) -> jnp.ndarray:
    """int32 `shape`: the head whose hs lanes each position's lane (last
    axis) falls in, `lane // hs`. The merged-lane bodies use it to pick
    each head's own output lanes out of a full-width accumulator."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return jax.lax.div(lane, jnp.int32(hs))


#: what the walk's tile ring may take of the scoped-VMEM limit
_WALK_VMEM_BYTES = VMEM_LIMIT_BYTES // 8


def _walk_shape(n_max: int, pair_bytes: int) -> tuple[int, int]:
    """(group, depth) of `_paged_kernel`'s walk, from the shapes of the
    call alone: `depth` k/v tile pairs of `pair_bytes` sit in VMEM, as
    many as `_WALK_VMEM_BYTES` holds between 2 and 8, and one joint
    softmax update takes up to `group` = half of them (never more than a
    sequence can hold), so that as many fetches again fly behind the tiles
    being computed. gpt2-xl's 852 KB pairs give (4, 8)."""
    depth = max(2, min(8, _WALK_VMEM_BYTES // max(pair_bytes, 1)))
    group = max(1, min(depth // 2, n_max))
    return group, depth


def _paged_kernel(cl_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                  sem, cur, acc_ref, m_ref, l_ref, *, scale: float, bs: int,
                  hs: int, rep: int, group: int):
    """Paged decode over MERGED-LANE pools (ops/block_pool.py `kv_lanes`):
    a k/v tile is (bs, L), every kv head's hs lanes side by side. The
    query rows arrive zero-extended to L lanes — row (r, g) holds query
    head g*rep + r in kv head g's lanes and zeros elsewhere — so ONE
    (R, L) x (L, bs) product gives every head's scores (the other heads'
    lanes multiply zeros, exactly) and ONE (R, bs) x (bs, L) product
    accumulates p @ v for all lanes; each row keeps only its own head's
    lanes at the end. No per-head slice at a 64-lane offset, no in-VMEM
    transpose of the tile, and the output is written lane-dense in the
    model's (.., n_kv * hs) order.

    A grid step is ONE SEQUENCE and walks all its live tiles. The pools
    stay in HBM (`pl.ANY`) and the kernel starts the fetches itself, into
    a ring of `depth` k/v tile pairs: a cursor (`cur`: sequence, block,
    tiles issued, tiles done; SMEM, kept from grid step to grid step) runs
    ahead over the live tiles of the WHOLE batch in the order the walk
    consumes them, so the first tiles of the next sequence are in flight
    while this one's last are computed. Nothing is fetched for a dead
    block, and there is no grid step without a live tile. Up to `group`
    tiles of the sequence go through ONE softmax update (all their score
    tiles, one row max over them, one `alpha`, the sum of their p @ v, one
    rescale of the accumulator): the tiles' products do not wait on each
    other. What a sequence's output is computed from, and in which
    order, is its own length's doing alone."""
    b = pl.program_id(0)
    depth, n_max = kbuf.shape[0], bt_ref.shape[1]

    def n_blocks(i):
        return jax.lax.min(
            jax.lax.div(jax.lax.max(cl_ref[i], 1) - 1, bs) + 1, n_max)

    def fetch(blk, slot):
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot],
                                      sem.at[1, slot]))

    def issue(_, carry):
        """Start the fetch of the tile under the cursor into the ring's
        next slot and move the cursor on, while a sequence is left and the
        ring has a slot whose tile is done."""
        pb, pj, issued = cur[0], cur[1], cur[2]

        @pl.when((pb < pl.num_programs(0)) & (issued - cur[3] < depth))
        def _():
            for copy in fetch(bt_ref[pb, pj], jax.lax.rem(issued, depth)):
                copy.start()
            cur[2] = issued + 1
            end = pj + 1 >= n_blocks(pb)
            cur[0] = jax.lax.select(end, pb + 1, pb)
            cur[1] = jax.lax.select(end, 0, pj + 1)
        return carry

    @pl.when(b == 0)
    def _():
        for i in range(4):
            cur[i] = 0

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    n, nb = cl_ref[b], n_blocks(b)

    def update(live, first, done):
        """One joint update over logical blocks [first, first + live): the
        ring's slots from `done` on."""
        slots = [jax.lax.rem(done + t, depth) for t in range(live)]
        for slot in slots:
            for copy in fetch(0, slot):
                copy.wait()
        q = q_ref[0]                                         # (R, L)
        scores = []
        for t, slot in enumerate(slots):
            s = jax.lax.dot_general(
                q, kbuf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (R, bs) f32
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
            v = vbuf[slot]
            d = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # (R, L) f32
            pv = d if pv is None else pv + d
        m_ref[:] = m_new
        l_ref[:] = l_new
        acc_ref[:] = acc_ref[:] * alpha + pv

    def body(g, carry):
        # top the ring up: all of it at the batch's first tile, then what
        # the last update freed
        jax.lax.fori_loop(0, depth, issue, 0)
        done, first = cur[3], g * group
        size = jax.lax.min(nb - first, group)
        for live in range(1, group + 1):
            pl.when(size == live)(
                functools.partial(update, live, first, done))
        cur[3] = done + size
        return carry

    jax.lax.fori_loop(0, jax.lax.div(nb + group - 1, group), body, 0)

    out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
    g_pad = out.shape[0] // rep
    shape = (g_pad, out.shape[1])
    own = _lane_head(shape, hs) == jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)                    # row g keeps kv head g's lanes
    for r in range(rep):                        # rows (r, g): one slab per r
        slab = out[r * g_pad:(r + 1) * g_pad]
        o_ref[0, r:r + 1, :] = jnp.sum(
            jnp.where(own, slab, 0.0), axis=0,
            keepdims=True).astype(o_ref.dtype)


def _paged_body_q8(cl_ref, bt_ref, *args, scale: float, block_s: int):
    del bt_ref
    _kernel_q8(cl_ref, *args, scale=scale, block_s=block_s)


def _zero_extend_q(q: jnp.ndarray, nkv: int, g_pad: int,
                   lanes: int) -> jnp.ndarray:
    """q (B, nh, hs) -> (B, rep * g_pad, lanes): row (r, g) is query head
    g*rep + r laid into kv head g's hs lanes of a merged-lane row, zeros
    everywhere else (`_paged_kernel`). g_pad >= nkv pads each r-slab to a
    sublane multiple."""
    B, nh, hs = q.shape
    rep = nh // nkv
    q4 = q.reshape(B, nkv, rep, hs).transpose(0, 2, 1, 3)   # (B, rep, g, hs)
    own = jnp.eye(nkv, dtype=bool)[None, None, :, :, None]
    qz = jnp.where(own, q4[:, :, :, None, :], 0) \
        .reshape(B, rep, nkv, nkv * hs)
    qz = jnp.pad(qz, ((0, 0), (0, 0), (0, g_pad - nkv),
                      (0, lanes - nkv * hs)))
    return qz.reshape(B, rep * g_pad, lanes)


def _lane_group_q(q3: jnp.ndarray, lanes: int) -> tuple:
    """(t, rep)-packed chunk rows a kv head, q3 (n_kv, rows, hs), laid out
    for the chunk kernels: (n_groups, heads a group, rows, gl), a lane
    group the gl = max(hs, 128) lanes of a merged-lane tile that hold whole
    heads, each head's rows zero-extended to them (own hs lanes, zeros in
    its neighbours'); heads past n_kv are the tile's pad lanes. Returns it
    with gl."""
    nkv, rows, hs = q3.shape
    gl = max(hs, 128)
    hpg, n_groups = gl // hs, lanes // gl
    q3 = jnp.pad(q3, ((0, n_groups * hpg - nkv), (0, 0), (0, 0))) \
        .reshape(n_groups, hpg, rows, hs)
    own = jnp.eye(hpg, dtype=bool)[None, :, None, :, None]
    return jnp.where(own, q3[:, :, :, None, :], 0) \
        .reshape(n_groups, hpg, rows, gl), gl


def _heads_of_lanes(out: jnp.ndarray, T: int, nh: int, nkv: int,
                    hs: int) -> jnp.ndarray:
    """A chunk kernel's (T * rep, L) result, lane l of row (t, r) head
    (l // hs) * rep + r, as (1, T, nh, hs)."""
    return out[:, :nkv * hs].reshape(T, nh // nkv, nkv, hs) \
        .transpose(0, 2, 1, 3).reshape(1, T, nh, hs)


# The two paged entry points are jitted on their own: a model calls them
# once a layer, and a jitted callee is traced and lowered once per program
# where a plain function is once per call site (48 layers x up to two
# kernels a step program) — seconds of every engine start, cached or cold.
@functools.partial(jax.jit, static_argnames=("scale", "n_kv_heads",
                                             "interpret"))
def paged_flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       block_tables: jnp.ndarray, cache_len: jnp.ndarray, *,
                       scale: float, n_kv_heads: int = 0,
                       k_scale: jnp.ndarray = None,
                       v_scale: jnp.ndarray = None,
                       interpret: bool = False) -> jnp.ndarray:
    """Single-token cached attention over a PAGED cache: q (B, nh, hs)
    against the pool buffers (ops/block_pool.py), with per-sequence block
    tables (B, max_blocks) int32 and valid lengths `cache_len` (B,).
    Returns (B, nh, hs).

    The pools are the merged-lane (n_blocks, bs, L) leaves
    (`block_pool.kv_lanes`; `n_kv_heads` says how many heads share the L
    lanes) — dense row-major on the device, so the kernel reads the very
    buffer the step's in-place write produced, in (bs, L) tiles with no
    padding in them. Grid (B,): a step is a sequence, and `_paged_kernel`
    walks its live tiles through the prefetched table with fetches of its
    own, several in flight and running ahead into the next sequence's
    (`_walk_shape` says how many, from the table's width and a tile's
    bytes); the last partial block masks `kpos >= cache_len`, a block
    past it is neither fetched nor stepped over.

    int8 pools keep the head axis, (n_blocks, bs, n_kv, hs) codes +
    (.., n_kv, 1) float32 scale sidecars, the `_q8` body and the
    contiguous kernel's machinery one indirection deeper: grid
    (B, max_blocks), the kv index map resolves logical j -> physical pool
    block, steps past a sequence's last valid block clamp to it and the
    revolving-buffer DMA, seeing an unchanged index, fetches nothing.
    Gate with `paged_flash_decode_usable`."""
    B, nh, hs = q.shape
    bs = k.shape[1]
    n_max = block_tables.shape[1]
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "int8 cache needs both k_scale and v_scale"
    nkv = k.shape[2] if quantized else n_kv_heads
    assert nkv and nh % nkv == 0, (nh, nkv)
    rep = nh // nkv

    cl = jnp.asarray(cache_len, jnp.int32).reshape(B)
    bt = jnp.asarray(block_tables, jnp.int32)

    if quantized:
        def q_idx(b, j, cl_ref, bt_ref):
            return (b, 0, 0, 0)

        def kv_idx(b, j, cl_ref, bt_ref):
            # clamp skipped steps to the last valid LOGICAL block, then map
            # to its physical pool block: the revolving buffer sees an
            # unchanged index -> no DMA for dead blocks (same trick as the
            # contiguous kernel, one table lookup deeper)
            last = jax.lax.div(jnp.maximum(cl_ref[b], 1) - 1, bs)
            return (bt_ref[b, jnp.minimum(j, last)], 0, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_max),
            in_specs=[pl.BlockSpec((1, nkv, rep, hs), q_idx),
                      pl.BlockSpec((1, bs, nkv, hs), kv_idx),
                      pl.BlockSpec((1, bs, nkv, 1), kv_idx),
                      pl.BlockSpec((1, bs, nkv, hs), kv_idx),
                      pl.BlockSpec((1, bs, nkv, 1), kv_idx)],
            out_specs=pl.BlockSpec((1, nkv, rep, hs), q_idx),
            scratch_shapes=[
                pltpu.VMEM((nkv, rep, hs), jnp.float32),
                pltpu.VMEM((nkv, rep, 1), jnp.float32),
                pltpu.VMEM((nkv, rep, 1), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            functools.partial(_paged_body_q8, scale=float(scale),
                              block_s=bs),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, nkv, rep, hs), q.dtype),
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel", "arbitrary")),
            name="paged_flash_decode_q8",
            interpret=interpret,
        )(cl, bt, q.reshape(B, nkv, rep, hs), k,
          k_scale.astype(jnp.float32), v, v_scale.astype(jnp.float32))
        return out.reshape(B, nh, hs)

    L = k.shape[2]
    g_pad = -(-nkv // 8) * 8
    R = rep * g_pad
    group, depth = _walk_shape(n_max, _pair_bytes(k))

    def q_idx(b, cl_ref, bt_ref):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, R, L), q_idx),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rep, L), q_idx),
        scratch_shapes=[
            pltpu.VMEM((depth, bs, L), k.dtype),
            pltpu.VMEM((depth, bs, L), v.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((R, L), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale), bs=bs, hs=hs,
                          rep=rep, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rep, L), q.dtype),
        # the cursor and the fetches in flight pass from a sequence to the
        # next: the grid runs in order
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        name="paged_flash_decode",
        interpret=interpret,
    )(cl, bt, _zero_extend_q(q, nkv, g_pad, L), k, v)
    # (B, rep, L): lane l of row r is head (l // hs) * rep + r
    return out[:, :, :nkv * hs].reshape(B, rep, nkv, hs) \
        .transpose(0, 2, 1, 3).reshape(B, nh, hs)


#: the chunk kernel's step (`_chunk_shape`): pool blocks one softmax update
#: takes side by side, and what ONE float32 score tile may take of the
#: scoped VMEM
_CHUNK_BLOCKS = 8
_CHUNK_SCORE_BYTES = VMEM_LIMIT_BYTES // 4


def _chunk_shape(T: int, rep: int, n_max: int, bs: int, hs: int = 128,
                 q_item: int = 2, kv_item: int = 2) -> tuple[int, int]:
    """(tq, group) of `_prefill_kernel`'s grid step, from the shapes of the
    call alone: a key tile is `group` pool blocks under ONE softmax update,
    `_CHUNK_BLOCKS` where the table is that wide and the whole table where
    it is not; a query tile is `tq` of the chunk's T positions (`tq * rep`
    packed rows), the largest divisor of T in whole sublanes whose float32
    score tile against that key tile stays inside `_CHUNK_SCORE_BYTES`. A
    256-row chunk over a table of 6-10 blocks is one query tile against
    one key tile; 1,024 x 6 rows over 136 blocks are two query tiles of
    3,072 rows against 1,024 keys a step; a table of one block gives
    (T, 1). Where the step so chosen passes the scoped VMEM as a whole
    (`_chunk_vmem_bytes`: 8 query heads a KV head of `hs` 256 lanes fill the
    score tile to its last byte, and three of them stand beside wider
    query, output and accumulator tiles) the query tile is halved until it
    fits: 1,024 x 8 rows at 256 lanes are four tiles of 2,048 rows. Every
    call that fitted before keeps its step."""
    group = max(1, min(n_max, _CHUNK_BLOCKS))
    rows = _CHUNK_SCORE_BYTES // (group * bs * 4)
    tq = _pick_block(T, max(rows // rep, 8), 8) or T
    lanes = max(hs, 128)
    while tq % 16 == 0 and _chunk_vmem_bytes(
            tq * rep, group * bs, lanes // hs, lanes, q_item,
            kv_item) > VMEM_LIMIT_BYTES:
        tq //= 2
    return tq, group


def _stack_tiles(tiles) -> jnp.ndarray:
    """A step's (rows, lanes) views of the keys, one behind the other in
    position order: its key tile."""
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)


def _softmax_update(q, k, v, visible, acc_ref, m_ref, l_ref, h, *,
                    scale: float, drop_masked: bool = False):
    """ONE online-softmax update of head `h`'s state over the whole key
    tile: q (rows, lanes) against k, v (keys, lanes) under `visible`
    (rows, keys). Operands go to the MXU in their own dtype, scores /
    state / accumulator are float32, `p` is cast to v's dtype.
    `drop_masked`: a row may have seen nothing yet (a window's tile), so a
    masked p is zeroed, not trusted to underflow."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # (rows, keys)
    s = jnp.where(visible, s, _NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if drop_masked:
        p = jnp.where(visible, p, 0.0)
    m_ref[h] = m_new
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _softmax_init(acc_ref, m_ref, l_ref):
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)


def _softmax_out(o_ref, acc_ref, l_ref, hs: int):
    """The lane group's output block, lane-dense: each head's own hs lanes
    picked out of its normalised accumulator."""
    head = _lane_head(o_ref.shape, hs)
    out = jnp.zeros(o_ref.shape, jnp.float32)
    for h in range(acc_ref.shape[0]):
        out = jnp.where(
            head == h, acc_ref[h] / jnp.maximum(l_ref[h], 1e-30), out)
    o_ref[:] = out.astype(o_ref.dtype)


def _prefill_kernel(meta_ref, bt_ref, q_ref, *refs, scale: float, bs: int,
                    hs: int, rep: int, group: int):
    """Chunked-prefill body over MERGED-LANE pools: the queries of ONE
    sequence's chunk against its own paged blocks, causal against the
    global positions `off + t`. Grid (lane groups, query tiles, key
    tiles): a lane group is the max(hs, 128) lanes of a k/v tile that hold
    whole heads (two 64-wide heads, or one head of 128+); a query tile is
    `tq` positions of the chunk, (t, rep)-packed rows zero-extended to the
    group's lanes per head (`_paged_kernel`'s trick at group width: the
    contraction is one MXU pass deep either way); a key tile is `group`
    pool blocks, each a (1, bs, lanes) view of the pool whose index map
    resolves its own logical block through the prefetched table
    (`_chunk_shape` says both sizes). The step stacks the views and makes
    ONE masked softmax update a head over all their keys: one row max, one
    `alpha`, one rescale of the accumulator for `group * bs` keys. A key
    tile whose first key lies past the query tile's last row is neither
    fetched nor computed (views past the tile's last needed block hold
    that block again and are masked with it), and the grid's third axis,
    a scalar of the call, ends with the chunk's last row. The
    online-softmax state is indexed by head and the output block written
    lane-dense, each head's own lanes picked out of its accumulator."""
    k_refs, v_refs = refs[:group], refs[group:2 * group]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * group:]
    i, j = pl.program_id(1), pl.program_id(2)
    hpg, n_rows = q_ref.shape[1], q_ref.shape[2]    # heads a group, tq * rep
    tq, keys = n_rows // rep, group * bs
    first = meta_ref[0] + i * tq                    # the tile's first query

    pl.when(j == 0)(functools.partial(_softmax_init, acc_ref, m_ref, l_ref))

    @pl.when(j * keys < first + tq)
    def _():
        k = _stack_tiles([r[0] for r in k_refs])            # (keys, lanes)
        v = _stack_tiles([r[0] for r in v_refs])
        # key c of the tile is visible to packed row r (position
        # first + r // rep) iff (j * keys + c - first) * rep <= r:
        # a row and a column of int32, no divide over the tile
        kcol = (j * keys - first + jax.lax.broadcasted_iota(
            jnp.int32, (1, keys), 1)) * rep
        visible = kcol <= jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, 1), 0)
        for h in range(hpg):
            _softmax_update(q_ref[0, h], k, v, visible, acc_ref, m_ref,
                            l_ref, h, scale=scale)

    pl.when(j == pl.num_programs(2) - 1)(
        functools.partial(_softmax_out, o_ref, acc_ref, l_ref, hs))


def _prefill_kernel_q8(meta_ref, bt_ref, q_ref, k_ref, ks_ref, v_ref,
                       vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                       scale: float, bs: int, rep: int):
    """int8-pool chunked prefill: codes + per-(row, kv-head) scale rows
    through the same block index map; dequantization folds into the
    score/probability tiles exactly as in `_kernel_q8`."""
    j = pl.program_id(0)
    off = meta_ref[0]
    n_rows = q_ref.shape[1]
    T = n_rows // rep
    last_j = jax.lax.div(jnp.maximum(off + T, 1) - 1, bs)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j <= last_j)
    def _():
        q = q_ref[:]                            # (nkv, T*rep, hs)
        dt = q.dtype
        k = k_ref[0].transpose(1, 0, 2).astype(dt)
        v = v_ref[0].transpose(1, 0, 2).astype(dt)
        ks = ks_ref[0].transpose(1, 2, 0)       # (nkv, 1, bs)
        vs = vs_ref[0].transpose(1, 2, 0)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = s * (ks * scale)
        qpos = off + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), rep)
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= qpos, s, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            (p * vs).astype(dt), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "n_kv_heads",
                                             "interpret"))
def paged_flash_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        block_tables: jnp.ndarray, q_offset, *,
                        scale: float, n_kv_heads: int = 0,
                        k_scale: jnp.ndarray = None,
                        v_scale: jnp.ndarray = None,
                        interpret: bool = False) -> jnp.ndarray:
    """Mixed-path chunk attention over a PAGED cache: q (1, T, nh, hs) —
    a prefill chunk of ONE sequence whose rows sit at global positions
    [q_offset, q_offset+T) — against the merged-lane (n_blocks, bs, L)
    pool (`block_pool.kv_lanes`, `n_kv_heads` heads in the L lanes; int8
    pools keep (n_blocks, bs, n_kv, hs) + scale sidecars and the `_q8`
    body), addressed through the sequence's block table (1, max_blocks)
    int32. The chunk's rows must already be written to the pool (the
    attention path writes before it reads, exactly like the wave
    prefill). Returns (1, T, nh, hs).

    This is `paged_flash_decode` generalized from one query row to a
    (t, rep)-packed query tile: the grid still walks logical blocks with
    the prefetched table resolving physical ids, steps past the chunk's
    last needed block clamp to it (no DMA), and the causal mask compares
    each row's global position `q_offset + t` against the block's key
    positions — so a chunk at an arbitrary block-aligned offset attends
    the sequence's own prior blocks and its own in-chunk prefix, never a
    neighbor's. Gate with `paged_flash_prefill_usable`."""
    B, T, nh, hs = q.shape
    assert B == 1, "chunk prefill attends one sequence at a time"
    bs = k.shape[1]
    n_max = block_tables.shape[1]
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "int8 cache needs both k_scale and v_scale"
    nkv = k.shape[2] if quantized else n_kv_heads
    assert nkv and nh % nkv == 0, (nh, nkv)
    rep = nh // nkv
    rows = T * rep

    meta = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))
    bt = jnp.asarray(block_tables, jnp.int32).reshape(n_max)
    # pack (t, rep) into the sublane dim: row r of kv head g is query
    # head g*rep + r%rep at chunk position r//rep
    q3 = q[0].reshape(T, nkv, rep, hs).transpose(1, 0, 2, 3) \
        .reshape(nkv, rows, hs)

    if quantized:
        def last_block(meta_ref):
            return jax.lax.div(jnp.maximum(meta_ref[0] + T, 1) - 1, bs)

        def q_idx(j, meta_ref, bt_ref):
            return (0, 0, 0)

        def kv_idx(j, meta_ref, bt_ref):
            return (bt_ref[jnp.minimum(j, last_block(meta_ref))], 0, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_max,),
            in_specs=[pl.BlockSpec((nkv, rows, hs), q_idx),
                      pl.BlockSpec((1, bs, nkv, hs), kv_idx),
                      pl.BlockSpec((1, bs, nkv, 1), kv_idx),
                      pl.BlockSpec((1, bs, nkv, hs), kv_idx),
                      pl.BlockSpec((1, bs, nkv, 1), kv_idx)],
            out_specs=pl.BlockSpec((nkv, rows, hs), q_idx),
            scratch_shapes=[
                pltpu.VMEM((nkv, rows, hs), jnp.float32),
                pltpu.VMEM((nkv, rows, 1), jnp.float32),
                pltpu.VMEM((nkv, rows, 1), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            functools.partial(_prefill_kernel_q8, scale=float(scale),
                              bs=bs, rep=rep),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nkv, rows, hs), q.dtype),
            compiler_params=tpu_compiler_params(
                dimension_semantics=("arbitrary",)),
            name="paged_flash_prefill_q8",
            interpret=interpret,
        )(meta, bt, q3, k, k_scale.astype(jnp.float32),
          v, v_scale.astype(jnp.float32))
        return out.reshape(nkv, T, rep, hs).transpose(1, 0, 2, 3) \
            .reshape(1, T, nh, hs)

    L = k.shape[2]
    qz, gl = _lane_group_q(q3, L)
    n_groups, hpg = qz.shape[:2]
    tq, group = _chunk_shape(T, rep, n_max, bs, hs,
                             jnp.dtype(q.dtype).itemsize,
                             jnp.dtype(k.dtype).itemsize)
    rows_q = tq * rep

    def q_idx(g, i, j, meta_ref, bt_ref):
        return (g, 0, i, 0)

    def kv_idx(t):
        def idx(g, i, j, meta_ref, bt_ref):
            # view t of key tile j holds logical block j * group + t while
            # query tile i needs it, and the tile's last needed block past
            # that: a key tile the diagonal ends in fetches no block the
            # chunk cannot see
            last = jax.lax.div(meta_ref[0] + (i + 1) * tq - 1, bs)
            return (bt_ref[jnp.minimum(j * group + t,
                                       jnp.minimum(last, n_max - 1))], 0, g)
        return idx

    def o_idx(g, i, j, meta_ref, bt_ref):
        return (i, g)

    kv_specs = [pl.BlockSpec((1, bs, gl), kv_idx(t)) for t in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # the third axis ends with the chunk's last row: as many key tiles
        # as the call's offset needs, not as the table is wide
        grid=(n_groups, T // tq, jnp.minimum(
            jax.lax.div(meta[0] + T - 1, group * bs) + 1,
            -(-n_max // group))),
        in_specs=[pl.BlockSpec((1, hpg, rows_q, gl), q_idx)] + 2 * kv_specs,
        out_specs=pl.BlockSpec((rows_q, gl), o_idx),
        scratch_shapes=[
            pltpu.VMEM((hpg, rows_q, gl), jnp.float32),
            pltpu.VMEM((hpg, rows_q, 1), jnp.float32),
            pltpu.VMEM((hpg, rows_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=float(scale), bs=bs,
                          hs=hs, rep=rep, group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, L), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="paged_flash_prefill",
        interpret=interpret,
    )(meta, bt, qz, *(group * [k] + group * [v]))
    return _heads_of_lanes(out, T, nh, nkv, hs)


def _common_decline(q, k, nh, nkv, hs, bs, what: str):
    """Checks shared by the three gates (dtypes, head geometry, the tile
    the hardware splits the KV length by, no live multi-device mesh)."""
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"query dtype {q.dtype} (kernel handles float32 / bfloat16)"
    if k.dtype != q.dtype and k.dtype != jnp.int8:
        return f"cache dtype {k.dtype} is neither {q.dtype} nor int8"
    if hs % 8 != 0 or not nkv or nh % nkv != 0:
        return f"head geometry nh={nh}, n_kv={nkv}, hs={hs}"
    step = 128 if jax.default_backend() == "tpu" else 8
    if bs % step != 0:
        return (f"{what} is not a multiple of {step} (the KV tile the "
                f"{jax.default_backend()} backend splits by)")
    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if mesh is not None and any(s > 1 for s in mesh.devices.shape):
        return ("a live multi-device mesh (GSPMD cannot partition a "
                "pallas_call)")
    return None


def _budget_decline(need: int):
    if need <= VMEM_LIMIT_BYTES:
        return None
    return (f"one grid step needs {need >> 20} MiB of VMEM, over the "
            f"{VMEM_LIMIT_BYTES >> 20} MiB scoped limit")


def _pair_bytes(k) -> int:
    """One k tile + one v tile of a merged-lane (n_blocks, bs, L) pool."""
    return 2 * k.shape[1] * k.shape[2] * jnp.dtype(k.dtype).itemsize


def _kv_tile_bytes(k, rows: int, width: int) -> int:
    """Double-buffered k+v tiles of (rows, width) elements — width is the
    merged lanes a step moves, or n_kv * hs of a head-major tile (+ its
    f32 scale rows for an int8 cache)."""
    tiles = 2 * 2 * rows * width * jnp.dtype(k.dtype).itemsize
    if k.dtype == jnp.int8:
        tiles += 2 * 2 * rows * k.shape[2] * 4
    return tiles


def _pool_heads(k, n_kv_heads: int) -> int:
    """kv heads of a pool leaf: the int8 pools carry the axis, the
    merged-lane ones are told (`block_pool.kv_lanes`)."""
    return k.shape[2] if k.ndim == 4 else n_kv_heads


def _chunk_vmem_bytes(rows: int, keys: int, heads: int, lanes: int,
                      q_item: int, kv_item: int) -> int:
    """What one grid step of a chunk kernel holds in VMEM: the query tile
    and the output block, double-buffered; the k/v views of the key tile,
    double-buffered, and the two stacked tiles made of them; a head's
    float32 accumulator with its `m` and `l` columns (a column is padded
    to 128 lanes there); three score-sized float32 temporaries (scores,
    `p`, the mask or the cast)."""
    return (2 * heads * rows * lanes * q_item + 2 * rows * lanes * q_item
            + (2 * 2 + 2) * keys * lanes * kv_item
            + heads * rows * (lanes + 2 * 128) * 4 + 3 * rows * keys * 4)


def paged_flash_prefill_decline(q, k, v, block_tables, n_kv_heads: int = 0):
    """Why the chunk-prefill kernel cannot take this call (None = it
    can), mirroring `paged_flash_decode_decline`: one sequence's
    (1, T>1, nh, hs) chunk, whole-block pool pages the hardware tiles, T
    a multiple of the sublane step, heads that tile the merged lanes in
    whole 128-lane groups, and one grid step (`_chunk_shape`'s query tile
    against its key tile of several blocks, `_chunk_vmem_bytes`) within
    the VMEM budget. The fallback is paged_gather + the naive masked
    path — identical semantics."""
    if q.ndim != 4 or q.shape[0] != 1 or q.shape[1] <= 1:
        return f"query shape {q.shape} is not one sequence's (1, T>1) chunk"
    _, T, nh, hs = q.shape
    bs, nkv = k.shape[1], _pool_heads(k, n_kv_heads)
    if T % 8 != 0:
        return f"chunk length {T} is not a sublane (8) multiple"
    why = _common_decline(q, k, nh, nkv, hs, bs, f"pool block size {bs}")
    if why is not None:
        return why
    rep = nh // nkv
    if k.ndim == 4:                 # int8 head-major tiles: every head a step
        rows = T * rep
        qtile = nkv * rows * hs * jnp.dtype(q.dtype).itemsize
        scratch = nkv * rows * (hs + 2) * 4
        return _budget_decline(_kv_tile_bytes(k, bs, nkv * hs) + qtile
                               + scratch + 3 * nkv * rows * bs * 4)
    if 128 % hs != 0 and hs % 128 != 0:
        return (f"head size {hs} neither divides nor is a multiple of the "
                "128 lanes a head group is cut by")
    lanes = max(hs, 128)            # one lane group: its heads, one at a time
    items = (jnp.dtype(q.dtype).itemsize, jnp.dtype(k.dtype).itemsize)
    tq, group = _chunk_shape(T, rep, block_tables.shape[1], bs, hs, *items)
    return _budget_decline(_chunk_vmem_bytes(
        tq * rep, group * bs, lanes // hs, lanes, *items))


def paged_flash_decode_decline(q, k, v, block_tables, n_kv_heads: int = 0):
    """Why the paged kernel cannot take this call (None = it can),
    mirroring `flash_decode_decline`: decode-shaped (B, 1, nh, hs) query,
    pool block size the hardware tiles (multiples of 128 rows on TPU —
    small CPU-test pages run in interpret mode at multiples of 8), no live
    multi-device mesh, and the walk's ring of (bs, L) tile pairs + the
    zero-extended query rows + the full-width accumulator + the score
    tiles of one joint update within the VMEM budget (`_walk_shape`; the
    int8 body: its double-buffered head-major tiles). The fallback is
    paged_gather + the naive path — identical semantics."""
    if q.ndim != 4 or q.shape[1] != 1:
        return f"query shape {q.shape} is not decode-shaped (B, 1, nh, hs)"
    _, _, nh, hs = q.shape
    bs, nkv = k.shape[1], _pool_heads(k, n_kv_heads)
    why = _common_decline(q, k, nh, nkv, hs, bs, f"pool block size {bs}")
    if why is not None:
        return why
    rep = nh // nkv
    if k.ndim == 4:                             # int8: head-major tiles
        scratch = nkv * rep * (hs + 2) * 4
        scores = 3 * nkv * rep * bs * 4
        return _budget_decline(_kv_tile_bytes(k, bs, nkv * hs) + scratch
                               + scores)
    return _budget_decline(_walk_vmem_bytes(q, k, block_tables, nkv))


def _walk_vmem_bytes(q, k, block_tables, nkv: int) -> int:
    """What one grid step of `_paged_kernel` holds in VMEM: the ring of
    tile pairs (`_walk_shape`), the double-buffered zero-extended query
    rows, the full-width accumulator with the p @ v sum and the epilogue
    beside it, and the score tiles of one joint update."""
    nh, hs = q.shape[-2:]
    bs, L = k.shape[1], k.shape[2]
    R = (nh // nkv) * (-(-nkv // 8) * 8)        # zero-extended query rows
    pair = _pair_bytes(k)
    group, depth = _walk_shape(block_tables.shape[1], pair)
    qtile = 2 * R * L * jnp.dtype(q.dtype).itemsize
    scratch = R * (L + 2) * 4 + 2 * R * L * 4
    scores = 3 * group * R * bs * 4
    return depth * pair + qtile + scratch + scores


def flash_decode_decline(q, k, v):
    """Why the contiguous kernel cannot take this call (None = it can):
    (B, 1, nh, hs)-shaped decode query, dtypes/shapes the kernel tiles,
    no live multi-device mesh (GSPMD cannot partition a pallas_call; a
    shard_map wrap over 'data' is future work — the naive path handles
    sharded decode meanwhile). An int8 k/v (the quantized cache's codes)
    is accepted — `_kernel_q8` carries it."""
    if q.ndim != 4 or q.shape[1] != 1:
        return f"query shape {q.shape} is not decode-shaped (B, 1, nh, hs)"
    _, _, nh, hs = q.shape
    S, nkv = k.shape[1], k.shape[2]
    step = 128 if jax.default_backend() == "tpu" else 8
    block_s = _pick_block(S, DEFAULT_BLOCK_S, step)
    if not block_s:
        return f"cache length {S} has no tile split in multiples of {step}"
    why = _common_decline(q, k, nh, nkv, hs, block_s, f"kv tile {block_s}")
    if why is not None:
        return why
    rep = nh // nkv
    scratch = nkv * rep * (hs + 2) * 4
    scores = 3 * nkv * rep * block_s * 4                # s, p, mask temps
    return _budget_decline(_kv_tile_bytes(k, block_s, nkv * hs) + scratch
                           + scores)


def paged_flash_prefill_usable(q, k, v, block_tables,
                               n_kv_heads: int = 0) -> bool:
    return paged_flash_prefill_decline(q, k, v, block_tables,
                                       n_kv_heads) is None


def paged_flash_decode_usable(q, k, v, block_tables,
                              n_kv_heads: int = 0) -> bool:
    return paged_flash_decode_decline(q, k, v, block_tables,
                                      n_kv_heads) is None


def flash_decode_usable(q, k, v) -> bool:
    return flash_decode_decline(q, k, v) is None
