"""Pallas TPU flash attention: blockwise online-softmax in VMEM, with a
hand-written FlashAttention-2-style backward (custom VJP).

This is the framework's native-kernel replacement for the fused attention
the reference delegates to `F.scaled_dot_product_attention` (reference
single-gpu/model.py:149). Design (per the Pallas TPU playbook):

* The (batch, head) pair is flattened into one ROW axis and the grid is
  (rows/block_h, q_blocks, kv_blocks), `dimension_semantics=('parallel',
  'parallel', 'arbitrary')`. Each grid step processes `block_h` rows'
  (block_q x block_k) score tiles batched through the MXU, streaming ONE
  (block_k, D) K/V tile per row; the online-softmax state (running max m,
  normalizer l, f32 accumulator) lives in VMEM scratch that persists
  across the innermost kv dimension. VMEM use is constant in sequence
  length — attention probabilities never exist in HBM, so memory is O(T)
  instead of O(T^2) and sequences of 32k+ compile.
* Why a row-group block: at the flagship shape (B16 H12 T1024 D64) with
  128x128 tiles the grid is ~12k steps/layer of ~2 MFLOP each and
  per-grid-step overhead dominates the kernel (v5e micro-bench, PERF.md
  round 4 — 128x128 lost ~50 ms/call to 256x512 from grid-step count
  alone). Grouping `block_h` rows per step divides the step count again
  without changing total VPU/MXU work.
* Causal masking is positional (qpos >= kpos), so the KV length S may
  exceed the query length T (prefill into a longer zero-filled cache
  buffer): the zero tail is always masked. Blocks strictly above the
  causal frontier are skipped: compute is predicated with `pl.when` and
  their index maps clamp to the last visible block so no fresh DMA is
  issued for skipped tiles. INSIDE a diagonal tile of a square causal
  tiling the same skip is static: the tile is worked in row slabs of
  `SLAB_W` rows that stop at the diagonal (`slab_plan`) — forward and dq
  query slab c against keys [0, (c + 1) w), dkv key slab c against query
  rows [c w, block_q) — plain slices of the VMEM refs unrolled at trace
  time, no grid step, BlockSpec or DMA of their own. Four slabs of 256
  compute 62.5% of a 1024 x 1024 tile. A call that is not causal, a
  rectangular tiling, a tile below the diagonal and a tile under two slabs
  keep the whole masked tile. A call of ONE tile (every training call up to
  T = 1024) carries no state from tile to tile and writes its outputs
  straight from the tile math: the m / l / accumulator scratch is for calls
  of several tiles.
* Backward = two kernels (FlashAttention-2): dq accumulates over kv tiles;
  dk/dv accumulate over q tiles; both recompute p from the saved
  logsumexp instead of storing probabilities.
* GQA never materializes repeated K/V: with `rep = nh // nkv > 1` the
  row group is 1 and the kv BlockSpec index maps send query row r to kv
  row r // rep, so the same kv tile serves the whole group straight from
  HBM (a materialized repeat would multiply KV bytes by the group size at
  exactly the long-S scales this kernel targets). The backward emits
  per-query-row dk/dv and group-sums them host-side. Head dims must be
  sublane multiples (hs % 8); there is no padding path — odd head dims
  fall back to the XLA impl via `flash_attention_usable`.
* One layout. The kernels take (B*H, T, D) rows, so `flash_attention_lse`
  transposes the model's BTNH operands in HBM on the way in and the output
  on the way out: 6.6 ms of the 42.3 ms attention core in `gpt2_train_b16`
  (PERF.md section 6, PR 29). Head-major projections are the repair
  (ROADMAP S2b), not a second kernel family.

The public entry points keep the interface the dispatcher
(ops/attention_core.py) fixed while this was a stub: `flash_attention` and
`flash_attention_usable`.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu.compat import (VMEM_LIMIT_BYTES,
                                            tpu_compiler_params, vma_of)

# The tiles. 1024 x 1024 q/kv tiles (the whole sequence in one tile at
# T = 1024: no online-softmax rescale between kv tiles) and one row a grid
# step are what won on a v5e at the train cell's shape (192 rows, T = 1024,
# D = 64, bf16), forward + backward: 3.83 ms a call against 4.20 for the
# former 256 x 512 x 8 over a 47-point sweep, and 95,793 against 93,327
# tokens/s/chip end to end in `gpt2_train_b16` (PERF.md section 6, PR 29).
# Shorter sequences take the largest tiles that divide them (`_pick_block`)
# and as many more rows a step as the tile is smaller, wider heads fewer
# (`_pick_group`, the VMEM limit). The `block_q/k/h` arguments are for the
# parity tests, which hold the kernels to the oracle at tilings small
# enough to exercise the tile-to-tile paths; the program passes none.
# SLAB_W: the rows of a causal slab inside a diagonal tile (`slab_plan`).
# At the same shape the three kernels took 0.516 / 0.613 / 0.775 ms a call
# (forward / dq / dkv) with slabs of 256 rows, 0.591 / 0.611 / 0.915 with
# 128 (56% of the elements, but a slab's fixed cost eight times) and 0.500 /
# 0.640 / 0.847 with 512, against 0.664 / 0.880 / 1.224 for the whole masked
# tile (PERF.md section 6, PR 51).
BLOCK_Q = 1024
BLOCK_K = 1024
BLOCK_H = 1
SLAB_W = 256

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free

_SEMANTICS = tpu_compiler_params(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _last_visible_kv(i, block_q: int, block_k: int):
    """Index of the last kv block the q tile `i` attends into (causal)."""
    return jax.lax.div(i * block_q + block_q - 1, block_k)


def _first_visible_q(j, block_q: int, block_k: int):
    """Index of the first q block that attends into kv tile `j` (causal)."""
    return jax.lax.div(j * block_k, block_q)


def _mask_scores(s, q0, k0):
    """Causal mask for one (g, rows, cols) score tile whose first row is
    query position `q0` and first column key position `k0`, both absolute:
    a query attends keys with kpos <= qpos (reference model.py:225-226 triu
    semantics with offset 0)."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    return jnp.where(qpos >= kpos, s, _NEG_INF)


def _bdot(a, b, trans_b=False):
    """Row-batched matmul with f32 accumulation: a (g, m, k) @ b (g, k, n)
    — or b (g, n, k) when trans_b — over the shared leading group dim."""
    dims = (((2,), (2 if trans_b else 1,)), ((0,), (0,)))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _bdot_t(a, b):
    """Row-batched a^T @ b: a (g, m, n), b (g, m, k) -> (g, n, k)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _mix_bits(seed0, seed1, row, qp, kp):
    """Counter-based uint32 hash (murmur3-finalizer style) over already-
    broadcast (attention row, query position, key position) uint32 arrays
    plus the caller seed. Pure jnp int ops: runs identically in the
    compiled kernel (VPU), in interpret mode (pltpu.prng_* has no CPU
    lowering), in the ring-attention einsum hops, and in plain host code
    (tests replay the exact mask for an oracle comparison)."""
    u32 = lambda a: jnp.asarray(a).astype(jnp.uint32)  # noqa: E731
    x = u32(row) * jnp.uint32(0x9E3779B1)
    x = x ^ (u32(qp) * jnp.uint32(0x85EBCA6B))
    x = x ^ (u32(kp) * jnp.uint32(0xC2B2AE35))
    x = x ^ u32(seed0)
    x = x + u32(seed1) * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def dropout_threshold(rate: float) -> jnp.ndarray:
    """uint32 threshold with P(bits < t) = rate."""
    return jnp.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))


def fold_seed_for_data_shard(seed, didx):
    """Decorrelate a (2,) int32 dropout seed across 'data' shards (each
    shard holds different samples at the same shard-local batch rows). ONE
    definition shared by the sp ring hops (ops/ring_attention.py) and the
    test-side host replay, so the fold can't drift between them."""
    return seed ^ (jnp.asarray(didx).astype(jnp.int32)
                   * jnp.int32(0x9E3779B9 - 2 ** 32))


def _dropout_bits(seed0, seed1, row0, q0, k0, shape):
    """_mix_bits keyed on the ABSOLUTE coordinates of every element of a
    (rows, q, k) tile starting at (row0, q0, k0). Absolute-position keying
    makes the mask independent of block sizes and of which kernel's grid
    order regenerates it."""
    u32 = lambda a: jnp.asarray(a).astype(jnp.uint32)  # noqa: E731
    row = u32(row0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    qp = u32(q0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    kp = u32(k0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
    return _mix_bits(seed0, seed1, row, qp, kp)


def _dropout_mask(seed_ref, at, shape, rate: float):
    """Scaled keep-mask for one (g, rows, cols) score tile that starts `at`
    = (attention row, query position, key position), all absolute;
    regenerated bit-identically in forward and both backward kernels.
    P(drop) = rate via a uint32 threshold; survivors are pre-scaled by
    1/(1-rate) (inverted dropout, the reference's
    F.scaled_dot_product_attention semantics)."""
    bits = _dropout_bits(seed_ref[0], seed_ref[1], *at, shape)
    return ((bits >= dropout_threshold(rate)).astype(jnp.float32)
            / (1.0 - rate))


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-manual-axes set: pallas
    calls inside shard_map (the ring-attention hop path) must declare how
    their outputs vary across mesh axes."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma_of(like))


def _kv_spec(rep: int, g: int, block_q: int, block_k: int, D: int,
             causal: bool):
    """Shared K/V BlockSpec for the forward and dq grids (both iterate
    (row-group r, q-tile i, kv-tile j)): GQA (g == 1) maps query row r to
    kv row r // rep — no materialized repeat — and skipped upper-triangle
    tiles clamp to the causal frontier so the revolving-buffer DMA sees an
    unchanged index (no fetch). One definition keeps forward and backward
    kv fetches in lockstep."""
    def kv_idx(r, i, j):
        jc = j if not causal \
            else jnp.minimum(j, _last_visible_kv(i, block_q, block_k))
        return (r if rep == 1 else r // rep, jc, 0)

    return pl.BlockSpec((g if rep == 1 else 1, block_k, D), kv_idx)


_LANE = 128


def _vmem_bytes(g: int, bq: int, bk: int, D: int, dsize: int) -> int:
    """Worst-case-kernel (dkv backward) VMEM estimate for one grid step of
    `g` rows, counting what Mosaic really allocates: the minor dim of every
    tile is padded to the 128-lane register tile, so a D=64 head tile
    occupies a D=128 one and each (bq, 1) lse/delta/m/l column occupies
    (bq, 128); I/O tiles are double-buffered; plus the f32 accumulator
    scratch and the f32 score/prob/dscore intermediates the body
    materializes. It is held against VMEM_LIMIT_BYTES, the scoped-VMEM
    limit the kernels hand Mosaic, so an oversized block/group config
    degrades (smaller row group, or the gate declines) instead of failing
    compilation with "exceeded scoped vmem limit". Checked against the v5e
    compiler's own minimum (device-free compile, binary search on the
    limit): g=8, 256x512, D=64 needs 24 MiB — the pre-padding estimate
    said 19 and the gate passed configs the compiler refused — this says
    30; it stays at or above the compiler's need from 128x128 to 512x1024
    tiles and D in {64, 128, 256}."""
    Dp = -(-D // _LANE) * _LANE
    col = g * bq * _LANE * 4                    # one (g, bq, 1) f32 column
    score = 3 * g * bq * bk * 4
    fwd = (2 * ((2 * g * bq * Dp + 2 * g * bk * Dp) * dsize + col)
           + g * bq * Dp * 4 + 2 * col + score)
    bwd = (2 * ((2 * g * bq * Dp + 2 * g * bk * Dp + 2 * g * bk * Dp)
                * dsize + 2 * col)
           + 2 * g * bk * Dp * 4 + score)
    return max(fwd, bwd)


def _pick_group(n_rows: int, rep: int, block_q: int, block_k: int, D: int,
                dsize: int) -> int:
    """Rows a grid step. BLOCK_H at the full BLOCK_Q x BLOCK_K tile and
    proportionally more where the sequence is shorter than the tile, so a
    grid step keeps its work (a 512 x 512 tile ran 12% slower in a group of
    1 than of 4, a 256 x 256 one 34%: PERF.md section 6, PR 29); a divisor
    of n_rows; shrunk until the per-step VMEM estimate fits the limit; 1
    unless kv rows map 1:1 (rep == 1 — with grouped rows a GQA group would
    need strided kv tiles)."""
    if rep != 1:
        return 1
    want = BLOCK_H * max(1, (BLOCK_Q * BLOCK_K) // (block_q * block_k))
    divisors = [g for g in range(min(want, n_rows), 0, -1) if n_rows % g == 0]
    req = divisors[0]
    g = next((g for g in divisors if _vmem_bytes(
        g, block_q, block_k, D, dsize) <= VMEM_LIMIT_BYTES), 1)
    if g != req and (req, g, block_q, block_k) not in _SHRINK_WARNED:
        # once per unique config: this runs at TRACE time, and repeated
        # jit traces / vmap would otherwise warn on every retrace
        _SHRINK_WARNED.add((req, g, block_q, block_k))
        warnings.warn(
            f"[flash] row group shrunk {req} -> {g} to fit the "
            f"{VMEM_LIMIT_BYTES >> 20} MiB VMEM limit at blocks "
            f"({block_q}, {block_k})", RuntimeWarning, stacklevel=2)
    return g


_SHRINK_WARNED: set = set()


# ---------------------------------------------------------------------------
# tile math (the FlashAttention-2 numerics; the kernels below load and store)
# ---------------------------------------------------------------------------
# `at` = (attention row, query position, key position) of the first element
# of the operands' score tile, absolute: what the causal mask and the
# dropout bits are keyed by, so a tile and a slab of it are the same call on
# other operands.

def _fwd_tile(q, k, v, at, seed_ref, prev, *, scale, causal, rate):
    """Online-softmax update for one (g, bq, D)x(g, bk, D) tile pair:
    `prev` = the rows' running (max m, normalizer l, f32 accumulator) after
    the kv tiles before this one, None where this is the first; returns the
    three after it. Operands stay in input dtype (bf16 on TPU): the MXU
    accumulates in f32 via preferred_element_type — casting inputs up would
    force slow fp32 MXU passes."""
    s = _bdot(q, k, trans_b=True) * scale               # (g, bq, bk) f32
    if causal:
        s = _mask_scores(s, at[1], at[2])
    m = jnp.max(s, axis=-1, keepdims=True)
    if prev is not None:
        m = jnp.maximum(prev[0], m)
    p = jnp.exp(s - m)
    # normalizer accumulates the UNdropped p (torch drops the
    # already-normalized attention weights); only the value accumulation
    # sees the mask
    l = jnp.sum(p, axis=-1, keepdims=True)
    if rate > 0.0:
        p = p * _dropout_mask(seed_ref, at, p.shape, rate)
    acc = _bdot(p.astype(v.dtype), v)
    if prev is not None:
        alpha = jnp.exp(prev[0] - m)
        l, acc = prev[1] * alpha + l, prev[2] * alpha + acc
    return m, l, acc


def _fwd_finalize(m, l, acc):
    """(normalized out (g, bq, D) f32, lse (g, bq, 1) f32)."""
    l_safe = jnp.maximum(l, 1e-30)
    return acc / l_safe, m + jnp.log(l_safe)


def _dq_tile(q, k, v, do, lse, delta, at, seed_ref, *, scale, causal, rate):
    """One tile's part of dq (unscaled, f32): ds = p * (M/(1-r) * (dO V^T)
    - delta); rowsum(dP*P) still equals rowsum(dO*O) = delta because O was
    computed with the SAME mask."""
    s = _bdot(q, k, trans_b=True) * scale
    if causal:
        s = _mask_scores(s, at[1], at[2])
    p = jnp.exp(s - lse)                                # (g, bq, bk) f32
    dp = _bdot(do, v, trans_b=True)
    if rate > 0.0:
        dp = dp * _dropout_mask(seed_ref, at, dp.shape, rate)
    ds = p * (dp - delta)
    return _bdot(ds.astype(k.dtype), k)


def _dkv_tile(q, k, v, do, lse, delta, at, seed_ref, *, scale, causal,
              rate):
    """One tile's parts of (dk unscaled, dv), f32; the dropout mask is
    regenerated from the same absolute coordinates as forward/dq, NOT this
    kernel's transposed grid order."""
    s = _bdot(q, k, trans_b=True) * scale               # (g, bq, bk) f32
    if causal:
        s = _mask_scores(s, at[1], at[2])
    p = jnp.exp(s - lse)
    dp = _bdot(do, v, trans_b=True)
    if rate > 0.0:
        mask = _dropout_mask(seed_ref, at, p.shape, rate)
        dv = _bdot_t((p * mask).astype(do.dtype), do)
        dp = dp * mask
    else:
        dv = _bdot_t(p.astype(do.dtype), do)
    ds = p * (dp - delta)
    return _bdot_t(ds.astype(q.dtype), q), dv


def _query_slabs(block: int, w: int) -> list:
    """Forward and dq, a diagonal tile cut by query rows: slab c is rows
    [c w, (c + 1) w) against keys [0, (c + 1) w). Each entry is (rows, keys,
    the slab's first query and key position within the tile); none where
    the call has no slab plan (`w` 0)."""
    return [(pl.ds(c * w, w), pl.ds(0, (c + 1) * w), c * w, 0)
            for c in range(block // w)] if w else []


def _key_slabs(block: int, w: int) -> list:
    """dkv, the same tile cut by keys: slab c is keys [c w, (c + 1) w)
    under query rows [c w, block)."""
    return [(pl.ds(c * w, block - c * w), pl.ds(c * w, w), c * w, c * w)
            for c in range(block // w)] if w else []


def _visit(i, j, visible, one_tile: bool, update, at, slabs: list):
    """Emit the body of tile (q tile i, kv tile j): `update(rows, keys, at)`
    slab by slab on a diagonal tile of a call that has a slab plan, over the
    whole masked tile on every other tile the causal frontier lets through. A call of ONE tile is known at trace time: one
    body is emitted, unpredicated."""
    def whole():
        update(slice(None), slice(None), at)

    def by_slab():
        for rows, keys, q0, k0 in slabs:
            update(rows, keys, (at[0], at[1] + q0, at[2] + k0))

    if one_tile:
        (by_slab if slabs else whole)()
    elif not slabs:
        pl.when(visible)(whole)
    else:
        pl.when(jnp.logical_and(visible, i != j))(whole)
        pl.when(i == j)(by_slab)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# A call of one tile (`one_tile`: the whole sequence, every training call up
# to T = BLOCK_Q) has no state to carry from tile to tile: its kernels write
# their outputs straight from the tile math and leave the scratch alone.

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale, block_q, block_k, causal, rate,
                slab, one_tile):
    r, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_j = _last_visible_kv(i, block_q, block_k) if causal \
        else pl.num_programs(2) - 1
    at = (r * q_ref.shape[0], i * block_q, j * block_k)

    def update(rows, keys, at):
        """One softmax update of `rows` of the q tile over `keys` of the
        kv tile."""
        prev = None if one_tile else (m_ref[:, rows], l_ref[:, rows],
                                      acc_ref[:, rows])
        m, l, acc = _fwd_tile(q_ref[:, rows], k_ref[:, keys], v_ref[:, keys],
                              at, seed_ref, prev, scale=scale, causal=causal,
                              rate=rate)
        if one_tile:
            o, lse = _fwd_finalize(m, l, acc)
            o_ref[:, rows] = o.astype(o_ref.dtype)
            lse_ref[:, rows] = lse
        else:
            m_ref[:, rows], l_ref[:, rows], acc_ref[:, rows] = m, l, acc

    if not one_tile:
        @pl.when(j == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

    _visit(i, j, j <= last_j, one_tile, update, at,
           _query_slabs(block_q, slab))

    if not one_tile:
        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            o, lse = _fwd_finalize(m_ref[:], l_ref[:], acc_ref[:])
            o_ref[:] = o.astype(o_ref.dtype)
            lse_ref[:] = lse


_SEED_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


# Jitted on their own (as the paged kernels are, ops/flash_decode.py): a
# model calls them once a layer, and a jitted callee is traced and lowered
# once per process and program where a plain function is once per call site
# — 12 layers x 3 kernels in model.init, the memory plan's shape probe and
# the train step cost the train cell 3.5-4 s of cached `setup_s`.
_KERNEL_STATICS = ("scale", "block_q", "block_k", "g", "interpret", "causal",
                   "rate", "slab")


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _fwd(q, k, v, seed, scale, block_q, block_k, g, interpret, causal=True,
         rate=0.0, slab=0):
    """q (N, T, D) rows = flattened (B, H); k/v (Nkv, S, D) with
    rep = N // Nkv -> out (N, T, D), lse (N, T, 1). `seed` (2,) int32
    feeds the in-kernel dropout PRNG (ignored at rate == 0). `slab`: rows
    a slab of a diagonal tile (`slab_plan`), 0 for none."""
    N, T, D = q.shape
    S, Nkv = k.shape[1], k.shape[0]
    rep = N // Nkv
    nq, nk = T // block_q, S // block_k

    kv_spec = _kv_spec(rep, g, block_q, block_k, D, causal)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, rate=rate,
                          slab=slab, one_tile=nq == nk == 1),
        grid=(N // g, nq, nk),
        in_specs=[
            _SEED_SPEC,
            pl.BlockSpec((g, block_q, D), lambda r, i, j: (r, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((g, block_q, D), lambda r, i, j: (r, i, 0)),
            # trailing singleton lane dim: TPU blocks need the last two dims
            # (8,128)-divisible OR equal to the array dims; (bq, 1) with
            # array (..., T, 1) qualifies.
            pl.BlockSpec((g, block_q, 1), lambda r, i, j: (r, i, 0)),
        ],
        out_shape=[
            _sds((N, T, D), q.dtype, q),
            _sds((N, T, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, block_q, D), jnp.float32),
            pltpu.VMEM((g, block_q, 1), jnp.float32),
            pltpu.VMEM((g, block_q, 1), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        name="flash_fwd",
        interpret=interpret,
    )(seed, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward (FlashAttention-2: recompute p from lse; delta = rowsum(do * o))
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, scale, block_q, block_k,
                   causal, rate, slab, one_tile):
    r, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_j = _last_visible_kv(i, block_q, block_k) if causal \
        else pl.num_programs(2) - 1
    at = (r * q_ref.shape[0], i * block_q, j * block_k)

    def update(rows, keys, at):
        dq = _dq_tile(q_ref[:, rows], k_ref[:, keys], v_ref[:, keys],
                      do_ref[:, rows], lse_ref[:, rows], delta_ref[:, rows],
                      at, seed_ref, scale=scale, causal=causal, rate=rate)
        if one_tile:
            dq_ref[:, rows] = (dq * scale).astype(dq_ref.dtype)
        else:
            dq_acc[:, rows] = dq_acc[:, rows] + dq

    if not one_tile:
        @pl.when(j == 0)
        def _():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    _visit(i, j, j <= last_j, one_tile, update, at,
           _query_slabs(block_q, slab))

    if not one_tile:
        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    block_q, block_k, causal, rate, slab, one_tile):
    r, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first_i = _first_visible_q(j, block_q, block_k) if causal else 0
    at = (r * q_ref.shape[0], i * block_q, j * block_k)

    def update(rows, keys, at):
        dk, dv = _dkv_tile(q_ref[:, rows], k_ref[:, keys], v_ref[:, keys],
                           do_ref[:, rows], lse_ref[:, rows],
                           delta_ref[:, rows], at, seed_ref, scale=scale,
                           causal=causal, rate=rate)
        if one_tile:
            dk_ref[:, keys] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[:, keys] = dv.astype(dv_ref.dtype)
        else:
            dk_acc[:, keys] = dk_acc[:, keys] + dk
            dv_acc[:, keys] = dv_acc[:, keys] + dv

    if not one_tile:
        @pl.when(i == 0)
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    _visit(i, j, i >= first_i, one_tile, update, at,
           _key_slabs(block_k, slab))

    if not one_tile:
        @pl.when(i == pl.num_programs(2) - 1)
        def _():
            dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _bwd_impl(scale, block_q, block_k, g, interpret, causal, rate, slab,
              res, do, dlse=None):
    """Shared backward: dlse (N, T, 1) is the cotangent of the logsumexp
    output when the caller differentiates through it (the ring merge does;
    plain flash_attention passes None). Math: with L = sum(do*out) +
    sum(dlse*lse), ds = p * (dp - delta + dlse) — i.e. dlse just shifts
    the per-row delta term, since d lse/d s_j = p_j."""
    q, k, v, seed, out, lse = res
    N, T, D = q.shape
    S, Nkv = k.shape[1], k.shape[0]
    rep = N // Nkv
    nq, nk = T // block_q, S // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # (N, T, 1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    kv_spec = _kv_spec(rep, g, block_q, block_k, D, causal)

    def q_row(r, i, j):
        return (r, i, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, rate=rate,
                          slab=slab, one_tile=nq == nk == 1),
        grid=(N // g, nq, nk),
        in_specs=[
            _SEED_SPEC,
            pl.BlockSpec((g, block_q, D), q_row),
            kv_spec,
            kv_spec,
            pl.BlockSpec((g, block_q, D), q_row),
            pl.BlockSpec((g, block_q, 1), q_row),
            pl.BlockSpec((g, block_q, 1), q_row),
        ],
        out_specs=pl.BlockSpec((g, block_q, D), q_row),
        out_shape=_sds((N, T, D), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((g, block_q, D), jnp.float32)],
        compiler_params=_SEMANTICS,
        name="flash_bwd_dq",
        interpret=interpret,
    )(seed, q, k, v, do, lse, delta)

    def q_idx(r, j, i):
        # clamp sub-frontier q tiles (skipped compute) to an already-visible
        # index so no fresh DMA is issued
        ic = i if not causal \
            else jnp.maximum(i, _first_visible_q(j, block_q, block_k))
        return (r, ic, 0)

    # dkv grid is (row-group, kv-tile j, q-tile i): kv tiles are the
    # resident operand (indexed by j directly, no causal clamp needed)
    kv_block = (g if rep == 1 else 1, block_k, D)

    def kv_row(r, j, i):
        return (r if rep == 1 else r // rep, j, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, rate=rate,
                          slab=slab, one_tile=nq == nk == 1),
        grid=(N // g, nk, nq),
        in_specs=[
            _SEED_SPEC,
            pl.BlockSpec((g, block_q, D), q_idx),
            pl.BlockSpec(kv_block, kv_row),
            pl.BlockSpec(kv_block, kv_row),
            pl.BlockSpec((g, block_q, D), q_idx),
            pl.BlockSpec((g, block_q, 1), q_idx),
            pl.BlockSpec((g, block_q, 1), q_idx),
        ],
        out_specs=[
            # per-QUERY-row dk/dv tiles (kv tiles are shared across a GQA
            # group, so writes would collide at the kv row count);
            # group-summed below
            pl.BlockSpec((g, block_k, D), lambda r, j, i: (r, j, 0)),
            pl.BlockSpec((g, block_k, D), lambda r, j, i: (r, j, 0)),
        ],
        out_shape=[
            _sds((N, S, D), k.dtype, q),
            _sds((N, S, D), v.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, block_k, D), jnp.float32),
            pltpu.VMEM((g, block_k, D), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        name="flash_bwd_dkv",
        interpret=interpret,
    )(seed, q, k, v, do, lse, delta)
    if rep > 1:
        # query rows r and r+1 ... sharing kv row r // rep are consecutive,
        # so the group-sum is a plain reshape-reduce to the kv row count
        dk = dk.reshape(Nkv, rep, S, D).sum(axis=1)
        dv = dv.reshape(Nkv, rep, S, D).sum(axis=1)
    return dq, dk, dv, None  # seed (int32) gets no cotangent


# One custom_vjp serves both public entries: (out, lse) with the lse
# output differentiable (the ring merge needs d/dlse; when a caller
# ignores lse, jax hands back a zero cotangent and the backward reduces
# to plain FlashAttention-2). `seed` is a traced (2,) int32 operand (no
# cotangent); `rate` is static.

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_lse(q, k, v, seed, scale, block_q, block_k, g, interpret,
               causal, rate, slab):
    return _fwd(q, k, v, seed, scale, block_q, block_k, g, interpret,
                causal, rate, slab)


def _flash_lse_fwd(q, k, v, seed, scale, block_q, block_k, g, interpret,
                   causal, rate, slab):
    out, lse = _fwd(q, k, v, seed, scale, block_q, block_k, g, interpret,
                    causal, rate, slab)
    return (out, lse), (q, k, v, seed, out, lse)


def _flash_lse_bwd(scale, block_q, block_k, g, interpret, causal, rate,
                   slab, res, cts):
    do, dlse = cts
    return _bwd_impl(scale, block_q, block_k, g, interpret, causal, rate,
                     slab, res, do, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# public entry points (interface fixed by ops/attention_core.py)
# ---------------------------------------------------------------------------

def _pick_block(n: int, preferred: int) -> int:
    """Largest divisor of n that is <= preferred and a multiple of 8."""
    b = min(preferred, n)
    while b > 8 and (n % b != 0):
        b -= 8
    return b if n % b == 0 else 0


def slab_plan(T: int, S: int, causal: bool = True, block_q: int = 0,
              block_k: int = 0):
    """(plan, share) of a call over T queries and S keys. `plan` is
    `(w, n_slabs)` where the call's diagonal tiles are worked in `n_slabs`
    row slabs of `w` = SLAB_W rows that stop at the diagonal, and None where
    they are not: a call that is not causal, a tiling that is not square, a
    tile that `w` does not cut into two slabs or more. `share` is the part
    of the T x S score square the kernels compute: every tile the causal
    frontier lets through, a slabbed diagonal tile counted at (n + 1) / 2n
    of its square. Static; the kernels, the dispatcher's path note and the
    tests read this one function."""
    bq = block_q or _pick_block(T, BLOCK_Q)
    bk = block_k or _pick_block(S, BLOCK_K)
    nq, nk = T // bq, S // bk
    if not causal:
        return None, 1.0
    tiles = sum(min(nk, (i * bq + bq - 1) // bk + 1) for i in range(nq))
    n = bq // SLAB_W
    if bq != bk or bq % SLAB_W or n < 2:
        return None, tiles / (nq * nk)
    skipped = min(nq, nk) * (n - 1) / (2 * n)
    return (SLAB_W, n), (tiles - skipped) / (nq * nk)


def flash_attention_decline(q, k, v, *, causal: bool = True):
    """Why the dispatcher may NOT send this call to the kernel — None
    when it may. Static (shapes/dtypes only)."""
    T, hs = q.shape[1], q.shape[3]
    S = k.shape[1]
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {q.dtype} (kernel handles float32 / bfloat16)"
    if T < 8 or S < 8:
        return f"T={T}, S={S}: decode-step shapes take the naive path"
    if hs % 8 != 0:
        return f"head dim {hs} is not a sublane (8) multiple"
    bq = _pick_block(T, BLOCK_Q)
    bk = _pick_block(S, BLOCK_K)
    if not (bq and bk):
        return f"no block split (multiple of 8) divides T={T}, S={S}"
    # even a group of 1 must fit the per-step VMEM limit
    dsize = jnp.dtype(q.dtype).itemsize
    need = _vmem_bytes(1, bq, bk, hs, dsize)
    if need <= VMEM_LIMIT_BYTES:
        return None
    return (f"one ({bq}, {bk}) tile step at head dim {hs} needs "
            f"{need >> 20} MiB of VMEM, over the "
            f"{VMEM_LIMIT_BYTES >> 20} MiB scoped limit")


def flash_attention_usable(q, k, v, *, causal: bool = True) -> bool:
    """Static gate for the dispatcher: shapes/dtypes this kernel handles
    (causal and full attention both supported since round 4)."""
    return flash_attention_decline(q, k, v, causal=causal) is None


def flash_attention_lse(q, k, v, *, scale: float, causal: bool = True,
                        block_q: int = 0, block_k: int = 0,
                        block_h: int = 0, dropout_rate: float = 0.0,
                        dropout_rng=None, interpret: bool = False):
    """Flash attention returning (out, lse) over BTNH-layout tensors.

    out: (B, T, nh, hs); lse: (B, T, nh) f32 logsumexp of the scaled
    scores — DIFFERENTIABLE (custom vjp folds d/dlse into the delta
    term). This is the building block for ring attention's cross-chunk
    online-softmax merge (ops/ring_attention.py): each chunk contributes
    a normalized partial (out_c, lse_c) pair and the merge is plain jnp.
    `causal=False` computes full (unmasked) attention — the visible
    off-diagonal chunks of a causal ring.

    `dropout_rate` > 0 applies attention-weight dropout INSIDE the kernel
    (reference model.py:149-151 SDPA dropout): normalized weights are
    masked/rescaled via the TPU per-core PRNG, reseeded per score tile
    from `dropout_rng` so forward and backward regenerate identical bits
    (no mask tensor ever exists in HBM). NOTE: lse is computed from the
    UNdropped scores (it is the true logsumexp). The sp ring path applies
    dropout in its einsum hops with GLOBAL-position keying instead
    (ops/ring_attention.py _hop_dropout_mask); flash hops stay rate==0.
    """
    B, T, nh, hs = q.shape
    S, nkv = k.shape[1], k.shape[2]
    assert hs % 8 == 0, "head dim must be a multiple of 8 (sublane)"
    assert nh % nkv == 0, "query heads must be a multiple of kv heads"
    rep = nh // nkv

    block_q = block_q or _pick_block(T, BLOCK_Q)
    block_k = block_k or _pick_block(S, BLOCK_K)
    assert block_q and T % block_q == 0 and block_k and S % block_k == 0, (
        f"no usable block split for T={T}, S={S} — gate with "
        f"flash_attention_usable first")

    rate = float(dropout_rate)
    if rate > 0.0:
        assert dropout_rng is not None, \
            "dropout_rate > 0 requires a dropout_rng key"
        assert rate < 1.0
        seed = jax.random.randint(dropout_rng, (2,), -2 ** 31, 2 ** 31 - 1,
                                  jnp.int32)
    else:
        seed = jnp.zeros((2,), jnp.int32)

    g = block_h or _pick_group(B * nh, rep, block_q, block_k, hs,
                               jnp.dtype(q.dtype).itemsize)
    assert (B * nh) % g == 0 and (g == 1 or rep == 1), (
        f"row group {g} must divide B*nh={B * nh} and needs nh == n_kv")

    # BTNH -> (B*H, T, D) row-major rows for group-blocked grids
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * nh, T, hs)
    kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * nkv, S, hs)
    vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * nkv, S, hs)
    plan, _ = slab_plan(T, S, causal, block_q, block_k)
    out, lse = _flash_lse(qt, kt, vt, seed, float(scale), block_q, block_k,
                          g, interpret, causal, rate, plan[0] if plan else 0)
    out = jnp.transpose(out.reshape(B, nh, T, hs), (0, 2, 1, 3))
    lse = jnp.transpose(lse.reshape(B, nh, T), (0, 2, 1))
    return out, lse


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    q_offset=0, block_q: int = 0, block_k: int = 0,
                    block_h: int = 0, dropout_rate: float = 0.0,
                    dropout_rng=None, interpret: bool = False) -> jnp.ndarray:
    """Flash attention over BTNH-layout tensors.

    q: (B, T, nh, hs); k, v: (B, S, nkv, hs) with nkv | nh. `q_offset`
    must be a static 0 (prefill/training; the dispatcher routes
    cached-decode offsets — including traced ones — to the naive path).
    GQA kv heads are shared via the kernel's index maps; K/V are never
    materialized per query head. `dropout_rate`/`dropout_rng` enable
    in-kernel attention-weight dropout (see flash_attention_lse).
    """
    assert isinstance(q_offset, int) and q_offset == 0, (
        "flash kernel requires a static q_offset == 0; cached-decode "
        "offsets must use the naive path")
    out, _ = flash_attention_lse(q, k, v, scale=scale, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 block_h=block_h,
                                 dropout_rate=dropout_rate,
                                 dropout_rng=dropout_rng,
                                 interpret=interpret)
    return out
