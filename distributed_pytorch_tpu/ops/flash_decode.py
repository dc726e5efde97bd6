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
  before the body runs, so grid steps past a sequence's last valid block
  are predicated off with `pl.when` AND their kv index map clamps to the
  last visible block — the revolving-buffer DMA sees an unchanged index
  and issues no fetch. A sequence three tokens into a 1024-slot cache
  costs one grid step, not eight: padded slots cost zero compute and
  zero HBM traffic.
* The last partial block masks `kpos >= cache_len` to a large negative
  (NaN-free) before the max/sum update.
* **Chunked-prefill variant** (`paged_flash_prefill`, round 12): the
  paged decode kernel generalized from one query row per sequence to a
  (T, rep)-packed query tile of ONE sequence — a prefill chunk written
  at an arbitrary block-aligned offset attends the sequence's own prior
  blocks plus its in-chunk causal prefix, with per-row global positions
  in the mask. This is the device half of the engine's fused
  chunk+decode step (engine/decode.py `prefill_chunk`); bf16 and int8
  pools ride the same block-table index map.

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
from distributed_pytorch_tpu.compat import tpu_compiler_params

# KV-length tile (lane dimension of the score tiles). Env knob so
# `mfu_sweep --variants decode` can ablate it per subprocess, like
# FLASH_BLOCK_* / GMM_BLOCK_*.
DEFAULT_BLOCK_S = config.knob("FLASH_DECODE_BLOCK")

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked rows NaN-free

# one grid step's buffers: double-buffered kv tiles + f32 scratch + scores
_VMEM_BUDGET = config.knob("FLASH_VMEM_BUDGET_MB") * 2 ** 20


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


def _paged_body(cl_ref, bt_ref, *args, scale: float, block_s: int):
    """Paged bf16 kernel: identical online-softmax body — the block table
    ref is consumed by the index maps only."""
    del bt_ref
    _kernel(cl_ref, *args, scale=scale, block_s=block_s)


def _paged_body_q8(cl_ref, bt_ref, *args, scale: float, block_s: int):
    del bt_ref
    _kernel_q8(cl_ref, *args, scale=scale, block_s=block_s)


def paged_flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       block_tables: jnp.ndarray, cache_len: jnp.ndarray, *,
                       scale: float, k_scale: jnp.ndarray = None,
                       v_scale: jnp.ndarray = None,
                       interpret: bool = False) -> jnp.ndarray:
    """Single-token cached attention over a PAGED cache: q (B, nh, hs)
    against (n_blocks, bs, n_kv, hs) pool buffers (ops/block_pool.py),
    with per-sequence block tables (B, max_blocks) int32 and valid
    lengths `cache_len` (B,). Returns (B, nh, hs).

    This is the contiguous kernel's `cache_len` scalar-prefetch
    generalized by ONE indirection: the grid walks each sequence's
    logical blocks (grid dim 1 = max_blocks) and the kv index map
    resolves logical j -> physical pool block through the prefetched
    table. The dead-block machinery is unchanged — steps past a
    sequence's last valid block clamp to it, the revolving-buffer DMA
    sees an unchanged physical index and fetches nothing, and the last
    partial block masks `kpos >= cache_len`. int8 pools bring their
    scale-sidecar pools through the same index map. Gate with
    `paged_flash_decode_usable`."""
    B, nh, hs = q.shape
    bs, nkv = k.shape[1], k.shape[2]
    n_max = block_tables.shape[1]
    rep = nh // nkv
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "int8 cache needs both k_scale and v_scale"

    cl = jnp.asarray(cache_len, jnp.int32).reshape(B)
    bt = jnp.asarray(block_tables, jnp.int32)
    q4 = q.reshape(B, nkv, rep, hs)

    def q_idx(b, j, cl_ref, bt_ref):
        return (b, 0, 0, 0)

    def kv_idx(b, j, cl_ref, bt_ref):
        # clamp skipped steps to the last valid LOGICAL block, then map to
        # its physical pool block: the revolving buffer sees an unchanged
        # index -> no DMA for dead blocks (same trick as the contiguous
        # kernel, one table lookup deeper)
        last = jax.lax.div(jnp.maximum(cl_ref[b], 1) - 1, bs)
        return (bt_ref[b, jnp.minimum(j, last)], 0, 0, 0)

    in_specs = [pl.BlockSpec((1, nkv, rep, hs), q_idx)]
    operands = [q4]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
            pl.BlockSpec((1, bs, nkv, 1), kv_idx),
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
            pl.BlockSpec((1, bs, nkv, 1), kv_idx),
        ]
        operands += [k, k_scale.astype(jnp.float32),
                     v, v_scale.astype(jnp.float32)]
        body = _paged_body_q8
    else:
        in_specs += [
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
        ]
        operands += [k, v]
        body = _paged_body

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_max),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, rep, hs), q_idx),
        scratch_shapes=[
            pltpu.VMEM((nkv, rep, hs), jnp.float32),
            pltpu.VMEM((nkv, rep, 1), jnp.float32),
            pltpu.VMEM((nkv, rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(body, scale=float(scale), block_s=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, rep, hs), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_flash_decode" + ("_q8" if quantized else ""),
        interpret=interpret,
    )(cl, bt, *operands)
    return out.reshape(B, nh, hs)


def _prefill_kernel(meta_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, scale: float, bs: int,
                    rep: int):
    """Chunked-prefill body: T queries of ONE sequence (packed (t, rep)
    into the sublane dim) against its own paged blocks, causal against
    the global positions `off + t`. Same online-softmax state as the
    decode kernels — only the mask gains the per-row query position."""
    j = pl.program_id(0)
    off = meta_ref[0]
    n_rows = q_ref.shape[1]                     # T * rep (static)
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
        k = k_ref[0].transpose(1, 0, 2)         # (nkv, bs, hs)
        v = v_ref[0].transpose(1, 0, 2)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (nkv, T*rep, bs)
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
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


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


def paged_flash_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        block_tables: jnp.ndarray, q_offset, *,
                        scale: float, k_scale: jnp.ndarray = None,
                        v_scale: jnp.ndarray = None,
                        interpret: bool = False) -> jnp.ndarray:
    """Mixed-path chunk attention over a PAGED cache: q (1, T, nh, hs) —
    a prefill chunk of ONE sequence whose rows sit at global positions
    [q_offset, q_offset+T) — against the (n_blocks, bs, n_kv, hs) pool,
    addressed through the sequence's block table (1, max_blocks) int32.
    The chunk's rows must already be written to the pool (the attention
    path writes before it reads, exactly like the wave prefill). Returns
    (1, T, nh, hs).

    This is `paged_flash_decode` generalized from one query row to a
    (t, rep)-packed query tile: the grid still walks logical blocks with
    the prefetched table resolving physical ids, steps past the chunk's
    last needed block clamp to it (no DMA), and the causal mask compares
    each row's global position `q_offset + t` against the block's key
    positions — so a chunk at an arbitrary block-aligned offset attends
    the sequence's own prior blocks and its own in-chunk prefix, never a
    neighbor's. int8 pools ride the same index map (`k_scale`/`v_scale`
    sidecar pools). Gate with `paged_flash_prefill_usable`."""
    B, T, nh, hs = q.shape
    assert B == 1, "chunk prefill attends one sequence at a time"
    bs, nkv = k.shape[1], k.shape[2]
    n_max = block_tables.shape[1]
    rep = nh // nkv
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), \
        "int8 cache needs both k_scale and v_scale"

    meta = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))
    bt = jnp.asarray(block_tables, jnp.int32).reshape(n_max)
    # pack (t, rep) into the sublane dim: row r of kv head g is query
    # head g*rep + r%rep at chunk position r//rep
    q3 = q[0].reshape(T, nkv, rep, hs).transpose(1, 0, 2, 3) \
        .reshape(nkv, T * rep, hs)

    def q_idx(j, meta_ref, bt_ref):
        return (0, 0, 0)

    def kv_idx(j, meta_ref, bt_ref):
        last = jax.lax.div(jnp.maximum(meta_ref[0] + T, 1) - 1, bs)
        return (bt_ref[jnp.minimum(j, last)], 0, 0, 0)

    in_specs = [pl.BlockSpec((nkv, T * rep, hs), q_idx)]
    operands = [q3]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
            pl.BlockSpec((1, bs, nkv, 1), kv_idx),
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
            pl.BlockSpec((1, bs, nkv, 1), kv_idx),
        ]
        operands += [k, k_scale.astype(jnp.float32),
                     v, v_scale.astype(jnp.float32)]
        body = _prefill_kernel_q8
    else:
        in_specs += [
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
            pl.BlockSpec((1, bs, nkv, hs), kv_idx),
        ]
        operands += [k, v]
        body = _prefill_kernel

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_max,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nkv, T * rep, hs), q_idx),
        scratch_shapes=[
            pltpu.VMEM((nkv, T * rep, hs), jnp.float32),
            pltpu.VMEM((nkv, T * rep, 1), jnp.float32),
            pltpu.VMEM((nkv, T * rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(body, scale=float(scale), bs=bs, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nkv, T * rep, hs), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        name="paged_flash_prefill" + ("_q8" if quantized else ""),
        interpret=interpret,
    )(meta, bt, *operands)
    return out.reshape(nkv, T, rep, hs).transpose(1, 0, 2, 3) \
        .reshape(1, T, nh, hs)


def _common_decline(q, k, nh, nkv, hs, bs, what: str):
    """Checks shared by the three gates (dtypes, head geometry, the tile
    the hardware splits the KV length by, no live multi-device mesh)."""
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"query dtype {q.dtype} (kernel handles float32 / bfloat16)"
    if k.dtype != q.dtype and k.dtype != jnp.int8:
        return f"cache dtype {k.dtype} is neither {q.dtype} nor int8"
    if hs % 8 != 0 or nh % nkv != 0:
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
    if need <= _VMEM_BUDGET:
        return None
    return (f"one grid step needs {need >> 20} MiB of VMEM, over the "
            f"{_VMEM_BUDGET >> 20} MiB scoped limit (FLASH_VMEM_BUDGET_MB)")


def _kv_tile_bytes(k, bs: int, nkv: int, hs: int) -> int:
    """Double-buffered k+v tiles (+ f32 scale rows for an int8 cache)."""
    tiles = 2 * 2 * bs * nkv * hs * jnp.dtype(k.dtype).itemsize
    if k.dtype == jnp.int8:
        tiles += 2 * 2 * bs * nkv * 4
    return tiles


def paged_flash_prefill_decline(q, k, v, block_tables):
    """Why the chunk-prefill kernel cannot take this call (None = it
    can), mirroring `paged_flash_decode_decline`: one sequence's
    (1, T>1, nh, hs) chunk, whole-block pool pages the hardware tiles, T
    a multiple of the sublane step, and the packed query tile + f32
    accumulator within the VMEM budget. The fallback is paged_gather +
    the naive masked path — identical semantics."""
    if q.ndim != 4 or q.shape[0] != 1 or q.shape[1] <= 1:
        return f"query shape {q.shape} is not one sequence's (1, T>1) chunk"
    _, T, nh, hs = q.shape
    bs, nkv = k.shape[1], k.shape[2]
    if T % 8 != 0:
        return f"chunk length {T} is not a sublane (8) multiple"
    why = _common_decline(q, k, nh, nkv, hs, bs, f"pool block size {bs}")
    if why is not None:
        return why
    rows = T * (nh // nkv)
    dsize = jnp.dtype(k.dtype).itemsize
    qtile = nkv * rows * hs * dsize
    scratch = nkv * rows * (hs + 2) * 4
    scores = 3 * nkv * rows * bs * 4
    return _budget_decline(_kv_tile_bytes(k, bs, nkv, hs) + qtile + scratch
                           + scores)


def paged_flash_decode_decline(q, k, v, block_tables):
    """Why the paged kernel cannot take this call (None = it can),
    mirroring `flash_decode_decline`: decode-shaped (B, 1, nh, hs) query,
    pool block size the hardware tiles (multiples of 128 rows on TPU —
    small CPU-test pages run in interpret mode at multiples of 8), no live
    multi-device mesh. The fallback is paged_gather + the naive path —
    identical semantics."""
    if q.ndim != 4 or q.shape[1] != 1:
        return f"query shape {q.shape} is not decode-shaped (B, 1, nh, hs)"
    _, _, nh, hs = q.shape
    bs, nkv = k.shape[1], k.shape[2]
    why = _common_decline(q, k, nh, nkv, hs, bs, f"pool block size {bs}")
    if why is not None:
        return why
    rep = nh // nkv
    scratch = nkv * rep * (hs + 2) * 4
    scores = 3 * nkv * rep * bs * 4
    return _budget_decline(_kv_tile_bytes(k, bs, nkv, hs) + scratch + scores)


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
    return _budget_decline(_kv_tile_bytes(k, block_s, nkv, hs) + scratch
                           + scores)


def paged_flash_prefill_usable(q, k, v, block_tables) -> bool:
    return paged_flash_prefill_decline(q, k, v, block_tables) is None


def paged_flash_decode_usable(q, k, v, block_tables) -> bool:
    return paged_flash_decode_decline(q, k, v, block_tables) is None


def flash_decode_usable(q, k, v) -> bool:
    return flash_decode_decline(q, k, v) is None
