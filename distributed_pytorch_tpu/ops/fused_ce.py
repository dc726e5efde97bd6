"""Pallas TPU fused cross-entropy: the lm-head matmul and the softmax/CE
reduction in one streaming kernel — logits NEVER exist in HBM.

Why: the chunked-CE scan (ops/losses.py) still materializes each
(B, chunk, V) fp32 logits block in HBM and re-reads it for logsumexp /
target-gather / backward; on the v5e profile that bucket is ~77 ms/step of
the 264 ms flagship step (PERF.md round 4) vs a ~25 ms FLOPs floor. This
kernel streams (token_block, vocab_block) tiles through VMEM with an
online logsumexp, so HBM traffic is just x, W and the per-token outputs —
the softmax never round-trips.

Structure (FlashAttention-2 applied to the vocab axis; reference CE is
`F.cross_entropy` over full logits, single-gpu/model.py:687-692):

* forward — grid (n_token_blocks, n_vocab_blocks), vocab innermost: one
  (bn, C) x tile is resident while (bv, C) W tiles stream; VMEM scratch
  holds running max m, normalizer l, and the target logit; the last vocab
  step emits per-token nll = lse - logit[target] and lse.
* backward dx — same grid: recomputes the score tile from the saved lse,
  p = exp(s - lse), dlogits = (p - onehot(target)) * d_nll, accumulates
  dx += dlogits @ W_tile in VMEM scratch.
* backward dW — transposed grid (n_vocab_blocks, n_token_blocks): one W
  tile resident, x tiles stream, accumulates dW_tile += dlogits^T @ x.

The vocab is zero-padded (host-side, ~1 MB copy) to a multiple of the
vocab block so no tile ever reads out of bounds; padded columns are masked
to -1e30 before the max. All accumulation is f32; matmul operands stay in
the input dtype (bf16 on TPU) so the MXU runs at full rate.

Sharding: tokens are independent, so under a live mesh the wrapper runs
the kernel inside shard_map over the 'data' axis (W replicated in-spec;
shard_map's transpose psums the W cotangent across shards). Vocab-parallel
lm_head (tp) and sequence-parallel T are NOT supported — callers gate on
model==1 and seq==1 (gpt.py does; `loss_impl='pallas'` that cannot be
honoured is an error there, naming the gate — never a quiet 'fused' run).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu import config
from distributed_pytorch_tpu.compat import tpu_compiler_params

DEFAULT_BLOCK_N = config.knob("CE_BLOCK_N")     # tokens
DEFAULT_BLOCK_V = config.knob("CE_BLOCK_V")     # vocab

_NEG_INF = -1e30

_SEMANTICS = tpu_compiler_params(
    dimension_semantics=("parallel", "arbitrary"))


def _dot(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a^T @ b with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _score_tile(x, w, j, bv, vocab_size):
    """(bn, bv) f32 logits tile with padded columns masked to -1e30.
    Returns (s, col) where col is the global vocab index per column."""
    s = _dot(x, w, trans_b=True)                          # (bn, bv) f32
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < vocab_size, s, _NEG_INF)
    return s, col


# ---------------------------------------------------------------------------
# forward: per-token nll + lse
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, t_ref, nll_ref, lse_ref, m_ref, l_ref, tgt_ref,
                *, bv, vocab_size):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        tgt_ref[:] = jnp.zeros_like(tgt_ref)

    s, col = _score_tile(x_ref[:], w_ref[:], j, bv, vocab_size)
    m_prev, l_prev = m_ref[:], l_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    l_ref[:] = l_prev * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True)
    m_ref[:] = m_new
    # target logit: exactly one vocab tile contains column t per row
    t = t_ref[:]                                          # (bn, 1) int32
    tgt_ref[:] = tgt_ref[:] + jnp.sum(
        jnp.where(col == t, s, 0.0), axis=-1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
        lse_ref[:] = lse
        nll_ref[:] = lse - tgt_ref[:]


def _fwd(x, w_pad, t, bn, bv, vocab_size, interpret):
    n, c = x.shape
    v_pad = w_pad.shape[0]
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, vocab_size=vocab_size),
        grid=(n // bn, v_pad // bv),
        in_specs=[
            pl.BlockSpec((bn, c), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, c), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        name="ce_fwd",
        interpret=interpret,
    )(x, w_pad, t)
    return nll, lse


# ---------------------------------------------------------------------------
# backward: dx (token-major) and dW (vocab-major), both recompute p from lse
# ---------------------------------------------------------------------------

def _dlogits(x, w, t, lse, coef, j, bv, vocab_size):
    """(bn, bv) dlogits tile: (p - onehot(target)) * coef, padded cols 0."""
    s, col = _score_tile(x, w, j, bv, vocab_size)
    p = jnp.exp(s - lse)                    # padded cols: exp(-1e30-lse)=0
    return (p - jnp.where(col == t, 1.0, 0.0)) * coef


def _bwd_dx_kernel(x_ref, w_ref, t_ref, lse_ref, coef_ref, dx_ref, dx_acc,
                   *, bv, vocab_size):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dx_acc[:] = jnp.zeros_like(dx_acc)

    w = w_ref[:]
    dl = _dlogits(x_ref[:], w, t_ref[:], lse_ref[:], coef_ref[:], j, bv,
                  vocab_size)
    dx_acc[:] = dx_acc[:] + _dot(dl.astype(w.dtype), w)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dx_ref[:] = dx_acc[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, t_ref, lse_ref, coef_ref, dw_ref, dw_acc,
                   *, bv, vocab_size):
    i = pl.program_id(1)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    x = x_ref[:]
    dl = _dlogits(x, w_ref[:], t_ref[:], lse_ref[:], coef_ref[:], j, bv,
                  vocab_size)
    dw_acc[:] = dw_acc[:] + _dot_t(dl.astype(x.dtype), x)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)


def _bwd(x, w_pad, t, lse, coef, bn, bv, vocab_size, interpret):
    n, c = x.shape
    v_pad = w_pad.shape[0]
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, bv=bv, vocab_size=vocab_size),
        grid=(n // bn, v_pad // bv),
        in_specs=[
            pl.BlockSpec((bn, c), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, c), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, c), jnp.float32)],
        compiler_params=_SEMANTICS,
        name="ce_bwd_dx",
        interpret=interpret,
    )(x, w_pad, t, lse, coef)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, bv=bv, vocab_size=vocab_size),
        grid=(v_pad // bv, n // bn),
        in_specs=[
            pl.BlockSpec((bn, c), lambda j, i: (i, 0)),
            pl.BlockSpec((bv, c), lambda j, i: (j, 0)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bv, c), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((v_pad, c), w_pad.dtype),
        scratch_shapes=[pltpu.VMEM((bv, c), jnp.float32)],
        compiler_params=_SEMANTICS,
        name="ce_bwd_dw",
        interpret=interpret,
    )(x, w_pad, t, lse, coef)
    return dx, dw


# ---------------------------------------------------------------------------
# custom VJP over per-token nll
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ce_nll(x, w, t, bn, bv, vocab_size, interpret):
    """Per-token nll (n, 1) f32. x (n, C); w (V, C); t (n, 1) int32.
    Rows whose target lies outside [0, V) get nll = lse (their target
    logit contribution is 0) — callers mask ignored rows OUTSIDE, which
    also zeroes their cotangent so the backward ignores them."""
    w_pad = _pad_vocab(w, bv)
    nll, _ = _fwd(x, w_pad, t, bn, bv, vocab_size, interpret)
    return nll


def _ce_nll_fwd(x, w, t, bn, bv, vocab_size, interpret):
    w_pad = _pad_vocab(w, bv)
    nll, lse = _fwd(x, w_pad, t, bn, bv, vocab_size, interpret)
    return nll, (x, w, t, lse)


def _ce_nll_bwd(bn, bv, vocab_size, interpret, res, d_nll):
    x, w, t, lse = res
    w_pad = _pad_vocab(w, bv)
    coef = d_nll.astype(jnp.float32)                     # (n, 1)
    dx, dw_pad = _bwd(x, w_pad, t, lse, coef, bn, bv, vocab_size, interpret)
    return dx, dw_pad[: w.shape[0]], None


_ce_nll.defvjp(_ce_nll_fwd, _ce_nll_bwd)


def _pad_vocab(w, bv):
    v = w.shape[0]
    v_pad = -(-v // bv) * bv
    if v_pad == v:
        return w
    return jnp.pad(w, ((0, v_pad - v), (0, 0)))


def _pick(n: int, preferred: int) -> int:
    """Largest divisor of n that is <= preferred and a multiple of 8;
    0 when no such divisor exists (incl. n == 0, e.g. an eval batch
    smaller than the data-axis size leaving zero local tokens)."""
    if n < 8:
        return 0
    b = min(preferred, n)
    while b > 8 and n % b != 0:
        b -= 8
    return b if (n % b == 0 and b % 8 == 0) else 0


def pallas_ce_decline(n_tokens: int, n_embd: int, dtype):
    """Why the kernel cannot take these shapes (None = it can)."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {dtype} (kernel handles float32 / bfloat16)"
    if n_embd % 128 != 0:          # lane-dim multiple (C is the minor dim)
        return f"n_embd {n_embd} is not a lane (128) multiple"
    if not _pick(n_tokens, DEFAULT_BLOCK_N):
        return (f"{n_tokens} local tokens have no tile divisor (multiple "
                f"of 8, <= {DEFAULT_BLOCK_N})")
    return None


def pallas_ce_usable(n_tokens: int, n_embd: int, dtype) -> bool:
    """Static gate: shapes/dtypes the kernel handles."""
    return pallas_ce_decline(n_tokens, n_embd, dtype) is None


def pallas_cross_entropy(x: jnp.ndarray, embedding: jnp.ndarray,
                         targets: jnp.ndarray, *, ignore_index: int = -1,
                         interpret: bool = False) -> jnp.ndarray:
    """Mean CE over valid targets; drop-in for fused_cross_entropy
    (ops/losses.py) with the same (B, T, C)/(V, C)/(B, T) signature.

    Under a live multi-device mesh the kernel runs inside shard_map over
    the 'data' axis (tokens are independent; W rides in replicated and its
    cotangent is psum'd by the shard_map transpose). Gate with
    `pallas_ce_usable` and seq==1/model==1 before calling.
    """
    B, T, C = x.shape
    mask = targets != ignore_index
    safe_t = jnp.where(mask, targets, -2)   # never matches a vocab column

    def local_nll(x_loc, w, t_loc):
        n = x_loc.shape[0] * x_loc.shape[1]
        bn = _pick(n, DEFAULT_BLOCK_N)
        assert bn, (
            f"pallas_cross_entropy: local token count {n} has no tile "
            f"divisor (multiple of 8, <= {DEFAULT_BLOCK_N}) — gate with "
            "pallas_ce_usable() and fall back to fused_cross_entropy")
        # vocab tiles need no divisor — the vocab is padded to a bv
        # multiple and padded columns are masked; bv just needs the
        # sublane multiple-of-8
        v = embedding.shape[0]
        bv = min(DEFAULT_BLOCK_V, -(-v // 8) * 8)
        nll = _ce_nll(x_loc.reshape(n, C), w,
                      t_loc.reshape(n, 1).astype(jnp.int32),
                      bn, bv, v, interpret)
        return nll.reshape(x_loc.shape[0], x_loc.shape[1])

    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if mesh is not None and mesh.shape.get("data", 1) > 1 \
            and not context.in_sp_region():
        from distributed_pytorch_tpu import compat
        nll = compat.shard_map(
            lambda xs, w, ts: local_nll(xs, w, ts),
            mesh=mesh,
            in_specs=(P("data"), P(), P("data")),
            out_specs=P("data"),
        )(x, embedding, safe_t)
    else:
        nll = local_nll(x, embedding, safe_t)

    denom = jnp.maximum(mask.sum(), 1)
    return jnp.where(mask, nll, 0.0).sum() / denom
