"""Rotary position embeddings (RoPE), in both pairings of the lanes.

Reference parity: `LLMconfig.apply_rotary_emb` + `LLM._precompute_freqs_cis`
(reference single-gpu/model.py:77-96,567-577): theta base 10000, pairs taken
*adjacently* along the head dim (x reshaped to (..., hs//2, 2)), rotation by
complex multiply. That is a classic model's, from a precomputed table.

The other pairing is the published `rotate_half` of the Hugging Face
families: lane i turns with lane i + hs/2, by the same angle
t * base^(-2i/hs) (`apply_rotary_emb(half=True)`). Which of the two a
model uses is its configuration's `LLMConfig.rope_pairing`, and the base
its `rope_theta`. A patterned model's attention layers are handed no
table (its length would be the model's context) and compute the angles
from the slots' own positions (`rope_angles`). The two pairings are one
rotation under a permutation of the lanes, so a checkpoint of one
convention is not a checkpoint of the other.

TPU-first divergence: no complex dtypes. XLA on TPU lowers complex arithmetic
to pairs of real ops anyway, and Pallas kernels can't consume complex inputs;
we precompute real (cos, sin) tables and rotate with two fused multiplies.
Numerics are identical (same pairing, same angles).
"""

from __future__ import annotations

import jax.numpy as jnp


def precompute_rope_freqs(dim: int, max_seq_len: int, base: float = 10000.0,
                          dtype=jnp.float32) -> jnp.ndarray:
    """Return a (max_seq_len, dim//2, 2) table of (cos, sin) angles.

    Matches reference _precompute_freqs_cis (model.py:567-577):
    theta_i = base^(-2i/dim), angle[t, i] = t * theta_i.
    """
    assert dim % 2 == 0, "head dimension must be even"
    theta = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    seq = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(seq, theta)  # (T, dim//2)
    return jnp.stack([jnp.cos(freqs), jnp.sin(freqs)], axis=-1).astype(dtype)


def rope_angles(pos, length: int, dim: int, base: float) -> jnp.ndarray:
    """The (cos, sin) of positions pos .. pos + length - 1 in the table's
    format, computed: (length, dim//2, 2) for a scalar `pos` (static or
    traced), (B, length, dim//2, 2) for a per-sequence (B,) array."""
    assert dim % 2 == 0, "head dimension must be even"
    theta = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    p = jnp.asarray(pos, jnp.int32)
    p = (p[:, None] if p.ndim else p) + jnp.arange(length, dtype=jnp.int32)
    ang = p.astype(jnp.float32)[..., None] * theta
    return jnp.stack([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def slice_rows(table: jnp.ndarray, pos, length: int) -> jnp.ndarray:
    """table[pos : pos+length] along axis 0, supporting traced `pos`
    (KV-cached decode), a per-sequence (B,) position array (slot-based
    ragged decode — returns a leading batch axis, (B, length, ...)), and
    the static pos==0 fast path. Shared by RoPE freq / positional-embedding
    lookups. Out-of-table positions clamp to the last row
    (dynamic_slice semantics) — the sliding-window behavior once the ring
    cache wraps past the table."""
    import jax
    if isinstance(pos, int) and pos == 0:
        return table[:length]
    pos = jnp.asarray(pos)
    if pos.ndim == 1:
        return jax.vmap(lambda p: jax.lax.dynamic_slice_in_dim(
            table, p, length, axis=0))(pos)
    return jax.lax.dynamic_slice_in_dim(table, pos, length, axis=0)


def apply_rotary_emb(x: jnp.ndarray, freqs: jnp.ndarray,
                     half: bool = False) -> jnp.ndarray:
    """Rotate pairs (x[..., 2i], x[..., 2i+1]) by the angles in `freqs`;
    with `half` the pairs (x[..., i], x[..., i + hs//2]) (`rotate_half`).

    x: (B, T, H, hs); freqs: (T, hs//2, 2) slice of the precomputed table
    (caller slices [start_pos : start_pos+T] for KV-cached decoding, like
    reference model.py:660), or a per-sequence (B, T, hs//2, 2) slice when
    sequences in the batch sit at different positions (slot-based ragged
    decode). Computation in fp32, cast back to x.dtype (matching reference
    `x.float()` ... `type_as(x)`).
    """
    B, T, H, hs = x.shape
    if half:
        xf = x.astype(jnp.float32)
        x_re, x_im = xf[..., :hs // 2], xf[..., hs // 2:]
    else:
        xf = x.astype(jnp.float32).reshape(B, T, H, hs // 2, 2)
        x_re, x_im = xf[..., 0], xf[..., 1]
    if freqs.ndim == 4:               # per-sequence rows
        cos = freqs[:, :, None, :, 0]  # (B, T, 1, hs//2)
        sin = freqs[:, :, None, :, 1]
    else:
        cos = freqs[None, :, None, :, 0]  # (1, T, 1, hs//2)
        sin = freqs[None, :, None, :, 1]
    out_re = x_re * cos - x_im * sin
    out_im = x_re * sin + x_im * cos
    if half:
        out = jnp.concatenate([out_re, out_im], axis=-1)
    else:
        out = jnp.stack([out_re, out_im], axis=-1).reshape(B, T, H, hs)
    return out.astype(x.dtype)
