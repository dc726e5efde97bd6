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


# YaRN's (fast, slow) turns over the original context, between which a
# frequency is blended: the published default of every configuration run
YARN_BETA = (32.0, 1.0)


def yarn_ramp(dim: int, base: float, original_len: int,
              beta: tuple = YARN_BETA) -> tuple:
    """(low, high, ramp) of YaRN over the dim//2 frequencies of `dim`
    rotated lanes, the published Hugging Face rule with `truncate` on:
    frequency i turns `original_len` base^(-2i/dim) / 2 pi times over
    the original context; `low` is the last index that still makes
    beta[0] (fast) turns, rounded down, `high` the first that makes no
    more than beta[1] (slow), rounded up, and the ramp runs 0 .. 1
    between them: 0 keeps a frequency, 1 divides it by the factor."""
    import math

    def index_of(turns: float) -> float:
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(index_of(beta[0])), 0)
    high = min(math.ceil(index_of(beta[1])), dim - 1)
    span = max(high - low, 1e-3)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    return low, high, ramp


def rope_angles(pos, length: int, dim: int, base: float, *,
                yarn: tuple = (), attn_factor: float = 1.0) -> jnp.ndarray:
    """The (cos, sin) of positions pos .. pos + length - 1 in the table's
    format, computed: (length, dim//2, 2) for a scalar `pos` (static or
    traced), (B, length, dim//2, 2) for a per-sequence (B,) array.
    `yarn` = (factor, original_len) blends each frequency between
    itself and itself / factor along `yarn_ramp`;
    `attn_factor` multiplies cos and sin (YaRN's attention temperature,
    folded into the rotation as the published code folds it)."""
    assert dim % 2 == 0, "head dimension must be even"
    theta = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if yarn:
        factor, original_len = yarn
        ramp = yarn_ramp(dim, base, original_len)[2]
        theta = (1.0 - ramp) * theta + ramp * theta / factor
    p = jnp.asarray(pos, jnp.int32)
    p = (p[:, None] if p.ndim else p) + jnp.arange(length, dtype=jnp.int32)
    ang = p.astype(jnp.float32)[..., None] * theta
    out = jnp.stack([jnp.cos(ang), jnp.sin(ang)], axis=-1)
    return out * attn_factor if attn_factor != 1.0 else out


def apply_partial_rotary(x: jnp.ndarray, freqs: jnp.ndarray, *,
                         half: bool = False) -> jnp.ndarray:
    """`apply_rotary_emb` on the first 2 * freqs.shape[-2] lanes of every
    head; the lanes behind them pass as they are (a published
    `partial_rotary_factor`). All lanes: `apply_rotary_emb` itself."""
    rd = 2 * freqs.shape[-2]
    if rd == x.shape[-1]:
        return apply_rotary_emb(x, freqs, half=half)
    return jnp.concatenate(
        [apply_rotary_emb(x[..., :rd], freqs, half=half), x[..., rd:]],
        axis=-1)


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
