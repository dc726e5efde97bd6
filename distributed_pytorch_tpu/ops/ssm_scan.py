"""The Mamba-2 state-space recurrence, in its two serving forms.

One head keeps a state h of (P, N): P the head size, N the state size.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t C_t + D * x_t

with A < 0 a scalar a head, dt_t > 0 a scalar a head and step, B_t and C_t
(N,) vectors shared by the heads of a group. `ssm_step` is that line for
one token of every sequence in a batch (decode). `ssd_chunked` is the same
recurrence over a whole (padded) sequence in the chunked "state-space
dual" form: inside a chunk of Q steps the outputs are one masked (Q, Q)
product, and only the chunk boundaries are carried sequentially, so a
256-token prefill is 2 sequential steps and otherwise matmuls
(arXiv:2405.21060, section 6). A step with dt = 0 is the identity on the
state (decay 1, input 0): that is how a chunk's pad rows are kept out of
it, a recurrence has no null block to land them in.

Both keep the state in float32. Plain jax.numpy: XLA fuses the decode line
into one pass over the state, which is its roofline (state in, state out).
`causal_conv` / `conv_step` are the depthwise width-K convolution in front
of it, with the last K - 1 inputs as its carried tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _heads(t: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(..., G, N) -> (..., H, N): a group's B or C serves H // G heads."""
    return jnp.repeat(t, n_heads // t.shape[-2], axis=-2)


def ssm_step(h, x, dt, A, B, C, D, live=None):
    """One token. h (S, H, P, N) float32; x (S, H, P); dt (S, H) after the
    softplus; A, D (H,); B, C (S, G, N). Returns (y (S, H, P) float32, h').
    Rows where `live` (S,) is False keep their state."""
    H = h.shape[1]
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = _heads(B.astype(jnp.float32), H)
    Ch = _heads(C.astype(jnp.float32), H)
    decay = jnp.exp(dt * A)[..., None, None]
    h_new = decay * h + (dt[..., None] * x)[..., None] * Bh[..., None, :]
    if live is not None:
        h_new = jnp.where(live[:, None, None, None], h_new, h)
    y = jnp.einsum("shpn,shn->shp", h_new, Ch) + D[:, None] * x
    return y, h_new


def ssd_chunked(x, dt, A, B, C, D, h0=None, *, chunk: int = 128):
    """A whole sequence. x (B, T, H, P); dt (B, T, H) after the softplus,
    0 on pad rows; A, D (H,); B, C (B, T, G, N); h0 (B, H, P, N) or None
    for zeros. Returns (y (B, T, H, P) float32, h_T). T is padded up to a
    multiple of the chunk here, with dt = 0."""
    Bb, T, H, P = x.shape
    Q = min(chunk, T)
    pad = (-T) % Q
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    B, C = B.astype(f32), C.astype(f32)
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc = (T + pad) // Q
    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H)
    Bc = _heads(B, H).reshape(Bb, nc, Q, H, -1)
    Cc = _heads(C, H).reshape(Bb, nc, Q, H, -1)
    cum = jnp.cumsum(dtc * A, axis=2)                       # (B, c, Q, H)
    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B, c, i, j, H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    L = jnp.where(mask, jnp.exp(jnp.where(mask, seg, 0.0)), 0.0)
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc) * L
    xdt = xc * dtc[..., None]
    y = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt)
    # what each chunk adds to the state at its end, and the carried state
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # (B, c, Q, H)
    add = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", Bc, to_end, xdt)
    total = jnp.exp(cum[:, :, -1, :])                       # (B, c, H)
    h = jnp.zeros((Bb, H, P, Bc.shape[-1]), f32) if h0 is None \
        else h0.astype(f32)
    enter = []
    for c in range(nc):                  # the only sequential part: T / Q
        enter.append(h)
        h = total[:, c, :, None, None] * h + add[:, c]
    enter = jnp.stack(enter, axis=1)                        # (B, c, H, P, N)
    y = y + jnp.einsum("bcihn,bchpn->bcihp",
                       Cc * jnp.exp(cum)[..., None], enter)
    y = y + D[:, None] * xc
    return y.reshape(Bb, nc * Q, H, P)[:, :T], h


def causal_conv(u, w, b, tail=None):
    """Depthwise causal convolution over time. u (B, T, D); w (K, D), w[k]
    on the input K - 1 - k steps back; b (D,) or None for no bias; tail
    (B, K - 1, D) the inputs before u[:, 0] (zeros when None). Returns (out
    (B, T, D), the input with its tail in front (B, K - 1 + T, D))."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((u.shape[0], K - 1, u.shape[2]), u.dtype)
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    T = u.shape[1]
    out = sum(full[:, k:k + T] * w[k].astype(u.dtype) for k in range(K))
    return (out if b is None else out + b.astype(u.dtype)), full


def conv_step(u, w, b, tail, live=None):
    """One token of `causal_conv`. u (S, D); tail (S, K - 1, D). Returns
    (out (S, D), tail'). Rows where `live` is False keep their tail."""
    win = jnp.concatenate([tail.astype(u.dtype), u[:, None]], axis=1)
    out = jnp.einsum("skd,kd->sd", win, w.astype(u.dtype))
    if b is not None:
        out = out + b.astype(u.dtype)
    new_tail = win[:, 1:].astype(tail.dtype)
    if live is not None:
        new_tail = jnp.where(live[:, None, None], new_tail, tail)
    return out, new_tail
