"""The scalar multipliers of a model parametrised for width transfer
(`LLMConfig.attn_in_mult` and its neighbours): each is applied where it is
published, apart from the matrix beside it, and in float32, because most of
them are no bfloat16 numbers (0.0375 is 0.03760 there). A multiplier of 1
adds no op: the programs of the configurations without them stay as they
were."""

from __future__ import annotations

import jax.numpy as jnp


def times(x: jnp.ndarray, mult: float) -> jnp.ndarray:
    """`x * mult` in float32, back in `x`'s dtype; `x` itself at 1."""
    if mult == 1.0:
        return x
    return (x.astype(jnp.float32) * mult).astype(x.dtype)


def segment_times(x: jnp.ndarray, widths, mults) -> jnp.ndarray:
    """The last axis of `x` in segments of `widths`, segment i times
    `mults[i]`; `x` itself where `mults` is empty."""
    if not mults:
        return x
    assert len(widths) == len(mults) and sum(widths) == x.shape[-1]
    vec = jnp.concatenate([jnp.full((w,), m, jnp.float32)
                           for w, m in zip(widths, mults)])
    return (x.astype(jnp.float32) * vec).astype(x.dtype)
