"""The FFN's nonlinearities by name, for the dense MLP (models/mlp.py
`mlp_apply`) and the grouped expert path (ops/grouped_matmul.py) alike.
`"gelu"` is the exact gelu with a differentiation rule of its own."""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@jax.custom_vjp
def gelu_exact(x: jnp.ndarray) -> jnp.ndarray:
    """Exact gelu, `0.5 x erfc(-x / sqrt 2)`, with a differentiation rule of
    its own. Undifferentiated (serving, evaluation) it IS
    `jax.nn.gelu(x, approximate=False)`. Left to autodiff under a gradient,
    the compiler saves x and clones the whole `erfc` expansion (~66 float32
    vector ops, two divides and an `exp` an element) into every consumer:
    the down-projection's forward, its weight gradient and its dgrad each
    ran at the vector unit's pace, not the MXU's (PERF.md section 6, PR 46).
    Here forward evaluates the expansion once and writes what backward
    needs, x and `erfc(-x / sqrt 2)` in x's dtype; gelu and gelu' are three
    and ten ops from that pair, cheap enough to ride in the matmuls that
    read them."""
    return jax.nn.gelu(x, approximate=False)


def _gelu_exact_fwd(x):
    # jax.nn.gelu's own expression, its erfc kept: the value is the primal's
    e = jax.lax.erfc(-x * np.sqrt(0.5).astype(x.dtype))
    # the pair EXISTS in HBM: without the barrier the compiler saves x alone
    # and evaluates the expansion again inside each consumer
    x, e = jax.lax.optimization_barrier((x, e))
    return 0.5 * x * e, (x, e)


def _gelu_exact_bwd(res, da):
    # gelu'(x) = 0.5 erfc(-x / sqrt 2) + x exp(-x^2 / 2) / sqrt(2 pi)
    x, e = res
    xf = x.astype(jnp.float32)
    g = 0.5 * e.astype(jnp.float32) + xf * jnp.exp(-0.5 * xf * xf) * (
        1.0 / math.sqrt(2.0 * math.pi))
    return ((da.astype(jnp.float32) * g).astype(x.dtype),)


gelu_exact.defvjp(_gelu_exact_fwd, _gelu_exact_bwd)


def activation(name: str) -> Callable[[jnp.ndarray], jnp.ndarray]:
    name = name.lower()
    table = {
        "relu": jax.nn.relu,
        "gelu": gelu_exact,
        "swish": jax.nn.silu,
        "silu": jax.nn.silu,
        "mish": jax.nn.mish,
        "selu": jax.nn.selu,
        "celu": jax.nn.celu,
        "elu": jax.nn.elu,
        "sigmoid": jax.nn.sigmoid,
        "lrelu": lambda x: jax.nn.leaky_relu(x, negative_slope=0.01),
        "tanh": jnp.tanh,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }
    return table.get(name, gelu_exact)


def is_gated(name: str) -> bool:
    return name.lower() in ("swiglu", "glu")
