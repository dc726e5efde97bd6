"""The exact gelu's differentiation rule (ops/activations.py `gelu_exact`):
forward evaluates the `erfc` expansion once and keeps (x, erfc) for
backward; the value is `jax.nn.gelu(x, approximate=False)` itself."""

from math import erfc, exp, pi, sqrt

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.models import mlp
from distributed_pytorch_tpu.ops import activations
from distributed_pytorch_tpu.ops.grouped_matmul import _apply_activation

F32, BF16 = jnp.float32, jnp.bfloat16
C, UP = 64, 256


def plain_gelu(x):
    return jax.nn.gelu(x, approximate=False)


def plain_ffn(x, w_fc, w_proj):
    return plain_gelu(x @ w_fc) @ w_proj


def ruled_ffn(x, w_fc, w_proj):
    return mlp.mlp_apply(x, w_fc, w_proj, "gelu")


def operands(dtype, seed=0):
    kx, kf, kp, kt = jax.random.split(jax.random.PRNGKey(seed), 4)
    # pre-activations of a few units either way: both branches of erfc
    x = jax.random.normal(kx, (4, 48, C), F32)
    w_fc = jax.random.normal(kf, (C, UP), F32) * (2.0 / C ** 0.5)
    w_proj = jax.random.normal(kp, (UP, C), F32) * UP ** -0.5
    t = jax.random.normal(kt, (4, 48, C), F32)
    return tuple(a.astype(dtype) for a in (x, w_fc, w_proj)), t


def ffn_grads(ffn, ops, t):
    def loss(x, w_fc, w_proj):
        return jnp.mean((ffn(x, w_fc, w_proj).astype(F32) - t) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*ops)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["gelu", "no_such_name"])
def test_forward_is_jax_nn_gelu_bitwise(name, dtype, jit):
    """`"gelu"` and the table's default are one function, and its value,
    differentiated or not, is the plain expression's, bit for bit: the
    trainer's forward and the engine's are one function of h."""
    act = activations.activation(name)
    assert act is activations.gelu_exact
    # a grid over both branches of erfc and its tails, and random draws
    x = jnp.concatenate([
        jnp.linspace(-12.0, 12.0, 4097),
        jax.random.normal(jax.random.PRNGKey(3), (4096,)) * 3.0,
        jnp.array([0.0, -0.0, 1e-30, -1e-30, 40.0, -40.0])]).astype(dtype)
    want = (jax.jit(plain_gelu) if jit else plain_gelu)(x)
    primal = (jax.jit(act) if jit else act)(x)
    fwd = lambda v: jax.vjp(act, v)[0]                      # noqa: E731
    ruled = (jax.jit(fwd) if jit else fwd)(x)
    bits = jnp.uint32 if dtype == F32 else jnp.uint16
    for got in (primal, ruled):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(got, bits)),
            np.asarray(jax.lax.bitcast_convert_type(want, bits)))


@pytest.mark.parametrize("which", [0, 1, 2], ids=["x", "w_fc", "w_proj"])
def test_ffn_gradients_float32_agree_with_autodiff(which):
    ops, t = operands(F32)
    got = ffn_grads(ruled_ffn, ops, t)[which]
    want = ffn_grads(plain_ffn, ops, t)[which]
    assert rel_rms(got, want) < 1e-6


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("which", [0, 1, 2], ids=["x", "w_fc", "w_proj"])
def test_ffn_gradients_bfloat16_no_further_than_the_plain_expression(
        which, seed):
    """bf16 compute against float32 autodiff of the plain expression on
    the same bf16 operands: the rule's error is within 1.5x of what plain
    bf16 autodiff reads itself (it keeps erfc in bf16 where autodiff
    rebuilds the derivative from the bf16 x: one rounding each)."""
    ops, t = operands(BF16, seed)
    want = ffn_grads(plain_ffn, tuple(a.astype(F32) for a in ops), t)[which]
    ruled = rel_rms(ffn_grads(ruled_ffn, ops, t)[which], want)
    plain = rel_rms(ffn_grads(plain_ffn, ops, t)[which], want)
    assert ruled <= 1.5 * plain, (ruled, plain)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_derivative_is_gelu_prime(dtype):
    x = jnp.linspace(-9.0, 9.0, 2049).astype(dtype)
    got = jax.vmap(jax.grad(activations.gelu_exact))(x)
    xf = np.asarray(x, np.float64)
    want = np.array([0.5 * erfc(-v / sqrt(2)) + v * exp(-v * v / 2)
                     / sqrt(2 * pi) for v in xf])
    tol = 1e-6 if dtype == F32 else 2.0 ** -7
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_gradients_under_vmap_over_an_expert_axis(dtype):
    """The vmapped experts of `scatter_dispatch` / `MoE`: the barrier and
    the rule pass `vmap`, and each expert reads what it reads alone."""
    E = 3
    per = [operands(dtype, seed) for seed in range(E)]
    xs, wfs, wps = (jnp.stack([p[0][i] for p in per]) for i in range(3))
    ts = jnp.stack([p[1] for p in per])

    def loss(x, w_fc, w_proj, t):
        return jnp.mean((ruled_ffn(x, w_fc, w_proj).astype(F32) - t) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    batched = jax.jit(jax.vmap(grad))(xs, wfs, wps, ts)
    for e in range(E):
        alone = jax.jit(grad)(xs[e], wfs[e], wps[e], ts[e])
        for got, want in zip(batched, alone):
            assert rel_rms(got[e], want) < (1e-6 if dtype == F32 else 2e-2)


@pytest.mark.parametrize("wrap", ["jax.checkpoint", "nn.remat"])
def test_gradients_under_remat(wrap):
    """`act_recomp` remats a Block: the forward rule runs again in backward
    and the gradients are the unwrapped ones."""
    ops, t = operands(BF16)
    want = ffn_grads(ruled_ffn, ops, t)
    if wrap == "jax.checkpoint":
        got = ffn_grads(jax.checkpoint(ruled_ffn), ops, t)
    else:
        class FFN(nn.Module):
            @nn.compact
            def __call__(self, x, w_fc, w_proj):
                return ruled_ffn(x, w_fc, w_proj)
        mod = nn.remat(FFN)()
        got = ffn_grads(lambda *a: mod.apply({}, *a), ops, t)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_second_derivative_is_right():
    """Backward builds gelu' from the saved pair with ordinary ops, so a
    second derivative goes through them: gelu''(x) = (2 - x^2) pdf(x)."""
    x = jnp.linspace(-6.0, 6.0, 513)
    got = jax.vmap(jax.grad(jax.grad(activations.gelu_exact)))(x)
    want = jax.vmap(jax.grad(jax.grad(plain_gelu)))(x)
    xf = np.asarray(x, np.float64)
    closed = (2.0 - xf ** 2) * np.exp(-xf ** 2 / 2) / np.sqrt(2 * np.pi)
    np.testing.assert_allclose(np.asarray(got), closed, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_expert_layers_entry_reaches_the_rule():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128), BF16)
    got = jax.grad(lambda v: _apply_activation(v, "gelu").astype(F32).sum())(x)
    want = jax.grad(lambda v: activations.gelu_exact(v).astype(F32).sum())(x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    text = jax.jit(jax.grad(
        lambda v: _apply_activation(v, "gelu").astype(F32).sum())
    ).lower(x).as_text()
    assert "optimization_barrier" in text
    # undifferentiated, the primal alone: no barrier in a serving program
    assert "optimization_barrier" not in jax.jit(
        lambda v: _apply_activation(v, "gelu")).lower(x).as_text()
