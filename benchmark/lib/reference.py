"""The plain reference: a GPT-2 forward pass and cross-entropy in
straightforward `jax.numpy`, float32, `jax.default_matmul_precision
("highest")`. No kernels, no cache, no batching tricks, no flax. It is
applied layer by layer to the program's OWN parameter tree (one jitted
function per kind of layer, every layer the same shapes), so no second copy
of the weights is made and the 48-layer model compiles as fast as one layer.

Follows the published GPT-2 block (pre-LN, fused qkv with bias, causal
softmax attention at 1/sqrt(head), learned positions, 4x FFN, tied head)
with the departures each configuration file lists under `changed`: the
exact (erf) gelu where the source has the tanh form, no FFN biases, and
LayerNorm epsilon 1e-6 (flax's default) where the source has 1e-5.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), pos_emb (T, C), ln_f/{scale,bias},
  block_<i>/{ln1,ln2}/{scale,bias},
  block_<i>/attn/{c_attn,c_proj}/{kernel,bias}, block_<i>/mlp/{c_fc,c_proj}
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
HI = "highest"


def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("n_head", "mask_diagonal"))
def block_forward(x, p, *, n_head: int, mask_diagonal: int = 0):
    """One transformer block on (B, T, C) float32 activations.
    `mask_diagonal` is 0, the causal mask; the tests shift it to show that
    the comparison sees a wrong mask."""
    with jax.default_matmul_precision(HI):
        B, T, C = x.shape
        hs = C // n_head
        h = _layer_norm(x, p["ln1"])
        qkv = h @ p["attn"]["c_attn"]["kernel"] + p["attn"]["c_attn"]["bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, n_head, hs).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, n_head, hs).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, n_head, hs).transpose(0, 2, 1, 3)
        att = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(hs))
        mask = jnp.tril(jnp.ones((T, T), bool), mask_diagonal)
        att = jnp.where(mask, att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        y = (att @ v).transpose(0, 2, 1, 3).reshape(B, T, C)
        y = y @ p["attn"]["c_proj"]["kernel"] + p["attn"]["c_proj"]["bias"]
        x = x + y
        h = _layer_norm(x, p["ln2"])
        h = jax.nn.gelu(h @ p["mlp"]["c_fc"], approximate=False)
        return x + h @ p["mlp"]["c_proj"]


@jax.jit
def embed(idx, tkn_emb, pos_emb):
    T = idx.shape[1]
    return tkn_emb[idx] + pos_emb[:T][None]


@jax.jit
def head_logits(x, ln_f, tkn_emb):
    with jax.default_matmul_precision(HI):
        return _layer_norm(x, ln_f) @ tkn_emb.T


@jax.jit
def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def check_supported(cfg: dict) -> None:
    """The reference covers what the GPT-2 configurations use and says so
    where a configuration asks for more (a later configuration brings its
    own reference beside its file)."""
    want = {"attn": "mha", "pos_emb": "learn", "non_linearity": "gelu"}
    for k, v in want.items():
        if str(cfg.get(k)).lower() != v:
            raise ValueError(f"the GPT-2 reference needs {k}={v!r}, the "
                             f"configuration has {cfg.get(k)!r}")
    if cfg.get("moe"):
        raise ValueError("the GPT-2 reference has no expert layers")


def forward_logits(params, cfg: dict, idx, mask_diagonal: int = 0):
    """(B, T) int32 ids -> (B, T, V) float32 logits."""
    check_supported(cfg)
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    x = embed(idx, f32(params["tkn_emb"]["embedding"]),
              f32(params["pos_emb"]))
    for i in range(cfg["n_layer"]):
        x = block_forward(x, f32(params[f"block_{i}"]), n_head=cfg["n_head"],
                          mask_diagonal=mask_diagonal)
    return head_logits(x, f32(params["ln_f"]),
                       f32(params["tkn_emb"]["embedding"]))


def loss(params, cfg: dict, idx, targets):
    return cross_entropy(forward_logits(params, cfg, idx), targets)


@jax.jit
def logits_error(system_logits, reference_logits):
    """How far the system's logits lie from the reference's, relative to
    the reference's own size: per position rms(system - reference) /
    rms(reference) over the vocabulary, of which the worst position and the
    median are returned. Rounding in a lower precision moves every position
    by about the same share (bf16 compute: 1.1-1.3% at 12 layers), so the
    worst position catches a fault in one place as well as one everywhere."""
    d = system_logits.astype(jnp.float32) - reference_logits
    rel = jnp.sqrt(jnp.mean(jnp.square(d), axis=-1)
                   / jnp.mean(jnp.square(reference_logits), axis=-1))
    return {"worst": jnp.max(rel), "median": jnp.median(rel)}
