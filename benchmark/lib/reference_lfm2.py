"""The plain reference of the LFM2 mixture-of-experts configurations (HF
`lfm2_moe`): the published layer equations in straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`. No kernels, no cache, no
batching of experts, no flax: the short convolution runs token by token over
its two carried inputs, the experts run one after another. It is applied
layer by layer to the program's OWN parameter tree (bf16 leaves, cast a
layer, and an expert, at a time), so it fits beside the idle engine on the
chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). With h = 2048, eps = 1e-5:

  embedding  x = E[ids]
  a block    x = x + op(RMSNorm(x)); a published layer is TWO blocks: its
             operator ('C' or '*') behind `operator_norm`, then its feed
             forward ('F' or 'E') behind `ffn_norm`
  C  [B | C | x'] = x W_in (h -> 3h, no bias); u = B * x';
     c_t = sum_{k=0..2} w[k] * u_{t-2+k} (depthwise, causal, `conv_L_cache`
     3, no bias, no activation); y = (C * c) W_out. From a zero tail.
  *  q (32 x 64), k, v (8 x 64) = x W_qkv, no biases; RMSNorm over the 64
     lanes of every q head and every k head, each with one learned
     64-vector, BEFORE the positions; RoPE at theta 1e6 in the rotate_half
     pairing (lane i with lane i + 32) on q and k; causal softmax at
     1/sqrt(64), a KV head serving 4 query heads; o = y W_o
  F  W_2 (silu(W_1 x) * W_3 x), width `intermediate_size` 11,776
  E  s = sigmoid(x W_r) over all 64, float32; the top 4 of s + b (the expert
     bias moves the SELECTION only); weights = s of the chosen, over their
     sum + 1e-6, times `routed_scaling_factor` 1. Expert e:
     W_2[e] (silu(W_1[e] x) * W_3[e] x), width 1,536. No shared expert.
  head       after the last layer one RMSNorm (`embedding_norm`: the OUTPUT
             norm), then logits = x E^T, tied

Departures from the published description: none in the equations; what the
catalog's `config` does not say (the norm's place, the tied head, QK-norm
before the positions, the pairing, the 1e-6) is listed under `assumed` in
the configuration file. The tree's layouts: `in_proj` (h, 3h) is [B | C |
x'] by columns; `conv_w` (3, h) has w[k] on the input 2 - k steps back;
the dense FFN's `c_fc` (h, 2F) is [W_1 | W_3] by columns; an expert's up
matrix (2F, h) is [W_1 ; W_3] by rows: the same numbers as the published
separate matrices, laid side by side.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), ln_f/scale, block_<i>/norm/scale, and by kind
  block_<i>/conv/{in_proj (C, 3C), conv_w (K, C), out_proj (C, C)}
  block_<i>/attn/{c_attn,c_proj}/kernel, attn/{q_norm,k_norm} (hs,)
  block_<i>/mlp/{c_fc (C, 2F), c_proj (F, C)}
  block_<i>/moe/{gate (C, 64), gate_bias (64,) float32,
                 experts_up (held, 2F, C), experts_down (held, F, C)}

`faults` (tests and PERF.md's second readings only) breaks one term so that
the comparison is shown to see it: FAULTS below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid import HI, _fp8, _head_slice, _norm

FAULTS = (
    "fp8_experts",        # every expert matrix rounded to float8_e4m3
    "bias_in_weights",    # weights = (s + b) of the chosen, not s
    "no_renorm",          # the chosen weights not divided by their sum
    "rope_off",           # no positions at all
    "rope_theta_1e4",     # the angles at base 10,000
    "rope_adjacent",      # lanes paired (2i, 2i + 1)
    "no_qk_norm",         # the per-head RMSNorms left out
    "conv_tap_dropped",   # the convolution without its oldest tap
    "no_c_gate",          # `C *` left out
    "dense_silu_on_w3",   # the dense FFN as silu(W_3 x) * W_1 x
    "fp8_mixers",         # every matrix of a 'C', '*' or 'F' block in fp8
)
ROUTE_EPS = 1e-6


def _rope(x, theta: float, adjacent: bool):
    """(B, T, H, hs) rotated at positions 0..T-1."""
    B, T, H, hs = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hs, 2, dtype=jnp.float32) / hs))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv        # (T, hs/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if adjacent:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(B, T, H, hs)
    a, b = x[..., :hs // 2], x[..., hs // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "hs", "eps",
                                             "theta", "faults"))
def attention_forward(x, p, *, n_head, n_kv, hs, eps, theta, faults=()):
    with jax.default_matmul_precision(HI):
        B, T, _ = x.shape
        qw = n_head * hs
        low = _fp8 if "fp8_mixers" in faults else (lambda w: w)
        qkv = x @ low(p["c_attn"]["kernel"].astype(jnp.float32))
        q, k, v = jnp.split(qkv, [qw, qw + n_kv * hs], axis=-1)
        q = q.reshape(B, T, n_head, hs)
        k = k.reshape(B, T, n_kv, hs)
        v = v.reshape(B, T, n_kv, hs)
        if "no_qk_norm" not in faults:
            q = _norm(q, p["q_norm"], eps=eps)
            k = _norm(k, p["k_norm"], eps=eps)
        if "rope_off" not in faults:
            th = 1e4 if "rope_theta_1e4" in faults else theta
            q = _rope(q, th, "rope_adjacent" in faults)
            k = _rope(k, th, "rope_adjacent" in faults)
        q = q.transpose(0, 2, 1, 3)
        k = jnp.repeat(k, n_head // n_kv, axis=2).transpose(0, 2, 1, 3)
        v = jnp.repeat(v, n_head // n_kv, axis=2).transpose(0, 2, 1, 3)
        att = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(hs))
        att = jnp.where(jnp.tril(jnp.ones((T, T), bool)), att, -jnp.inf)
        y = (jax.nn.softmax(att, axis=-1) @ v).transpose(0, 2, 1, 3)
        return y.reshape(B, T, qw) @ low(p["c_proj"]["kernel"].astype(
            jnp.float32))


@functools.partial(jax.jit, static_argnames=("faults",))
def conv_forward(x, p, *, faults=()):
    """(B, T, C) float32 -> the mixer's output, token by token from a zero
    tail: the carry is the last K - 1 inputs u of the convolution."""
    with jax.default_matmul_precision(HI):
        w_in, w, w_out = (p[n].astype(jnp.float32)
                          for n in ("in_proj", "conv_w", "out_proj"))
        K = w.shape[0]
        if "conv_tap_dropped" in faults:
            w = w.at[0].set(0.0)
        if "fp8_mixers" in faults:
            w_in, w_out = _fp8(w_in), _fp8(w_out)
        b, c, xp = jnp.split(x @ w_in, 3, axis=-1)
        u = b * xp

        def token(tail, u_t):
            win = jnp.concatenate([tail, u_t[:, None]], axis=1)  # (B, K, C)
            return win[:, 1:], jnp.sum(win * w, axis=1)

        _, y = jax.lax.scan(token, jnp.zeros((x.shape[0], K - 1,
                                              x.shape[2])),
                            jnp.swapaxes(u, 0, 1))
        y = jnp.swapaxes(y, 0, 1)
        return (y if "no_c_gate" in faults else c * y) @ w_out


@functools.partial(jax.jit, static_argnames=("faults",))
def dense_forward(x, p, *, faults=()):
    with jax.default_matmul_precision(HI):
        w_up, w_down = (p[n].astype(jnp.float32) for n in ("c_fc", "c_proj"))
        if "fp8_mixers" in faults:
            w_up, w_down = _fp8(w_up), _fp8(w_down)
        a, b = jnp.split(x @ w_up, 2, axis=-1)
        if "dense_silu_on_w3" in faults:
            a, b = b, a
        return (jax.nn.silu(a) * b) @ w_down


@jax.jit
def scores(x, gate):
    """(N, C) -> the router's sigmoid scores over all routed experts."""
    with jax.default_matmul_precision(HI):
        return jax.nn.sigmoid(x @ gate.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "scale", "faults"))
def route(x, gate, bias, *, k, scale, faults=()):
    """(N, C) -> (ids (N, k) over all routed experts, weights (N, k))."""
    with jax.default_matmul_precision(HI):
        s = jax.nn.sigmoid(x @ gate.astype(jnp.float32))
        biased = s + bias.astype(jnp.float32)
        _, idx = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(
            biased if "bias_in_weights" in faults else s, idx, axis=1)
        if "no_renorm" not in faults:
            w = w / (jnp.sum(w, axis=1, keepdims=True) + ROUTE_EPS)
        return idx, w * scale


@functools.partial(jax.jit, static_argnames=("fp8",))
def _expert(x, w_up, w_down, weight, fp8=False):
    """One gated expert on every row, times the row's weight for it (0
    where the row did not choose it). w_up (2F, C) = [W_1 ; W_3] by rows,
    w_down (F, C)."""
    with jax.default_matmul_precision(HI):
        w_up, w_down = w_up.astype(jnp.float32), w_down.astype(jnp.float32)
        if fp8:
            w_up, w_down = _fp8(w_up), _fp8(w_down)
        a, b = jnp.split(x @ w_up.T, 2, axis=-1)
        return ((jax.nn.silu(a) * b) @ w_down) * weight[:, None]


def experts_forward(x, p, *, k, scale, first=0, held=None, faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds.
    Expert by expert, so one expert's float32 matrices exist at a time."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], p["gate_bias"], k=k, scale=scale,
                   faults=tuple(f for f in faults
                                if f in ("bias_in_weights", "no_renorm")))
    n_held = p["experts_up"].shape[0]
    out = jnp.zeros_like(xf)
    for e in (range(first, first + n_held) if held is None else held):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        out = out + _expert(xf, p["experts_up"][e - first],
                            p["experts_down"][e - first], weight,
                            fp8="fp8_experts" in faults)
    return out.reshape(B, T, C)


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's operator on its normed input `h`: `kind` 'C', '*', 'F'
    or 'E', `p` the block's parameters."""
    if kind == "C":
        return conv_forward(h, p["conv"], faults=tuple(
            f for f in faults
            if f in ("conv_tap_dropped", "no_c_gate", "fp8_mixers")))
    if kind == "*":
        return attention_forward(
            h, p["attn"], n_head=cfg["n_head"], n_kv=cfg["n_kv_heads"],
            hs=cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"],
            eps=cfg.get("norm_eps", 1e-5),
            theta=float(cfg.get("rope_theta", 1e4)),
            faults=tuple(f for f in faults if f.startswith("rope_")
                         or f in ("no_qk_norm", "fp8_mixers")))
    if kind == "F":
        return dense_forward(h, p["mlp"], faults=tuple(
            f for f in faults if f in ("dense_silu_on_w3", "fp8_mixers")))
    return experts_forward(h, p["moe"], k=cfg["n_act"] - cfg["n_shared"],
                           scale=cfg.get("routed_scale", 1.0),
                           first=(cfg.get("experts_held") or (0, 0))[0],
                           faults=tuple(faults))


def forward_hidden(params, cfg: dict, idx, faults=(), before_experts=None):
    """(B, T) ids -> (B, T, C) float32 before the final norm.
    `before_experts(i, h, block)` may replace an expert block's parameters
    given its normed input (the runner's bias calibration)."""
    eps = cfg.get("norm_eps", 1e-5)
    x = params["tkn_emb"]["embedding"][idx].astype(jnp.float32)
    for i, kind in enumerate(cfg["layer_pattern"]):
        p = params[f"block_{i}"]
        h = _norm(x, p["norm"]["scale"], eps=eps)
        if kind == "E" and before_experts is not None:
            p = before_experts(i, h, p)
        x = x + mixer_forward(cfg, kind, p, h, faults)
    return x


def forward_logits(params, cfg: dict, idx, faults=(), last: int = 0,
                   vocab_slices: int = 8):
    """(B, T) int32 ids -> (B, T, V) float32 logits, or of the last `last`
    positions only. The tied head is applied a slice of the vocabulary at a
    time (its float32 copy is 0.5 GB whole)."""
    x = forward_hidden(params, cfg, idx, faults)
    if last:
        x = x[:, -last:]
    head = params["tkn_emb"]["embedding"]
    eps = cfg.get("norm_eps", 1e-5)
    V = head.shape[0]
    step = -(-V // vocab_slices)
    return jnp.concatenate(
        [_head_slice(x, params["ln_f"]["scale"], head[v:v + step], eps=eps)
         for v in range(0, V, step)], axis=-1)
