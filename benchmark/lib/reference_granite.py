"""The plain reference of the Granite 4.0-H configurations (HF
`granitemoehybrid`): the published layer equations in straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`. No kernels,
no cache, no chunked scan, no batching of experts, no flax: the state-space
layer is the sequential recurrence, one token after another (the function
`reference_hybrid.mamba_forward`, imported: Granite's Mamba-2 mixer is the
equations that file has, at 128 heads and one group), the experts run one
after another. It is applied layer by layer to the program's OWN parameter
tree (bf16 leaves, cast a layer, and an expert, at a time), so it fits
beside the idle engine on the chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). With h = 4096, eps = 1e-5:

  embedding  x = E[ids] * embed_mult                         (12)
  a block    x = x + resid_mult * mixer(RMSNorm(x))          (0.22)
             a published layer is TWO blocks: its mixer ('M' or '*'), then
             its expert layer ('E'), each behind its own RMSNorm
  M  [z | xBC | dt] = x W_in; xBC = silu(causal depthwise conv(xBC) + b);
     x, B, C = split(xBC); dt = softplus(dt + dt_bias); A = -exp(A_log);
     S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t; y_t = S_t C_t + D x_t;
     y = RMSNorm over all of d_inner of (y * silu(z)), times a weight;
     out = y W_out
  *  q, k, v = x W_qkv; causal softmax(q k^T * attn_scale) (1/128, not
     1/sqrt(128)), a KV head serving n_head / n_kv_heads query heads; no
     positional term; o = y W_o
  E  l = x W_r (float32); the top k by LOGIT; weights = softmax over THOSE k
     logits; no bias, no scale. Expert e: [a | b] = W_in[e] x,
     W_out[e] (silu(a) * b), for the chosen experts that are HELD (ids
     first .. first + count) with the weights computed over all (not
     renormalised over the held); what the absent ones would add is left
     out. Plus the shared expert, gated alike, always on.
  head       tied: logits = RMSNorm(x) E^T / logits_div      (16)

Departures from the published description: none in the equations. The tree
holds the routed up stack as (expert, 2F, C), which IS `input_linear`'s
published layout (out by in), and the shared expert's as (C, 2Fs), the
transpose of the published one: the same numbers.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), ln_f/scale, block_<i>/norm/scale, and by kind
  block_<i>/ssm/{in_proj (C, d_in), conv_w (K, conv_dim), conv_b, dt_bias,
                 A_log, D, norm_w, out_proj (d_inner, C)}
  block_<i>/attn/{c_attn,c_proj}/kernel
  block_<i>/moe/{gate (C, n_routed), experts_up (held, 2F, C),
                 experts_down (held, F, C), shared_up (C, 2Fs),
                 shared_down (Fs, C)}

`faults` (tests and PERF.md's second readings only) breaks one term so that
the comparison is shown to see it: "fp8_experts" rounds every expert matrix
to float8_e4m3 with one scale a matrix; "no_gate_half" computes an expert
from `b` alone; "embed_mult_1", "resid_mult_1", "attn_scale_1",
"logits_div_1" set one multiplier to 1; "softmax_all" takes the softmax over
all the router's logits; "no_skip" drops D x.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid import (HI, _fp8, _head_slice, _norm,
                                            mamba_forward)

FAULTS = ("fp8_experts", "no_gate_half", "embed_mult_1", "resid_mult_1",
          "attn_scale_1", "logits_div_1", "softmax_all", "no_skip")


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "hs",
                                             "scale"))
def attention_forward(x, p, *, n_head, n_kv, hs, scale):
    with jax.default_matmul_precision(HI):
        B, T, _ = x.shape
        qw = n_head * hs
        qkv = x @ p["c_attn"]["kernel"].astype(jnp.float32)
        q, k, v = jnp.split(qkv, [qw, qw + n_kv * hs], axis=-1)
        q = q.reshape(B, T, n_head, hs).transpose(0, 2, 1, 3)
        k = jnp.repeat(k.reshape(B, T, n_kv, hs), n_head // n_kv,
                       axis=2).transpose(0, 2, 1, 3)
        v = jnp.repeat(v.reshape(B, T, n_kv, hs), n_head // n_kv,
                       axis=2).transpose(0, 2, 1, 3)
        att = (q @ k.transpose(0, 1, 3, 2)) * scale
        att = jnp.where(jnp.tril(jnp.ones((T, T), bool)), att, -jnp.inf)
        y = (jax.nn.softmax(att, axis=-1) @ v).transpose(0, 2, 1, 3)
        return y.reshape(B, T, qw) @ p["c_proj"]["kernel"].astype(
            jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "over_all"))
def route(x, gate, *, k, over_all=False):
    """(N, C) -> (ids (N, k) over all routed experts, weights (N, k)): the
    top k logits and the softmax over those k."""
    with jax.default_matmul_precision(HI):
        logits = x @ gate.astype(jnp.float32)
        top, idx = jax.lax.top_k(logits, k)
        if over_all:                     # the fault: softmax, THEN the top k
            return idx, jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                            idx, axis=1)
        return idx, jax.nn.softmax(top, axis=-1)


@functools.partial(jax.jit, static_argnames=("fp8", "gate_half"))
def _expert(x, w_up, w_down, weight, fp8=False, gate_half=True):
    """One gated expert on every row, times the row's weight for it (0
    where the row did not choose it). w_up (2F, C) = [a | b] by rows,
    w_down (F, C)."""
    with jax.default_matmul_precision(HI):
        w_up, w_down = w_up.astype(jnp.float32), w_down.astype(jnp.float32)
        if fp8:
            w_up, w_down = _fp8(w_up), _fp8(w_down)
        a, b = jnp.split(x @ w_up.T, 2, axis=-1)
        h = jax.nn.silu(a) * b if gate_half else b
        return (h @ w_down) * weight[:, None]


def experts_forward(x, p, *, k, first, held=None, shared=True, faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds.
    Expert by expert, so one expert's float32 matrices exist at a time."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], k=k, over_all="softmax_all" in faults)
    n_held = p["experts_up"].shape[0]
    kw = dict(fp8="fp8_experts" in faults,
              gate_half="no_gate_half" not in faults)
    out = jnp.zeros_like(xf)
    for e in (range(first, first + n_held) if held is None else held):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        out = out + _expert(xf, p["experts_up"][e - first],
                            p["experts_down"][e - first], weight, **kw)
    if shared:
        out = out + _expert(xf, p["shared_up"].T, p["shared_down"],
                            jnp.ones((xf.shape[0],), jnp.float32), **kw)
    return out.reshape(B, T, C)


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's mixer on its normed input `h`: `kind` 'M', '*' or 'E',
    `p` the block's parameters."""
    eps = cfg.get("norm_eps", 1e-5)
    if kind == "M":
        return mamba_forward(
            h, p["ssm"], H=cfg["ssm_heads"], P=cfg["ssm_head_dim"],
            G=cfg["ssm_groups"], N=cfg["ssm_state"], eps=eps,
            faults=tuple(f for f in faults if f == "no_skip"))
    if kind == "*":
        hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
        scale = 1.0 if "attn_scale_1" in faults else \
            cfg.get("attn_scale") or hs ** -0.5
        return attention_forward(h, p["attn"], n_head=cfg["n_head"],
                                 n_kv=cfg["n_kv_heads"], hs=hs,
                                 scale=float(scale))
    return experts_forward(h, p["moe"], k=cfg["n_act"] - cfg["n_shared"],
                           first=(cfg.get("experts_held") or (0, 0))[0],
                           faults=tuple(faults))


def forward_hidden(params, cfg: dict, idx, faults=()):
    """(B, T) ids -> (B, T, C) float32 before the final norm."""
    eps = cfg.get("norm_eps", 1e-5)
    r = 1.0 if "resid_mult_1" in faults else cfg.get("resid_mult", 1.0)
    x = params["tkn_emb"]["embedding"][idx].astype(jnp.float32)
    if "embed_mult_1" not in faults:
        x = x * cfg.get("embed_mult", 1.0)
    for i, kind in enumerate(cfg["layer_pattern"]):
        p = params[f"block_{i}"]
        h = _norm(x, p["norm"]["scale"], eps=eps)
        x = x + r * mixer_forward(cfg, kind, p, h, faults)
    return x


def forward_logits(params, cfg: dict, idx, faults=(), last: int = 0,
                   vocab_slices: int = 8):
    """(B, T) int32 ids -> (B, T, V) float32 logits, or of the last `last`
    positions only. The tied head is applied a slice of the vocabulary at a
    time (its float32 copy is 0.8 GB whole)."""
    x = forward_hidden(params, cfg, idx, faults)
    if last:
        x = x[:, -last:]
    head = params["tkn_emb"]["embedding"]
    eps = cfg.get("norm_eps", 1e-5)
    V = head.shape[0]
    step = -(-V // vocab_slices)
    logits = jnp.concatenate(
        [_head_slice(x, params["ln_f"]["scale"], head[v:v + step], eps=eps)
         for v in range(0, V, step)], axis=-1)
    return logits if "logits_div_1" in faults \
        else logits / cfg.get("logits_div", 1.0)
