"""The plain reference of the Falcon-H1 configurations (HF `falcon_h1`): the
published layer equations in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no chunked
scan, no batching, no flax: the state-space branch is the sequential
recurrence, one token after another; attention is a block of query rows
against ALL keys under an explicit mask. It is applied layer by layer to the
program's OWN parameter tree (bf16 leaves, cast a layer, and a slice of the
FFN, at a time), so it fits beside the idle engine on the chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). A published layer is TWO blocks of its pattern, 'P'
then 'F', each behind its own RMSNorm (eps from the configuration):

  embedding  x = E[ids] * embed_mult
  P  h = RMSNorm(x);  x = x + attn_out_mult * Attn(attn_in_mult * h)
                            + ssm_out_mult * Mamba(ssm_in_mult * h)
     Attn(u): q = W_q u (n_head x hs), k = (W_k u) * key_mult (n_kv x hs),
       v = W_v u; no biases; RoPE in the rotate_half pairing (lane i with
       lane i + hs / 2) at `rope_theta` over all lanes, on q and k; causal
       softmax(q k^T / sqrt(hs)) v, a KV head serving n_head / n_kv query
       heads; W_o
     Mamba(u): [z | x | B | C | dt] = (W_in u) * ssm_mults, a number a
       segment; xBC = silu(conv1d(xBC; width K, bias)), causal, depthwise;
       dt = softplus(dt + dt_bias); A = -exp(A_log);
       h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;
       y_t = h_t C_t + D x_t; y = RMSNorm over G groups of (y * silu(z)),
       times a weight; W_out
  F  x = x + mlp_down_mult * W_down(silu(mlp_gate_mult * W_gate u) * W_up u),
     u = RMSNorm(x)
  head       logits = (W_head RMSNorm(x)) / logits_div, untied

Every multiplier is applied apart, where it is published, whatever the
program does with it. Departures from the published description: none in
the equations; what the catalog's `config` does not say (where each
multiplier sits, the order of the five segments, the pairing, the gated
norm's place) is listed under `assumed` in the configuration file. The
tree's layouts: `c_attn` (C, q + k + v) is [W_q | W_k | W_v] by columns;
`in_proj` (C, d_in) is [z | x | B | C | dt] by columns; `conv_w` (K, D) has
w[k] on the input K - 1 - k steps back; the FFN's `c_fc` (C, 2F) is
[W_gate | W_up] by columns.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), lm_head (V, C), ln_f/scale,
  block_<i>/norm/scale, and by kind
  P  block_<i>/attn/{c_attn,c_proj}/kernel,
     block_<i>/ssm/{in_proj (C, d_in), conv_w (K, conv_dim), conv_b,
                    dt_bias, A_log, D, norm_w, out_proj (d_inner, C)}
  F  block_<i>/mlp/{c_fc (C, 2F), c_proj (F, C)}

`faults` (tests and PERF.md's second readings only) spoils ONE term each so
that the comparison is shown to see it: FAULTS below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid import (HI, _f32, _fp8, _head_slice,
                                            _norm, _rms_norm)
from benchmark.lib.reference_lfm2 import _rope

#: the configuration's multipliers that are not 1, each set to 1: "<key of
#: llm_config>_1", the five segment multipliers one by one
SEGMENTS = ("z", "x", "B", "C", "dt")
MULT_FAULTS = tuple(f"{k}_1" for k in (
    "embed_mult", "attn_out_mult", "key_mult", "ssm_in_mult", "ssm_out_mult",
    *(f"ssm_mults.{s}" for s in SEGMENTS), "mlp_gate_mult", "mlp_down_mult",
    "logits_div"))
FAULTS = MULT_FAULTS + (
    "no_attn_branch",     # a 'P' block without its attention branch
    "no_ssm_branch",      # ... without its state-space branch
    "no_skip",            # y_t without D x_t
    "no_gate",            # the norm of y, not of y * silu(z)
    "norm_one_group",     # the gated norm over all of d_inner at once
    "norm_before_gate",   # RMSNorm(y) * silu(z)
    "no_conv_bias",       # the convolution without its bias
    "rope_off",           # no positions at all
    "rope_adjacent",      # lanes paired (2i, 2i + 1)
    "rope_theta_1e4",     # the angles at base 10,000
    "softmax_scale_1",    # softmax(q k^T) without the 1/sqrt(hs)
    "fp8_mixers",         # every matrix of a 'P' block in float8_e4m3
    "fp8_dense",          # every matrix of an 'F' block in float8_e4m3
)
Q_ROWS = 256                # query rows a block of the attention


def mult(cfg: dict, key: str, faults=()) -> float:
    """The multiplier `key` of `cfg` ("ssm_mults.x": one of the five), 1
    where `faults` sets it so or the configuration has none."""
    if f"{key}_1" in faults:
        return 1.0
    name, _, seg = key.partition(".")
    if seg:
        five = cfg.get(name) or (1.0,) * 5
        return float(five[SEGMENTS.index(seg)])
    return float(cfg.get(name, 1.0))


def _own(faults, mine) -> tuple:
    return tuple(f for f in faults if f in mine)


_ATTN_FAULTS = ("rope_off", "rope_adjacent", "rope_theta_1e4",
                "softmax_scale_1", "fp8_mixers")
_SSM_FAULTS = ("no_skip", "no_gate", "norm_one_group", "norm_before_gate",
               "no_conv_bias", "fp8_mixers")


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "hs", "theta",
                                             "key_mult", "faults"))
def attention_forward(x, p, *, n_head, n_kv, hs, theta, key_mult, faults=()):
    """(B, T, C) float32 -> the branch's output, before its multiplier."""
    with jax.default_matmul_precision(HI):
        B, T, _ = x.shape
        qw = n_head * hs
        low = _fp8 if "fp8_mixers" in faults else (lambda w: w)
        qkv = x @ low(p["c_attn"]["kernel"].astype(jnp.float32))
        q, k, v = jnp.split(qkv, [qw, qw + n_kv * hs], axis=-1)
        q = q.reshape(B, T, n_head, hs)
        k = k.reshape(B, T, n_kv, hs) * key_mult
        v = v.reshape(B, T, n_kv, hs)
        if "rope_off" not in faults:
            th = 1e4 if "rope_theta_1e4" in faults else theta
            q = _rope(q, th, "rope_adjacent" in faults)
            k = _rope(k, th, "rope_adjacent" in faults)
        scale = 1.0 if "softmax_scale_1" in faults \
            else 1.0 / jnp.sqrt(jnp.float32(hs))
        k = jnp.repeat(k, n_head // n_kv, axis=2).transpose(0, 2, 3, 1)
        v = jnp.repeat(v, n_head // n_kv, axis=2).transpose(0, 2, 1, 3)
        keys = jnp.arange(T)
        out = []
        for t0 in range(0, T, Q_ROWS):          # a block of query rows
            qb = q[:, t0:t0 + Q_ROWS].transpose(0, 2, 1, 3)
            att = (qb @ k) * scale              # against ALL keys
            rows = t0 + jnp.arange(qb.shape[2])
            att = jnp.where(keys[None, :] <= rows[:, None], att, -jnp.inf)
            out.append((jax.nn.softmax(att, axis=-1) @ v
                        ).transpose(0, 2, 1, 3))
        y = jnp.concatenate(out, axis=1).reshape(B, T, qw)
        return y @ low(p["c_proj"]["kernel"].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("H", "P", "G", "N", "eps",
                                             "mults", "faults"))
def mamba_forward(x, p, *, H, P, G, N, eps, mults, faults=()):
    """(B, T, C) float32 -> the branch's output, before its multiplier,
    token by token from a zero state. `mults`: the five segment
    multipliers."""
    with jax.default_matmul_precision(HI):
        p = _f32(p)
        B, T, _ = x.shape
        d_inner, K = H * P, p["conv_w"].shape[0]
        conv_dim = d_inner + 2 * G * N
        w_in, w_out = p["in_proj"], p["out_proj"]
        if "fp8_mixers" in faults:
            w_in, w_out = _fp8(w_in), _fp8(w_out)
        mup = jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in zip(
            (d_inner, d_inner, G * N, G * N, H), mults)])
        zxd = (x @ w_in) * mup
        z, xbc, dt = jnp.split(zxd, [d_inner, d_inner + conv_dim], axis=-1)
        pad = jnp.concatenate([jnp.zeros((B, K - 1, conv_dim)), xbc], axis=1)
        xbc = sum(pad[:, k:k + T] * p["conv_w"][k] for k in range(K))
        if "no_conv_bias" not in faults:
            xbc = xbc + p["conv_b"]
        xbc = jax.nn.silu(xbc)
        xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + G * N], axis=-1)
        xs = xs.reshape(B, T, H, P)
        Bm = jnp.repeat(Bm.reshape(B, T, G, N), H // G, axis=2)
        Cm = jnp.repeat(Cm.reshape(B, T, G, N), H // G, axis=2)
        dt = jax.nn.softplus(dt + p["dt_bias"])              # (B, T, H)
        A = -jnp.exp(p["A_log"])

        def token(h, t):
            x_t, b_t, c_t, dt_t = t
            h = (jnp.exp(dt_t * A)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
            return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

        swap = lambda a: jnp.swapaxes(a, 0, 1)               # noqa: E731
        _, y = jax.lax.scan(token, jnp.zeros((B, H, P, N)),
                            (swap(xs), swap(Bm), swap(Cm), swap(dt)))
        y = swap(y)
        if "no_skip" not in faults:
            y = y + p["D"][:, None] * xs
        y = y.reshape(B, T, d_inner)
        groups = 1 if "norm_one_group" in faults else G

        def norm(t):
            return _rms_norm(t.reshape(B, T, groups, d_inner // groups),
                             1.0, eps).reshape(B, T, d_inner)

        if "no_gate" in faults:
            y = norm(y)
        elif "norm_before_gate" in faults:
            y = norm(y) * jax.nn.silu(z)
        else:
            y = norm(y * jax.nn.silu(z))
        return (y * p["norm_w"]) @ w_out


@functools.partial(jax.jit, static_argnames=("gate_mult", "fp8"))
def _dense_slice(x, w_gate, w_up, w_down, *, gate_mult, fp8=False):
    with jax.default_matmul_precision(HI):
        w_gate, w_up, w_down = (w.astype(jnp.float32)
                                for w in (w_gate, w_up, w_down))
        if fp8:
            w_gate, w_up, w_down = _fp8(w_gate), _fp8(w_up), _fp8(w_down)
        return (jax.nn.silu((x @ w_gate) * gate_mult) * (x @ w_up)) @ w_down


def dense_forward(x, p, *, gate_mult, faults=(), slices: int = 4):
    """The FFN before its down multiplier, a slice of its width at a time
    (a whole float32 copy of its three matrices is 1.3 GB)."""
    F = p["c_proj"].shape[0]
    step = -(-F // slices)
    return sum(_dense_slice(x, p["c_fc"][:, f:f + step],
                            p["c_fc"][:, F + f:F + f + step],
                            p["c_proj"][f:f + step], gate_mult=gate_mult,
                            fp8="fp8_dense" in faults)
               for f in range(0, F, step))


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """What one block adds to the residual stream, from its normed input
    `h`: `kind` 'P' or 'F', `p` the block's parameters."""
    faults = tuple(faults)
    m = functools.partial(mult, cfg, faults=faults)
    if kind == "F":
        return m("mlp_down_mult") * dense_forward(
            h, p["mlp"], gate_mult=m("mlp_gate_mult"), faults=faults)
    assert kind == "P", kind
    out = jnp.zeros_like(h)
    if "no_attn_branch" not in faults:
        out = out + m("attn_out_mult") * attention_forward(
            h * m("attn_in_mult"), p["attn"], n_head=cfg["n_head"],
            n_kv=cfg["n_kv_heads"],
            hs=cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"],
            theta=float(cfg.get("rope_theta", 1e4)),
            key_mult=m("key_mult"), faults=_own(faults, _ATTN_FAULTS))
    if "no_ssm_branch" not in faults:
        out = out + m("ssm_out_mult") * mamba_forward(
            h * m("ssm_in_mult"), p["ssm"], H=cfg["ssm_heads"],
            P=cfg["ssm_head_dim"], G=cfg["ssm_groups"], N=cfg["ssm_state"],
            eps=cfg.get("norm_eps", 1e-5),
            mults=tuple(m(f"ssm_mults.{s}") for s in SEGMENTS),
            faults=_own(faults, _SSM_FAULTS))
    return out


def forward_hidden(params, cfg: dict, idx, faults=(), before_experts=None):
    """(B, T) ids -> (B, T, C) float32 before the final norm.
    `before_experts` is the interface's (a model without experts never
    calls it)."""
    eps = cfg.get("norm_eps", 1e-5)
    x = params["tkn_emb"]["embedding"][idx].astype(jnp.float32) \
        * mult(cfg, "embed_mult", faults)
    for i, kind in enumerate(cfg["layer_pattern"]):
        p = params[f"block_{i}"]
        x = x + mixer_forward(cfg, kind, p,
                              _norm(x, p["norm"]["scale"], eps=eps), faults)
    return x


def forward_logits(params, cfg: dict, idx, faults=(), last: int = 0,
                   vocab_slices: int = 8):
    """(B, T) int32 ids -> (B, T, V) float32 logits, or of the last `last`
    positions only. The head is applied a slice of the vocabulary at a
    time."""
    x = forward_hidden(params, cfg, idx, faults)
    if last:
        x = x[:, -last:]
    head = params["lm_head"]
    eps = cfg.get("norm_eps", 1e-5)
    V = head.shape[0]
    step = -(-V // vocab_slices)
    logits = jnp.concatenate(
        [_head_slice(x, params["ln_f"]["scale"], head[v:v + step], eps=eps)
         for v in range(0, V, step)], axis=-1)
    return logits / mult(cfg, "logits_div", faults)
