"""The plain reference of the patterned (Nemotron-H) configurations: the
published layer equations in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no chunked
scan, no flax: the state-space layer is the sequential recurrence, one
token after another. It is applied layer by layer to the program's OWN
parameter tree (bf16 leaves, cast a layer, and an expert, at a time), so it
fits beside the idle engine on the chip.

Every block is x <- x + mixer(RMSNorm(x)), eps from the configuration; a
final RMSNorm; an untied head. `cfg` is the configuration file's
`llm_config` (the keyword arguments of the program's LLMConfig).

  M  [z | xBC | dt] = x W_in; xBC = silu(causal depthwise conv(xBC) + b);
     x, B, C = split(xBC); dt = softplus(dt + dt_bias); A = -exp(A_log);
     h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t; y_t = h_t C_t + D x_t;
     y = RMSNorm over G groups of (y * silu(z)), times a weight; out = y W_out
  *  q, k, v = x W_qkv; causal softmax at 1/sqrt(head size), a KV head
     serving n_head / n_kv_heads query heads; no positional term; o = y W_o
  E  s = sigmoid(x W_g); the top k of s + b; weights s of the chosen over
     their sum, times the routed scale; e(x) = W_down relu(W_up x)^2 for
     the chosen experts that are HELD (ids first .. first + count), what the
     absent ones would add left out; plus the shared expert, always on

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), lm_head (V, C), ln_f/scale,
  block_<i>/norm/scale, and by kind
  block_<i>/ssm/{in_proj (C, d_in), conv_w (K, conv_dim), conv_b, dt_bias,
                 A_log, D, norm_w, out_proj (d_inner, C)}
  block_<i>/attn/{c_attn,c_proj}/kernel
  block_<i>/moe/{gate (C, n_routed), gate_bias, experts_up (held, F, C: out
                 by in), experts_down (held, F, C), shared_up (C, Fs),
                 shared_down (Fs, C)}

`faults` (tests and PERF.md's second readings only) breaks one term so that
the comparison is shown to see it: "no_skip" drops D x, "no_gate" drops the
silu(z) gate, "bf16_state" rounds the carried state to bfloat16 after every
token, "fp8_experts" rounds every expert matrix to float8_e4m3 with one
scale a matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = "highest"


def _f32(t):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


@functools.partial(jax.jit, static_argnames=("H", "P", "G", "N", "eps",
                                             "faults"))
def mamba_forward(x, p, *, H, P, G, N, eps, faults=()):
    """(B, T, C) float32 -> the mixer's output, from a zero state."""
    with jax.default_matmul_precision(HI):
        p = _f32(p)
        B, T, _ = x.shape
        d_inner, K = H * P, p["conv_w"].shape[0]
        conv_dim = d_inner + 2 * G * N
        zxd = x @ p["in_proj"]
        z, xbc, dt = jnp.split(zxd, [d_inner, d_inner + conv_dim], axis=-1)
        pad = jnp.concatenate([jnp.zeros((B, K - 1, conv_dim)), xbc], axis=1)
        xbc = sum(pad[:, k:k + T] * p["conv_w"][k] for k in range(K))
        xbc = jax.nn.silu(xbc + p["conv_b"])
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
            if "bf16_state" in faults:
                h = h.astype(jnp.bfloat16).astype(jnp.float32)
            return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

        swap = lambda a: jnp.swapaxes(a, 0, 1)               # noqa: E731
        _, y = jax.lax.scan(token, jnp.zeros((B, H, P, N)),
                            (swap(xs), swap(Bm), swap(Cm), swap(dt)))
        y = swap(y)
        if "no_skip" not in faults:
            y = y + p["D"][:, None] * xs
        y = y.reshape(B, T, d_inner)
        if "no_gate" not in faults:
            y = y * jax.nn.silu(z)
        y = _rms_norm(y.reshape(B, T, G, d_inner // G), 1.0, eps)
        return (y.reshape(B, T, d_inner) * p["norm_w"]) @ p["out_proj"]


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "hs"))
def attention_forward(x, p, *, n_head, n_kv, hs):
    with jax.default_matmul_precision(HI):
        p = _f32(p)
        B, T, _ = x.shape
        qw = n_head * hs
        qkv = x @ p["c_attn"]["kernel"]
        q, k, v = jnp.split(qkv, [qw, qw + n_kv * hs], axis=-1)
        q = q.reshape(B, T, n_head, hs).transpose(0, 2, 1, 3)
        k = jnp.repeat(k.reshape(B, T, n_kv, hs), n_head // n_kv,
                       axis=2).transpose(0, 2, 1, 3)
        v = jnp.repeat(v.reshape(B, T, n_kv, hs), n_head // n_kv,
                       axis=2).transpose(0, 2, 1, 3)
        att = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(hs))
        att = jnp.where(jnp.tril(jnp.ones((T, T), bool)), att, -jnp.inf)
        y = (jax.nn.softmax(att, axis=-1) @ v).transpose(0, 2, 1, 3)
        return y.reshape(B, T, qw) @ p["c_proj"]["kernel"]


@jax.jit
def scores(x, gate):
    """(N, C) -> the router's sigmoid scores over all routed experts."""
    with jax.default_matmul_precision(HI):
        return jax.nn.sigmoid(x @ gate.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "scale"))
def route(x, gate, bias, *, k, scale):
    """(N, C) -> (ids (N, k) over all routed experts, weights (N, k))."""
    with jax.default_matmul_precision(HI):
        s = jax.nn.sigmoid(x @ gate.astype(jnp.float32))
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=1)
        return idx, w / jnp.sum(w, axis=1, keepdims=True) * scale


def _fp8(a):
    s = jnp.max(jnp.abs(a)) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("fp8",))
def _expert(x, w_up, w_down, weight, fp8=False):
    """One ungated relu^2 expert on every row, times the row's weight for
    it (0 where the row did not choose it). w_up (F, C), w_down (F, C)."""
    with jax.default_matmul_precision(HI):
        w_up, w_down = w_up.astype(jnp.float32), w_down.astype(jnp.float32)
        if fp8:
            w_up, w_down = _fp8(w_up), _fp8(w_down)
        h = jnp.square(jax.nn.relu(x @ w_up.T))
        return (h @ w_down) * weight[:, None]


def experts_forward(x, p, *, k, scale, first, held=None, shared=True,
                    faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds.
    Expert by expert, so one expert's float32 matrices exist at a time."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], p["gate_bias"], k=k, scale=scale)
    n_held = p["experts_up"].shape[0]
    out = jnp.zeros_like(xf)
    for e in (range(first, first + n_held) if held is None else held):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        out = out + _expert(xf, p["experts_up"][e - first],
                            p["experts_down"][e - first], weight,
                            fp8="fp8_experts" in faults)
    if shared:
        out = out + _expert(xf, p["shared_up"].T, p["shared_down"],
                            jnp.ones((xf.shape[0],), jnp.float32),
                            fp8="fp8_experts" in faults)
    return out.reshape(B, T, C)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, *, eps):
    return _rms_norm(x, scale.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_slice(x, ln_f, rows, *, eps):
    with jax.default_matmul_precision(HI):
        return _rms_norm(x, ln_f.astype(jnp.float32), eps) \
            @ rows.astype(jnp.float32).T


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's mixer on its normed input `h`: `kind` 'M', '*' or 'E',
    `p` the block's parameters."""
    eps = cfg.get("norm_eps", 1e-5)
    if kind == "M":
        return mamba_forward(h, p["ssm"], H=cfg["ssm_heads"],
                             P=cfg["ssm_head_dim"], G=cfg["ssm_groups"],
                             N=cfg["ssm_state"], eps=eps,
                             faults=tuple(faults))
    if kind == "*":
        return attention_forward(h, p["attn"], n_head=cfg["n_head"],
                                 n_kv=cfg["n_kv_heads"],
                                 hs=cfg.get("head_dim")
                                 or cfg["n_embd"] // cfg["n_head"])
    return experts_forward(h, p["moe"], k=cfg["n_act"] - cfg["n_shared"],
                           scale=cfg.get("routed_scale", 1.0),
                           first=(cfg.get("experts_held") or (0, 0))[0],
                           faults=tuple(faults))


def forward_hidden(params, cfg: dict, idx, faults=(), before_experts=None):
    """(B, T) ids -> (B, T, C) float32 before the final norm.
    `before_experts(i, h, block)` may hand back another block to run layer
    i's experts with (the runner sets a router's bias from `h` there)."""
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
    positions only. The head is applied a slice of the vocabulary at a
    time (its float32 copy is 0.7 GB whole)."""
    x = forward_hidden(params, cfg, idx, faults)
    if last:
        x = x[:, -last:]
    head = params["lm_head"]
    eps = cfg.get("norm_eps", 1e-5)
    V = head.shape[0]
    step = -(-V // vocab_slices)
    return jnp.concatenate(
        [_head_slice(x, params["ln_f"]["scale"], head[v:v + step], eps=eps)
         for v in range(0, V, step)], axis=-1)
