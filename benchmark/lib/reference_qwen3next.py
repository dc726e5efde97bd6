"""The plain reference of the Qwen3-Next-80B-A3B-Instruct configuration (HF
`qwen3_next`): the published layer equations in straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`. No kernels, no cache, no
chunks, no WY form, no batching of experts, no flax: a Gated-DeltaNet layer
is the LITERAL recurrence, one `lax.scan` step a position; its convolution a
sum of four shifted rows; a full-attention layer is ONE masked score square;
the router takes the softmax over all 512 logits first, as published; the
experts run one after another. It is applied layer by layer to the program's
OWN parameter tree (bf16 leaves, cast a layer, and an expert, at a time), so
it fits beside the idle engine on the chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). With hidden 2,048, eps 1e-6, no biases:

  embedding  x = E[ids]
  norm       x_hat * (1 + w): every block's norm, the final norm and the two
             QK-norms (zero-centred). The Gated-DeltaNet output norm alone
             scales by w.
  a block    x = x + op(norm(x)); a published layer is TWO blocks: its mixer
             ('G' or '*'), then its expert layer ('E')
  G  [q' | k' | v' | z] = h W_qkvz (2,048 + 2,048 + 4,096 + 4,096);
     [b | a] = h W_ba (32 + 32); [q' | k' | v'] through a causal depthwise
     convolution of 4 taps (no bias), then silu; 16 key heads of 128: q =
     q'' / |q''|_2 x 128^-1/2, k = k'' / |k''|_2 (epsilon 1e-6 inside the
     root); value head j = 0..31 reads key head j // 2; g_j = -exp(A_log_j)
     softplus(a_j + dt_bias_j), NOT clamped; beta_j = sigmoid(b_j); per value
     head, from S = 0 at the sequence's start:
         S' = exp(g_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
         o_t = S^T q_t
     y = [RMSNorm_128(o_j) * w * silu(z_j)]_j W_o. No positions.
  *  [q' | gate] and k', v from one projection (16 heads over 2, 256 lanes);
     q = RoPE(RMSNorm_256(q') (1 + w_q)), k likewise: the first 64 lanes of
     a head rotated, lane i with lane i + 32 (`rotate_half`), by p *
     theta^(-2i/64), theta 1e7; scores q k^T / 16, causal, softmax in
     float32; y = (o * sigmoid(gate)) W_o, a gate a CHANNEL.
  E  p = softmax over all 512 of h W_r, float32; the top 10 of p; weights
     p_i / (their sum). Expert e: W_2[e] (silu(W_1[e] u) * W_3[e] u), width
     512; plus sigmoid(h w_sg) x one shared expert of the same form.
  head       after the last layer one norm, then logits = x H^T, the head H
             a matrix of its own

Departures from the published code, each where it changes no number a
comparison reads: (1) no cache: every call is a full forward pass from
position 0 and a zero state; (2) W_qkvz's and W_ba's columns stand fused by
KIND, each kind head-major, where the published code lays them out a key
head at a time (a permutation of columns of a drawn matrix); (3) the full
attention's projection is ONE matrix `c_attn` = [q | k | v | gate], each
head-major, where the published `q_proj` interleaves a head's query and
gate (again a permutation); (4) the routed experts this chip does not hold
add nothing (`experts_held`: the cut, in program and reference alike); (5)
the multi-token-prediction module is neither held nor run; (6) rotary
angles in float32 from the positions, no table.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), lm_head (V, C), ln_f/scale,
  block_<i>/norm/scale, and by kind
  block_<i>/gdn/{W_qkvz, W_ba, conv_w (4, 8,192: row j on the input 3 - j
                 steps back), A_log (32,) f32, dt_bias (32,) f32,
                 o_norm (128,), W_o}
  block_<i>/attn/{c_attn/kernel (C, 9,216), c_proj/kernel (4,096, C),
                  q_norm (256,), k_norm (256,)}
  block_<i>/moe/{gate (C, 512), experts_up (held, 2F, C) = [W_1 ; W_3] by
                 rows, experts_down (held, F, C), shared_up (C, 2F) by
                 columns, shared_down (F, C), shared_gate (C, 1)}

`faults` (tests and PERF.md's second readings only) breaks one term so that
the comparison is shown to see it: FAULTS below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = "highest"

GDN_FAULTS = (
    "key_head_tiled",     # value head j reads key head j % 16, not j // 2
    "no_softplus",        # g = -exp(A_log) (a + dt_bias), capped at 0 (a
                          # growing state reads nan, which tells nothing)
    "beta_one",           # beta = 1
    "no_delta",           # S = alpha S + beta k v^T: nothing taken back
    "sigmoid_z",          # sigmoid(z) for silu(z)
    "no_conv",            # the convolution left out (silu stays)
    "out_norm_1pw",       # the output norm scaled by 1 + w
    "bf16_state",         # the state rounded to bfloat16 after every token
    "fp8_w_qkvz",         # W_qkvz rounded to float8 e4m3
)
ATTN_FAULTS = (
    "no_attn_gate",       # the output gate left out
    "attn_gate_head_mean",  # a head's mean gate in every channel's place
    "rope_all_lanes",     # all 256 lanes rotated
    "rope_adjacent",      # lane 2i with 2i + 1
    "fp8_pool_rows",      # the rotated keys and the values (what a cache
                          # holds) rounded to float8 e4m3
)
ROUTE_FAULTS = (
    "no_renorm",          # the top 10 of p as they are
)
FAULTS = GDN_FAULTS + ATTN_FAULTS + ROUTE_FAULTS + (
    "norm_w_not_1pw",     # every zero-centred norm scaled by w
    "no_shared_gate",     # the shared expert added as it is
    "fp8_experts",        # every expert matrix rounded to float8 e4m3
)
L2_EPS = 1e-6


def round_fp8(a):
    """`a` rounded to 4 exponent and 3 mantissa bits under one scale a
    tensor (`jax.lax.reduce_precision`: a float32 -> float8 -> float32
    pair alone is removed by the TPU compiler as excess precision)."""
    s = jnp.max(jnp.abs(a)) / 240.0
    return jax.lax.reduce_precision(a / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps, faults=()):
    """The zero-centred norm: x_hat (1 + w)."""
    w = _f32(w)
    return _rms(x, eps) * (w if "norm_w_not_1pw" in faults else 1.0 + w)


# ---------------------------------------------------------------------------
# G: the literal recurrence
# ---------------------------------------------------------------------------

def _recurrence(q, k, v, alpha, beta, faults=()):
    """A position at a time from a zero state: q, k, v (B, T, H, d), alpha
    and beta (B, T, H) -> (o (B, T, H, d), the state after the last
    position (B, H, d, d))."""
    B, _, H, d = q.shape

    def token(S, x):
        q_t, k_t, v_t, a_t, b_t = x                          # (B, H, .)
        S = a_t[..., None, None] * S
        take = 0.0 if "no_delta" in faults else \
            jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + b_t[..., None, None] * k_t[..., None] \
            * (v_t - take)[..., None, :]
        if "bf16_state" in faults:
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(
        token, jnp.zeros((B, H, d, d), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1), S


@functools.partial(jax.jit, static_argnames=("faults",))
def gdn_state_after(q, k, v, g, beta, faults=()):
    """The state ONE sequence's rows leave behind, from a zero state, given
    the recurrence's own operands as a program made them: q, k, v
    (T, H, d), the log decay g and beta (T, H) -> (H, d, d) float32. What
    the runner holds a slot's `state` leaf to (`slot_state`)."""
    with jax.default_matmul_precision(HI):
        q, k, v, g, beta = (_f32(t)[None] for t in (q, k, v, g, beta))
        return _recurrence(q, k, v, jnp.exp(g), beta, faults)[1][0]


@functools.partial(jax.jit, static_argnames=("H", "Hk", "d", "eps",
                                             "faults"))
def gdn_forward(h, p, *, H, Hk, d, eps, faults=()):
    """(B, T, C) float32 normed input from position 0 and a zero state ->
    the layer's output before the residual add."""
    with jax.default_matmul_precision(HI):
        B, T, _ = h.shape
        Dk, Dv = Hk * d, H * d
        w_qkvz = _f32(p["W_qkvz"])
        if "fp8_w_qkvz" in faults:
            w_qkvz = round_fp8(w_qkvz)
        qkvz = h @ w_qkvz
        u, z = qkvz[..., :2 * Dk + Dv], qkvz[..., 2 * Dk + Dv:]
        if "no_conv" not in faults:
            taps = _f32(p["conv_w"])                         # (4, 2Dk + Dv)
            K = taps.shape[0]
            padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
            u = sum(padded[:, j:j + T] * taps[j] for j in range(K))
        u = jax.nn.silu(u)
        q = u[..., :Dk].reshape(B, T, Hk, d)
        k = u[..., Dk:2 * Dk].reshape(B, T, Hk, d)
        v = u[..., 2 * Dk:].reshape(B, T, H, d)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) \
            * d ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        rep = H // Hk
        if "key_head_tiled" in faults:
            q, k = (jnp.tile(t, (1, 1, rep, 1)) for t in (q, k))
        else:
            q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))
        ba = h @ _f32(p["W_ba"])
        b, a = ba[..., :H], ba[..., H:]
        x = a + _f32(p["dt_bias"])
        g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(x)
        if "no_softplus" in faults:
            g = jnp.minimum(-jnp.exp(_f32(p["A_log"])) * x, 0.0)
        beta = jnp.ones_like(b) if "beta_one" in faults \
            else jax.nn.sigmoid(b)
        o, _ = _recurrence(q, k, v, jnp.exp(g), beta, faults)
        w = _f32(p["o_norm"])
        o = _rms(o, eps) * (1.0 + w if "out_norm_1pw" in faults else w)
        z = z.reshape(B, T, H, d)
        o = o * (jax.nn.sigmoid(z) if "sigmoid_z" in faults
                 else jax.nn.silu(z))
        return o.reshape(B, T, Dv) @ _f32(p["W_o"])


# ---------------------------------------------------------------------------
# *: gated attention, one masked score square
# ---------------------------------------------------------------------------

def _rope(x, theta: float, lanes: int, adjacent: bool):
    """(B, T, heads, hs) at positions 0..T-1: the first `lanes` lanes of a
    head rotated, lane i with lane i + lanes / 2 (`adjacent`: 2i with
    2i + 1), the rest passed on."""
    T = x.shape[1]
    inv = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv    # (T, lanes/2)
    cos, sin = (f(ang)[None, :, None] for f in (jnp.cos, jnp.sin))
    rot, rest = x[..., :lanes], x[..., lanes:]
    if adjacent:
        a, b = rot[..., 0::2], rot[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(rot.shape)
    else:
        a, b = rot[..., :lanes // 2], rot[..., lanes // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
    return jnp.concatenate([out, rest], axis=-1)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "hs", "lanes",
                                             "theta", "eps", "faults"))
def attention_forward(h, p, *, nh, nkv, hs, lanes, theta, eps, faults=()):
    """(B, T, C) float32 normed input from position 0 -> the layer's output
    before the residual add."""
    with jax.default_matmul_precision(HI):
        B, T, _ = h.shape
        qw, kw = nh * hs, nkv * hs
        proj = h @ _f32(p["c_attn"]["kernel"])
        q = proj[..., :qw].reshape(B, T, nh, hs)
        k = proj[..., qw:qw + kw].reshape(B, T, nkv, hs)
        v = proj[..., qw + kw:qw + 2 * kw].reshape(B, T, nkv, hs)
        gate = proj[..., qw + 2 * kw:].reshape(B, T, nh, hs)
        q = _norm(q, p["q_norm"], eps, faults)
        k = _norm(k, p["k_norm"], eps, faults)
        n_rot = hs if "rope_all_lanes" in faults else lanes
        adjacent = "rope_adjacent" in faults
        q, k = _rope(q, theta, n_rot, adjacent), \
            _rope(k, theta, n_rot, adjacent)
        if "fp8_pool_rows" in faults:
            k, v = round_fp8(k), round_fp8(v)
        rep = nh // nkv
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
        att = jnp.einsum("bqnd,bsnd->bnqs", q, k) * hs ** -0.5
        pos = jnp.arange(T)
        att = jnp.where(pos[:, None] >= pos[None, :], att, -jnp.inf)
        o = jnp.einsum("bnqs,bsnd->bqnd", jax.nn.softmax(att, axis=-1), v)
        if "attn_gate_head_mean" in faults:
            gate = jnp.broadcast_to(jnp.mean(gate, -1, keepdims=True),
                                    gate.shape)
        if "no_attn_gate" not in faults:
            o = o * jax.nn.sigmoid(gate)
        return o.reshape(B, T, qw) @ _f32(p["c_proj"]["kernel"])


# ---------------------------------------------------------------------------
# E: the router in the published order, the experts one by one
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "faults"))
def route(x, gate, *, k, faults=()):
    """(N, C) -> (ids (N, k) over all routed experts, weights (N, k)):
    softmax over ALL the logits, the top k of it, divided by their sum."""
    with jax.default_matmul_precision(HI):
        p = jax.nn.softmax(x @ _f32(gate), axis=-1)
        w, idx = jax.lax.top_k(p, k)
        if "no_renorm" not in faults:
            w = w / jnp.sum(w, axis=1, keepdims=True)
        return idx, w


@jax.jit
def scores(x, gate):
    """(N, C) -> the router's probabilities over all routed experts."""
    with jax.default_matmul_precision(HI):
        return jax.nn.softmax(x @ _f32(gate), axis=-1)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _expert(x, w_up, w_down, weight, fp8=False):
    """One gated expert on every row, times the row's weight for it (0
    where the row did not choose it). w_up (2F, C) = [W_1 ; W_3] by rows,
    w_down (F, C)."""
    with jax.default_matmul_precision(HI):
        w_up, w_down = _f32(w_up), _f32(w_down)
        if fp8:
            w_up, w_down = round_fp8(w_up), round_fp8(w_down)
        a, b = jnp.split(x @ w_up.T, 2, axis=-1)
        return ((jax.nn.silu(a) * b) @ w_down) * weight[:, None]


def experts_forward(x, p, *, k, first=0, held=None, shared=True, faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds;
    `shared` False leaves the shared expert's part out (the shares-add-up
    test counts it once). Expert by expert."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], k=k,
                   faults=tuple(f for f in faults if f in ROUTE_FAULTS))
    n_held = p["experts_up"].shape[0]
    fp8 = "fp8_experts" in faults
    out = jnp.zeros_like(xf)
    for e in (range(first, first + n_held) if held is None else held):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        out = out + _expert(xf, p["experts_up"][e - first],
                            p["experts_down"][e - first], weight, fp8=fp8)
    if shared:
        with jax.default_matmul_precision(HI):
            g = jnp.ones((xf.shape[0],), jnp.float32) \
                if "no_shared_gate" in faults \
                else jax.nn.sigmoid(xf @ _f32(p["shared_gate"]))[:, 0]
        out = out + _expert(xf, p["shared_up"].T, p["shared_down"], g,
                            fp8=fp8)
    return out.reshape(B, T, C)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _eps(cfg: dict) -> float:
    return cfg.get("norm_eps", 1e-5)


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's operator on its normed input `h` (B, T, C), the rows at
    positions 0..T-1 (a 'G' block from a zero state): `kind` 'G', '*' or
    'E', `p` the block's parameters."""
    if kind == "G":
        return gdn_forward(h, p["gdn"], H=cfg["gdn_heads"],
                           Hk=cfg["gdn_key_heads"], d=cfg["gdn_head_dim"],
                           eps=_eps(cfg),
                           faults=tuple(f for f in faults
                                        if f in GDN_FAULTS))
    if kind == "*":
        hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
        return attention_forward(
            h, p["attn"], nh=cfg["n_head"], nkv=cfg["n_kv_heads"], hs=hs,
            lanes=int(hs * cfg.get("rotary_frac", 1.0)),
            theta=float(cfg.get("rope_theta", 1e4)), eps=_eps(cfg),
            faults=tuple(f for f in faults
                         if f in ATTN_FAULTS + ("norm_w_not_1pw",)))
    assert kind == "E", kind
    return experts_forward(h, p["moe"], k=cfg["n_act"] - cfg["n_shared"],
                           first=(cfg.get("experts_held") or (0, 0))[0],
                           faults=tuple(faults))


def forward_hidden(params, cfg: dict, idx, faults=(), before_experts=None):
    """(B, T) ids -> (B, T, C) float32 before the final norm.
    `before_experts(i, h, block)` may replace an expert block's parameters
    given its normed input."""
    x = _f32(params["tkn_emb"]["embedding"][idx])
    for i, kind in enumerate(cfg["layer_pattern"]):
        p = params[f"block_{i}"]
        h = _norm(x, p["norm"]["scale"], _eps(cfg), faults)
        if kind == "E" and before_experts is not None:
            p = before_experts(i, h, p)
        x = x + mixer_forward(cfg, kind, p, h, faults)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "faults"))
def _head_slice(x, ln_f, rows, *, eps, faults=()):
    with jax.default_matmul_precision(HI):
        return _norm(x, ln_f, eps, faults) @ _f32(rows).T


def forward_logits(params, cfg: dict, idx, faults=(), last: int = 0,
                   vocab_slices: int = 4):
    """(B, T) int32 ids -> (B, T, V) float32 logits, or of the last `last`
    positions only, through the head of its own, a slice of the vocabulary
    at a time."""
    x = forward_hidden(params, cfg, idx, faults)
    if last:
        x = x[:, -last:]
    head = params["lm_head"]
    V = head.shape[0]
    step = -(-V // vocab_slices)
    norm_faults = tuple(f for f in faults if f == "norm_w_not_1pw")
    return jnp.concatenate(
        [_head_slice(x, params["ln_f"]["scale"], head[v:v + step],
                     eps=_eps(cfg), faults=norm_faults)
         for v in range(0, V, step)], axis=-1)
