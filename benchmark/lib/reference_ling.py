"""The plain reference of the Ling-3.0-flash-VL configuration's language
model (HF `bailing_hybrid`): the published layer equations in
straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no chunks,
no WY form, no absorption, no batching of experts, no flax: a KDA layer is
the LITERAL recurrence, one `lax.scan` step a position; its convolution a
sum of four shifted rows; a latent layer makes every position's per-head
key and value from its latent and attends a block of query rows at a time
under an explicit mask; the router scores its groups in a loop; the experts
run one after another. It is applied layer by layer to the program's OWN
parameter tree (bf16 leaves, cast a layer, and an expert, at a time), so it
fits beside the idle engine on the chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). With hidden 2,560, eps 1e-6, 32 heads of 128:

  embedding  x = E[ids]
  a block    x = x + op(RMSNorm(x)); a published layer is TWO blocks: its
             mixer ('K' or 'L'), then its feed forward ('F' dense, 'E'
             sparse), each behind its own RMSNorm
  K  [q' | k' | v'] = h W_qkv (3 x 4,096); each channel through a causal
     depthwise convolution of 4 taps (no bias), then silu; a head's
     q = q'' / |q''|_2 x 128^-1/2, k = k'' / |k''|_2 (epsilon 1e-6 inside
     the root); g = -5 sigmoid(exp(A_log_head) (h W_a + dt_bias)), a value a
     head a channel, alpha = exp(g); beta = sigmoid(h W_beta), a scalar a
     head; per head, from S = 0 at the sequence's start:
         S' = Diag(alpha_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
         o_t = S^T q_t
     y = [RMSNorm_128(o_head) * sigmoid(h W_g)_head]_heads W_o. No
     positions.
  L  q = h W_q (no query latent: `q_lora_rank` null), a head's 192 =
     [q_nope 128 | q_rope 64]; [c_kv 512 | k_r 64] = h W_kva; c =
     RMSNorm_512(c_kv); k_r is ONE key head shared by all 32 query heads.
     RoPE on q_rope (every head) and k_r, the published way
     (`rope_interleave` true): the 64 lanes viewed as 32 pairs (2i, 2i + 1),
     transposed to halves, then `rotate_half` by p * theta^(-2i/64), theta
     6e6. [k_nope_n 128 | v_n 128] = c W_kvb,n. score_n(t, s) = (q_nope_n(t)
     . k_nope_n(s) + q_rope_n(t) . k_r(s)) / sqrt(192), causal, softmax in
     float32; y = [o_0 .. o_31] W_o.
  F  W_down (silu(W_gate u) * W_up u), width 6,144
  E  s = sigmoid(u W_r) over all 512, float32; s' = s + b; the 512 are 8
     groups of 64 consecutive ids, a group scores the sum of its two
     largest s', the 4 best groups are kept and every s' outside them is
     masked; the top 8 of the masked s'; weights = s of the chosen over
     (their sum + 1e-20), times 2.5. Expert e: W_2[e] (silu(W_1[e] u) *
     W_3[e] u), width 768; plus one shared expert of the same form and
     width, added as it is.
  head       after the last layer one RMSNorm, then logits = x H^T, the
             head H a matrix of its own

Departures from the published description, each where it changes no number
a comparison reads: (1) no cache: every call is a full forward pass from
position 0 and from a zero state; (2) the rotated lanes of a latent layer
stay in the halves order the published transpose leaves them in, for q_rope
and k_r alike (a permutation common to both; the program pairs adjacent
lanes in place); (3) latent attention by blocks of `QUERY_BLOCK` query
rows, for memory; (4) the routed experts this chip does not hold add
nothing (`experts_held`: the cut, in program and reference alike); (5) the
vision tower and the multi-token-prediction module are neither held nor
run; (6) no clamp on the gated units (`expert_swiglu_limit_list` is 0 for
every held layer).

The tree's layouts: `W_qkv` (C, 3 x 4,096) is [q' | k' | v'] by columns,
each head-major; `conv_w` (4, 12,288), row j on the input 3 - j steps back;
`W_a` (C, 4,096) head-major; `W_bg` (C, 64) = [beta (32) | gate (32)];
`W_q` (C, 32 x 192) and `W_kvb` (512, 32 x 256) head-major by columns, a
head's `[nope | rope]` and `[k_nope | v]`; `W_kva` (C, 576) = [c_kv | k_r];
the dense FFN's `c_fc` (C, 2F) = [W_gate | W_up] by columns, an expert's up
matrix (2F, C) = [W_1 ; W_3] by rows, `shared_up` (C, 2F) by columns.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), lm_head (V, C), ln_f/scale,
  block_<i>/norm/scale, and by kind
  block_<i>/kda/{W_qkv, W_a, W_bg, conv_w, A_log (32,) f32,
                 dt_bias (4096,) f32, o_norm (128,), W_o}
  block_<i>/latent_attn/{W_q, W_kva, kv_norm, W_kvb, W_o}
  block_<i>/mlp/{c_fc (C, 2F), c_proj (F, C)}
  block_<i>/moe/{gate (C, 512), gate_bias (512,) float32,
                 experts_up (held, 2F, C), experts_down (held, F, C),
                 shared_up (C, 2F), shared_down (F, C)}

`faults` (tests and PERF.md's second readings only) breaks one term so that
the comparison is shown to see it: FAULTS below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid import HI, _head_slice, _norm, _rms_norm
from benchmark.lib.reference_lfm2 import _expert, dense_forward, scores  # noqa: F401

KDA_FAULTS = (
    "alpha_one",          # no decay: alpha = 1
    "unbounded_gate",     # g = -exp(A_log) softplus(a + dt_bias)
    "beta_one",           # beta = 1
    "no_delta",           # S = alpha S + beta k v^T: nothing taken back
    "decay_after_delta",  # the decay on the state AFTER the write
    "k_not_normalised",   # k'' as the convolution leaves it
    "no_q_scale",         # q without 128^-1/2
    "no_conv",            # the convolution left out (silu stays)
    "no_silu",            # the convolution without its silu
    "no_head_gate",       # the head-wise output gate left out
    "no_out_norm",        # the heads' RMSNorm left out
    "bf16_state",         # the state rounded to bfloat16 after every token
    "fp8_w_a",            # W_a rounded to float8 e4m3
    "fp8_kda",            # every KDA matrix rounded to float8 e4m3
)
LATENT_FAULTS = (
    "rope_off",           # no rotation at all in the latent layer
    "no_kv_norm",         # the key/value latent not normed
    "fp8_w_kvb",          # W_kvb rounded to float8 e4m3
    "fp8_latent_rows",    # c and the rotated k_r (what a cache would hold)
)
ROUTE_FAULTS = (
    "no_group_limit",     # the top 8 of all 512
    "group_top1",         # a group scores its one largest s'
    "no_routed_scale",    # x 2.5 left out
)
FAULTS = KDA_FAULTS + LATENT_FAULTS + ROUTE_FAULTS + (
    "no_shared",          # the shared expert left out
    "fp8_experts",        # every expert matrix rounded to float8 e4m3
    "fp8_dense",          # the dense FFN's two matrices in float8 e4m3
)
QUERY_BLOCK = 128
ROUTE_EPS = 1e-20
L2_EPS = 1e-6


def round_fp8(a):
    """`a` rounded to 4 exponent and 3 mantissa bits under one scale a
    tensor (`jax.lax.reduce_precision`: a float32 -> float8 -> float32
    pair alone is removed by the TPU compiler as excess precision)."""
    s = jnp.max(jnp.abs(a)) / 240.0
    return jax.lax.reduce_precision(a / s, exponent_bits=4,
                                    mantissa_bits=3) * s


# ---------------------------------------------------------------------------
# K: the literal recurrence
# ---------------------------------------------------------------------------

def _recurrence(q, k, v, alpha, beta, faults=()):
    """Step 6, a position at a time from a zero state: q, k, v, alpha
    (B, T, H, d), beta (B, T, H) -> (o (B, T, H, d), the state after the
    last position (B, H, d, d))."""
    B, _, H, d = q.shape

    def token(S, x):
        q_t, k_t, v_t, a_t, b_t = x                          # (B, H, .)
        if "decay_after_delta" in faults:
            S = S + b_t[..., None, None] * k_t[..., None] * (
                v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))[..., None, :]
            S = a_t[..., None] * S
        else:
            S = a_t[..., None] * S
            take = 0.0 if "no_delta" in faults else \
                jnp.einsum("bhkv,bhk->bhv", S, k_t)
            S = S + b_t[..., None, None] * k_t[..., None] \
                * (v_t - take)[..., None, :]
        if "bf16_state" in faults:
            # (a float32 -> bfloat16 -> float32 pair alone is removed by
            # the TPU compiler as excess precision: it read as sound)
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(
        token, jnp.zeros((B, H, d, d), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1), S


@functools.partial(jax.jit, static_argnames=("faults",))
def kda_state_after(q, k, v, g, beta, faults=()):
    """The state ONE sequence's rows leave behind, from a zero state, given
    the recurrence's own operands as a program made them: q, k, v and the
    log decay g (T, H, d), beta (T, H) -> (H, d, d) float32. What the
    runner holds a slot's `state` leaf to (`slot_state`): with the
    operands common to both sides, what is left is the state's own
    arithmetic and the precision it is kept in."""
    with jax.default_matmul_precision(HI):
        q, k, v, g, beta = (t.astype(jnp.float32)[None]
                            for t in (q, k, v, g, beta))
        return _recurrence(q, k, v, jnp.exp(g), beta, faults)[1][0]


@functools.partial(jax.jit, static_argnames=("H", "d", "bound", "eps",
                                             "faults"))
def kda_forward(h, p, *, H, d, bound, eps, faults=()):
    """(B, T, C) float32 normed input from position 0 and a zero state ->
    the layer's output before the residual add. Steps 1-7 literally."""
    with jax.default_matmul_precision(HI):
        B, T, _ = h.shape
        low = round_fp8 if "fp8_kda" in faults else (lambda w: w)
        w = {n: low(p[n].astype(jnp.float32))
             for n in ("W_qkv", "W_a", "W_bg", "W_o")}
        if "fp8_w_a" in faults:
            w["W_a"] = round_fp8(w["W_a"])
        u = h @ w["W_qkv"]                                   # (B, T, 3 H d)
        if "no_conv" not in faults:
            taps = p["conv_w"].astype(jnp.float32)           # (4, 3 H d)
            K = taps.shape[0]
            padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
            u = sum(padded[:, j:j + T] * taps[j] for j in range(K))
        if "no_silu" not in faults:
            u = jax.nn.silu(u)
        q, k, v = (t.reshape(B, T, H, d) for t in jnp.split(u, 3, axis=-1))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        if "no_q_scale" not in faults:
            q = q * d ** -0.5
        if "k_not_normalised" not in faults:
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        rate = jnp.repeat(jnp.exp(p["A_log"].astype(jnp.float32)), d)
        x = h @ w["W_a"] + p["dt_bias"].astype(jnp.float32)
        g = -rate * jax.nn.softplus(x) if "unbounded_gate" in faults \
            else bound * jax.nn.sigmoid(rate * x)
        alpha = jnp.exp(g).reshape(B, T, H, d)
        if "alpha_one" in faults:
            alpha = jnp.ones_like(alpha)
        bg = jax.nn.sigmoid(h @ w["W_bg"])
        beta, gate = bg[..., :H], bg[..., H:]
        if "beta_one" in faults:
            beta = jnp.ones_like(beta)

        o, _ = _recurrence(q, k, v, alpha, beta, faults)
        if "no_out_norm" not in faults:
            o = _rms_norm(o, p["o_norm"].astype(jnp.float32), eps)
        if "no_head_gate" not in faults:
            o = o * gate[..., None]
        return o.reshape(B, T, H * d) @ w["W_o"]


# ---------------------------------------------------------------------------
# L: latent attention, not absorbed, without a query latent
# ---------------------------------------------------------------------------

def rope_published(x, theta: float):
    """(B, T, H, d) at positions 0..T-1, the published way: pairs (2i,
    2i + 1) transposed to halves [evens | odds], then `rotate_half`. The
    result stays in halves order."""
    B, T, H, d = x.shape
    x = x.reshape(B, T, H, d // 2, 2).swapaxes(-1, -2).reshape(B, T, H, d)
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None]   # (1,T,1,d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("nh", "dn", "dr", "dv", "lc",
                                             "eps", "theta", "faults"))
def latent_forward(h, p, *, nh, dn, dr, dv, lc, eps, theta, faults=()):
    """(B, T, C) float32 normed input from position 0 -> the layer's
    output before the residual add."""
    with jax.default_matmul_precision(HI):
        B, T, _ = h.shape
        w = {n: p[n].astype(jnp.float32)
             for n in ("W_q", "W_kva", "W_kvb", "W_o")}
        if "fp8_w_kvb" in faults:
            w["W_kvb"] = round_fp8(w["W_kvb"])
        q = (h @ w["W_q"]).reshape(B, T, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        ckr = h @ w["W_kva"]
        c, k_r = ckr[..., :lc], ckr[..., None, lc:]          # k_r (B,T,1,dr)
        if "no_kv_norm" not in faults:
            c = _rms_norm(c, p["kv_norm"].astype(jnp.float32), eps)
        if "rope_off" not in faults:
            q_rope, k_r = rope_published(q_rope, theta), \
                rope_published(k_r, theta)
        if "fp8_latent_rows" in faults:
            c, k_r = round_fp8(c), round_fp8(k_r)
        kv = (c @ w["W_kvb"]).reshape(B, T, nh, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k_r = jnp.broadcast_to(k_r, (B, T, nh, dr))
        scale = 1.0 / jnp.sqrt(jnp.float32(dn + dr))
        pad = -T % QUERY_BLOCK
        blocks = [jnp.moveaxis(jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                               .reshape(B, -1, QUERY_BLOCK, nh, a.shape[-1]),
                               1, 0) for a in (q_nope, q_rope)]
        kpos = jnp.arange(T)

        def block(args):
            qn, qr, start = args                     # (B, QB, nh, .)
            qpos = start + jnp.arange(QUERY_BLOCK)
            att = (jnp.einsum("bqnd,bsnd->bnqs", qn, k_nope)
                   + jnp.einsum("bqnr,bsnr->bnqs", qr, k_r)) * scale
            att = jnp.where(qpos[:, None] >= kpos[None, :], att, -jnp.inf)
            return jnp.einsum("bnqs,bsnv->bqnv",
                              jax.nn.softmax(att, axis=-1), v)

        n_blocks = blocks[0].shape[0]
        y = jax.lax.map(block, (*blocks,
                                jnp.arange(n_blocks) * QUERY_BLOCK))
        y = jnp.moveaxis(y, 0, 1).reshape(B, -1, nh * dv)[:, :T]
        return y @ w["W_o"]


# ---------------------------------------------------------------------------
# E: the router group by group, the experts one by one
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "scale", "n_group",
                                             "topk_group", "faults"))
def route(x, gate, bias, *, k, scale, n_group=1, topk_group=1, faults=()):
    """(N, C) -> (ids (N, k) over all routed experts, weights (N, k))."""
    with jax.default_matmul_precision(HI):
        s = jax.nn.sigmoid(x @ gate.astype(jnp.float32))
        biased = s + bias.astype(jnp.float32)
        E = s.shape[1]
        if n_group > 1 and "no_group_limit" not in faults:
            size = E // n_group
            best = 1 if "group_top1" in faults else 2
            group_score = jnp.stack(
                [jnp.sum(jax.lax.top_k(biased[:, g * size:(g + 1) * size],
                                       best)[0], axis=1)
                 for g in range(n_group)], axis=1)           # (N, n_group)
            _, kept = jax.lax.top_k(group_score, topk_group)
            masked = []
            for g in range(n_group):
                inside = jnp.any(kept == g, axis=1, keepdims=True)
                masked.append(jnp.where(
                    inside, biased[:, g * size:(g + 1) * size], -jnp.inf))
            biased = jnp.concatenate(masked, axis=1)
        _, idx = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(s, idx, axis=1)
        w = w / (jnp.sum(w, axis=1, keepdims=True) + ROUTE_EPS)
        return idx, w if "no_routed_scale" in faults else w * scale


def experts_forward(x, p, *, k, scale, n_group=1, topk_group=1, first=0,
                    held=None, shared=True, faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds;
    `shared` False leaves the shared expert's part out (the shares-add-up
    test counts it once). Expert by expert."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], p["gate_bias"], k=k, scale=scale,
                   n_group=n_group, topk_group=topk_group,
                   faults=tuple(f for f in faults if f in ROUTE_FAULTS))
    n_held = p["experts_up"].shape[0]
    fp8 = "fp8_experts" in faults
    out = jnp.zeros_like(xf)
    for e in (range(first, first + n_held) if held is None else held):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        out = out + _expert(xf, p["experts_up"][e - first],
                            p["experts_down"][e - first], weight, fp8=fp8)
    if shared and "no_shared" not in faults:
        out = out + _expert(xf, p["shared_up"].T, p["shared_down"],
                            jnp.ones((xf.shape[0],), jnp.float32), fp8=fp8)
    return out.reshape(B, T, C)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _latent_widths(cfg: dict) -> dict:
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return dict(nh=cfg["n_head"], dn=cfg.get("qk_nope_head_dim") or hs,
                dr=cfg["rope_head_dim"], dv=cfg.get("v_head_dim") or hs,
                lc=cfg["kv_latent_dim"], eps=cfg.get("norm_eps", 1e-5),
                theta=float(cfg.get("rope_theta", 1e4)))


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's operator on its normed input `h` (B, T, C), the rows at
    positions 0..T-1 (a 'K' block from a zero state): `kind` 'K', 'L', 'F'
    or 'E', `p` the block's parameters."""
    if kind == "K":
        return kda_forward(h, p["kda"], H=cfg["kda_heads"],
                           d=cfg["kda_head_dim"],
                           bound=float(cfg.get("kda_lower_bound", -5.0)),
                           eps=cfg.get("norm_eps", 1e-5),
                           faults=tuple(f for f in faults
                                        if f in KDA_FAULTS))
    if kind == "L":
        return latent_forward(h, p["latent_attn"], **_latent_widths(cfg),
                              faults=tuple(f for f in faults
                                           if f in LATENT_FAULTS))
    if kind == "F":
        return dense_forward(h, p["mlp"], faults=("fp8_mixers",)
                             if "fp8_dense" in faults else ())
    assert kind == "E", kind
    return experts_forward(h, p["moe"], k=cfg["n_act"] - cfg["n_shared"],
                           scale=cfg.get("routed_scale", 1.0),
                           n_group=cfg.get("n_group", 1),
                           topk_group=cfg.get("topk_group", 1),
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
                   vocab_slices: int = 4):
    """(B, T) int32 ids -> (B, T, V) float32 logits, or of the last `last`
    positions only, through the head of its own, a slice of the vocabulary
    at a time."""
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
