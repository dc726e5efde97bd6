"""The plain reference of the JoyAI-LLM-Flash configuration (HF
`joyai_llm_flash`, `deepseek_v3`-style modules): the published layer
equations in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no
absorption, no batching of experts, no flax: every position's per-head key
and value are made from its latent, `[k_nope_n | v_n] = c W_kvb,n`, attention
runs a block of query rows at a time against ALL the keys of the sequence
under an explicit mask, the experts run one after another. It is applied
layer by layer to the program's OWN parameter tree (bf16 leaves, cast a
layer, and an expert, at a time), so it fits beside the idle engine on the
chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). With hidden 2048, eps 1e-6, 32 heads:

  embedding  x = E[ids]
  a block    x = x + op(RMSNorm(x)); a published layer is TWO blocks: its
             attention ('L'), then its feed forward ('F' dense, 'E' sparse),
             each behind its own RMSNorm
  L  c_q = RMSNorm_1536(h W_qa); q = c_q W_qb, a head's 192 = [q_nope 128 |
     q_rope 64]; [c_kv 512 | k_r 64] = h W_kva; c = RMSNorm_512(c_kv); k_r
     is ONE key head shared by all 32 query heads. RoPE on q_rope (every
     head) and k_r, the published way (`rope_interleave` true): the 64
     lanes viewed as 32 pairs (2i, 2i + 1), transposed to halves [evens |
     odds], then `rotate_half` by p * theta^(-2i/64), theta 32e6, no
     scaling. [k_nope_n 128 | v_n 128] = c W_kvb,n. score_n(t, s) =
     (q_nope_n(t) . k_nope_n(s) + q_rope_n(t) . k_r(s)) / sqrt(192),
     causal, softmax in float32; y = [o_0 .. o_31] W_o.
  F  W_down (silu(W_gate u) * W_up u), width `intermediate_size` 7,168
  E  s = sigmoid(u W_r) over all 256, float32; the top 8 of s + b (the
     selection bias moves the SELECTION only; `n_group` = `topk_group` = 1:
     the group limit is the whole set); weights = s of the chosen over
     (their sum + 1e-20), times `routed_scaling_factor` 2.5. Expert e:
     W_2[e] (silu(W_1[e] u) * W_3[e] u), width 768; plus one shared expert
     of the same form and width, added as it is.
  head       after the last layer one RMSNorm, then logits = x H^T, the
             head H a matrix of its own (`tie_word_embeddings` false)

Departures from the published code, each where it changes no number a
comparison reads: (1) no cache and no `past_key_values`: every call is a
full forward pass from position 0; (2) the rotated lanes stay in the
halves order the published transpose leaves them in, for q_rope and k_r
alike (a permutation common to both leaves every score as it was; the
program pairs adjacent lanes in place, and a test holds the two equal);
(3) attention by blocks of `QUERY_BLOCK` query rows, for memory; (4) the
routed experts this chip does not hold add nothing (`experts_held`: the
cut, in program and reference alike); (5) the multi-token-prediction module
(`num_nextn_predict_layers` 1) is neither held nor run: the 40 layers
alone give the model's own next-token logits; (6) group-limited routing is
not written out: with one group it is the plain top 8.

The tree's layouts: `W_qb` (1536, 32 x 192) and `W_kvb` (512, 32 x 256)
are head-major by columns, a head's `[nope | rope]` and `[k_nope | v]`;
`W_kva` (2048, 576) is `[c_kv | k_r]`; the dense FFN's `c_fc` (h, 2F) is
[W_gate | W_up] by columns, an expert's up matrix (2F, h) is [W_1 ; W_3] by
rows, `shared_up` (h, 2F) by columns.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), lm_head (V, C), ln_f/scale,
  block_<i>/norm/scale, and by kind
  block_<i>/latent_attn/{W_qa, q_norm, W_qb, W_kva, kv_norm, W_kvb, W_o}
  block_<i>/mlp/{c_fc (C, 2F), c_proj (F, C)}
  block_<i>/moe/{gate (C, 256), gate_bias (256,) float32,
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

FAULTS = (
    "no_q_norm",          # the query latent not normed
    "no_kv_norm",         # the key/value latent not normed
    "scale_nope",         # scores over sqrt(128), not sqrt(192)
    "rope_off",           # no rotation at all
    "theta_1e4",          # the angles' base 10,000
    "rope_first_lanes",   # a head's FIRST 64 lanes rotate, not its last
    "rope_no_transpose",  # halves paired as they lie: lane i with i + 32
    "k_r_per_head",       # head n's rotary key is k_r rolled by n lanes
    "k_r_unrotated",      # the shared key enters the scores unrotated
    "no_renorm",          # the chosen weights not divided by their sum
    "no_routed_scale",    # x 2.5 left out
    "bias_in_weights",    # the selection bias enters the weights
    "no_shared",          # the shared expert left out
    "fp8_experts",        # every expert matrix rounded to float8 e4m3
    "fp8_latent_rows",    # c and the rotated k_r (what a cache would hold)
    "fp8_w_kvb",          # W_kvb rounded to float8 e4m3
    "fp8_attention",      # every attention matrix rounded to float8 e4m3
    "fp8_dense",          # the dense FFN's two matrices in float8 e4m3
)
QUERY_BLOCK = 128
ROUTE_EPS = 1e-20


def round_fp8(a):
    """`a` rounded to 4 exponent and 3 mantissa bits under one scale a
    tensor (`jax.lax.reduce_precision`: a float32 -> float8 -> float32
    pair alone is removed by the TPU compiler as excess precision, PERF.md
    section 7)."""
    s = jnp.max(jnp.abs(a)) / 240.0
    return jax.lax.reduce_precision(a / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def rope_published(x, theta: float, transpose: bool = True):
    """(B, T, H, d) at positions 0..T-1, the published way: pairs (2i,
    2i + 1) transposed to halves [evens | odds], then `rotate_half`. The
    result stays in halves order."""
    B, T, H, d = x.shape
    if transpose:
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
    output before the residual add. Steps 1-5 literally."""
    with jax.default_matmul_precision(HI):
        B, T, _ = h.shape
        low = round_fp8 if "fp8_attention" in faults else (lambda w: w)
        w = {k: low(p[k].astype(jnp.float32))
             for k in ("W_qa", "W_qb", "W_kva", "W_kvb", "W_o")}
        if "fp8_w_kvb" in faults:
            w["W_kvb"] = round_fp8(w["W_kvb"])
        c_q = h @ w["W_qa"]
        if "no_q_norm" not in faults:
            c_q = _rms_norm(c_q, p["q_norm"].astype(jnp.float32), eps)
        q = (c_q @ w["W_qb"]).reshape(B, T, nh, dn + dr)
        if "rope_first_lanes" in faults:
            q_rope, q_nope = q[..., :dr], q[..., dr:]
        else:
            q_nope, q_rope = q[..., :dn], q[..., dn:]
        ckr = h @ w["W_kva"]
        c, k_r = ckr[..., :lc], ckr[..., None, lc:]          # k_r (B,T,1,dr)
        if "no_kv_norm" not in faults:
            c = _rms_norm(c, p["kv_norm"].astype(jnp.float32), eps)
        if "k_r_per_head" in faults:
            k_r = jnp.concatenate([jnp.roll(k_r, n, axis=-1)
                                   for n in range(nh)], axis=2)
        if "rope_off" not in faults:
            base = 1e4 if "theta_1e4" in faults else theta
            tr = "rope_no_transpose" not in faults
            q_rope = rope_published(q_rope, base, tr)
            if "k_r_unrotated" not in faults:
                k_r = rope_published(k_r, base, tr)
        if "fp8_latent_rows" in faults:
            c, k_r = round_fp8(c), round_fp8(k_r)
        kv = (c @ w["W_kvb"]).reshape(B, T, nh, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k_r = jnp.broadcast_to(k_r, (B, T, nh, dr))
        scale = 1.0 / jnp.sqrt(jnp.float32(
            dn if "scale_nope" in faults else dn + dr))
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
        return idx, w if "no_routed_scale" in faults else w * scale


def experts_forward(x, p, *, k, scale, first=0, held=None, shared=True,
                    faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds;
    `shared` False leaves the shared expert's part out (the shares-add-up
    test counts it once). Expert by expert."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], p["gate_bias"], k=k, scale=scale,
                   faults=tuple(f for f in faults if f in (
                       "no_renorm", "no_routed_scale", "bias_in_weights")))
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


def _widths(cfg: dict) -> dict:
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    return dict(nh=cfg["n_head"], dn=cfg.get("qk_nope_head_dim") or hs,
                dr=cfg["rope_head_dim"], dv=cfg.get("v_head_dim") or hs,
                lc=cfg["kv_latent_dim"], eps=cfg.get("norm_eps", 1e-5),
                theta=float(cfg.get("rope_theta", 1e4)))


_LATENT_FAULTS = FAULTS[:9] + ("fp8_latent_rows", "fp8_w_kvb",
                               "fp8_attention")


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's operator on its normed input `h` (B, T, C), the rows at
    positions 0..T-1: `kind` 'L', 'F' or 'E', `p` the block's parameters."""
    if kind == "L":
        return latent_forward(h, p["latent_attn"], **_widths(cfg),
                              faults=tuple(f for f in faults
                                           if f in _LATENT_FAULTS))
    if kind == "F":
        return dense_forward(h, p["mlp"], faults=("fp8_mixers",)
                             if "fp8_dense" in faults else ())
    assert kind == "E", kind
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
