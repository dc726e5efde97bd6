"""The plain reference of the Laguna configurations (HF `laguna`): the
published layer equations in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`. No kernels, no cache, no ring, no
batching of experts, no flax: attention runs a block of query rows at a time
against ALL the keys of the sequence under an explicit mask, the experts run
one after another. It is applied layer by layer to the program's OWN
parameter tree (bf16 leaves, cast a layer, and an expert, at a time), so it
fits beside the idle engine on the chip.

`cfg` is the configuration file's `llm_config` (the keyword arguments of the
program's LLMConfig). With h = 3072, eps = 1e-6, head size 128, 8 KV heads:

  embedding  x = E[ids]
  a block    x = x + op(RMSNorm(x)); a published layer is TWO blocks: its
             attention ('*' full, 'W' sliding), then its feed forward ('F'
             dense, 'E' sparse), each behind its own RMSNorm
  * / W  q (H x 128; H = 48 full, 72 sliding), k, v (8 x 128) = x W_qkv, no
     biases; g = sigmoid(x W_g) in R^H (`gating: per-head`); positions on
     q and k (below); o_head = g_head * softmax(q k^T / sqrt(128) + mask) v;
     y = concat(o) W_o. Mask: key j visible to query i iff 0 <= i - j, and
     in a sliding layer also i - j < 512 (`sliding_window`).
     Positions, `rotate_half` pairing in both kinds. Sliding: all 128
     lanes, inv_freq_i = 10000^(-2i/128). Full: the first 64 lanes
     (`partial_rotary_factor` 0.5; lanes 64-127 pass), YaRN at theta 5e5:
     inv_freq_i = theta^(-2i/64), i < 32; low = floor(64 ln(8192 / (32 *
     2 pi)) / (2 ln theta)) = 9, high = ceil(64 ln(8192 / (2 pi)) / (2 ln
     theta)) = 18; ramp_i = clip((i - low) / (high - low), 0, 1);
     inv_freq'_i = (1 - ramp_i) inv_freq_i + ramp_i inv_freq_i / 128; cos
     and sin times `attention_factor` 1.4852030263919618.
  F  W_2 (silu(W_1 x) * W_3 x), width `intermediate_size` 12,288
  E  s = sigmoid(x W_r) over all 256, float32; the top 10 of s + b (the
     selection bias of the tree moves the SELECTION only); weights = s of
     the chosen over their sum (`norm_topk_prob`), times
     `moe_routed_scaling_factor` 2.5, on the experts' OUTPUT. Expert e:
     W_2[e] (silu(W_1[e] x) * W_3[e] x), width 1,024; plus one shared
     expert of the same form and width, added as it is.
  head       after the last layer one RMSNorm, then logits = x H^T, the
             head H a matrix of its own (`tie_word_embeddings` false)

What the catalog's `config` does not say is listed under `assumed` in the
configuration file. The tree's layouts: `c_attn` (h, (H + 16) x 128) is
[q | k | v] by columns; `c_gate` (h, H); the dense FFN's `c_fc` (h, 2F) is
[W_1 | W_3] by columns, an expert's up matrix (2F, h) is [W_1 ; W_3] by
rows, `shared_up` (h, 2F) by columns.

Parameter tree (the program's `variables["params"]`):
  tkn_emb/embedding (V, C), lm_head (V, C), ln_f/scale,
  block_<i>/norm/scale, and by kind
  block_<i>/attn/{c_attn,c_gate,c_proj}/kernel
  block_<i>/mlp/{c_fc (C, 2F), c_proj (F, C)}
  block_<i>/moe/{gate (C, 256), gate_bias (256,) float32,
                 experts_up (held, 2F, C), experts_down (held, F, C),
                 shared_up (C, 2F), shared_down (F, C)}

`faults` (tests and PERF.md's second readings only) breaks one term so that
the comparison is shown to see it: FAULTS below.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid import HI, _fp8, _head_slice, _norm
from benchmark.lib.reference_lfm2 import _expert, dense_forward, scores  # noqa: F401

FAULTS = (
    "window_off",         # a sliding layer sees every earlier key
    "window_448",         # the window 448 keys, not 512
    "rope_swapped",       # sliding layers rotated as full ones, and back
    "rotate_all_lanes",   # a full layer rotates all 128 lanes
    "yarn_factor_1",      # YaRN's factor 1: the frequencies unblended
    "attn_factor_1",      # cos and sin not multiplied
    "no_gate",            # the per-head gate left out
    "gate_scalar",        # ONE gate a token: sigmoid of the heads' summed
                          # logits, on every head
    "no_renorm",          # the chosen weights not divided by their sum
    "no_routed_scale",    # x 2.5 left out
    "no_shared",          # the shared expert left out
    "fp8_experts",        # every expert matrix rounded to float8_e4m3
    "fp8_attention",      # every attention matrix rounded to float8_e4m3
    "fp8_dense",          # the dense FFN's two matrices in float8_e4m3
)
QUERY_BLOCK = 128


def rope_rule(cfg: dict, kind: str) -> tuple:
    """(lanes rotated, inv_freq (lanes / 2,) as a tuple, factor on cos and
    sin) of an attention layer of `kind`, from the configuration."""
    hs = cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"]
    if kind == "W":
        theta = float(cfg.get("window_rope_theta", 1e4))
        return hs, tuple(theta ** (-2.0 * i / hs) for i in range(hs // 2)), \
            1.0
    d = int(hs * cfg.get("rotary_frac", 1.0))
    theta = float(cfg.get("rope_theta", 1e4))
    inv = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    factor = float(cfg.get("rope_factor", 1.0))
    if factor > 1.0:
        fast, slow = 32.0, 1.0      # beta_fast, beta_slow as published
        n = cfg["rope_original_len"]

        def index_of(turns):
            return d * math.log(n / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(index_of(fast)), 0)
        high = min(math.ceil(index_of(slow)), d - 1)
        for i in range(d // 2):
            ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
            inv[i] = (1 - ramp) * inv[i] + ramp * inv[i] / factor
    return d, tuple(inv), float(cfg.get("rope_attn_factor", 1.0))


def _rope(x, lanes: int, inv: tuple, factor: float):
    """(B, T, H, hs) at positions 0..T-1: the first `lanes` lanes turned
    (lane i with lane i + lanes / 2), the others as they are."""
    T = x.shape[1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * factor)[None, :, None]
    sin = (jnp.sin(ang) * factor)[None, :, None]
    a, b, rest = x[..., :lanes // 2], x[..., lanes // 2:lanes], x[..., lanes:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "hs", "window",
                                             "rope", "gated", "faults"))
def attention_forward(x, p, *, n_head, n_kv, hs, window, rope, gated,
                      faults=()):
    """(B, T, C) float32 from position 0. `window` 0: every earlier key."""
    with jax.default_matmul_precision(HI):
        B, T, _ = x.shape
        qw = n_head * hs
        low = _fp8 if "fp8_attention" in faults else (lambda w: w)
        qkv = x @ low(p["c_attn"]["kernel"].astype(jnp.float32))
        q, k, v = jnp.split(qkv, [qw, qw + n_kv * hs], axis=-1)
        q = _rope(q.reshape(B, T, n_head, hs), *rope)
        k = _rope(k.reshape(B, T, n_kv, hs), *rope)
        v = v.reshape(B, T, n_kv, hs)
        rep = n_head // n_kv
        pad = -T % QUERY_BLOCK
        qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            B, -1, QUERY_BLOCK, n_kv, rep, hs)
        kpos = jnp.arange(T)

        def block(args):
            qi, start = args                    # (B, QB, n_kv, rep, hs)
            qpos = start + jnp.arange(QUERY_BLOCK)
            back = qpos[:, None] - kpos[None, :]
            mask = back >= 0
            if window:
                mask = mask & (back < window)
            att = jnp.einsum("bqgrh,bsgh->bgrqs", qi, k) \
                / jnp.sqrt(jnp.float32(hs))
            att = jnp.where(mask, att, -jnp.inf)
            return jnp.einsum("bgrqs,bsgh->bqgrh",
                              jax.nn.softmax(att, axis=-1), v)

        n_blocks = qb.shape[1]
        y = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0),
                                jnp.arange(n_blocks) * QUERY_BLOCK))
        y = jnp.moveaxis(y, 0, 1).reshape(B, -1, n_head, hs)[:, :T]
        if gated and "no_gate" not in faults:
            logit = x @ low(p["c_gate"]["kernel"].astype(jnp.float32))
            if "gate_scalar" in faults:
                logit = jnp.sum(logit, axis=-1, keepdims=True)
            y = y * jax.nn.sigmoid(logit)[..., None]
        return y.reshape(B, T, qw) @ low(p["c_proj"]["kernel"].astype(
            jnp.float32))


@functools.partial(jax.jit, static_argnames=("k", "scale", "faults"))
def route(x, gate, bias, *, k, scale, faults=()):
    """(N, C) -> (ids (N, k) over all routed experts, weights (N, k))."""
    with jax.default_matmul_precision(HI):
        s = jax.nn.sigmoid(x @ gate.astype(jnp.float32))
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=1)
        if "no_renorm" not in faults:
            w = w / jnp.sum(w, axis=1, keepdims=True)
        return idx, w if "no_routed_scale" in faults else w * scale


@functools.partial(jax.jit, static_argnames=("fp8",))
def _shared(x, w_up, w_down, fp8=False):
    with jax.default_matmul_precision(HI):
        w_up, w_down = w_up.astype(jnp.float32), w_down.astype(jnp.float32)
        if fp8:
            w_up, w_down = _fp8(w_up), _fp8(w_down)
        a, b = jnp.split(x @ w_up, 2, axis=-1)
        return (jax.nn.silu(a) * b) @ w_down


def experts_forward(x, p, *, k, scale, first=0, held=None, shared=True,
                    faults=()):
    """The expert layer's output for (B, T, C). `held` = ids (over all
    routed experts) whose part is added: default, those the tree holds;
    `shared` False leaves the shared expert's part out (the shares-add-up
    test counts it once). Expert by expert."""
    B, T, C = x.shape
    xf = x.reshape(-1, C)
    idx, w = route(xf, p["gate"], p["gate_bias"], k=k, scale=scale,
                   faults=tuple(f for f in faults
                                if f in ("no_renorm", "no_routed_scale")))
    n_held = p["experts_up"].shape[0]
    fp8 = "fp8_experts" in faults
    out = jnp.zeros_like(xf)
    for e in (range(first, first + n_held) if held is None else held):
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)
        out = out + _expert(xf, p["experts_up"][e - first],
                            p["experts_down"][e - first], weight, fp8=fp8)
    if shared and "no_shared" not in faults:
        out = out + _shared(xf, p["shared_up"], p["shared_down"], fp8=fp8)
    return out.reshape(B, T, C)


def mixer_forward(cfg: dict, kind: str, p: dict, h, faults=()):
    """One block's operator on its normed input `h` (B, T, C), the rows at
    positions 0..T-1: `kind` '*', 'W', 'F' or 'E', `p` the block's
    parameters."""
    if kind in "*W":
        rule = rope_rule(cfg, kind)
        if "rope_swapped" in faults:
            rule = rope_rule(cfg, "*W".replace(kind, ""))
        if kind == "*":
            if "rotate_all_lanes" in faults:
                full = dict(cfg, rotary_frac=1.0)
                rule = rope_rule(full, "*")
            if "yarn_factor_1" in faults:
                rule = rope_rule(dict(cfg, rope_factor=1.0), "*")
            if "attn_factor_1" in faults:
                rule = rule[:2] + (1.0,)
        window = 0
        if kind == "W" and "window_off" not in faults:
            window = 448 if "window_448" in faults else cfg["window"]
        return attention_forward(
            h, p["attn"],
            n_head=cfg["window_heads"] if kind == "W" else cfg["n_head"],
            n_kv=cfg["n_kv_heads"],
            hs=cfg.get("head_dim") or cfg["n_embd"] // cfg["n_head"],
            window=window, rope=rule, gated=bool(cfg.get("attn_gate")),
            faults=tuple(f for f in faults
                         if f in ("no_gate", "gate_scalar",
                                  "fp8_attention")))
    if kind == "F":
        return dense_forward(h, p["mlp"], faults=("fp8_mixers",)
                             if "fp8_dense" in faults else ())
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
