"""Delta-rule linear attention in attention's place in a patterned model,
two members of the family: `KDA` (`LLMConfig.layer_pattern` 'K': a decay a
channel, Kimi Delta Attention, arXiv 2510.26692), described here, and
`GatedDeltaNet` ('G': a decay a head under fewer key heads than value
heads, a silu gate a channel; arXiv 2412.06464), at the file's end with its
own equations. KDA, for a normed input h (B, T, C), H
heads of d = `kda_head_dim` (d_k = d_v), no biases, no positions:

    [q' | k' | v'] = h W_qkv                 C -> 3 H d
    [q'' | k'' | v] = silu(causal depthwise conv1d(., K taps, no bias))
    (the four taps' sum and the silu in float32 on the compute dtype's rows)
    q = q'' / |q''|_2 d^-1/2,  k = k'' / |k''|_2      L2 over a head's lanes
    g = lower_bound * sigmoid(exp(A_log) (h W_a + dt_bias))
                                             a log decay a head a CHANNEL,
                                             in (lower_bound, 0)
    [beta | gate] = sigmoid(h W_bg)          one scalar a head, each
    S' = Diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
    y = [RMSNorm_d(o_head) * gate_head]_heads W_o

What a sequence carries from token to token is a leaf a slot, never a block
of the paged cache: the convolution's last K - 1 rows of [q' | k' | v']
(`tail`, compute dtype) and the state S (`state`, float32 (H, d, d)),
`init_kda_cache`. The recurrence is ops/delta_rule.py's, the convolution
ops/ssm_scan.py's `causal_conv` / `conv_step`. Three ways in, as
models/ssm.py's:

* no cache: a whole sequence from a zero state (tests);
* `state_ctx["live"]`: one token of every slot, `kda_step`; rows that are
  not live keep their state and tail;
* `state_ctx["slot"]` / `["valid_len"]`: a chunk of ONE sequence into its
  slot's row, `kda_chunk`. A chunk at position 0 starts from zeros
  whatever the slot held (a reused slot is the classic fault), a later one
  from the slot's state; rows past `valid_len` are pads and advance
  neither the state (g = 0, beta = 0) nor the tail.

Scopes: `kda_proj` (W_qkv, W_a, W_bg), `kda_conv` (both forms and the
silu), `kda_gate` (the L2 norms, g, beta and the output gate's sigmoid),
`attn_kda` (the step or the chunk form; the chunk form of a cached chunk
also under `kda_chunk`, inside it), `kda_out` (the heads' RMSNorm, the
gate, W_o).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.models.ssm import chunk_start
from distributed_pytorch_tpu.ops import delta_rule, ssm_scan

_DENSE_INIT = nn.initializers.normal(stddev=0.02)
L2_EPS = 1e-6


def init_kda_cache(cfg: LLMConfig, n_slots: int, dtype) -> dict:
    """One slot's row of each: the state and the convolution tail."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    return {"state": jnp.zeros((n_slots, *delta_rule.state_shape(H, d)),
                               jnp.float32),
            "tail": jnp.zeros((n_slots, cfg.kda_conv - 1, 3 * H * d), dtype)}


def _a_log_init(key, shape, dtype):
    """log of a rate uniform on (1/4, 1)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.25, 1.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """Uniform on (-6, 0): with `_a_log_init`'s rates and a unit-variance
    h W_a the sigmoid stays off its ends and a drawn layer's decays spread
    from a token's memory (g near -3.6) to thousands of tokens' (-0.005)."""
    return jax.random.uniform(key, shape, jnp.float32, -6.0, 0.0).astype(dtype)


def l2_normalise(x):
    """x / |x|_2 over the last axis, in float32 (epsilon inside the root)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _convolved(qkv, conv_w, cache, ctx: dict, pos):
    """[q' | k' | v'] through the causal depthwise convolution and silu, in
    float32 on the compute dtype's rows, by the way in (both mixers'):
    -> (u (B, T, .); the tails after one token of every slot, or a chunk's
    inputs behind its slot's tail; the slot's state at a cached chunk's
    start; how many of a cached chunk's rows are real)."""
    f32 = jnp.float32
    if cache is not None and "live" in ctx:
        assert qkv.shape[1] == 1, "the one-token recurrence takes one " \
            "token a slot"
        u, tail = ssm_scan.conv_step(qkv[:, 0].astype(f32), conv_w, None,
                                     cache["tail"], ctx["live"])
        return jax.nn.silu(u)[:, None], tail, None, None
    tail0 = S0 = valid = None
    if cache is not None:
        assert qkv.shape[0] == 1, "a chunk is one sequence's"
        slot, valid = ctx["slot"], ctx["valid_len"][0]
        tail0 = chunk_start(cache["tail"], slot, pos)
        S0 = chunk_start(cache["state"], slot, pos)[0]
    u, full = ssm_scan.causal_conv(qkv.astype(f32), conv_w, None, tail0)
    return jax.nn.silu(u), full, S0, valid


def _after_chunk(cache: dict, S, full, slot, valid, K: int) -> dict:
    """A slot's leaves after its chunk: the state at the chunk's end and,
    as its tail, the last K - 1 REAL inputs."""
    tail = jax.lax.dynamic_slice_in_dim(full, valid, K - 1, axis=1)
    return {"state": jax.lax.dynamic_update_index_in_dim(
                cache["state"], S, slot, 0),
            "tail": jax.lax.dynamic_update_index_in_dim(
                cache["tail"], tail[0].astype(cache["tail"].dtype), slot, 0)}


class KDA(nn.Module):
    config: LLMConfig
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache: Optional[dict] = None, pos=0,
                 state_ctx: Optional[dict] = None):
        cfg = self.config
        Bb, T, C = x.shape
        H, d, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        D = H * d
        dt_ = x.dtype
        pd = self.param_dtype
        f32 = jnp.float32
        w_qkv = self.param("W_qkv", _DENSE_INIT, (C, 3 * D), pd)
        w_a = self.param("W_a", _DENSE_INIT, (C, D), pd)
        w_bg = self.param("W_bg", _DENSE_INIT, (C, 2 * H), pd)
        conv_w = self.param("conv_w", nn.initializers.normal(stddev=0.2),
                            (K, 3 * D), pd)
        # H + H d scalars: float32 whatever the tree's dtype
        a_log = self.param("A_log", _a_log_init, (H,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (D,), f32)
        o_norm = self.param("o_norm", nn.initializers.ones, (d,), pd)
        w_o = self.param("W_o", _DENSE_INIT, (D, C), pd)

        with jax.named_scope("kda_proj"):
            # q' k' v' in the compute dtype, as the tail keeps them; the
            # decay's and the gates' inputs in float32 (g is summed over a
            # memory of thousands of tokens: a bf16 `a` is 0.2% a step)
            qkv = x @ w_qkv.astype(dt_)
            a = jnp.dot(x, w_a.astype(dt_), preferred_element_type=f32)
            bg = jnp.dot(x, w_bg.astype(dt_), preferred_element_type=f32)
        ctx = state_ctx or {}
        stepping = cache is not None and "live" in ctx
        new_cache = None
        with jax.named_scope("kda_conv"):
            u, tail, S0, valid = _convolved(qkv, conv_w, cache, ctx, pos)
        with jax.named_scope("kda_gate"):
            q, k, v = (t.reshape(Bb, T, H, d) for t in jnp.split(u, 3, -1))
            q = l2_normalise(q) * (float(d) ** -0.5)
            k = l2_normalise(k)
            rate = jnp.repeat(jnp.exp(a_log), d)
            g = (cfg.kda_lower_bound * jax.nn.sigmoid(
                rate * (a + dt_bias))).reshape(Bb, T, H, d)
            beta, gate = jnp.split(jax.nn.sigmoid(bg), 2, -1)
            if cache is not None and not stepping:
                real = (jnp.arange(T) < valid)[None, :, None]
                g = jnp.where(real[..., None], g, 0.0)
                beta = jnp.where(real, beta, 0.0)
        with jax.named_scope("attn_kda"):
            if stepping:
                o, S = delta_rule.kda_step(cache["state"], q[:, 0], k[:, 0],
                                           v[:, 0], g[:, 0], beta[:, 0],
                                           ctx["live"])
                o = o[:, None]
                new_cache = {"state": S, "tail": tail}
            elif cache is not None:
                # a name of its own for the chunk form: no kernel's name
                # tells its ops from the step's in a device trace
                with jax.named_scope("kda_chunk"):
                    o, S = delta_rule.kda_chunk(q[0], k[0], v[0], g[0],
                                                beta[0], S0)
                o = o[None]
                new_cache = _after_chunk(cache, S, tail, ctx["slot"], valid,
                                         K)
            else:
                o = jnp.stack([delta_rule.kda_chunk(
                    q[b], k[b], v[b], g[b], beta[b])[0] for b in range(Bb)])
        with jax.named_scope("kda_out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.norm_eps)
            y = (o * o_norm.astype(f32) * gate[..., None]).astype(dt_)
            y = y.reshape(Bb, T, D) @ w_o.astype(dt_)
        return y, new_cache


# ---------------------------------------------------------------------------
# 'G': the gated delta rule with a decay a head (Gated DeltaNet)
# ---------------------------------------------------------------------------

def init_gdn_cache(cfg: LLMConfig, n_slots: int, dtype) -> dict:
    """One slot's row of each: the state a VALUE head and the convolution
    tail over [q' | k' | v']."""
    H, Hk, d = cfg.gdn_heads, cfg.gdn_key_heads, cfg.gdn_head_dim
    return {"state": jnp.zeros((n_slots, *delta_rule.state_shape(H, d)),
                               jnp.float32),
            "tail": jnp.zeros((n_slots, cfg.gdn_conv - 1,
                               (2 * Hk + H) * d), dtype)}


def _gdn_a_log_init(key, shape, dtype):
    """log of a rate uniform on (1/16, 16): the published draw (0, 16) with
    its lower end kept off log 0."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0 / 16,
                                      16.0)).astype(dtype)


def _gdn_dt_bias_init(key, shape, dtype):
    """The published draw: softplus^-1 of a step log-uniform on (1e-3,
    1e-1). With `_gdn_a_log_init`'s rates a drawn layer's log decays spread
    from next to nothing to several a token; nothing bounds them."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class GatedDeltaNet(nn.Module):
    """The 'G' mixer (Gated DeltaNet, arXiv 2412.06464, as the `qwen3_next`
    family publishes it). For a normed input h (B, T, C), Hk key heads
    under H value heads (`gdn_key_heads`, `gdn_heads`), both of d =
    `gdn_head_dim` lanes, no biases, no positions:

        [q' | k' | v' | z] = h W_qkvz          C -> (2 Hk + 2 H) d
        [b | a]           = h W_ba             C -> 2 H, float32 results
        [q'' | k'' | v]   = silu(causal depthwise conv1d(. K taps, no bias))
        q_i = q''_i / |q''_i|_2 d^-1/2,  k_i = k''_i / |k''_i|_2
        value head j reads key head j // (H / Hk)     (repeat, NOT tile)
        g_j = -exp(A_log_j) softplus(a_j + dt_bias_j)   one log decay a
                                               value head, float32, UNBOUNDED
        beta_j = sigmoid(b_j)
        S' = exp(g_j) S;  S = S' + beta_j k (v_j - S'^T k)^T;  o_j = S^T q
        y = [RMSNorm_d(o_j) * w * silu(z_j)]_j W_o     (weight w, not 1 + w)

    The columns of W_qkvz and W_ba stand fused by KIND, each kind head-major
    (the published code lays them out a key head at a time: a permutation of
    columns). A slot keeps `state` float32 (H, d, d) and `tail` (K - 1,
    (2 Hk + H) d) in the compute dtype, `init_gdn_cache`; the three ways in
    are `KDA`'s (no cache | `state_ctx["live"]` | `["slot"]`, `["valid_len"]`),
    with the same rules for a chunk at position 0 and for pad rows. One
    token is `delta_rule.kda_step` with the head's decay broadcast over its
    channels, a chunk `delta_rule.gdn_chunk`: no g is clamped anywhere.

    Scopes: `gdn_proj` (W_qkvz, W_ba), `gdn_conv`, `gdn_gate` (the L2
    norms, g, beta), `attn_gdn` (the step or the chunk form; a cached
    chunk's also under `gdn_chunk`, inside it), `gdn_out` (the heads'
    RMSNorm, the silu gate a channel, W_o)."""

    config: LLMConfig
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache: Optional[dict] = None, pos=0,
                 state_ctx: Optional[dict] = None):
        cfg = self.config
        Bb, T, C = x.shape
        H, Hk, d, K = (cfg.gdn_heads, cfg.gdn_key_heads, cfg.gdn_head_dim,
                       cfg.gdn_conv)
        Dk, Dv = Hk * d, H * d
        dt_ = x.dtype
        pd = self.param_dtype
        f32 = jnp.float32
        w_qkvz = self.param("W_qkvz", _DENSE_INIT, (C, 2 * Dk + 2 * Dv), pd)
        w_ba = self.param("W_ba", _DENSE_INIT, (C, 2 * H), pd)
        conv_w = self.param("conv_w", nn.initializers.normal(stddev=0.2),
                            (K, 2 * Dk + Dv), pd)
        a_log = self.param("A_log", _gdn_a_log_init, (H,), f32)
        dt_bias = self.param("dt_bias", _gdn_dt_bias_init, (H,), f32)
        o_norm = self.param("o_norm", nn.initializers.ones, (d,), pd)
        w_o = self.param("W_o", _DENSE_INIT, (Dv, C), pd)

        with jax.named_scope("gdn_proj"):
            qkvz = x @ w_qkvz.astype(dt_)
            qkv, z = qkvz[..., :2 * Dk + Dv], qkvz[..., 2 * Dk + Dv:]
            # the decay is summed over a memory of thousands of tokens
            ba = jnp.dot(x, w_ba.astype(dt_), preferred_element_type=f32)
        ctx = state_ctx or {}
        stepping = cache is not None and "live" in ctx
        new_cache = None
        with jax.named_scope("gdn_conv"):
            u, tail, S0, valid = _convolved(qkv, conv_w, cache, ctx, pos)
        with jax.named_scope("gdn_gate"):
            q, k, v = jnp.split(u, [Dk, 2 * Dk], -1)
            q = l2_normalise(q.reshape(Bb, T, Hk, d)) * (float(d) ** -0.5)
            k = l2_normalise(k.reshape(Bb, T, Hk, d))
            # value head j reads key head j // (H / Hk)
            q, k = (jnp.repeat(t, H // Hk, axis=2) for t in (q, k))
            v = v.reshape(Bb, T, H, d)
            b, a = ba[..., :H], ba[..., H:]
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)  # (B, T, H)
            beta = jax.nn.sigmoid(b)
            if cache is not None and not stepping:
                real = (jnp.arange(T) < valid)[None, :, None]
                g = jnp.where(real, g, 0.0)
                beta = jnp.where(real, beta, 0.0)
        with jax.named_scope("attn_gdn"):
            if stepping:
                g1 = jnp.broadcast_to(g[:, 0, :, None], (Bb, H, d))
                o, S = delta_rule.kda_step(cache["state"], q[:, 0], k[:, 0],
                                           v[:, 0], g1, beta[:, 0],
                                           ctx["live"])
                o = o[:, None]
                new_cache = {"state": S, "tail": tail}
            elif cache is not None:
                with jax.named_scope("gdn_chunk"):
                    o, S = delta_rule.gdn_chunk(q[0], k[0], v[0], g[0],
                                                beta[0], S0)
                o = o[None]
                new_cache = _after_chunk(cache, S, tail, ctx["slot"], valid,
                                         K)
            else:
                o = jnp.stack([delta_rule.gdn_chunk(
                    q[i], k[i], v[i], g[i], beta[i])[0] for i in range(Bb)])
        with jax.named_scope("gdn_out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.norm_eps)
            gate = jax.nn.silu(z.astype(f32)).reshape(Bb, T, H, d)
            y = (o * o_norm.astype(f32) * gate).astype(dt_)
            y = y.reshape(Bb, T, Dv) @ w_o.astype(dt_)
        return y, new_cache
