"""The Mamba-2 mixer: a state-space layer that takes attention's place in a
patterned model (`LLMConfig.layer_pattern` 'M').

    [z | xBC | dt] = x W_in                  C -> d_inner + conv_dim + H
                     (times `cfg.ssm_mults`, a number a segment of
                     [z | x | B | C | dt], where the configuration has them)
    xBC = silu(causal depthwise conv1d(xBC, width K, bias))
    x, B, C = split(xBC)                     H x P | G x N | G x N
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t,  y_t = h_t C_t + D x_t
    y = RMSNorm over groups of d_inner / G of (y * silu(z)), times a weight
    out = y W_out                            d_inner -> C

d_inner = heads x head size (not an expansion of C), conv_dim = d_inner +
2 G N. What a sequence carries from token to token is no block of a paged
cache but a leaf a slot: the convolution's last K - 1 inputs (compute
dtype) and the state h (float32, state-major (N, H x P): ops/ssm_scan.py),
`init_ssm_cache`. Three ways in:

* no cache: a whole sequence from a zero state (training shape, tests);
* `state_ctx["live"]`: one token of every slot, the one-token recurrence;
  rows that are not live keep their state and tail;
* `state_ctx["slot"]` / `["valid_len"]`: a chunk of ONE sequence into its
  slot's row, the chunked scan. A chunk at position 0 starts from zeros
  whatever the slot held (a reused slot is the classic fault), a later
  one from the slot's state; rows past `valid_len` are pads and advance
  neither the state (dt = 0) nor the tail.

The recurrent mixers of a patterned model, by module: this one ('M', and a
'P' block's state-space branch; recurrence in ops/ssm_scan.py), the gated
short convolution ('C', models/shortconv.py: a tail and no state) and KDA
('K', models/linear_attention.py: recurrence in ops/delta_rule.py).
`chunk_start` below is the one rule all three start a chunk by.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.ops import ssm_scan
from distributed_pytorch_tpu.ops.mup import segment_times

_DENSE_INIT = nn.initializers.normal(stddev=0.02)


def ssm_dims(cfg: LLMConfig) -> tuple:
    """(d_inner, conv_dim, in-projection width)."""
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_inner, conv_dim, d_inner + conv_dim + cfg.ssm_heads


def init_ssm_cache(cfg: LLMConfig, n_slots: int, dtype) -> dict:
    """One slot's row of each: the convolution tail and the state."""
    _, conv_dim, _ = ssm_dims(cfg)
    return {"conv": jnp.zeros((n_slots, cfg.ssm_conv - 1, conv_dim), dtype),
            "ssm": jnp.zeros((n_slots, *ssm_scan.state_shape(
                cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)),
                jnp.float32)}


def chunk_start(leaf: jnp.ndarray, slot, pos) -> jnp.ndarray:
    """What a chunk at position `pos` of the sequence in `slot` starts
    from: zeros at position 0, whatever the slot's last occupant left
    there, else the slot's row, (1, ...)."""
    row = jax.lax.dynamic_index_in_dim(leaf, slot, 0)
    return jnp.where(jnp.asarray(pos, jnp.int32) == 0,
                     jnp.zeros_like(row), row)


def _dt_bias_init(key, shape, dtype):
    """softplus^-1 of a step size log-uniform on [1e-3, 1e-1] (the
    published `time_step_min` / `time_step_max`)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def gated_group_norm(y, z, weight, n_groups: int, eps: float):
    """RMSNorm over `n_groups` equal groups of the last axis of
    y * silu(z), in float32, times `weight`."""
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    shape = g.shape
    g = g.reshape(*shape[:-1], n_groups, shape[-1] // n_groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape) * weight.astype(jnp.float32)


class Mamba2(nn.Module):
    config: LLMConfig
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache: Optional[dict] = None, pos=0,
                 state_ctx: Optional[dict] = None):
        cfg = self.config
        Bb, T, C = x.shape
        H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
        d_inner, conv_dim, d_in = ssm_dims(cfg)
        dt_ = x.dtype
        pd = self.param_dtype
        w_in = self.param("in_proj", _DENSE_INIT, (C, d_in), pd)
        conv_w = self.param("conv_w", nn.initializers.normal(stddev=0.2),
                            (cfg.ssm_conv, conv_dim), pd)
        conv_b = self.param("conv_b", nn.initializers.zeros, (conv_dim,), pd)
        # 3 x H scalars: float32 whatever the tree's dtype
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        norm_w = self.param("norm_w", nn.initializers.ones, (d_inner,), pd)
        w_out = self.param("out_proj", _DENSE_INIT, (d_inner, C), pd)

        zxd = segment_times(x @ w_in.astype(dt_),
                            (d_inner, d_inner, G * N, G * N, H),
                            cfg.ssm_mults)
        z, xbc, dt_raw = jnp.split(zxd, [d_inner, d_inner + conv_dim], axis=-1)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
        A = -jnp.exp(a_log)
        ctx = state_ctx or {}
        new_cache = None

        def split_xbc(u):
            xs, Bm, Cm = jnp.split(u, [d_inner, d_inner + G * N], axis=-1)
            lead = u.shape[:-1]
            return (xs.reshape(*lead, H, P), Bm.reshape(*lead, G, N),
                    Cm.reshape(*lead, G, N))

        if cache is not None and "live" in ctx:
            assert T == 1, "the one-token recurrence takes one token a slot"
            live = ctx["live"]
            with jax.named_scope("ssm_conv"):
                u, tail = ssm_scan.conv_step(xbc[:, 0], conv_w, conv_b,
                                             cache["conv"], live)
                u = jax.nn.silu(u)
            with jax.named_scope("ssm_step"):
                xs, Bm, Cm = split_xbc(u)
                y, h = ssm_scan.ssm_step(cache["ssm"], xs, dt[:, 0], A, Bm,
                                         Cm, d_skip, live)
            y = y[:, None]
            new_cache = {"conv": tail, "ssm": h}
        else:
            tail0 = h0 = None
            if cache is not None:
                assert Bb == 1, "a chunk is one sequence's"
                slot, valid = ctx["slot"], ctx["valid_len"][0]
                tail0 = chunk_start(cache["conv"], slot, pos)
                h0 = chunk_start(cache["ssm"], slot, pos)
                dt = jnp.where((jnp.arange(T) < valid)[None, :, None],
                               dt, 0.0)
            with jax.named_scope("ssm_conv"):
                u, full = ssm_scan.causal_conv(xbc, conv_w, conv_b, tail0)
                u = jax.nn.silu(u)
            with jax.named_scope("ssm_scan"):
                xs, Bm, Cm = split_xbc(u)
                y, h = ssm_scan.ssd_chunked(xs, dt, A, Bm, Cm, d_skip, h0,
                                            chunk=cfg.ssm_chunk)
            if cache is not None:
                # the tail after the chunk: the last K - 1 REAL inputs
                tail = jax.lax.dynamic_slice_in_dim(
                    full, valid, cfg.ssm_conv - 1, axis=1)
                new_cache = {
                    "conv": jax.lax.dynamic_update_index_in_dim(
                        cache["conv"], tail[0].astype(cache["conv"].dtype),
                        slot, 0),
                    "ssm": jax.lax.dynamic_update_index_in_dim(
                        cache["ssm"], h[0], slot, 0)}
        y = gated_group_norm(y.reshape(Bb, T, d_inner), z, norm_w, G,
                             cfg.norm_eps).astype(dt_)
        return y @ w_out.astype(dt_), new_cache
