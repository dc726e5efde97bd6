"""The gated short-convolution mixer: a layer that takes attention's place
in a patterned model (`LLMConfig.layer_pattern` 'C'; the `conv` operator of
the published LFM2 family).

    [B | C | x'] = x W_in                    C -> 3 C, no bias
    u = B * x'
    c_t = sum_k w[k] * u_{t - (K-1) + k}     depthwise, causal, K taps
                                             (`cfg.conv_len`), no bias,
                                             no activation
    out = (C * c) W_out                      C -> C

No recurrence: what a sequence carries from token to token is the
convolution's last K - 1 inputs `u`, a row a slot in the compute dtype
(`init_conv_cache`: the `conv` leaf of `models/ssm.py`'s cache and no `ssm`
leaf beside it). The convolution and its tail are `ops/ssm_scan.py`'s
`causal_conv` / `conv_step`, the three ways in `models/ssm.py`'s:

* no cache: a whole sequence from a zero tail (tests);
* `state_ctx["live"]`: one token of every slot; rows that are not live
  keep their tail;
* `state_ctx["slot"]` / `["valid_len"]`: a chunk of ONE sequence into its
  slot's row. A chunk at position 0 starts from zeros whatever the slot
  held (`ssm.chunk_start`), a later one from the slot's tail; the tail it
  leaves is the last K - 1 REAL inputs, so pad rows never reach it.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.models import ssm
from distributed_pytorch_tpu.ops import ssm_scan

_DENSE_INIT = nn.initializers.normal(stddev=0.02)


def init_conv_cache(cfg: LLMConfig, n_slots: int, dtype) -> dict:
    """One slot's row: the convolution's tail."""
    return {"conv": jnp.zeros((n_slots, cfg.conv_len - 1, cfg.n_embd),
                              dtype)}


class ShortConv(nn.Module):
    config: LLMConfig
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache: Optional[dict] = None, pos=0,
                 state_ctx: Optional[dict] = None):
        cfg = self.config
        Bb, T, C = x.shape
        K = cfg.conv_len
        dt_ = x.dtype
        pd = self.param_dtype
        w_in = self.param("in_proj", _DENSE_INIT, (C, 3 * C), pd)
        conv_w = self.param("conv_w", nn.initializers.normal(stddev=0.2),
                            (K, C), pd)
        w_out = self.param("out_proj", _DENSE_INIT, (C, C), pd)

        b, c, xp = jnp.split(x @ w_in.astype(dt_), 3, axis=-1)
        u = b * xp
        ctx = state_ctx or {}
        new_cache = None
        if cache is not None and "live" in ctx:
            assert T == 1, "the one-token form takes one token a slot"
            with jax.named_scope("conv_step"):
                y, tail = ssm_scan.conv_step(u[:, 0], conv_w, None,
                                             cache["conv"], ctx["live"])
            y = y[:, None]
            new_cache = {"conv": tail}
        else:
            tail0 = None
            if cache is not None:
                assert Bb == 1, "a chunk is one sequence's"
                slot, valid = ctx["slot"], ctx["valid_len"][0]
                tail0 = ssm.chunk_start(cache["conv"], slot, pos)
            with jax.named_scope("conv_chunk"):
                y, full = ssm_scan.causal_conv(u, conv_w, None, tail0)
            if cache is not None:
                # the tail after the chunk: the last K - 1 REAL inputs
                tail = jax.lax.dynamic_slice_in_dim(full, valid, K - 1,
                                                    axis=1)
                new_cache = {"conv": jax.lax.dynamic_update_index_in_dim(
                    cache["conv"], tail[0].astype(cache["conv"].dtype),
                    slot, 0)}
        return (c * y) @ w_out.astype(dt_), new_cache
