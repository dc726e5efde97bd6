"""Feed-forward layers: dense MLP (13 activations incl. swiglu) and
DeepSeekMoE with aux-loss-free balancing.

Reference parity map:
* `MLP` — reference single-gpu/model.py:365-398: bias-free up/down
  projections; swiglu as ONE fused 2*up_dim projection split in half
  (reference :371-373,389-391); otherwise an activation map of 12 choices.
  Divergence: the reference's 'glu' entry is shape-inconsistent (nn.GLU
  halves the feature dim, so its c_proj would reject the result); here
  'glu' is implemented like swiglu but with a sigmoid gate, which is what
  GLU means — documented rather than reproduced as a crash.
* `MoE` — reference model.py:409-506 (DeepSeekMoE, arXiv:2412.19437 flavor):
  first n_shared experts always-on bypassing the router; top-k routing over
  the remaining n_routed experts (n_act INCLUDES shared, reference :425);
  two balancing modes: (a) aux-loss-free — a non-learned bias added to
  router logits for top-k *selection only*, gates from un-biased logits
  (reference :451-458), bias nudged toward uniform load at speed gamma
  during training (reference :466-470), plus complementary aux loss
  alpha * n_routed * sum(pi*fi) (reference :472-474); (b) classic aux loss
  coeff * n_routed * sum(pi*fi) (reference :476-487).

TPU-first design (SURVEY §7 hard part (a)):
* Expert weights are STACKED with a leading (n_exp, ...) axis — one pytree
  leaf per projection, shardable over an 'expert' mesh axis for expert
  parallelism (capability absent from the reference, whose dispatch is a
  data-dependent Python loop over experts, model.py:489-506).
* Dispatch is static-shape, three modes (LLMConfig.moe_impl):
  - 'dense' evaluates every routed expert on every token and combines with
    a (tokens, n_routed) gate matrix that is zero outside the top-k —
    bitwise-equal semantics to the reference loop (no capacity limit, no
    token dropping) at n_routed/k extra FLOPs; good for small expert
    counts and as the semantics oracle.
  - 'scatter' is the capacity-bounded sort-based dispatch: assignments are
    stable-sorted by expert, each expert takes its first
    `capacity = ceil(capacity_factor * N*k/E)` tokens into an (E, cap, C)
    buffer (later tokens are DROPPED, GShard-style position priority —
    the dropped fraction is surfaced as the `dropped_frac` moe_state
    metric / `moe_dropped_frac` train metric), expert FFNs run batched
    over the leading expert axis, and results scatter-add back weighted
    by their gates. O(active) FLOPs like the reference's Python loop
    (model.py:489-506) but static-shape for XLA; the (E, cap, C) buffers
    carry a 'expert'-axis sharding constraint so under the ep recipe
    GSPMD turns dispatch/return into all-to-alls over the expert mesh
    axis.
  - 'grouped' is the dropless Pallas ragged grouped-matmul dispatch
    (ops/grouped_matmul.py, MegaBlocks arXiv:2211.15841 flavor): tokens
    stay packed in one expert-sorted buffer (no capacity padding, zero
    dropped assignments), every expert's x_e @ W_e streams weight tiles
    per group, the shared experts ride the same kernel as always-on
    groups, and the combine gates are applied at the kernel's output
    write. Falls back to 'dense' — identical semantics, more FLOPs —
    where the kernel can't run (pipeline-vmapped blocks, live 'model' or
    'seq' mesh axes, non-lane-aligned widths; see grouped_usable).
* The aux-free bias is cross-batch mutable state; it lives in the 'moe_state'
  variable collection, carried in the train state. Under pjit the batch is
  global, so load statistics (and hence the bias update) are computed over
  the GLOBAL batch — unlike the reference, where each DDP rank's bias
  drifts independently (no sync anywhere in kaggle-zero*.py). Documented
  intentional improvement.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.ops.activations import activation, is_gated
from distributed_pytorch_tpu.ops.mup import times

_DENSE_INIT = nn.initializers.normal(stddev=0.02)


def mlp_apply(x: jnp.ndarray, w_fc: jnp.ndarray, w_proj: jnp.ndarray,
              non_linearity: str, *, overlap: bool = False,
              qnames: tuple | None = None, gate_mult: float = 1.0,
              down_mult: float = 1.0) -> jnp.ndarray:
    """Apply one MLP given its kernels; shared by dense MLP and experts.

    Gated variants ('swiglu'/'glu'): w_fc is (C, 2*up_dim), split in half,
    h = act(x1) * x2 (reference model.py:389-391). Others: (C, up_dim).

    `overlap=True` (dense MLP only — expert kernels are 3D/vmapped) offers
    both matmuls to the collective-matmul dispatcher
    (ops/collective_matmul.py): under an active OVERLAP=on ZeRO-3 step the
    param all-gather runs as a ppermute ring fused with the matmul;
    otherwise the dispatcher declines and the plain `@` below is
    bit-identical to the pre-overlap code path.

    `qnames=(fc_path, proj_path)` (dense MLP only) offers both matmuls to
    the weight-only-int8 store first (ops/quant.py): under an engine
    decode step with quantized params they read int8 codes +
    per-output-channel scales (applied before the gating split — exact,
    the scale is per column of the fused fc output); elsewhere the lookup
    misses and nothing changes.

    `gate_mult` multiplies a gated variant's x1 inside its activation and
    `down_mult` the result (`LLMConfig.mlp_gate_mult`, `mlp_down_mult`;
    ops/mup.py); at 1 they add no op.
    """
    h = None
    if qnames is not None:
        from distributed_pytorch_tpu.ops.quant import maybe_quantized_matmul
        h = maybe_quantized_matmul(x, qnames[0])
    if h is None and overlap:
        from distributed_pytorch_tpu.ops.collective_matmul import (
            maybe_overlap_matmul)
        h = maybe_overlap_matmul(x, w_fc, names=("c_fc",))
    if h is None:
        h = x @ w_fc
    if is_gated(non_linearity):
        x1, x2 = jnp.split(h, 2, axis=-1)
        x1 = times(x1, gate_mult)
        gate = jax.nn.silu(x1) if non_linearity.lower() == "swiglu" \
            else jax.nn.sigmoid(x1)
        h = gate * x2
    else:
        h = activation(non_linearity)(h)
    y = None
    if qnames is not None:
        from distributed_pytorch_tpu.ops.quant import maybe_quantized_matmul
        y = maybe_quantized_matmul(h, qnames[1])
    if y is None and overlap:
        from distributed_pytorch_tpu.ops.collective_matmul import (
            maybe_overlap_matmul)
        y = maybe_overlap_matmul(h, w_proj, names=("c_proj",))
    if y is None:
        y = h @ w_proj
    return times(y, down_mult)


class MLP(nn.Module):
    """Dense feed-forward block (reference model.py:365-398). An 'F' layer
    of a patterned model is this block at a width of its own (`up_dim`,
    0 = `cfg.up_dim`: `cfg.dense_up_dim` beside experts of `cfg.up_dim`)
    with its leaves in `param_dtype`; gated, `c_fc` is [a | b] by columns
    and the block `m_down * (silu(m_gate * a) * b) c_proj` (the two
    multipliers 1 but for a configuration that publishes them)."""

    config: LLMConfig
    up_dim: int = 0
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.config
        C, up = cfg.n_embd, self.up_dim or cfg.up_dim
        fc_out = 2 * up if is_gated(cfg.non_linearity) else up
        w_fc = self.param("c_fc", _DENSE_INIT, (C, fc_out), self.param_dtype)
        w_proj = self.param("c_proj", _DENSE_INIT, (up, C), self.param_dtype)
        y = mlp_apply(x, w_fc.astype(x.dtype), w_proj.astype(x.dtype),
                      cfg.non_linearity, overlap=True,
                      qnames=((*self.path, "c_fc"), (*self.path, "c_proj")),
                      gate_mult=cfg.mlp_gate_mult,
                      down_mult=cfg.mlp_down_mult)
        return nn.Dropout(cfg.dropout, deterministic=deterministic)(y)


def _expert_constraint(t: jnp.ndarray) -> jnp.ndarray:
    """Pin a (n_experts, capacity, ...) dispatch buffer to the mesh: expert
    axis over 'expert' (GSPMD lowers dispatch/return as all-to-alls over
    ICI instead of gathering all tokens onto every expert shard) and the
    capacity axis over 'data'. The latter is what keeps per-device dispatch
    memory independent of dp size (round-3 VERDICT #4): global capacity
    grows with the global batch, but each device holds only its
    cap/dp slice — without it, a dp x ep mesh materializes
    (E/ep, cf*N_global*k/E, C) per device."""
    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if mesh is None:
        return t
    axes: list = [None] * t.ndim
    if "expert" in mesh.axis_names and mesh.shape["expert"] > 1:
        axes[0] = "expert"
    if t.ndim >= 2 and "data" in mesh.axis_names \
            and mesh.shape["data"] > 1 and t.shape[1] % mesh.shape["data"] == 0:
        axes[1] = "data"
    if all(a is None for a in axes):
        return t
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, P(*axes)))


def scatter_dispatch(x_flat: jnp.ndarray, topk_idx: jnp.ndarray,
                     topk_gates: jnp.ndarray, experts_fc: jnp.ndarray,
                     experts_proj: jnp.ndarray, *, non_linearity: str,
                     capacity: int) -> jnp.ndarray:
    """Capacity-bounded sort-based routed-expert dispatch.

    x_flat (N, C); topk_idx/topk_gates (N, k) over E routed experts whose
    stacked kernels are experts_fc (E, C, fc_out) / experts_proj (E, up, C).
    Returns (N, C). Tokens beyond an expert's `capacity` are dropped
    (earlier tokens win — GShard position priority); with capacity >=
    max expert load this is numerically the reference loop
    (single-gpu/model.py:489-506) up to summation order.
    """
    N, k = topk_idx.shape
    E = experts_fc.shape[0]
    dt = x_flat.dtype

    flat_e = topk_idx.reshape(-1)                          # (N*k,)
    flat_g = topk_gates.reshape(-1).astype(jnp.float32)
    flat_t = jnp.arange(N * k, dtype=jnp.int32) // k       # owning token

    order = jnp.argsort(flat_e, stable=True)               # group by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                   # segment offsets
    pos = jnp.arange(N * k, dtype=jnp.int32) - starts[se]  # rank within expert
    keep = pos < capacity

    # slot in the flattened (E*capacity) buffer; dropped assignments all
    # land in one overflow cell that is sliced away
    slot = jnp.where(keep, se * capacity + pos, E * capacity)
    buf_tok = jnp.zeros((E * capacity + 1,), jnp.int32).at[slot].set(st)
    buf_gate = jnp.zeros((E * capacity + 1,), jnp.float32).at[slot].set(sg)
    tok_grid = buf_tok[:-1].reshape(E, capacity)
    gate_grid = buf_gate[:-1].reshape(E, capacity)
    # unfilled slots keep token 0 with gate 0: computed then zeroed — wasted
    # lanes, never wrong

    xg = _expert_constraint(x_flat[tok_grid])              # (E, cap, C)

    def one(wf, wp, xe):
        return mlp_apply(xe, wf.astype(dt), wp.astype(dt), non_linearity)

    y = jax.vmap(one)(experts_fc, experts_proj, xg)        # (E, cap, C)
    y = _expert_constraint(y * gate_grid[..., None].astype(dt))

    return jnp.zeros_like(x_flat).at[tok_grid.reshape(-1)].add(
        y.reshape(E * capacity, -1))


class MoE(nn.Module):
    """DeepSeekMoE layer (reference model.py:409-506). Returns (y, aux_loss).

    Expert parameters are stacked: experts_fc (n_exp, C, fc_out) and
    experts_proj (n_exp, up, C); expert e of the reference's ModuleList is
    slice [e]. First n_shared experts are shared (always active)."""

    config: LLMConfig

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True, stats_weight=None):
        """`stats_weight` gates the cross-batch statistics (aux loss and the
        aux-free bias update) without touching the token outputs: the
        pipeline schedule (models/pipeline.py) passes 0.0 for buffer slots
        holding no real microbatch so their deterministic zero-token routing
        can't pollute the load balance, and 1/M for valid slots so the
        per-optimizer-step bias movement and aux total are microbatch-
        count-invariant. None/1.0 elsewhere."""
        cfg = self.config
        B, T, C = x.shape
        sw = 1.0 if stats_weight is None else stats_weight
        up = cfg.up_dim
        n_exp, n_shared = cfg.n_exp, cfg.n_shared
        n_routed, k = cfg.n_routed, cfg.n_act_routed
        fc_out = 2 * up if is_gated(cfg.non_linearity) else up
        dt = x.dtype

        experts_fc = self.param("experts_fc", _DENSE_INIT,
                                (n_exp, C, fc_out), jnp.float32)
        experts_proj = self.param("experts_proj", _DENSE_INIT,
                                  (n_exp, up, C), jnp.float32)
        gate_kernel = self.param("gate", _DENSE_INIT, (C, n_routed), jnp.float32)

        x_flat = x.reshape(-1, C)  # (N, C)
        n_tokens = x_flat.shape[0]

        use_grouped = False
        if cfg.moe_impl == "grouped":
            from distributed_pytorch_tpu.ops.grouped_matmul import \
                grouped_usable
            use_grouped = grouped_usable(cfg, B, dt)

        # ---------------- shared expert path (reference :440-445) ----------
        def one_expert(wf, wp):
            return mlp_apply(x_flat, wf.astype(dt), wp.astype(dt),
                             cfg.non_linearity)

        if n_shared > 0 and not use_grouped:
            shared_out = jax.vmap(one_expert)(
                experts_fc[:n_shared], experts_proj[:n_shared]).sum(axis=0)
        else:
            # grouped: shared experts ride the grouped kernel as always-on
            # groups (one group per shared expert, every token, gate 1.0)
            shared_out = jnp.zeros_like(x_flat)

        # ---------------- router (fp32 for numerics) -----------------------
        router_logits = (x_flat.astype(jnp.float32)
                         @ gate_kernel.astype(jnp.float32))  # (N, n_routed)

        if cfg.aux_free:
            bias = self.variable(
                "moe_state", "expert_bias",
                lambda: jnp.zeros((n_routed,), jnp.float32))
            biased = router_logits + bias.value
            _, topk_idx = jax.lax.top_k(biased, k)
            # gates from UN-biased logits of the selected experts (ref :457-458)
            topk_orig = jnp.take_along_axis(router_logits, topk_idx, axis=1)
            topk_gates = jax.nn.softmax(topk_orig, axis=1)
            one_hot = jax.nn.one_hot(topk_idx, n_routed, dtype=jnp.float32)
            fi = jax.lax.stop_gradient(one_hot.sum(axis=(0, 1)) / n_tokens)
            if not deterministic and self.is_mutable_collection("moe_state"):
                # online bias update toward uniform load (reference :466-470);
                # fi here is over the GLOBAL batch under pjit. `sw` zeroes
                # the step for pipeline bubble slots.
                delta = 1.0 / n_routed - fi
                bias.value = bias.value + cfg.gamma * delta * sw
            pi = jax.nn.softmax(router_logits, axis=1).mean(axis=0)
            aux_loss = cfg.alpha * n_routed * jnp.sum(pi * fi)
        else:
            _, topk_idx = jax.lax.top_k(router_logits, k)
            topk_vals = jnp.take_along_axis(router_logits, topk_idx, axis=1)
            topk_gates = jax.nn.softmax(topk_vals, axis=1)
            one_hot = jax.nn.one_hot(topk_idx, n_routed, dtype=jnp.float32)
            fi = jax.lax.stop_gradient(one_hot.sum(axis=(0, 1)) / n_tokens)
            pi = jax.nn.softmax(router_logits, axis=1).mean(axis=0)
            aux_loss = cfg.coeff * n_routed * jnp.sum(pi * fi)

        # ---------------- routed dispatch (see module docstring) -----------
        dropped_frac = jnp.float32(0.0)
        if cfg.moe_impl == "scatter":
            capacity = max(k, math.ceil(
                cfg.capacity_factor * n_tokens * k / n_routed))
            # round up so the buffers' capacity axis is divisible by the
            # 'data' mesh axis and _expert_constraint can shard it (extra
            # slots only ever reduce drops, never change kept tokens)
            from distributed_pytorch_tpu.parallel import context
            mesh = context.get_mesh()
            if mesh is not None and "data" in mesh.axis_names:
                dp = mesh.shape["data"]
                capacity = -(-capacity // dp) * dp
            routed_out = scatter_dispatch(
                x_flat, topk_idx, topk_gates,
                experts_fc[n_shared:], experts_proj[n_shared:],
                non_linearity=cfg.non_linearity, capacity=capacity)
            # assignments past an expert's capacity are silently dropped
            # (GShard position priority) — surface the fraction so the
            # drop is visible in train logs / bench JSON. 'grouped' and
            # 'dense' are dropless by construction and report 0.
            load = jnp.zeros((n_routed,), jnp.int32).at[
                topk_idx.reshape(-1)].add(1)
            dropped_frac = (jnp.maximum(load - capacity, 0).sum()
                            / jnp.float32(n_tokens * k))
        elif use_grouped:
            from distributed_pytorch_tpu.ops.grouped_matmul import \
                grouped_dispatch
            # includes the shared experts as always-on groups (shared_out
            # above is zeros on this path)
            routed_out = grouped_dispatch(
                x_flat, topk_idx, topk_gates, experts_fc, experts_proj,
                non_linearity=cfg.non_linearity, n_shared=n_shared)
        else:
            # combine[t, e] = gate weight of expert e for token t (0 if
            # unrouted)
            combine = (one_hot * topk_gates[..., None]).sum(axis=1)  # (N, E)
            all_routed = jax.vmap(one_expert)(
                experts_fc[n_shared:], experts_proj[n_shared:])  # (E, N, C)
            routed_out = jnp.einsum("enc,ne->nc", all_routed,
                                    combine.astype(dt))

        # cross-batch metric state, carried like the aux-free bias; only
        # real microbatches write (sw=0 pipeline bubble slots hold zero
        # tokens whose deterministic routing would fake a drop rate)
        drop_var = self.variable("moe_state", "dropped_frac",
                                 lambda: jnp.float32(0.0))
        if not deterministic and self.is_mutable_collection("moe_state"):
            sw_arr = jnp.asarray(sw, jnp.float32)
            drop_var.value = jnp.where(sw_arr > 0, dropped_frac,
                                       drop_var.value)

        y = (shared_out + routed_out).reshape(B, T, C)
        return y, aux_loss.astype(jnp.float32) * sw


def limit_to_groups(biased: jnp.ndarray, n_group: int,
                    topk_group: int) -> jnp.ndarray:
    """The group limit of the `deepseek_v3` router on s + b (N, E): the
    experts are `n_group` groups of E / n_group consecutive ids, a group
    scores the sum of its two largest s + b (a value that stands twice
    counts twice), the `topk_group` best groups are kept (of two groups
    with equal scores the one with the lower id ranks first) and every
    s + b outside them becomes -inf, so that the top k that follows lies
    inside the kept groups.

    By maxima and comparisons alone, a few fused reductions: a `top_k`
    here is a whole sort of each group's lanes on the chip, ten times the
    time (5.3% of the Ling cell's device time: ledger, PR 67)."""
    N, E = biased.shape
    by_group = biased.reshape(N, n_group, E // n_group)
    largest = jnp.max(by_group, axis=-1)
    # the second largest: the maximum with ONE occurrence of the largest out
    first = jnp.argmax(by_group, axis=-1)[..., None]
    second = jnp.max(jnp.where(jnp.arange(E // n_group) == first, -jnp.inf,
                               by_group), axis=-1)
    score = largest + second                                 # (N, n_group)
    # a group's rank is the number of groups ahead of it
    g = jnp.arange(n_group)
    mine, other = score[:, :, None], score[:, None, :]
    ahead = (other > mine) | ((other == mine) & (g[None, :] < g[:, None]))
    keep = jnp.sum(ahead, axis=-1) < topk_group
    return jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(N, E)


def route_sigmoid(scores_in: jnp.ndarray, gate: jnp.ndarray,
                  bias: jnp.ndarray, k: int, scale: float,
                  n_group: int = 1, topk_group: int = 1):
    """The router of the 'E' layers. s = sigmoid(x W_g) in float32 over
    every routed expert; the top k of s + b are chosen (the correction
    bias moves the SELECTION only); their weights are the unbiased s of
    the chosen, divided by their sum, times `scale`. With `n_group` > 1
    the choice is group-limited first (`limit_to_groups`: groups of
    consecutive ids, a group's score the sum of its two largest s + b,
    the `topk_group` best groups kept, of equal scores the lower group
    first, the rest masked before the top k, selected without a sort);
    `n_group` 1 is the path as it was, not an op more. Returns (ids
    (N, k), weights (N, k) float32)."""
    # float32 in earnest: a TPU rounds a float32 product's operands to
    # bfloat16 unless told otherwise, and the selection is discontinuous
    s = jax.nn.sigmoid(jnp.dot(scores_in.astype(jnp.float32),
                               gate.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    biased = s + bias.astype(jnp.float32)
    if n_group > 1:
        with jax.named_scope("route_groups"):
            biased = limit_to_groups(biased, n_group, topk_group)
    _, idx = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * scale


def route_softmax_topk(scores_in: jnp.ndarray, gate: jnp.ndarray, k: int):
    """The other router of the 'E' layers (`cfg.router` 'softmax_topk'):
    l = x W_r in float32 over every routed expert; the top k by LOGIT; their
    weights are the softmax over THOSE k logits. No bias, no scale. Returns
    (ids (N, k), weights (N, k) float32, each row summing to one)."""
    logits = jnp.dot(scores_in.astype(jnp.float32), gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, k)
    return idx, jax.nn.softmax(top, axis=-1)


class RoutedExperts(nn.Module):
    """An 'E' layer of a patterned model: routed experts of which this
    chip holds a share, plus one shared expert of another width that every
    token takes (none, and no leaves or work for one, where `cfg.n_shared`
    is 0; with `cfg.shared_gate` its output times sigmoid(h w_sg), leaf
    `shared_gate` (C, 1), inside `moe_shared`). Router and expert kind are
    independent choices: `cfg.router` picks the router (`route_sigmoid`,
    with its `gate_bias` leaf, or `route_softmax_topk`, without); a gated
    `cfg.non_linearity` ('swiglu': silu(a) * b) makes both kinds of expert
    gated, their up matrices 2 x the width, [a | b].

    The router is as wide as the model's routed experts (`cfg.n_routed`)
    and picks `cfg.n_act_routed` of them; `cfg.experts_held` = (first,
    count) says which of them live here (`experts_up` / `experts_down`
    hold `count`). A token's result is the part its held experts give:
    what the absent ones would add is left out, here as in a deployment
    before the exchange that adds the shares up. The routed part runs the
    grouped kernels of ops/grouped_matmul.py (`held_experts_ffn`); the
    shared expert is two plain matmuls (its width differs, so it cannot
    ride the grouped kernel as a group).

    `x` may be a list of row sets (and `row_mask` the list of their masks:
    a fused step's chunk and its decode tokens): the held experts' kernels
    then run ONCE over all the rows (`held_experts_ffn(cuts=)`), which
    reads each hit expert's matrices once, and the result comes back a set
    each. A decode row has to read the same, bit for bit, whether or not a
    chunk rides beside it (greedy streams part at the first near-tie
    otherwise). The routed part does by construction: a token gathers its
    own k rows of the kernels' result and adds them in the router's order
    by written-out float32 adds, and the kernels are bitwise blind to the
    rows beside a row, to their number and to the tile (my chip runs, PR
    37). The router and the shared expert are XLA matmuls, which the
    compiler rounds and associates by the shapes it finds (summed over all
    rows at once, 11% of a decode row's bf16 elements moved, PR 37): they
    stay a call a set, in the shapes a set has alone, and the shared
    expert's output is added to a set's routed sum by one float32 add.

    With `row_mask` (N,) only the rows that are real are sent to routed
    experts (the others get the shared expert's part alone), and the layer
    also returns what the routing did for them: tokens a held expert,
    assignments to absent experts and, for the softmax router (whose
    weights are not renormalised over the held), the sum of the weights that
    fell on held experts; and the tiles the expert kernels ran (`stats`).
    """

    config: LLMConfig
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, row_mask=None):
        from distributed_pytorch_tpu.ops.grouped_matmul import (
            _apply_activation, held_experts_ffn)
        cfg = self.config
        listed = isinstance(x, (list, tuple))
        xs, masks = (list(x), list(row_mask)) if listed else ([x], [row_mask])
        many = len(xs) > 1
        C = xs[0].shape[-1]
        dt = xs[0].dtype
        pd = self.param_dtype
        F = cfg.up_dim
        Fs = cfg.shared_up_dim or F
        first, n_held = cfg.experts_held or (0, cfg.n_routed)
        nl = cfg.non_linearity.lower()
        fan = 2 if is_gated(nl) else 1
        sigmoid = cfg.router == "sigmoid"
        gate = self.param("gate", _DENSE_INIT, (C, cfg.n_routed), pd)
        if sigmoid:
            # drawn, not zeros: a trained model's correction bias moves the
            # selection, and a zero one would leave that path untested
            bias = self.param("gate_bias",
                              nn.initializers.normal(stddev=0.1),
                              (cfg.n_routed,), jnp.float32)
        # (held, fan x F, C), out by in: ops/grouped_matmul.py says why
        w_up = self.param("experts_up", _DENSE_INIT, (n_held, fan * F, C),
                          pd)
        w_down = self.param("experts_down", _DENSE_INIT, (n_held, F, C), pd)
        if cfg.n_shared:
            s_up = self.param("shared_up", _DENSE_INIT, (C, fan * Fs), pd)
            s_down = self.param("shared_down", _DENSE_INIT, (Fs, C), pd)
            if cfg.shared_gate:
                s_gate = self.param("shared_gate", _DENSE_INIT, (C, 1), pd)

        flats = [x.reshape(-1, C) for x in xs]
        with jax.named_scope("moe_route"):
            routes = [route_sigmoid(x_flat, gate, bias, cfg.n_act_routed,
                                    cfg.routed_scale, cfg.n_group,
                                    cfg.topk_group) if sigmoid else
                      route_softmax_topk(x_flat, gate, cfg.n_act_routed)
                      for x_flat in flats]
        if many:
            x_flat = jnp.concatenate(flats)
            idx = jnp.concatenate([i for i, _ in routes])
            w = jnp.concatenate([g for _, g in routes])
            row_mask = jnp.concatenate(masks)
        else:
            x_flat, (idx, w), row_mask = flats[0], routes[0], masks[0]
        # rows that are not real (a chunk's pads, dead slots) are routed
        # nowhere: identical garbage rows all pick the same six experts and
        # would cost those experts tile after tile of weight reads (128 pad
        # rows: ~2 ms of a 38 ms chunk-carrying step, more or less by the
        # seed's luck in which of the six are held; my chip run, PR 33)
        sent = idx if row_mask is None else \
            jnp.where(row_mask[:, None], idx, -1)
        tiles = None                  # the dense path runs no tiles
        sizes = [f.shape[0] for f in flats]
        with jax.named_scope("moe_experts"):
            if nl in ("relu2", "swiglu"):
                routed, tiles = held_experts_ffn(
                    x_flat, sent, w, w_up, w_down, first=first,
                    n_routed=cfg.n_routed, gated=nl == "swiglu",
                    cuts=tuple(sizes) if many else None)
            else:
                local = sent - first
                comb = (jax.nn.one_hot(local, n_held, dtype=jnp.float32)
                        * w[..., None]).sum(axis=1)              # (N, held)
                h = _apply_activation(
                    jnp.einsum("nc,efc->enf", x_flat, w_up.astype(dt)), nl)
                routed = jnp.einsum("enf,efc,ne->nc", h, w_down.astype(dt),
                                    comb.astype(dt)).astype(jnp.float32)
                if many:
                    routed = jnp.split(routed, np.cumsum(sizes)[:-1])
        routed = routed if many else [routed]
        shared = [None] * len(flats)
        if cfg.n_shared:
            with jax.named_scope("moe_shared"):
                shared = [_apply_activation(f @ s_up.astype(dt), nl)
                          @ s_down.astype(dt) for f in flats]
                if cfg.shared_gate:
                    # one scalar a token, its sigmoid in float32
                    shared = [(sh.astype(jnp.float32) * jax.nn.sigmoid(
                        jnp.dot(f, s_gate.astype(dt),
                                preferred_element_type=jnp.float32))
                               ).astype(dt) for sh, f in zip(shared, flats)]
        # under the combine's name: one float32 add a row set after the
        # token-side sum of `held_experts_ffn`, which the compiler may
        # fuse into that sum's last op (none where no expert is shared)
        with jax.named_scope("moe_combine"):
            ys = [(r if sh is None else r + sh.astype(jnp.float32)
                   ).astype(dt).reshape(x.shape)
                  for r, sh, x in zip(routed, shared, xs)]
        stats = None
        if row_mask is not None:
            local = idx - first
            held = (local >= 0) & (local < n_held) & row_mask[:, None]
            # a one-hot's column sums, as the packing counts them
            # (ops/grouped_matmul.py held_packing): the chip walks a
            # scatter-add index by index
            tokens = jnp.sum(
                jnp.where(held, local, -1).reshape(-1, 1)
                == np.arange(n_held, dtype=np.int32), axis=0,
                dtype=jnp.int32)
            stats = {"tokens": tokens[None],
                     "absent": (jnp.sum(row_mask) * idx.shape[1]
                                - jnp.sum(tokens)).astype(jnp.int32)[None]}
            if not sigmoid:
                # a leaf of this router alone: `route_sigmoid` renormalises
                # over the chosen, so there is no share to count
                stats["held_gate"] = jnp.sum(jnp.where(held, w, 0.0))[None]
            if tiles is not None:
                stats["tiles"] = tiles
        return (ys if listed else ys[0]), stats
