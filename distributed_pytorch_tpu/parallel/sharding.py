"""PartitionSpec tables — the heart of the recipe system.

Each reference entry point maps to a rule set over (param pytree, optimizer
state, gradient accumulator, batch):

| recipe  | params      | opt state (m/v) | grad accum | reference analogue |
|---------|-------------|-----------------|------------|--------------------|
| single  | replicated  | replicated      | replicated | single-gpu/train.py |
| dp      | replicated  | replicated      | replicated | DDP (ddp/train.py:284) |
| zero1   | replicated  | sharded('data') | replicated | ZeroRedundancyOptimizer (kaggle-zero1.py:1071-1078) |
| zero2   | replicated  | sharded('data') | sharded    | kaggle-zero2.py:1062 (bucket-view approx; ours is true reduce-scatter ZeRO-2) |
| fsdp    | sharded('data') | sharded     | sharded    | FSDP FULL_SHARD (kaggle-fsdp.py:1076-1086) |
| tp      | head/ffn dims over 'model' | like params | like params | absent (README.md:7 goal) |
| fsdp_tp | 'model' + leftover over 'data' | like params | like params | absent |
| ep      | experts over 'expert' (+leftover 'data') | like params | like params | absent |
| sp      | like fsdp; activations sequence-sharded | sharded | sharded | absent |

With these specs alone, GSPMD derives every collective the reference issues
by hand or via wrappers: DDP's bucketed all-reduce (grad psum over 'data'),
ZeRO-1's post-step param broadcast (all-gather of updated shards), FSDP's
per-layer param all-gather + grad reduce-scatter. `find_unused_parameters`
(ddp/train.py:284) and manual `require_backward_grad_sync` suppression
(ddp/train.py:315) have no analogue — unrouted experts simply get zero
gradients, and accumulation is a scan inside one jit step.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Recipe = str  # one of config.PARALLELISM_RECIPES

# Recipes whose *parameters* are sharded over 'data' (ZeRO-3 family).
_PARAM_SHARDED = ("fsdp", "fsdp_tp", "sp")
# Recipes whose *optimizer state* is sharded over 'data' (ZeRO-1 and up).
_OPT_SHARDED = ("zero1", "zero2") + _PARAM_SHARDED
# Recipes whose *gradient accumulator* is sharded over 'data' (ZeRO-2 and up).
_GRAD_SHARDED = ("zero2",) + _PARAM_SHARDED

# Tensor-parallel table: (path-suffix match) -> axis index to shard over
# 'model'. Column-parallel outputs (qkv, up-proj, MLA up-projections) shard
# the output dim; row-parallel inputs (c_proj, W_o) shard the input dim, so
# activations stay head-sharded between them and GSPMD inserts exactly one
# psum per block, megatron-style.
_TP_RULES: tuple[tuple[tuple[str, ...], int], ...] = (
    # Vocab-parallel tied embedding/lm_head (megatron-style): the largest
    # single matrix in small GPTs (50304x768 = 39% of 124M params). Lookup
    # becomes masked-gather+psum, the tied logits matmul column-parallel —
    # GSPMD derives both from this one spec. (Round-1 gap: tkn_emb was
    # fully replicated under tp.)
    (("tkn_emb", "embedding"), 0),
    (("c_attn", "kernel"), 1),
    (("c_attn", "bias"), 0),
    (("c_proj", "kernel"), 0),       # attention out-proj (_OverlapDense)
    (("c_fc",), 1),                  # mlp up-proj (param, no /kernel suffix)
    # mlp down-proj is a BARE param named c_proj (models/mlp.py:162), so
    # the ("c_proj", "kernel") suffix above never matched it — found by
    # parallel/shardcheck.py (replicated-large: 1.3%/layer of the 124M
    # params silently replicated under tp). Row-parallel input dim, like
    # its attention namesake.
    (("c_proj",), 0),
    (("W_uq",), 1),                  # MLA: per-head dims are outputs
    (("W_uk",), 1),
    (("W_uv",), 1),
    (("W_qr",), 1),
    (("W_o",), 0),
    (("experts_fc",), 2),
    (("experts_proj",), 1),
)


def _path_names(path) -> tuple[str, ...]:
    return tuple(getattr(p, "key", getattr(p, "name", str(p))) for p in path)


def _tp_axis(names: tuple[str, ...]) -> Optional[int]:
    for suffix, axis in _TP_RULES:
        if names[-len(suffix):] == suffix:
            return axis
    return None


def _largest_divisible_axis(shape, n: int, taken: set[int]) -> Optional[int]:
    """Greedy ZeRO-style sharding: the largest axis divisible by `n` not
    already claimed by another mesh axis. FSDP in the reference flattens and
    chunks every param (FULL_SHARD); an axis split is the GSPMD-native
    equivalent and keeps layouts MXU-friendly."""
    best, best_dim = None, 0
    for i, d in enumerate(shape):
        if i in taken or d % n != 0:
            continue
        if d > best_dim:
            best, best_dim = i, d
    return best


def spec_for_param(names: tuple[str, ...], shape: tuple[int, ...],
                   recipe: Recipe, mesh: Mesh) -> P:
    """PartitionSpec for one parameter (or same-shaped opt-state leaf).

    Stacked-pipeline leaves (path under 'blocks', models/pipeline.py) carry
    a leading layer axis: it shards over 'pipe' (that IS the stage
    assignment — contiguous L/S layer groups per stage) and every
    positional rule below shifts right by one."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes: list[Optional[str]] = [None] * len(shape)
    taken: set[int] = set()

    stacked = bool(names) and names[0] == "blocks"
    off = 1 if stacked else 0
    if stacked:
        taken.add(0)  # the layer axis belongs to 'pipe' (or stays whole)
        if sizes.get("pipe", 1) > 1 and shape[0] % sizes["pipe"] == 0:
            axes[0] = "pipe"

    if sizes.get("expert", 1) > 1 and names and \
            names[-1].startswith("experts_"):
        axes[off] = "expert"
        taken.add(off)

    if sizes.get("model", 1) > 1:
        ti = _tp_axis(names)
        if ti is not None:
            ti += off
        if ti is not None and ti < len(shape) and \
                shape[ti] % sizes["model"] == 0 and ti not in taken:
            axes[ti] = "model"
            taken.add(ti)

    if recipe in _PARAM_SHARDED and sizes.get("data", 1) > 1:
        di = _largest_divisible_axis(shape, sizes["data"], taken)
        if di is not None:
            axes[di] = "data"

    return P(*axes)


def params_pspecs(params: Any, recipe: Recipe, mesh: Mesh) -> Any:
    """Map a parameter pytree (or eval_shape thereof) to PartitionSpecs."""
    def rule(path, leaf):
        return spec_for_param(_path_names(path), tuple(leaf.shape),
                              recipe, mesh)
    return jax.tree_util.tree_map_with_path(rule, params)


def _spec_like(shape: tuple[int, ...], recipe: Recipe, mesh: Mesh,
               sharded: bool) -> P:
    if not sharded or not shape:
        return P()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if sizes.get("data", 1) <= 1:
        return P()
    di = _largest_divisible_axis(shape, sizes["data"], set())
    axes: list[Optional[str]] = [None] * len(shape)
    if di is not None:
        axes[di] = "data"
    return P(*axes)


def shard_like_params(tree: Any, params_shapes: Any, params_specs: Any,
                      recipe: Recipe, mesh: Mesh) -> Any:
    """Specs for any pytree that embeds params-shaped leaves (optax states,
    grad accumulators): a leaf whose shape matches some parameter takes that
    parameter's spec when the recipe shards that tensor class, otherwise P().

    `params_shapes`/`params_specs`: matching pytrees of shapes and specs.
    """
    shard_opt = recipe in _OPT_SHARDED
    index: dict[tuple[int, ...], P] = {}

    # shape tuples would flatten to ints without is_leaf; P is a real leaf
    shapes_flat = jax.tree_util.tree_leaves(
        params_shapes, is_leaf=lambda x: isinstance(x, tuple))
    specs_flat = jax.tree_util.tree_leaves(params_specs)
    for shp, spec in zip(shapes_flat, specs_flat):
        shp = tuple(shp)
        # prefer a sharded spec on collision
        if shp not in index or index[shp] == P():
            index[shp] = spec

    def rule(leaf):
        shape = tuple(leaf.shape) if hasattr(leaf, "shape") else ()
        if not shape or not shard_opt:
            return P()
        if shape in index:
            spec = index[shape]
            if any(a is not None for a in spec):
                return spec
            # param replicated (e.g. zero1/zero2 params) — ZeRO still
            # shards the matching moments over 'data':
            return _spec_like(shape, recipe, mesh, True)
        return P()

    return jax.tree_util.tree_map(rule, tree)


def grads_pspecs(params_shapes: Any, params_specs: Any, recipe: Recipe,
                 mesh: Mesh) -> Any:
    """Specs for the gradient-accumulation buffer (ZeRO-2's contribution:
    reduce-scattered grads, strictly stronger than the reference's
    `gradient_as_bucket_view=True` memory trick, kaggle-zero2.py:1062)."""
    shard = recipe in _GRAD_SHARDED

    def rule(shape, spec):
        shape = tuple(shape)
        if not shard or not shape:
            return P()
        if any(a is not None for a in spec):
            return spec
        return _spec_like(shape, recipe, mesh, True)

    return jax.tree_util.tree_map(rule, params_shapes, params_specs,
                                  is_leaf=lambda x: isinstance(x, tuple))


def batch_pspec(recipe: Recipe, mesh: Mesh, *, leading_accum: bool = False) -> P:
    """Sharding for an (B, T) token batch: batch dim over 'data', sequence
    dim over 'seq' (the sp recipe). With `leading_accum`, a grad-accum axis
    (A, B, T) leads and stays replicated — the scan iterates it."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_axis = "data" if sizes.get("data", 1) > 1 else None
    t_axis = "seq" if sizes.get("seq", 1) > 1 else None
    if leading_accum:
        return P(None, b_axis, t_axis)
    return P(b_axis, t_axis)


def moe_dispatch_specs() -> tuple[P, P, P]:
    """shard_map specs for the grouped-MoE dispatch (ops/grouped_matmul.py):
    (token-tensor spec, stacked-expert-weight spec, output spec).

    Tokens (x_flat / topk_idx / topk_gates, all (N, ...)) split over
    'data' — they are already stored that way, so entering the region
    moves no token bytes. Expert-stacked weights split their leading
    n_exp axis over 'expert' (an all-gather over 'data' materializes the
    ZeRO-3 shards, exactly the gather GSPMD would emit before a padded
    dense dispatch). The output returns data-sharded after the in-body
    psum over 'expert'. One definition here so the dispatch's manual specs
    cannot drift from the recipe tables above."""
    tok = P("data", None)
    w = P("expert", None, None)
    return tok, w, P("data", None)


def decode_cache_pspec(shape: tuple[int, ...], mesh: Mesh,
                       kv_heads: Optional[tuple[int, int]] = None) -> P:
    """PartitionSpec for one decode KV-cache buffer (engine.DecodeEngine).

    The kv heads go over 'model' (the megatron layout: the qkv projection
    already emits head-sharded activations under tp, so cache reads/writes
    stay local) and the leading (block / slot) axis over 'data'. Where the
    heads live depends on the leaf:
    * int8 codes and scale sidecars (n_blocks, bs, n_kv, hs|1): the head
      axis is index 2 of the 4-D shape;
    * float GQA pools (n_blocks, bs, L) merge the heads into lanes
      (`ops.block_pool.kv_lanes`) — the caller says so with `kv_heads` =
      (n_kv_heads, head_size). A lane split is a head split only when the
      lanes carry no pad (L == n_kv * hs) and the heads divide, so those
      pools shard axis 2; a padded one (gpt2-xl's 25 x 64 -> 1664) stays
      whole on every model shard, as its 25 heads did before;
    * MLA latent buffers (.., latent[, dhr]) have no head axis (`kv_heads`
      None) — blocks over 'data' only.
    One definition here so the engine's cache layout cannot drift from the
    recipe tables above."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes: list[Optional[str]] = [None] * len(shape)
    tp = sizes.get("model", 1)
    if len(shape) == 4:
        heads = shape[2]
    elif kv_heads is not None and shape[2] == kv_heads[0] * kv_heads[1]:
        heads = kv_heads[0]
    else:
        heads = 1
    if tp > 1 and heads > 1 and heads % tp == 0:
        axes[2] = "model"
    if sizes.get("data", 1) > 1 and shape[0] % sizes["data"] == 0:
        axes[0] = "data"
    return P(*axes)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
