"""The jit-compiled training and eval steps.

One `train_step` serves every recipe — grad accumulation is a `lax.scan`
over micro-batches *inside* the compiled step (the reference's inner Python
loop with `require_backward_grad_sync` suppression, multi-gpu/ddp/train.py:
313-325, becomes a scan whose grad psum GSPMD naturally defers to the
optimizer update), followed by global-norm clip + AdamW (reference
train.py:345-352 unscale/clip/step; no GradScaler — bf16 needs none).

Collectives are never written by hand here: the in/out shardings from
parallel/sharding.py make GSPMD insert the all-reduce (dp), all-gather
(zero1 param refresh, fsdp layer gathers) and reduce-scatter (zero2/fsdp
grads) that the reference gets from DDP/ZeroRedundancyOptimizer/FSDP.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_pytorch_tpu import config as cfg_mod
from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.obs.retrace import TraceGuard, guarded
from distributed_pytorch_tpu.parallel import context, sharding as shd
from distributed_pytorch_tpu.train.state import TrainState

# Recipes whose gradient accumulator is constrained sharded over 'data'
# (true ZeRO-2 reduce-scatter semantics — strictly stronger than the
# reference's `gradient_as_bucket_view=True` memory trick,
# kaggle-zero2.py:1062 — plus the param-sharded family).
_SHARDED_GRAD_RECIPES = ("zero2", "fsdp", "fsdp_tp", "sp")


def _dropped_frac(moe_state) -> jnp.ndarray:
    """Mean of the per-layer `dropped_frac` moe_state leaves (models/mlp.py):
    the fraction of routed assignments silently dropped past capacity in
    'scatter' mode this step — 0 by construction for 'dense'/'grouped'.
    Leaves are scalars in the loop model and (L,) under the pipeline's
    stacked moe_state."""
    vals = [jnp.mean(leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(moe_state)[0]
            if getattr(path[-1], "key", None) == "dropped_frac"]
    if not vals:
        return jnp.float32(0.0)
    return jnp.mean(jnp.stack(vals))


def _grad_shardings(params, recipe: str, mesh: Mesh):
    """NamedSharding tree for the grad accumulator (leaves, safe to tree_map)."""
    p_specs = shd.params_pspecs(params, recipe, mesh)
    p_shapes = jax.tree_util.tree_map(lambda l: tuple(l.shape), params)
    g_specs = shd.grads_pspecs(p_shapes, p_specs, recipe, mesh)
    return shd.named(mesh, g_specs)


def make_grads_fn(model, model_cfg: LLMConfig, train_cfg: TrainConfig,
                  mesh: Optional[Mesh] = None):
    """Build the gradient half of the train step — the micro-batch
    accumulation scan with sharded-accumulator constraints, gather
    hoisting and poison fault injection — shared verbatim by the in-HBM
    `make_train_step` and the ZeRO-Offload device program
    (train/offload.py), so the two paths cannot diverge numerically.

    Returns `(grads_fn, overlap_mode)` where
    `grads_fn(params, moe_state, step, x, y) -> (grads, new_moe, losses)`.
    The caller is responsible for wrapping the trace in
    `context.use_mesh(mesh)` / `context.use_overlap(overlap_mode, recipe)`.
    """
    from distributed_pytorch_tpu.ops import collective_matmul as cm
    recipe = train_cfg.parallelism
    # Fault injection for the anomaly guard (same spirit as scripts/
    # fault_inject.py on the serving side): TRAIN_POISON_IT=<k> makes
    # iteration k's batch produce NaN loss AND NaN grads — exactly what
    # a corrupt data shard does — so the skip/record/resume path is
    # testable without waiting for a real bad batch.
    poison_it = cfg_mod.knob("TRAIN_POISON_IT")
    overlap_mode = cm.resolve_mode(getattr(train_cfg, "overlap", "auto"))
    overlap_on = (overlap_mode == "on" and mesh is not None
                  and recipe in cm._ZERO3_RECIPES
                  and mesh.shape.get("data", 1) > 1)

    def loss_fn(params, moe_state, x, y, dropout_rng):
        variables = {"params": params}
        has_moe = bool(moe_state)
        if has_moe:
            variables["moe_state"] = moe_state
        out = model.apply(variables, x, y, deterministic=False,
                          rngs={"dropout": dropout_rng},
                          mutable=["moe_state"] if has_moe else False)
        if has_moe:
            (_, loss, _), mutated = out
            new_moe = mutated.get("moe_state", moe_state)
        else:
            _, loss, _ = out
            new_moe = moe_state
        return loss, new_moe

    def grads_fn(params, moe_state, step, x, y):
        accum = x.shape[0]
        base_rng = jax.random.fold_in(
            jax.random.PRNGKey(train_cfg.seed), step)

        if mesh is not None and recipe in _SHARDED_GRAD_RECIPES:
            g_sh = _grad_shardings(params, recipe, mesh)

            def grad_constraint(g):
                return jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, g, g_sh)
        else:
            def grad_constraint(g):
                return g

        # gather hoisting (see make_train_step docstring): with accum > 1,
        # one param all-gather per optimizer step beats one per micro-step;
        # with_sharding_constraint-to-replicated is a numeric identity, so
        # parity with the oracle is untouched. Grads are taken w.r.t. the
        # gathered tree (same values) and reduce-scatter per micro-step
        # through grad_constraint, preserving ZeRO grad sharding.
        hoist = overlap_on and accum > 1
        if hoist:
            repl = NamedSharding(mesh, P())
            loss_params = jax.tree_util.tree_map(
                lambda p: jax.lax.with_sharding_constraint(p, repl),
                params)
        else:
            loss_params = params

        zeros = grad_constraint(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))

        def micro_step(carry, xs):
            g_acc, moe_state = carry
            xi, yi, idx = xs
            rng = jax.random.fold_in(base_rng, idx)
            (loss, new_moe), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(loss_params, moe_state, xi, yi, rng)
            g_acc = grad_constraint(
                jax.tree_util.tree_map(jnp.add, g_acc, grads))
            return (g_acc, new_moe), loss

        with context.hoisted_gathers(hoist):
            (g_acc, new_moe), losses = jax.lax.scan(
                micro_step, (zeros, moe_state),
                (x, y, jnp.arange(accum)))
        grads = jax.tree_util.tree_map(lambda g: g / accum, g_acc)

        if poison_it >= 0:
            # fault injection (see above): NaN-bomb this iteration's
            # loss and gradients, as a poisoned batch would
            bomb = jnp.where(step == poison_it,
                             jnp.float32(jnp.nan), jnp.float32(1.0))
            losses = losses * bomb
            grads = jax.tree_util.tree_map(lambda g: g * bomb, grads)
        return grads, new_moe, losses

    return grads_fn, overlap_mode


def make_train_step(model, tx: optax.GradientTransformation,
                    model_cfg: LLMConfig, train_cfg: TrainConfig,
                    mesh: Optional[Mesh] = None,
                    state_sharding: Optional[Any] = None,
                    offload: bool = False):
    """Build the jitted `train_step(state, x, y) -> (state, metrics)`.

    x, y: (accum, B_global, T) int32 — the whole logical batch for one
    optimizer step; axis 0 is scanned (grad accumulation, reference
    single-gpu/train.py:338-345).

    Overlap interaction (ops/collective_matmul.py): the resolved OVERLAP
    mode is published for the trace so the model's matmul call sites can
    ring their ZeRO-3 param gathers. With grad accumulation (accum > 1)
    the per-layer gathers are instead HOISTED out of the micro-batch scan:
    params are constrained replicated ONCE before the scan (one all-gather
    per optimizer step instead of one per accumulation micro-step — the
    standard FSDP no-reshard-between-microbatches trade: full fp32 params
    resident for the step), gradients still reduce-scatter per micro-step
    through the sharded-accumulator constraint, and the in-model rings
    stand down via context.gathers_hoisted.

    `offload=True` dispatches to the ZeRO-Offload split step
    (train/offload.py): the device program stops at the gradients, the
    optimizer state lives in host RAM and the AdamW update runs there.
    """
    if offload:
        from distributed_pytorch_tpu.train import offload as offload_mod
        return offload_mod.make_offload_train_step(
            model, tx, model_cfg, train_cfg, mesh, state_sharding)
    recipe = train_cfg.parallelism
    # Anomaly guard (ISSUE 10): 'warn' adds a device-side nonfinite flag
    # to the step metrics (drained with them at sync boundaries — zero
    # extra host round-trips); 'skip' additionally withholds the
    # optimizer/moe update for a poisoned (NaN/inf loss or grad-norm)
    # step so training keeps going on the last good params. 'off'
    # removes the metric entirely.
    anomaly = getattr(train_cfg, "anomaly", "warn")
    grads_fn, overlap_mode = make_grads_fn(model, model_cfg, train_cfg,
                                           mesh)

    # one trace serves the whole run: batch shapes are fixed by the config
    # and state.step is a traced value. A mid-run retrace means a shape or
    # weak-type leak — the guard counts it (and the loop's expect(0)
    # window pins the offending iteration); see obs/retrace.py.
    guard = TraceGuard("train.step")

    def train_step(state: TrainState, x: jnp.ndarray, y: jnp.ndarray):
        guard.mark()  # trace-time side effect
        # publish the mesh (+ overlap mode) for the duration of TRACING:
        # sequence-parallel attention (ops/ring_attention.py) reads the
        # mesh to shard_map over 'seq'; the collective-matmul dispatcher
        # reads (mode, recipe) to decide whether to ring param gathers
        with context.use_mesh(mesh), \
                context.use_overlap(overlap_mode, recipe):
            return _train_step_body(state, x, y)

    def _train_step_body(state: TrainState, x: jnp.ndarray, y: jnp.ndarray):
        grads, new_moe, losses = grads_fn(state.params, state.moe_state,
                                          state.step, x, y)
        # scopes of obs/trace.py SCOPES: no module name reaches these ops
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope("grad_norm"):
            grad_norm = optax.global_norm(grads)

        metrics = {
            "loss": losses.mean(),
            "grad_norm": grad_norm,
        }
        if anomaly != "off":
            finite = (jnp.isfinite(metrics["loss"])
                      & jnp.isfinite(metrics["grad_norm"]))
            metrics["nonfinite"] = (~finite).astype(jnp.float32)
        if anomaly == "skip":
            # withhold the whole update (params, optimizer moments AND
            # moe routing state) when the step is poisoned: jnp.where
            # on a scalar predicate selects per-leaf, so NaN updates
            # never touch the kept values. state.step still advances —
            # the loop's data stream and LR schedule are it-keyed, and
            # a skipped step must consume its batch, not replay it.
            def _keep_old(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new, old)

            new_params = _keep_old(new_params, state.params)
            new_opt = _keep_old(new_opt, state.opt_state)
            new_moe = _keep_old(new_moe, state.moe_state)
            metrics["update_skipped"] = metrics["nonfinite"]
        if model_cfg.moe:
            metrics["moe_dropped_frac"] = _dropped_frac(new_moe)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt, moe_state=new_moe)
        return new_state, metrics

    if mesh is None:
        return guarded(jax.jit(train_step, donate_argnums=(0,)), guard)

    batch_sh = NamedSharding(mesh, shd.batch_pspec(recipe, mesh,
                                                   leading_accum=True))
    repl = NamedSharding(mesh, P())
    metrics_sh = {"loss": repl, "grad_norm": repl}
    if anomaly != "off":
        metrics_sh["nonfinite"] = repl
    if anomaly == "skip":
        metrics_sh["update_skipped"] = repl
    if model_cfg.moe:
        metrics_sh["moe_dropped_frac"] = repl
    return guarded(jax.jit(
        train_step,
        in_shardings=(state_sharding, batch_sh, batch_sh),
        out_shardings=(state_sharding, metrics_sh),
        donate_argnums=(0,),
    ), guard)


def trace_train_step(model, tx: optax.GradientTransformation,
                     model_cfg: LLMConfig, train_cfg: TrainConfig,
                     state_shapes, mesh: Optional[Mesh] = None,
                     accum: int = 1):
    """Trace — never run — the REAL jitted train step over abstract state.

    The static comms auditor (parallel/commscheck.py) entry: builds the
    same `make_train_step` program the trainer executes (same shardings,
    same donation) and traces it with ShapeDtypeStructs, so the returned
    `jax.stages.Traced` carries the closed jaxpr, per-argument donation
    flags (`args_info`) and output avals without allocating a single
    buffer. `state_shapes` is the eval_shape of the state init (see
    train/state.create_train_state); batch shape is (accum, B, T) like
    the real step's."""
    from distributed_pytorch_tpu.train.state import state_shardings
    sh = (state_shardings(state_shapes, train_cfg.parallelism, mesh)
          if mesh is not None else None)
    step = make_train_step(model, tx, model_cfg, train_cfg, mesh, sh)
    batch = jax.ShapeDtypeStruct(
        (accum, train_cfg.batch_size, model_cfg.block_size), jnp.int32)
    # GuardedFn delegates .trace to the underlying PjitFunction
    return step.trace(state_shapes, batch, batch)


def make_eval_step(model, train_cfg: TrainConfig,
                   mesh: Optional[Mesh] = None,
                   state_sharding: Optional[Any] = None):
    """Jitted eval loss on one (B, T) batch (reference estimate_loss,
    single-gpu/train.py:280-293). Unlike the reference's DDP variant —
    which prints rank-0's *local* estimate (multi-gpu/ddp/train.py:308-311)
    — under pjit the loss is over the GLOBAL batch."""

    def eval_step(state: TrainState, x, y):
        with context.use_mesh(mesh):
            variables = {"params": state.params}
            if state.moe_state:
                variables["moe_state"] = state.moe_state
            _, loss, _ = model.apply(variables, x, y, deterministic=True)
            return loss

    if mesh is None:
        return jax.jit(eval_step)
    recipe = train_cfg.parallelism
    batch_sh = NamedSharding(mesh, shd.batch_pspec(recipe, mesh))
    return jax.jit(eval_step,
                   in_shardings=(state_sharding, batch_sh, batch_sh),
                   out_shardings=NamedSharding(mesh, P()))
