"""Collective matmul: ZeRO-3 param all-gathers and grad reduce-scatters
fused into ppermute rings that overlap with the matmuls consuming them.

Under the param-sharded recipes (fsdp / fsdp_tp / sp), every Block matmul
needs the full weight while storage holds only a 1/dp shard: GSPMD's
default schedule emits a blocking all-gather before the matmul and a
blocking reduce-scatter after the grad matmul, and at the 350M-1.5B ladder
scales those collectives become the step's critical path (BASELINE.json
north star). Megatron-LM (arXiv:2104.04473) and GSPMD's own collective-
matmul pass (arXiv:2105.04663 §3.4) both show the fix: decompose the
matmul over weight shards so each ring hop's ppermute is in flight while
the previous shard's partial matmul runs on the MXU.

Primitives (all shard_map bodies over the 'data' mesh axis, wrapped in ONE
custom_vjp at the logical level so forward and backward each get their own
dedicated ring):

* **all-gather ⊗ matmul** (forward / recompute): `y = x @ W` with W
  data-sharded on the contraction dim (K-ring: each arriving shard
  multiplies its x column block into a running accumulator) or on the
  output dim (N-ring: each arriving shard writes its output column block).
* **matmul ⊗ reduce-scatter** (grad path): `dW = x^T @ dy` where each hop
  computes the partial block owned by the accumulator's final destination
  and adds it to the acc arriving from the left neighbor — true ZeRO-2/3
  reduce-scatter semantics, overlapped.
* **bidirectional ring**: shards circulate clockwise AND counter-clockwise
  (ceil((dp-1)/2) sequential hops instead of dp-1), using both ICI
  directions — `OVERLAP_RING=uni|bidir` selects, default bidir.

Dispatch: `maybe_overlap_matmul` returns None (caller keeps its plain
GSPMD matmul, bit-identical to before this module existed) unless ALL of:
`OVERLAP` resolves to 'on' (env var wins over TrainConfig.overlap; 'auto'
currently falls back to the known-good GSPMD path until a hardware number
exists — flip `_AUTO_RESOLVES_TO` after the first chip measurement), the ambient
recipe is ZeRO-3-family, the mesh has a live 'data' axis, the param's
recipe spec actually shards it over 'data', shapes divide, and we are not
inside an sp shard_map region or a hoisted-gather scan (train/step.py).

The 'model' axis composes when it lands on the matmul's OUTPUT dim (the
megatron column-parallel case, e.g. c_fc under fsdp_tp): the ring runs
per tp shard and dx picks up one psum over 'model'. 'model' on the
contraction dim disqualifies (row-parallel matmuls keep the GSPMD path).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from distributed_pytorch_tpu import compat, config
from distributed_pytorch_tpu.parallel import context
from distributed_pytorch_tpu.parallel.sharding import spec_for_param

# Recipes whose params are data-sharded (mirrors sharding._PARAM_SHARDED;
# re-declared here so an import cycle can't form through parallel.sharding).
_ZERO3_RECIPES = ("fsdp", "fsdp_tp", "sp")

# What 'auto' means today: GSPMD. The first chip run that measures
# OVERLAP=on faster flips this to "on": the four-chip fsdp cell PERF.md
# section 7 lists (`gpt2xl_train_fsdp4`), run once with OVERLAP=on; no
# code change is needed to take the measurement.
_AUTO_RESOLVES_TO = "off"


def resolve_mode(config_mode: str = "auto") -> str:
    """'on' | 'off' after applying env-var precedence and the auto default.

    The OVERLAP env var (on/off/auto) wins over the TrainConfig field so
    a run can A/B without a config plumb-through."""
    mode = config.knob("OVERLAP") or config_mode
    if mode not in ("auto", "on", "off"):
        raise ValueError(f"OVERLAP must be auto|on|off, got {mode!r}")
    return _AUTO_RESOLVES_TO if mode == "auto" else mode


def _ring_style() -> bool:
    """True = bidirectional (both ICI directions, ~half the sequential
    hops); env OVERLAP_RING=uni forces the one-way ring for A/B."""
    return config.knob("OVERLAP_RING") != "uni"


# ---------------------------------------------------------------------------
# ring drivers (run inside shard_map)
# ---------------------------------------------------------------------------

def _ring_visit(w_l, axis: str, dp: int, bidir: bool,
                visit: Callable[[jnp.ndarray, jnp.ndarray], None]) -> None:
    """Call `visit(src, shard)` once per ring source, issuing each hop's
    ppermute BEFORE the previous shard's compute so XLA's async
    collective-permute overlaps the transfer with the matmul (`src` is the
    traced origin device of the shard on the 'data' ring)."""
    idx = jax.lax.axis_index(axis)
    if dp <= 2 or not bidir:
        perm = [(i, (i + 1) % dp) for i in range(dp)]
        pend = jax.lax.ppermute(w_l, axis, perm) if dp > 1 else None
        visit(idx, w_l)
        for s in range(1, dp):
            cur = pend
            pend = jax.lax.ppermute(cur, axis, perm) if s < dp - 1 else None
            visit((idx - s) % dp, cur)
        return
    # bidirectional: right ring carries sources idx-1..idx-n_right,
    # left ring idx+1..idx+n_left; ceil((dp-1)/2) sequential hops
    n_right = dp // 2
    n_left = dp - 1 - n_right
    perm_r = [(i, (i + 1) % dp) for i in range(dp)]
    perm_l = [(i, (i - 1) % dp) for i in range(dp)]
    pend_r = jax.lax.ppermute(w_l, axis, perm_r)
    pend_l = jax.lax.ppermute(w_l, axis, perm_l) if n_left else None
    visit(idx, w_l)
    for h in range(1, n_right + 1):
        cur_r, cur_l = pend_r, pend_l
        pend_r = jax.lax.ppermute(cur_r, axis, perm_r) if h < n_right \
            else None
        pend_l = jax.lax.ppermute(cur_l, axis, perm_l) if h < n_left \
            else None
        visit((idx - h) % dp, cur_r)
        if h <= n_left:
            visit((idx + h) % dp, cur_l)


def _ring_reduce_scatter(partial_fn: Callable[[jnp.ndarray], jnp.ndarray],
                         axis: str, dp: int) -> jnp.ndarray:
    """matmul ⊗ reduce-scatter: `partial_fn(tgt)` computes this device's
    partial for ring block `tgt`; the accumulator travels i -> i+1 each hop
    and lands home fully reduced after dp-1 hops. The ppermute is issued
    before the next partial's matmul, so transfer overlaps compute."""
    idx = jax.lax.axis_index(axis)
    if dp == 1:
        return partial_fn(idx)
    perm = [(i, (i + 1) % dp) for i in range(dp)]
    acc = partial_fn((idx + dp - 1) % dp)
    for s in range(1, dp):
        acc_in = jax.lax.ppermute(acc, axis, perm)      # in flight...
        p = partial_fn((idx + dp - 1 - s) % dp)         # ...during this
        acc = acc_in + p
    return acc


# ---------------------------------------------------------------------------
# the custom-vjp collective matmul (logical level)
# ---------------------------------------------------------------------------

def _dot2(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot2_tn(a, b):
    """a^T @ b with f32 accumulation: (m, k), (m, n) -> (k, n)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=256)
def _build_cm(mesh: Mesh, w_spec: P, transpose_b: bool, data_on_k: bool,
              model_on_n: bool, seq_live: bool, bidir: bool,
              out_dtype_name: Optional[str]):
    """One custom_vjp collective matmul per static configuration.

    Logical contract: y = x @ W where W = w.T when transpose_b (w is the
    stored param, e.g. the (V, C) embedding for the (C, V) lm head).
    x: (B, T, K); w 2D with `w_spec` its recipe PartitionSpec. `data_on_k`:
    whether 'data' lands on W's contraction dim (K-ring) or output dim
    (N-ring). `model_on_n`: W additionally 'model'-sharded on its output
    dim (and y/dy carry that sharding)."""
    dp = mesh.shape["data"]
    seq = "seq" if seq_live else None
    x_spec = P("data", seq, None)
    y_spec = P("data", seq, "model" if model_on_n else None)

    def _orient(w_s):
        return w_s.T if transpose_b else w_s            # (K_part, N_part)

    def fwd_local(x_l, w_l):
        B, T, K = x_l.shape
        x2 = x_l.reshape(B * T, K)
        box = {}

        if data_on_k:
            def visit(src, w_s):
                w2 = _orient(w_s)                       # (Kc, N_loc)
                kc = w2.shape[0]
                x_blk = jax.lax.dynamic_slice_in_dim(x2, src * kc, kc,
                                                     axis=1)
                c = _dot2(x_blk, w2)
                box["acc"] = c if "acc" not in box else box["acc"] + c
        else:
            def visit(src, w_s):
                w2 = _orient(w_s)                       # (K, Nc)
                nc = w2.shape[1]
                if "acc" not in box:
                    box["acc"] = jnp.zeros((B * T, nc * dp), jnp.float32)
                box["acc"] = jax.lax.dynamic_update_slice(
                    box["acc"], _dot2(x2, w2), (0, src * nc))

        _ring_visit(w_l, "data", dp, bidir, visit)
        y2 = box["acc"]
        dt = jnp.dtype(out_dtype_name) if out_dtype_name else x_l.dtype
        return y2.reshape(B, T, y2.shape[-1]).astype(dt)

    def dx_local(dy_l, w_l):
        B, T, N = dy_l.shape
        dy2 = dy_l.reshape(B * T, N).astype(jnp.float32)
        box = {}

        if data_on_k:
            # W^T is output-sharded on K: N-style ring writing K blocks
            def visit(src, w_s):
                w2 = _orient(w_s)                       # (Kc, N_loc)
                kc = w2.shape[0]
                if "acc" not in box:
                    box["acc"] = jnp.zeros((B * T, kc * dp), jnp.float32)
                box["acc"] = jax.lax.dynamic_update_slice(
                    box["acc"], _dot2(dy2, w2.astype(jnp.float32).T),
                    (0, src * kc))
        else:
            # W^T contraction-sharded on N: accumulate over dy column blocks
            def visit(src, w_s):
                w2 = _orient(w_s)                       # (K, Nc)
                nc = w2.shape[1]
                dy_blk = jax.lax.dynamic_slice_in_dim(dy2, src * nc, nc,
                                                      axis=1)
                c = _dot2(dy_blk, w2.astype(jnp.float32).T)
                box["acc"] = c if "acc" not in box else box["acc"] + c

        _ring_visit(w_l, "data", dp, bidir, visit)
        dx2 = box["acc"]
        if model_on_n:
            # each tp shard contracted only its N/tp slice of dy
            dx2 = jax.lax.psum(dx2, "model")
        return dx2.reshape(B, T, dx2.shape[-1])

    def dw_local(x_l, dy_l):
        B, T, K = x_l.shape
        x2 = x_l.reshape(B * T, K)
        dy2 = dy_l.reshape(B * T, -1)

        if data_on_k:
            kc = K // dp

            def partial(tgt):
                x_blk = jax.lax.dynamic_slice_in_dim(x2, tgt * kc, kc,
                                                     axis=1)
                return _dot2_tn(x_blk, dy2)             # (kc, N_loc) f32
        else:
            nglob = dy2.shape[1]
            nc = nglob // dp

            def partial(tgt):
                dy_blk = jax.lax.dynamic_slice_in_dim(dy2, tgt * nc, nc,
                                                      axis=1)
                return _dot2_tn(x2, dy_blk)             # (K, nc) f32

        dw = _ring_reduce_scatter(partial, "data", dp)
        if seq_live:
            dw = jax.lax.psum(dw, "seq")                # sum over T shards
        return dw.T if transpose_b else dw

    fwd_sm = compat.shard_map(fwd_local, mesh=mesh,
                              in_specs=(x_spec, w_spec), out_specs=y_spec)
    dx_sm = compat.shard_map(dx_local, mesh=mesh,
                             in_specs=(y_spec, w_spec), out_specs=x_spec)
    dw_sm = compat.shard_map(dw_local, mesh=mesh,
                             in_specs=(x_spec, y_spec), out_specs=w_spec)

    @jax.custom_vjp
    def cm(x, w):
        return fwd_sm(x, w)

    def cm_fwd(x, w):
        return fwd_sm(x, w), (x, w)

    def cm_bwd(res, dy):
        x, w = res
        dx = dx_sm(dy, w).astype(x.dtype)
        dw = dw_sm(x, dy).astype(w.dtype)
        return dx, dw

    cm.defvjp(cm_fwd, cm_bwd)
    return cm


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def maybe_overlap_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                         names: tuple[str, ...],
                         transpose_b: bool = False,
                         out_dtype=None) -> Optional[jnp.ndarray]:
    """y = x @ w (x @ w.T when transpose_b) through the collective-matmul
    ring, or None when the caller should keep its plain GSPMD matmul.

    `names`: the param's path suffix (e.g. ('c_fc',) or
    ('tkn_emb', 'embedding')) — fed to the SAME spec table the recipe uses
    (parallel/sharding.spec_for_param) so the ring's in_specs cannot drift
    from how the param is actually stored."""
    mode, recipe = context.overlap_state()
    if resolve_mode(mode) != "on" or recipe not in _ZERO3_RECIPES:
        return None
    if context.gathers_hoisted() or context.in_sp_region():
        return None
    mesh = context.get_mesh()
    if mesh is None or w.ndim != 2 or x.ndim != 3:
        return None
    dp = mesh.shape.get("data", 1)
    if dp <= 1 or x.shape[0] % dp != 0:
        return None
    sp = mesh.shape.get("seq", 1)
    seq_live = sp > 1
    if seq_live and x.shape[1] % sp != 0:
        return None

    spec = spec_for_param(names, tuple(w.shape), recipe, mesh)
    axes = tuple(spec) + (None,) * (2 - len(tuple(spec)))
    if "data" not in axes:
        return None                                     # recipe left w whole
    data_w_axis = axes.index("data")
    # map the stored-orientation axis onto the logical matmul: w is (K, N),
    # or (N, K) when transpose_b
    data_on_k = (data_w_axis == 0) != transpose_b
    model_on_n = False
    if "model" in axes:
        model_w_axis = axes.index("model")
        if (model_w_axis == 0) != transpose_b:
            return None                                 # row-parallel: GSPMD
        model_on_n = True
    # contraction dim must agree between x and w
    k_w_axis = 1 if transpose_b else 0
    if x.shape[-1] != w.shape[k_w_axis]:
        return None

    cm = _build_cm(mesh, spec, transpose_b, data_on_k, model_on_n,
                   seq_live, _ring_style(),
                   jnp.dtype(out_dtype).name if out_dtype else None)
    return cm(x, w)
