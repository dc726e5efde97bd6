"""Pallas TPU ragged grouped matmul: dropless MoE expert dispatch.

Why: the 'scatter' dispatch (models/mlp.py) is XLA-legal but pays twice —
it materializes (E, capacity, C) gather/scatter buffers in HBM on BOTH
sides of the expert FFNs, and it silently DROPS routed assignments past
`capacity` (GShard position priority). GSPMD lowers the ep recipe's
dispatch/return as all-to-alls but leaves the per-expert matmuls padded
and dense (arXiv:2105.04663 §3.3) — exactly the waste a ragged grouped
kernel removes (MegaBlocks, arXiv:2211.15841).

Layout: routed assignments are stable-sorted by expert into ONE packed
buffer whose groups are padded only to the next token-tile boundary
(bm rows, not `capacity`), so the buffer holds every assignment — dropless
by construction. A scalar-prefetch array maps each bm-row tile to its
expert, so the kernel streams exactly one expert's weight tile per grid
step and empty experts get ZERO grid steps (they own no tiles). The
combine weights (router gates) are applied at the second matmul's output
write, so the scatter-add back to (N, C) is the only HBM round trip on
the return path.

Kernels (all f32-accumulated; operands stay in the input dtype so the MXU
runs at full rate):

* forward  — grid (token_tiles, n_tiles): one (bm, K) x tile and the
  owning expert's (K, bn) weight tile are resident; output written once,
  optionally scaled per row by the combine gate.
* backward dx (token-major) — grid (token_tiles, k_tiles):
  dx = (dy * gate) @ W_e^T, streamed over K tiles of the same expert tile
  the forward read.
* backward dW (group-major) — grid (k_tiles, n_tiles, token_tiles), token
  tiles innermost: consecutive tiles of one expert hit the SAME output
  block, which stays resident in VMEM and accumulates
  dW_e += x_tile^T @ (dy_tile * gate); the block flushes when the group
  changes. Experts that own no tiles are never visited — their dW is
  masked to zero afterwards.

Sharding: under a live mesh the dispatch runs inside shard_map over
('data', 'expert') (specs from parallel/sharding.moe_dispatch_specs).
Tokens ride in data-sharded (they already are — zero dispatch
collectives); each expert shard packs ONLY the assignments routed to its
local experts (non-local assignments keep their slot with gate 0, so they
cost tile-rounding FLOPs but contribute nothing) and one psum over
'expert' combines the partial outputs. This replaces the scatter path's
all-to-all pair with a single combine-reduction: under XLA's static
shapes a dropless all-to-all needs worst-case (every assignment to one
shard) buffers, which is the replicated layout anyway — the psum costs
the same bytes as the return all-to-all + gather it replaces and keeps
the dropless guarantee.

Shared experts reuse the same kernel as always-on groups: the dispatch
prepends one group per shared expert containing every token with gate
1.0, so shared + routed experts stream through one packed kernel pair
and one combine scatter-add.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu import compat, config
from distributed_pytorch_tpu.ops.activations import activation, is_gated
from distributed_pytorch_tpu.parallel import context

DEFAULT_BLOCK_M = config.knob("GMM_BLOCK_M")   # token rows
DEFAULT_BLOCK_N = config.knob("GMM_BLOCK_N")   # out features
DEFAULT_BLOCK_K = config.knob("GMM_BLOCK_K")   # contraction


def _pick(n: int, preferred: int, step: int) -> int:
    """Largest divisor of n that is <= preferred and a multiple of `step`;
    n itself when no such divisor exists (tiny test dims)."""
    b = min(preferred, n)
    b -= b % step
    while b > step and n % b != 0:
        b -= step
    return b if (b >= step and n % b == 0) else n


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b^T with f32 accumulation: (m, n), (k, n) -> (m, k)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a^T @ b with f32 accumulation: (m, k), (m, n) -> (k, n)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel_scaled(g_ref, x_ref, w_ref, s_ref, o_ref):
    del g_ref  # consumed by the index maps (weight-tile selection)
    o = _dot(x_ref[:], w_ref[0]) * s_ref[:]
    o_ref[:] = o.astype(o_ref.dtype)


def _fwd_kernel(g_ref, x_ref, w_ref, o_ref):
    del g_ref
    o_ref[:] = _dot(x_ref[:], w_ref[0]).astype(o_ref.dtype)


def _dx_kernel_scaled(g_ref, dy_ref, w_ref, s_ref, o_ref):
    del g_ref
    d = dy_ref[:].astype(jnp.float32) * s_ref[:]
    o_ref[:] = _dot_nt(d.astype(dy_ref.dtype), w_ref[0]).astype(o_ref.dtype)


def _dx_kernel(g_ref, dy_ref, w_ref, o_ref):
    del g_ref
    o_ref[:] = _dot_nt(dy_ref[:], w_ref[0]).astype(o_ref.dtype)


def _dw_kernel(g_ref, f_ref, x_ref, dy_ref, *rest):
    # rest = (s_ref?, dw_ref) — gate operand present only in scaled calls
    if len(rest) == 2:
        s_ref, dw_ref = rest
        dy = dy_ref[:].astype(jnp.float32) * s_ref[:]
    else:
        (dw_ref,) = rest
        dy = dy_ref[:]
    del g_ref
    i = pl.program_id(2)
    part = _dot_tn(x_ref[:], dy.astype(x_ref.dtype))

    @pl.when(f_ref[i] == 1)
    def _():
        dw_ref[:] = part[None].astype(dw_ref.dtype)

    @pl.when(f_ref[i] == 0)
    def _():
        dw_ref[:] = dw_ref[:] + part[None].astype(dw_ref.dtype)


def _fwd_call(x_pad, w, scales, tile_group, bm, interpret):
    P, K = x_pad.shape
    E, _, N = w.shape
    num_tiles = P // bm
    bn = _pick(N, DEFAULT_BLOCK_N, 8 if interpret else 128)
    in_specs = [
        pl.BlockSpec((bm, K), lambda i, j, g: (i, 0)),
        pl.BlockSpec((1, K, bn), lambda i, j, g: (g[i], 0, j)),
    ]
    args = [tile_group, x_pad, w]
    kern = _fwd_kernel
    if scales is not None:
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, g: (i, 0)))
        args.append(scales)
        kern = _fwd_kernel_scaled
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles, N // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, g: (i, j)),
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, N), x_pad.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        name="gmm_fwd",
        interpret=interpret,
    )(*args)


def _dx_call(dy, w, scales, tile_group, bm, interpret):
    P, N = dy.shape
    E, K, _ = w.shape
    num_tiles = P // bm
    bk = _pick(K, DEFAULT_BLOCK_K, 8 if interpret else 128)
    in_specs = [
        pl.BlockSpec((bm, N), lambda i, k, g: (i, 0)),
        pl.BlockSpec((1, bk, N), lambda i, k, g: (g[i], k, 0)),
    ]
    args = [tile_group, dy, w]
    kern = _dx_kernel
    if scales is not None:
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, k, g: (i, 0)))
        args.append(scales)
        kern = _dx_kernel_scaled
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda i, k, g: (i, k)),
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, K), dy.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        name="gmm_dx",
        interpret=interpret,
    )(*args)


def _dw_call_impl(x_pad, dy, scales, tile_group, tile_first, n_experts,
                  bm, interpret):
    P, K = x_pad.shape
    _, N = dy.shape
    num_tiles = P // bm
    step = 8 if interpret else 128
    bk = _pick(K, DEFAULT_BLOCK_K, step)
    bn = _pick(N, DEFAULT_BLOCK_N, step)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda k, j, i, g, f: (i, k)),
        pl.BlockSpec((bm, bn), lambda k, j, i, g, f: (i, j)),
    ]
    args = [tile_group, tile_first, x_pad, dy]
    if scales is not None:
        in_specs.append(pl.BlockSpec((bm, 1), lambda k, j, i, g, f: (i, 0)))
        args.append(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // bk, N // bn, num_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bk, bn),
                               lambda k, j, i, g, f: (g[i], k, j)),
    )
    return pl.pallas_call(
        _dw_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gmm_dw",
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# custom VJP over the tile-aligned buffer
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gmm(x_pad, w, scales, tile_group, tile_first, counts, static):
    """y[r] = (x_pad[r] @ w[expert_of_tile(r)]) * scales[r].

    x_pad (P, K): tile-aligned expert-sorted rows (P = num_tiles * bm);
    w (E, K, N); scales (P, 1) f32 or None; tile_group/tile_first
    (num_tiles,) int32 metadata from _gmm_metadata; counts (E,) int32 real
    rows per group (dW masking). static = (bm, interpret)."""
    bm, interpret = static
    return _fwd_call(x_pad, w, scales, tile_group, bm, interpret)


def _gmm_fwd(x_pad, w, scales, tile_group, tile_first, counts, static):
    y = _gmm(x_pad, w, scales, tile_group, tile_first, counts, static)
    return y, (x_pad, w, scales, tile_group, tile_first, counts)


def _gmm_bwd(static, res, dy):
    bm, interpret = static
    x_pad, w, scales, tile_group, tile_first, counts = res
    ds = None
    if scales is not None:
        # gate cotangent needs the unscaled product; recompute it rather
        # than storing a second (P, N) buffer from forward
        y_us = _fwd_call(x_pad, w, None, tile_group, bm, interpret)
        ds = jnp.sum(dy.astype(jnp.float32) * y_us.astype(jnp.float32),
                     axis=-1, keepdims=True)
    dx = _dx_call(dy, w, scales, tile_group, bm, interpret)
    dw = _dw_call_impl(x_pad, dy, scales, tile_group, tile_first,
                       w.shape[0], bm, interpret)
    # experts owning zero tiles were never visited — their blocks hold
    # whatever the buffer started with, not zeros
    dw = jnp.where(counts[:, None, None] > 0, dw, 0.0)
    return (dx.astype(x_pad.dtype), dw.astype(w.dtype), ds, None, None,
            None)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(x_pad: jnp.ndarray, w: jnp.ndarray, tile_group: jnp.ndarray,
        tile_first: jnp.ndarray, counts: jnp.ndarray, *,
        scales: Optional[jnp.ndarray] = None, bm: int,
        interpret: bool) -> jnp.ndarray:
    """Ragged grouped matmul over a tile-aligned expert-sorted buffer."""
    return _gmm(x_pad, w, scales, tile_group, tile_first, counts,
                (bm, interpret))


# ---------------------------------------------------------------------------
# dispatch metadata + the full routed/shared dispatch
# ---------------------------------------------------------------------------

def _gmm_metadata(flat_e: jnp.ndarray, n_groups: int, n_tiles: int,
                  bm: int):
    """(counts, slot_for_sorted_rank, tile_group, tile_first) for a flat
    expert-id vector. Groups are padded to the next bm multiple; tile t
    belongs to the group whose padded region covers rows [t*bm, (t+1)*bm).
    Empty groups own zero tiles (skipped entirely); trailing unused tiles
    resolve to the last group — their rows carry gate 0, so they add
    nothing anywhere (forward, dx, dW)."""
    counts = jnp.zeros((n_groups,), jnp.int32).at[flat_e].add(1)
    padded = -(-counts // bm) * bm
    pstart = jnp.cumsum(padded) - padded                   # padded offsets
    tile_start = pstart // bm                              # (E,)
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = (jnp.searchsorted(tile_start, t, side="right") - 1
                  ).astype(jnp.int32)
    tile_first = (t == tile_start[tile_group]).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts                   # packed offsets
    return counts, pstart, starts, tile_group, tile_first


def _pack_rows(x_flat, flat_e, flat_t, flat_g, n_groups, bm):
    """Sort assignments by expert and place them in the tile-aligned
    buffer. Returns (x_pad, row_tok, row_gate, metadata...). Unfilled
    slots keep token 0 with gate 0: computed then zeroed — wasted lanes,
    never wrong (same trick as scatter_dispatch)."""
    A = flat_e.shape[0]
    n_tiles = -(-A // bm) + n_groups
    P = n_tiles * bm

    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    sg = flat_g[order]

    counts, pstart, starts, tile_group, tile_first = _gmm_metadata(
        flat_e, n_groups, n_tiles, bm)
    pos = jnp.arange(A, dtype=jnp.int32) - starts[se]      # rank in group
    slot = pstart[se] + pos                                # unique, < P

    row_tok = jnp.zeros((P,), jnp.int32).at[slot].set(st)
    row_gate = jnp.zeros((P, 1), jnp.float32).at[slot, 0].set(sg)
    x_pad = x_flat[row_tok]
    return x_pad, row_tok, row_gate, counts, tile_group, tile_first


def _apply_activation(h: jnp.ndarray, non_linearity: str) -> jnp.ndarray:
    """The MLP nonlinearity on the packed hidden buffer (models/mlp.py
    mlp_apply semantics)."""
    if is_gated(non_linearity):
        x1, x2 = jnp.split(h, 2, axis=-1)
        gate = jax.nn.silu(x1) if non_linearity.lower() == "swiglu" \
            else jax.nn.sigmoid(x1)
        return gate * x2
    return activation(non_linearity)(h)


def _local_grouped_dispatch(x_flat, topk_idx, topk_gates, experts_fc,
                            experts_proj, *, non_linearity: str,
                            n_shared: int, expert_axis: bool,
                            bm: int, interpret: bool) -> jnp.ndarray:
    """Per-device dropless dispatch over the LOCAL expert slice.

    Expert ids are global: [0, n_shared) shared (every token, gate 1.0),
    [n_shared, n_shared + n_routed) routed. With a live 'expert' axis each
    shard keeps only assignments whose global id falls in its slice;
    non-local assignments stay in the buffer re-tagged to the last local
    group with gate 0 (zero contribution, tile-rounding FLOPs only)."""
    with context.expert_region():
        N, C = x_flat.shape
        k = topk_idx.shape[1]
        E_loc = experts_fc.shape[0]
        dt = x_flat.dtype

        lo = jnp.int32(0)
        if expert_axis:
            lo = jax.lax.axis_index("expert") * E_loc

        tok = jnp.arange(N, dtype=jnp.int32)
        ids = [jnp.full((N,), e, jnp.int32) for e in range(n_shared)]
        gts = [jnp.ones((N,), jnp.float32) for _ in range(n_shared)]
        toks = [tok for _ in range(n_shared)]
        ids.append((topk_idx + n_shared).astype(jnp.int32).reshape(-1))
        gts.append(topk_gates.astype(jnp.float32).reshape(-1))
        toks.append(jnp.repeat(tok, k))
        flat_e = jnp.concatenate(ids)
        flat_g = jnp.concatenate(gts)
        flat_t = jnp.concatenate(toks)

        local = (flat_e >= lo) & (flat_e < lo + E_loc)
        flat_e = jnp.where(local, flat_e - lo, E_loc - 1)
        flat_g = jnp.where(local, flat_g, 0.0)

        x_pad, row_tok, row_gate, counts, tile_group, tile_first = \
            _pack_rows(x_flat, flat_e, flat_t, flat_g, E_loc, bm)

        h = gmm(x_pad, experts_fc.astype(dt), tile_group, tile_first,
                counts, bm=bm, interpret=interpret)
        h = _apply_activation(h, non_linearity)
        y = gmm(h, experts_proj.astype(dt), tile_group, tile_first,
                counts, scales=row_gate, bm=bm, interpret=interpret)

        out = jnp.zeros_like(x_flat).at[row_tok].add(y)
        if expert_axis:
            out = jax.lax.psum(out, "expert")
        return out


def grouped_usable(cfg, batch_size: int, dtype) -> bool:
    """Static gate for the grouped path. False -> callers fall back to the
    'dense' combine (identical dropless semantics, E/k x the FLOPs)."""
    if getattr(cfg, "pp_stages", 1) > 1:
        # the pipeline vmaps Blocks over the layer axis; neither shard_map
        # nor pallas_call composes with that on this jax
        return False
    if context.in_expert_region() or context.in_sp_region():
        return False
    fc_out = 2 * cfg.up_dim \
        if cfg.non_linearity.lower() in ("swiglu", "glu") else cfg.up_dim
    lane = 128 if jax.default_backend() == "tpu" else 8
    if any(d % lane for d in (cfg.n_embd, cfg.up_dim, fc_out)):
        return False
    if jax.default_backend() == "tpu" and \
            jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)):
        return False
    mesh = context.get_mesh()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get("model", 1) > 1 or sizes.get("seq", 1) > 1:
            return False  # tp shards fc_out, sp shards T: scatter/dense
        if batch_size % sizes.get("data", 1):
            return False
        if cfg.n_exp % sizes.get("expert", 1):
            return False
    return True


def grouped_dispatch(x_flat: jnp.ndarray, topk_idx: jnp.ndarray,
                     topk_gates: jnp.ndarray, experts_fc: jnp.ndarray,
                     experts_proj: jnp.ndarray, *, non_linearity: str,
                     n_shared: int = 0,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Dropless grouped-matmul MoE dispatch (module docstring).

    x_flat (N, C); topk_idx/topk_gates (N, k) over the ROUTED experts;
    experts_fc/experts_proj (n_exp, ...) stacked kernels INCLUDING the
    n_shared leading shared experts. Returns shared + routed outputs
    combined, (N, C). Gate with `grouped_usable` first."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # small tiles keep the tile-rounding waste proportionate on the tiny
    # interpret-mode test shapes; hardware uses the MXU-sized default
    bm = 8 if interpret else DEFAULT_BLOCK_M

    mesh = context.get_mesh()
    local = functools.partial(
        _local_grouped_dispatch, non_linearity=non_linearity,
        n_shared=n_shared, bm=bm, interpret=interpret)

    if mesh is None or all(
            mesh.shape.get(ax, 1) <= 1 for ax in ("data", "expert")):
        return local(x_flat, topk_idx, topk_gates, experts_fc,
                     experts_proj, expert_axis=False)

    from distributed_pytorch_tpu.parallel.sharding import moe_dispatch_specs
    tok_spec, w_spec, out_spec = moe_dispatch_specs()
    body = compat.shard_map(
        functools.partial(local, expert_axis=True),
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec),
        out_specs=out_spec,
    )
    return body(x_flat, topk_idx, topk_gates, experts_fc, experts_proj)


# ---------------------------------------------------------------------------
# a chip's share of the experts, at serving batch sizes
# ---------------------------------------------------------------------------
# The dispatch above is the trainer's: every assignment is local or pads the
# last group, K is resident whole and the out-feature tile is a multiple of
# 128. A serving step hands an expert layer 64 to 320 tokens, of which a
# held expert sees a handful: the work is reading each HIT expert's two
# matrices once, at the HBM rate. So: one token tile an expert (nearly
# always), the weights streamed in large blocks under it, assignments to
# experts this chip does not hold dropped before the packing (they cost
# nothing, not even a pad row), and the trailing unused tiles mapped onto
# the last used block so that they move no bytes. Widths need not be
# multiples of 128: the 1856-wide hidden axis is a block's full extent, and
# it is never an array's minor axis. The up matrices are held (E, F, C), out
# by in, for that reason: the device lays a parameter out with a minor axis
# that is a multiple of 128 where it has one, so an (E, C, 1856) operand
# reaches the kernel through a whole-stack relayout copy (device-free
# compile, ISSUE 33: 639 MB a call), and an (E, 1856, C) one as it lies.

def _held_up_kernel(g_ref, n_ref, x_ref, w_ref, o_ref, acc_ref, *, nk: int,
                    gated: bool = False):
    """relu(x W^T)^2 of one tile; `gated`: the block is the expert's whole
    (2F, bk) slab of [a | b] as published, and the epilogue silu(a) * b
    over the two halves of the accumulator. In float32 either way."""
    del g_ref
    i, k = pl.program_id(0), pl.program_id(1)
    used = i < n_ref[0]

    @pl.when(jnp.logical_and(used, k == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(used)
    def _():
        acc_ref[...] += _dot_nt(x_ref[...], w_ref[0])

    @pl.when(k == nk - 1)
    def _():
        if gated:
            F = o_ref.shape[1]
            a, b = acc_ref[:, :F], acc_ref[:, F:]
            h = a * jax.nn.sigmoid(a) * b
        else:
            h = jnp.maximum(acc_ref[...], 0.0)   # relu(.)^2, in float32
            h = h * h
        o_ref[...] = jnp.where(used, h, 0.0).astype(o_ref.dtype)


def _held_down_kernel(g_ref, n_ref, h_ref, w_ref, s_ref, o_ref):
    del g_ref
    used = pl.program_id(0) < n_ref[0]

    @pl.when(used)
    def _():
        o_ref[...] = (_dot(h_ref[...], w_ref[0]) * s_ref[...]
                      ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _split(n: int, step: int) -> int:
    """n over 3, else over 4, where that is a whole multiple of `step`,
    else n: the streamed axis of an expert's matrix in a few blocks, so
    that the next block's DMA runs under this one's product."""
    for parts in (3, 4):
        if n % (parts * step) == 0:
            return n // parts
    return n


def _held_up_call(x_pad, w, tile_group, n_used, bm, interpret, gated=False):
    """relu(x W_up^T)^2 a tile, or for a gated stack (2F rows an expert,
    [a | b]) silu(a) * b: (P, F) either way, under a kernel name of its
    own each."""
    P, K = x_pad.shape
    F2 = w.shape[1]
    F = F2 // 2 if gated else F2
    bk = _split(K, 128)
    nk = K // bk
    # an unused tile asks for the block the last used one held: no DMA
    last = nk - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(P // bm, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k, g, n: (
                i, jnp.where(i < n[0], k, last))),
            pl.BlockSpec((1, F2, bk), lambda i, k, g, n: (
                g[i], 0, jnp.where(i < n[0], k, last))),
        ],
        out_specs=pl.BlockSpec((bm, F), lambda i, k, g, n: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, F2), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_held_up_kernel, nk=nk, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, F), x_pad.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="expert_matmul_gated_up" if gated else "expert_matmul_up",
        interpret=interpret,
    )(tile_group, n_used, x_pad, w)


def _held_down_call(h, w, gates, tile_group, n_used, bm, interpret):
    P, F = h.shape
    _, _, C = w.shape
    bn = _split(C, 128)
    nn_ = C // bn
    last = nn_ - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(P // bm, nn_),
        in_specs=[
            pl.BlockSpec((bm, F), lambda i, j, g, n: (i, 0)),
            pl.BlockSpec((1, F, bn), lambda i, j, g, n: (
                g[i], 0, jnp.where(i < n[0], j, last))),
            pl.BlockSpec((bm, 1), lambda i, j, g, n: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, g, n: (i, j)))
    return pl.pallas_call(
        _held_down_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, C), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="expert_matmul_down", interpret=interpret,
    )(tile_group, n_used, h, w, gates)


def held_tile_rows(n_tokens: int, k: int, n_routed: int) -> int:
    """Token rows a tile, from the rows a held expert expects of a call (m
    = tokens x k / router width): the power of two that holds m + 3
    sqrt(m), a count's mean and three of its deviations, 16 at the least (a
    bf16 tile's sublanes) and 128 at the most, and 128 only where the mean
    alone fills 64. A second tile of one expert reads its matrices a second
    time, and a tile's rows cost next to nothing beside that in the kernels
    (they are bound by the weight bytes); but every tile row is also a row
    of the packed buffers, a tile a held expert whatever it got, and a tile
    of 128 that the mean does not half fill costs more in those rows than
    the second tiles it saves: 320 rows at top 10 of 72 (a fused step's
    chunk and decode rows in one call, 44 expected) ran 4.7 ms a program
    faster at 64 (5,504 packed rows, 2.0 second tiles a call) than at 128
    (7,808, 0.06) (my chip run, PR 37). Top 6 of 128: 16 rows for 64 tokens
    (3 expected), 32 for a 256-row chunk (12) and for 320 (15); top 10 of
    72: 32 (8.9), 64 (35.6), 64 (44.4)."""
    m = n_tokens * k / n_routed
    return int(min(128 if m >= 64 else 64, max(16, 2 ** math.ceil(
        math.log2(m + 3.0 * math.sqrt(m))))))


_RANK_BLOCK = 128    # assignments a block of the running count


def _earlier(n: int):
    """(n, n) numpy 0/1: [i, j] = 1 where i < j. `sum_i x[i] * [i, j]` is
    the exclusive running sum of x."""
    return np.arange(n)[:, None] < np.arange(n)[None, :]


def held_packing(topk_idx: jnp.ndarray, topk_gates: jnp.ndarray, *,
                 first: int, n_held: int, bm: int):
    """Where each assignment to a held expert goes in the packed buffer, and
    what each packed row and tile is. Returns `row_tok` (P,) int32 the token
    a row holds, `row_gate` (P, 1) float32 its gate, `group` (n_tiles,)
    int32 the expert a tile belongs to, `n_used` (1,) int32 the tiles that
    hold rows, `slot_of` (N, k) int32 the packing's inverse in the router's
    order: the row assignment j of token t got, P = none. P = n_tiles x
    `bm`, n_tiles = ceil(N k / bm) + n_held. An expert's rows fill tiles of
    its own, the experts in order, an expert's rows in the order of the
    flattened (N, k) assignments (what a stable sort by expert gives); a
    pad row keeps token 0 with gate 0.0; the tiles past `n_used` repeat the
    last used tile's expert, so that they move no bytes.

    No sort, and no indexing an element at a time but ONE scatter: the chip
    walks an int32 gather or scatter index by index (3-6 ns an element: 35
    to 65 us each at the 10,880 assignments of a 1,088-row call, and a
    call had eight to ten beside a sort, my chip run, PR 58), and every
    one of them stood between the router and the first byte of expert
    weights. What is left are dense ops over the one-hot of the
    assignments, (n_held, blocks, 128) with the assignments on the lanes.
    An assignment's rank among its expert's is the exclusive running count
    along its expert's row of the one-hot: inside a block of 128 a product
    with a strictly triangular 0/1 matrix (exact at any matmul precision:
    the operands are 0 or 1 and float32 adds them), across blocks the
    running sum of the blocks' int32 totals. Its slot is its expert's
    first row + its rank, picked by the one-hot itself, and is already in
    the router's order: `slot_of` is a reshape. The one scatter writes
    (token, the gate's bits) of every kept assignment at its slot."""
    N, k = topk_idx.shape
    A = N * k
    n_tiles = -(-A // bm) + n_held
    P = n_tiles * bm
    assert P < 2 ** 24, P     # float32 holds every slot exactly
    B = _RANK_BLOCK
    nb = -(-A // B)
    i32, f32 = jnp.int32, jnp.float32

    def before(x):
        """Exclusive running sum along the last axis."""
        return jnp.sum(jnp.where(_earlier(x.shape[-1]), x[..., :, None], 0),
                       axis=-2)

    # an absent expert, a masked row (-1) and the last block's pads match
    # no held expert: a column of zeros
    e = jnp.pad(topk_idx.reshape(-1).astype(i32) - first,
                (0, nb * B - A), constant_values=-1).reshape(nb, B)
    hot = e[None] == np.arange(n_held, dtype=np.int32)[:, None, None]
    in_block = jnp.sum(hot, axis=2, dtype=i32)            # (n_held, nb)
    counts = jnp.sum(in_block, axis=1)
    padded = -(-counts // bm) * bm                        # whole tiles
    pstart = before(padded)                               # (n_held,)
    rank = jnp.einsum("gbj,ji->gbi", hot.astype(f32),
                      _earlier(B).astype(np.float32),
                      preferred_element_type=f32)
    base = (pstart[:, None] + before(in_block))[:, :, None].astype(f32)
    slot = jnp.sum(jnp.where(hot, base + rank, 0.0), axis=0).astype(i32)
    slot = jnp.where((e >= 0) & (e < n_held), slot, P)    # P: dropped
    slot = slot.reshape(-1)[:A]

    tok = np.arange(A, dtype=np.int32) // k
    gate_bits = jax.lax.bitcast_convert_type(
        topk_gates.reshape(-1).astype(f32), i32)
    rows = jnp.zeros((P, 2), i32).at[slot].set(
        jnp.stack([tok, gate_bits], axis=1), mode="drop")
    row_tok = rows[:, 0]
    row_gate = jax.lax.bitcast_convert_type(rows[:, 1:], f32)

    tile_start = pstart // bm
    n_used = jnp.sum(padded) // bm
    # a tile's expert: the last whose first tile is not past it; an unused
    # tile asks as the last used one
    t = jnp.minimum(np.arange(n_tiles, dtype=np.int32),
                    jnp.maximum(n_used - 1, 0))
    group = jnp.clip(
        jnp.sum(tile_start[None, :] <= t[:, None], axis=1, dtype=i32) - 1,
        0, n_held - 1)
    return row_tok, row_gate, group, n_used.reshape(1), slot.reshape(N, k)


def held_experts_ffn(x_flat: jnp.ndarray, topk_idx: jnp.ndarray,
                     topk_gates: jnp.ndarray, w_up: jnp.ndarray,
                     w_down: jnp.ndarray, *, first: int, n_routed: int,
                     gated: bool = False, cuts: Optional[tuple] = None,
                     interpret: Optional[bool] = None):
    """sum over a token's top-k of gate * W_down[e] relu(W_up[e] x)^2, for
    the experts e in [first, first + n_held) that `w_up` (n_held, F, C: out
    by in) and `w_down` (n_held, F, C) hold. `topk_idx` (N, k) are ids over ALL
    `n_routed` experts; what the absent ones would add is left out. Dropless.
    `gated`: `w_up` is (n_held, 2F, C), [a | b], and an expert computes
    W_down[e] (silu(a) * b). Returns ((N, C) float32, the tiles the two
    kernels ran (1,) int32: one for every expert hit and one more for
    every further `held_tile_rows` rows of its own).

    The combine is the token's: a row GATHERS the (at most k) gated rows
    its held experts wrote into the packed (P, C) float32 result, by the
    packing's inverse `slot_of` (N, k), and adds them left to right in the
    order the router returned its assignments, k - 1 written-out float32
    adds; an assignment that was dropped before the packing (absent
    expert, masked row) adds exactly 0.0. No packed row is walked that no
    token asks for (the pads), and nothing is scattered.

    `cuts` = (n_0, n_1, ...), summing to N: the rows are several row sets,
    one after the other, and the result comes back a set each, summed a
    set (the ops a set has in a call of its own). The kernels, which read
    the weights, run once over all. A row's value depends on its own k
    slots and its own rows of the kernels' result alone, and the kernels
    are bitwise blind to the rows beside a row (my chip runs, PR 37), so a
    set reads the same bit for bit in a call of its own and beside other
    sets BY CONSTRUCTION: the order of a row's terms is the program's
    text, not the pattern the compiler finds (a scatter-add over packed
    rows, which this replaced, had to be one a set to match, PR 37, and
    ran at a seventh to a quarter of the row gather's rate, PR 40)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N, C = x_flat.shape
    k = topk_idx.shape[1]
    n_held = w_up.shape[0]
    bm = held_tile_rows(N, k, n_routed)

    # the two scopes (obs/trace.py MIXER_SCOPES) are names on the ops and
    # nothing else
    with jax.named_scope("moe_pack"):
        row_tok, row_gate, group, n_used, slot_of = held_packing(
            topk_idx, topk_gates, first=first, n_held=n_held, bm=bm)
        packed = x_flat[row_tok]
    P = packed.shape[0]

    dt = x_flat.dtype
    h = _held_up_call(packed, w_up.astype(dt), group, n_used, bm, interpret,
                      gated)
    y = _held_down_call(h, w_down.astype(dt), row_gate, group, n_used, bm,
                        interpret)

    def token_sum(slots):
        """(n, k) slots of a row set -> its (n, C) float32 sum."""
        n = slots.shape[0]
        # one gather in k-major order, (k, n, C): term j of every token is
        # g[j], no relayout of a k axis; a dropped assignment's index is
        # clamped and its value selected away
        at_j = slots.T
        g = y[jnp.minimum(at_j.reshape(-1), P - 1)].reshape(k, n, C)
        terms = [jnp.where((at_j[j] < P)[:, None], g[j], 0.0)
                 for j in range(k)]
        out = terms[0]
        for term in terms[1:]:    # written out: the order is the text's
            out = out + term
        return out

    with jax.named_scope("moe_combine"):
        if cuts is None:
            return token_sum(slot_of), n_used
        outs, at = [], 0
        for n in cuts:
            outs.append(token_sum(slot_of[at:at + n]))
            at += n
        return outs, n_used
