"""Fused scaled-dot-product attention dispatch.

This is the framework's named equivalent of the reference's delegated
`F.scaled_dot_product_attention` CUDA kernel (reference
single-gpu/model.py:149). Implementations:

* 'xla'    — `jax.nn.dot_product_attention`: XLA fuses QK^T+softmax+PV and
             tiles onto the MXU; supports GQA (n_kv_heads dividing n_head)
             without materializing repeated KV.
* 'pallas' — hand-written TPU flash-attention kernel (ops/flash_attention.py),
             blockwise online softmax in VMEM.
* 'naive'  — explicit einsum path; supports attention-weight dropout, KV-cache
             offset masks (scalar or per-sequence arrays), and arbitrary
             masks. The decode fallback and the reference semantics oracle
             in tests.
* decode fast path — single-token KV-cached calls route to the split-KV
             Pallas flash-decode kernel (ops/flash_decode.py) when
             `flash_decode_usable` holds (FLASH_DECODE=auto|on|off;
             'auto' = TPU only), else fall through to 'naive'.
* 'auto'   — on a TPU, a training-shaped call (not KV-cached, static
             offset 0) takes the pallas kernel whenever its gate passes and
             it would run on each shard's own operands (`_per_shard_decline`),
             from `_FLASH_MIN_KEYS` keys up (measured at T = 256..2048,
             PERF.md section 6, PR 29); xla otherwise, with the reason
             noted. dropout>0 routes to the pallas kernel's IN-KERNEL
             dropout on TPU (round 5 — parity with CUDA SDPA dropout,
             reference model.py:149-151); non-flash shapes / non-TPU fall
             back to naive.

No path hides the device: every choice is recorded (obs/paths.py `note`,
printed beside each compiled program by the trainer and the serve CLI),
a kernel asked for BY NAME that its gate declines is an error naming the
gate (`attn_impl='pallas'`; `FLASH_DECODE=on` on a TPU backend), and on a
TPU backend no kernel call carries `interpret=True`.

Layout convention: q (B, T, nh, hs); k, v (B, S, n_kv, hs) — "BTNH", the
layout jax.nn.dot_product_attention and the Pallas kernel both want, avoiding
the reference's transpose dance to (B, nh, T, hs).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.obs import paths


# The crossover of XLA's fused attention against the flash kernels, forward
# + backward at 16,384 tokens a call, 12 heads of 64, bf16, on a v5e (PERF.md
# section 6, PR 29): XLA 2.17 ms to the kernels' 2.72 at T = 256, 4.14 to
# 2.98 at 512, 7.87 to 3.78 at 1024, 15.10 to 5.80 at 2048. Below this many
# keys a training-shaped `auto` call keeps XLA.
_FLASH_MIN_KEYS = 512

# Memory guard for the `auto` calls no chip run has measured (KV-cached
# prefill; a live 'model' or 'pipe' mesh axis): XLA's fused attention
# materialises the O(T*S) score matrix (OOM by 32k keys) while the flash
# kernel stays O(T), so beyond this many keys they take the kernel anyway.
# Not a statement about speed. Training-shaped calls on one device or a
# data-only mesh do not read it.
_XLA_SCORES_MAX_KEYS = 4096


def _on_tpu() -> bool:
    # no except: a backend that fails to start must fail the program, not
    # answer "not a TPU" and turn the run into an interpreted or XLA one
    return jax.default_backend() == "tpu"


def _decode_kernel_wanted(kernel: str, why_not) -> bool:
    """FLASH_DECODE routing for one decode-shaped call: True = run
    `kernel`. `why_not` is the usable gate's reason (None = usable).
    'auto' takes the kernel on a TPU when the gate allows and says which
    way it went; 'on' is a request by name — declined on a TPU backend it
    is an error naming the gate. Off-TPU 'on' means "interpret mode for
    the parity tests": a decline there hides no device, so the reference
    path carries the call and the choice is noted."""
    from distributed_pytorch_tpu.ops.flash_decode import decode_mode
    mode = decode_mode()
    if mode == "off" or (mode == "auto" and not _on_tpu()):
        paths.note("decode_attention", "gather+naive",
                   f"FLASH_DECODE={mode}")
        return False
    if why_not is None:
        paths.note("decode_attention", kernel, f"FLASH_DECODE={mode}")
        return True
    if mode == "on" and _on_tpu():
        raise paths.declined("FLASH_DECODE=on", f"{kernel}_usable", why_not)
    paths.note("decode_attention", "gather+naive",
               f"{kernel}_usable declined: {why_not}")
    return False


def _per_shard_decline(q) -> Optional[str]:
    """Why a Pallas call on `q` would NOT run on each shard's own operands
    under the ambient mesh — None when it would: no mesh, one device, a
    shard_map body already, or a mesh whose batch divides over 'data' with
    'model' and 'pipe' at 1 (what `_shard_map_over_data` wraps). Under a
    live 'model' or 'pipe' axis GSPMD would all-gather the operands and
    replicate the kernel on every device."""
    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if mesh is None or context.in_sp_region():
        return None
    for axis in ("model", "pipe"):
        if mesh.shape.get(axis, 1) > 1:
            return (f"mesh axis {axis!r} is live ({mesh.shape[axis]}): the "
                    "kernel would be replicated over gathered operands")
    dp = mesh.shape.get("data", 1)
    if q.shape[0] % dp != 0:
        return f"batch {q.shape[0]} does not divide over data={dp}"
    return None


def _shard_map_over_data(fn, q, has_rng: bool = False):
    """Batch-parallel shard_map wrapper for a pallas call under a live
    multi-device mesh: GSPMD cannot partition a pallas_call (it would
    replicate the compute after all-gathering the operands), so on dp/fsdp
    meshes the kernel runs per data shard with explicitly local batches.
    Returns None when no wrap is needed (single device) or when the gates
    don't hold (head-sharded tp activations, pipeline vmap bodies, batch
    not divisible) — those paths keep the unwrapped call/XLA fallback."""
    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if (mesh is None or context.in_sp_region()
            or mesh.shape.get("data", 1) <= 1
            or _per_shard_decline(q) is not None):
        return None
    from jax.sharding import PartitionSpec as P
    spec = P("data", None, None, None)

    if has_rng:
        def body(a, b, c, rng):
            with context.sp_region():   # suppress nested sp/wrap routing
                # per-data-shard masks: each shard holds different samples
                # at the same local batch rows
                rng = jax.random.fold_in(rng, jax.lax.axis_index("data"))
                return fn(a, b, c, rng)

        from distributed_pytorch_tpu import compat
        return compat.shard_map(body, mesh=mesh,
                                in_specs=(spec, spec, spec, P()),
                                out_specs=spec)

    def body(a, b, c):
        with context.sp_region():
            return fn(a, b, c)

    from distributed_pytorch_tpu import compat
    return compat.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec)


def _naive_sdpa(q, k, v, *, scale, q_offset, dropout_rate=0.0,
                dropout_rng=None, causal=True):
    """Reference-semantics einsum attention with cache-offset causal mask.

    Mask matches reference model.py:225-226: query global position =
    q_offset + i may attend key positions j <= q_offset + i. `q_offset`
    may be a per-sequence (B,) array (slot-based ragged decode: each
    sequence in the batch sits at its own cache position).
    """
    B, T, nh, hs = q.shape
    S, nkv = k.shape[1], k.shape[2]
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    attn = jnp.einsum("btnh,bsnh->bnts", qf, kf) * scale
    if causal:
        qpos = (jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1, 1, 1))
                + jnp.arange(T)[None, :, None])     # (B|1, T, 1)
        kpos = jnp.arange(S)[None, None, :]
        mask = qpos >= kpos  # (B|1, T, S)
        attn = jnp.where(mask[:, None], attn, -jnp.inf)
    attn = jax.nn.softmax(attn, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, attn.shape)
        attn = jnp.where(keep, attn / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum("bnts,bsnh->btnh", attn.astype(v.dtype), v)
    return out


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
         causal: bool = True,
         scale: Optional[float] = None,
         q_offset: int | jnp.ndarray = 0,
         dropout_rate: float = 0.0,
         dropout_rng=None,
         impl: str = "auto",
         decode: bool = False,
         k_scale: Optional[jnp.ndarray] = None,
         v_scale: Optional[jnp.ndarray] = None,
         block_tables: Optional[jnp.ndarray] = None,
         n_kv_heads: int = 0) -> jnp.ndarray:
    """Scaled dot-product attention over (B, T, N, H)-layout tensors.

    `q_offset` is the global position of q[:, 0] (nonzero during KV-cached
    decode, cf. reference start_pos plumbing at model.py:641-650).
    `decode=True` marks a KV-cached call (prefill or single-token): it is
    exempt from the ring/ulysses fail-loud check below — decoding is never
    sequence-parallel, even when a prompt exactly fills the cache and the
    shapes look like a training step.

    `k_scale`/`v_scale` (B, S, n_kv, 1) mark an int8-quantized KV cache
    (ops/quant.py): k/v hold int8 codes. The flash-decode kernel
    dequantizes in VMEM (half the cache DMA); every other path
    dequantizes the buffers up front and proceeds unchanged.

    `block_tables` (B, max_blocks) int32 marks k/v (and the scale
    sidecars) as PAGED pools (ops/block_pool.py): single-token decode
    routes to the paged flash kernel (block-table scalar prefetch — no
    gather, no full-buffer stream); every other path materializes the
    logical per-sequence view with one `paged_gather` and proceeds
    unchanged — the gathered view holds identical values at identical
    logical positions, so downstream numerics match the contiguous cache.
    Float pools are the merged-lane (n_blocks, bs, L) leaves
    (`block_pool.kv_lanes`), whose shape no longer says how many heads
    share the lanes: `n_kv_heads` does (int8 pools keep the head axis).
    """
    hs = q.shape[-1]
    scale = (1.0 / hs ** 0.5) if scale is None else scale

    if impl not in ("auto", "pallas", "xla", "naive", "ring", "zigzag",
                    "ulysses"):
        raise ValueError(f"unknown attention impl {impl!r}; expected "
                         "'auto' | 'pallas' | 'xla' | 'naive' | 'ring' | "
                         "'zigzag' | 'ulysses'")

    use_dropout = dropout_rate > 0.0 and dropout_rng is not None

    if block_tables is not None:
        # paged KV cache: kernel first (single-token decode), else gather
        # the logical view and fall through to the shared routing below
        if (decode and causal and q.shape[1] == 1 and not use_dropout
                and impl in ("auto", "pallas", "xla")):
            from distributed_pytorch_tpu.ops.flash_decode import (
                paged_flash_decode, paged_flash_decode_decline)
            if _decode_kernel_wanted(
                    "paged_flash_decode",
                    paged_flash_decode_decline(q, k, v, block_tables,
                                               n_kv_heads)):
                cl = jnp.broadcast_to(jnp.reshape(
                    jnp.asarray(q_offset, jnp.int32), (-1,)) + 1,
                    (q.shape[0],))
                out = paged_flash_decode(q[:, 0], k, v, block_tables, cl,
                                         scale=scale, n_kv_heads=n_kv_heads,
                                         k_scale=k_scale,
                                         v_scale=v_scale,
                                         interpret=not _on_tpu())
                return out[:, None]
        # mixed prefill+decode path: a multi-token chunk (or a whole
        # bucketed-wave suffix) of ONE sequence, written at q_offset and
        # attending causally over the sequence's own prior blocks — the
        # chunk kernel streams those blocks through the table prefetch
        # instead of gathering the whole logical view
        if (decode and causal and q.shape[1] > 1 and q.shape[0] == 1
                and not use_dropout and impl in ("auto", "pallas", "xla")):
            from distributed_pytorch_tpu.ops.flash_decode import (
                paged_flash_prefill, paged_flash_prefill_decline)
            if _decode_kernel_wanted(
                    "paged_flash_prefill",
                    paged_flash_prefill_decline(q, k, v, block_tables,
                                                n_kv_heads)):
                off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1,))[0]
                return paged_flash_prefill(q, k, v, block_tables, off,
                                           scale=scale,
                                           n_kv_heads=n_kv_heads,
                                           k_scale=k_scale,
                                           v_scale=v_scale,
                                           interpret=not _on_tpu())
        from distributed_pytorch_tpu.ops.block_pool import paged_gather
        heads = (n_kv_heads or k.shape[2], q.shape[-1])
        k = paged_gather(k, block_tables, heads)
        v = paged_gather(v, block_tables, heads)
        if k_scale is not None:
            k_scale = paged_gather(k_scale, block_tables)
            v_scale = paged_gather(v_scale, block_tables)
        if k.dtype != jnp.int8:
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)

    # KV-cached single-token decode: the memory-bound fast path. The
    # split-KV Pallas kernel (ops/flash_decode.py) streams each sequence's
    # VALID cache rows exactly once (per-sequence cache_len scalar-prefetch
    # skips dead slots entirely) instead of the naive einsum's full-buffer
    # read + per-query-head K/V repeat. _decode_kernel_wanted holds the
    # FLASH_DECODE contract (auto chooses and says so; on is by name).
    if (decode and causal and q.shape[1] == 1 and not use_dropout
            and impl in ("auto", "pallas", "xla")):
        from distributed_pytorch_tpu.ops.flash_decode import (
            flash_decode, flash_decode_decline)
        if _decode_kernel_wanted("flash_decode",
                                 flash_decode_decline(q, k, v)):
            # valid rows per sequence: the query's global position + 1,
            # capped at the buffer length (ring cache wrapped)
            cl = jnp.minimum(
                jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1,)) + 1,
                k.shape[1])
            cl = jnp.broadcast_to(cl, (q.shape[0],))
            out = flash_decode(q[:, 0], k, v, cl, scale=scale,
                               k_scale=k_scale, v_scale=v_scale,
                               interpret=not _on_tpu())
            return out[:, None]

    if k_scale is not None:
        # int8 cache on a non-kernel path (prefill, kernel gate declined,
        # FLASH_DECODE=off): dequantize up front — identical semantics to
        # a bf16 cache holding the dequantized values, more HBM traffic.
        from distributed_pytorch_tpu.ops.quant import dequantize_int8
        k = dequantize_int8(k, k_scale, q.dtype)
        v = dequantize_int8(v, v_scale, q.dtype)

    # Sequence parallelism: when the ambient mesh (parallel/context.py) has
    # a live 'seq' axis and shapes allow, full-sequence causal attention
    # runs as ring/Ulysses over explicit 'seq' collectives instead of
    # letting GSPMD all-gather the whole sequence per device.
    # NOTE: this routing is a trace-time decision — the ambient mesh is not
    # part of jax.jit's cache key. Callers must establish context.use_mesh
    # BEFORE the first (tracing) call of their jitted function, as the
    # trainer's step builders do (train/step.py); a function first traced
    # without the mesh keeps its GSPMD full-gather path.
    from distributed_pytorch_tpu.parallel import context
    sp = context.seq_axis_size()
    sp_live = sp > 1 and not context.in_sp_region()

    if sp_live and impl in ("auto", "ring", "zigzag", "ulysses"):
        static_zero = isinstance(q_offset, int) and q_offset == 0
        mesh = context.get_mesh()
        dp = mesh.shape["data"]
        T, S, B = q.shape[1], k.shape[1], q.shape[0]
        sp_ok = (causal and static_zero and T == S and T % sp == 0
                 and B % dp == 0 and T // sp > 0)
        if sp_ok:
            from distributed_pytorch_tpu.ops.ring_attention import sp_sdpa
            if impl == "ulysses":
                sp_impl = "ulysses"
            elif impl == "ring":
                sp_impl = "ring"      # explicit: contiguous schedule
            else:                     # 'auto'/'zigzag': load-balanced
                sp_impl = "zigzag"    # (falls back to ring inside when
                                      # the stripe split doesn't divide)
            if (sp_impl == "ulysses"
                    and (q.shape[2] % sp or k.shape[2] % sp)):
                sp_impl = "zigzag"  # head counts not sp-divisible
            # dropout composes with sp since round 5: the ring/zig-zag
            # einsum hops draw a global-position-keyed mask (sp_sdpa);
            # ulysses reroutes to zigzag inside when rate > 0
            return sp_sdpa(q, k, v, scale=scale, causal=causal,
                           impl=sp_impl,
                           dropout_rate=dropout_rate if use_dropout else 0.0,
                           dropout_rng=dropout_rng)
    if impl in ("ring", "zigzag", "ulysses"):
        # De-trap (round-3 VERDICT #9): an explicit ring/ulysses request
        # on training-like shapes (full causal self-attention) with NO
        # live 'seq' axis means the caller traced without
        # context.use_mesh — the old silent GSPMD-full-gather fallback
        # hid exactly the bug the ambient-mesh design risks. Fail loud.
        # Decode-shaped calls (T != S, cache offsets) legitimately fall
        # back: decoding isn't sequence-parallel even in sp training.
        training_like = (causal and not decode
                         and q.shape[1] == k.shape[1]
                         and q.shape[1] > 1
                         and isinstance(q_offset, int) and q_offset == 0)
        if training_like and sp <= 1 and not context.in_sp_region():
            raise ValueError(
                f"attn_impl={impl!r} requested but no live 'seq' mesh "
                "axis is visible at trace time. Establish the mesh "
                "around tracing (parallel.context.use_mesh, as the "
                "trainer's step builders do) or use the 'sp' recipe; "
                "a silent fallback here would lose sequence "
                "parallelism without any signal.")
        impl = "auto"  # shapes don't allow sp (e.g. decode steps)

    static_zero = isinstance(q_offset, int) and q_offset == 0

    def flash_why_not():
        """Why the flash kernel cannot take this call (None = it can)."""
        from distributed_pytorch_tpu.ops.flash_attention import \
            flash_attention_decline
        if not static_zero:
            return "q_offset is traced or nonzero (a KV-cached call)"
        return flash_attention_decline(q, k, v, causal=causal)

    def run_flash():
        from distributed_pytorch_tpu.ops.flash_attention import (
            flash_attention, slab_plan)
        plan, share = slab_plan(q.shape[1], k.shape[1], causal)
        slabs = (f"{plan[1]} causal slabs of {plan[0]} rows a diagonal tile"
                 if plan else "no slabs")
        paths.note("attention", "pallas flash",
                   f"attn_impl={impl}; {slabs}: {share:.1%} of the score "
                   "square computed")
        if use_dropout:
            def fn(a, b, c, rng):
                return flash_attention(a, b, c, scale=scale, causal=causal,
                                       dropout_rate=dropout_rate,
                                       dropout_rng=rng)
            wrapped = _shard_map_over_data(fn, q, has_rng=True)
            if wrapped is not None:
                return wrapped(q, k, v, dropout_rng)
            return fn(q, k, v, dropout_rng)
        fn = functools.partial(flash_attention, scale=scale, causal=causal)
        wrapped = _shard_map_over_data(fn, q)
        if wrapped is not None:
            return wrapped(q, k, v)
        return fn(q, k, v)

    if impl == "pallas" and not decode:
        # asked for BY NAME on a training/prefill-shaped call: honoured
        # or an error naming the gate — never a quiet XLA run under the
        # kernel's name. (KV-cached decode calls are outside the flash
        # kernel's contract; FLASH_DECODE governs their kernels above.)
        why = flash_why_not()
        if why is not None:
            raise paths.declined("attn_impl='pallas'",
                                 "flash_attention_usable", why)
        return run_flash()

    if use_dropout:
        # the flash kernel applies attention-weight dropout IN-KERNEL
        # (round-5: mask bits regenerated per tile, never in HBM) — the
        # reference's fused-SDPA-with-dropout equivalent (model.py:149-151).
        # XLA's fused attention has no dropout, so non-flash shapes fall to
        # the naive einsum path; honoring the caller's dropout beats
        # honoring their impl choice.
        if impl in ("auto", "pallas") and _on_tpu():
            why = flash_why_not()
            if why is None:
                return run_flash()
            paths.note("attention", "naive einsum",
                       f"dropout; flash_attention_usable declined: {why}")
        else:
            paths.note("attention", "naive einsum",
                       f"dropout; attn_impl={impl}, backend "
                       f"{jax.default_backend()}")
        impl = "naive"
    elif impl == "auto":
        if _on_tpu():
            long = k.shape[1] > _XLA_SCORES_MAX_KEYS
            why = flash_why_not()
            if why is not None:
                why = f"flash_attention_usable declined: {why}"
            elif not long:
                # a training-shaped call takes the kernel wherever it runs
                # on each shard's own operands: from _FLASH_MIN_KEYS up,
                # forward + backward beat XLA's materialised [B, nh, T, S]
                # scores at every measured T (512..2048 at 12 x 64, 1024 at
                # 25 x 64). A KV-cached call and a live 'model'/'pipe' axis
                # were measured by nobody and keep XLA up to the guard.
                if decode:
                    why = "a KV-cached call"
                elif k.shape[1] < _FLASH_MIN_KEYS:
                    why = (f"{k.shape[1]} keys < {_FLASH_MIN_KEYS}: XLA's "
                           "fused attention measured faster")
                else:
                    why = _per_shard_decline(q)
            if why is None:
                return run_flash()
            if long or not decode:
                paths.note("attention", "xla", f"auto: {why}")
        elif not decode:
            paths.note("attention", "xla",
                       f"auto: backend {jax.default_backend()}")
        impl = "xla"
    elif impl == "pallas":
        # decode=True: a KV-cached prefill may still use the flash kernel
        # when its offset is a static 0 and the shapes tile
        if flash_why_not() is None:
            return run_flash()
        impl = "xla"
    elif impl == "xla" and not decode:
        paths.note("attention", "xla", "attn_impl=xla")

    if impl == "xla":
        if static_zero:
            return jax.nn.dot_product_attention(
                q, k, v, scale=scale, is_causal=causal, implementation="xla")
        impl = "naive"  # offset masks -> explicit path

    return _naive_sdpa(q, k, v, scale=scale, q_offset=q_offset,
                       dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                       causal=causal)
