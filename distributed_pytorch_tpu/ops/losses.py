"""Cross-entropy losses for the weight-tied LM head.

The reference computes `F.cross_entropy(logits.view(-1, V), targets)` over
fully materialized logits (reference single-gpu/model.py:687-692). At
GPT-vocab scale that materialization is the single biggest activation in the
step: (B, T, V) fp32 is ~3.3 GB for B=16, T=1024, V=50304 — plus the
log-softmax intermediate and d_logits in backward. On a v5e this
memory-bound tail was the prime suspect for the round-3 MFU gap
(VERDICT round 3, weak #1).

`fused_cross_entropy` never materializes the full logits: the sequence axis
is split into chunks and a `lax.scan` computes each chunk's
`logsumexp(logits) - logit[target]`, so at most one (B, chunk, V) block
exists at a time. It has a differentiation rule of its own (PERF.md section
6, PR 47): under a gradient the SAME scan pulls `1 / count` back through
each block while its logits exist, emits `dx` chunk by chunk and carries
`dW`; backward only scales the pair by the loss's cotangent. Left to
autodiff under `jax.checkpoint` backward built and reduced every float32
block a second time: a fourth head matmul and six passes over a block
where four do. Undifferentiated (evaluation) it is one matmul a chunk. The
lm-head matmul itself runs in the compute dtype with fp32 accumulation
(`preferred_element_type`), which is MXU-native and slightly *better*
numerics than the reference's cast-then-log_softmax.

Sharding: chunking slices T while keeping the (B, chunk) token dims, so a
'data'-sharded batch stays sharded inside every chunk (all devices active
every scan iteration) and GSPMD's handling of a sharded embedding (tp
vocab-parallel psum, fsdp all-gather — hoisted out of the scan as
loop-invariant) is unchanged. Under a live 'seq' axis
`sp_fused_cross_entropy` runs the same chunk scan per device over the
LOCAL T shard inside shard_map, each over the psum of the valid counts,
and psums the per-device sums — no seq-sharded full-logits
materialization.

`tied_head_loss` is what the model calls: which of the three a program runs
(`loss_impl`, the mesh's axes, the shapes) is decided there and nowhere
else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu import compat
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops.collective_matmul import maybe_overlap_matmul
from distributed_pytorch_tpu.parallel import context


def _default_logits(x: jnp.ndarray, embedding: jnp.ndarray) -> jnp.ndarray:
    """x (..., C) @ embedding^T (V, C) -> (..., V) fp32 — the plain GSPMD
    lm-head matmul. Callers may override with `logits_fn` (`tied_head_loss`
    routes the collective-matmul ring through it under OVERLAP=on)."""
    return jax.lax.dot_general(
        x, embedding, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def unchunked_cross_entropy(x: jnp.ndarray, embedding: jnp.ndarray,
                            targets: jnp.ndarray, *,
                            ignore_index: int = -1,
                            logits_fn=None) -> jnp.ndarray:
    """Mean CE over valid targets, full (B, T, V) logits (semantics oracle;
    mirrors reference model.py:687-692 incl. ignore_index=-1)."""
    logits = (logits_fn or _default_logits)(x, embedding)  # (B, T, V) fp32
    mask = targets != ignore_index
    safe = jnp.where(mask, targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1)
    return jnp.where(mask, nll, 0.0).sum() / denom


def _chunk_for(T: int, V: int, target_tokens: int = 128,
               min_chunk: int = 16) -> int:
    """Largest divisor of T that is <= target_tokens (0 = don't chunk).

    Chunking only pays when the full logits block is big; tiny vocabularies
    (tests) or short sequences skip it so the scan overhead never hurts the
    small-model path. A divisor below `min_chunk` (awkward T, e.g. prime)
    would degrade to a near-per-token scan — fall back to unchunked
    instead."""
    if T <= target_tokens or V < 8192:
        return 0
    for c in range(target_tokens, min_chunk - 1, -1):
        if T % c == 0 and T // c > 1:
            return c
    return 0


def _resolve_chunk(T: int, V: int, chunk: int) -> int:
    """The chunk the scan runs with: `chunk` (0 = `_chunk_for`'s choice) if
    it splits T into more than one block, else 0 = nothing to chunk."""
    if chunk <= 0:
        chunk = _chunk_for(T, V)
    return chunk if chunk > 0 and T % chunk == 0 and T // chunk > 1 else 0


def _block_nll_sum(x_c, embedding, t_c, ignore_index, logits_fn):
    """Sum of `logsumexp(logits) - logit[target]` over one block's valid
    targets; the (B, chunk, V) logits, their row max and lse in fp32."""
    logits = (logits_fn or _default_logits)(x_c, embedding)
    mask = t_c != ignore_index
    safe = jnp.where(mask, t_c, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.where(mask, lse - tgt, 0.0).sum()


def _as_chunks(x, targets, chunk):
    """(n_chunks, B, chunk, ...): the scan iterates T-slices, B stays a real
    dim so its 'data' sharding survives inside every chunk."""
    B, T, C = x.shape
    n_chunks = T // chunk
    return (jnp.moveaxis(x.reshape(B, n_chunks, chunk, C), 1, 0),
            jnp.moveaxis(targets.reshape(B, n_chunks, chunk), 1, 0))


def _varying_like(a, like):
    """`a` typed to vary over the mesh axes `like` varies over (a checked
    shard_map's vma typing: a scan carry has its updates' type, a cotangent
    its primal's); identity everywhere else."""
    return compat.pcast_varying(a, compat.vma_of(like) - compat.vma_of(a))


_PLAIN_SCAN = "fused, plain scan"
_GRADS_IN_FORWARD = "fused, gradients in the forward scan"


def _note(path, replaces, xs):
    paths.note("loss", path, f"{xs.shape[0]} chunks of {xs.shape[2]} tokens",
               replaces=replaces)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scan_mean_nll(x, embedding, targets, denom, ignore_index, chunk,
                   logits_fn):
    """Sum of nll over valid targets / `denom`, one T-chunk at a time. This
    body is the PRIMAL (no gradient taken: evaluation, the benchmark's
    check): one head matmul a chunk and nothing kept."""
    xs, ts = _as_chunks(x, targets, chunk)
    _note(_PLAIN_SCAN, ("fused",), xs)

    # accumulate via stacked scan OUTPUTS, not the carry: a scalar-zero
    # carry would be unvarying over the mesh axes while the chunk sums vary
    # (shard_map vma typing), and (n_chunks,) scalars are free
    def body(carry, xt):
        return carry, _block_nll_sum(xt[0], embedding, xt[1], ignore_index,
                                     logits_fn)

    _, sums = jax.lax.scan(body, None, (xs, ts))
    return sums.sum() / denom


def _scan_mean_nll_fwd(x, embedding, targets, denom, ignore_index, chunk,
                       logits_fn):
    """The rule under a gradient: each chunk's `(softmax - onehot) * mask /
    denom` is pulled back through the block WHILE its logits exist, so the
    block the value read is the block the gradient reads. The loss is the
    last thing forward does and its value a mean whose cotangent is a
    scalar: nothing is gained by waiting for backward, which would have to
    build every block again. `jax.vjp` of the block, not hand-written
    matmuls: a `logits_fn` keeps its own transpose and a sharded embedding
    its propagation."""
    xs, ts = _as_chunks(x, targets, chunk)
    _note(_GRADS_IN_FORWARD, ("fused", _PLAIN_SCAN), xs)
    scale = 1.0 / denom

    def body(dW, xt):
        x_c, t_c = xt
        s, pull = jax.vjp(
            lambda a, e: _block_nll_sum(a, e, t_c, ignore_index, logits_fn),
            x_c, embedding)
        dx_c, dW_c = pull(_varying_like(scale, s))
        return dW + dW_c, (s, dx_c)

    # the accumulator in the embedding's dtype, as autodiff's own was: a
    # float32 one is better numerics for 154 MB more traffic a chunk, and
    # read +0.6 ms a step in the train cell (PERF.md section 6, PR 47)
    dW0 = _varying_like(jnp.zeros_like(embedding), embedding)
    dW, (sums, dxs) = jax.lax.scan(body, dW0, (xs, ts))
    dx = jnp.moveaxis(dxs, 0, 1).reshape(x.shape)
    return sums.sum() / denom, (dx, dW)


def _scan_mean_nll_bwd(ignore_index, chunk, logits_fn, res, g):
    dx, dW = res
    return ((g * dx).astype(dx.dtype), (g * dW).astype(dW.dtype), None, None)


_scan_mean_nll.defvjp(_scan_mean_nll_fwd, _scan_mean_nll_bwd)


def sp_fused_cross_entropy(x: jnp.ndarray, embedding: jnp.ndarray,
                           targets: jnp.ndarray, *,
                           ignore_index: int = -1,
                           chunk: int = 0) -> jnp.ndarray:
    """Sequence-parallel chunked CE: each device chunk-scans its LOCAL
    (B/dp, T/sp) token shard inside shard_map over the psum of the valid
    counts, then the per-device sums are psum'd over ('data', 'seq') for
    the global mean.

    This replaces the round-4 fallback where any live 'seq' axis demoted
    the loss to unchunked full-logits CE — a (B, T/sp, V) fp32
    materialization per device, the largest activation at GPT vocab and
    exactly the long-context configs sp exists for (round-4 VERDICT
    weak #6). Here every device stays active through its own chunk scan
    and at most (B/dp, chunk, V) logits exist per device at a time.

    Callers gate on: live 'seq' axis, no vocab-parallel embedding (tp —
    the replicated in_spec would all-gather a 'model'-sharded embedding),
    and B divisible by dp (`tied_head_loss`)."""
    mesh = context.get_mesh()
    assert mesh is not None and context.seq_axis_size() > 1

    def local_body(x_l, emb, t_l):
        # the caller's chunk is sized against the GLOBAL T; inside
        # shard_map the shard is T/sp, so a non-dividing chunk must be
        # re-derived locally (not silently degrade to one full-logits
        # block — the exact materialization this path removes)
        t_local, V = x_l.shape[1], emb.shape[0]
        c = (_resolve_chunk(t_local, V, chunk)
             or _resolve_chunk(t_local, V, 0))
        # each device's sum over the GLOBAL valid count
        n = jax.lax.psum((t_l != ignore_index).sum(), ("data", "seq"))
        denom = jnp.maximum(n, 1)
        # the replicated embedding typed as the shard's rows are: its
        # gradient is then summed over the mesh once, behind the scan
        emb = _varying_like(emb, x_l)
        if c:
            s = _scan_mean_nll(x_l, emb, t_l, denom, ignore_index, c, None)
        else:   # tiny local T or V: one block under plain autodiff
            s = _block_nll_sum(x_l, emb, t_l, ignore_index, None) / denom
        return jax.lax.psum(s, ("data", "seq"))

    fn = compat.shard_map(
        local_body, mesh=mesh,
        in_specs=(P("data", "seq", None), P(None, None), P("data", "seq")),
        out_specs=P())
    return fn(x, embedding, targets)


def fused_cross_entropy(x: jnp.ndarray, embedding: jnp.ndarray,
                        targets: jnp.ndarray, *,
                        ignore_index: int = -1,
                        chunk: int = 0, logits_fn=None) -> jnp.ndarray:
    """Chunked weight-tied CE: logits are computed one T-chunk at a time,
    ONCE (under a gradient the chunk's `dx` and `dW` are taken in the same
    scan step, `_scan_mean_nll_fwd`); the (B, T, V) block never exists.

    x: (B, T, C) hidden states (compute dtype); embedding: (V, C);
    targets: (B, T) int with `ignore_index` masking. `chunk=0` picks a
    divisor of T automatically (or falls back to the unchunked oracle when
    chunking can't help). `logits_fn(x_chunk, embedding)` overrides the
    per-chunk lm-head matmul (collective-matmul routing,
    `tied_head_loss`).
    """
    chunk = _resolve_chunk(x.shape[1], embedding.shape[0], chunk)
    if not chunk:
        return unchunked_cross_entropy(x, embedding, targets,
                                       ignore_index=ignore_index,
                                       logits_fn=logits_fn)
    denom = jnp.maximum((targets != ignore_index).sum(), 1)
    return _scan_mean_nll(x, embedding, targets, denom, ignore_index, chunk,
                          logits_fn)


def _ring_or_plain_logits(x_c: jnp.ndarray, emb: jnp.ndarray) -> jnp.ndarray:
    """lm-head gather as a collective matmul (the (V, C) embedding is the
    largest single param ZeRO-3 shards): under OVERLAP=on the per-chunk
    logits matmul rings the vocab shards; the dispatcher declines
    everywhere else and the default plain matmul is bit-identical."""
    y = maybe_overlap_matmul(x_c, emb, names=("tkn_emb", "embedding"),
                             transpose_b=True, out_dtype=jnp.float32)
    return y if y is not None else _default_logits(x_c, emb)


def tied_head_loss(x: jnp.ndarray, embedding: jnp.ndarray,
                   targets: jnp.ndarray, *, impl: str,
                   chunk: int) -> jnp.ndarray:
    """Mean CE of the weight-tied head over valid targets (ignore_index
    -1, reference model.py:559-560, :689), fp32-accumulated: `impl`
    (`LLMConfig.loss_impl`) 'fused' or 'unchunked', `chunk` the T-chunk of
    'fused' (0 = auto). The census entry `loss` says what ran: the chunk
    scan replaces the note below with the rule it ran, gradients in the
    forward scan under a gradient and the plain scan otherwise.

    Under a live 'seq' axis 'fused' chunks over the LOCAL T shard inside
    shard_map (`sp_fused_cross_entropy`) instead of materializing
    seq-sharded full logits; its gates: no vocab-parallel embedding, B
    divisible by dp, T by sp. Where one declines, the full-logits oracle
    runs."""
    paths.note("loss", impl, f"loss_impl={impl}")
    sp = context.seq_axis_size()
    if impl == "fused" and sp > 1:
        mesh = context.get_mesh()
        tp, dp = mesh.shape.get("model", 1), mesh.shape.get("data", 1)
        if tp == 1 and x.shape[0] % dp == 0 and x.shape[1] % sp == 0:
            return sp_fused_cross_entropy(x, embedding, targets, chunk=chunk)
        impl = "unchunked"
    if impl == "fused":
        return fused_cross_entropy(x, embedding, targets, chunk=chunk,
                                   logits_fn=_ring_or_plain_logits)
    return unchunked_cross_entropy(x, embedding, targets,
                                   logits_fn=_ring_or_plain_logits)
