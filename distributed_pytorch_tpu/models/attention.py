"""Attention flavors: GQA (unifying MHA/MQA/GQA) and Multi-head Latent
Attention (MLA): the classic block's two teaching versions, with and
without decoupled RoPE, and the pattern's published one.

Reference parity map:
* `GQA`      — reference single-gpu/model.py:98-155 (fused qkv projection,
               optional RoPE, KV-cache append, SDPA).
* `NaiveMLA` — reference `NaiveMHLA` model.py:157-235 (MLA without RoPE,
               latent KV cache).
* `FullMLA`  — reference `FullMHLA` model.py:237-345 (DeepSeek-V2 MLA with
               decoupled RoPE: NoPE content path + single shared rotary key
               head; scores scaled by 1/sqrt(hs+dhr); cache {'c_kv','k_r'}).
* `Attention` — dispatch (model.py:347-363): mha/mqa/gqa -> GQA; mla ->
               NaiveMLA (pos_emb != 'rope') or FullMLA (pos_emb == 'rope').
* `LatentAttention` — no reference twin: a patterned model's 'L' layer, MLA
               as DeepSeek-V2/V3 and their descendants publish it (an
               RMSNorm on each latent, a head `[nope | rope]` / value wide,
               one shared rotated key head), over a paged pool of latent
               rows with kernels of its own (ops/latent_attention.py).

TPU-first design notes (intentional divergences, documented per SURVEY §7;
notes 1 and 2 are about `NaiveMLA` / `FullMLA`, the classic block's MLA.
`LatentAttention` has the two forms too, and chooses by what a call is, not
by train / eval: one token of every slot attends ABSORBED over cached rows,
a chunk UP-PROJECTS the rows it reads, and a test holds the two equal):

1. **Training path materializes per-head K/V** from the latents and calls the
   fused SDPA/flash kernel — large batched matmuls that tile onto the MXU —
   instead of the reference's chain of small latent-space matmuls with an
   explicitly materialized O(T^2) mask (model.py:225-226,333-334).

2. **Weight absorption** (reference model.py:178-202,283-297) becomes the
   *decode* path: queries are pulled into the KV-latent space
   (q_abs = q @ W_uk_h^T) so each new token attends directly over the cached
   compressed c_kv, and per-head outputs are expanded back through W_uv
   before W_o. Unlike the reference — whose absorbed matrices double-apply
   the query down/up projections in `NaiveMHLA` (k_eff includes
   W_dq^T W_uq^T, model.py:196) and fold W_o into a per-head output slice
   (model.py:197) — this absorption is the algebraically exact DeepSeek-V2
   rewrite, so materialized-vs-absorbed equivalence is asserted by unit test
   (tests/test_mla.py) rather than guarded by a VAL_RUN flag (the
   reference's "16 hrs to debug" train/eval divergence, model.py:195,290).

3. Functional, static-shape KV caches: fixed (B, S_max, ...) buffers updated
   with `dynamic_update_slice` at position `pos`, because XLA requires static
   shapes — replacing the reference's concat-and-grow caches (model.py:137-142).

4. Paged decode caches (ops/block_pool.py): when `block_tables` is passed,
   the cache leaves are (n_blocks, block_size, ...) POOLS shared by every
   sequence, and writes/reads indirect through per-sequence block tables —
   `paged_update` replaces the ring write, the flash kernel prefetches the
   table, and the naive/absorbed paths read a `paged_gather`ed logical view
   (identical values at identical logical positions, so they are
   bit-compatible with the contiguous cache). A float k/v pool is
   (n_blocks, block_size, L): the kv heads merged into one lane axis, L =
   n_kv_heads * head_size rounded up to a multiple of 128 — the shape
   whose default device layout, XLA's in-place row write and the Pallas
   operand layout are one and the same dense row-major order, so a
   serving step never copies a pool (`block_pool.kv_lanes` has the
   reasoning). The contiguous path below stays for training and the
   one-shot generate oracle. The classic MLA's latent pools keep their
   two leaves and reach no kernel; an 'L' layer's pool is ONE leaf of
   latent rows, `[c | rope(k_r) | 0]` in whole 128-lane tiles and with no
   head axis (`ops.latent_attention.row_lanes`), for the same reason.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.ops.attention_core import sdpa
from distributed_pytorch_tpu.ops.mup import times
from distributed_pytorch_tpu.ops.rope import (apply_partial_rotary,
                                              apply_rotary_emb, rope_angles,
                                              slice_rows)

Cache = dict[str, jnp.ndarray]

_DENSE_INIT = nn.initializers.normal(stddev=0.02)


class _OverlapDense(nn.Module):
    """nn.Dense twin (identical param tree — kernel/bias under this
    module's name — init, and dtype semantics) whose matmul is offered to
    the collective-matmul dispatcher (ops/collective_matmul.py) first.

    Used for the fused qkv and attention out-projection: under an active
    OVERLAP=on ZeRO-3 step their param all-gathers run as ppermute rings
    fused with the matmul (closing the round-6 ROADMAP gap — the MLP and
    lm-head already ring; these two call sites were the last GSPMD-default
    gathers). Everywhere else the dispatcher declines and the plain `@`
    below is bit-identical to nn.Dense."""

    features: int
    dtype: Any = jnp.float32
    use_bias: bool = True
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _DENSE_INIT,
                            (x.shape[-1], self.features), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), self.param_dtype) \
            if self.use_bias else None
        kd = kernel.astype(self.dtype)
        # weight-only int8 decode (ops/quant.py): when the engine's step
        # runs under use_quantized_params, the matmul reads int8 codes +
        # per-output-channel scales instead of the bf16 kernel; everywhere
        # else the lookup misses and nothing changes
        from distributed_pytorch_tpu.ops.quant import maybe_quantized_matmul
        y = maybe_quantized_matmul(x, (*self.path, "kernel"))
        if y is None:
            from distributed_pytorch_tpu.ops.collective_matmul import (
                maybe_overlap_matmul)
            y = maybe_overlap_matmul(x, kd, names=(self.name, "kernel"))
        if y is None:
            y = x @ kd
        return y if bias is None else y + bias.astype(self.dtype)


#: a zero-centred norm's weights as drawn (`cfg.norm_zero_centred`): a
#: trained model's are not zero, and zeros would leave `1 + w` and `1` the
#: same program
ZERO_CENTRED_INIT = nn.initializers.normal(stddev=0.1)


def _head_rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float,
                   zero_centred: bool = False) -> jnp.ndarray:
    """RMSNorm over the lanes of every head of (B, T, heads, hs), in
    float32, times one learned (hs,) vector w (`zero_centred`: 1 + w)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    return (xf * (1.0 + w if zero_centred else w)).astype(x.dtype)


def _update_cache(cache_arr: jnp.ndarray, new: jnp.ndarray, pos) -> jnp.ndarray:
    """Write `new` (B, T, ...) into the static buffer at [:, pos:pos+T].

    `pos` is the GLOBAL token position: a static int (prefill), a traced
    scalar, or a per-sequence (B,) array (slot-based ragged decode —
    independent sequences in a batch sit at different positions). Traced
    positions write modulo the buffer length: the cache is a RING — once
    the window fills, the new row lands on the slot holding the oldest
    entry. One O(1) dynamic-slice write per token replaces the legacy
    roll-by-one window's O(S) HBM shift (generate.py pre-round-8), and is
    content-identical to it: both keep exactly the last S entries, and
    attention is permutation-invariant over fully-valid slots."""
    new = new.astype(cache_arr.dtype)
    zeros = (0,) * (new.ndim - 2)
    S = cache_arr.shape[1]
    if isinstance(pos, int):
        return jax.lax.dynamic_update_slice(cache_arr, new, (0, pos, *zeros))
    pos = jnp.asarray(pos, jnp.int32)
    start = jax.lax.rem(pos, jnp.int32(S))
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(cache_arr, new,
                                            (jnp.int32(0), start, *zeros))
    # per-sequence slots: one row-write per sequence
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, *zeros))
    )(cache_arr, new, start)


class GQA(nn.Module):
    """Grouped-query attention; n_kv_heads == n_head gives MHA, == 1 MQA.

    Follows reference model.py:98-155: one fused qkv projection of width
    n_embd + 2*n_kv_heads*head_size (with bias, as reference :112-114), RoPE
    on q/k when pos_emb == 'rope', output projection + residual dropout.

    A patterned model's '*' layer is this class too, configured: with
    `cfg.qk_norm` an RMSNorm over the lanes of every q head and every k
    head (leaves `q_norm`, `k_norm`, a head-size vector each) BEFORE the
    positions; RoPE pairs the lanes as `cfg.rope_pairing` says, and where
    no table is handed in (`freqs` None) takes its angles from the rows'
    own positions at `cfg.rope_theta` (ops/rope.py): of the first
    `cfg.rotary_frac` of the lanes, YaRN's where `cfg.rope_factor` is over 1.
    Keys go into the cache normed and rotated, and times `cfg.key_mult`
    (ops/mup.py) from the projection on. With `cfg.attn_gate` True or
    'head' every query head's output is multiplied by a gate of its own,
    the sigmoid of a linear map (leaf `c_gate`, (C, heads)) of the layer's
    input, before `c_proj`; with 'channel' by a gate a CHANNEL, the sigmoid
    of nh x hs further columns of the query's projection (`c_attn` = [q | k
    | v | gate], each head-major). `cfg.norm_zero_centred` makes the
    QK-norms scale by 1 + w.

    `kind` 'W' is the pattern's window layer, the same class at
    `cfg.window_heads` query heads, plain RoPE at `cfg.window_rope_theta`
    over all lanes, and a mask that ends `cfg.window` keys back. Its cache
    is no block pool but a ring a slot, {"k", "v"}: (n_slots, R, L)
    (ops/window_attention.py), addressed by the engine's `state_ctx` as
    the per-slot leaves of the 'M' and 'C' layers are: one token of every
    slot (`live`), or a chunk of one (`slot`, `valid_len`).
    """

    config: LLMConfig
    attn_impl: str = "auto"
    param_dtype: Any = jnp.float32
    kind: str = "*"

    @nn.compact
    def __call__(self, x, freqs, cache: Optional[Cache] = None, pos=0, *,
                 deterministic: bool = True, block_tables=None,
                 state_ctx: Optional[dict] = None):
        cfg = self.config
        B, T, C = x.shape
        windowed = self.kind == "W"
        nh = cfg.window_heads if windowed else cfg.n_head
        nkvh, hs = cfg.n_kv_heads, cfg.head_size
        qw = nh * hs            # = C unless the config sets `head_dim`
        dense = dict(use_bias=cfg.attn_bias, param_dtype=self.param_dtype)
        gate = None

        if cfg.attn_gate_kind == "channel":
            # the gate shares the query's projection: [q | k | v | gate]
            qkv = _OverlapDense(2 * qw + 2 * nkvh * hs, x.dtype,
                                name="c_attn", **dense)(x)
            qkv, gate = qkv[..., :-qw], qkv[..., -qw:]
        else:
            qkv = _OverlapDense(qw + 2 * nkvh * hs, x.dtype, name="c_attn",
                                **dense)(x)
        q, k, v = jnp.split(qkv, [qw, qw + nkvh * hs], axis=-1)
        q = q.reshape(B, T, nh, hs)
        k = k.reshape(B, T, nkvh, hs)
        v = v.reshape(B, T, nkvh, hs)
        k = times(k, cfg.key_mult)

        if cfg.qk_norm:
            with jax.named_scope("qk_norm"):
                zc = cfg.norm_zero_centred
                init = ZERO_CENTRED_INIT if zc else nn.initializers.ones
                q = _head_rms_norm(q, self.param(
                    "q_norm", init, (hs,), self.param_dtype), cfg.norm_eps,
                    zc)
                k = _head_rms_norm(k, self.param(
                    "k_norm", init, (hs,), self.param_dtype), cfg.norm_eps,
                    zc)
        if cfg.pos_emb == "rope":
            with jax.named_scope("rope"):
                if windowed:
                    f = rope_angles(pos, T, hs, cfg.window_rope_theta)
                elif freqs is None:
                    # no table (a patterned model's context is its
                    # cache's, models/gpt.py): the angles of the rows' own
                    # positions, over the lanes that rotate
                    yarn = (cfg.rope_factor, cfg.rope_original_len) \
                        if cfg.rope_factor > 1.0 else ()
                    f = rope_angles(pos, T, int(hs * cfg.rotary_frac),
                                    cfg.rope_theta, yarn=yarn,
                                    attn_factor=cfg.rope_attn_factor)
                else:
                    f = slice_rows(freqs, pos, T)
                half = cfg.rope_pairing == "half"
                q = apply_partial_rotary(q, f, half=half)
                k = apply_partial_rotary(k, f, half=half)
        if windowed:
            y = self._window(q, k, v, cache, pos, state_ctx or {})
            return self._project(x, *y, dense, deterministic, gate)

        new_cache = None
        q_offset = 0
        k_scale = v_scale = None
        if cache is not None:
            # paged caches write through the block table, contiguous ones
            # through the O(1) ring write — same rows, one indirection
            upd = _update_cache
            if block_tables is not None:
                from distributed_pytorch_tpu.ops.block_pool import \
                    paged_update

                def upd(arr, new, p):
                    return paged_update(arr, new, p, block_tables)
            if "k_scale" in cache:
                # int8 cache: quantize on the write — codes land in the
                # int8 buffers, per-(row, kv-head) scales in the f32
                # sidecars, all via the same O(1) row writes
                from distributed_pytorch_tpu.ops.quant import quantize_kv
                k_q, k_s = quantize_kv(k)
                v_q, v_s = quantize_kv(v)
                with jax.named_scope("kv_update"):
                    k = upd(cache["k"], k_q, pos)
                    v = upd(cache["v"], v_q, pos)
                    k_scale = upd(cache["k_scale"], k_s, pos)
                    v_scale = upd(cache["v_scale"], v_s, pos)
                new_cache = {"k": k, "k_scale": k_scale,
                             "v": v, "v_scale": v_scale}
            else:
                with jax.named_scope("kv_update"):
                    k = upd(cache["k"], k, pos)
                    v = upd(cache["v"], v, pos)
                new_cache = {"k": k, "v": v}
            q_offset = pos

        drop_rng = None
        if cfg.dropout > 0.0 and not deterministic:
            drop_rng = self.make_rng("dropout")
        raw = k_scale is not None or block_tables is not None
        with jax.named_scope("attn_core"):
            y = sdpa(q, k if raw else k.astype(q.dtype),
                     v if raw else v.astype(q.dtype),
                     causal=True, q_offset=q_offset,
                     dropout_rate=cfg.dropout, dropout_rng=drop_rng,
                     impl=self.attn_impl, decode=cache is not None,
                     k_scale=k_scale, v_scale=v_scale,
                     block_tables=block_tables, n_kv_heads=nkvh,
                     scale=cfg.attn_scale or None)
        return self._project(x, y, new_cache, dense, deterministic, gate)

    def _project(self, x, y, new_cache, dense: dict, deterministic: bool,
                 gate=None):
        """The heads' outputs (B, T, heads, hs), each times its gate where
        the configuration has one (a head's from `c_gate`, or `gate`: a
        channel's, the query projection's further columns), through
        `c_proj`."""
        cfg = self.config
        B, T, nh, hs = y.shape
        if gate is not None:
            with jax.named_scope("attn_gate"):
                y = y * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    y.dtype).reshape(B, T, nh, hs)
        elif cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                gate = _OverlapDense(nh, x.dtype, name="c_gate",
                                     use_bias=False,
                                     param_dtype=self.param_dtype)(x)
                y = y * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(y.dtype)[..., None]
        y = _OverlapDense(x.shape[-1], x.dtype, name="c_proj",
                          **dense)(y.reshape(B, T, nh * hs))
        y = nn.Dropout(cfg.dropout, deterministic=deterministic)(y)
        return y, new_cache

    def _window(self, q, k, v, cache, pos, ctx: dict):
        """A 'W' layer's attention and what it leaves in the slot's ring
        (ops/window_attention.py): -> (heads' outputs, new cache)."""
        from distributed_pytorch_tpu.ops import window_attention as wa
        cfg = self.config
        kw = dict(window=cfg.window,
                  scale=cfg.attn_scale or 1.0 / cfg.head_size ** 0.5)
        if cache is None:
            with jax.named_scope("attn_window"):
                return wa.window_attention(q, k, v, **kw), None
        kw["n_kv_heads"] = cfg.n_kv_heads
        rk, rv = cache["k"], cache["v"]
        if "live" in ctx:
            assert q.shape[1] == 1, "one token a slot"
            with jax.named_scope("kv_update_window"):
                rk = wa.ring_write_token(rk, k, pos, ctx["live"])
                rv = wa.ring_write_token(rv, v, pos, ctx["live"])
            with jax.named_scope("attn_window"):
                y = wa.window_decode(q, rk, rv, pos, ctx["live"], **kw)
            return y, {"k": rk, "v": rv}
        assert q.shape[0] == 1, "a chunk is one sequence's"
        slot, valid = ctx["slot"], ctx["valid_len"][0]
        R, L = rk.shape[1:]
        with jax.named_scope("kv_update_window"):
            from distributed_pytorch_tpu.ops.block_pool import merge_heads
            keys, values = (jnp.concatenate([
                wa.ring_logical(jax.lax.dynamic_index_in_dim(
                    ring, slot, 0, keepdims=False), pos),
                merge_heads(new.astype(ring.dtype), L)[0]])
                for ring, new in ((rk, k), (rv, v)))
            rk, rv = (jax.lax.dynamic_update_index_in_dim(
                ring, wa.ring_after(rows, R, pos, valid), slot, 0)
                for ring, rows in ((rk, keys), (rv, values)))
        with jax.named_scope("attn_window"):
            y = wa.window_chunk(q, keys, values, pos, **kw)
        return y, {"k": rk, "v": rv}


def _qmm(mod: nn.Module, x: jnp.ndarray, kernel: jnp.ndarray,
         name: str) -> jnp.ndarray:
    """`x @ kernel` with the weight-only-int8 store consulted first
    (ops/quant.py): under an engine decode step with quantized params the
    matmul reads int8 codes + per-output-channel scales; everywhere else
    it is the plain cast-and-matmul."""
    from distributed_pytorch_tpu.ops.quant import maybe_quantized_matmul
    y = maybe_quantized_matmul(x, (*mod.path, name))
    return y if y is not None else x @ kernel.astype(x.dtype)


def _mla_kernels(mod: nn.Module, cfg: LLMConfig, C: int, *, rope: bool) -> dict:
    """Declare the MLA projection kernels (all bias-free, reference
    model.py:165-170,250-263). Declared via self.param (not nn.Dense) because
    the decode path contracts W_uk/W_uv against the cache in absorbed form."""
    nlq, nlkv = cfg.q_latent_dim, cfg.kv_latent_dim
    ks = {
        "W_dq": mod.param("W_dq", _DENSE_INIT, (C, nlq), jnp.float32),
        "W_uq": mod.param("W_uq", _DENSE_INIT, (nlq, C), jnp.float32),
        "W_dkv": mod.param("W_dkv", _DENSE_INIT, (C, nlkv), jnp.float32),
        "W_uk": mod.param("W_uk", _DENSE_INIT, (nlkv, C), jnp.float32),
        "W_uv": mod.param("W_uv", _DENSE_INIT, (nlkv, C), jnp.float32),
        "W_o": mod.param("W_o", _DENSE_INIT, (C, C), jnp.float32),
    }
    if rope:
        dhr = cfg.rope_head_dim
        ks["W_qr"] = mod.param("W_qr", _DENSE_INIT, (nlq, cfg.n_head * dhr),
                               jnp.float32)
        ks["W_kr"] = mod.param("W_kr", _DENSE_INIT, (C, dhr), jnp.float32)
    return ks


def _absorbed_decode(q_c, c_kv, kuk, kuv, pos, scale, extra_scores=None):
    """Shared MLA decode: attend over the compressed latent cache with exact
    weight absorption (module docstring note 2).

    q_c: (B,T,nh,hs) content queries; c_kv: (B,S,nlkv) latent cache buffer;
    kuk/kuv: (nlkv, C) up-projections; extra_scores: optional (B,nh,T,S)
    additive term (FullMLA's decoupled-rotary scores, reference
    model.py:320-326). Returns (B, T, nh*hs) pre-W_o output."""
    B, T, nh, hs = q_c.shape
    S = c_kv.shape[1]
    dt = q_c.dtype
    nlkv = kuk.shape[0]
    kuk_h = kuk.reshape(nlkv, nh, hs).astype(dt)
    kuv_h = kuv.reshape(nlkv, nh, hs).astype(dt)
    # q_abs[b,t,n,l] = q . W_uk_h^T : attend in latent space
    q_abs = jnp.einsum("btnh,lnh->btnl", q_c, kuk_h)
    attn = jnp.einsum("btnl,bsl->bnts", q_abs, c_kv.astype(dt))
    if extra_scores is not None:
        attn = attn + extra_scores
    attn = attn * scale
    attn = jnp.where(_causal_cache_mask(pos, T, S)[:, None], attn, -jnp.inf)
    attn = jax.nn.softmax(attn.astype(jnp.float32), axis=-1).astype(dt)
    out_lat = jnp.einsum("bnts,bsl->btnl", attn, c_kv.astype(dt))
    return jnp.einsum("btnl,lnh->btnh", out_lat, kuv_h).reshape(B, T, nh * hs)


def _causal_cache_mask(pos, T: int, S: int) -> jnp.ndarray:
    """(B|1, T, S) bool mask: query at global position pos+i attends cache
    slots j <= pos+i. `pos` scalar or per-sequence (B,) array. Under the
    ring cache (global pos >= S) every slot is valid — slot indices never
    exceed S-1, so the comparison degenerates to all-true, matching the
    legacy roll window's fully-valid buffer."""
    qpos = (jnp.reshape(jnp.asarray(pos, jnp.int32), (-1, 1, 1))
            + jnp.arange(T)[None, :, None])
    kpos = jnp.arange(S)[None, None, :]
    return qpos >= kpos


class NaiveMLA(nn.Module):
    """MLA without RoPE (reference `NaiveMHLA`, model.py:157-235).

    Projections (all bias-free, reference :165-170): W_dq (C->q_latent),
    W_uq (q_latent->C), W_dkv (C->kv_latent), W_uk/W_uv (kv_latent->C),
    W_o (C->C). Cache stores only the compressed c_kv (B, S, kv_latent)
    (reference :204-211). Decode uses exact weight absorption (see module
    docstring note 2).
    """

    config: LLMConfig
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, freqs, cache: Optional[Cache] = None, pos=0, *,
                 deterministic: bool = True, block_tables=None):
        cfg = self.config
        B, T, C = x.shape
        nh, hs = cfg.n_head, cfg.head_size
        dt = x.dtype

        ks = _mla_kernels(self, cfg, C, rope=False)
        q = _qmm(self, _qmm(self, x, ks["W_dq"], "W_dq"), ks["W_uq"], "W_uq")
        q = q.reshape(B, T, nh, hs)
        new_c_kv = _qmm(self, x, ks["W_dkv"], "W_dkv")  # (B, T, nlkv)

        if cache is None:
            # Training/full-sequence: materialize per-head K/V -> fused SDPA.
            k = (new_c_kv @ ks["W_uk"].astype(dt)).reshape(B, T, nh, hs)
            v = (new_c_kv @ ks["W_uv"].astype(dt)).reshape(B, T, nh, hs)
            drop_rng = None
            if cfg.dropout > 0.0 and not deterministic:
                drop_rng = self.make_rng("dropout")
            with jax.named_scope("attn_core"):
                y = sdpa(q, k, v, causal=True, dropout_rate=cfg.dropout,
                         dropout_rng=drop_rng, impl=self.attn_impl)
            y = y.reshape(B, T, C)
            new_cache = None
        else:
            if block_tables is not None:
                from distributed_pytorch_tpu.ops.block_pool import (
                    paged_gather, paged_update)
                with jax.named_scope("kv_update"):
                    pool = paged_update(cache["c_kv"], new_c_kv, pos,
                                        block_tables)
                new_cache = {"c_kv": pool}
                # absorbed decode attends the logical view; rows past each
                # sequence's extent are causally masked to weight 0
                with jax.named_scope("attn_core"):
                    c_kv = paged_gather(pool, block_tables)
            else:
                with jax.named_scope("kv_update"):
                    c_kv = _update_cache(cache["c_kv"], new_c_kv, pos)
                new_cache = {"c_kv": c_kv}
            from distributed_pytorch_tpu.ops.quant import \
                maybe_dequantized_param
            kuk = maybe_dequantized_param((*self.path, "W_uk"), ks["W_uk"])
            kuv = maybe_dequantized_param((*self.path, "W_uv"), ks["W_uv"])
            with jax.named_scope("attn_core"):
                y = _absorbed_decode(q, c_kv, kuk, kuv, pos,
                                     1.0 / jnp.sqrt(float(hs)))

        y = _qmm(self, y, ks["W_o"], "W_o")
        y = nn.Dropout(cfg.dropout, deterministic=deterministic)(y)
        return y, new_cache


class FullMLA(nn.Module):
    """DeepSeek-V2 MLA with decoupled RoPE (reference `FullMHLA`,
    model.py:237-345).

    Content (NoPE) path through latents exactly as NaiveMLA; rotary path adds
    per-head rotary queries W_qr (q_latent -> nh*dhr) and a single shared
    rotary key head W_kr (C -> dhr) (reference :258-259). Scores are
    q_c.k_c + q_r.k_r scaled by 1/sqrt(hs+dhr) (reference :326). Cache:
    {'c_kv': (B,S,nlkv), 'k_r': (B,S,1,dhr)} (reference :343).
    """

    config: LLMConfig
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x, freqs, cache: Optional[Cache] = None, pos=0, *,
                 deterministic: bool = True, block_tables=None):
        cfg = self.config
        B, T, C = x.shape
        nh, hs = cfg.n_head, cfg.head_size
        dhr = cfg.rope_head_dim
        dt = x.dtype

        ks = _mla_kernels(self, cfg, C, rope=True)
        f = slice_rows(freqs, pos, T)

        c_q = _qmm(self, x, ks["W_dq"], "W_dq")                    # (B,T,nlq)
        q_c = _qmm(self, c_q, ks["W_uq"], "W_uq").reshape(B, T, nh, hs)
        q_r = apply_rotary_emb(
            _qmm(self, c_q, ks["W_qr"], "W_qr").reshape(B, T, nh, dhr), f)
        new_c_kv = _qmm(self, x, ks["W_dkv"], "W_dkv")             # (B,T,nlkv)
        new_k_r = apply_rotary_emb(
            _qmm(self, x, ks["W_kr"], "W_kr")[:, :, None, :], f)

        scale = 1.0 / jnp.sqrt(float(hs + dhr))

        if cache is None:
            k_c = (new_c_kv @ ks["W_uk"].astype(dt)).reshape(B, T, nh, hs)
            v = (new_c_kv @ ks["W_uv"].astype(dt)).reshape(B, T, nh, hs)
            # Concatenate content+rotary features -> ONE fused SDPA call with
            # joint scale (equivalent to reference's attn_c + attn_r sum,
            # model.py:320-326, but flash-kernel friendly).
            q_cat = jnp.concatenate([q_c, q_r], axis=-1)
            k_cat = jnp.concatenate(
                [k_c, jnp.broadcast_to(new_k_r, (B, T, nh, dhr))], axis=-1)
            # fused kernels need equal head dims: zero-pad v to hs+dhr and
            # slice the output back (exact — padded cols contribute nothing)
            v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dhr)))
            drop_rng = None
            if cfg.dropout > 0.0 and not deterministic:
                drop_rng = self.make_rng("dropout")
            with jax.named_scope("attn_core"):
                y = sdpa(q_cat, k_cat, v_pad, causal=True, scale=scale,
                         dropout_rate=cfg.dropout, dropout_rng=drop_rng,
                         impl=self.attn_impl)
            y = y[..., :hs].reshape(B, T, C)
            new_cache = None
        else:
            if block_tables is not None:
                from distributed_pytorch_tpu.ops.block_pool import (
                    paged_gather, paged_update)
                with jax.named_scope("kv_update"):
                    ckv_pool = paged_update(cache["c_kv"], new_c_kv, pos,
                                            block_tables)
                    kr_pool = paged_update(cache["k_r"], new_k_r, pos,
                                           block_tables)
                new_cache = {"c_kv": ckv_pool, "k_r": kr_pool}
                with jax.named_scope("attn_core"):
                    c_kv = paged_gather(ckv_pool, block_tables)
                    k_r = paged_gather(kr_pool, block_tables)
            else:
                with jax.named_scope("kv_update"):
                    c_kv = _update_cache(cache["c_kv"], new_c_kv, pos)
                    k_r = _update_cache(cache["k_r"], new_k_r, pos)
                new_cache = {"c_kv": c_kv, "k_r": k_r}
            from distributed_pytorch_tpu.ops.quant import \
                maybe_dequantized_param
            kuk = maybe_dequantized_param((*self.path, "W_uk"), ks["W_uk"])
            kuv = maybe_dequantized_param((*self.path, "W_uv"), ks["W_uv"])
            with jax.named_scope("attn_core"):
                # decoupled-rotary scores; single shared key head
                # broadcasts
                attn_r = jnp.einsum("btnh,bskh->bnts", q_r, k_r.astype(dt))
                y = _absorbed_decode(q_c, c_kv, kuk, kuv, pos,
                                     scale, extra_scores=attn_r)

        y = _qmm(self, y, ks["W_o"], "W_o")
        y = nn.Dropout(cfg.dropout, deterministic=deterministic)(y)
        return y, new_cache


class LatentAttention(nn.Module):
    """A patterned model's 'L' layer: latent attention as published (HF
    `deepseek_v3`-style modules), no biases. For a normed input h (B, T, C):

      c_q = RMSNorm(h W_qa) (`q_latent_dim`); q = c_q W_qb, a head's
            `[q_nope (qk_nope_head_dim) | q_rope (rope_head_dim)]`; with
            `q_latent_dim` 0 there is no query latent and no query norm:
            q = h W_q in ONE matrix (leaf `W_q`; published `q_lora_rank`
            null)
      [c_kv | k_r] = h W_kva; c = RMSNorm(c_kv) (`kv_latent_dim`); k_r is
            ONE key head every query head shares
      RoPE on q_rope and k_r at the rows' own positions (`rope_theta`,
            `rope_pairing`; a lane permutation common to both leaves
            every score as it was)
      [k_nope_n | v_n] = c W_kvb,n; score_n = (q_nope_n . k_nope_n +
            q_rope_n . k_r) / sqrt(nope + rope); y = [o_0 .. o_nh] W_o

    What is cached is the row `[c | rope(k_r) | 0]` (ops/latent_attention.py
    `cache_rows`), one a position with no head axis, in the layer's ONE
    pool leaf (n_blocks, bs, L), written through the block table under
    scope `kv_update`. Scopes: `latent_q` (the query path, and for one
    token of every slot the absorption q~ = q_nope W_kvb^K^T), `latent_kv`,
    `kv_update`, `attn_latent` (latent_flash_decode | latent_flash_prefill,
    or their XLA twins), `latent_out` (W_kvb^V where the attention ran
    absorbed, and W_o). Without a cache the T rows attend to themselves,
    up-projected."""

    config: LLMConfig
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, cache=None, pos=0, *, block_tables=None):
        from distributed_pytorch_tpu.ops import latent_attention as la
        from distributed_pytorch_tpu.ops.quant import maybe_dequantized_param
        cfg = self.config
        B, T, C = x.shape
        nh, dr = cfg.n_head, cfg.rope_head_dim
        dn = cfg.qk_nope_head_dim or cfg.head_size
        dv = cfg.v_head_dim or cfg.head_size
        nlq, lc = cfg.q_latent_dim, cfg.kv_latent_dim
        lanes = la.row_lanes(lc, dr)
        scale = 1.0 / float(dn + dr) ** 0.5

        def mat(name, *shape):
            return self.param(name, _DENSE_INIT, shape, self.param_dtype)

        def vec(name, n):
            return self.param(name, nn.initializers.ones, (n,),
                              self.param_dtype)

        if nlq:
            w_qa, w_qb = mat("W_qa", C, nlq), mat("W_qb", nlq,
                                                  nh * (dn + dr))
        else:
            w_q = mat("W_q", C, nh * (dn + dr))
        w_kva = mat("W_kva", C, lc + dr)
        w_kvb = maybe_dequantized_param(
            (*self.path, "W_kvb"), mat("W_kvb", lc, nh * (dn + dv))
        ).astype(x.dtype).reshape(lc, nh, dn + dv)
        w_o = mat("W_o", nh * dv, C)
        q_norm = vec("q_norm", nlq) if nlq else None
        kv_norm = vec("kv_norm", lc)
        f = rope_angles(pos, T, dr, cfg.rope_theta)
        half = cfg.rope_pairing == "half"
        # one token of every slot attends absorbed; everything else
        # up-projects the rows it reads
        absorbed = cache is not None and T == 1

        with jax.named_scope("latent_q"):
            if nlq:
                c_q = _head_rms_norm(_qmm(self, x, w_qa, "W_qa"), q_norm,
                                     cfg.norm_eps)
                q = _qmm(self, c_q, w_qb, "W_qb")
            else:
                q = _qmm(self, x, w_q, "W_q")
            q = q.reshape(B, T, nh, dn + dr)
            q_nope = q[..., :dn]
            q_rope = apply_rotary_emb(q[..., dn:], f, half=half)
            if absorbed:
                q_rows = la.cache_rows(
                    jnp.einsum("btnd,lnd->btnl", q_nope, w_kvb[..., :dn]),
                    q_rope, lanes)
        with jax.named_scope("latent_kv"):
            ckr = _qmm(self, x, w_kva, "W_kva")
            rows = la.cache_rows(
                _head_rms_norm(ckr[..., :lc], kv_norm, cfg.norm_eps),
                apply_rotary_emb(ckr[:, :, None, lc:], f, half=half)[:, :, 0],
                lanes)

        if cache is None:
            with jax.named_scope("attn_latent"):
                y = la.attend_rows(q_nope, q_rope, rows, w_kvb,
                                   la.causal_visible(0, T, T), scale)
        else:
            assert block_tables is not None, \
                "a latent layer's cache is a paged pool"
            from distributed_pytorch_tpu.ops.block_pool import (paged_gather,
                                                                paged_update)
            with jax.named_scope("kv_update"):
                cache = paged_update(cache, rows, pos, block_tables)
            with jax.named_scope("attn_latent"):
                if absorbed:
                    o_lat = la.latent_decode(
                        q_rows[:, 0], cache, block_tables,
                        jnp.broadcast_to(jnp.reshape(jnp.asarray(
                            pos, jnp.int32), (-1,)) + 1, (B,)),
                        scale=scale, lc=lc)
                elif B == 1:
                    y = la.latent_chunk(
                        q_nope, q_rope, cache, w_kvb, block_tables,
                        jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,))[0],
                        scale=scale)
                else:
                    # several rows of several sequences (a verify window)
                    view = paged_gather(cache, block_tables)
                    y = la.attend_rows(
                        q_nope, q_rope, view, w_kvb,
                        la.causal_visible(pos, T, view.shape[1]), scale)
        with jax.named_scope("latent_out"):
            if absorbed:
                y = jnp.einsum("bnl,lnv->bnv", o_lat, w_kvb[..., dn:])[:, None]
            y = _qmm(self, y.reshape(B, T, nh * dv), w_o, "W_o")
        return y, cache


def init_latent_cache(config: LLMConfig, n_blocks: int, block_size: int,
                      dtype=jnp.float32) -> jnp.ndarray:
    """An 'L' layer's pool: ONE leaf (n_blocks, bs, L) of latent rows
    `[c | rope(k_r) | 0]` (ops/latent_attention.py `row_lanes`), no head
    axis; block 0 is the null block."""
    from distributed_pytorch_tpu.ops.latent_attention import row_lanes
    assert jnp.dtype(dtype) != jnp.int8, \
        "a latent pool has no int8 form (quant_kv_usable declines it)"
    return jnp.zeros((n_blocks, block_size, row_lanes(
        config.kv_latent_dim, config.rope_head_dim)), dtype)


def Attention(config: LLMConfig, attn_impl: str = "auto",
              name: str = "attn") -> nn.Module:
    """Flavor dispatch (reference model.py:347-363): mha/mqa/gqa -> GQA;
    mla -> FullMLA when pos_emb == 'rope' else NaiveMLA.

    A factory (not a wrapper module) so the flavor module sits directly at
    `block_i/attn/` in the param tree with no redundant nesting level."""
    if config.attn in ("mha", "mqa", "gqa"):
        return GQA(config, attn_impl, name=name)
    if config.pos_emb == "rope":
        return FullMLA(config, attn_impl, name=name)
    return NaiveMLA(config, attn_impl, name=name)


def init_attn_cache(config: LLMConfig, batch_size: int, max_len: int,
                    dtype=jnp.float32) -> Cache:
    """Per-layer static-shape KV cache buffers (see module docstring note 3).

    `dtype=jnp.int8` builds the quantized cache (ops/quant.py): int8 code
    buffers plus f32 per-(row, kv-head) scale sidecars — the (B, S, n_kv,
    1) layout keeps `sharding.decode_cache_pspec` placing the kv-head axis
    over 'model' and slots over 'data' exactly like the code buffers.
    GQA family only; gate with `quant_kv_usable` (MLA falls back to bf16)."""
    B, S = batch_size, max_len
    if config.attn in ("mha", "mqa", "gqa"):
        shape = (B, S, config.n_kv_heads, config.head_size)
        if jnp.dtype(dtype) == jnp.int8:
            sc = (B, S, config.n_kv_heads, 1)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sc, jnp.float32),
                    "v": jnp.zeros(shape, jnp.int8),
                    "v_scale": jnp.zeros(sc, jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            "int8 KV cache supports the GQA family only (quant_kv_usable "
            "gates this; MLA latent caches stay in the compute dtype)")
    cache = {"c_kv": jnp.zeros((B, S, config.kv_latent_dim), dtype)}
    if config.pos_emb == "rope":
        cache["k_r"] = jnp.zeros((B, S, 1, config.rope_head_dim), dtype)
    return cache


def init_window_cache(config: LLMConfig, n_slots: int, block_size: int,
                      dtype=jnp.float32) -> Cache:
    """A 'W' layer's state: a ring a slot of the window's rows in whole
    blocks, merged lanes (ops/window_attention.py). Its bytes do not know
    `max_len`."""
    from distributed_pytorch_tpu.ops.block_pool import kv_lanes
    from distributed_pytorch_tpu.ops.window_attention import ring_rows
    assert jnp.dtype(dtype) != jnp.int8, \
        "a window layer's ring has no int8 form yet"
    shape = (n_slots, ring_rows(config.window, block_size),
             kv_lanes(config.n_kv_heads, config.head_size))
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_attn_cache(config: LLMConfig, n_blocks: int, block_size: int,
                          dtype=jnp.float32) -> Cache:
    """Per-layer paged KV POOL buffers (module docstring note 4), the
    (B, S) row axes of `init_attn_cache` replaced by (n_blocks,
    block_size). Float k/v pools merge the kv heads into one lane axis,
    (n_blocks, block_size, L) with L = `block_pool.kv_lanes` (n_kv_heads *
    head_size rounded up to 128: gpt2-xl 1600 -> 1664, gpt2 768): the one
    shape whose device layout, in-place write and kernel operand agree.
    The int8 pools keep the head axis, codes (.., n_kv, hs) and float32
    scale sidecars (.., n_kv, 1), with their head-major kernels; MLA
    latent pools reach no kernel and keep theirs. Block 0 is the null
    block (ops/block_pool.py)."""
    nb, bs = n_blocks, block_size
    if config.attn in ("mha", "mqa", "gqa"):
        if jnp.dtype(dtype) == jnp.int8:
            shape = (nb, bs, config.n_kv_heads, config.head_size)
            sc = (nb, bs, config.n_kv_heads, 1)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sc, jnp.float32),
                    "v": jnp.zeros(shape, jnp.int8),
                    "v_scale": jnp.zeros(sc, jnp.float32)}
        from distributed_pytorch_tpu.ops.block_pool import kv_lanes
        shape = (nb, bs, kv_lanes(config.n_kv_heads, config.head_size))
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError(
            "int8 KV cache supports the GQA family only (quant_kv_usable "
            "gates this; MLA latent caches stay in the compute dtype)")
    cache = {"c_kv": jnp.zeros((nb, bs, config.kv_latent_dim), dtype)}
    if config.pos_emb == "rope":
        cache["k_r"] = jnp.zeros((nb, bs, 1, config.rope_head_dim), dtype)
    return cache
