"""The LLM: token/positional embeddings, pre-LN transformer blocks, weight-
tied LM head, CE loss with MoE aux-loss accumulation, KV-cached decoding.

Reference parity map (single-gpu/model.py):
* `Block` — :508-533: pre-LN attention + (MLP | MoE) with residuals; returns
  (x, cache, aux_loss), aux_loss = 0.0 for dense blocks (:530).
* `LLM`   — :535-747: token embedding + one of three positional schemes
  (:541-552: 'learn' = learned table, 'sin' = fixed sinusoidal buffer,
  'rope' = precomputed rotary angles), dropout, n_layer blocks, final LN,
  weight-tied lm_head (:559-560), N(0, 0.02) init for all dense/embedding
  weights (:579-586), forward with cache-offset start_pos (:641-650),
  per-layer aux-loss accumulation added as total_aux/n_layer (:687-692),
  last-position-only logits when targets are absent (:694).

TPU-first notes:
* Parameters are fp32; compute runs in `compute_dtype` (bf16 on TPU) — pure
  bf16 matmuls with fp32 master weights replaces the reference's
  fp16 autocast + GradScaler (SURVEY §5 mixed-precision divergence).
* `act_recomp` wraps each Block in `nn.remat` (reference wraps Blocks in
  torch checkpoint, model.py:677-680), trading FLOPs for HBM.
* Caches are fixed-size buffers + a `pos` index (XLA static shapes), created
  by `init_cache`; `pos` replaces the reference's len-of-cache start_pos.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.models.attention import (GQA,
                                                      ZERO_CENTRED_INIT,
                                                      Attention,
                                                      LatentAttention,
                                                      init_attn_cache,
                                                      init_latent_cache,
                                                      init_window_cache)
from distributed_pytorch_tpu.models.linear_attention import (
    KDA, GatedDeltaNet, init_gdn_cache, init_kda_cache)
from distributed_pytorch_tpu.models.mlp import MLP, MoE, RoutedExperts
from distributed_pytorch_tpu.models.shortconv import (ShortConv,
                                                      init_conv_cache)
from distributed_pytorch_tpu.models.ssm import Mamba2, init_ssm_cache
from distributed_pytorch_tpu.ops.losses import tied_head_loss
from distributed_pytorch_tpu.ops.mup import times
from distributed_pytorch_tpu.ops.rope import precompute_rope_freqs, slice_rows

_EMBED_INIT = nn.initializers.normal(stddev=0.02)


class RMSNorm(nn.Module):
    """x / rms(x) * weight, the mean in float32; `zero_centred`
    (`cfg.norm_zero_centred`): times 1 + weight, the sum in float32."""

    eps: float = 1e-5
    param_dtype: Any = jnp.float32
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", ZERO_CENTRED_INIT if self.zero_centred
                       else nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype).astype(jnp.float32)
        if self.zero_centred:
            w = 1.0 + w
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + self.eps)
        return (xf * w).astype(x.dtype)


def merge_expert_stats(before: Optional[dict], new: dict) -> dict:
    """An 'E' layer's cache slot carries what its calls of ONE program
    routed: a row a call. One call a program, but for a fused step that
    runs the model twice (a quantised engine's: engine/decode.py)."""
    if before is None:
        return new
    return {k: jnp.concatenate([before[k], new[k]]) for k in new}


class Rows(NamedTuple):
    """One row set of a program: what `LLM.__call__` takes apart as `idx`,
    `pos`, `block_tables`, `state_ctx` and `logits_idx`, together. A tuple
    of them as `idx` is ONE walk over the layers for all (a fused step's
    chunk (1, N) and its decode tokens (n_slots, 1)): `scope` names what a
    set runs alone."""

    idx: Any
    pos: Any = 0
    block_tables: Any = None
    state_ctx: Optional[dict] = None
    logits_idx: Any = None
    scope: Optional[str] = None


def _scope(name: Optional[str]):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _real_rows(x, state_ctx: dict):
    """(B x T,) bool: the rows of `x` the engine says are real."""
    if "live" in state_ctx:
        return state_ctx["live"]
    return jnp.arange(x.shape[1]) < state_ctx["valid_len"][0]


class MixerSum(nn.Module):
    """What a 'P' block adds to the residual stream: `attn_out_mult * a +
    ssm_out_mult * s`, one float32 sum of its two branches' outputs. A
    module without parameters, so that the sum has a name of its own
    (`mixer_sum`) in a device trace and for whoever taps the modules."""

    config: LLMConfig

    def __call__(self, a, s):
        cfg = self.config
        return (a.astype(jnp.float32) * cfg.attn_out_mult
                + s.astype(jnp.float32) * cfg.ssm_out_mult).astype(a.dtype)


class MixerBlock(nn.Module):
    """One layer of a patterned model: `x + r * mixer(RMSNorm(x))` (r =
    `cfg.resid_mult`), the mixer one of 'M' (models/ssm.py), 'C'
    (models/shortconv.py), 'E' (models/mlp.py RoutedExperts), 'F'
    (models/mlp.py MLP at `cfg.dense_up_dim`), '*' (GQA), 'W' (GQA over
    a window of the last `cfg.window` positions), 'L' (LatentAttention,
    module `latent_attn`: pools of latent rows), 'K' (KDA, module `kda`,
    models/linear_attention.py), 'G' (GatedDeltaNet, module `gdn`, the
    same file: a slot's leaves as 'K''s) or 'P': TWO mixers on the one
    normed
    input h, `mixer_sum(attn(a_in * h), ssm(s_in * h))` (`MixerSum`;
    modules `attn` and `ssm` as in a '*' and an 'M' block). What each
    keeps between calls sits in the layer's cache slot: per-slot state
    leaves ('M': tail and state, 'C': tail, 'K': a matrix-valued state a
    head and tail, 'W': a ring of the window's keys and values), this
    program's routing counts, block pools ('*'), nothing ('F'),
    pools AND state leaves ('P': {"pools", "slot_state"}, so the rows'
    block table and their state context reach the same block).
    `state_ctx` (the engine's: which rows are live, or which slot a chunk
    belongs to and how many of its rows are real) reaches the kinds that
    have no null block to land a pad in.

    `xs` are the hidden rows of the program's row sets (`rows`, one or
    several). An 'M', 'C', 'K', 'G', 'F', '*', 'W' or 'L' layer takes them in
    turn, the cache flowing from one to the next; an 'E' layer is position-wise
    and makes ONE call over all their rows, so its experts' matrices are
    read once."""

    config: LLMConfig
    kind: str
    attn_impl: str = "auto"
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, xs, rows, freqs, cache=None):
        cfg = self.config
        pd = self.param_dtype
        norm = RMSNorm(cfg.norm_eps, pd, cfg.norm_zero_centred, name="norm")
        hs = []
        for x, r in zip(xs, rows):
            with _scope(r.scope):
                hs.append(norm(x))
        if self.kind == "E":
            moe = RoutedExperts(cfg, pd, name="moe")
            masks = [None if r.state_ctx is None
                     else _real_rows(h, r.state_ctx)
                     for h, r in zip(hs, rows)]
            # every set's rows in one call of the held experts' kernels:
            # their matrices are read once, and a row gets what it would
            # get alone (an expert layer carries nothing from row to row)
            ys, stats = moe(hs, masks)
            new_cache = None if stats is None \
                else merge_expert_stats(cache, stats)
        else:
            if self.kind == "P":
                return self._parallel(xs, hs, rows, freqs, cache)
            mixer = {
                "M": lambda: Mamba2(cfg, pd, name="ssm"),
                "C": lambda: ShortConv(cfg, pd, name="conv"),
                "K": lambda: KDA(cfg, pd, name="kda"),
                "G": lambda: GatedDeltaNet(cfg, pd, name="gdn"),
                "F": lambda: MLP(cfg, cfg.dense_up_dim, pd, name="mlp"),
                "*": lambda: GQA(cfg, self.attn_impl, pd, name="attn"),
                "W": lambda: GQA(cfg, self.attn_impl, pd, "W", name="attn"),
                "L": lambda: LatentAttention(cfg, pd, name="latent_attn"),
            }[self.kind]()
            ys, new_cache = [], cache
            for h, r in zip(hs, rows):
                with _scope(r.scope):
                    if self.kind in "MCKG":
                        y, new_cache = mixer(h, new_cache, r.pos,
                                             r.state_ctx)
                    elif self.kind == "F":
                        y = mixer(h)
                    elif self.kind == "L":
                        y, new_cache = mixer(h, new_cache, r.pos,
                                             block_tables=r.block_tables)
                    else:
                        # '*' reads its rows through the table, 'W' its
                        # slot's ring through the state context
                        y, new_cache = mixer(
                            h, freqs, new_cache, r.pos, deterministic=True,
                            block_tables=r.block_tables,
                            state_ctx=r.state_ctx)
                ys.append(y)
        out = []
        for x, y, r in zip(xs, ys, rows):
            with _scope(r.scope):
                if cfg.resid_mult != 1.0:
                    # in float32: 0.22 is no bfloat16 number (0.2197 is)
                    y = (y.astype(jnp.float32)
                         * cfg.resid_mult).astype(y.dtype)
                out.append(x + y)
        return out, new_cache

    def _parallel(self, xs, hs, rows, freqs, cache):
        """A 'P' block from its normed inputs on: both mixers on every
        row set in turn, each with its own half of the cache slot."""
        cfg = self.config
        attn = GQA(cfg, self.attn_impl, self.param_dtype, name="attn")
        ssm = Mamba2(cfg, self.param_dtype, name="ssm")
        add = MixerSum(cfg, name="mixer_sum")
        pools, state = (None, None) if cache is None else \
            (cache["pools"], cache["slot_state"])
        out = []
        for x, h, r in zip(xs, hs, rows):
            with _scope(r.scope):
                a, pools = attn(
                    times(h, cfg.attn_in_mult), freqs, pools, r.pos,
                    deterministic=True, block_tables=r.block_tables,
                    state_ctx=r.state_ctx)
                s, state = ssm(times(h, cfg.ssm_in_mult), state, r.pos,
                               r.state_ctx)
                out.append(x + add(a, s))
        return out, None if cache is None else \
            {"pools": pools, "slot_state": state}


class Block(nn.Module):
    """Pre-LN transformer block (reference model.py:508-533).

    `deterministic` is a module attribute (not a call arg) so the whole
    block can be wrapped in `nn.remat` without static-argnum plumbing.
    `remat_attn` remats only the attention sublayer — the reference's
    deliberate kaggle-script granularity (kaggle-ddp.py:526-534): the
    O(T^2) score tensor is recomputed in backward, the O(T) FFN/MoE
    activations stay saved."""

    config: LLMConfig
    attn_impl: str = "auto"
    deterministic: bool = True
    remat_attn: bool = False

    @nn.compact
    def __call__(self, x, freqs, cache=None, pos=0, stats_weight=None,
                 block_tables=None):
        cfg = self.config
        deterministic = self.deterministic
        ln1 = nn.LayerNorm(dtype=x.dtype, param_dtype=jnp.float32, name="ln1")
        ln2 = nn.LayerNorm(dtype=x.dtype, param_dtype=jnp.float32, name="ln2")
        attn = Attention(cfg, self.attn_impl)
        if self.remat_attn:
            # remat over a function whose only remat argument is the hidden
            # state; freqs/cache/pos ride the closure (captured residuals,
            # cheap) so the flavor modules' keyword-only `deterministic`
            # needs no static-argnum plumbing. Param path stays `attn`.
            def attn_fn(mdl, h):
                return mdl(h, freqs, cache, pos, deterministic=deterministic,
                           block_tables=block_tables)
            attn_out, new_cache = nn.remat(attn_fn, prevent_cse=False)(
                attn, ln1(x))
        else:
            attn_out, new_cache = attn(ln1(x), freqs, cache, pos,
                                       deterministic=deterministic,
                                       block_tables=block_tables)
        x = x + attn_out
        if cfg.moe:
            moe_out, aux_loss = MoE(cfg, name="moe")(
                ln2(x), deterministic=deterministic,
                stats_weight=stats_weight)
            x = x + moe_out
        else:
            aux_loss = jnp.float32(0.0)
            x = x + MLP(cfg, name="mlp")(ln2(x), deterministic=deterministic)
        return x, new_cache, aux_loss


def _sin_table(block_size: int, n_embd: int) -> jnp.ndarray:
    """Fixed sinusoidal table (reference model.py:544-550)."""
    position = jnp.arange(block_size, dtype=jnp.float32)[:, None]
    div_term = jnp.exp(jnp.arange(0, n_embd, 2, dtype=jnp.float32)
                       * (-math.log(10000.0) / n_embd))
    angles = position * div_term  # (T, C/2)
    tab = jnp.zeros((block_size, n_embd), jnp.float32)
    tab = tab.at[:, 0::2].set(jnp.sin(angles))
    tab = tab.at[:, 1::2].set(jnp.cos(angles))
    return tab


class LLM(nn.Module):
    """The full model (reference model.py:535-747)."""

    config: LLMConfig
    compute_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    # a patterned model (`config.layer_pattern`) creates its parameters
    # in this dtype: bf16 weights that never exist in float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, idx, targets=None, caches=None, pos=0, *,
                 deterministic: bool = True, logits_idx=None,
                 block_tables=None, all_logits: bool = False,
                 state_ctx=None):
        """`pos` is the global position of idx[:, 0] — a static int, a
        traced scalar, or a per-sequence (B,) array (slot-based ragged
        decode; each sequence in the batch sits at its own cache
        position). `logits_idx` (B,) selects which position's logits to
        return when targets is None (default: the last) — the bucketed
        prefill path, where right-padded prompts end at different rows;
        `all_logits=True` returns every position's logits instead (the
        speculative verify step scores all K+1 draft positions at once).
        `block_tables` (B, max_blocks) int32 marks the caches as PAGED
        pools (init_paged_cache); reads and writes then indirect through
        the table (ops/block_pool.py). `state_ctx` is the engine's word
        to the layers of a patterned model that keep per-slot state or
        count per-row (`MixerBlock`): {"live": (B,) bool} for one token
        of every slot, {"slot": i, "valid_len": (1,)} for a chunk of one
        sequence.

        A patterned model also takes a tuple of `Rows` as `idx` (each
        with its own `pos`, `block_tables`, `state_ctx`, `logits_idx`):
        the row sets of one program, walked through the layers together
        so that an expert layer makes one call over all of them; logits
        come back as a tuple, a set each."""
        cfg = self.config
        dt = self.compute_dtype
        patterned = bool(cfg.layer_pattern)
        pd = self.param_dtype if patterned else jnp.float32
        rows = idx if isinstance(idx, tuple) else \
            (Rows(idx, pos, block_tables, state_ctx, logits_idx),)
        assert len(rows) == 1 or (patterned and targets is None
                                  and not all_logits), \
            "several row sets: a patterned model's cached forward alone"

        tkn_emb = nn.Embed(cfg.vocab_size, cfg.n_embd,
                           embedding_init=_EMBED_INIT,
                           param_dtype=pd, dtype=dt, name="tkn_emb")
        freqs = pos_tab = None
        if cfg.pos_emb == "rope":
            d = cfg.rope_head_dim if cfg.attn == "mla" else cfg.head_size
            # constant under jit; XLA folds it (reference precomputes a
            # complex buffer, model.py:567-577)
            # (a patterned model computes its angles from the positions,
            # models/attention.py GQA: no table as long as its context)
            freqs = None if patterned else \
                precompute_rope_freqs(d, cfg.block_size, cfg.rope_theta)
        elif cfg.pos_emb == "learn":
            pos_tab = self.param("pos_emb", _EMBED_INIT,
                                 (cfg.block_size, cfg.n_embd), jnp.float32)
        elif cfg.pos_emb == "sin":
            pos_tab = _sin_table(cfg.block_size, cfg.n_embd)
        drop = nn.Dropout(cfg.dropout, deterministic=deterministic)
        xs = []
        for r in rows:
            with _scope(r.scope):
                x = tkn_emb(r.idx)
                if cfg.embed_mult != 1.0:
                    x = x * jnp.asarray(cfg.embed_mult, x.dtype)
                if pos_tab is not None:
                    p = slice_rows(pos_tab, r.pos,
                                   r.idx.shape[1]).astype(dt)
                    # per-seq rows vs shared
                    x = x + (p if p.ndim == 3 else p[None])
                xs.append(drop(x))
        x = xs[0]

        if cfg.pp_stages > 1:
            # pipeline-parallel block stack (models/pipeline.py): stacked
            # layer axis over the 'pipe' mesh axis, microbatch tick loop
            if caches is not None:
                raise ValueError(
                    "pipeline-parallel models don't support KV-cached "
                    "decoding; restore the checkpoint with pp_stages=1 "
                    "(train/checkpoint.py unstacks the block params) to "
                    "sample from it")
            from distributed_pytorch_tpu.models.pipeline import run_pipeline
            x, total_aux = run_pipeline(self, cfg, self.attn_impl,
                                        deterministic, x, freqs)
            new_caches = [None] * cfg.n_layer
        else:
            if caches is None:
                caches = [None] * cfg.n_layer

            block_cls = Block
            remat_attn = False
            if cfg.act_recomp:
                if cfg.act_recomp_policy == "attn":
                    remat_attn = True  # attention-only (kaggle-ddp.py:526-534)
                else:
                    # Whole-block remat (reference model.py:677-680).
                    block_cls = nn.remat(Block, prevent_cse=False)

            new_caches = []
            total_aux = jnp.float32(0.0)
            for i in range(cfg.n_layer):
                if patterned:
                    blk = MixerBlock(cfg, cfg.layer_pattern[i],
                                     self.attn_impl, pd, name=f"block_{i}")
                    xs, new_cache = blk(xs, rows, freqs, caches[i])
                else:
                    blk = block_cls(cfg, self.attn_impl, deterministic,
                                    remat_attn, name=f"block_{i}")
                    x, new_cache, aux = blk(x, freqs, caches[i], pos,
                                            block_tables=block_tables)
                    total_aux = total_aux + aux
                new_caches.append(new_cache)

        ln_f = RMSNorm(cfg.norm_eps, pd, cfg.norm_zero_centred,
                       name="ln_f") if patterned else \
            nn.LayerNorm(dtype=dt, param_dtype=jnp.float32, name="ln_f")
        if len(rows) == 1:
            x = ln_f(xs[0] if patterned else x)
        head = None if cfg.tie_head else self.param(
            "lm_head", _EMBED_INIT, (cfg.vocab_size, cfg.n_embd), pd)

        def last_logits(x, logits_idx):
            """The cached forward's logits: of every position, of the
            last, or of a row a sequence."""
            if all_logits:
                sel = x                            # every position (verify)
            elif logits_idx is None:
                sel = x[:, -1:, :]                 # last position only (:694)
            else:
                # bucketed prefill: each sequence's true last token sits at
                # its own row of the right-padded buffer
                sel = jnp.take_along_axis(
                    x, jnp.reshape(logits_idx, (-1, 1, 1)).astype(jnp.int32),
                    axis=1)
            # weight-only int8 decode: the tied lm-head matmul — the
            # single largest weight read of a decode step — reads int8
            # codes + per-vocab-row scales when the engine's quantized
            # store is active (ops/quant.py); otherwise the plain attend
            from distributed_pytorch_tpu.ops.quant import \
                maybe_quantized_matmul
            with jax.named_scope("lm_head"):
                if head is not None:
                    logits = jnp.einsum("btc,vc->btv", sel, head.astype(dt))
                else:
                    logits = maybe_quantized_matmul(
                        sel, ("tkn_emb", "embedding"), transpose_b=True)
                if logits is None:
                    logits = tkn_emb.attend(sel)   # (B, 1, V)
                if cfg.logits_div != 1.0:
                    logits = logits / jnp.asarray(cfg.logits_div,
                                                  logits.dtype)
            return logits

        if len(rows) > 1:
            out = []
            for x, r in zip(xs, rows):
                with _scope(r.scope):
                    out.append(last_logits(ln_f(x), r.logits_idx))
            return tuple(out), None, new_caches

        if targets is not None:
            assert head is None and cfg.logits_div == 1.0, \
                "the training loss runs the tied head only, logits undivided"
            # scope `loss` (obs/trace.py SCOPES): head matmul + CE, every impl
            with jax.named_scope("loss"):
                main_loss = tied_head_loss(
                    x, tkn_emb.embedding.astype(dt), targets,
                    impl=cfg.loss_impl, chunk=cfg.loss_chunk)
                loss = main_loss + total_aux / cfg.n_layer
            # full logits stay available to callers (tests, analysis); when
            # unused — as in the trainer, which takes only `loss` — XLA
            # dead-code-eliminates this matmul.
            logits = tkn_emb.attend(x)
        else:
            logits = last_logits(x, logits_idx)
            loss = None

        return logits, loss, new_caches


def init_cache(config: LLMConfig, batch_size: int,
               max_len: Optional[int] = None, dtype=jnp.float32):
    """Create the per-layer static KV-cache pytree for decoding.

    `dtype` should match the model's compute_dtype (fp32 default mirrors
    LLM's; pass bfloat16 for bf16 inference). The buffers are RINGS under
    traced positions (models/attention.py `_update_cache`): decoding past
    `max_len` overwrites the oldest slot in O(1) — the static-shape
    equivalent of the reference's trim-to-block_size-1 sliding window
    (model.py:711-730), without the legacy roll's O(S) shift per token.
    """
    max_len = max_len or config.block_size
    return [init_attn_cache(config, batch_size, max_len, dtype)
            for _ in range(config.n_layer)]


def init_paged_cache(config: LLMConfig, n_blocks: int, block_size: int,
                     dtype=jnp.float32, n_slots: int = 0):
    """Per-layer paged KV-cache pytree: one (n_blocks, block_size, ...)
    pool set per layer that keeps a sequence's whole history, shared by
    every sequence through per-sequence block tables (engine/decode.py
    owns the tables; one table serves all those layers because block ids
    are allocated for all of them at once). Pass the tables to
    `LLM.__call__(block_tables=...)`.

    A patterned model holds two kinds of state in the one tree: block
    pools for its '*' layers, and leaves with a row a slot (`n_slots`)
    that no table addresses: convolution tail and state for its 'M'
    layers (models/ssm.py), the tail alone for its 'C' layers
    (models/shortconv.py), a ring of the last `window` keys and values
    for its 'W' layers (models/attention.py `init_window_cache`: whatever
    the pools' `n_blocks` and the engine's `max_len` are), nothing for
    'F' and 'E' layers (an 'E' slot carries a program's routing counts
    out, never in), a float32 state (heads, d_k, d_v) and a convolution
    tail for its 'K' and 'G' layers (models/linear_attention.py), which may
    stand beside 'L' or '*' layers' pools in this one tree. An 'L' layer's
    pool is ONE
    leaf of latent rows with no head axis (models/attention.py
    `init_latent_cache`), addressed by the same tables. A 'P' layer holds
    BOTH kinds, keyed by what they are
    (`config.LAYER_KEEPS`): {"pools": its attention branch's block pools,
    "slot_state": its state-space branch's tail and state}."""
    from distributed_pytorch_tpu.models.attention import init_paged_attn_cache
    if config.layer_pattern:
        assert n_slots > 0 or not config.slot_state, \
            "state-space, convolution, linear-attention and window layers " \
            "keep a row a slot: pass n_slots"
        make = {"M": lambda: init_ssm_cache(config, n_slots, dtype),
                "C": lambda: init_conv_cache(config, n_slots, dtype),
                "K": lambda: init_kda_cache(config, n_slots, dtype),
                "G": lambda: init_gdn_cache(config, n_slots, dtype),
                "W": lambda: init_window_cache(config, n_slots, block_size,
                                               dtype),
                "*": lambda: init_paged_attn_cache(config, n_blocks,
                                                   block_size, dtype),
                "L": lambda: init_latent_cache(config, n_blocks, block_size,
                                               dtype),
                "P": lambda: {"pools": make["*"](),
                              "slot_state": make["M"]()}}
        return [make[kind]() if kind in make else None
                for kind in config.layer_pattern]
    return [init_paged_attn_cache(config, n_blocks, block_size, dtype)
            for _ in range(config.n_layer)]


def count_params(params, config: LLMConfig) -> tuple[int, int]:
    """(total, active) parameter counts (reference get_num_params,
    model.py:588-617): active counts shared experts + n_act_routed routed
    experts per MoE block, everything else fully."""
    sizes = jax.tree_util.tree_map(lambda x: int(x.size), params)
    flat = jax.tree_util.tree_flatten_with_path(sizes)[0]
    total = sum(v for _, v in flat)
    if not config.moe:
        return total, total
    inactive = 0
    n_routed, k = config.n_routed, config.n_act_routed
    for path, size in flat:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if any(k_ in ("experts_fc", "experts_proj") for k_ in keys):
            per_expert = size // config.n_exp
            inactive += per_expert * (n_routed - k)
    return total, total - inactive
