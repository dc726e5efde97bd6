"""Continuous-batching decode engine over a PAGED KV cache with radix
prefix reuse.

The serving-shaped inference path the ROADMAP's "heavy traffic from
millions of users" north star needs. Round 8 built this engine on a fixed
(n_slots, S) slot cache; this round replaces the slot cache with a
vLLM-style paged cache (ops/block_pool.py) because the slot cache paid
for the worst case twice — S rows of HBM per slot regardless of the
actual sequence length, and a full prefill per request even when
thousands of requests share a system prompt:

* **Paged pool + block tables**: ONE (n_blocks, block_size, ...) pool set
  per layer that keeps a sequence's whole history lives for the engine's
  lifetime (a patterned model's window layers keep a ring a slot instead,
  no block of any pool and no column of the table: models/gpt.py
  `init_paged_cache`, ops/window_attention.py) — float k/v pools as
  (n_blocks, block_size, L), the kv heads merged into L = n_kv * head_size
  lanes rounded up to 128, the one shape the donated argument, the
  in-place row write and the kernels all hold in the same dense layout,
  so no step copies a pool (ops/block_pool.py); each live sequence owns an
  ordered list of blocks recorded in a per-sequence row of the
  (n_slots, max_blocks) block table. Cache writes indirect through the
  table (`paged_update`); the flash-decode kernel prefetches the table
  row and DMAs blocks straight from the pool; non-kernel paths read a
  gathered logical view — bit-compatible with the old contiguous cache.
  Retired slots' table rows are zeroed so the fused step's dead-slot
  write lands in the reserved null block, never in a reallocated one.
* **Radix prefix reuse**: full prompt blocks are content-addressed by
  chain key (block_pool docstring); at admission the longest cached
  block-chain prefix is SHARED (refcounted, immutable — copy-on-write at
  block granularity: the partial tail is always private), and only the
  suffix is prefilled, into its pow2 bucket. A shared system prompt
  prefills once; followers admit with a near-empty prefill — at high
  shared-prefix traffic this beats any kernel win (PERF.md). Retiring
  sequences publish their full blocks into the refcount-0 LRU, so hot
  prefixes stay resident in HBM that would otherwise idle.
* **Block-level preemption, not shedding**: when a live sequence needs a
  block and the pool is exhausted (every block referenced), the
  youngest-admitted live sequence is retired with reason 'preempted'
  carrying its tokens so far — callers (engine.run, serve/scheduler.py)
  REQUEUE it; its published blocks make the re-prefill a prefix-cache
  hit. 'cache_full' now only means a single sequence hit `max_len`;
  admission-side exhaustion raises `NoFreeBlocks` (the request stays
  queued — shed remains reserved for admission-bound overflow).
* **Bucketed prefill / one fused step / mesh-awareness** are unchanged
  from round 8: suffixes are right-padded to pow2 buckets (one compiled
  prefill per bucket — prefix length is traced, so reuse does not add
  traces), every live slot advances in a single jitted step traced once,
  and under a mesh the pools shard kv heads over 'model' (a merged-lane
  pool: its lanes, when they carry no pad) and blocks over 'data' via
  `sharding.decode_cache_pspec`.
* **Chunked prefill fused into the decode step** (`prefill_chunk=N`,
  round 12 — Sarathi-style): instead of one monolithic bucket prefill
  per admission that stalls every live decode stream, each admitted
  prompt is split into <=N-token chunks and ONE chunk rides each fused
  step next to all live decode tokens, in a single jitted program
  (`_get_fused_step_fn`). The chunk buffer is a fixed (1, N) trace; the
  slot, write offset, and valid length are TRACED arguments — no new
  traces per prompt length, and the pow2 buckets retire to a chunk-size
  pad. A patterned model's program walks its layers once with both row
  sets, so that an expert layer reads its held experts once for the
  chunk's rows and the decode rows together (`make_fused_step_fn`,
  `merged_program_share`). The program computes all N chunk rows beside
  the decode rows whatever they hold, so a step's cost does not depend on
  the take: the
  oldest partial prompt fills the buffer, min(N, what is left of it) ids
  (`_next_chunk`), and a prompt of up to N ids is ONE chunk-carrying
  program. N is a multiple of the block size and a reused prefix is whole
  blocks, so every chunk writes at a block-aligned offset. What a chunk
  costs the live streams is set by N, the program's shape; how full the
  programs' chunk rows ran is `chunk_fill_share`. While a slot prefills it
  is PARKED: live=False (token frozen) and its device position points at
  the always-empty last table column, so the fused decode write lands in
  the null block, never in its real cache. Per-slot prefill progress
  (`_Slot.suffix_done`) composes with everything else: a mid-prefill
  preemption retires the partial with its already-written full blocks
  registered in the radix index, so the requeued resume re-admits with a
  prefix hit and only the tail left to chunk in. `prefill_chunk=0` keeps
  the legacy all-or-nothing wave path (the A/B baseline).
* **Self-speculative decoding** (`SPEC_DECODE=auto|on|off`, `SPEC_K`;
  round 16): decode is bandwidth-bound — every step reads the full
  weights to emit ONE token per slot. The spec step amortizes that read:
  a host-side n-gram / prompt-lookup drafter (`ngram_propose`) proposes
  up to K tokens per live slot from the slot's own emitted history +
  prompt, and ONE jitted verify program (`make_spec_step_fn`) runs the
  K+1-token cached forward for every slot at once, accepts the longest
  draft prefix matching the model's own greedy argmax, and emits one
  free correction token past it — exact acceptance, so greedy output is
  bit-identical to the plain step (pinned in tests/test_spec_decode.py).
  Accepted tokens advance `pos` and the paged cache by a variable
  per-slot stride (`paged_update`'s multi-row branch); rejected tails
  roll back nothing — their rows sit past the new position, causally
  masked and overwritten before they could ever be attended, exactly
  like the slot cache's retired rows. Draft buffers are fixed (n_slots,
  K) traces with per-slot validity lengths TRACED, so any draft mix
  shares one compiled program. Greedy only: temperature>0 falls back to
  the plain step (acceptance compares argmax, which would change the
  sampling distribution).

* **Host-RAM KV tier** (`KV_HOST_TIER=auto|on|off`, `KV_HOST_BLOCKS`;
  this round — the ZeRO-Offload thesis applied to serving): the prefix
  cache was capped at HBM size — an evicted refcount-0 registered block
  was simply gone. With the tier on, the pool's eviction hook DEMOTES
  the block's rows (every cache leaf, int8 scale sidecars included) to a
  host-side pool (ops/kv_tier.py) with its own block budget and LRU,
  still keyed by the radix chain key; `_match_prefix` becomes tier-aware
  (HBM hit > host hit > miss) and PROMOTES a host-hit chain back into
  freshly allocated HBM blocks via one batched device_put plus a single
  fixed-shape jitted copy program — before the slot's first step, so
  the step/admit families never trace anything new and the promote cost
  lands in queue-wait, not ITL. One PCIe copy buys back a prefill; the
  host/HBM ratio multiplies the effective prefix cache. The engine also
  exports a compact radix-prefix digest (`kv_digest`) that
  serve/router.py uses for cache-aware sticky dispatch across replicas.

* **One step program in flight** (PR 31): `step()` plans and dispatches
  program k+1 BEFORE it drains program k, so the device always has its
  next program queued while the host fetches tokens, retires, and the
  scheduler emits, yields to its clients and admits. What makes that
  possible: almost everything the host plans with is a COUNT — positions,
  generated tokens against the budget, prefill progress, the blocks a
  write needs, the chunk's slot/offset/length, `budget` and `cache_full`
  retirement — and `tok`/`pos`/`live`/`caches` already flow from program
  to program as device arrays. Only four things need the tokens' VALUES,
  and they wait for the drain: the stream append, the `eos` test,
  publishing generated rows' blocks, and the `Retired` record. So `_Slot`
  is split into planned counts (advanced at dispatch) and observed tokens
  (appended at the drain). Retirement by count is planned: the slot is
  out of k+1's mask. Retirement by value (`eos`) lags one program: the
  slot runs one token too far, the token is dropped, its row lies in the
  sequence's own block and is released with it (`overrun_tokens`). A
  result belongs to the occupant that was planned, not to the slot
  (`_Program.occupants`), so `cancel()` and `admit()` between calls stay
  legal while a program runs. The lookahead declines itself where the
  plan needs values — a speculative engine (drafts read the tokens), a
  dry pool (a preemption hands out the victim's tokens), a host-tier
  promotion, wave mode — and the turn is then the same code with nothing
  queued ahead, recorded as the dispatch's `drain_reason`;
  `overlap_share` is the fraction of programs that had a predecessor
  running. The rng fold `t` is the program's number either way.

Host/device split as before: sampling, cache writes, and positions are
device-side; the allocator, radix index, and retirement logic are plain
Python on the host thread that owns the engine.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.engine.counts import (RETIRE_REASONS,
                                                   EngineCounts)
from distributed_pytorch_tpu.models.generate import sample_token
from distributed_pytorch_tpu.models.gpt import Rows, init_paged_cache
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.obs.retrace import TraceGuard
from distributed_pytorch_tpu.obs.trace import phase
from distributed_pytorch_tpu.ops import kv_tier
from distributed_pytorch_tpu.ops.block_pool import (BlockPool, NoFreeBlocks,
                                                    _child_digest, chain_keys)
from distributed_pytorch_tpu.parallel import context


# ----------------------------------------------------------------------
# device-program factories
# ----------------------------------------------------------------------
# The engine's three compiled families live at MODULE level so the static
# comms auditor (parallel/commscheck.py) traces the exact program the
# engine serves — a copy of the step body in the auditor would drift the
# first time the engine changed. `on_trace` carries the engine's
# trace-guard side effect; the auditor passes None (its traces must not
# count against a live engine's budget).

def _ctx_kw(model, **ctx) -> dict:
    """`state_ctx` for a patterned model's layers that keep per-slot state
    or count per row (models/gpt.py MixerBlock); nothing for the others,
    whose programs stay as they were."""
    return {"state_ctx": ctx} if model.config.layer_pattern else {}


def make_step_fn(model, sample_fn, *, on_trace=None):
    """Plain decode step: advance every live slot by one token."""

    def step(variables, caches, tok, pos, live, bt, rng, t, qparams):
        if on_trace is not None:
            on_trace()  # trace-time side effect
        from distributed_pytorch_tpu.ops.quant import use_quantized_params
        with use_quantized_params(qparams), jax.named_scope("decode"):
            # quantized weights (when a store is active): decode
            # matmuls read int8 codes instead of the bf16 kernels —
            # the unused bf16 leaves are pruned from the compiled step
            logits, _, caches = model.apply(
                variables, tok[:, None], None, caches, pos,
                deterministic=True, block_tables=bt,
                **_ctx_kw(model, live=live))
        with jax.named_scope("sample"):
            nxt = sample_fn(logits[:, -1, :], jax.random.fold_in(rng, t))
        # dead slots: freeze the token and position (their table row is
        # zeroed, so the write lands in the null block — nothing reads
        # it, no cleanup needed)
        nxt = jnp.where(live, nxt, tok)
        pos = pos + live.astype(jnp.int32)
        return caches, nxt, pos

    return step


def make_fused_step_fn(model, sample_fn, n_slots: int, table_width: int,
                       *, on_trace=None):
    """The chunked-prefill step: ONE program that runs <=N prefill tokens
    of one partial prompt plus every live decode token, at the cost of N
    rows whatever the take. The chunk buffer is a fixed (1, prefill_chunk)
    shape; the target slot, block-aligned write offset, and valid length
    are traced, so the whole serving mix shares this single trace (the
    chunked analogue of `prefix_len` being traced in the wave admit).

    A patterned model whose weights are not quantised walks its layers
    ONCE with both row sets (models/gpt.py `Rows`): the chunk and the
    decode tokens touch disjoint slots, block tables and state rows, each
    goes through a state-space or attention layer on its own, chunk
    first, and an expert layer makes one call over all their rows, so the
    held experts are read once a program. A classic model, and a
    quantised engine (whose chunk runs outside the store and whose decode
    tokens inside it: two sets of weights), run the model twice."""
    W = table_width

    def fused_step(variables, caches, tok, pos, live, bt, rng, t,
                   qparams, ctoks, cslot, coff, clen, cdone):
        if on_trace is not None:
            on_trace()  # trace-time side effect
        # chunk prefill: write [coff, coff+N) of the chunk slot's
        # logical sequence (rows past clen are pads landing in the
        # null block via zero table entries) and attend causally over
        # the sequence's own prior blocks. Runs OUTSIDE the quantized
        # store, like the wave admit — prefill stays bf16 under
        # weight-only int8.
        bt_row = jax.lax.dynamic_slice(
            bt, (cslot, jnp.int32(0)), (1, W))

        def sample_first(clogits):
            with jax.named_scope("sample"):
                return sample_fn(clogits[:, -1, :],
                                 jax.random.fold_in(rng, 2 ** 21 + t))

        if model.config.layer_pattern and qparams is None:
            with jax.named_scope("decode"):
                (clogits, logits), _, caches = model.apply(
                    variables,
                    (Rows(ctoks, coff, bt_row,
                          {"slot": cslot, "valid_len": clen}, clen - 1,
                          scope="chunk_prefill"),
                     Rows(tok[:, None], pos, bt, {"live": live})),
                    None, caches, deterministic=True)
            first = sample_first(clogits)
        else:
            with jax.named_scope("chunk_prefill"):
                clogits, _, caches = model.apply(
                    variables, ctoks, None, caches, coff,
                    deterministic=True, logits_idx=clen - 1,
                    block_tables=bt_row,
                    **_ctx_kw(model, slot=cslot, valid_len=clen))
            first = sample_first(clogits)
            from distributed_pytorch_tpu.ops.quant import \
                use_quantized_params
            with use_quantized_params(qparams), jax.named_scope("decode"):
                logits, _, caches = model.apply(
                    variables, tok[:, None], None, caches, pos,
                    deterministic=True, block_tables=bt,
                    **_ctx_kw(model, live=live))
        with jax.named_scope("sample"):
            nxt = sample_fn(logits[:, -1, :], jax.random.fold_in(rng, t))
        # dead/parked slots freeze their token; parked positions point
        # at the null block so the decode write above was harmless
        nxt = jnp.where(live, nxt, tok)
        pos = pos + live.astype(jnp.int32)
        # a chunk that completes its prompt activates the slot
        # in-step: first sampled token + true position land exactly
        # like a wave admit's would
        sel = (jnp.arange(n_slots) == cslot) & cdone
        nxt = jnp.where(sel, first[0], nxt)
        pos = jnp.where(sel, coff + clen[0], pos)
        live = jnp.logical_or(live, sel)
        return caches, nxt, pos, live

    return fused_step


def make_admit_fn(model, sample_fn, *, on_trace=None):
    """Wave-mode bucket prefill: suffix prefill straight into the slot's
    pool blocks. One compiled program per pow2 bucket — the prompt buffer
    shape is the bucket; prefix/true lengths and the slot are traced."""

    def admit(variables, caches, tok, pos, live, bt, prompt, prefix_len,
              true_len, slot, rng):
        if on_trace is not None:
            on_trace()
        # the reused prefix is already resident, so the forward starts at
        # prefix_len (TRACED — any prefix length shares this bucket's
        # compiled program) and attends the whole logical view
        bt_row = jax.lax.dynamic_slice(
            bt, (slot, jnp.int32(0)), (1, bt.shape[1]))
        logits, _, caches = model.apply(
            variables, prompt, None, caches, prefix_len,
            deterministic=True, logits_idx=true_len - 1,
            block_tables=bt_row,
            **_ctx_kw(model, slot=slot, valid_len=true_len))
        with jax.named_scope("sample"):
            first = sample_fn(logits[:, -1, :], rng)
        tok = tok.at[slot].set(first[0])
        pos = pos.at[slot].set(prefix_len + true_len[0])
        live = live.at[slot].set(True)
        return caches, tok, pos, live, first

    return admit


def ngram_propose(tokens, k: int, *, min_match: int = 2,
                  max_match: int = 4) -> list:
    """Host-side n-gram / prompt-lookup drafter: find the most recent
    earlier occurrence of the sequence's current suffix n-gram (longest
    match first, n in [min_match, max_match]) and propose the up-to-k
    tokens that followed it. Pure Python over the slot's token list — no
    device work, no model — so a draft costs microseconds against a
    step's milliseconds. Returns [] on a miss (the slot rides the verify
    step with draft_len 0, emitting exactly the plain step's token)."""
    L = len(tokens)
    if k <= 0 or L < min_match + 1:
        return []
    for n in range(min(max_match, L - 1), min_match - 1, -1):
        pattern = tokens[L - n:]
        for i in range(L - n - 1, -1, -1):
            if tokens[i:i + n] == pattern:
                cont = tokens[i + n:i + n + k]
                if cont:
                    return [int(t) for t in cont]
                break  # suffix-adjacent match with nothing after it
    return []


def make_spec_step_fn(model, sample_fn, spec_k: int, *, on_trace=None):
    """Speculative verify step: ONE program scores every live slot's
    committed token + K draft tokens in a single K+1-position cached
    forward (the batched generalization of the chunk forward), computes
    each slot's accept length — the longest draft prefix where the
    model's own greedy argmax equals the draft — and emits the free
    correction token at the first mismatch (or the bonus position when
    the whole draft holds). The draft buffer is a fixed (n_slots, K)
    shape; per-slot validity lengths are TRACED, so every draft mix
    shares this single trace. KV rows for all K+1 positions are written
    through `paged_update`'s multi-row branch BEFORE attention (write-
    then-attend, as everywhere else); rows past a slot's accepted length
    are rejected-tail garbage at positions the causal mask hides until
    later steps overwrite them — no rollback needed."""
    K = spec_k

    def spec_step(variables, caches, tok, pos, live, bt, rng, t, qparams,
                  draft, draft_len):
        if on_trace is not None:
            on_trace()  # trace-time side effect
        from distributed_pytorch_tpu.ops.quant import use_quantized_params
        seq = jnp.concatenate([tok[:, None], draft], axis=1)  # (B, K+1)
        with use_quantized_params(qparams), jax.named_scope("decode"):
            logits, _, caches = model.apply(
                variables, seq, None, caches, pos, deterministic=True,
                block_tables=bt, all_logits=True)          # (B, K+1, V)
        B = seq.shape[0]
        V = logits.shape[-1]
        # greedy targets at every position, through the SAME sample_fn as
        # the plain step (argmax at temperature 0 — rng is ignored, so
        # the fold_in choice cannot perturb parity)
        with jax.named_scope("sample"):
            g = sample_fn(logits.reshape(B * (K + 1), V),
                          jax.random.fold_in(rng, t)).reshape(B, K + 1)
        # accept length: longest draft prefix matching the targets,
        # masked to each slot's valid draft length
        valid = jnp.arange(K)[None, :] < draft_len[:, None]
        match = (draft == g[:, :K]) & valid
        acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        # the correction token: the target right past the accepted prefix
        nxt = jnp.take_along_axis(g, acc[:, None], axis=1)[:, 0]
        # dead slots freeze token/pos and report 0 accepted (their table
        # rows are zeroed, so the K+1 writes landed in the null block)
        nxt = jnp.where(live, nxt, tok)
        acc = jnp.where(live, acc, 0)
        pos = pos + jnp.where(live, acc + 1, 0)
        return caches, nxt, pos, acc

    return spec_step


def prefill_bucket_for(prompt_len: int, min_bucket: int, block_size: int,
                       max_len: int) -> int:
    """The pow2 bucket a (suffix of this length's) prefill runs in —
    admissions sharing a bucket share one compiled prefill trace. The
    floor is max(min_bucket, block_size) so buckets stay whole blocks."""
    b = max(min_bucket, block_size)
    while b < prompt_len:
        b *= 2
    return min(b, max_len)


def enumerate_prefill_buckets(min_bucket: int, block_size: int,
                              max_len: int) -> list:
    """Every distinct bucket `prefill_bucket_for` can return over prompt
    lengths 1..max_len — i.e. the complete static set of wave-admit
    program signatures. Closed form, no tracing: the floor bucket, then
    doublings clipped at max_len."""
    buckets = []
    b = min(max(min_bucket, block_size), max_len)
    while True:
        buckets.append(b)
        if b >= max_len:
            break
        b = min(b * 2, max_len)
    return buckets


def enumerate_trace_signatures(*, min_bucket: int, block_size: int,
                               max_len: int, prefill_chunk: int,
                               spec_k: int = 0) -> dict:
    """Statically enumerate the distinct compiled-program signatures one
    engine configuration can legitimately build, keyed by trace-guard
    family (obs/retrace.py). Chunked mode fuses prefill into the decode
    step (one fused_step program, plus the chunk-free plain step), so its
    admit count is 0 for ANY prompt mix; wave mode compiles one admit per
    pow2 bucket. Speculative decoding (spec_k > 0) adds exactly ONE
    spec_step program: the draft buffer is a fixed (n_slots, K) shape
    and validity lengths are traced, so every draft mix — including the
    all-miss mix — shares it. The host KV tier adds exactly ONE promote
    program regardless of chain length (the copy's shape is one block's
    rows; the block id is traced), counted here as the static max — a
    tier-off engine budgets it to 0 and never builds it.
    parallel/commscheck.py asserts these counts against the engine's
    TraceGuard budgets at lint time."""
    buckets = enumerate_prefill_buckets(min_bucket, block_size, max_len)
    spec = 1 if spec_k else 0
    if prefill_chunk:
        return {"step": 1, "fused_step": 1, "admit": 0,
                "spec_step": spec, "promote": 1, "buckets": []}
    return {"step": 1, "fused_step": 0, "admit": len(buckets),
            "spec_step": spec, "promote": 1, "buckets": buckets}


@dataclasses.dataclass
class Retired:
    """A finished sequence: its tokens (prompt + generated) and why it
    stopped — 'eos' | 'budget' | 'cache_full' | 'cancelled' |
    'preempted' (the pool needed its blocks; resubmit `tokens` with the
    remaining budget to resume from the retained prefix blocks)."""

    tokens: list
    reason: str
    prompt_len: int


@dataclasses.dataclass
class Admission:
    """What `admit()` hands back: the sequence id, the first sampled token
    (prefill samples it — a streaming caller's TTFT token; None in
    chunked-prefill mode, where the first token arrives from the fused
    step that runs the prompt's LAST chunk), prefix-cache accounting
    (`prefix_len` reused tokens, `prefilled` suffix tokens to compute),
    and, for a request that finished AT prefill (1-token budget, instant
    EOS — wave mode only), its `Retired` record."""

    seq_id: int
    first_token: Optional[int]
    retired: Optional[Retired] = None
    prefix_len: int = 0
    prefilled: int = 0


@dataclasses.dataclass
class StepResult:
    """One fused step's host-visible output: `emitted` maps every sequence
    that advanced this step to the LIST of tokens it emitted, in stream
    order — one token on a plain step (including a sequence whose final
    prefill chunk ran this step: its entry is the first sampled token),
    up to K+1 on a speculative step (accepted draft prefix + the
    correction token, truncated at EOS); `retired` holds the sequences
    that finished, including any preempted BEFORE the step ran (those
    emit no token). `prefill_tokens` is the chunk work fused into this
    step (0 on pure decode steps and in wave mode) — the scheduler feeds
    it to the `prefill_tokens_per_step` histogram. `drafted`/`accepted`
    count this step's speculative proposals and how many of them the
    verify accepted (both 0 on non-spec steps) — the scheduler's
    spec_drafted_tokens/spec_accepted_tokens counters and the flight
    ring's per-step acceptance view read these."""

    emitted: dict
    retired: dict
    prefill_tokens: int = 0
    drafted: int = 0
    accepted: int = 0


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one occupied table row. Split by what
    the host knows when: `n_new`, `pos`, `suffix_done` and `done` are
    PLANNED, advanced when a program is dispatched (they are counts, so
    the next program can be planned while this one runs); `tokens` is
    OBSERVED, appended when the program's sampled tokens drain. Between
    the two the planned counts run one program ahead of `tokens`."""

    seq_id: int
    tokens: list          # prompt + generated tokens drained so far
    prompt_len: int
    n_new: int            # generated tokens planned so far
    max_new: int
    pos: int              # device pos mirror: next cache write position
                          # (for a partial slot: prefill rows planned)
    blocks: list          # owned physical block ids, logical order
    order: int            # admission counter (preemption picks the max)
    # retirement by count ('budget' | 'cache_full'), planned with the
    # slot's last program: out of every later program's live mask, in its
    # slot until that program drains and hands out the `Retired` record
    done: Optional[str] = None
    # chunked-prefill progress (prefill_chunk > 0): the suffix left to
    # compute after the prefix-cache hit, and how much of it has been
    # chunked into the cache so far. suffix_done < len(suffix) marks the
    # slot PARTIAL: parked out of the decode batch until its last chunk.
    suffix: Optional[list] = None
    suffix_done: int = 0
    prefix_len: int = 0


@dataclasses.dataclass
class _Program:
    """One dispatched step program whose sampled tokens have not drained
    yet: what the host planned for it by count, and the device array its
    tokens arrive in. A result belongs to the OCCUPANT that was planned,
    not to the slot: `occupants` carries slot -> seq_id, and a token for
    an occupant that left meanwhile (eos one program late, a cancel) is
    dropped at the drain, never credited to the slot's next one."""

    t: int                  # the program's number (its rng fold, n_steps)
    kind: str               # 'decode' | 'fused' | 'spec'
    occupants: dict         # slot -> seq_id receiving a token, stream order
    retiring: dict          # slot -> 'budget' | 'cache_full' with this one
    preempted: dict         # seq_id -> Retired, yielded before it ran
    n_live: int             # decoding slots in its mask
    # cache tiles of `block_size` rows its decode attention call walks for
    # those slots, from the planned lengths: the paged kernel's grid step
    # is a sequence and holds every one of its live tiles
    # (`EngineCounts.decode_tiles_per_grid_step`)
    live_tiles: int = 0
    # key rows ONE layer's attention calls of this program read, by the
    # planned lengths: {"decode" | "chunk": (of a whole history, of the
    # window)}; a model with window layers alone
    kv_rows: Optional[dict] = None
    # (query, key) pairs ONE layer's chunk call lets through: (under the
    # causal mask, under the window's too) (`_causal_pairs`)
    chunk_pairs: tuple = (0, 0)
    # the fused chunk: (slot, seq_id, take, prefill rows after it)
    chunk: Optional[tuple] = None
    # a speculative step: (draft, draft_len, device accept lengths)
    spec: Optional[tuple] = None
    overlapped: bool = False        # dispatched behind a running program
    drain_reason: Optional[str] = None   # where it was not: why
    # what it runs with, from the plan to the dispatch: (block tables,
    # a rebuilt live mask or None for the last program's own, the chunk's
    # traced arguments or ())
    inputs: Optional[tuple] = None
    tok: Any = None         # device (n_slots,) sampled tokens, once queued
    # a patterned model's: its chunk starts a slot's recurrent state anew;
    # the device arrays its expert layers' routing counts arrive in
    state_reset: bool = False
    expert_stats: Any = None


def _causal_pairs(off: int, take: int, window: int = 0) -> int:
    """(query, key) pairs of a chunk of `take` rows behind `off` cached
    ones: the query at position off + t sees off + t + 1 keys, its own
    included, at most `window` of them where there is one."""
    whole = min(take, max(window - off, 0)) if window else take
    return whole * off + whole * (whole + 1) // 2 + (take - whole) * window


class _WouldPreempt(Exception):
    """Planning the next program behind a running one met a dry pool: a
    preemption hands out the victim's tokens, which the running program
    is still producing — the lookahead declines and the turn drains."""


class DecodeEngine:
    """Continuous batching over the paged KV cache: admit prompts (sharing
    any cached prefix), step all live slots in one fused jitted call,
    retire finished sequences, preempt-and-requeue when the pool runs dry.

    >>> eng = DecodeEngine(model, variables, n_slots=8, temperature=0.0)
    >>> outs = eng.run(prompts, max_new_tokens=64)   # list of token lists

    Paging knobs: `block_size` (KV rows per block, pow2; default 16 capped
    at `min_bucket` so the pow2 buckets stay block-aligned — serving on
    TPU wants 128+ so the paged kernel's DMA tiles are worth it),
    `n_blocks` (pool size; default sized to the old slot cache's
    n_slots x max_len footprint, i.e. never preempts under slot-cache
    load; smaller pools trade preemption for HBM), `prefix_cache=False`
    disables content-addressed reuse (the A/B baseline).

    `prefill_chunk=N` fuses Sarathi-style chunked prefill into the step
    (module docstring): each fused step runs the next min(N, what is
    left) prompt ids of the oldest partial prompt plus all live decode
    tokens in ONE trace — bounded ITL under prefill-heavy load. N must be
    a multiple of `block_size`. The chunk buffer is N rows whatever it
    holds, so N sets what a chunk-carrying step costs: a smaller N is a
    cheaper step and more of them a prompt. 0 (default) keeps the
    all-or-nothing bucketed wave prefill (the A/B baseline).

    Quantized serving (ops/quant.py) is unchanged: `cache_dtype='int8'`
    quantizes on the block write (scale sidecars ride pool-shaped
    buffers), `quantize_weights=True` runs decode matmuls on int8 codes.

    The stable accounting surface a scheduler reads: `n_free`/`occupancy`
    plus the paged additions `block_utilization`/`block_fragmentation`
    (never the private `_slots`), and as attributes of the engine every
    count of `counts` (engine/counts.py: `retire_counts`, ...).
    """

    def __init__(self, model, variables: dict, *, n_slots: int = 8,
                 max_len: Optional[int] = None, cache_dtype=None,
                 quantize_weights: bool = False,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 eos_id: Optional[int] = None, rng=None,
                 mesh=None, recipe: str = "single", min_bucket: int = 16,
                 block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefill_chunk: int = 0,
                 spec_decode: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 host_tier: Optional[bool] = None,
                 host_blocks: Optional[int] = None,
                 flight_capacity: int = 4096,
                 aot_store=None):
        cfg = model.config
        self.model = model
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len or cfg.block_size
        assert self.max_len <= cfg.block_size
        # Quantized serving knobs (ops/quant.py) — see class docstring.
        from distributed_pytorch_tpu.ops import quant
        if cache_dtype is not None and not isinstance(cache_dtype, str):
            cache_dtype = jnp.dtype(cache_dtype).name
        want_kv = quant.resolve_gate(quant.kv_quant_mode(),
                                     cache_dtype == "int8")
        if want_kv and quant.quant_kv_usable(cfg):
            self.cache_dtype = jnp.int8
        elif cache_dtype and cache_dtype != "int8":
            self.cache_dtype = jnp.dtype(cache_dtype)
        else:
            self.cache_dtype = model.compute_dtype
        self.kv_quantized = self.cache_dtype == jnp.int8
        self.weights_quantized = quant.resolve_gate(quant.weight_quant_mode(),
                                                    quantize_weights)
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.min_bucket = min_bucket
        # speculative decoding (module docstring): SPEC_DECODE=auto defers
        # to the constructor request, on/off overrides it — the same
        # resolve_gate contract as the quant knobs. Greedy only: the
        # verify compares argmax targets, so any temperature>0 engine
        # silently keeps the plain step regardless of the gate.
        from distributed_pytorch_tpu.config import knob
        k = spec_k if spec_k is not None else knob("SPEC_K")
        self.spec_k = max(int(k), 0)
        self.spec_decode = (quant.resolve_gate(knob("SPEC_DECODE"),
                                               bool(spec_decode))
                            and self.spec_k > 0 and temperature == 0.0)
        # a model with recurrent or window layers (`cfg.slot_state`) keeps
        # per-slot state that is no block of the pool. Resident blocks are then
        # NOT a prefix's state, and a rejected draft cannot be rolled
        # back: until state snapshots exist, prefix reuse, the host tier
        # and speculation stand down, aloud (`features_declined`, the
        # paths log) and counted (`prefix_reuse_declined`, an admission
        # each), never silently. A preempted sequence resumes by
        # recomputing from its tokens.
        self.features_declined: list[str] = []
        self.prefix_reuse_declined = 0
        self._prefix_asked = bool(prefix_cache)
        if cfg.slot_state:
            assert mesh is None, \
                "per-slot state leaves have no sharding rule yet"
            for name, asked in (("prefix_cache", prefix_cache),
                                ("spec_decode", self.spec_decode),
                                ("host_tier", host_tier)):
                if asked:
                    self.features_declined.append(name)
                    paths.note(name, "declined",
                               f"{cfg.slot_state} keep per-slot state "
                               "with no snapshot or roll-back yet")
            prefix_cache, host_tier, host_blocks = False, False, 0
            self.spec_decode = False
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._mesh = mesh
        self._recipe = recipe

        # paged-cache geometry: pow2 blocks no larger than the smallest
        # prefill bucket, so every bucket is a whole number of blocks
        bs = block_size or min(16, min_bucket)
        assert bs > 0 and bs & (bs - 1) == 0, \
            f"block_size must be a power of two, got {bs}"
        assert self.max_len % bs == 0, \
            f"max_len {self.max_len} not a multiple of block_size {bs}"
        self.block_size = bs
        self.max_blocks = self.max_len // bs
        if n_blocks is None:
            # slot-cache-equivalent footprint (+ null block), rounded up
            # so the pool's block axis stays 'data'-shardable on a mesh
            n_blocks = n_slots * self.max_blocks + 1
            n_blocks += (-n_blocks) % 8
        assert n_blocks > self.max_blocks, (
            f"pool of {n_blocks} blocks cannot hold one max_len sequence "
            f"({self.max_blocks} blocks) plus the null block")
        self.n_blocks = n_blocks
        self.block_pool = BlockPool(n_blocks, bs)
        self.prefix_cache = prefix_cache

        # host-RAM second tier (ops/kv_tier.py): KV_HOST_TIER=auto defers
        # to the constructor request / a nonzero KV_HOST_BLOCKS budget,
        # on/off overrides — the resolve shape the quant knobs use.
        # Meaningless without the radix index (no chain keys to demote
        # under), so prefix_cache=False forces it off.
        tier_mode = knob("KV_HOST_TIER")
        if host_tier is not None:
            tier_mode = "on" if host_tier else "off"
        hb = host_blocks if host_blocks is not None \
            else int(knob("KV_HOST_BLOCKS"))
        tier_on = prefix_cache and (
            tier_mode == "on" or (tier_mode == "auto" and hb > 0))
        if tier_on and hb <= 0:
            hb = self.n_blocks       # default budget: mirror the HBM pool
        self.host_tier = kv_tier.HostTier(hb) if tier_on else None
        if self.host_tier is not None:
            self.block_pool.on_evict = self._demote_block
        # cumulative ancestry digest -> cached depth (blocks), LRU-capped:
        # the router-facing radix-prefix digest (`kv_digest`). Maintained
        # even with the tier off — stickiness pays for plain HBM prefix
        # reuse too.
        self._digest_k = max(int(knob("KV_TIER_DIGEST_K")), 1)
        self._digest_index: collections.OrderedDict[str, int] = \
            collections.OrderedDict()
        self._digest_cap = max(64, 8 * self._digest_k)

        # chunked prefill (module docstring): the rows of the fused step's
        # chunk buffer. Whole blocks, so every chunk's write offset stays
        # block-aligned (paged_update's prefill contract).
        if prefill_chunk:
            assert prefill_chunk % bs == 0 and prefill_chunk >= bs, (
                f"prefill_chunk {prefill_chunk} must be a positive "
                f"multiple of block_size {bs}")
            prefill_chunk = min(prefill_chunk, self.max_len)
        self.prefill_chunk = prefill_chunk
        # slack table columns absorb the fixed-size chunk buffer's
        # overhang: the last chunk of a prompt ending near max_len writes
        # its full (block-aligned) buffer, and the rows past the prompt
        # must slice table entries that exist AND are zero (null-block
        # writes) — without the slack, dynamic_slice would clamp the
        # start and corrupt earlier blocks
        self.table_width = self.max_blocks + \
            (prefill_chunk // bs if prefill_chunk else 0)
        # partial slots park their decode-write position in the last
        # table column, which is never allocated: the fused step's
        # unavoidable write for a not-yet-live slot lands in block 0
        self._park_pos = (self.table_width - 1) * bs

        if mesh is not None:
            from distributed_pytorch_tpu.parallel import sharding as shd
            from jax.sharding import NamedSharding
            p_sh = shd.named(mesh, shd.params_pspecs(variables["params"],
                                                     recipe, mesh))
            sh_tree = {"params": p_sh}
            if "moe_state" in variables:
                sh_tree["moe_state"] = jax.tree_util.tree_map(
                    lambda _: NamedSharding(mesh, shd.P()),
                    variables["moe_state"])
            variables = jax.device_put(variables, sh_tree)
        self.variables = variables

        # weight-only int8: quantized once per engine (from the placed
        # params, so shardings carry through); passed as an ARGUMENT to
        # the jitted step — closing over concrete arrays would bake them
        # into the executable as constants
        self._qparams = None
        if self.weights_quantized:
            from distributed_pytorch_tpu.ops.quant import quantize_params
            with self._ctx():
                self._qparams = jax.jit(quantize_params)(variables["params"])

        caches = init_paged_cache(cfg, n_blocks, bs, dtype=self.cache_dtype,
                                  n_slots=n_slots)
        # an 'E' layer's cache slot carries a program's routing counts
        # OUT (models/gpt.py merge_expert_stats): `_dispatch` takes them
        # off the tree before it is donated to the next program
        self._expert_layers = [i for i, kind in
                               enumerate(cfg.layer_pattern) if kind == "E"]
        if mesh is not None:
            from distributed_pytorch_tpu.parallel import sharding as shd
            from jax.sharding import NamedSharding
            kv_heads = ((cfg.n_kv_heads, cfg.head_size)
                        if cfg.attn != "mla" else None)
            caches = jax.tree_util.tree_map(
                lambda c: jax.device_put(c, NamedSharding(
                    mesh, shd.decode_cache_pspec(tuple(c.shape), mesh,
                                                 kv_heads))),
                caches)
        self.caches = caches
        self.tok = jnp.zeros((n_slots,), jnp.int32)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self.live = jnp.zeros((n_slots,), bool)
        # host-mirrored block tables: rows of physical block ids per slot;
        # zeroed rows route dead-slot writes to the null block
        self._tables_h = np.zeros((n_slots, self.table_width), np.int32)
        self._tables_dirty = True
        self.block_tables = None
        self._sync_tables()

        self._slots: dict[int, _Slot] = {}     # slot index -> bookkeeping
        self._next_id = 0
        self._t = 0                            # programs dispatched (rng)
        self._n_admits = 0
        # one step program in flight (class docstring): dispatched by the
        # last step() call behind the one it drained, drained by the next
        self._inflight: Optional[_Program] = None
        # why the last call queued nothing behind the program it drained:
        # the `drain_reason` of the next dispatch
        self._declined: Optional[str] = None
        # the planned live set moved where no program's own output
        # follows (a retirement, a cancel): the mask is rebuilt from the
        # host's plan before the next dispatch
        self._live_dirty = False
        # a host-tier promotion rewrote the pools outside the step
        # programs since the last dispatch
        self._pools_rewritten = False
        # donation keeps the big pool in place on TPU; CPU jit warns on
        # unusable donations, so skip it there
        self._donate = (1,) if jax.default_backend() == "tpu" else ()
        self._step_fn = None
        self._fused_step_fn = None
        self._spec_step_fn = None
        self._promote_fn = None
        self._admit_fns: dict[int, Any] = {}
        # retrace guards (obs/retrace.py): each compiled family budgets
        # its legitimate trace count — step/fused_step trace ONCE for any
        # serving mix, admit once per prompt bucket (budget raised at
        # bucket creation). `step_traces`/`fused_step_traces` properties
        # keep the historical int surface for tests and bench asserts.
        self.trace_guards: dict[str, TraceGuard] = {
            "step": TraceGuard("engine.step"),
            "fused_step": TraceGuard("engine.fused_step"),
            "admit": TraceGuard("engine.admit", budget=0),
            "spec_step": TraceGuard(
                "engine.spec_step",
                budget=1 if self.spec_decode else 0),
            "promote": TraceGuard(
                "engine.promote",
                budget=1 if self.host_tier is not None else 0),
        }
        self.admit_traces: dict[int, int] = {}  # bucket -> trace count
        # AOT program store (parallel/aot_store.py, ISSUE 18): every
        # compiled-family getter routes through _build_aot — hit means a
        # deserialized executable and NO trace (the guards above stay at
        # 0 on a warmed spin-up), miss compiles as usual and writes
        # back. None (the default with the AOT_STORE knob off) keeps the
        # plain JIT path byte-for-byte.
        if aot_store is None:
            from distributed_pytorch_tpu.parallel.aot_store import \
                resolve_store
            aot_store = resolve_store()
        self.aot_store = aot_store or None   # False = explicitly off
        self._aot_origin = "runtime"
        # all the engine counts; a name this class lacks is read off it
        self.counts = EngineCounts(cfg, self.caches, n_slots, prefill_chunk)
        # step-level flight recorder (obs/flight.py): one record per
        # fused step in a bounded ring — the /debug/timeline payload and
        # the runs/*.jsonl post-hoc artifact
        self.flight = FlightRecorder(capacity=flight_capacity)
        # a turn of the recorder begins at the last record while work
        # waits for the caller: the last step() left live slots and
        # nothing emptied them since. `_traces_seen`: the trace guards'
        # sum at the turn's start
        self._work_waits = False
        self._traces_seen = 0

    # ------------------------------------------------------------------
    # jitted device programs
    # ------------------------------------------------------------------

    def _ctx(self):
        return (context.use_mesh(self._mesh) if self._mesh is not None
                else contextlib.nullcontext())

    def _sample(self, logits, rng):
        return sample_token(logits, rng, temperature=self.temperature,
                            top_k=self.top_k)

    def _sync_tables(self) -> None:
        """Push the host block tables to the device when they changed —
        BEFORE any step/admit, so a retired slot's zeroed row is live by
        the time the next dead-slot write could land."""
        if not self._tables_dirty:
            return
        bt = jnp.asarray(self._tables_h)
        if self._mesh is not None:
            from distributed_pytorch_tpu.parallel import sharding as shd
            from jax.sharding import NamedSharding
            bt = jax.device_put(bt, NamedSharding(self._mesh, shd.P()))
        self.block_tables = bt
        self._tables_dirty = False

    # -- AOT program store (parallel/aot_store.py, ISSUE 18) ------------

    def _sds_leaf(self, leaf):
        sh = leaf.sharding if (self._mesh is not None
                               and hasattr(leaf, "sharding")) else None
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)

    def _aot_avals(self, family: str, bucket: Optional[int] = None):
        """The exact call-site avals of one compiled family, derived
        from the live engine state (so store keys match between a
        warming process and a serving replica by construction)."""
        sds = lambda t: jax.tree_util.tree_map(self._sds_leaf, t)
        s32 = jax.ShapeDtypeStruct((), jnp.int32)
        if family == "admit":
            return (sds(self.variables), sds(self.caches), sds(self.tok),
                    sds(self.pos), sds(self.live),
                    self._sds_leaf(self.block_tables),
                    jax.ShapeDtypeStruct((1, bucket), jnp.int32), s32,
                    jax.ShapeDtypeStruct((1,), jnp.int32), s32,
                    sds(self._rng))
        if family == "promote":
            rows = jax.tree_util.tree_map(
                lambda c: jax.ShapeDtypeStruct(c.shape[1:], c.dtype),
                self.caches)
            return (sds(self.caches), rows, s32)
        base = (sds(self.variables), sds(self.caches), sds(self.tok),
                sds(self.pos), sds(self.live),
                self._sds_leaf(self.block_tables), sds(self._rng), s32,
                sds(self._qparams))
        if family == "fused_step":
            return base + (
                jax.ShapeDtypeStruct((1, self.prefill_chunk), jnp.int32),
                s32, s32, jax.ShapeDtypeStruct((1,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.bool_))
        if family == "spec_step":
            return base + (
                jax.ShapeDtypeStruct((self.n_slots, self.spec_k),
                                     jnp.int32),
                jax.ShapeDtypeStruct((self.n_slots,), jnp.int32))
        assert family == "step", family
        return base

    def _aot_env(self, family: str,
                 bucket: Optional[int] = None) -> dict:
        """Program-identity env for store keys AND the crosscheck's
        geometry record (aot_store.crosscheck re-enumerates the static
        program universe from this)."""
        env = {
            "kind": "engine",
            "model_cfg": dataclasses.asdict(self.cfg),
            "geometry": {
                "n_slots": self.n_slots, "max_len": self.max_len,
                "min_bucket": self.min_bucket,
                "block_size": self.block_size,
                "n_blocks": self.n_blocks,
                "table_width": self.table_width,
                "prefill_chunk": self.prefill_chunk,
                "spec_k": self.spec_k if self.spec_decode else 0,
                "host_tier": self.host_tier is not None,
                "cache_dtype": jnp.dtype(self.cache_dtype).name,
                "weights_quantized": self.weights_quantized,
                "temperature": self.temperature, "top_k": self.top_k,
                "recipe": self._recipe,
                "mesh": (dict(zip(self._mesh.axis_names,
                                  [int(x) for x in
                                   self._mesh.devices.shape]))
                         if self._mesh is not None else None),
            },
        }
        if bucket is not None:
            env["bucket"] = int(bucket)
        return env

    def _build_aot(self, family: str, jitted,
                   bucket: Optional[int] = None):
        """Route one compiled family through the AOT store: hit =
        deserialized executable (no trace), miss = lower+compile NOW
        (the guard marks, exactly like a cold first call) + write-back.
        Store off: the jitted fn passes through untouched."""
        if self.aot_store is None:
            return jitted
        from distributed_pytorch_tpu.parallel.aot_store import \
            SafeCompiled
        avals = self._aot_avals(family, bucket)
        with self._ctx():
            compiled = self.aot_store.build(
                family, jitted, avals, self._aot_env(family, bucket),
                origin=self._aot_origin)
        return SafeCompiled(compiled, jitted, self.aot_store, family)

    def warm_aot(self, origin: str = "warm") -> dict:
        """Eagerly build (load or compile+store) every program this
        configuration can request — `enumerate_trace_signatures`
        exactly: the plain step, the fused step (chunked) or one admit
        per pow2 bucket (wave), the spec step and the tier promote when
        their gates are on. After a warmed spin-up the engine serves
        with zero JIT compiles (TraceGuard counts stay 0). Returns the
        store's stats ({} with the store off)."""
        if self.aot_store is None:
            return {}
        prev, self._aot_origin = self._aot_origin, origin
        try:
            self._get_step_fn()
            if self.prefill_chunk:
                self._get_fused_step_fn()
            else:
                for b in enumerate_prefill_buckets(
                        self.min_bucket, self.block_size, self.max_len):
                    self._get_admit_fn(b)
            if self.spec_decode:
                self._get_spec_step_fn()
            if self.host_tier is not None:
                self._get_promote_fn()
        finally:
            self._aot_origin = prev
        return self.aot_store.stats()

    def describe_programs(self) -> dict:
        """Compile — ahead of the first request — the step programs this
        configuration serves with (the plain decode step; the fused
        chunk+decode step when `prefill_chunk` is set) and describe each
        (obs/paths.compile_and_describe: compile seconds, Pallas kernels
        BY NAME, memory accounting, dispatcher choices). The trace is
        shared with the later calls (the guards still count one); the
        executable is shared when the call's argument placement matches
        these avals and otherwise comes from the persistent compile cache
        — today the engine's first calls re-lower once or twice as its
        state arrays go from uncommitted to committed (seen with
        jax_log_compiles; PERF.md open questions). Store-built programs
        (an AOT hit has no lowering to read) are skipped."""
        from distributed_pytorch_tpu.obs import paths
        out = {}
        getters = {"step": self._get_step_fn}
        if self.prefill_chunk:
            getters["fused_step"] = self._get_fused_step_fn
        for family, get in getters.items():
            fn = get()
            if not hasattr(fn, "lower"):
                continue
            with self._ctx():
                out[f"engine.{family}"] = paths.compile_and_describe(
                    fn, *self._aot_avals(family))
        return out

    @property
    def aot_stats(self) -> dict:
        return self.aot_store.stats() if self.aot_store is not None \
            else {}

    def _get_step_fn(self):
        if self._step_fn is not None:
            return self._step_fn
        step = make_step_fn(self.model, self._sample,
                            on_trace=self.trace_guards["step"].mark)
        self._step_fn = self._build_aot(
            "step", jax.jit(step, donate_argnums=self._donate))
        return self._step_fn

    def _get_fused_step_fn(self):
        if self._fused_step_fn is not None:
            return self._fused_step_fn
        fused_step = make_fused_step_fn(
            self.model, self._sample, self.n_slots, self.table_width,
            on_trace=self.trace_guards["fused_step"].mark)
        self._fused_step_fn = self._build_aot(
            "fused_step", jax.jit(fused_step,
                                  donate_argnums=self._donate))
        return self._fused_step_fn

    def _get_spec_step_fn(self):
        if self._spec_step_fn is not None:
            return self._spec_step_fn
        spec = make_spec_step_fn(
            self.model, self._sample, self.spec_k,
            on_trace=self.trace_guards["spec_step"].mark)
        self._spec_step_fn = self._build_aot(
            "spec_step", jax.jit(spec, donate_argnums=self._donate))
        return self._spec_step_fn

    def _get_promote_fn(self):
        if self._promote_fn is not None:
            return self._promote_fn
        fn = kv_tier.make_promote_block_fn(
            on_trace=self.trace_guards["promote"].mark)
        # promote donates the CACHES (arg 0, vs arg 1 in the step
        # families) so the pool recycles in place on TPU
        donate = (0,) if jax.default_backend() == "tpu" else ()
        self._promote_fn = self._build_aot(
            "promote", jax.jit(fn, donate_argnums=donate))
        return self._promote_fn

    def _get_admit_fn(self, bucket: int):
        fn = self._admit_fns.get(bucket)
        if fn is not None:
            return fn

        def on_trace():
            self.trace_guards["admit"].mark()
            self.admit_traces[bucket] = self.admit_traces.get(bucket, 0) + 1

        admit = make_admit_fn(self.model, self._sample, on_trace=on_trace)
        # a fresh bucket legitimately compiles one new program; a RE-trace
        # of an existing bucket stays over budget and trips the guard
        self.trace_guards["admit"].allow()
        fn = self._build_aot("admit",
                             jax.jit(admit, donate_argnums=self._donate),
                             bucket=bucket)
        self._admit_fns[bucket] = fn
        return fn

    # ------------------------------------------------------------------
    # host API
    # ------------------------------------------------------------------

    @property
    def step_traces(self) -> int:
        return self.trace_guards["step"].count

    @property
    def fused_step_traces(self) -> int:
        return self.trace_guards["fused_step"].count

    @property
    def spec_step_traces(self) -> int:
        return self.trace_guards["spec_step"].count

    def _n_traces(self) -> int:
        return sum(g.count for g in self.trace_guards.values())

    def __getattr__(self, name: str):
        """A name the engine itself lacks: one of its counts."""
        if name == "counts":        # not built yet
            raise AttributeError(name)
        return getattr(self.counts, name)

    @property
    def free_slots(self) -> list[int]:
        return [s for s in range(self.n_slots) if s not in self._slots]

    @staticmethod
    def _is_partial(seq: _Slot) -> bool:
        """A chunked admission whose prompt is not fully in the cache yet
        (by plan) — parked out of the decode batch until its last chunk
        runs."""
        return seq.suffix is not None and seq.suffix_done < len(seq.suffix)

    def _live_slots(self) -> list[int]:
        """Slots decoding in the next program to be planned: occupied,
        not mid-prefill, not retired by count."""
        return [s for s, seq in self._slots.items()
                if seq.done is None and not self._is_partial(seq)]

    def _live_mask(self):
        """The planned live set as a device mask."""
        mask = np.zeros((self.n_slots,), bool)
        mask[self._live_slots()] = True
        return jnp.asarray(mask)

    @property
    def n_live(self) -> int:
        return len(self._slots)

    @property
    def n_free(self) -> int:
        return self.n_slots - len(self._slots)

    @property
    def occupancy(self) -> float:
        """Live fraction of the slot table, 0.0..1.0."""
        return len(self._slots) / self.n_slots

    @property
    def block_utilization(self) -> float:
        """Referenced fraction of the block pool (cached-but-unreferenced
        prefix blocks are reclaimable and don't count)."""
        return self.block_pool.utilization

    @property
    def block_fragmentation(self) -> float:
        """Internal fragmentation of live blocks: the fraction of rows in
        referenced blocks not (yet) holding a valid token — the paged
        analogue of the slot cache's (S - len)/S waste, now bounded by
        one partial block per sequence."""
        live_blocks = sum(len(s.blocks) for s in self._slots.values())
        if not live_blocks:
            return 0.0
        used = sum(min(s.pos, len(s.blocks) * self.block_size)
                   for s in self._slots.values())
        return 1.0 - used / (live_blocks * self.block_size)

    @property
    def n_steps(self) -> int:
        """Step programs dispatched so far (serving tests bound slot
        release latency in steps, not wall-clock). Every one ran for a
        slot that was live by plan; rows an `eos` or a cancel made
        useless after the fact are counted in `overrun_tokens`."""
        return self._t

    @property
    def live_seq_ids(self) -> list[int]:
        return [s.seq_id for s in self._slots.values()]

    def set_budget(self, seq_id: int, max_new_tokens: int) -> None:
        """Re-budget a live sequence (bench ragged windows re-arm the warm
        slots this way instead of poking `_slots`)."""
        for slot, seq in self._slots.items():
            if seq.seq_id == seq_id:
                seq.max_new = max_new_tokens
                fl = self._inflight
                if fl is not None and fl.occupants.get(slot) == seq_id:
                    # the running program's token is already planned:
                    # whether it is the last follows the new budget
                    fl.retiring.pop(slot, None)
                    seq.done = None
                    self._plan_retirement(slot, seq, fl.retiring)
                    self._live_dirty = True
                return
        raise KeyError(f"seq {seq_id} is not live")

    def prefill_bucket(self, prompt_len: int) -> int:
        """See `prefill_bucket_for` (module level, shared with the static
        signature enumeration in parallel/commscheck.py)."""
        return prefill_bucket_for(prompt_len, self.min_bucket,
                                  self.block_size, self.max_len)

    def _note_digest(self, digest: bytes, depth: int) -> None:
        """Fold one cumulative ancestry digest into the router-facing
        index, keeping the deepest cached depth seen for it and aging
        cold chains out LRU-first."""
        idx = self._digest_index
        hexd = digest.hex()
        idx[hexd] = max(idx.get(hexd, 0), depth)
        idx.move_to_end(hexd)
        while len(idx) > self._digest_cap:
            idx.popitem(last=False)

    def _register_blocks(self, tokens: list, n_full: int,
                         blocks: list) -> None:
        """Publish the first `n_full` full blocks of `tokens` under their
        chain keys (first-writer-wins, so re-publishing a chunked prompt's
        earlier blocks is a no-op) and record the chain's cumulative
        digests for `kv_digest`. The single register path — admission,
        retirement, and per-chunk publication all land here."""
        if not self.prefix_cache or n_full <= 0:
            return
        keys = chain_keys(tokens, self.block_size, n_full)
        for key, blk in zip(keys, blocks):
            self.block_pool.register(blk, key)
        # the digest of the first d blocks is key d's parent; the full
        # chain needs one extra fold past the last key
        for depth in range(1, n_full):
            self._note_digest(keys[depth][0], depth)
        self._note_digest(_child_digest(*keys[-1]), n_full)

    def kv_digest(self, k: Optional[int] = None) -> dict:
        """Compact radix-prefix digest for the router's health probe: the
        top-k cumulative chain digests by cached depth (in blocks),
        deepest first. A replica that recently served a prefix advertises
        it here whether the blocks sit in HBM or the host tier — both
        re-admit as hits — and the router steers same-prefix requests
        back (serve/router.py sticky dispatch)."""
        if k is None:
            k = self._digest_k
        entries = sorted(self._digest_index.items(),
                         key=lambda kv: -kv[1])[:k]
        return {"block_size": self.block_size,
                "entries": [[depth, hexd] for hexd, depth in entries]}

    # -- host-tier accounting (scheduler gauges read these) -------------
    @property
    def host_tier_occupancy(self) -> float:
        return self.host_tier.occupancy if self.host_tier else 0.0

    @property
    def host_tier_hit_rate(self) -> float:
        return self.host_tier.hit_rate if self.host_tier else 0.0

    @property
    def promote_traces(self) -> int:
        return self.trace_guards["promote"].count

    def _plan_retirement(self, slot: int, seq: _Slot,
                         retiring: dict) -> None:
        """Retirement by COUNT, decided when the slot's token is planned:
        the budget or the table ends with this program, so the slot is
        out of every later program's mask (`done`) and the program that
        produces the token carries the reason to its drain (`retiring`).
        Retirement by VALUE (`eos`) is seen at the drain and wins."""
        if seq.n_new >= seq.max_new:
            seq.done = "budget"
        elif seq.pos >= self.max_len:  # table capacity: no next row exists
            seq.done = "cache_full"
        if seq.done is not None:
            retiring[slot] = seq.done
            self._live_dirty = True

    def _retire(self, slot: int, reason: str) -> Retired:
        seq = self._slots.pop(slot)
        self.counts.retired(reason)
        self._live_dirty = True
        # publish the sequence's full blocks into the prefix cache before
        # releasing: refcount-0 registered blocks land on the LRU, so a
        # follow-up (or a preemption resume) re-admits with a prefix hit
        # — and with the host tier on, a later eviction demotes instead
        # of dropping, so even a preempted-under-pressure prefix resumes
        # from cache. Only rows whose tokens have drained are published:
        # the row a program still in flight writes for this sequence
        # (`pos` runs one ahead of `tokens` then) holds a token the host
        # has not seen, and lies past the last full block counted here.
        full = min(seq.pos, len(seq.tokens) - 1,
                   len(seq.blocks) * self.block_size) // self.block_size
        self._register_blocks(seq.tokens, full, seq.blocks)
        self.block_pool.release_all(seq.blocks)
        self._tables_h[slot, :] = 0
        self._tables_dirty = True
        return Retired(tokens=seq.tokens, reason=reason,
                       prompt_len=seq.prompt_len)

    def cancel(self, seq_id: int) -> Optional[Retired]:
        """Free a live sequence's slot and blocks immediately (client
        disconnect). Returns its partial `Retired(reason='cancelled')`, or
        None when the id is not live (already retired — the token stream
        won the race)."""
        for slot, seq in self._slots.items():
            if seq.seq_id == seq_id:
                # legal while a program runs for this occupant: its token
                # is dropped at the drain (`_Program.occupants`), and its
                # row write into the released block precedes, in device
                # order, every write of the block's next owner
                return self._retire(slot, "cancelled")
        return None

    def _demote_block(self, blk: int, key: tuple) -> None:
        """Block-pool eviction hook: instead of losing the evicted
        block's KV, snapshot its rows to the host tier under the same
        chain key. Fires inside `alloc()` wherever the engine allocates
        (admission, `_ensure_blocks` growth after a preemption, chunk
        growth, spec-draft growth) — the block is refcount-0 and its
        device contents still intact when this runs."""
        self.host_tier.demote(key, kv_tier.snapshot_block(self.caches, blk))

    def _promote_blocks(self, staged: list) -> None:
        """Flush staged promotions: ONE batched host->device transfer
        for every staged block's rows (a list of block pytrees is itself
        a pytree, so this is a single `device_put`), then the one
        fixed-shape jitted copy program per block. Runs at admission
        time, before the slot's first prefill/step — the promote cost
        lands in queue-wait, and the step families never trace anything
        new for it."""
        self._pools_rewritten = True
        rows_dev = jax.device_put([rows for _, rows in staged])
        fn = self._get_promote_fn()
        with self._ctx():
            for (blk, _), rows in zip(staged, rows_dev):
                self.caches = fn(self.caches, rows, jnp.int32(blk))

    def _match_prefix(self, toks: list) -> tuple[int, list]:
        """Longest cached block-chain prefix of `toks`, capped so at least
        one suffix token remains to prefill (the prefill must produce the
        logits the first sampled token comes from). Tier-aware: an HBM
        hit shares the resident block; a host-tier hit allocates a fresh
        HBM block, re-registers the chain key, and stages the host rows
        for promotion; the first full miss ends the walk. Returns
        (prefix_len, matched block ids) WITH one reference taken per
        matched block — refs must be taken inside the walk, because a
        host-hit `alloc()` can evict from the LRU and a matched block
        must never be the one evicted. Callers own the refs
        (`release_all(matched)` on admission rollback)."""
        if not self.prefix_cache:
            return 0, []
        matched: list[int] = []
        staged: list[tuple[int, Any]] = []
        limit = (len(toks) - 1) // self.block_size
        for key in chain_keys(toks, self.block_size, limit):
            blk = self.block_pool.lookup(key)
            if blk is not None:
                self.block_pool.ref(blk)
                matched.append(blk)
                continue
            if self.host_tier is None or not self.host_tier.contains(key):
                break
            blk = self.block_pool.alloc()    # ref=1; eviction demotes
            if blk is None:
                break      # pool saturated: stop promoting, prefill rest
            staged.append((blk, self.host_tier.pop(key)))
            # re-register under the same key: the chain stays addressable
            # and deeper same-prefix admissions hit it in HBM again.
            # Registration precedes the flush, but nothing can read or
            # evict the block before `_promote_blocks` below — it is
            # referenced and no device program runs during the walk.
            self.block_pool.register(blk, key)
            matched.append(blk)
        if staged:
            self._promote_blocks(staged)
        return len(matched) * self.block_size, matched

    def admit(self, prompt, max_new_tokens: int,
              seq_id: Optional[int] = None) -> Admission:
        """Prefill `prompt` (1D int sequence) into a free slot, reusing
        any cached block-aligned prefix. Returns an `Admission` (seq id +
        first sampled token + prefix accounting + `retired` when the
        request finished at prefill). Raises AssertionError when no slot
        is free (check `free_slots`) and `NoFreeBlocks` when the pool
        cannot cover the suffix even after evicting every unreferenced
        cached block — the caller keeps the request queued and admits
        again after a retirement.

        With `prefill_chunk` set, admission is bookkeeping only: the slot
        is parked, blocks for the FIRST chunk are reserved (NoFreeBlocks
        keeps the admission-bound contract), and the prompt is chunked
        into subsequent fused steps — `first_token` is None and arrives
        via `StepResult.emitted` when the last chunk runs."""
        if not self._slots:
            self._work_waits = False    # an idle engine: nobody waited
        with phase("engine.admit",
                   chunked=int(self.prefill_chunk > 0)) as admitting:
            free = self.free_slots
            assert free, "no free slot — step()/retire before admitting"
            assert max_new_tokens >= 1
            slot = free[0]
            toks = [int(t) for t in prompt]
            # keep at least one free cache row to decode into
            toks = toks[-(self.max_len - 1):]
            L = len(toks)
            bs = self.block_size
            if self._prefix_asked and not self.prefix_cache:
                self.prefix_reuse_declined += 1   # match length 0, counted
            prefix_len, matched = self._match_prefix(toks)
            if self.prefill_chunk:
                return self._admit_chunked(slot, toks, L, prefix_len,
                                           matched, max_new_tokens, seq_id)
            suffix = toks[prefix_len:]
            bucket = min(self.prefill_bucket(len(suffix)),
                         self.max_len - prefix_len)
            admitting.set(bucket=bucket)
            # matched blocks arrive referenced from the tier-aware walk
            # (alloc below may evict from the LRU, and a matched block must
            # not be the one evicted — or demoted)
            new_ids = self.block_pool.alloc_many(bucket // bs)
            if new_ids is None:
                self.block_pool.release_all(matched)
                raise NoFreeBlocks(
                    f"pool exhausted: {self.block_pool.n_referenced} of "
                    f"{self.block_pool.capacity} blocks referenced by "
                    f"{self.n_live} live sequences; admit after a "
                    f"retirement")
            blocks = matched + new_ids
            self._tables_h[slot, :] = 0
            self._tables_h[slot, :len(blocks)] = blocks
            self._tables_dirty = True
            self._sync_tables()

            padded = jnp.asarray(suffix + [0] * (bucket - len(suffix)),
                                 jnp.int32)[None]
            if seq_id is None:
                seq_id = self._next_id
            self._next_id = max(self._next_id, seq_id) + 1
            rng = jax.random.fold_in(self._rng, 2 ** 20 + self._n_admits)
            self._n_admits += 1
            with self._ctx():
                out = self._get_admit_fn(bucket)(
                    self.variables, self.caches, self.tok, self.pos, self.live,
                    self.block_tables, padded, jnp.int32(prefix_len),
                    jnp.asarray([len(suffix)], jnp.int32),
                    jnp.int32(slot), rng)
            self.caches, self.tok, self.pos, self.live, first = out
            # THE admit sync boundary: the first sampled token must reach the
            # host to stream it to the caller (a patterned model's routing
            # counts ride the same transfer)
            first, stats = jax.device_get(  # lint: allow(host-sync)
                (first, self._take_expert_stats()))
            first_tok = int(first[0])
            self._slots[slot] = _Slot(seq_id=seq_id, tokens=toks + [first_tok],
                                      prompt_len=L, n_new=1,
                                      max_new=max_new_tokens, pos=L,
                                      blocks=blocks,
                                      order=self.counts.n_admitted)
            self.counts.admitted(L, prefix_len, wave_prefilled=len(suffix),
                                 stats=stats)
            # publish the prompt's full blocks now — immutable as of this
            # prefill — so concurrent same-prefix requests hit immediately
            self._register_blocks(toks, L // bs, blocks)
            # a 1-token request (or instant EOS) finishes at admission
            retired = None
            seq = self._slots[slot]
            self._plan_retirement(slot, seq, {})
            reason = "eos" if first_tok == self.eos_id else seq.done
            if reason is not None:
                retired = self._retire(slot, reason)
            return Admission(seq_id=seq_id, first_token=first_tok,
                             retired=retired, prefix_len=prefix_len,
                             prefilled=len(suffix))

    def _admit_chunked(self, slot: int, toks: list, L: int,
                       prefix_len: int, matched: list, max_new_tokens: int,
                       seq_id: Optional[int]) -> Admission:
        """Chunked-mode admission: no device call, no prefill trace. The
        slot is parked (live=False, write position in the always-zero
        last table column) and the suffix waits for the step loop to
        chunk it in. Only the first chunk's blocks are reserved here —
        `NoFreeBlocks` still means "stay queued" — the rest allocate
        lazily per chunk, so a long prompt never holds blocks for rows it
        hasn't written."""
        bs = self.block_size
        suffix = toks[prefix_len:]
        first_rows = prefix_len + min(self.prefill_chunk, len(suffix))
        need = -(-first_rows // bs) - len(matched)
        # matched blocks arrive referenced from the tier-aware walk
        new_ids = self.block_pool.alloc_many(max(need, 0))
        if new_ids is None:
            self.block_pool.release_all(matched)
            raise NoFreeBlocks(
                f"pool exhausted: {self.block_pool.n_referenced} of "
                f"{self.block_pool.capacity} blocks referenced by "
                f"{self.n_live} live sequences; admit after a retirement")
        blocks = matched + new_ids
        self._tables_h[slot, :] = 0
        self._tables_h[slot, :len(blocks)] = blocks
        self._tables_dirty = True
        # park the decode write: the fused step writes every slot's row,
        # and this slot's table row is real — point it at the null block
        self.pos = self.pos.at[slot].set(self._park_pos)
        if seq_id is None:
            seq_id = self._next_id
        self._next_id = max(self._next_id, seq_id) + 1
        self._slots[slot] = _Slot(
            seq_id=seq_id, tokens=list(toks), prompt_len=L, n_new=0,
            max_new=max_new_tokens, pos=prefix_len, blocks=blocks,
            order=self.counts.n_admitted, suffix=suffix, suffix_done=0,
            prefix_len=prefix_len)
        self.counts.admitted(L, prefix_len)
        return Admission(seq_id=seq_id, first_token=None,
                         prefix_len=prefix_len, prefilled=len(suffix))

    def _next_chunk(self, preempted: dict,
                    ahead: bool) -> Optional[tuple[int, int]]:
        """Pick this step's prefill work: the OLDEST partial prompt fills
        the chunk buffer, `min(prefill_chunk, what is left of it)` ids —
        the rows the fused program computes whatever they hold. Grows the
        slot's block list to cover the chunk, preempting youngest-first
        when the pool is dry (the partial itself is usually youngest —
        then the next-oldest partial gets its turn). Returns
        (slot, take) or None; preemption victims land in `preempted`.
        Planning `ahead` of a running program never preempts: it raises
        `_WouldPreempt` (the blocks grown so far stay; the drained turn
        that follows needs them too)."""
        bs = self.block_size
        while True:
            partials = [(seq.order, slot) for slot, seq in
                        self._slots.items() if self._is_partial(seq)]
            if not partials:
                return None
            slot = min(partials)[1]
            seq = self._slots[slot]
            take = min(self.prefill_chunk, len(seq.suffix) - seq.suffix_done)
            need = -(-(seq.prefix_len + seq.suffix_done + take) // bs)
            ok = True
            while len(seq.blocks) < need:
                blk = self.block_pool.alloc()
                if blk is None:
                    if ahead:
                        raise _WouldPreempt
                    victim = self._pick_victim()
                    vseq = self._slots[victim]
                    preempted[vseq.seq_id] = self._retire(victim,
                                                          "preempted")
                    if victim == slot:
                        ok = False
                        break
                    continue
                self._tables_h[slot, len(seq.blocks)] = blk
                seq.blocks.append(blk)
                self._tables_dirty = True
            if ok:
                return slot, take

    def _pick_victim(self) -> int:
        """Slot of the youngest-admitted live sequence — the vLLM-style
        recompute-preemption order: the last one in has the least sunk
        decode work and the best chance of a prefix hit on resume."""
        return max(self._slots, key=lambda s: self._slots[s].order)

    def _ensure_blocks(self, preempted: dict, ahead: bool) -> None:
        """Grow every live sequence's block list to cover its next write;
        when the pool is dry (all blocks referenced), preempt
        youngest-first until the allocation succeeds — or, planning
        `ahead` of a running program, raise `_WouldPreempt`. The victims
        land in `preempted` as {seq_id: Retired(reason='preempted')}."""
        for slot in sorted(self._slots):
            seq = self._slots.get(slot)
            # partial slots don't decode-write; their growth is per-chunk
            # (_next_chunk) so idle prefill rows never hold blocks. A slot
            # retired by count has no next write.
            if seq is not None and (seq.done is not None
                                    or self._is_partial(seq)):
                continue
            while seq is not None and \
                    seq.pos >= len(seq.blocks) * self.block_size:
                blk = self.block_pool.alloc()
                if blk is not None:
                    self._tables_h[slot, len(seq.blocks)] = blk
                    seq.blocks.append(blk)
                    self._tables_dirty = True
                    continue
                if ahead:
                    raise _WouldPreempt
                victim = self._pick_victim()
                vseq = self._slots[victim]
                preempted[vseq.seq_id] = self._retire(victim, "preempted")
                if victim == slot:
                    seq = None       # preempted itself; stop growing it

    def _spec_drafts(self) -> Optional[tuple]:
        """Host-side drafting for one speculative step: an (n_slots, K)
        draft buffer + per-slot validity lengths, or None when this step
        must run the plain program. Clamps each slot's draft so the
        emitted run (accepted + correction) can never overshoot its
        budget or the cache (`n <= max_new - n_new - 1`,
        `n <= max_len - pos - 1`), grows block lists to cover the deepest
        acceptable row — SHRINKING the draft instead of preempting when
        the pool runs dry, speculation must never evict live work — and
        falls back entirely when any live slot sits too close to the
        position-table end: `slice_rows`' (B,) dynamic_slice start clamps
        near the boundary, which would mis-rotate ALL K+1 rows of that
        slot (the committed write included). Such slots retire within K
        steps anyway, so the fallback window is brief."""
        K = self.spec_k
        draft = np.zeros((self.n_slots, K), np.int32)
        dlen = np.zeros((self.n_slots,), np.int32)
        any_draft = False
        for slot in self._live_slots():
            seq = self._slots[slot]
            if seq.pos + K + 1 > self.max_len:
                return None              # rope-table clamp hazard
            prop = ngram_propose(seq.tokens, K)
            n = min(len(prop), seq.max_new - seq.n_new - 1,
                    self.max_len - seq.pos - 1)
            while n > 0 and \
                    seq.pos + n >= len(seq.blocks) * self.block_size:
                blk = self.block_pool.alloc()
                if blk is None:
                    n = len(seq.blocks) * self.block_size - seq.pos - 1
                    break
                self._tables_h[slot, len(seq.blocks)] = blk
                seq.blocks.append(blk)
                self._tables_dirty = True
            if n <= 0:
                continue
            draft[slot, :n] = prop[:n]
            dlen[slot] = n
            any_draft = True
        if not any_draft:
            return None                  # nothing to verify: plain step
        return draft, dlen

    def _lookahead_declined(self) -> Optional[str]:
        """Why the next program cannot be planned while one runs, from
        what the engine can observe — or None. `wave`: admission runs its
        own prefill program and drains its first token, so the turn is
        synchronous anyway. `spec`: the drafter reads the tokens the
        running program is still producing. `tier`: a host-tier promotion
        rewrote the pools outside the step programs since the last
        dispatch. (A dry pool, `preempt`, shows only while planning:
        `_WouldPreempt`.)"""
        if not self.prefill_chunk:
            return "wave"
        if self.spec_decode:
            return "spec"
        if self._pools_rewritten:
            return "tier"
        return None

    def _plan(self, preempted: dict, ahead: bool) -> Optional[_Program]:
        """Plan the next step program, by count alone: block growth, the
        chunk pick, the live mask and the table it will run with, then
        the planned advance of every slot it serves (`_Slot`). Host work
        only: `_dispatch` enqueues it. `ahead` = it will queue behind a
        program that has not drained: the plan then may not preempt
        (`_WouldPreempt`). Returns None when there is nothing to run —
        every slot retires, by count, with the running program, or the
        preemptions emptied the engine."""
        self._ensure_blocks(preempted, ahead)
        chunk = self._next_chunk(preempted, ahead) \
            if self.prefill_chunk else None
        live = self._live_slots()           # decoding slots this program
        if chunk is None and not live:
            return None
        # speculative drafting happens BEFORE the table sync (it may
        # grow block lists to cover accepted rows); a chunked step
        # never speculates — the chunk already owns the step's spare
        # compute
        spec = None
        if self.spec_decode and chunk is None:
            spec = self._spec_drafts()
        reason = None if ahead else (
            self._declined or self._lookahead_declined() or "first")
        prog = _Program(
            t=self._t, occupants={}, retiring={}, preempted=preempted,
            n_live=len(live), spec=spec, overlapped=ahead,
            drain_reason=reason,
            kind=("fused" if chunk is not None
                  else "spec" if spec is not None else "decode"))
        self._t += 1
        self._pools_rewritten = False
        self.counts.planned(self._t, reason)
        # what the program runs with, fixed now: a later plan may move
        # the host's tables and mask before this one is enqueued. A mask
        # is rebuilt only where the planned live set moved outside the
        # programs (a retirement, a cancel); a chunk's last program
        # activates its slot itself, on the device and in the plan alike
        live_in = self._live_mask() if self._live_dirty else None
        self._live_dirty = False
        self._sync_tables()
        chunk_in = ()
        # the planned advance: what this program does to every slot it
        # serves is a count. A speculative program's stride is a value
        # (its accept lengths) and advances at the drain.
        for slot in live:
            seq = self._slots[slot]
            prog.occupants[slot] = seq.seq_id
            if spec is None:
                seq.n_new += 1
                seq.pos += 1
                # the rows its attention call reads, this token's included
                prog.live_tiles += -(-seq.pos // self.block_size)
                if self.counts.plans_kv_rows:
                    self._plan_kv_rows(prog, "decode", seq.pos,
                                       min(seq.pos, self.cfg.window))
                self._plan_retirement(slot, seq, prog.retiring)
        if chunk is not None:
            slot_c, take = chunk
            seq_c = self._slots[slot_c]
            off = seq_c.prefix_len + seq_c.suffix_done
            buf = seq_c.suffix[seq_c.suffix_done:seq_c.suffix_done + take]
            # the chunk's progress; its last one promotes the slot to
            # live with its first sampled token, exactly where a wave
            # admit would have left it
            seq_c.suffix_done += take
            seq_c.pos = off + take
            chunk_done = not self._is_partial(seq_c)
            prog.chunk = (slot_c, seq_c.seq_id, take, seq_c.pos)
            prog.state_reset = self.cfg.recurrent and off == 0
            if self.counts.plans_kv_rows:
                # the chunk's `take` queries see the `off` rows before
                # them; of those a window layer's see the last window - 1
                self._plan_kv_rows(prog, "chunk", off + take,
                                   min(off, self.cfg.window - 1) + take)
                prog.chunk_pairs = (_causal_pairs(off, take),
                                    _causal_pairs(off, take,
                                                  self.cfg.window))
            chunk_in = (
                jnp.asarray(buf + [0] * (self.prefill_chunk - take),
                            jnp.int32)[None],
                jnp.int32(slot_c), jnp.int32(off),
                jnp.asarray([take], jnp.int32), jnp.bool_(chunk_done))
            if chunk_done:
                seq_c.n_new = 1
                prog.occupants[slot_c] = seq_c.seq_id
                self._plan_retirement(slot_c, seq_c, prog.retiring)
        prog.inputs = (self.block_tables, live_in, chunk_in)
        return prog

    def _plan_kv_rows(self, prog: _Program, what: str, whole: int,
                      windowed: int) -> None:
        """Add one attention call's key rows to the program's count: of
        a layer that keeps the `whole` history, and of a window layer."""
        rows = prog.kv_rows = prog.kv_rows or {}
        a, b = rows.get(what, (0, 0))
        rows[what] = (a + whole, b + windowed)

    @property
    def resident_bytes_by_kind(self) -> dict:
        """Bytes the engine holds between programs, by kind of state:
        the weights, the block pools of the layers that keep a whole
        history, the window layers' rings, the other per-slot leaves."""
        def nbytes(tree):
            return sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(tree))
        by = {"weights": nbytes(self.variables), "pools": 0, "window": 0,
              "slot_state": 0}
        for keeps, leaf in zip(self.cfg.layer_keeps, self.caches):
            for what in keeps:
                # a layer that keeps two kinds keys its slot by them
                by[what] += nbytes(leaf[what] if len(keeps) > 1 else leaf)
        return by

    def _dispatch(self, prog: _Program) -> None:
        """Enqueue a planned program behind whatever the device is
        running: `tok`/`pos`/`live`/`caches` flow from program to program
        as device arrays (only the caches are donated, so an earlier
        program's `tok` stays readable for its drain)."""
        tables, live, chunk_in = prog.inputs
        if live is None:
            live = self.live
        args = (self.variables, self.caches, self.tok, self.pos, live,
                tables, self._rng, jnp.int32(prog.t), self._qparams)
        if prog.kind == "fused":
            out = self._get_fused_step_fn()(*args, *chunk_in)
            self.caches, self.tok, self.pos, self.live = out
        elif prog.kind == "spec":
            draft_h, dlen_h = prog.spec
            out = self._get_spec_step_fn()(
                *args, jnp.asarray(draft_h), jnp.asarray(dlen_h))
            self.caches, self.tok, self.pos, acc_dev = out
            prog.spec = (draft_h, dlen_h, acc_dev)
            self.live = live
        else:
            self.caches, self.tok, self.pos = self._get_step_fn()(*args)
            self.live = live
        prog.tok, prog.inputs = self.tok, None
        prog.expert_stats = self._take_expert_stats()

    def _take_expert_stats(self) -> Optional[list]:
        """The routing counts the program just enqueued will have written
        into its 'E' layers' cache slots, taken off the tree: the next
        program gets None there, and these stay readable for the drain."""
        if not self._expert_layers:
            return None
        caches = list(self.caches)
        stats = [caches[i] for i in self._expert_layers]
        for i in self._expert_layers:
            caches[i] = None
        self.caches = caches
        return stats

    def _holds(self, slot: int, seq_id: int) -> bool:
        """Whether the occupant a program was planned for still holds its
        slot."""
        seq = self._slots.get(slot)
        return seq is not None and seq.seq_id == seq_id

    def _drain(self, prog: _Program, step: int, acc: dict,
               t_step0: float) -> StepResult:
        """Fetch one program's sampled tokens and do what needs their
        VALUES: append them to their occupants' streams, test `eos`,
        publish the blocks that became full, hand out the `Retired`
        records (retirements by count carry the reason the plan gave
        them, `_Program.retiring`)."""
        # THE step sync boundary: every slot's sampled token drains to the
        # host once per program (plus the per-slot accept lengths of a
        # speculative one — one transfer, not two)
        stats = None
        with phase("engine.wait", acc, step=step, program=prog.t):
            if prog.spec is not None:
                draft_h, dlen_h, acc_dev = prog.spec
                sampled, accepted_h = jax.device_get(  # lint: allow(host-sync)
                    (prog.tok, acc_dev))
            else:
                sampled, stats = jax.device_get(  # lint: allow(host-sync)
                    (prog.tok, prog.expert_stats))
        with phase("engine.retire", acc, step=step) as retire:
            emitted: dict[int, list] = {}
            retired: dict[int, Retired] = dict(prog.preempted)
            drafted = accepted = overrun = prefill_tokens = 0
            if prog.chunk is not None:
                # publish the chunk's blocks that just became
                # full+immutable into the radix index (register is
                # first-writer-wins, so re-publishing earlier ones is a
                # no-op)
                slot_c, sid_c, prefill_tokens, rows = prog.chunk
                if self._holds(slot_c, sid_c):
                    seq_c = self._slots[slot_c]
                    full = min(rows, len(seq_c.blocks) * self.block_size) \
                        // self.block_size
                    self._register_blocks(seq_c.tokens, full, seq_c.blocks)
            for slot, sid in prog.occupants.items():
                if not self._holds(slot, sid):
                    overrun += 1       # eos one program late, or a cancel
                    continue
                seq = self._slots[slot]
                toks = [int(sampled[slot])]
                if prog.spec is not None:
                    # accepted draft prefix + the correction token, in
                    # stream order. EOS inside the accepted span ends the
                    # stream AT the EOS token: everything past it is
                    # dropped (the device pos runs ahead, but the slot
                    # retires this step and its zeroed table row makes
                    # the overshoot unreachable — the next occupant
                    # rewrites those rows before they could ever be
                    # attended)
                    acc_s = int(accepted_h[slot])
                    toks = [int(draft_h[slot, j])
                            for j in range(acc_s)] + toks
                    if self.eos_id is not None and self.eos_id in toks:
                        toks = toks[:toks.index(self.eos_id) + 1]
                    seq.n_new += len(toks)
                    seq.pos += len(toks)
                    accepted += acc_s
                    self._plan_retirement(slot, seq, prog.retiring)
                seq.tokens.extend(toks)
                emitted[sid] = toks
                reason = prog.retiring.get(slot)
                if self.eos_id is not None and toks[-1] == self.eos_id:
                    reason = "eos"
                if reason is not None:
                    retired[sid] = self._retire(slot, reason)
            if prog.spec is not None:
                drafted = int(dlen_h.sum())
            # the program's share of every count, booked once; what comes
            # back is its flight record's fields (engine/counts.py)
            record = self.counts.drained(
                prog, stats, emitted=sum(len(v) for v in emitted.values()),
                overrun=overrun, drafted=drafted, accepted=accepted,
                retired=len(retired) - len(prog.preempted))
            # one record per drained program: `step` counts completed
            # programs (this one's number + 1); the four times are this
            # CALL's phases (prepare and dispatch: of the program the call
            # queued, this one's on a drained turn), and retire_ms runs
            # to this stamp, so they sum to step_ms less the few
            # microseconds between phases
            t_rec = time.perf_counter()
            parts = {"prepare": acc.get("engine.prepare", 0.0) * 1e3,
                     "dispatch": acc.get("engine.dispatch", 0.0) * 1e3,
                     "wait": acc["engine.wait"] * 1e3,
                     "retire": (t_rec - retire.t0) * 1e3}
            # the turn (obs/flight.py): this call and the caller's gap
            # before it; a trace guard that fired inside is its `compile`
            traces, seen = self._n_traces(), self._traces_seen
            self._traces_seen = traces
            self.flight.record_turn(
                "engine", parts, t_rec, kind=prog.kind,
                compiled=traces != seen, step=prog.t + 1,
                step_ms=round((t_rec - t_step0) * 1e3, 3),
                **{f"{k}_ms": round(v, 3) for k, v in parts.items()},
                blocks_in_use=self.block_pool.n_referenced, **record)
        return StepResult(emitted=emitted, retired=retired,
                          prefill_tokens=prefill_tokens,
                          drafted=drafted, accepted=accepted)

    def step(self) -> StepResult:
        """Advance every live slot one token — or, on a speculative step
        (`spec_decode` on, drafts available), up to `spec_k`+1 tokens —
        fusing in one prefill chunk of the oldest partial prompt when
        `prefill_chunk` is set. Returns a `StepResult`:
        {seq_id: [tokens]} emitted this step in stream order (including
        the first token of a prompt whose LAST chunk ran), plus
        {seq_id: Retired} for the sequences that finished (with WHY —
        eos | budget | cache_full | preempted; preempted ones yielded
        their blocks BEFORE the step and emit no token — requeue
        them).

        One program is kept in flight: a call plans and dispatches
        program k+1 BEFORE it drains program k, so the device has its
        next program queued while the host fetches k's tokens, retires,
        and the caller emits and admits. The call still returns k's
        result, one program's per call. The lookahead declines itself
        (`_lookahead_declined`, `_WouldPreempt`) in the turns whose plan
        needs the running program's tokens; the turn is then the same
        code with nothing queued ahead — the next call dispatches AND
        drains its program, and records why (`drain_reason`)."""
        cur, self._inflight = self._inflight, None
        if cur is not None and not (
                any(self._holds(s, i) for s, i in cur.occupants.items())
                or (cur.chunk is not None
                    and self._holds(*cur.chunk[:2]))):
            # everyone it ran for was cancelled meanwhile: nothing to
            # hand out (its writes precede, in device order, whatever
            # comes next)
            self.counts.overran(len(cur.occupants))
            cur = None
        if cur is None and not self._slots:
            self._work_waits = False
            return StepResult({}, {})
        # the call's host phases (obs/trace.py PHASES): leaves in the
        # profiler's trace, joined by `step` = the number of the program
        # this call drains, and the split of step_ms in the flight
        # record, from the same stamps
        step = cur.t if cur is not None else self._t
        acc: dict = {}
        # the flight record's turn: begun at the last record where that
        # call left live slots (work waited for the caller), else here
        t_step0 = self.flight.begin_turn(self._work_waits)
        if not self._work_waits:
            self._traces_seen = self._n_traces()
        queue: list[_Program] = []
        nxt = None
        with phase("engine.prepare", acc, step=step):
            if cur is None:                 # a drained turn: plan k too
                preempted: dict[int, Retired] = {}
                cur = self._plan(preempted, ahead=False)
                if cur is None:
                    return StepResult({}, preempted)
                queue.append(cur)
            why = self._lookahead_declined()
            if why is None:
                try:
                    nxt = self._plan({}, ahead=True)
                except _WouldPreempt:
                    why = "preempt"
            if nxt is not None:
                queue.append(nxt)
        if queue:
            # stats of the program that stays queued when the call
            # returns (on a burst's first call the one it drains goes
            # out first, under the same phase)
            last = queue[-1]
            with phase("engine.dispatch", acc, step=step, program=last.t,
                       kind=last.kind, n_live=last.n_live,
                       prefill_tokens=last.chunk[2] if last.chunk else 0,
                       overlapped=int(last.overlapped),
                       drain_reason=last.drain_reason or "none"), \
                    self._ctx():
                for prog in queue:
                    self._dispatch(prog)
        res = self._drain(cur, step, acc, t_step0)
        if not self._slots:
            # end of work. By count the plan queues nothing behind a
            # program that retires the last slot; a program that ran on
            # because an `eos` showed only now served no one else
            if nxt is not None:
                self.counts.overran(len(nxt.occupants))
            nxt = why = None
        self._inflight, self._declined = nxt, why
        self._work_waits = bool(self._slots)
        return res

    def run(self, prompts, max_new_tokens,
            progress=None) -> list[list]:
        """Decode a whole batch of prompts with continuous batching: admit
        as slots (and blocks) free up, step until everything retires,
        REQUEUE preempted sequences at the head with their remaining
        budget. Returns prompt + generated tokens per input, in input
        order. `max_new_tokens` is a shared int or a per-prompt list (the
        serving parity tests replay mixed budgets offline through this
        path)."""
        budgets = (list(max_new_tokens)
                   if isinstance(max_new_tokens, (list, tuple))
                   else [max_new_tokens] * len(prompts))
        assert len(budgets) == len(prompts)
        pending = [(i, p, b) for i, p, b in
                   zip(range(len(prompts)), prompts, budgets)]
        results: dict[int, list] = {}
        generated: dict[int, int] = dict.fromkeys(range(len(prompts)), 0)
        idx_for: dict[int, int] = {}
        while pending or self._slots:
            while pending and self.free_slots:
                i, p, b = pending[0]
                try:
                    adm = self.admit(p, b)
                except NoFreeBlocks:
                    assert self._slots, \
                        "pool exhausted with no live sequence to retire"
                    break                      # step; retirements free blocks
                pending.pop(0)
                idx_for[adm.seq_id] = i
                if adm.retired is not None:  # finished at prefill
                    results[i] = adm.retired.tokens
            t0 = time.perf_counter()
            if self._slots:
                for sid, ret in self.step().retired.items():
                    i = idx_for.pop(sid)
                    generated[i] += len(ret.tokens) - ret.prompt_len
                    if ret.reason == "preempted":
                        # resume later from the retained prefix blocks:
                        # resubmit everything so far as the new prompt
                        pending.insert(0, (i, ret.tokens,
                                           budgets[i] - generated[i]))
                    else:
                        results[i] = ret.tokens
            if progress is not None:
                progress(self.n_live, time.perf_counter() - t0)
        return [results[i] for i in range(len(prompts))]
