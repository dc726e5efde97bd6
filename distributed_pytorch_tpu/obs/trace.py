"""Structured span/event recorder for end-to-end request tracing.

Design constraints (ISSUE 9 acceptance bar: with tracing disabled the
serve hot path must be indistinguishable from the recorder compiled out):

* **Near-zero overhead when disabled**: every public entry point checks
  one attribute and returns; `span()` hands back a shared no-op context
  manager, so a disabled recorder costs one attribute load + one branch
  per call site. Nothing is allocated, nothing is locked.
* **Hot-path discipline when enabled**: the serving layers record spans
  at TERMINAL events (retire/shed/failover), computed from timestamps
  they already collect for the latency histograms — per-token work gains
  no recorder calls either way.
* **Thread-safe bounded ring**: spans land in a `deque(maxlen=capacity)`
  under a lock (the scheduler's event loop and the engine's executor
  thread both record); old spans fall off the back, `dropped` counts
  them. Monotonic clocks (`time.perf_counter`) order everything recorded
  in one process; cross-process stitching re-bases on the dispatcher's
  clock (serve/router.py).
* **Two export formats**: Chrome-trace JSON (`to_chrome()` — load in
  Perfetto / chrome://tracing) and JSONL (`dump_jsonl()` — grep/pandas).

A span is a plain dict:
    {"trace": id, "span": n, "parent": n|None, "name": str, "cat": str,
     "t0": perf_counter_seconds, "dur": seconds, "attrs": {...}}

The recorder above follows REQUESTS, on `perf_counter`. What the PROGRAM
was doing — which part of an engine step, scheduler pass or train
iteration the host was in, which region of a compiled step a device op
belongs to — goes into the profiler's own trace, on the clock the profiler
stamps the device with: `phase` (host, `PHASES`) and the `jax.named_scope`
names of `SCOPES` (device), both at the end of this file. The benchmark's
readers (benchmark/lib/trace_spans.py) and `scripts/profile_step.py
--analyze_only` read them back by these names.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import uuid
from typing import Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

TRACE_HEADER = "X-Trace-Id"


def new_trace_id() -> str:
    """16-hex-char request trace id (uuid4-derived, collision-safe at
    serving volumes, short enough for log lines and headers)."""
    return uuid.uuid4().hex[:16]


class _NullSpan:
    """The disabled-mode span: a shared, stateless context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """A live (entered, not yet recorded) span."""

    __slots__ = ("_rec", "name", "trace", "parent", "cat", "attrs", "t0")

    def __init__(self, rec: "TraceRecorder", name: str, trace: str,
                 parent: Optional[int], cat: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.trace = trace
        self.parent = parent
        self.cat = cat
        self.attrs = attrs
        self.t0 = time.perf_counter()

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> bool:
        self._rec.add(self.name, self.trace,
                      t0=self.t0, dur=time.perf_counter() - self.t0,
                      parent=self.parent, cat=self.cat, **self.attrs)
        return False


class TraceRecorder:
    """Thread-safe bounded span ring with Perfetto/JSONL export.

    >>> rec = TraceRecorder()
    >>> tid = new_trace_id()
    >>> with rec.span("prefill", tid, cat="sched", bucket=64):
    ...     run_prefill()
    >>> rec.spans_for(tid)
    [{'name': 'prefill', ...}]
    """

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self.dropped = 0          # spans evicted off the ring's back
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._next = 1

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------

    def add(self, name: str, trace: Optional[str], *, t0: float,
            dur: float, parent: Optional[int] = None, cat: str = "",
            **attrs) -> Optional[int]:
        """Record one finished span. No-op (None) when disabled or when
        the event has no trace id to hang from."""
        if not self.enabled or trace is None:
            return None
        with self._lock:
            sid = self._next
            self._next += 1
            if len(self._spans) == self.capacity:
                self.dropped += 1
            self._spans.append({"trace": trace, "span": sid,
                                "parent": parent, "name": name, "cat": cat,
                                "t0": t0, "dur": dur, "attrs": attrs})
            return sid

    def event(self, name: str, trace: Optional[str], *, cat: str = "",
              t: Optional[float] = None, parent: Optional[int] = None,
              **attrs) -> Optional[int]:
        """Record an instant (zero-duration) event on a trace."""
        if not self.enabled or trace is None:
            return None
        return self.add(name, trace, t0=time.perf_counter() if t is None
                        else t, dur=0.0, parent=parent, cat=cat, **attrs)

    def span(self, name: str, trace: Optional[str], *,
             parent: Optional[int] = None, cat: str = "", **attrs):
        """Context manager measuring a code region. Disabled (or
        trace-less) recorders hand back a shared no-op."""
        if not self.enabled or trace is None:
            return _NULL_SPAN
        return _Span(self, name, trace, parent, cat, attrs)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    # ------------------------------------------------------------------
    # reading
    # ------------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._spans)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._spans)

    def spans_for(self, trace: str) -> list[dict]:
        """All recorded spans of one trace, in t0 order."""
        return sorted((s for s in self.snapshot() if s["trace"] == trace),
                      key=lambda s: s["t0"])

    def summary(self, trace: str,
                base: Optional[float] = None) -> list[dict]:
        """Compact per-request span list for completion payloads and
        cross-process stitching: offsets in ms relative to `base` (the
        trace's earliest span when omitted), so the receiving process can
        re-base them onto its own clock."""
        spans = self.spans_for(trace)
        if not spans:
            return []
        if base is None:
            base = spans[0]["t0"]
        return [{"name": s["name"], "cat": s["cat"],
                 "off_ms": round((s["t0"] - base) * 1e3, 3),
                 "dur_ms": round(s["dur"] * 1e3, 3),
                 "attrs": s["attrs"]} for s in spans]

    def ingest(self, trace: str, summary: list[dict], *, base: float,
               **extra_attrs) -> None:
        """Record a peer process's `summary()` spans onto this recorder,
        re-based at `base` on THIS process's monotonic clock (the router
        uses its dispatch timestamp) — a failed-over stream stitches into
        one timeline this way."""
        if not self.enabled:
            return
        for s in summary:
            try:
                self.add(s.get("name", "?"), trace,
                         t0=base + float(s.get("off_ms", 0.0)) / 1e3,
                         dur=float(s.get("dur_ms", 0.0)) / 1e3,
                         cat=s.get("cat", ""),
                         **{**s.get("attrs", {}), **extra_attrs})
            except (TypeError, ValueError):
                continue          # a malformed peer span never poisons us

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------

    def to_chrome(self, trace: Optional[str] = None) -> dict:
        """Chrome trace event format (the JSON Perfetto and
        chrome://tracing open directly): one complete ('X') event per
        span, timestamps in microseconds, grouped on one pid with a
        thread track per category so router/sched/engine lanes stack."""
        spans = self.spans_for(trace) if trace else \
            sorted(self.snapshot(), key=lambda s: s["t0"])
        tids: dict[str, int] = {}
        events = []
        for s in spans:
            lane = s["cat"] or "main"
            tid = tids.setdefault(lane, len(tids))
            events.append({"name": s["name"], "ph": "X", "cat": lane,
                           "pid": 0, "tid": tid,
                           "ts": round(s["t0"] * 1e6, 3),
                           "dur": round(s["dur"] * 1e6, 3),
                           "args": {"trace": s["trace"], **s["attrs"]}})
        meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                 "args": {"name": lane}} for lane, tid in tids.items()]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def dump_jsonl(self, path: str, trace: Optional[str] = None) -> str:
        """One span per line (ring order); returns the path written."""
        spans = self.spans_for(trace) if trace else self.snapshot()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(s) + "\n")
        return path


# ----------------------------------------------------------------------
# process-wide default recorder (the serving layers share one ring so a
# request's router/scheduler/server spans land in the same place)
# ----------------------------------------------------------------------

from distributed_pytorch_tpu import config as _config

_default = TraceRecorder(
    capacity=_config.knob("TRACE_CAPACITY"),
    enabled=_config.knob("TRACE"))


def get_recorder() -> TraceRecorder:
    return _default


def set_recorder(rec: TraceRecorder) -> TraceRecorder:
    """Swap the process default (tests install a fresh ring)."""
    global _default
    _default = rec
    return rec


# ----------------------------------------------------------------------
# the program in the profiler's trace: host phases and device scopes
# ----------------------------------------------------------------------

#: Host phases. LEAVES: contiguous on their thread, never nested in one
#: another, no enclosing "whole step" span (an idle gap of the device is
#: named after the host event that covers most of it, and an enclosing
#: span would take every gap and say nothing). A step is recovered from
#: the phases' shared `step` stat. Per engine step, scheduler pass or
#: train iteration; never per token or per request.
PHASES = {
    # engine executor thread, DecodeEngine.step, in this order. One call
    # = one `step` stat = the number of the program the call DRAINS
    # (wait, retire). With a program in flight, prepare and dispatch are
    # the NEXT program's (stat `program` on dispatch and wait says whose);
    # on a drained turn all four are one program's. The first call of a
    # burst plans and enqueues two (its own and the one behind it) under
    # its one prepare and one dispatch; the last enqueues none
    "engine.prepare": "block growth/preemption, chunk pick, drafts, "
                      "live mask, table sync, chunk padding",
    "engine.dispatch": "the jitted step call (enqueue; step marker); "
                       "stats program, overlapped, drain_reason",
    "engine.wait": "device_get of a program's sampled tokens (stat "
                   "program)",
    "engine.retire": "host bookkeeping that needs the tokens' values, up "
                     "to and including flight.record; folds the routing "
                     "counts fetched with them into the engine's counters "
                     "(expert_calls a program, merged_programs: the "
                     "chunk-carrying ones with one expert call a layer)",
    # engine executor thread, DecodeEngine.admit
    "engine.admit": "one admission: prefix match, blocks, wave prefill "
                    "(stat bucket) or chunked bookkeeping (stat chunked)",
    # scheduler event loop, Scheduler._run only
    "sched.admit": "cancellations, shedding, class preemption, the "
                   "admission wave with its executor hop, tier/AOT sync",
    "sched.emit": "a step's result fanned out to the handles, retire "
                  "and requeue handling",
    "sched.idle": "parked on the wake event: no live and no queued work",
    # trainer main thread, train()
    "train.dispatch": "the jitted train_step call (enqueue; step marker)",
    "train.data": "next_batch: the host's fetch of the next batch",
    "train.sync": "device_get of the window's queued step metrics",
    "train.drain": "anomaly monitor, telemetry records, the log line",
    "train.eval": "estimate_loss at an eval boundary",
    "train.ckpt": "the interval checkpoint's synchronous part",
}

#: Host events that are no phase of a loop: they fall inside any phase, on
#: any thread. Outside `PHASES` on purpose: the benchmark's readers of the
#: phases (`benchmark/lib/trace_spans.PHASE_LAYERS`) read what they read
#: without them, while whatever names an idle gap after every host event
#: (`trace_reduce.attribute_gaps`, `scripts/profile_step.py`) can name one
#: after these.
HOST_GC = "host.gc"
HOST_EVENTS = {
    HOST_GC: "one pause of the cyclic collector (stat generation), opened "
             "and closed on the thread that triggered it by the process's "
             "gc.callbacks hook (obs/flight.py), which also books its "
             "seconds into the flight records' gc_ms",
}

#: The phases that launch a step's device work: opened as a
#: StepTraceAnnotation (`step_num` = the phase's `step`), so XProf groups
#: the device work they enqueue under that step.
STEP_PHASES = frozenset({"engine.dispatch", "train.dispatch"})

#: `jax.named_scope` names inside the compiled steps, where no flax
#: module name reaches (modules name themselves: `block_<i>/attn`,
#: `block_<i>/mlp`, `ln_f`, `tkn_emb`). They land in every op's `op_name`;
#: the backward pass carries them as `transpose(jvp(<scope>))`. None may
#: contain a kernel's name (`paged_flash_decode`, `flash_*`): the
#: benchmark finds kernels by pattern on the op's name.
SCOPES = {
    "kv_update": "every KV-cache write (models/attention.py)",
    "attn_core": "the attention core: sdpa, or gather + absorbed decode "
                 "for MLA (models/attention.py)",
    "lm_head": "the inference logits matmul (models/gpt.py)",
    "loss": "the cross-entropy branch, head matmul included, every "
            "loss_impl (ops/losses.py tied_head_loss)",
    "optimizer": "tx.update + apply_updates (train/step.py)",
    "grad_norm": "optax.global_norm of the gradients (train/step.py)",
    "sample": "each sample_fn call of the engine's programs",
    "chunk_prefill": "the chunk's model.apply in fused_step; in a "
                     "patterned model's one walk over both row sets, what "
                     "the chunk's rows run alone (inside `decode`)",
    "decode": "the decode model.apply in step, fused_step, spec_step; a "
              "patterned model's fused_step whole, its expert layers' one "
              "call over the chunk's rows and the decode rows included",
}


#: The flax module names a patterned model's blocks bring beside the
#: classic ones (`attn`, `mlp`: an 'F' block's dense FFN is module `mlp`,
#: a '*' block's GQA module `attn`), each block's RMSNorm included.
MIXER_MODULES = {
    "ssm": "a Mamba-2 mixer's projections and gated norm (models/ssm.py)",
    "conv": "a gated short-convolution mixer's two projections and its "
            "two gates, B * x' and C * c (models/shortconv.py)",
    "moe": "an expert layer outside its scopes (models/mlp.py)",
    "norm": "the RMSNorm in front of every mixer (models/gpt.py)",
    "mixer_sum": "a 'P' block's sum of its two branches, `attn` and `ssm` "
                 "under the names and scopes they have alone, each times "
                 "its output multiplier, in float32 (models/gpt.py "
                 "MixerSum): the one place that tells where the branches "
                 "of one block meet",
    "latent_attn": "an 'L' block's latent attention outside its scopes "
                   "(models/attention.py LatentAttention)",
    "kda": "a 'K' block's linear attention outside its scopes "
           "(models/linear_attention.py KDA)",
    "gdn": "a 'G' block's gated delta rule outside its scopes "
           "(models/linear_attention.py GatedDeltaNet)",
}

#: The scopes of a patterned model's mixers (`LLMConfig.layer_pattern`),
#: under the flax module names of `MIXER_MODULES`. A table of their own for
#: one reason: the benchmark's `lib/trace_spans.SCOPE_NAMES` is held to
#: `SCOPES` by a test of the benchmark, and the PR that brought these
#: (33, a `model_config`) may add benchmark files and edit none. They are
#: read through `benchmark/readers/trace_scope_named_ms.py`, which takes
#: the names as an argument; a `benchmark` PR appends them to SCOPE_NAMES
#: and merges the two tables.
MIXER_SCOPES = {
    "ssm_conv": "the depthwise causal convolution and its silu, chunk and "
                "one-token forms (models/ssm.py)",
    "ssm_scan": "the chunked state-space scan of a prefill chunk "
                "(ops/ssm_scan.py ssd_chunked)",
    "ssm_step": "the one-token recurrence of a decode step "
                "(ops/ssm_scan.py ssm_step)",
    # a 'C' mixer's convolution, its two forms apart (PR 45): other ops
    # than `ssm_conv`'s, no bias and no silu
    "conv_chunk": "the depthwise causal convolution over a chunk's rows "
                  "from the slot's tail, zeros at a first chunk "
                  "(models/shortconv.py, ops/ssm_scan.py causal_conv)",
    "conv_step": "one token of it for every slot, and the tail's shift "
                 "(ops/ssm_scan.py conv_step)",
    # inside module `attn`, in front of `kv_update` and `attn_core` (PR 45)
    "qk_norm": "the RMSNorm over the lanes of every q head and every k "
               "head (models/attention.py GQA, `cfg.qk_norm`)",
    "rope": "rotary positions: the angles (a patterned model's from the "
            "rows' own positions at `cfg.rope_theta`, a classic one's "
            "rows of its table) and the rotation of q and k in the "
            "pairing `cfg.rope_pairing` names (ops/rope.py)",
    # a window layer's ('W', PR 49), so that a device trace tells it from
    # a layer that keeps the whole history (`attn_core`, `kv_update`)
    "attn_window": "a window layer's attention core over the slot's ring: "
                   "window_flash_decode, or window_flash_prefill over the "
                   "ring in position order and the chunk's own rows "
                   "(ops/window_attention.py)",
    "kv_update_window": "a window layer's ring write: one row a live slot, "
                        "or the last `window` real rows a chunk leaves "
                        "(ops/window_attention.py ring_write_token, "
                        "ring_logical, ring_after)",
    "attn_gate": "the output gate: a head's (its projection `c_gate`, "
                 "the sigmoid, the product with the heads' outputs) or a "
                 "channel's (the sigmoid of the query projection's further "
                 "columns and the product; the columns themselves are "
                 "`c_attn`'s) (models/attention.py GQA, `cfg.attn_gate`)",
    "moe_route": "the router: sigmoid scores, bias-corrected top-k, "
                 "renormalised weights (models/mlp.py route_sigmoid), or "
                 "the top-k logits and their softmax (route_softmax_topk)",
    "moe_experts": "the held routed experts: packing, the two kernels "
                   "(expert_matmul_up or expert_matmul_gated_up, "
                   "expert_matmul_down), the combine "
                   "(ops/grouped_matmul.py held_experts_ffn)",
    "moe_shared": "the shared expert's two matmuls and, with "
                  "`cfg.shared_gate`, its scalar gate a token: w_sg, the "
                  "sigmoid, the product (models/mlp.py)",
    # inside `moe_experts`, around what is not a kernel (PR 38)
    "moe_pack": "where each assignment to a held expert goes: counts, "
                "ranks and slots from the one-hot of the assignments, the "
                "tile table, ONE scatter for the packing's inverse "
                "(ops/grouped_matmul.py held_packing), and the gather of "
                "the rows into the packed (P, C) buffer (held_experts_ffn)",
    "moe_combine": "the token-side sum: one gather of every token's k "
                   "rows of the packed float32 result, by the packing's "
                   "inverse, added in the router's order by written-out "
                   "float32 adds, so a row is bitwise blind to the rows "
                   "beside it by construction (held_experts_ffn); and the "
                   "add of the shared expert's output, one a row set "
                   "(models/mlp.py)",
    # a latent layer's ('L', PR 59), module `latent_attn`; its cache write
    # is `kv_update` as every other layer's
    "latent_q": "the query path: W_qa, the RMSNorm of the query latent, "
                "W_qb, the rotation of every head's rotary lanes, and for "
                "one token of every slot the absorption q_nope W_kvb^K^T "
                "and the lay-out over a cached row's lanes "
                "(models/attention.py LatentAttention)",
    "latent_kv": "W_kva, the RMSNorm of the key/value latent, the rotation "
                 "of the one shared key head, the cached row `[c | k_r | 0]`",
    "attn_latent": "the attention core over the pool of latent rows: "
                   "latent_flash_decode (absorbed, every live row read "
                   "once) or latent_flash_prefill (a chunk, its key tiles "
                   "up-projected in the kernel), or their XLA twins "
                   "(ops/latent_attention.py)",
    "latent_out": "W_kvb^V on `sum p c` where the attention ran absorbed, "
                  "and W_o",
    # a linear-attention layer's ('K', PR 62), module `kda`
    "kda_proj": "the projections of the normed input: W_qkv, W_a (the "
                "decay's) and W_bg (beta and the output gate, a scalar a "
                "head each) (models/linear_attention.py)",
    "kda_conv": "the depthwise causal convolution over [q' | k' | v'] and "
                "its silu, chunk and one-token forms, the tail's shift "
                "(ops/ssm_scan.py causal_conv, conv_step)",
    "kda_gate": "the L2 norms of q and k, the bounded log decay a channel, "
                "beta and the output gate's sigmoid",
    "attn_kda": "the delta rule on the slot's state: kda_state_step (one "
                "token of every live slot, the state in place) or the "
                "chunked WY form of a prefill chunk, or their XLA twins "
                "(ops/delta_rule.py)",
    "kda_chunk": "inside `attn_kda`: the chunked WY form of a cached "
                 "prefill chunk alone, fused XLA with no kernel name of "
                 "its own (ops/delta_rule.py kda_chunk)",
    "kda_out": "the heads' RMSNorm, the head-wise gate and W_o",
    # a gated-delta-rule layer's ('G', PR 67), module `gdn`
    "gdn_proj": "the projections of the normed input: W_qkvz (q', k', v' "
                "and the output gate's z in one matrix) and W_ba (beta and "
                "the decay's input, a scalar a value head each) "
                "(models/linear_attention.py GatedDeltaNet)",
    "gdn_conv": "the depthwise causal convolution over [q' | k' | v'] and "
                "its silu, chunk and one-token forms, the tail's shift",
    "gdn_gate": "the L2 norms of q and k, the key heads repeated under "
                "their value heads, the unbounded log decay a head "
                "(-exp(A_log) softplus(.)) and beta",
    "attn_gdn": "the delta rule on the slot's state: kda_state_step with "
                "the head's decay broadcast over its channels (one token "
                "of every live slot, the state in place) or the chunked "
                "form of a prefill chunk, or their XLA twins "
                "(ops/delta_rule.py)",
    "gdn_chunk": "inside `attn_gdn`: the chunked WY form of a cached "
                 "prefill chunk alone, a decay a head, fused XLA with no "
                 "kernel name of its own (ops/delta_rule.py gdn_chunk)",
    "gdn_out": "the heads' RMSNorm, the silu gate a channel and W_o",
    # inside `moe_route`, where `cfg.n_group` > 1 alone (PR 62)
    "route_groups": "the group limit of the sigmoid router: a group's "
                    "score (its two largest s + b), the best groups kept, "
                    "the rest masked (models/mlp.py limit_to_groups)",
}


class phase:
    """One host phase: a TraceMe on the profiler's clock (one atomic load
    when no profile runs; its stats are encoded only while one does) whose
    `perf_counter` duration is also added to `acc[name]` (seconds), the
    per-step accumulator the caller hands to its flight record.

    >>> acc = {}
    >>> with phase("engine.wait", acc, step=7):
    ...     sampled = jax.device_get(tok)
    >>> acc["engine.wait"]
    """

    __slots__ = ("name", "acc", "t0", "_ann")

    def __init__(self, name: str, acc: Optional[dict] = None, **stats):
        self.name = name
        self.acc = acc
        if name in STEP_PHASES:
            self._ann = StepTraceAnnotation(name, step_num=stats["step"],
                                            **stats)
        else:
            self._ann = TraceAnnotation(name, **stats)

    def set(self, **stats) -> None:
        """Stats known only inside the phase (an admission's bucket)."""
        self._ann.set_metadata(**stats)

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        if self.acc is not None:
            self.acc[self.name] = self.acc.get(self.name, 0.0) + dt
        return False
