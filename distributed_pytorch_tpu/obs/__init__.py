"""Observability: what the program was doing, on a clock that can be laid
beside the device's.

**One clock for program and device: the profiler's.** A `jax.profiler`
capture (`obs.profile`: the trainer's `--profile`, a replica's
`POST /admin/profile`, the benchmark's traced slice) stamps the device's
ops and the host's threads on one clock. The program writes itself into
that trace in two ways, both tabled in `obs.trace`:

* **host phases** (`obs.trace.phase`, names in `PHASES`): leaf spans per
  engine step (`engine.prepare` / `engine.dispatch` / `engine.wait` /
  `engine.retire`, `engine.admit`), per scheduler pass (`sched.admit` /
  `sched.emit` / `sched.idle`) and per train iteration (`train.dispatch` /
  `train.data` / `train.sync` / `train.drain` / `train.eval` /
  `train.ckpt`), joined by a `step` stat. A phase is a TraceMe: one atomic
  load when no capture runs. An idle gap of the device is named after the
  phase that covers it. `HOST_EVENTS` (`host.gc`) are spans that are no
  phase of a loop and fall inside any.
* **named scopes** (`jax.named_scope`, names in `SCOPES`) in the compiled
  steps where no flax module name reaches: `kv_update`, `attn_core`,
  `lm_head`, `loss`, `optimizer`, `grad_norm`, `sample`, `chunk_prefill`,
  `decode`; a patterned model's mixers have theirs in `MIXER_SCOPES`
  (`ssm_*`, `conv_chunk`, `conv_step`, `qk_norm`, `rope`, `moe_route`,
  `moe_experts` with `moe_pack` and `moe_combine` inside, `moe_shared`).
  They land in every device op's name path, so device time has an owner in
  the program's own words.

`scripts/profile_step.py --analyze_only --trace_dir <dir>` and the
benchmark's per-layer metrics read both back with one reduction
(benchmark/lib/trace_reduce.py, trace_spans.py).

**The always-on recorders keep their own clocks, and take their numbers
from the same stamps.** They answer questions a capture is too short for:

* `obs.flight` — one compact record per engine step in a bounded ring
  (`step_ms` and its split `prepare_ms` / `dispatch_ms` / `wait_ms` /
  `retire_ms` from the phases' `perf_counter` stamps, `n_live`,
  `prefill_tokens`, ...), on a wall-anchored monotonic clock, served at
  `GET /debug/timeline`. `train/telemetry.py` wraps the same ring for the
  trainer (`step_ms`, `data_ms`, `dispatch_ms`, `sync_ms`, `ckpt_ms`),
  dumped to `runs/<run>/train_timeline.jsonl`. A record is also a TURN:
  what it covers plus what preceded it since the last one (`t0`, `gap_ms`
  = the caller's time between two `step()` calls while work waited), with
  what else ran in it: `gc_ms` / `gc_gen` (collector pauses, from one
  process-wide `gc.callbacks` hook that also writes each pause into the
  profiler's trace as `host.gc`, `obs.trace.HOST_EVENTS`), `cpu_ms` (the
  writer thread's own CPU time), where it stood while off the CPU
  (`sched_delay_ms`, `steal_ms`, `nivcsw`), `capturing`. A turn over 3x
  the running median of the last 256 of its KIND (the drained program's
  `decode` | `fused` | `spec`), or one that compiled, is STALLED:
  booked with its `kind`, its `owner` (`gap` or a phase), its `cause`
  (`compile` | `capture` | `gc` | `descheduled` | `caller` | `host_busy` |
  `blocked` | `mixed`) and its excess into a process-wide log of 256 that
  ordinary records never evict (`obs.flight.stall_log()`,
  `stall_totals()`). It is read at `GET /debug/timeline` (`stalls`,
  `stall_totals`), at `/metrics` (`serve_engine_stalls_total{cause}`,
  `..._stall_seconds_total{cause}`, `serve_host_gc_pause_seconds_total
  {generation}`, `serve_host_sched_delay_seconds_total{reason}`; `train_*`
  twins) and by the benchmark's `stall_share_pct.*`, `stall_max_ms.*`,
  `host_gc_ms_per_s.*` and, laid on a capture by the program number,
  `idle_stalled_pct.serve`.
* `obs.trace.TraceRecorder` — per-REQUEST spans on `perf_counter` (router
  dispatch, queue wait, prefill, decode, failover, retire) under an
  `X-Trace-Id`, recorded at terminal events only, exportable as
  Chrome-trace/Perfetto JSON or JSONL.
* `obs.retrace`, `obs.paths` — trace-count guards and which path a
  compiled program took.
* `obs.slo` — SLO targets with multi-window burn rates from the router's
  federated metrics. `obs.replay` is the offline read side of the
  recorders' dumps (timeline fits, cost tables for sim/fleetsim.py).
"""

from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.obs.retrace import (RetraceError, TraceGuard,
                                                 guarded)
from distributed_pytorch_tpu.obs.slo import SLOTarget, SLOTracker
from distributed_pytorch_tpu.obs.trace import (TraceRecorder, get_recorder,
                                               new_trace_id, set_recorder)

__all__ = ["FlightRecorder", "RetraceError", "SLOTarget", "SLOTracker",
           "TraceGuard", "TraceRecorder", "get_recorder", "guarded",
           "new_trace_id", "set_recorder"]
