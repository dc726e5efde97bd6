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
  phase that covers it.
* **named scopes** (`jax.named_scope`, names in `SCOPES`) in the compiled
  steps where no flax module name reaches: `kv_update`, `attn_core`,
  `lm_head`, `loss`, `optimizer`, `grad_norm`, `sample`, `chunk_prefill`,
  `decode`. They land in every device op's name path, so device time has
  an owner in the program's own words.

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
  dumped to `runs/<run>/train_timeline.jsonl`.
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
