"""Shared `jax.profiler` wrapper: one capture convention for train,
serve, and bench.

Before this module, device profiling lived in two disconnected places —
a hardcoded `jax.profiler.start_trace("profile_trace")` in the train
loop and scripts/profile_step.py's own dir handling — and the serving
stack had none at all. Now every capture lands under
`runs/<run>/profile/` (jax writes a timestamped
`plugins/profile/<ts>/*.xplane.pb` inside, so repeated captures
accumulate side by side) and every surface goes through the same three
entry points:

* `start_profile(...)` / `stop_profile()` — the train loop's bracketing
  pair (`TrainConfig.profile` + `profile_dir`);
* `profile_trace(...)` — context manager for bench legs
  (`BENCH_PROFILE=1`) and scripts;
* `capture(duration_ms, ...)` — the blocking timed capture behind the
  replica's `POST /admin/profile?duration_ms=` endpoint (run it in an
  executor thread; `jax.profiler` is process-global, so one capture at a
  time — concurrent requests get a clean `ProfilerBusy`).

A capture holds the device's ops and, on the same clock, the program's
host phases and named scopes (obs/trace.py PHASES, SCOPES). Read it with
`scripts/profile_step.py --analyze_only --trace_dir <dir>`: busy/idle, op
time, device time by scope and idle time by phase, through the benchmark's
reduction (benchmark/lib/trace_reduce.py, trace_spans.py); or open the
`.xplane.pb` with xprof / Perfetto.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

DEFAULT_ROOT = "runs"

_lock = threading.Lock()
_active_dir: Optional[str] = None


class ProfilerBusy(RuntimeError):
    """A capture is already running (jax.profiler is process-global)."""


def profile_dir(run: str = "profile", root: Optional[str] = None) -> str:
    """The capture directory for a run: `<root>/<run>/profile`, created."""
    d = os.path.join(root or DEFAULT_ROOT, run, "profile")
    os.makedirs(d, exist_ok=True)
    return d


def active() -> Optional[str]:
    """The directory of the in-flight capture, or None."""
    return _active_dir


def start_profile(out_dir: Optional[str] = None, *,
                  run: str = "profile") -> str:
    """Start a device trace into `out_dir` (default
    `runs/<run>/profile`); returns the directory. Raises `ProfilerBusy`
    when a capture is already running."""
    global _active_dir
    import jax
    d = out_dir or profile_dir(run)
    os.makedirs(d, exist_ok=True)
    with _lock:
        if _active_dir is not None:
            raise ProfilerBusy(f"profiler already tracing into "
                               f"{_active_dir}")
        jax.profiler.start_trace(d)
        _active_dir = d
    return d


def stop_profile() -> Optional[str]:
    """Stop the in-flight trace; returns its directory (None when no
    capture was running — safe to call unconditionally)."""
    global _active_dir
    import jax
    with _lock:
        if _active_dir is None:
            return None
        d = _active_dir
        try:
            jax.profiler.stop_trace()
        finally:
            _active_dir = None
    return d


@contextlib.contextmanager
def profile_trace(out_dir: Optional[str] = None, *,
                  run: str = "profile", enabled: bool = True):
    """Context-managed capture; yields the output dir (None when
    disabled, so call sites can log it unconditionally)."""
    if not enabled:
        yield None
        return
    d = start_profile(out_dir, run=run)
    try:
        yield d
    finally:
        stop_profile()


def capture(duration_ms: float, out_dir: Optional[str] = None, *,
            run: str = "serve") -> str:
    """Blocking timed capture (the `POST /admin/profile` body): trace for
    `duration_ms`, then stop. Run it in a worker thread from async code —
    the device keeps stepping, this thread just sleeps out the window."""
    d = start_profile(out_dir, run=run)
    try:
        time.sleep(max(0.0, duration_ms) / 1e3)
    finally:
        stop_profile()
    return d
