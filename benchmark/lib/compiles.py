"""Every XLA compile (or load from the persistent cache) of the process,
stamped on the benchmark's clock through `jax.monitoring`. The program's
TraceGuards count retraces; a program that is lowered again for the same
trace (committed against uncommitted arguments, PERF.md section 7) passes
them unseen and shows here. Nothing may compile inside the window."""

from __future__ import annotations

from benchmark.lib import stats

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        import jax
        self.events: list = []          # (t_end, fun_name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.events.append((stats.now(), kw.get("fun_name"), duration))

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]

    def total_seconds(self, until: float) -> float:
        return sum(e[2] for e in self.events if e[0] <= until)
