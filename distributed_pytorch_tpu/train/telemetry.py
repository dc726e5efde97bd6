"""Training-side observability: the train flight recorder, a live
Prometheus/timeline HTTP endpoint, and the loss/grad anomaly monitor.

Round 14 instrumented the *serving* stack (obs/ trace ring, engine
flight recorder, on-demand profiling); the training loop still logged
loss/dt/tok-s/MFU to stdout and one terminal stats.json. This module
closes the training half (ISSUE 10), reusing the round-14 primitives:

* `TrainTelemetry` — per-logged-step records `{it, loss, grad_norm,
  step_ms, data_ms, dispatch_ms, sync_ms, ckpt_ms, tokens_per_s, mfu}`
  (data/dispatch/sync are the loop's host phases, obs/trace.py) land in an
  `obs.flight.FlightRecorder` ring, dumped to
  `runs/<run>/train_timeline.jsonl` at checkpoint boundaries and exit.
  Everything is fed at the loop's existing SYNC BOUNDARIES (the
  log/eval/ckpt drain that already blocks on the queued metric
  futures), so the per-step hot path stays device-async; with
  `telemetry=False` every call site is one attribute check, no
  allocation — the same disabled-mode bound obs/trace.py holds itself
  to.
* `TrainMetrics` — step-phase histograms + counters + live gauges on
  the obs/prom.py machinery (the serving metrics' Histogram and
  info-gauge idiom), rendered as Prometheus text. Unlike ServeMetrics it takes a
  lock: the train loop writes from the main thread while the telemetry
  HTTP thread renders.
* `TelemetryServer` — an opt-in stdlib HTTP thread (`--metrics_port`)
  serving `/metrics`, `/debug/timeline`, and `/healthz` on the main
  host, so a multi-hour TPU run is inspectable without killing it.
* `AnomalyMonitor` — NaN/inf detection and a rolling grad-norm spike
  monitor, drained from the same host-side boundary the loop already
  fetches loss/grad_norm floats at. The device-side half (skipping the
  poisoned optimizer update under `anomaly='skip'`) lives in
  train/step.py; this side records the event — with the offending
  batch's data-shard coordinates, which are fully determined by
  (dataset, seed, step) since the loader is step-keyed — so the batch
  is reproducible post-hoc.
"""

from __future__ import annotations

import json
import math
import statistics
import threading
import urllib.parse
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from distributed_pytorch_tpu.obs import flight as obs_flight
from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.obs.prom import (Histogram, _render_info,
                                              render_families)

# Train steps span ~1 ms (tiny CPU smoke) to tens of seconds (1.5B with
# remat); the serve grid covers the same decades.
STEP_SECONDS_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class TrainMetrics:
    """Prometheus registry for the training loop (obs/prom.py
    Histogram + info-gauge machinery, plus a lock — the loop observes
    from the main thread while the TelemetryServer thread renders)."""

    COUNTERS = ("steps", "checkpoints", "anomalies", "updates_skipped",
                "evals", "resumes", "ckpt_fallbacks")

    def __init__(self):
        self._lock = threading.Lock()
        self.step_s = Histogram(
            "train_step_seconds",
            "optimizer step wall-clock (boundary-window average)",
            buckets=STEP_SECONDS_BUCKETS)
        self.data_s = Histogram(
            "train_data_seconds",
            "host time fetching/sharding the next batch, per step",
            buckets=STEP_SECONDS_BUCKETS)
        self.sync_s = Histogram(
            "train_sync_seconds",
            "host blocked draining queued step metrics at one boundary",
            buckets=STEP_SECONDS_BUCKETS)
        self.ckpt_s = Histogram(
            "train_ckpt_snapshot_seconds",
            "synchronous pre-save snapshot copy per checkpoint",
            buckets=STEP_SECONDS_BUCKETS)
        self.counters = dict.fromkeys(self.COUNTERS, 0)
        self.anomaly_counts: dict[str, int] = {}       # kind -> n
        self.build_info: dict[str, str] = {}
        self._gauges: dict[str, tuple[Callable[[], float], str]] = {}
        # the trainer's stalled turns and the collector's pauses
        # (obs/flight.py), the twins of serve_engine_stalls_total & co.
        self._families = obs_flight.metric_families("train", "train",
                                                    "train_host")

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def anomaly(self, kind: str) -> None:
        with self._lock:
            self.counters["anomalies"] += 1
            self.anomaly_counts[kind] = self.anomaly_counts.get(kind, 0) + 1

    def observe_phases(self, *, step_s: Optional[float] = None,
                       data_s: Optional[float] = None,
                       sync_s: Optional[float] = None,
                       ckpt_s: Optional[float] = None) -> None:
        with self._lock:
            if step_s is not None:
                self.step_s.observe(step_s)
            if data_s is not None:
                self.data_s.observe(data_s)
            if sync_s is not None:
                self.sync_s.observe(sync_s)
            if ckpt_s is not None:
                self.ckpt_s.observe(ckpt_s)

    def register_gauge(self, name: str, fn: Callable[[], float],
                       help_: str = "") -> None:
        self._gauges[name] = (fn, help_)

    def set_build_info(self, **info) -> None:
        self.build_info.update({k: str(v) for k, v in info.items()})

    def render_prometheus(self) -> str:
        with self._lock:
            lines: list[str] = _render_info(
                "train_build_info",
                "training run provenance (labels; value always 1)",
                self.build_info)
            for h in (self.step_s, self.data_s, self.sync_s, self.ckpt_s):
                lines += h.render()
            lines += ["# HELP train_events_total training loop lifecycle",
                      "# TYPE train_events_total counter"]
            for name in self.COUNTERS:
                lines.append(f'train_events_total{{event="{name}"}} '
                             f'{self.counters[name]}')
            for kind, n in sorted(self.anomaly_counts.items()):
                lines.append(f'train_anomalies_total{{kind="{kind}"}} {n}')
        lines += render_families(self._families)
        for name, (fn, help_) in sorted(self._gauges.items()):
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} gauge")
            try:
                lines.append(f"{name} {float(fn())}")
            except Exception:  # pragma: no cover — gauge died mid-run
                lines.append(f"{name} NaN")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """JSON-serializable state for `/metrics.json`, shape-compatible
        with ServeMetrics.snapshot() so the same federation merge/render
        helpers apply (serve/metrics.py)."""
        gauges = {}
        for name, (fn, _) in sorted(self._gauges.items()):
            try:
                gauges[name] = round(float(fn()), 6)
            except Exception:  # pragma: no cover — gauge died mid-run
                gauges[name] = None
        with self._lock:
            return {"kind": "train",
                    "histograms": {h.name: h.to_dict() for h in
                                   (self.step_s, self.data_s,
                                    self.sync_s, self.ckpt_s)},
                    "counters": dict(self.counters),
                    "anomaly_by_kind": dict(self.anomaly_counts),
                    "gauges": gauges,
                    "build_info": dict(self.build_info)}


class SupervisorMetrics:
    """Registry for the elastic-training supervisor (train/supervisor.py,
    ISSUE 14): gang lifecycle event counters, generation / live-host /
    restart gauges, per-worker heartbeat ages, and the last verified
    checkpoint step — the live pane the gang previously lacked. Locked
    like TrainMetrics (the supervisor's watch loop writes while the
    TelemetryServer thread renders); jax-free, like the supervisor."""

    def __init__(self):
        self._lock = threading.Lock()
        self.event_counts: dict[str, int] = {}        # timeline events
        self.build_info: dict[str, str] = {}
        self._gauges: dict[str, tuple[Callable[[], float], str]] = {}
        # slot -> heartbeat age in seconds, evaluated per render (the
        # supervisor installs a reader over its hb files)
        self._hb_ages_fn: Optional[Callable[[], dict]] = None

    def event(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.event_counts[name] = self.event_counts.get(name, 0) + n

    def register_gauge(self, name: str, fn: Callable[[], float],
                       help_: str = "") -> None:
        self._gauges[name] = (fn, help_)

    def set_build_info(self, **info) -> None:
        self.build_info.update({k: str(v) for k, v in info.items()})

    def set_heartbeat_ages_fn(self, fn: Callable[[], dict]) -> None:
        self._hb_ages_fn = fn

    def _hb_ages(self) -> dict:
        if self._hb_ages_fn is None:
            return {}
        try:
            return {str(k): round(float(v), 3)
                    for k, v in self._hb_ages_fn().items()}
        except Exception:  # pragma: no cover — hb files mid-rotation
            return {}

    def render_prometheus(self) -> str:
        lines: list[str] = _render_info(
            "supervisor_build_info",
            "supervisor run provenance (labels; value always 1)",
            self.build_info)
        with self._lock:
            lines += ["# HELP supervisor_events_total gang lifecycle "
                      "events (timeline event names)",
                      "# TYPE supervisor_events_total counter"]
            for name, n in sorted(self.event_counts.items()):
                lines.append(
                    f'supervisor_events_total{{event="{name}"}} {n}')
        ages = self._hb_ages()
        if ages:
            lines += ["# HELP supervisor_heartbeat_age_seconds seconds "
                      "since each worker's last heartbeat write",
                      "# TYPE supervisor_heartbeat_age_seconds gauge"]
            for slot, age in sorted(ages.items()):
                lines.append(
                    f'supervisor_heartbeat_age_seconds{{slot="{slot}"}} '
                    f"{age}")
        for name, (fn, help_) in sorted(self._gauges.items()):
            if help_:
                lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} gauge")
            try:
                lines.append(f"{name} {float(fn())}")
            except Exception:  # pragma: no cover — gauge died mid-run
                lines.append(f"{name} NaN")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """Shape-compatible with the other registries' snapshots (no
        histograms — the supervisor's distributions live in its timeline
        and come out of obs/replay.py instead)."""
        gauges = {}
        for name, (fn, _) in sorted(self._gauges.items()):
            try:
                gauges[name] = round(float(fn()), 6)
            except Exception:  # pragma: no cover — gauge died mid-run
                gauges[name] = None
        with self._lock:
            counters = dict(self.event_counts)
        return {"kind": "supervisor", "histograms": {},
                "counters": counters, "gauges": gauges,
                "heartbeat_age_s": self._hb_ages(),
                "build_info": dict(self.build_info)}


class AnomalyMonitor:
    """Host-side loss/grad anomaly detection, fed at sync boundaries.

    Two detectors behind one `mode` knob ('skip' | 'warn' | 'off'):

    * **nonfinite** — NaN/inf loss or grad norm. Under 'skip' the
      compiled step already withheld the optimizer update (train/
      step.py); this side only records the event.
    * **grad_spike** — a finite grad norm more than `spike_factor` x
      the rolling median of the last `window` healthy steps (median,
      not mean: one spike must not drag its own threshold up). Spikes
      are detectable only after the update was applied (the step is
      device-async by design), so they warn — the instrument for
      deciding whether a run needs tighter clipping, not a rollback.

    Events carry the poisoned batch's data-shard coordinates: the
    loader is step-keyed, so (dataset, seed, batch_step, dp_shards)
    reproduces the exact global batch on any host."""

    def __init__(self, mode: str = "warn", *, window: int = 64,
                 spike_factor: float = 8.0, min_history: int = 8):
        assert mode in ("skip", "warn", "off"), f"bad anomaly mode {mode!r}"
        self.mode = mode
        self.spike_factor = spike_factor
        self.min_history = min_history
        self._norms: deque = deque(maxlen=window)
        self.events: list[dict] = []

    def observe(self, *, it: int, loss: float, grad_norm: float,
                skipped: bool = False,
                coords: Optional[dict] = None) -> Optional[dict]:
        """Score one drained step; returns the anomaly event (also kept
        in `self.events`) or None."""
        if self.mode == "off":
            return None
        ev: Optional[dict] = None
        if not (math.isfinite(loss) and math.isfinite(grad_norm)):
            ev = {"kind": "nonfinite"}
        else:
            if len(self._norms) >= self.min_history:
                med = statistics.median(self._norms)
                if med > 0.0 and grad_norm > self.spike_factor * med:
                    ev = {"kind": "grad_spike",
                          "rolling_median_grad_norm": round(med, 6)}
            # only healthy norms feed the baseline: a spike (or NaN)
            # must not inflate the threshold that would catch the next
            if ev is None:
                self._norms.append(grad_norm)
        if ev is not None:
            ev.update({"event": "anomaly", "it": it, "loss": loss,
                       "grad_norm": grad_norm, "skipped": bool(skipped)})
            if coords:
                ev["data_coords"] = dict(coords)
            self.events.append(ev)
        return ev


class TrainTelemetry:
    """The train loop's one observability handle: flight ring +
    Prometheus registry + anomaly monitor + last-known-state gauges.

    Disabled mode (`enabled=False`) is the acceptance bar: the loop
    guards every telemetry call site with `if tel.enabled:` so a
    disabled run pays one attribute check per step and allocates
    nothing (the AnomalyMonitor still runs — it is a training-
    correctness guard, not observability, and costs two isfinite
    checks on floats the loop already fetched)."""

    def __init__(self, *, run: str = "train", enabled: bool = True,
                 anomaly: str = "warn", capacity: int = 4096):
        self.enabled = enabled
        self.run = run
        self.flight = FlightRecorder(capacity=capacity, enabled=enabled)
        self.metrics = TrainMetrics()
        self.anomalies = AnomalyMonitor(anomaly)
        # last-known state for gauges + /healthz (plain dict: written by
        # the loop, read by the HTTP thread — GIL-atomic item access)
        self.last: dict = {"it": -1, "loss": float("nan"),
                           "tokens_per_s": 0.0, "mfu": None,
                           "hbm_gb": None}
        if enabled:
            m = self.metrics
            m.register_gauge("train_iteration", lambda: self.last["it"],
                             "last drained iteration")
            m.register_gauge("train_last_loss", lambda: self.last["loss"],
                             "loss at the last drained step")
            m.register_gauge("train_tokens_per_sec",
                             lambda: self.last["tokens_per_s"],
                             "tokens/sec over the last boundary window")
            m.register_gauge("train_mfu", lambda: self.last["mfu"] or 0.0,
                             "MFU over the last boundary window")
            m.register_gauge("train_hbm_peak_gb",
                             lambda: self.last["hbm_gb"] or 0.0,
                             "peak HBM watermark, in use + reserved (GiB, "
                             "device 0)")

    def record_step(self, **fields) -> None:
        """Append one per-step record (callers pre-filter Nones and
        guard on `self.enabled`; re-checked here for direct users)."""
        if not self.enabled:
            return
        self.flight.record(**fields)

    def record_anomaly(self, ev: dict) -> None:
        """Anomaly events ride the same timeline as step records (the
        `event: anomaly` key distinguishes them) and bump the
        Prometheus anomaly counter — counted even when the ring is
        disabled, so /metrics never under-reports incidents."""
        self.metrics.anomaly(ev.get("kind", "?"))
        if ev.get("skipped"):
            self.metrics.inc("updates_skipped")
        if self.enabled:
            self.flight.record(**ev)

    def status(self) -> dict:
        """The /healthz body: liveness + the last drained step."""
        return {"ok": True, "run": self.run, "it": self.last["it"],
                "loss": self.last["loss"],
                "tokens_per_s": self.last["tokens_per_s"],
                "anomalies": len(self.anomalies.events),
                "steps_recorded": self.flight.total}

    def dump(self, path: str) -> str:
        """Write the retained timeline as JSONL; returns the path."""
        return self.flight.dump_jsonl(path)


class TelemetryServer:
    """Opt-in stdlib HTTP thread exposing a live training run.

    Routes (mirroring the replica server's observability plane):
    * `GET /metrics`        — Prometheus text (TrainMetrics)
    * `GET /metrics.json`   — the registry's federation snapshot (when
      the registry implements `snapshot()` — all of them do)
    * `GET /debug/timeline` — the flight ring's last `?n=` records
    * `GET /healthz`        — `TrainTelemetry.status()` JSON

    `telemetry` is duck-typed: anything with `.metrics` (a registry with
    `render_prometheus()`) and `.flight` (a FlightRecorder) works — the
    supervisor passes its own SupervisorMetrics/flight pair.

    Runs daemonized so a wedged scrape can never hold the process at
    exit; port 0 binds an ephemeral port (tests), the bound port is in
    `.port` and the loop's log line."""

    def __init__(self, telemetry: TrainTelemetry, *,
                 host: str = "127.0.0.1", port: int = 0,
                 status_fn: Optional[Callable[[], dict]] = None):
        tel = telemetry
        status = status_fn or telemetry.status

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):           # no stderr chatter
                pass

            def _send(self, code: int, body: bytes,
                      ctype: str = "application/json") -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path, _, qs = self.path.partition("?")
                query = {k: v[0] for k, v in
                         urllib.parse.parse_qs(qs).items()}
                if path == "/metrics":
                    self._send(200,
                               tel.metrics.render_prometheus().encode(),
                               "text/plain; version=0.0.4; charset=utf-8")
                elif path == "/metrics.json":
                    snap = getattr(tel.metrics, "snapshot", None)
                    if snap is None:
                        self._send(404, b'{"error": "registry has no '
                                        b'snapshot"}')
                        return
                    self._send(200, json.dumps(snap()).encode())
                elif path == "/debug/timeline":
                    try:
                        n = max(1, int(query.get("n", "512")))
                    except ValueError:
                        self._send(400, b'{"error": "bad n"}')
                        return
                    fl = tel.flight
                    self._send(200, json.dumps(
                        {"entries": fl.entries(n), "n_steps": fl.total,
                         "dropped": fl.dropped, "capacity": fl.capacity,
                         "stalls": obs_flight.stall_log(),
                         "stall_totals": obs_flight.stall_totals()}
                    ).encode())
                elif path == "/healthz":
                    try:
                        body = status()
                    except Exception as e:  # noqa: BLE001 — stay alive
                        body = {"ok": False, "error": repr(e)}
                    self._send(200 if body.get("ok") else 503,
                               json.dumps(body).encode())
                else:
                    self._send(404, b'{"error": "not found"}')

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "TelemetryServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.1},
            name="train-telemetry", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
