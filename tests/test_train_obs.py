"""Training-side observability (ISSUE 10): the anomaly guard's
poisoned-step skip/record/resume contract, the live telemetry endpoint
(/metrics + /debug/timeline + /healthz answered MID-RUN), the
memplan-predicted-vs-measured watermark report in stats.json, the
atomic checkpoint-boundary stats refresh, and the disabled-mode
overhead bound (one attribute check, no allocation)."""

import glob
import json
import math
import os
import re
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.train.loop import train
from distributed_pytorch_tpu.train.state import create_train_state
from distributed_pytorch_tpu.train.step import make_train_step
from distributed_pytorch_tpu.train.telemetry import (AnomalyMonitor,
                                                     TrainMetrics,
                                                     TrainTelemetry)

TINY = dict(vocab_size=256, block_size=32, n_embd=32, n_head=4,
            n_kv_heads=4, n_layer=2, up_dim=64)


def _tc(**kw):
    base = dict(dataset="synthetic", data_dir="bench_data",
                total_batch_size=2 * 2 * 32, batch_size=2,
                max_iters=5, parallelism="single", eval=False,
                log_interval=100, save_stats=False, learning_rate=1e-3,
                warmup_steps=2)
    base.update(kw)
    return TrainConfig(**base)


@pytest.fixture()
def in_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    return tmp_path


def _tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Anomaly guard: device side (train/step.py)
# ---------------------------------------------------------------------------

def test_anomaly_skip_withholds_update_bitwise(monkeypatch):
    """A poisoned (NaN loss + NaN grads) step under anomaly='skip'
    leaves params AND optimizer state bit-equal to the pre-step
    snapshot, flags the step in the metrics, and the next (clean) step
    trains normally — the run survives the batch."""
    monkeypatch.setenv("TRAIN_POISON_IT", "1")    # poison state.step == 1
    mc = LLMConfig(**TINY)
    tc = _tc(anomaly="skip")
    model, tx, state, _ = create_train_state(mc, tc, None)
    step = make_train_step(model, tx, mc, tc, None, None)
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (1, 2, 32), 0, TINY["vocab_size"])
    y = jax.random.randint(jax.random.fold_in(rng, 1), (1, 2, 32), 0,
                           TINY["vocab_size"])

    state, m0 = step(state, x, y)                 # step 0: clean
    assert float(m0["nonfinite"]) == 0.0
    assert float(m0["update_skipped"]) == 0.0
    snap_params = jax.device_get(state.params)
    snap_opt = jax.device_get(state.opt_state)

    state, m1 = step(state, x, y)                 # step 1: poisoned
    assert math.isnan(float(m1["loss"]))
    assert float(m1["nonfinite"]) == 1.0
    assert float(m1["update_skipped"]) == 1.0
    _tree_equal(jax.device_get(state.params), snap_params)
    _tree_equal(jax.device_get(state.opt_state), snap_opt)
    assert int(jax.device_get(state.step)) == 2   # step still advances

    state, m2 = step(state, x, y)                 # step 2: clean again
    assert math.isfinite(float(m2["loss"]))
    assert float(m2["update_skipped"]) == 0.0
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(
            jax.device_get(state.params)),
            jax.tree_util.tree_leaves(snap_params)))
    assert changed, "clean step after the skip did not train"


def test_anomaly_warn_keeps_metric_but_applies_update(monkeypatch):
    """'warn' flags the step but never rewrites the update — and 'off'
    strips the metric entirely (the zero-cost path)."""
    monkeypatch.setenv("TRAIN_POISON_IT", "0")
    mc = LLMConfig(**TINY)
    rng = jax.random.PRNGKey(0)
    x = jax.random.randint(rng, (1, 2, 32), 0, TINY["vocab_size"])

    tc = _tc(anomaly="warn")
    model, tx, state, _ = create_train_state(mc, tc, None)
    step = make_train_step(model, tx, mc, tc, None, None)
    state, m = step(state, x, x)
    assert float(m["nonfinite"]) == 1.0
    assert "update_skipped" not in m
    # the NaN update went through — that is what 'warn' means
    assert any(np.isnan(np.asarray(l)).any() for l in
               jax.tree_util.tree_leaves(jax.device_get(state.params)))

    tc_off = _tc(anomaly="off")
    model, tx, state, _ = create_train_state(mc, tc_off, None)
    step = make_train_step(model, tx, mc, tc_off, None, None)
    _, m = step(state, x, x)
    assert "nonfinite" not in m and "update_skipped" not in m


# ---------------------------------------------------------------------------
# Anomaly guard: loop + timeline (the ISSUE 10 satellite test)
# ---------------------------------------------------------------------------

def test_poisoned_batch_skipped_event_in_timeline_run_resumes(
        in_tmp, monkeypatch):
    """e2e through train(): the poisoned batch at iteration k is
    skipped, the anomaly event (with the batch's data-shard
    coordinates) lands in stats AND the dumped train_timeline.jsonl,
    and training resumes with finite loss."""
    k = 2
    monkeypatch.setenv("TRAIN_POISON_IT", str(k))
    mc = LLMConfig(**TINY)
    stats = train(mc, _tc(anomaly="skip", max_iters=5, log_interval=1,
                          file_name="poisonrun", save_stats=True),
                  log=lambda s: None)

    assert math.isnan(stats["train_losses"][k])
    assert all(math.isfinite(l) for l in stats["train_losses"][k + 1:])
    assert math.isfinite(stats["final_loss"])

    (ev,) = stats["anomalies"]
    assert ev["kind"] == "nonfinite" and ev["it"] == k and ev["skipped"]
    coords = ev["data_coords"]
    assert coords["batch_step"] == k
    assert coords["dataset"] == "synthetic"
    assert "seed" in coords and "dp_shards" in coords

    # the event rides the same timeline as the step records
    path = stats["artifacts"]["train_timeline"]
    lines = [json.loads(ln) for ln in open(path)]
    anomaly_lines = [l for l in lines if l.get("event") == "anomaly"]
    assert len(anomaly_lines) == 1 and anomaly_lines[0]["it"] == k
    step_lines = [l for l in lines if "loss" in l and "event" not in l]
    assert {l["it"] for l in step_lines} == set(range(6))
    # phase fields present on post-compile records
    steady = [l for l in step_lines if not l.get("compile_window")]
    assert steady and all("step_ms" in l and "data_ms" in l
                          for l in steady)
    # stats.json carries the anomaly ledger too
    rec = json.load(open(os.path.join("checkpoints", "poisonrun",
                                      "stats.json")))
    assert rec["n_anomalies"] == 1


def test_grad_spike_monitor_and_off_mode():
    mon = AnomalyMonitor("warn", spike_factor=5.0, min_history=4)
    for i in range(6):
        assert mon.observe(it=i, loss=1.0,
                           grad_norm=1.0 + 0.01 * i) is None
    ev = mon.observe(it=6, loss=1.0, grad_norm=50.0)
    assert ev is not None and ev["kind"] == "grad_spike"
    assert ev["rolling_median_grad_norm"] > 0
    # the spike did not feed the baseline: a same-size follow-up still trips
    assert mon.observe(it=7, loss=1.0, grad_norm=50.0)["kind"] == \
        "grad_spike"
    assert mon.observe(it=8, loss=float("nan"),
                       grad_norm=1.0)["kind"] == "nonfinite"
    assert len(mon.events) == 3
    off = AnomalyMonitor("off")
    assert off.observe(it=0, loss=float("nan"),
                       grad_norm=float("inf")) is None
    assert off.events == []


# ---------------------------------------------------------------------------
# Live telemetry endpoint: served MID-RUN (the ISSUE 10 e2e bar)
# ---------------------------------------------------------------------------

def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def test_metrics_endpoint_serves_mid_run(in_tmp):
    """train(metrics_port=0) answers /metrics, /debug/timeline and
    /healthz while the loop is mid-run (the log callback parks the
    training thread at a boundary; the telemetry thread keeps
    serving), and stats.json carries the per-device
    {memplan_predicted_gb, measured_peak_gb, delta} rows."""
    mc = LLMConfig(**TINY)
    tc = _tc(max_iters=8, log_interval=2, metrics_port=0,
             save_stats=True, file_name="telrun")
    found = {"port": None}
    reached, release = threading.Event(), threading.Event()

    def cb(s):
        m = re.search(r"http://127\.0\.0\.1:(\d+)/metrics", s)
        if m:
            found["port"] = int(m.group(1))
        # park the loop at the first post-compile boundary: the run is
        # provably mid-flight while the main thread scrapes
        if s.startswith("iter") and found["port"] \
                and not reached.is_set():
            reached.set()
            release.wait(timeout=60)

    out = {}
    th = threading.Thread(
        target=lambda: out.update(stats=train(mc, tc, log=cb)),
        daemon=True)
    th.start()
    try:
        assert reached.wait(timeout=300), "run produced no boundary line"
        port = found["port"]
        text = _get(f"http://127.0.0.1:{port}/metrics").decode()
        assert "train_build_info" in text and 'run="telrun"' in text
        assert "train_step_seconds_bucket" in text
        assert 'train_events_total{event="steps"}' in text
        assert "train_iteration" in text

        tl = json.loads(_get(
            f"http://127.0.0.1:{port}/debug/timeline?n=8"))
        assert tl["n_steps"] >= 1 and tl["entries"]
        assert {"it", "loss", "grad_norm"} <= set(tl["entries"][-1])
        # the boundary record is a turn of the recorder (obs/flight.py),
        # and the first window, which compiles, is in the stall log
        assert {"t0", "turn_ms", "gc_ms", "cpu_ms", "sync_ms"} <= \
            set(tl["entries"][-1]) and "gap_ms" not in tl["entries"][-1]
        first = [s for s in tl["stalls"] if s.get("compile_window")][-1]
        assert (first["source"], first["cause"]) == ("train", "compile")
        assert first["owner"] in ("data", "dispatch", "sync", "drain")
        assert tl["stall_totals"]["sources"]["train"]["turns"] >= 1
        assert 'train_stalls_total{cause="compile"}' in text
        assert 'train_host_sched_delay_seconds_total{reason="steal"}' \
            in text
        # the trainer hands no kind: its log windows are one
        assert list(tl["stall_totals"]["sources"]["train"]["kinds"]) \
            == ["turn"] and "kind" not in first
        assert "train_stall_seconds_total" in text
        assert 'train_host_gc_pause_seconds_total{generation="0"}' in text

        hz = json.loads(_get(f"http://127.0.0.1:{port}/healthz"))
        assert hz["ok"] and hz["run"] == "telrun" and hz["it"] >= 0
    finally:
        release.set()
    th.join(timeout=300)
    assert not th.is_alive(), "train thread did not finish"

    stats = out["stats"]
    assert stats["telemetry_port"] == found["port"]
    # the server is down after the run
    with pytest.raises(Exception):
        _get(f"http://127.0.0.1:{found['port']}/healthz", timeout=2)

    # memplan-vs-watermark rows: keys always present (values None on
    # backends without memory_stats — CPU), in BOTH stats.json homes
    for home in (os.path.join("checkpoints", "telrun", "stats.json"),
                 os.path.join("runs", "telrun", "stats.json")):
        rec = json.load(open(home))
        devs = rec["memplan"]["devices"]
        assert devs, "no per-device memplan rows"
        for d in devs:
            assert {"device", "memplan_predicted_gb", "measured_peak_gb",
                    "delta"} <= set(d)
        assert rec["memplan"]["predicted_gb"] is not None
    assert os.path.exists(os.path.join("runs", "telrun",
                                       "train_timeline.jsonl"))


# ---------------------------------------------------------------------------
# Disabled mode: the obs/ overhead bar
# ---------------------------------------------------------------------------

def test_disabled_telemetry_records_nothing_and_is_cheap():
    tel = TrainTelemetry(enabled=False)
    tel.record_step(it=0, loss=1.0)
    assert tel.flight.total == 0 and len(tel.flight) == 0
    # the loop guards every call site with `if tel.enabled:` — measure
    # that guard (same 5 µs/call bound test_obs.py holds obs/trace to)
    n = 100_000
    t0 = time.perf_counter()
    acc = 0
    for _ in range(n):
        if tel.enabled:
            acc += 1                               # pragma: no cover
    per_call = (time.perf_counter() - t0) / n
    assert acc == 0
    assert per_call < 5e-6, f"disabled-mode guard cost {per_call:.2e}s"


def test_telemetry_off_run_leaves_no_timeline(in_tmp):
    mc = LLMConfig(**TINY)
    stats = train(mc, _tc(max_iters=2, telemetry=False, metrics_port=0,
                          file_name="quietrun"), log=lambda s: None)
    assert "telemetry_port" not in stats
    assert "artifacts" not in stats
    assert not os.path.exists(os.path.join("runs", "quietrun",
                                           "train_timeline.jsonl"))
    # the memplan report is end-of-run only (no per-step cost): kept
    assert stats["memplan"]["devices"]


# ---------------------------------------------------------------------------
# Atomic stats refresh at checkpoint boundaries
# ---------------------------------------------------------------------------

def test_stats_refreshed_atomically_at_each_checkpoint(in_tmp):
    mc = LLMConfig(**TINY)
    seen = []

    def cb(s):
        if s.startswith("checkpoint (async)"):
            p = os.path.join("checkpoints", "ckrun", "stats.json")
            n = len(json.load(open(p))["train_losses"]) \
                if os.path.exists(p) else -1
            seen.append(n)

    stats = train(mc, _tc(max_iters=6, ckpt_interval=2, log_interval=2,
                          save_stats=True, file_name="ckrun"), log=cb)
    # three interval saves, each preceded by a readable refresh whose
    # loss curve grows — a SIGKILL between them loses at most one window
    assert len(seen) == 3
    assert seen[0] > 0 and seen == sorted(seen)
    # tmp+rename left no droppings
    assert not glob.glob(os.path.join("checkpoints", "ckrun", "*.tmp"))
    assert not glob.glob(os.path.join("runs", "ckrun", "*.tmp"))
    # the runs/ mirror matches the final record
    final = json.load(open(os.path.join("runs", "ckrun", "stats.json")))
    assert final["train_losses"] == stats["train_losses"]
    # and the timeline was refreshed at the boundaries too
    tl = os.path.join("runs", "ckrun", "train_timeline.jsonl")
    assert os.path.exists(tl)
    ck = [json.loads(l) for l in open(tl)
          if json.loads(l).get("event") == "ckpt"]
    assert len(ck) == 3 and all("ckpt_ms" in e for e in ck)


# ---------------------------------------------------------------------------
# TrainMetrics rendering
# ---------------------------------------------------------------------------

def test_train_metrics_prometheus_render():
    m = TrainMetrics()
    m.observe_phases(step_s=0.01, data_s=0.001, sync_s=0.002, ckpt_s=0.5)
    m.observe_phases(step_s=0.02)
    m.inc("steps", 4)
    m.anomaly("nonfinite")
    m.anomaly("grad_spike")
    m.anomaly("grad_spike")
    m.set_build_info(run="x", recipe="single")
    m.register_gauge("train_iteration", lambda: 7, "last iter")
    text = m.render_prometheus()
    for series in ("train_step_seconds_bucket", "train_data_seconds_sum",
                   "train_sync_seconds_count",
                   "train_ckpt_snapshot_seconds_count"):
        assert series in text
    assert 'train_events_total{event="steps"} 4' in text
    assert 'train_events_total{event="anomalies"} 3' in text
    assert 'train_anomalies_total{kind="nonfinite"} 1' in text
    assert 'train_anomalies_total{kind="grad_spike"} 2' in text
    assert 'recipe="single"' in text
    assert "train_iteration 7" in text
    assert m.step_s.count == 2
