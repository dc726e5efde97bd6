"""Fleet observability (ISSUE 14): cross-process metrics federation,
SLO burn-rate accounting, and the timeline-replay cost-model extractor.

Three layers under test:

* serve/metrics.py federation — histogram snapshots merge EXACTLY (a
  fleet page is bit-equal to summing per-replica scrapes), reservoirs
  concatenate-and-cap with bounded quantile error, and `render_fleet`
  emits fleet-summed series next to per-replica labeled ones;
* obs/slo.py — declarative targets turned into multi-window burn rates
  and error-budget gauges, driven here by an injected clock;
* obs/replay.py + the train/supervisor registries — the deterministic
  analyzer fits the PERF.md step model on synthetic timelines with a
  known ground truth, and the supervisor's opt-in telemetry serves the
  same /metrics.json federation snapshot the replicas do.

The e2e test reuses the test_router.py idiom: real in-process
ServeApp/Scheduler/DecodeEngine replicas behind a Router whose
federation pull is cranked down to the probe cadence.
"""

import asyncio
import json
import os
import random
import urllib.request

import pytest

from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.obs.prom import LATENCY_BUCKETS, Histogram
from distributed_pytorch_tpu.obs.slo import SLOTarget, SLOTracker
from distributed_pytorch_tpu.serve.metrics import (ServeMetrics,
                                                   merge_histograms,
                                                   render_fleet,
                                                   render_hist_snap)


# ----------------------------------------------------------------------
# histogram merge exactness
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 1729])
def test_merge_bit_equal_to_single_process(seed):
    """N-replica merge == single-process observation, bit-equal on
    bucket counts/count and exact (modulo float addition order) on sum:
    the federation invariant the fleet page advertises."""
    rng = random.Random(seed)
    vals = [rng.expovariate(10.0) for _ in range(3000)]
    whole = Histogram("h", "x")
    parts = [Histogram("h", "x") for _ in range(3)]
    for i, v in enumerate(vals):
        whole.observe(v)
        parts[i % 3].observe(v)
    merged = merge_histograms([p.to_dict() for p in parts])
    assert merged["counts"] == whole.counts          # bit-equal ints
    assert merged["count"] == whole.count
    assert merged["sum"] == pytest.approx(whole.sum, rel=1e-12)
    # and the rendered cumulative bucket lines agree line-for-line
    # (all but `_sum`, whose float addition order legitimately differs)
    drop = f"{merged['name']}_sum"
    assert ([ln for ln in render_hist_snap(merged)[2:]
             if not ln.startswith(drop)]
            == [ln for ln in render_hist_snap(whole.to_dict())[2:]
                if not ln.startswith(drop)])


def test_merge_rejects_bucket_mismatch():
    a = Histogram("h", "x", buckets=(0.1, 1.0))
    b = Histogram("h", "x", buckets=(0.2, 1.0))
    b.observe(0.15)
    with pytest.raises(ValueError, match="bucket mismatch"):
        a.merge_from(b.to_dict())


def test_merged_reservoir_cap_and_quantile_bounds():
    """Reservoirs concatenate capped at max_samples; the merged quantile
    stays within the bucket grid's resolution of the exact pooled
    quantile (same seeded distribution in every shard, so truncation
    keeps the estimate honest)."""
    rng = random.Random(3)
    shards = []
    pooled = []
    for _ in range(4):
        h = Histogram("h", "x")
        for _ in range(500):
            v = rng.uniform(0.0, 1.0)
            h.observe(v)
            pooled.append(v)
        shards.append(h.to_dict())
    cap = 600                       # < 2000 pooled: truncation engages
    merged = Histogram.from_dict(shards[0], max_samples=cap)
    for s in shards[1:]:
        merged.merge_from(s)
    assert len(merged._samples) == cap
    assert merged.count == 2000     # counts are NEVER truncated
    exact = sorted(pooled)[len(pooled) // 2]
    assert merged.quantile(0.5) == pytest.approx(exact, abs=0.1)


def test_count_le_exact_at_bucket_edges():
    h = Histogram("h", "x")
    obs = [0.003, 0.05, 0.049, 0.051, 0.5, 2.0]
    for v in obs:
        h.observe(v)
    assert 0.05 in LATENCY_BUCKETS and 0.5 in LATENCY_BUCKETS
    assert h.count_le(0.05) == sum(1 for v in obs if v <= 0.05)
    assert h.count_le(0.5) == sum(1 for v in obs if v <= 0.5)
    assert h.count_le(1e9) == h.count


# ----------------------------------------------------------------------
# render_fleet (pure, no sockets)
# ----------------------------------------------------------------------

def test_render_fleet_sums_and_labels():
    reps = {}
    rng = random.Random(11)
    expected_completed = 0
    for i in range(3):
        m = ServeMetrics()
        for _ in range(50):
            m.ttft.observe(rng.expovariate(5.0))
        n = rng.randrange(1, 9)
        m.inc("completed", n)
        expected_completed += n
        m.set_weights_version(f"step_10-cafe{i:04d}")
        reps[f"127.0.0.1:800{i}"] = m.snapshot()
    page = render_fleet(reps)
    lines = page.splitlines()
    assert "serve_fleet_replicas 3" in lines
    # the unlabeled fleet series is bit-equal to merging the snapshots
    merged = merge_histograms(
        [s["histograms"]["serve_ttft_seconds"] for s in reps.values()])
    for want in render_hist_snap(merged, header=False):
        assert want in lines, want
    # every replica appears as a labeled series of the same histogram
    for r, snap in reps.items():
        cnt = snap["histograms"]["serve_ttft_seconds"]["count"]
        assert f'serve_ttft_seconds_count{{replica="{r}"}} {cnt}' in lines
        wv = snap["weights_version"]
        assert (f'serve_weights_version{{replica="{r}",'
                f'version="{wv}"}} 1' in lines)
    assert ('serve_fleet_requests_total{event="completed"} '
            f"{expected_completed}" in lines)


# ----------------------------------------------------------------------
# SLO tracker (injected clock)
# ----------------------------------------------------------------------

def _tracker(windows=(10.0, 100.0)):
    clock = {"t": 0.0}
    targets = [SLOTarget("lat", "latency", objective=0.99,
                         threshold_s=0.05),
               SLOTarget("avail", "availability", objective=0.9)]
    tr = SLOTracker(targets, windows_s=windows,
                    now_fn=lambda: clock["t"])
    return tr, clock


def test_slo_burn_rate_windows_and_budget():
    tr, clock = _tracker()
    tr.update({"lat": (0, 0), "avail": (0, 0)})
    # 100 events, 2 bad -> bad fraction 2% = 2x the 1% budget
    clock["t"] = 5.0
    tr.update({"lat": (98, 100), "avail": (100, 100)})
    assert tr.burn_rate("lat", 10.0) == pytest.approx(2.0)
    assert tr.burn_rate("avail", 10.0) == 0.0
    assert tr.budget_remaining("lat") == pytest.approx(1 - 0.02 / 0.01)
    # the bad burst ages OUT of the short window but still counts
    # against the cumulative budget
    clock["t"] = 50.0
    tr.update({"lat": (198, 200), "avail": (200, 200)})
    assert tr.burn_rate("lat", 10.0) == 0.0        # clean recent window
    assert tr.burn_rate("lat", 100.0) == pytest.approx(1.0)
    assert tr.budget_remaining("lat") == pytest.approx(0.0)
    assert tr.budget_remaining("avail") == 1.0


def test_slo_budget_exhaustion_goes_negative():
    tr, clock = _tracker()
    tr.update({"avail": (0, 0)})
    clock["t"] = 1.0
    tr.update({"avail": (50, 100)})    # 50% bad vs a 10% budget
    assert tr.budget_remaining("avail") < 0
    snap = tr.snapshot()
    assert snap["avail"]["budget_remaining"] < 0
    assert snap["avail"]["burn_rate"]["10"] == pytest.approx(5.0)
    txt = "\n".join(tr.render_prometheus())
    assert 'slo_burn_rate{slo="avail",window_s="10"} 5.000000' in txt
    assert 'slo_error_budget_remaining{slo="avail"} -4.000000' in txt


def test_slo_no_events_is_silent():
    tr, _ = _tracker()
    assert tr.burn_rate("lat", 10.0) == 0.0
    assert tr.budget_remaining("lat") == 1.0


# ----------------------------------------------------------------------
# timeline replay: known ground truth
# ----------------------------------------------------------------------

def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_replay_fits_known_step_model(tmp_path):
    """Synthetic engine timeline with step_ms = 2 + 0.01·prefill_tokens
    exactly; the fit must recover (a, b) and exclude the planted compile
    outlier."""
    from distributed_pytorch_tpu.obs import replay
    rng = random.Random(5)
    recs = [{"step": 0, "step_ms": 500.0, "prefill_tokens": 0,
             "n_live": 1}]                       # compile step
    for i in range(1, 200):
        x = rng.choice([0, 0, 0, 64, 128, 256])
        recs.append({"step": i, "step_ms": 2.0 + 0.01 * x,
                     "prefill_tokens": x, "n_live": 4})
    _write_jsonl(tmp_path / "timeline.jsonl", recs)
    _write_jsonl(tmp_path / "trace.jsonl", [
        {"trace": "t", "span": i, "parent": None, "name": name,
         "cat": "sched", "t0": 0.0, "dur": dur, "attrs": {}}
        for i, (name, dur) in enumerate(
            [("sched.queue", 0.004), ("sched.queue", 0.006),
             ("sched.prefill", 0.010), ("sched.prefill", 0.012)])])
    a = replay.write_report(str(tmp_path))
    assert not a["degenerate"] and not a["notes"]
    m = a["engine"]["step_model"]
    assert m["a_ms"] == pytest.approx(2.0, abs=1e-6)
    assert m["b_ms_per_prefill_token"] == pytest.approx(0.01, abs=1e-9)
    assert m["mae_pct"] == pytest.approx(0.0, abs=1e-6)
    assert m["warmup_excluded"] == 1
    tm = a["trace"]["ttft_model"]
    assert tm["predicted_ttft_p50_ms"] == pytest.approx(4 + 10, abs=2.1)
    # artifacts on disk, machine-readable model round-trips
    with open(a["cost_model_json"]) as f:
        cm = json.load(f)
    assert cm["engine"]["step_model"] == m
    assert os.path.exists(a["report_md"])
    assert "step_ms ≈ 2.0 + 0.01" in open(a["report_md"]).read()


def test_replay_supervisor_and_train_sections(tmp_path):
    from distributed_pytorch_tpu.obs import replay
    _write_jsonl(tmp_path / "supervisor_timeline.jsonl", [
        {"event": "gang_spawn", "t": 0.0},
        {"event": "worker_down", "t": 5.0},
        {"event": "gang_restart", "t": 6.5},
        {"event": "completed", "t": 20.0}])
    _write_jsonl(tmp_path / "train_timeline.jsonl", [
        {"it": i, "loss": 5.0 - 0.1 * i, "step_ms": 10.0 + (i == 0) * 400,
         "data_ms": 1.0, "sync_ms": 0.5, "ckpt_ms": 0.0,
         "tokens_per_s": 1000.0, "grad_norm": 1.0,
         "compile_window": i == 0} for i in range(20)])
    a = replay.analyze(str(tmp_path))
    assert not a["degenerate"]
    sup = a["supervisor"]
    assert sup["events"]["worker_down"] == 1
    assert sup["final_event"] == "completed"
    assert sup["recovery_s"]["p50"] == pytest.approx(1.5)
    trn = a["train"]
    assert trn["iterations"] == 20
    assert trn["loss_first"] == 5.0 and trn["loss_last"] == 3.1
    assert trn["compile_windows"] == 1


def test_replay_degenerate_on_empty_dir(tmp_path):
    from distributed_pytorch_tpu.obs import replay
    (tmp_path / "noise.jsonl").write_text('{"unrelated": 1}\n')
    a = replay.analyze(str(tmp_path))
    assert a["degenerate"]
    assert a["files"]["skipped"]


def test_obs_report_cli_exit_codes(tmp_path):
    """scripts/obs_report.py: 0 on a clean fit, 2 on a degenerate run
    dir — the CI gate's contract."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    good = tmp_path / "good"
    good.mkdir()
    _write_jsonl(good / "timeline.jsonl",
                 [{"step": i, "step_ms": 2.0, "prefill_tokens": 0,
                   "n_live": 1} for i in range(30)])
    assert mod.main([str(good)]) == 0
    empty = tmp_path / "empty"
    empty.mkdir()
    assert mod.main([str(empty)]) == 2
    assert mod.main([str(tmp_path / "missing")]) == 2


# ----------------------------------------------------------------------
# supervisor/train registries + TelemetryServer federation route
# ----------------------------------------------------------------------

def test_supervisor_metrics_snapshot_and_server():
    from distributed_pytorch_tpu.train.telemetry import (SupervisorMetrics,
                                                         TelemetryServer)

    class Tel:                        # duck-typed: .metrics + .flight
        metrics = SupervisorMetrics()
        flight = FlightRecorder(capacity=16)

    m = Tel.metrics
    m.event("gang_spawn")
    m.event("worker_down")
    m.event("gang_restart")
    m.set_build_info(run="t", hosts=2)
    m.register_gauge("supervisor_generation", lambda: 2.0)
    m.set_heartbeat_ages_fn(lambda: {0: 0.25, 1: 1.5})
    txt = m.render_prometheus()
    assert 'supervisor_events_total{event="worker_down"} 1' in txt
    assert 'supervisor_heartbeat_age_seconds{slot="1"} 1.5' in txt
    assert "supervisor_generation 2.0" in txt

    srv = TelemetryServer(Tel(), port=0,
                          status_fn=lambda: {"ok": True}).start()
    try:
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics.json",
            timeout=5).read())
    finally:
        srv.stop()
    assert snap["kind"] == "supervisor"
    assert snap["counters"] == {"gang_spawn": 1, "worker_down": 1,
                                "gang_restart": 1}
    assert snap["histograms"] == {}
    assert snap["heartbeat_age_s"] == {"0": 0.25, "1": 1.5}
    assert snap["gauges"]["supervisor_generation"] == 2.0


def test_train_metrics_snapshot_shape():
    from distributed_pytorch_tpu.train.telemetry import TrainMetrics
    m = TrainMetrics()
    m.observe_phases(step_s=0.01, data_s=0.001, sync_s=0.0)
    snap = m.snapshot()
    assert snap["kind"] == "train"
    assert snap["histograms"]["train_step_seconds"]["count"] == 1
    # the federation snapshot merges with the serve-side machinery
    merged = merge_histograms(
        [snap["histograms"]["train_step_seconds"]] * 2)
    assert merged["count"] == 2


# ----------------------------------------------------------------------
# e2e: replicas + router federation pull + /metrics/fleet
# ----------------------------------------------------------------------

def test_fleet_endpoint_e2e():
    """3 real in-process replicas behind a Router with the federation
    pull on the probe cadence: /metrics/fleet's unlabeled bucket sums
    are bit-equal to merging the replicas' own /metrics.json scrapes,
    per-replica labeled series are present, and the router's /metrics
    carries the SLO gauges."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.serve.router import Router, RouterApp
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    from distributed_pytorch_tpu.serve.server import ServeApp

    cfg = LLMConfig(vocab_size=97, block_size=64, n_embd=48, n_head=4,
                    n_kv_heads=2, attn="gqa", n_layer=2, up_dim=64,
                    non_linearity="swiglu", pos_emb="rope", dropout=0.0)
    model = LLM(cfg, attn_impl="naive")
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = dict(model.init({"params": rng, "dropout": rng}, x, x))

    class Rep:
        def __init__(self):
            self.eng = DecodeEngine(model, variables, n_slots=2,
                                    temperature=0.0, min_bucket=8)
            self.sched = Scheduler(self.eng, max_queue=32)
            self.sched.metrics.set_weights_version("demo")
            self.app = ServeApp(self.sched, port=0)

        async def start(self):
            await self.sched.start()
            await self.app.start()
            return self

        @property
        def addr(self):
            return f"127.0.0.1:{self.app.port}"

        async def stop(self):
            await self.app.stop()
            await self.sched.stop()

    async def http_get(port, path):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
        writer.close()
        head, _, body = data.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), body.decode()

    async def main():
        reps = [await Rep().start() for _ in range(3)]
        router = Router([r.addr for r in reps], probe_interval_s=0.05,
                        probe_timeout_s=2.0, fleet_poll_interval_s=0.0)
        await router.start()
        app = RouterApp(router, port=0)
        await app.start()
        prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
        outs = await asyncio.gather(*(router.complete(p, 4)
                                      for p in prompts))
        # wait until every replica's final counts have federated in
        deadline = asyncio.get_running_loop().time() + 10
        while asyncio.get_running_loop().time() < deadline:
            snaps = router.fleet_snapshots()
            done = sum(s["counters"]["completed"]
                       for s in snaps.values())
            if len(snaps) == 3 and done == len(prompts):
                break
            await asyncio.sleep(0.05)
        direct = {}
        for r in reps:
            status, body = await http_get(r.app.port, "/metrics.json")
            assert status == 200
            direct[r.addr] = json.loads(body)
        f_status, fleet = await http_get(app.port, "/metrics/fleet")
        m_status, rmetrics = await http_get(app.port, "/metrics")
        j_status, rjson = await http_get(app.port, "/metrics.json")
        await app.stop()
        await router.stop()
        for r in reps:
            await r.stop()
        return outs, direct, (f_status, fleet), (m_status, rmetrics), \
            (j_status, rjson)

    outs, direct, (f_status, fleet), (m_status, rmetrics), \
        (j_status, rjson) = asyncio.run(asyncio.wait_for(main(), 300))
    assert all(o["reason"] == "budget" for o in outs)
    assert f_status == 200
    lines = fleet.splitlines()
    assert "serve_fleet_replicas 3" in lines
    # bit-equality: the unlabeled fleet series == merging the replicas'
    # OWN scrapes (every histogram name, every bucket line)
    for hn in ("serve_ttft_seconds", "serve_itl_seconds",
               "serve_e2e_seconds"):
        merged = merge_histograms(
            [s["histograms"][hn] for s in direct.values()])
        for want in render_hist_snap(merged, header=False):
            assert want in lines, want
    for addr, snap in direct.items():
        assert (f'serve_fleet_requests_total{{event="completed",'
                f'replica="{addr}"}} {snap["counters"]["completed"]}'
                in lines)
        assert (f'serve_weights_version{{replica="{addr}",'
                f'version="demo"}} 1' in lines)
    done_total = sum(s["counters"]["completed"] for s in direct.values())
    assert f'serve_fleet_requests_total{{event="completed"}} {done_total}' \
        in lines
    # router /metrics carries the SLO gauges; /metrics.json federates
    assert m_status == 200
    assert 'slo_burn_rate{slo="ttft_p99",window_s="300"}' in rmetrics
    assert 'slo_error_budget_remaining{slo="availability"}' in rmetrics
    assert j_status == 200 and json.loads(rjson)["kind"] == "router"
