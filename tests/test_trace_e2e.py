"""End-to-end request tracing across the serving stack (ISSUE 9): the
`X-Trace-Id` header propagates router -> replica HTTP -> scheduler ->
engine, lifecycle spans land on ONE trace (a mid-stream replica kill
included — the failed-over stream stitches into a single timeline), the
replica's `/debug/trace/<id>` + `/debug/timeline` endpoints serve the
recorded evidence, `/metrics` carries the build-info provenance gauge,
and `POST /admin/profile` captures a device trace on a live replica.

Replicas are in-process ServeApp/Scheduler/DecodeEngine stacks on
localhost ports (the tests/test_router.py harness); every async body
runs under a hard wait_for so a tracing bug fails fast, never hangs."""

import asyncio
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.obs import trace as obs_trace
from distributed_pytorch_tpu.serve.router import Router, RouterApp
from distributed_pytorch_tpu.serve.scheduler import Scheduler
from distributed_pytorch_tpu.serve.server import ServeApp


def tiny_cfg(**kw):
    base = dict(vocab_size=97, block_size=64, n_embd=48, n_head=4,
                n_kv_heads=2, attn="gqa", n_layer=2, up_dim=64,
                non_linearity="swiglu", pos_emb="rope", dropout=0.0)
    base.update(kw)
    return LLMConfig(**base)


@pytest.fixture(scope="module")
def mv():
    cfg = tiny_cfg()
    model = LLM(cfg, attn_impl="naive")
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = dict(model.init({"params": rng, "dropout": rng}, x, x))
    return cfg, model, variables


def run_async(coro, timeout=300):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class Rep:
    """In-process replica (the test_router.py harness): engine +
    scheduler + HTTP server; `step_delay` throttles the engine so a kill
    can land mid-stream; chunked prefill on so the traced prefill phase
    is the fused-chunk path."""

    def __init__(self, mv, *, port=0, n_slots=2, step_delay=0.0,
                 prefill_chunk=0):
        _, model, variables = mv
        self.eng = DecodeEngine(model, variables, n_slots=n_slots,
                                temperature=0.0, min_bucket=8,
                                prefill_chunk=prefill_chunk)
        if step_delay:
            orig = self.eng.step

            def slow_step():
                time.sleep(step_delay)
                return orig()

            self.eng.step = slow_step
        self.sched = Scheduler(self.eng, max_queue=32)
        self.app = ServeApp(self.sched, port=port)

    async def start(self):
        await self.sched.start()
        await self.app.start()
        return self

    @property
    def addr(self) -> str:
        return f"127.0.0.1:{self.app.port}"

    async def kill(self):
        self.app.abort()
        await self.sched.stop()

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


async def http_post(port, path, obj, headers=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(obj).encode()
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    writer.write(f"POST {path} HTTP/1.1\r\nHost: t\r\n{extra}"
                 f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    return reader, writer


async def read_sse(reader, on_token=None):
    tokens, done = [], None
    while True:
        line = (await reader.readline()).decode().strip()
        if not line:
            continue
        assert line.startswith("data: "), line
        payload = line[len("data: "):]
        if payload == "[DONE]":
            break
        ev = json.loads(payload)
        if "token" in ev:
            tokens.append(ev["token"])
            if on_token is not None:
                await on_token(len(tokens))
        else:
            done = ev
            if "error" in ev:
                break
    return tokens, done


def span_names(spans):
    return [s["name"] for s in spans]


# ----------------------------------------------------------------------
# single replica: header propagation + lifecycle spans + /debug/trace
# ----------------------------------------------------------------------

def test_trace_id_propagates_and_spans_cover_lifecycle(mv):
    """A client-supplied X-Trace-Id comes back on the done event with a
    span summary covering queue -> (chunked) prefill -> decode ->
    retire, and /debug/trace/<id> replays the same trace — in summary
    and Perfetto form."""
    tid = obs_trace.new_trace_id()

    async def main():
        rep = await Rep(mv, prefill_chunk=16).start()
        reader, writer = await http_post(
            rep.app.port, "/v1/completions",
            {"prompt": [1, 2, 3, 4, 5], "max_tokens": 6},
            headers={"X-Trace-Id": tid})
        assert int((await reader.readline()).split(b" ")[1]) == 200
        while (await reader.readline()).strip():
            pass
        tokens, done = await read_sse(reader)
        writer.close()
        dbg = await http_get(rep.app.port, f"/debug/trace/{tid}")
        chrome = await http_get(rep.app.port,
                                f"/debug/trace/{tid}?fmt=chrome")
        missing = await http_get(rep.app.port, "/debug/trace/deadbeef00")
        await rep.stop()
        return tokens, done, dbg, chrome, missing

    tokens, done, (d_st, d_body), (c_st, c_body), (m_st, _) = \
        run_async(main())
    assert len(tokens) == 6
    assert done["done"] and done["trace_id"] == tid
    names = span_names(done["spans"])
    for want in ("sched.queue", "sched.prefill", "sched.decode",
                 "sched.retire", "replica.http"):
        assert want in names, f"{want} missing from {names}"
    # chunked prefill genuinely ran inside the prefill span's window
    prefill = next(s for s in done["spans"]
                   if s["name"] == "sched.prefill")
    assert prefill["attrs"]["prefilled"] == 5
    retire = next(s for s in done["spans"] if s["name"] == "sched.retire")
    assert retire["attrs"]["reason"] == "budget"
    # offsets are relative to request receipt: everything in-window
    assert all(s["off_ms"] >= 0 for s in done["spans"])
    # /debug/trace agrees
    assert d_st == 200
    dbg = json.loads(d_body)
    assert dbg["trace_id"] == tid
    assert set(span_names(done["spans"])) <= set(span_names(dbg["spans"]))
    # Perfetto export is well-formed
    assert c_st == 200
    doc = json.loads(c_body)
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert evs and all({"name", "ts", "dur", "pid", "tid"} <= set(e)
                       for e in evs)
    assert m_st == 404


def test_unfronted_server_mints_trace_id(mv):
    """No X-Trace-Id header: the replica mints one and the non-stream
    JSON body carries it plus the span summary."""

    async def main():
        rep = await Rep(mv).start()
        reader, writer = await http_post(
            rep.app.port, "/v1/completions",
            {"prompt": [7, 8, 9], "max_tokens": 4, "stream": False})
        data = await reader.read()
        writer.close()
        await rep.stop()
        head, _, body = data.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), json.loads(body)

    status, body = run_async(main())
    assert status == 200
    assert len(body["tokens"]) == 4
    assert len(body["trace_id"]) == 16
    assert "sched.retire" in span_names(body["spans"])


# ----------------------------------------------------------------------
# the acceptance property: mid-stream kill -> ONE stitched trace
# ----------------------------------------------------------------------

def test_failover_produces_single_stitched_trace(mv):
    """Router-fronted request whose replica is killed mid-stream: the
    client sees one gapless stream, and /debug/trace/<id> on the ROUTER
    shows ONE trace whose spans cover router dispatch (the dead attempt
    marked failed), the failover re-dispatch, and BOTH replicas'
    scheduler spans (queue/prefill/decode) re-based onto the router's
    clock — plus the retire event from the finishing replica."""
    prompt, budget = [1, 2, 3], 24

    async def main():
        rep_a = await Rep(mv, step_delay=0.05).start()
        rep_b = await Rep(mv).start()
        router = Router([rep_a.addr], probe_interval_s=0.05,
                        backoff_base_s=0.05, connect_timeout_s=1.0)
        await router.start()
        app = RouterApp(router, port=0)
        await app.start()

        killed = asyncio.Event()

        async def on_token(i):
            if i == 4 and not killed.is_set():
                killed.set()
                router.add_replica(rep_b.addr)
                await router.probe_all()
                await rep_a.kill()

        reader, writer = await http_post(
            app.port, "/v1/completions",
            {"prompt": prompt, "max_tokens": budget})
        assert int((await reader.readline()).split(b" ")[1]) == 200
        while (await reader.readline()).strip():
            pass
        tokens, done = await read_sse(reader, on_token=on_token)
        writer.close()

        tid = done["trace_id"]
        dbg = await http_get(app.port, f"/debug/trace/{tid}")
        await app.stop()
        await router.stop()
        await rep_b.stop()
        return tokens, done, tid, dbg

    tokens, done, tid, (d_st, d_body) = run_async(main())
    # gapless full-budget stream (bit-parity is test_router.py's job)
    assert len(tokens) == done["n_tokens"] == 24
    assert done["failovers"] >= 1
    assert d_st == 200
    dbg = json.loads(d_body)
    assert dbg["trace_id"] == tid
    names = span_names(dbg["spans"])
    # router-side: the request span, >= 2 dispatch attempts (the dead
    # one marked replica_failure, the finisher done), the failover event
    assert "router.request" in names
    dispatches = [s for s in dbg["spans"]
                  if s["name"] == "router.dispatch"]
    assert len(dispatches) >= 2
    outcomes = {s["attrs"]["outcome"] for s in dispatches}
    assert "replica_failure" in outcomes and "done" in outcomes
    assert "router.failover" in names
    # replica-side spans were ingested from BOTH replicas onto this one
    # trace — the failed-over stream reads as one timeline
    replicas_seen = {s["attrs"].get("replica") for s in dbg["spans"]
                     if s["name"] == "replica.http"}
    assert len(replicas_seen) >= 1      # the finisher always reports
    for want in ("sched.queue", "sched.decode", "sched.retire"):
        assert want in names, f"{want} missing from stitched trace"
    # the FINISHING replica's retire is 'budget'; the killed replica may
    # also have left a 'cancelled' retire on the same trace (in-process
    # replicas share the recorder ring) — both belong to this request
    retires = [s["attrs"]["reason"] for s in dbg["spans"]
               if s["name"] == "sched.retire"]
    assert "budget" in retires


# ----------------------------------------------------------------------
# /debug/timeline + build info + /admin/profile
# ----------------------------------------------------------------------

def test_debug_timeline_after_load_burst(mv):
    """A scripted burst of concurrent requests must leave a step-level
    flight record: n_live reaching the burst width, emitted tokens, and
    bounded-ring metadata. The timeline is also dumped under runs/ —
    the artifact tier1.yml uploads from CI."""

    async def main():
        rep = await Rep(mv, n_slots=4).start()
        handles = [rep.sched.submit([i + 1, i + 2, i + 3], 8)
                   for i in range(6)]
        await asyncio.gather(*(h.result() for h in handles))
        status, body = await http_get(rep.app.port,
                                      "/debug/timeline?n=512")
        status2, body2 = await http_get(rep.app.port,
                                        "/debug/timeline?n=2")
        _, metrics = await http_get(rep.app.port, "/metrics")
        flight = rep.eng.flight
        await rep.stop()
        return status, json.loads(body), status2, json.loads(body2), \
            flight, metrics

    status, body, status2, body2, flight, metrics = run_async(main())
    assert status == 200
    entries = body["entries"]
    assert entries and body["n_steps"] == flight.total
    for e in entries:
        assert {"t", "step", "step_ms", "n_live", "prefill_tokens",
                "emitted", "blocks_in_use", "preemptions",
                "overlapped", "drain_reason", "overrun",
                # the turn (obs/flight.py)
                "t0", "turn_ms", "gc_ms", "cpu_ms", "capturing",
                "kind"} <= set(e)
    # the process's stalled turns ride beside the ring, newest last: this
    # engine's first step traced its program
    stalls = [s for s in body["stalls"] if s["source"] == "engine"]
    assert stalls and stalls == sorted(stalls, key=lambda s: s["t"])
    assert {"owner", "cause", "excess_ms", "median_ms"} <= set(stalls[-1])
    assert "compile" in {s["cause"] for s in stalls}
    # each says its program's kind and, where the platform counts it
    # (Linux), where its thread stood
    stood = {"sched_delay_ms", "steal_ms", "nivcsw"}
    for s in stalls:
        assert s["kind"] in ("decode", "fused", "spec")
        assert stood <= set(s) or not stood & set(entries[-1])
    totals = body["stall_totals"]
    assert totals["sources"]["engine"]["turns"] >= len(entries)
    kinds = totals["sources"]["engine"]["kinds"]
    assert sum(k["turns"] for k in kinds.values()) \
        == totals["sources"]["engine"]["turns"]
    assert 'serve_host_sched_delay_seconds_total{reason="run_queue"}' \
        in metrics
    assert len(totals["gc_pause_seconds"]) == 3
    assert "# TYPE serve_engine_stalls_total counter" in metrics
    assert 'serve_engine_stalls_total{cause="compile"}' in metrics
    assert 'serve_engine_stall_seconds_total{cause="compile"}' in metrics
    assert 'serve_host_gc_pause_seconds_total{generation="2"}' in metrics
    # a wave engine never queues a program behind a running one, and
    # the record says why
    assert {(e["overlapped"], e["drain_reason"]) for e in entries} == \
        {(False, "wave")}
    # the burst genuinely batched: some step decoded >= 2 streams and
    # tokens were emitted across the window
    assert max(e["n_live"] for e in entries) >= 2
    # wave mode samples each request's FIRST token at admission, so the
    # steps account for budget-1 tokens per request
    assert sum(e["emitted"] for e in entries) >= 6 * 7
    assert all(e["step_ms"] > 0 for e in entries)
    # ?n= bounds the payload
    assert status2 == 200 and len(body2["entries"]) == 2
    # persist for the CI artifact upload (runs/**/*.jsonl in tier1.yml)
    path = flight.dump_jsonl(
        os.path.join("runs", "ci_trace_e2e", "timeline.jsonl"))
    assert os.path.getsize(path) > 0


def test_lookahead_counter_where_operators_look(mv, tmp_path):
    """One program in flight (PR 31), seen from outside: `/debug/timeline`
    says per program whether it was queued behind a running one and why
    not, `/metrics` carries `serve_engine_overlap_share`, and in a traced
    run the four `engine.*` phases of one `step()` call share one `step`
    stat (dispatch and wait also say which program they are about), so
    the benchmark's three readings of them (benchmark/lib/trace_spans.py:
    extent, sum, turnaround) stay positive and under one turn of the
    loop."""
    from benchmark.lib import trace_spans as ts
    four = ["engine.prepare", "engine.dispatch", "engine.wait",
            "engine.retire"]

    async def main():
        rep = await Rep(mv, n_slots=3, prefill_chunk=16).start()
        rep.eng.run([list(range(1, 20))], 3)      # compile both programs
        n0 = rep.eng.n_steps
        jax.profiler.start_trace(str(tmp_path))
        try:
            handles = [rep.sched.submit(list(range(i + 1, i + n)), 12)
                       for i, n in enumerate((9, 20, 30, 12, 5))]
            await asyncio.gather(*(h.result() for h in handles))
        finally:
            jax.profiler.stop_trace()
        _, timeline = await http_get(rep.app.port, "/debug/timeline?n=512")
        _, metrics = await http_get(rep.app.port, "/metrics")
        eng = rep.eng
        await rep.stop()
        return json.loads(timeline), metrics, eng, n0

    timeline, metrics, eng, n0 = run_async(main())
    entries = timeline["entries"]
    # the engine's lifetime shares ride the payload beside the records
    assert timeline["overlap_share"] == pytest.approx(eng.overlap_share)
    assert timeline["chunk_fill_share"] == \
        pytest.approx(eng.chunk_fill_share)
    assert 0 < eng.chunk_fill_share <= 1
    assert timeline["chunk_programs_per_prompt"] == \
        pytest.approx(eng.chunk_programs_per_prompt) == \
        pytest.approx(9 / 6)       # 19, 19 and 29 ids: two chunks of 16 each
    mine = [e for e in entries if e["step"] > n0]
    assert len(mine) >= 12
    assert {e["drain_reason"] for e in mine if not e["overlapped"]} == \
        {"first"}
    share = sum(e["overlapped"] for e in mine) / len(mine)
    assert share > 0.8
    gauge = next(ln for ln in metrics.splitlines()
                 if ln.startswith("serve_engine_overlap_share "))
    assert float(gauge.split()[1]) == pytest.approx(eng.overlap_share)
    assert eng.overlap_share > 0.5

    sl = ts.load(str(tmp_path))
    evs = sorted((e for e in ts.phase_events(sl, "engine.")
                  if e[0] != "engine.admit"), key=lambda e: e[1])
    # the calls, as runs of phases on the engine's thread: each starts
    # with a prepare and shares one `step` stat
    calls = []
    for e in evs:
        if e[0] == "engine.prepare":
            calls.append([])
        calls[-1].append(e)
    assert len(calls) >= 12
    for phases in calls:
        names = [e[0] for e in phases]
        assert names in (four, four[:1] + four[2:], four[:1]), names
        assert len({e[3]["step"] for e in phases}) == 1
        step = phases[0][3]["step"]
        for e in phases:
            if e[0] == "engine.wait":
                assert e[3]["program"] == step      # the call drains it
            if e[0] == "engine.dispatch":
                assert e[3]["program"] == step + int(e[3]["overlapped"])
                assert e[3]["drain_reason"] == (
                    "none" if e[3]["overlapped"] else "first")
    steps = ts.steps_by_stat(evs, four)
    pairs = [k for k in sorted(steps) if k + 1 in steps]
    assert len(pairs) >= 8
    turn = {k: (steps[k + 1]["engine.prepare"][0]
                - steps[k]["engine.prepare"][0]) / 1e6 for k in pairs}
    sub = {k: steps[k] for k in pairs}
    extent = ts.step_extent_ms(sub, "engine.prepare", "engine.retire")
    host = ts.step_sum_ms(sub, ["engine.prepare", "engine.dispatch",
                                "engine.retire"])
    around = ts.step_turnaround_ms(steps, "engine.retire", "engine.prepare")
    assert len(around) == len(pairs)
    for k, ext, h, a in zip(pairs, extent, host, around):
        assert 0 < h < ext < turn[k], (k, h, ext, turn[k])
        assert 0 < a < turn[k], (k, a, turn[k])
        assert ext + a == pytest.approx(turn[k], rel=1e-6)


def test_build_info_gauges_on_metrics(mv):
    async def main():
        rep = await Rep(mv, prefill_chunk=16).start()
        router = Router([rep.addr], probe_interval_s=0.05)
        await router.start()
        app = RouterApp(router, port=0)
        await app.start()
        _, rep_metrics = await http_get(rep.app.port, "/metrics")
        _, router_metrics = await http_get(app.port, "/metrics")
        await app.stop()
        await router.stop()
        await rep.stop()
        return rep_metrics, router_metrics

    rep_metrics, router_metrics = run_async(main())
    line = next(ln for ln in rep_metrics.splitlines()
                if ln.startswith("serve_build_info{"))
    assert 'prefill_chunk="16"' in line
    assert 'kv_block="8"' in line
    assert 'cache_dtype="' in line
    assert f'jax="{jax.__version__}"' in line
    assert line.endswith(" 1")
    r_line = next(ln for ln in router_metrics.splitlines()
                  if ln.startswith("router_build_info{"))
    assert 'replicas="1"' in r_line


def test_admin_profile_captures_on_live_replica(mv, tmp_path):
    async def main():
        rep = await Rep(mv).start()
        rep.app.profile_dir = str(tmp_path / "cap")
        # keep the engine busy while the capture window is open
        h = rep.sched.submit([1, 2, 3], 16)
        reader, writer = await http_post(
            rep.app.port, "/admin/profile?duration_ms=50", {})
        data = await reader.read()
        writer.close()
        bad_reader, bad_writer = await http_post(
            rep.app.port, "/admin/profile?duration_ms=0", {})
        bad = await bad_reader.read()
        bad_writer.close()
        await h.result()
        await rep.stop()
        return data, bad

    data, bad = run_async(main())
    head, _, body = data.partition(b"\r\n\r\n")
    assert int(head.split(b" ")[1]) == 200, data
    out = json.loads(body)
    assert out["duration_ms"] == 50
    assert os.path.isdir(out["profile_dir"])
    assert any(files for _, _, files in os.walk(out["profile_dir"])), \
        "capture wrote no profiler artifacts"
    assert int(bad.split(b" ")[1]) == 400
