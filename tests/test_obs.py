"""obs/ subsystem unit tests: trace-recorder ring bounds and
disabled-mode overhead, Chrome-trace/Perfetto export schema validity,
cross-process span stitching (ingest/re-base), the flight recorder's
ring + JSONL dump, the shared jax.profiler wrapper's guard rails, and the
program's phases and named scopes in the profiler's trace."""

import json
import os
import time

import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.obs import profile as obs_profile
from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.obs.trace import (TraceRecorder, new_trace_id)


# ----------------------------------------------------------------------
# TraceRecorder
# ----------------------------------------------------------------------

def test_trace_ids_unique_and_short():
    ids = {new_trace_id() for _ in range(256)}
    assert len(ids) == 256
    assert all(len(t) == 16 for t in ids)


def test_ring_bound_and_dropped_counter():
    rec = TraceRecorder(capacity=16)
    tid = new_trace_id()
    for i in range(40):
        rec.add(f"s{i}", tid, t0=float(i), dur=0.1)
    assert len(rec) == 16
    assert rec.dropped == 40 - 16
    # the ring keeps the NEWEST spans
    names = [s["name"] for s in rec.snapshot()]
    assert names[0] == "s24" and names[-1] == "s39"


def test_disabled_records_nothing_and_is_cheap():
    rec = TraceRecorder(capacity=64, enabled=False)
    tid = new_trace_id()
    with rec.span("x", tid):
        pass
    rec.add("y", tid, t0=0.0, dur=1.0)
    rec.event("z", tid)
    assert len(rec) == 0
    # overhead bound: the disabled path is one attribute check — 100k
    # calls must stay far under the cost of a single fused decode step
    # per call (generous 5 µs/call bound absorbs CI jitter)
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        rec.span("hot", tid)
        rec.event("hot", tid)
    per_call = (time.perf_counter() - t0) / (2 * n)
    assert per_call < 5e-6, f"disabled-recorder call cost {per_call:.2e}s"


def test_none_trace_id_is_noop_even_when_enabled():
    rec = TraceRecorder()
    rec.add("a", None, t0=0.0, dur=1.0)
    rec.event("b", None)
    with rec.span("c", None):
        pass
    assert len(rec) == 0


def test_span_context_manager_times_and_sets_attrs():
    rec = TraceRecorder()
    tid = new_trace_id()
    with rec.span("work", tid, cat="test", fixed=1) as sp:
        time.sleep(0.01)
        sp.set(extra="yes")
    (s,) = rec.spans_for(tid)
    assert s["name"] == "work" and s["cat"] == "test"
    assert s["dur"] >= 0.009
    assert s["attrs"] == {"fixed": 1, "extra": "yes"}


def test_spans_for_filters_and_orders():
    rec = TraceRecorder()
    t1, t2 = new_trace_id(), new_trace_id()
    rec.add("late", t1, t0=2.0, dur=0.1)
    rec.add("other", t2, t0=0.5, dur=0.1)
    rec.add("early", t1, t0=1.0, dur=0.1)
    assert [s["name"] for s in rec.spans_for(t1)] == ["early", "late"]


def test_summary_offsets_and_ingest_rebase():
    replica = TraceRecorder()
    tid = new_trace_id()
    replica.add("sched.queue", tid, t0=100.0, dur=0.005, cat="sched")
    replica.add("sched.decode", tid, t0=100.010, dur=0.040, cat="sched")
    summ = replica.summary(tid, base=100.0)
    assert summ[0]["off_ms"] == 0.0
    assert summ[1]["off_ms"] == pytest.approx(10.0, abs=1e-6)
    # the router re-bases on its own clock at the dispatch timestamp
    router = TraceRecorder()
    router.ingest(tid, summ, base=500.0, replica="r1")
    spans = router.spans_for(tid)
    assert spans[0]["t0"] == pytest.approx(500.0)
    assert spans[1]["t0"] == pytest.approx(500.010)
    assert all(s["attrs"]["replica"] == "r1" for s in spans)
    # malformed peer spans are skipped, never raised
    router.ingest(tid, [{"off_ms": "not-a-number"}], base=0.0)


def test_chrome_export_schema():
    rec = TraceRecorder()
    tid = new_trace_id()
    rec.add("router.request", tid, t0=1.0, dur=0.5, cat="router", n=1)
    rec.add("sched.decode", tid, t0=1.1, dur=0.3, cat="sched")
    doc = json.loads(json.dumps(rec.to_chrome(tid)))   # JSON-serializable
    assert isinstance(doc["traceEvents"], list)
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(evs) == 2
    for e in evs:
        assert {"name", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
        assert e["args"]["trace"] == tid
    # ts is microseconds
    assert evs[0]["ts"] == pytest.approx(1.0e6)
    assert evs[0]["dur"] == pytest.approx(0.5e6)
    # one thread-name metadata record per category lane
    assert {m["args"]["name"] for m in metas} == {"router", "sched"}


def test_trace_dump_jsonl_roundtrip(tmp_path):
    rec = TraceRecorder()
    tid = new_trace_id()
    rec.add("a", tid, t0=0.0, dur=1.0, k="v")
    path = rec.dump_jsonl(str(tmp_path / "sub" / "trace.jsonl"), tid)
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["name"] == "a" and lines[0]["attrs"] == {"k": "v"}


# ----------------------------------------------------------------------
# FlightRecorder
# ----------------------------------------------------------------------

def test_flight_ring_bound_and_totals():
    fl = FlightRecorder(capacity=8)
    for i in range(20):
        fl.record(step=i, step_ms=1.0)
    assert len(fl) == 8
    assert fl.total == 20
    assert fl.dropped == 12
    ent = fl.entries()
    assert [e["step"] for e in ent] == list(range(12, 20))
    assert all("t" in e for e in ent)
    assert [e["step"] for e in fl.entries(n=3)] == [17, 18, 19]


def test_flight_disabled_and_dump(tmp_path):
    fl = FlightRecorder(capacity=8, enabled=False)
    fl.record(step=1)
    assert len(fl) == 0 and fl.total == 0
    fl.enabled = True
    fl.record(step=1, n_live=3)
    path = fl.dump_jsonl(str(tmp_path / "timeline.jsonl"))
    (rec,) = [json.loads(ln) for ln in open(path)]
    assert rec["step"] == 1 and rec["n_live"] == 3


# ----------------------------------------------------------------------
# obs/profile.py — the shared jax.profiler wrapper
# ----------------------------------------------------------------------

def test_profile_dir_convention(tmp_path):
    d = obs_profile.profile_dir("myrun", root=str(tmp_path))
    assert d == os.path.join(str(tmp_path), "myrun", "profile")
    assert os.path.isdir(d)


def test_profile_capture_and_busy_guard(tmp_path):
    """ONE start/stop cycle covering the whole surface (each
    jax.profiler export costs seconds in a warm process, so the guard,
    context-manager, and artifact checks share a single capture)."""
    # disabled context manager: no capture, yields None
    with obs_profile.profile_trace(str(tmp_path / "x"), enabled=False) \
            as d:
        assert d is None
    assert obs_profile.active() is None
    out = str(tmp_path / "cap")
    d = obs_profile.start_profile(out)
    assert d == out and obs_profile.active() == out
    # the process-global profiler admits one capture at a time: both
    # direct start and the timed-capture helper bounce off the guard
    with pytest.raises(obs_profile.ProfilerBusy):
        obs_profile.start_profile(str(tmp_path / "other"))
    with pytest.raises(obs_profile.ProfilerBusy):
        obs_profile.capture(10, str(tmp_path / "other"))
    jnp.square(jnp.arange(64.0)).block_until_ready()   # traced work
    assert obs_profile.stop_profile() == out
    assert obs_profile.active() is None
    assert obs_profile.stop_profile() is None          # idempotent
    # the capture left a jax profiler artifact tree behind
    assert any(files for _, _, files in os.walk(out)), \
        "profiler capture wrote nothing"


# ----------------------------------------------------------------------
# obs/trace.py phases and scopes: the program in the profiler's trace
# ----------------------------------------------------------------------

_TINY = dict(vocab_size=256, block_size=64, n_embd=32, n_head=2,
             n_kv_heads=2, n_layer=2, up_dim=64, attn="mha",
             pos_emb="learn", non_linearity="gelu")
_ENGINE4 = ["engine.prepare", "engine.dispatch", "engine.wait",
            "engine.retire"]


def _tiny_engine():
    import jax
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    model = LLM(LLMConfig(**_TINY))
    key = jax.random.PRNGKey(0)
    variables = jax.jit(model.init)({"params": key, "dropout": key},
                                    jnp.zeros((1, 8), jnp.int32))
    return model, DecodeEngine(model, variables, n_slots=2, max_len=64,
                               block_size=8, prefill_chunk=16,
                               temperature=0.0, min_bucket=8)


def test_phase_without_a_capture_times_and_opens_no_file(tmp_path,
                                                         monkeypatch):
    from distributed_pytorch_tpu.obs.trace import PHASES, STEP_PHASES, phase
    monkeypatch.chdir(tmp_path)
    assert STEP_PHASES <= set(PHASES)
    acc = {}
    with phase("engine.wait", acc, step=3):
        time.sleep(0.002)
    with phase("engine.wait", acc, step=4):
        time.sleep(0.002)
    with phase("engine.dispatch", acc, step=4, kind="decode") as ph:
        ph.set(n_live=1)
    with phase("sched.idle"):                 # no accumulator: trace only
        pass
    assert set(acc) == {"engine.wait", "engine.dispatch"}
    assert 0.004 <= acc["engine.wait"] < 0.5
    assert 0 <= acc["engine.dispatch"] < 0.1
    assert os.listdir(tmp_path) == []


def test_engine_step_phases_in_a_capture_and_in_the_flight_record(tmp_path):
    """Per `step()` call one shared `step` stat (the number of the program
    the call drains) over its leaves, in order, disjoint; the flight record
    splits step_ms by the same stamps. With one program in flight (PR 31)
    a call's prepare and dispatch belong to the NEXT program (stat
    `program`): the first call of a burst plans and enqueues two under
    its one prepare and one dispatch, the last none."""
    import jax
    from jax.profiler import ProfileData
    _, eng = _tiny_engine()
    eng.run([[5, 6, 7, 8, 9]], 3)             # compile both programs
    n0 = len(eng.flight.entries())
    out = str(tmp_path / "cap")
    d = obs_profile.start_profile(out)
    try:
        eng.admit(list(range(1, 31)), 6)      # two chunks, then decode
        eng.admit([3, 4, 5], 4)
        while eng.n_live or eng.n_free < eng.n_slots:
            eng.step()
    finally:
        obs_profile.stop_profile()
    hits = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
            if f.endswith(".xplane.pb")]
    assert len(hits) == 1
    evs = []
    for plane in ProfileData.from_file(hits[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    evs.sort()
    admits = [e for e in evs if e[2] == "engine.admit"]
    assert [e[3]["chunked"] for e in admits] == [1, 1]
    steps = [e for e in evs if e[2] != "engine.admit"]
    assert all(a[1] <= b[0] for a, b in zip(steps, steps[1:]))  # disjoint
    recs = eng.flight.entries()[n0:]
    assert len(recs) >= 6
    by_program = {rec["step"] - 1: rec for rec in recs}
    calls: dict = {}
    for e in steps:
        calls.setdefault(e[3]["step"], []).append(e)
    assert sorted(calls) == sorted(by_program)   # one record a call
    last = max(calls)
    between = []
    for k, phases in calls.items():
        rec = by_program[k]
        want = _ENGINE4[:1] + _ENGINE4[2:] if k == last else _ENGINE4
        assert [e[2] for e in phases] == want, k
        # the call drains program k, whatever it dispatched
        wait = next(e[3] for e in phases if e[2] == "engine.wait")
        assert wait["program"] == k
        disps = [e[3] for e in phases if e[2] == "engine.dispatch"]
        assert [d_["program"] for d_ in disps] == \
            ([k + 1] if k != last else [])
        for disp in disps:
            assert disp["step_num"] == disp["step"] == k
            assert disp["kind"] in ("fused", "decode")
            mine = by_program[disp["program"]]
            assert disp["prefill_tokens"] == mine["prefill_tokens"]
            assert bool(disp["overlapped"]) == mine["overlapped"]
            assert disp["drain_reason"] == (mine["drain_reason"] or "none")
        parts = [rec[k_] for k_ in ("prepare_ms", "dispatch_ms", "wait_ms",
                                    "retire_ms")]
        assert all(p >= 0 for p in parts)
        assert sum(parts) <= rec["step_ms"] + 0.01     # each rounds to 1 us
        between.append(rec["step_ms"] - sum(parts))
    # what the four leave out are the stretches between the phases: ~30 us
    # each while a capture encodes their stats, of a call that takes ~0.5
    # ms here (the median: a loaded box may take the thread away in one)
    assert sorted(between)[len(between) // 2] < 0.2
    # only the burst's first program had no running one to queue behind
    assert [(r["overlapped"], r["drain_reason"]) for r in recs] == \
        [(False, "first")] + [(True, None)] * (len(recs) - 1)
    # rising programs, and the chunk-carrying ones are the fused program
    disp = [e[3] for e in steps if e[2] == "engine.dispatch"]
    nums = [d_["program"] for d_ in disp]
    assert nums == sorted(set(nums)) == sorted(by_program)[1:]
    kinds = [d_["kind"] for d_ in disp]
    assert kinds[0] == "fused" and kinds[-1] == "decode"
    # two chunks of the long prompt, one of the short: three fused programs
    assert [r["prefill_tokens"] > 0 for r in recs[:4]] == [True] * 3 + [False]


def _compiled_train_step():
    import jax
    from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
    from distributed_pytorch_tpu.train.state import create_train_state
    from distributed_pytorch_tpu.train.step import make_train_step
    mc = LLMConfig(**_TINY)
    tc = TrainConfig(batch_size=2, total_batch_size=128,
                     parallelism="single", dataset="synthetic")
    model, tx, state, _ = create_train_state(mc, tc, None)
    step = make_train_step(model, tx, mc, tc, None, None)
    x = jnp.zeros((1, 2, 64), jnp.int32)
    lowered = step.lower(state, x, x)
    # `grad_norm` is there as the program is lowered; the optimizer's own
    # global-norm clip computes the same reduction, so XLA folds the two
    # and the compiled text keeps `optimizer`'s name for it (which is why
    # one metric reads the two scopes together)
    assert "grad_norm" in lowered.as_text(debug_info=True)
    return lowered.compile().as_text()


def _compiled_engine_step(fused: bool):
    import jax
    from distributed_pytorch_tpu.engine.decode import (make_fused_step_fn,
                                                       make_step_fn)
    model, eng = _tiny_engine()
    args = (eng.variables, eng.caches, eng.tok, eng.pos, eng.live,
            eng.block_tables, eng._rng, jnp.int32(0), eng._qparams)
    if not fused:
        fn = make_step_fn(model, eng._sample)
    else:
        fn = make_fused_step_fn(model, eng._sample, eng.n_slots,
                                eng.table_width)
        args += (jnp.zeros((1, eng.prefill_chunk), jnp.int32), jnp.int32(0),
                 jnp.int32(0), jnp.asarray([4], jnp.int32), jnp.bool_(True))
    with eng._ctx():
        return jax.jit(fn).lower(*args).compile().as_text()


_SCOPES_OF = {
    "train_step": {"attn_core", "loss", "optimizer", "grad_norm"},
    "step": {"decode", "kv_update", "attn_core", "lm_head", "sample"},
    "fused_step": {"chunk_prefill", "decode", "kv_update", "attn_core",
                   "lm_head", "sample"},
}


@pytest.mark.parametrize("program", sorted(_SCOPES_OF))
def test_named_scopes_reach_the_compiled_op_names(program):
    """Every scope of the table is in some compiled program's `op_name`s,
    beside the module names; the backward pass carries the model's as
    `transpose(jvp(...))`; none contains a kernel's name."""
    import re
    from distributed_pytorch_tpu.obs.trace import SCOPES
    assert set().union(*_SCOPES_OF.values()) == set(SCOPES)
    for name in SCOPES:
        assert not re.search(r"flash|paged|pallas", name), name
    text = (_compiled_train_step() if program == "train_step"
            else _compiled_engine_step(program == "fused_step"))
    paths = set(re.findall(r'op_name="([^"]+)"', text))
    assert all(p.startswith(f"jit({program})") for p in paths
               if p.startswith("jit("))
    parts = [set(re.split(r"[/()]", p)) for p in paths]
    for scope in _SCOPES_OF[program] - {"grad_norm"}:
        assert any(scope in p for p in parts), scope
    for module in ("attn", "mlp", "ln_f"):
        assert any(module in p for p in parts), module
    if program == "train_step":
        for scope in ("attn_core", "loss"):
            assert any(scope in p and "jvp" in p and "transpose" not in p
                       for p in parts), scope
            assert any(scope in p and "transpose" in p for p in parts), scope
    else:
        # the cache write and the attention core are apart, in every layer
        assert any({"kv_update", "attn", "block_1"} <= p for p in parts)
        assert not any({"kv_update", "attn_core"} <= p for p in parts)


# ----------------------------------------------------------------------
# obs/paths.py: which path a compiled program took, said where it is read
# ----------------------------------------------------------------------

_HLO = '''
  %jvp_flash_fwd_.1 = (bf16[96,1024,64]{2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(flash_fwd)/pallas_call" stack_frame_id=11}
  %t.3 = bf16[96,1024,64]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(flash_bwd_dq))/pallas_call" stack_frame_id=9}
  %t.4 = bf16[96,1024,64]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(flash_bwd_dq))/pallas_call" stack_frame_id=9}
  %d.1 = bf16[8,12,1,64]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/paged_flash_decode/pallas_call"}
  %x.1 = f32[8]{0} custom-call(%q), custom_call_target="Sharding"
%all-reduce-scatter.39 (input.39: bf16[768,4096]) -> bf16[768,1024] {
  %ag.1 = bf16[768,4096]{1,0} all-gather-start(%p), dimensions={1}
  %ag.2 = bf16[768,4096]{1,0} all-gather-done(%ag.1)
  %ar.1 = f32[] all-reduce(%l), to_apply=%add
'''


def test_kernel_census_reads_kernel_names_off_compiled_text():
    from distributed_pytorch_tpu.obs import paths
    assert paths.kernel_census(_HLO) == {
        "flash_fwd": 1, "flash_bwd_dq": 2, "paged_flash_decode": 1}
    assert paths.kernel_census("ENTRY main { ROOT %a = f32[] add(x, y) }") \
        == {}


def test_collective_census_counts_async_pairs_once_and_fused_rs():
    from distributed_pytorch_tpu.obs import paths
    assert paths.collective_census(_HLO) == {
        "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1}


def test_auto_choice_is_recorded_per_program():
    import jax.numpy as jnp
    from distributed_pytorch_tpu.obs import paths
    from distributed_pytorch_tpu.ops.attention_core import sdpa
    q = jnp.zeros((1, 16, 2, 8), jnp.float32)
    paths.note("loss", "fused", "left over from an earlier trace")
    paths.reset()
    sdpa(q, q, q, impl="auto")
    assert paths.choices() == {"attention": "xla (auto: backend cpu)"}


def test_attn_impl_pallas_declined_is_an_error_naming_the_gate():
    """Asked for BY NAME and refused by the usable gate: an error that
    names the gate and its reason — never a quiet XLA run."""
    import jax.numpy as jnp
    import pytest
    from distributed_pytorch_tpu.obs import paths
    from distributed_pytorch_tpu.ops.attention_core import sdpa
    q = jnp.zeros((1, 16, 2, 12), jnp.float32)       # head dim 12
    with pytest.raises(paths.PathDeclined,
                       match="flash_attention_usable declined: head dim 12"):
        sdpa(q, q, q, impl="pallas")
    # a KV-cached call is outside the flash kernel's contract: no error
    out = sdpa(q, q, q, impl="pallas", decode=True, q_offset=0)
    assert out.shape == q.shape


def test_flash_decode_on_declined_is_an_error_on_a_tpu(monkeypatch):
    """FLASH_DECODE=on that its gate declines: an error on a TPU backend;
    off-TPU ('on' = interpret mode for the parity tests) the reference path
    carries the call and the choice is noted (tests/test_flash_decode.py)."""
    import jax.numpy as jnp
    import pytest
    from distributed_pytorch_tpu.obs import paths
    from distributed_pytorch_tpu.ops import attention_core as core
    q = jnp.zeros((2, 1, 4, 16), jnp.float32)
    kv = jnp.zeros((2, 9, 2, 16), jnp.float32)        # S=9: no tile split
    pos = jnp.array([3, 8], jnp.int32)
    monkeypatch.setenv("FLASH_DECODE", "on")
    paths.reset()
    core.sdpa(q, kv, kv, q_offset=pos, decode=True)
    assert "flash_decode_usable declined" in \
        paths.choices()["decode_attention"]
    monkeypatch.setattr(core, "_on_tpu", lambda: True)
    with pytest.raises(paths.PathDeclined,
                       match="FLASH_DECODE=on .* flash_decode_usable"):
        core.sdpa(q, kv, kv, q_offset=pos, decode=True)
