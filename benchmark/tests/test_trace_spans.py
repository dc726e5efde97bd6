"""lib/trace_spans.py and its three readers: the arithmetic on plain event
lists (no trace), and a CPU rehearsal in which the program runs under a
capture written where the runners write theirs. A CPU capture holds the
program's host phases and no device plane, so the program-span readers
return numbers from it and the device readers return None."""

import asyncio
import os

import pytest

from benchmark.lib import harness, trace_reduce
from benchmark.lib import trace_spans as ts
from benchmark.readers import trace_idle_owner, trace_scope_ms

ENGINE4 = ["engine.prepare", "engine.dispatch", "engine.wait",
           "engine.retire"]


# ---------------------------------------------------------------------------
# scope paths and owners, from recorded HLO instruction text
# ---------------------------------------------------------------------------

# three instructions of gpt2xl_serve_closed24's `jit_step` as a v5e trace
# names them (my chip run, PR 25), operands shortened
COPY_IN = ("%copy.567 = bf16[200,128,25,64]{3,2,1,0:T(8,128)(2,1)} "
           "copy(bf16[200,128,25,64]{1,3,2,0:T(8,128)(2,1)S(1)} "
           "%custom-call.238)")
SCATTER = ("%fusion.20 = bf16[200,128,25,64]{3,2,1,0:T(8,128)(2,1)} "
           "fusion(bf16[200,128,25,64]{3,2,1,0:T(8,128)(2,1)} %copy.567, "
           "s32[24]{0:T(128)S(1)} %fusion.7167), kind=kCustom, "
           "calls=%fused_computation.20")
COPY_OUT = ("%copy.813 = bf16[200,128,25,64]{1,3,2,0:T(8,128)(2,1)} "
            "copy(bf16[200,128,25,64]{3,2,1,0:T(8,128)(2,1)} %fusion.20)")
WITH_METADATA = (
    '%dot.7 = f32[16,128]{1,0} dot(f32[16,768]{1,0} %p.1, f32[768,128]{1,0} '
    '%p.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, '
    'metadata={op_name="jit(train_step)/while/body/closed_call/'
    'transpose(jvp(LLM))/loss/dot_general" source_file="gpt.py" '
    'source_line=231}')


def test_scope_path_from_tf_op_from_metadata_and_without_either():
    assert ts.scope_path(
        SCATTER, "jit(step)/decode/LLM/block_8/attn/kv_update/scatter:") \
        == "jit(step)/decode/LLM/block_8/attn/kv_update/scatter"
    path = ts.scope_path(WITH_METADATA)
    assert path.endswith("transpose(jvp(LLM))/loss/dot_general")
    assert ts.owner(path) == "loss"            # backward op, same scope
    assert ts.scope_path(COPY_IN) == ""
    assert ts.owner("") is None


@pytest.mark.parametrize("path, want", [
    ("jit(fused_step)/decode/LLM/block_3/attn/kv_update/scatter",
     "kv_update"),
    ("jit(fused_step)/chunk_prefill/LLM/block_3/attn/c_attn/dot_general",
     "attn"),
    ("jit(step)/decode/LLM/block_22/attn/attn_core/paged_flash_decode/"
     "pallas_call", "attn_core"),
    ("jit(step)/decode/LLM/add", "decode"),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(LLM))/block_1/"
     "mlp/dot_general", "mlp"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/add", None),
    ("caches[8]['v']", None),                  # a program argument's name
])
def test_owner_is_the_innermost_known_name(path, want):
    assert ts.owner(path) == want


def test_every_scope_of_the_program_is_known_here():
    from distributed_pytorch_tpu.obs.trace import SCOPES
    assert set(SCOPES) <= set(ts.SCOPE_NAMES)


def test_layout_copies_inherit_their_owner_by_dataflow():
    """XLA names the pool's layout copies after the program argument, or
    not at all; they take the owner of the scatter they feed / follow.
    The same text in another program is another op."""
    ops = [("in", 1, COPY_IN, "caches[8]['v']"),
           ("scatter", 1, SCATTER,
            "jit(step)/decode/LLM/block_8/attn/kv_update/scatter"),
           ("out", 1, COPY_OUT, ""),
           ("alone", 2, COPY_OUT, "")]
    own = ts.owners(ops)
    assert own["scatter"] == ("kv_update", False)
    assert own["in"] == ("kv_update", True)
    assert own["out"] == ("kv_update", True)
    assert own["alone"] == (ts.UNSCOPED, False)


# ---------------------------------------------------------------------------
# device self time by owner, per whole step
# ---------------------------------------------------------------------------

def test_self_time_by_owner_with_a_while_and_cut_steps():
    ms = 1e6
    modules = [("jit_train_step(1)", 0.0, 5 * ms),        # touches the edge
               ("jit_train_step(1)", 10 * ms, 20 * ms),
               ("jit_convert(2)", 31 * ms, 1 * ms),       # not a step
               ("jit_train_step(1)", 40 * ms, 20 * ms),
               ("jit_train_step(1)", 70 * ms, 10 * ms)]   # touches the edge
    ops = [("fusion.0 fusion f32[8]", 0.0, 5 * ms, "mlp", False)]
    for t0 in (10 * ms, 40 * ms):
        ops += [
            # a while of 12 ms that encloses 10 ms of body ops
            ("while.1 while (f32[8])", t0, 12 * ms, ts.UNSCOPED, False),
            ("fusion.1 fusion f32[8]", t0 + 1 * ms, 6 * ms, "attn_core",
             False),
            ("fusion.2 fusion f32[4]", t0 + 7 * ms, 4 * ms, "mlp", False),
            ("copy.3 copy f32[8]", t0 + 12 * ms, 3 * ms, "loss", True),
            ("fusion.4 fusion f32[2]", t0 + 16 * ms, 4 * ms, "optimizer",
             False)]
    ops.append(("fusion.9 fusion f32[8]", 70 * ms, 10 * ms, "mlp", False))
    lo, hi = 0.0, 80 * ms
    steps = ts.whole_modules(modules, [r"^jit_train_step\("], lo, hi)
    assert [s[1] for s in steps] == [10 * ms, 40 * ms]
    out = ts.self_time_by_owner(ops, steps)
    per_step = {k: (a / 2 / ms, b / 2 / ms)
                for k, (a, b) in out["owners"].items()}
    assert per_step == {ts.UNSCOPED: (2.0, 0.0), "attn_core": (6.0, 0.0),
                        "mlp": (4.0, 0.0), "loss": (0.0, 3.0),
                        "optimizer": (4.0, 0.0)}
    # the scopes and the unscoped remainder are the device's self time
    in_steps = [(o[0], o[1], o[2]) for o in ops
                if any(s[1] <= o[1] and o[1] + o[2] <= s[1] + s[2]
                       for s in steps)]
    assert sum(a + b for a, b in out["owners"].values()) == pytest.approx(
        sum(ns for _, ns in trace_reduce.self_times(in_steps)))
    assert out["families"][("loss", "copy f32[8]")] == 6 * ms
    t = dict(out, steps=len(steps))
    lines = []
    trace_scope_ms.say_table(t, lines.append)
    assert "2 whole step programs, 19.000 ms a step" in lines[0]
    assert any("attn_core" in l and "6.000 ms a step" in l for l in lines)


# ---------------------------------------------------------------------------
# host phases by step
# ---------------------------------------------------------------------------

def _engine_step(step, t0, parts=(2.0, 1.0, 10.0, 3.0), names=ENGINE4):
    evs, t = [], t0
    for name, ms in zip(names, parts):
        evs.append((name, t * 1e6, ms * 1e6, {"step": step}))
        t += ms
    return evs


def test_steps_by_stat_drops_cut_steps_and_keeps_the_later_prepare():
    evs = _engine_step(4, 0.0, names=ENGINE4[2:], parts=(10.0, 3.0))  # cut
    evs += _engine_step(5, 20.0)
    # an engine step that returned after prepare keeps its number
    evs += [("engine.prepare", 37e6, 0.5e6, {"step": 6})]
    evs += _engine_step(6, 40.0)
    evs += _engine_step(7, 60.0, names=ENGINE4[:2], parts=(2.0, 1.0))  # cut
    evs += [("sched.emit", 36e6, 1e6, {"queued": 0, "live": 3}),
            ("engine.admit", 38e6, 1e6, {"chunked": 1})]
    steps = ts.steps_by_stat(evs, ENGINE4)
    assert sorted(steps) == [5, 6]
    assert steps[6]["engine.prepare"] == (40e6, 42e6)
    assert ts.step_extent_ms(steps, ENGINE4[0], ENGINE4[-1]) == [16.0, 16.0]
    assert ts.step_sum_ms(steps, [ENGINE4[0], ENGINE4[1], ENGINE4[3]]) \
        == [6.0, 6.0]
    assert ts.step_turnaround_ms(steps, ENGINE4[-1], ENGINE4[0]) == [4.0]


def test_idle_gap_split_over_two_phases_and_an_unowned_remainder():
    ms = 1e6
    device = [("a", 0.0, 10 * ms), ("b", 20 * ms, 10 * ms),
              ("c", 30 * ms, 5 * ms), ("d", 39 * ms, 1 * ms)]
    gaps = ts.all_gaps(device)
    assert gaps == [(10 * ms, 10 * ms), (35 * ms, 4 * ms)]
    engine = [("engine.wait", 5 * ms, 8 * ms, None),        # 10..13 idle
              ("engine.retire", 13 * ms, 3 * ms, None),     # 13..16 idle
              ("engine.prepare", 38 * ms, 4 * ms, None)]    # 38..39 idle
    # the scheduler's phase overlaps the engine's: only what the engine
    # left (16..18, 35..36) is its own
    sched = [("sched.emit", 12 * ms, 6 * ms, None),
             ("sched.admit", 34 * ms, 2 * ms, None)]
    owned = ts.split_idle(gaps, [engine, sched])
    assert owned == {"engine.wait": 3 * ms, "engine.retire": 3 * ms,
                     "engine.prepare": 1 * ms, "sched.emit": 2 * ms,
                     "sched.admit": 1 * ms, "unowned": 4 * ms}
    assert sum(owned.values()) == sum(d for _, d in gaps)
    # the reader's table: ops as the slice holds them, phases by layer
    sl = {"ops": [(n, s, d, "attn", False) for n, s, d in device],
          "phases": {"python3": engine + sched}}
    assert trace_idle_owner.table(sl, ["engine.", "sched."]) == owned
    assert trace_idle_owner.table(dict(sl, phases={}), ["engine."]) is None
    lines = []
    trace_idle_owner.say_table(owned, lines.append)
    assert "14.000 ms idle" in lines[0] and len(lines) == 7


# ---------------------------------------------------------------------------
# the CPU rehearsal: the program under a capture, the readers on its file
# ---------------------------------------------------------------------------

TINY = {"vocab_size": 1024, "block_size": 64, "n_embd": 64, "n_head": 4,
        "attn": "mha", "n_layer": 2, "up_dim": 256,
        "non_linearity": "gelu", "pos_emb": "learn"}


def _metric(name):
    spec, reader = harness.load_layer_metric(name)
    return reader.read({}, spec.get("args", {}))


@pytest.fixture
def in_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    return tmp_path


def _serve_under_capture(steps_wanted=12):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import DecodeEngine
    from distributed_pytorch_tpu.models.gpt import LLM
    from distributed_pytorch_tpu.serve.scheduler import Scheduler

    model = LLM(LLMConfig(**TINY))
    key = jax.random.PRNGKey(0)
    variables = jax.jit(model.init)({"params": key, "dropout": key},
                                    jnp.zeros((1, 8), jnp.int32))
    engine = DecodeEngine(model, variables, n_slots=3, max_len=64,
                          block_size=8, prefill_chunk=16, temperature=0.0,
                          min_bucket=8)
    rng = np.random.default_rng(0)
    engine.run([rng.integers(0, 1024, 20).tolist()], 4)   # both programs

    async def drive():
        sched = Scheduler(engine, max_queue=16)
        await sched.start()
        trace_reduce.start_trace("trace")
        try:
            handles = [sched.submit(rng.integers(0, 1024, n).tolist(), 10)
                       for n in (9, 20, 30, 12)]
            for h in handles:
                await h.result()
        finally:
            jax.profiler.stop_trace()
            await sched.stop()
    asyncio.run(asyncio.wait_for(drive(), 120))
    return engine


def test_rehearsal_serve_phases_reach_the_span_readers(in_tmp):
    engine = _serve_under_capture()
    sl = ts.load()
    assert sl["ops"] is None and sl["modules"] is None   # no device plane
    names = {e[0] for evs in sl["phases"].values() for e in evs}
    assert set(ENGINE4) | {"engine.admit", "sched.admit",
                           "sched.emit"} <= names
    # leaves: the phases of one layer (one thread) never overlap
    for layer in ("engine.", "sched."):
        evs = sorted(ts.phase_events(sl, layer), key=lambda e: e[1])
        assert all(a[1] + a[2] <= b[1] for a, b in zip(evs, evs[1:])), layer
    steps = ts.steps_by_stat(ts.phase_events(sl, "engine."), ENGINE4)
    assert len(steps) >= 8
    span = _metric("engine_step_span_ms")
    host = _metric("engine_host_ms")
    turn = _metric("sched_turnaround_ms")
    assert 0 < host <= span and turn > 0
    # the flight record is the same step on perf_counter's clock
    flight = {r["step"] - 1: r for r in engine.flight.entries()}
    inside = [k for k in steps if k in flight]
    assert inside
    for k in inside:
        ext = (steps[k]["engine.retire"][1]
               - steps[k]["engine.prepare"][0]) / 1e6
        assert ext == pytest.approx(flight[k]["step_ms"], rel=0.2, abs=0.3)
    for name in ("kv_update_ms.serve", "attn_core_ms.serve",
                 "unscoped_pct.serve", "idle_unowned_pct.serve"):
        assert _metric(name) is None


def test_rehearsal_train_windows_reach_the_span_reader(in_tmp):
    import jax
    from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
    from distributed_pytorch_tpu.train.loop import train

    class _Stop(Exception):
        pass

    boundaries = []

    def log(s):
        if not s.startswith("iter "):
            return
        boundaries.append(s)
        if len(boundaries) == 1:
            trace_reduce.start_trace("trace")
        elif len(boundaries) == 4:          # three log windows later
            jax.profiler.stop_trace()
            raise _Stop()

    cfg = TrainConfig(parallelism="single", batch_size=2,
                      total_batch_size=128, compute_dtype="float32",
                      log_interval=2, max_iters=1000, dataset="synthetic",
                      data_dir=os.path.join(str(in_tmp), "data"),
                      eval=False, save_model=False, save_stats=False,
                      file_name="tiny")
    with pytest.raises(_Stop):
        train(LLMConfig(**TINY), cfg, log=log)
    sl = ts.load()
    evs = ts.phase_events(sl, "train.")
    by_name = {}
    for e in evs:
        by_name.setdefault(e[0], []).append(e)
    assert len(by_name["train.dispatch"]) == 6          # 3 windows of 2
    assert len(by_name["train.data"]) == 6
    assert len(by_name["train.sync"]) == 3
    assert {e[3]["n_steps"] for e in by_name["train.sync"]} == {2}
    steps = [e[3]["step"] for e in by_name["train.dispatch"]]
    assert steps == list(range(steps[0], steps[0] + 6))
    assert _metric("dispatch_ms.train") > 0
    for name in ("attn_core_ms.train", "mlp_ms.train", "loss_ms.train",
                 "optimizer_ms.train"):
        assert _metric(name) is None


def test_the_wire_reader_and_profile_data_agree(in_tmp):
    """Same events, same clock: `read_xspace` against the
    `jax.profiler.ProfileData` reader of trace_reduce."""
    _serve_under_capture()
    path = trace_reduce.find_xplane("trace")
    planes = trace_reduce.load_planes(path)
    space = ts.read_xspace(path)
    assert set(space) == set(planes)
    for pname, lines in planes.items():
        for lname, evs in lines.items():
            mine = space[pname]["lines"][lname]
            assert len(mine) == len(evs)
            meta = space[pname]["meta"]
            a = sorted((n, s, d) for n, s, d in evs)
            b = sorted((meta[m][0], s, d) for m, s, d, _ in mine)
            for (n1, s1, d1), (n2, s2, d2) in zip(a, b):
                assert n1 == n2 and abs(s1 - s2) <= 1 and abs(d1 - d2) <= 1
