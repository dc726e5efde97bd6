"""Turns and stalls of the always-on step ring (obs/flight.py): what a
record gains (the caller's gap, the collector's pauses, the thread's CPU
time and where it stood off the CPU), which turns are judged stalled among
the turns of their kind, and who owns each injected fault on a small CPU
engine."""

import gc
import statistics
import threading
import time

import jax
import pytest

from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.engine import decode as decode_mod
from distributed_pytorch_tpu.obs import flight
from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.serve.metrics import ServeMetrics
from test_engine import build, tiny_cfg

PHASES = {"prepare": 1.0, "dispatch": 1.0, "wait": 7.0, "retire": 1.0}


@pytest.fixture(autouse=True)
def fresh_log():
    """The log and totals are the process's: each test starts them empty,
    and leaves them empty for the file that shares its worker next (a
    scripted stall of no kind stood in `/debug/timeline` of
    tests/test_trace_e2e.py whenever that file followed this one)."""
    def clear():
        with flight._lock:
            flight._stalls.clear()
            flight._totals.clear()
    clear()
    yield
    clear()


class Clock:
    """`time` as obs/flight.py sees it, moved by hand (seconds)."""

    def __init__(self):
        self.now = 100.0
        self.cpu = 0.0
        # what `flight._stood` reads: seconds on a run queue, seconds
        # stolen from the machine, involuntary switches
        self.delay, self.steal, self.nivcsw = 5.0, 6421.69, 40

    def perf_counter(self):
        return self.now

    def thread_time(self):
        return self.cpu

    def stood(self):
        return self.delay, self.steal, self.nivcsw

    monotonic = time = perf_counter


def _turn(fl, clock, ms, *, gap_ms=0.0, cpu_ms=0.0, source="engine",
          compiled=False, phases=PHASES, waited=True, kind=None,
          delay_ms=0.0, steal_ms=0.0, **fields):
    """One scripted engine turn of `gap_ms` + `ms`."""
    clock.now += gap_ms / 1e3
    fl.begin_turn(waited)
    clock.now += ms / 1e3
    clock.cpu += cpu_ms / 1e3
    clock.delay += delay_ms / 1e3
    clock.steal += steal_ms / 1e3
    clock.nivcsw += bool(delay_ms)
    fl.record_turn(source, phases, clock.now, kind=kind, compiled=compiled,
                   step_ms=ms, **fields)
    return fl.entries()[-1]


@pytest.fixture()
def scripted(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(flight, "time", clock)
    monkeypatch.setattr(flight, "_stood", clock.stood)
    return FlightRecorder(capacity=64), clock


# ---- which turns are stalled ---------------------------------------------

@pytest.mark.parametrize("kind", ["decode", "fused"])
@pytest.mark.parametrize("factor, stalled", [(1.9, False), (2.9, False),
                                             (3.2, True), (40.0, True)])
def test_a_long_turn_is_flagged_and_a_chunk_carrying_one_never(
        scripted, factor, stalled, kind):
    """Plain programs of 10 ms with every other one a chunk-carrying
    program of 2.9x (Laguna's 10.134 / 29.208 ms, ledger PR 56): each kind
    has its own median, and only a turn over 3x the median of ITS kind is
    booked, with its excess over that median."""
    fl, clock = scripted
    medians = {"decode": 10.0, "fused": 29.0}
    for i in range(160):
        k = "fused" if i % 2 else "decode"
        rec = _turn(fl, clock, medians[k], kind=k)
        assert "cause" not in rec and rec["kind"] == k
    rec = _turn(fl, clock, medians[kind] * factor, kind=kind)
    assert ("cause" in rec) == stalled
    assert len(flight.stall_log()) == int(stalled)
    if stalled:
        assert rec["median_ms"] == medians[kind] and rec["kind"] == kind
        assert rec["excess_ms"] == pytest.approx(
            medians[kind] * (factor - 1))
        assert flight.stall_log()[-1] is rec and rec["source"] == "engine"
    tot = flight.stall_totals()["sources"]["engine"]
    assert tot["turns"] == 161
    assert sum(c["count"] for c in tot["causes"].values()) == int(stalled)
    assert {k: v["median_ms"] for k, v in tot["kinds"].items()} == medians
    assert sum(v["turns"] for v in tot["kinds"].values()) == 161


#: (plain ms, chunk-carrying ms from .. to) of the ledger's two-program
#: loops: Laguna (PR 56: 10.134 / 29.208, far-offset chunks to 48) and the
#: long-context cell PR 53 was refused with (15.172 / 64.864 = 4.3x)
LOOPS = {"laguna_2.9x": (10.0, 30.0, 48.0), "pr53_4.3x": (15.0, 65.0, 65.0)}


def _two_program_loop(fl, clock, shape, turns=1000):
    plain, lo, hi = LOOPS[shape]
    for i in range(turns):
        if i % 4 == 3:      # a chunk-carrying program every fourth
            ms, k = lo + (hi - lo) * ((i // 4) % 7) / 6.0, "fused"
        else:
            ms, k = plain, "decode"
        assert "cause" not in _turn(fl, clock, ms, kind=k)
    return statistics.median(
        lo + (hi - lo) * j / 6.0 for j in range(7)) if hi > lo else lo


@pytest.mark.parametrize("kind", ["decode", "fused"])
@pytest.mark.parametrize("shape", list(LOOPS))
def test_a_loop_of_two_programs_books_the_stop_alone(scripted, shape, kind):
    """1,000 turns of a loop whose chunk-carrying program is 2.9-4.8x or
    4.3x its plain one book nothing (one median over both booked 16-21% of
    their seconds: ledger, PR 53 and Laguna before PR 50), and a stop of
    120 ms in a turn of either kind is booked with its excess over the
    median of its own kind."""
    fl, clock = scripted
    fused = _two_program_loop(fl, clock, shape)
    assert not flight.stall_log()
    median = {"decode": LOOPS[shape][0], "fused": fused}[kind]
    rec = _turn(fl, clock, median + 120.0, kind=kind)
    if median + 120.0 > flight.STALL_FACTOR * median:
        assert rec["kind"] == kind and rec["median_ms"] == median
        assert rec["excess_ms"] == pytest.approx(120.0)
        assert flight.stall_log() == [rec]
    else:
        # a 65 ms program with the stop inside it is 2.85x: under the
        # factor, as any turn of one kind of that length
        assert (shape, kind) == ("pr53_4.3x", "fused")
        assert "cause" not in rec
        assert _turn(fl, clock, 3.1 * median, kind=kind)["median_ms"] \
            == median
    by = flight.stall_totals()["sources"]["engine"]["kinds"]
    assert by["decode"]["turns"] + by["fused"]["turns"] == 1001 + (
        "cause" not in rec)
    assert by["decode"]["median_ms"] == LOOPS[shape][0]


def test_no_median_no_verdict_and_a_stall_does_not_feed_it(scripted):
    fl, clock = scripted
    for _ in range(flight.MIN_TURNS - 1):
        assert "cause" not in _turn(fl, clock, 10.0)
    assert "cause" not in _turn(fl, clock, 500.0)     # too young to judge
    for _ in range(flight.MIN_TURNS):
        _turn(fl, clock, 10.0)
    for _ in range(flight.REGIME_TURNS - 1):
        assert _turn(fl, clock, 100.0)["median_ms"] == 10.0


def test_a_kind_is_young_on_its_own(scripted):
    """A kind's first `MIN_TURNS` turns get no verdict, however old the
    recorder is by its other kind's turns; from then on they do."""
    fl, clock = scripted
    for _ in range(200):
        _turn(fl, clock, 10.0, kind="decode")
    for _ in range(flight.MIN_TURNS - 1):
        assert "cause" not in _turn(fl, clock, 30.0, kind="fused")
    assert "cause" not in _turn(fl, clock, 500.0, kind="fused")
    assert _turn(fl, clock, 500.0, kind="decode")["median_ms"] == 10.0
    assert "cause" not in _turn(fl, clock, 30.0, kind="fused")
    rec = _turn(fl, clock, 500.0, kind="fused")
    assert (rec["kind"], rec["median_ms"]) == ("fused", 30.0)
    assert flight.stall_totals()["sources"]["engine"]["kinds"]["fused"] == {
        "turns": flight.MIN_TURNS + 2, "median_ms": 30.0,
        "turn_seconds": pytest.approx(
            (flight.MIN_TURNS * 30.0 + 1000.0) / 1e3)}


def test_another_load_of_one_kind_leaves_the_others_median_alone(scripted):
    """`REGIME_TURNS` long turns in a row of ONE kind (longer chunks from
    here on) relearn that kind's median; the plain programs between them
    neither break the row nor lose their own."""
    fl, clock = scripted
    for chunk_ms, turns in ((30.0, 280), (100.0, 400)):
        for i in range(turns):
            if i % 4 == 3:
                _turn(fl, clock, chunk_ms, kind="fused")
            else:
                _turn(fl, clock, 10.0, kind="decode")
    log = flight.stall_log()
    assert len(log) == flight.REGIME_TURNS
    assert {s["kind"] for s in log} == {"fused"}
    assert _turn(fl, clock, 400.0, kind="fused")["median_ms"] == 100.0
    assert _turn(fl, clock, 31.0, kind="decode")["median_ms"] == 10.0


def test_another_load_is_learnt_anew(scripted):
    """Turns that all run long are no stalls but another load: after
    REGIME_TURNS in a row the median is taken from the turns that follow,
    and the log does not fill with them."""
    fl, clock = scripted
    for _ in range(70):
        _turn(fl, clock, 10.0)
    for _ in range(200):
        _turn(fl, clock, 50.0)
    assert len(flight.stall_log()) == flight.REGIME_TURNS
    assert "cause" not in fl.entries()[-1]
    assert _turn(fl, clock, 400.0)["median_ms"] == 50.0


@pytest.mark.parametrize("case, want", [
    (dict(compiled=True, gc=300.0, capturing=True), ("dispatch", "compile")),
    (dict(capturing=True, gc=300.0), ("dispatch", "capture")),
    (dict(gc=160.0), ("dispatch", "gc")),
    (dict(gc=100.0, cpu_ms=250.0), ("dispatch", "host_busy")),
    (dict(cpu_ms=20.0), ("dispatch", "blocked")),
    (dict(cpu_ms=90.0), ("dispatch", "mixed")),
    (dict(gap=True, cpu_ms=1.0), ("gap", "caller")),
    (dict(gap=True, gc=160.0), ("gap", "gc")),
    # where the thread stood: on a run queue, or the machine's CPUs stolen
    (dict(cpu_ms=20.0, delay_ms=150.0), ("dispatch", "descheduled")),
    (dict(cpu_ms=20.0, steal_ms=150.0), ("dispatch", "descheduled")),
    (dict(cpu_ms=20.0, delay_ms=80.0, steal_ms=70.0),
     ("dispatch", "descheduled")),
    (dict(cpu_ms=20.0, delay_ms=70.0, steal_ms=70.0),
     ("dispatch", "blocked")),
    (dict(cpu_ms=250.0, delay_ms=40.0), ("dispatch", "host_busy")),
    (dict(gc=160.0, delay_ms=200.0), ("dispatch", "gc")),
    (dict(gap=True, delay_ms=290.0), ("gap", "descheduled")),
    (dict(capturing=True, delay_ms=290.0), ("dispatch", "capture")),
], ids=lambda v: None if isinstance(v, tuple) else "-".join(v))
def test_cause_is_the_first_that_holds(scripted, monkeypatch, case, want):
    """A 310 ms turn against a median of 10: excess 300."""
    fl, clock = scripted
    for _ in range(flight.MIN_TURNS):
        _turn(fl, clock, 10.0)
    if case.get("capturing"):
        monkeypatch.setattr(flight.TraceAnnotation, "is_enabled",
                            staticmethod(lambda: True))
    if "gc" in case:
        seconds = list(flight._gc_seconds)
        seconds[2] += case["gc"] / 1e3
        pauses = list(flight._gc_pauses)
        pauses[2] += 1
        monkeypatch.setattr(flight, "_gc_seconds", seconds)
        monkeypatch.setattr(flight, "_gc_pauses", pauses)
    long = dict(PHASES, dispatch=301.0)
    stood = {k: case.get(k, 0.0) for k in ("cpu_ms", "delay_ms", "steal_ms")}
    if case.get("gap"):
        rec = _turn(fl, clock, 10.0, gap_ms=300.0, **stood)
    else:
        rec = _turn(fl, clock, 310.0, phases=long, **stood,
                    compiled=case.get("compiled", False))
    assert (rec["owner"], rec["cause"]) == want
    assert rec["excess_ms"] == pytest.approx(300.0)
    assert rec["sched_delay_ms"] == pytest.approx(stood["delay_ms"])
    assert rec["steal_ms"] == pytest.approx(stood["steal_ms"])
    assert rec["nivcsw"] == bool(stood["delay_ms"])
    if "gc" in case:
        assert rec["gc_ms"] == pytest.approx(case["gc"])
        assert rec["gc_gen"] == 2
    tot = flight.stall_totals()["sources"]["engine"]
    assert tot["causes"][want[1]] == {
        "count": 1, "excess_seconds": pytest.approx(0.3),
        "longest_ms": pytest.approx(300.0)}
    assert tot["sched_delay_seconds"] == pytest.approx(
        stood["delay_ms"] / 1e3)
    assert tot["steal_seconds"] == pytest.approx(stood["steal_ms"] / 1e3)


def test_a_turn_under_a_capture_from_end_to_end_is_judged_as_any(
        scripted, monkeypatch):
    """`capture` is the capture's start and stop (each takes seconds). A
    stop in the middle of a traced slice has another owner: the record says
    `capturing`, the cause is what the turn's own fields say."""
    fl, clock = scripted
    for _ in range(flight.MIN_TURNS):
        _turn(fl, clock, 10.0)
    monkeypatch.setattr(flight.TraceAnnotation, "is_enabled",
                        staticmethod(lambda: True))
    first = _turn(fl, clock, 130.0)             # it came on in this turn
    assert (first["capturing"], first["cause"]) == (True, "capture")
    assert "cause" not in _turn(fl, clock, 10.0)
    rec = _turn(fl, clock, 130.0, delay_ms=118.0)
    assert (rec["capturing"], rec["cause"]) == (True, "descheduled")
    rec = _turn(fl, clock, 130.0, phases=dict(PHASES, wait=127.0))
    assert (rec["capturing"], rec["owner"], rec["cause"]) == (
        True, "wait", "blocked")


def test_a_platform_without_the_counts_books_no_field(monkeypatch, tmp_path):
    """No `/proc/thread-self/schedstat`, no `/proc/stat`, no
    `RUSAGE_THREAD`: a record has none of the three fields, a stalled turn
    is judged by the rest, and nothing raises. Where the files are there
    (Linux) the real reads give numbers that never shrink."""
    real = flight._stood()
    again = flight._stood()
    for a, b in zip(real, again):
        assert (a is None) == (b is None) and (a is None or b >= a >= 0)
    monkeypatch.setattr(flight, "_tls", threading.local())
    monkeypatch.setattr(flight, "_proc_stat", None)
    monkeypatch.setattr(flight, "_SCHEDSTAT", str(tmp_path / "none"))
    monkeypatch.setattr(flight, "_PROC_STAT", str(tmp_path / "none"))
    monkeypatch.setattr(flight, "resource", None)
    assert flight._stood() == (None, None, None)
    # a file that is there and holds something else is no count either,
    # nor is the `cpu` line of a sandboxed kernel that counts nothing (the
    # benchmark's chip machines): zeros from end to end
    for text in ("cpu  1 2 3\n", "cpu  0 0 0 0 0 0 0 0 0 0\ncpu0 0 0\n"):
        (tmp_path / "stat").write_text(text)
        monkeypatch.setattr(flight, "_proc_stat", None)
        monkeypatch.setattr(flight, "_PROC_STAT", str(tmp_path / "stat"))
        assert flight._stood() == (None, None, None)
    assert flight._proc_stat is False           # not read again
    (tmp_path / "stat").write_text("cpu  5 0 7 900 1 0 2 300 0 0\n")
    monkeypatch.setattr(flight, "_proc_stat", None)
    assert flight._stood() == (None, 300 / flight._TICKS_PER_S, None)
    monkeypatch.setattr(flight, "_proc_stat", None)
    monkeypatch.setattr(flight, "_PROC_STAT", str(tmp_path / "none"))
    fl = FlightRecorder(capacity=64)
    for _ in range(flight.MIN_TURNS + 1):
        fl.begin_turn(True)
        time.sleep(0.002)
        fl.record_turn("engine", PHASES, time.perf_counter(), kind="decode")
    fl.begin_turn(True)
    time.sleep(0.05)
    fl.record_turn("engine", dict(PHASES, wait=50.0), time.perf_counter(),
                   kind="decode")
    rec = fl.entries()[-1]
    assert (rec["owner"], rec["cause"]) == ("wait", "blocked")
    for r in fl.entries():
        assert not {"sched_delay_ms", "steal_ms", "nivcsw"} & set(r)
    tot = flight.stall_totals()["sources"]["engine"]
    assert tot["sched_delay_seconds"] == tot["steal_seconds"] == 0.0


@pytest.mark.parametrize("waited, inside, want", [
    (True, False, "capture"), (True, True, "capture"), (False, False, None)],
    ids=["in_the_gap", "inside_the_turn_before", "nobody_waited"])
def test_a_capture_stopped_in_the_gap_is_the_turns(scripted, monkeypatch,
                                                   waited, inside, want):
    """Writing a capture out takes seconds, after the TraceMes are off: the
    turn whose gap held the stop began while it ran, so it is the
    capture's, not the caller's; so is the turn after one INSIDE which
    another thread stopped it (the benchmark's event loop does, while the
    engine's thread is in `step()`), and no turn after that. With no work
    waiting the gap is nobody's and the next turn begins after it."""
    fl, clock = scripted
    for _ in range(flight.MIN_TURNS):
        _turn(fl, clock, 10.0)
    on = [True]
    monkeypatch.setattr(flight.TraceAnnotation, "is_enabled",
                        staticmethod(lambda: on[0]))
    assert _turn(fl, clock, 10.0)["capturing"] is True
    if inside:
        clock.now += 0.0005
        fl.begin_turn(True)
        on[0] = False               # stopped between entry and record
        clock.now += 0.01
        fl.record_turn("engine", PHASES, clock.now, step_ms=10.0)
        assert fl.entries()[-1]["capturing"] is True
    on[0] = False
    rec = _turn(fl, clock, 10.0, gap_ms=9000.0, waited=waited)
    assert rec.get("cause") == want and rec["capturing"] is waited
    assert ("gap_ms" in rec) == waited
    after = _turn(fl, clock, 10.0, gap_ms=0.5)
    assert after["capturing"] is False and "cause" not in after


def test_the_log_survives_the_ring(scripted):
    """5,000 later records evict the stalled turn from the ring of 64 and
    not from the process's log; sources keep their own totals."""
    fl, clock = scripted
    for _ in range(flight.MIN_TURNS):
        _turn(fl, clock, 10.0)
    stall = _turn(fl, clock, 200.0, step=-7)
    other = FlightRecorder(capacity=8)
    for _ in range(5000):           # two writers, neither waits
        _turn(fl, clock, 10.0, waited=False)
        _turn(other, clock, 1010.0, source="train", waited=False)
    assert fl.dropped > 4000 and stall not in fl.entries()
    assert [s["step"] for s in flight.stall_log()] == [-7]
    assert flight.stall_log()[-1]["excess_ms"] == pytest.approx(190.0)
    totals = flight.stall_totals()["sources"]
    assert totals["engine"]["turns"] == 5001 + flight.MIN_TURNS
    assert totals["train"]["turns"] == 5000 and not totals["train"]["causes"]
    assert totals["train"]["turn_seconds"] == pytest.approx(
        5000 * 1.01, rel=1e-6)


def test_a_disabled_recorder_books_nothing():
    fl = FlightRecorder(enabled=False)
    fl.begin_turn()
    fl.record_turn("engine", PHASES, time.perf_counter(), compiled=True)
    assert not len(fl) and not flight.stall_log()
    assert not flight.stall_totals()["sources"]


# ---- the collector's pauses ------------------------------------------------

def test_host_gc_opens_and_closes_on_one_thread(monkeypatch):
    """Every pause is one `host.gc` annotation, entered and left on the
    thread that triggered the collection, and its seconds are booked under
    its generation."""
    seen = []

    class Ann:
        is_enabled = staticmethod(lambda: True)

        def __init__(self, name, **stats):
            self.what = (name, stats["generation"])

        def __enter__(self):
            seen.append(("open", threading.get_ident(), self.what))

        def __exit__(self, *exc):
            seen.append(("close", threading.get_ident(), self.what))

    FlightRecorder()                        # installs the hook, once
    assert gc.callbacks.count(flight._on_gc) == 1
    FlightRecorder()
    assert gc.callbacks.count(flight._on_gc) == 1
    monkeypatch.setattr(flight, "TraceAnnotation", Ann)
    before = flight.stall_totals()

    def collect():
        for gen in (0, 1, 2):
            gc.collect(gen)

    # one after the other: a collection asked for while another thread's
    # runs is skipped by the collector itself
    for _ in range(4):
        th = threading.Thread(target=collect)
        th.start()
        th.join(timeout=60)
        assert not th.is_alive()
    monkeypatch.undo()
    mine = [s for s in seen if s[1] != threading.get_ident()]
    assert len(mine) >= 24
    for a, b in zip(seen[::2], seen[1::2]):
        assert (a[0], b[0]) == ("open", "close") and a[1:] == b[1:]
        assert a[2][0] == "host.gc"
    after = flight.stall_totals()
    for gen in (0, 1, 2):
        assert after["gc_pauses"][gen] >= before["gc_pauses"][gen] + 4
        assert after["gc_pause_seconds"][gen] > \
            before["gc_pause_seconds"][gen]


# ---- injected faults on a small engine ------------------------------------

FAULT_S = 0.25


def _engine():
    model, variables = build(tiny_cfg())
    return DecodeEngine(model, variables, n_slots=3, temperature=0.0,
                        prefill_chunk=16, block_size=16)


def _warm(eng, steps=24):
    for p in ([1, 2, 3, 4, 5], [7] * 9):
        eng.admit(p, 60)
    for _ in range(steps):
        eng.step()


def _stall_of(rec):
    assert rec.get("cause"), f"the turn was not booked: {rec}"
    assert rec in flight.stall_log()
    assert rec["excess_ms"] > 0.8 * FAULT_S * 1e3
    return rec["owner"], rec["cause"]


def _fault_gc(eng, monkeypatch):
    # a large cyclic heap made with the collector off, collected in the
    # gap: the pause is the caller's time and the collector's doing
    gc.disable()
    try:
        junk = [[] for _ in range(400_000)]
        for j in junk:
            j.append(j)
        eng.step()          # the making is this turn's (a `caller` stall)
        del junk, j
        t0 = time.perf_counter()
        gc.collect()
        pause = time.perf_counter() - t0
    finally:
        gc.enable()
    eng.step()
    rec = eng.flight.entries()[-1]
    assert rec["gc_gen"] == 2
    assert rec["gc_ms"] == pytest.approx(pause * 1e3, rel=0.2)
    assert rec["excess_ms"] > 0.5 * pause * 1e3
    assert (rec["owner"], rec["cause"]) == ("gap", "gc")
    return None


def _fault_caller(eng, monkeypatch):
    time.sleep(FAULT_S)
    eng.step()
    rec = eng.flight.entries()[-1]
    assert rec["gap_ms"] == pytest.approx(FAULT_S * 1e3, rel=0.2)
    return rec


def _fault_host_busy(eng, monkeypatch):
    plan = eng._plan

    def busy(*a, **kw):
        t_end = time.thread_time() + FAULT_S
        while time.thread_time() < t_end:
            pass
        return plan(*a, **kw)

    monkeypatch.setattr(eng, "_plan", busy)
    eng.step()
    monkeypatch.undo()
    rec = eng.flight.entries()[-1]
    assert rec["cpu_ms"] > 0.9 * FAULT_S * 1e3
    return rec


def _fault_blocked(eng, monkeypatch):
    get = jax.device_get

    def slow(x):
        time.sleep(FAULT_S)
        return get(x)

    monkeypatch.setattr(decode_mod.jax, "device_get", slow)
    eng.step()
    monkeypatch.undo()
    rec = eng.flight.entries()[-1]
    assert rec["cpu_ms"] < 0.1 * FAULT_S * 1e3
    return rec


@pytest.mark.parametrize("fault, want", [
    (_fault_gc, None),
    (_fault_caller, ("gap", "caller")),
    (_fault_host_busy, ("prepare", "host_busy")),
    (_fault_blocked, ("wait", "blocked")),
], ids=["gc", "caller", "host_busy", "blocked"])
def test_an_injected_fault_names_its_owner(monkeypatch, fault, want):
    eng = _engine()
    _warm(eng)
    rec = fault(eng, monkeypatch)
    if want is not None:
        assert _stall_of(rec) == want
        assert rec["median_ms"] < 0.2 * FAULT_S * 1e3


def test_an_engines_records_carry_their_programs_kind():
    """The engine hands the drained program's kind: a chunk-carrying
    program's record says `fused`, a plain one's `decode`, the stalled
    ones among them (the first call compiles) too, and the totals
    count the turns kind by kind; on Linux every record also says where
    the thread stood."""
    eng = _engine()
    _warm(eng)
    recs = eng.flight.entries()
    assert {r["kind"] for r in recs} == {"decode", "fused"}
    for r in recs:
        assert (r["kind"] == "fused") == (r["prefill_tokens"] > 0)
    log = flight.stall_log()
    assert log and log[0]["cause"] == "compile"
    assert all(s["kind"] in ("decode", "fused") for s in log)
    by = flight.stall_totals()["sources"]["engine"]["kinds"]
    assert {k: v["turns"] for k, v in by.items()} == {
        k: sum(r["kind"] == k for r in recs) for k in ("decode", "fused")}
    if flight._stood() != (None, None, None):
        for r in recs:
            assert r["sched_delay_ms"] >= 0 and r["steal_ms"] >= 0
            assert r["nivcsw"] >= 0


def test_a_first_call_that_traces_is_a_compile_and_idle_time_no_gap():
    """The first step of an engine traces its program: booked as `compile`
    whatever the median. A sleep while NO slot is live is nobody's gap:
    the next record has no `gap_ms` and is not booked for it."""
    eng = _engine()
    eng.admit([1, 2, 3, 4, 5], 30)
    eng.step()
    first = eng.flight.entries()[0]
    assert first["cause"] == "compile" and "gap_ms" not in first
    assert first["owner"] in ("prepare", "dispatch")
    assert first in flight.stall_log()
    while eng.n_live:
        eng.step()
    n = eng.flight.total
    time.sleep(FAULT_S)
    eng.admit([3, 4, 5, 6], 8)
    eng.step()
    rec = eng.flight.entries()[n - eng.flight.total]
    assert "gap_ms" not in rec and rec["turn_ms"] < 0.5 * FAULT_S * 1e3
    assert rec.get("cause") in (None, "compile", "blocked", "mixed",
                                "host_busy")
    eng.step()
    assert "gap_ms" in eng.flight.entries()[-1]


def test_turns_tile_the_wall_time():
    """`gap_ms + step_ms` of a record is its turn: the turn of a record
    that waited begins where the last one ended, so consecutive records
    tile the time between their stamps."""
    eng = _engine()
    _warm(eng, steps=40)
    recs = [r for r in eng.flight.entries() if "gap_ms" in r]
    assert len(recs) >= 30
    for r in recs:
        assert r["gap_ms"] + r["step_ms"] == pytest.approx(r["turn_ms"],
                                                           abs=2e-3)
        for key in ("t0", "gc_ms", "cpu_ms", "capturing"):
            assert key in r
        assert r["capturing"] is False and r["cpu_ms"] <= r["turn_ms"] + 1
    all_recs = eng.flight.entries()
    for a, b in zip(all_recs, all_recs[1:]):
        assert "gap_ms" in b
        assert b["t0"] - a["t0"] == pytest.approx(a["turn_ms"] / 1e3,
                                                  rel=0.01, abs=5e-6)
    wall = all_recs[-1]["t0"] - all_recs[0]["t0"]
    assert sum(r["turn_ms"] for r in all_recs[:-1]) / 1e3 == \
        pytest.approx(wall, rel=0.01)


# ---- where an operator reads it -------------------------------------------

def test_metric_families_render_the_totals(scripted):
    fl, clock = scripted
    for _ in range(flight.MIN_TURNS):
        _turn(fl, clock, 10.0)
    _turn(fl, clock, 10.0, gap_ms=190.0)
    _turn(fl, clock, 10.0, delay_ms=150.0, steal_ms=30.0)
    metrics = ServeMetrics()
    for name, family in flight.metric_families(
            "engine", "serve_engine", "serve_host").items():
        metrics.register_family(name, *family)
    text = metrics.render_prometheus()
    assert "# TYPE serve_engine_stalls_total counter" in text
    assert 'serve_engine_stalls_total{cause="caller"} 1' in text
    line = next(ln for ln in text.splitlines() if ln.startswith(
        'serve_engine_stall_seconds_total{cause="caller"}'))
    assert float(line.split()[-1]) == pytest.approx(0.19)
    for gen in "012":
        assert f'serve_host_gc_pause_seconds_total{{generation="{gen}"}}' \
            in text
    assert "# TYPE serve_host_sched_delay_seconds_total counter" in text
    for reason, want in (("run_queue", 0.15), ("steal", 0.03)):
        line = next(ln for ln in text.splitlines() if ln.startswith(
            f'serve_host_sched_delay_seconds_total{{reason="{reason}"}}'))
        assert float(line.split()[-1]) == pytest.approx(want)
