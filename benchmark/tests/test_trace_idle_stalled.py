"""The ring's stalled turns laid on the device trace
(`readers/trace_idle_stalled_pct.py`): a stalled turn's extent from its
call's phases on a hand-made slice, the idle gaps split over the extents by
cause, what is left out, and on the CPU rehearsal (the program under a real
capture) every record's extent against its own `turn_ms`."""

import pytest

from benchmark.lib import stats
from benchmark.lib import trace_spans as ts
from benchmark.readers import trace_idle_stalled_pct as reader
from benchmark.tests.test_trace_spans import (ENGINE4, _engine_step,
                                              _serve_under_capture, in_tmp)
from distributed_pytorch_tpu.obs import flight

MS = 1e6


def _rec(step, cause, turn_ms, gap_ms=None, source="engine"):
    rec = {"source": source, "step": step, "cause": cause, "kind": "decode",
           "turn_ms": turn_ms, "excess_ms": turn_ms - 16.0,
           "median_ms": 16.0, "owner": "wait", "cpu_ms": 0.0,
           "t0": stats.T_PROCESS_START + 40.0 + step}
    if gap_ms is not None:
        rec["gap_ms"] = gap_ms
    return rec


def _slice():
    """Calls of 16 ms (prepare 2, dispatch 1, wait 10, retire 3) for
    programs 4..9, a caller's gap of 4 ms between them; the call of program
    6 waits 100 ms, and the gap before program 8's call is 124 ms."""
    evs, t = [], 0.0
    evs += _engine_step(4, t, names=ENGINE4[2:], parts=(10.0, 3.0))  # cut
    t = 13.0 + 4.0
    for step in (5, 6, 7, 8, 9):
        if step == 8:
            t += 120.0
        parts = (2.0, 1.0, 100.0 if step == 6 else 10.0, 3.0)
        evs += _engine_step(step, t, parts=parts)
        t += sum(parts) + 4.0
    return evs


def test_a_stalled_turns_extent_is_its_calls_phases():
    evs = _slice()
    steps = ts.steps_by_stat(evs, ENGINE4)
    assert steps[6]["engine.prepare"][0] == 37 * MS
    log = [
        # program 6: its turn began at program 5's retire (33), 4 ms of gap
        _rec(7, "blocked", 110.0, gap_ms=4.0),
        # program 8: 124 ms of gap before a plain call
        _rec(9, "descheduled", 140.0, gap_ms=124.0),
        # program 5's turn began at program 4's retire, which the slice has
        _rec(6, "gc", 20.0, gap_ms=4.0),
        # no work waited: the turn begins at its own prepare
        _rec(8, "capture", 16.0),
        # phases the slice's edge cut, a number the slice does not hold, a
        # number of another engine's (the extent is not the turn)
        _rec(5, "blocked", 16.0, gap_ms=3.0),
        _rec(40, "blocked", 100.0, gap_ms=4.0),
        _rec(10, "blocked", 900.0, gap_ms=4.0),
    ]
    got = reader.extents(evs, log, "engine.")
    assert got == [("blocked", 33 * MS, 110 * MS),
                   ("descheduled", 163 * MS, 140 * MS),
                   ("gc", 13 * MS, 20 * MS),
                   ("capture", 147 * MS, 16 * MS)]
    # the first call of the slice: nothing before it to begin at
    first = _engine_step(5, 17.0)
    assert reader.extents(first, [_rec(6, "blocked", 20.0, gap_ms=4.0)],
                          "engine.") == []
    assert reader.extents(first, [_rec(6, "blocked", 16.0)], "engine.") \
        == [("blocked", 17 * MS, 16 * MS)]


def test_idle_time_splits_into_stalled_turns_by_cause_and_outside():
    evs = _slice()
    # the device: busy but for 50..130 (inside program 6's turn, 33..143),
    # 150..200 (half inside program 8's turn, which begins at 163: 37 ms
    # of it) and 310..312 (outside any stalled turn)
    device = [("a", 0.0, 50 * MS), ("b", 130 * MS, 20 * MS),
              ("c", 200 * MS, 110 * MS), ("d", 312 * MS, 8 * MS)]
    sl = {"ops": [(n, s, d, "attn", False) for n, s, d in device],
          "phases": {"python3": evs}}
    log = [_rec(7, "blocked", 110.0, gap_ms=4.0),
           _rec(9, "descheduled", 140.0, gap_ms=124.0)]
    owned = reader.table(sl, log, "engine.")
    assert owned == {"blocked": 80 * MS, "descheduled": 37 * MS,
                     "outside": 15 * MS, "left out": 0.0}
    # a turn of the capture's own is left out, of both sides of the share
    captured = [dict(log[0], cause="capture"), log[1]]
    owned = reader.table(sl, captured, "engine.")
    assert owned == {"descheduled": 37 * MS, "outside": 15 * MS,
                     "left out": 80 * MS}
    assert reader.table(dict(sl, ops=None), log, "engine.") is None
    assert reader.table(dict(sl, phases={}), log, "engine.") is None


@pytest.fixture()
def sliced(monkeypatch):
    evs = _slice()
    device = [("a", 0.0, 50 * MS), ("b", 130 * MS, 20 * MS),
              ("c", 200 * MS, 110 * MS), ("d", 312 * MS, 8 * MS)]
    sl = {"ops": [(n, s, d, "attn", False) for n, s, d in device],
          "phases": {"python3": evs}}
    monkeypatch.setattr(ts, "load", lambda: sl)
    totals = {"sources": {"engine": {"turns": 9, "kinds": {
        "decode": {"turns": 7, "turn_seconds": 0.35, "median_ms": 16.0},
        "fused": {"turns": 2, "turn_seconds": 0.1, "median_ms": None}}}}}
    monkeypatch.setattr(flight, "stall_totals", lambda: totals)
    return sl


ARGS = {"source": "engine", "layer": "engine."}


def test_the_value_and_what_it_says(sliced, monkeypatch, capsys):
    log = [_rec(7, "blocked", 110.0, gap_ms=4.0),
           _rec(9, "descheduled", 140.0, gap_ms=124.0),
           _rec(9, "blocked", 140.0, gap_ms=124.0, source="train")]
    monkeypatch.setattr(flight, "stall_log", lambda: log)
    got = reader.read({"counters": {}}, ARGS)
    assert got == pytest.approx(100.0 * 117.0 / 132.0)
    out = capsys.readouterr().out.splitlines()
    # what the ring judged by: the stalls kept with their kind, its median
    # and where the thread stood (None: the platform keeps no such count),
    # then the kinds
    assert out[0] == (
        "[bench +  47.00s] stall 94.0 ms engine kind decode (its median "
        "16.0) owner wait cause blocked: sched_delay_ms None steal_ms None "
        "nivcsw None cpu_ms 0.0 of turn_ms 110.0")
    assert out[1].startswith("[bench +  49.00s] stall 124.0 ms engine kind")
    assert out[2] == (
        "[bench] engine turns by kind: decode 7 turns, 0.35 s, running "
        "median 16.0 ms | fused 2 turns, 0.10 s, running median None ms")
    assert "132.000 ms idle in the slice" in out[3]
    assert [ln.split()[1] for ln in out[4:]] == [
        "blocked", "descheduled", "outside", "left"]
    # no stalled turn in the slice: all of the idle time lies outside
    monkeypatch.setattr(flight, "stall_log", lambda: [])
    assert reader.read({"counters": {}}, ARGS) == 0.0
    # every idle gap inside a turn left out: nothing to take a share of
    monkeypatch.setattr(ts, "load", lambda: dict(sliced, ops=[
        (n, s, d, "attn", False) for n, s, d in
        [("a", 0.0, 50 * MS), ("b", 130 * MS, 200 * MS)]]))
    monkeypatch.setattr(flight, "stall_log",
                        lambda: [_rec(7, "capture", 110.0, gap_ms=4.0)])
    assert reader.read({"counters": {}}, ARGS) is None


def test_none_without_a_slice_or_a_program_that_books_kinds(
        sliced, monkeypatch):
    monkeypatch.setattr(flight, "stall_log", lambda: [])
    assert reader.read({}, ARGS) is None                        # no run
    old = {"sources": {"engine": {"turns": 9, "causes": {}}}}
    monkeypatch.setattr(flight, "stall_totals", lambda: old)
    assert reader.read({"counters": {}}, ARGS) is None          # before PR 57
    monkeypatch.setattr(flight, "stall_totals", lambda: {"sources": {}})
    assert reader.read({"counters": {}}, ARGS) is None          # no turn
    monkeypatch.setattr(ts, "load", lambda: None)
    assert reader.read({"counters": {}}, ARGS) is None          # no slice
    monkeypatch.delattr(flight, "stall_log")
    monkeypatch.setattr(ts, "load", lambda: sliced)
    assert reader.read({"counters": {}}, ARGS) is None          # before PR 38


def test_rehearsal_every_records_extent_is_its_turn(in_tmp):
    """The program under a real capture on the CPU: taken as stalled, every
    record whose phases the slice holds whole has an extent, and the extent
    is the record's own turn on the profiler's clock (the join the reader
    rests on); without a device plane the metric reads None."""
    engine = _serve_under_capture()
    sl = ts.load()
    events = ts.phase_events(sl, "engine.")
    steps = ts.steps_by_stat(events, ("engine.prepare", "engine.retire"))
    recs = [dict(r, source="engine", cause="blocked")
            for r in engine.flight.entries()]
    inside = [r for r in recs if r["step"] - 1 in steps]
    assert len(inside) >= 8
    got = reader.extents(events, recs, "engine.")
    # all but the slice's first call (no call before it to begin at)
    assert len(got) >= len(inside) - 1
    by_end = {round(own["engine.retire"][1]): k for k, own in steps.items()}
    turn = {r["step"] - 1: r["turn_ms"] for r in recs}
    for cause, start, dur in got:
        assert cause == "blocked"
        assert dur / 1e6 == pytest.approx(
            turn[by_end[round(start + dur)]], rel=0.02, abs=1.0)
    spec_args = {"source": "engine", "layer": "engine."}
    assert reader.read({"counters": {}}, spec_args) is None
