"""The reader of the program's stall log (`readers/flight_stalls.py`) on a
faked log: what it leaves out, what it returns by `stat`, what it prints,
and None where there is nothing to read."""

import pytest

from benchmark.lib import stats
from benchmark.readers import flight_stalls
from distributed_pytorch_tpu.obs import flight


def _stall(source, cause, excess_ms, t0):
    return {"source": source, "cause": cause, "owner": "gap",
            "excess_ms": excess_ms, "median_ms": 20.0, "gc_ms": 1.5,
            "cpu_ms": 3.0, "t0": stats.T_PROCESS_START + t0}


LOG = [_stall("engine", "compile", 21000.0, 5.0),
       _stall("engine", "caller", 150.0, 40.0),
       _stall("train", "blocked", 9000.0, 41.0),
       _stall("engine", "capture", 400.0, 71.0),
       _stall("engine", "blocked", 1350.0, 55.0)]
TOTALS = {"sources": {"engine": {
    "turns": 2000, "turn_seconds": 61.4, "gc_seconds": 0.08,
    "causes": {"compile": {"count": 1, "excess_seconds": 21.0,
                           "longest_ms": 21000.0},
               "capture": {"count": 1, "excess_seconds": 0.4,
                           "longest_ms": 400.0},
               "caller": {"count": 1, "excess_seconds": 0.15,
                          "longest_ms": 150.0},
               "blocked": {"count": 1, "excess_seconds": 1.35,
                           "longest_ms": 1350.0}}},
    "train": {"turns": 0, "turn_seconds": 0.0, "gc_seconds": 0.0,
              "causes": {}}},
    "gc_pause_seconds": [0.05, 0.02, 0.01], "gc_pauses": [50, 4, 1]}


@pytest.fixture()
def faked(monkeypatch):
    monkeypatch.setattr(flight, "stall_log", lambda: list(LOG))
    monkeypatch.setattr(flight, "stall_totals", lambda: TOTALS)
    monkeypatch.setattr(flight_stalls, "_SAID", set())


@pytest.mark.parametrize("stat, want", [
    ("share_pct", 100.0 * 1.5 / 40.0), ("max_ms", 1350.0),
    ("gc_ms_per_s", (80.0 - 2 * 1.5) / 40.0)])
def test_stats_leave_out_compile_and_capture(faked, capsys, stat, want):
    obs = {"counters": {}}
    got = flight_stalls.read(obs, {"source": "engine", "stat": stat})
    assert got == pytest.approx(want)
    out = [ln for ln in capsys.readouterr().out.splitlines() if "stall" in ln]
    assert len(out) == 2 and "compile" not in "".join(out)
    assert out[0].startswith("[bench +  40.00s] stall 150.0 ms engine owner "
                             "gap cause caller gc_ms 1.5 cpu_ms 3.0")
    assert out[1].startswith("[bench +  55.00s] stall 1350.0 ms")
    # said once a run, whatever the number of metrics that read it
    flight_stalls.read(obs, {"source": "engine", "stat": "max_ms"})
    assert "stall" not in capsys.readouterr().out


def test_no_stall_reads_zero(faked, monkeypatch):
    quiet = {"sources": {"engine": {"turns": 10, "turn_seconds": 0.5,
                                    "gc_seconds": 0.001, "causes": {}}},
             "gc_pause_seconds": [0, 0, 0], "gc_pauses": [0, 0, 0]}
    monkeypatch.setattr(flight, "stall_totals", lambda: quiet)
    monkeypatch.setattr(flight, "stall_log", lambda: [])
    obs = {"counters": {}}
    for stat, want in (("share_pct", 0.0), ("max_ms", 0.0),
                       ("gc_ms_per_s", 2.0)):
        assert flight_stalls.read(
            obs, {"source": "engine", "stat": stat}) == pytest.approx(want)


def test_none_without_a_log_or_a_turn(faked, monkeypatch):
    args = {"source": "train", "stat": "share_pct"}
    assert flight_stalls.read({"counters": {}}, args) is None   # no turn
    assert flight_stalls.read({}, {"source": "engine",
                                   "stat": "max_ms"}) is None   # no run
    monkeypatch.delattr(flight, "stall_log")    # a program before PR 38
    assert flight_stalls.read({"counters": {}}, {
        "source": "engine", "stat": "max_ms"}) is None
