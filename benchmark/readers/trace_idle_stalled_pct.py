"""The step ring's stalled turns laid on the device trace: of device 0's idle
time in the traced slice, the share that lies inside turns the program's
always-on ring booked as stalled (`distributed_pytorch_tpu.obs.flight`'s
`stall_log()`), so that a line whose `device_idle_pct.*` jumps says whether
the ring saw the stop, and under which cause.

No clock is converted and no span is added. The program's host phases are
TraceMe events on the profiler's clock, with stat `step` = the number of the
program their call drains; a flight record's `step` is that number plus one
(`engine/decode.py::_drain`). A stalled turn's extent is taken from the
phases of `args['layer']` (`engine.`): it ends where its call's `retire`
phase ends (the record is stamped inside it) and, where the record has a
`gap_ms` (work waited for the caller: the turn began at the last record),
begins where the call before it ended its `retire`, else where its own
`prepare` begins. A record whose phases the slice's edge cut (no such step
in the slice, no earlier `retire`), or whose extent is not its own `turn_ms`
(another engine's step number), is left out. The idle gaps (all of them,
`lib/trace_spans.all_gaps`) are split over the extents by cause
(`split_idle`); what no stalled turn covers is `outside`.

Causes `compile` and `capture` are left out as `readers/flight_stalls.py`
leaves them out (`LEFT_OUT`): their turns' idle time of the numerator and of
the idle time it is a share of (the slice's own start and stop). None where
the slice holds no device op, no idle time or no phase, and where the
program books no `kind` (any before PR 57).

Before the value it prints what the ring judged by: every stalled turn kept,
over the process's life, as `[bench +<turn's start>s] stall ...` with its
`kind`, the median of that kind it was judged by and where its thread stood
(`sched_delay_ms`, `steal_ms`, `nivcsw`: each `None` where the platform
keeps no such count, as on the benchmark's chip machines), the turns and
the running median of each kind, then the idle ms by cause, `outside` and
`left out`."""

import bisect

from benchmark.lib import stats, trace_spans
from benchmark.readers.flight_stalls import LEFT_OUT

#: an extent is its record's turn within this much (the record is stamped a
#: few tens of us before its `retire` phase ends)
_SAME_MS, _SAME_SHARE = 1.0, 0.02


def extents(events, log, layer: str) -> list:
    """[(cause, start_ns, dur_ns)] of the stalled turns of `log` whose
    phases `events` = [(name, start_ns, dur_ns, stats)] hold whole."""
    first, last = layer + "prepare", layer + "retire"
    steps = trace_spans.steps_by_stat(events, (first, last))
    ends = sorted(e[1] + e[2] for e in events if e[0] == last)
    out = []
    for rec in log:
        own = steps.get(rec.get("step", 0) - 1)
        if own is None:
            continue
        start, end = own[first][0], own[last][1]
        if "gap_ms" in rec:
            before = bisect.bisect_right(ends, start)
            if not before:
                continue        # the slice's edge cut the call before
            start = ends[before - 1]
        ms = (end - start) / 1e6
        if abs(ms - rec["turn_ms"]) > max(_SAME_MS,
                                          _SAME_SHARE * rec["turn_ms"]):
            continue
        out.append((rec["cause"], start, end - start))
    return out


def table(sl: dict, log, layer: str) -> dict:
    """{cause: idle ns inside its stalled turns, "outside": ns, "left
    out": ns} or None."""
    events = trace_spans.phase_events(sl, layer)
    if not sl["ops"] or not events:
        return None
    owned = trace_spans.split_idle(trace_spans.all_gaps(sl["ops"]),
                                   [extents(events, log, layer)])
    owned["outside"] = owned.pop("unowned")
    owned["left out"] = sum(owned.pop(c, 0.0) for c in LEFT_OUT)
    return owned


def _say(line: str, t0: float = None) -> None:
    at = "" if t0 is None else f" +{t0 - stats.T_PROCESS_START:7.2f}s"
    print(f"[bench{at}] {line}", flush=True)


def say_judged(source: str, kept: list, kinds: dict) -> None:
    for s in kept:
        _say(f"stall {s['excess_ms']:.1f} ms {source} kind {s.get('kind')} "
             f"(its median {s['median_ms']}) owner {s['owner']} cause "
             f"{s['cause']}: sched_delay_ms {s.get('sched_delay_ms')} "
             f"steal_ms {s.get('steal_ms')} nivcsw {s.get('nivcsw')} cpu_ms "
             f"{s['cpu_ms']} of turn_ms {s['turn_ms']}", s["t0"])
    _say(f"{source} turns by kind: " + " | ".join(
        f"{k} {v['turns']} turns, {v['turn_seconds']:.2f} s, running "
        f"median {v['median_ms']} ms" for k, v in sorted(kinds.items())))


def read(obs: dict, args: dict):
    if not obs:
        return None             # no run was made: nothing to read
    sl = trace_spans.load()
    if sl is None:
        return None
    try:
        from distributed_pytorch_tpu.obs import flight
        log, totals = flight.stall_log(), flight.stall_totals()
    except (ImportError, AttributeError):
        return None
    source = args["source"]
    tot = totals["sources"].get(source, {})
    if "kinds" not in tot:
        return None
    mine = [s for s in log if s["source"] == source]
    owned = table(sl, mine, args["layer"])
    if owned is None:
        return None
    say_judged(source, [s for s in mine if s["cause"] not in LEFT_OUT],
               tot["kinds"])
    total = sum(owned.values())
    _say(f"device idle inside the step ring's stalled turns: "
         f"{total / 1e6:.3f} ms idle in the slice")
    for name, ns in sorted(owned.items(), key=lambda kv: -kv[1]):
        _say(f"  {name:<16} {ns / 1e6:9.3f} ms "
             f"{100.0 * ns / total if total else 0.0:5.1f}%")
    kept = total - owned["left out"]
    if kept <= 0.0:
        return None
    return 100.0 * (kept - owned["outside"]) / kept
