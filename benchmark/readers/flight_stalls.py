"""The stalled turns the program's always-on step ring booked
(`distributed_pytorch_tpu.obs.flight`: a turn = one flight record and the
caller's gap before it; stalled = over 3x the running median): the
process-wide log and totals, read after the run in the runner's process.
They cover the PROCESS'S LIFE, not the window: `obs` carries no stamp of the
window's edges and the runners hand it none.

Causes `compile` and `capture` are left out, of the stalls and, by their
excess, of the seconds they are a share of: the warm-up's compiles and the
traced slice's own start and stop are not the system's. Every remaining
stall is printed once a run as `[bench +<turn's start>s] stall ...`, on the
clock of the `window opens` / `window closes` lines. `args['source']` =
`engine` | `train`; by `args['stat']`: `share_pct` (excess seconds of the
stalled turns / seconds of all turns, x 100), `max_ms` (the longest stalled
turn's excess; 0 with none), `gc_ms_per_s` (collector pause ms that ended
inside the turns kept, a second of them). None where the program keeps no such
log (any before PR 38) or `source` made no turn."""

from benchmark.lib import stats

LEFT_OUT = ("compile", "capture")
_SAID: set = set()


def _say(line: str, t0: float) -> None:
    print(f"[bench +{t0 - stats.T_PROCESS_START:7.2f}s] {line}", flush=True)


def read(obs: dict, args: dict):
    if not obs:
        return None             # no run was made: nothing to read
    try:
        from distributed_pytorch_tpu.obs import flight
        log, totals = flight.stall_log(), flight.stall_totals()
    except (ImportError, AttributeError):
        return None
    source = args["source"]
    tot = totals["sources"].get(source)
    if not tot or not tot["turns"]:
        return None
    mine = [s for s in log if s["source"] == source]
    kept = [s for s in mine if s["cause"] not in LEFT_OUT]
    if source not in _SAID:
        _SAID.add(source)
        for s in kept:
            _say(f"stall {s['excess_ms']:.1f} ms {source} owner "
                 f"{s['owner']} cause {s['cause']} gc_ms {s['gc_ms']} "
                 f"cpu_ms {s['cpu_ms']} (turn median {s['median_ms']})",
                 s["t0"])
    causes = tot["causes"]
    seconds = tot["turn_seconds"] - sum(
        causes[c]["excess_seconds"] for c in LEFT_OUT if c in causes)
    if seconds <= 0.0:
        return None
    booked = [v for c, v in causes.items() if c not in LEFT_OUT]
    if args["stat"] == "share_pct":
        return 100.0 * sum(v["excess_seconds"] for v in booked) / seconds
    if args["stat"] == "max_ms":
        return max((v["longest_ms"] for v in booked), default=0.0)
    if args["stat"] == "gc_ms_per_s":
        # less the pauses inside the turns left out (a compile allocates
        # enough for a full collection or two)
        left = sum(s["gc_ms"] for s in mine if s["cause"] in LEFT_OUT)
        return (1e3 * tot["gc_seconds"] - left) / seconds
    raise ValueError(f"flight_stalls: unknown stat {args['stat']!r}")
