"""Who the device waited for: every idle gap of device 0 in the traced
slice, split over the program's host phases that overlap it
(`lib/trace_spans.split_idle`). The phases of one thread are leaves, so
they never overlap; threads are taken in the order of `args['priority']`
(name prefixes), because the scheduler's loop awaits the engine's step and
its phases overlap the engine's. The value is the share of the idle time
that no phase covers.

Before the value it prints the idle milliseconds per phase name and
`unowned`. None where the slice holds no device ops or no phase."""

from benchmark.lib import trace_spans


def table(sl: dict, priority) -> dict:
    """{phase name: idle ns, "unowned": ns} or None."""
    groups = [trace_spans.phase_events(sl, p) for p in priority]
    if not sl["ops"] or not any(groups):
        return None
    return trace_spans.split_idle(trace_spans.all_gaps(sl["ops"]), groups)


def _say(line: str) -> None:
    print(f"[bench] {line}", flush=True)


def say_table(owned: dict, say=print) -> None:
    total = sum(owned.values())
    say(f"device idle by program phase: {total / 1e6:.3f} ms idle "
        f"in the slice")
    for name, ns in sorted(owned.items(), key=lambda kv: -kv[1]):
        say(f"  {name:<16} {ns / 1e6:9.3f} ms "
            f"{100.0 * ns / total if total else 0.0:5.1f}%")


def read(obs: dict, args: dict):
    sl = trace_spans.load()
    if sl is None:
        return None
    owned = table(sl, args["priority"])
    if owned is None:
        return None
    say_table(owned, _say)
    total = sum(owned.values())
    return 100.0 * owned["unowned"] / total if total else None
