"""Device self time a step under the program's named scopes and module
names (`lib/trace_spans.py`): over the step programs of `args['modules']`
that lie wholly inside the slice, the self time of device 0's ops whose
owner is one of `args['scopes']`, in ms a step, or with `stat` = `pct` as a
share of all self time in those steps. An op is owned by the innermost
scope on its own path, or, where that names none, by the op it feeds or is
fed by (inherited). The scopes' self times and the `unscoped` remainder add
up to the device's self time in those steps.

Before the first value of a slice it prints the table: ms a step per
owner (own + inherited) with its three largest op families. None where no
op of the slice has such an owner (a program without these scopes)."""

from benchmark.lib import trace_spans


def table(sl: dict, modules) -> dict:
    """{"steps": n, "owners": ..., "families": ...} of the slice's whole
    step programs, or None where it has none."""
    ops = sl["ops"]
    lo = min(o[1] for o in ops)
    hi = max(o[1] + o[2] for o in ops)
    steps = trace_spans.whole_modules(sl["modules"], modules, lo, hi)
    if not steps:
        return None
    out = trace_spans.self_time_by_owner(ops, steps)
    out["steps"] = len(steps)
    return out


def _say(line: str) -> None:
    print(f"[bench] {line}", flush=True)


def say_table(t: dict, say=print) -> None:
    n = t["steps"]
    total = sum(a + b for a, b in t["owners"].values())
    say(f"device self time by scope: {n} whole step programs, "
        f"{total / n / 1e6:.3f} ms a step")
    for own, (a, b) in sorted(t["owners"].items(),
                              key=lambda kv: -sum(kv[1])):
        say(f"  {own:<14} {(a + b) / n / 1e6:9.3f} ms a step "
            f"{100.0 * (a + b) / total:5.1f}% (inherited by dataflow "
            f"{b / n / 1e6:.3f})")
        fams = sorted(((f, ns) for (o, f), ns in t["families"].items()
                       if o == own), key=lambda kv: -kv[1])[:3]
        for fam, ns in fams:
            say(f"      {fam}: {ns / n / 1e6:.3f}")


def read(obs: dict, args: dict):
    sl = trace_spans.load()
    if sl is None or not sl["ops"]:
        return None
    # one reduction (and one printed table) per slice and set of programs,
    # however many metrics read it
    memo = sl.setdefault("scope_tables", {})
    key = tuple(args["modules"])
    if key not in memo:
        memo[key] = table(sl, args["modules"])
        if memo[key] is not None:
            say_table(memo[key], _say)
    t = memo[key]
    if t is None:
        return None
    hit = [t["owners"][s] for s in args["scopes"] if s in t["owners"]]
    if not hit:
        return None
    ns = sum(a + b for a, b in hit)
    if args.get("stat") == "pct":
        return 100.0 * ns / sum(a + b for a, b in t["owners"].values())
    return ns / t["steps"] / 1e6
