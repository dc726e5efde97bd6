"""`trace_scope_ms` for programs whose scopes `lib/trace_spans.SCOPE_NAMES`
does not list: device self time a step under `args['scopes']`, where an op's
owner is the innermost name on its path out of SCOPE_NAMES plus
`args['names']` (a patterned model's mixer scopes and module names), or,
where its path names none, the op it feeds or is fed by. `stat` = `pct`
gives the share of all self time in those steps. Prints its table once a
slice and set of names. None where the slice has no op with such an owner
(a program without these scopes, as every program before PR 33)."""

import os

from benchmark.lib import trace_reduce, trace_spans
from benchmark.readers import trace_scope_ms

_MEMO: dict = {}


def _ops_and_modules(names: tuple, trace_dir: str = "trace"):
    """Device 0's ops `(short name, start, dur, owner, inherited)` and its
    module events, owners taken over `names`."""
    try:
        path = os.path.abspath(trace_reduce.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path), names)
    if key in _MEMO:
        return _MEMO[key]
    space = trace_spans.read_xspace(path, lambda plane, name: False)
    devices = sorted((int(m.group(2)), p) for p in space
                     for m in [trace_reduce.DEVICE_PLANE.match(p)] if m)
    out = None
    if devices:
        plane = space[devices[0][1]]
        meta = plane["meta"]
        evs = plane["lines"].get(trace_reduce.OPS_LINE, [])
        if evs:
            own = trace_spans.owners(
                [(mid, meta[mid][1].get("program_id"), meta[mid][0],
                  trace_spans.scope_path(meta[mid][0],
                                         meta[mid][1].get("tf_op")))
                 for mid in {e[0] for e in evs}], names)
            ops = [(trace_reduce.short_op_name(meta[mid][0]), start, dur,
                    *own[mid]) for mid, start, dur, _ in evs]
            mods = [(meta[mid][0], start, dur) for mid, start, dur, _ in
                    plane["lines"].get("XLA Modules", [])]
            out = {"ops": ops, "modules": mods}
    _MEMO.clear()
    _MEMO[key] = out
    return out


def table(names, modules, trace_dir: str = "trace", say=None):
    """The slice's table over SCOPE_NAMES + `names` (`trace_scope_ms.
    table`'s shape), printed through `say` the first time; None where the
    slice has no device ops or no whole step program."""
    sl = _ops_and_modules(tuple(trace_spans.SCOPE_NAMES) + tuple(names),
                          trace_dir)
    if sl is None:
        return None
    key = ("table", tuple(modules))
    if key not in sl:
        sl[key] = trace_scope_ms.table(sl, modules)
        if sl[key] is not None and say is not None:
            trace_scope_ms.say_table(sl[key], say)
    return sl[key]


def read(obs: dict, args: dict):
    if not obs.get("trace"):
        return None
    t = table(args["names"], args["modules"], say=trace_scope_ms._say)
    if t is None:
        return None
    hit = [t["owners"][s] for s in args["scopes"] if s in t["owners"]]
    if not hit:
        return None
    ns = sum(a + b for a, b in hit)
    if args.get("stat") == "pct":
        return 100.0 * ns / sum(a + b for a, b in t["owners"].values())
    return ns / t["steps"] / 1e6
