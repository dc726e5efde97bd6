"""From a profiler trace to numbers.

Reads an `.xplane.pb` with `jax.profiler.ProfileData.from_file` and nothing
else. Every piece of arithmetic works on plain `(name, start_ns, dur_ns)`
lists, so it is testable without a trace (benchmark/tests).

What a TPU trace looks like (one plane per chip, `/device:TPU:<n>`): the
line `XLA Ops` holds one event per executed HLO op (fusions, custom calls =
Pallas kernels under their `name=`, collectives under their HLO name), and
ops that contain others (a `while`, a `conditional`) appear as events that
enclose their children on the same line. `Async XLA Ops` holds what XLA
made asynchronous (copies, collectives), `XLA Modules` one event per
executed program. Host threads live on `/host:CPU`. An op's event name is
its whole HLO instruction; `short_op_name` keeps what identifies it.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Event = tuple  # (name, start_ns, dur_ns)


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def start_trace(trace_dir: str) -> None:
    """Start the profiler into an emptied `trace_dir`, Python tracer off
    (host TraceMe events stay: they name the idle gaps)."""
    import shutil
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` dir."""
    hits = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


_HLO = re.compile(r"^%?(?P<lhs>[^\s=]+) = (?P<rest>.*)$", re.S)
_TYPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OPCODE = re.compile(r"(?<![\w.%-])([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KERNEL = re.compile(r'kernel_name[\\"=: ]+([A-Za-z_][\w.-]*)')


def short_op_name(name: str) -> str:
    """A device op's event name is its whole HLO instruction. Keep what
    identifies it: `<result name> <opcode> <first result type>`, plus the
    custom-call target and kernel name where there is one. Operands are
    dropped, so a fusion that merely CONSUMES `%all-gather.3` no longer
    carries that word. Names that are not HLO text pass unchanged."""
    m = _HLO.match(name)
    if not m:
        return name
    rest = m.group("rest")
    typ = _TYPE.search(rest)
    op = _OPCODE.search(rest)
    parts = [m.group("lhs")]
    if op:
        parts.append(op.group(1))
    if typ and (not op or typ.start() < op.start()):
        parts.append(typ.group(0))
    for rx in (_TARGET, _KERNEL):
        hit = rx.search(rest)
        if hit:
            parts.append(hit.group(1))
    return " ".join(parts)


def load_planes(path: str) -> dict:
    """{plane name: {line name: [(name, start_ns, dur_ns), ...]}} for the
    whole file. Lines that share a name within a plane are merged; device
    op names are shortened (`short_op_name`)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: dict = {}
    cache: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                name = e.name
                if on_device:
                    if name not in cache:
                        cache[name] = short_op_name(name)
                    name = cache[name]
                evs.append((name, float(e.start_ns), float(e.duration_ns)))
    return out


def device_lines(planes: dict, line_name: str = OPS_LINE) -> dict:
    """{device index: events of `line_name`} over the device planes."""
    out = {}
    for pname, lines in planes.items():
        m = DEVICE_PLANE.match(pname)
        if m and lines.get(line_name):
            out[int(m.group(2))] = sorted(lines[line_name],
                                          key=lambda e: (e[1], -e[2]))
    return out


def describe(planes: dict) -> list:
    """[(plane, line, n_events)]: what a person looks at first."""
    return [(p, l, len(evs)) for p, lines in planes.items()
            for l, evs in lines.items()]


# ---------------------------------------------------------------------------
# arithmetic on event lists
# ---------------------------------------------------------------------------

def merged_intervals(events) -> list:
    """Union of the events' intervals as sorted, disjoint [start, end]."""
    out: list = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def busy_ns(events) -> float:
    """Time in which at least one event ran (overlaps counted once)."""
    return sum(e - s for s, e in merged_intervals(events))


def span_ns(events) -> float:
    """First start to last end."""
    if not events:
        return 0.0
    return max(s + d for _, s, d in events) - min(s for _, s, _ in events)


def idle_gaps(events, top: int = 10) -> list:
    """The longest intervals inside the span in which no event ran, as
    (start_ns, dur_ns), longest first."""
    iv = merged_intervals(events)
    gaps = [(a[1], b[0] - a[1]) for a, b in zip(iv, iv[1:]) if b[0] > a[1]]
    return sorted(gaps, key=lambda g: -g[1])[:top]


def self_times(events) -> list:
    """[(name, self_ns)] per event of ONE line: its duration less the part
    covered by events nested inside it (a `while` that encloses its body's
    ops keeps only its own overhead). Events on a line nest or are
    disjoint; a partial overlap is treated as disjoint from its end on."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: list = []            # [name, end, self_ns]

    def pop_until(t):
        while stack and stack[-1][1] <= t:
            name, _, self_ns = stack.pop()
            out.append((name, max(self_ns, 0.0)))

    for name, start, dur in evs:
        pop_until(start)
        end = start + dur
        if stack:
            end = min(end, stack[-1][1])
            stack[-1][2] -= end - start
        stack.append([name, end, end - start])
    pop_until(float("inf"))
    return out


_INSTANCE = re.compile(r"[.\-_]\d+$")


def op_family(name: str) -> str:
    """Instances of one op kind under one name. A shortened HLO name
    (`short_op_name`) drops its result name and keeps opcode, result type
    and kernel: `copy.591 copy bf16[200,128,25,64]` -> `copy
    bf16[200,128,25,64]`, so the same op of every layer falls together. A
    custom call keeps its result name without the instance number, because
    that is the Pallas kernel's `name=`. Any other name loses its instance
    suffix: `fusion.123` -> `fusion`."""
    head, _, tail = name.partition(" ")
    if tail and not tail.startswith("custom-call"):
        return tail
    prev = None
    while prev != head:
        prev, head = head, _INSTANCE.sub("", head)
    return f"{head} {tail}" if tail else head


def top_ops(events, top: int = 10, by_family: bool = False) -> list:
    """[(name, seconds)] by self time, largest first."""
    acc: dict = {}
    for name, ns in self_times(events):
        key = op_family(name) if by_family else name
        acc[key] = acc.get(key, 0.0) + ns
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / 1e9] for k, v in rows]


def matching_ns(events, patterns) -> float:
    """Union time of the events whose name matches any regex in
    `patterns` (nested matches are counted once)."""
    rx = [re.compile(p) for p in patterns]
    hit = [e for e in events if any(r.search(e[0]) for r in rx)]
    return busy_ns(hit)


def count_matching(events, patterns) -> int:
    rx = [re.compile(p) for p in patterns]
    return sum(1 for e in events if any(r.search(e[0]) for r in rx))


def attribute_gaps(gaps, host_events, top: int = 10) -> list:
    """[(label, seconds)] for idle gaps: the host event that covers most of
    each gap names it (`unattributed` where no host event overlaps). Device
    and host lines of one trace share a clock."""
    rows = []
    for g_start, g_dur in gaps[:top]:
        g_end = g_start + g_dur
        best, best_cover = "unattributed", 0.0
        for name, start, dur in host_events:
            cover = min(g_end, start + dur) - max(g_start, start)
            if cover > best_cover:
                best, best_cover = name, cover
        rows.append([best, g_dur / 1e9])
    return rows


# ---------------------------------------------------------------------------
# the summary a run keeps
# ---------------------------------------------------------------------------

def summarize(planes: dict, n_chips: int) -> dict:
    """Busy and window seconds averaged over the chips used, per-device op
    events of device 0, and the breakdown. Raises if no device op ran."""
    per_dev = device_lines(planes)
    if not per_dev:
        raise RuntimeError(
            "the trace holds no device plane with an 'XLA Ops' line: "
            f"{[(p, l, n) for p, l, n in describe(planes)][:20]}")
    used = sorted(per_dev)[:n_chips]
    busy = [busy_ns(per_dev[d]) for d in used]
    span = [span_ns(per_dev[d]) for d in used]
    dev0 = per_dev[used[0]]
    host = []
    for lname, evs in planes.get(HOST_PLANE, {}).items():
        host.extend(e for e in evs if e[2] > 0)
    gaps = idle_gaps(dev0, top=10)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": sum(span) / len(span) / 1e9,
        "devices": used,
        "ops_dev0": dev0,
        "breakdown": {
            "device_ops": top_ops(dev0, top=10, by_family=True),
            "idle_gaps": attribute_gaps(gaps, host, top=10),
        },
    }


def reduce_trace_dir(trace_dir: str, n_chips: int, steps: int, say) -> dict:
    """Load the newest trace under `trace_dir`, say what it holds, and
    return its summary with the number of program steps it spans."""
    planes = load_planes(find_xplane(trace_dir))
    for plane, line, n in describe(planes):
        say(f"  trace plane {plane!r} line {line!r}: {n} events")
    summary = summarize(planes, n_chips)
    summary["steps"] = steps
    say(f"trace: {steps} steps, busy {summary['busy_s']:.3f}s of "
        f"{summary['window_s']:.3f}s on devices {summary['devices']}")
    for name, sec in summary["breakdown"]["device_ops"]:
        say(f"  op {name}: {sec * 1e3:.2f} ms")
    for name, sec in summary["breakdown"]["idle_gaps"][:5]:
        say(f"  gap {name}: {sec * 1e3:.2f} ms")
    return summary
