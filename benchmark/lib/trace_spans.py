"""The program's own phases and code regions, read back from a traced slice.

The program (distributed_pytorch_tpu/obs/trace.py) opens leaf host phases
as TraceMe events (`engine.prepare`, `sched.emit`, `train.dispatch`, ...,
joined by their `step` stat) and names regions of its compiled steps with
`jax.named_scope` (`kv_update`, `attn_core`, `loss`, ...) beside the flax
module names (`attn`, `mlp`, ...). This module finds both in the slice's
`.xplane.pb` and reduces them with pure functions on plain lists.

Where the scope path of a device op lives (looked at by hand on a v5e
trace, PR 25): NOT in the event's name (the HLO instruction text, which
carries no `metadata={...}`) and NOT in the event's stats (`device_offset_ps`,
`device_duration_ps`, a time scale). It is the stat `tf_op` of the event's
METADATA entry (`jit(fused_step)/decode/LLM/block_3/attn/kv_update/scatter:`),
beside `program_id`, `source` and `hlo_category`. `jax.profiler.ProfileData`
does not hand out metadata stats, so this module decodes the file's
protobuf wire format itself (`read_xspace`; field numbers of
tsl/profiler/protobuf/xplane.proto; no dependency). Times are computed as
ProfileData computes them (line timestamp + event offset), so they sit on
`trace_reduce`'s clock; a test holds the two readers to each other.

An op whose own path names no scope (a layout copy XLA names after a
program ARGUMENT, `caches[8]['v']:`, or not at all) takes the owner of the
op it feeds or is fed by, inside its program (`owners`): reported apart as
inherited, so a table says how much was assigned by dataflow.
"""

from __future__ import annotations

import os
import re
import struct
from typing import Optional

from benchmark.lib import stats as stats_lib
from benchmark.lib import trace_reduce

#: The program's host phases are named `<layer>.<phase>`.
PHASE_LAYERS = ("engine.", "sched.", "train.")

#: Names that own device ops: the program's named scopes (obs/trace.py
#: SCOPES; a test holds this list to that table) and the flax module names
#: of models/gpt.py. The innermost one on an op's path owns it.
SCOPE_NAMES = ("kv_update", "attn_core", "lm_head", "loss", "optimizer",
               "grad_norm", "sample", "chunk_prefill", "decode",
               "attn", "mlp", "moe", "ln1", "ln2", "ln_f", "tkn_emb",
               "pos_emb")
UNSCOPED = "unscoped"


# ---------------------------------------------------------------------------
# the file: protobuf wire format, as far as the readers need it
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> tuple:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, start: int, end: int):
    """(field number, value) over one message: an int for a varint, the
    raw bytes for a fixed width, a (start, end) pair for a length-delimited
    field."""
    i = start
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = b[i:i + 8]
            i += 8
        elif wire == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, v


def _text(b: bytes, span: tuple) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stat(b: bytes, span: tuple, stat_names: dict) -> tuple:
    """(name, value) of one XStat."""
    name, value = None, None
    for f, v in _fields(b, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >> 63 else v
        elif f in (5, 6):
            value = _text(b, v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(b: bytes, span: tuple) -> tuple:
    key, value = None, None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def read_xspace(path: str, want_stats=lambda plane, name: False) -> dict:
    """{plane: {"lines": {line: [(metadata id, start_ns, dur_ns, stats)]},
    "meta": {metadata id: (event name, stats of the metadata entry)}}} of
    an `.xplane.pb`.

    An event's `stats` are decoded (a dict) where `want_stats(plane name,
    event name)` says so, else None. A device op's metadata entry holds
    `tf_op`, `program_id`, `source`, ... Lines that share a name within a
    plane are merged, as `trace_reduce.load_planes` merges them."""
    with open(path, "rb") as f:
        b = f.read()
    out: dict = {}
    for f, plane_span in _fields(b, 0, len(b)):
        if f != 1:
            continue
        pname, line_spans, meta_spans, stat_names = "", [], [], {}
        for f2, v in _fields(b, *plane_span):
            if f2 == 2:
                pname = _text(b, v)
            elif f2 == 3:
                line_spans.append(v)
            elif f2 == 4:
                meta_spans.append(v)
            elif f2 == 5:
                key, span = _map_entry(b, v)
                for f3, v3 in _fields(b, *span):
                    if f3 == 2:
                        stat_names[key] = _text(b, v3)
        meta: dict = {}
        for span in meta_spans:
            key, mspan = _map_entry(b, span)
            name, mstats = "", {}
            for f3, v3 in _fields(b, *mspan):
                if f3 == 2:
                    name = _text(b, v3)
                elif f3 == 5:
                    k, val = _stat(b, v3, stat_names)
                    mstats[k] = val
            meta[key] = (name, mstats)
        lines: dict = {}
        for span in line_spans:
            lname, t0_ns, ev_spans = "", 0, []
            for f3, v3 in _fields(b, *span):
                if f3 == 2:
                    lname = _text(b, v3)
                elif f3 == 3:
                    t0_ns = v3
                elif f3 == 4:
                    ev_spans.append(v3)
            evs = lines.setdefault(lname, [])
            for espan in ev_spans:
                mid = off_ps = dur_ps = 0
                stat_spans = []
                for f4, v4 in _fields(b, *espan):
                    if f4 == 1:
                        mid = v4
                    elif f4 == 2:
                        off_ps = v4
                    elif f4 == 3:
                        dur_ps = v4
                    elif f4 == 4:
                        stat_spans.append(v4)
                st = None
                if want_stats(pname, meta.get(mid, ("",))[0]):
                    st = dict(_stat(b, s, stat_names) for s in stat_spans)
                evs.append((mid, t0_ns + off_ps / 1e3, dur_ps / 1e3, st))
        out[pname] = {"lines": lines, "meta": meta}
    return out


# ---------------------------------------------------------------------------
# scope paths and owners
# ---------------------------------------------------------------------------

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_PART = re.compile(r"[/();:]")
_HLO_NAME = re.compile(r"%([\w.\-]+)")


def scope_path(hlo_text: str, tf_op: Optional[str] = None) -> str:
    """The op's path through the program's scopes: the metadata entry's
    `tf_op` where the trace has one, else the `op_name` of a `metadata={}`
    in the instruction text, else ''. Backward ops read
    `.../transpose(jvp(LLM))/block_3/attn/attn_core/...`."""
    if tf_op:
        return tf_op.rstrip(":")
    m = _OP_NAME.search(hlo_text)
    return m.group(1) if m else ""


def owner(path: str, names=SCOPE_NAMES) -> Optional[str]:
    """The innermost component of `path` that is one of `names`."""
    for part in reversed(_PART.split(path)):
        if part in names:
            return part
    return None


def owners(ops, names=SCOPE_NAMES) -> dict:
    """{key: (owner, inherited)} for `ops` = [(key, program, hlo_text,
    path)]. An op owns itself where its path names a scope. One that names
    none (XLA's layout copies carry a program argument's name, or nothing)
    takes the owner of an op it consumes, else of one that consumes it,
    within its program, round by round until nothing changes; what stays
    without one is UNSCOPED."""
    own: dict = {}
    by_lhs: dict = {}
    operands: dict = {}
    consumers: dict = {}
    for key, program, text, path in ops:
        own[key] = (owner(path, names), False)
        lhs, _, rest = text.partition(" = ")
        by_lhs[(program, lhs.lstrip("%"))] = key
        operands[key] = [(program, n) for n in _HLO_NAME.findall(rest)]
    for key, refs in operands.items():
        operands[key] = [by_lhs[r] for r in refs
                         if r in by_lhs and by_lhs[r] != key]
        for src in operands[key]:
            consumers.setdefault(src, []).append(key)
    todo = [k for k, (o, _) in own.items() if o is None]
    while todo:
        found = {}
        for key in todo:
            for other in operands[key] + consumers.get(key, []):
                if own[other][0] is not None:
                    found[key] = (own[other][0], True)
                    break
        if not found:
            break
        own.update(found)
        todo = [k for k in todo if k not in found]
    return {k: (o or UNSCOPED, inh) for k, (o, inh) in own.items()}


# ---------------------------------------------------------------------------
# arithmetic on event lists
# ---------------------------------------------------------------------------

def whole_modules(modules, patterns, lo_ns: float, hi_ns: float) -> list:
    """The step programs (`XLA Modules` events whose name matches a regex
    of `patterns`) that lie wholly inside (lo, hi), the extent of the
    device's ops: one that touches an edge may have been cut by the
    slice."""
    rx = [re.compile(p) for p in patterns]
    hit = [m for m in modules if any(r.search(m[0]) for r in rx)]
    return sorted((m for m in hit if m[1] > lo_ns and m[1] + m[2] < hi_ns),
                  key=lambda m: m[1])


def self_time_by_owner(ops, steps) -> dict:
    """{owner: [own_ns, inherited_ns]} and {(owner, family): ns} over the
    ops that lie inside one of `steps` (disjoint (name, start, dur)
    modules). `ops` = [(short name, start_ns, dur_ns, owner, inherited)]
    of one line; self time as `trace_reduce.self_times` defines it."""
    bounds = sorted((s, s + d) for _, s, d in steps)
    inside, i = [], 0
    for op in sorted(ops, key=lambda e: e[1]):
        while i < len(bounds) and bounds[i][1] <= op[1]:
            i += 1
        if i < len(bounds) and bounds[i][0] <= op[1] \
                and op[1] + op[2] <= bounds[i][1]:
            inside.append(op)
    selfs = trace_reduce.self_times(
        [(n, op[1], op[2]) for n, op in enumerate(inside)])
    totals: dict = {}
    families: dict = {}
    for n, ns in selfs:
        name, _, _, own, inherited = inside[n]
        totals.setdefault(own, [0.0, 0.0])[int(bool(inherited))] += ns
        fam = (own, trace_reduce.op_family(name))
        families[fam] = families.get(fam, 0.0) + ns
    return {"owners": totals, "families": families}


def steps_by_stat(events, required, stat: str = "step") -> dict:
    """{step: {phase name: (start_ns, end_ns)}} from phase events `(name,
    start_ns, dur_ns, stats)`, for the steps that have every phase of
    `required`: a step the slice's edge cut lacks one and is dropped.
    Where a phase occurs twice in a step (an engine step that returned
    after `engine.prepare` keeps its number), the later one stands."""
    steps: dict = {}
    for name, start, dur, st in sorted(events, key=lambda e: e[1]):
        if st is None or st.get(stat) is None:
            continue
        steps.setdefault(int(st[stat]), {})[name] = (start, start + dur)
    return {k: v for k, v in steps.items() if all(r in v for r in required)}


def step_extent_ms(steps: dict, first: str, last: str) -> list:
    """Start of `first` to end of `last`, per step."""
    return [(p[last][1] - p[first][0]) / 1e6 for _, p in sorted(steps.items())]


def step_sum_ms(steps: dict, phases) -> list:
    """Summed durations of `phases`, per step."""
    return [sum(p[n][1] - p[n][0] for n in phases) / 1e6
            for _, p in sorted(steps.items())]


def step_turnaround_ms(steps: dict, after: str, before: str) -> list:
    """End of `after` in step n to start of `before` in step n + 1, for
    consecutive whole steps."""
    return [(steps[k + 1][before][0] - steps[k][after][1]) / 1e6
            for k in sorted(steps) if k + 1 in steps]


def split_idle(gaps, phase_groups) -> dict:
    """{phase name: idle ns} with the rest under 'unowned': each idle gap
    `(start_ns, dur_ns)` of the device is split over the phases that
    overlap it. `phase_groups` are lists of leaf phases `(name, start_ns,
    dur_ns, ...)`, one per thread, most telling thread first: a stretch
    that a phase of an earlier group covers is not offered to a later one
    (the scheduler awaits the engine, so its phases overlap the
    engine's)."""
    rest = sorted((s, s + d) for s, d in gaps if d > 0)
    owned: dict = {}
    for group in phase_groups:
        spans = sorted((e[1], e[1] + e[2], e[0]) for e in group)
        left, j = [], 0
        for a, b in rest:
            while j < len(spans) and spans[j][1] <= a:
                j += 1
            k, at = j, a
            while k < len(spans) and spans[k][0] < b:
                s, e, name = spans[k]
                if s > at:
                    left.append((at, s))
                cover = min(e, b) - max(s, at)
                if cover > 0:
                    owned[name] = owned.get(name, 0.0) + cover
                at = max(at, min(e, b))
                k += 1
            if at < b:
                left.append((at, b))
        rest = left
    owned["unowned"] = sum(b - a for a, b in rest)
    return owned


def all_gaps(events) -> list:
    """Every interval inside the events' span in which none ran, as
    (start_ns, dur_ns)."""
    iv = trace_reduce.merged_intervals([e[:3] for e in events])
    return [(a[1], b[0] - a[1]) for a, b in zip(iv, iv[1:]) if b[0] > a[1]]


# ---------------------------------------------------------------------------
# the slice of a run, loaded once per process
# ---------------------------------------------------------------------------

_LOADED: tuple = (None, None)        # (path and mtime, its slice)


def _is_phase(plane: str, name: str) -> bool:
    return plane == trace_reduce.HOST_PLANE and name.startswith(PHASE_LAYERS)


def load(trace_dir: str = "trace") -> Optional[dict]:
    """The newest slice under `trace_dir` (both runners write it under
    `trace/` of the cell's work directory, which is the working directory
    by then), or None where there is none:

    {"phases": {thread line: [(name, start_ns, dur_ns, stats)]},
     "ops": [(short name, start_ns, dur_ns, owner, inherited)] and
     "modules": [(name, start_ns, dur_ns)] of the first device plane
     (None where the file holds no device plane, as a CPU capture)}."""
    try:
        path = os.path.abspath(trace_reduce.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    global _LOADED
    key = (path, os.path.getmtime(path))
    if _LOADED[0] != key:
        _LOADED = (key, _reduce(read_xspace(path, _is_phase)))
    return _LOADED[1]


def _reduce(space: dict) -> dict:
    phases = {}
    host = space.get(trace_reduce.HOST_PLANE, {"lines": {}, "meta": {}})
    for lname, evs in host["lines"].items():
        mine = [(host["meta"][mid][0], start, dur, st)
                for mid, start, dur, st in evs if st is not None]
        if mine:
            phases[lname] = mine
    out = {"phases": phases, "ops": None, "modules": None}
    devices = sorted((int(m.group(2)), p) for p in space
                     for m in [trace_reduce.DEVICE_PLANE.match(p)] if m)
    if not devices:
        return out
    plane = space[devices[0][1]]
    meta = plane["meta"]
    evs = plane["lines"].get(trace_reduce.OPS_LINE, [])
    if not evs:
        return out
    own = owners([(mid, meta[mid][1].get("program_id"), meta[mid][0],
                   scope_path(meta[mid][0], meta[mid][1].get("tf_op")))
                  for mid in {e[0] for e in evs}])
    short = {mid: trace_reduce.short_op_name(meta[mid][0]) for mid in own}
    out["ops"] = [(short[mid], start, dur, *own[mid])
                  for mid, start, dur, _ in evs]
    out["modules"] = [(meta[mid][0], start, dur) for mid, start, dur, _ in
                      plane["lines"].get("XLA Modules", [])]
    return out


def phase_events(sl: dict, prefix: str) -> list:
    """The slice's phases whose name starts with `prefix`, over all
    threads."""
    return [e for evs in sl["phases"].values() for e in evs
            if e[0].startswith(prefix)]


def median(values) -> Optional[float]:
    return stats_lib.median(values) if values else None
