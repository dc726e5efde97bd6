"""Step-level flight recorder: the engine's always-on black box.

The serving histograms (serve/metrics.py) answer "what were the
quantiles"; they cannot answer "what happened around 14:03:07 when ITL
p99 spiked". The flight recorder can: every fused step appends one
compact record — `{step, step_ms, prepare_ms, dispatch_ms, wait_ms,
retire_ms, n_live, prefill_tokens, emitted, blocks_in_use, preemptions}`
(the four parts of step_ms are the engine's host phases, obs/trace.py) —
to a bounded ring, so the last few
thousand steps are always reconstructable, at the cost of one dict
append per multi-millisecond device step. A record is one drained step
program: `overlapped` says whether it was queued behind a running one
(the device did not wait for the host before it), `drain_reason` why not
(`first` | `wave` | `spec` | `tier` | `preempt`), `overrun` how many of
its tokens were for an occupant that had left (an `eos` seen one program
late, a cancel), `decode_live_tiles` / `decode_live_steps` what its decode
attention call walked by the planned lengths (live cache tiles, and the
paged kernel's grid steps that held them: one a decoding sequence). The
four times are those of the `step()` call that drained it. Served live at
`GET /debug/timeline` (serve/server.py) and dumped to `runs/*.jsonl` by
the bench legs and the fault-injection harness for post-hoc analysis
against the PERF.md latency models.

**Turns and stalls** (`begin_turn` / `record_turn`). A TURN is what one
record covers plus what preceded it since the last one: for the engine from
the previous record's stamp, while work waited for the caller, to this
one's (`gap_ms` + `step_ms`), for the trainer one drained log window. A
turn's record also says what else ran in it: `gc_ms` / `gc_gen` (collector
pauses that ended inside it, any thread, from one process-wide
`gc.callbacks` hook that also writes each pause into the profiler's trace
as `host.gc`), `cpu_ms` (the writer thread's own CPU time over the turn:
computing or asleep), where the thread STOOD while it was off the CPU
(`sched_delay_ms`, `steal_ms`, `nivcsw`: `_stood`) and `capturing` (a
profiler capture at either end, or one that went off inside the turn
before: writing it out takes seconds).

A turn is judged against the turns of its own KIND (`record_turn(kind=)`:
the engine hands the drained program's `decode` | `fused` | `spec`, whose
times lie 2.3-4.3x apart in the cells with long chunks; the trainer hands
none and its turns are one kind, `ONE_KIND`). Per kind the recorder keeps
the last `MEDIAN_TURNS` unstalled turns and their running median; a turn
longer than `STALL_FACTOR` x the median of its kind, or one that compiled,
is STALLED: booked with its `kind`, its `owner` (its largest part), its
`cause` (`CAUSES`) and its `excess_ms` over that `median_ms` into a
process-wide log that ordinary records never evict (`stall_log()`), beside
process-wide totals (`stall_totals()`). A kind without a median yet gets no
verdict. Process-wide because what is measured is: a collection or a
descheduling stops every thread.
"""

from __future__ import annotations

import collections
import gc
import json
import os
import statistics
import threading
import time
from typing import Optional

try:
    import resource
except ImportError:             # no such module off POSIX: no `nivcsw`
    resource = None

from distributed_pytorch_tpu.obs.trace import HOST_GC, TraceAnnotation

#: A turn is stalled beyond this many running medians of its kind; the
#: stalls hunted are 5-200x.
STALL_FACTOR = 3.0
MEDIAN_TURNS = 256      # unstalled turns the running median looks back over
MEDIAN_EVERY = 64       # ... and is taken anew every so many of them
#: unstalled turns before there is a median to judge by: a recorder's first
#: few are its loop's warm-up (two slots live where sixty-four will be), and
#: a median of four of them flagged every program of the load that followed
MIN_TURNS = 16
#: this many stalled turns in a row are no stalls but another load (a batch
#: three times the size): the median is learnt anew from the turns after
REGIME_TURNS = 16
STALL_LOG = 256         # stalled turns the process keeps

#: the kind of a source whose turns are all one (the trainer's log windows)
ONE_KIND = "turn"

#: Why a turn stalled, the first that holds. `compile`: a trace guard fired
#: inside it. `capture`: a profiler capture started or stopped in it or in
#: the turn before (a turn that ran under one from end to end is judged as
#: any other). `gc`: collector pauses of at least half the excess.
#: `descheduled`: the writer thread stood runnable without a CPU, or the
#: machine's CPUs were stolen from it, for at least half the excess
#: (`sched_delay_ms` + `steal_ms`): the host's scheduler, a quota, a
#: hypervisor. `caller`: the owner is the gap between two calls.
#: `host_busy` / `blocked`: the owner is a phase of the writer's thread,
#: which spent at least half / under a tenth of it on the CPU (Python
#: computing / asleep with a CPU to be had: waiting on the runtime, the
#: device or a lock). `mixed`: none of them.
CAUSES = ("compile", "capture", "gc", "descheduled", "caller", "host_busy",
          "blocked", "mixed")

# process-wide: the collector's pauses, the stalled turns of every recorder
# and the totals they are shares of
_lock = threading.Lock()
_stalls: collections.deque = collections.deque(maxlen=STALL_LOG)
_totals: dict[str, dict] = {}           # source -> turns, seconds, causes
_gc_seconds = [0.0, 0.0, 0.0]           # pause seconds by generation
_gc_pauses = [0, 0, 0]
_gc_open: Optional[tuple] = None        # (start stamp, annotation | None)
_TICKS_PER_S = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


# where a thread stood: the kernel's own counts, read on descriptors opened
# once (None = not tried yet, False = the platform has none)
_SCHEDSTAT = "/proc/thread-self/schedstat"
_PROC_STAT = "/proc/stat"
_tls = threading.local()                # .schedstat: the thread's own file
_proc_stat = None                       # the machine's


def _opened(path: str):
    try:
        return open(path, "rb", buffering=0)
    except OSError:
        return False


def _field(f, n: int) -> Optional[int]:
    """Field `n` of the first line of an open `/proc` file."""
    if not f:
        return None
    try:
        return int(os.pread(f.fileno(), 192, 0).split(None, n + 1)[n])
    except (OSError, IndexError, ValueError):
        return None


def _stood() -> tuple:
    """Where the calling thread has stood so far while it was off the CPU,
    as the kernel counts it: (`delay_s`, `steal_s`, `nivcsw`), each None
    where the platform keeps no such count.

    `delay_s`: seconds the thread was runnable and had no CPU
    (`/proc/thread-self/schedstat`, second field, ns): the host's scheduler
    had others to run, or the CPU quota of the process's group was spent.
    `steal_s`: seconds of CPU time stolen from the machine (`/proc/stat`,
    the `cpu` line's `steal`, clock ticks of 10 ms), SUMMED over its CPUs
    as that line has it: a hypervisor ran someone else on them. A stop of
    the whole machine reads as many times its length as CPUs had work, and
    a stolen CPU other than the thread's counts too; a thread asleep through
    a theft has no run-queue wait to show, so this is the only sign of it.
    `nivcsw`: the thread's involuntary context switches (`getrusage(
    RUSAGE_THREAD)`).

    Two `pread`s and one system call, fewer where a count is not kept. The
    thread's file is opened by the thread itself, once (the path resolves to
    whoever opens it)."""
    global _proc_stat
    sched = getattr(_tls, "schedstat", None)
    if sched is None:
        sched = _tls.schedstat = _opened(_SCHEDSTAT)
    if _proc_stat is None:
        _proc_stat = _opened(_PROC_STAT)
        # a `cpu` line of zeros from end to end is a kernel that counts
        # nothing (a sandboxed one, as on the benchmark's chip machines:
        # PERF.md section 6, PR 57): no count, and not read again
        if _proc_stat and not any(_field(_proc_stat, n)
                                  for n in range(1, 9)):
            _proc_stat.close()
            _proc_stat = False
    delay, steal = _field(sched, 1), _field(_proc_stat, 8)
    nivcsw = None
    if resource is not None and hasattr(resource, "RUSAGE_THREAD"):
        nivcsw = resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw
    return (None if delay is None else delay / 1e9,
            None if steal is None else steal / _TICKS_PER_S, nivcsw)


def _on_gc(when: str, info: dict) -> None:
    """The process's one `gc.callbacks` hook. Start and stop of a collection
    run on the thread that triggered it, under the interpreter lock, and
    collections do not nest: the open pause is one module slot."""
    global _gc_open
    if when == "start":
        ann = None
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation(HOST_GC, generation=info["generation"])
            ann.__enter__()
        _gc_open = (time.perf_counter(), ann)
    elif _gc_open is not None:
        (t0, ann), _gc_open = _gc_open, None
        if ann is not None:
            ann.__exit__(None, None, None)
        _gc_seconds[info["generation"]] += time.perf_counter() - t0
        _gc_pauses[info["generation"]] += 1


def stall_log() -> list[dict]:
    """The process's stalled turns (the last `STALL_LOG`), newest last: the
    whole flight record (its `kind` and where its thread stood among it)
    plus `source`, `owner`, `cause`, `excess_ms`, `median_ms` (its kind's)."""
    with _lock:
        return list(_stalls)


def stall_totals() -> dict:
    """What the stalls are shares of. Per source (`engine` | `train`):
    `turns`, `turn_seconds`, `gc_seconds` (pauses that ended inside its
    turns), `sched_delay_seconds` / `steal_seconds` (`_stood`, over its
    turns), per cause `count`, `excess_seconds`, `longest_ms` and per kind
    of turn `kinds: {kind: {turns, turn_seconds, median_ms}}` (the running
    median its turns are judged by now, None before there is one); for the
    process the collector's pause seconds and pauses by generation."""
    with _lock:
        return {"sources": {src: {**tot, **{
                    by: {k: dict(v) for k, v in tot[by].items()}
                    for by in ("causes", "kinds")}}
                            for src, tot in _totals.items()},
                "gc_pause_seconds": list(_gc_seconds),
                "gc_pauses": list(_gc_pauses)}


def metric_families(source: str, prefix: str, host_prefix: str) -> dict:
    """The `/metrics` view of the totals, for `register_family` of a
    registry: `<prefix>_stalls_total{cause}`, `<prefix>_stall_seconds_total
    {cause}` (excess seconds) of `source`'s turns, `<host_prefix>_sched_
    delay_seconds_total{reason}` (seconds over those turns that their thread
    stood runnable without a CPU, `run_queue`, and that were stolen from
    the machine's CPUs, `steal`: `_stood`) and the process's
    `<host_prefix>_gc_pause_seconds_total{generation}`."""
    def mine():
        return stall_totals()["sources"].get(source, {})

    def causes(key):
        return {c: v[key] for c, v in mine().get("causes", {}).items()}
    return {
        f"{prefix}_stalls_total": (
            "cause", lambda: causes("count"),
            f"stalled {source} turns (over {STALL_FACTOR:g}x the running "
            "median, or compiling) by cause; /debug/timeline `stalls`"),
        f"{prefix}_stall_seconds_total": (
            "cause", lambda: causes("excess_seconds"),
            f"seconds stalled {source} turns ran over the running median"),
        f"{host_prefix}_gc_pause_seconds_total": (
            "generation",
            lambda: dict(enumerate(stall_totals()["gc_pause_seconds"])),
            "seconds the cyclic collector paused the process"),
        f"{host_prefix}_sched_delay_seconds_total": (
            "reason",
            lambda: {"run_queue": mine().get("sched_delay_seconds", 0.0),
                     "steal": mine().get("steal_seconds", 0.0)},
            f"seconds of the {source} turns their thread stood runnable "
            "without a CPU (run_queue) and CPU seconds stolen from the "
            "machine meanwhile (steal, summed over its CPUs)"),
    }


def _cause(owner: str, owner_ms: float, excess_ms: float, rec: dict,
           compiled: bool, capture_edge: bool) -> str:
    if compiled:
        return "compile"
    if capture_edge:
        return "capture"
    if rec["gc_ms"] >= 0.5 * excess_ms:
        return "gc"
    if rec.get("sched_delay_ms", 0.0) + rec.get("steal_ms", 0.0) \
            >= 0.5 * excess_ms:
        return "descheduled"
    if owner == "gap":
        return "caller"
    if rec["cpu_ms"] >= 0.5 * owner_ms:
        return "host_busy"
    if rec["cpu_ms"] < 0.1 * owner_ms:
        return "blocked"
    return "mixed"


class _Running:
    """The unstalled turns of one kind and their running median, in ms."""

    __slots__ = ("turns", "fed", "median", "stalled_in_a_row")

    def __init__(self):
        self.turns: collections.deque = collections.deque(
            maxlen=MEDIAN_TURNS)
        self.fed = 0
        self.median: Optional[float] = None
        self.stalled_in_a_row = 0

    def judge(self, turn_ms: float, compiled: bool) -> Optional[float]:
        """The turn's excess over the running median where it is stalled,
        else None; an unstalled turn feeds the median."""
        if compiled:
            return max(turn_ms - (self.median or 0.0), 0.0)
        if self.median is not None and turn_ms > STALL_FACTOR * self.median:
            self.stalled_in_a_row += 1
            excess = turn_ms - self.median
            if self.stalled_in_a_row >= REGIME_TURNS:
                self.turns.clear()
                self.fed, self.median = 0, None
            return excess
        self.stalled_in_a_row = 0
        self.turns.append(turn_ms)
        self.fed += 1
        # early on whenever the count doubles, so that a young recorder
        # judges by more than its first few turns
        if self.fed % MEDIAN_EVERY == 0 or (
                MIN_TURNS <= self.fed < MEDIAN_EVERY
                and self.fed & (self.fed - 1) == 0):
            self.median = statistics.median(self.turns)
        return None


def _grown(now, was) -> Optional[float]:
    return None if now is None or was is None else max(now - was, 0)


class FlightRecorder:
    """Thread-safe bounded ring of per-step records.

    >>> fl = FlightRecorder(capacity=4096)
    >>> fl.record(step=1, step_ms=3.7, n_live=8)
    >>> fl.entries(n=100)       # the last 100 steps
    >>> fl.dump_jsonl("runs/serve/timeline.jsonl")
    """

    def __init__(self, capacity: int = 4096, enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self.dropped = 0           # records evicted off the ring's back
        self.total = 0             # records ever written
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        # one wall-clock read at construction anchors the timeline to
        # absolute time (so `t` still correlates with server logs and
        # Prometheus scrapes); per-record stamps advance MONOTONICALLY
        # from it, so an NTP slew mid-run can never make step timestamps
        # jump backwards or overlap
        self._wall0 = time.time()  # lint: allow(wall-clock)
        self._mono0 = time.monotonic()
        # the open turn (one writer thread): its start, its entry, and the
        # marks its record takes the growth of
        self._t0: Optional[float] = None
        self._t_in = 0.0
        self._cpu0 = 0.0
        self._stood0: tuple = (None, None, None)
        self._gc0 = (0.0, (0, 0, 0))
        # a capture is the open turn's | ran at the writer's last stamp |
        # has run at every stamp of the open turn
        self._capturing = self._capture_on = self._capture_through = False
        # the running median of the unstalled turns, a kind of turn
        self._running: dict[str, _Running] = collections.defaultdict(
            _Running)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def record(self, **fields) -> None:
        """Append one step record, stamped with `t` = the construction
        wall-clock anchor plus a monotonic delta."""
        if not self.enabled:
            return
        self._append(fields)

    def _append(self, fields: dict) -> None:
        fields["t"] = round(self._wall0 + (time.monotonic() - self._mono0), 4)
        with self._lock:
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self.total += 1
            self._ring.append(fields)

    def begin_turn(self, waited: bool = False) -> float:
        """The writer enters a turn's own work; returns the `perf_counter`
        stamp of the entry. `waited`: work waited for this call since the
        last record, so the turn began THERE (its `gap_ms`) and keeps that
        record's collector mark; otherwise it begins here. A trainer calls
        this once: each of its turns begins at the last one's record."""
        self._t_in = t = time.perf_counter()
        self._capture_on = on = TraceAnnotation.is_enabled()
        if not waited or self._t0 is None:
            self._t0 = t
            self._cpu0 = time.thread_time()
            self._stood0 = _stood()
            self._gc0 = (sum(_gc_seconds), tuple(_gc_pauses))
            self._capturing = self._capture_through = on
        else:
            # a capture that ran at the last record and was stopped in the
            # gap (writing it out takes seconds) is this turn's too
            self._capturing = self._capturing or on
            self._capture_through = self._capture_through and on
        return t

    def record_turn(self, source: str, phases: dict, t1: float, *,
                    kind: Optional[str] = None, compiled: bool = False,
                    **fields) -> None:
        """Close the open turn at stamp `t1` with one record of `fields`
        plus the turn's own (module docstring), judge it among the turns of
        its `kind` (None: the source's turns are one kind), and open the
        next at `t1`. `phases` = the ms of each part an `owner` can be
        (`gap` is added here); `compiled` = a trace guard fired inside."""
        if not self.enabled:
            return
        turn_ms = (t1 - self._t0) * 1e3
        cpu = time.thread_time()
        stood = _stood()
        on = TraceAnnotation.is_enabled()
        gc_s, gc_n = sum(_gc_seconds), tuple(_gc_pauses)
        rec = fields
        if kind is not None:
            rec["kind"] = kind
        rec["t0"] = round(self._t0, 6)
        rec["turn_ms"] = round(turn_ms, 3)
        if self._t_in > self._t0:
            rec["gap_ms"] = round((self._t_in - self._t0) * 1e3, 3)
        rec["gc_ms"] = round((gc_s - self._gc0[0]) * 1e3, 3)
        if gc_n != self._gc0[1]:
            rec["gc_gen"] = max(g for g in range(3)
                                if gc_n[g] != self._gc0[1][g])
        # one read a turn (a system call: ~6 us alone on the bench host, ~37
        # beside the runtime's busy threads): a turn that waited takes the
        # growth since the last record, its caller's gap on this thread
        # included
        rec["cpu_ms"] = round(max(cpu - self._cpu0, 0.0) * 1e3, 3)
        # where the thread stood meanwhile (`_stood`), on the same stamp:
        # absent where the platform keeps no such count
        delay, steal, nivcsw = map(_grown, stood, self._stood0)
        if delay is not None:
            rec["sched_delay_ms"] = round(delay * 1e3, 3)
        if steal is not None:
            rec["steal_ms"] = round(steal * 1e3, 3)
        if nivcsw is not None:
            rec["nivcsw"] = nivcsw
        rec["capturing"] = self._capturing or on
        # started or stopped in this turn or stopped in the one before: a
        # turn that ran under a capture from end to end is judged as any
        capture_edge = rec["capturing"] and not (self._capture_through
                                                 and on)
        # the next turn opens here, whether or not its writer says so. A
        # capture that went off since the writer's last stamp (another
        # thread stopped it) is written out for seconds yet: the next
        # turn's too
        self._t0 = self._t_in = t1
        self._cpu0, self._stood0, self._gc0 = cpu, stood, (gc_s, gc_n)
        self._capturing, self._capture_on = on or self._capture_on, on
        self._capture_through = on
        kind = ONE_KIND if kind is None else kind
        running = self._running[kind]
        median = running.median or 0.0
        stall = running.judge(turn_ms, compiled)
        if stall is not None:
            parts = dict(phases, gap=rec.get("gap_ms", 0.0))
            owner = max(parts, key=parts.get)
            rec.update(source=source, owner=owner,
                       cause=_cause(owner, parts[owner], stall, rec,
                                    compiled, capture_edge),
                       excess_ms=round(stall, 3),
                       median_ms=round(median, 3))
        self._append(rec)
        with _lock:
            tot = _totals.setdefault(source, {
                "turns": 0, "turn_seconds": 0.0, "gc_seconds": 0.0,
                "sched_delay_seconds": 0.0, "steal_seconds": 0.0,
                "causes": {}, "kinds": {}})
            tot["turns"] += 1
            tot["turn_seconds"] += turn_ms / 1e3
            tot["gc_seconds"] += rec["gc_ms"] / 1e3
            tot["sched_delay_seconds"] += delay or 0.0
            tot["steal_seconds"] += steal or 0.0
            of = tot["kinds"].setdefault(kind, {
                "turns": 0, "turn_seconds": 0.0, "median_ms": None})
            of["turns"] += 1
            of["turn_seconds"] += turn_ms / 1e3
            of["median_ms"] = running.median and round(running.median, 3)
            if stall is not None:
                by = tot["causes"].setdefault(rec["cause"], {
                    "count": 0, "excess_seconds": 0.0, "longest_ms": 0.0})
                by["count"] += 1
                by["excess_seconds"] += stall / 1e3
                by["longest_ms"] = max(by["longest_ms"], rec["excess_ms"])
                _stalls.append(rec)

    def __len__(self) -> int:
        return len(self._ring)

    def entries(self, n: Optional[int] = None) -> list[dict]:
        """The last `n` records (all retained when None), oldest first."""
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-n:]

    def dump_jsonl(self, path: str) -> str:
        """Write every retained record as JSONL; returns the path."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            for rec in self.entries():
                f.write(json.dumps(rec) + "\n")
        return path
