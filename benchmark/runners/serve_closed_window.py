"""Runner of kind `serve_closed_window`: `serve_closed_patterned`'s run, for a
patterned model with window attention layers ('W': a ring a slot beside the
block pools). Nothing of that runner is copied (ROADMAP D12): its `run` is
imported and called; this file adds what it lacks, and no more:

  * the letter 'W' among the mixers its `step_programs` procedure probes
    (`_MIXER_MODULES`: a 'W' block's mixer is the module `attn` too);
  * the window's counters, read off the engine over the window as the
    expert counters are (`WindowCounts.FIELDS`): key rows the full layers'
    and the window layers' attention calls read, rows the window saved;
  * over the traced slice, what ONE call of each attention kernel had to
    read or compute (`flops_laguna`), for the four rooflines;
  * resident bytes by kind of state and the paths the two step programs
    took, said;
  * a probe that DONATES the cache tree it is handed (`_donating`): the
    `step_programs` procedure jits the engine's step functions once more
    without donation, which holds every pool twice, and this cell's pools
    are 8.6 GB of a 16 GB chip (the first run on the chip ended there,
    `RESOURCE_EXHAUSTED`; PERF.md section 6, PR 49). The engine's own tree
    is consumed by the first probed call, so the procedure has to be the
    mix's last.

Four module names of that runner are replaced for the length of a run
(`GraniteCounts`, `_drive`, `_probed`, `_MIXER_MODULES`) and put back;
`_check_base` holds them to the shapes counted on before a run starts.
"""

from __future__ import annotations

import contextlib
import inspect

import jax

from benchmark.runners import serve_closed_patterned as base

KINDS = ("chunk", "decode")


class WindowCounts(base.GraniteCounts):
    FIELDS = base.GraniteCounts.FIELDS + (
        "window_rows_saved",
        "chunk_attn_pairs_by.full", "chunk_attn_pairs_by.window",
        *(f"{name}.{kind}" for name in ("kv_rows_read_full_by",
                                        "kv_rows_read_window_by")
          for kind in KINDS))


_probed = base._probed


def _check_base() -> None:
    """The four private names of the accepted runner that `run` replaces
    for the length of a run, held to the shapes this file counts on, so
    that a move over there fails HERE and by name, not silently (they go
    when a `benchmark` PR brings the four repairs of PERF.md section 7)."""
    def params(fn):
        return list(inspect.signature(fn).parameters)
    assert params(base._probed) == ["step"], params(base._probed)
    assert hasattr(base._probed(lambda *a: a), "__wrapped__"), \
        "`_probed` returns a jitted function"
    assert inspect.iscoroutinefunction(base._drive) and params(
        base._drive) == ["ctx", "engine", "timed", "vocab", "records"], \
        params(base._drive)
    assert isinstance(base._MIXER_MODULES, dict) \
        and base._MIXER_MODULES.get("*") == "attn", base._MIXER_MODULES
    counts = base.GraniteCounts
    assert params(counts.__init__) == ["self", "engine"] \
        and {"chunk_programs", "n_steps"} <= set(counts.FIELDS) \
        and callable(counts.between) and callable(counts._read), counts


def _waited(fn):
    """`fn`, its results waited for. `step_program_rows` advances its
    numpy `pos` in place right behind a call, and on the CPU a
    `jnp.asarray` of a numpy array that happens to lie aligned IS that
    array: a program still running then reads the next step's positions
    (the rehearsal failed one run in three by it)."""
    return lambda *args: jax.block_until_ready(fn(*args))


def _donating(step):
    """`base._probed(step)`, the cache tree (argument 1) donated."""
    return _waited(jax.jit(_probed(step).__wrapped__, donate_argnums=(1,)))


@contextlib.contextmanager
def _patched(**names):
    """`base`'s module names replaced for one run."""
    saved = {k: getattr(base, k) for k in names}
    for k, v in names.items():
        setattr(base, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(base, k, v)


def window_counters(grew: dict, llm: dict) -> dict:
    """The window's counters of the attention layers, from the growth of
    the engine's lifetime counts."""
    full = sum(grew[f"kv_rows_read_full_by.{k}"] for k in KINDS)
    window = sum(grew[f"kv_rows_read_window_by.{k}"] for k in KINDS)
    saved = grew["window_rows_saved"]
    return {"kv_rows_read_full": full, "kv_rows_read_window": window,
            "window_rows_saved": saved,
            "window_rows_saved_pct": 100.0 * saved / max(saved + window, 1)}


def kernel_work(sl: dict, llm: dict, flops, chunk: int,
                itemsize: int) -> dict:
    """What ONE call of each of the four attention kernels had to move or
    compute, mean over the calls of the traced slice (`sl`: the growth of
    the engine's counts over it)."""
    n_full = llm["layer_pattern"].count("*")
    n_win = llm["layer_pattern"].count("W")
    plain_and_chunk = max(sl["n_steps"], 1)
    chunks = max(sl["chunk_programs"], 1)
    out = {"window_decode_bytes_per_call": flops.window_decode_bytes_per_call(
        llm, sl["kv_rows_read_window_by.decode"] / n_win / plain_and_chunk,
        itemsize)}
    # a chunk call's keys: the rows before the chunk and its own; its
    # operations: the pairs its mask lets through, of its real rows
    for name, kind, rows, pairs in (
            ("paged_prefill", "*", sl["kv_rows_read_full_by.chunk"] / n_full,
             sl["chunk_attn_pairs_by.full"] / n_full),
            ("window_prefill", "W",
             sl["kv_rows_read_window_by.chunk"] / n_win,
             sl["chunk_attn_pairs_by.window"] / n_win)):
        out[f"{name}_ops_per_call"] = flops.chunk_attention_ops(
            llm, kind, pairs / chunks)
        out[f"{name}_bytes_per_call"] = flops.chunk_attention_bytes(
            llm, rows / chunks, chunk, kind, itemsize)
    return out


def run(ctx: dict) -> dict:
    say = ctx["say"]
    held: dict = {}

    class Counts(WindowCounts):
        def __init__(self, engine):
            super().__init__(engine)
            held["timed"], held["engine"] = self, engine

    async def drive(*args):
        held["marks"] = await held["drive"](*args)
        return held["marks"]

    assert "step_programs" not in ctx["traffic"]["reference_procedures"][
        :-1], "step_programs consumes the engine's cache tree: name it last"
    _check_base()
    held["drive"] = base._drive
    with _patched(GraniteCounts=Counts, _drive=drive, _probed=_donating,
                  _MIXER_MODULES={**base._MIXER_MODULES, "W": "attn"}):
        out = base.run(ctx)
    timed, engine, marks = held["timed"], held["engine"], held["marks"]
    llm = ctx["config"]["llm_config"]
    flops = base._lib(ctx["traffic"]["flops"])
    counters = out["observations"]["counters"]
    grew = timed.between(marks["t_open"], marks["t_close"])
    counters.update(window_counters(grew, llm))
    by = engine.resident_bytes_by_kind
    say(f"resident bytes by kind of state: {by} = {sum(by.values())} "
        f"({100.0 * sum(by.values()) / ctx['peaks']['hbm_bytes']:.1f}% of "
        f"the chip); a slot's ring in a window layer "
        f"{by['window'] // max(engine.n_slots, 1)} B over "
        f"{llm['layer_pattern'].count('W')} layers, whatever max_len")
    say(f"attention in the window: key rows read by the full layers' calls "
        f"{counters['kv_rows_read_full']}, by the window layers' "
        f"{counters['kv_rows_read_window']}, rows the window saved "
        f"{counters['window_rows_saved']} "
        f"({counters['window_rows_saved_pct']:.2f}% of what those layers "
        f"would have read of a whole history); a window decode call reads "
        f"{grew['kv_rows_read_window_by.decode'] / max(grew['n_steps'], 1) / max(llm['layer_pattern'].count('W'), 1) / max(engine.n_slots, 1) / engine.block_size:.2f} "
        f"tiles of {engine.block_size} rows a slot")
    from distributed_pytorch_tpu.obs import flight, paths
    # the window opens `warm_s` of wall time into a schedule that is not
    # stationary, so a freeze BEFORE it moves what the window holds (a run
    # read 5% low so, PERF.md section 6, PR 49) and the in-window list
    # above is blind to it
    causes = flight.stall_totals()["sources"].get("engine", {}).get(
        "causes", {})
    say("the engine's stalled turns over the process's life, by cause "
        "(count, excess s, longest ms): "
        f"{ {c: (v['count'], round(v['excess_seconds'], 3), round(v['longest_ms'], 1)) for c, v in causes.items()} }")
    chosen = paths.choices()
    say(f"paths the programs traced in this process took: {chosen}")
    say("attention calls that fell back to paged_gather or the masked XLA "
        f"path: {sum('gather' in v for v in chosen.values())}")
    if ctx["trace"]:
        import numpy as np
        sl = timed.between(marks["t_trace0"], marks["t_trace1"])
        counters.update(kernel_work(
            sl, llm, flops, engine.prefill_chunk,
            np.dtype(engine.cache_dtype).itemsize))
    return out
