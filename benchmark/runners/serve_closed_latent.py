"""Runner of kind `serve_closed_latent`: `serve_closed_patterned`'s run, for a
patterned model whose attention is latent ('L': ONE pool leaf of latent rows a
layer, no head axis, no per-slot state) in front of a dense FFN or an expert
layer. Nothing of that runner is copied (ROADMAP D12): its `run` is imported
and called, with `serve_closed_window`'s helpers around it, as
`serve_closed_parallel.py` does; this file adds what both lack, and no more:

  * the letter 'L' among the blocks `step_programs` probes (an 'L' block's
    mixer is the module `latent_attn`);
  * two counters, read off the engine over the window as the expert counters
    are (`LatentCounts.FIELDS`): the live latent rows the decode and the chunk
    calls had to read (`latent_rows_read_by`, booked at the plan from the
    planned lengths), the (query, key) pairs the chunk calls' masks let
    through (`chunk_attn_pairs_by`);
  * over the traced slice, what ONE call of each latent kernel had to read or
    compute (`flops_joyai`), for the two rooflines;
  * resident bytes by kind of state, the latent pools' beside what the
    mathematics needs of them, when the first wave of chunk programs had
    drained (the mix's `warm_s` stands behind it), and the paths the two
    step programs took, said in every run;
  * `serve_closed_window._donating` as the probe: 9.4 GB of pools cannot be
    held twice. The engine's own tree is consumed by the first probed call,
    so `step_programs` is the mix's last procedure.
"""

from __future__ import annotations

import numpy as np

from benchmark.runners import serve_closed_patterned as base
from benchmark.runners.serve_closed_window import (KINDS, _check_base,
                                                   _donating, _patched)


class LatentCounts(base.GraniteCounts):
    FIELDS = base.GraniteCounts.FIELDS + (
        "chunk_attn_pairs_by.full",
        *(f"latent_rows_read_by.{kind}" for kind in KINDS))


def latent_counters(grew: dict) -> dict:
    """The window's counters of the latent layers, from the growth of the
    engine's lifetime counts."""
    return {"latent_rows_read": sum(grew[f"latent_rows_read_by.{k}"]
                                    for k in KINDS),
            "chunk_attn_pairs": grew["chunk_attn_pairs_by.full"]}


def kernel_work(sl: dict, llm: dict, flops, itemsize: int) -> dict:
    """What ONE call of each latent kernel had to move or compute, mean
    over the calls of the traced slice (`sl`: the growth of the engine's
    counts over it). Every program makes one decode call a latent layer,
    a chunk-carrying one a chunk call a layer beside it."""
    n = llm["layer_pattern"].count("L")
    return {
        "latent_decode_bytes_per_call": flops.latent_decode_bytes_per_call(
            llm, sl["latent_rows_read_by.decode"] / n
            / max(sl["n_steps"], 1), itemsize),
        "latent_prefill_ops_per_call": flops.chunk_attention_ops(
            llm, sl["chunk_attn_pairs_by.full"] / n
            / max(sl["chunk_programs"], 1))}


def first_wave(timed, t_start: float) -> tuple:
    """(chunk programs, seconds after `t_start`) of the first wave: the
    chunk-carrying programs the engine drained before its first plain one
    since `t_start` (every slot's first prompt prefilled, all slots
    decoding). The mix's `warm_s` is set behind it."""
    col = 1 + timed.FIELDS.index("chunk_programs")
    before = [c for c in timed.counts if c[0] < t_start]
    base_n = before[-1][col] if before else 0
    last = base_n
    for row in timed.counts[len(before):]:
        if row[col] == last:
            return last - base_n, row[0] - t_start
        last = row[col]
    return last - base_n, float("nan")


def run(ctx: dict) -> dict:
    say = ctx["say"]
    held: dict = {}

    class Counts(LatentCounts):
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
                  _MIXER_MODULES={**base._MIXER_MODULES, "L": "latent_attn"}):
        out = base.run(ctx)
    timed, engine, marks = held["timed"], held["engine"], held["marks"]
    llm = ctx["config"]["llm_config"]
    flops = base._lib(ctx["traffic"]["flops"])
    counters = out["observations"]["counters"]
    grew = timed.between(marks["t_open"], marks["t_close"])
    counters.update(latent_counters(grew))
    by = engine.resident_bytes_by_kind
    itemsize = np.dtype(engine.cache_dtype).itemsize
    rows = engine.n_blocks * engine.block_size
    n = llm["layer_pattern"].count("L")
    say(f"resident bytes by kind of state: {by} = {sum(by.values())} "
        f"({100.0 * sum(by.values()) / ctx['peaks']['hbm_bytes']:.1f}% of "
        f"the chip); the latent pools keep {rows} rows x {n} layers x "
        f"{flops.pool_row_bytes(llm, itemsize)} B (whole 128-lane tiles) = "
        f"{rows * flops.kv_bytes_per_row(llm, itemsize)} B, of which the "
        f"mathematics needs {flops.latent_row_bytes(llm, itemsize)} B a row "
        "a layer")
    say(f"latent attention in the window: live rows the calls had to read "
        f"{counters['latent_rows_read']} (decode "
        f"{grew['latent_rows_read_by.decode']}, chunk "
        f"{grew['latent_rows_read_by.chunk']}), (query, key) pairs of the "
        f"chunk calls {counters['chunk_attn_pairs']}; a decode call reads "
        f"{grew['latent_rows_read_by.decode'] / n / max(grew['n_steps'], 1):.0f}"
        f" rows, a chunk call sees "
        f"{grew['chunk_attn_pairs_by.full'] / n / max(grew['chunk_programs'], 1):.0f}"
        " pairs")
    warm_s = ctx["traffic"]["warm_s"]
    wave = first_wave(timed, marks["t_open"] - warm_s)
    say(f"the first wave: {wave[0]} chunk-carrying programs, drained "
        f"{wave[1]:.2f} s after the clients started; the window opened at "
        f"{warm_s} s")
    from distributed_pytorch_tpu.obs import paths
    chosen = paths.choices()
    say(f"paths the programs traced in this process took: {chosen}")
    say("attention calls that fell back to paged_gather or the masked XLA "
        f"path: {sum('gather' in v for v in chosen.values())}")
    if ctx["trace"]:
        sl = timed.between(marks["t_trace0"], marks["t_trace1"])
        counters.update(kernel_work(sl, llm, flops, itemsize))
    return out
