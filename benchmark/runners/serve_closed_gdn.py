"""Runner of kind `serve_closed_gdn`: `serve_closed_patterned`'s run, for a
patterned model whose mixers are the gated delta rule with a decay a head
('G': a float32 state of (value heads, d, d) and a convolution tail a slot)
beside gated GQA over block pools ('*') in ONE cache tree, every mixer in
front of an expert layer. Nothing of that runner is copied (ROADMAP D12): its
`run` is imported and called with `serve_closed_window`'s helpers around it,
as `serve_closed_delta.py` does, whose `StateTap` this file subclasses. The
accepted `serve_closed_delta` kind could not take the cell as it is: its
probes name the letters 'K' and 'L', its tap `kda_chunk`, its counters the
latent rows. This file adds what differs, and no more:

  * the letter 'G' among the blocks `step_programs` probes (module `gdn`;
    '*' is the accepted runner's own);
  * the counters of the two kinds of mixer, read off the engine over the
    window (`GdnCounts.FIELDS`): what the 'G' layers' calls had to step
    (`kda_slot_steps_by`, the engine's one slot-step counter: live slots x
    layers of the decode calls, real chunk rows x layers of the chunk
    calls), the live rows the '*' layers' calls had to read
    (`kv_rows_read_full_by`) and the (query, key) pairs of their chunk calls
    (`chunk_attn_pairs_by`);
  * over the traced slice, what ONE call of each kernel had to read or
    compute (`flops_qwen3next`), for the four rooflines;
  * resident bytes by kind (weights, state, tails, pools) beside what the
    shapes say, the first wave of chunk programs (the mix's `warm_s` stands
    behind it) and the paths the two step programs took, said in every run;
  * a fourth procedure of `correct`, `slot_state`, as the delta runner's:
    the `state` leaf a judged slot holds after its chunk and decode programs
    against `reference_qwen3next.gdn_state_after` over the recurrence's OWN
    operands as the probed programs handed them to `ops/delta_rule.py`
    (`gdn_chunk`; `kda_step`, whose decay arrives broadcast over a head's
    channels and is read back as the scalar it is).

Every import of the program is the accepted runner's: a program from before
this configuration's PR leaves in `build_engine` (it lacks the pattern
letter), with its message and at once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import compared
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners.serve_closed_delta import StateTap
from benchmark.runners.serve_closed_latent import first_wave
from benchmark.runners.serve_closed_window import (KINDS, _check_base,
                                                   _patched)

_MIXER_MODULES = {"G": "gdn"}


class GdnCounts(base.GraniteCounts):
    FIELDS = base.GraniteCounts.FIELDS + (
        "chunk_attn_pairs_by.full",
        *(f"{name}.{kind}" for name in ("kv_rows_read_full_by",
                                        "kda_slot_steps_by")
          for kind in KINDS))


def gdn_counters(grew: dict) -> dict:
    """The window's counters of the two kinds of mixer, from the growth of
    the engine's lifetime counts."""
    return {"gdn_slot_steps": sum(grew[f"kda_slot_steps_by.{k}"]
                                  for k in KINDS),
            "kv_rows_read_full": sum(grew[f"kv_rows_read_full_by.{k}"]
                                     for k in KINDS),
            "chunk_attn_pairs": grew["chunk_attn_pairs_by.full"]}


def kernel_work(sl: dict, llm: dict, flops, itemsize: int) -> dict:
    """What ONE call of each kernel had to move or compute, mean over the
    calls of the traced slice (`sl`: the growth of the engine's counts over
    it). Every program makes one decode call a layer, a chunk-carrying one
    a chunk call a layer beside it. (`paged_decode_bytes_per_call` is the
    accepted runner's, from the same module.)"""
    n_g = llm["layer_pattern"].count("G")
    n_a = llm["layer_pattern"].count("*")
    steps, chunks = max(sl["n_steps"], 1), max(sl["chunk_programs"], 1)
    return {
        "gdn_step_bytes_per_call": flops.gdn_step_bytes_per_call(
            llm, sl["kda_slot_steps_by.decode"] / n_g / steps),
        "gdn_chunk_bytes_per_call": flops.gdn_chunk_bytes_per_call(
            llm, sl["kda_slot_steps_by.chunk"] / n_g / chunks, itemsize)
        if sl["chunk_programs"] else 0.0,
        # chunk calls a step program of the slice, one a 'G' layer of the
        # programs that carried a chunk
        "gdn_chunk_calls_per_step": n_g * sl["chunk_programs"] / steps,
        "paged_prefill_ops_per_call": flops.chunk_attention_ops(
            llm, sl["chunk_attn_pairs_by.full"] / n_a / chunks)
        if sl["chunk_programs"] else 0.0}


def resident_by_kind(engine) -> dict:
    """Bytes the engine holds between programs: the weights, and the cache
    tree's leaves by what they are (a 'G' layer's `state` and `tail`, a '*'
    layer's pools)."""
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))
    by = {"weights": nbytes(engine.variables), "gdn_state": 0,
          "gdn_tails": 0, "kv_pools": 0}
    for kind, leaf in zip(engine.cfg.layer_pattern, engine.caches):
        if kind == "G":
            by["gdn_state"] += nbytes(leaf["state"])
            by["gdn_tails"] += nbytes(leaf["tail"])
        elif kind == "*":
            by["kv_pools"] += nbytes(leaf)
    return by


class GdnTap(StateTap):
    """`serve_closed_delta.StateTap` over the 'G' layers' two calls: a chunk
    is `gdn_chunk`'s (q, k, v, g (T, H), beta), a token `kda_step`'s, whose g
    (S, H, d_k) is the head's scalar broadcast over its channels."""

    CALLS = {"gdn_chunk": slice(0, 5), "kda_step": slice(1, 6)}

    def _keep(self, args, caches, seen) -> None:
        # the delta tap's book-keeping reads a chunk's operands under its
        # own name and a decay a channel: hand it both in its shapes
        seen = {"kda_chunk": seen["gdn_chunk"],
                "kda_step": [(q, k, v, g[..., 0], beta)
                             for q, k, v, g, beta in seen["kda_step"]]}
        super()._keep(args, caches, seen)


def slot_state_check(ctx, tap: StateTap, llm: dict, faults=()) -> dict:
    """Per judged slot and 'G' layer, rms(the slot's `state` leaf - the
    reference's state) / rms(the reference's), the reference's from zeros
    over the operands the slot's programs were handed; the worst must lie
    within `state_error`."""
    ref = base._lib(ctx["traffic"]["reference"])
    assert tap.caches is not None, "slot_state reads step_programs' drive: " \
        "name it right behind"
    layers = [i for i, kind in enumerate(llm["layer_pattern"])
              if kind == "G"]
    by_slot = {}
    for s, calls in tap.rows.items():
        assert calls and all(len(call) == len(layers) for call in calls), \
            "a `gdn_chunk` or `kda_step` call a 'G' layer and program"
        errs = []
        for n, i in enumerate(layers):
            want = ref.gdn_state_after(
                *(np.concatenate([call[n][j] for call in calls])
                  for j in range(5)), faults=tuple(faults))
            d = tap.caches[i]["state"][s].astype(jnp.float32) - want
            errs.append(float(jnp.sqrt(jnp.mean(d * d)
                                       / jnp.mean(want * want))))
        by_slot[s] = errs
    got = {"by_slot": by_slot, "worst": max(map(max, by_slot.values())),
           "rows": {s: sum(len(call[0][0]) for call in calls)
                    for s, calls in tap.rows.items()}}
    return {**got, "ok": all(c["ok"] for c in slot_state_numbers(
        got, ctx["traffic"]["reference_limits"]))}


def slot_state_numbers(got: dict, lim: dict) -> list:
    return [compared.entry("state_error.G", got["worst"],
                           lim["state_error"]["G"], "at_most")]


def _say_slot_state(say, lim: dict, got: dict) -> None:
    say(f"reference, the state a slot is left with: rms(the `state` leaf - "
        f"the literal recurrence from zeros over the operands the slot's "
        f"programs handed `gdn_chunk` and `kda_step`) / rms(the "
        f"recurrence's), worst 'G' layer and slot {got['worst']:.3g} "
        f"(tolerance {lim['state_error']['G']}); by slot "
        + str({s: [float("%.3g" % e) for e in errs]
               for s, errs in got["by_slot"].items()})
        + f" over {got['rows']} rows")


def run(ctx: dict) -> dict:
    say = ctx["say"]
    held: dict = {}
    tap = GdnTap(ctx["traffic"]["engine"]["n_slots"])

    class Counts(GdnCounts):
        def __init__(self, engine):
            super().__init__(engine)
            held["timed"], held["engine"] = self, engine
            by = resident_by_kind(engine)
            say(f"resident bytes by kind: {by} = {sum(by.values())} "
                f"({100.0 * sum(by.values()) / ctx['peaks']['hbm_bytes']:.1f}"
                "% of the chip)")

    async def drive(*args):
        held["marks"] = await held["drive"](*args)
        return held["marks"]

    assert ctx["traffic"]["reference_procedures"][-2:] == [
        "step_programs", "slot_state"], \
        "step_programs consumes the engine's cache tree and slot_state " \
        "reads its drive: name them last, in this order"
    _check_base()
    assert all(len(p) == 3 for p in base.PROCEDURES.values()), \
        "a procedure is (check, say, numbers)"
    held["drive"] = base._drive
    slot_state = (lambda ctx, engine, llm, variables, vocab:
                  slot_state_check(ctx, tap, llm),
                  _say_slot_state, slot_state_numbers)
    with _patched(GraniteCounts=Counts, _drive=drive, _probed=tap.probe,
                  _MIXER_MODULES={**base._MIXER_MODULES, **_MIXER_MODULES},
                  PROCEDURES={**base.PROCEDURES, "slot_state": slot_state}):
        out = base.run(ctx)
    timed, engine, marks = held["timed"], held["engine"], held["marks"]
    llm = ctx["config"]["llm_config"]
    flops = base._lib(ctx["traffic"]["flops"])
    counters = out["observations"]["counters"]
    grew = timed.between(marks["t_open"], marks["t_close"])
    counters.update(gdn_counters(grew))
    itemsize = np.dtype(engine.cache_dtype).itemsize
    n_g = llm["layer_pattern"].count("G")
    n_a = llm["layer_pattern"].count("*")
    steps = max(grew["n_steps"], 1)
    say(f"a slot keeps {n_g} states x {flops.gdn_state_bytes(llm)} B + "
        f"{n_g} tails x {flops.gdn_tail_bytes(llm, itemsize)} B whatever "
        f"its context, and {n_a} x {flops.kv_bytes_per_row(llm, itemsize)} B "
        f"of pool a live row")
    say(f"the mixers in the window: slot steps of the 'G' layers' calls "
        f"{counters['gdn_slot_steps']} (decode "
        f"{grew['kda_slot_steps_by.decode']} = "
        f"{grew['kda_slot_steps_by.decode'] / n_g / steps:.1f} live slots a "
        f"call, chunk rows {grew['kda_slot_steps_by.chunk']}), live rows "
        f"the '*' layers' calls had to read {counters['kv_rows_read_full']} "
        f"(decode {grew['kv_rows_read_full_by.decode']} = "
        f"{grew['kv_rows_read_full_by.decode'] / n_a / steps:.0f} rows a "
        f"call, chunk {grew['kv_rows_read_full_by.chunk']}), (query, key) "
        f"pairs of the chunk calls {counters['chunk_attn_pairs']}")
    warm_s = ctx["traffic"]["warm_s"]
    wave = first_wave(timed, marks["t_open"] - warm_s)
    ended = sum(1 for row in timed.counts
                if marks["t_open"] - warm_s <= row[0] < marks["t_open"])
    say(f"the first wave: {wave[0]} chunk-carrying programs, drained "
        f"{wave[1]:.2f} s after the clients started; {ended} programs "
        f"before the window opened at {warm_s} s")
    from distributed_pytorch_tpu.obs import paths
    chosen = paths.choices()
    say(f"paths the programs traced in this process took: {chosen}")
    say("attention calls that fell back to paged_gather or the masked XLA "
        f"path: {sum('gather' in v for v in chosen.values())}")
    if ctx["trace"]:
        sl = timed.between(marks["t_trace0"], marks["t_trace1"])
        counters.update(kernel_work(sl, llm, flops, itemsize))
    return out
