"""Runner of kind `serve_closed_delta`: `serve_closed_patterned`'s run, for a
patterned model whose mixers are delta-rule linear attention ('K': a float32
state of (heads, d, d) and a convolution tail a slot) beside latent attention
('L': ONE pool leaf of latent rows a layer) in ONE cache tree, in front of a
dense FFN or an expert layer. Nothing of that runner is copied (ROADMAP D12):
its `run` is imported and called, with `serve_closed_window`'s helpers around
it, as `serve_closed_latent.py` does; this file adds what they lack, and no
more:

  * the letters 'K' and 'L' among the blocks `step_programs` probes (their
    mixers are the modules `kda` and `latent_attn`);
  * three counters, read off the engine over the window as the expert
    counters are (`DeltaCounts.FIELDS`): what the 'K' layers' calls had to
    step (`kda_slot_steps_by`: live slots x layers of the decode calls, real
    chunk rows x layers of the chunk calls, booked from the plan), the live
    latent rows the 'L' layers' calls had to read (`latent_rows_read_by`),
    the (query, key) pairs of their chunk calls (`chunk_attn_pairs_by`);
  * over the traced slice, what ONE call of each kernel had to read or
    compute (`flops_ling`), for the four rooflines;
  * resident bytes by kind (weights, state, tails, latent pool) beside what
    the shapes say, the first wave of chunk programs (the mix's `warm_s`
    stands behind it) and the paths the two step programs took, said in
    every run;
  * a probe that DONATES the cache tree, as `serve_closed_window._donating`
    does: the state and the pool cannot be held twice beside 5.6 GB of
    weights. The engine's own tree is consumed by the first probed call, so
    `step_programs` comes after every procedure that runs the engine;
  * a fourth procedure of `correct`, `slot_state`, right behind
    `step_programs` and read off the same drive (`StateTap`): the `state`
    leaf a judged slot holds after its chunk and decode programs against
    `reference_ling.kda_state_after` over the recurrence's OWN operands
    (q, k, v, the log decay, beta) as the probed programs handed them to
    `ops/delta_rule.py`. A block's output cannot tell a float32 state from
    a bfloat16 one (the operands' bf16 rounding, 0.4%, lies over a state's
    2^-9); with the operands common to both sides the state's own
    arithmetic and the precision it is kept in are all that is left.

Every import of the program is the accepted runner's: a program from before
this configuration's PR leaves in `build_engine` (it lacks the pattern letter
and the router's group fields), with its message and at once.
"""

from __future__ import annotations

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import compared
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners.serve_closed_latent import first_wave
from benchmark.runners.serve_closed_window import (KINDS, _check_base,
                                                   _patched, _probed)

_MIXER_MODULES = {"K": "kda", "L": "latent_attn"}


class DeltaCounts(base.GraniteCounts):
    FIELDS = base.GraniteCounts.FIELDS + (
        "chunk_attn_pairs_by.full",
        *(f"{name}.{kind}" for name in ("latent_rows_read_by",
                                        "kda_slot_steps_by")
          for kind in KINDS))


def delta_counters(grew: dict) -> dict:
    """The window's counters of the two kinds of mixer, from the growth of
    the engine's lifetime counts."""
    return {"kda_slot_steps": sum(grew[f"kda_slot_steps_by.{k}"]
                                  for k in KINDS),
            "latent_rows_read": sum(grew[f"latent_rows_read_by.{k}"]
                                    for k in KINDS),
            "chunk_attn_pairs": grew["chunk_attn_pairs_by.full"]}


def kernel_work(sl: dict, llm: dict, flops, itemsize: int) -> dict:
    """What ONE call of each kernel had to move or compute, mean over the
    calls of the traced slice (`sl`: the growth of the engine's counts over
    it). Every program makes one decode call a layer, a chunk-carrying one
    a chunk call a layer beside it."""
    n_k = llm["layer_pattern"].count("K")
    n_l = llm["layer_pattern"].count("L")
    steps, chunks = max(sl["n_steps"], 1), max(sl["chunk_programs"], 1)
    return {
        "kda_step_bytes_per_call": flops.kda_step_bytes_per_call(
            llm, sl["kda_slot_steps_by.decode"] / n_k / steps),
        "kda_chunk_bytes_per_call": flops.kda_chunk_bytes_per_call(
            llm, sl["kda_slot_steps_by.chunk"] / n_k / chunks, itemsize)
        if sl["chunk_programs"] else 0.0,
        # chunk calls a step program of the slice, one a 'K' layer of the
        # programs that carried a chunk
        "kda_chunk_calls_per_step": n_k * sl["chunk_programs"] / steps,
        "latent_decode_bytes_per_call": flops.latent_decode_bytes_per_call(
            llm, sl["latent_rows_read_by.decode"] / n_l / steps, itemsize),
        "latent_prefill_ops_per_call": flops.chunk_attention_ops(
            llm, sl["chunk_attn_pairs_by.full"] / n_l / chunks)}


def resident_by_kind(engine) -> dict:
    """Bytes the engine holds between programs: the weights, and the cache
    tree's leaves by what they are (a 'K' layer's `state` and `tail`, an
    'L' layer's pool)."""
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))
    by = {"weights": nbytes(engine.variables), "kda_state": 0,
          "kda_tails": 0, "latent_pools": 0}
    for kind, leaf in zip(engine.cfg.layer_pattern, engine.caches):
        if kind == "K":
            by["kda_state"] += nbytes(leaf["state"])
            by["kda_tails"] += nbytes(leaf["tail"])
        elif kind == "L":
            by["latent_pools"] += nbytes(leaf)
    return by


# ---------------------------------------------------------------------------
# `correct`, a fourth procedure: the state a slot is left with
# ---------------------------------------------------------------------------

class StateTap:
    """The probe of `step_programs` (as `serve_closed_window._donating`:
    the cache tree donated, the results waited for), and beside it what
    `slot_state` needs of the same drive: for the slots it judges, the
    operands every 'K' layer's `kda_chunk` and `kda_step` call was handed,
    in the order of the programs, and the cache tree the last program
    returned. The two functions are wrapped while a program is TRACED and
    hand their arguments on untouched: the program's ops are the ones the
    window timed, with five more results a call."""

    #: (the function, where q, k, v, g, beta stand among its arguments)
    CALLS = {"kda_chunk": slice(0, 5), "kda_step": slice(1, 6)}

    def __init__(self, n_slots: int):
        # the first, the middle and the last slot, and the one
        # `step_program_rows` admits late, beside plain programs
        self.slots = sorted({0, 1, n_slots // 2, n_slots - 1})
        self.rows = {s: [] for s in self.slots}     # [[(q, k, v, g, beta)
        self.caches = None                          #   a 'K' layer] a call]

    def probe(self, step):
        from distributed_pytorch_tpu.ops import delta_rule
        inner = _probed(step).__wrapped__
        pick = jnp.asarray(self.slots)

        def probed(*args):
            seen = {name: [] for name in self.CALLS}

            def tapped(name):
                fn = getattr(delta_rule, name)

                def call(*a, **kw):
                    seen[name].append(a[self.CALLS[name]])
                    return fn(*a, **kw)
                return call

            with mock.patch.multiple(
                    delta_rule, **{name: tapped(name) for name in seen}):
                results, got = inner(*args)
            # a decode call's operands of the judged slots alone
            seen["kda_step"] = [tuple(t[pick] for t in ops)
                                for ops in seen["kda_step"]]
            return results, got, seen

        jitted = jax.jit(probed, donate_argnums=(1,))

        def run(*args):
            results, got, seen = jax.block_until_ready(jitted(*args))
            self._keep(args, results[0], seen)
            return results, got

        return run

    def _keep(self, args, caches, seen) -> None:
        self.caches = caches
        was = np.asarray(args[4])                   # live before the call
        if len(args) > 9:                           # a chunk at offset 0
            slot, valid = int(args[10]), int(np.asarray(args[12])[0])
            assert int(args[11]) == 0, "a first chunk starts from zeros"
            if slot in self.rows:
                self.rows[slot] = [[tuple(np.asarray(t)[:valid]
                                          for t in ops)
                                    for ops in seen["kda_chunk"]]]
        for j, s in enumerate(self.slots):
            if was[s]:
                self.rows[s].append([tuple(np.asarray(t)[j:j + 1]
                                           for t in ops)
                                     for ops in seen["kda_step"]])


def slot_state_check(ctx, tap: StateTap, llm: dict, faults=()) -> dict:
    """Per judged slot and 'K' layer, rms(the slot's `state` leaf - the
    reference's state) / rms(the reference's), the reference's from zeros
    over the operands the slot's programs were handed; the worst must lie
    within `state_error`."""
    ref = base._lib(ctx["traffic"]["reference"])
    assert tap.caches is not None, "slot_state reads step_programs' drive: " \
        "name it right behind"
    layers = [i for i, kind in enumerate(llm["layer_pattern"])
              if kind == "K"]
    by_slot = {}
    for s, calls in tap.rows.items():
        assert calls and all(len(call) == len(layers) for call in calls), \
            "a `kda_chunk` or `kda_step` call a 'K' layer and program"
        errs = []
        for n, i in enumerate(layers):
            want = ref.kda_state_after(
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
    return [compared.entry("state_error.K", got["worst"],
                           lim["state_error"]["K"], "at_most")]


def _say_slot_state(say, lim: dict, got: dict) -> None:
    say(f"reference, the state a slot is left with: rms(the `state` leaf - "
        f"the literal recurrence from zeros over the operands the slot's "
        f"programs handed `kda_chunk` and `kda_step`) / rms(the "
        f"recurrence's), worst 'K' layer and slot {got['worst']:.3g} "
        f"(tolerance {lim['state_error']['K']}); by slot "
        f"{ {s: [float('%.3g' % e) for e in errs] for s, errs in got['by_slot'].items()} } "
        f"over {got['rows']} rows")


def run(ctx: dict) -> dict:
    say = ctx["say"]
    held: dict = {}
    tap = StateTap(ctx["traffic"]["engine"]["n_slots"])

    class Counts(DeltaCounts):
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
    counters.update(delta_counters(grew))
    itemsize = np.dtype(engine.cache_dtype).itemsize
    n_k = llm["layer_pattern"].count("K")
    n_l = llm["layer_pattern"].count("L")
    steps = max(grew["n_steps"], 1)
    say(f"a slot keeps {n_k} states x {flops.kda_state_bytes(llm)} B + "
        f"{n_k} tails x {flops.kda_tail_bytes(llm, itemsize)} B whatever "
        f"its context, and {n_l} x {flops.pool_row_bytes(llm, itemsize)} B "
        f"of latent pool a row (the mathematics needs "
        f"{flops.latent_row_bytes(llm, itemsize)})")
    say(f"the mixers in the window: slot steps of the 'K' layers' calls "
        f"{counters['kda_slot_steps']} (decode "
        f"{grew['kda_slot_steps_by.decode']} = "
        f"{grew['kda_slot_steps_by.decode'] / n_k / steps:.1f} live slots a "
        f"call, chunk rows {grew['kda_slot_steps_by.chunk']}), live latent "
        f"rows the 'L' layers' calls had to read "
        f"{counters['latent_rows_read']} (decode "
        f"{grew['latent_rows_read_by.decode']} = "
        f"{grew['latent_rows_read_by.decode'] / n_l / steps:.0f} rows a "
        f"call, chunk {grew['latent_rows_read_by.chunk']}), (query, key) "
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
