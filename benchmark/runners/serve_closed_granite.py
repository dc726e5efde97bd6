"""Runner of kind `serve_closed_granite`: the closed loop of `serve_closed`
(its `_drive`, its clock, its warm-up, by import) over a Granite 4.0-H
shaped patterned model, and of `serve_closed_hybrid` what serves unchanged:
the counting engine (extended by the counters this configuration brought)
and the two jitted drivers of the cache-path check.

What it brings of its own: the engine build (a bf16 tree from the seed,
its embedding settled: `settle_embedding`), the two checks of `correct`
against `benchmark/lib/reference_granite.py` (`serve_closed_hybrid`'s name
their reference module in their bodies, so the procedures are here over
this one's; the engine-path one runs a full house and repeats two prompts),
the resident-bytes reckoning printed with every run, the new counters
(second tiles of the expert kernels by call kind, the share of the routing
weights that fell on held experts, the share of programs that carried a
chunk) and the step clock split by the two programs the loop alternates.

Order of a run: as `serve_closed`'s.
"""

from __future__ import annotations

import asyncio
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops_granite, reference_granite, stats, synth
from benchmark.lib import compared, compiles, harness, trace_reduce
from benchmark.runners.serve_closed import (TimedEngine, _drive,
                                            warm_programs)
from benchmark.runners.serve_closed_hybrid import (CountingEngine,
                                                   _path_decode,
                                                   _path_prefill)

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM

# `correct` has the hybrid cell's two limits (`serve_closed_hybrid`'s
# comment says why no limit on a worst position can be tight where a top-k
# is discontinuous: here the 10th and 11th of 72 logits), with values of this
# mix's own (`reference_limits` in the traffic file, PERF.md section 2):
#
# 1. Logits through the cache, teacher-forced (`cache_path_check`): chunked
#    prefill into a used slot, then one token at a time beside a dead slot,
#    against the reference's full forward pass; the MEDIAN over the
#    positions of four sequences of rms(system - reference) / rms(reference)
#    within `logit_error_median`, and each sequence's own median within
#    `logit_error_sequence` (a state not zeroed moves only the sequences
#    that went into the used slot).
# 2. Tokens through the engine, the timed path (`reference_check`): the
#    engine's own step programs at the timed sizes, every slot used before.
#    An emitted token's GAP is how far its reference logit lies under the
#    reference maximum of its position, in standard deviations of that
#    position's reference logits over the vocabulary (a tied head over a
#    settled embedding gives small logits; their scale says nothing). The
#    share of emitted tokens with a gap within `logit_tolerance` has to be
#    at least `token_share` over all and `sequence_share` in every
#    sequence, and the mean gap, each capped at `gap_cap`, within
#    `mean_gap`: a logit error of e moves the share by about e and the
#    mean gap by about e squared, so the mild faults (a state not zeroed,
#    fp8, the softmax over all 72) show in the second.


class GraniteCounts(CountingEngine):
    """`CountingEngine` with the counters PR 36 brought. The by-kind ones
    are dict entries on the engine; `_read` flattens them."""

    KINDS = ("chunk", "decode")
    FIELDS = CountingEngine.FIELDS + (
        "expert_second_tiles", "held_gate_sum", "chunk_programs", "n_steps",
        *(f"{name}.{kind}" for name in ("expert_calls_by",
                                        "expert_second_tiles_by",
                                        "expert_second_tile_calls_by")
          for kind in ("chunk", "decode")))

    def step(self):
        res = TimedEngine.step(self)     # not CountingEngine's: its FIELDS
        self.counts.append((self.steps[-1][1],
                            *(self._read(f) for f in self.FIELDS)))
        return res

    def _read(self, field: str):
        name, _, kind = field.partition(".")
        value = getattr(self._eng, name)
        return value[kind] if kind else value

    def steps_by_mode(self, t0: float, t1: float) -> dict:
        """The durations (ms) of the steps inside [t0, t1], apart by
        whether the program the step drained carried a prefill chunk
        (`chunk_programs` grew over the step): the loop's two programs."""
        col = 1 + self.FIELDS.index("chunk_programs")
        out = {"plain": [], "chunk": []}
        for i, (a, b, *_) in enumerate(self.steps):
            if i and t0 <= a and b <= t1:
                grew = self.counts[i][col] - self.counts[i - 1][col]
                out["chunk" if grew else "plain"].append((b - a) * 1e3)
        return out


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def _divided(w, by: float):
    return (w.astype(jnp.float32) / by).astype(w.dtype)


def settle_embedding(params: dict, llm: dict) -> dict:
    """The embedding's rows (= the tied head's) divided by `embed_mult`,
    so that what enters the residual stream, E[id] x 12, has the 0.02 of
    every other drawn matrix. As drawn (N(0, 0.02), then x 12) the input
    token's own row is 0.24 of a residual stream of ~1.3 all the way to
    the tied head, where it meets itself: its logit stands at 12 |E|^2 /
    rms(x) / 16 = 0.9 over others that spread by 0.08 (reckoned from the
    draw), greedy decoding repeated the last id for ever, and the
    engine-path limit of `correct` passed every fault that left that one
    logit on top (160 of 160 tokens under seven of nine faults, my chip
    run, PR 36).
    A trained model's embedding under a x 12 multiplier is small for the
    same reason. Part of the weights the seed makes (`changed` in the
    configuration file); the reference gets the same tree."""
    emb = dict(params["tkn_emb"])
    emb["embedding"] = _divided(emb["embedding"], float(llm["embed_mult"]))
    return {**params, "tkn_emb": emb}


def build_engine(ctx: dict):
    t = ctx["traffic"]
    llm = ctx["config"]["llm_config"]
    dt = jnp.dtype(t["compute_dtype"])
    try:
        model_cfg = LLMConfig(**llm)
        model = LLM(model_cfg, compute_dtype=dt, attn_impl=t["attn_impl"],
                    param_dtype=dt)
    except TypeError as e:
        # a program from before PR 36 (the parent side of its check): it
        # has no softmax router and no multipliers. Say so and leave.
        raise SystemExit(f"benchmark: this program cannot build the "
                         f"configuration: {e}")
    seed = harness.seed31(ctx["seed"])
    variables = jax.jit(model.init)({"params": jax.random.PRNGKey(seed)},
                                    jnp.zeros((1, 8), jnp.int32))
    variables = {"params": settle_embedding(dict(variables["params"]), llm)}
    jax.block_until_ready(variables)
    engine = DecodeEngine(model, variables, **t["engine"])
    return model_cfg, llm, variables, engine


def resident(engine, llm: dict) -> dict:
    """Bytes the engine holds between steps: the tree's leaves as they
    are, beside what the shapes say they should be."""
    leaves = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    measured = {"weights": leaves(engine.variables),
                "caches": leaves(engine.caches)}
    measured["total"] = sum(measured.values())
    planned = flops_granite.resident_bytes(
        llm, engine.n_slots, engine.n_blocks, engine.block_size,
        np.dtype(engine.cache_dtype).itemsize)
    return {"measured": measured, "planned": planned}


def cache_path_check(ctx, model, llm: dict, variables, vocab: int,
                     faults=()) -> dict:
    """Limit 1: the program's model through its own cache tree, without
    the engine (`serve_closed_hybrid.cache_path_check`'s procedure: two
    slots; slot 1 takes the longest reference prompt in chunks, decodes
    `reference_new_tokens` teacher-forced tokens beside a dead slot 0; then
    the shortest prompt goes into the SAME slot and blocks, then the middle
    one, then the shortest again). `faults` spoils the reference."""
    from distributed_pytorch_tpu.models.gpt import init_paged_cache
    t, e = ctx["traffic"], ctx["traffic"]["engine"]
    n_new, bs, chunk = t["reference_new_tokens"], e["block_size"], \
        e["prefill_chunk"]
    lens = sorted(t["reference_prompt_lens"])
    width = e["max_len"] // bs + chunk // bs
    n_blocks = e["max_len"] // bs + 1
    caches = init_paged_cache(model.config, n_blocks, bs,
                              dtype=model.compute_dtype, n_slots=2)
    bt = np.zeros((2, width), np.int32)
    bt[1, :n_blocks - 1] = np.arange(1, n_blocks)
    bt = jnp.asarray(bt)
    errs = []
    for j, L in enumerate((lens[-1], lens[0], lens[len(lens) // 2],
                           lens[0])):
        seq = synth.sample_tokens(ctx["seed"] + 11 + j, (L + n_new,), vocab)
        rows = []
        step = max(bs, chunk // 2)       # a prompt over it takes two chunks
        for off in range(0, L, step):
            n = min(step, L - off)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n] = seq[off:off + n]
            row, caches = _path_prefill(
                model, variables, caches, bt[1:], jnp.asarray(buf),
                jnp.int32(off), jnp.asarray([n], jnp.int32))
        rows.append(row)
        for i in range(L, L + n_new - 1):
            row, caches = _path_decode(
                model, variables, caches, bt,
                jnp.asarray([0, int(seq[i])], jnp.int32),
                jnp.asarray([0, i], jnp.int32))
            rows.append(row)
        want = reference_granite.forward_logits(
            variables["params"], llm, jnp.asarray(seq[None, :L + n_new - 1]),
            last=n_new, faults=faults)[0]
        d = jnp.stack(rows).astype(jnp.float32) - want
        errs.append(np.asarray(jnp.sqrt(
            jnp.mean(d * d, axis=-1) / jnp.mean(want * want, axis=-1))))
    by_sequence = [float(np.median(e)) for e in errs]
    errs = np.concatenate(errs)
    lim = t["reference_limits"]
    return {"median": float(np.median(errs)), "worst": float(errs.max()),
            "positions": int(errs.size), "by_sequence": by_sequence,
            "ok": float(np.median(errs)) <= lim["logit_error_median"]
            and max(by_sequence) <= lim["logit_error_sequence"]}


def engine_outputs(ctx, engine, vocab: int):
    """(prompts, what the engine made of them): the prompts of
    `reference_prompt_lens` admitted LAST of a full house (beside them one
    unjudged prompt for every other slot, lengths spread over the mix's
    range), so that their chunks ride fused steps beside live slots and
    their tokens come out of 64-slot decode steps, as in the window; then
    the longest alone and after it the shortest alone, each into a slot
    another sequence has just left."""
    t = ctx["traffic"]
    n_new = t["reference_engine_tokens"]
    lens = list(t["reference_prompt_lens"])
    prompts = [synth.sample_tokens(ctx["seed"] + 3 + i, (n,), vocab).tolist()
               for i, n in enumerate(lens)]
    lo, hi = t["prompt_len"]
    beside = [synth.sample_tokens(ctx["seed"] + 1003 + i, (int(n),),
                                  vocab).tolist()
              for i, n in enumerate(np.linspace(
                  lo, hi, engine.n_slots - len(prompts)).round())]
    longest, shortest = int(np.argmax(lens)), int(np.argmin(lens))
    outs = engine.run(beside + prompts, n_new)[len(beside):]
    outs += engine.run([prompts[longest]], n_new)
    outs += engine.run([prompts[shortest]], n_new)
    return prompts + [prompts[longest], prompts[shortest]], outs, \
        [longest, shortest]


def reference_check(ctx, engine, llm: dict, variables, vocab: int,
                    faults=()) -> dict:
    """Limit 2, on what the engine's own step programs emit at the timed
    sizes (`engine_outputs`; after a window every slot has held other
    sequences' state). Each sequence is scored by the reference's full
    forward pass over prompt + emitted tokens (`reference_engine_tokens`
    new ones a sequence): the gaps of the emitted tokens, in deviations of
    the position's reference logits. And the two prompts that ran twice
    must read the same the second time, alone in a used slot, as the first
    time in a full house: `repeat_share` of the repeated sequences' new
    tokens equal the first run's (a state not zeroed parts them within a
    few tokens, whatever the reference says). `faults` spoils the
    reference."""
    t = ctx["traffic"]
    lim = t["reference_limits"]
    n_new = t["reference_engine_tokens"]
    prompts, outs, again = engine_outputs(ctx, engine, vocab)
    gaps, agree = [], 0
    for prompt, full in zip(prompts, outs):
        full = [int(x) for x in full]
        assert full[:len(prompt)] == prompt and \
            len(full) == len(prompt) + n_new, "engine.run changed its shape"
        logits = reference_granite.forward_logits(
            variables["params"], llm, jnp.asarray([full[:-1]], jnp.int32),
            last=n_new, faults=faults)[0]
        rows = np.asarray(logits, np.float64)
        took = rows[np.arange(n_new), full[len(prompt):]]
        gaps.append((rows.max(axis=-1) - took) / rows.std(axis=-1))
        agree += int(np.sum(rows.argmax(axis=-1) == full[len(prompt):]))
    first = len(prompts) - len(again)
    repeat = float(np.mean([
        np.mean(np.asarray(outs[first + j][-n_new:])
                == np.asarray(outs[i][-n_new:]))
        for j, i in enumerate(again)]))
    shares = [float(np.mean(g <= lim["logit_tolerance"])) for g in gaps]
    share = float(np.mean(shares))          # sequences are equally long
    mean_gap = float(np.mean(np.minimum(np.concatenate(gaps),
                                        lim["gap_cap"])))
    return {"worst_gap": float(max(g.max() for g in gaps)),
            "top1_agree": agree, "tokens": n_new * len(gaps),
            "shares": shares, "share": share, "mean_gap": mean_gap,
            "repeat_share": repeat,
            "ok": share >= lim["token_share"]
            and min(shares) >= lim["sequence_share"]
            and mean_gap <= lim["mean_gap"]
            and repeat >= lim["repeat_share"]}


def expert_counters(grew: dict, llm: dict) -> dict:
    """The window's counters of the expert layers, from the growth of the
    engine's lifetime counts."""
    n_routed = llm["n_exp"] - llm["n_shared"]
    n_held = (llm.get("experts_held") or (0, n_routed))[1]
    routed = grew["held_assignments"] + grew["absent_assignments"]
    rows = routed / (llm["n_act"] - llm["n_shared"])
    out = {"experts_hit_pct": 100.0 * grew["experts_hit"]
           / (grew["expert_calls"] * n_held),
           "absent_assignments_pct": 100.0 * grew["absent_assignments"]
           / max(routed, 1),
           # tiles beyond a hit expert's first over the hits: the share of
           # expert matrices read a second time
           "expert_second_tiles_pct": 100.0 * grew["expert_second_tiles"]
           / max(grew["experts_hit"], 1),
           "held_gate_share_pct": 100.0 * grew["held_gate_sum"]
           / max(rows, 1),
           "chunk_program_share_pct": 100.0 * grew["chunk_programs"]
           / max(grew["n_steps"], 1),
           "state_resets": grew["state_resets"],
           "prefix_reuse_declined": grew["prefix_reuse_declined"]}
    for kind in GraniteCounts.KINDS:
        calls = max(grew[f"expert_calls_by.{kind}"], 1)
        out[f"second_tile_calls_pct.{kind}"] = 100.0 * grew[
            f"expert_second_tile_calls_by.{kind}"] / calls
        out[f"second_tiles_per_call.{kind}"] = grew[
            f"expert_second_tiles_by.{kind}"] / calls
    return out


def run(ctx: dict) -> dict:
    say = ctx["say"]
    t = ctx["traffic"]
    os.chdir(ctx["work_dir"])
    compile_log = compiles.CompileLog()
    model_cfg, llm, variables, engine = build_engine(ctx)
    vocab = model_cfg.vocab_size
    res_b = resident(engine, llm)
    say(f"engine: {engine.n_slots} slots, {engine.n_blocks} blocks of "
        f"{engine.block_size}, chunk {engine.prefill_chunk}, cache "
        f"{np.dtype(engine.cache_dtype).name}; declined "
        f"{engine.features_declined}")
    say(f"resident bytes: weights {res_b['measured']['weights']} + state "
        f"and pools {res_b['measured']['caches']} = "
        f"{res_b['measured']['total']} "
        f"({100.0 * res_b['measured']['total'] / ctx['peaks']['hbm_bytes']:.1f}"
        f"% of the chip); from shapes: {res_b['planned']}")
    warm_programs(engine, vocab, t)
    say("step programs compiled")
    timed = GraniteCounts(engine)
    records: list = []
    marks = asyncio.run(_drive(ctx, engine, timed, vocab, records))

    t_open, t_close = marks["t_open"], marks["t_close"]
    setup_s = t_open - stats.T_PROCESS_START
    # the rate over whole engine steps, as `serve_closed` takes it
    ends = [s[1] for s in timed.steps if t_open <= s[1] < t_close]
    if len(ends) < 2:
        raise RuntimeError("fewer than two engine steps ended in the window")
    r_open, r_close = ends[0], ends[-1]
    window = r_close - r_open
    tok_in = 0
    ttft, itl = [], []
    attempted = failed = short = 0
    for r in records:
        ts = r["tok_t"]
        tok_in += sum(1 for x in ts if r_open <= x < r_close)
        itl.extend(b - a for a, b in zip(ts, ts[1:]) if t_open <= b < t_close)
        bad_retire = r["done"] is not None and (
            r["done"] != "budget" or len(ts) != r["budget"])
        short += int(bad_retire)
        if t_open <= r["t_submit"] < t_close:
            attempted += 1
            if ts and ts[0] <= marks["t_grace"]:
                ttft.append(ts[0] - r["t_submit"])
                failed += int(bad_retire)
            else:
                failed += 1         # no first token a grace after the close
    done_in = sum(1 for r in records if r["done"] == "budget" and r["tok_t"]
                  and t_open <= r["tok_t"][-1] < t_close)
    if not itl:
        raise RuntimeError("no token followed another inside the window")
    e2e = {"serve_tokens_per_s": tok_in / window, "setup_s": setup_s,
           "itl_p95_ms": stats.percentile(itl, 95) * 1e3}
    ttft_ms = [x * 1e3 for x in ttft]
    say(f"window {t_close - t_open:.3f}s, {len(ends) - 1} whole engine steps "
        f"in {window:.3f}s: {tok_in} tokens -> "
        f"{e2e['serve_tokens_per_s']:.1f} tokens/s; requests submitted "
        f"{attempted}, completed {done_in} "
        f"({done_in / (t_close - t_open):.3f}/s), "
        f"failed {failed}, short {short}; setup {setup_s:.2f}s")
    say(f"ttft ms {stats.summarize(ttft_ms)}; itl ms "
        f"{stats.summarize([x * 1e3 for x in itl])} p95 "
        f"{e2e['itl_p95_ms']}; inter-token gaps {len(itl)}")

    steps_in = [s for s in timed.steps if t_open <= s[0] and s[1] <= t_close]
    late = compile_log.between(t_open, t_close)
    retraces = marks["traces_close"] - marks["traces_open"]
    say(f"compiles: {len(compile_log.events)} programs, "
        f"{compile_log.total_seconds(t_open):.1f}s of set-up; inside the "
        f"window {[e[1] for e in late]}, retraces {retraces}")
    grew = timed.between(t_open, t_close)
    counters = {"compiles_in_window": max(len(late), retraces)}
    if grew.get("expert_calls"):
        n_held = (llm.get("experts_held") or (0, 0))[1]
        counters.update(expert_counters(grew, llm))
        say(f"expert layers in the window: {grew['expert_calls']} calls, "
            f"{grew['experts_hit'] / grew['expert_calls']:.2f} of {n_held} "
            f"held experts hit a call "
            f"({counters['experts_hit_pct']:.2f}%), assignments to absent "
            f"experts {counters['absent_assignments_pct']:.2f}%, share of "
            f"the routing weights on held experts "
            f"{counters['held_gate_share_pct']:.2f}%; second tiles "
            f"{counters['expert_second_tiles_pct']:.3f}% of the hits: "
            + ", ".join(
                f"{kind} calls with one "
                f"{counters[f'second_tile_calls_pct.{kind}']:.2f}% "
                f"({counters[f'second_tiles_per_call.{kind}']:.3f} a call)"
                for kind in GraniteCounts.KINDS)
            + f"; programs with a chunk "
            f"{counters['chunk_program_share_pct']:.2f}%; state resets "
            f"{grew['state_resets']}, prefix reuse declined "
            f"{grew['prefix_reuse_declined']}; overlap_share "
            f"{engine.overlap_share:.4f} drain_reasons "
            f"{engine.drain_reasons}")
    modes = timed.steps_by_mode(t_open, t_close)
    obs = {"peaks": ctx["peaks"], "counters": counters,
           "clock": {"engine_step_ms": [(b - a) * 1e3
                                        for a, b, *_ in steps_in],
                     "engine_step_plain_ms": modes["plain"],
                     "engine_step_chunk_ms": modes["chunk"],
                     "occupancy_pct": [100.0 * s[2] for s in steps_in],
                     "ttft_ms": ttft_ms}}
    if steps_in:
        live = sum(s[3] for s in steps_in) / len(steps_in)
        pool_rows = engine.n_blocks * engine.block_size
        say(f"engine steps in window: {len(steps_in)}, mean "
            f"{sum(obs['clock']['engine_step_ms']) / len(steps_in):.3f} ms "
            f"(the median flips between the two programs), "
            + ", ".join(f"{len(v)} {k} median {stats.median(v):.3f} ms"
                        for k, v in modes.items() if v)
            + f"; mean occupancy {sum(obs['clock']['occupancy_pct']) / len(steps_in):.1f}%"
            f"; cache fill: mean live rows {live:.0f} of {pool_rows} reserved "
            f"= {100.0 * live / pool_rows:.1f}%")
        reck = flops_granite.decode_step_bytes(
            llm, engine.n_slots,
            grew.get("experts_hit", 0) / max(grew.get("expert_calls", 1), 1),
            live)
        say("a plain step must move, GB: " + ", ".join(
            f"{k} {v / 1e9:.3f}" for k, v in reck.items())
            + f" = {reck['total'] / ctx['peaks']['hbm_bytes_per_s'] * 1e3:.2f}"
            " ms at the HBM peak")
    if ctx["trace"]:
        traced = [s for s in timed.steps
                  if s[1] > marks["t_trace0"] and s[0] < marks["t_trace1"]]
        sl = timed.between(marks["t_trace0"], marks["t_trace1"])
        if sl.get("expert_calls"):
            # every expert HIT is a tile and so is every second tile
            tiles = (sl["experts_hit"] + sl["expert_second_tiles"]) \
                / sl["expert_calls"]
            counters["expert_up_bytes_per_call"] = \
                flops_granite.expert_up_bytes_per_call(llm, tiles)
            counters["expert_down_bytes_per_call"] = \
                flops_granite.expert_down_bytes_per_call(llm, tiles)
        obs["trace"] = trace_reduce.reduce_trace_dir(
            marks["trace_dir"], ctx["chips"], len(traced), say)

    ref = reference_check(ctx, engine, llm, variables, vocab)
    lim = t["reference_limits"]
    say(f"reference, tokens through the engine: share of emitted tokens "
        f"within {lim['logit_tolerance']} deviations of the reference "
        f"maximum {ref['share']:.4f} (at least {lim['token_share']}), by "
        f"sequence {[round(x, 3) for x in ref['shares']]} (each at least "
        f"{lim['sequence_share']}); mean gap, each capped at "
        f"{lim['gap_cap']}, {ref['mean_gap']:.5f} (within "
        f"{lim['mean_gap']}); the two repeated prompts emit the first "
        f"run's tokens again in {ref['repeat_share']:.4f} of their "
        f"positions (at least {lim['repeat_share']}); worst gap "
        f"{ref['worst_gap']:.4f}; top-1 agrees on {ref['top1_agree']} of "
        f"{ref['tokens']}")
    path = cache_path_check(ctx, engine.model, llm, variables, vocab)
    say(f"reference, logits through the cache: median relative error over "
        f"{path['positions']} positions {path['median']:.5f} (tolerance "
        f"{lim['logit_error_median']}), by sequence "
        f"{[round(x, 5) for x in path['by_sequence']]} (each within "
        f"{lim['logit_error_sequence']}), worst position "
        f"{path['worst']:.4f}")
    return {"correct": bool(ref["ok"] and path["ok"] and short == 0),
            "compared": compared.budgets(short)
            + compared.engine_tokens(ref, lim) + compared.cache_path(path, lim),
            "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "observations": obs,
            "memory_peak_bytes": marks["memory_peak"]}
