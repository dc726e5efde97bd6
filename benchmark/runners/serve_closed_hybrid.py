"""Runner of kind `serve_closed_hybrid`: the closed loop of `serve_closed`
(its `_drive` with `request_sizes` inside, its clock, its warm-up, by import) over
a patterned model (state-space, expert and attention layers by a per-layer
pattern) whose bf16 parameter tree never exists in float32.

What it brings of its own: the engine build (`LLM(..., param_dtype=bf16)`,
one jitted init from the seed), the reference check against
`benchmark/lib/reference_hybrid.py`, the resident-bytes reckoning printed
with every run, and the counters the new layers write (experts hit a call,
assignments to absent experts, state resets, prefix reuse declined), read
from the engine after every step on the benchmark's clock.

Order of a run: as `serve_closed`'s.
"""

from __future__ import annotations

import asyncio
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops_hybrid, reference_hybrid, stats, synth
from benchmark.lib import compared, compiles, harness, trace_reduce
from benchmark.runners.serve_closed import (TimedEngine, _drive,
                                            warm_programs)

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM

# `correct`, part (a), has two limits here, and both have to hold. Their
# values are the mix's (`reference_limits` in the traffic file), set from
# readings at the mix's own sizes (PERF.md section 2). An expert layer's
# top-k is discontinuous: bf16 rounding of a hidden state flips the 6th and
# 7th expert of 1-2% of the rows of a layer (my chip run, PR 33: a row whose
# selection differs is off by 15-27% of the layer's output, one whose
# selection agrees by 0.4-0.9%), and a row so moved early in a sequence
# moves every later position through the recurrent state. No limit on a
# worst position can be tight then; the limits are on what most positions do.
#
# 1. Logits through the cache, teacher-forced (`cache_path_check`): the
#    program's model prefilling in chunks into a slot's state and blocks and
#    then decoding one token at a time, against the reference's full forward
#    pass: per position rms(system - reference) / rms(reference) over the
#    vocabulary, and the MEDIAN over the positions of four sequences within
#    `logit_error_median`.
# 2. Tokens through the engine, the timed path (`reference_check`): each
#    emitted token's reference logit within `logit_tolerance` of the
#    reference maximum at its position, for at least `token_share` of all
#    emitted tokens and `sequence_share` of every sequence's (a flipped
#    expert sends a token's logit down by the logits' spread; a state not
#    zeroed, a lost gate or skip term does that to most of a sequence).


class CountingEngine(TimedEngine):
    """`TimedEngine`, and after every step the engine's lifetime counters
    of the patterned layers, stamped with the step's end."""

    FIELDS = ("expert_calls", "experts_hit", "held_assignments",
              "absent_assignments", "state_resets", "prefix_reuse_declined")

    def __init__(self, engine):
        super().__init__(engine)
        self.counts: list = []          # (t_end, *FIELDS)

    def step(self):
        res = super().step()
        self.counts.append((self.steps[-1][1],
                            *(getattr(self._eng, f) for f in self.FIELDS)))
        return res

    def between(self, t0: float, t1: float) -> dict:
        """Each counter's growth over the steps that ended in [t0, t1)."""
        rows = [c for c in self.counts if t0 <= c[0] < t1]
        if len(rows) < 2:
            return {}
        return {f: rows[-1][i + 1] - rows[0][i + 1]
                for i, f in enumerate(self.FIELDS)}


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def _centred(w, axis: int):
    return (w - jnp.mean(w.astype(jnp.float32), axis=axis, keepdims=True)
            ).astype(w.dtype)


def centre_expert_outputs(params: dict, llm: dict) -> dict:
    """Every expert's down matrix with its mean over the hidden axis taken
    off, stack by stack and in place. relu(.)^2 is positive, so a drawn
    W_down turns its mean into one direction that EVERY token of every
    sequence receives, layer upon layer (the residual grew from 0.02 to
    11.7 over 16 layers, my chip run, PR 33): all rows of a step then
    score the experts alike, greedy decoding emits the same few tokens in
    every sequence, and how many experts a step hits (its weight bytes, so
    its time) is the seed's luck: 43.7-51.1 of 64 over six seeds, 2,008-
    2,101 tokens/s. A trained expert's output has no such common part.
    Part of the weights the seed makes; the reference gets the same tree."""
    out = dict(params)
    for i, kind in enumerate(llm["layer_pattern"]):
        if kind == "E":
            moe = dict(params[f"block_{i}"]["moe"])
            moe["experts_down"] = _centred(moe["experts_down"], 1)
            moe["shared_down"] = _centred(moe["shared_down"], 0)
            out[f"block_{i}"] = {**params[f"block_{i}"], "moe": moe}
    return out


def balance_router_bias(params: dict, llm: dict, seed: int,
                        shape=(8, 256)) -> dict:
    """The routers' correction bias as training's load balancing leaves
    it: minus each routed expert's mean score over seeded calibration
    tokens, layer after layer (a layer's input follows from the layers
    before it, their biases set), computed with the plain reference.

    With random weights every token's hidden state shares a large common
    part (the mean of relu(.)^2 through W_down, layer upon layer), so the
    sigmoid scores of one expert lie close together over tokens and far
    apart over experts: without a balancing bias all tokens choose nearly
    the same top 6 (a bias drawn at random: 22.6 of 64 held experts hit
    by 64 tokens, my chip run, PR 33). A trained router's bias exists to
    undo exactly that; this is its stand-in, and part of the weights the
    seed makes. Returns the tree with `gate_bias` replaced."""
    out = dict(params)

    def set_bias(i, h, block):
        s = reference_hybrid.scores(h.reshape(-1, h.shape[-1]),
                                    block["moe"]["gate"])
        bias = (jnp.mean(s) - jnp.mean(s, axis=0)).astype(
            block["moe"]["gate_bias"].dtype)
        out[f"block_{i}"] = {**block,
                             "moe": {**block["moe"], "gate_bias": bias}}
        return out[f"block_{i}"]

    idx = jnp.asarray(synth.sample_tokens(seed + 1, shape,
                                          llm["vocab_size"]))
    reference_hybrid.forward_hidden(params, llm, idx,
                                    before_experts=set_bias)
    return out


def build_engine(ctx: dict):
    t = ctx["traffic"]
    llm = ctx["config"]["llm_config"]
    dt = jnp.dtype(t["compute_dtype"])
    try:
        model_cfg = LLMConfig(**llm)
        model = LLM(model_cfg, compute_dtype=dt, attn_impl=t["attn_impl"],
                    param_dtype=dt)
    except TypeError as e:
        # a program from before PR 33 (the parent side of its check): it
        # has no per-layer pattern. Say so and leave, at once.
        raise SystemExit(f"benchmark: this program cannot build the "
                         f"configuration: {e}")
    seed = harness.seed31(ctx["seed"])
    variables = jax.jit(model.init)({"params": jax.random.PRNGKey(seed)},
                                    jnp.zeros((1, 8), jnp.int32))
    params = centre_expert_outputs(variables["params"], llm)
    variables = {"params": balance_router_bias(params, llm, seed)}
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
    planned = flops_hybrid.resident_bytes(
        llm, engine.n_slots, engine.n_blocks, engine.block_size,
        np.dtype(engine.cache_dtype).itemsize)
    return {"measured": measured, "planned": planned}


def _without_counts(model, caches: list) -> list:
    """The cache tree as the next call takes it: an expert layer's slot
    carries one call's routing counts OUT (the engine takes them off the
    tree the same way); fed back in they would grow, a new shape a call."""
    return [None if kind == "E" else c
            for kind, c in zip(model.config.layer_pattern, caches)]


@functools.partial(jax.jit, static_argnums=0, donate_argnums=2)
def _path_prefill(model, variables, caches, bt_row, toks, off, n):
    logits, _, caches = model.apply(
        variables, toks, None, caches, off, logits_idx=n - 1,
        block_tables=bt_row, state_ctx={"slot": jnp.int32(1),
                                        "valid_len": n})
    return logits[0, -1], _without_counts(model, caches)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=2)
def _path_decode(model, variables, caches, bt, tok, pos):
    logits, _, caches = model.apply(
        variables, tok[:, None], None, caches, pos, block_tables=bt,
        state_ctx={"live": jnp.asarray([False, True])})
    return logits[1, -1], _without_counts(model, caches)


def cache_path_check(ctx, model, llm: dict, variables, vocab: int,
                     faults=()) -> dict:
    """Limit 1: the program's model through its own cache tree, without
    the engine. Two slots; slot 1 takes the longest reference prompt in
    chunks of the engine's block-aligned budget, then decodes
    `reference_new_tokens` teacher-forced tokens beside a dead slot 0;
    then the shortest prompt goes into the SAME slot and blocks, then the
    middle one, then the shortest again: four sequences, so that one whose
    early rows a flipped expert has moved (all its later positions carry
    that through the recurrent state) is a minority of the positions.
    Returns the median and the worst position's relative logit error.
    `faults` spoils the reference (PERF.md's second readings, the
    tests)."""
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
        want = reference_hybrid.forward_logits(
            variables["params"], llm,
            jnp.asarray(seq[None, :L + n_new - 1]), last=n_new,
            faults=faults)[0]
        d = jnp.stack(rows).astype(jnp.float32) - want
        errs.append(np.asarray(jnp.sqrt(
            jnp.mean(d * d, axis=-1) / jnp.mean(want * want, axis=-1))))
    by_sequence = [float(np.median(e)) for e in errs]
    errs = np.concatenate(errs)
    return {"median": float(np.median(errs)), "worst": float(errs.max()),
            "positions": int(errs.size), "by_sequence": by_sequence,
            "ok": float(np.median(errs))
            <= t["reference_limits"]["logit_error_median"]}


def reference_check(ctx, engine, llm: dict, variables, vocab: int,
                    faults=()) -> dict:
    """Limit 2: seeded prompts of the timed sizes (one chunk, two chunks, a
    whole chunk buffer) prefilled and decoded through the idle engine; then
    the longest alone and after it the shortest alone, which the engine
    admits into the slot the longest has just left (a state not zeroed
    shows there). Of all emitted tokens at least `token_share`, and of
    every sequence's at least `sequence_share`, have their reference logit
    within `logit_tolerance` of the reference maximum at their position."""
    t = ctx["traffic"]
    lim = t["reference_limits"]
    n_new = t["reference_new_tokens"]
    lens = list(t["reference_prompt_lens"])
    prompts = [synth.sample_tokens(ctx["seed"] + 3 + i, (n,), vocab).tolist()
               for i, n in enumerate(lens)]
    outs = engine.run(prompts, n_new)
    outs += engine.run([prompts[int(np.argmax(lens))]], n_new)
    again = int(np.argmin(lens))
    outs += engine.run([prompts[again]], n_new)
    prompts = prompts + [prompts[int(np.argmax(lens))], prompts[again]]
    shares, worst, agree, total = [], 0.0, 0, 0
    for prompt, full in zip(prompts, outs):
        full = [int(x) for x in full]
        assert full[:len(prompt)] == prompt and \
            len(full) == len(prompt) + n_new, "engine.run changed its shape"
        logits = reference_hybrid.forward_logits(
            variables["params"], llm, jnp.asarray([full[:-1]], jnp.int32),
            last=n_new, faults=faults)[0]
        rows = np.asarray(logits, np.float32)
        gaps = rows.max(axis=-1) - rows[np.arange(n_new), full[len(prompt):]]
        shares.append(float(np.mean(gaps <= lim["logit_tolerance"])))
        worst = max(worst, float(gaps.max()))
        agree += int(np.sum(rows.argmax(axis=-1) == full[len(prompt):]))
        total += n_new
    share = float(np.mean(shares))          # sequences are equally long
    return {"worst_gap": worst, "top1_agree": agree, "tokens": total,
            "shares": shares, "share": share,
            "ok": share >= lim["token_share"]
            and min(shares) >= lim["sequence_share"]}


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
    timed = CountingEngine(engine)
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
        f"{e2e['itl_p95_ms']}")

    steps_in = [s for s in timed.steps if t_open <= s[0] and s[1] <= t_close]
    late = compile_log.between(t_open, t_close)
    retraces = marks["traces_close"] - marks["traces_open"]
    say(f"compiles: {len(compile_log.events)} programs, "
        f"{compile_log.total_seconds(t_open):.1f}s of set-up; inside the "
        f"window {[e[1] for e in late]}, retraces {retraces}")
    grew = timed.between(t_open, t_close)
    counters = {"compiles_in_window": max(len(late), retraces)}
    if grew.get("expert_calls"):
        n_held = (llm.get("experts_held")
                  or (0, llm["n_exp"] - llm["n_shared"]))[1]
        routed = grew["held_assignments"] + grew["absent_assignments"]
        counters.update(
            experts_hit_pct=100.0 * grew["experts_hit"]
            / (grew["expert_calls"] * n_held),
            absent_assignments_pct=100.0 * grew["absent_assignments"]
            / max(routed, 1),
            state_resets=grew["state_resets"],
            prefix_reuse_declined=grew["prefix_reuse_declined"])
        say(f"expert layers in the window: {grew['expert_calls']} calls, "
            f"{grew['experts_hit'] / grew['expert_calls']:.2f} of {n_held} "
            f"held experts hit a call "
            f"({counters['experts_hit_pct']:.2f}%), assignments to absent "
            f"experts {counters['absent_assignments_pct']:.2f}%; state "
            f"resets {grew['state_resets']}, prefix reuse declined "
            f"{grew['prefix_reuse_declined']}; overlap_share "
            f"{engine.overlap_share:.4f} drain_reasons "
            f"{engine.drain_reasons}")
    obs = {"peaks": ctx["peaks"], "counters": counters,
           "clock": {"engine_step_ms": [(b - a) * 1e3
                                        for a, b, *_ in steps_in],
                     "occupancy_pct": [100.0 * s[2] for s in steps_in],
                     "ttft_ms": ttft_ms}}
    if steps_in:
        live = sum(s[3] for s in steps_in) / len(steps_in)
        pool_rows = engine.n_blocks * engine.block_size
        say(f"engine steps in window: {len(steps_in)}, median "
            f"{stats.median(obs['clock']['engine_step_ms']):.3f} ms, mean "
            f"occupancy {sum(obs['clock']['occupancy_pct']) / len(steps_in):.1f}%"
            f"; cache fill: mean live rows {live:.0f} of {pool_rows} reserved "
            f"= {100.0 * live / pool_rows:.1f}%")
        reck = flops_hybrid.decode_step_bytes(
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
            counters["expert_matmul_bytes_per_call"] = \
                flops_hybrid.expert_matmul_bytes_per_call(
                    llm, sl["experts_hit"] / sl["expert_calls"])
        obs["trace"] = trace_reduce.reduce_trace_dir(
            marks["trace_dir"], ctx["chips"], len(traced), say)

    ref = reference_check(ctx, engine, llm, variables, vocab)
    lim = t["reference_limits"]
    say(f"reference, tokens through the engine: share of emitted tokens "
        f"within {lim['logit_tolerance']} of the reference maximum "
        f"{ref['share']:.4f} (at least {lim['token_share']}), by sequence "
        f"{[round(x, 3) for x in ref['shares']]} (each at least "
        f"{lim['sequence_share']}); worst gap {ref['worst_gap']:.4f}; top-1 "
        f"agrees on {ref['top1_agree']} of {ref['tokens']}")
    path = cache_path_check(ctx, engine.model, llm, variables, vocab)
    say(f"reference, logits through the cache: median relative error over "
        f"{path['positions']} positions {path['median']:.5f} (tolerance "
        f"{lim['logit_error_median']}), by sequence "
        f"{[round(x, 5) for x in path['by_sequence']]}, worst position "
        f"{path['worst']:.4f}")
    return {"correct": bool(ref["ok"] and path["ok"] and short == 0),
            "compared": compared.budgets(short)
            + compared.engine_tokens(ref, lim) + compared.cache_path(path, lim),
            "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "observations": obs,
            "memory_peak_bytes": marks["memory_peak"]}
