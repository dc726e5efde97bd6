"""Runner of kind `serve_closed_patterned`: the closed loop of `serve_closed`
over ANY patterned model (`LLMConfig.layer_pattern`), with everything that
tells one configuration from another named in the traffic file:

  `reference`          the plain reference module under `benchmark/lib/`
                       (`forward_logits(params, llm, ids, faults=, last=)`,
                       `forward_hidden(..., before_experts=)`, `scores`)
  `flops`              the module of parameters and bytes (`resident_bytes`,
                       `decode_step_bytes`, `expert_up_bytes_per_call`,
                       `expert_down_bytes_per_call`,
                       `paged_decode_bytes_per_call`)
  `tree_conditioning`  what is done to the drawn tree, in order, by name
                       (`CONDITIONING`); the reference gets the same tree
  `reference_procedures`, `reference_limits`: which comparisons decide
                       `correct` (`PROCEDURES`) and their limits
  `schedule_seed`      the constant the ORDER AND PAIRING of the request
                       lengths are drawn from

ROADMAP D12 asks for this one runner in place of `serve_closed_hybrid` and
`serve_closed_granite`; folding those two cells into it is a `benchmark`
PR's (their files are not this PR's to edit). What serves them unchanged is
imported from them, not copied: the clocked engine and its counters, the two
jitted drivers of the cache-path check, the warm-up, the window's counters.

What is new here is the schedule. `serve_closed._drive` draws the sizes of
request k from `--seed`; every seed then offers the same work in ANOTHER
order, and at 32 slots the order alone spread `itl_p95_ms` wider than the
driver admits (PR 43). Here `--seed` draws the weights and the token ids
only: request k's prompt length and budget come from `schedule_seed`, so
every run admits the same lengths at the same steps.

Order of a run: as `serve_closed`'s.
"""

from __future__ import annotations

import asyncio
import importlib
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import (compared, compiles, harness, peaks, stats, synth,
                           trace_reduce)
from benchmark.runners.serve_closed import (_spaced, warm_programs)
from benchmark.runners.serve_closed_granite import (GraniteCounts,
                                                    expert_counters)
from benchmark.runners.serve_closed_hybrid import _path_decode, _path_prefill

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.engine.decode import (make_fused_step_fn,
                                                   make_step_fn)
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.serve.scheduler import (EngineError, Scheduler,
                                                     ShedError)


def _lib(name: str):
    return importlib.import_module(f"benchmark.lib.{name}")


# ---------------------------------------------------------------------------
# the schedule
# ---------------------------------------------------------------------------

def request_sizes(t: dict, k: int) -> tuple:
    """(prompt_len, budget) of the k-th request: round k // clients draws
    one order for the spaced prompt lengths and one for the spaced budgets
    from the mix's `schedule_seed`. No run's seed enters."""
    n = t["clients"]
    rng = np.random.default_rng([int(t["schedule_seed"]), k // n])
    plens = rng.permutation(_spaced(*t["prompt_len"], n))
    budgets = rng.permutation(_spaced(*t["output_len"], n))
    return int(plens[k % n]), int(budgets[k % n])


def request_ids(seed: int, k: int, plen: int, vocab: int) -> list:
    """The k-th request's prompt: ids from `--seed`."""
    return np.random.default_rng([harness.seed31(seed), k, 1]).integers(
        0, vocab, plen).tolist()


async def _drive(ctx, engine, timed, vocab: int, records: list):
    """`serve_closed._drive` with the sizes from the schedule: clients,
    window, grace and traced slice as there."""
    t = ctx["traffic"]
    say = ctx["say"]
    counter = itertools.count()
    sched = Scheduler(timed, max_queue=4 * engine.n_slots)
    await sched.start()

    async def client():
        while True:
            k = next(counter)
            plen, budget = request_sizes(t, k)
            prompt = request_ids(ctx["seed"], k, plen, vocab)
            rec = {"k": k, "budget": budget, "tok_t": [], "done": None,
                   "t_submit": stats.now()}
            records.append(rec)
            try:
                h = sched.submit(prompt, budget)
            except ShedError:
                rec["done"] = "shed"
                await asyncio.sleep(0.01)
                continue
            try:
                async for _ in h:
                    rec["tok_t"].append(stats.now())
                rec["done"] = h.retired.reason if h.retired else "error"
            except (ShedError, EngineError):
                rec["done"] = "error"

    def guards():
        return sum(g.count for g in engine.trace_guards.values())

    clients = [asyncio.create_task(client()) for _ in range(t["clients"])]
    marks = {}
    try:
        await asyncio.sleep(t["warm_s"])
        marks["traces_open"] = guards()
        marks["t_open"] = stats.now()
        say("window opens")
        await asyncio.sleep(ctx["seconds"])
        marks["t_close"] = stats.now()
        marks["traces_close"] = guards()
        marks["memory_peak"] = peaks.memory_peak_bytes()
        say("window closes")
        await asyncio.sleep(t["ttft_grace_s"])
        marks["t_grace"] = stats.now()
        if ctx["trace"]:
            trace_dir = os.path.join(ctx["work_dir"], "trace")
            trace_reduce.start_trace(trace_dir)
            marks["t_trace0"] = stats.now()
            await asyncio.sleep(t["trace_s"])
            marks["t_trace1"] = stats.now()
            jax.profiler.stop_trace()
            marks["trace_dir"] = trace_dir
            say("traced slice written")
    finally:
        for c in clients:
            c.cancel()
        await asyncio.gather(*clients, return_exceptions=True)
        await sched.stop()
    return marks


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------

def balance_router_bias(params: dict, llm: dict, ctx: dict) -> dict:
    """The sigmoid routers' correction bias as training's load balancing
    leaves it: minus each routed expert's mean score over seeded
    calibration tokens, layer after layer (a layer's input follows from
    the layers before it, their biases set), computed with the mix's plain
    reference (`serve_closed_hybrid.balance_router_bias`'s rule over any
    reference module). Returns the tree with every `gate_bias` replaced."""
    ref = _lib(ctx["traffic"]["reference"])
    out = dict(params)

    def set_bias(i, h, block):
        s = ref.scores(h.reshape(-1, h.shape[-1]), block["moe"]["gate"])
        bias = (jnp.mean(s) - jnp.mean(s, axis=0)).astype(
            block["moe"]["gate_bias"].dtype)
        out[f"block_{i}"] = {**block,
                             "moe": {**block["moe"], "gate_bias": bias}}
        return out[f"block_{i}"]

    idx = jnp.asarray(synth.sample_tokens(
        harness.seed31(ctx["seed"]) + 1,
        tuple(ctx["traffic"]["calibration_shape"]), llm["vocab_size"]))
    ref.forward_hidden(params, llm, idx, before_experts=set_bias)
    return out


#: What a traffic file's `tree_conditioning` may name: each takes (tree,
#: `llm_config`, ctx) and returns the tree. Part of the weights the seed
#: makes, said under `changed` in the configuration file. The accepted
#: cells' `settle_embedding` and `centre_expert_outputs` come in with the
#: `benchmark` PR that moves those cells onto this runner.
CONDITIONING = {"balance_router_bias": balance_router_bias}


def build_engine(ctx: dict):
    t = ctx["traffic"]
    llm = ctx["config"]["llm_config"]
    dt = jnp.dtype(t["compute_dtype"])
    try:
        model_cfg = LLMConfig(**llm)
        model = LLM(model_cfg, compute_dtype=dt, attn_impl=t["attn_impl"],
                    param_dtype=dt)
    except (TypeError, AssertionError) as e:
        # a program from before the configuration's PR (the parent side of
        # its check): it lacks a field or a pattern kind. Say so and
        # leave, at once.
        raise SystemExit(f"benchmark: this program cannot build the "
                         f"configuration: {e!r}")
    seed = harness.seed31(ctx["seed"])
    variables = jax.jit(model.init)({"params": jax.random.PRNGKey(seed)},
                                    jnp.zeros((1, 8), jnp.int32))
    params = dict(variables["params"])
    for name in t["tree_conditioning"]:
        params = CONDITIONING[name](params, llm, ctx)
    variables = {"params": params}
    jax.block_until_ready(variables)
    engine = DecodeEngine(model, variables, **t["engine"])
    return model_cfg, llm, variables, engine


def resident(engine, llm: dict, flops) -> dict:
    """Bytes the engine holds between steps: the tree's leaves as they
    are, beside what the shapes say they should be."""
    leaves = lambda t: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    measured = {"weights": leaves(engine.variables),
                "caches": leaves(engine.caches)}
    measured["total"] = sum(measured.values())
    planned = flops.resident_bytes(
        llm, engine.n_slots, engine.n_blocks, engine.block_size,
        np.dtype(engine.cache_dtype).itemsize)
    return {"measured": measured, "planned": planned}


# ---------------------------------------------------------------------------
# `correct`: the granite cell's two procedures over the mix's reference, and
# a third that a discontinuous router cannot blur, inside the timed programs
# ---------------------------------------------------------------------------
# 1. `cache_path`: logits through the cache, teacher-forced: chunked
#    prefill into a fresh and into USED slots, then one token at a time
#    beside a dead slot, against the reference's full forward pass; the
#    MEDIAN over the positions of four sequences of rms(system - reference)
#    / rms(reference) within `logit_error_median`, each sequence's own
#    median within `logit_error_sequence`.
# 2. `engine_tokens_full_house`: tokens through the engine's own step
#    programs at the timed sizes, the judged prompts admitted LAST of a full
#    house, then two of them again alone in used slots. An emitted token's
#    GAP is how far its reference logit lies under the reference maximum of
#    its position, in deviations of that position's reference logits. Share
#    of tokens with a gap within `logit_tolerance` at least `token_share`
#    of all and `sequence_share` of every sequence's; the mean gap, each
#    capped at `gap_cap`, within `mean_gap`; the repeated prompts emit the
#    first run's tokens again in at least `repeat_share` of their positions;
#    at most `echo_share` of the tokens are their own input id.
# 3. `step_programs`: every block against the reference's block ON THE
#    PROGRAM'S OWN INPUT, INSIDE THE TWO PROGRAMS THE WINDOW TIMES. Where a
#    router takes the top k of scores that lie close together, bf16
#    rounding of the hidden state flips the k-th and (k+1)-th expert of a
#    share of the rows, and a flipped expert carries a whole 1/k of a
#    renormalised layer: through 1 and 2 the flips of all layers before a
#    position are in its logits, and a term that moves a layer by a few
#    percent (the experts in fp8) hides under them. Here the engine's own
#    step functions are jitted once more with every block's normed input
#    and mixer output as further results (`_probed`: nothing of the
#    program is rewritten, flax hands the values over as the modules
#    return them, each behind an `optimization_barrier`: without it the
#    compiler, free to keep more than bfloat16 between the ops it fuses,
#    wrote out an input that was not the one the block consumed, and the
#    decode rows of a deep expert block read 0.010 against the same
#    module's 0.0023 alone on that input; my chip run, PR 45) and driven
#    as the engine drives them, teacher-forced: a
#    house of n_slots filled by one chunk-carrying program a slot (the
#    merged walk: a chunk's rows and the live decode rows in one expert
#    call) into the tails and pools the window's last occupants left,
#    `reference_plain_steps` plain programs beside one slot still dead,
#    that slot admitted, as many plain programs with every slot live. The
#    reference's mixer runs on the captured input: a position-wise kind
#    ('E', 'F') row by row (so both route alike, but for exact ties), a
#    kind with a history ('C', '*') over a judged slot's rows from its
#    first on, the chunk's and then each decode program's, so a decode row
#    is held to the tail or the cached keys that the EARLIER programs
#    wrote. Per block and form (a chunk's rows, decode rows) the MEDIAN
#    over the rows of rms(system - reference) / rms(reference) has to lie
#    within `step_error_median` of the block's kind: rounding alone.


def cache_path_check(ctx, model, llm: dict, variables, vocab: int,
                     faults=()) -> dict:
    from distributed_pytorch_tpu.models.gpt import init_paged_cache
    ref = _lib(ctx["traffic"]["reference"])
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
        want = ref.forward_logits(
            variables["params"], llm, jnp.asarray(seq[None, :L + n_new - 1]),
            last=n_new, faults=faults)[0]
        d = jnp.stack(rows).astype(jnp.float32) - want
        errs.append(np.asarray(jnp.sqrt(
            jnp.mean(d * d, axis=-1) / jnp.mean(want * want, axis=-1))))
    by_sequence = [float(np.median(e)) for e in errs]
    errs = np.concatenate(errs)
    lim = t["reference_limits"]
    got = {"median": float(np.median(errs)), "worst": float(errs.max()),
           "positions": int(errs.size), "by_sequence": by_sequence}
    return {**got, "ok": all(c["ok"] for c in compared.cache_path(got, lim))}


_MIXER_MODULES = {"M": "ssm", "C": "conv", "E": "moe", "F": "mlp",
                  "*": "attn"}


def _probed(step):
    """One of the engine's step functions (`engine/decode.py`
    `make_step_fn`, `make_fused_step_fn`: the bodies of the programs the
    window timed, row sets, kernels and all), jitted with what every
    block's norm and mixer returned beside its own results: {module path:
    [an array a row set, in the program's order: a chunk's rows before the
    decode rows]}."""
    import flax.linen as nn
    names = ("norm", *_MIXER_MODULES.values())

    def probed(*args):
        got = {}

        def tap(call, a, kw, context):
            out = call(*a, **kw)
            path = context.module.path
            if context.method_name == "__call__" and len(path) == 2 \
                    and path[1] in names:
                y, rest = (out[0], out[1:]) if isinstance(out, tuple) \
                    else (out, None)                    # (y, cache | stats)
                # pinned: the program's next op and this probe read the ONE
                # array the model's code states. Left free, the compiler
                # hands a block's input on at more than bfloat16 inside
                # its fusions, and the array it writes out for the probe
                # is not the one the block consumed (header)
                y = jax.lax.optimization_barrier(y)
                got.setdefault("/".join(path), []).extend(
                    y if isinstance(y, (list, tuple)) else [y])
                out = y if rest is None else (y, *rest)
            return out

        with nn.intercept_methods(tap):
            return step(*args), got

    return jax.jit(probed)


def step_program_rows(ctx, engine, llm: dict, vocab: int) -> dict:
    """Drive the engine's two step programs, probed, as the header says;
    what they made of their own inputs, to be judged."""
    t = ctx["traffic"]
    n, W, chunk = engine.n_slots, engine.table_width, engine.prefill_chunk
    per_slot = engine.max_blocks
    assert engine.n_blocks > n * per_slot, "a block list a slot"
    n_plain = t["reference_plain_steps"]
    late = 1                        # dead through the first plain steps
    order = [s for s in range(n) if s != late] + [late]
    judged = {order[i] for i in (n // 3, 2 * n // 3, n - 2, n - 1)}
    ops = [*order[:-1], *[None] * n_plain, late, *[None] * n_plain]
    lens = {s: min(request_sizes(t, k)[0], chunk)
            for k, s in enumerate(order)}
    seqs = {s: synth.sample_tokens(ctx["seed"] + 31 + s,
                                   (lens[s] + len(ops),), vocab)
            for s in order}
    pattern = llm["layer_pattern"]
    plain = _probed(make_step_fn(engine.model, engine._sample))
    fused = _probed(make_fused_step_fn(engine.model, engine._sample, n, W))
    # a USED house: the tails and pools as the engine's last occupants
    # left them (its own programs donate their tree, these do not)
    caches = engine.caches
    tok = np.zeros(n, np.int32)
    pos = np.full(n, engine._park_pos, np.int32)
    live = np.zeros(n, bool)
    bt = np.zeros((n, W), np.int32)
    # block i: a judged slot's rows in order (the kinds with a history),
    # or the kept programs' rows by form (the position-wise kinds)
    history = {s: [] for s in judged}
    rows = {"chunk": [], "decode": []}

    def blocks(got, which, pick):
        out = []
        for i, kind in enumerate(pattern):
            h = got[f"block_{i}/norm"][which]
            y = got[f"block_{i}/{_MIXER_MODULES[kind]}"][which]
            out.append(tuple(np.asarray(a)[pick] for a in (h, y)))
        return out

    for step_no, c in enumerate(ops):
        was = live.copy()
        for s in np.flatnonzero(was):
            tok[s] = seqs[s][pos[s]]
        if c is not None:
            bt[c, :per_slot] = 1 + c * per_slot + np.arange(per_slot)
        args = (engine.variables, caches, jnp.asarray(tok), jnp.asarray(pos),
                jnp.asarray(live), jnp.asarray(bt), engine._rng,
                jnp.int32(step_no), engine._qparams)
        if c is None:
            (caches, _, pos_d), got = plain(*args)
        else:
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :lens[c]] = seqs[c][:lens[c]]
            (caches, _, pos_d, live_d), got = fused(
                *args, jnp.asarray(buf), jnp.int32(c), jnp.int32(0),
                jnp.asarray([lens[c]], jnp.int32), jnp.bool_(True))
            live[c], pos[c] = True, lens[c]
            assert np.array_equal(np.asarray(live_d), live)
            if c in judged:
                first = blocks(got, 0, (0, slice(0, lens[c])))
                history[c].append(first)
                rows["chunk"].append(first)
        pos[was] += 1
        assert np.array_equal(np.asarray(pos_d)[live], pos[live])
        # an expert layer's slot carries a program's counts out, never in
        caches = [None if kind == "E" else leaf
                  for kind, leaf in zip(pattern, caches)]
        if not was.any():
            continue
        decode = blocks(got, -1, (slice(None), 0))
        for s in judged:
            if was[s]:
                history[s].append([(h[s:s + 1], y[s:s + 1])
                                   for h, y in decode])
        if c is None or c in judged:
            rows["decode"].append([(h[was], y[was]) for h, y in decode])
    return {"history": history, "rows": rows, "lens": lens,
            "programs": {"chunk": n, "plain": 2 * n_plain}}


def step_programs_check(ctx, engine, llm: dict, variables, vocab: int,
                        faults=(), made=None) -> dict:
    """`made`: `step_program_rows`'s, where one drive is judged more than
    once (by the sound reference and by spoilt ones)."""
    ref = _lib(ctx["traffic"]["reference"])
    made = made or step_program_rows(ctx, engine, llm, vocab)
    history, rows, lens = made["history"], made["rows"], made["lens"]
    pattern = llm["layer_pattern"]

    def errors(kind, p, h, y):
        """Row by row, rms(program - reference) / rms(reference) of one
        block's mixer on the program's own normed input (T, C)."""
        want = ref.mixer_forward(llm, kind, p, jnp.asarray(
            h, jnp.float32)[None], tuple(faults))[0]
        d = jnp.asarray(y, jnp.float32) - want
        return np.asarray(jnp.sqrt(jnp.mean(d * d, axis=-1)
                                   / jnp.mean(want * want, axis=-1)))

    by_block = []
    for i, kind in enumerate(pattern):
        p = variables["params"][f"block_{i}"]
        err = {"chunk": [], "decode": []}
        if kind in "FE":
            for form, kept in rows.items():
                err[form].append(errors(kind, p, *(
                    np.concatenate([k[i][j] for k in kept])
                    for j in (0, 1))))
        else:
            for s, kept in history.items():
                e = errors(kind, p, *(np.concatenate([k[i][j] for k in kept])
                                      for j in (0, 1)))
                err["chunk"].append(e[:lens[s]])
                err["decode"].append(e[lens[s]:])
        by_block.append({form: float(np.median(np.concatenate(e)))
                         for form, e in err.items()})
    by_kind = {}
    for kind, b in zip(pattern, by_block):
        for form, e in b.items():
            by_kind.setdefault(kind, {}).setdefault(form, 0.0)
            by_kind[kind][form] = max(by_kind[kind][form], e)
    got = {"by_block": by_block, "by_kind": by_kind,
           "programs": made["programs"],
           "rows": {form: sum(len(k[0][0]) for k in kept)
                    for form, kept in rows.items()},
           "judged_slots": sorted(history)}
    return {**got, "ok": all(c["ok"] for c in compared.step_programs(
        got, ctx["traffic"]["reference_limits"]))}


def engine_outputs(ctx, engine, vocab: int):
    """(prompts, what the engine made of them, which ran twice): the
    prompts of `reference_prompt_lens` admitted LAST of a full house
    (beside them one unjudged prompt for every other slot, lengths spread
    over the mix's range), then the longest alone and after it the
    shortest alone, each into a slot another sequence has just left."""
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
    ref = _lib(ctx["traffic"]["reference"])
    t = ctx["traffic"]
    lim = t["reference_limits"]
    n_new = t["reference_engine_tokens"]
    prompts, outs, again = engine_outputs(ctx, engine, vocab)
    gaps, agree, echoed = [], 0, 0
    for prompt, full in zip(prompts, outs):
        full = [int(x) for x in full]
        assert full[:len(prompt)] == prompt and \
            len(full) == len(prompt) + n_new, "engine.run changed its shape"
        logits = ref.forward_logits(
            variables["params"], llm, jnp.asarray([full[:-1]], jnp.int32),
            last=n_new, faults=faults)[0]
        rows = np.asarray(logits, np.float64)
        new = np.asarray(full[len(prompt):])
        took = rows[np.arange(n_new), new]
        gaps.append((rows.max(axis=-1) - took) / rows.std(axis=-1))
        agree += int(np.sum(rows.argmax(axis=-1) == new))
        # the granite cell's disease: a tied head that hands the input's
        # own id back, under which the tokens say nothing
        echoed += int(np.sum(new == np.asarray(full[len(prompt) - 1:-1])))
    first = len(prompts) - len(again)
    repeat = float(np.mean([
        np.mean(np.asarray(outs[first + j][-n_new:])
                == np.asarray(outs[i][-n_new:]))
        for j, i in enumerate(again)]))
    shares = [float(np.mean(g <= lim["logit_tolerance"])) for g in gaps]
    share = float(np.mean(shares))          # sequences are equally long
    mean_gap = float(np.mean(np.minimum(np.concatenate(gaps),
                                        lim["gap_cap"])))
    tokens = n_new * len(gaps)
    got = {"worst_gap": float(max(g.max() for g in gaps)),
           "top1_agree": agree, "tokens": tokens,
           "echo_share": echoed / tokens,
           "shares": shares, "share": share, "mean_gap": mean_gap,
           "repeat_share": repeat}
    # held to the limits the mix names (`compared.engine_tokens`): a mix
    # that names no `sequence_share` reports the sequences and holds none
    return {**got, "ok": all(c["ok"]
                             for c in compared.engine_tokens(got, lim))}


def _say_engine_tokens(say, lim: dict, ref: dict) -> None:
    say(f"reference, tokens through the engine: share of emitted tokens "
        f"within {lim['logit_tolerance']} deviations of the reference "
        f"maximum {ref['share']:.4f} (at least {lim['token_share']}), by "
        f"sequence {[round(x, 3) for x in ref['shares']]} (each at least "
        f"{lim.get('sequence_share', 'nothing: not held')}); mean gap, each "
        f"capped at "
        f"{lim['gap_cap']}, {ref['mean_gap']:.5f} (within "
        f"{lim['mean_gap']}); the two repeated prompts emit the first "
        f"run's tokens again in {ref['repeat_share']:.4f} of their "
        f"positions (at least {lim['repeat_share']}); tokens that echo "
        f"their input id {ref['echo_share']:.4f} (at most "
        f"{lim['echo_share']}); worst gap {ref['worst_gap']:.4f}; top-1 "
        f"agrees on {ref['top1_agree']} of {ref['tokens']}")


def _say_cache_path(say, lim: dict, path: dict) -> None:
    say(f"reference, logits through the cache: median relative error over "
        f"{path['positions']} positions {path['median']:.5f} (tolerance "
        f"{lim['logit_error_median']}), by sequence "
        f"{[round(x, 5) for x in path['by_sequence']]} (each within "
        f"{lim['logit_error_sequence']}), worst position "
        f"{path['worst']:.4f}")


def _say_step_programs(say, lim: dict, got: dict) -> None:
    say(f"reference, block by block inside the engine's step programs "
        f"({got['programs']['chunk']} chunk-carrying, one admission each "
        f"into a used house, and {got['programs']['plain']} plain, half "
        f"beside a dead slot, half with every slot live; teacher-forced): "
        f"median over the rows of a block's relative error on the program's "
        f"own input, worst block by kind and form "
        f"{ {k: {f: round(e, 5) for f, e in b.items()} for k, b in got['by_kind'].items()} } "
        f"(tolerances {lim['step_error_median']}, both forms); position-wise "
        f"kinds over {got['rows']} rows, the others over slots "
        f"{got['judged_slots']} from their first row on; by block "
        f"{[[round(e, 4) for e in b.values()] for b in got['by_block']]}")


#: What a traffic file's `reference_procedures` may name: (the check over
#: (ctx, engine, llm, variables, vocab), how its reading is said, its
#: numbers beside their limits for the result line).
PROCEDURES = {
    "engine_tokens_full_house": (reference_check, _say_engine_tokens,
                                 compared.engine_tokens),
    "cache_path": (lambda ctx, engine, llm, variables, vocab:
                   cache_path_check(ctx, engine.model, llm, variables,
                                    vocab), _say_cache_path,
                   compared.cache_path),
    "step_programs": (step_programs_check, _say_step_programs,
                      compared.step_programs),
}


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def window_stalls(t_open: float, t_close: float) -> list:
    """The stalled turns the program's always-on step ring booked for the
    engine INSIDE the window (`obs.flight.stall_log`, on the benchmark's
    clock): what a run that made fewer steps than its neighbours lost them
    to. The `stall_*.serve` metrics read the process's life and a traced run
    alone; this is said in every run. Empty where the program keeps no log."""
    try:
        from distributed_pytorch_tpu.obs import flight
        log = flight.stall_log()
    except (ImportError, AttributeError):
        return []
    return [s for s in log
            if s["source"] == "engine" and t_open <= s["t0"] < t_close]


def run(ctx: dict) -> dict:
    say = ctx["say"]
    t = ctx["traffic"]
    flops = _lib(t["flops"])
    os.chdir(ctx["work_dir"])
    compile_log = compiles.CompileLog()
    model_cfg, llm, variables, engine = build_engine(ctx)
    vocab = model_cfg.vocab_size
    res_b = resident(engine, llm, flops)
    say(f"engine: {engine.n_slots} slots, {engine.n_blocks} blocks of "
        f"{engine.block_size}, chunk {engine.prefill_chunk}, cache "
        f"{np.dtype(engine.cache_dtype).name}; declined "
        f"{engine.features_declined}; tree conditioned by "
        f"{t['tree_conditioning']}")
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
    say(f"schedule (seed {t['schedule_seed']} of the mix): requests "
        f"{records[0]['k']}..{records[-1]['k']} offered, the first sizes "
        f"{[request_sizes(t, k) for k in range(4)]}")

    steps_in = [s for s in timed.steps if t_open <= s[0] and s[1] <= t_close]
    late = compile_log.between(t_open, t_close)
    retraces = marks["traces_close"] - marks["traces_open"]
    say(f"compiles: {len(compile_log.events)} programs, "
        f"{compile_log.total_seconds(t_open):.1f}s of set-up; inside the "
        f"window {[e[1] for e in late]}, retraces {retraces}")
    stalls = window_stalls(t_open, t_close)
    say(f"stalled turns of the engine's step ring inside the window: "
        f"{len(stalls)}, {sum(s['excess_ms'] for s in stalls):.1f} ms over "
        f"their median: "
        f"{[(s['cause'], s['owner'], s['excess_ms']) for s in stalls]}")
    grew = timed.between(t_open, t_close)
    counters = {"compiles_in_window": max(len(late), retraces)}
    if grew.get("expert_calls"):
        counters.update(expert_counters(grew, llm))
        say(f"expert layers in the window: {grew['expert_calls']} calls, "
            f"{grew['experts_hit'] / grew['expert_calls']:.2f} held experts "
            f"hit a call ({counters['experts_hit_pct']:.2f}%), assignments "
            f"to absent experts {counters['absent_assignments_pct']:.2f}%; "
            f"second tiles {counters['expert_second_tiles_pct']:.3f}% of "
            f"the hits: " + ", ".join(
                f"{kind} calls with one "
                f"{counters[f'second_tile_calls_pct.{kind}']:.2f}% "
                f"({counters[f'second_tiles_per_call.{kind}']:.3f} a call)"
                for kind in GraniteCounts.KINDS)
            + f"; programs with a chunk "
            f"{counters['chunk_program_share_pct']:.2f}% of "
            f"{grew['n_steps']}; state resets {grew['state_resets']}, "
            f"prefix reuse declined {grew['prefix_reuse_declined']}; "
            f"overlap_share {engine.overlap_share:.4f} merged_program_share "
            f"{engine.merged_program_share:.4f} drain_reasons "
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
            f"{sum(obs['clock']['engine_step_ms']) / len(steps_in):.3f} ms, "
            + ", ".join(f"{len(v)} {k} median {stats.median(v):.3f} ms"
                        for k, v in modes.items() if v)
            + f"; mean occupancy {sum(obs['clock']['occupancy_pct']) / len(steps_in):.1f}%"
            f"; cache fill: mean live rows {live:.0f} of {pool_rows} reserved "
            f"= {100.0 * live / pool_rows:.1f}%")
        reck = flops.decode_step_bytes(
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
                flops.expert_up_bytes_per_call(llm, tiles)
            counters["expert_down_bytes_per_call"] = \
                flops.expert_down_bytes_per_call(llm, tiles)
        counters["paged_decode_bytes_per_call"] = \
            flops.paged_decode_bytes_per_call(
                llm, sum(s[3] for s in traced) / len(traced),
                np.dtype(engine.cache_dtype).itemsize)
        obs["trace"] = trace_reduce.reduce_trace_dir(
            marks["trace_dir"], ctx["chips"], len(traced), say)

    lim = t["reference_limits"]
    ok = short == 0
    held = compared.budgets(short)
    for name in t["reference_procedures"]:
        check, tell, numbers = PROCEDURES[name]
        reading = check(ctx, engine, llm, variables, vocab)
        tell(say, lim, reading)
        ok = ok and reading["ok"]
        held += numbers(reading, lim)
    return {"correct": bool(ok), "compared": held,
            "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "observations": obs,
            "memory_peak_bytes": marks["memory_peak"]}
