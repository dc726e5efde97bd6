"""Runner of kind `serve_closed`: N clients in a closed loop against the
program's `Scheduler` over its `DecodeEngine`, in one process.

Each client submits its next request when its last one retires (callers
that wait for a reply: evaluation harnesses, batch generation). `--seed`
makes the weights, the token ids and the sizes of every request
(`request_sizes`): each round of `clients` consecutive requests takes the
`clients` evenly spaced values of the prompt range and of the output range,
paired and ordered by a draw from the seed. Every seed so offers the same
amount of work in another order, at any speed of the system, and no seed
replays another's schedule. Every token is stamped on the client's side of
the scheduler, on the benchmark's clock. A thin proxy around the engine
stamps each `step()` the scheduler makes and keeps the live lengths the
bytes model needs: spans from the benchmark's own files, around the calls
into the layer.

Order of a run: weights (one jitted init from the seed) -> engine -> both
step programs compiled -> scheduler + clients for `warm_s` seconds -> the
window opens (`setup_s` ends) -> `--seconds` -> the window closes, counting
stops -> a short grace in which only first tokens of requests submitted
inside the window are still taken -> with `--trace 1` a traced slice of the
same loop -> stop, then the reference check on the idle engine.
"""

from __future__ import annotations

import asyncio
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import flops as flops_lib
from benchmark.lib import compiles, harness, peaks, reference, stats, synth
from benchmark.lib import compared, trace_reduce

# imported with the runner, which run.py resolves BEFORE it touches the
# chip: Python imports run 2.5x slower once the TPU runtime's threads are up
# (42 s against 16 s for the trainer's chain, my chip runs, PR 24)
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.serve.scheduler import (EngineError, Scheduler,
                                                     ShedError)

# greedy decoding through the engine (bf16 compute, paged bf16 cache, the
# Pallas kernels) against the float32 reference's full forward pass: with
# random weights the top logits lie close together and the largest changes
# on rounding, so tokens are not compared. Each emitted token's REFERENCE
# logit has to lie within this much of the reference maximum at that
# position. bf16 rounding moves a logit of these models by a few 1e-3 (8
# mantissa bits on values under 1); 0.05 holds that with room, and a wrong
# cache row, mask or position sends the emitted token's logit down by the
# spread of the logits (~0.3-1.0), which fails.
LOGIT_TOLERANCE = 0.05


class TimedEngine:
    """The engine as the scheduler sees it, with the benchmark's clock
    around `step()` and the live lengths of decoding sequences."""

    def __init__(self, engine):
        self._eng = engine
        self.steps: list = []      # (t0, t1, occupancy, live_rows, emitted)
        self._len: dict = {}       # seq id -> rows its cache holds
        self._decoding: set = set()

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def admit(self, prompt, max_new_tokens, *a, **kw):
        adm = self._eng.admit(prompt, max_new_tokens, *a, **kw)
        self._len[adm.seq_id] = min(len(prompt), self._eng.max_len - 1)
        if adm.first_token is not None:
            self._decoding.add(adm.seq_id)
        return adm

    def cancel(self, seq_id):
        self._len.pop(seq_id, None)
        self._decoding.discard(seq_id)
        return self._eng.cancel(seq_id)

    def step(self):
        occ = self._eng.occupancy
        rows = sum(self._len[s] for s in self._decoding)
        t0 = stats.now()
        res = self._eng.step()
        t1 = stats.now()
        n_emitted = 0
        for sid, toks in res.emitted.items():
            if sid in self._len:
                self._len[sid] += len(toks)
                self._decoding.add(sid)
            n_emitted += len(toks)
        for sid in res.retired:
            self._len.pop(sid, None)
            self._decoding.discard(sid)
        self.steps.append((t0, t1, occ, rows, n_emitted))
        return res


def _spaced(lo: int, hi: int, n: int) -> list:
    """The n evenly spaced whole numbers of [lo, hi]: the middles of n
    equal shares of the range, so their mean is the range's."""
    return [lo + int((i + 0.5) * (hi - lo + 1) / n) for i in range(n)]


def request_sizes(t: dict, seed: int, k: int) -> tuple:
    """(prompt_len, budget) of the k-th request: round k // clients draws
    one order for the spaced prompt lengths and one for the spaced budgets
    from the seed."""
    n = t["clients"]
    rng = np.random.default_rng([harness.seed31(seed), k // n])
    plens = rng.permutation(_spaced(*t["prompt_len"], n))
    budgets = rng.permutation(_spaced(*t["output_len"], n))
    return int(plens[k % n]), int(budgets[k % n])


def build_engine(ctx: dict):
    t = ctx["traffic"]
    llm = ctx["config"]["llm_config"]
    model_cfg = LLMConfig(**llm)
    model = LLM(model_cfg, compute_dtype=jnp.dtype(t["compute_dtype"]),
                attn_impl=t["attn_impl"])
    key = jax.random.PRNGKey(harness.seed31(ctx["seed"]))
    dummy = jnp.zeros((1, 8), jnp.int32)
    # float32 masters, as the serving loader holds a checkpoint today
    variables = jax.jit(model.init)({"params": key, "dropout": key}, dummy)
    jax.block_until_ready(variables)
    engine = DecodeEngine(model, variables, **t["engine"])
    return model_cfg, llm, variables, engine


def warm_programs(engine, vocab: int, t: dict) -> None:
    """Compile the two step programs the mix can reach (the chunk-carrying
    fused step and the plain decode step) before any client exists."""
    lo, hi = t["prompt_len"]
    rng = np.random.default_rng(0)
    engine.admit(rng.integers(0, vocab, hi).tolist(), 4)
    engine.admit(rng.integers(0, vocab, lo).tolist(), 4)
    while engine.n_live or engine.n_free < engine.n_slots:
        engine.step()


async def _drive(ctx, engine, timed, vocab: int, records: list):
    t = ctx["traffic"]
    say = ctx["say"]
    counter = itertools.count()
    sched = Scheduler(timed, max_queue=4 * engine.n_slots)
    await sched.start()

    async def client():
        while True:
            k = next(counter)
            plen, budget = request_sizes(t, ctx["seed"], k)
            prompt = np.random.default_rng(
                [harness.seed31(ctx["seed"]), k, 1]).integers(
                    0, vocab, plen).tolist()
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


def reference_check(ctx, engine, llm: dict, variables, vocab: int) -> dict:
    """(a) of `correct`: two seeded prompts prefilled and decoded through
    the (now idle) engine; every emitted token's reference logit lies
    within LOGIT_TOLERANCE of the reference maximum at its position."""
    t = ctx["traffic"]
    n_new = t["reference_new_tokens"]
    lens = t["reference_prompt_lens"]
    prompts = [synth.sample_tokens(ctx["seed"] + 3 + i, (n,), vocab).tolist()
               for i, n in enumerate(lens)]
    outs = engine.run(prompts, n_new)
    worst, agree, total = 0.0, 0, 0
    for prompt, full in zip(prompts, outs):
        full = [int(x) for x in full]
        assert full[:len(prompt)] == prompt and \
            len(full) == len(prompt) + n_new, "engine.run changed its shape"
        logits = reference.forward_logits(
            variables["params"], llm, jnp.asarray([full[:-1]], jnp.int32))[0]
        rows = np.asarray(logits[len(prompt) - 1:], np.float32)
        for row, tok in zip(rows, full[len(prompt):]):
            worst = max(worst, float(row.max() - row[tok]))
            agree += int(int(row.argmax()) == tok)
            total += 1
    return {"worst_gap": worst, "top1_agree": agree, "tokens": total,
            "ok": worst <= LOGIT_TOLERANCE}


def run(ctx: dict) -> dict:
    say = ctx["say"]
    t = ctx["traffic"]
    os.chdir(ctx["work_dir"])
    compile_log = compiles.CompileLog()
    model_cfg, llm, variables, engine = build_engine(ctx)
    vocab = model_cfg.vocab_size
    say(f"engine: {engine.n_slots} slots, {engine.n_blocks} blocks of "
        f"{engine.block_size}, chunk {engine.prefill_chunk}, cache "
        f"{np.dtype(engine.cache_dtype).name}")
    warm_programs(engine, vocab, t)
    say("step programs compiled")
    timed = TimedEngine(engine)
    records: list = []
    marks = asyncio.run(_drive(ctx, engine, timed, vocab, records))

    t_open, t_close = marks["t_open"], marks["t_close"]
    setup_s = t_open - stats.T_PROCESS_START
    # the rate is taken over whole engine steps: from the end of the first
    # step that ended inside the window to the end of the last one. A
    # step's tokens reach the clients after its end and before the next
    # step's, so the client stamps in [first end, last end) are exactly the
    # tokens of the steps between, and the window's edges no longer cut a
    # step in two (24 tokens, 0.4% of a 30 s window, came and went with it)
    ends = [s[1] for s in timed.steps if t_open <= s[1] < t_close]
    if len(ends) < 2:
        raise RuntimeError("fewer than two engine steps ended in the window")
    r_open, r_close = ends[0], ends[-1]
    window = r_close - r_open
    tok_in = 0
    ttft, itl = [], []
    attempted = failed = 0
    short = 0
    for r in records:
        ts = r["tok_t"]
        tok_in += sum(1 for x in ts if r_open <= x < r_close)
        itl.extend(b - a for a, b in zip(ts, ts[1:]) if t_open <= b < t_close)
        # finished, by whatever road, with anything but exactly its budget
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
    # time to first token is no end-to-end metric of this cell yet: a
    # window completes some 35 requests, so its 95th percentile would be
    # the second or third largest sample. It is printed with the highest
    # percentile the count supports, and its median is a per-layer metric.
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
    say(f"compiles: {len(compile_log.events)} programs, "
        f"{compile_log.total_seconds(t_open):.1f}s of set-up; inside the "
        f"window {[e[1] for e in late]}, retraces "
        f"{marks['traces_close'] - marks['traces_open']}")
    obs = {"peaks": ctx["peaks"],
           "counters": {"compiles_in_window": max(
               len(late), marks["traces_close"] - marks["traces_open"])},
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
    if ctx["trace"]:
        # every step that overlaps the slice: the trace holds part of the
        # kernel calls of the two at its edges, and live rows move by 24 of
        # ~5,000 a step, so the mean over these is the mean over the calls
        traced = [s for s in timed.steps
                  if s[1] > marks["t_trace0"] and s[0] < marks["t_trace1"]]
        itemsize = np.dtype(engine.cache_dtype).itemsize
        obs["counters"]["paged_decode_bytes_per_call"] = sum(
            flops_lib.paged_decode_bytes_per_call(llm, [s[3]], itemsize)
            for s in traced) / len(traced)
        obs["trace"] = trace_reduce.reduce_trace_dir(
            marks["trace_dir"], ctx["chips"], len(traced), say)

    ref = reference_check(ctx, engine, llm, variables, vocab)
    say(f"reference: worst gap of an emitted token's reference logit to the "
        f"reference maximum {ref['worst_gap']:.4f} (tolerance "
        f"{LOGIT_TOLERANCE}); top-1 agrees on {ref['top1_agree']} of "
        f"{ref['tokens']}")
    return {"correct": bool(ref["ok"] and short == 0),
            "compared": compared.budgets(short) + [compared.entry(
                "worst_gap", ref["worst_gap"], LOGIT_TOLERANCE, "at_most")],
            "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "observations": obs,
            "memory_peak_bytes": marks["memory_peak"]}
