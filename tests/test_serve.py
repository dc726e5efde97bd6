"""Async scheduler (serve/scheduler.py) over the DecodeEngine: FCFS
no-starvation, bucket-grouped admission waves, mid-decode cancellation
freeing the slot within a step, bounded-queue shed (an error, never a
hang), queue-wait deadlines, and stream parity with the offline engine."""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.serve.scheduler import (EngineError,
                                                     Scheduler, ShedError)


def tiny_cfg(**kw):
    base = dict(vocab_size=97, block_size=64, n_embd=48, n_head=4,
                n_kv_heads=2, attn="gqa", n_layer=2, up_dim=64,
                non_linearity="swiglu", pos_emb="rope", dropout=0.0)
    base.update(kw)
    return LLMConfig(**base)


@pytest.fixture(scope="module")
def mv():
    cfg = tiny_cfg()
    model = LLM(cfg, attn_impl="naive")
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = dict(model.init({"params": rng, "dropout": rng}, x, x))
    return cfg, model, variables


def run_async(coro, timeout=300):
    """Every test is wrapped in a hard timeout: a scheduler bug must fail
    the test, not hang the suite (and CI's serve step runs under its own
    `timeout` for the same reason)."""
    return asyncio.run(asyncio.wait_for(coro, timeout))


def make_engine(mv, n_slots=2, **kw):
    _, model, variables = mv
    kw.setdefault("temperature", 0.0)
    kw.setdefault("min_bucket", 8)
    return DecodeEngine(model, variables, n_slots=n_slots, **kw)


# ----------------------------------------------------------------------
# FCFS / starvation
# ----------------------------------------------------------------------

def test_fcfs_no_starvation_behind_short_stream(mv):
    """A queued long request is admitted in submission order even while a
    stream of later short requests keeps arriving — FCFS means nothing
    starves."""

    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=32)
        await sched.start()
        first = sched.submit([1, 2, 3], 2)
        long = sched.submit([4, 5, 6], 8)
        shorts = [sched.submit([7 + i], 2) for i in range(5)]
        handles = [first, long] + shorts
        await asyncio.gather(*(h.result() for h in handles))
        await sched.stop()
        return eng, sched, handles

    eng, sched, handles = run_async(main())
    admits = [h.admitted_at for h in handles]
    assert all(a is not None for a in admits), "a request starved"
    # single slot + same bucket for everyone: admission order must equal
    # submission order — in particular the long request admitted before
    # every short submitted after it
    assert admits == sorted(admits)
    assert all(h.retired.reason == "budget" for h in handles)
    # "max wait bounded": the whole run bounds every queue wait
    assert sched.metrics.queue_wait.max < 300
    assert sched.metrics.counters["admitted"] == len(handles)
    assert sched.metrics.counters["shed"] == 0


def test_admission_wave_groups_by_prefill_bucket(mv):
    """Within one admission wave, prompts are grouped by pow2 bucket so
    same-bucket prefills run back-to-back on one compiled trace; across
    the wave nothing is reordered beyond that (stable sort)."""

    async def main():
        eng = make_engine(mv, n_slots=4)
        sched = Scheduler(eng, max_queue=8)
        # queue BEFORE starting the loop: one wave admits all four
        h_big1 = sched.submit(list(range(1, 18)), 2)    # bucket 32
        h_small1 = sched.submit([1, 2, 3], 2)           # bucket 8
        h_big2 = sched.submit(list(range(1, 21)), 2)    # bucket 32
        h_small2 = sched.submit([4, 5], 2)              # bucket 8
        await sched.start()
        handles = [h_big1, h_small1, h_big2, h_small2]
        await asyncio.gather(*(h.result() for h in handles))
        await sched.stop()
        return eng, handles

    eng, (h_big1, h_small1, h_big2, h_small2) = run_async(main())
    # both bucket-8 prefills ran before both bucket-32 prefills
    assert max(h_small1.admitted_at, h_small2.admitted_at) \
        < min(h_big1.admitted_at, h_big2.admitted_at)
    # stable within a bucket: submission order preserved
    assert h_small1.admitted_at < h_small2.admitted_at
    assert h_big1.admitted_at < h_big2.admitted_at
    assert set(eng.admit_traces) == {8, 32}
    assert set(eng.admit_traces.values()) == {1}


# ----------------------------------------------------------------------
# cancellation
# ----------------------------------------------------------------------

def test_cancel_mid_decode_frees_slot_within_one_step(mv):
    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        h = sched.submit([1, 2, 3], 40)
        got = []
        async for tok in h:
            got.append(tok)
            if len(got) == 3:
                break
        steps_at_cancel = eng.n_steps
        h.cancel()
        ret = await h.result()
        steps_done = eng.n_steps
        # the slot must be reusable immediately: a fresh request decodes
        b = sched.submit([9, 8, 7], 2)
        await b.result()
        await sched.stop()
        return eng, h, ret, got, steps_at_cancel, steps_done, b

    eng, h, ret, got, s0, s1, b = run_async(main())
    assert ret.reason == "cancelled"
    # the loop free-runs, so one step may be in flight when cancel lands
    # and one more may start before the flag is applied — but never the
    # remaining ~37 steps of budget. With one program queued ahead (PR
    # 31) the bound stands: `n_steps` counts programs DISPATCHED, the
    # call that is running when the cancel lands has already queued the
    # one behind it (counted in s0 or the first of the two), and the
    # cancelled occupant's token in that program is dropped, not emitted
    assert s1 - s0 <= 2, f"cancel took {s1 - s0} steps to free the slot"
    assert eng.retire_counts["cancelled"] == 1
    assert len(h.tokens) < 10          # nowhere near the 40-token budget
    assert b.retired.reason == "budget"
    assert eng.n_live == 0


def test_cancel_while_queued_never_touches_engine(mv):
    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 30)
        await a.__anext__()                       # a holds the only slot
        q = sched.submit([4, 5], 10)              # parked in the queue
        q.cancel()
        ret = await q.result()
        a.cancel()
        await a.result()
        await sched.stop()
        return eng, sched, ret, q

    eng, sched, ret, q = run_async(main())
    assert ret.reason == "cancelled"
    assert q.admitted_at is None                  # never reached a slot
    assert eng.n_admitted == 1                    # only a touched the engine
    assert sched.metrics.counters["cancelled"] == 2


# ----------------------------------------------------------------------
# backpressure: bounded queue + deadlines, shed is an error not a hang
# ----------------------------------------------------------------------

def test_queue_bound_sheds_immediately(mv):
    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=2)
        await sched.start()
        a = sched.submit([1, 2, 3], 40)
        await a.__anext__()                       # admitted: queue empty
        b = sched.submit([4], 2)
        c = sched.submit([5], 2)
        with pytest.raises(ShedError) as ei:
            sched.submit([6], 2)
        a.cancel()
        await asyncio.gather(a.result(), b.result(), c.result())
        await sched.stop()
        return sched, ei.value

    sched, err = run_async(main())
    assert err.cause == "queue_full"
    assert sched.metrics.counters["shed"] == 1
    assert sched.metrics.shed_counts == {"queue_full": 1}
    # the two queued requests still completed (bound ≠ starvation)
    assert sched.metrics.counters["completed"] == 2


def test_deadline_shed_surfaces_as_error(mv):
    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 30)
        await a.__anext__()
        b = sched.submit([4, 5], 10, deadline_s=0.0)  # can't make it
        with pytest.raises(ShedError) as ei:
            await b.result()
        a.cancel()
        await a.result()
        await sched.stop()
        return sched, ei.value

    sched, err = run_async(main())
    assert err.cause == "deadline"
    assert sched.metrics.shed_counts.get("deadline") == 1


def test_stop_sheds_queued_and_cancels_live(mv):
    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 40)
        await a.__anext__()
        b = sched.submit([4, 5], 10)              # still queued
        await sched.stop()
        assert a.retired is not None and a.retired.reason == "cancelled"
        with pytest.raises(ShedError) as ei:
            await b.result()
        assert ei.value.cause == "shutdown"
        with pytest.raises(ShedError):
            sched.submit([6], 2)                  # post-stop submit sheds
        return eng

    eng = run_async(main())
    assert eng.n_live == 0


# ----------------------------------------------------------------------
# engine failure: every pending stream errors (never hangs), health flips
# ----------------------------------------------------------------------

def test_step_loop_crash_fails_all_pending_and_flips_health(mv):
    """Regression: an exception escaping the background step loop must
    fail EVERY pending handle with an explicit EngineError — the live
    stream AND the queued one — flip `healthy` False (healthz 503), and
    shed later submits immediately. Before the fix, handles could wait
    forever on a loop that no longer existed."""

    async def main():
        eng = make_engine(mv, n_slots=1)
        calls = []
        orig_step = eng.step

        def dying_step():
            calls.append(1)
            if len(calls) >= 2:
                raise RuntimeError("device lost")
            return orig_step()

        eng.step = dying_step
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 30)       # takes the only slot
        b = sched.submit([4, 5], 10)          # parked in the queue
        errors = []
        for h in (a, b):
            try:
                await h.result()
            except EngineError as e:
                errors.append(e)
        healthy = sched.healthy
        try:
            sched.submit([6], 2)
            post_shed = None
        except ShedError as e:
            post_shed = e
        await sched.stop()
        return sched, errors, healthy, post_shed

    sched, errors, healthy, post_shed = run_async(main(), timeout=60)
    assert len(errors) == 2, "a pending stream hung or finished silently"
    assert all("device lost" in str(e) for e in errors)
    assert healthy is False
    assert sched.failed is not None
    assert post_shed is not None and post_shed.cause == "engine_error"


def test_admission_crash_fails_wave_popped_requests(mv):
    """Regression for the subtle half of the bug: an admission wave pops
    requests off the queue into a loop-local list BEFORE admitting them.
    If `engine.admit` then raises, those requests are in neither `_live`
    nor `_queue` — the old crash guard missed them and their streams
    hung forever. The pending-handle registry must fail them too."""

    async def main():
        eng = make_engine(mv, n_slots=2)
        calls = []
        orig_admit = eng.admit

        def dying_admit(prompt, max_new):
            calls.append(1)
            if len(calls) >= 2:
                raise RuntimeError("admit exploded")
            return orig_admit(prompt, max_new)

        eng.admit = dying_admit
        sched = Scheduler(eng, max_queue=8)
        # queue BOTH before the loop starts: one wave pops both, the
        # second admit raises with request #2 in the wave-local list
        a = sched.submit([1, 2, 3], 4)
        b = sched.submit([4, 5], 4)
        await sched.start()
        errors = []
        for h in (a, b):
            try:
                await h.result()
            except EngineError as e:
                errors.append(e)
        await sched.stop()
        return errors

    errors = run_async(main(), timeout=60)
    assert len(errors) == 2, \
        "a wave-popped request's stream hung on an admission crash"


# ----------------------------------------------------------------------
# draining: admission stops, queued + live work still completes
# ----------------------------------------------------------------------

def test_drain_sheds_new_serves_queued_and_live(mv):
    async def main():
        eng = make_engine(mv, n_slots=1)
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 8)        # live on the only slot
        b = sched.submit([4, 5], 4)           # queued
        await a.__anext__()
        assert not sched.draining
        sched.drain()
        try:
            sched.submit([6], 2)
            shed = None
        except ShedError as e:
            shed = e
        ra = await a.result()
        rb = await b.result()
        drained = sched.drained
        healthy = sched.healthy               # loop alive, just gated
        await sched.stop()
        return sched, shed, ra, rb, drained, healthy, a, b

    sched, shed, ra, rb, drained, healthy, a, b = run_async(main())
    assert shed is not None and shed.cause == "draining"
    assert sched.metrics.shed_counts.get("draining") == 1
    # drain never drops accepted work: the live stream AND the queued
    # one both deliver their full budgets
    assert ra.reason == "budget" and len(a.tokens) == 8
    assert rb.reason == "budget" and len(b.tokens) == 4
    assert drained is True
    assert healthy is True


# ----------------------------------------------------------------------
# block-level preemption: requeued, never shed
# ----------------------------------------------------------------------

def test_preempted_requests_requeue_not_shed(mv):
    """With a block pool too small for every live sequence's full output,
    the engine preempts mid-decode — the scheduler must resubmit the
    victim at the queue head and every request must still deliver its
    full budget: zero requests lost, zero shed."""

    async def main():
        # capacity 11 blocks; two 48-row sequences need 6 blocks each
        eng = make_engine(mv, n_slots=2, n_blocks=12)
        sched = Scheduler(eng, max_queue=16)
        await sched.start()
        handles = [sched.submit([i + 1, i + 2, i + 3], 45) for i in range(2)]
        await asyncio.gather(*(h.result() for h in handles))
        await sched.stop()
        return eng, sched, handles

    eng, sched, handles = run_async(main())
    assert eng.retire_counts["preempted"] >= 1, \
        "pool was sized to force preemption"
    m = sched.metrics
    assert m.counters["preempted"] == m.counters["requeued"] >= 1
    assert m.counters["shed"] == 0
    assert m.counters["completed"] == len(handles)
    for h in handles:
        assert h.retired.reason == "budget"
        assert len(h.tokens) == 45            # the full budget, seamless
        assert h.retired.prompt_len == 3      # original prompt, not resume
        assert h.retired.tokens[:3] == h.retired.tokens[:3]
        assert h.retired.tokens[3:] == h.tokens
    # preemption resumes hit the prefix cache (retained blocks)
    assert eng.prefix_hit_tokens > 0
    # gauges are exported through the bench summary
    s = m.summary()
    assert "serve_block_utilization" in s["gauges"]
    assert "serve_prefix_hit_rate" in s["gauges"]


def test_preemption_budget_ignores_consumer_lag(mv):
    """The resume budget must come from the scheduler-side served count,
    not the consumer-paced handle.tokens: a client that hasn't drained a
    single token when preemption lands must still receive EXACTLY its
    budget (no re-generated duplicates, no over-emission, no crash from a
    <=0 resume budget after repeated preemptions)."""

    async def main():
        eng = make_engine(mv, n_slots=2, n_blocks=12)
        sched = Scheduler(eng, max_queue=16)
        await sched.start()
        handles = [sched.submit([i + 1, i + 2, i + 3], 45) for i in range(2)]
        # do NOT drain: wait for retirement with the streams untouched,
        # so handle.tokens stays empty through every preemption/resume
        while any(h.retired is None for h in handles):
            await asyncio.sleep(0.01)
        assert all(len(h.tokens) == 0 for h in handles)  # truly undrained
        await asyncio.gather(*(h.result() for h in handles))
        await sched.stop()
        return eng, sched, handles

    eng, sched, handles = run_async(main())
    assert eng.retire_counts["preempted"] >= 1, \
        "pool was sized to force preemption"
    assert sched.metrics.counters["shed"] == 0
    for h in handles:
        assert h.retired.reason == "budget"
        assert len(h.tokens) == 45            # exactly the budget
        assert h.retired.prompt_len == 3
        assert h.retired.tokens[3:] == h.tokens


def test_truncated_prompt_reports_kept_prompt_len(mv):
    """A prompt >= max_len is truncated by the engine to its last
    max_len-1 tokens; the final record's prompt_len must point at the
    generated-output boundary WITHIN ret.tokens (slicing
    tokens[prompt_len:] yields exactly the generated stream), not the
    untruncated submitted length."""

    async def main():
        eng = make_engine(mv, n_slots=1)          # max_len = block_size = 64
        sched = Scheduler(eng, max_queue=4)
        await sched.start()
        h = sched.submit(list(range(1, 71)), 2)   # 70 tokens > max_len
        ret = await h.result()
        await sched.stop()
        return h, ret

    h, ret = run_async(main())
    assert ret.reason == "budget"
    assert ret.prompt_len == 63                   # the kept suffix
    assert len(ret.tokens) == 63 + 2
    assert ret.tokens[ret.prompt_len:] == h.tokens


# ----------------------------------------------------------------------
# stream parity with the offline engine
# ----------------------------------------------------------------------

def test_streams_match_offline_engine_greedy(mv):
    """Concurrent scheduler streams are bit-identical to the offline
    DecodeEngine run with the same per-request budgets (greedy)."""
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [20] * 17, [42, 43],
               [9], [60, 61, 62, 63], [30] * 12, [2, 4, 6]]
    budgets = [2, 6, 3, 5, 4, 2, 6, 3]

    async def main():
        eng = make_engine(mv, n_slots=2)
        sched = Scheduler(eng, max_queue=16)
        await sched.start()
        handles = [sched.submit(p, b) for p, b in zip(prompts, budgets)]
        await asyncio.gather(*(h.result() for h in handles))
        await sched.stop()
        return sched, handles

    sched, handles = run_async(main())
    ref_eng = make_engine(mv, n_slots=2)
    refs = ref_eng.run(prompts, budgets)
    for p, b, h, ref in zip(prompts, budgets, handles, refs):
        assert h.retired.tokens == ref, f"stream diverged for prompt {p}"
        assert h.tokens == ref[len(p):]           # streamed = generated
        assert h.retired.reason == "budget"
        assert len(h.tokens) == b
    m = sched.metrics
    assert m.counters["admitted"] == len(prompts)
    assert m.ttft.count == len(prompts)
    assert m.itl.count > 0
    assert m.e2e.count == len(prompts)
    assert m.mean_occupancy > 0.5                 # 8 reqs through 2 slots


# ----------------------------------------------------------------------
# chunked prefill through the scheduler (round 12: live streams never stall)
# ----------------------------------------------------------------------

def test_chunked_decode_priority_live_stream_never_stalls(mv):
    """The chunked-prefill contract end-to-end: while a long prompt
    chunks into the fused step, every already-live stream emits a token
    on EVERY step — decode work is never preempted by prefill work — and
    the chunks are full: the program computes 16 chunk rows whatever
    decodes beside them. The per-step emission log is recorded inside the
    engine-step wrapper, so the assertion is exact, not timing-based."""

    async def main():
        eng = make_engine(mv, n_slots=2, prefill_chunk=16, block_size=8)
        log = []
        orig_step = eng.step

        def recording_step():
            res = orig_step()
            log.append((set(res.emitted), res.prefill_tokens))
            return res

        eng.step = recording_step
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 30)
        async for _ in a:                    # A is live and decoding
            break
        b = sched.submit(list(range(1, 40)), 4)
        await asyncio.gather(a.result(), b.result())
        await sched.stop()
        return eng, sched, a, b, log

    eng, sched, a, b, log = run_async(main())
    a_id, b_id = a._req.seq_id, b._req.seq_id
    b_first = next(i for i, (em, _) in enumerate(log) if b_id in em)
    # B's 39-token prompt chunked in over three steps: the oldest partial
    # prompt fills the 16-row chunk buffer while A decodes beside it
    chunk_steps = [i for i, (_, pt) in enumerate(log[:b_first + 1]) if pt]
    assert [log[i][1] for i in chunk_steps] == [3, 16, 16, 7], \
        f"expected A's chunk and three of B's, got {chunk_steps}"
    chunk_steps = chunk_steps[1:]
    # the pinned property: A emitted on every step of B's chunk-in
    # window (A retires on budget later, so it is live throughout)
    for i in range(chunk_steps[0], b_first + 1):
        assert a_id in log[i][0], f"live stream stalled at step {i}"
    # B's first token came from the fused step that ran its last chunk
    assert b_id not in {s for em, _ in log[:b_first] for s in em}
    # observability: the per-step histogram saw every chunk and sums to
    # the tokens actually prefilled
    h = sched.metrics.prefill_tokens_per_step.summary(unit="tok", scale=1.0)
    assert h["count"] == len(log)
    assert sched.metrics.prefill_tokens_per_step.sum == \
        eng.prefilled_tokens
    # 42 ids in four chunk-carrying programs of 16 rows, two prompts
    gauges = sched.metrics.summary()["gauges"]
    assert gauges["serve_chunk_fill_share"] == \
        pytest.approx(42 / 64, abs=1e-4)
    assert gauges["serve_chunk_programs_per_prompt"] == pytest.approx(2.0)
    assert "serve_chunk_fill_share 0.65625" in \
        sched.metrics.render_prometheus()
    # greedy parity with the offline chunked engine
    ref_eng = make_engine(mv, n_slots=2, prefill_chunk=16, block_size=8)
    refs = ref_eng.run([[1, 2, 3], list(range(1, 40))], [30, 4])
    assert a.retired.tokens == refs[0]
    assert b.retired.tokens == refs[1]


def test_wave_admission_records_decode_stall(mv):
    """The decode_stall counter pins the wave baseline's failure mode: a
    monolithic admission that runs while streams are live books its full
    prefill wall-clock as stall time (the chunked path admits without
    running any prefill, so the same counter stays near zero there)."""

    async def main():
        eng = make_engine(mv, n_slots=2)
        sched = Scheduler(eng, max_queue=8)
        await sched.start()
        a = sched.submit([1, 2, 3], 20)
        async for _ in a:                    # A is live when B admits
            break
        b = sched.submit(list(range(1, 40)), 2)
        await asyncio.gather(a.result(), b.result())
        await sched.stop()
        return sched

    sched = run_async(main())
    assert sched.metrics.decode_stall_s > 0.0
    gauges = sched.metrics.summary()["gauges"]
    assert gauges["serve_decode_stall_ms"] > 0.0
    # wave mode books prefilled-tokens-per-ADMISSION into the histogram
    assert sched.metrics.prefill_tokens_per_step.count >= 2
