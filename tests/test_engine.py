"""DecodeEngine (engine/decode.py): continuous batching over the slot
cache. Greedy engine output must be bit-identical to the one-shot
`generate` path per prompt regardless of admission/retirement order; the
fused step must trace exactly once across a ragged run; prefill traces are
bounded by the power-of-two buckets; and the whole thing runs under a tp
CPU mesh with a sharded cache."""

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.generate import generate
from distributed_pytorch_tpu.models.gpt import LLM


def tiny_cfg(**kw):
    base = dict(vocab_size=97, block_size=64, n_embd=48, n_head=4,
                n_kv_heads=2, attn="gqa", n_layer=2, up_dim=64,
                non_linearity="swiglu", pos_emb="rope", dropout=0.0,
                q_latent_dim=16, kv_latent_dim=16, rope_head_dim=8)
    base.update(kw)
    return LLMConfig(**base)


def build(cfg, seed=0, attn_impl="naive"):
    model = LLM(cfg, attn_impl=attn_impl)
    rng = jax.random.PRNGKey(seed)
    x = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = model.init({"params": rng, "dropout": rng}, x, x)
    return model, {k: v for k, v in variables.items()}


PROMPTS = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [20] * 17, [42, 43], [9]]


@pytest.mark.parametrize("kw", [
    dict(attn="gqa", n_kv_heads=2, pos_emb="rope"),
    dict(attn="mla", pos_emb="rope"),
    dict(attn="mha", pos_emb="learn"),
], ids=["gqa-rope", "mla-rope", "mha-learn"])
def test_engine_matches_generate_greedy(kw):
    """Ragged continuous batching (5 prompts through 2 slots) is
    token-identical to decoding each prompt alone — slot reuse, pad rows,
    and neighbors at other positions must be invisible."""
    cfg = tiny_cfg(**kw)
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8)
    outs = eng.run(PROMPTS, max_new_tokens=6)
    for p, o in zip(PROMPTS, outs):
        ref = generate(model, variables, jnp.asarray(p, jnp.int32)[None], 6,
                       temperature=0.0)[0].tolist()
        assert o == ref, f"engine diverged from generate for prompt {p}"


def test_single_step_trace_and_bucketed_prefill():
    """One compiled step function serves the whole ragged run (no
    per-admission retrace); prefill compiles once per power-of-two
    bucket."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=3, temperature=0.0,
                       min_bucket=8)
    eng.run(PROMPTS, max_new_tokens=5)
    assert eng.step_traces == 1
    # prompt lens 3,7,17,2,1 -> buckets {8, 32}; each traced exactly once
    assert eng.admit_traces == {8: 3, 32: 1} or \
        set(eng.admit_traces.values()) == {1} and \
        set(eng.admit_traces) == {8, 32}
    # second run with the same buckets: zero new traces
    eng2_out = eng.run([[3, 1], [4, 1, 5, 9, 2, 6]], max_new_tokens=4)
    assert eng.step_traces == 1
    assert set(eng.admit_traces) == {8, 32}
    assert len(eng2_out) == 2


def test_engine_moe():
    cfg = tiny_cfg(moe=True, n_exp=4, n_shared=1, n_act=2, aux_free=True)
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8)
    outs = eng.run(PROMPTS[:3], max_new_tokens=4)
    for p, o in zip(PROMPTS[:3], outs):
        ref = generate(model, variables, jnp.asarray(p, jnp.int32)[None], 4,
                       temperature=0.0)[0].tolist()
        assert o == ref


def test_eos_and_budget_retirement():
    """A sequence retires on EOS, the rest run to their budget; retired
    slots are reusable immediately."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    # discover the greedy continuation, then use its first generated token
    # as the 'EOS' id for one prompt
    ref = generate(model, variables, jnp.asarray([[1, 2, 3]], jnp.int32), 5,
                   temperature=0.0)[0].tolist()
    eos = ref[3]
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       eos_id=eos, min_bucket=8)
    outs = eng.run([[1, 2, 3], [5, 6, 7, 8]], max_new_tokens=5)
    assert outs[0] == ref[:4]          # stopped at the EOS token
    assert len(outs[1]) in (4 + 5, 9)  # full budget unless EOS hit
    assert eng.free_slots == [0, 1]


def test_cache_full_retires_before_wrap():
    """A slot whose next write would wrap the ring retires instead of
    silently entering sliding-window territory."""
    cfg = tiny_cfg(block_size=16)
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8)
    out = eng.run([[1, 2, 3, 4, 5]], max_new_tokens=1000)
    # every cache row fills (the final sampled token needs no row):
    # 5 prompt + 11 written + 1 unwritten = max_len + 1 tokens
    assert len(out[0]) == cfg.block_size + 1


def test_engine_tp_mesh_sharded_cache():
    """The engine decodes under a tensor-parallel CPU mesh: params laid
    out by the tp recipe tables, the merged-lane pools' lanes (2 kv heads
    x 64 = 128, no pad: a lane split is a head split) sharded over
    'model', and greedy outputs identical to the unsharded engine."""
    from distributed_pytorch_tpu.parallel.mesh import mesh_for

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device CPU platform")
    cfg = tiny_cfg(attn="gqa", n_kv_heads=2, n_head=4, n_embd=256)
    model, variables = build(cfg)
    ref_eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                           min_bucket=8)
    refs = ref_eng.run(PROMPTS[:4], max_new_tokens=5)

    mesh = mesh_for("tp", tp_size=2)
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, mesh=mesh, recipe="tp")
    k_cache = eng.caches[0]["k"]  # (n_blocks, bs, n_kv * hs)
    assert k_cache.shape[2] == 128
    spec = k_cache.sharding.spec
    assert spec[2] == "model", f"kv heads not tp-sharded: {spec}"
    outs = eng.run(PROMPTS[:4], max_new_tokens=5)
    assert outs == refs


def test_engine_tp_mesh_padded_lanes_stay_whole():
    """2 kv heads x 12 = 24 lanes padded to 128: a lane split would cut
    across heads and pad, so under tp the pool is whole on every model
    shard (sharding.decode_cache_pspec), and decoding still agrees."""
    from distributed_pytorch_tpu.parallel.mesh import mesh_for

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device CPU platform")
    cfg = tiny_cfg(attn="gqa", n_kv_heads=2, n_head=4)
    model, variables = build(cfg)
    refs = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                        min_bucket=8).run(PROMPTS[:3], max_new_tokens=4)
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, mesh=mesh_for("tp", tp_size=2),
                       recipe="tp")
    k_cache = eng.caches[0]["k"]
    assert k_cache.shape[2] == 128
    assert "model" not in tuple(k_cache.sharding.spec)
    assert eng.run(PROMPTS[:3], max_new_tokens=4) == refs


@pytest.mark.parametrize("kw", [
    dict(attn="gqa", n_kv_heads=2, pos_emb="rope"),
    dict(attn="mla", pos_emb="rope"),
], ids=["gqa-rope", "mla-rope"])
def test_prefix_reuse_bit_identical(kw):
    """Prompts sharing a block-aligned prefix admit with a prefix-cache
    hit (only the suffix prefills) and still decode bit-identically to
    the one-shot oracle — shared blocks are immutable, positions line
    up, and the traced prefix length adds no prefill traces."""
    cfg = tiny_cfg(**kw)
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8)
    shared = list(range(1, 25))                  # 3 full 8-blocks
    prompts = [shared + [30, 31], shared + [40], shared + [50, 51, 52]]
    outs = eng.run(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        ref = generate(model, variables, jnp.asarray(p, jnp.int32)[None], 6,
                       temperature=0.0)[0].tolist()
        assert o == ref, f"prefix-reuse diverged for prompt {p}"
    # followers 2 and 3 hit the 24-token prefix
    assert eng.prefix_hit_tokens == 2 * 24
    assert eng.prefilled_tokens < sum(len(p) for p in prompts)
    assert eng.prefix_hit_rate > 0.5
    # reuse rides the SAME bucket traces (prefix length is traced)
    assert eng.step_traces == 1


def test_prefix_cache_off_is_the_baseline():
    cfg = tiny_cfg()
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, prefix_cache=False)
    shared = list(range(1, 25))
    prompts = [shared + [30, 31], shared + [40]]
    outs = eng.run(prompts, max_new_tokens=4)
    assert eng.prefix_hit_tokens == 0
    assert eng.prefilled_tokens == sum(len(p) for p in prompts)
    ref_eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                           min_bucket=8)
    assert outs == ref_eng.run(prompts, max_new_tokens=4)


def test_preemption_requeues_and_stays_bit_identical():
    """A pool too small for every live sequence's full output preempts
    the youngest mid-decode; run() requeues it (tokens so far become the
    prompt, retained blocks give a prefix hit) and the final outputs are
    STILL bit-identical to the oracle — preemption must be invisible in
    the tokens."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    # bs=8, max_len=64 -> 8 blocks/seq worst case; capacity 11 blocks
    # cannot hold two 6-block sequences once both grow
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, n_blocks=12)
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11]]
    outs = eng.run(prompts, max_new_tokens=40)
    assert eng.retire_counts["preempted"] >= 1, \
        "pool was sized to force preemption"
    for p, o in zip(prompts, outs):
        ref = generate(model, variables, jnp.asarray(p, jnp.int32)[None],
                       40, temperature=0.0)[0].tolist()
        assert o == ref, "preemption/resume changed the output"
    assert eng.block_pool.n_referenced == 0      # nothing leaked


def test_engine_paged_kernel_matches_naive(monkeypatch):
    """FLASH_DECODE=on drives the fused step through the PAGED kernel
    (interpret off-TPU) — tokens must match the FLASH_DECODE=off
    gather+naive engine exactly."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    monkeypatch.setenv("FLASH_DECODE", "off")
    ref_eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                           min_bucket=8)
    refs = ref_eng.run(PROMPTS[:3], max_new_tokens=5)
    monkeypatch.setenv("FLASH_DECODE", "on")
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8)
    assert eng.run(PROMPTS[:3], max_new_tokens=5) == refs


# ----------------------------------------------------------------------
# chunked prefill fused into the decode step (prefill_chunk > 0)
# ----------------------------------------------------------------------

# mixed mix on purpose: a trivial prompt, a multi-chunk long prompt, and
# a mid-size one — lengths chosen so prompt + budget stays under max_len
# (past it the engine retires 'cache_full' by design and the one-shot
# oracle no longer defines the answer)
CHUNK_PROMPTS = [[1, 2, 3], list(range(1, 40)), [7] * 10]


@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["native", "int8"])
@pytest.mark.parametrize("kw", [
    dict(attn="mha", n_kv_heads=4, pos_emb="learn"),
    dict(attn="gqa", n_kv_heads=2, pos_emb="rope"),
    dict(attn="mla", pos_emb="rope"),
], ids=["mha", "gqa", "mla"])
def test_chunked_matches_oneshot(kw, cache_dtype):
    """Chunked-vs-oneshot greedy bit-parity matrix: splitting a prompt
    into fused <=16-token chunks must be invisible in the tokens for
    dense/GQA/MLA and for the int8 KV cache (per-row scales make the
    quantization chunking-independent). The native legs are also pinned
    against the one-shot `generate` oracle; int8 legs against the wave
    engine (the int8-vs-bf16 tolerance is test_quant.py's contract)."""
    cfg = tiny_cfg(**kw)
    model, variables = build(cfg)
    kwargs = dict(n_slots=2, temperature=0.0, min_bucket=8, block_size=8,
                  cache_dtype=cache_dtype)
    wave = DecodeEngine(model, variables, **kwargs)
    refs = wave.run([list(p) for p in CHUNK_PROMPTS], max_new_tokens=12)
    if cache_dtype is None:
        for p, r in zip(CHUNK_PROMPTS, refs):
            assert r == generate(model, variables,
                                 jnp.asarray(p, jnp.int32)[None], 12,
                                 temperature=0.0)[0].tolist()
    eng = DecodeEngine(model, variables, prefill_chunk=16, **kwargs)
    outs = eng.run([list(p) for p in CHUNK_PROMPTS], max_new_tokens=12)
    assert outs == refs, "chunked prefill changed the greedy output"
    assert eng.fused_step_traces == 1
    assert eng.admit_traces == {}, "chunked admission must not prefill"


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["prefix-on", "prefix-off"])
def test_chunked_prefix_reuse_bit_identical(prefix_cache):
    """Chunking composes with radix prefix matching: a re-admitted prompt
    hits the blocks its own chunks registered (chunk boundaries register
    full blocks as they fill — not only at retirement) and skips straight
    to the tail, still bit-identical to the oracle; the prefix-off
    baseline re-chunks everything and must agree too."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8, prefill_chunk=16, block_size=8,
                       prefix_cache=prefix_cache)
    p = list(range(1, 40))
    ref = generate(model, variables, jnp.asarray(p, jnp.int32)[None], 12,
                   temperature=0.0)[0].tolist()
    assert eng.run([list(p)], max_new_tokens=12)[0] == ref
    # second admission of the same prompt: block-aligned prefix served
    # from cache (the partial tail stays private, so < len(p))
    assert eng.run([list(p)], max_new_tokens=12)[0] == ref
    if prefix_cache:
        assert 0 < eng.prefix_hit_tokens < 2 * len(p)
    else:
        assert eng.prefix_hit_tokens == 0
        assert eng.prefilled_tokens == 2 * len(p)
    assert eng.fused_step_traces == 1


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["prefix-on", "prefix-off"])
def test_chunked_mid_prefill_preemption_bit_identical(prefix_cache):
    """A pool too small for a decode stream plus a multi-chunk prompt
    preempts the partial MID-PREFILL; run() requeues it and the resume
    (a prefix hit on its already-written blocks when the cache is on, a
    full re-chunk when off) still produces oracle-identical tokens."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    # bs=8: the 39-token prompt needs 5 blocks mid-prefill and 8 by
    # budget end, the short stream grows to 3 — 8 usable blocks force a
    # preemption while the long prompt is still chunking in
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, prefill_chunk=16, block_size=8,
                       n_blocks=9, prefix_cache=prefix_cache)
    prompts = [[1, 2, 3], list(range(1, 40))]
    outs = eng.run([list(p) for p in prompts], max_new_tokens=20)
    assert eng.retire_counts["preempted"] >= 1, \
        "pool was sized to force a mid-prefill preemption"
    for p, o in zip(prompts, outs):
        ref = generate(model, variables, jnp.asarray(p, jnp.int32)[None],
                       20, temperature=0.0)[0].tolist()
        assert o == ref, "mid-prefill preemption changed the output"
    assert (eng.prefix_hit_tokens > 0) == prefix_cache
    assert eng.block_pool.n_referenced == 0      # nothing leaked


def test_chunked_single_fused_trace_across_prompt_mix():
    """ONE fused-step trace regardless of prompt mix: chunk slot, write
    offset, and valid length are traced arguments, so 1-token prompts,
    multi-chunk prompts, and back-to-back runs all share the compiled
    program — and chunked admission adds zero prefill traces."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    eng = DecodeEngine(model, variables, n_slots=3, temperature=0.0,
                       min_bucket=8, prefill_chunk=16, block_size=8)
    eng.run([[9], [1, 2, 3], list(range(1, 40)), [7] * 10, [42, 43]],
            max_new_tokens=5)
    assert eng.fused_step_traces == 1
    assert eng.step_traces <= 1          # pure-decode steps share one too
    assert eng.admit_traces == {}
    eng.run([[2, 4, 6], list(range(50, 80))], max_new_tokens=4)
    assert eng.fused_step_traces == 1
    assert eng.step_traces <= 1
    assert eng.admit_traces == {}


@pytest.mark.parametrize("n_ids, takes", [
    (16, [16]), (17, [16, 1]), (39, [16, 16, 7]),
], ids=["one-chunk", "one-id-over", "three-chunks"])
def test_chunk_fills_the_rows_its_program_computes(n_ids, takes):
    """The fused program computes `prefill_chunk` chunk rows whatever they
    hold, so the pick fills them: beside two live decoding slots a prompt
    of `prefill_chunk` ids is ONE chunk-carrying program and one id more
    makes two, every chunk starts on a block boundary, both live streams
    emit on every step of the chunk-in, every stream is the one-shot
    oracle's, and the fill counter is real ids over rows computed."""
    cfg, model, variables = _mv()
    eng = DecodeEngine(model, variables, n_slots=3, temperature=0.0,
                       min_bucket=8, block_size=8, prefill_chunk=16)
    picks = []
    pick = eng._next_chunk

    def recording_pick(preempted, ahead):
        out = pick(preempted, ahead)
        if out is not None:
            seq = eng._slots[out[0]]
            picks.append((seq.prefix_len + seq.suffix_done, out[1]))
        return out

    eng._next_chunk = recording_pick
    prompts = {"A": [50, 51, 52], "B": [60, 61, 62, 63],
               "P": list(range(1, n_ids + 1))}
    budgets = {"A": 30, "B": 30, "P": 6}
    sid = {n: eng.admit(prompts[n], budgets[n]).seq_id for n in "AB"}
    streams = {n: [] for n in prompts}
    name_of = {v: k for k, v in sid.items()}

    def step():
        res = eng.step()
        for s, toks in res.emitted.items():
            streams[name_of[s]] += toks
        return res

    while not (streams["A"] and streams["B"]):
        step()                              # both decode from here on
    assert (eng.chunk_programs, eng.chunked_prompts) == (2, 2)
    assert picks == [(0, 3), (0, 4)]
    del picks[:]
    sid["P"] = eng.admit(prompts["P"], budgets["P"]).seq_id
    name_of[sid["P"]] = "P"
    carried = []
    while eng.n_live:
        res = step()
        if res.prefill_tokens:
            carried.append((res.prefill_tokens, set(res.emitted)))
    assert picks == [(16 * i, t) for i, t in enumerate(takes)]
    assert all(off % eng.block_size == 0 for off, _ in picks)
    assert [t for t, _ in carried] == takes
    for _, emitted in carried[:-1]:
        assert emitted == {sid["A"], sid["B"]}, "a live stream stalled"
    assert carried[-1][1] == set(sid.values())   # P's first token with it
    for n, p in prompts.items():
        want = generate(model, variables, jnp.asarray(p, jnp.int32)[None],
                        budgets[n], temperature=0.0)[0].tolist()
        assert streams[n] == want[len(p):], n
    assert eng.chunk_programs == 2 + len(takes)
    assert eng.chunked_prompts == 3
    assert eng.prefilled_tokens == 7 + n_ids
    assert eng.chunk_fill_share == pytest.approx(
        (7 + n_ids) / ((2 + len(takes)) * 16))
    assert eng.chunk_programs_per_prompt == pytest.approx(
        (2 + len(takes)) / 3)
    recs = eng.flight.entries()
    assert sum(r["prefill_tokens"] > 0 for r in recs) == eng.chunk_programs
    assert eng.fused_step_traces == 1 and eng.step_traces == 1


def _metric(sched, name) -> float:
    for line in sched.metrics.render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError(f"{name} is not at /metrics")


@pytest.mark.parametrize("chunk", [0, 16], ids=["wave", "chunked"])
def test_decode_tiles_per_grid_step_reads_what_the_lengths_give(chunk):
    """`decode_live_tiles` / `decode_live_steps` are host arithmetic on the
    planned lengths: a prompt of P ids with a budget of N takes N - 1
    decode programs (its first token comes with the prefill), the k-th of
    which reads P + k rows = ceil((P + k) / block_size) tiles in ONE grid
    step of the paged kernel. The same numbers at /metrics, in
    /debug/timeline and summed over the flight records."""
    import json

    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    from distributed_pytorch_tpu.serve.server import ServeApp
    cfg, model, variables = _mv()
    eng = DecodeEngine(model, variables, n_slots=3, temperature=0.0,
                       min_bucket=8, block_size=8, prefill_chunk=chunk)
    sched = Scheduler(eng, max_queue=4)
    assert eng.decode_tiles_per_grid_step == 0.0    # nothing ran yet
    reqs = {3: 9, 8: 2, 21: 12}                     # prompt ids -> budget
    eng.run([list(range(1, p + 1)) for p in reqs], list(reqs.values()))
    steps = sum(n - 1 for n in reqs.values())
    tiles = sum(-(-(p + k) // 8) for p, n in reqs.items()
                for k in range(1, n))
    assert (eng.decode_live_tiles, eng.decode_live_steps) == (tiles, steps)
    assert eng.decode_tiles_per_grid_step == pytest.approx(tiles / steps)
    assert 1.0 < tiles / steps <= eng.table_width
    assert _metric(sched, "serve_decode_tiles_per_grid_step") == \
        pytest.approx(tiles / steps)
    recs = eng.flight.entries()
    assert sum(r["decode_live_tiles"] for r in recs) == tiles
    assert sum(r["decode_live_steps"] for r in recs) == steps
    assert all(r["decode_live_steps"] == r["n_live"] for r in recs)
    app = ServeApp.__new__(ServeApp)
    app.scheduler = type("S", (), {"engine": eng})()
    payload = json.loads(app._debug_timeline({}).split(b"\r\n\r\n", 1)[1])
    assert payload["decode_tiles_per_grid_step"] == \
        pytest.approx(tiles / steps)
    assert payload["entries"][-1]["decode_live_tiles"] == \
        recs[-1]["decode_live_tiles"]


def test_a_wave_engine_carries_no_chunk():
    """`chunk_fill_share` counts fused programs' ids only: a wave engine
    prefills at admission and reads 0."""
    cfg, model, variables = _mv()
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, block_size=8)
    eng.run([[1, 2, 3], list(range(1, 20))], 3)
    assert eng.prefilled_tokens == 22
    assert (eng.chunk_programs, eng.chunked_prompts) == (0, 0)
    assert eng.chunk_fill_share == 0.0
    assert eng.chunk_programs_per_prompt == 0.0


def test_chunked_engine_kernel_matches_naive(monkeypatch):
    """FLASH_DECODE=on drives the fused chunk through the paged chunk-
    prefill kernel (interpret off-TPU) and decode through the paged
    decode kernel — tokens must match the FLASH_DECODE=off gather+naive
    chunked engine exactly."""
    cfg = tiny_cfg()
    model, variables = build(cfg, attn_impl="auto")
    kwargs = dict(n_slots=2, temperature=0.0, min_bucket=8,
                  prefill_chunk=16, block_size=8)
    monkeypatch.setenv("FLASH_DECODE", "off")
    ref_eng = DecodeEngine(model, variables, **kwargs)
    refs = ref_eng.run([list(p) for p in CHUNK_PROMPTS], max_new_tokens=8)
    monkeypatch.setenv("FLASH_DECODE", "on")
    eng = DecodeEngine(model, variables, **kwargs)
    assert eng.run([list(p) for p in CHUNK_PROMPTS],
                   max_new_tokens=8) == refs


def test_engine_fsdp_mesh_runs():
    """fsdp recipe: params sharded over 'data', slot axis of the cache
    sharded over 'data' (2 slots x dp2)."""
    from distributed_pytorch_tpu.parallel.mesh import mesh_for

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device CPU platform")
    cfg = tiny_cfg()
    model, variables = build(cfg)
    mesh = mesh_for("fsdp", dp_size=2, devices=jax.devices()[:2])
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, mesh=mesh, recipe="fsdp")
    spec = eng.caches[0]["k"].sharding.spec
    assert spec[0] == "data", f"slot axis not data-sharded: {spec}"
    outs = eng.run(PROMPTS[:2], max_new_tokens=4)
    ref_eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                           min_bucket=8)
    assert outs == ref_eng.run(PROMPTS[:2], max_new_tokens=4)


# ----------------------------------------------------------------------
# one step program in flight (PR 31): the next program is planned from
# counts and queued behind the running one; the tokens do not change
# ----------------------------------------------------------------------

_MV: dict = {}


def _mv():
    if not _MV:
        cfg = tiny_cfg()
        _MV["cfg"], (_MV["model"], _MV["variables"]) = cfg, build(cfg)
    return _MV["cfg"], _MV["model"], _MV["variables"]


#: name -> (prompt, budget). A: three chunks (16, 16 and 7 ids, whatever
#: decodes beside them) and E two; D is cancelled while a
#: program runs for it and E takes its slot at once; F ends with its first
#: token; budgets end on different steps.
LOOKAHEAD_REQS = {
    "A": (list(range(1, 40)), 14),
    "B": ([1, 2, 3], 5),
    "D": ([7, 8, 9, 10], 30),
    "C": ([5, 6, 7, 8, 9, 10, 11], 12),
    "E": ([20] * 17 + [3, 4], 6),
    "F": ([42, 43], 1),
    "G": ([9], 7),
}


def _drive_lookahead_schedule(eng, chunked):
    """The schedule of the equivalence test, call by call: admissions
    BETWEEN calls (two before the first, then one a call as slots free),
    a cancel of D once it holds three tokens with E admitted into its
    slot in the same gap. Per request: its stream, its `Retired` record,
    and for each token where it was sampled (kind, rng fold index, slot)
    so a sampling oracle can replay the engine's keys."""
    reqs = LOOKAHEAD_REQS
    queue = [n for n in reqs if n != "E"]
    name_of, sid_of, slot_of = {}, {}, {}
    streams = {n: [] for n in reqs}
    events = {n: [] for n in reqs}
    retired = {}
    seen = {"cancel_in_flight": None}

    def admit(name):
        slot = eng.free_slots[0]
        n_adm = eng._n_admits
        adm = eng.admit(*reqs[name])
        name_of[adm.seq_id], sid_of[name], slot_of[name] = \
            name, adm.seq_id, slot
        if adm.first_token is not None:           # wave: the TTFT token
            streams[name].append(adm.first_token)
            events[name].append(("admit", n_adm, 0))
        if adm.retired is not None:
            retired[name] = adm.retired

    calls = 0
    while queue or eng.n_live:
        for _ in range(2 if calls == 0 else 1):
            if queue and eng.free_slots:
                admit(queue.pop(0))
        n_rec = eng.flight.total
        res = eng.step()
        calls += 1
        assert calls < 400, "the schedule does not end"
        if eng.flight.total == n_rec:
            assert not res.emitted
            continue
        t = eng.flight.entries()[-1]["step"] - 1   # the program drained
        for sid, toks in res.emitted.items():
            name = name_of[sid]
            assert len(toks) == 1
            first = chunked and not streams[name]
            streams[name] += toks
            events[name].append(("first", t, 0) if first
                                else ("decode", t, slot_of[name]))
        for sid, ret in res.retired.items():
            retired[name_of[sid]] = ret
        if "D" not in retired and len(streams["D"]) >= 3:
            fl = eng._inflight
            seen["cancel_in_flight"] = fl is not None and \
                fl.occupants.get(slot_of["D"]) == sid_of["D"]
            retired["D"] = eng.cancel(sid_of["D"])
            assert eng.free_slots == [slot_of["D"]]
            admit("E")                      # the same slot, the same gap
            assert slot_of["E"] == slot_of["D"]
    return streams, retired, events, seen


def _replay_sampling(model, variables, eng, prompt, events):
    """The request decoded alone on the host, with the engine's own keys:
    every token is `sample_token` of the full forward's last logits under
    `fold_in(rng, t)` of the program that sampled it (2**21 + t for a
    chunk's first token, 2**20 + n for a wave admission's), in the row of
    its slot."""
    from distributed_pytorch_tpu.models.generate import sample_token
    T = model.config.block_size
    if "last_logits" not in _MV:              # one trace for the module
        _MV["last_logits"] = jax.jit(
            lambda idx, n: model.apply(variables, idx, None, None, 0,
                                       logits_idx=n[None] - 1)[0][:, -1, :])
    last_logits = _MV["last_logits"]

    toks, out = list(prompt), []
    for kind, idx, slot in events:
        row = last_logits(jnp.asarray([toks + [0] * (T - len(toks))],
                                      jnp.int32), jnp.int32(len(toks)))
        if kind == "decode":
            logits = jnp.zeros((eng.n_slots, row.shape[-1])).at[slot].set(
                row[0])
            key = jax.random.fold_in(eng._rng, idx)
        else:
            logits, slot = row, 0
            key = jax.random.fold_in(
                eng._rng, (2 ** 21 if kind == "first" else 2 ** 20) + idx)
        tok = int(sample_token(logits, key, temperature=eng.temperature,
                               top_k=eng.top_k)[slot])
        out.append(tok)
        toks.append(tok)
    return out


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["prefix-on", "prefix-off"])
@pytest.mark.parametrize("chunk", [16, 0], ids=["chunked", "wave"])
def test_one_program_in_flight_gives_every_request_its_own_tokens(
        chunk, prefix_cache, sampled):
    """One seeded schedule with admissions between calls, a prompt of
    three chunks, budgets that end on different steps, an `eos_id` that
    fires mid-stream, a cancel while a program runs for the cancelled
    occupant and a re-admission into the same slot in the same gap: every
    request's stream and `Retired` record equal the request decoded alone
    (greedy: `generate`; seeded sampling: the engine's `fold_in(rng, t)`
    replayed on the host), and no token of the cancelled occupant reaches
    the slot's next one. The wave engine runs the same code with the
    lookahead declined."""
    cfg, model, variables = _mv()
    temperature = 0.8 if sampled else 0.0

    def engine(eos_id):
        return DecodeEngine(
            model, variables, n_slots=3, temperature=temperature,
            min_bucket=8, block_size=8, prefill_chunk=chunk,
            prefix_cache=prefix_cache, eos_id=eos_id,
            rng=jax.random.PRNGKey(7))

    # a dry pass finds a token some stream emits mid-way: the eos id
    dry, _, _, _ = _drive_lookahead_schedule(engine(None), bool(chunk))
    eos = dry["C"][3]
    eng = engine(eos)
    streams, retired, events, seen = _drive_lookahead_schedule(
        eng, bool(chunk))
    assert seen["cancel_in_flight"] == bool(chunk)
    mid_stream_eos = 0
    for name, (prompt, budget) in LOOKAHEAD_REQS.items():
        got, ret = streams[name], retired[name]
        if sampled:
            want = _replay_sampling(model, variables, eng, prompt,
                                    events[name])
        else:
            want = generate(model, variables,
                            jnp.asarray(prompt, jnp.int32)[None], budget,
                            temperature=0.0)[0].tolist()[len(prompt):]
        if eos in want:
            want = want[:want.index(eos) + 1]
        if name == "D":
            assert 3 <= len(got) < budget and got == want[:len(got)]
            assert ret.reason == "cancelled"
        else:
            assert got == want, f"{name} did not receive its own tokens"
            assert ret.reason == ("eos" if got[-1] == eos else "budget")
            assert len(got) == budget or got[-1] == eos
            mid_stream_eos += int(got[-1] == eos and len(got) < budget)
        assert ret.tokens == prompt + got and ret.prompt_len == len(prompt)
    assert mid_stream_eos >= 1, "the schedule was built to see an eos"
    # nothing left behind: no program queued, no slot, no block
    assert eng._inflight is None and eng.free_slots == [0, 1, 2]
    assert eng.block_pool.n_referenced == 0
    recs = eng.flight.entries()
    if chunk:
        assert eng.overlap_share > 0.8
        assert set(eng.drain_reasons) == {"first"}
        # D's token in flight at the cancel, and one for every stream
        # whose eos showed a program late
        n_eos = sum(r.reason == "eos" for r in retired.values())
        assert eng.overrun_tokens == 1 + n_eos
        assert sum(r["overrun"] for r in recs) <= eng.overrun_tokens
        assert eng.fused_step_traces == 1 and eng.step_traces == 1
        # a prompt is cut at every 16 ids, beside whatever decodes
        carried = [r["prefill_tokens"] for r in recs if r["prefill_tokens"]]
        if not prefix_cache:
            assert sorted(carried) == sorted(
                min(16, len(p) - off) for p, _ in LOOKAHEAD_REQS.values()
                for off in range(0, len(p), 16))
        assert eng.chunk_programs == len(carried)
        assert eng.chunked_prompts == len(LOOKAHEAD_REQS)
    else:
        assert eng.overlap_share == 0.0 and eng.overrun_tokens == 0
        assert eng.drain_reasons == {"wave": eng.n_steps}
        assert not any(r["overlapped"] for r in recs)


def test_end_of_work_leaves_no_program_queued():
    """By count the engine knows when nothing will be live: nothing is
    queued behind the program that retires the last slot, and `n_steps`
    counts only programs that ran for a slot live by plan. An `eos` shows
    a program late: that overrun is counted (`n_steps`) and named
    (`overrun_tokens`), and dropped at the end of work."""
    cfg, model, variables = _mv()
    prompt = [1, 2, 3]
    # seeded sampling: this model's greedy streams repeat one token
    kw = dict(n_slots=2, temperature=0.8, rng=jax.random.PRNGKey(3),
              min_bucket=8, block_size=8, prefill_chunk=16)
    eng = DecodeEngine(model, variables, **kw)
    ref = eng.run([prompt], 8)[0]
    gen = ref[len(prompt):]
    # 1 fused program + 7 decode programs, each with a record; only the
    # first had no running program to queue behind
    assert eng.n_steps == eng.flight.total == 8
    assert eng._inflight is None and eng.overrun_tokens == 0
    assert eng.overlap_share == 7 / 8 and eng.drain_reasons == {"first": 1}
    j = next(i for i in range(2, 8) if gen[i] not in gen[:i])
    eng = DecodeEngine(model, variables, eos_id=gen[j], **kw)
    assert eng.run([prompt], 8) == [ref[:len(prompt) + j + 1]]
    # j + 1 programs handed out tokens; one more ran on for the slot and
    # was dropped when the eos emptied the engine
    assert eng.flight.total == j + 1 and eng.n_steps == j + 2
    assert eng.overrun_tokens == 1 and eng._inflight is None
    assert eng.retire_counts["eos"] == 1 and eng.free_slots == [0, 1]


def test_lookahead_declines_for_a_speculative_engine():
    """The drafter reads the tokens the running program is producing:
    every turn of a speculative engine is drained (`spec`), results as
    ever."""
    cfg, model, variables = _mv()
    prompts = [[3, 4, 5, 3, 4, 5, 3, 4], [7, 7, 7, 7, 7, 7]]
    kw = dict(n_slots=2, temperature=0.0, min_bucket=8, block_size=8,
              prefill_chunk=16)
    plain = DecodeEngine(model, variables, **kw)
    eng = DecodeEngine(model, variables, spec_decode=True, spec_k=3, **kw)
    assert eng.spec_decode
    assert eng.run(prompts, 16) == plain.run(prompts, 16)
    assert eng.spec_drafted_tokens > 0
    assert eng.overlap_share == 0.0
    assert eng.drain_reasons == {"spec": eng.n_steps}
    assert plain.overlap_share > 0.8


def test_lookahead_declines_before_a_preemption():
    """A preemption hands out the victim's tokens, which a running
    program would still be producing: the turn that meets a dry pool
    queues nothing ahead (`preempt`), preempts with the engine drained,
    and the tokens are the oracle's."""
    cfg, model, variables = _mv()
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, prefill_chunk=16, block_size=8,
                       n_blocks=9)
    prompts = [[1, 2, 3], list(range(1, 40))]
    outs = eng.run([list(p) for p in prompts], max_new_tokens=20)
    assert eng.retire_counts["preempted"] >= 1
    for p, o in zip(prompts, outs):
        assert o == generate(model, variables,
                             jnp.asarray(p, jnp.int32)[None], 20,
                             temperature=0.0)[0].tolist()
    recs = eng.flight.entries()
    hit = [r for r in recs if r["preemptions"]]
    assert hit and all(not r["overlapped"]
                       and r["drain_reason"] == "preempt" for r in hit)
    assert eng.drain_reasons["preempt"] >= len(hit)
    assert 0 < eng.overlap_share < 1
    assert eng.block_pool.n_referenced == 0 and eng._inflight is None


def test_lookahead_declines_after_a_host_tier_promotion():
    """A promotion rewrites the pools outside the step programs: the
    call after it queues nothing ahead, and the next program is
    dispatched with the engine drained (`tier`)."""
    cfg, model, variables = _mv()
    a = [(7 * i + 3) % 97 for i in range(33)]
    churn = [[(11 * i + j + 1) % 97 for i in range(33)] for j in range(3)]
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, block_size=8, prefill_chunk=16,
                       n_blocks=12, host_tier=True, host_blocks=64)
    ref = generate(model, variables, jnp.asarray([a], jnp.int32), 6,
                   temperature=0.0)[0].tolist()
    assert eng.run([a], 6) == [ref]
    for c in churn:                           # evict a's chain to the host
        eng.run([c], 8)
    assert eng.host_tier.counters()["demoted"] > 0
    assert "tier" not in eng.drain_reasons
    long = eng.admit([5, 6, 7], 30)           # a stream that keeps running
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None          # a program runs ...
    promoted = eng.host_tier.counters()["promoted"]
    adm = eng.admit(a, 6)                     # ... while a's chain returns
    assert eng.host_tier.counters()["promoted"] > promoted
    assert adm.prefix_len > 0
    n0 = eng.flight.total
    out = None
    while out is None:
        out = eng.step().retired.get(adm.seq_id)
    assert out.tokens == ref and out.reason == "budget"
    recs = eng.flight.entries()[n0 - eng.flight.total:]
    # the first call drains the program that was running and queues
    # nothing; the second dispatches with nothing ahead, and says why
    assert recs[0]["overlapped"] and not recs[1]["overlapped"]
    assert recs[1]["drain_reason"] == "tier"
    assert all(r["overlapped"] for r in recs[2:])
    assert eng.drain_reasons["tier"] == 1
    eng.cancel(long.seq_id)
