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
