"""Host-RAM KV tier (ops/kv_tier.py + the engine/scheduler/router
wiring): HostTier budget/LRU accounting, demote-at-eviction, promote-hit
bit parity against a never-evicted baseline across attention flavors and
cache dtypes, COW safety when a promoted chain forks, preemption-resume
through a demoted prefix, the one-promote-trace pin, knob gating, and
the radix-prefix digest advertisement the cache-aware router matches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.generate import generate
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.ops import kv_tier


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


# all prompt tokens MUST stay < vocab_size: out-of-vocab ids embed to
# NaN rows which poison recycled cache blocks through exact masking
A = [(7 * i + 3) % 97 for i in range(27)]        # 3 full blocks @ bs 8
CHURN = [[(11 * i + j + 1) % 97 for i in range(33)] for j in range(3)]
SCHEDULE = [(A, 6)] + [(c, 8) for c in CHURN] + [(A, 6)]


def tier_engine(model, variables, cache_dtype=None, *, n_blocks=12,
                host_tier=True, host_blocks=64, n_slots=2):
    """Engine with a pool tiny enough that the CHURN prompts genuinely
    evict A's chain (11 usable blocks vs ~18 of churn working set)."""
    return DecodeEngine(model, variables, n_slots=n_slots,
                        temperature=0.0, min_bucket=8,
                        cache_dtype=cache_dtype, n_blocks=n_blocks,
                        host_tier=host_tier, host_blocks=host_blocks)


def run_schedule(eng, schedule):
    """One request at a time, in order — deterministic eviction order."""
    return [eng.run([p], b)[0] for p, b in schedule]


# ----------------------------------------------------------------------
# HostTier unit tests (no device work)
# ----------------------------------------------------------------------

def test_host_tier_lru_cap_and_counters():
    tier = kv_tier.HostTier(2)
    rows = {"k": np.ones((4, 2), np.float32)}     # 32 bytes
    tier.demote(("a",), rows)
    tier.demote(("b",), rows)
    assert tier.n_blocks == 2 and tier.occupancy == 1.0
    tier.demote(("c",), rows)                     # cap: LRU ("a") dropped
    assert tier.counters()["dropped"] == 1
    assert not tier.contains(("a",)) and tier.contains(("b",))
    # re-demoting a resident key refreshes LRU position, no double store
    tier.demote(("b",), rows)
    assert tier.n_blocks == 2 and tier.counters()["demoted"] == 3
    tier.demote(("d",), rows)                     # "c" is now LRU
    assert not tier.contains(("c",)) and tier.contains(("b",))
    # promotion CONSUMES the entry: one copy across the two tiers
    got = tier.pop(("b",))
    assert np.array_equal(got["k"], rows["k"])
    assert not tier.contains(("b",))
    c = tier.counters()
    assert c["promoted"] == 1 and c["resident_blocks"] == 1
    assert tier.drain_promote_events() == [32]
    assert tier.drain_promote_events() == []      # drained
    # probe accounting feeds the hit-rate gauge
    assert 0.0 < tier.hit_rate < 1.0


def test_host_tier_needs_positive_budget():
    with pytest.raises(AssertionError):
        kv_tier.HostTier(0)


# ----------------------------------------------------------------------
# engine: demote at eviction, promote on radix hit, bit parity
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kw,cache_dtype", [
    (dict(attn="mha", n_kv_heads=4), None),
    (dict(attn="mha", n_kv_heads=4), "int8"),
    (dict(attn="gqa", n_kv_heads=2), "bfloat16"),
    (dict(attn="gqa", n_kv_heads=2), "int8"),
    (dict(attn="mla"), "bfloat16"),
    (dict(attn="mla"), "int8"),
], ids=["mha-native", "mha-int8", "gqa-bf16", "gqa-int8",
        "mla-bf16", "mla-int8"])
def test_promote_hit_parity_vs_never_evicted(kw, cache_dtype):
    """Run A, churn the tiny pool until A's chain demotes to host RAM,
    run A again (promote path). Every output must be bit-identical to
    the same schedule on a pool big enough that nothing ever evicts —
    the promoted rows ARE the rows that were demoted."""
    cfg = tiny_cfg(**kw)
    model, variables = build(cfg)
    eng = tier_engine(model, variables, cache_dtype)
    outs = run_schedule(eng, SCHEDULE)
    c = eng.host_tier.counters()
    assert c["demoted"] > 0, "churn never evicted — the pool is too big"
    assert c["promoted"] > 0, "re-admitting A never promoted"
    assert c["dropped"] == 0
    assert eng.promote_traces == 1       # ONE compiled promote program
    base = tier_engine(model, variables, cache_dtype, n_blocks=64,
                       host_tier=False)
    refs = run_schedule(base, SCHEDULE)
    assert base.host_tier is None and base.promote_traces == 0
    for (p, _), out, ref in zip(SCHEDULE, outs, refs):
        assert out == ref, f"promote path diverged for prompt {p[:4]}..."
    # the tier holds the warm pool's prefix hit rate on a pool that evicts,
    # and a hit costs a host->HBM copy, never a re-prefill
    assert eng.prefix_hit_tokens == base.prefix_hit_tokens > 0
    assert eng.prefilled_tokens == base.prefilled_tokens


def test_promote_hit_matches_offline_generate():
    """The full demote->promote round trip against the offline one-shot
    path (native cache): re-admitted A continues exactly as generate."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    eng = tier_engine(model, variables)
    outs = run_schedule(eng, SCHEDULE)
    assert eng.host_tier.counters()["promoted"] > 0
    ref = generate(model, variables, jnp.asarray(A, jnp.int32)[None], 6,
                   temperature=0.0)[0].tolist()
    assert outs[0] == ref and outs[-1] == ref


def test_cow_fork_on_promoted_chain():
    """Two concurrent requests fork off the SAME promoted prefix with
    different suffixes: the shared promoted blocks must stay immutable
    (partial tails are always private), and both streams must match the
    never-evicted baseline."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    fork = [[t for t in A] + [50], [t for t in A] + [60]]
    eng = tier_engine(model, variables)
    run_schedule(eng, SCHEDULE[:-1])     # A cached, then demoted by churn
    outs = eng.run(fork, max_new_tokens=5)
    assert eng.host_tier.counters()["promoted"] > 0
    base = tier_engine(model, variables, n_blocks=64, host_tier=False)
    run_schedule(base, SCHEDULE[:-1])
    refs = base.run(fork, max_new_tokens=5)
    assert outs == refs


def test_preemption_resume_through_demoted_prefix():
    """Pool pressure mid-decode preempts the youngest sequence; with the
    tier on, the blocks its resume needs may have been demoted in the
    meantime. run() requeues, the resume promotes, and the output stays
    bit-identical to an unpressured run."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    prompts = [[(5 * i + j + 2) % 97 for i in range(30)] for j in range(3)]
    eng = tier_engine(model, variables)
    outs = eng.run(prompts, max_new_tokens=20)
    assert eng.retire_counts["preempted"] > 0, \
        "pool never preempted — pressure too low for the test to bite"
    assert eng.host_tier.counters()["demoted"] > 0
    base = tier_engine(model, variables, n_blocks=64, host_tier=False)
    refs = base.run(prompts, max_new_tokens=20)
    assert outs == refs


def test_host_lru_cap_bounds_tier_and_counts_drops():
    """A 2-block host budget under heavy churn: the tier never holds
    more than its cap and every overflow is a counted drop — the only
    way tier-managed KV is ever lost."""
    cfg = tiny_cfg()
    model, variables = build(cfg)
    eng = tier_engine(model, variables, host_blocks=2)
    run_schedule(eng, SCHEDULE)
    c = eng.host_tier.counters()
    assert c["resident_blocks"] <= 2
    assert c["dropped"] > 0
    assert c["dropped"] + c["promoted"] + c["resident_blocks"] \
        == c["demoted"]


# ----------------------------------------------------------------------
# gating: knobs, prefix_cache, tier-off engines
# ----------------------------------------------------------------------

def test_tier_gating_constructor_and_knobs(monkeypatch):
    cfg = tiny_cfg()
    model, variables = build(cfg)
    # constructor off beats any knob
    monkeypatch.setenv("KV_HOST_TIER", "on")
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8, host_tier=False)
    assert eng.host_tier is None and eng.block_pool.on_evict is None
    # knob on, no budget: defaults to mirroring the HBM pool
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8)
    assert eng.host_tier is not None
    assert eng.host_tier.capacity == eng.n_blocks
    # auto + zero budget = off; auto + budget = on with that budget
    monkeypatch.setenv("KV_HOST_TIER", "auto")
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8)
    assert eng.host_tier is None
    monkeypatch.setenv("KV_HOST_BLOCKS", "7")
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8)
    assert eng.host_tier is not None and eng.host_tier.capacity == 7
    # no radix index -> nothing to key demotions under -> forced off
    eng = DecodeEngine(model, variables, n_slots=1, temperature=0.0,
                       min_bucket=8, prefix_cache=False, host_tier=True)
    assert eng.host_tier is None


# ----------------------------------------------------------------------
# the router-facing radix-prefix digest
# ----------------------------------------------------------------------

def test_kv_digest_matches_router_prompt_digests():
    """The engine's advertised chain digests and the router's
    client-side prompt digests are the same fold: after serving A, a
    same-prefix prompt must match at exactly A's full-block depth — and
    the advertisement works with the tier OFF too (stickiness pays for
    plain HBM reuse)."""
    from distributed_pytorch_tpu.serve.router import prompt_chain_digests
    cfg = tiny_cfg()
    model, variables = build(cfg)
    for tier in (True, False):
        eng = tier_engine(model, variables, n_blocks=64, host_tier=tier)
        assert eng.kv_digest()["entries"] == []      # nothing cached yet
        eng.run([A], max_new_tokens=6)
        adv = eng.kv_digest()
        assert adv["block_size"] == eng.block_size
        depths = [d for d, _ in adv["entries"]]
        assert depths == sorted(depths, reverse=True)  # deepest first
        assert eng.kv_digest(1)["entries"] == adv["entries"][:1]
        index = {hx: d for d, hx in adv["entries"]}
        cands = prompt_chain_digests([t for t in A] + [50],
                                     adv["block_size"])
        match = next((d for d, hx in cands if hx in index), 0)
        assert match == len(A) // eng.block_size, \
            "same-prefix prompt must match at its full-block depth"
        # an unrelated prompt matches nothing
        other = prompt_chain_digests([96 - t for t in A],
                                     adv["block_size"])
        assert all(hx not in index for _, hx in other)


def test_prompt_chain_digests_shape():
    from distributed_pytorch_tpu.serve.router import prompt_chain_digests
    assert prompt_chain_digests([1, 2, 3], 8) == []      # no full block
    assert prompt_chain_digests([1] * 20, 0) == []       # no advert yet
    two = prompt_chain_digests([1] * 20, 8)              # 2 full blocks
    assert [d for d, _ in two] == [2, 1]
    # digests are chain (ancestry) digests: depth 1 of a different
    # prefix differs, same prefix agrees
    assert prompt_chain_digests([1] * 9, 8)[0][1] == two[1][1]
    assert prompt_chain_digests([2] * 9, 8)[0][1] != two[1][1]
