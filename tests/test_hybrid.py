"""A patterned model (`LLMConfig.layer_pattern`: Mamba-2 state-space
layers, sigmoid-routed experts of which the chip holds a share, attention
without positions) at a small size on the CPU, seeded weights, float32,
against the plain reference (benchmark/lib/reference_hybrid.py): the mixer
in its three forms, the engine with two kinds of state in one cache tree,
the expert shares, the router, and what the engine declines."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_hybrid as ref
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import mlp as mlp_mod
from distributed_pytorch_tpu.models import ssm as ssm_mod
from distributed_pytorch_tpu.models.gpt import LLM

LLM_KW = dict(
    vocab_size=256, block_size=128, n_embd=64, n_layer=5,
    layer_pattern="ME*EM", pos_emb="none", non_linearity="relu2", up_dim=48,
    shared_up_dim=96, n_exp=9, n_shared=1, n_act=4, experts_held=(0, 4),
    routed_scale=2.5, attn="gqa", n_head=4, n_kv_heads=2, head_dim=32,
    attn_bias=False, tie_head=False, ssm_heads=4, ssm_head_dim=16,
    ssm_groups=2, ssm_state=16, ssm_conv=4, ssm_chunk=8)
HI = jax.default_matmul_precision("highest")


@pytest.fixture(scope="module")
def mv():
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = model.init({"params": jax.random.PRNGKey(1)},
                           jnp.zeros((1, 8), jnp.int32))
    return cfg, model, variables


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def _worst_gap(variables, prompts, outs, n_new):
    """The benchmark's comparison: each emitted token's reference logit
    against the reference maximum at its position."""
    worst = 0.0
    for p, o in zip(prompts, outs):
        o = [int(t) for t in o]
        assert o[:len(p)] == p and len(o) == len(p) + n_new
        logits = ref.forward_logits(variables["params"], LLM_KW,
                                    jnp.asarray([o[:-1]], jnp.int32),
                                    last=n_new)[0]
        for row, tok in zip(np.asarray(logits), o[len(p):]):
            worst = max(worst, float(row.max() - row[tok]))
    return worst


def _engine(model, variables, **kw):
    kw = {"n_slots": 2, "max_len": 128, "block_size": 8,
          "prefill_chunk": 16, "temperature": 0.0, "min_bucket": 8,
          "prefix_cache": False, **kw}
    return DecodeEngine(model, variables, **kw)


# (1) the mixer: chunked form = one-token recurrence = reference ----------

@pytest.mark.parametrize("T", [5, 8, 21])
def test_mixer_chunked_recurrent_and_reference_agree(T):
    """Lengths that are no multiple of the chunk (8), below and above it."""
    cfg = LLMConfig(**LLM_KW)
    mixer = ssm_mod.Mamba2(cfg)
    x = jax.random.normal(jax.random.PRNGKey(T), (2, T, cfg.n_embd))
    params = mixer.init(jax.random.PRNGKey(0), x)
    p = params["params"]
    with HI:
        whole, _ = mixer.apply(params, x)
        want = ref.mamba_forward(x, p, H=4, P=16, G=2, N=16, eps=cfg.norm_eps)
        # one token at a time through a slot's state, a dead slot beside
        cache = ssm_mod.init_ssm_cache(cfg, 3, jnp.float32)
        live = jnp.asarray([True, True, False])
        x3 = jnp.concatenate([x, x[:1] * 0 + 7.0], axis=0)
        steps = []
        for t in range(T):
            y, cache = mixer.apply(params, x3[:, t:t + 1], cache, t,
                                   {"live": live})
            steps.append(y[:2, 0])
    np.testing.assert_allclose(whole, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(jnp.stack(steps, 1), want, atol=2e-5,
                               rtol=2e-5)
    assert not np.asarray(cache["ssm"][2]).any(), "a dead slot advanced"
    assert not np.asarray(cache["conv"][2]).any()


def test_mixer_chunks_carry_state_and_pads_advance_nothing():
    """A sequence in two chunks of a padded buffer, into slot 1 of 2 that
    held another's state: the first starts from zeros, the second from the
    first's state, pad rows past `valid_len` move neither state nor tail."""
    cfg = LLMConfig(**LLM_KW)
    mixer = ssm_mod.Mamba2(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 13, cfg.n_embd))
    params = mixer.init(jax.random.PRNGKey(0), x)
    with HI:
        want = ref.mamba_forward(x, params["params"], H=4, P=16, G=2, N=16,
                                 eps=cfg.norm_eps)
        cache = jax.tree_util.tree_map(
            lambda a: a + 3.0, ssm_mod.init_ssm_cache(cfg, 2, jnp.float32))
        got = []
        for off, n in ((0, 8), (8, 5)):
            buf = jnp.zeros((1, 8, cfg.n_embd)).at[:, :n].set(
                x[:, off:off + n]).at[:, n:].set(99.0)       # loud pads
            y, cache = mixer.apply(
                params, buf, cache, off,
                {"slot": jnp.int32(1),
                 "valid_len": jnp.asarray([n], jnp.int32)})
            got.append(y[:, :n])
        # the state after 13 real tokens = the recurrence's, token by token
        c2 = ssm_mod.init_ssm_cache(cfg, 1, jnp.float32)
        for t in range(13):
            _, c2 = mixer.apply(params, x[:, t:t + 1], c2, t,
                                {"live": jnp.asarray([True])})
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(cache["ssm"][1], c2["ssm"][0], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(cache["conv"][1], c2["conv"][0], atol=1e-6)
    np.testing.assert_allclose(cache["ssm"][0], 3.0)     # the other slot


# (2) the engine: chunks -> state -> decode, a slot retired and reused -----

@pytest.mark.parametrize("prefill_chunk", [16, 0])
def test_engine_matches_the_reference_through_reused_slots(mv,
                                                           prefill_chunk):
    """Five prompts through two slots (several chunks, one chunk, a
    shorter prompt into a slot a longer one left): every emitted token is
    the reference's full forward pass's choice."""
    cfg, model, variables = mv
    prompts = _prompts((37, 9, 20, 50, 5))
    eng = _engine(model, variables, prefill_chunk=prefill_chunk)
    with HI:
        outs = eng.run(prompts, 6)
        assert _worst_gap(variables, prompts, outs, 6) < 1e-5
    assert eng.state_resets == 5
    assert eng.retire_counts["budget"] == 5
    if prefill_chunk:
        assert eng.fused_step_traces == 1 and eng.step_traces == 1
        assert eng.overlap_share > 0.8       # PR 31's lookahead stays on
    # the expert layers' counters: 2 layers, 3 routed picks a real row
    rows = eng.held_assignments + eng.absent_assignments
    assert rows == 2 * 3 * (sum(map(len, prompts)) + 5 * 5)
    assert eng.expert_tokens.sum() == eng.held_assignments
    assert 0 < eng.experts_hit <= eng.expert_calls * 4
    rec = eng.flight.entries()[-1]
    assert {"experts_hit", "absent_assignments", "state_reset"} <= set(rec)


@pytest.mark.parametrize("n_ids, takes", [(16, [16]), (17, [16, 1])],
                         ids=["one-chunk", "one-id-over"])
def test_a_chunk_fills_its_rows_beside_a_live_slot(mv, n_ids, takes):
    """A recurrent model's chunk is filled like any other: beside a slot
    that decodes, a prompt of `prefill_chunk` ids is one chunk-carrying
    program (one state reset, at position 0) and one id more carries the
    state into a second; the live stream emits with each, and every
    emitted token is the reference's choice."""
    cfg, model, variables = mv
    live, prompt = _prompts((5, n_ids), seed=n_ids)
    eng = _engine(model, variables)
    streams = {}
    with HI:
        a = eng.admit(live, 12).seq_id
        while a not in streams:
            for s, toks in eng.step().emitted.items():
                streams.setdefault(s, []).extend(toks)
        resets = eng.state_resets
        p = eng.admit(prompt, 6).seq_id
        carried = []
        while eng.n_live:
            res = eng.step()
            for s, toks in res.emitted.items():
                streams.setdefault(s, []).extend(toks)
            if res.prefill_tokens:
                carried.append((res.prefill_tokens, set(res.emitted)))
        assert [t for t, _ in carried] == takes
        assert all(a in emitted for _, emitted in carried)
        assert p in carried[-1][1]
        assert eng.state_resets == resets + 1 == 2
        assert _worst_gap(variables, [live], [live + streams[a]],
                          12) < 1e-5
        assert _worst_gap(variables, [prompt], [prompt + streams[p]],
                          6) < 1e-5
    assert eng.chunk_programs == 1 + len(takes)
    assert eng.chunk_fill_share == pytest.approx(
        (5 + n_ids) / ((1 + len(takes)) * 16))


def test_a_state_not_zeroed_shows(mv, monkeypatch):
    """The classic fault, put into the program: a chunk at position 0 that
    starts from what the slot's last occupant left there."""
    cfg, model, variables = mv
    monkeypatch.setattr(
        ssm_mod, "chunk_start",
        lambda leaf, slot, pos: jax.lax.dynamic_index_in_dim(leaf, slot, 0))
    prompts = _prompts((37, 9, 20, 50, 5))
    eng = _engine(model, variables)
    with HI:
        outs = eng.run(prompts, 6)
        assert _worst_gap(variables, prompts, outs, 6) > 1e-3


# (3) the two expert shares + the shared expert once = the uncut layer -----

def test_expert_shares_add_up_to_the_uncut_layer():
    whole = LLMConfig(**{**LLM_KW, "experts_held": ()})
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 11, 64))
    layer = mlp_mod.RoutedExperts(whole)
    p = layer.init(jax.random.PRNGKey(0), x)["params"]
    with HI:
        uncut, _ = layer.apply({"params": p}, x)
        want = ref.experts_forward(x, p, k=3, scale=2.5, first=0)
        shared_only = ref.experts_forward(x, p, k=3, scale=2.5, first=0,
                                          held=())
        parts = []
        for first in (0, 4):
            cfg = dataclasses.replace(whole, experts_held=(first, 4))
            share = {**p, "experts_up": p["experts_up"][first:first + 4],
                     "experts_down": p["experts_down"][first:first + 4]}
            y, stats = mlp_mod.RoutedExperts(cfg).apply(
                {"params": share}, x, jnp.ones((22,), bool))
            parts.append(y)
            np.testing.assert_allclose(
                y, ref.experts_forward(x, share, k=3, scale=2.5,
                                       first=first), atol=2e-5, rtol=2e-5)
            assert int(stats["tokens"].sum() + stats["absent"].sum()) == 66
        # rows that are not real are sent to no routed expert
        mask = jnp.arange(22) % 3 != 0
        part, stats = mlp_mod.RoutedExperts(whole).apply({"params": p}, x,
                                                         mask)
        part = np.asarray(part).reshape(22, -1)
        np.testing.assert_allclose(
            part[~np.asarray(mask)],
            np.asarray(shared_only).reshape(22, -1)[~np.asarray(mask)],
            atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            part[np.asarray(mask)],
            np.asarray(uncut).reshape(22, -1)[np.asarray(mask)], atol=2e-5,
            rtol=2e-5)
        assert int(stats["tokens"].sum()) == 3 * int(mask.sum())
    np.testing.assert_allclose(uncut, want, atol=2e-5, rtol=2e-5)
    # every chip computes the shared expert alike: counted once
    np.testing.assert_allclose(parts[0] + parts[1] - shared_only, uncut,
                               atol=2e-5, rtol=2e-5)


# (4) the router -----------------------------------------------------------

def test_router_bias_moves_the_selection_and_not_the_weights():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (32, 64))
    gate = jax.random.normal(jax.random.fold_in(key, 1), (64, 8)) * 0.1
    zero = jnp.zeros((8,))
    idx0, w0 = mlp_mod.route_sigmoid(x, gate, zero, 3, 2.5)
    np.testing.assert_allclose(w0.sum(axis=1), 2.5, rtol=1e-5)
    s = jax.nn.sigmoid(x @ gate)
    np.testing.assert_array_equal(idx0, jax.lax.top_k(s, 3)[1])
    # a large bias on expert 5 puts it into every row's choice ...
    bias = zero.at[5].set(10.0)
    idx1, w1 = mlp_mod.route_sigmoid(x, gate, bias, 3, 2.5)
    assert (np.asarray(idx1) == 5).any(axis=1).all()
    assert not (np.asarray(idx0) == 5).any(axis=1).all()
    # ... and its weight is still its unbiased score over the chosen sum
    picked = jnp.take_along_axis(s, idx1, axis=1)
    np.testing.assert_allclose(w1, picked / picked.sum(1, keepdims=True)
                               * 2.5, rtol=1e-5)
    np.testing.assert_allclose(w1.sum(axis=1), 2.5, rtol=1e-5)
    ridx, rw = ref.route(x, gate, bias, k=3, scale=2.5)
    np.testing.assert_array_equal(idx1, ridx)
    np.testing.assert_allclose(w1, rw, rtol=1e-5)


# (5), (6) the lookahead declined, a preemption: the same tokens -----------

@pytest.mark.parametrize("how", ["declined", "wave"])
def test_the_lookahead_declined_gives_the_same_tokens(mv, monkeypatch, how):
    cfg, model, variables = mv
    prompts = _prompts((37, 9, 20, 50, 5), seed=1)
    with HI:
        want = _engine(model, variables).run(prompts, 8)
        if how == "declined":
            monkeypatch.setattr(DecodeEngine, "_lookahead_declined",
                                lambda self: "tier")
        eng = _engine(model, variables,
                      prefill_chunk=16 if how == "declined" else 0)
        got = eng.run(prompts, 8)
    assert eng.overlap_share == 0.0
    assert got == want


def test_preempt_and_requeue_gives_the_same_tokens(mv):
    """A pool too small for both sequences preempts one mid-flight; it
    resumes by recomputing from its tokens (resident blocks are not a
    recurrent model's prefix state: no prefix hit) and ends on the tokens
    of an engine that never preempted."""
    cfg, model, variables = mv
    prompts = [[1, 2, 3], list(range(1, 40))]
    with HI:
        want = _engine(model, variables, max_len=64).run(prompts, 20)
        eng = _engine(model, variables, max_len=64, n_blocks=9,
                      prefix_cache=True)
        got = eng.run([list(p) for p in prompts], 20)
    assert eng.retire_counts["preempted"] >= 1, \
        "the pool was sized to force a preemption"
    assert got == want
    assert eng.prefix_hit_tokens == 0
    assert eng.block_pool.n_referenced == 0
    assert eng.state_resets == 2 + eng.retire_counts["preempted"]


# (7) what a recurrent model stands down, aloud and counted ----------------

def test_prefix_reuse_speculation_and_the_host_tier_are_declined(mv):
    cfg, model, variables = mv
    shared = list(range(1, 33))
    eng = _engine(model, variables, prefix_cache=True, spec_decode=True,
                  host_tier=True)
    assert eng.features_declined == ["prefix_cache", "spec_decode",
                                     "host_tier"]
    assert not eng.prefix_cache and not eng.spec_decode \
        and eng.host_tier is None
    with HI:
        outs = eng.run([shared + [40], shared + [41], shared + [42]], 4)
        assert _worst_gap(variables, [shared + [40], shared + [41],
                                      shared + [42]], outs, 4) < 1e-5
    assert eng.prefix_reuse_declined == 3        # an admission each
    assert eng.prefix_hit_tokens == 0 and eng.prefix_hit_rate == 0.0
    assert eng.spec_step_traces == 0
    quiet = _engine(model, variables)            # nothing asked, none said
    assert quiet.features_declined == [] and quiet.prefix_reuse_declined == 0


def test_a_dense_model_is_asked_nothing_new(mv):
    """The blocks the accepted cells run keep their tree and their call."""
    cfg = LLMConfig(vocab_size=256, block_size=64, n_embd=64, n_head=4,
                    attn="mha", n_layer=2, up_dim=128, non_linearity="gelu",
                    pos_emb="learn")
    model = LLM(cfg)
    v = model.init({"params": jax.random.PRNGKey(0)},
                   jnp.zeros((1, 8), jnp.int32))
    assert set(v["params"]["block_0"]) == {"ln1", "ln2", "attn", "mlp"}
    assert set(v["params"]["block_0"]["attn"]["c_attn"]) == {"kernel", "bias"}
    assert "lm_head" not in v["params"]
    eng = DecodeEngine(model, v, n_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16, min_bucket=8)
    eng.run([[1, 2, 3]], 3)
    assert eng.state_resets == 0 and eng.expert_calls == 0
    assert "experts_hit" not in eng.flight.entries()[-1]


# what the new layers write: scopes in the programs, counters at /metrics --

@pytest.mark.parametrize("fused", [False, True])
def test_mixer_scopes_reach_the_compiled_op_names(mv, fused):
    import re
    from distributed_pytorch_tpu.engine.decode import (make_fused_step_fn,
                                                       make_step_fn)
    from distributed_pytorch_tpu.obs.trace import MIXER_SCOPES, SCOPES
    assert not set(MIXER_SCOPES) & set(SCOPES)
    for name in MIXER_SCOPES:
        assert "expert_matmul" not in name      # kernels are found by name
    cfg, model, variables = mv
    eng = _engine(model, variables)
    args = (eng.variables, eng.caches, eng.tok, eng.pos, eng.live,
            eng.block_tables, eng._rng, jnp.int32(0), eng._qparams)
    if fused:
        fn = make_fused_step_fn(model, eng._sample, eng.n_slots,
                                eng.table_width)
        args += (jnp.zeros((1, eng.prefill_chunk), jnp.int32), jnp.int32(0),
                 jnp.int32(0), jnp.asarray([4], jnp.int32), jnp.bool_(True))
    else:
        fn = make_step_fn(model, eng._sample)
    # compiled anew: the persistent cache's key leaves the op names out, so
    # a hit hands back the names of whichever tree wrote the entry
    from distributed_pytorch_tpu.parallel.aot_store import (
        _no_persistent_cache)
    with _no_persistent_cache():
        text = jax.jit(fn).lower(*args).compile().as_text()
    parts = [set(re.split(r"[/()]", p))
             for p in re.findall(r'op_name="([^"]+)"', text)]
    # the scopes of the kinds this pattern has ('M', 'E'; the others'
    # are tests/test_lfm2.py's)
    want = {s for s in MIXER_SCOPES if s.startswith(("ssm_", "moe_"))} \
        - ({"ssm_scan"} if not fused else set())
    for scope in want | {"ssm", "moe", "attn", "norm", "attn_core",
                         "kv_update", "lm_head", "decode"}:
        assert any(scope in p for p in parts), scope
    assert fused or not any("ssm_scan" in p for p in parts)


def test_counters_reach_metrics_and_the_flight_record(mv):
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    cfg, model, variables = mv
    eng = _engine(model, variables, prefix_cache=True)
    sched = Scheduler(eng, max_queue=4)
    with HI:
        eng.run(_prompts((20, 9)), 4)
    text = sched.metrics.render_prometheus()
    got = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.partition(" ")
            got[name] = float(value)
    assert got["serve_state_resets_total"] == 2
    assert got["serve_prefix_reuse_declined_total"] == 2
    assert 0 < got["serve_experts_hit_per_call"] <= 4
    assert 0 < got["serve_expert_absent_assignments_share"] < 1
    assert got["serve_expert_tokens_max_over_mean"] >= 1
    recs = eng.flight.entries()
    assert sum(r["state_reset"] for r in recs) == 2
    assert sum(r["experts_hit"] for r in recs) == eng.experts_hit
    assert sum(r["absent_assignments"] for r in recs) \
        == eng.absent_assignments
