"""An LFM2 mixture-of-experts shaped patterned model (gated short-convolution
mixers beside RoPE'd, QK-normed GQA in one cache tree, a leading dense FFN
block of its own width, sigmoid-routed GATED experts with no shared expert,
a tied head) at a small size on the CPU, seeded weights, float32, against
the plain reference (benchmark/lib/reference_lfm2.py): the tree, the whole
forward pass, what each term is worth, the cache path with slots at
different positions, the convolution's tail, the expert kernels fed by the
sigmoid router and their counters. And what the models that were there are
NOT asked."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_lfm2 as ref
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import mlp as mlp_mod
from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
from distributed_pytorch_tpu.ops import grouped_matmul as gm
from distributed_pytorch_tpu.ops import rope

# the cell's pattern in little: the leading dense layer once, then a period
# of one attention and two convolution layers, each with its expert layer
LLM_KW = dict(
    vocab_size=256, block_size=4096, n_embd=64, n_layer=8,
    layer_pattern="CF*ECECE", pos_emb="rope", rope_theta=1e6,
    rope_pairing="half", qk_norm=True,
    tie_head=True, attn="gqa", n_head=4, n_kv_heads=2, head_dim=16,
    attn_bias=False, non_linearity="swiglu", up_dim=48, dense_up_dim=160,
    n_exp=8, n_shared=0, n_act=3, router="sigmoid", routed_scale=1.0,
    conv_len=3)
HI = jax.default_matmul_precision("highest")


def _big(variables):
    """Weights a few times the draw, so that at 64 wide every term moves
    the logits by more than float32 rounding; the two QK-norm vectors and
    the block norms off one, so that leaving one out shows."""
    def scale(path, a):
        name = str(path[-1])
        if a.ndim >= 2:
            return a * 6.0
        if "q_norm" in name or "k_norm" in name:
            return a * (1.0 + 0.5 * jnp.cos(jnp.arange(a.shape[0])))
        return a
    return jax.tree_util.tree_map_with_path(scale, variables)


@pytest.fixture(scope="module")
def mv():
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = _big(model.init({"params": jax.random.PRNGKey(1)},
                                jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, variables


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def _engine(model, variables, **kw):
    kw = {"n_slots": 2, "max_len": 128, "block_size": 8,
          "prefill_chunk": 16, "temperature": 0.0, "min_bucket": 8,
          "prefix_cache": False, **kw}
    return DecodeEngine(model, variables, **kw)


def _rel(got, want):
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt((d * d).mean() / (np.asarray(want) ** 2).mean()))


def _strip(cfg, caches):
    return [None if k == "E" else c
            for k, c in zip(cfg.layer_pattern, caches)]


# (1) the tree, the whole forward pass, what each term is worth -------------

def test_the_tree_is_the_published_one(mv):
    cfg, model, variables = mv
    p = variables["params"]
    assert "lm_head" not in p                            # tied
    assert set(p["block_0"]) == {"norm", "conv"}
    conv = p["block_0"]["conv"]
    assert set(conv) == {"in_proj", "conv_w", "out_proj"}   # no bias
    assert conv["in_proj"].shape == (64, 3 * 64)         # [B | C | x']
    assert conv["conv_w"].shape == (3, 64)
    assert conv["out_proj"].shape == (64, 64)
    assert set(p["block_1"]) == {"norm", "mlp"}
    assert p["block_1"]["mlp"]["c_fc"].shape == (64, 2 * 160)   # own width
    assert p["block_1"]["mlp"]["c_proj"].shape == (160, 64)
    attn = p["block_2"]["attn"]
    assert set(attn) == {"c_attn", "c_proj", "q_norm", "k_norm"}
    assert set(attn["c_attn"]) == {"kernel"}             # no biases
    assert attn["q_norm"].shape == attn["k_norm"].shape == (16,)
    moe = p["block_3"]["moe"]
    assert set(moe) == {"gate", "gate_bias", "experts_up", "experts_down"}
    assert moe["experts_up"].shape == (8, 2 * 48, 64)    # [a | b], gated
    assert moe["experts_down"].shape == (8, 48, 64)
    assert moe["gate"].shape == (64, 8) and moe["gate_bias"].shape == (8,)
    # a slot carries a convolution's tail alone, no state beside it
    caches = init_paged_cache(cfg, 5, 8, dtype=jnp.float32, n_slots=3)
    assert [None if c is None else sorted(c) for c in caches] == [
        ["conv"], None, ["k", "v"], None, ["conv"], None, ["conv"], None]
    assert caches[0]["conv"].shape == (3, 2, 64)
    assert cfg.recurrent


def test_full_forward_matches_the_reference(mv):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((23, 23), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
    assert _rel(got, want) < 2e-5
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_term_left_out_fails_the_comparison(mv, fault):
    """fp8 experts, the bias in the weights, no renormalisation, RoPE off,
    at base 10,000 or pairing adjacent lanes, no QK-norm, a tap of the
    convolution dropped, `C *` dropped: the reference so spoilt is far
    from the program."""
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((23, 23), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        spoilt = ref.forward_logits(variables["params"], LLM_KW, idx,
                                    faults=(fault,))
    assert _rel(got, spoilt) > 5e-3, fault


@pytest.mark.parametrize("field, other", [("rope_theta", 1e4),
                                          ("rope_pairing", "adjacent"),
                                          ("qk_norm", False),
                                          ("pos_emb", "none")])
def test_each_field_reaches_the_program(mv, field, other):
    """The same from the program's side: the model built with one field
    changed is far from the reference."""
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((23,), seed=4), jnp.int32)
    built = LLM(dataclasses.replace(cfg, **{field: other}),
                compute_dtype=jnp.float32, attn_impl="naive")
    with HI:
        got, _, _ = built.apply(variables, idx, all_logits=True)
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
    assert _rel(got, want) > 5e-3


def test_rope_pairings_and_computed_angles():
    """`rope_angles` is the table's rows, at any position; the half
    pairing is the adjacent one under the lanes' permutation."""
    tab = rope.precompute_rope_freqs(16, 64, 1e6)
    np.testing.assert_allclose(rope.rope_angles(5, 7, 16, 1e6), tab[5:12],
                               atol=1e-6)
    per = rope.rope_angles(jnp.asarray([0, 9]), 3, 16, 1e6)
    np.testing.assert_allclose(per[1], tab[9:12], atol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 16))
    half = rope.apply_rotary_emb(x, per, half=True)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    # lanes (i, i + 8) of the permuted x are lanes (2i, 2i + 1) of x
    adj = rope.apply_rotary_emb(x[..., np.argsort(perm)], per)
    np.testing.assert_allclose(half, adj[..., perm], atol=1e-6)
    assert float(jnp.abs(half - rope.apply_rotary_emb(x, per)).max()) > 0.1


# (2) the cache path: chunks into a used slot, slots at different positions -

def test_chunked_prefill_into_a_used_slot_then_decode_gives_the_logits(mv):
    """The model through its own cache tree, without the engine: two
    sequences of different lengths, each in two chunks into a slot whose
    tail held another sequence's inputs, then teacher-forced decode of
    BOTH in one call (each at its own position: RoPE a slot) beside a dead
    slot 0: every position's logits are the reference's full forward
    pass's, and the dead slot's tail stays what it was."""
    cfg, model, variables = mv
    bs, chunk, n_new = 8, 16, 7
    lens = {1: 21, 2: 30}
    seqs = {s: np.asarray(_prompts((L + n_new,), seed=5 + s)[0])
            for s, L in lens.items()}
    caches = init_paged_cache(cfg, 17, bs, dtype=jnp.float32, n_slots=3)
    caches = [None if c is None else jax.tree_util.tree_map(
        lambda a: a + 3.0, c) if "conv" in c else c for c in caches]
    bt = np.zeros((3, 10), np.int32)
    bt[1, :8] = np.arange(1, 9)
    bt[2, :8] = np.arange(9, 17)
    bt = jnp.asarray(bt)
    rows = {1: [], 2: []}
    with HI:
        for s, L in lens.items():
            for off in range(0, L, chunk):
                n = min(chunk, L - off)
                buf = np.zeros((1, chunk), np.int32)
                buf[0, :n] = seqs[s][off:off + n]
                logits, _, caches = model.apply(
                    variables, jnp.asarray(buf), None, caches,
                    jnp.int32(off), logits_idx=jnp.asarray([n - 1]),
                    block_tables=bt[s:s + 1],
                    state_ctx={"slot": jnp.int32(s),
                               "valid_len": jnp.asarray([n], jnp.int32)})
                caches = _strip(cfg, caches)
            rows[s].append(logits[0, -1])
        for i in range(n_new - 1):
            tok = [0] + [int(seqs[s][lens[s] + i]) for s in (1, 2)]
            pos = [0] + [lens[s] + i for s in (1, 2)]
            logits, _, caches = model.apply(
                variables, jnp.asarray(tok, jnp.int32)[:, None], None,
                caches, jnp.asarray(pos, jnp.int32), block_tables=bt,
                state_ctx={"live": jnp.asarray([False, True, True])})
            caches = _strip(cfg, caches)
            for s in (1, 2):
                rows[s].append(logits[s, -1])
        for s, L in lens.items():
            want = ref.forward_logits(
                variables["params"], LLM_KW,
                jnp.asarray(seqs[s][None, :L + n_new - 1]), last=n_new)[0]
            assert _rel(jnp.stack(rows[s]), want) < 2e-5, s
    np.testing.assert_allclose(caches[0]["conv"][0], 3.0)    # the dead slot


def test_a_first_chunk_zeroes_the_tail_and_pads_never_reach_it(mv):
    """One 'C' block: a chunk at position 0 reads zeros whatever the slot
    held; the tail it leaves is the last two REAL inputs u = B * x'; a
    later chunk starts from it; the other slot's row is untouched."""
    cfg, model, variables = mv
    from distributed_pytorch_tpu.models.shortconv import ShortConv
    p = variables["params"]["block_0"]["conv"]
    layer = ShortConv(cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 16, 64))
    used = {"conv": jnp.full((2, 2, 64), 3.0)}
    ctx = lambda n: {"slot": jnp.int32(1),  # noqa: E731
                     "valid_len": jnp.asarray([n], jnp.int32)}
    with HI:
        whole, _ = layer.apply({"params": p}, x[:, :11])
        first, c1 = layer.apply({"params": p}, x, used, jnp.int32(0),
                                ctx(5))
        b, _, xp = jnp.split(x[0] @ p["in_proj"], 3, axis=-1)
        np.testing.assert_allclose(first[0, :5], whole[0, :5], atol=1e-5)
        np.testing.assert_allclose(c1["conv"][1], (b * xp)[3:5], atol=1e-5)
        np.testing.assert_allclose(c1["conv"][0], 3.0)
        later, c2 = layer.apply({"params": p}, x[:, 5:], c1, jnp.int32(5),
                                ctx(6))
        np.testing.assert_allclose(later[0, :6], whole[0, 5:11], atol=1e-5)
        np.testing.assert_allclose(c2["conv"][1], (b * xp)[9:11], atol=1e-5)
        # the one-token form: a row that is not live keeps its tail
        y, c3 = layer.apply({"params": p}, x[0, 10:12, None], c2, 0,
                            {"live": jnp.asarray([False, True])})
        np.testing.assert_allclose(c3["conv"][0], 3.0)
        np.testing.assert_allclose(
            y[1, 0], layer.apply({"params": p}, x[:, :12])[0][0, 11],
            atol=1e-5)
    # and the fault the comparison has to see: a tail NOT zeroed
    assert float(jnp.abs(layer.apply(
        {"params": p}, x, used, jnp.int32(3), ctx(5))[0][0, :2]
        - whole[0, :2]).max()) > 1e-2


@pytest.mark.parametrize("prefill_chunk", [16, 0])
def test_engine_matches_the_reference_through_reused_slots(mv,
                                                           prefill_chunk):
    cfg, model, variables = mv
    prompts = _prompts((37, 9, 20, 50, 5))
    eng = _engine(model, variables, prefill_chunk=prefill_chunk,
                  prefix_cache=True)
    n_new = 6
    with HI:
        outs = eng.run(prompts, n_new)
        worst = 0.0
        for p, o in zip(prompts, outs):
            o = [int(t) for t in o]
            assert o[:len(p)] == p and len(o) == len(p) + n_new
            logits = ref.forward_logits(variables["params"], LLM_KW,
                                        jnp.asarray([o[:-1]], jnp.int32),
                                        last=n_new)[0]
            for row, tok in zip(np.asarray(logits), o[len(p):]):
                worst = max(worst, float(row.max() - row[tok]))
    assert worst < 1e-5
    # a convolution tail is per-slot state: the model is `recurrent`
    assert eng.state_resets == 5
    assert eng.features_declined == ["prefix_cache"]
    assert eng.absent_assignments == 0                   # every expert held
    assert eng.held_assignments == 3 * 3 * (sum(map(len, prompts)) + 5 * 5)
    if prefill_chunk:
        assert eng.merged_program_share == 1.0


# (3) the gated kernels fed by the sigmoid router ---------------------------

@pytest.mark.parametrize("n_tokens", [8, 40])
def test_held_experts_ffn_fed_by_route_sigmoid_matches_the_dense_einsum(
        n_tokens):
    """`route_sigmoid` (bias-corrected selection, renormalised unbiased
    weights) into the GATED kernels: a router and an expert kind that had
    not met. The tile count comes out beside the result."""
    ks = jax.random.split(jax.random.PRNGKey(n_tokens), 5)
    C, F, E, k = 64, 48, 8, 3
    x = jax.random.normal(ks[0], (n_tokens, C))
    gate = jax.random.normal(ks[1], (C, E)) * 0.3
    bias = jax.random.normal(ks[2], (E,)) * 0.2
    w_up = jax.random.normal(ks[3], (E, 2 * F, C)) * 0.1
    w_down = jax.random.normal(ks[4], (E, F, C)) * 0.1
    with HI:
        idx, w = mlp_mod.route_sigmoid(x, gate, bias, k, 1.0)
        ridx, rw = ref.route(x, gate, bias, k=k, scale=1.0)
        np.testing.assert_array_equal(idx, ridx)
        np.testing.assert_allclose(w, rw, rtol=1e-5)     # 1e-20 and 1e-6
        got, tiles = gm.held_experts_ffn(x, idx, w, w_up, w_down, first=0,
                                         n_routed=E, gated=True,
                                         interpret=True)
        comb = (jax.nn.one_hot(idx, E) * w[..., None]).sum(1)
        a, b = jnp.split(jnp.einsum("nc,efc->enf", x, w_up), 2, axis=-1)
        want = jnp.einsum("enf,efc,ne->nc", jax.nn.silu(a) * b, w_down, comb)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    s = jax.nn.sigmoid(x @ gate)
    # the bias moves the selection, never a weight
    np.testing.assert_array_equal(idx, jax.lax.top_k(s + bias, k)[1])
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    tile = gm.held_tile_rows(n_tokens, k, E)
    assert int(tiles[0]) == int(np.ceil(counts / tile).sum())


def test_the_layer_has_no_shared_expert_and_carries_its_tiles_out(mv):
    """`n_shared` 0: no shared leaves and no `moe_shared` work; the layer
    is the reference's routed sum alone; a sigmoid-routed GATED layer
    carries its kernels' tile count out (and no held-gate share: its
    weights are renormalised over the chosen)."""
    cfg, model, variables = mv
    moe = variables["params"]["block_3"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 11, 64))
    layer = mlp_mod.RoutedExperts(cfg)
    with HI:
        y, stats = layer.apply({"params": moe}, x, jnp.ones((22,), bool))
        want = ref.experts_forward(x, moe, k=3, scale=1.0)
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)
    assert set(stats) == {"tokens", "absent", "tiles"}
    assert int(stats["tokens"].sum()) == 66 and int(stats["absent"][0]) == 0
    text = jax.jit(lambda v, x: layer.apply({"params": v}, x)[0]).lower(
        moe, x).as_text(debug_info=True)
    assert "moe_shared" not in text and "moe_experts" in text
    # both row sets of a fused step in one call: each set reads what it
    # reads alone
    with HI:
        ys, _ = layer.apply({"params": moe}, [x[:1], x[1:, :3]],
                            [jnp.ones((11,), bool), jnp.ones((3,), bool)])
        alone, _ = layer.apply({"params": moe}, x[1:, :3])
    np.testing.assert_array_equal(ys[1], alone)


def test_the_engine_counts_the_tiles_its_programs_ran(mv):
    cfg, model, variables = mv
    eng = _engine(model, variables)
    with HI:
        eng.run(_prompts((37, 9, 20)), 5)
    assert eng.expert_calls_by["chunk"] > 0 < eng.expert_calls_by["decode"]
    assert sum(eng.expert_calls_by.values()) == eng.expert_calls
    assert eng.expert_second_tiles == 0      # 16-row tiles, 6 rows expected
    assert eng.held_gate_share == 0.0


# (4) scopes in the programs, counters at /metrics --------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_new_scopes_reach_the_compiled_op_names(mv, fused):
    import re
    from distributed_pytorch_tpu.engine.decode import (make_fused_step_fn,
                                                       make_step_fn)
    from distributed_pytorch_tpu.obs.trace import (MIXER_MODULES,
                                                   MIXER_SCOPES, SCOPES)
    assert not set(MIXER_SCOPES) & set(SCOPES)
    assert {"conv_chunk", "conv_step", "qk_norm", "rope"} <= set(
        MIXER_SCOPES) and "conv" in MIXER_MODULES
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
    from distributed_pytorch_tpu.parallel.aot_store import (
        _no_persistent_cache)
    with _no_persistent_cache():
        text = jax.jit(fn).lower(*args).compile().as_text()
    parts = [set(re.split(r"[/()]", p))
             for p in re.findall(r'op_name="([^"]+)"', text)]
    want = {"conv", "conv_step", "mlp", "moe", "attn", "norm", "qk_norm",
            "rope", "attn_core", "kv_update", "moe_route", "moe_experts",
            "moe_pack", "moe_combine", "lm_head", "decode"}
    for scope in want | ({"conv_chunk", "chunk_prefill"} if fused
                         else set()):
        assert any(scope in p for p in parts), scope
    assert fused or not any("conv_chunk" in p for p in parts)
    assert not any("moe_shared" in p or "ssm" in p for p in parts)


def test_counters_reach_metrics_and_the_flight_record(mv):
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    cfg, model, variables = mv
    eng = _engine(model, variables)
    sched = Scheduler(eng, max_queue=4)
    with HI:
        eng.run(_prompts((20, 9)), 4)
    got = {}
    for line in sched.metrics.render_prometheus().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.partition(" ")
            got[name] = float(value)
    # a conv-only model's first chunks are state resets
    assert got["serve_state_resets_total"] == eng.state_resets == 2
    assert got["serve_experts_hit_per_call"] == pytest.approx(
        eng.experts_hit / eng.expert_calls)
    assert got["serve_expert_absent_assignments_share"] == 0.0
    assert got["serve_expert_second_tiles_per_call"] == pytest.approx(
        eng.expert_second_tiles / eng.expert_calls)
    assert got["serve_merged_program_share"] == 1.0
    recs = eng.flight.entries()
    assert sum(r["state_reset"] for r in recs) == 2
    assert sum(r["experts_hit"] for r in recs) == eng.experts_hit
    assert all("expert_second_tiles" in r for r in recs)
    # 3 expert layers a program, of either kind
    assert {r["expert_calls"] for r in recs} == {3}


# (5) what the models that were there are not asked -------------------------

def test_the_nemotron_and_granite_shapes_and_a_classic_model_are_asked_nothing_new():
    """Their leaves and their expert layers' stats are what they were: a
    shared expert where `n_shared` is 1, no QK-norm leaves; every layer
    that runs the expert kernels carries their `tiles` out, `held_gate` is
    the softmax router's alone; a classic rope model reads its table at the
    adjacent pairing unless its configuration says otherwise."""
    from tests.test_granite import LLM_KW as GRANITE_KW
    from tests.test_hybrid import LLM_KW as NEMOTRON_KW
    for kw, stat_keys in ((NEMOTRON_KW, {"tokens", "absent", "tiles"}),
                          (GRANITE_KW, {"tokens", "absent", "held_gate",
                                        "tiles"})):
        cfg = LLMConfig(**kw)
        assert (cfg.qk_norm, cfg.rope_theta, cfg.rope_pairing,
                cfg.dense_up_dim) == (False, 10000.0, "adjacent", 0)
        v = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive").init(
            {"params": jax.random.PRNGKey(1)}, jnp.zeros((1, 8), jnp.int32))
        blocks = {k: set(b) - {"norm"} for k, b in v["params"].items()
                  if k.startswith("block_")}
        assert set().union(*blocks.values()) == {"ssm", "moe", "attn"}
        i = cfg.layer_pattern.index("E")
        moe = v["params"][f"block_{i}"]["moe"]
        assert {"shared_up", "shared_down"} <= set(moe)
        j = cfg.layer_pattern.index("*")
        assert set(v["params"][f"block_{j}"]["attn"]) == {"c_attn", "c_proj"}
        _, stats = mlp_mod.RoutedExperts(cfg).apply(
            {"params": moe}, jnp.ones((1, 4, 64)), jnp.ones((4,), bool))
        assert set(stats) == stat_keys
    classic = LLMConfig(vocab_size=256, block_size=64, n_embd=64, n_head=4,
                        n_kv_heads=2, attn="gqa", n_layer=2, up_dim=128,
                        pos_emb="rope")
    model = LLM(classic)
    v = model.init({"params": jax.random.PRNGKey(0)},
                   jnp.zeros((1, 8), jnp.int32))
    assert set(v["params"]["block_0"]["attn"]) == {"c_attn", "c_proj"}
    assert set(v["params"]["block_0"]["mlp"]) == {"c_fc", "c_proj"}
    assert v["params"]["block_0"]["mlp"]["c_fc"].dtype == jnp.float32
    text = jax.jit(model.apply).lower(
        v, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    assert "qk_norm" not in text
    half = LLM(dataclasses.replace(classic, rope_pairing="half"))
    x = jnp.arange(8, dtype=jnp.int32)[None]
    assert _rel(half.apply(v, x)[0], model.apply(v, x)[0]) > 5e-3
    with pytest.raises(AssertionError, match="patterned"):
        dataclasses.replace(classic, qk_norm=True)
