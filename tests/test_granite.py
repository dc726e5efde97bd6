"""A Granite 4.0-H shaped patterned model (a Mamba-2 or attention mixer
and a gated softmax-top-k expert layer as two blocks of one published
layer, four scalar multipliers, a tied head) at a small size on the CPU,
seeded weights, float32, against the plain reference
(benchmark/lib/reference_granite.py): the whole forward pass, the engine's
cache path, the gated expert kernels, the router, the two-chip share, what
the comparison sees, the tile rule and the new counters. And what the
models that were there are NOT asked."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_granite as ref
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import mlp as mlp_mod
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.ops import grouped_matmul as gm

# the cell's pattern in little: a published layer = mixer block + expert
# block; 7 of 8 routed experts' worth of routing lands on 2 shares of 4
LLM_KW = dict(
    vocab_size=256, block_size=128, n_embd=64, n_layer=6,
    layer_pattern="MEME*E", pos_emb="none", non_linearity="swiglu",
    up_dim=48, shared_up_dim=96, n_exp=9, n_shared=1, n_act=4,
    experts_held=(0, 4), router="softmax_topk", attn="gqa", n_head=4,
    n_kv_heads=2, head_dim=32, attn_bias=False, tie_head=True,
    embed_mult=12.0, resid_mult=0.22, attn_scale=1.0 / 32, logits_div=16.0,
    ssm_heads=8, ssm_head_dim=16, ssm_groups=1, ssm_state=16, ssm_conv=4,
    ssm_chunk=8)
HI = jax.default_matmul_precision("highest")


def _big(variables):
    """Weights a few times the N(0, 0.02) draw, so that at 64 wide every
    term (the router's logits, the attention scores, the gate half) moves
    the logits by more than float32 rounding."""
    return jax.tree_util.tree_map(lambda a: a * 6.0 if a.ndim >= 2 else a,
                                  variables)


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


def _worst_gap(variables, prompts, outs, n_new):
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


def _rel(got, want):
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt((d * d).mean() / (np.asarray(want) ** 2).mean()))


# (1) the whole forward pass, and what each term is worth ------------------

def test_the_tree_is_the_published_one(mv):
    cfg, model, variables = mv
    p = variables["params"]
    assert "lm_head" not in p                           # tied
    moe = p["block_1"]["moe"]
    assert set(moe) == {"gate", "experts_up", "experts_down", "shared_up",
                        "shared_down"}                   # no gate_bias
    assert moe["experts_up"].shape == (4, 2 * 48, 64)    # [a | b], out by in
    assert moe["experts_down"].shape == (4, 48, 64)
    assert moe["shared_up"].shape == (64, 2 * 96)
    assert moe["gate"].shape == (64, 8)                  # the router's width
    assert set(p["block_4"]) == {"norm", "attn"}
    assert set(p["block_0"]) == {"norm", "ssm"}


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
    """Each multiplier set to 1, the gate half dropped, the softmax over
    all logits, fp8 experts, no `D x`: the reference so spoilt is far from
    the program."""
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((23, 23), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        spoilt = ref.forward_logits(variables["params"], LLM_KW, idx,
                                    faults=(fault,))
    assert _rel(got, spoilt) > 5e-3, fault


@pytest.mark.parametrize("field, one", [("embed_mult", 1.0),
                                        ("resid_mult", 1.0),
                                        ("attn_scale", 1.0),
                                        ("logits_div", 1.0)])
def test_each_multiplier_reaches_the_program(mv, field, one):
    """The same from the program's side: the model built with one
    multiplier at 1 is far from the reference with all four."""
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((23,), seed=4), jnp.int32)
    other = LLM(dataclasses.replace(cfg, **{field: one}),
                compute_dtype=jnp.float32, attn_impl="naive")
    with HI:
        got, _, _ = other.apply(variables, idx, all_logits=True)
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
    assert _rel(got, want) > 5e-3


# (2) the engine: chunks into a used slot, then decode through the cache ---

@pytest.mark.parametrize("prefill_chunk", [16, 0])
def test_engine_matches_the_reference_through_reused_slots(mv,
                                                           prefill_chunk):
    cfg, model, variables = mv
    prompts = _prompts((37, 9, 20, 50, 5))
    eng = _engine(model, variables, prefill_chunk=prefill_chunk)
    with HI:
        outs = eng.run(prompts, 6)
        assert _worst_gap(variables, prompts, outs, 6) < 1e-5
    assert eng.state_resets == 5
    assert eng.features_declined == []
    rows = eng.held_assignments + eng.absent_assignments
    assert rows == 3 * 3 * (sum(map(len, prompts)) + 5 * 5)
    assert 0 < eng.experts_hit <= eng.expert_calls * 4
    assert 0.0 < eng.held_gate_share < 1.0
    rec = eng.flight.entries()[-1]
    assert {"experts_hit", "absent_assignments", "expert_second_tiles",
            "state_reset"} <= set(rec)


def test_chunked_prefill_into_a_used_slot_then_decode_gives_the_logits(mv):
    """The model through its own cache tree, without the engine: a prompt
    in two chunks into slot 1, which held another sequence's state, then
    teacher-forced decode beside a dead slot 0: every position's logits
    are the reference's full forward pass's."""
    from distributed_pytorch_tpu.models.gpt import init_paged_cache
    cfg, model, variables = mv
    seq = np.asarray(_prompts((21 + 8,), seed=5)[0])
    bs, chunk, L = 8, 16, 21
    caches = init_paged_cache(cfg, 9, bs, dtype=jnp.float32, n_slots=2)
    caches = [None if c is None else jax.tree_util.tree_map(
        lambda a: a + 3.0, c) if "ssm" in c else c for c in caches]
    bt = np.zeros((2, 10), np.int32)
    bt[1, :8] = np.arange(1, 9)
    bt = jnp.asarray(bt)
    strip = lambda cs: [None if k == "E" else c  # noqa: E731
                        for k, c in zip(cfg.layer_pattern, cs)]
    rows = []
    with HI:
        for off in range(0, L, chunk):
            n = min(chunk, L - off)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n] = seq[off:off + n]
            logits, _, caches = model.apply(
                variables, jnp.asarray(buf), None, caches, jnp.int32(off),
                logits_idx=jnp.asarray([n - 1]), block_tables=bt[1:],
                state_ctx={"slot": jnp.int32(1),
                           "valid_len": jnp.asarray([n], jnp.int32)})
            caches = strip(caches)
        rows.append(logits[0, -1])
        for i in range(L, L + 7):
            logits, _, caches = model.apply(
                variables, jnp.asarray([[0], [int(seq[i])]], jnp.int32),
                None, caches, jnp.asarray([0, i], jnp.int32),
                block_tables=bt,
                state_ctx={"live": jnp.asarray([False, True])})
            caches = strip(caches)
            rows.append(logits[1, -1])
        want = ref.forward_logits(variables["params"], LLM_KW,
                                  jnp.asarray(seq[None, :L + 7]), last=8)[0]
    assert _rel(jnp.stack(rows), want) < 2e-5


# (3) the gated kernels against the dense einsum ---------------------------

@pytest.mark.parametrize("n_tokens", [8, 40])
def test_gated_held_experts_ffn_matches_the_dense_einsum(n_tokens):
    """Ids over 8 routed experts of which 3 (ids 2..4) are held: absent
    assignments, rows sent nowhere (-1), an expert nobody chose."""
    key = jax.random.PRNGKey(n_tokens)
    ks = jax.random.split(key, 5)
    C, F, n_held, first, k = 64, 48, 3, 2, 3
    x = jax.random.normal(ks[0], (n_tokens, C))
    w_up = jax.random.normal(ks[1], (n_held, 2 * F, C)) * 0.1
    w_down = jax.random.normal(ks[2], (n_held, F, C)) * 0.1
    idx = jnp.stack([jax.random.permutation(jax.random.fold_in(ks[3], i),
                                            8)[:k]
                     for i in range(n_tokens)])
    idx = jnp.where(idx == 3, 7, idx)                  # held 3: never chosen
    idx = idx.at[::5].set(-1)                          # pad rows
    gates = jax.random.uniform(ks[4], (n_tokens, k))
    with HI:
        got, tiles = gm.held_experts_ffn(x, idx, gates, w_up, w_down,
                                         first=first, n_routed=8,
                                         gated=True, interpret=True)
        local = idx - first
        comb = (jax.nn.one_hot(local, n_held) * gates[..., None]).sum(1)
        a, b = jnp.split(jnp.einsum("nc,efc->enf", x, w_up), 2, axis=-1)
        want = jnp.einsum("enf,efc,ne->nc", jax.nn.silu(a) * b, w_down, comb)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[::5]).any()              # sent nowhere: zeros
    hit = sum(bool((local == e).any()) for e in range(n_held))
    assert int(tiles[0]) == hit == 2                   # one tile a hit expert


def test_the_kernels_tile_count_is_carried_out():
    """What `held_experts_ffn` returns beside the result is the tiles its
    kernels ran: 40 rows of top 3 of 64 get a tile of 16, every row's first
    choice is held expert 0 (three tiles), the others fall where they
    fall."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    N, C, F, n_held, k = 40, 64, 48, 3, 3
    assert gm.held_tile_rows(N, k, 64) == 16
    x = jax.random.normal(ks[0], (N, C))
    w_up = jax.random.normal(ks[1], (n_held, 2 * F, C)) * 0.1
    w_down = jax.random.normal(ks[2], (n_held, F, C)) * 0.1
    idx = jax.random.randint(ks[3], (N, k), 1, 64).at[:, 0].set(0)
    with HI:
        got, tiles = gm.held_experts_ffn(
            x, idx, jnp.ones((N, k)), w_up, w_down, first=0, n_routed=64,
            gated=True, interpret=True)
        comb = jax.nn.one_hot(idx, n_held).sum(1)
        a, b = jnp.split(jnp.einsum("nc,efc->enf", x, w_up), 2, axis=-1)
        want = jnp.einsum("enf,efc,ne->nc", jax.nn.silu(a) * b, w_down, comb)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=64)[:n_held]
    assert counts[0] == 40 and int(tiles[0]) == int(
        np.ceil(counts / 16).sum()) >= 3


def test_the_sigmoid_glu_gate_takes_the_dense_path():
    """'glu' (a sigmoid gate) has no fused epilogue: the layer runs the
    dense einsum, and computes sigmoid(a) * b."""
    cfg = LLMConfig(**{**LLM_KW, "non_linearity": "glu"})
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 64))
    layer = mlp_mod.RoutedExperts(cfg)
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    with HI:
        y, _ = layer.apply({"params": p}, x)
        idx, w = ref.route(x[0], p["gate"], k=3)
        want = jnp.zeros((5, 64))
        for e in range(4):
            a, b = jnp.split(x[0] @ p["experts_up"][e].T, 2, axis=-1)
            want += ((jax.nn.sigmoid(a) * b) @ p["experts_down"][e]) \
                * jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)[:, None]
        a, b = jnp.split(x[0] @ p["shared_up"], 2, axis=-1)
        want += (jax.nn.sigmoid(a) * b) @ p["shared_down"]
    np.testing.assert_allclose(y[0], want, atol=2e-5, rtol=2e-5)


# (4) the router -----------------------------------------------------------

def test_softmax_topk_gates_sum_to_one_over_the_chosen():
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (32, 64))
    gate = jax.random.normal(jax.random.fold_in(key, 1), (64, 72)) * 0.3
    idx, w = mlp_mod.route_softmax_topk(x, gate, 10)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-6)
    logits = x @ gate
    np.testing.assert_array_equal(idx, jax.lax.top_k(logits, 10)[1])
    top = jnp.take_along_axis(logits, idx, axis=1)
    np.testing.assert_allclose(w, jax.nn.softmax(top, axis=-1), rtol=1e-5)
    # NOT the softmax over all 72 with the ten picked out of it
    over_all = jnp.take_along_axis(jax.nn.softmax(logits, -1), idx, axis=1)
    assert float(jnp.abs(w - over_all).max()) > 1e-2
    ridx, rw = ref.route(x, gate, k=10)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(w, rw, rtol=1e-5)
    # on a share the held experts keep those weights: their sum over a row
    # is the row's share, under one wherever an absent expert was chosen
    held = idx < 36
    share = jnp.sum(jnp.where(held, w, 0.0), axis=1)
    assert float(share.max()) < 1.0 and float(share.min()) > 0.0


# (5) the two shares + the shared expert once = the uncut layer ------------

def test_expert_shares_add_up_to_the_uncut_layer():
    whole = LLMConfig(**{**LLM_KW, "experts_held": ()})
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 11, 64))
    layer = mlp_mod.RoutedExperts(whole)
    p = layer.init(jax.random.PRNGKey(0), x)["params"]
    p = jax.tree_util.tree_map(lambda a: a * 6.0, p)
    with HI:
        uncut, _ = layer.apply({"params": p}, x)
        want = ref.experts_forward(x, p, k=3, first=0)
        shared_only = ref.experts_forward(x, p, k=3, first=0, held=())
        parts, gate_shares = [], []
        for first in (0, 4):
            cfg = dataclasses.replace(whole, experts_held=(first, 4))
            share = {**p, "experts_up": p["experts_up"][first:first + 4],
                     "experts_down": p["experts_down"][first:first + 4]}
            y, stats = mlp_mod.RoutedExperts(cfg).apply(
                {"params": share}, x, jnp.ones((22,), bool))
            parts.append(y)
            np.testing.assert_allclose(
                y, ref.experts_forward(x, share, k=3, first=first),
                atol=2e-5, rtol=2e-5)
            assert int(stats["tokens"].sum() + stats["absent"].sum()) == 66
            gate_shares.append(float(stats["held_gate"][0]))
    np.testing.assert_allclose(uncut, want, atol=2e-5, rtol=2e-5)
    # the gates are computed over all 8 and NOT renormalised over the held:
    # the two shares' held gates add up to one a row ...
    assert sum(gate_shares) == pytest.approx(22.0, rel=1e-5)
    assert 0 < gate_shares[0] < 22.0
    # ... and the parts, the shared expert counted once, to the uncut layer
    np.testing.assert_allclose(parts[0] + parts[1] - shared_only, uncut,
                               atol=2e-5, rtol=2e-5)


# (6) the tile rule ---------------------------------------------------------

def test_tile_rows_follow_the_rows_an_expert_expects():
    # top 6 of 128, 64 tokens and a 256-row chunk: what PR 33 ran with
    assert gm.held_tile_rows(64, 6, 128) == 16
    assert gm.held_tile_rows(256, 6, 128) == 32
    # top 10 of 72: 8.9 rows expected of a decode call, 35.6 of a chunk
    assert gm.held_tile_rows(64, 10, 72) == 32
    assert gm.held_tile_rows(256, 10, 72) == 64
    assert gm.held_tile_rows(1, 10, 72) == 16
    assert gm.held_tile_rows(4096, 10, 72) == 128


def test_second_tiles_are_counted_by_call_kind(mv):
    """The kernels' tile count of each call, less the experts the call
    hit, is its second tiles, counted under what the call carried (a fused
    program's chunk call comes first). A 16-row tile: 17 rows are two
    tiles, 33 are three."""
    cfg, model, variables = mv
    eng = _engine(model, variables)
    tokens = np.asarray([[17, 0, 16, 3], [1, 0, 0, 33]])
    stats = [{"tokens": tokens, "absent": np.asarray([5, 4]),
              "held_gate": np.asarray([10.0, 5.0], np.float32),
              "tiles": np.asarray([2 + 1 + 1, 1 + 3])}]
    hit, absent, second = eng.count_experts(stats, ("chunk", "decode"))
    assert (hit, absent, second) == (5, 9, 1 + 2)
    assert eng.expert_second_tiles_by == {"chunk": 1, "decode": 2}
    assert eng.expert_second_tile_calls_by == {"chunk": 1, "decode": 1}
    assert eng.expert_calls_by == {"chunk": 1, "decode": 1}
    # 70 held + 9 absent assignments at top 3: 79 / 3 rows, 15 of gates
    assert eng.held_gate_share == pytest.approx(15.0 / (79 / 3))
    # a layer without the leaf (the sigmoid-routed programs) counts none
    del stats[0]["tiles"]
    assert eng.count_experts(stats, ("chunk", "decode")) == (5, 9, 0)
    assert eng.expert_second_tiles == 3


def test_the_engine_counts_the_tiles_its_programs_ran(mv):
    """Through the engine: the `tiles` leaf rides every program out, each
    call is counted under its kind, and two slots or a 16-row chunk at top
    3 of 8 never fill a 16-row tile twice (6 rows expected a chunk)."""
    cfg, model, variables = mv
    eng = _engine(model, variables)
    with HI:
        eng.run(_prompts((37, 9, 20)), 5)
    assert eng.expert_calls_by["chunk"] > 0 < eng.expert_calls_by["decode"]
    assert sum(eng.expert_calls_by.values()) == eng.expert_calls
    assert eng.expert_second_tiles == 0


# (7) the counters at /metrics and in the flight record ---------------------

def test_new_counters_reach_metrics_and_the_flight_record(mv):
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
    assert got["serve_expert_held_gate_share"] == pytest.approx(
        eng.held_gate_share)
    assert 0.0 < got["serve_expert_held_gate_share"] < 1.0
    assert got["serve_expert_second_tiles_per_call"] == pytest.approx(
        eng.expert_second_tiles / eng.expert_calls)
    assert sum(eng.expert_calls_by.values()) == eng.expert_calls
    recs = eng.flight.entries()
    assert sum(r["expert_second_tiles"] for r in recs) \
        == eng.expert_second_tiles


# (8) what the models that were there are not asked -------------------------

def _fingerprint(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(tree),
                             key=lambda pl: str(pl[0])):
        h.update(str(path).encode())
        h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()[:16]


def test_the_nemotron_shape_and_a_dense_model_are_asked_nothing_new():
    """The sigmoid-routed relu^2 model keeps its tree (a `gate_bias`, an
    untied head, ungated stacks), its seeded values and its outputs; a
    dense model its tree. Since PR 45 every layer that runs the expert
    kernels carries their `tiles` out, this one too; the held weights' sum
    stays the softmax router's."""
    from tests.test_hybrid import LLM_KW as NEMOTRON_KW
    cfg = LLMConfig(**NEMOTRON_KW)
    assert (cfg.router, cfg.embed_mult, cfg.resid_mult, cfg.attn_scale,
            cfg.logits_div) == ("sigmoid", 1.0, 1.0, 0.0, 1.0)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    v = model.init({"params": jax.random.PRNGKey(1)},
                   jnp.zeros((1, 8), jnp.int32))
    moe = v["params"]["block_1"]["moe"]
    assert set(moe) == {"gate", "gate_bias", "experts_up", "experts_down",
                        "shared_up", "shared_down"}
    assert moe["experts_up"].shape == (4, 48, 64)
    assert "lm_head" in v["params"]
    idx = jnp.asarray(_prompts((12,), seed=9), jnp.int32)
    with HI:
        logits, _, _ = model.apply(v, idx, all_logits=True)
    # the parent commit's values (PR 35's tree, this seed)
    assert _fingerprint(v["params"]) == NEMOTRON_TREE
    np.testing.assert_allclose(logits[0, -1, :4], NEMOTRON_LOGITS[:4],
                               atol=2e-6)
    assert float(jnp.abs(logits).mean()) == pytest.approx(
        NEMOTRON_LOGITS[4], rel=1e-5)
    y, stats = mlp_mod.RoutedExperts(cfg).apply(
        {"params": moe}, jnp.ones((1, 4, 64)), jnp.ones((4,), bool))
    assert set(stats) == {"tokens", "absent", "tiles"}

    dense = LLMConfig(vocab_size=256, block_size=64, n_embd=64, n_head=4,
                      attn="mha", n_layer=2, up_dim=128,
                      non_linearity="gelu", pos_emb="learn")
    dv = LLM(dense).init({"params": jax.random.PRNGKey(0)},
                         jnp.zeros((1, 8), jnp.int32))
    assert set(dv["params"]["block_0"]) == {"ln1", "ln2", "attn", "mlp"}
    assert _fingerprint(dv["params"]) == DENSE_TREE
    with pytest.raises(AssertionError, match="patterned"):
        dataclasses.replace(dense, resid_mult=0.5)


NEMOTRON_TREE = "90e0eb591d9e75ba"
NEMOTRON_LOGITS = (-0.018239, -0.175551, 0.014133, 0.077755, 0.126018)
DENSE_TREE = "5a4cd076f58bfc3f"
