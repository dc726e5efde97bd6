"""The gated delta rule with a decay a head ('G', Gated DeltaNet) beside gated
GQA of wide heads ('*', a gate a channel from the query's own projection) in
ONE cache tree, every mixer in front of softmax-routed experts with a gated
shared expert, zero-centred norms, at a small size on the CPU: every width a
stand-in, every RATIO of the published model kept (3 G : 1 *, 2 value heads a
key head, 8 query heads a KV head, a quarter of a head's lanes rotated, top
10 of 64, an eighth held). (a) the model's and the engine's logits, prefill
in chunks and then decode, into a USED slot, against
`benchmark/lib/reference_qwen3next.py` (float32, the literal recurrence, no
cache); (b) every fault of the reference fails; (c) the chunk form and the
one-token form against a float64 literal recurrence, with decays down to
-80 a token; (d) the two routers agree; (e) the eight shares add up; (f)
with the new fields at their defaults the accepted kinds of block lower to
the parent's text; (g) what a configuration may say; (h) the tree's count."""

import dataclasses
import functools
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops_qwen3next
from benchmark.lib import reference_qwen3next as ref
from distributed_pytorch_tpu.config import LAYER_KEEPS, LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import mlp as mlp_mod
from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops import delta_rule as dr

LLM_KW = dict(
    vocab_size=256, block_size=1 << 15, n_embd=64, n_layer=8,
    layer_pattern="GEGEGE*E", pos_emb="rope", rope_theta=1e7,
    rope_pairing="half", rotary_frac=0.25, norm_eps=1e-6,
    norm_zero_centred=True, tie_head=False, attn="gqa", n_head=8,
    n_kv_heads=1, head_dim=32, qk_norm=True, attn_gate="channel",
    attn_bias=False, non_linearity="swiglu", up_dim=24, shared_up_dim=24,
    n_exp=65, n_shared=1, n_act=11, router="softmax_topk", shared_gate=True,
    gdn_heads=4, gdn_key_heads=2, gdn_head_dim=16, gdn_conv=4)
HI = jax.default_matmul_precision("highest")


def _big(variables):
    """Weights a few times the draw, so that at 64 wide every term moves
    the logits by more than float32 rounding."""
    return jax.tree_util.tree_map(lambda a: a * 6.0 if a.ndim >= 2 else a,
                                  variables)


@pytest.fixture(scope="module")
def mv():
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32)
    variables = _big(model.init({"params": jax.random.PRNGKey(1)},
                                jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, variables


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def _rel(got, want):
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt((d * d).mean()
                         / (np.asarray(want, np.float64) ** 2).mean()))


def _strip(cfg, caches):
    return [None if k == "E" else c
            for k, c in zip(cfg.layer_pattern, caches)]


# (1) the tree, the whole forward pass, what each term is worth -------------

def test_the_tree_is_the_published_one(mv):
    cfg, model, variables = mv
    p = variables["params"]
    assert LAYER_KEEPS["G"] == ("slot_state",) and cfg.recurrent \
        and cfg.slot_state == "recurrent layers" \
        and cfg.layers_keeping("slot_state") == 3 \
        and cfg.layers_keeping("pools") == 1 \
        and cfg.attn_gate_kind == "channel"
    gdn = {k: v.shape for k, v in p["block_0"]["gdn"].items()}
    assert gdn == {"W_qkvz": (64, 2 * 32 + 2 * 64), "W_ba": (64, 8),
                   "conv_w": (4, 2 * 32 + 64), "A_log": (4,),
                   "dt_bias": (4,), "o_norm": (16,), "W_o": (64, 64)}
    assert p["block_0"]["gdn"]["A_log"].dtype == jnp.float32
    attn = jax.tree_util.tree_map(lambda a: a.shape, p["block_6"]["attn"])
    # [q | k | v | gate]: 8 x 32 + 32 + 32 + 8 x 32; no leaf `c_gate`
    assert attn == {"c_attn": {"kernel": (64, 576)},
                    "c_proj": {"kernel": (256, 64)},
                    "q_norm": (32,), "k_norm": (32,)}
    moe = p["block_1"]["moe"]
    assert moe["gate"].shape == (64, 64) and "gate_bias" not in moe \
        and moe["shared_gate"].shape == (64, 1)
    # a zero-centred norm's drawn weights lie about 0, the output norm's at 1
    assert abs(float(p["block_0"]["norm"]["scale"].mean())) < 0.1 \
        and float(p["block_0"]["gdn"]["o_norm"].mean()) == 1.0
    total = sum(int(a.size) for a in jax.tree_util.tree_leaves(p))
    assert total == flops_qwen3next.total_params(LLM_KW)


def test_one_cache_tree_holds_both_kinds_of_leaf(mv):
    cfg, _, _ = mv
    caches = init_paged_cache(cfg, 9, 8, dtype=jnp.bfloat16, n_slots=3)
    assert [c is None for c in caches] == [k == "E" for k in
                                           cfg.layer_pattern]
    assert caches[0]["state"].shape == (3, 4, 16, 16) \
        and caches[0]["state"].dtype == jnp.float32
    assert caches[0]["tail"].shape == (3, 3, 128) \
        and caches[0]["tail"].dtype == jnp.bfloat16
    assert caches[6]["k"].shape == (9, 8, 128)       # one KV head of 32
    with pytest.raises(AssertionError, match="pass n_slots"):
        init_paged_cache(cfg, 9, 8)


def test_full_forward_matches_the_reference(mv):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((45, 45), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
    assert _rel(got, want) < 2e-5
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_term_left_out_fails_the_comparison(mv, fault):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((45, 45), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        spoilt = ref.forward_logits(variables["params"], LLM_KW, idx,
                                    faults=(fault,))
    assert not _rel(got, spoilt) <= 2e-3, fault   # an unstable one reads nan


@pytest.mark.parametrize("kind,block", [("G", 0), ("*", 6), ("E", 1)])
def test_a_block_alone_against_the_references(mv, kind, block):
    """`mixer_forward`, what the benchmark's `step_programs` holds a block
    to, on a drawn input."""
    from distributed_pytorch_tpu.models.attention import GQA
    from distributed_pytorch_tpu.models.linear_attention import GatedDeltaNet
    cfg, _, variables = mv
    name = {"G": "gdn", "*": "attn", "E": "moe"}[kind]
    p = variables["params"][f"block_{block}"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 37, 64))
    with HI:
        if kind == "G":
            got = GatedDeltaNet(cfg).apply({"params": p[name]}, h)[0]
        elif kind == "*":
            got = GQA(cfg).apply({"params": p[name]}, h, None)[0]
        else:
            got = mlp_mod.RoutedExperts(cfg).apply({"params": p[name]}, h)[0]
        want = ref.mixer_forward(LLM_KW, kind, p, h)
    assert _rel(got, want) < 2e-5


# (2) the two forms of the recurrence ----------------------------------------

def _operands(T, H=2, d=16, seed=0, low=-6.0):
    """float64 operands of the rule: L2-normalised k, a scaled q, log decays
    uniform on (`low`, 0) a head and row."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(T, H, d)) * d ** -0.5
    k = rng.normal(size=(T, H, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, H, d))
    g = rng.uniform(low, 0.0, size=(T, H))
    beta = rng.uniform(0.05, 1.0, size=(T, H))
    S0 = rng.normal(size=(H, d, d))
    return q, k, v, g, beta, S0


def _literal(q, k, v, g, beta, S0):
    """The three lines, a row at a time, in float64 numpy."""
    S = np.array(S0, np.float64)
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, None, None] * S
        u = v[t] - np.einsum("hkv,hk->hv", S, k[t])
        S = S + beta[t][:, None, None] * k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


@pytest.mark.parametrize("T,C,start,low", [
    (64, 16, True, -6.0),       # whole sub-chunks from a nonzero state
    (50, 16, True, -6.0),       # a partial last sub-chunk
    (37, 8, False, -6.0),       # from zeros, another sub-chunk size
    (9, 16, True, -6.0),        # fewer rows than a sub-chunk
    (48, 16, True, -80.0),      # decays down to -80 a token: 16 x 80 = 1,280
    (40, 32, True, -80.0),      # in a sub-chunk, far past float32's exponent
    (33, 16, True, -0.01),      # a memory of hundreds of tokens
])
def test_chunk_form_is_the_literal_recurrence(monkeypatch, T, C, start, low):
    monkeypatch.setattr(dr, "SUB_CHUNK", C)
    q, k, v, g, beta, S0 = _operands(T, seed=T, low=low)
    if low == -80.0:
        g[:, 0] = -80.0                  # one head at the floor throughout
    want_o, want_S = _literal(q, k, v, g, beta,
                              S0 if start else np.zeros_like(S0))
    args = [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta)]
    S_in = jnp.asarray(S0, jnp.float32) if start else None
    for form in (dr.gdn_chunk, dr.gdn_scan):
        o, S = form(*args, S_in)
        assert np.isfinite(np.asarray(o)).all() \
            and np.isfinite(np.asarray(S)).all()
        assert _rel(o, want_o) < 3e-6 and _rel(S, want_S) < 3e-6, form


def test_the_kda_form_cannot_take_such_a_decay():
    """Why 'G' is no field of 'K': the form that inverts a sub-chunk's
    decay about its middle row overflows at -80 a token."""
    q, k, v, g, beta, S0 = _operands(32, seed=1, low=-80.0)
    g[:, 0] = -80.0
    a = [jnp.asarray(t, jnp.float32) for t in (q, k, v)]
    gc = jnp.broadcast_to(jnp.asarray(g, jnp.float32)[..., None], q.shape)
    o, _ = dr.kda_chunk(*a, gc, jnp.asarray(beta, jnp.float32))
    assert not np.isfinite(np.asarray(o)).all()


def test_pad_rows_move_nothing():
    q, k, v, g, beta, S0 = (jnp.asarray(t, jnp.float32)
                            for t in _operands(32, seed=5))
    real = jnp.arange(32) < 19
    _, want = dr.gdn_chunk(q[:19], k[:19], v[:19], g[:19], beta[:19], S0)
    _, got = dr.gdn_chunk(q, k, v, jnp.where(real[:, None], g, 0.0),
                          jnp.where(real[:, None], beta, 0.0), S0)
    assert _rel(got, want) < 1e-6


@pytest.mark.parametrize("live", [None, (1, 0, 1, 1, 0)])
def test_one_token_form_is_the_literal_recurrence(live):
    """`kda_step` with the head's decay broadcast over its channels, as the
    mixer calls it: 5 slots, decays down to -80."""
    q, k, v, g, beta, _ = _operands(5, H=3, d=8, seed=2, low=-80.0)
    S = np.random.default_rng(9).normal(size=(5, 3, 8, 8))
    mask = None if live is None else jnp.asarray(live, bool)
    gb = jnp.broadcast_to(jnp.asarray(g, jnp.float32)[..., None], (5, 3, 8))
    o, Sn = dr.kda_step(jnp.asarray(S, jnp.float32),
                        *(jnp.asarray(t, jnp.float32) for t in (q, k, v)),
                        gb, jnp.asarray(beta, jnp.float32), mask)
    for s in range(5):
        want_o, want_S = _literal(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                  g[s:s + 1], beta[s:s + 1], S[s])
        if live is None or live[s]:
            assert _rel(o[s], want_o[0]) < 2e-6 \
                and _rel(Sn[s], want_S) < 2e-6
        else:
            assert np.array_equal(Sn[s], np.asarray(S[s], np.float32))


def test_the_step_kernel_takes_a_decay_broadcast_over_a_head():
    """`kda_state_step` in interpret mode, handed the scalar decay a head
    broadcast over the channels, against its XLA twin: unchanged."""
    q, k, v, g, beta, _ = _operands(4, H=2, d=128, seed=6, low=-20.0)
    S = jax.random.normal(jax.random.PRNGKey(2), (4, 2, 128, 128))
    args = [jnp.asarray(t, jnp.float32) for t in (q, k, v)]
    gb = jnp.broadcast_to(jnp.asarray(g, jnp.float32)[..., None],
                          (4, 2, 128))
    b = jnp.asarray(beta, jnp.float32)
    live = jnp.asarray([1, 1, 0, 1], bool)
    o, Sn = dr.kda_step_kernel(S, *args, gb, b, live, interpret=True)
    want_o, want_S = dr.kda_step_xla(S, *args, gb, b, live)
    np.testing.assert_allclose(Sn, want_S, atol=2e-6)
    np.testing.assert_allclose(o[np.asarray(live)],
                               want_o[np.asarray(live)], atol=2e-5)


@pytest.mark.parametrize("fault,least,most", [
    ((), 0.0, 2e-6),                     # float32: the same recurrence
    (("bf16_state",), 1e-3, 2e-2),       # rounded a token: 2^-9 a value
    (("no_delta",), 0.05, 10.0),
])
def test_the_references_state_after_a_chunk_and_its_tokens(fault, least,
                                                           most):
    """`reference_qwen3next.gdn_state_after` (what the benchmark's
    `slot_state` holds a slot's `state` leaf to) over the operands of a
    chunk and of eight tokens after it, against what the two serving forms
    leave."""
    q, k, v, g, beta, _ = (jnp.asarray(t, jnp.float32)
                           for t in _operands(40, H=3, d=16, seed=4,
                                              low=-0.5))
    _, S = dr.gdn_chunk(q[:32], k[:32], v[:32], g[:32], beta[:32])
    for t in range(32, 40):
        gb = jnp.broadcast_to(g[t:t + 1, :, None], (1, 3, 16))
        _, S = dr.kda_step_xla(S[None], q[t:t + 1], k[t:t + 1], v[t:t + 1],
                               gb, beta[t:t + 1])
        S = S[0]
    want = ref.gdn_state_after(q, k, v, g, beta, faults=fault)
    err = float(jnp.sqrt(jnp.mean((S - want) ** 2) / jnp.mean(want ** 2)))
    assert least <= err <= most, err


# (3) the routers, the shared gate, the shares --------------------------------

def test_the_two_routers_agree_to_rounding():
    """The published order (softmax over all, top k, renormalise) and the
    program's (`route_softmax_topk`: top k logits, softmax over those) pick
    the same experts and the same weights to float32 rounding."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (200, 64))
    gate = jax.random.normal(ks[1], (64, 512)) * 0.3
    with HI:
        idx, w = mlp_mod.route_softmax_topk(x, gate, 10)
        want_idx, want_w = ref.route(x, gate, k=10)
    assert np.array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("chips", [8, 4])
def test_the_shares_add_up_to_the_uncut_layer(mv, chips):
    """`chips` chips share a layer's 64 experts: the parts experts
    [n c, n c + n) give, added, with the gated shared expert counted once,
    are the layer with every expert held. And the program's share is the
    reference's."""
    cfg, model, variables = mv
    whole = variables["params"]["block_3"]["moe"]
    n = 64 // chips
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 19, 64))
    with HI:
        want = ref.experts_forward(h, whole, k=10, first=0)
        parts = 0.0
        for chip in range(chips):
            share = dict(whole,
                         experts_up=whole["experts_up"][n * chip:][:n],
                         experts_down=whole["experts_down"][n * chip:][:n])
            parts = parts + ref.experts_forward(
                h, share, k=10, first=n * chip, shared=chip == 0)
            held = dataclasses.replace(cfg, experts_held=(n * chip, n))
            got = mlp_mod.RoutedExperts(held).apply({"params": share}, h)[0]
            alone = ref.experts_forward(h, share, k=10, first=n * chip)
            assert _rel(got, alone) < 2e-5
    assert _rel(parts, want) < 1e-5


# (4) through the cache and the engine ---------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def _chunk_logits(model, variables, caches, buf, off, bt_row, slot, n):
    logits, _, caches = model.apply(
        variables, buf, None, caches, off, all_logits=True,
        block_tables=bt_row, state_ctx={"slot": slot, "valid_len": n})
    return logits, _strip(model.config, caches)


@functools.partial(jax.jit, static_argnums=0)
def _token_logits(model, variables, caches, tok, pos, bt, live):
    logits, _, caches = model.apply(
        variables, tok[:, None], None, caches, pos, block_tables=bt,
        state_ctx={"live": live})
    return logits, _strip(model.config, caches)


def _teacher_forced(model, variables, cfg, seq, lens, chunk, slot, caches,
                    bt):
    """Prefill `lens` ids in chunks of `chunk` rows into `slot`, then one
    token at a time beside dead slots: every position's logits."""
    rows = []
    for off in range(0, lens, chunk):
        n = min(chunk, lens - off)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :n] = seq[off:off + n]
        logits, caches = _chunk_logits(
            model, variables, caches, jnp.asarray(buf), jnp.int32(off),
            bt[slot:slot + 1], jnp.int32(slot), jnp.asarray([n], jnp.int32))
        rows.extend(np.asarray(logits[0, :n]))
    for i in range(lens, len(seq)):
        tok = np.zeros(bt.shape[0], np.int32)
        pos = np.zeros(bt.shape[0], np.int32)
        live = np.zeros(bt.shape[0], bool)
        tok[slot], pos[slot], live[slot] = seq[i], i, True
        logits, caches = _token_logits(
            model, variables, caches, jnp.asarray(tok), jnp.asarray(pos),
            bt, jnp.asarray(live))
        rows.append(np.asarray(logits[slot, -1]))
    return np.stack(rows), caches


def _house():
    bt = np.zeros((2, 16 + 2), np.int32)
    bt[1, :16] = 1 + np.arange(16)
    return jnp.asarray(bt)


def test_cache_path_across_chunks_and_a_used_slot(mv):
    """Chunks of 16: a prompt of 45 (three chunks, the last part-filled,
    the second starting mid-sequence from the slot's state), 30 tokens
    decoded behind it, then a shorter sequence into the SAME slot, whose
    state, tail and blocks still hold the first one's: every position's
    logits against the reference's full forward pass."""
    cfg, model, variables = mv
    caches = init_paged_cache(cfg, 1 + 16, 8, dtype=jnp.float32, n_slots=2)
    bt = _house()
    paths.reset()
    for lens, total, seed in ((45, 75, 7), (13, 40, 8)):
        seq = np.asarray(_prompts((total,), seed=seed)[0])
        with HI:
            got, caches = _teacher_forced(model, variables, cfg, seq, lens,
                                          16, 1, caches, bt)
            want = ref.forward_logits(variables["params"], LLM_KW,
                                      jnp.asarray(seq[None]))[0]
        assert _rel(got, want) < 3e-5, lens
    # the dead slot's leaves never moved
    assert not np.asarray(caches[0]["state"][0]).any() \
        and not np.asarray(caches[0]["tail"][0]).any()
    # no option picks a path: the notes say which ran
    chosen = paths.choices()
    assert chosen["kda_step"] == ("xla (kda_step_kernel_decline: the cpu "
                                  "backend is no TPU)")
    assert chosen["gdn_chunk"].startswith("xla_wy (a decay a head")


def test_a_reused_slot_whose_state_is_not_reset_fails(mv, monkeypatch):
    """The fault of the PROGRAM the cell's `cache_path` is there for."""
    from distributed_pytorch_tpu.models import linear_attention as la
    cfg, model, variables = mv
    monkeypatch.setattr(
        la, "chunk_start",
        lambda leaf, slot, pos: jax.lax.dynamic_index_in_dim(leaf, slot, 0))
    jax.clear_caches()
    caches = init_paged_cache(cfg, 1 + 16, 8, dtype=jnp.float32, n_slots=2)
    bt = _house()
    errs = []
    for lens, total, seed in ((45, 60, 7), (13, 30, 8)):
        seq = np.asarray(_prompts((total,), seed=seed)[0])
        with HI:
            got, caches = _teacher_forced(model, variables, cfg, seq, lens,
                                          16, 1, caches, bt)
            want = ref.forward_logits(variables["params"], LLM_KW,
                                      jnp.asarray(seq[None]))[0]
        errs.append(_rel(got, want))
    jax.clear_caches()
    assert errs[0] < 3e-5 < 1e-2 < errs[1], errs


def test_the_engine_against_the_references_logits(mv):
    """Prefill in several chunks, then decode, through the engine's own
    programs (chunks beside decoding slots, slots reused): every emitted
    token is the argmax of the reference's LOGITS on the sequence so far,
    and leads the runner-up there; the counters of both kinds of mixer,
    booked from the plan; what stands down for per-slot state, aloud."""
    cfg, model, variables = mv
    eng = DecodeEngine(model, variables, n_slots=3, max_len=128,
                       block_size=8, prefill_chunk=16, temperature=0.0,
                       min_bucket=8, prefix_cache=True)
    assert eng.features_declined == ["prefix_cache"]
    prompts = _prompts((5, 37, 50, 23, 41), seed=11)
    with HI:
        outs = eng.run(prompts, 30)
        for prompt, full in zip(prompts, outs):
            logits = np.asarray(ref.forward_logits(
                variables["params"], LLM_KW,
                jnp.asarray([full[:-1]], jnp.int32), last=30)[0])
            new = np.asarray(full[len(prompt):])
            assert np.array_equal(logits.argmax(-1), new)
    # three 'G' layers: a chunk's real rows, and 29 decode steps a sequence
    assert eng.kda_slot_steps_by == {"chunk": 3 * sum(map(len, prompts)),
                                     "decode": 3 * 5 * 29}
    assert eng.state_resets == 5
    # the one '*' layer's rows and pairs are planned beside 'G' layers
    decode_rows = sum(n + i for n in map(len, prompts) for i in range(1, 30))
    assert eng.kv_rows_read_full_by["decode"] == decode_rows
    assert eng.chunk_attn_pairs_by["full"] == sum(
        n * (n + 1) // 2 for n in map(len, prompts))
    by = eng.resident_bytes_by_kind
    assert by["slot_state"] == 3 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4) \
        and by["pools"] == 2 * eng.n_blocks * 8 * 128 * 4


# (5) defaults leave the accepted programs as they were ----------------------

#: sha256 (16 hex) of the lowered text of small models of the accepted
#: kinds of block, made on the PARENT tree (PR 66) by this file's
#: `_lowered`: every field this PR adds is at its default in them. The three
#: `ling_like` texts were made again on PR 69's tree, whose group limit
#: (`n_group` 2 here) selects without `top_k`: with `limit_to_groups` of PR
#: 66 put back that tree gave PR 66's three (7d7b35bd601bd09a,
#: 3476b005a442bf81, 4ec9f97d6f8ea5bb), so nothing else of them had moved
PARENT_TEXTS = {
    "laguna_like.forward": "8afa019ee2e5069a",
    "laguna_like.decode": "d152329ca265798f",
    "laguna_like.chunk": "aa4aef09bcc8dee5",
    "granite_like.forward": "a92bf88b305ef00f",
    "granite_like.decode": "b336ca5d61385469",
    "granite_like.chunk": "1b55ded1385132a4",
    "ling_like.forward": "27f109c3a1c11cef",
    "ling_like.decode": "8f0d0044502b5fa0",
    "ling_like.chunk": "167a3b2a966e22bf",
    "kda_chunk": "6e7b7564554c08a5",
}
ACCEPTED_KINDS = {
    # '*' with a gate a HEAD, QK-norm, partial rotation, 'W', sigmoid experts
    "laguna_like": dict(
        vocab_size=64, n_embd=32, n_layer=4, layer_pattern="*EWF",
        attn="gqa", n_head=4, n_kv_heads=2, head_dim=8, attn_gate=True,
        attn_bias=False, qk_norm=True, pos_emb="rope", rope_pairing="half",
        rotary_frac=0.5, window=8, window_heads=4, n_exp=9, n_shared=1,
        n_act=3, router="sigmoid", up_dim=16, dense_up_dim=32,
        non_linearity="swiglu", tie_head=False),
    # 'M', '*' without positions, softmax-routed experts, a shared expert
    "granite_like": dict(
        vocab_size=64, n_embd=32, n_layer=4, layer_pattern="ME*E",
        attn="gqa", n_head=4, n_kv_heads=2, pos_emb="none", ssm_heads=4,
        ssm_head_dim=8, ssm_state=8, n_exp=9, n_shared=1, n_act=3,
        router="softmax_topk", up_dim=16, shared_up_dim=24,
        non_linearity="swiglu", attn_bias=False),
    # 'K' (ops/delta_rule.py), 'L', the group-limited router
    "ling_like": dict(
        vocab_size=64, n_embd=32, n_layer=4, layer_pattern="KFLE",
        attn="mla", n_head=4, q_latent_dim=0, kv_latent_dim=16,
        rope_head_dim=8, qk_nope_head_dim=8, v_head_dim=8, pos_emb="rope",
        attn_bias=False, kda_heads=2, kda_head_dim=8, n_exp=9, n_shared=1,
        n_act=3, router="sigmoid", n_group=2, topk_group=1, up_dim=16,
        dense_up_dim=32, non_linearity="swiglu", tie_head=False),
}


def _sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _lowered(kw: dict) -> dict:
    """The three ways into a small model, lowered from shapes alone."""
    cfg = LLMConfig(**kw)
    model = LLM(cfg)
    shapes = jax.eval_shape(model.init, {"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32))
    caches = jax.eval_shape(lambda: init_paged_cache(
        cfg, 9, 8, dtype=jnp.float32, n_slots=2))
    bt = jnp.zeros((2, 4), jnp.int32)
    i32 = jnp.int32

    def step(v, c, tok, pos, live):
        return model.apply(v, tok[:, None], None, c, pos, block_tables=bt,
                           state_ctx={"live": live})[0]

    def chunk(v, c, buf, off, n):
        return model.apply(v, buf, None, c, off, all_logits=True,
                           block_tables=bt[:1],
                           state_ctx={"slot": i32(1), "valid_len": n})[0]

    return {
        "forward": _sha(jax.jit(lambda v, i: model.apply(v, i)[0]).lower(
            shapes, jnp.zeros((2, 12), i32))),
        "decode": _sha(jax.jit(step).lower(
            shapes, caches, jnp.zeros((2,), i32), jnp.zeros((2,), i32),
            jnp.ones((2,), bool))),
        "chunk": _sha(jax.jit(chunk).lower(
            shapes, caches, jnp.zeros((1, 8), i32), i32(0),
            jnp.ones((1,), i32)))}


@pytest.mark.parametrize("name", sorted(ACCEPTED_KINDS))
def test_defaults_lower_the_accepted_blocks_to_the_parents(name):
    """`GQA`, `RoutedExperts`, the norm and the model's walk gained a
    branch each: with the new fields at their defaults the programs' text
    is the parent's, byte for byte."""
    got = _lowered(ACCEPTED_KINDS[name])
    assert {f"{name}.{k}": v for k, v in got.items()} == {
        k: v for k, v in PARENT_TEXTS.items() if k.startswith(name + ".")}


def test_the_kda_chunk_form_lowers_to_the_parents():
    """`kda_chunk`'s last lines moved into a function the two chunk forms
    share: the same text."""
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32) for s in (
        (40, 2, 8), (40, 2, 8), (40, 2, 8), (40, 2, 8), (40, 2), (2, 8, 8))]
    assert _sha(jax.jit(dr.kda_chunk).lower(*args)) == \
        PARENT_TEXTS["kda_chunk"]


# (6) what a configuration may and may not say -------------------------------

@pytest.mark.parametrize("change,told", [
    (dict(gdn_heads=0), "a 'G' layer needs"),
    (dict(gdn_key_heads=3), "is no multiple of gdn_key_heads"),
    (dict(gdn_conv=1), "a 'G' layer needs"),
    (dict(attn_gate="lane"), "attn_gate 'lane' is none of"),
    (dict(layer_pattern="GEGEGEGE"), "`attn_gate` without a GQA layer"),
    (dict(n_shared=0, n_exp=64, n_act=10), "n_shared is 0"),
    (dict(layer_pattern="G*G*G*G*"), "`shared_gate` without an 'E' layer"),
    (dict(layer_pattern="*E*E*E*E"), "`gdn_\\*` widths without a 'G' layer"),
    (dict(layer_pattern="", n_layer=8), "a patterned model's"),
])
def test_an_inconsistent_configuration_is_refused(change, told):
    with pytest.raises(AssertionError, match=told):
        LLMConfig(**{**LLM_KW, **change})


def test_a_gate_a_head_is_still_said_with_true():
    """`attn_gate` True keeps meaning a gate a head from `c_gate`."""
    cfg = LLMConfig(**{**LLM_KW, "attn_gate": True})
    assert cfg.attn_gate_kind == "head"
    assert LLMConfig(**{**LLM_KW, "attn_gate": "head"}).attn_gate_kind == \
        "head" and LLMConfig(**{**LLM_KW, "attn_gate": False}
                             ).attn_gate_kind == ""
    shapes = jax.eval_shape(LLM(cfg).init, {"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32))["params"]
    attn = shapes["block_6"]["attn"]
    assert attn["c_gate"]["kernel"].shape == (64, 8) \
        and attn["c_attn"]["kernel"].shape == (64, 8 * 32 + 2 * 32)


def test_the_scopes_and_the_module_are_in_the_tables():
    from distributed_pytorch_tpu.obs.trace import MIXER_MODULES, MIXER_SCOPES
    assert "gdn" in MIXER_MODULES
    assert {"gdn_proj", "gdn_conv", "gdn_gate", "attn_gdn", "gdn_chunk",
            "gdn_out", "attn_gate", "moe_shared"} <= set(MIXER_SCOPES)
    assert "channel" in MIXER_SCOPES["attn_gate"] \
        and "shared_gate" in MIXER_SCOPES["moe_shared"]
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32)
    text = jax.jit(lambda v, i: model.apply(v, i)[0]).lower(
        jax.eval_shape(model.init, {"params": jax.random.PRNGKey(0)},
                       jnp.zeros((1, 8), jnp.int32)),
        jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    for scope in ("gdn_proj", "gdn_conv", "gdn_gate", "attn_gdn", "gdn_out",
                  "attn_gate", "moe_shared"):
        assert f"/{scope}/" in text or f"{scope}\"" in text, scope


def test_the_counters_are_the_one_records(mv):
    """The 'G' layers are booked by the engine's one slot-step counter, in
    `EngineCounts` and nowhere else."""
    from distributed_pytorch_tpu.engine import counts
    cfg, _, _ = mv
    caches = init_paged_cache(cfg, 9, 8, dtype=jnp.float32, n_slots=2)
    c = counts.EngineCounts(cfg, caches, 2, 16)
    assert c.n_kda == 3 and c.n_full == 1 and c.plans_kv_rows
    assert {"kda_slot_steps", "kda_slot_steps_by"} <= {
        r.name for r in counts.READINGS}


def test_the_files_parameters_are_the_trees_count():
    """The configuration file's `parameters` against the tree the program
    builds at the published widths (shapes alone: nothing is allocated)."""
    from benchmark.lib import harness
    conf = harness.resolve_cell(harness.load_benchmark(),
                                "qwen3next_serve_closed32_32k")["config"]
    llm = conf["llm_config"]
    model = LLM(LLMConfig(**llm), compute_dtype=jnp.bfloat16,
                param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, {"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32))["params"]
    total = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert total == flops_qwen3next.total_params(llm) == 2929374400
    assert f"{total:,}" in conf["parameters"]
    count = lambda t: sum(int(np.prod(a.shape))  # noqa: E731
                          for a in jax.tree_util.tree_leaves(t))
    assert count(shapes["block_0"]["gdn"]) == sum(
        flops_qwen3next.gdn_params(llm).values()) == 33718464
    assert count(shapes["block_6"]["attn"]) == sum(
        flops_qwen3next.attn_params(llm).values()) == 27263488
    assert count(shapes["block_1"]) == flops_qwen3next.layer_params(
        llm, "E") == 205522944 + 2048
    # every published width, and the cut
    assert (llm["n_embd"], llm["gdn_key_heads"], llm["gdn_heads"],
            llm["gdn_head_dim"], llm["gdn_conv"]) == (2048, 16, 32, 128, 4)
    assert (llm["n_head"], llm["n_kv_heads"], llm["head_dim"],
            llm["rotary_frac"] * llm["head_dim"]) == (16, 2, 256, 64)
    assert (llm["up_dim"], llm["n_exp"] - llm["n_shared"],
            llm["n_act"] - llm["n_shared"], llm["experts_held"]) == \
        (512, 512, 10, [0, 64])
    assert llm["layer_pattern"] == "GEGEGE*E" * 3 \
        and llm["vocab_size"] == 18992
    assert json.loads(json.dumps(llm)) == llm
