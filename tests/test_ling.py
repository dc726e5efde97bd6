"""Delta-rule linear attention (KDA) as a patterned model's 'K' layer beside
latent attention without a query latent ('L') in ONE cache tree, in front of
group-limited sigmoid experts, at a small size on the CPU: every width a
stand-in, every RATIO of the published model kept (5 K : 1 L, d_k = d_v, 8
groups of which the top 4 are kept, top 8, one group held). (a) the model's
and the engine's logits, prefill in chunks and then decode, into a USED slot,
against `benchmark/lib/reference_ling.py` (float32, the literal recurrence,
no cache); (b) the chunked form and the one-token form against the literal
recurrence; (c) the step kernel in interpret mode against its XLA twin, with
a dead slot; (d) every branch of each decline function and the paths line;
(e) the group-limited choice against a literal loop over groups; (f) the
eight shares add up to the uncut layer; (g) latent attention with no query
latent, and the tree with both kinds of leaf; (h) the tree's count."""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops_ling
from benchmark.lib import reference_ling as ref
from distributed_pytorch_tpu.config import LAYER_KEEPS, LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import mlp as mlp_mod
from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops import delta_rule as dr

LLM_KW = dict(
    vocab_size=256, block_size=1 << 17, n_embd=64, n_layer=14,
    layer_pattern="KFKEKEKELEKEKE", pos_emb="rope", rope_theta=6e6,
    rope_pairing="adjacent", norm_eps=1e-6, tie_head=False, attn="mla",
    n_head=4, q_latent_dim=0, kv_latent_dim=32, rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=24, dense_up_dim=96, shared_up_dim=24,
    n_exp=65, n_shared=1, n_act=9, router="sigmoid", routed_scale=2.5,
    n_group=8, topk_group=4,
    kda_heads=4, kda_head_dim=16, kda_conv=4, kda_lower_bound=-5.0)
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
    return float(np.sqrt((d * d).mean() / (np.asarray(want) ** 2).mean()))


def _strip(cfg, caches):
    return [None if k == "E" else c
            for k, c in zip(cfg.layer_pattern, caches)]


# (1) the tree, the whole forward pass, what each term is worth -------------

def test_the_tree_is_the_published_one(mv):
    cfg, model, variables = mv
    p = variables["params"]
    assert LAYER_KEEPS["K"] == ("slot_state",) and cfg.recurrent \
        and cfg.slot_state == "recurrent layers" \
        and cfg.layers_keeping("slot_state") == 6 \
        and cfg.layers_keeping("pools") == 1
    kda = {k: v.shape for k, v in p["block_0"]["kda"].items()}
    assert kda == {"W_qkv": (64, 192), "W_a": (64, 64), "W_bg": (64, 8),
                   "conv_w": (4, 192), "A_log": (4,), "dt_bias": (64,),
                   "o_norm": (16,), "W_o": (64, 64)}
    assert p["block_0"]["kda"]["A_log"].dtype == jnp.float32
    lat = {k: v.shape for k, v in p["block_8"]["latent_attn"].items()}
    assert lat == {"W_q": (64, 4 * 24), "W_kva": (64, 40),
                   "kv_norm": (32,), "W_kvb": (32, 4 * 32), "W_o": (64, 64)}
    assert p["block_3"]["moe"]["gate"].shape == (64, 64)
    total = sum(int(a.size) for a in jax.tree_util.tree_leaves(p))
    assert total == flops_ling.total_params(LLM_KW) + 6 * 64   # + the biases


def test_one_cache_tree_holds_both_kinds_of_leaf(mv):
    cfg, _, _ = mv
    caches = init_paged_cache(cfg, 9, 8, dtype=jnp.bfloat16, n_slots=3)
    assert [c is None for c in caches] == [k in "FE" for k in
                                           cfg.layer_pattern]
    assert caches[0]["state"].shape == (3, 4, 16, 16) \
        and caches[0]["state"].dtype == jnp.float32
    assert caches[0]["tail"].shape == (3, 3, 192) \
        and caches[0]["tail"].dtype == jnp.bfloat16
    assert caches[8].shape == (9, 8, 128)            # the latent pool
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


def test_latent_attention_without_a_query_latent(mv):
    """`q_latent_dim` 0: q = h W_q, no latent and no norm; the layer alone
    against the reference's."""
    from distributed_pytorch_tpu.models.attention import LatentAttention
    cfg, _, variables = mv
    p = variables["params"]["block_8"]["latent_attn"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 37, 64))
    with HI:
        got, _ = LatentAttention(cfg).apply({"params": p}, h)
        want = ref.mixer_forward(LLM_KW, "L",
                                 variables["params"]["block_8"], h)
    assert _rel(got, want) < 1e-5


# (2) the two forms against the literal recurrence ---------------------------

def _operands(T, H=2, d=16, seed=0, at_bound=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, d)))
    v = jax.random.normal(ks[2], (T, H, d))
    g = -5.0 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], (T, H, d))
                              - 2)
    if at_bound:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    S0 = jax.random.normal(ks[5], (H, d, d))
    return q, k, v, g, beta, S0


def _literal(q, k, v, g, beta, S0):
    """Step 6 of the issue in numpy float64, a position at a time."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    S = np.zeros(S0.shape) if S0 is None else np.asarray(S0, np.float64)
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[..., None] * S
        u = v[t] - np.einsum("hkv,hk->hv", S, k[t])
        S = S + beta[t][:, None, None] * k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


@pytest.mark.parametrize("T,C,start,at_bound", [
    (64, 16, True, False),      # whole sub-chunks from a nonzero state
    (50, 16, True, False),      # a partial last sub-chunk
    (37, 8, False, False),      # from zeros, another sub-chunk size
    (23, 4, True, False),
    (9, 16, True, False),       # fewer rows than a sub-chunk
    (48, 16, True, True),       # the gate at its bound -5 a whole chunk
    (32, 32, True, False),      # one sub-chunk of 32: 16 x 5 a side
])
def test_chunked_form_is_the_literal_recurrence(monkeypatch, T, C, start,
                                                at_bound):
    monkeypatch.setattr(dr, "SUB_CHUNK", C)
    q, k, v, g, beta, S0 = _operands(T, seed=T, at_bound=at_bound)
    S0 = S0 if start else None
    want_o, want_S = _literal(q, k, v, g, beta, np.zeros((2, 16, 16))
                              if S0 is None else S0)
    o, S = dr.kda_chunk(q, k, v, g, beta, S0)
    assert _rel(o, want_o) < 2e-6 and _rel(S, want_S) < 2e-6
    o, S = dr.kda_scan(q, k, v, g, beta, S0)
    assert _rel(o, want_o) < 2e-6 and _rel(S, want_S) < 2e-6


def test_pad_rows_move_nothing():
    """g = 0 and beta = 0 on a chunk's pad rows: the state at the chunk's
    end is the state after its real rows."""
    q, k, v, g, beta, S0 = _operands(32, seed=5)
    real = (jnp.arange(32) < 19)
    _, want = dr.kda_chunk(q[:19], k[:19], v[:19], g[:19], beta[:19], S0)
    _, got = dr.kda_chunk(q, k, v, jnp.where(real[:, None, None], g, 0.0),
                          jnp.where(real[:, None], beta, 0.0), S0)
    assert _rel(got, want) < 1e-6


@pytest.mark.parametrize("live", [None, (1, 0, 1, 1, 0)])
def test_one_token_form_is_the_literal_recurrence(live):
    q, k, v, g, beta, _ = _operands(5, H=3, d=8, seed=2)     # 5 slots
    S = jax.random.normal(jax.random.PRNGKey(9), (5, 3, 8, 8))
    mask = None if live is None else jnp.asarray(live, bool)
    o, Sn = dr.kda_step_xla(S, q, k, v, g, beta, mask)
    for s in range(5):
        want_o, want_S = _literal(q[s:s + 1], k[s:s + 1], v[s:s + 1],
                                  g[s:s + 1], beta[s:s + 1], S[s])
        if live is None or live[s]:
            assert _rel(o[s], want_o[0]) < 1e-6 \
                and _rel(Sn[s], want_S) < 1e-6
        else:
            assert np.array_equal(Sn[s], S[s])


# (3) the step kernel in interpret mode against its twin ---------------------

def _a_slot(H, d):
    return H * d * d * 4


#: the interpreter that runs a DMA at its WAIT, the latest the kernel allows
#: it, and watches for races: a buffer of the ring read before its read was
#: waited for, or refilled before its write was, reads stale rows there
_LATE = dict(dma_execution_mode="on_wait", detect_races=True)


@pytest.mark.parametrize("n,H,d,phase_bytes,live,late", [
    (6, 2, 8, 16 << 20, (1, 1, 0, 1, 1, 1), False),   # one phase, a dead slot
    (8, 2, 8, 2 * _a_slot(2, 8), (1, 0, 1, 1, 1, 1, 0, 1), False),  # 4 of 2
    (5, 3, 16, _a_slot(3, 16), None, False),     # a slot a phase, all live
    (4, 1, 8, 16 << 20, (0, 0, 0, 0), False),    # nothing live
    # the ring's edges: 1, 2, 3 and 5 phases
    (4, 2, 8, 16 << 20, None, True),                          # 1 phase
    (8, 2, 8, 4 * _a_slot(2, 8), None, True),                 # 2 phases
    (12, 2, 8, 4 * _a_slot(2, 8), None, True),                # 3 phases
    (10, 2, 8, 2 * _a_slot(2, 8), None, True),                # 5 phases
    # one slot a phase (the first half of a phase is empty): 1, 2, 3 phases
    (1, 2, 8, _a_slot(2, 8), None, True),
    (2, 2, 8, _a_slot(2, 8), (0, 1), True),
    (3, 2, 8, _a_slot(2, 8), (1, 0, 1), True),
    # an odd count of slots a phase: 3 phases of 5, 2 phases of 3
    (15, 1, 8, 5 * _a_slot(1, 8), None, True),
    (6, 2, 8, 3 * _a_slot(2, 8), (1, 0, 1, 0, 1, 1), False),
    # dead slots in the first half of every phase, in the second half, a
    # whole dead phase in the middle, at the start and at the end
    (12, 2, 8, 4 * _a_slot(2, 8), (0, 0, 1, 1) * 3, True),
    (12, 2, 8, 4 * _a_slot(2, 8), (1, 1, 0, 0) * 3, True),
    (12, 2, 8, 4 * _a_slot(2, 8), (1,) * 4 + (0,) * 4 + (1,) * 4, True),
    (10, 2, 8, 2 * _a_slot(2, 8), (0, 0) + (1,) * 8, False),
    (10, 2, 8, 2 * _a_slot(2, 8), (1,) * 8 + (0, 0), True),
])
def test_step_kernel_against_its_twin(monkeypatch, n, H, d, phase_bytes,
                                      live, late):
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(dr, "_PHASE_BYTES", phase_bytes)
    jax.clear_caches()
    q, k, v, g, beta, _ = _operands(n, H=H, d=d, seed=n)
    S = jax.random.normal(jax.random.PRNGKey(n), (n, H, d, d))
    mask = None if live is None else jnp.asarray(live, bool)
    assert dr.kda_step_kernel_decline(S, interpret=True) is None
    want_o, want_S = dr.kda_step_xla(S, q, k, v, g, beta, mask)
    o, Sn = dr.kda_step_kernel(
        S, q, k, v, g, beta, mask,
        interpret=pltpu.InterpretParams(**_LATE) if late else True)
    alive = np.ones(n, bool) if live is None else np.asarray(live, bool)
    np.testing.assert_allclose(np.asarray(o)[alive],
                               np.asarray(want_o)[alive], atol=2e-6)
    np.testing.assert_allclose(Sn, want_S, atol=2e-6)
    assert np.array_equal(np.asarray(Sn)[~alive], np.asarray(S)[~alive])
    assert not np.asarray(o)[~alive].any()        # a dead slot's o is zeros
    if late:
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
        assert not interpret_pallas_call.races.races_found
    jax.clear_caches()


def test_a_phase_of_the_step_kernel_and_the_rings_bytes():
    """16 MB of whole slots that divide the call, and THREE such buffers in
    the gate's count: at the published shape 8 slots a phase and 49.5 MiB,
    inside the 64 MiB scoped limit."""
    from distributed_pytorch_tpu.compat import VMEM_LIMIT_BYTES
    assert dr._PHASE_BYTES == 16 << 20 and dr._RING == 3
    assert dr._step_slots(192, 32, 128, 128) == 8
    assert dr._step_slots(6, 32, 128, 128) == 6
    assert dr._step_slots(7, 32, 128, 128) == 7
    assert dr._step_slots(18, 32, 128, 128) == 6
    assert dr._step_slots(5, 64, 256, 256) == 1       # one at least
    state = 8 * 32 * 128 * 128 * 4
    blocks = 2 * 4 * 8 * (128 * 128 + 2 * 32 * 128)   # columns, v, o: twice
    assert dr._step_vmem_bytes(8, 32, 128, 128) == 3 * state + blocks \
        == 51_904_512 < VMEM_LIMIT_BYTES


# (4) the gates and the paths line -------------------------------------------

@pytest.mark.parametrize("change,told", [
    (dict(backend=True), "the cpu backend is no TPU"),
    (dict(dtype=jnp.bfloat16), "is not float32 (S, H, d_k, d_v)"),
    (dict(shape=(4, 2, 8 * 8)), "is not float32 (S, H, d_k, d_v)"),
    (dict(shape=(4, 2, 12, 8)), "no whole tiles of 8 x 8"),
    (dict(shape=(4, 2, 8, 12)), "no whole tiles of 8 x 8"),
    (dict(lane128=True), "no whole tiles of 8 x 128"),
    (dict(shape=(2, 128, 256, 256)), "VMEM"),
    # 24 MiB a slot: two buffers would fit the 64 MiB, the ring's three do not
    (dict(shape=(2, 96, 256, 256)), "needs 73 MiB of VMEM"),
    (dict(shape=(2, 64, 256, 256)), None),           # 16 MiB a slot: 3 fit
    (dict(), None),
])
def test_step_gate(monkeypatch, change, told):
    S = jax.ShapeDtypeStruct(change.get("shape", (4, 2, 8, 8)),
                             change.get("dtype", jnp.float32))
    if change.get("lane128"):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    why = dr.kda_step_kernel_decline(
        S, interpret=not (change.get("backend") or change.get("lane128")))
    assert (why is None) if told is None else (told in why), why


def test_a_mesh_declines_the_step_kernel(monkeypatch):
    from distributed_pytorch_tpu.parallel import context

    class TwoChips:
        devices = np.zeros((2, 1))
    monkeypatch.setattr(context, "get_mesh", lambda: TwoChips)
    assert "multi-device mesh" in dr.kda_step_kernel_decline(
        jax.ShapeDtypeStruct((4, 2, 8, 8), jnp.float32), interpret=True)


@pytest.mark.parametrize("fault,least,most", [
    ((), 0.0, 2e-6),                     # float32: the same recurrence
    (("bf16_state",), 1e-3, 1e-2),       # rounded a token: 2^-9 a value
    (("no_delta",), 0.05, 10.0),
])
def test_the_references_state_after_a_chunk_and_its_tokens(fault, least,
                                                           most):
    """`reference_ling.kda_state_after` (what the benchmark's `slot_state`
    holds a slot's `state` leaf to) over the operands of a chunk and of
    eight tokens after it, against what the two serving forms leave: the
    same state to float32 rounding, and a state kept in bfloat16 or a
    recurrence that takes nothing back apart from it."""
    q, k, v, g, beta, _ = _operands(40, H=3, d=16, seed=4)
    _, S = dr.kda_chunk(q[:32], k[:32], v[:32], g[:32], beta[:32])
    for t in range(32, 40):
        _, S = dr.kda_step_xla(S[None], q[t:t + 1], k[t:t + 1], v[t:t + 1],
                               g[t:t + 1], beta[t:t + 1])
        S = S[0]
    want = ref.kda_state_after(q, k, v, g, beta, faults=fault)
    err = float(jnp.sqrt(jnp.mean((S - want) ** 2) / jnp.mean(want ** 2)))
    assert least <= err <= most, err


def test_the_paths_line_says_which_ran(mv):
    """No option picks a path: the gates choose from shapes and device."""
    cfg, model, variables = mv
    caches = init_paged_cache(cfg, 1 + 8, 8, dtype=jnp.float32, n_slots=1)
    bt = jnp.asarray(np.concatenate([1 + np.arange(8), [0, 0]])[None],
                     jnp.int32)
    seq = np.asarray(_prompts((24,), seed=9)[0])
    jax.clear_caches()
    paths.reset()
    with HI:
        got, caches = _teacher_forced(model, variables, cfg, seq, 16, 16, 0,
                                      caches, bt)
        want = ref.forward_logits(variables["params"], LLM_KW,
                                  jnp.asarray(seq[None]))[0]
    chosen = paths.choices()
    assert chosen["kda_step"] == ("xla (kda_step_kernel_decline: the cpu "
                                  "backend is no TPU)")
    assert chosen["kda_chunk"] == ("xla_wy (forward substitution in "
                                   "sub-chunks of 16 rows)")
    assert _rel(got, want) < 3e-5


def test_the_kernels_note_names_the_phase_and_the_ring(monkeypatch):
    """Where the gate lets a call through, the note says the phase AND the
    ring: the record of which schedule a program's six calls ran."""
    monkeypatch.setattr(dr, "_PHASE_BYTES", 2 * _a_slot(2, 128))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dr, "kda_step_kernel",
                        functools.partial(dr.kda_step_kernel, interpret=True))
    jax.clear_caches()
    q, k, v, g, beta, _ = _operands(6, H=2, d=128, seed=1)
    S = jax.random.normal(jax.random.PRNGKey(2), (6, 2, 128, 128))
    paths.reset()
    o, Sn = dr.kda_step(S, q, k, v, g, beta)
    assert paths.choices()["kda_step"] == \
        "kda_state_step (state in place, 2 slots a phase, a ring of 3)"
    np.testing.assert_allclose(Sn, dr.kda_step_xla(S, q, k, v, g, beta)[1],
                               atol=2e-6)
    jax.clear_caches()


# (5) the group-limited choice -----------------------------------------------

def _route_as_it_was(scores_in, gate, bias, k, scale):
    """`route_sigmoid` of the parent commit, line for line."""
    s = jax.nn.sigmoid(jnp.dot(scores_in.astype(jnp.float32),
                               gate.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=1)
    return idx, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * scale


def _router(seed=0, N=50, C=32, E=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (N, C)),
            jax.random.normal(ks[1], (C, E)) * 0.3,
            jax.random.normal(ks[2], (E,)) * 0.1)


def test_one_group_is_the_router_as_it_was_bit_for_bit():
    x, gate, bias = _router()
    a = jax.jit(lambda *t: mlp_mod.route_sigmoid(*t, 8, 2.5))(x, gate, bias)
    b = jax.jit(lambda *t: _route_as_it_was(*t, 8, 2.5))(x, gate, bias)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    # and the program holds the same ops: no group code where n_group is 1
    def ops(fn):
        text = jax.jit(lambda *t: fn(*t, 8, 2.5)).lower(x, gate,
                                                         bias).as_text()
        return [ln.split("=", 1)[1].split("loc(")[0] for ln in
                text.splitlines() if " = stablehlo." in ln]
    assert ops(mlp_mod.route_sigmoid) == ops(_route_as_it_was)


@pytest.mark.parametrize("n_group,topk_group,k", [(8, 4, 8), (4, 1, 3),
                                                  (2, 2, 8)])
def test_group_limit_against_a_literal_loop(n_group, topk_group, k):
    x, gate, bias = _router(seed=n_group)
    idx, w = mlp_mod.route_sigmoid(x, gate, bias, k, 2.5, n_group,
                                   topk_group)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, gate, precision=jax.lax.Precision.HIGHEST)), np.float64)
    sb = s + np.asarray(bias, np.float64)
    size = 64 // n_group
    for n in range(x.shape[0]):
        score = [np.sort(sb[n, g * size:(g + 1) * size])[-2:].sum()
                 for g in range(n_group)]
        kept = np.argsort(score)[::-1][:topk_group]
        allowed = [e for e in range(64) if e // size in kept]
        want = sorted(allowed, key=lambda e: -sb[n, e])[:k]
        assert sorted(np.asarray(idx[n]).tolist()) == sorted(want)
        chosen = s[n, np.asarray(idx[n])]
        np.testing.assert_allclose(w[n], chosen / chosen.sum() * 2.5,
                                   rtol=1e-5)
    # and the reference's, written group by group
    ridx, rw = ref.route(x, gate, bias, k=k, scale=2.5, n_group=n_group,
                         topk_group=topk_group)
    assert np.array_equal(np.sort(idx, 1), np.sort(ridx, 1))
    np.testing.assert_allclose(np.sort(w, 1), np.sort(rw, 1), rtol=1e-5)


def test_a_tie_between_groups_goes_to_the_lower_group():
    """Two groups with the same two best scores: the lower id is kept, in
    the program and in the reference alike."""
    biased = jnp.asarray([[.9, .8, .1, .1, .9, .8, .2, .2, .5, .4, .0, .0,
                           .3, .3, .3, .3]])          # 4 groups of 4
    kept = mlp_mod.limit_to_groups(biased, 4, 1)
    assert np.isfinite(np.asarray(kept[0, :4])).all() \
        and np.isneginf(np.asarray(kept[0, 4:])).all()
    kept = mlp_mod.limit_to_groups(biased, 4, 2)
    assert np.isfinite(np.asarray(kept[0, :8])).all() \
        and np.isneginf(np.asarray(kept[0, 8:])).all()


def _limit_by_top_k(biased, n_group, topk_group):
    """`limit_to_groups` as it was until PR 69, line for line: the two
    largest of a group and the best groups by `top_k`, which sorts."""
    N, E = biased.shape
    by_group = biased.reshape(N, n_group, E // n_group)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(score, topk_group)               # (N, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(N, E)


@pytest.mark.parametrize("levels", [(None, None, None), (1, 2, 4)],
                         ids=["random", "few_levels"])
@pytest.mark.parametrize("rows", [192, 256])
@pytest.mark.parametrize("n_group,topk_group,E", [
    (8, 4, 512), (4, 1, 64), (2, 2, 64), (8, 8, 512), (8, 1, 512)])
def test_the_group_limit_is_the_top_k_forms_element_for_element(
        n_group, topk_group, E, rows, levels):
    """Maxima and a rank count against the sorts they replaced, on the
    whole masked array. `few_levels` quantises s + b to 2, 3 and 5 values,
    so a group's maximum stands twice or more, whole groups are equal
    lanes and groups' scores tie: every tie rule is on the path."""
    rng = np.random.default_rng(n_group * 1000 + topk_group * 10 + rows)
    new = jax.jit(mlp_mod.limit_to_groups, static_argnums=(1, 2))
    old = jax.jit(_limit_by_top_k, static_argnums=(1, 2))
    for top in levels:
        biased = rng.random((rows, E), dtype=np.float32)
        if top:
            biased = np.round(biased * top) / np.float32(8)
        want = np.asarray(old(biased, n_group, topk_group))
        got = np.asarray(new(biased, n_group, topk_group))
        assert np.array_equal(got, want)
        kept = np.isfinite(got).reshape(rows, n_group, -1)
        assert (kept.all(-1) | ~kept.any(-1)).all()
        assert (kept.all(-1).sum(-1) == topk_group).all()


@pytest.mark.parametrize("chips", [8, 4])
def test_the_shares_add_up_to_the_uncut_layer(mv, chips):
    """`chips` chips share a layer's 64 experts, a routing group (or two) a
    chip: each one's part of the routed sum, added, is the layer with every
    expert held; the shared expert is counted once. And the program's share
    is the reference's."""
    cfg, model, variables = mv
    whole = variables["params"]["block_3"]["moe"]
    n = 64 // chips
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 19, 64))
    kw = dict(k=8, scale=2.5, n_group=8, topk_group=4)
    from distributed_pytorch_tpu.models.mlp import RoutedExperts
    with HI:
        want = ref.experts_forward(h, whole, first=0, **kw)
        parts = 0.0
        for chip in range(chips):
            share = dict(whole,
                         experts_up=whole["experts_up"][n * chip:][:n],
                         experts_down=whole["experts_down"][n * chip:][:n])
            parts = parts + ref.experts_forward(
                h, share, first=n * chip, shared=chip == 0, **kw)
            held = dataclasses.replace(cfg, experts_held=(n * chip, n))
            got = RoutedExperts(held).apply({"params": share}, h)[0]
            alone = ref.experts_forward(h, share, first=n * chip, **kw)
            assert _rel(got, alone) < 2e-5
    assert _rel(parts, want) < 1e-5


# (6) through the cache and the engine ---------------------------------------

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


def test_cache_path_across_chunks_and_a_used_slot(mv):
    """Chunks of 16: a prompt of 45 (three chunks, the last part-filled),
    30 tokens decoded behind it, then a shorter sequence into the SAME
    slot, whose state, tail and blocks still hold the first one's: every
    position's logits against the reference's full forward pass. A state
    that is not reset fails the second sequence."""
    cfg, model, variables = mv
    caches = init_paged_cache(cfg, 1 + 16, 8, dtype=jnp.float32, n_slots=2)
    bt = np.zeros((2, 16 + 2), np.int32)
    bt[1, :16] = 1 + np.arange(16)
    bt = jnp.asarray(bt)
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


def test_a_reused_slot_whose_state_is_not_reset_fails(mv, monkeypatch):
    """The fault of the PROGRAM the cell's `cache_path` is there for."""
    from distributed_pytorch_tpu.models import linear_attention as la
    cfg, model, variables = mv
    monkeypatch.setattr(
        la, "chunk_start",
        lambda leaf, slot, pos: jax.lax.dynamic_index_in_dim(leaf, slot, 0))
    jax.clear_caches()
    caches = init_paged_cache(cfg, 1 + 16, 8, dtype=jnp.float32, n_slots=2)
    bt = np.zeros((2, 16 + 2), np.int32)
    bt[1, :16] = 1 + np.arange(16)
    bt = jnp.asarray(bt)
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


def test_the_engine_emits_the_references_tokens(mv):
    """Greedy tokens through the engine's own programs (chunks beside
    decoding slots, slots reused): every emitted token is the reference's
    argmax on the sequence so far; the counters of both kinds of mixer,
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
            logits = ref.forward_logits(
                variables["params"], LLM_KW,
                jnp.asarray([full[:-1]], jnp.int32), last=30)[0]
            assert np.array_equal(np.asarray(logits).argmax(-1),
                                  np.asarray(full[len(prompt):]))
    # six 'K' layers: a chunk's real rows, and 29 decode steps a sequence
    # (the first token comes with the last chunk)
    assert eng.kda_slot_steps_by == {"chunk": 6 * sum(map(len, prompts)),
                                     "decode": 6 * 5 * 29}
    assert eng.state_resets == 5
    decode_rows = sum(n + i for n in map(len, prompts) for i in range(1, 30))
    assert eng.latent_rows_read_by["decode"] == decode_rows    # one 'L'
    by = eng.resident_bytes_by_kind
    assert by["slot_state"] == 6 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4) \
        and by["pools"] == eng.n_blocks * 8 * 128 * 4 and by["window"] == 0


# (7) what a configuration may and may not say -------------------------------

@pytest.mark.parametrize("change,told", [
    (dict(layer_pattern="KF*EKEKELEKEKE"), "no GQA layer"),
    (dict(kda_heads=0), "a 'K' layer needs"),
    (dict(kda_lower_bound=-6.0), "has to fit float32's exponent"),
    (dict(kda_lower_bound=0.5), "has to fit float32's exponent"),
    (dict(n_group=5), "n_group divides the routed experts"),
    (dict(topk_group=9), "n_group divides the routed experts"),
    (dict(n_group=8, topk_group=1, n_act=10), "the kept groups hold top k"),
    (dict(n_group=8, router="softmax_topk"), "the sigmoid router's"),
    (dict(kv_latent_dim=0), None),
])
def test_an_inconsistent_configuration_is_refused(change, told):
    with pytest.raises(AssertionError, match=told):
        LLMConfig(**{**LLM_KW, **change})


@pytest.mark.parametrize("pattern", ["KLMC", "LKEF", "MLKE"])
def test_what_may_stand_beside_a_latent_layer(pattern):
    """The 'L' assertion allows every neighbour that is no GQA layer:
    per-slot state of any kind beside the latent pool, in one tree."""
    cfg = LLMConfig(**{**LLM_KW, "layer_pattern": pattern, "n_layer": 4,
                       "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 8})
    caches = init_paged_cache(cfg, 9, 8, dtype=jnp.float32, n_slots=2)
    kinds = {k: c for k, c in zip(pattern, caches)}
    assert kinds["L"].ndim == 3 and set(kinds["K"]) == {"state", "tail"}


def test_the_scopes_and_the_module_are_in_the_tables():
    from distributed_pytorch_tpu.obs.trace import MIXER_MODULES, MIXER_SCOPES
    assert "kda" in MIXER_MODULES
    assert {"kda_proj", "kda_conv", "kda_gate", "attn_kda", "kda_chunk",
            "kda_out", "route_groups"} <= set(MIXER_SCOPES)
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32)
    text = jax.jit(lambda v, i: model.apply(v, i)[0]).lower(
        jax.eval_shape(model.init, {"params": jax.random.PRNGKey(0)},
                       jnp.zeros((1, 8), jnp.int32)),
        jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    for scope in ("kda_proj", "kda_conv", "kda_gate", "attn_kda", "kda_out",
                  "route_groups"):
        assert f"/{scope}/" in text or f"{scope}\"" in text, scope


def test_the_files_parameters_are_the_trees_count():
    """The configuration file's `parameters` against the tree the program
    builds at the published widths (shapes alone: nothing is allocated)."""
    from benchmark.lib import harness
    conf = harness.resolve_cell(harness.load_benchmark(),
                                "ling3_flash_serve_closed192")["config"]
    llm = conf["llm_config"]
    model = LLM(LLMConfig(**llm), compute_dtype=jnp.bfloat16,
                param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, {"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32))["params"]
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    bias = sum(int(np.prod(a.shape)) for p, a in leaves
               if getattr(p[-1], "key", "") == "gate_bias")
    total = sum(int(np.prod(a.shape)) for _, a in leaves) - bias
    assert total == flops_ling.total_params(llm) == 2803760064
    assert f"{total:,}" in conf["parameters"] and bias == 6 * 512
    kda = shapes["block_0"]["kda"]
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        kda)) == sum(flops_ling.kda_params(llm).values()) == 52646048
    assert json.loads(json.dumps(llm)) == llm
