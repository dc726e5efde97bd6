"""A Falcon-H1 shaped patterned model (in EVERY layer a Mamba-2 mixer and GQA
side by side on one normed input, then a dense gated FFN; thirteen scalar
multipliers; an untied head) at a small size on the CPU, seeded weights,
float32, against the plain reference (benchmark/lib/reference_falcon_h1.py):
the tree and the cache slot of two kinds, the whole forward pass, what each
term is worth, the cache path with a used slot beside a dead one, the engine
through reused slots and its counters, the scopes. And what the models that
were there are NOT asked."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops_falcon_h1 as flops
from benchmark.lib import reference_falcon_h1 as ref
from distributed_pytorch_tpu.config import LAYER_KEEPS, LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
from distributed_pytorch_tpu.ops import mup

# the cell's pattern in little, the published multipliers as they are
LLM_KW = dict(
    vocab_size=256, block_size=4096, n_embd=64, n_layer=6,
    layer_pattern="PFPFPF", pos_emb="rope", rope_theta=1e11,
    rope_pairing="half", norm_eps=1e-5, tie_head=False, attn="gqa",
    n_head=10, n_kv_heads=2, head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=96, dense_up_dim=96,
    embed_mult=5.656854249492381, logits_div=128.0, attn_in_mult=1.0,
    attn_out_mult=0.0375, key_mult=0.011048543456039804, ssm_in_mult=0.25,
    ssm_out_mult=0.08838834764831845,
    ssm_mults=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
               0.3535533905932738),
    mlp_gate_mult=0.1767766952966369, mlp_down_mult=0.011160714285714284,
    ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=32, ssm_conv=4,
    ssm_chunk=8)
HI = jax.default_matmul_precision("highest")
TOLERANCE = 1e-4        # float32 here: the program against the reference


def conditioned(variables, seed=5):
    """The tree as the benchmark's runner conditions it (each matrix a
    multiplier follows divided by it, the convolutions' biases drawn), the
    matrices a few times the draw so that at 64 wide every term moves the
    logits by more than float32 rounding."""
    from benchmark.runners import serve_closed_parallel as runner
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, variables["params"])
    ctx = {"seed": seed}
    for rule in ("divide_by_multipliers", "draw_conv_bias"):
        params = runner.CONDITIONING[rule](params, LLM_KW, ctx)
    return {"params": params}


@pytest.fixture(scope="module")
def mv():
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = conditioned(model.init({"params": jax.random.PRNGKey(1)},
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


# (1) the tree, the cache slot of two kinds ---------------------------------

def test_the_tree_and_the_cache_slot_of_two_kinds(mv):
    cfg, model, variables = mv
    p = variables["params"]
    assert p["lm_head"].shape == (256, 64)               # untied
    assert set(p["block_0"]) == {"norm", "attn", "ssm"}  # ONE norm, both
    assert set(p["block_1"]) == {"norm", "mlp"}
    attn = p["block_0"]["attn"]
    assert set(attn) == {"c_attn", "c_proj"} \
        and set(attn["c_attn"]) == {"kernel"}            # no biases
    assert attn["c_attn"]["kernel"].shape == (64, 160 + 2 * 32)
    ssm = p["block_0"]["ssm"]
    assert ssm["in_proj"].shape == (64, 64 + (64 + 2 * 2 * 32) + 4)
    assert ssm["conv_w"].shape == (4, 192) and ssm["norm_w"].shape == (64,)
    assert p["block_1"]["mlp"]["c_fc"].shape == (64, 2 * 96)
    # the multipliers are no leaves: nothing is folded into a matrix
    assert flops.total_params(LLM_KW) == sum(
        a.size for a in jax.tree_util.tree_leaves(p))
    assert LAYER_KEEPS["P"] == ("pools", "slot_state")
    assert cfg.layer_keeps == (("pools", "slot_state"), ()) * 3
    assert cfg.layers_keeping("pools") == cfg.layers_keeping(
        "slot_state") == 3 and cfg.layers_keeping("window") == 0
    assert cfg.recurrent and cfg.slot_state == "recurrent layers"
    caches = init_paged_cache(cfg, 5, 8, dtype=jnp.float32, n_slots=3)
    assert [None if c is None else sorted(c) for c in caches] == [
        ["pools", "slot_state"], None] * 3
    slot = caches[0]
    assert sorted(slot["pools"]) == ["k", "v"] \
        and slot["pools"]["k"].shape == (5, 8, 128)      # 2 x 16 -> 128
    # state-major: 32 state rows, 4 heads x 16 side by side on the lanes
    assert slot["slot_state"]["ssm"].shape == (3, 32, 64) \
        and slot["slot_state"]["ssm"].dtype == jnp.float32
    assert slot["slot_state"]["conv"].shape == (3, 3, 192)


def test_resident_bytes_split_a_slot_and_state_does_not_know_max_len(mv):
    cfg, model, variables = mv
    short = _engine(model, variables, max_len=64)
    long = _engine(model, variables, max_len=256)
    a, b = short.resident_bytes_by_kind, long.resident_bytes_by_kind
    state = 3 * 2 * (4 * 16 * 32 * 4 + 3 * 192 * 4)
    assert a["slot_state"] == b["slot_state"] == state
    assert a["window"] == b["window"] == 0
    for eng, by in ((short, a), (long, b)):
        assert by["pools"] == 3 * 2 * eng.n_blocks * 8 * 128 * 4
        assert by["pools"] + by["slot_state"] == sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(eng.caches))
    assert b["pools"] > 2 * a["pools"]
    assert short.state_bytes_slot == 3 * 4 * 16 * 32 * 4


# (2) the whole forward pass, what each term is worth -----------------------

def test_full_forward_matches_the_reference(mv):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((23, 23), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
    assert _rel(got, want) < 2e-5
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_the_fault_list_is_the_issues():
    assert len(ref.MULT_FAULTS) == 13 and len(ref.FAULTS) == 26
    assert len(set(ref.FAULTS)) == 26
    # every multiplier of the configuration that is not 1 has its fault
    cell = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs",
        "falcon-h1-34b-instruct.json")))["llm_config"]
    not_one = [k for k, v in cell.items()
               if (k.endswith("_mult") or k == "logits_div") and v != 1.0]
    assert {f"{k}_1" for k in not_one} | {
        f"ssm_mults.{s}_1" for s in ref.SEGMENTS} == set(ref.MULT_FAULTS)
    assert cell["attn_in_mult"] == 1.0 and all(
        m != 1.0 for m in cell["ssm_mults"])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_term_spoilt_moves_the_reference(mv, fault):
    """By far more than the tolerance the program is held to."""
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((40,), seed=4), jnp.int32)
    with HI:
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
        spoilt = ref.forward_logits(variables["params"], LLM_KW, idx,
                                    faults=(fault,))
    assert _rel(spoilt, want) > 20 * TOLERANCE, fault


@pytest.mark.parametrize("field,other", [
    ("attn_in_mult", 0.5), ("attn_out_mult", 1.0), ("key_mult", 1.0),
    ("ssm_in_mult", 1.0), ("ssm_out_mult", 1.0), ("ssm_mults", ()),
    ("mlp_gate_mult", 1.0), ("mlp_down_mult", 1.0), ("embed_mult", 1.0),
    ("logits_div", 1.0), ("rope_pairing", "adjacent"),
    ("rope_theta", 1e4)])
def test_each_field_reaches_the_program(mv, field, other):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((24,), seed=5), jnp.int32)
    moved = LLM(dataclasses.replace(cfg, **{field: other}),
                compute_dtype=jnp.float32, attn_impl="naive")
    with HI:
        a = model.apply(variables, idx, all_logits=True)[0]
        b = moved.apply(variables, idx, all_logits=True)[0]
    assert _rel(b, a) > 20 * TOLERANCE, field


def test_a_multiplier_of_one_adds_no_op():
    x = jnp.ones((2, 8), jnp.bfloat16)
    assert mup.times(x, 1.0) is x and mup.segment_times(x, (8,), ()) is x
    y = mup.times(x, 0.0375)
    assert y.dtype == x.dtype and float(y[0, 0]) == float(
        jnp.asarray(0.0375, jnp.bfloat16))
    z = mup.segment_times(jnp.ones((1, 6), jnp.float32), (2, 4), (0.5, 3.0))
    assert z.tolist() == [[0.5, 0.5, 3.0, 3.0, 3.0, 3.0]]
    with pytest.raises(AssertionError):
        LLMConfig(**{**LLM_KW, "ssm_mults": (0.5, 0.5)})
    with pytest.raises(AssertionError, match="patterned"):
        LLMConfig(vocab_size=256, block_size=64, n_embd=64, n_head=4,
                  n_kv_heads=2, n_layer=2, up_dim=128, key_mult=0.5)


# (3) through the cache -----------------------------------------------------

def test_chunked_prefill_into_a_used_slot_then_decode_gives_the_logits(mv):
    """Slot 1 takes a first sequence, then a SECOND one in three chunks
    (the last partial) over what the first left in its state, tail and
    blocks, then decodes beside slot 0, which is dead throughout: every
    row against the reference's full forward pass of the second."""
    cfg, model, variables = mv
    bs, chunk, n_new = 8, 16, 6
    caches = init_paged_cache(cfg, 17, bs, dtype=jnp.float32, n_slots=2)
    bt = np.zeros((2, 16 + 2), np.int32)
    bt[1, :16] = np.arange(1, 17)
    bt = jnp.asarray(bt)
    rows = None
    for seq in _prompts((29 + n_new, 37 + n_new), seed=6):
        L = len(seq) - n_new
        rows = []
        for off in range(0, L, chunk):
            n = min(chunk, L - off)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n] = seq[off:off + n]
            with HI:
                logits, _, caches = model.apply(
                    variables, jnp.asarray(buf), None, caches,
                    jnp.int32(off), logits_idx=jnp.asarray([n - 1]),
                    block_tables=bt[1:],
                    state_ctx={"slot": jnp.int32(1),
                               "valid_len": jnp.asarray([n], jnp.int32)})
        rows.append(logits[0, -1])
        for i in range(L, L + n_new - 1):
            with HI:
                logits, _, caches = model.apply(
                    variables, jnp.asarray([[0], [seq[i]]], jnp.int32),
                    None, caches, jnp.asarray([0, i], jnp.int32),
                    block_tables=bt,
                    state_ctx={"live": jnp.asarray([False, True])})
            rows.append(logits[1, -1])
    with HI:
        want = ref.forward_logits(variables["params"], LLM_KW,
                                  jnp.asarray([seq[:-1]]), last=n_new)[0]
    assert _rel(jnp.stack(rows), want) < TOLERANCE
    # the dead slot's state and tail were never touched
    state = caches[0]["slot_state"]
    assert not np.asarray(state["ssm"][0]).any() \
        and not np.asarray(state["conv"][0]).any()
    assert np.asarray(state["ssm"][1]).any()


def test_engine_matches_the_reference_through_reused_slots(mv):
    cfg, model, variables = mv
    prompts = _prompts((5, 20, 37, 9, 30), seed=7)
    eng = _engine(model, variables)
    with HI:
        outs = eng.run(prompts, 6)
    for prompt, full in zip(prompts, outs):
        full = [int(t) for t in full]
        assert full[:len(prompt)] == prompt and len(full) == len(prompt) + 6
        with HI:
            want = np.asarray(ref.forward_logits(
                variables["params"], LLM_KW, jnp.asarray([full[:-1]]),
                last=6)[0])
        new = np.asarray(full[len(prompt):])
        gap = (want.max(axis=-1) - want[np.arange(6), new]) \
            / want.std(axis=-1)
        assert gap.max() < 1e-3, (prompt, gap)
    # what the engine counted: a first chunk a prompt; the attention
    # branches' rows and the state-space branches' bytes, 3 layers each
    assert eng.state_resets == 5 and eng.prefix_reuse_declined == 0
    assert eng.features_declined == []
    assert eng.plans_kv_rows and eng.n_full == 3 and eng.n_window == 0
    state = 3 * 4 * 16 * 32 * 4
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert eng.ssm_state_bytes_by["chunk"] == 2 * state * chunks
    assert eng.ssm_state_bytes_by["decode"] == 2 * state * 5 * 5
    assert eng.ssm_state_bytes == sum(eng.ssm_state_bytes_by.values())
    assert eng.kv_rows_read_full_by["chunk"] == 3 * sum(
        min(off + 16, len(p)) for p in prompts
        for off in range(0, len(p), 16))
    assert eng.kv_rows_read_full_by["decode"] == 3 * sum(
        len(p) + i for p in prompts for i in range(1, 6))
    assert eng.window_rows_saved == 0
    recs = eng.flight.entries()
    assert sum(r["ssm_state_bytes"] for r in recs) == eng.ssm_state_bytes
    assert sum(r["kv_rows_read_full"] for r in recs) \
        == eng.kv_rows_read_full
    assert sum(r["state_reset"] for r in recs) == 5


def test_features_that_need_a_snapshot_stand_down_aloud(mv):
    cfg, model, variables = mv
    eng = _engine(model, variables, prefix_cache=True, spec_decode=True,
                  spec_k=2)
    assert eng.features_declined == ["prefix_cache", "spec_decode"]
    assert not eng.spec_decode


# (4) the scopes and the counters' way out ----------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_both_branches_and_their_sum_reach_the_compiled_op_names(mv, fused):
    import re
    from distributed_pytorch_tpu.engine.decode import (make_fused_step_fn,
                                                       make_step_fn)
    from distributed_pytorch_tpu.obs.trace import (MIXER_MODULES,
                                                   MIXER_SCOPES, SCOPES)
    assert "mixer_sum" in MIXER_MODULES and "mixer_sum" not in SCOPES \
        and "mixer_sum" not in MIXER_SCOPES
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
    paths = [re.split(r"[/()]", p)
             for p in re.findall(r'op_name="([^"]+)"', text)]
    parts = [set(p) for p in paths]
    want = {"attn", "ssm", "mixer_sum", "mlp", "norm", "rope", "attn_core",
            "kv_update", "ssm_conv", "ssm_step", "lm_head", "decode"}
    for scope in want | ({"ssm_scan", "chunk_prefill"} if fused else set()):
        assert any(scope in p for p in parts), scope
    assert fused or not any("ssm_scan" in p for p in parts)
    # both branches of ONE block, under the block's name
    for branch in ("attn", "ssm", "mixer_sum"):
        assert any("block_0" in p and branch in p for p in parts), branch
    assert not any("block_1" in p and ("ssm" in p or "attn" in p)
                   for p in parts)
    assert not any("moe" in p or "conv_step" in p for p in parts)


def test_counters_reach_metrics_and_the_timeline(mv):
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
    assert got["serve_state_resets_total"] == eng.state_resets == 2
    assert got["serve_ssm_state_bytes_total"] == eng.ssm_state_bytes > 0
    assert got["serve_kv_rows_read_full_total"] == eng.kv_rows_read_full > 0
    assert got["serve_kv_rows_read_window_total"] == 0
    by = eng.resident_bytes_by_kind
    for kind in ("weights", "pools", "window", "slot_state"):
        assert got[f"serve_resident_bytes_{kind}"] == by[kind]
    assert by["pools"] > 0 and by["slot_state"] > 0 and by["window"] == 0


# (5) the configuration file ------------------------------------------------

def test_the_configuration_files_parameters_are_the_trees():
    conf = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs",
        "falcon-h1-34b-instruct.json")))
    llm = conf["llm_config"]
    big = LLMConfig(**llm)
    shapes = jax.eval_shape(
        lambda k: LLM(big, param_dtype=jnp.bfloat16).init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(a.size for a in leaves) == conf["parameters"]["total"] \
        == flops.total_params(llm) == 4205319008
    par = conf["parameters"]
    assert par["a_layer"] == par["attention_a_layer"] + par["ssm_a_layer"] \
        + par["ffn_a_layer"] + par["norms_a_layer"] == 430120032
    assert par["total"] == par["layers"] * par["a_layer"] \
        + par["embedding_and_head"] + par["final_norm"]
    block = shapes["params"]["block_0"]
    count = lambda t: sum(a.size for a in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert count(block["attn"]) == par["attention_a_layer"]
    assert count(block["ssm"]) == par["ssm_a_layer"]
    assert count(shapes["params"]["block_1"]["mlp"]) == par["ffn_a_layer"]
    # A_log, D and dt_bias float32, every other leaf bfloat16
    f32 = [a for a in leaves if a.dtype == jnp.float32]
    assert len(f32) == 3 * 9 and all(a.shape == (32,) for a in f32)
    # every width as published, the cuts in `reduced` alone
    assert (big.n_embd, big.n_head, big.n_kv_heads, big.head_size,
            big.dense_up_dim) == (5120, 20, 4, 128, 21504) == (
        conf["hidden_size"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"],
        conf["intermediate_size"])
    assert (big.ssm_heads, big.ssm_head_dim, big.ssm_state, big.ssm_groups,
            big.ssm_conv, big.ssm_chunk) == (32, 128, 256, 2, 4, 128) == (
        conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"],
        conf["mamba_n_groups"], conf["mamba_d_conv"],
        conf["mamba_chunk_size"])
    assert big.ssm_heads * big.ssm_head_dim == conf["mamba_d_ssm"]
    assert big.layer_pattern == "PF" * conf["num_hidden_layers"]
    assert set(conf["reduced"]) == set(conf["published"]) == {
        "num_hidden_layers", "vocab_size"}
    assert conf["published"] == {"num_hidden_layers": 72,
                                 "vocab_size": 261120}
    assert big.vocab_size == conf["vocab_size"] == 261120 // 8
    assert (big.embed_mult, 1 / big.logits_div, big.attn_in_mult,
            big.attn_out_mult, big.key_mult, big.ssm_in_mult,
            big.ssm_out_mult, list(big.ssm_mults),
            [big.mlp_gate_mult, big.mlp_down_mult]) == (
        conf["embedding_multiplier"], conf["lm_head_multiplier"],
        conf["attention_in_multiplier"], conf["attention_out_multiplier"],
        conf["key_multiplier"], conf["ssm_in_multiplier"],
        conf["ssm_out_multiplier"], conf["ssm_multipliers"],
        conf["mlp_multipliers"])
    assert big.rope_theta == conf["rope_theta"] == 1e11 \
        and big.rope_pairing == "half" and not big.tie_head
    assert len(conf["assumed"]) >= 8 and len(conf["changed"]) >= 4 \
        and "v5e-8" in conf["deployment"]


# (6) what the models that were there are not asked -------------------------

def test_the_accepted_shapes_are_asked_nothing_new():
    """Their leaves, their cache slots and their programs' op names are
    what they were: a slot is a pool OR per-slot leaves, no multiplier's
    op, no `mixer_sum`; the engine plans key rows for a window model and
    a 'P' model alone."""
    from tests.test_granite import LLM_KW as GRANITE_KW
    from tests.test_hybrid import LLM_KW as NEMOTRON_KW
    from tests.test_laguna import LLM_KW as LAGUNA_KW
    from tests.test_lfm2 import LLM_KW as LFM2_KW
    slots = {"M": ["conv", "ssm"], "C": ["conv"], "*": ["k", "v"],
             "W": ["k", "v"], "E": None, "F": None}
    for kw in (NEMOTRON_KW, GRANITE_KW, LFM2_KW, LAGUNA_KW):
        cfg = LLMConfig(**kw)
        assert (cfg.attn_in_mult, cfg.attn_out_mult, cfg.key_mult,
                cfg.ssm_in_mult, cfg.ssm_out_mult, cfg.ssm_mults,
                cfg.mlp_gate_mult, cfg.mlp_down_mult) == (
            1.0, 1.0, 1.0, 1.0, 1.0, (), 1.0, 1.0)
        caches = init_paged_cache(cfg, 5, 8, dtype=jnp.float32, n_slots=2)
        assert [None if c is None else sorted(c) for c in caches] == [
            slots[k] for k in cfg.layer_pattern]
        assert cfg.recurrent == any(k in "MC" for k in cfg.layer_pattern)
        model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
        v = model.init({"params": jax.random.PRNGKey(1)},
                       jnp.zeros((1, 8), jnp.int32))
        text = jax.jit(lambda v, x: model.apply(v, x)).lower(
            v, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
        assert "mixer_sum" not in text
        eng = _engine(model, v, n_slots=2)
        assert eng.plans_kv_rows == ("W" in cfg.layer_pattern)
        assert eng.n_full == cfg.layer_pattern.count("*")
        by = eng.resident_bytes_by_kind
        assert (by["pools"] > 0) == ("*" in cfg.layer_pattern)
        assert (by["window"] > 0) == ("W" in cfg.layer_pattern)
        assert (by["slot_state"] > 0) == cfg.recurrent
    classic = LLMConfig(vocab_size=256, block_size=64, n_embd=64, n_head=4,
                        n_kv_heads=2, attn="gqa", n_layer=2, up_dim=128,
                        pos_emb="rope")
    assert classic.layer_keeps == (("pools",),) * 2 and not classic.recurrent
    assert classic.layers_keeping("pools") == 2
