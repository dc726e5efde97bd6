"""The `serve_closed_granite` runner and its reference check on the CPU at a
small size: the runner end to end (paths, arguments, control flow; no
number it produces is a device number), the arithmetic of the cut, and what
its comparison sees. The comparison the runner makes (`reference_check` and
`cache_path_check`, with limits set as the cell's are: between the program's
reading and the mildest fault's) passes the program and fails the expert
stacks in fp8, the gate half dropped, each of the four multipliers set to 1,
the softmax taken over all the router's logits, a missing `D x` and a state
not zeroed. The program's side runs untouched: a fault is put into the
reference, or (the state) into the one function that zeroes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops_granite, harness, reference_granite
from benchmark.runners import serve_closed_granite as runner
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import ssm as ssm_mod
from distributed_pytorch_tpu.models.gpt import LLM

# small widths, the cell's pattern in little (a published layer = a mixer
# block and an expert block), every multiplier away from 1
TINY = dict(
    vocab_size=512, block_size=256, n_embd=64, n_layer=8,
    layer_pattern="MEME*EME", pos_emb="none", non_linearity="swiglu",
    up_dim=48, shared_up_dim=96, n_exp=9, n_shared=1, n_act=4,
    experts_held=[0, 4], router="softmax_topk", attn="gqa", n_head=4,
    n_kv_heads=2, head_dim=32, attn_bias=False, tie_head=True,
    embed_mult=12.0, resid_mult=0.22, attn_scale=0.03125, logits_div=16.0,
    ssm_heads=8, ssm_head_dim=16, ssm_groups=1, ssm_state=16, ssm_conv=4,
    ssm_chunk=8)
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_granite", "clients": 3,
           "prompt_len": [4, 24], "output_len": [4, 12],
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 64, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference_prompt_lens": [9, 20, 16],
           "reference_new_tokens": 32, "reference_engine_tokens": 32,
           # this size's two readings (float32 here): the program reads
           # 1e-6 and every token the reference's; the mildest fault 0.03
           # (logits) and a mean gap of 0.0045 (tokens)
           "reference_limits": {"logit_error_median": 0.01,
                                "logit_error_sequence": 0.01,
                                "logit_tolerance": 0.05,
                                "token_share": 0.95, "sequence_share": 0.9,
                                "gap_cap": 1.0, "mean_gap": 0.002,
                                "repeat_share": 0.9}}


@pytest.fixture
def back_to_cwd():
    cwd = os.getcwd()
    yield
    os.chdir(cwd)


def _ctx(tmp_path, seconds=2.0):
    said = []
    return {"cell": {"name": "tiny_granite", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": 2 ** 31 + 12345, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_granite_runner_end_to_end(tmp_path, back_to_cwd):
    ctx, said = _ctx(tmp_path)
    out = runner.run(ctx)
    assert out["correct"], said
    assert len(out["compared"]) > 2 and all(
        c["ok"] for c in out["compared"]), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    c = out["observations"]["counters"]
    assert c["compiles_in_window"] == 0, said
    assert 0 < c["experts_hit_pct"] <= 100
    assert 0 < c["absent_assignments_pct"] < 100
    assert 0 < c["held_gate_share_pct"] < 100
    assert 0 < c["chunk_program_share_pct"] < 100
    assert c["expert_second_tiles_pct"] >= 0
    assert c["state_resets"] > 0 and c["prefix_reuse_declined"] == 0
    # the step clock apart by the two programs the loop alternates
    clock = out["observations"]["clock"]
    plain, chunk = clock["engine_step_plain_ms"], clock["engine_step_chunk_ms"]
    assert plain and chunk
    assert 0 <= len(clock["engine_step_ms"]) - len(plain) - len(chunk) <= 1
    text = "\n".join(said)
    assert "resident bytes" in text and "second tiles" in text


def test_the_cell_resolves_to_the_published_widths():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, "granite4h_serve_closed64")
    assert res["runner"] is runner
    conf, llm = res["config"], res["config"]["llm_config"]
    cfg = LLMConfig(**llm)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-small")
    assert set(entry["reduced"]) == set(conf["reduced"]) == set(
        conf["published"])
    # a published layer is two blocks: its mixer, then its expert layer
    kinds = {"mamba": "M", "attention": "*"}
    assert cfg.layer_pattern == "".join(
        kinds[k] + "E" for k in conf["layer_types"])
    assert conf["published"]["layer_types"][:10] == conf["layer_types"]
    assert len(conf["layer_types"]) == conf["num_hidden_layers"] == 10
    assert cfg.recurrent and cfg.tie_head == conf["tie_word_embeddings"]
    # every width of the source, under the program's names
    assert (cfg.n_embd, cfg.up_dim, cfg.shared_up_dim) == (
        conf["hidden_size"], conf["intermediate_size"],
        conf["shared_intermediate_size"])
    assert (cfg.n_head, cfg.n_kv_heads, cfg.head_size) == (
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["hidden_size"] // conf["num_attention_heads"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_n_groups"],
        conf["mamba_d_state"], conf["mamba_d_conv"],
        conf["mamba_chunk_size"])
    assert cfg.ssm_heads * cfg.ssm_head_dim == \
        conf["mamba_expand"] * conf["hidden_size"]
    assert (cfg.embed_mult, cfg.resid_mult, cfg.attn_scale,
            cfg.logits_div) == (
        conf["embedding_multiplier"], conf["residual_multiplier"],
        conf["attention_multiplier"], conf["logits_scaling"])
    assert cfg.n_routed == conf["router_width"] == conf["published"][
        "num_local_experts"]
    assert cfg.n_act_routed == conf["num_experts_per_tok"]
    assert cfg.experts_held == (0, conf["num_local_experts"])
    assert cfg.router == "softmax_topk" and cfg.non_linearity == "swiglu"
    assert cfg.vocab_size == conf["vocab_size"] == \
        conf["published"]["vocab_size"] // 2
    assert cfg.norm_eps == conf["rms_norm_eps"]
    # the cut's arithmetic, from the shapes (ISSUE 36)
    assert round(flops_granite.total_params(llm) / 1e9, 3) == 4.757
    assert round(flops_granite.layer_params(llm, "M") / 1e6, 2) == 102.29
    assert round(flops_granite.layer_params(llm, "*") / 1e6, 2) == 41.95
    assert round(flops_granite.layer_params(llm, "E") / 1e6, 2) == 358.91
    assert flops_granite.expert_up_bytes_per_call(llm, 1) == 12582912
    assert flops_granite.expert_down_bytes_per_call(llm, 1) == 6291456
    held = flops_granite.resident_bytes(llm, 64, 264, 128)
    assert 12.0e9 < held["total"] < 12.2e9
    step = flops_granite.decode_step_bytes(llm, 64, 36, 0)
    assert round(step["held_experts"] / 1e9, 2) == 6.79
    assert round(step["state"] / 1e9, 2) == 4.89


def test_the_traffic_is_the_issues():
    t = harness.resolve_cell(harness.load_benchmark(),
                             "granite4h_serve_closed64")["traffic"]
    assert (t["clients"], t["prompt_len"], t["output_len"]) == (
        64, [64, 256], [64, 192])
    assert t["engine"] == {"n_slots": 64, "max_len": 512, "block_size": 128,
                           "prefill_chunk": 256, "temperature": 0.0,
                           "prefix_cache": False}
    assert (t["compute_dtype"], t["warm_s"], t["trace_s"]) == (
        "bfloat16", 5.0, 3.0)


def test_flops_count_the_tree():
    """`total_params` from shapes = the leaves of the program's tree."""
    cfg = LLMConfig(**TINY)
    shapes = jax.eval_shape(
        lambda k: LLM(cfg).init({"params": k}, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert flops_granite.total_params(TINY) == leaves


# ---------------------------------------------------------------------------
# what the comparison sees
# ---------------------------------------------------------------------------

def _big_init(variables):
    """Weights a few times the cell's N(0, 0.02) draw, so that logits at 64
    wide spread as the cell's do at 4096 (std ~ 0.02 * sqrt(width))."""
    return jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 and 512 not in a.shape else a,
        variables)


def _drawn():
    cfg = LLMConfig(**TINY)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    return cfg, model, _big_init(model.init(
        {"params": jax.random.PRNGKey(7)}, jnp.zeros((1, 8), jnp.int32)))


@pytest.fixture(scope="module")
def setup():
    """The tree as the runner makes it: drawn, the embedding settled."""
    cfg, model, variables = _drawn()
    return cfg, model, {"params": runner.settle_embedding(
        dict(variables["params"]), TINY)}


def _check(model, variables, tmp_path, faults=()):
    """Both limits of `correct`, as the runner applies them."""
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    with jax.default_matmul_precision("highest"):
        tokens = runner.reference_check(ctx, eng, TINY, variables, 512,
                                        faults)
        logits = runner.cache_path_check(ctx, model, TINY, variables, 512,
                                         faults)
    return {"ok": tokens["ok"] and logits["ok"], "tokens": tokens,
            "logits": logits}


def test_the_program_passes(setup, tmp_path):
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path)
    assert res["ok"] and res["tokens"]["tokens"] == 5 * 32, res
    assert res["tokens"]["repeat_share"] == 1.0, res["tokens"]
    assert res["logits"]["positions"] == 4 * 32
    assert res["logits"]["median"] < 1e-4, res     # float32 here


@pytest.mark.parametrize("fault", reference_granite.FAULTS)
def test_a_spoilt_reference_fails(setup, tmp_path, fault):
    """By BOTH limits: the logits through the cache, and the tokens the
    engine's own programs emitted (but for the logits left undivided, which
    no argmax can show)."""
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, (fault,))
    assert not res["logits"]["ok"], res
    assert res["tokens"]["ok"] == (fault == "logits_div_1"), res["tokens"]


def test_the_settled_embedding_is_the_draw_over_its_multiplier(tmp_path):
    """`settle_embedding` divides the embedding's rows (= the tied head's)
    by `embed_mult` and touches nothing else; and why: on the tree as drawn
    the input token's own logit tops every position, the engine repeats
    it, and the tokens limit passes the mild faults."""
    cfg, model, drawn = _drawn()
    before = np.array(drawn["params"]["tkn_emb"]["embedding"])
    settled = runner.settle_embedding(dict(drawn["params"]), TINY)
    np.testing.assert_allclose(settled["tkn_emb"]["embedding"] * 12.0,
                               before, rtol=1e-6)
    assert all(settled[k] is drawn["params"][k] for k in settled
               if k != "tkn_emb")
    _, _, drawn = _drawn()               # the first was donated
    for fault in ("fp8_experts", "softmax_all"):
        res = _check(model, drawn, tmp_path, (fault,))
        assert res["tokens"]["ok"] and not res["logits"]["ok"], res


def test_a_state_not_zeroed_fails(setup, tmp_path, monkeypatch):
    cfg, model, variables = setup
    monkeypatch.setattr(
        ssm_mod, "chunk_start",
        lambda leaf, slot, pos: jax.lax.dynamic_index_in_dim(leaf, slot, 0))
    runner._path_prefill.clear_cache()      # traced with the true one
    try:
        res = _check(model, variables, tmp_path)
    finally:
        runner._path_prefill.clear_cache()
    assert not res["tokens"]["ok"] and not res["logits"]["ok"], res
    # a prompt run twice, in slots with different pasts, reads differently
    assert res["tokens"]["repeat_share"] < 0.9, res["tokens"]
    first, *later = res["logits"]["by_sequence"]
    assert first < 1e-4 and min(later) > 0.01, res
