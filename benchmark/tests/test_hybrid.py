"""The `serve_closed_hybrid` runner and its reference check on the CPU at a
small size: the runner end to end (paths, arguments, control flow; no
number it produces is a device number), and what its comparison sees. The
comparison the runner makes (`reference_check`, with the runner's own
tolerances) passes the program and fails expert weights rounded to fp8, a
missing `D x`, a missing gate term and a state not zeroed. The program's
side runs untouched: the fault is put into the weights it is given, into
the reference, or (the state) into the one function that zeroes. A carried
state rounded to bfloat16 is the one fault of ISSUE 33's list that no limit
on logits can see beside a bf16 system's own rounding: the last test says
by how much."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops_hybrid, harness, reference_hybrid
from benchmark.runners import serve_closed_hybrid as runner
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import ssm as ssm_mod
from distributed_pytorch_tpu.models.gpt import LLM

# small widths, the cell's pattern in little; weights drawn larger than the
# cell's N(0, 0.02) so that the logits spread as the cell's do at its widths
TINY = dict(
    vocab_size=512, block_size=256, n_embd=64, n_layer=6,
    layer_pattern="MEM*EM", pos_emb="none", non_linearity="relu2",
    up_dim=48, shared_up_dim=96, n_exp=9, n_shared=1, n_act=4,
    experts_held=[0, 4], routed_scale=2.5, attn="gqa", n_head=4,
    n_kv_heads=2, head_dim=32, attn_bias=False, tie_head=False, ssm_heads=4,
    ssm_head_dim=16, ssm_groups=2, ssm_state=16, ssm_conv=4, ssm_chunk=8)
# the comparison's tests run the CELL's own 16-layer pattern at these
# widths: an error in one kind of layer compounds through the seven of its
# kind (and through the experts it flips downstream) as it does in the cell
DEEP = {**TINY, "n_layer": 16, "layer_pattern": "MEMEM*EMEMEM*EME"}
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_hybrid", "clients": 3,
           "prompt_len": [4, 24], "output_len": [4, 12],
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 3, "max_len": 64, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference_prompt_lens": [9, 20, 16],
           "reference_new_tokens": 32,
           # this size's two readings (float32 here): the program reads
           # 1e-6 and every token the reference's; the mildest fault 0.05
           "reference_limits": {"logit_error_median": 0.02,
                                "logit_tolerance": 0.05,
                                "token_share": 0.95, "sequence_share": 0.9}}


@pytest.fixture
def back_to_cwd():
    cwd = os.getcwd()
    yield
    os.chdir(cwd)


def _ctx(tmp_path, seconds=2.0):
    said = []
    return {"cell": {"name": "tiny_hybrid", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": 2 ** 31 + 12345, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_hybrid_runner_end_to_end(tmp_path, back_to_cwd):
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
    assert c["state_resets"] > 0 and c["prefix_reuse_declined"] == 0
    text = "\n".join(said)
    assert "resident bytes" in text and "overlap_share" in text


def test_the_cell_resolves_to_the_published_widths():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, "nemotron_h_serve_closed64")
    assert res["runner"] is runner
    conf, llm = res["config"], res["config"]["llm_config"]
    cfg = LLMConfig(**llm)
    assert cfg.recurrent and cfg.layer_pattern == conf[
        "hybrid_override_pattern"]
    # every width of the source, under the program's names
    assert (cfg.n_embd, cfg.up_dim, cfg.shared_up_dim) == (
        conf["hidden_size"], conf["moe_intermediate_size"],
        conf["moe_shared_expert_intermediate_size"])
    assert (cfg.n_head, cfg.n_kv_heads, cfg.head_size) == (
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"])
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        conf["mamba_num_heads"], conf["mamba_head_dim"], conf["n_groups"],
        conf["ssm_state_size"], conf["conv_kernel"], conf["chunk_size"])
    assert cfg.n_routed == conf["router_width"] == conf["published"][
        "n_routed_experts"]
    assert cfg.n_act_routed == conf["num_experts_per_tok"]
    assert cfg.experts_held == (0, conf["n_routed_experts"])
    assert cfg.routed_scale == conf["routed_scaling_factor"]
    assert cfg.vocab_size == conf["vocab_size"]
    assert conf["published"]["hybrid_override_pattern"].startswith(
        cfg.layer_pattern)
    # the cut's arithmetic, from the shapes (ISSUE 33)
    assert round(flops_hybrid.total_params(llm) / 1e9, 3) == 5.283
    assert round(flops_hybrid.layer_params(llm, "E") / 1e6, 1) == 658.9
    assert round(flops_hybrid.state_bytes_per_slot(llm) / 1e6, 1) == 14.9
    held = flops_hybrid.resident_bytes(llm, 64, 264, 128)
    assert 11.5e9 < held["total"] < 11.7e9


def test_flops_count_the_tree(tmp_path):
    """`total_params` from shapes = the leaves of the program's tree."""
    cfg = LLMConfig(**TINY)
    shapes = jax.eval_shape(
        lambda k: LLM(cfg).init({"params": k}, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert flops_hybrid.total_params(TINY) == leaves


# ---------------------------------------------------------------------------
# what the comparison sees
# ---------------------------------------------------------------------------

def _big_init(variables):
    """Weights a few times the cell's N(0, 0.02) draw, so that logits at 64
    wide spread as the cell's do at 2688 (std ~ 0.02 * sqrt(width)), and
    the expert matrices twice that again, so that an expert layer adds to
    the residual two to three times what a state-space layer adds, as in
    the cell (my chip run, PR 33: |y| 2.0-3.5 against 1.3)."""
    def scale(path, a):
        if a.ndim < 2 or 512 in (a.shape[0], a.shape[-1]):
            return a
        name = str(path[-1])
        return a * (12.0 if "experts" in name or "shared" in name else 6.0)
    return jax.tree_util.tree_map_with_path(scale, variables)


@pytest.fixture(scope="module")
def setup():
    cfg = LLMConfig(**DEEP)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = _big_init(model.init({"params": jax.random.PRNGKey(7)},
                                     jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, variables


def _check(model, sys_variables, ref_variables, tmp_path, faults=()):
    """Both limits of `correct`, as the runner applies them; the system's
    side gets `sys_variables`, the reference's `ref_variables` and
    `faults`."""
    eng = DecodeEngine(model, sys_variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    true_forward = reference_hybrid.forward_logits
    swap = lambda params, *a, **kw: true_forward(  # noqa: E731
        ref_variables["params"], *a, **kw)
    reference_hybrid.forward_logits = swap
    try:
        with jax.default_matmul_precision("highest"):
            tokens = runner.reference_check(ctx, eng, DEEP, sys_variables,
                                            512, faults)
            logits = runner.cache_path_check(ctx, model, DEEP, sys_variables,
                                             512, faults)
    finally:
        reference_hybrid.forward_logits = true_forward
    return {"ok": tokens["ok"] and logits["ok"], "tokens": tokens,
            "logits": logits}


def _blocks(params, kind, fn):
    out = dict(params)
    for i, k in enumerate(DEEP["layer_pattern"]):
        if k == kind:
            out[f"block_{i}"] = fn(dict(params[f"block_{i}"]))
    return out


def _fp8(a):
    s = jnp.max(jnp.abs(a)) / 448.0
    return ((a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * s).astype(a.dtype)


def test_the_program_passes(setup, tmp_path):
    cfg, model, variables = setup
    res = _check(model, variables, variables, tmp_path)
    assert res["ok"] and res["tokens"]["tokens"] == 5 * 32, res
    assert res["logits"]["positions"] == 4 * 32
    assert res["logits"]["median"] < 1e-4, res     # float32 here


@pytest.mark.parametrize("fault", ["fp8_expert_weights", "no_skip_term"])
def test_a_spoilt_program_fails(setup, tmp_path, fault):
    cfg, model, variables = setup
    p = variables["params"]

    def fp8_experts(block):
        moe = dict(block["moe"])
        for k in ("experts_up", "experts_down", "shared_up", "shared_down"):
            moe[k] = _fp8(moe[k])
        block["moe"] = moe
        return block

    def no_skip(block):
        block["ssm"] = {**block["ssm"], "D": jnp.zeros_like(block["ssm"]["D"])}
        return block

    spoilt = _blocks(p, "E", fp8_experts) if fault == "fp8_expert_weights" \
        else _blocks(p, "M", no_skip)
    res = _check(model, {"params": spoilt}, variables, tmp_path)
    assert not res["ok"], res


@pytest.mark.parametrize("fault", ["no_gate", "no_skip", "fp8_experts"])
def test_a_spoilt_reference_fails(setup, tmp_path, fault):
    """The same faults from the other side: the reference computed with
    the expert matrices in fp8 (the nearest precision below the
    configuration's bf16 weights), without the gate, without `D x`."""
    cfg, model, variables = setup
    res = _check(model, variables, variables, tmp_path, (fault,))
    assert not res["ok"], res


def test_a_state_not_zeroed_fails(setup, tmp_path, monkeypatch):
    cfg, model, variables = setup
    monkeypatch.setattr(
        ssm_mod, "chunk_start",
        lambda leaf, slot, pos: jax.lax.dynamic_index_in_dim(leaf, slot, 0))
    runner._path_prefill.clear_cache()      # traced with the true one
    try:
        res = _check(model, variables, variables, tmp_path)
    finally:
        runner._path_prefill.clear_cache()
    assert not res["ok"] and not res["logits"]["ok"], res
    # the sequence that started clean reads as the program does, the three
    # into the used slot do not
    first, *later = res["logits"]["by_sequence"]
    assert first < 1e-4 and min(later) > 0.02, res


def test_a_bf16_state_is_below_what_a_limit_on_logits_can_see(setup,
                                                              tmp_path):
    """The carried state rounded to bfloat16 after every token moves the
    logits a thousand times more than float32 rounding does and still a
    tenth of what bf16 COMPUTE moves them (0.6% on the chip, PERF.md
    section 2: the two readings there agree to four digits). The state's
    128 entries a head average the rounding out. So the median limit, set
    to pass a bf16 system, passes this too: said here, not hidden."""
    cfg, model, variables = setup
    clean = _check(model, variables, variables, tmp_path)["logits"]
    spoilt = _check(model, variables, variables, tmp_path,
                    ("bf16_state",))["logits"]
    assert spoilt["median"] > 100 * clean["median"]
    cell = harness.resolve_cell(harness.load_benchmark(),
                                "nemotron_h_serve_closed64")["traffic"]
    assert spoilt["median"] < \
        cell["reference_limits"]["logit_error_median"] / 10


def test_named_reader_reads_nothing_where_there_is_nothing(tmp_path,
                                                           back_to_cwd):
    from benchmark.readers import trace_scope_named_ms
    os.chdir(tmp_path)
    with open(os.path.join(harness.BENCH_DIR, "layer_metrics",
                           "ssm_ms.json")) as f:
        args = json.load(f)["args"]
    assert trace_scope_named_ms.read({}, args) is None
    assert trace_scope_named_ms.read({"trace": {"busy_s": 1}}, args) is None
