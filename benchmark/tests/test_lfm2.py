"""The `serve_closed_patterned` runner and the LFM2 cell on the CPU at a
small size: the runner end to end (paths, arguments, control flow; no
number it produces is a device number), the configuration file's
arithmetic, the fixed schedule, the resolution of the cell and of every
metric that lists it, and what the comparison sees: it passes the program and
fails each term spoilt in the REFERENCE (`reference_lfm2.FAULTS`) and, in
the PROGRAM, a convolution tail not zeroed at a first chunk."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops_lfm2, harness, reference_lfm2
from benchmark.runners import serve_closed_patterned as runner
from benchmark.runners import serve_closed_window as window
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models import ssm as ssm_mod
from distributed_pytorch_tpu.models.gpt import LLM

CELL = "lfm2moe_serve_closed128"
# small widths, the cell's pattern in little
TINY = dict(
    vocab_size=512, block_size=4096, n_embd=64, n_layer=8,
    layer_pattern="CF*ECECE", pos_emb="rope", rope_theta=1e6,
    rope_pairing="half", qk_norm=True,
    tie_head=True, attn="gqa", n_head=4, n_kv_heads=2, head_dim=16,
    attn_bias=False, non_linearity="swiglu", up_dim=48, dense_up_dim=160,
    n_exp=8, n_shared=0, n_act=3, router="sigmoid", routed_scale=1.0,
    conv_len=3)
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_patterned", "clients": 3,
           "prompt_len": [4, 24], "output_len": [4, 12],
           "schedule_seed": 45,
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 64, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference": "reference_lfm2", "flops": "flops_lfm2",
           "tree_conditioning": ["balance_router_bias"],
           "calibration_shape": [4, 32],
           "reference_procedures": ["engine_tokens_full_house",
                                    "cache_path", "step_programs"],
           "reference_prompt_lens": [9, 20, 16],
           "reference_new_tokens": 32, "reference_engine_tokens": 32,
           "reference_plain_steps": 3,
           # this size's two readings (float32 here): the program reads
           # 1e-6 and every token the reference's; the mildest fault
           # (fp8 experts) 0.02 by the logits
           "reference_limits": {"logit_error_median": 0.005,
                                "logit_error_sequence": 0.005,
                                "step_error_median": dict.fromkeys(
                                    "CF*E", 0.005),
                                "logit_tolerance": 0.05,
                                "token_share": 0.95, "sequence_share": 0.9,
                                "gap_cap": 1.0, "mean_gap": 0.002,
                                "repeat_share": 0.9, "echo_share": 0.2}}


@pytest.fixture
def back_to_cwd():
    cwd = os.getcwd()
    yield
    os.chdir(cwd)


def _ctx(tmp_path, seconds=2.0, seed=2 ** 31 + 12345):
    said = []
    return {"cell": {"name": "tiny_lfm2", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": seed, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def _probe_waits():
    """`step_program_rows` advances its numpy `pos` in place right behind a
    jitted call, and on the CPU a `jnp.asarray` of an aligned numpy array
    IS that array: a program still running reads the next step's positions
    (its own assert fails, one run in a few under load; PERF.md section 7).
    For the length of the `with`, the probe's results are waited for, as
    the window runner does."""
    probed = runner._probed
    return window._patched(
        _probed=lambda step: window._waited(probed(step)))


@pytest.fixture
def probe_waits():
    with _probe_waits():
        yield


def test_patterned_runner_end_to_end(tmp_path, back_to_cwd, probe_waits):
    ctx, said = _ctx(tmp_path)
    # the runner's own draw, N(0, 0.02) at 64 wide: the layers add next to
    # nothing to E[id], which meets itself in the tied head, and greedy
    # decoding echoes its input (the disease the limit is there for; at
    # the cell's widths the stream is ~1 and the own row 0.02 of it)
    ctx["traffic"]["reference_limits"] = {
        **TRAFFIC["reference_limits"], "echo_share": 1.0}
    out = runner.run(ctx)
    assert out["correct"], said
    assert len(out["compared"]) > 2 and all(
        c["ok"] for c in out["compared"]), out["compared"]
    assert "echo their input id 1.0000" in "\n".join(said)
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    c = out["observations"]["counters"]
    assert c["compiles_in_window"] == 0, said
    assert 0 < c["experts_hit_pct"] <= 100
    assert c["absent_assignments_pct"] == 0          # every expert held
    assert 0 < c["chunk_program_share_pct"] < 100
    assert c["expert_second_tiles_pct"] >= 0
    assert c["state_resets"] > 0 and c["prefix_reuse_declined"] == 0
    clock = out["observations"]["clock"]
    plain, chunk = clock["engine_step_plain_ms"], clock["engine_step_chunk_ms"]
    assert plain and chunk
    assert 0 <= len(clock["engine_step_ms"]) - len(plain) - len(chunk) <= 1
    text = "\n".join(said)
    assert "resident bytes" in text and "second tiles" in text
    assert "merged_program_share 1.0000" in text
    assert "schedule (seed 45 of the mix)" in text
    assert "step ring inside the window" in text


# ---------------------------------------------------------------------------
# the configuration, the cell, the schedule
# ---------------------------------------------------------------------------

def test_the_cell_resolves_to_the_published_widths():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, CELL)
    assert res["runner"] is runner and res["cell"]["chips"] == 1
    conf, llm = res["config"], res["config"]["llm_config"]
    cfg = LLMConfig(**llm)
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["source"] == conf["source"]
    assert set(entry["reduced"]) == set(conf["reduced"]) == set(
        conf["published"]) == {"num_hidden_layers", "layer_types",
                               "num_dense_layers"}
    # kept: published layer 0 (the leading dense layers count once) and
    # layers 2-9, two whole periods; a layer is operator + feed forward
    pub = conf["published"]["layer_types"]
    assert conf["layer_types"] == [pub[0]] + pub[2:10]
    assert len(conf["layer_types"]) == conf["num_hidden_layers"] == 9
    assert pub[2:6] == pub[6:10] == ["full_attention"] + ["conv"] * 3
    op = {"conv": "C", "full_attention": "*"}
    assert cfg.layer_pattern == "".join(
        op[k] + ("F" if i < conf["num_dense_layers"] else "E")
        for i, k in enumerate(conf["layer_types"]))
    # every width of the source, under the program's names
    assert (cfg.n_embd, cfg.dense_up_dim, cfg.up_dim) == (
        conf["hidden_size"], conf["intermediate_size"],
        conf["moe_intermediate_size"]) == (2048, 11776, 1536)
    assert (cfg.n_head, cfg.n_kv_heads, cfg.head_size) == (
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["hidden_size"] // conf["num_attention_heads"]) == (32, 8, 64)
    assert (cfg.n_routed, cfg.n_act_routed, cfg.n_shared) == (
        conf["num_experts"], conf["num_experts_per_tok"], 0) == (64, 4, 0)
    assert cfg.experts_held == () and cfg.vocab_size == conf[
        "vocab_size"] == 65536                   # nothing is shared out
    assert cfg.router == "sigmoid" and conf["use_expert_bias"] and \
        conf["norm_topk_prob"]
    assert cfg.routed_scale == conf["routed_scaling_factor"]
    assert (cfg.conv_len, cfg.norm_eps, cfg.rope_theta) == (
        conf["conv_L_cache"], conf["norm_eps"],
        conf["rope_parameters"]["rope_theta"])
    assert cfg.qk_norm and cfg.pos_emb == "rope" and cfg.tie_head \
        and cfg.recurrent and not conf["conv_bias"]
    assert cfg.block_size == conf["max_position_embeddings"]
    # the cut's arithmetic, from the shapes (ISSUE 45)
    f = flops_lfm2
    assert round(f.layer_params(llm, "C") / 1e6, 2) == 16.79
    assert round(f.layer_params(llm, "*") / 1e6, 2) == 10.49
    assert round(f.layer_params(llm, "F") / 1e6, 2) == 72.35
    assert round(f.layer_params(llm, "E") / 1e6, 2) == 604.11
    assert round(f.total_params(llm) / 1e9, 3) == 5.178
    assert "5.178B" in conf["parameters"] and "10.36 GB" in conf["parameters"]
    assert f.expert_up_bytes_per_call(llm, 1) == 12582912
    assert f.expert_down_bytes_per_call(llm, 1) == 6291456
    assert f.paged_decode_bytes_per_call(llm, 1) == 2048
    e = res["traffic"]["engine"]
    held = f.resident_bytes(llm, e["n_slots"],
                            e["n_slots"] * e["max_len"] // e["block_size"]
                            + 1, e["block_size"])
    assert held["state"] == 128 * 7 * 2 * 2048 * 2
    assert 0.60 * 16e9 < 10.85e9 < held["total"] < 10.95e9
    step = f.decode_step_bytes(llm, 128, 64, 0)
    assert round(step["experts"] / 1e9, 2) == 9.66
    assert round(step["head"] / 1e9, 2) == 0.27


def test_the_traffic_is_the_issues():
    t = harness.resolve_cell(harness.load_benchmark(), CELL)["traffic"]
    assert (t["clients"], t["prompt_len"], t["output_len"]) == (
        128, [64, 256], [256, 768])
    assert t["engine"] == {"n_slots": 128, "max_len": 1024,
                           "block_size": 128, "prefill_chunk": 256,
                           "temperature": 0.0, "prefix_cache": False}
    assert (t["compute_dtype"], t["warm_s"], t["trace_s"]) == (
        "bfloat16", 5.0, 3.0)
    assert isinstance(t["schedule_seed"], int)
    assert set(t["tree_conditioning"]) <= set(runner.CONDITIONING)
    assert set(t["reference_procedures"]) <= set(runner.PROCEDURES)
    assert t["prompt_len"][1] + t["output_len"][1] <= t["engine"]["max_len"]


def test_the_schedule_is_the_mixs_and_the_ids_are_the_seeds():
    """Two `--seed`s give the same sequence of lengths and different ids;
    every round of 128 takes the 128 spaced values of each range."""
    t = harness.resolve_cell(harness.load_benchmark(), CELL)["traffic"]
    sizes = [runner.request_sizes(t, k) for k in range(3 * 128)]
    assert sizes == [runner.request_sizes(dict(t), k)
                     for k in range(3 * 128)]
    for r in range(3):
        plens, budgets = zip(*sizes[r * 128:(r + 1) * 128])
        assert len(set(plens)) == len(set(budgets)) == 128
        assert (min(plens), max(plens)) == (64, 256)
        # the middles of 128 equal shares of 256..768: mean 512
        assert 256 <= min(budgets) <= 260 and 764 <= max(budgets) <= 768
        assert abs(sum(budgets) / 128 - 512) < 1
    assert sizes[:128] != sizes[128:256]          # a draw a round
    assert [runner.request_sizes({**t, "schedule_seed": 46}, k)
            for k in range(128)] != sizes[:128]
    a = [runner.request_ids(440010101, k, sizes[k][0], 65536)
         for k in range(4)]
    b = [runner.request_ids(2440020202, k, sizes[k][0], 65536)
         for k in range(4)]
    assert [len(x) for x in a] == [len(x) for x in b] == [
        s[0] for s in sizes[:4]]
    assert all(x != y for x, y in zip(a, b))
    assert a == [runner.request_ids(440010101, k, sizes[k][0], 65536)
                 for k in range(4)]


def test_every_lfm2_metric_resolves_on_an_accepted_reader():
    bench = harness.load_benchmark()
    # the entries that LIST the cell, whatever their names: a reading it
    # shares with other cells is one entry over all of them
    mine = harness.metrics_of_cell(bench, "per_layer", CELL)
    # (29 since PR 57 listed the cell in `idle_stalled_pct.serve`; the
    # length of `per_layer` is held in one place, test_resolution.py)
    assert len(mine) == 29 and {"kv_update_ms.lfm2", "engine_step_mean_ms",
                                "stall_share_pct.serve",
                                "idle_stalled_pct.serve",
                                "experts_hit_pct.load"} <= {
        m["name"] for m in mine}
    accepted = {"counter", "client_clock", "trace_scope_ms",
                "trace_scope_named_ms", "trace_roofline_pct",
                "trace_idle_pct", "trace_idle_owner", "trace_span_ms",
                "flight_stalls", "trace_idle_stalled_pct"}
    for m in mine:
        spec, reader = harness.load_layer_metric(m["name"])
        assert spec["reader"] in accepted \
            and "serve_closed_patterned" in spec["kinds"]
        assert reader.read({}, spec.get("args", {})) is None
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == (
            m["name"] != "train_tokens_per_s")
    # a roofline's bytes reach its reader under the name the runner writes
    work = {harness.load_layer_metric(m["name"])[0]["args"]["work_per_call"]
            for m in mine if "_roofline" in m["name"]}
    assert work == {"expert_up_bytes_per_call", "expert_down_bytes_per_call",
                    "paged_decode_bytes_per_call"}
    # one set of names, so the slice is reduced once for all of them
    names = {json.dumps(harness.load_layer_metric(m["name"])[0]["args"]
                        ["names"]) for m in mine
             if harness.load_layer_metric(m["name"])[0]["reader"]
             == "trace_scope_named_ms"}
    assert len(names) == 1


def test_flops_count_the_tree():
    """`total_params` from shapes = the leaves of the program's tree."""
    cfg = LLMConfig(**TINY)
    shapes = jax.eval_shape(
        lambda k: LLM(cfg).init({"params": k}, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert flops_lfm2.total_params(TINY) == leaves


# ---------------------------------------------------------------------------
# what the comparison sees
# ---------------------------------------------------------------------------

def _big_init(variables):
    """Weights a few times the cell's N(0, 0.02) draw, so that logits at 64
    wide spread as the cell's do at 2048."""
    return jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, variables)


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    """The tree as the runner makes it: drawn, the routers' bias set."""
    cfg = LLMConfig(**TINY)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = _big_init(model.init(
        {"params": jax.random.PRNGKey(7)}, jnp.zeros((1, 8), jnp.int32)))
    ctx, _ = _ctx(tmp_path_factory.mktemp("w"))
    with jax.default_matmul_precision("highest"):
        params = runner.balance_router_bias(dict(variables["params"]), TINY,
                                            ctx)
    return cfg, model, variables, {"params": params}


def test_the_bias_is_minus_the_mean_score(setup):
    """`balance_router_bias` replaces every `gate_bias` and nothing else;
    with it the calibration tokens' top 3 spread over all 8 experts."""
    cfg, model, drawn, variables = setup
    before, after = drawn["params"], variables["params"]
    for i, kind in enumerate(cfg.layer_pattern):
        if kind != "E":
            assert after[f"block_{i}"] is before[f"block_{i}"]
            continue
        b = after[f"block_{i}"]["moe"]["gate_bias"]
        assert b.shape == (8,) and abs(float(b.mean())) < 1e-6
        assert float(jnp.abs(b).max()) > 1e-3
        assert all(after[f"block_{i}"]["moe"][k]
                   is before[f"block_{i}"]["moe"][k]
                   for k in ("gate", "experts_up", "experts_down"))


@pytest.fixture(scope="module")
def driven(setup, tmp_path_factory):
    """ONE drive of the engine's two step programs, judged by the sound
    reference and by every spoilt one."""
    cfg, model, _, variables = setup
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path_factory.mktemp("d"))
    with _probe_waits(), jax.default_matmul_precision("highest"):
        return runner.step_program_rows(ctx, eng, TINY, 512)


def _check(model, variables, tmp_path, faults=(), made=None):
    """The three procedures of `correct`, as the runner applies them."""
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    with jax.default_matmul_precision("highest"):
        tokens = runner.reference_check(ctx, eng, TINY, variables, 512,
                                        faults)
        logits = runner.cache_path_check(ctx, model, TINY, variables, 512,
                                         faults)
        layers = runner.step_programs_check(ctx, eng, TINY, variables, 512,
                                            faults, made=made)
    return {"ok": tokens["ok"] and logits["ok"] and layers["ok"],
            "tokens": tokens, "logits": logits, "layers": layers}


def test_the_program_passes(setup, driven, tmp_path):
    cfg, model, _, variables = setup
    res = _check(model, variables, tmp_path, made=driven)
    assert res["ok"] and res["tokens"]["tokens"] == 5 * 32, res
    assert res["tokens"]["repeat_share"] == 1.0, res["tokens"]
    assert res["tokens"]["echo_share"] < 0.1, res["tokens"]
    assert res["logits"]["positions"] == 4 * 32
    assert res["logits"]["median"] < 1e-4, res     # float32 here
    steps = res["layers"]
    assert len(steps["by_block"]) == 8 and max(
        e for b in steps["by_block"] for e in b.values()) < 1e-4, res
    # the engine's own two programs at its own sizes: a chunk-carrying one
    # a slot, plain ones beside a dead slot and with every slot live
    assert steps["programs"] == {"chunk": 5, "plain": 6}, steps
    assert steps["rows"]["decode"] == 3 * 4 + 3 * 5 + sum(
        (1, 3, 4)), steps          # + beside the judged slots' chunks


@pytest.mark.parametrize("fault", reference_lfm2.FAULTS)
def test_a_spoilt_reference_fails(setup, driven, tmp_path, fault):
    """Each by at least one limit: all by the logits through the cache
    and, in the kind of block the term lives in and in no other, block by
    block inside the engine's step programs, a chunk's rows and the decode
    rows alike."""
    cfg, model, _, variables = setup
    res = _check(model, variables, tmp_path, (fault,), made=driven)
    assert not res["ok"] and not res["logits"]["ok"], res
    assert not res["layers"]["ok"], res
    kinds = {"fp8_experts": "E", "bias_in_weights": "E", "no_renorm": "E",
             "conv_tap_dropped": "C", "no_c_gate": "C",
             "dense_silu_on_w3": "F", "fp8_mixers": "CF*"}.get(fault, "*")
    for k, by_form in res["layers"]["by_kind"].items():
        for form, err in by_form.items():
            assert (err > 0.005) == (k in kinds), (fault, res["layers"])


def test_a_tail_not_zeroed_fails(setup, tmp_path, monkeypatch, probe_waits):
    """The PROGRAM's fault: a first chunk that starts from what the slot's
    last occupant left. The sequences that went into a used slot read
    wrong; the first, into a fresh one, reads right."""
    cfg, model, _, variables = setup
    monkeypatch.setattr(
        ssm_mod, "chunk_start",
        lambda leaf, slot, pos: jax.lax.dynamic_index_in_dim(leaf, slot, 0))
    runner._path_prefill.clear_cache()      # traced with the true one
    try:
        res = _check(model, variables, tmp_path)
    finally:
        runner._path_prefill.clear_cache()
    assert not res["logits"]["ok"], res
    first, *later = res["logits"]["by_sequence"]
    assert first < 1e-4 and min(later) > 0.005, res
    # two rows of a chunk read wrong: the median over its rows is blind
    assert res["layers"]["ok"], res
