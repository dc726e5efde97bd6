"""The `serve_closed_window` runner and the Laguna cell on the CPU at a small
size: the runner end to end (paths, arguments, control flow; no number it
produces is a device number), the configuration file's arithmetic against
the tree and a hand count, the fixed schedule, the resolution of the cell
and of every metric that lists it, and what the comparison sees: it passes the
program and fails each term spoilt in the REFERENCE
(`reference_laguna.FAULTS`)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops_laguna, harness, reference_laguna
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners import serve_closed_window as runner
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.engine.decode import _causal_pairs
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.ops import rope

CELL = "laguna_serve_closed64_long"
# small widths, the cell's pattern in little; a window (20) that is no
# multiple of the block (8)
TINY = dict(
    vocab_size=512, block_size=1 << 20, n_embd=64, n_layer=8,
    layer_pattern="*FWEWE*E", pos_emb="rope", rope_theta=5e5,
    rope_pairing="half", rotary_frac=0.5,
    rope_factor=128.0, rope_original_len=64,
    rope_attn_factor=1.4852030263919618, attn_gate=True, window=20,
    window_heads=6, window_rope_theta=1e4, norm_eps=1e-6, tie_head=False,
    attn="gqa", n_head=4, n_kv_heads=2, head_dim=32, attn_bias=False,
    non_linearity="swiglu", up_dim=32, dense_up_dim=160, shared_up_dim=32,
    n_exp=9, n_shared=1, n_act=4, router="sigmoid", routed_scale=2.5)
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_window", "clients": 3,
           "prompt_len": [20, 60], "output_len": [4, 12],
           "schedule_seed": 49,
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 128, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference": "reference_laguna", "flops": "flops_laguna",
           "tree_conditioning": ["balance_router_bias"],
           "calibration_shape": [4, 32],
           "reference_procedures": ["engine_tokens_full_house",
                                    "cache_path", "step_programs"],
           # across the window (20), a ring's wrap (24), two chunks (16)
           "reference_prompt_lens": [22, 50, 37],
           "reference_new_tokens": 32, "reference_engine_tokens": 32,
           "reference_plain_steps": 3,
           "reference_limits": {"logit_error_median": 0.005,
                                "logit_error_sequence": 0.005,
                                "step_error_median": dict.fromkeys(
                                    "*WFE", 0.005),
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
    return {"cell": {"name": "tiny_laguna", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": seed, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_window_runner_end_to_end(tmp_path, back_to_cwd):
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
    assert 0 < c["chunk_program_share_pct"] < 100
    assert c["kv_rows_read_window"] > 0 and c["window_rows_saved"] > 0
    assert c["kv_rows_read_full"] > c["kv_rows_read_window"] * 2 / 2
    assert 0 < c["window_rows_saved_pct"] < 100
    assert c["state_resets"] == 0           # a ring is masked, not zeroed
    text = "\n".join(said)
    assert "resident bytes by kind of state" in text
    assert "'window': " + str(2 * 2 * 5 * 24 * 128 * 4) in text
    assert "declined []" in text and "schedule (seed 49 of the mix)" in text
    assert "fell back to paged_gather" in text
    assert "stalled turns over the process's life" in text
    # the accepted runner is as it was when the run is over
    assert base._MIXER_MODULES.get("W") is None
    assert base.GraniteCounts.__name__ == "GraniteCounts"


@pytest.mark.parametrize("name,moved", [
    ("_probed", lambda step, donate=False: None),
    ("_drive", lambda ctx, engine, timed, vocab, records: None),
    ("_MIXER_MODULES", {"*": "attention"}),
    ("GraniteCounts", type("Counts", (), {"FIELDS": ()})),
])
def test_a_moved_name_of_the_accepted_runner_is_told(monkeypatch, name,
                                                     moved):
    """The thin runner replaces four private names of
    `serve_closed_patterned` for a run: one that changed shape fails the
    run before it starts, by name."""
    runner._check_base()
    monkeypatch.setattr(base, name, moved)
    with pytest.raises(AssertionError):
        runner._check_base()


def test_kernel_work_over_a_slice():
    """What one call of each attention kernel had to do, from the growth
    of the engine's counts over a slice, against a hand count."""
    conf = harness.resolve_cell(harness.load_benchmark(), CELL)["config"]
    llm = conf["llm_config"]
    # 10 programs, 4 with a chunk: three whole ones at offsets 0, 5,120
    # and 12,288 and a prompt's last, 200 rows behind 13,312 (the pairs
    # are the engine's count of REAL rows: `_causal_pairs`); 64 slots of
    # 8,000 rows decoding in every program
    chunks = [(0, 1024), (5120, 1024), (12288, 1024), (13312, 200)]
    pairs = sum(_causal_pairs(o, t) for o, t in chunks)
    assert pairs == sum(o + i + 1 for o, t in chunks for i in range(t))
    wpairs = sum(_causal_pairs(o, t, 512) for o, t in chunks)
    # a prompt's first chunk fills its window row by row, the rest see 512
    assert wpairs == 512 * 513 // 2 + (512 + 2 * 1024 + 200) * 512
    keys = sum(o + t for o, t in chunks)
    sl = {"n_steps": 10, "chunk_programs": 4,
          "kv_rows_read_window_by.decode": 3 * 10 * 64 * 512,
          "kv_rows_read_full_by.chunk": 2 * keys,
          "kv_rows_read_window_by.chunk": 3 * sum(511 + t
                                                  for _, t in chunks),
          "chunk_attn_pairs_by.full": 2 * pairs,
          "chunk_attn_pairs_by.window": 3 * wpairs}
    w = runner.kernel_work(sl, llm, flops_laguna, 1024, 2)
    assert w["window_decode_bytes_per_call"] == 64 * 512 * 4096
    assert w["paged_prefill_ops_per_call"] == 4 * 48 * 128 * pairs / 4
    assert w["window_prefill_ops_per_call"] == 4 * 72 * 128 * wpairs / 4
    # every call counted as 1,024 rows at the mean offset, as the first
    # cut counted, reads the partial chunk at five times its work
    assert 4 * 1024 * 48 * 128 * (keys / 4 - 512) > \
        1.4 * w["paged_prefill_ops_per_call"]
    assert w["paged_prefill_bytes_per_call"] == \
        keys / 4 * 4096 + 2 * 1024 * 48 * 128 * 2
    grew = {"window_rows_saved": 900, "kv_rows_read_full_by.chunk": 10,
            "kv_rows_read_full_by.decode": 990,
            "kv_rows_read_window_by.chunk": 40,
            "kv_rows_read_window_by.decode": 60}
    assert runner.window_counters(grew, llm) == {
        "kv_rows_read_full": 1000, "kv_rows_read_window": 100,
        "window_rows_saved": 900, "window_rows_saved_pct": 90.0}


# ---------------------------------------------------------------------------
# the configuration, the cell, the schedule
# ---------------------------------------------------------------------------

def test_the_cell_resolves_to_the_published_widths():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, CELL)
    assert res["runner"] is runner and res["cell"]["chips"] == 1
    conf, llm = res["config"], res["config"]["llm_config"]
    cfg = LLMConfig(**llm)
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-s-2.1")
    assert entry["source"] == conf["source"]
    assert set(entry["reduced"]) == set(conf["reduced"]) == set(
        conf["published"]) == {
            "num_hidden_layers", "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer", "gating_types", "num_experts",
            "vocab_size"}
    pub = conf["published"]
    # kept: published layers 0-4, the leading dense layer and one whole
    # period; a layer is attention + feed forward
    assert conf["num_hidden_layers"] == 5 and pub["num_hidden_layers"] == 48
    for k in ("layer_types", "mlp_layer_types", "gating_types",
              "num_attention_heads_per_layer"):
        assert conf[k] == pub[k][:5] and len(pub[k]) == 48
    kinds = {"full_attention": "*", "sliding_attention": "W",
             "dense": "F", "sparse": "E"}
    assert cfg.layer_pattern == "".join(
        kinds[a] + kinds[m] for a, m in zip(conf["layer_types"],
                                            conf["mlp_layer_types"]))
    # every width as published
    assert cfg.n_embd == conf["hidden_size"] == 3072
    assert cfg.head_size == conf["head_dim"] == 128
    assert cfg.n_kv_heads == conf["num_key_value_heads"] == 8
    assert [cfg.window_heads if t == "sliding_attention" else cfg.n_head
            for t in conf["layer_types"]] == \
        conf["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert cfg.window == conf["sliding_window"] == 512
    assert cfg.dense_up_dim == conf["intermediate_size"] == 12288
    assert cfg.up_dim == conf["moe_intermediate_size"] == 1024
    assert cfg.shared_up_dim == conf["shared_expert_intermediate_size"]
    assert cfg.n_act_routed == conf["num_experts_per_tok"] == 10
    assert cfg.n_routed == pub["num_experts"] == 256      # router's width
    assert tuple(cfg.experts_held) == (0, conf["num_experts"]) == (0, 32)
    assert cfg.routed_scale == conf["moe_routed_scaling_factor"] == 2.5
    assert cfg.vocab_size == conf["vocab_size"] == pub["vocab_size"] // 8
    assert cfg.norm_eps == conf["rms_norm_eps"] and not cfg.tie_head \
        and not conf["tie_word_embeddings"] and not cfg.attn_bias
    assert cfg.block_size == conf["max_position_embeddings"]
    full = conf["rope_parameters"]["full_attention"]
    slide = conf["rope_parameters"]["sliding_attention"]
    assert (cfg.rope_theta, cfg.rope_factor, cfg.rope_original_len,
            rope.YARN_BETA, cfg.rope_attn_factor, cfg.rotary_frac) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"],
        (full["beta_fast"], full["beta_slow"]), full["attention_factor"],
        full["partial_rotary_factor"])
    assert full["rope_type"] == "yarn" and cfg.rope_factor > 1
    assert (cfg.window_rope_theta, slide["rope_type"],
            slide["partial_rotary_factor"]) == (slide["rope_theta"],
                                                "default", 1)
    assert cfg.attn_gate and conf["gating"] == "per-head"
    assert cfg.slot_state == "window layers" and not cfg.recurrent
    assert len(conf["assumed"]) >= 8 and "48 chips" in conf["deployment"]


def test_the_cuts_arithmetic_is_the_issues():
    res = harness.resolve_cell(harness.load_benchmark(), CELL)
    conf, llm = res["config"], res["config"]["llm_config"]
    f = flops_laguna
    assert f.layer_params(llm, "*") - 3072 == 44187648
    assert f.layer_params(llm, "W") - 3072 == 63135744
    assert f.layer_params(llm, "F") - 3072 == 113246208
    assert f.layer_params(llm, "E") - 3072 == 312213504
    assert f.total_params(llm) == 1716986880
    assert "1,716,986,880" in conf["parameters"] \
        and "3.43 GB" in conf["parameters"]
    # bytes against a hand count
    assert f.kv_bytes_per_row(llm) == 8 * 128 * 2 * 2 == 4096
    assert f.expert_up_bytes_per_call(llm, 1) == 2 * 1024 * 3072 * 2
    assert f.expert_down_bytes_per_call(llm, 1) == 1024 * 3072 * 2
    assert f.paged_decode_bytes_per_call(llm, 1000) == 4096000
    assert f.window_decode_bytes_per_call(llm, 64 * 512) == 64 * 512 * 4096
    assert f.chunk_attention_ops(llm, "W", 1024 * 512) == \
        4 * 1024 * 72 * 128 * 512
    e = res["traffic"]["engine"]
    # 64 x 128 blocks and the null block, rounded up to a multiple of 8
    # as the engine rounds (engine/decode.py)
    assert -(-(e["n_slots"] * e["max_len"] // e["block_size"] + 1) // 8) \
        * 8 == 8200
    held = f.resident_bytes(llm, e["n_slots"], 8200, e["block_size"])
    assert held["kv_pools"] == 2 * 8200 * 128 * 4096          # 8.60 GB
    assert held["window_rings"] == 3 * 64 * 512 * 4096        # 0.40 GB
    assert 12e9 < held["total"] < 14e9 and held["total"] > 0.25 * 16e9
    # a window layer's bytes do not know max_len
    longer = f.resident_bytes(llm, 64, 4 * 8200, 128)
    assert longer["window_rings"] == held["window_rings"]
    step = f.decode_step_bytes(llm, 64, 0.92 * 32, 64 * 7900)
    assert round(step["attention_full"] / 1e9, 1) == 4.3
    assert round(step["attention_window"] / 1e9, 2) == 0.78
    assert round(step["experts"] / 1e9, 1) == 2.2
    share = (step["attention_full"] + step["attention_window"]) \
        / step["total"]
    assert 0.5 < share < 0.7


def test_flops_count_the_tree():
    """`total_params` from shapes = the leaves of the program's tree, less
    the routers' selection bias (a float32 buffer)."""
    cfg = LLMConfig(**TINY)
    shapes = jax.eval_shape(
        lambda k: LLM(cfg).init({"params": k}, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    bias = TINY["layer_pattern"].count("E") * (TINY["n_exp"]
                                               - TINY["n_shared"])
    assert flops_laguna.total_params(TINY) == leaves - bias
    # and at the cell's size, by shapes alone
    llm = harness.resolve_cell(harness.load_benchmark(),
                               CELL)["config"]["llm_config"]
    big = LLMConfig(**llm)
    shapes = jax.eval_shape(
        lambda k: LLM(big, param_dtype=jnp.bfloat16).init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert leaves - 4 * 256 == 1716986880


def test_the_traffic_is_the_issues():
    t = harness.resolve_cell(harness.load_benchmark(), CELL)["traffic"]
    assert (t["clients"], t["prompt_len"], t["output_len"]) == (
        64, [512, 14336], [512, 1536])
    assert t["engine"] == {"n_slots": 64, "max_len": 16384,
                           "block_size": 128, "prefill_chunk": 1024,
                           "temperature": 0.0, "prefix_cache": False}
    assert (t["compute_dtype"], t["warm_s"], t["trace_s"],
            t["schedule_seed"]) == ("bfloat16", 12.0, 3.0, 49)
    assert set(t["tree_conditioning"]) <= set(base.CONDITIONING)
    assert set(t["reference_procedures"]) <= set(base.PROCEDURES)
    assert t["prompt_len"][1] + t["output_len"][1] <= t["engine"]["max_len"]
    # the judged prompts cross the window, a ring's wrap and two chunks
    lens = sorted(t["reference_prompt_lens"])
    assert 512 < lens[0] and lens[1] > 1024 and lens[2] > 2048
    assert set(t["reference_limits"]["step_error_median"]) == set("*WFE")
    sizes = [base.request_sizes(t, k) for k in range(2 * 64)]
    for r in range(2):
        plens, budgets = zip(*sizes[r * 64:(r + 1) * 64])
        assert len(set(plens)) == len(set(budgets)) == 64
        assert min(plens) >= 512 and max(plens) <= 14336
        assert max(p + b for p, b in sizes) <= 16384


def test_every_laguna_metric_resolves_on_an_accepted_reader():
    bench = harness.load_benchmark()
    # the cell's metrics are the entries that LIST it, whatever their names:
    # a reading it shares with other cells is one entry over all of them.
    # 27 + 2 of issue 49's 32 (PERF.md section 3 names the three left out)
    # + PR 57's `idle_stalled_pct.serve`; the length of `per_layer` is held
    # in one place, test_resolution.py
    mine = harness.metrics_of_cell(bench, "per_layer", CELL)
    assert len(mine) == 27 + 2 + 1
    assert {"stall_share_pct.serve", "stall_max_ms.serve",
            "batch_occupancy_pct", "engine_step_mean_ms",
            "expert_second_tiles_pct", "idle_stalled_pct.serve",
            "experts_hit_pct.load"} <= {m["name"] for m in mine}
    accepted = {"counter", "client_clock", "trace_scope_ms",
                "trace_scope_named_ms", "trace_roofline_pct",
                "trace_idle_pct", "trace_idle_owner", "trace_span_ms",
                "flight_stalls", "trace_idle_stalled_pct"}
    for m in mine:
        spec, reader = harness.load_layer_metric(m["name"])
        assert spec["reader"] in accepted \
            and "serve_closed_window" in spec["kinds"]
        assert reader.read({}, spec.get("args", {})) is None
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == (
            m["name"] != "train_tokens_per_s")
    # a roofline's work reaches its reader under the name a runner writes
    work = {harness.load_layer_metric(m["name"])[0]["args"]["work_per_call"]
            for m in mine if "_roofline" in m["name"]}
    assert work == {"expert_up_bytes_per_call", "expert_down_bytes_per_call",
                    "paged_decode_bytes_per_call",
                    "window_decode_bytes_per_call",
                    "paged_prefill_ops_per_call",
                    "window_prefill_ops_per_call"}
    names = {json.dumps(harness.load_layer_metric(m["name"])[0]["args"]
                        ["names"]) for m in mine
             if harness.load_layer_metric(m["name"])[0]["reader"]
             == "trace_scope_named_ms"}
    assert len(names) == 1
    from distributed_pytorch_tpu.obs.trace import MIXER_SCOPES
    assert {"attn_window", "kv_update_window", "attn_gate"} <= set(
        MIXER_SCOPES) & set(json.loads(names.pop()))


# ---------------------------------------------------------------------------
# what the comparison sees
# ---------------------------------------------------------------------------

def _big_init(variables):
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
        params = base.balance_router_bias(dict(variables["params"]), TINY,
                                          ctx)
    return cfg, model, {"params": params}


@pytest.fixture(scope="module")
def driven(setup, tmp_path_factory):
    """ONE drive of the engine's two step programs, judged by the sound
    reference and by every spoilt one."""
    cfg, model, variables = setup
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path_factory.mktemp("d"))
    probed = base._probed
    with runner._patched(_MIXER_MODULES={**base._MIXER_MODULES,
                                         "W": "attn"},
                         _probed=lambda step: runner._waited(probed(step))), \
            jax.default_matmul_precision("highest"):
        return base.step_program_rows(ctx, eng, TINY, 512)


def _check(model, variables, tmp_path, faults=(), made=None):
    """The logits through the cache and the step programs' blocks, as the
    runner applies them."""
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    with jax.default_matmul_precision("highest"):
        logits = base.cache_path_check(ctx, model, TINY, variables, 512,
                                       faults)
        layers = base.step_programs_check(ctx, eng, TINY, variables, 512,
                                          faults, made=made)
    return {"ok": logits["ok"] and layers["ok"], "logits": logits,
            "layers": layers}


def test_the_program_passes(setup, driven, tmp_path):
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, made=driven)
    assert res["ok"], res
    assert res["logits"]["positions"] == 4 * 32
    assert res["logits"]["median"] < 1e-4, res     # float32 here
    steps = res["layers"]
    assert len(steps["by_block"]) == 8 and max(
        e for b in steps["by_block"] for e in b.values()) < 1e-4, res
    assert set(steps["by_kind"]) == set("*WFE")


@pytest.mark.parametrize("fault", reference_laguna.FAULTS)
def test_a_spoilt_reference_fails(setup, driven, tmp_path, fault):
    """Each by the logits through the cache (prompts across the window and
    a wrap) and, block by block inside the engine's step programs, in the
    kind of block the term lives in and in no other."""
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, (fault,), made=driven)
    assert not res["ok"], res
    if fault.startswith("window_"):
        # the step programs' chunks are 16 rows here, inside the window
        # (20; the fault's 448 is wider still): the logits through the
        # cache, whose prompts cross it, are what sees these two
        assert not res["logits"]["ok"] and res["layers"]["ok"], res
        return
    kinds = {"rope_swapped": "*W",
             "rotate_all_lanes": "*", "yarn_factor_1": "*",
             "attn_factor_1": "*", "no_gate": "*W", "gate_scalar": "*W",
             "fp8_attention": "*W", "fp8_dense": "F", "fp8_experts": "E",
             "no_renorm": "E",
             "no_routed_scale": "E", "no_shared": "E"}[fault]
    for k, by_form in res["layers"]["by_kind"].items():
        assert (max(by_form.values()) > 0.005) == (k in kinds), (
            fault, res["layers"]["by_kind"])
    if fault not in ("yarn_factor_1",):
        assert not res["logits"]["ok"], res
