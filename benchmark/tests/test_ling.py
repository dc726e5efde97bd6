"""The `serve_closed_delta` runner and the Ling-3.0-flash-VL cell on the CPU
at a small size: the runner end to end (paths, arguments, control flow; no
number it produces is a device number), the configuration file's arithmetic
against the issue's, the fixed schedule, the resolution of the cell and of
every metric that lists it, the flops module's formulas, and what the
comparison sees: it passes the program and fails each term spoilt in the
REFERENCE (`reference_ling.FAULTS`), in the kind of block the term lives in.
Every width is a small stand-in, every RATIO kept: 5 'K' layers to 1 'L',
d_k = d_v, 8 groups of which the top 4 are kept, top 8, one group held."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops_ling, harness, reference_ling
from benchmark.runners import serve_closed_delta as runner
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners import serve_closed_window as window
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM

CELL = "ling3_flash_serve_closed192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    vocab_size=512, block_size=1 << 17, n_embd=64, n_layer=14,
    layer_pattern="KFKEKEKELEKEKE", pos_emb="rope", rope_theta=6e6,
    rope_pairing="adjacent", norm_eps=1e-6, tie_head=False, attn="mla",
    n_head=4, q_latent_dim=0, kv_latent_dim=32, rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=24, dense_up_dim=96, shared_up_dim=24,
    n_exp=65, n_shared=1, n_act=9, router="sigmoid", routed_scale=2.5,
    n_group=8, topk_group=4, experts_held=[0, 8],
    kda_heads=4, kda_head_dim=16, kda_conv=4, kda_lower_bound=-5.0)
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_delta", "clients": 3,
           "prompt_len": [20, 60], "output_len": [4, 12],
           "schedule_seed": 62,
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 128, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference": "reference_ling", "flops": "flops_ling",
           "tree_conditioning": ["balance_router_bias"],
           "calibration_shape": [4, 32],
           "reference_procedures": ["engine_tokens_full_house",
                                    "cache_path", "step_programs",
                                    "slot_state"],
           # inside one chunk (16), across two, two chunks and a half
           "reference_prompt_lens": [12, 27, 40],
           "reference_new_tokens": 32, "reference_engine_tokens": 32,
           "reference_plain_steps": 3,
           "reference_limits": {"logit_error_median": 0.005,
                                "logit_error_sequence": 0.005,
                                "step_error_median": dict.fromkeys(
                                    "KLFE", 0.0005),
                                "state_error": {"K": 1e-5},
                                "logit_tolerance": 0.05,
                                "token_share": 0.95, "sequence_share": 0.9,
                                "gap_cap": 1.0, "mean_gap": 0.002,
                                "repeat_share": 0.9, "echo_share": 1.0}}


@pytest.fixture
def back_to_cwd():
    cwd = os.getcwd()
    yield
    os.chdir(cwd)


def _ctx(tmp_path, seconds=2.0, seed=2 ** 31 + 12345):
    said = []
    return {"cell": {"name": "tiny_ling", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": seed, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_delta_runner_end_to_end(tmp_path, back_to_cwd):
    ctx, said = _ctx(tmp_path)
    out = runner.run(ctx)
    assert out["correct"], said
    names = {c["name"] for c in out["compared"]}
    assert {f"step_error.{k}.{form}" for k in "KLFE"
            for form in ("chunk", "decode")} <= names
    assert {"logit_error_median", "token_share", "state_error.K"} <= names
    assert all(c["ok"] for c in out["compared"]), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    c = out["observations"]["counters"]
    assert c["compiles_in_window"] == 0, said
    assert 0 < c["chunk_program_share_pct"] < 100
    # six 'K' layers step the same slots, one 'L' layer reads the rows
    assert c["kda_slot_steps"] > 0 and c["kda_slot_steps"] % 6 == 0
    assert c["latent_rows_read"] > 0 and c["chunk_attn_pairs"] > 0
    assert c["state_resets"] > 0 and 0 < c["experts_hit_pct"] <= 100
    text = "\n".join(said)
    # 6 layers x 5 slots x 4 heads x 16 x 16 float32; x 3 rows x 192 lanes;
    # 1 layer x (5 x 16 + 8 = 88 blocks) x 8 rows x 128 lanes x float32
    assert "resident bytes by kind: " in text
    assert "'kda_state': " + str(6 * 5 * 4 * 16 * 16 * 4) in text
    assert "'kda_tails': " + str(6 * 5 * 3 * 192 * 4) in text
    assert "'latent_pools': " + str(88 * 8 * 128 * 4) in text
    assert "declined []" in text and "schedule (seed 62 of the mix)" in text
    assert "the mixers in the window" in text and "the first wave" in text
    assert "'kda_step': 'xla (kda_step_kernel_decline: the cpu backend" \
        in text and "'kda_chunk': 'xla_wy" in text
    # the accepted runner is as it was when the run is over
    assert base._MIXER_MODULES.get("K") is None
    assert base.GraniteCounts.__name__ == "GraniteCounts"


def test_the_cell_is_the_issues():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, CELL)
    assert res["runner"] is runner and res["cell"]["chips"] == 1
    conf, t = res["config"], res["traffic"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling-3.0-flash-vl")
    assert entry["source"] == conf["source"] and set(entry["reduced"]) == \
        set(conf["reduced"]) == {"num_hidden_layers", "num_experts",
                                 "vocab_size"}
    assert (t["clients"], t["prompt_len"], t["output_len"],
            t["schedule_seed"]) == (192, [256, 1792], [512, 3584], 62)
    assert t["engine"] == {"n_slots": 192, "max_len": 5376,
                           "block_size": 128, "prefill_chunk": 256,
                           "temperature": 0.0, "prefix_cache": False}
    assert (t["compute_dtype"], t["trace_s"]) == ("bfloat16", 3.0)
    assert t["warm_s"] == int(t["warm_s"])
    assert t["tree_conditioning"] == ["balance_router_bias"]
    assert t["reference_procedures"] == ["engine_tokens_full_house",
                                         "cache_path", "step_programs",
                                         "slot_state"]
    assert set(t["reference_limits"]["state_error"]) == {"K"}
    # inside one chunk of 256, across two, across three
    assert t["reference_prompt_lens"] == [200, 300, 600]
    assert set(t["reference_limits"]["step_error_median"]) == set("KLFE")
    assert t["prompt_len"][1] + t["output_len"][1] == t["engine"]["max_len"]
    sizes = [base.request_sizes(t, k) for k in range(2 * 192)]
    for r in range(2):
        plens, budgets = zip(*sizes[r * 192:(r + 1) * 192])
        assert len(set(plens)) == len(set(budgets)) == 192
        assert min(plens) >= 256 and max(plens) <= 1792
        assert min(budgets) >= 512 and max(budgets) <= 3584
    assert sizes == [base.request_sizes(t, k) for k in range(2 * 192)]
    # about a third of the programs carry a chunk: far from the 5% a 95th
    # percentile stands on
    chunks = sum(-(-p // 256) for p, _ in sizes[:192])
    tokens = sum(b for _, b in sizes[:192]) / 192
    assert 0.2 < chunks / (chunks + tokens) < 0.5
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == (
            m["name"] != "train_tokens_per_s")


def _row():
    return json.loads(next(ln for ln in open(CATALOG)
                           if '"Ling-3.0-flash-VL"' in ln))


@pytest.mark.parametrize("key", sorted(_row()["config"])
                         if os.path.exists(CATALOG) else [])
def test_every_published_key_is_in_the_file(key):
    """The catalog row's `config`, number for number, but for `reduced`."""
    row = _row()
    conf = harness.resolve_cell(harness.load_benchmark(), CELL)["config"]
    assert conf["source"] == row["source_url"]
    if key in conf["reduced"]:
        assert conf["published"][key] == row["config"][key] != conf[key]
    else:
        assert conf[key] == row["config"][key]


def test_the_cuts_arithmetic_is_the_issues():
    res = harness.resolve_cell(harness.load_benchmark(), CELL)
    conf = res["config"]
    llm, e = conf["llm_config"], res["traffic"]["engine"]
    f = flops_ling
    assert f.kda_params(llm) == {
        "W_qkv": 3 * 10485760, "W_a": 10485760, "W_bg": 2 * 81920,
        "W_o": 10485760, "conv_w": 49152, "A_log": 32, "dt_bias": 4096,
        "o_norm": 128}
    assert sum(f.kda_params(llm).values()) == 52646048
    assert sum(f.latent_params(llm).values()) == 31883776
    assert f.layer_params(llm, "F") == 47185920 + 2560
    assert f.expert_up_elems(llm) + f.expert_down_elems(llm) == 5898240
    # the issue's expert block counted the 512 float32 of selection bias
    assert f.layer_params(llm, "E") + 512 - 2560 == 384696832
    assert f.total_params(llm) + 6 * 512 == 2803763136
    assert "2,803,760,064" in conf["parameters"] \
        and "5.61 GB" in conf["parameters"]
    assert llm["layer_pattern"] == "KF" + "KE" * 3 + "LE" + "KE" * 2 \
        and conf["num_hidden_layers"] == 7 and conf["num_experts"] == 64
    assert conf["vocab_size"] == llm["vocab_size"] == 157184 // 8
    assert (llm["q_latent_dim"], llm["kv_latent_dim"], llm["rope_head_dim"],
            llm["qk_nope_head_dim"], llm["v_head_dim"], llm["n_head"]) == (
        0, conf["kv_lora_rank"], conf["qk_rope_head_dim"],
        conf["qk_nope_head_dim"], conf["v_head_dim"],
        conf["num_attention_heads"]) and conf["q_lora_rank"] is None
    assert (llm["kda_heads"], llm["kda_head_dim"], llm["kda_conv"],
            llm["kda_lower_bound"]) == (
        conf["num_attention_heads"], conf["head_dim"],
        conf["short_conv_kernel_size"], conf["kda_lower_bound"])
    assert llm["n_act"] - llm["n_shared"] == conf["num_experts_per_tok"] \
        and llm["n_exp"] - llm["n_shared"] == 512 \
        and (llm["n_group"], llm["topk_group"]) == (conf["n_group"],
                                                    conf["topk_group"]) \
        and llm["experts_held"] == [0, 512 // conf["n_group"]] \
        and llm["routed_scale"] == conf["routed_scaling_factor"] \
        and llm["rope_theta"] == conf["rope_theta"] \
        and llm["dense_up_dim"] == conf["intermediate_size"]
    assert any("vision tower" in c for c in conf["changed"]) \
        and any("multi-token-prediction" in c for c in conf["changed"]) \
        and any("clamp" in c for c in conf["changed"])
    assert "8 chips share each layer" in conf["deployment"]
    # a slot: six states and six tails; a row of the one latent layer
    assert f.kda_state_bytes(llm) == 32 * 128 * 128 * 4 == 2097152
    assert 6 * f.kda_state_bytes(llm) == 12582912 \
        and 6 * f.kda_tail_bytes(llm) == 442368
    assert f.latent_row_bytes(llm) == 1152 and f.pool_row_bytes(llm) == 1280
    # the rooflines' floors: the state read ONCE; a chunk's five row sets
    assert f.kda_step_bytes_per_call(llm, 192) == 192 * 2097152
    assert f.kda_chunk_bytes_per_call(llm, 256) == \
        256 * 5 * 4096 * 2 + 2097152
    assert f.latent_decode_bytes_per_call(llm, 1000) == 1152000
    assert f.chunk_attention_ops(llm, 1000) == 1000 * 32 * (192 + 128) * 2
    n_blocks = -(-(e["n_slots"] * e["max_len"] // e["block_size"] + 1)
                 // 8) * 8
    held = f.resident_bytes(llm, e["n_slots"], n_blocks, e["block_size"])
    assert n_blocks == 8072 and held["weights"] == 2 * 2803760064
    assert held["kda_state"] + held["kda_tails"] == 192 * (12582912 + 442368)
    assert held["latent_pools"] == 8072 * 128 * 1280
    assert 0.57 * 16e9 < held["total"] < 0.61 * 16e9
    step = f.decode_step_bytes(llm, 192, 0.95 * 64, 192 * 2200)
    assert 0.48 < (step["kda_state"] + step["kda_weights"]) \
        / step["total"] < 0.56
    assert 0.38 < (step["experts"] + step["routers_shared"]) \
        / step["total"] < 0.46


def test_every_ling_metric_resolves():
    bench = harness.load_benchmark()
    # the cell's metrics are the entries that LIST it: 34 = the 11 readings
    # of its own under `.ling` + 23 it shares with accepted cells, each ONE
    # entry over all of them (that no twin of a shared entry is left beside
    # it: test_resolution.py::test_no_cell_reads_one_reading_twice)
    mine = harness.metrics_of_cell(bench, "per_layer", CELL)
    own = [m["name"] for m in mine if m["name"].endswith(".ling")]
    assert len(mine) == 34 and len(own) == 11
    readers = {harness.load_layer_metric(m["name"])[0]["reader"]
               for m in bench["per_layer"] if not m["name"].endswith(".ling")}
    for m in mine:
        spec, reader = harness.load_layer_metric(m["name"])
        if m["name"] in own:
            assert m["workloads"] == [CELL]
            assert spec["kinds"] == ["serve_closed_delta"]
        else:
            assert len(m["workloads"]) > 1 and m["workloads"][-1] == CELL
            assert "serve_closed_delta" in spec["kinds"]
        assert spec["reader"] in readers, "an accepted reader"
        assert reader.read({}, spec.get("args", {})) is None
        assert (spec["unit"], spec["moves"], spec["layer"]) == (
            m["unit"], m["moves"], m["layer"])
    work = {harness.load_layer_metric(m["name"])[0]["args"]["work_per_call"]
            for m in mine if "_roofline" in m["name"]}
    assert work == {"kda_step_bytes_per_call", "kda_chunk_bytes_per_call",
                    "latent_decode_bytes_per_call",
                    "latent_prefill_ops_per_call",
                    "expert_up_bytes_per_call", "expert_down_bytes_per_call"}
    names = {json.dumps(harness.load_layer_metric(m["name"])[0]["args"]
                        ["names"]) for m in mine
             if "names" in harness.load_layer_metric(m["name"])[0]["args"]}
    assert len(names) == 1
    from distributed_pytorch_tpu.obs.trace import MIXER_MODULES, MIXER_SCOPES
    from benchmark.lib.trace_spans import SCOPE_NAMES
    named = set(json.loads(names.pop()))
    assert named <= set(MIXER_MODULES) | set(MIXER_SCOPES) | set(SCOPE_NAMES)
    assert {"kda", "kda_proj", "kda_conv", "kda_gate", "attn_kda",
            "kda_chunk", "kda_out", "route_groups", "attn_latent"} <= named


def test_kernel_work_of_the_slice():
    llm = harness.resolve_cell(harness.load_benchmark(), CELL)["config"][
        "llm_config"]
    sl = {"kda_slot_steps_by.decode": 6 * 10 * 190, "n_steps": 10,
          "kda_slot_steps_by.chunk": 6 * 4 * 250, "chunk_programs": 4,
          "latent_rows_read_by.decode": 10 * 420000,
          "chunk_attn_pairs_by.full": 4 * 300000}
    work = runner.kernel_work(sl, llm, flops_ling, 2)
    assert work == {"kda_step_bytes_per_call": 190 * 2097152,
                    "kda_chunk_bytes_per_call": 250 * 40960 + 2097152,
                    "kda_chunk_calls_per_step": 2.4,
                    "latent_decode_bytes_per_call": 420000 * 1152,
                    "latent_prefill_ops_per_call": 300000 * 20480.0}
    # a slice without a chunk-carrying program books no chunk work: the
    # reader then leaves the metric out
    none = runner.kernel_work({**sl, "chunk_programs": 0,
                               "kda_slot_steps_by.chunk": 0,
                               "chunk_attn_pairs_by.full": 0}, llm,
                              flops_ling, 2)
    assert none["kda_chunk_bytes_per_call"] == 0.0 \
        and none["kda_chunk_calls_per_step"] == 0.0


# ---------------------------------------------------------------------------
# what the comparison sees
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    """The tree as the runner makes it, the matrices a few times the draw
    so that at 64 wide every term shows over float32 rounding."""
    cfg = LLMConfig(**TINY)
    model = LLM(cfg, compute_dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a,
        model.init({"params": jax.random.PRNGKey(7)},
                   jnp.zeros((1, 8), jnp.int32))["params"])
    ctx = {"seed": 7, "traffic": TRAFFIC}
    for rule in TRAFFIC["tree_conditioning"]:
        params = base.CONDITIONING[rule](params, TINY, ctx)
    return cfg, model, {"params": params}


def _drive(setup, tmp_path):
    """(`step_program_rows`'s, the tap that probed it): ONE drive of the
    engine's two step programs through the runner's own probe."""
    cfg, model, variables = setup
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    tap = runner.StateTap(eng.n_slots)
    with window._patched(_MIXER_MODULES={**base._MIXER_MODULES,
                                         **runner._MIXER_MODULES},
                         _probed=tap.probe), \
            jax.default_matmul_precision("highest"):
        return base.step_program_rows(ctx, eng, TINY, 512), tap


@pytest.fixture(scope="module")
def driven_tap(setup, tmp_path_factory):
    return _drive(setup, tmp_path_factory.mktemp("d"))


@pytest.fixture(scope="module")
def driven(driven_tap):
    """The drive, judged by the sound reference and by every spoilt one."""
    return driven_tap[0]


def _check(model, variables, tmp_path, faults=(), made=None):
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    with jax.default_matmul_precision("highest"):
        return base.step_programs_check(ctx, eng, TINY, variables, 512,
                                        faults, made=made)


def test_the_program_passes(setup, driven, tmp_path):
    cfg, model, variables = setup
    steps = _check(model, variables, tmp_path, made=driven)
    assert steps["ok"], steps
    assert len(steps["by_block"]) == 14 and max(
        e for b in steps["by_block"] for e in b.values()) < 1e-4, steps
    assert set(steps["by_kind"]) == set("KLFE")


@pytest.mark.parametrize("fault", reference_ling.FAULTS)
def test_a_spoilt_reference_fails(setup, driven, tmp_path, fault):
    """Each block by block inside the engine's step programs, in the kind
    of block the term lives in and in no other."""
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, (fault,), made=driven)
    assert not res["ok"], res
    kinds = "K" if fault in reference_ling.KDA_FAULTS else \
        "L" if fault in reference_ling.LATENT_FAULTS else \
        "F" if fault == "fp8_dense" else "E"
    for k, by_form in res["by_kind"].items():
        assert (not max(by_form.values()) <= 0.0005) == (k in kinds), (
            fault, res["by_kind"])


@pytest.mark.parametrize("fault,ok", [((), True), (("bf16_state",), False),
                                      (("no_delta",), False)])
def test_slot_state_holds_the_leaf_to_the_literal_recurrence(
        driven_tap, tmp_path, fault, ok):
    """Over the recurrence's own operands the state of every judged slot
    (the late one too) is the reference's to float32 rounding in all six
    'K' layers; a reference that keeps its state in bfloat16 stands 2^-9
    apart, a thousand times the sound reading."""
    _, tap = driven_tap
    ctx, _ = _ctx(tmp_path)
    got = runner.slot_state_check(ctx, tap, TINY, fault)
    assert got["ok"] == ok, got
    assert sorted(got["by_slot"]) == [0, 1, 2, 4] and all(
        len(errs) == 6 for errs in got["by_slot"].values())
    # a chunk's rows and then a row a program the slot was live in
    assert all(16 < n <= 16 + 2 * 3 + 5 for n in got["rows"].values()), got
    if ok:
        assert got["worst"] < 1e-6, got
    elif fault == ("bf16_state",):
        assert 1e-3 < got["worst"] < 1e-2, got


def test_a_program_that_keeps_its_state_in_bfloat16_fails_slot_state(
        setup, tmp_path, monkeypatch):
    """The fault in the PROGRAM: both forms hand back a state rounded to
    bfloat16 (what a bf16 leaf would hold)."""
    from distributed_pytorch_tpu.ops import delta_rule

    def rounded(fn):
        def call(*a, **kw):
            o, S = fn(*a, **kw)
            return o, jax.lax.reduce_precision(S, exponent_bits=8,
                                               mantissa_bits=7)
        return call

    for name in ("kda_step", "kda_chunk"):
        monkeypatch.setattr(delta_rule, name,
                            rounded(getattr(delta_rule, name)))
    _, tap = _drive(setup, tmp_path)
    ctx, _ = _ctx(tmp_path)
    got = runner.slot_state_check(ctx, tap, TINY)
    assert not got["ok"] and 1e-3 < got["worst"] < 1e-2, got
