"""The `serve_closed_latent` runner and the JoyAI-LLM-Flash cell on the CPU at
a small size: the runner end to end (paths, arguments, control flow; no
number it produces is a device number), the configuration file's arithmetic
against the issue's, the fixed schedule, the resolution of the cell and of
every metric that lists it, the flops module's formulas, and what the
comparison sees: it passes the program and fails each term spoilt in the
REFERENCE (`reference_joyai.FAULTS`), in the kind of block the term lives
in. Every width is a small stand-in, every RATIO kept: nope : rope : value =
2 : 1 : 2, a q-latent and a kv-latent, one shared rotary key."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops_joyai, harness, reference_joyai
from benchmark.runners import serve_closed_latent as runner
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners import serve_closed_window as window
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM

CELL = "joyai_flash_serve_closed64_long"
TINY = dict(
    vocab_size=512, block_size=1 << 17, n_embd=64, n_layer=6,
    layer_pattern="LFLELE", pos_emb="rope", rope_theta=32e6,
    rope_pairing="adjacent", norm_eps=1e-6, tie_head=False, attn="mla",
    n_head=4, q_latent_dim=48, kv_latent_dim=32, rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=24, dense_up_dim=96, shared_up_dim=24,
    n_exp=17, n_shared=1, n_act=5, router="sigmoid", routed_scale=2.5,
    experts_held=[0, 8])
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_latent", "clients": 3,
           "prompt_len": [20, 60], "output_len": [4, 12],
           "schedule_seed": 59,
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 128, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference": "reference_joyai", "flops": "flops_joyai",
           "tree_conditioning": ["balance_router_bias"],
           "calibration_shape": [4, 32],
           "reference_procedures": ["engine_tokens_full_house",
                                    "cache_path", "step_programs"],
           # inside one chunk (16), across two, two chunks and a half
           "reference_prompt_lens": [12, 27, 40],
           "reference_new_tokens": 32, "reference_engine_tokens": 32,
           "reference_plain_steps": 3,
           "reference_limits": {"logit_error_median": 0.005,
                                "logit_error_sequence": 0.005,
                                "step_error_median": dict.fromkeys(
                                    "LFE", 0.005),
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
    return {"cell": {"name": "tiny_joyai", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": seed, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_latent_runner_end_to_end(tmp_path, back_to_cwd):
    ctx, said = _ctx(tmp_path)
    out = runner.run(ctx)
    assert out["correct"], said
    names = {c["name"] for c in out["compared"]}
    assert {f"step_error.{k}.{form}" for k in "LFE"
            for form in ("chunk", "decode")} <= names
    assert {"logit_error_median", "token_share"} <= names
    assert all(c["ok"] for c in out["compared"]), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    c = out["observations"]["counters"]
    assert c["compiles_in_window"] == 0, said
    assert 0 < c["chunk_program_share_pct"] < 100
    # three latent layers read the same rows
    assert c["latent_rows_read"] > 0 and c["latent_rows_read"] % 3 == 0
    assert c["chunk_attn_pairs"] > 0 and c["chunk_attn_pairs"] % 3 == 0
    assert 0 < c["experts_hit_pct"] <= 100
    text = "\n".join(said)
    assert "resident bytes by kind of state" in text
    # 3 layers x (5 x 16 + 8 = 88 blocks) x 8 rows x 128 lanes x float32
    assert "'pools': " + str(3 * 88 * 8 * 128 * 4) in text \
        and "'window': 0" in text and "'slot_state': 0" in text
    assert "the mathematics needs 160 B a row" in text    # (32 + 8) x 4
    assert "declined []" in text and "schedule (seed 59 of the mix)" in text
    assert "fell back to paged_gather" in text
    assert "latent attention in the window" in text
    # the accepted runner is as it was when the run is over
    assert base._MIXER_MODULES.get("L") is None
    assert base.GraniteCounts.__name__ == "GraniteCounts"


def test_the_cell_is_the_issues():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, CELL)
    assert res["runner"] is runner and res["cell"]["chips"] == 1
    conf, t = res["config"], res["traffic"]
    entry = next(c for c in bench["configs"] if c["name"] == "joyai-llm-flash")
    assert entry["source"] == conf["source"] and set(entry["reduced"]) == \
        set(conf["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                 "vocab_size"}
    assert (t["clients"], t["prompt_len"], t["output_len"],
            t["schedule_seed"]) == (64, [512, 14336], [512, 1536], 59)
    assert t["engine"] == {"n_slots": 64, "max_len": 16384,
                           "block_size": 128, "prefill_chunk": 1024,
                           "temperature": 0.0, "prefix_cache": False}
    assert (t["compute_dtype"], t["trace_s"]) == ("bfloat16", 3.0)
    assert t["warm_s"] == int(t["warm_s"])
    assert t["tree_conditioning"] == ["balance_router_bias"]
    assert t["reference_procedures"] == ["engine_tokens_full_house",
                                         "cache_path", "step_programs"]
    # inside one chunk of 1,024, across two, across three
    assert t["reference_prompt_lens"] == [520, 1300, 2600]
    assert set(t["reference_limits"]["step_error_median"]) == set("LFE")
    lim, said = t["reference_limits"], []
    base._say_engine_tokens(said.append, lim, {
        "share": 1.0, "shares": [1.0], "mean_gap": 0.0, "repeat_share": 1.0,
        "echo_share": 0.0, "worst_gap": 0.0, "top1_agree": 1, "tokens": 1})
    base._say_cache_path(said.append, lim, {
        "positions": 1, "median": 0.0, "by_sequence": [0.0], "worst": 0.0})
    base._say_step_programs(said.append, lim, {
        "programs": {"chunk": 1, "plain": 2}, "by_kind": {}, "rows": {},
        "judged_slots": [], "by_block": []})
    assert len(said) == 3
    assert t["prompt_len"][1] + t["output_len"][1] <= t["engine"]["max_len"]
    sizes = [base.request_sizes(t, k) for k in range(2 * 64)]
    for r in range(2):
        plens, budgets = zip(*sizes[r * 64:(r + 1) * 64])
        assert len(set(plens)) == len(set(budgets)) == 64
        assert min(plens) >= 512 and max(plens) <= 14336
        assert min(budgets) >= 512 and max(budgets) <= 1536
    assert sizes == [base.request_sizes(t, k) for k in range(2 * 64)]
    # about half of the programs carry a chunk: far from the 5% a 95th
    # percentile stands on
    chunks = sum(-(-p // 1024) for p, _ in sizes[:64])
    tokens = sum(b for _, b in sizes[:64]) / 64
    assert 0.25 < chunks / (chunks + tokens) < 0.75


@pytest.mark.parametrize("key", sorted(json.loads(next(
    ln for ln in open("/opt/skills/guides/model-configs/architectures.jsonl")
    if '"JoyAI-LLM-Flash"' in ln))["config"])
    if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl")
    else [])
def test_every_published_key_is_in_the_file(key):
    """The catalog row's `config`, number for number, but for `reduced`."""
    row = json.loads(next(
        ln for ln in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"JoyAI-LLM-Flash"' in ln))
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
    f = flops_joyai
    assert f.attention_params(llm) == {
        "W_qa": 3145728, "W_qb": 9437184, "W_kva": 1179648,
        "W_kvb": 4194304, "W_o": 8388608, "norms": 2048}
    assert sum(f.attention_params(llm).values()) == 26347520
    # the issue's layer 0 and expert layer: an attention block and its
    # feed forward, two norms between them, the bias a buffer apart
    assert f.layer_params(llm, "L") + f.layer_params(llm, "F") == 70391808
    assert f.layer_params(llm, "L") + f.layer_params(llm, "E") + 256 \
        == 182589696
    assert f.expert_up_elems(llm) + f.expert_down_elems(llm) == 4718592
    assert f.total_params(llm) == 1232121856
    assert "1,232,121,856" in conf["parameters"] \
        and "2.46 GB" in conf["parameters"]
    assert llm["layer_pattern"] == "LF" + "LE" * 6 \
        and conf["num_hidden_layers"] == 7 and conf["n_routed_experts"] == 32
    assert conf["vocab_size"] == llm["vocab_size"] == 129280 // 8
    assert (llm["q_latent_dim"], llm["kv_latent_dim"], llm["rope_head_dim"],
            llm["qk_nope_head_dim"], llm["v_head_dim"], llm["n_head"]) == (
        conf["q_lora_rank"], conf["kv_lora_rank"], conf["qk_rope_head_dim"],
        conf["qk_nope_head_dim"], conf["v_head_dim"],
        conf["num_attention_heads"])
    assert llm["n_act"] - llm["n_shared"] == conf["num_experts_per_tok"] \
        and llm["n_exp"] - llm["n_shared"] == 256 \
        and llm["routed_scale"] == conf["routed_scaling_factor"] \
        and llm["rope_theta"] == conf["rope_theta"]
    assert any("num_nextn_predict_layers" in c for c in conf["changed"])
    assert "8 chips share each layer" in conf["deployment"]
    # a row: what the mathematics needs, and what the pool keeps
    assert f.latent_row_bytes(llm) == 1152 and f.pool_row_bytes(llm) == 1280
    assert f.latent_decode_bytes_per_call(llm, 1000) == 1152000
    assert f.chunk_attention_ops(llm, 1000) == 1000 * 32 * (192 + 128) * 2
    assert f.absorbed_decode_ops_per_row(llm) == 69632
    n_blocks = -(-(e["n_slots"] * e["max_len"] // e["block_size"] + 1)
                 // 8) * 8
    held = f.resident_bytes(llm, e["n_slots"], n_blocks, e["block_size"])
    assert n_blocks == 8200 and held["weights"] == 2 * 1232121856
    assert held["latent_pools"] == 7 * 8200 * 128 * 1280
    assert 0.70 * 16e9 < held["total"] < 0.78 * 16e9
    step = f.decode_step_bytes(llm, 64, 0.865 * 32, 480000)
    assert 0.6 < step["latent_rows"] / step["total"] < 0.7


def test_every_joyai_metric_resolves():
    bench = harness.load_benchmark()
    # the cell's metrics are the entries that LIST it, whatever their names:
    # a reading it shares with other cells is one entry over all of them
    # (29 = the 7 scope times under `.joyai` + 22 shared, the two latent
    # rooflines among them since the Ling cell reads them too; the length
    # of `per_layer` is held in one place, test_resolution.py)
    mine = harness.metrics_of_cell(bench, "per_layer", CELL)
    own = [m for m in mine if m["name"].endswith(".joyai")]
    assert len(mine) == 29 and len(own) == 7
    assert all(m["workloads"] == [CELL] for m in own)
    for m in mine:
        spec, reader = harness.load_layer_metric(m["name"])
        assert "serve_closed_latent" in spec["kinds"]
        assert reader.read({}, spec.get("args", {})) is None
        assert (spec["unit"], spec["moves"], spec["layer"]) == (
            m["unit"], m["moves"], m["layer"])
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == (
            m["name"] != "train_tokens_per_s")
    work = {harness.load_layer_metric(m["name"])[0]["args"]["work_per_call"]
            for m in mine if "_roofline" in m["name"]}
    assert work == {"latent_decode_bytes_per_call",
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
    assert {"latent_q", "latent_kv", "attn_latent", "latent_out",
            "latent_attn"} <= named
    # what the cell shares it reads under the accepted entry's own name:
    # no twin under a suffix is left beside it
    listed = {m["name"] for m in mine}
    everything = {m["name"] for m in bench["per_layer"]}
    for shared in ("engine_step_mean_ms", "stall_share_pct.serve",
                   "idle_stalled_pct.serve", "expert_matmul_up_roofline",
                   "experts_hit_pct.load", "unscoped_pct.serve",
                   "latent_decode_roofline", "latent_prefill_roofline"):
        assert shared in listed
        assert shared.split(".")[0] + ".joyai" not in everything
    # a scope time of its own reads the scopes the Laguna cell's entry
    # reads, over its own vocabulary of names: another reading
    a = harness.load_layer_metric("moe_pack_ms.joyai")[0]
    b = harness.load_layer_metric("moe_pack_ms.laguna")[0]
    assert a["reader"] == b["reader"]
    assert a["args"]["scopes"] == b["args"]["scopes"]
    assert a["args"]["names"] != b["args"]["names"]


def test_kernel_work_of_the_slice():
    llm = harness.resolve_cell(harness.load_benchmark(), CELL)["config"][
        "llm_config"]
    sl = {"latent_rows_read_by.decode": 7 * 10 * 480000, "n_steps": 10,
          "chunk_attn_pairs_by.full": 7 * 4 * 5000000, "chunk_programs": 4}
    work = runner.kernel_work(sl, llm, flops_joyai, 2)
    assert work == {"latent_decode_bytes_per_call": 480000 * 1152,
                    "latent_prefill_ops_per_call": 5000000 * 20480.0}


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


@pytest.fixture(scope="module")
def driven(setup, tmp_path_factory):
    """ONE drive of the engine's two step programs, judged by the sound
    reference and by every spoilt one."""
    cfg, model, variables = setup
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path_factory.mktemp("d"))
    probed = base._probed
    with window._patched(_MIXER_MODULES={**base._MIXER_MODULES,
                                         "L": "latent_attn"},
                         _probed=lambda step: window._waited(probed(step))), \
            jax.default_matmul_precision("highest"):
        return base.step_program_rows(ctx, eng, TINY, 512)


def _check(model, variables, tmp_path, faults=(), made=None):
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
    assert len(steps["by_block"]) == 6 and max(
        e for b in steps["by_block"] for e in b.values()) < 1e-4, res
    assert set(steps["by_kind"]) == set("LFE")


@pytest.mark.parametrize("fault", reference_joyai.FAULTS)
def test_a_spoilt_reference_fails(setup, driven, tmp_path, fault):
    """Each block by block inside the engine's step programs, in the kind
    of block the term lives in and in no other."""
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, (fault,), made=driven)
    assert not res["ok"], res
    if fault == "fp8_dense":
        kinds = "F"
    elif fault in ("no_renorm", "no_routed_scale", "bias_in_weights",
                   "no_shared", "fp8_experts"):
        kinds = "E"
    else:
        kinds = "L"
    for k, by_form in res["layers"]["by_kind"].items():
        assert (max(by_form.values()) > 0.005) == (k in kinds), (
            fault, res["layers"]["by_kind"])
