"""The `serve_closed_gdn` runner and the Qwen3-Next cell on the CPU at a small
size: the runner end to end (paths, arguments, control flow; no number it
produces is a device number), the configuration file against the catalog's
row and the issue's arithmetic, the fixed schedule, the resolution of the
cell and of every metric that lists it, the flops module's formulas, and
what the comparison sees: it passes the program and fails each term spoilt
in the REFERENCE (`reference_qwen3next.FAULTS`), in the kind of block the
term lives in. Every width is a small stand-in, every RATIO kept: 3 'G'
layers to 1 '*', 2 value heads a key head, 8 query heads a KV head, a
quarter of the lanes rotated, top 10 of 64, an eighth held."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops_qwen3next, harness, reference_qwen3next
from benchmark.runners import serve_closed_gdn as runner
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners import serve_closed_window as window
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM

CELL = "qwen3next_serve_closed32_32k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    vocab_size=512, block_size=1 << 15, n_embd=64, n_layer=8,
    layer_pattern="GEGEGE*E", pos_emb="rope", rope_theta=1e7,
    rope_pairing="half", rotary_frac=0.25, norm_eps=1e-6,
    norm_zero_centred=True, tie_head=False, attn="gqa", n_head=8,
    n_kv_heads=1, head_dim=32, qk_norm=True, attn_gate="channel",
    attn_bias=False, non_linearity="swiglu", up_dim=32, shared_up_dim=32,
    n_exp=65, n_shared=1, n_act=11, router="softmax_topk", shared_gate=True,
    experts_held=[0, 8], gdn_heads=4, gdn_key_heads=2, gdn_head_dim=16,
    gdn_conv=4)
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_gdn", "clients": 3,
           "prompt_len": [20, 60], "output_len": [4, 12],
           "schedule_seed": 67,
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 128, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference": "reference_qwen3next", "flops": "flops_qwen3next",
           "tree_conditioning": [],
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
                                    "G*E", 0.0005),
                                "state_error": {"G": 1e-5},
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
    return {"cell": {"name": "tiny_qwen3next", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": seed, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_gdn_runner_end_to_end(tmp_path, back_to_cwd):
    ctx, said = _ctx(tmp_path)
    out = runner.run(ctx)
    assert out["correct"], said
    names = {c["name"] for c in out["compared"]}
    assert {f"step_error.{k}.{form}" for k in ("G", "attn", "E")
            for form in ("chunk", "decode")} <= names
    assert {"logit_error_median", "token_share", "state_error.G"} <= names
    assert all(c["ok"] for c in out["compared"]), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    c = out["observations"]["counters"]
    assert c["compiles_in_window"] == 0, said
    assert 0 < c["chunk_program_share_pct"] < 100
    # three 'G' layers step the same slots, one '*' layer reads the rows
    assert c["gdn_slot_steps"] > 0 and c["gdn_slot_steps"] % 3 == 0
    assert c["kv_rows_read_full"] > 0 and c["chunk_attn_pairs"] > 0
    assert c["state_resets"] > 0 and 0 < c["experts_hit_pct"] <= 100
    text = "\n".join(said)
    # 3 layers x 5 slots x 4 heads x 16 x 16 float32; x 3 rows x 128 lanes;
    # 1 layer x (5 x 16 + 8 = 88 blocks) x 8 rows x 128 lanes x k, v
    assert "resident bytes by kind: " in text
    assert "'gdn_state': " + str(3 * 5 * 4 * 16 * 16 * 4) in text
    assert "'gdn_tails': " + str(3 * 5 * 3 * 128 * 4) in text
    assert "'kv_pools': " + str(2 * 88 * 8 * 128 * 4) in text
    assert "declined []" in text and "schedule (seed 67 of the mix)" in text
    assert "the mixers in the window" in text and "the first wave" in text
    assert "'kda_step': 'xla (kda_step_kernel_decline: the cpu backend" \
        in text and "'gdn_chunk': 'xla_wy" in text
    # the accepted runner is as it was when the run is over
    assert base._MIXER_MODULES.get("G") is None
    assert base.GraniteCounts.__name__ == "GraniteCounts"


def test_the_cell_is_the_issues():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, CELL)
    assert res["runner"] is runner and res["cell"]["chips"] == 1
    conf, t = res["config"], res["traffic"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b-instruct")
    assert entry["source"] == conf["source"] and set(entry["reduced"]) == \
        set(conf["reduced"]) == {"num_hidden_layers", "num_experts",
                                 "vocab_size"}
    assert (t["clients"], t["prompt_len"], t["output_len"],
            t["schedule_seed"]) == (32, [2048, 30720], [768, 2048], 67)
    assert t["engine"] == {"n_slots": 32, "max_len": 32768,
                           "block_size": 128, "prefill_chunk": 1024,
                           "temperature": 0.0, "prefix_cache": False}
    assert (t["compute_dtype"], t["trace_s"]) == ("bfloat16", 3.0)
    assert t["warm_s"] == int(t["warm_s"])
    assert t["tree_conditioning"] == []
    assert t["reference_procedures"] == ["engine_tokens_full_house",
                                         "cache_path", "step_programs",
                                         "slot_state"]
    assert set(t["reference_limits"]["state_error"]) == {"G"}
    assert set(t["reference_limits"]["step_error_median"]) == set("G*E")
    # one prompt over two chunks of 1,024
    assert max(t["reference_prompt_lens"]) > 2048
    assert t["prompt_len"][1] + t["output_len"][1] == t["engine"]["max_len"]
    sizes = [base.request_sizes(t, k) for k in range(2 * 32)]
    for r in range(2):
        plens, budgets = zip(*sizes[r * 32:(r + 1) * 32])
        assert len(set(plens)) == len(set(budgets)) == 32
        # the middles of 32 equal shares of each range
        assert min(plens) == 2496 and max(plens) == 30272
        assert min(budgets) == 788 and max(budgets) == 2028
    assert sizes == [base.request_sizes(t, k) for k in range(2 * 32)]
    # ~17 chunks and ~1,400 tokens a request: over a third of the programs
    # carry a chunk, far from the 5% a 95th percentile stands on
    chunks = sum(-(-p // 1024) for p, _ in sizes[:32]) / 32
    tokens = sum(b for _, b in sizes[:32]) / 32
    assert 0.25 < chunks * 32 / (chunks * 32 + tokens) < 0.45
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == (
            m["name"] != "train_tokens_per_s")
    # every limit names the two readings it lies between
    readings = t["reference_readings"]
    for name in ("logit_error_median", "logit_error_sequence",
                 "step_error_median.G", "step_error_median.*",
                 "step_error_median.E", "state_error.G", "token_share",
                 "sequence_share", "mean_gap"):
        assert {"sound", "fault"} <= set(readings[name]), name


def _row():
    return json.loads(next(ln for ln in open(CATALOG)
                           if '"Qwen3-Next-80B-A3B-Instruct"' in ln))


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
    f = flops_qwen3next
    assert f.gdn_params(llm) == {
        "W_qkvz": 25165824, "W_ba": 131072, "conv_w": 32768, "A_log": 32,
        "dt_bias": 32, "o_norm": 128, "W_o": 8388608}
    assert sum(f.gdn_params(llm).values()) == 33718464
    assert f.attn_params(llm) == {"c_attn": 16777216 + 2 * 1048576,
                                  "c_proj": 8388608, "q_norm": 256,
                                  "k_norm": 256}
    assert sum(f.attn_params(llm).values()) == 27263488
    assert f.layer_params(llm, "E") == 205522944 + 2048
    assert f.total_params(llm) == 9 * 33718464 + 3 * 27263488 \
        + 12 * 205522944 + 12 * 4096 + 2048 + 77791232 == 2929374400
    assert "2,929,374,400" in conf["parameters"] \
        and "5.86 GB" in conf["parameters"]
    assert llm["layer_pattern"] == "GEGEGE*E" * 3 \
        and conf["num_hidden_layers"] == 12 and conf["num_experts"] == 64 \
        and conf["full_attention_interval"] == 4
    assert conf["vocab_size"] == llm["vocab_size"] == 151936 // 8
    assert (llm["gdn_key_heads"], llm["gdn_heads"], llm["gdn_head_dim"],
            llm["gdn_conv"]) == (
        conf["linear_num_key_heads"], conf["linear_num_value_heads"],
        conf["linear_key_head_dim"], conf["linear_conv_kernel_dim"])
    assert (llm["n_head"], llm["n_kv_heads"], llm["head_dim"],
            llm["rotary_frac"], llm["rope_theta"], llm["norm_eps"]) == (
        conf["num_attention_heads"], conf["num_key_value_heads"],
        conf["head_dim"], conf["partial_rotary_factor"],
        conf["rope_theta"], conf["rms_norm_eps"])
    assert llm["n_act"] - llm["n_shared"] == conf["num_experts_per_tok"] \
        and llm["n_exp"] - llm["n_shared"] == 512 \
        and llm["experts_held"] == [0, 512 // 8] \
        and llm["up_dim"] == conf["moe_intermediate_size"] \
        and llm["shared_up_dim"] == conf["shared_expert_intermediate_size"] \
        and llm["n_embd"] == conf["hidden_size"]
    assert (llm["attn_gate"], llm["shared_gate"], llm["norm_zero_centred"],
            llm["router"], llm["tie_head"]) == (
        "channel", True, True, "softmax_topk", False)
    assert any("multi-token-prediction" in c for c in conf["changed"])
    assert "8 chips share each layer" in conf["deployment"] \
        and "4 stages, 32 chips" in conf["deployment"]
    # a slot: nine states and nine tails; a row of each of three pools
    assert f.gdn_state_bytes(llm) == 32 * 128 * 128 * 4 == 2097152
    assert 9 * f.gdn_state_bytes(llm) == 18874368 \
        and 9 * f.gdn_tail_bytes(llm) == 442368
    assert f.kv_bytes_per_row(llm) == 2048
    # the rooflines' floors
    assert f.gdn_step_bytes_per_call(llm, 32) == 32 * (
        2 * 2097152 + 6 * 32 * 128 * 4)
    assert f.gdn_chunk_bytes_per_call(llm, 1024) == \
        1024 * (12288 * 2 + 64 * 4) + 2 * 2097152
    assert f.paged_decode_bytes_per_call(llm, 1000) == 2048000
    assert f.chunk_attention_ops(llm, 1000) == 1000 * 16 * 256 * 4
    n_blocks = -(-(e["n_slots"] * e["max_len"] // e["block_size"] + 1)
                 // 8) * 8
    held = f.resident_bytes(llm, e["n_slots"], n_blocks, e["block_size"])
    assert n_blocks == 8200 and held["weights"] == 2 * 2929374400
    assert held["gdn_state"] + held["gdn_tails"] == 32 * (18874368 + 442368)
    assert held["kv_pools"] == 3 * 8200 * 128 * 2048
    assert 0.78 * 16e9 < held["total"] < 0.84 * 16e9
    step = f.decode_step_bytes(llm, 32, 30, 32 * 17000)
    assert 0.6 < (step["gdn_state"] + step["gdn_weights"]
                  + step["attention_rows"] + step["attention_weights"]) \
        / step["total"] < 0.75


def test_every_qwen3next_metric_resolves():
    bench = harness.load_benchmark()
    mine = harness.metrics_of_cell(bench, "per_layer", CELL)
    assert len(mine) == 32 and all(m["name"].endswith(".qwen3next")
                                   and m["workloads"] == [CELL]
                                   for m in mine)
    assert len(bench["per_layer"]) == 124
    readers = {harness.load_layer_metric(m["name"])[0]["reader"]
               for m in bench["per_layer"]
               if not m["name"].endswith(".qwen3next")}
    accepted = {m["name"]: m for m in bench["per_layer"]}
    twins = 0
    for m in mine:
        spec, reader = harness.load_layer_metric(m["name"])
        assert spec["kinds"] == ["serve_closed_gdn"]
        assert spec["reader"] in readers, "an accepted reader"
        assert reader.read({}, spec.get("args", {})) is None
        assert (spec["unit"], spec["moves"], spec["layer"]) == (
            m["unit"], m["moves"], m["layer"])
        first = m["name"][:-len(".qwen3next")]
        if first in accepted and CELL not in accepted[first].get(
                "workloads", []):
            # a twin of an accepted entry: its reader and args as they are
            # written there, for the next `benchmark` PR to fold
            other, _ = harness.load_layer_metric(first)
            twins += (other["reader"], json.dumps(other.get("args", {}))
                      ) == (spec["reader"], json.dumps(spec.get("args", {})))
    # the 21 the issue lists and `paged_decode_roofline`, whose accepted
    # file's `kinds` is not this PR's to widen
    assert twins == 22
    work = {harness.load_layer_metric(m["name"])[0]["args"]["work_per_call"]
            for m in mine if "_roofline" in m["name"]}
    assert work == {"gdn_step_bytes_per_call", "gdn_chunk_bytes_per_call",
                    "paged_decode_bytes_per_call",
                    "paged_prefill_ops_per_call",
                    "expert_up_bytes_per_call", "expert_down_bytes_per_call"}
    names = {json.dumps(harness.load_layer_metric(m["name"])[0]["args"]
                        ["names"]) for m in mine
             if "names" in harness.load_layer_metric(m["name"])[0]["args"]}
    assert len(names) == 1, "ONE vocabulary of names"
    from distributed_pytorch_tpu.obs.trace import MIXER_MODULES, MIXER_SCOPES
    from benchmark.lib.trace_spans import SCOPE_NAMES
    named = set(json.loads(names.pop()))
    assert named <= set(MIXER_MODULES) | set(MIXER_SCOPES) | set(SCOPE_NAMES)
    assert {"gdn", "gdn_proj", "gdn_conv", "gdn_gate", "attn_gdn",
            "gdn_chunk", "gdn_out", "attn_gate", "moe_shared"} <= named


def test_kernel_work_of_the_slice():
    llm = harness.resolve_cell(harness.load_benchmark(), CELL)["config"][
        "llm_config"]
    sl = {"kda_slot_steps_by.decode": 9 * 10 * 31, "n_steps": 10,
          "kda_slot_steps_by.chunk": 9 * 4 * 1000, "chunk_programs": 4,
          "chunk_attn_pairs_by.full": 3 * 4 * 9000000}
    work = runner.kernel_work(sl, llm, flops_qwen3next, 2)
    assert work == {
        "gdn_step_bytes_per_call": 31 * (2 * 2097152 + 98304),
        "gdn_chunk_bytes_per_call": 1000 * (24576 + 256) + 2 * 2097152,
        "gdn_chunk_calls_per_step": 3.6,
        "paged_prefill_ops_per_call": 9000000 * 16384.0}
    # a slice without a chunk-carrying program books no chunk work: the
    # readers then leave the metrics out
    none = runner.kernel_work({**sl, "chunk_programs": 0,
                               "kda_slot_steps_by.chunk": 0,
                               "chunk_attn_pairs_by.full": 0}, llm,
                              flops_qwen3next, 2)
    assert none["gdn_chunk_bytes_per_call"] == 0.0 \
        and none["gdn_chunk_calls_per_step"] == 0.0 \
        and none["paged_prefill_ops_per_call"] == 0.0


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
    return cfg, model, {"params": params}


def _drive(setup, tmp_path):
    """(`step_program_rows`'s, the tap that probed it): ONE drive of the
    engine's two step programs through the runner's own probe."""
    cfg, model, variables = setup
    eng = DecodeEngine(model, variables, **TRAFFIC["engine"])
    ctx, _ = _ctx(tmp_path)
    tap = runner.GdnTap(eng.n_slots)
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
    assert len(steps["by_block"]) == 8 and max(
        e for b in steps["by_block"] for e in b.values()) < 1e-4, steps
    assert set(steps["by_kind"]) == set("G*E")


#: the faults a block's OWN input cannot show: the block norms and the final
#: norm stand outside every mixer (`step_programs` hands the reference the
#: program's normed input); the QK-norms inside '*' do show
_IN_BLOCK = {"norm_w_not_1pw": "*"}


@pytest.mark.parametrize("fault", reference_qwen3next.FAULTS)
def test_a_spoilt_reference_fails(setup, driven, tmp_path, fault):
    """Each block by block inside the engine's step programs, in the kind
    of block the term lives in and in no other."""
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, (fault,), made=driven)
    assert not res["ok"], res
    kinds = "G" if fault in reference_qwen3next.GDN_FAULTS else \
        "*" if fault in reference_qwen3next.ATTN_FAULTS else \
        _IN_BLOCK.get(fault, "E")
    for k, by_form in res["by_kind"].items():
        assert (not max(by_form.values()) <= 0.0005) == (k in kinds), (
            fault, res["by_kind"])


@pytest.mark.parametrize("fault,ok", [((), True), (("bf16_state",), False),
                                      (("no_delta",), False)])
def test_slot_state_holds_the_leaf_to_the_literal_recurrence(
        driven_tap, tmp_path, fault, ok):
    """Over the recurrence's own operands the state of every judged slot
    (the late one too) is the reference's to float32 rounding in all three
    'G' layers; a reference that keeps its state in bfloat16 stands 2^-9
    apart, a thousand times the sound reading."""
    _, tap = driven_tap
    ctx, _ = _ctx(tmp_path)
    got = runner.slot_state_check(ctx, tap, TINY, fault)
    assert got["ok"] == ok, got
    assert sorted(got["by_slot"]) == [0, 1, 2, 4] and all(
        len(errs) == 3 for errs in got["by_slot"].values())
    # a chunk's rows and then a row a program the slot was live in
    assert all(16 < n <= 16 + 2 * 3 + 5 for n in got["rows"].values()), got
    if ok:
        assert got["worst"] < 1e-6, got
    elif fault == ("bf16_state",):
        assert 1e-3 < got["worst"] < 2e-2, got


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

    for name in ("kda_step", "gdn_chunk"):
        monkeypatch.setattr(delta_rule, name,
                            rounded(getattr(delta_rule, name)))
    _, tap = _drive(setup, tmp_path)
    ctx, _ = _ctx(tmp_path)
    got = runner.slot_state_check(ctx, tap, TINY)
    assert not got["ok"] and 1e-3 < got["worst"] < 2e-2, got
