"""The `serve_closed_parallel` runner and the Falcon-H1 cell on the CPU at a
small size: the runner end to end (paths, arguments, control flow; no number
it produces is a device number), the configuration file's arithmetic against
a hand count, the fixed schedule, the resolution of the cell and of every
metric that lists it, the new reader, and what the comparison sees: it passes
the program and fails each term spoilt in the REFERENCE
(`reference_falcon_h1.FAULTS`), in the kind of block the term lives in."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import flops_falcon_h1, harness, reference_falcon_h1
from benchmark.readers import trace_scope_roofline_pct
from benchmark.runners import serve_closed_parallel as runner
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners import serve_closed_window as window
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM

CELL = "falcon_h1_serve_closed64"
TINY = dict(
    vocab_size=512, block_size=1 << 18, n_embd=64, n_layer=6,
    layer_pattern="PFPFPF", pos_emb="rope", rope_theta=1e11,
    rope_pairing="half", norm_eps=1e-5, tie_head=False, attn="gqa",
    n_head=10, n_kv_heads=2, head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=96, dense_up_dim=96,
    embed_mult=5.656854249492381, logits_div=128.0, attn_in_mult=1.0,
    attn_out_mult=0.0375, key_mult=0.011048543456039804, ssm_in_mult=0.25,
    ssm_out_mult=0.08838834764831845,
    ssm_mults=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
               0.3535533905932738],
    mlp_gate_mult=0.1767766952966369, mlp_down_mult=0.011160714285714284,
    ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=32, ssm_conv=4,
    ssm_chunk=8)
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
TRAFFIC = {"kind": "serve_closed_parallel", "clients": 3,
           "prompt_len": [20, 60], "output_len": [4, 12],
           "schedule_seed": 54,
           "compute_dtype": "float32", "attn_impl": "auto",
           "engine": {"n_slots": 5, "max_len": 128, "block_size": 8,
                      "prefill_chunk": 16, "temperature": 0.0,
                      "prefix_cache": False, "min_bucket": 8},
           "warm_s": 1.0, "ttft_grace_s": 0.5, "trace_s": 0.5,
           "reference": "reference_falcon_h1", "flops": "flops_falcon_h1",
           "tree_conditioning": ["divide_by_multipliers", "draw_conv_bias"],
           "reference_procedures": ["engine_tokens_full_house",
                                    "cache_path", "step_programs"],
           # inside one chunk (16), across two, two chunks and a half
           "reference_prompt_lens": [12, 27, 40],
           "reference_new_tokens": 32, "reference_engine_tokens": 32,
           "reference_plain_steps": 3,
           "reference_limits": {"logit_error_median": 0.005,
                                "logit_error_sequence": 0.005,
                                "step_error_median": dict.fromkeys(
                                    "PF", 0.005),
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
    return {"cell": {"name": "tiny_falcon", "chips": 1},
            "config": {"llm_config": dict(TINY)}, "traffic": dict(TRAFFIC),
            "seed": seed, "seconds": seconds, "trace": False,
            "chips": 1, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def test_parallel_runner_end_to_end(tmp_path, back_to_cwd):
    ctx, said = _ctx(tmp_path)
    out = runner.run(ctx)
    assert out["correct"], said
    names = {c["name"] for c in out["compared"]}
    assert {"step_error.P.chunk", "step_error.P.decode",
            "step_error.F.chunk", "step_error.F.decode",
            "logit_error_median", "token_share"} <= names
    assert all(c["ok"] for c in out["compared"]), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    c = out["observations"]["counters"]
    assert c["compiles_in_window"] == 0, said
    assert 0 < c["chunk_program_share_pct"] < 100
    assert c["state_resets"] > 0 and c["kv_rows_read_full"] > 0
    # 3 layers x 4 x 16 x 32 float32, in and out, a decoding slot a program
    assert c["ssm_state_bytes"] > 0 \
        and c["ssm_state_bytes"] % (2 * 3 * 4 * 16 * 32 * 4) == 0
    assert "experts_hit_pct" not in c
    text = "\n".join(said)
    assert "resident bytes by kind of state" in text
    assert "'slot_state': " + str(3 * 5 * (4 * 16 * 32 * 4 + 3 * 192 * 4)) \
        in text and "'window': 0" in text
    assert "declined []" in text and "schedule (seed 54 of the mix)" in text
    assert "tree conditioned by ['divide_by_multipliers', 'draw_conv_bias']" \
        in text
    assert "fell back to paged_gather" in text
    # the accepted runner is as it was when the run is over
    assert base._MIXER_MODULES.get("P") is None
    assert set(base.CONDITIONING) == {"balance_router_bias"}
    assert base.GraniteCounts.__name__ == "GraniteCounts"


def test_the_cell_is_the_issues():
    bench = harness.load_benchmark()
    res = harness.resolve_cell(bench, CELL)
    assert res["runner"] is runner and res["cell"]["chips"] == 1
    conf, t = res["config"], res["traffic"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "falcon-h1-34b-instruct")
    assert entry["source"] == conf["source"] and set(entry["reduced"]) == \
        set(conf["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert (t["clients"], t["prompt_len"], t["output_len"],
            t["schedule_seed"]) == (64, [256, 1280], [256, 768], 54)
    assert t["engine"] == {"n_slots": 64, "max_len": 2048,
                           "block_size": 128, "prefill_chunk": 512,
                           "temperature": 0.0, "prefix_cache": False}
    assert (t["compute_dtype"], t["warm_s"], t["ttft_grace_s"],
            t["trace_s"]) == ("bfloat16", 8.0, 2.0, 3.0)
    assert set(t["tree_conditioning"]) == set(runner.CONDITIONING)
    assert set(t["reference_procedures"]) <= set(base.PROCEDURES) \
        and t["reference_procedures"][-1] == "step_programs"
    # inside one chunk, across two, two chunks and a half
    assert t["reference_prompt_lens"] == [300, 700, 1280]
    assert set(t["reference_limits"]["step_error_median"]) == set("PF")
    # the accepted runner's three tellers find every limit they name (a
    # first set of six on the chip ended in a KeyError after its window)
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
    # an untied head cannot hand the input's id back: the echo share is
    # said and held to nothing
    assert lim["echo_share"] == 1.0
    assert t["prompt_len"][1] + t["output_len"][1] <= t["engine"]["max_len"]
    sizes = [base.request_sizes(t, k) for k in range(2 * 64)]
    for r in range(2):
        plens, budgets = zip(*sizes[r * 64:(r + 1) * 64])
        assert len(set(plens)) == len(set(budgets)) == 64
        assert min(plens) >= 256 and max(plens) <= 1280
        assert min(budgets) >= 256 and max(budgets) <= 768
    assert sizes == [base.request_sizes(t, k) for k in range(2 * 64)]


def test_the_cuts_arithmetic_is_the_issues():
    res = harness.resolve_cell(harness.load_benchmark(), CELL)
    llm, e = res["config"]["llm_config"], res["traffic"]["engine"]
    f = flops_falcon_h1
    assert f.branch_params(llm) == {"attention": 31457280, "ssm": 68351072}
    assert f.layer_params(llm, "F") - 5120 == 330301440
    assert f.layer_params(llm, "P") + f.layer_params(llm, "F") == 430120032
    assert f.total_params(llm) == 4205319008
    assert f.ssm_state_bytes(llm) == 32 * 128 * 256 * 4 == 4194304
    assert f.kv_bytes_per_row(llm) == 9 * 2 * 4 * 128 * 2 == 9 * 2048
    assert f.state_bytes_per_slot(llm) == 9 * (4194304 + 3 * 5120 * 2)
    assert f.paged_decode_bytes_per_call(llm, 1000) == 2048000
    assert f.ssm_step_bytes_per_call(llm, 64) == 2 * 64 * 4194304
    n_blocks = -(-(e["n_slots"] * e["max_len"] // e["block_size"] + 1)
                 // 8) * 8
    held = f.resident_bytes(llm, e["n_slots"], n_blocks, e["block_size"])
    assert held["weights"] == 2 * 4205319008
    assert held["state"] == 64 * 9 * (4194304 + 30720)
    assert 13.2e9 < held["total"] < 13.35e9 and held["total"] > 0.8 * 16e9
    # a slot's state does not know max_len
    longer = f.resident_bytes(llm, 64, 4 * n_blocks, 128)
    assert longer["state"] == held["state"] \
        and longer["kv_pools"] == 4 * held["kv_pools"]
    step = f.decode_step_bytes(llm, 64, 0, 65000)
    assert round(step["dense_ffn"] / 1e9, 2) == 5.95
    assert round(step["ssm_state"] / 1e9, 2) == 4.87
    assert round(step["total"] / 1e9, 1) == 14.1
    mixers = step["attention"] + step["ssm_weights"] + step["ssm_state"]
    assert 0.5 < mixers / step["total"] < 0.6


def test_every_falcon_metric_resolves():
    bench = harness.load_benchmark()
    # the cell's metrics are the entries that LIST it, whatever their names:
    # a reading it shares with other cells is one entry over all of them
    # (24 = the 6 no accepted entry repeats + 18 shared; the length of
    # `per_layer` is held in one place, test_resolution.py)
    mine = harness.metrics_of_cell(bench, "per_layer", CELL)
    own = [m for m in mine if m["name"].endswith(".falcon")]
    assert len(mine) == 24 and len(own) == 6
    assert all(m["workloads"] == [CELL] for m in own)
    readers = set()
    for m in mine:
        spec, reader = harness.load_layer_metric(m["name"])
        readers.add(spec["reader"])
        assert "serve_closed_parallel" in spec["kinds"]
        assert reader.read({}, spec.get("args", {})) is None
    assert "trace_scope_roofline_pct" in readers
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == (
            m["name"] != "train_tokens_per_s")
    work = {harness.load_layer_metric(m["name"])[0]["args"]["work_per_call"]
            for m in mine if "_roofline" in m["name"]}
    assert work == {"paged_decode_bytes_per_call", "ssm_step_bytes_per_call"}
    names = {json.dumps(harness.load_layer_metric(m["name"])[0]["args"]
                        ["names"]) for m in mine
             if "names" in harness.load_layer_metric(m["name"])[0]["args"]}
    assert len(names) == 1
    from distributed_pytorch_tpu.obs.trace import MIXER_MODULES, MIXER_SCOPES
    assert set(json.loads(names.pop())) <= set(MIXER_MODULES) | set(
        MIXER_SCOPES)
    # what the cell shares it reads under the accepted entry's own name:
    # no twin under a suffix is left beside it
    listed = {m["name"] for m in mine}
    everything = {m["name"] for m in bench["per_layer"]}
    for shared in ("engine_step_mean_ms", "stall_share_pct.serve",
                   "idle_stalled_pct.serve", "paged_decode_roofline",
                   "state_resets", "unscoped_pct.serve"):
        assert shared in listed
        assert shared.split(".")[0] + ".falcon" not in everything


def test_the_scope_roofline_reader(monkeypatch):
    args = harness.load_layer_metric("ssm_step_roofline.falcon")[0]["args"]
    obs = {"trace": {"busy_s": 1.0}, "peaks": {"hbm_bytes_per_s": 800e9},
           "counters": {"ssm_step_bytes_per_call": 4e8,
                        "ssm_step_calls_per_step": 9}}
    seen = {}

    def scope_ms(o, a):
        seen.update(a)
        return 9.0                  # ms a step under the scope: 1 ms a call
    monkeypatch.setattr(trace_scope_roofline_pct.trace_scope_named_ms,
                        "read", scope_ms)
    # 4e8 B / 800e9 B/s = 0.5 ms least, over 1 ms a call
    assert trace_scope_roofline_pct.read(obs, args) == pytest.approx(50.0)
    assert seen["scopes"] == ["ssm_step"] and set(seen) == {
        "modules", "scopes", "names"}
    # a program without the scope or the counter: nothing, and no raise
    monkeypatch.setattr(trace_scope_roofline_pct.trace_scope_named_ms,
                        "read", lambda o, a: None)
    assert trace_scope_roofline_pct.read(obs, args) is None
    assert trace_scope_roofline_pct.read(
        {**obs, "counters": {}}, args) is None
    assert trace_scope_roofline_pct.read({"counters": obs["counters"]},
                                         args) is None


def test_conditioning_undoes_each_multiplier_and_no_other():
    """multiplier x conditioned matrix = the drawn matrix, column by
    column; what no multiplier follows is left as drawn."""
    cfg = LLMConfig(**TINY)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    drawn = model.init({"params": jax.random.PRNGKey(3)},
                       jnp.zeros((1, 8), jnp.int32))["params"]
    keep = jax.tree_util.tree_map(jnp.array, drawn)     # donated below
    got = runner.divide_by_multipliers(drawn, TINY, {"seed": 1})
    close = lambda a, b: bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-7))  # noqa: E731
    assert close(got["tkn_emb"]["embedding"] * TINY["embed_mult"],
                 keep["tkn_emb"]["embedding"])
    assert close(got["lm_head"] / TINY["logits_div"], keep["lm_head"])
    a, a0 = got["block_0"]["attn"], keep["block_0"]["attn"]
    qkv, qkv0 = a["c_attn"]["kernel"], a0["c_attn"]["kernel"]
    assert close(qkv[:, :160], qkv0[:, :160])
    assert close(qkv[:, 160:192] * TINY["key_mult"], qkv0[:, 160:192])
    assert close(qkv[:, 192:], qkv0[:, 192:])
    assert close(a["c_proj"]["kernel"] * TINY["attn_out_mult"],
                 a0["c_proj"]["kernel"])
    s, s0 = got["block_0"]["ssm"], keep["block_0"]["ssm"]
    edges = [0, 64, 128, 192, 256, 260]
    for (lo, hi), m in zip(zip(edges, edges[1:]), TINY["ssm_mults"]):
        assert close(s["in_proj"][:, lo:hi] * m * TINY["ssm_in_mult"],
                     s0["in_proj"][:, lo:hi])
    assert close(s["out_proj"] * TINY["ssm_out_mult"], s0["out_proj"])
    for leaf in ("conv_w", "conv_b", "A_log", "D", "dt_bias", "norm_w"):
        assert close(s[leaf], s0[leaf])
    m, m0 = got["block_1"]["mlp"], keep["block_1"]["mlp"]
    assert close(m["c_fc"][:, :96] * TINY["mlp_gate_mult"],
                 m0["c_fc"][:, :96])
    assert close(m["c_fc"][:, 96:], m0["c_fc"][:, 96:])
    assert close(m["c_proj"] * TINY["mlp_down_mult"], m0["c_proj"])
    biased = runner.draw_conv_bias(got, TINY, {"seed": 1})
    b = biased["block_2"]["ssm"]["conv_b"]
    assert float(jnp.abs(b).max()) <= 0.5 and float(jnp.std(b)) > 0.2
    assert biased["block_1"] is got["block_1"]


# ---------------------------------------------------------------------------
# what the comparison sees
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    """The tree as the runner makes it, the matrices a few times the draw
    so that at 64 wide every term shows over float32 rounding."""
    cfg = LLMConfig(**TINY)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    params = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a,
        model.init({"params": jax.random.PRNGKey(7)},
                   jnp.zeros((1, 8), jnp.int32))["params"])
    for rule in TRAFFIC["tree_conditioning"]:
        params = runner.CONDITIONING[rule](params, TINY, {"seed": 7})
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
                                         "P": "mixer_sum"},
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
    assert set(steps["by_kind"]) == set("PF")


@pytest.mark.parametrize("fault", reference_falcon_h1.FAULTS)
def test_a_spoilt_reference_fails(setup, driven, tmp_path, fault):
    """Each by the logits through the cache and, block by block inside the
    engine's step programs, in the kind of block the term lives in and in
    no other; the embedding's and the head's multipliers live in no block
    and show in the logits alone."""
    cfg, model, variables = setup
    res = _check(model, variables, tmp_path, (fault,), made=driven)
    assert not res["ok"], res
    assert not res["logits"]["ok"], res
    if fault in ("embed_mult_1", "logits_div_1"):
        kinds = ""
    elif fault.startswith("mlp_") or fault == "fp8_dense":
        kinds = "F"
    else:
        kinds = "P"
    for k, by_form in res["layers"]["by_kind"].items():
        assert (max(by_form.values()) > 0.005) == (k in kinds), (
            fault, res["layers"]["by_kind"])
