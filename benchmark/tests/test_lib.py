"""The yardstick's arithmetic: percentiles, trace reduction on synthetic
event lists, FLOPs against the program's own count, the plain reference
against the program's model at a tiny size on the CPU."""

import dataclasses

import numpy as np
import pytest

from benchmark.lib import flops, harness, stats, trace_reduce


# ---- clock and percentile arithmetic --------------------------------------

def test_percentile_interpolates_like_numpy():
    vals = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (0, 25, 50, 90, 95, 100):
        assert stats.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert stats.median(vals) == 5.0


@pytest.mark.parametrize("n,want", [(5, None), (20, 50.0), (100, 90.0),
                                    (200, 95.0), (1000, 99.0),
                                    (10000, 99.9), (199, 90.0)])
def test_highest_tail_needs_ten_samples_beyond(n, want):
    assert stats.highest_supported_tail(n) == want


def test_summarize():
    s = stats.summarize(list(range(1, 401)))
    assert s["n"] == 400 and s["tail_q"] == 95.0
    assert s["median"] == 200.5
    assert stats.summarize(list(range(35)))["tail_q"] == 50.0


# ---- trace reduction on synthetic event lists -----------------------------

EVENTS = [("a", 0.0, 10.0), ("b", 5.0, 10.0),      # overlap: busy 0..15
          ("c", 25.0, 5.0),                         # gap 15..25
          ("d", 30.0, 10.0)]                        # touches c: no gap


def test_union_counts_overlap_once_and_finds_the_gap():
    assert trace_reduce.merged_intervals(EVENTS) == [[0.0, 15.0],
                                                     [25.0, 40.0]]
    assert trace_reduce.busy_ns(EVENTS) == 30.0
    assert trace_reduce.span_ns(EVENTS) == 40.0
    assert trace_reduce.idle_gaps(EVENTS) == [(15.0, 10.0)]


def test_self_time_takes_children_out_of_the_parent():
    evs = [("while.1", 0.0, 100.0), ("fusion.1", 10.0, 30.0),
           ("fusion.2", 50.0, 40.0), ("fusion.1", 120.0, 30.0)]
    got = dict()
    for name, ns in trace_reduce.self_times(evs):
        got[name] = got.get(name, 0.0) + ns
    assert got == {"while.1": 30.0, "fusion.1": 60.0, "fusion.2": 40.0}
    top = trace_reduce.top_ops(evs, top=2)
    assert top[0] == ["fusion.1", 60.0 / 1e9]
    fam = dict(map(tuple, trace_reduce.top_ops(evs, by_family=True)))
    assert fam["fusion"] == pytest.approx(100.0 / 1e9)
    layers = [("copy.1 copy bf16[8,4]", 0.0, 5.0),
              ("copy.2 copy bf16[8,4]", 5.0, 5.0),
              ("fusion.3 fusion f32[2]", 10.0, 1.0)]
    assert trace_reduce.top_ops(layers, by_family=True)[0] == \
        ["copy bf16[8,4]", 10.0 / 1e9]
    assert trace_reduce.op_family(
        "paged_flash_decode.7 custom-call bf16[2] tpu_custom_call") == \
        "paged_flash_decode custom-call bf16[2] tpu_custom_call"


def test_matching_and_gap_attribution():
    evs = [("all-gather-start.1 all-gather-start f32[8]", 0.0, 10.0),
           ("fusion.7 fusion f32[8]", 5.0, 10.0),
           ("reduce-scatter.2 reduce-scatter f32[2]", 20.0, 5.0)]
    assert trace_reduce.matching_ns(evs, ["all-gather",
                                          "reduce-scatter"]) == 15.0
    assert trace_reduce.count_matching(evs, ["all-reduce"]) == 0
    host = [("device_get", 14.0, 8.0), ("dispatch", 0.0, 3.0)]
    rows = trace_reduce.attribute_gaps(trace_reduce.idle_gaps(evs), host)
    assert rows == [["device_get", 5.0 / 1e9]]
    assert trace_reduce.attribute_gaps([(100.0, 5.0)], host) == \
        [["unattributed", 5.0 / 1e9]]


def test_two_device_lines_average():
    planes = {"/device:TPU:0": {"XLA Ops": [("x", 0.0, 50.0),
                                            ("y", 50.0, 50.0)]},
              "/device:TPU:1": {"XLA Ops": [("x", 0.0, 25.0),
                                            ("y", 75.0, 25.0)]},
              "/host:CPU": {"main": [("wait", 20.0, 60.0)]}}
    s = trace_reduce.summarize(planes, 2)
    assert s["busy_s"] == pytest.approx(75.0 / 1e9)
    assert s["window_s"] == pytest.approx(100.0 / 1e9)
    assert s["devices"] == [0, 1]
    one = trace_reduce.summarize(planes, 1)
    assert one["busy_s"] == pytest.approx(100.0 / 1e9)
    with pytest.raises(RuntimeError, match="no device plane"):
        trace_reduce.summarize({"/host:CPU": {"main": []}}, 1)


def test_short_op_name_drops_operands():
    full = ('%fusion.5 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]'
            '{1,0} %all-gather.3), kind=kLoop, calls=%fused_computation.5')
    short = trace_reduce.short_op_name(full)
    assert short == "fusion.5 fusion bf16[8,128]"
    assert "all-gather" not in short
    call = ('%custom-call.9 = bf16[24,25,64]{2,1,0} custom-call(bf16[24,25,'
            '64]{2,1,0} %q), custom_call_target="tpu_custom_call", '
            'backend_config={"kernel_name": "paged_flash_decode"}')
    assert "paged_flash_decode" in trace_reduce.short_op_name(call)
    assert trace_reduce.short_op_name("dot_general.1") == "dot_general.1"


# ---- FLOPs and bytes against the program's own count ----------------------

def _configs():
    bench = harness.load_benchmark()
    return [harness._load_json(
        f"{harness.ROOT}/{c['file']}", c["name"]) for c in bench["configs"]]


@pytest.mark.parametrize("config", _configs(), ids=lambda c: c["name"])
def test_flops_agree_with_the_program(config):
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.train import metrics as M
    llm = config["llm_config"]
    cfg = LLMConfig(**llm)
    assert dataclasses.asdict(cfg)["act_recomp"] is False
    T = cfg.block_size
    assert flops.matmul_params_per_token(llm) == \
        M.matmul_params_per_token(cfg)
    assert flops.model_flops_per_token(llm, T) * 16384 == pytest.approx(
        M.step_flops(cfg, 16384, T), rel=1e-12)
    assert flops.kv_bytes_per_token(llm, 2) == M.kv_bytes_per_token(cfg, 2)


def test_published_sizes():
    xl = next(c for c in _configs() if c["name"] == "gpt2-xl")["llm_config"]
    assert flops.kv_bytes_per_token(xl, 2) == 307_200
    assert flops.paged_decode_bytes(xl, [100, 28], 2) == 128 * 307_200
    assert flops.paged_decode_bytes_per_call(xl, [100, 28], 2) == 128 * 6_400
    # 48 x 30.7M + 80.5M tied head, the head counted once
    assert 1.55e9 < flops.matmul_params_per_token(xl) < 1.56e9


# ---- the plain reference against the program's model ----------------------

def test_reference_matches_program_model_float32():
    import jax
    import jax.numpy as jnp
    from benchmark.lib import reference
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.models.gpt import LLM
    llm = {"vocab_size": 512, "block_size": 32, "n_embd": 64, "n_head": 4,
           "attn": "mha", "n_layer": 3, "up_dim": 256,
           "non_linearity": "gelu", "pos_emb": "learn"}
    model = LLM(LLMConfig(**llm), compute_dtype=jnp.float32,
                attn_impl="naive")
    key = jax.random.PRNGKey(3)
    x = jax.random.randint(key, (2, 24), 0, 512)
    y = jnp.roll(x, -1, axis=1)
    variables = model.init({"params": key, "dropout": key}, x, y)
    # make biases and LayerNorm parameters matter
    variables = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jax.random.normal(key, a.shape, a.dtype),
        variables)
    logits, loss, _ = model.apply(variables, x, y)
    ref_logits = reference.forward_logits(variables["params"], llm, x)
    assert float(jnp.max(jnp.abs(ref_logits - logits))) < 2e-4
    assert float(reference.loss(variables["params"], llm, x, y)) == \
        pytest.approx(float(loss), abs=1e-4)
    with pytest.raises(ValueError, match="pos_emb"):
        reference.forward_logits(variables["params"],
                                 dict(llm, pos_emb="rope"), x)


# ---- readers on synthetic observations -------------------------------------

def test_readers_reduce_what_the_runner_observed():
    from benchmark.readers import (client_clock, counter, timeline_field,
                                   trace_idle_pct, trace_roofline_pct)
    ops = [("custom-call.1 custom-call bf16[2] tpu_custom_call "
            "paged_flash_decode", 0.0, 2e6),
           ("fusion.2 fusion f32[2]", 2e6, 6e6),
           ("custom-call.7 custom-call bf16[2] tpu_custom_call "
            "paged_flash_decode", 8e6, 2e6)]
    obs = {"timeline": [{"it": 1, "step_ms": 10.0}, {"it": 2, "step_ms": 30.0},
                        {"it": 3, "step_ms": 20.0, "sync_ms": 7.0}],
           "counters": {"compiles_in_window": 0, "bytes": 819e9 * 1e-3},
           "clock": {"engine_step_ms": [1.0, 2.0, 9.0]},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"busy_s": 0.8, "window_s": 1.0, "steps": 2,
                     "ops_dev0": ops}}
    assert timeline_field.read(obs, {"field": "step_ms"}) == 20.0
    assert timeline_field.read(obs, {"field": "sync_ms"}) == 7.0
    assert timeline_field.read(obs, {"field": "absent"}) is None
    assert counter.read(obs, {"name": "compiles_in_window"}) == 0
    assert client_clock.read(obs, {"series": "engine_step_ms"}) == 2.0
    assert client_clock.read(obs, {"series": "engine_step_ms",
                                   "stat": "mean"}) == 4.0
    assert trace_idle_pct.read(obs, {}) == pytest.approx(20.0)
    # 1 ms of bytes at peak a call against two calls of 2 ms: half the
    # roofline, however many calls the slice holds
    args = {"patterns": ["paged_flash_decode"], "work_per_call": "bytes",
            "bound": "hbm_bytes_per_s"}
    assert trace_roofline_pct.read(obs, args) == pytest.approx(50.0)
    assert trace_roofline_pct.read(
        obs, dict(args, patterns=["no_such_kernel"])) is None
