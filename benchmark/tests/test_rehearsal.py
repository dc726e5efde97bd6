"""CPU rehearsals: every runner called from Python at a tiny shape (the
tests/test_chip_smoke.py idiom). They prove paths, arguments and control
flow; no number they produce is a device number, and run.py itself refuses
to print one without a chip (test_no_chip_no_result)."""

import os
import subprocess
import sys

import pytest

from benchmark.lib import harness

TINY = {"vocab_size": 1024, "block_size": 64, "n_embd": 64, "n_head": 4,
        "attn": "mha", "n_layer": 2, "up_dim": 256,
        "non_linearity": "gelu", "pos_emb": "learn"}
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def _ctx(tmp_path, name, traffic, *, chips=1, trace=False, seconds=1.0):
    said = []
    return {"cell": {"name": name, "chips": chips},
            "config": {"llm_config": dict(TINY)}, "traffic": traffic,
            "seed": 2 ** 31 + 12345, "seconds": seconds, "trace": trace,
            "chips": chips, "work_dir": str(tmp_path), "peaks": FAKE_PEAKS,
            "say": said.append}, said


def _train_traffic(**over):
    t = {"kind": "train",
         "train_config": {"parallelism": "single", "batch_size": 2,
                          "total_batch_size": 128,
                          "compute_dtype": "float32", "attn_impl": "auto",
                          "log_interval": 2, "max_iters": 100000},
         "synthetic_tokens": 2 ** 14,
         "warmup_windows": 1, "trace_windows": 1,
         "reference_sample": [2, 32]}
    t["train_config"].update(over)
    return t


@pytest.fixture
def back_to_cwd():
    cwd = os.getcwd()
    yield
    os.chdir(cwd)


def test_train_runner_single(tmp_path, back_to_cwd):
    from benchmark.runners import train
    ctx, said = _ctx(tmp_path, "tiny_train", _train_traffic())
    out = train.run(ctx)
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert out["end_to_end"]["setup_s"] > 0
    assert out["correct"], said
    assert len(out["compared"]) >= 2 and all(
        c["ok"] for c in out["compared"]), out["compared"]
    obs = out["observations"]
    assert obs["counters"]["compiles_in_window"] == 0
    assert all("step_ms" in e for e in obs["timeline"])


def test_no_chip_no_result():
    """Without an accelerator run.py exits non-zero and prints no line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", harness.load_benchmark()["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT,
        timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "metrics" not in p.stdout


def test_serve_closed_runner(tmp_path, back_to_cwd):
    from benchmark.runners import serve_closed
    traffic = {"kind": "serve_closed", "clients": 3,
               "prompt_len": [4, 24], "output_len": [4, 12],
               "compute_dtype": "float32", "attn_impl": "auto",
               "engine": {"n_slots": 3, "max_len": 64, "block_size": 8,
                          "prefill_chunk": 16, "temperature": 0.0,
                          "prefix_cache": True, "min_bucket": 8},
               "warm_s": 1.0, "ttft_grace_s": 0.5,
               "trace_s": 0.5, "reference_prompt_lens": [9, 20],
               "reference_new_tokens": 4}
    ctx, said = _ctx(tmp_path, "tiny_serve", traffic, seconds=2.0)
    out = serve_closed.run(ctx)
    assert out["correct"], said
    assert len(out["compared"]) >= 2 and all(
        c["ok"] for c in out["compared"]), out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0, said
    for k in ("serve_tokens_per_s", "itl_p95_ms", "setup_s"):
        assert out["end_to_end"][k] > 0
    assert "ttft_p95_ms" not in out["end_to_end"]
    obs = out["observations"]
    assert obs["counters"]["compiles_in_window"] == 0, said
    assert obs["clock"]["engine_step_ms"] and obs["clock"]["ttft_ms"]
    assert 0 < max(obs["clock"]["occupancy_pct"]) <= 100


def test_every_seed_offers_the_same_sizes_in_another_order():
    from benchmark.runners import serve_closed
    t = {"clients": 24, "prompt_len": [64, 256], "output_len": [64, 192]}
    rounds = {}
    for seed in (1, 2 ** 31 + 7):
        sizes = [serve_closed.request_sizes(t, seed, k) for k in range(72)]
        for r in range(3):
            p, b = zip(*sizes[24 * r:24 * r + 24])
            assert sorted(p) == serve_closed._spaced(64, 256, 24)
            assert sorted(b) == serve_closed._spaced(64, 192, 24)
        rounds[seed] = sizes
        assert sizes[:24] != sizes[24:48]           # a draw for every round
        assert sizes == [serve_closed.request_sizes(t, seed, k)
                         for k in range(72)]        # the seed decides
    assert rounds[1] != rounds[2 ** 31 + 7]
    p = serve_closed._spaced(64, 256, 24)
    assert min(p) >= 64 and max(p) <= 256 and sum(p) / 24 == pytest.approx(
        160, abs=1)
    b = serve_closed._spaced(64, 192, 24)
    assert min(b) >= 64 and max(b) <= 192 and sum(b) / 24 == pytest.approx(
        128, abs=1)
