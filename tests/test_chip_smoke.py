"""The CPU rehearsal of chip_smoke.py: the SAME phase functions the chip
run calls, at a tiny model shape and on "cpu" — both are function
arguments only a Python caller can pass; `main()` always passes the
flagship and "tpu" and has no command-line way to ask for less.

What this proves here: paths, flags, control flow, the JSON lines, the
HTTP client, SIGTERM handling, the engine-vs-generate comparison, and the
fsdp mesh on four virtual devices. What it cannot: anything about the
chip — no compiled Pallas kernel runs on a CPU (tests/
test_aot_tpu_compile.py asks the chip's compiler; chip_smoke.py runs
them)."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Shape(
    ("--n_layer", "2", "--n_embd", "128", "--n_head", "4", "--n_kv_heads",
     "4", "--attn", "mha", "--up_dim", "256", "--vocab_size", "1024",
     "--block_size", "128",
     # 5 steps of 512 tokens: a falling loss needs a rate this size
     "--learning_rate", "3e-3"), seq_len=128, vocab=1024)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """phase_train once; the serve test reuses its checkpoint."""
    out = str(tmp_path_factory.mktemp("smoke"))
    rec = chip_smoke.phase_train(out, seed=0, platform="cpu", shape=TINY,
                                 batch=4, iters=4, kernel_iters=-1)
    return out, rec


def test_train_phase_line(trained):
    out, rec = trained
    json.dumps(rec)                                  # one JSON line
    assert rec["phase"] == "train" and rec["ok"]
    assert rec["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert rec["steps"] == 5 and rec["retraces"] == 0
    assert abs(rec["loss_first"] - math.log(1024)) < 1.0
    assert rec["loss_last"] < rec["loss_first"]
    assert rec["seconds"] > rec["compile_seconds"] > 0
    assert rec["kernels"] == {"xla": {}}             # a CPU holds no kernel
    # asked for by name: the leg the pallas one is compared with
    assert rec["paths"]["attention"] == "xla (attn_impl=xla)"
    assert rec["mfu"] is None                        # no peak for a CPU
    assert rec["step_ms"] > 0 and rec["tok_s_chip"] > 0
    assert rec["checkpoint_files_verified"] > 3
    assert os.path.exists(os.path.join(rec["checkpoint"], "manifest.json"))


def test_train_phase_insists_on_its_platform(tmp_path):
    """Asked for a TPU on a machine without one, the trainer child dies
    at backend start-up and the phase fails with its output."""
    with pytest.raises(chip_smoke.PhaseFailed) as e:
        chip_smoke.phase_train(str(tmp_path), seed=0, platform="tpu",
                               shape=TINY, batch=2, iters=1,
                               kernel_iters=-1,
                               env={"JAX_PLATFORMS": ""})
    assert e.value.phase == "train" and "tpu" in e.value.log.lower()


def test_serve_phase_line(trained):
    out, train_rec = trained
    rec = chip_smoke.phase_serve(
        out, train_rec["checkpoint"], seed=0, platform="cpu", shape=TINY,
        slots=4, kv_block=16, prefill_chunk=32, prompt_lens=(8, 40, 90),
        new_tokens=8)
    json.dumps(rec)
    assert rec["phase"] == "serve" and rec["ok"]
    assert rec["device"]["platform"] == "cpu"
    assert rec["requests"] == 6 and rec["tokens_each"] == 8
    assert rec["prompt_lens"] == [8, 40, 90, 8, 40, 90]
    assert rec["identical_requests_bit_identical"]
    assert rec["prefix_hit_tokens"] >= 16 and 0 < rec["prefix_hit_rate"] < 1
    assert len(rec["ttft_s"]) == 6 and rec["itl_median_s"] > 0
    assert set(rec["kernels"]) == {"engine.step", "engine.fused_step"}
    assert rec["engine_vs_generate"]["first_token_equal"]
    assert rec["sigterm_exit_code"] == 0
    assert rec["seconds"] > rec["compile_seconds"] > 0


def test_multichip_phase_on_four_virtual_devices(tmp_path):
    """--multichip's comparison on 4 virtual CPU devices: four non-empty,
    near-equal fsdp shards of a quarter of the dp state each, collectives
    in the fsdp step, fsdp and dp losses equal step by step."""
    rec = chip_smoke.phase_multichip(
        str(tmp_path), seed=0, platform="cpu", shape=TINY, n_devices=4,
        batch=2, iters=2,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    json.dumps(rec)
    assert rec["phase"] == "multichip" and rec["ok"]
    assert rec["device"]["count"] == 4
    shards = rec["fsdp_state_bytes_per_device"]
    assert len(shards) == 4 and min(shards) > 0
    assert max(shards) <= 1.05 * min(shards)
    assert max(shards) <= 1.1 * rec["dp_state_bytes_per_device"] / 4
    assert rec["fsdp_collectives"].get("all-gather", 0) > 0
    assert rec["max_loss_delta"] <= rec["tolerance"]
    assert rec["steps"] == 3 and len(rec["losses"]["fsdp"]) == 3


def test_no_chip_exits_nonzero_without_ok(tmp_path):
    """`python chip_smoke.py` as the driver runs it, where jax finds no
    accelerator: non-zero, the failing phase's output shown, and no final
    {"ok": true} line."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--out",
         str(tmp_path)], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"phase": "kernels", "ok": False, "error": last["error"]}
    assert "jax found 'cpu'" in r.stdout


def test_alone_without_the_package_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo: non-zero, and no result line."""
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "No module named 'distributed_pytorch_tpu'" in r.stderr
