"""Config / CLI override system tests (reference single-gpu/train.py:
136-206): flag surface, generic routing onto the owning dataclass,
`--total_batch_size_str "2**14"` arithmetic evaluation, cross-field
attention normalization, act_recomp linking, and validation failures. The
reference has no tests for any of this (SURVEY.md §4)."""

import dataclasses

import pytest

from distributed_pytorch_tpu.config import (LLMConfig, TrainConfig,
                                            build_parser, configs_from_args,
                                            flagship_gpt124m)


def _parse(argv):
    args = build_parser().parse_args(argv)
    return configs_from_args(args)


def test_every_field_has_a_flag():
    """Flag surface covers both dataclasses (reference exposes ~33 flags;
    ours exposes all fields, a superset)."""
    parser = build_parser()
    flags = {a.dest for a in parser._actions}
    for cfg in (LLMConfig(), TrainConfig()):
        for f in dataclasses.fields(cfg):
            want = ("total_batch_size_str" if f.name == "total_batch_size"
                    else f.name)
            assert want in flags, f"missing --{want}"


def test_defaults_round_trip():
    mc, tc = _parse([])
    assert mc == LLMConfig()
    assert tc == TrainConfig()


def test_total_batch_size_str_expression():
    # reference eval()'s the string (train.py:186-188); ours is AST-gated
    _, tc = _parse(["--total_batch_size_str", "2**14"])
    assert tc.total_batch_size == 16384
    with pytest.raises(ValueError):
        _parse(["--total_batch_size_str", "__import__('os')"])


def test_routing_to_owning_dataclass():
    mc, tc = _parse(["--n_embd", "128", "--learning_rate", "1e-2",
                     "--attn", "MQA"])
    assert mc.n_embd == 128
    assert tc.learning_rate == pytest.approx(1e-2)
    assert mc.attn == "mqa"  # strings lowercased (reference train.py:192)


def test_non_linearity_case_preserved():
    # the reference exempts non_linearity from lowercasing; our ACTIVATIONS
    # check is case-insensitive but the value must pass through
    mc, _ = _parse(["--non_linearity", "SwiGLU"])
    assert mc.non_linearity == "SwiGLU"


def test_attention_normalization():
    # mha -> n_kv_heads = n_head; mqa -> 1 (reference train.py:198-206)
    mc, _ = _parse(["--attn", "mha", "--n_head", "8", "--n_kv_heads", "2"])
    assert mc.n_kv_heads == 8
    mc, _ = _parse(["--attn", "mqa", "--n_head", "8"])
    assert mc.n_kv_heads == 1


def test_act_recomp_linked_into_model_config():
    # train flag wins and is copied into the model config (train.py:189-190)
    mc, tc = _parse(["--act_recomp"])
    assert tc.act_recomp and mc.act_recomp


def test_bool_flags():
    mc, tc = _parse(["--moe", "--eval"])
    assert mc.moe and tc.eval
    # default-True flags expose --no-<name>
    _, tc = _parse(["--no-save_stats"])
    assert not tc.save_stats


def test_validation_failures():
    with pytest.raises(AssertionError):
        LLMConfig(attn="gqa", n_head=8, n_kv_heads=3)
    with pytest.raises(ValueError):
        LLMConfig(attn="nope")
    with pytest.raises(AssertionError):
        LLMConfig(loss_chunk=100)          # must divide block_size
    with pytest.raises(AssertionError):
        LLMConfig(n_layer=6, pp_stages=4)  # must divide n_layer
    # pp x moe is SUPPORTED since round 5 (models/pipeline.py)
    assert LLMConfig(moe=True, pp_stages=2, n_layer=4).moe
    with pytest.raises(AssertionError):
        TrainConfig(parallelism="5d")


def test_parallelism_and_axis_flags():
    _, tc = _parse(["--parallelism", "pp", "--pp_size", "2",
                    "--tp_size", "2"])
    assert tc.parallelism == "pp" and tc.pp_size == 2 and tc.tp_size == 2


def test_flagship_config():
    c = flagship_gpt124m()
    assert (c.n_embd, c.n_layer, c.n_head) == (768, 12, 12)
    c2 = flagship_gpt124m(act_recomp=True)
    assert c2.act_recomp and c2.n_embd == 768


def test_cli_main_smoke(tmp_path, monkeypatch):
    """End-to-end `python -m distributed_pytorch_tpu` on a tiny synthetic
    run: the five reference trainer invocations collapsed into one CLI."""
    monkeypatch.chdir(tmp_path)
    from distributed_pytorch_tpu.__main__ import main
    main(["--dataset", "synthetic", "--data_dir", str(tmp_path),
          "--vocab_size", "256", "--block_size", "32", "--n_embd", "32",
          "--n_head", "4", "--n_kv_heads", "2", "--n_layer", "2",
          "--up_dim", "48", "--max_iters", "3", "--batch_size", "2",
          "--total_batch_size_str", "8*2*32", "--parallelism", "dp",
          "--no-save_stats"])


# ---------------------------------------------------------------------------
# the one compile-cache placement rule (config.enable_compile_cache)
# ---------------------------------------------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "from distributed_pytorch_tpu import config\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "got = config.enable_compile_cache()\n"
    "print(repr((before, got, jax.config.jax_compilation_cache_dir)))\n")


def _cache_probe(env_dir):
    import ast
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return root, ast.literal_eval(r.stdout.strip().splitlines()[-1])


def test_compile_cache_env_var_wins(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own handling of it stands and
    the helper sets NO directory in code."""
    d = str(tmp_path / "from_env")
    _, (before, got, after) = _cache_probe(d)
    assert before == d          # jax read the variable itself
    assert got == d and after == d


def test_compile_cache_default_is_fixed_checkout_path():
    """Unset: `<checkout>/.jax_cache` — a fixed path (the path is part of
    the cache key), equal across two processes, never /tmp, a pid or a
    tempfile."""
    import os
    root, (before, got, after) = _cache_probe(None)
    _, second = _cache_probe(None)
    assert before is None
    assert got == after == os.path.join(root, ".jax_cache")
    assert second[1:] == (got, after)
    assert "/tmp" not in got and str(os.getpid()) not in got
