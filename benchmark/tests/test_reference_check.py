"""What the train cells' reference check sees: at the real gpt2
configuration and sequence length, on the CPU, the comparison the runner
makes (`train.compare_to_reference`, with the runner's own tolerances)
passes the program's bf16 model and fails attention taken out, a mask one
off and weights rounded to fp8. The program's side runs untouched; the
fault is put into the weights it is given or into the reference."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import harness, reference, synth
from benchmark.runners import train
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.models.gpt import LLM

T = 1024


@pytest.fixture(scope="module")
def setup():
    with open(os.path.join(harness.BENCH_DIR, "configs", "gpt2.json")) as f:
        llm = json.load(f)["llm_config"]
    cfg = LLMConfig(**llm)
    model = LLM(cfg, compute_dtype=jnp.bfloat16, attn_impl="auto")
    key = jax.random.PRNGKey(5)
    dummy = jnp.zeros((1, T), jnp.int32)
    variables = jax.jit(model.init)({"params": key, "dropout": key},
                                    dummy, dummy)
    toks = synth.sample_tokens(7, (1, T + 1), cfg.vocab_size)
    return (model, variables, llm, jnp.asarray(toks[:, :-1]),
            jnp.asarray(toks[:, 1:]))


def _blocks(params, fn):
    """`params` with fn(block, i) applied to a copy of every block."""
    out = dict(params)
    for name in params:
        if name.startswith("block_"):
            out[name] = fn(dict(params[name]), int(name.split("_")[1]))
    return out


def _fp8(a):
    """Rounded to float8_e4m3 with one scale a tensor, as an fp8 matmul
    path holds its weights."""
    if a.ndim < 2:
        return a
    s = jnp.max(jnp.abs(a)) / 448.0
    return ((a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * s).astype(a.dtype)


def _no_attention(only_layer=None):
    def fn(block, i):
        if only_layer is None or i == only_layer:
            attn = dict(block["attn"])
            attn["c_proj"] = jax.tree_util.tree_map(jnp.zeros_like,
                                                    attn["c_proj"])
            block["attn"] = attn
        return block
    return fn


def _fp8_attention(block, i):
    block["attn"] = jax.tree_util.tree_map(_fp8, block["attn"])
    return block


def test_the_program_in_bf16_passes(setup):
    model, variables, llm, x, y = setup
    res = train.compare_to_reference(model, variables, variables["params"],
                                     llm, x, y)
    assert res["ok"], res
    # the tolerance is a few times the error, not hundreds of times
    assert res["logit_error_worst"] > train.LOGIT_ERROR_TOLERANCE / 5, res


@pytest.mark.parametrize("fault", ["no_attention", "no_attention_last_layer",
                                   "fp8_weights", "fp8_attention_weights"])
def test_a_spoilt_program_fails(setup, fault):
    model, variables, llm, x, y = setup
    p = variables["params"]
    spoilt = {"no_attention": lambda: _blocks(p, _no_attention()),
              "no_attention_last_layer":
                  lambda: _blocks(p, _no_attention(llm["n_layer"] - 1)),
              "fp8_weights": lambda: jax.tree_util.tree_map(_fp8, p),
              "fp8_attention_weights": lambda: _blocks(p, _fp8_attention),
              }[fault]()
    res = train.compare_to_reference(model, {"params": spoilt}, p, llm, x, y)
    assert not res["ok"], res
    assert res["logit_error_worst"] > train.LOGIT_ERROR_TOLERANCE, res


@pytest.mark.parametrize("diagonal", [-1, 1])
def test_a_mask_one_off_fails(setup, diagonal, monkeypatch):
    model, variables, llm, x, y = setup
    true_forward = reference.forward_logits
    monkeypatch.setattr(
        reference, "forward_logits",
        lambda params, cfg, idx: true_forward(params, cfg, idx, diagonal))
    res = train.compare_to_reference(model, variables, variables["params"],
                                     llm, x, y)
    assert not res["ok"], res
