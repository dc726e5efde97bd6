"""Runner of kind `serve_closed_parallel`: `serve_closed_patterned`'s run, for
a patterned model whose blocks run TWO mixers side by side on one normed
input ('P': a state-space mixer and attention; every such layer keeps a
slot's state AND blocks of the paged pool) and that has no expert layer.
Nothing of that runner is copied (ROADMAP D12): its `run` is imported and
called, with `serve_closed_window`'s helpers around it; this file adds what
both lack, and no more:

  * the letter 'P' among the blocks `step_programs` probes: what such a
    block adds to the residual stream is what its module `mixer_sum`
    returns (both branches, each times its output multiplier);
  * two rules for the drawn tree (`CONDITIONING`): `divide_by_multipliers`
    and `draw_conv_bias`, said under `changed` in the configuration file;
  * the counters of the two kinds of state, read off the engine over the
    window as the expert counters are (`ParallelCounts.FIELDS`): key rows
    the attention branches' calls read, float32 state the state-space
    branches' calls moved, state resets;
  * over the traced slice, the state bytes ONE one-token recurrence had to
    move (`flops.ssm_step_bytes_per_call`), for its roofline;
  * resident bytes by kind of state and the paths the two step programs
    took, said;
  * `serve_closed_window._donating` as the probe: 4.9 GB of pools and state
    held twice does not fit beside 8.4 GB of weights. The engine's own tree
    is consumed by the first probed call, so `step_programs` is the mix's
    last procedure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import harness
from benchmark.runners import serve_closed_patterned as base
from benchmark.runners.serve_closed_window import (KINDS, _check_base,
                                                   _donating, _patched)


class ParallelCounts(base.GraniteCounts):
    FIELDS = base.GraniteCounts.FIELDS + tuple(
        f"{name}.{kind}" for name in ("kv_rows_read_full_by",
                                      "ssm_state_bytes_by")
        for kind in KINDS)


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2), donate_argnums=0)
def _columns_divided(w, edges: tuple, by: tuple):
    """`w` (rows, columns), the columns [edges[i], edges[i + 1]) divided by
    `by[i]`, in float32, back in `w`'s dtype."""
    vec = jnp.concatenate([jnp.full((b - a,), 1.0 / m, jnp.float32)
                           for a, b, m in zip(edges, edges[1:], by)])
    return (w.astype(jnp.float32) * vec).astype(w.dtype)


def divide_by_multipliers(params: dict, llm: dict, ctx: dict) -> dict:
    """Each matrix that a published multiplier follows, divided by it, so
    that multiplier x matrix has the deviation of a plain draw (the
    configuration file's `changed` says why: as drawn, the multipliers
    silence whole terms, and a comparison under which a lost term passes
    is not support). The program still applies every multiplier, apart."""
    def whole(w, m):
        return _columns_divided(w, (0, w.shape[1]), (float(m),))

    F, hs = llm["dense_up_dim"], llm["head_dim"]
    qw, kvw = llm["n_head"] * hs, llm["n_kv_heads"] * hs
    d_inner = llm["ssm_heads"] * llm["ssm_head_dim"]
    gn = llm["ssm_groups"] * llm["ssm_state"]
    seg = np.cumsum([0, d_inner, d_inner, gn, gn, llm["ssm_heads"]])
    out = dict(params)
    out["tkn_emb"] = {"embedding": whole(params["tkn_emb"]["embedding"],
                                         llm["embed_mult"])}
    out["lm_head"] = whole(params["lm_head"], 1.0 / llm["logits_div"])
    for i, kind in enumerate(llm["layer_pattern"]):
        b = dict(params[f"block_{i}"])
        if kind == "F":
            m = b["mlp"]
            b["mlp"] = {"c_fc": _columns_divided(
                m["c_fc"], (0, F, 2 * F), (llm["mlp_gate_mult"], 1.0)),
                "c_proj": whole(m["c_proj"], llm["mlp_down_mult"])}
        else:
            a, s = b["attn"], b["ssm"]
            b["attn"] = {
                "c_attn": {"kernel": _columns_divided(
                    a["c_attn"]["kernel"], (0, qw, qw + kvw, qw + 2 * kvw),
                    tuple(llm["attn_in_mult"] * m
                          for m in (1.0, llm["key_mult"], 1.0)))},
                "c_proj": {"kernel": whole(a["c_proj"]["kernel"],
                                           llm["attn_out_mult"])}}
            b["ssm"] = {**s, "in_proj": _columns_divided(
                s["in_proj"], tuple(int(e) for e in seg),
                tuple(llm["ssm_in_mult"] * m for m in llm["ssm_mults"])),
                "out_proj": whole(s["out_proj"], llm["ssm_out_mult"])}
        out[f"block_{i}"] = b
    return out


def draw_conv_bias(params: dict, llm: dict, ctx: dict) -> dict:
    """The convolutions' biases as torch's Conv1d draws them, uniform on
    +-1/sqrt(K): the program draws zeros, under which the bias is no term
    of the comparison."""
    key = jax.random.PRNGKey(harness.seed31(ctx["seed"]) + 2)
    bound = 1.0 / float(np.sqrt(llm["ssm_conv"]))
    out = dict(params)
    for i, kind in enumerate(llm["layer_pattern"]):
        if kind != "P":
            continue
        b = params[f"block_{i}"]
        bias = b["ssm"]["conv_b"]
        out[f"block_{i}"] = {**b, "ssm": {**b["ssm"], "conv_b": jax.random.uniform(
            jax.random.fold_in(key, i), bias.shape, jnp.float32, -bound,
            bound).astype(bias.dtype)}}
    return out


CONDITIONING = {"divide_by_multipliers": divide_by_multipliers,
                "draw_conv_bias": draw_conv_bias}


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def state_counters(grew: dict) -> dict:
    """The counters of the two kinds of state, from the growth of the
    engine's lifetime counts."""
    return {"state_resets": grew["state_resets"],
            "chunk_program_share_pct": 100.0 * grew["chunk_programs"]
            / max(grew["n_steps"], 1),
            "kv_rows_read_full": sum(
                grew[f"kv_rows_read_full_by.{k}"] for k in KINDS),
            "ssm_state_bytes": sum(
                grew[f"ssm_state_bytes_by.{k}"] for k in KINDS)}


def run(ctx: dict) -> dict:
    say = ctx["say"]
    held: dict = {}

    class Counts(ParallelCounts):
        def __init__(self, engine):
            super().__init__(engine)
            held["timed"], held["engine"] = self, engine

    async def drive(*args):
        held["marks"] = await held["drive"](*args)
        return held["marks"]

    assert "step_programs" not in ctx["traffic"]["reference_procedures"][
        :-1], "step_programs consumes the engine's cache tree: name it last"
    _check_base()
    held["drive"] = base._drive
    with _patched(GraniteCounts=Counts, _drive=drive, _probed=_donating,
                  _MIXER_MODULES={**base._MIXER_MODULES, "P": "mixer_sum"},
                  CONDITIONING={**base.CONDITIONING, **CONDITIONING}):
        out = base.run(ctx)
    timed, engine, marks = held["timed"], held["engine"], held["marks"]
    llm = ctx["config"]["llm_config"]
    flops = base._lib(ctx["traffic"]["flops"])
    counters = out["observations"]["counters"]
    grew = timed.between(marks["t_open"], marks["t_close"])
    counters.update(state_counters(grew))
    by = engine.resident_bytes_by_kind
    n_p = llm["layer_pattern"].count("P")
    say(f"resident bytes by kind of state: {by} = {sum(by.values())} "
        f"({100.0 * sum(by.values()) / ctx['peaks']['hbm_bytes']:.1f}% of "
        f"the chip); a slot's state and tails "
        f"{by['slot_state'] // max(engine.n_slots, 1)} B over {n_p} layers, "
        f"whatever max_len, and up to "
        f"{engine.max_blocks * engine.block_size * flops.kv_bytes_per_row(llm, np.dtype(engine.cache_dtype).itemsize)}"
        f" B of the pools")
    say(f"the two kinds of state in the window: key rows read by the "
        f"attention branches' calls {counters['kv_rows_read_full']} "
        f"(chunk {grew['kv_rows_read_full_by.chunk']}, decode "
        f"{grew['kv_rows_read_full_by.decode']}), float32 state moved by the "
        f"state-space branches' calls {counters['ssm_state_bytes']} B "
        f"(chunk {grew['ssm_state_bytes_by.chunk']}, decode "
        f"{grew['ssm_state_bytes_by.decode']}), state resets "
        f"{counters['state_resets']}, prefix reuse declined "
        f"{grew['prefix_reuse_declined']}; programs with a chunk "
        f"{counters['chunk_program_share_pct']:.2f}% of {grew['n_steps']}; "
        f"overlap_share "
        f"{engine.overlap_share:.4f} drain_reasons {engine.drain_reasons}")
    from distributed_pytorch_tpu.obs import paths
    chosen = paths.choices()
    say(f"paths the programs traced in this process took: {chosen}")
    say("attention calls that fell back to paged_gather or the masked XLA "
        f"path: {sum('gather' in v for v in chosen.values())}")
    if ctx["trace"]:
        sl = timed.between(marks["t_trace0"], marks["t_trace1"])
        # a layer's call moves the state of a program's decoding slots:
        # their mean over the slice's programs, from the engine's count
        live = sl["ssm_state_bytes_by.decode"] / max(sl["n_steps"], 1) \
            / flops.ssm_step_bytes_per_call(llm, 1.0) / n_p
        counters["ssm_step_bytes_per_call"] = \
            flops.ssm_step_bytes_per_call(llm, live)
        counters["ssm_step_calls_per_step"] = n_p
    return out
