"""The seam between the engine and what it counts (engine/counts.py): every
entry of the ONE table is readable off the engine, stands at `/metrics` and
in `/debug/timeline` where the table says so, and agrees with the sum of the
flight record's per-program fields; every total that has a per-program field
is that field's sum. And the operator's surface (gauge names and help texts,
timeline keys, flight-record keys of a classic and a patterned engine) is
the golden's, which was made at the parent commit of PR 64
(`python tests/test_counts.py <file>` writes it), plus that PR's two
additions."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
try:
    from distributed_pytorch_tpu.engine import counts
except ImportError:     # the parent's tree, where the golden is made
    counts = None
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.serve.scheduler import Scheduler
from distributed_pytorch_tpu.serve.server import ServeApp

GOLDEN = pathlib.Path(__file__).with_name("operator_surface_golden.json")
CHUNK = 16
CLASSIC = dict(vocab_size=256, block_size=64, n_embd=32, n_layer=2, n_head=2,
               n_kv_heads=2, attn="gqa", pos_emb="rope", up_dim=64,
               non_linearity="gelu")
# one layer of every kind that books a count of its own: linear attention,
# experts (softmax-routed: the tile counts), state space, latent attention
PATTERNED = dict(
    vocab_size=256, block_size=128, n_embd=64, n_layer=6,
    layer_pattern="KEMLEF", pos_emb="rope", rope_theta=6e6,
    rope_pairing="adjacent", norm_eps=1e-6, tie_head=False, attn="mla",
    n_head=4, q_latent_dim=0, kv_latent_dim=32, rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=24, dense_up_dim=96, shared_up_dim=24,
    n_exp=9, n_shared=1, n_act=4, router="softmax_topk", experts_held=(0, 4),
    kda_heads=4, kda_head_dim=16, kda_conv=4, kda_lower_bound=-5.0,
    ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=16, ssm_conv=4,
    ssm_chunk=8)
# the recorder's own fields that depend on the platform or on the turn
_TURN_FIELDS = {"owner", "cause", "excess_ms", "median_ms", "source",
                "gc_gen", "gap_ms", "sched_delay_ms", "steal_ms", "nivcsw"}


def _model(kw):
    model = LLM(LLMConfig(**kw), compute_dtype=jnp.float32)
    return model, model.init({"params": jax.random.PRNGKey(0)},
                             jnp.zeros((1, 8), jnp.int32))


def _prompts(lens):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def driven(kw, **engine_kw):
    """An engine of `kw` behind a scheduler, driven over three prompts (one
    of two chunks) through two slots: chunk-carrying and plain programs.
    Returns (engine, scheduler, the /debug/timeline payload)."""
    model, variables = _model(kw)
    eng = DecodeEngine(model, variables, n_slots=2, max_len=64, block_size=8,
                       min_bucket=8, prefix_cache=False,
                       **{"prefill_chunk": CHUNK, **engine_kw})
    sched = Scheduler(eng, max_queue=4)
    eng.run(_prompts((20, 9, 5)), 4)
    app = ServeApp.__new__(ServeApp)
    app.scheduler = type("S", (), {"engine": eng})()
    payload = json.loads(app._debug_timeline({}).split(b"\r\n\r\n", 1)[1])
    return eng, sched, payload


def surface(eng, sched, payload) -> dict:
    """What an operator can name of a driven engine. Needs nothing of
    engine/counts.py, so the parent's tree makes the golden with it."""
    return {"gauges": {name: text for name, (_, text) in
                       sorted(sched.metrics._gauges.items())
                       if name.startswith("serve_")},
            "timeline": sorted(payload),
            "flight_record": sorted(
                set().union(*map(set, eng.flight.entries())) - _TURN_FIELDS)}


@pytest.fixture(scope="module")
def patterned():
    return driven(PATTERNED)


@pytest.fixture(scope="module")
def classic():
    return driven(CLASSIC)


def _sum(recs, field):
    return sum(r[field] for r in recs)


# a derived reading, from the flight record's per-program fields
FROM_RECORDS = {
    "overlap_share": lambda recs: _sum(recs, "overlapped") / len(recs),
    "tokens_per_step": lambda recs: _sum(recs, "emitted") / len(recs),
    "chunk_fill_share": lambda recs: _sum(recs, "prefill_tokens") / (
        CHUNK * sum(r["prefill_tokens"] > 0 for r in recs)),
    "decode_tiles_per_grid_step": lambda recs: _sum(
        recs, "decode_live_tiles") / _sum(recs, "decode_live_steps"),
    "experts_hit_per_call": lambda recs: _sum(recs, "experts_hit")
    / _sum(recs, "expert_calls"),
    "expert_second_tiles_per_call": lambda recs: _sum(
        recs, "expert_second_tiles") / _sum(recs, "expert_calls"),
    "merged_program_share": lambda recs: np.mean(
        [r["expert_calls"] == PATTERNED["layer_pattern"].count("E")
         for r in recs if r["prefill_tokens"]]),
}
# a total of the engine and the per-program field it is the sum of
TOTAL_OF = {"experts_hit": "experts_hit", "expert_calls": "expert_calls",
            "absent_assignments": "absent_assignments",
            "expert_second_tiles": "expert_second_tiles",
            "state_resets": "state_reset",
            "ssm_state_bytes": "ssm_state_bytes",
            "kv_rows_read_full": "kv_rows_read_full",
            "kv_rows_read_window": "kv_rows_read_window",
            "decode_live_tiles": "decode_live_tiles",
            "decode_live_steps": "decode_live_steps",
            "prefilled_tokens": "prefill_tokens",
            "emitted_tokens": "emitted", "overrun_tokens": "overrun",
            "overlapped_programs": "overlapped",
            "spec_drafted_tokens": "drafted",
            "spec_accepted_tokens": "accepted"}


# what this engine never moves: no prefix cache, no drafts, no window layer,
# too few rows for an expert's second tile
ZERO_HERE = {"prefix_hit_rate", "accepted_token_rate", "kv_rows_read_window",
             "window_rows_saved", "expert_second_tiles_per_call"}


@pytest.mark.parametrize("reading", counts.READINGS if counts else (),
                         ids=lambda r: r.name)
def test_a_reading_stands_wherever_the_table_says(patterned, reading):
    eng, sched, payload = patterned
    value = getattr(eng, reading.name)          # forwarded to eng.counts
    assert value == getattr(eng.counts, reading.name)
    moved = sum(value.values()) if isinstance(value, dict) else value
    assert (moved == 0) == (reading.name in ZERO_HERE)
    if reading.metric:
        read, text = sched.metrics._gauges[reading.metric]
        assert text == reading.help and text
        assert read() == value
        assert f"# HELP {reading.metric} {text}" in sched.metrics.render_prometheus()
    assert (reading.name in payload) == reading.timeline
    if reading.timeline:
        assert payload[reading.name] == value
    recs = eng.flight.entries()
    assert len(recs) == eng.n_steps == eng.counts.n_programs
    if reading.name in TOTAL_OF:
        assert _sum(recs, TOTAL_OF[reading.name]) == value
    if reading.name in FROM_RECORDS:
        assert FROM_RECORDS[reading.name](recs) == pytest.approx(value)


@pytest.mark.parametrize("total", sorted(TOTAL_OF))
def test_a_total_is_the_sum_of_its_programs_shares(patterned, total):
    eng = patterned[0]
    assert getattr(eng, total) == _sum(eng.flight.entries(), TOTAL_OF[total])


def test_an_engine_without_counts_reads_the_tables_zero():
    """A test's fake engine still gets every gauge and timeline key."""
    fake = object()
    got = {name: read() for name, read, _ in counts.gauges(fake)}
    assert got == {r.metric: r.zero for r in counts.READINGS if r.metric}
    assert got["serve_engine_tokens_per_step"] == 1.0
    assert counts.timeline(fake) == {r.name: r.zero for r in counts.READINGS
                                     if r.timeline}


def test_the_engine_holds_no_counter_of_its_own(patterned):
    """A counter set on the engine would shadow the forwarded name."""
    eng = patterned[0]
    mine = set(vars(eng.counts)) | {
        n for n, v in vars(type(eng.counts)).items()
        if isinstance(v, property)}
    assert not mine & (set(vars(eng)) | set(vars(type(eng))))
    with pytest.raises(AttributeError):
        eng.no_such_count


def test_a_wave_admission_books_its_own_prefill():
    """No chunk ever drains for it: the admission books the prompt's state
    reset, state bytes, linear-attention steps and expert calls itself."""
    eng, _, _ = driven(PATTERNED, prefill_chunk=0)
    lens, pat = (20, 9, 5), PATTERNED["layer_pattern"]
    c = eng.counts
    assert c.n_admitted == c.state_resets == len(lens)
    assert c.prompt_tokens == c.prefilled_tokens == sum(lens)
    assert c.kda_slot_steps_by["chunk"] == sum(lens) * pat.count("K")
    assert c.ssm_state_bytes_by["chunk"] == \
        len(lens) * 2 * c.state_bytes_slot > 0
    recs = eng.flight.entries()
    assert c.expert_calls == \
        _sum(recs, "expert_calls") + len(lens) * pat.count("E")
    assert c.chunk_programs == 0 and c.overlap_share == 0.0 \
        and set(c.drain_reasons) == {"wave"}


# -- the operator's surface against the golden made at the parent ----------

#: what PR 64 added, through the table alone
ADDED = {"gauges": {"serve_kda_slot_steps_total"},
         "timeline": {"kda_slot_steps_by"}, "flight_record": set()}


@pytest.mark.parametrize("what", ["classic", "patterned"])
@pytest.mark.parametrize("part", ["gauges", "timeline", "flight_record"])
def test_the_operators_surface_is_the_parents(request, what, part):
    now = surface(*request.getfixturevalue(what))[part]
    golden = json.loads(GOLDEN.read_text())[what][part]
    if part == "gauges":                    # the help texts too
        assert {k: v for k, v in now.items() if k in golden} == golden
    assert set(now) - set(golden) == ADDED[part]
    assert not set(golden) - set(now)


if __name__ == "__main__":
    pathlib.Path(sys.argv[1]).write_text(json.dumps(
        {what: surface(*driven(kw)) for what, kw in
         (("classic", CLASSIC), ("patterned", PATTERNED))},
        indent=1, sort_keys=True) + "\n")
