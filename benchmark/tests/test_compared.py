"""The numbers of `correct` beside their limits: what a runner hands back
under `compared`, what `run.py` prints of it, and that a mix holds the
limits it names and no others."""

import json

import pytest

from benchmark.lib import compared, harness

TOKENS = {"share": 0.8125, "shares": [0.703, 0.906, 0.875, 0.875, 0.703],
          "mean_gap": 0.04647, "repeat_share": 1.0, "echo_share": 0.0}
LIMITS = {"token_share": 0.76, "sequence_share": 0.735, "mean_gap": 0.06,
          "repeat_share": 0.9, "echo_share": 0.2, "logit_error_median": 0.2,
          "logit_error_sequence": 0.24,
          "step_error_median": {"C": 0.015, "*": 0.015}}


def _by_name(entries):
    return {c["name"]: c for c in entries}


@pytest.mark.parametrize("value,limit,holds,ok", [
    (0.76, 0.76, "at_least", True), (0.7599, 0.76, "at_least", False),
    (0.06, 0.06, "at_most", True), (0.0601, 0.06, "at_most", False),
    (0, 0, "at_most", True), (1, 0, "at_most", False)])
def test_a_limit_itself_passes(value, limit, holds, ok):
    assert compared.entry("x", value, limit, holds)["ok"] is ok


def test_the_refused_runs_reading_fails_by_its_sequence_alone():
    """Seed 913484337 of `lfm2moe_serve_closed128` under the limits it was
    refused by (my chip run, PR 52): one sequence of 128 tokens at 0.703."""
    got = _by_name(compared.engine_tokens(TOKENS, LIMITS))
    assert [n for n, c in got.items() if not c["ok"]] == ["sequence_share"]
    assert got["sequence_share"]["value"] == 0.703


def test_a_mix_holds_the_limits_it_names_and_no_others():
    lim = {k: v for k, v in LIMITS.items() if k != "sequence_share"}
    got = _by_name(compared.engine_tokens(TOKENS, lim))
    assert "sequence_share" not in got and all(c["ok"] for c in got.values())
    # the hybrid cell's mix: two shares, a median
    lim = {"token_share": 0.45, "sequence_share": 0.25,
           "logit_error_median": 0.22}
    assert set(_by_name(compared.engine_tokens(TOKENS, lim))) == {
        "token_share", "sequence_share"}
    path = {"median": 0.1, "by_sequence": [0.3, 0.1]}
    assert set(_by_name(compared.cache_path(path, lim))) == {
        "logit_error_median"}
    got = _by_name(compared.cache_path(path, LIMITS))
    assert not got["logit_error_sequence"]["ok"]
    assert got["logit_error_median"]["ok"]


def test_step_programs_names_every_kind_and_form():
    got = _by_name(compared.step_programs(
        {"by_kind": {"C": {"chunk": 0.004, "decode": 0.02},
                     "*": {"chunk": 0.004, "decode": 0.004}}}, LIMITS))
    assert set(got) == {"step_error.C.chunk", "step_error.C.decode",
                        "step_error.attn.chunk", "step_error.attn.decode"}
    assert [n for n, c in got.items() if not c["ok"]] == [
        "step_error.C.decode"]


def test_what_run_py_prints():
    held = compared.budgets(0) + compared.engine_tokens(TOKENS, LIMITS)
    lines = compared.lines(held)
    assert len(lines) == len(held) and all(
        ln.startswith("compared ") and "\n" not in ln for ln in lines)
    assert lines[2] == "compared sequence_share 0.703 (at least 0.735) FAILS"
    line = json.loads(json.dumps(compared.of_line(held)))
    assert line["sequence_share"] == [0.703, 0.735, False]
    assert line["requests_short"] == [0.0, 0.0, True]
    # the contract's names: letters, digits, `_`, `.`, `-`
    assert all(n.replace("_", "").replace(".", "").isalnum() for n in line)


def test_the_lfm2_mix_holds_no_single_sequence():
    """PR 52, after the refusal: a sequence's share is the reading of 128
    tokens and swings with the prompt (0.703 to 0.92 over sound runs); the
    mix reports it and holds the share of all tokens (PERF.md section 2)."""
    bench = harness.load_benchmark()
    t = harness.resolve_cell(bench, "lfm2moe_serve_closed128")["traffic"]
    assert "sequence_share" not in t["reference_limits"]
    assert {"token_share", "mean_gap", "repeat_share", "echo_share"} <= set(
        t["reference_limits"])
