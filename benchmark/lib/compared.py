"""The numbers of `correct`, each beside its limit, for the result line.

A runner decides `correct` as it always did; beside it, it hands back under
`compared` what it held against which limit, as a list of these entries, and
`run.py` prints them as the last lines on standard error and under the last
key of the result line. The driver keeps only the ends of both for a run that
read false, so the names are short and nothing else follows them.

    {"name": "token_share", "value": 0.8125, "limit": 0.65,
     "holds": "at_least", "ok": true}
"""

from __future__ import annotations


def entry(name: str, value, limit, holds: str) -> dict:
    """`holds`: "at_most" or "at_least" (the limit itself passes)."""
    value = float(value)
    ok = value <= limit if holds == "at_most" else value >= limit
    return {"name": name, "value": value, "limit": float(limit),
            "holds": holds, "ok": bool(ok)}


def budgets(short: int) -> list:
    """Requests that retired on anything but their budget."""
    return [entry("requests_short", short, 0, "at_most")]


def engine_tokens(ref: dict, lim: dict) -> list:
    """A reading of a `reference_check` (tokens through the engine) under a
    mix's `reference_limits`: the limits the mix names, no others."""
    out = [entry("token_share", ref["share"], lim["token_share"],
                 "at_least")]
    if "sequence_share" in lim:
        out.append(entry("sequence_share", min(ref["shares"]),
                         lim["sequence_share"], "at_least"))
    for key in ("mean_gap", "echo_share"):
        if key in lim and key in ref:
            out.append(entry(key, ref[key], lim[key], "at_most"))
    if "repeat_share" in lim and "repeat_share" in ref:
        out.append(entry("repeat_share", ref["repeat_share"],
                         lim["repeat_share"], "at_least"))
    return out


def cache_path(path: dict, lim: dict) -> list:
    out = [entry("logit_error_median", path["median"],
                 lim["logit_error_median"], "at_most")]
    if "logit_error_sequence" in lim:
        out.append(entry("logit_error_sequence", max(path["by_sequence"]),
                         lim["logit_error_sequence"], "at_most"))
    return out


def step_programs(got: dict, lim: dict) -> list:
    return [entry(f"step_error.{'attn' if kind == '*' else kind}.{form}",
                  err, lim["step_error_median"][kind], "at_most")
            for kind, by_form in got["by_kind"].items()
            for form, err in by_form.items()]


def lines(compared: list) -> list:
    """What `run.py` prints on standard error, one line a number."""
    return [f"compared {c['name']} {c['value']:.6g} "
            f"({'at most' if c['holds'] == 'at_most' else 'at least'} "
            f"{c['limit']:.6g}) {'ok' if c['ok'] else 'FAILS'}"
            for c in compared]


def of_line(compared: list) -> dict:
    """The result line's last key: {name: [value, limit, ok]}."""
    return {c["name"]: [c["value"], c["limit"], c["ok"]] for c in compared}
