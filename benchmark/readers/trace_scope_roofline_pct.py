"""`trace_roofline_pct` for a piece of a program that is no kernel of its
own but a named scope XLA fuses as it likes (the one-token state-space
recurrence under `ssm_step`): the least time the chip could take for the
bytes ONE call needs, over the mean device self time of the ops under
`args['scopes']` a call. The time is `trace_scope_named_ms`'s (an op's owner
the innermost name on its path, `args['names']` beside SCOPE_NAMES) over
the whole step programs of `args['modules']` in the slice, divided by the
calls a step program makes (`counters[args['calls_per_step']]`, one a
layer); the bytes come from shapes through the configuration's flops
module, evaluated by the runner over the slice's steps
(`counters[args['work_per_call']]`). None where the slice has no op under
those scopes or the runner booked no work (a program without them)."""

from benchmark.readers import trace_scope_named_ms


def read(obs: dict, args: dict):
    counters = obs.get("counters", {})
    work = counters.get(args["work_per_call"])
    calls = counters.get(args["calls_per_step"])
    if not obs.get("trace") or not work or not calls:
        return None
    ms_a_step = trace_scope_named_ms.read(
        obs, {k: args[k] for k in ("modules", "scopes", "names")})
    if not ms_a_step:
        return None
    least_s = work / obs["peaks"][args["bound"]]
    return 100.0 * least_s / (ms_a_step / 1e3 / calls)
