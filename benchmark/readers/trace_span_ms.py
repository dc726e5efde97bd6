"""Milliseconds of the program's own host phases in the traced slice, per
step (`lib/trace_spans.py`): the phases of `args['require']` that share a
`step` stat make one step, and a step that lacks one (cut by the slice's
edge) is left out. `kind` says what is taken of each step, over
`args['phases']`:

  extent      start of the first phase to the end of the second
  sum         the phases' durations, summed
  turnaround  end of the first phase in step n to the start of the second
              in step n + 1 (what lies between two steps of the layer)

The median over the steps; None where the slice holds no such phase (a
program without them, as before PR 25)."""

from benchmark.lib import trace_spans


def read(obs: dict, args: dict):
    sl = trace_spans.load()
    if sl is None:
        return None
    steps = trace_spans.steps_by_stat(
        trace_spans.phase_events(sl, args["layer"]), args["require"])
    a, b = args["phases"][0], args["phases"][-1]
    if args["kind"] == "extent":
        vals = trace_spans.step_extent_ms(steps, a, b)
    elif args["kind"] == "turnaround":
        vals = trace_spans.step_turnaround_ms(steps, a, b)
    else:
        vals = trace_spans.step_sum_ms(steps, args["phases"])
    return trace_spans.median(vals)
