"""A kernel's share of its roofline: the least time the chip could take for
the bytes (or operations) one call of the kernel needs, over the mean
device time of its calls in the traced slice. Per call, so that a slice
whose edges cut a step in two reads the same as one that does not. The
bytes come from shapes, through a function in benchmark/lib/flops.py that
the runner evaluated over the steps of the slice
(`counters[args['work_per_call']]`); `bound` names the peak they are set
against."""

from benchmark.lib import trace_reduce


def read(obs: dict, args: dict):
    tr = obs.get("trace")
    work = obs.get("counters", {}).get(args["work_per_call"])
    if not tr or not work:
        return None
    calls = trace_reduce.count_matching(tr["ops_dev0"], args["patterns"])
    if not calls:
        return None
    ns = trace_reduce.matching_ns(tr["ops_dev0"], args["patterns"])
    least_s = work / obs["peaks"][args["bound"]]
    return 100.0 * least_s / (ns / 1e9 / calls)
