"""A series the benchmark timed itself, on its own clock, around the calls
into a layer (the engine steps the scheduler makes, say)."""

from benchmark.lib import stats


def read(obs: dict, args: dict):
    vals = obs.get("clock", {}).get(args["series"])
    if not vals:
        return None
    if args.get("stat", "median") == "mean":
        return sum(vals) / len(vals)
    return stats.median(vals)
