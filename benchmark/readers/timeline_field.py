"""A field of the program's step timeline (the trainer's flight recorder,
`train_timeline.jsonl` records), reduced over the steps of the window."""

from benchmark.lib import stats


def read(obs: dict, args: dict):
    vals = [e[args["field"]] for e in obs.get("timeline", [])
            if isinstance(e.get(args["field"]), (int, float))]
    if not vals:
        return None
    if args.get("stat", "median") == "mean":
        return sum(vals) / len(vals)
    return stats.median(vals)
