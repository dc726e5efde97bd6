"""A count or a gauge the runner read from the program (retrace guards,
memory watermark), as a difference over the window where it is a count."""


def read(obs: dict, args: dict):
    return obs.get("counters", {}).get(args["name"])
