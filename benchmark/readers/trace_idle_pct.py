"""The device's idle share of the traced slice: 1 - union of the device-op
intervals / traced span, averaged over the chips used."""


def read(obs: dict, args: dict):
    tr = obs.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
