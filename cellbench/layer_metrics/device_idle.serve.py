"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over the
chips used."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("chips") or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / obs["window_s"])
