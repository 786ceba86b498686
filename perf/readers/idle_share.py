"""1 - (union of device-operation intervals) / traced window, averaged over chips."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["window_s"] or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
