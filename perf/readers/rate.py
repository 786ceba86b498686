"""Work completed inside the window over the window's seconds (optionally per chip).
All the work and all the time of the window: nothing is trimmed."""


def read(obs, count, per_chip=False):
    done = obs.get("counts", {}).get(count)
    if done is None or not obs.get("window_s"):
        return None
    rate = done / obs["window_s"]
    return rate / obs["chips"] if per_chip else rate
