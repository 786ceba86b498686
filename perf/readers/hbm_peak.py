"""`memory_stats()` after the window: `peak_bytes_in_use` + `peak_bytes_reserved`, largest over the cell's chips, in GB."""


def read(obs):
    peak = obs.get("device", {}).get("memory_peak_bytes")
    return peak / 1e9 if peak else None
