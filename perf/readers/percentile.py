"""A percentile of ALL the samples of one kind taken in the window (nearest rank)."""

import math


def nearest_rank(values, q):
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, math.ceil(q / 100.0 * len(ordered)) - 1))]


def read(obs, samples, q):
    values = obs.get("samples", {}).get(samples)
    return nearest_rank(values, q) if values else None
