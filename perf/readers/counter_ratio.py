"""Window delta of program counters: numerator series over denominator series, or
over a count the benchmark took (`per_count`), times `scale`.

A series is `{"metric": name, "series": "label=value"}`; leaving `series` out sums
every series of the metric."""


def delta(obs, spec):
    before, after = obs["counters"]["before"], obs["counters"]["after"]

    def total(snapshot):
        series = snapshot.get(spec["metric"], {}).get("series", {})
        if "series" in spec:
            return series.get(spec["series"], 0.0)
        return sum(value for value in series.values() if isinstance(value, (int, float)))

    return total(after) - total(before)


def read(obs, numerator, denominator=None, per_count=None, scale=1.0):
    if "counters" not in obs:
        return None
    top = sum(delta(obs, spec) for spec in numerator)
    bottom = sum(delta(obs, spec) for spec in denominator) if denominator else obs.get("counts", {}).get(per_count)
    return scale * top / bottom if bottom else None
