"""`counter_ratio`, left out on a program that lacks the numerator: where only the
denominator's metric exists (an older program), `counter_ratio` would read 0.0, which
says "took no time" where the truth is "not measured"."""

from perf.readers import counter_ratio


def read(obs, numerator, **kwargs):
    after = obs.get("counters", {}).get("after", {})
    if any(spec["metric"] not in after for spec in numerator):
        return None
    return counter_ratio.read(obs, numerator, **kwargs)
