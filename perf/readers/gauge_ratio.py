"""A gauge's value when the window closed over another's, times `scale`: what the
program held at that moment, per unit of what it held it for. A series is
`{"metric": name, "series": "label=value"}`. A program without the gauge (a parent
commit), or a denominator at zero, gives nothing."""


def level(obs, spec):
    series = obs["counters"]["after"].get(spec["metric"], {}).get("series", {})
    return series.get(spec["series"])


def read(obs, numerator, denominator, scale=1.0):
    if "counters" not in obs:
        return None
    top, bottom = level(obs, numerator), level(obs, denominator)
    return scale * top / bottom if top is not None and bottom else None
