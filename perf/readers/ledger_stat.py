"""A statistic (median or mean) of one field over the program's ledger records that
fell inside the window: `ledger` is "serving" (ServingLedger) or "rounds"
(RoundLedger); `where` keeps records whose fields equal the given values."""

import statistics


def select(obs, ledger, field, where=None):
    records = obs.get(ledger) or []
    return [r[field] for r in records if field in r and all(r.get(k) == v for k, v in (where or {}).items())]


def read(obs, ledger, field, stat="median", where=None, scale=1.0, times_config=None):
    values = select(obs, ledger, field, where)
    if not values:
        return None
    value = statistics.median(values) if stat == "median" else statistics.fmean(values)
    if times_config:  # e.g. occupancy x max_batch_size = rows in a device batch
        section, key = times_config
        value *= obs["config"][section][key]
    return scale * value
