"""Median of the benchmark's own samples less the median of a ledger field over the
same requests: what lies between the two clocks (the wire, serialisation, the RPC)."""

import statistics

from perf.readers.ledger_stat import select


def read(obs, samples, ledger, field, where=None, ledger_scale=1000.0):
    mine = obs.get("samples", {}).get(samples)
    theirs = select(obs, ledger, field, where)
    if not mine or not theirs:
        return None
    return statistics.median(mine) - ledger_scale * statistics.median(theirs)
