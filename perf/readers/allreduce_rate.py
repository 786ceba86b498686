"""Bytes averaged per peer over the all-reduce phase: the window delta of the
averaging bytes-sent counter over the seconds the window's rounds spent in their
all-reduce (`total_s` of the RoundLedger's records, summed over peers), in MB/s."""

from perf.readers.counter_ratio import delta


def read(obs, metric="hivemind_averaging_bytes_sent_total", min_group_size=2):
    rounds = [r for r in obs.get("rounds") or [] if (r.get("group_size") or 0) >= min_group_size]
    seconds = sum(r.get("total_s", 0.0) for r in rounds)
    if not seconds or "counters" not in obs:
        return None
    return delta(obs, {"metric": metric}) / seconds / 1e6
