"""Device time of the program's own jitted programs in the traced seconds, from the
runner's sums by program name (`programs`: the profiler's `XLA Modules` line, which
the reduced trace does not keep): milliseconds a run of the programs whose name
matches `pattern`. A runner that sums no programs, or a trace without a match, gives
nothing."""

import re


def read(obs, pattern):
    regex = re.compile(pattern)
    matched = [entry for name, entry in (obs.get("programs") or {}).items() if regex.search(name)]
    runs = sum(entry["count"] for entry in matched)
    return 1000.0 * sum(entry["seconds"] for entry in matched) / runs if runs else None
