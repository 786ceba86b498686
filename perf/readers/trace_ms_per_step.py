"""Device time of the operations matching `pattern` in the traced seconds, per
step. Steps are counted in the trace itself where `step_pattern` is given (events
matching it / `events_per_step`), so that a step cut by the trace's edge counts by
the part of it that was traced; otherwise by the benchmark's count of the steps
that completed inside the traced seconds."""

from perf.trace_reduce import ops_matching


def steps_traced(obs, step_pattern=None, events_per_step=1, events_per_step_from_config=None):
    if events_per_step_from_config:  # e.g. one forward kernel call per layer per step
        section, key = events_per_step_from_config
        events_per_step = obs["config"][section][key]
    if step_pattern:
        return ops_matching(obs["trace"]["ops"], step_pattern)["count"] / obs["trace"]["devices"] / events_per_step
    return obs.get("traced", {}).get("steps")


def read(obs, pattern, step_pattern=None, events_per_step=1, events_per_step_from_config=None):
    if not obs.get("trace") or not obs["trace"]["devices"]:
        return None
    matched = ops_matching(obs["trace"]["ops"], pattern)
    steps = steps_traced(obs, step_pattern, events_per_step, events_per_step_from_config)
    if not matched["count"] or not steps:
        return None
    return 1000.0 * matched["seconds"] / steps
