"""`counter_ratio` over the LEAD-IN's counters (`counters_lead`: from the moment the
slots are told to go until the window opens), for a cell whose window holds none of
what the ratio counts (every prompt of `long_sessions` is sent in the lead-in). A
runner that does not read the counters there, or a program that lacks the numerator,
gives nothing."""

from perf.readers import counter_ratio_present


def read(obs, numerator, **kwargs):
    if "counters_lead" not in obs:
        return None
    return counter_ratio_present.read({**obs, "counters": obs["counters_lead"]}, numerator, **kwargs)
