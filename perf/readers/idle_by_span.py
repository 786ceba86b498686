"""The device's idle time inside the traced window, attributed to the PROGRAM's own
spans: a share (%) of all idle seconds of the window, summed over the device planes.

The program writes its synchronous spans into the profiler's host plane as
`hivemind:<span name>` (`hivemind_tpu.telemetry.tracing.trace_sync`), on the device
trace's clock. This reader opens the run's `.xplane.pb` again and gives EVERY idle
nanosecond (window less the union of the `XLA Ops` intervals, per device plane) to
the spans open at that moment, on whatever thread. One of:

    labels      the shortest open program span's name matches this regular expression
    outside     no open program span's name matches it
    unlabelled  neither a program span nor one of the benchmark's own calls
                (`bench:<label>`) is open

It does not go through `trace_reduce.reduce_planes`, which labels only the 2000
longest gaps, by their starting point. A call of the benchmark's that was open when
the trace started or stopped is missing from the trace; the runner's own record of
its calls (`perf.runtime`, on this process's clock) fills those in, placed on the
trace's clock by the calls that are in both. A PROGRAM span cut by an edge of the
trace is not recovered: its children that began inside the trace are there, so
what is lost is at most the innermost span in flight at each edge. Returns None
where there is no trace, no window annotation, no program span or no idle time."""

import bisect
import functools
import re
import statistics

from perf.trace_reduce import ANNOTATION_PREFIX as BENCH_PREFIX
from perf.trace_reduce import DEVICE_PLANE, OP_LINES, WINDOW_ANNOTATION, _union, find_xplane, load_planes

PROGRAM_PREFIX = "hivemind:"  # `hivemind_tpu.telemetry.tracing.ANNOTATION_PREFIX`, which a parent commit may lack


def idle_intervals(planes, window):
    """Per device plane, the parts of `window` in which no operation runs, in order."""
    per_device = []
    for plane, lines in sorted(planes.items()):
        if not DEVICE_PLANE.match(plane):
            continue
        busy = _union((max(s, window[0]), min(s + d, window[1])) for line in OP_LINES
                      for _name, s, d in lines.get(line, []) if s + d > window[0] and s < window[1])
        edges = [window[0]] + [t for interval in busy for t in interval] + [window[1]]
        per_device.append([(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a])
    return per_device


def host_annotations(planes):
    """(name, start ns, end ns) of the window annotation, the program's spans and the benchmark's calls."""
    return [(name, start, start + duration)
            for plane, lines in planes.items() if not DEVICE_PLANE.match(plane)
            for events in lines.values() for name, start, duration in events
            if name.startswith((PROGRAM_PREFIX, BENCH_PREFIX))]


def cut_bench_calls(annotations, host_spans, tolerance_ns=200e3):
    """The benchmark's calls that an edge of the trace cut, on the trace's clock.
    `host_spans`: (start s, end s, label) on this process's clock. The offset between
    the clocks is the median over the calls found in both (same label, same length)."""
    traced = {}
    for name, start, end in annotations:
        if name.startswith(BENCH_PREFIX):
            traced.setdefault(name[len(BENCH_PREFIX):], []).append((start, end))
    offsets, missing = [], []
    for start_s, end_s, label in host_spans:
        twins = [s for s, e in traced.get(label, []) if abs((e - s) - (end_s - start_s) * 1e9) <= tolerance_ns]
        if len(twins) == 1:
            offsets.append(twins[0] - start_s * 1e9)
        elif not twins:
            missing.append((start_s, end_s, label))
    if not offsets:
        return []
    offset = statistics.median(offsets)
    return [(BENCH_PREFIX + label, start_s * 1e9 + offset, end_s * 1e9 + offset) for start_s, end_s, label in missing]


def idle_where(idle, annotations, window, wanted):
    """(idle ns of the moments whose open annotations satisfy `wanted`, all idle ns), of ONE
    device: `idle` is disjoint and in order. `wanted` takes the open annotations as
    [(length ns, name), ...]."""
    starts, before = [a for a, _b in idle], [0.0]
    for a, b in idle:
        before.append(before[-1] + b - a)

    def idle_until(t):
        i = bisect.bisect_right(starts, t)
        return before[i] - (max(idle[i - 1][1] - t, 0.0) if i else 0.0)

    spans = sorted((max(s, window[0]), min(e, window[1]), e - s, name) for name, s, e in annotations
                   if name != WINDOW_ANNOTATION and e > window[0] and s < window[1])
    points = sorted({window[0], window[1]} | {s for s, _e, _l, _n in spans} | {e for _s, e, _l, _n in spans})
    picked, active, following = 0.0, [], 0
    for a, b in zip(points, points[1:]):
        while following < len(spans) and spans[following][0] <= a:
            active.append(spans[following])
            following += 1
        active = [span for span in active if span[1] > a]
        if wanted([(length, name) for _s, _e, length, name in active]):
            picked += idle_until(b) - idle_until(a)
    return picked, before[-1]


def share(planes, labels=None, outside=None, unlabelled=False, host_spans=()):
    annotations = host_annotations(planes)
    windows = [(s, e) for name, s, e in annotations if name == WINDOW_ANNOTATION]
    if not windows:
        return None
    if not any(name.startswith(PROGRAM_PREFIX) for name, _s, _e in annotations):
        return None  # a program without such spans: nothing to attribute to
    window = max(windows, key=lambda w: w[1] - w[0])
    annotations += cut_bench_calls(annotations, host_spans)

    def wanted(open_now):
        if unlabelled:
            return not open_now
        program = [(length, name[len(PROGRAM_PREFIX):]) for length, name in open_now if name.startswith(PROGRAM_PREFIX)]
        if labels is not None:  # the shortest open program span decides
            return bool(program) and bool(re.search(labels, min(program)[1]))
        return not any(re.search(outside, name) for _length, name in program)

    found = [idle_where(idle, annotations, window, wanted) for idle in idle_intervals(planes, window)]
    picked, total = sum(p for p, _t in found), sum(t for _p, t in found)
    return 100.0 * picked / total if total else None


@functools.lru_cache(maxsize=1)  # a cell's metrics of this reader share one parse of the trace
def _planes(path, _modified):
    return load_planes(path)


def read(obs, labels=None, outside=None, unlabelled=False):
    if not obs.get("trace"):
        return None
    import os

    from perf import runtime

    path = find_xplane(str(runtime.TRACE_DIR))
    if path is None:
        return None
    with runtime._SPANS_LOCK:
        host_spans = list(runtime._SPANS)
    return share(_planes(path, os.path.getmtime(path)), labels=labels, outside=outside, unlabelled=unlabelled,
                 host_spans=host_spans)
