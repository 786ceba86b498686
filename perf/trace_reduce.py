"""From a profiler trace (`.xplane.pb`) to busy and idle time, time per operation
and the longest idle gaps — read with nothing but jax's own `ProfileData`.

What counts as the device being busy: the union of the intervals of the events on
each device plane's operation line (`XLA Ops`), clipped to the traced window. The
window is the interval of the host annotation the benchmark wraps around its traced
seconds (`WINDOW_ANNOTATION`); without one it is the span of the device events.
Every number is seconds, as measured, averaged over the device planes where the
caller asks for one figure."""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

WINDOW_ANNOTATION = "bench:window"
ANNOTATION_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops",)
LABELLED_GAPS = 2000  # only the longest gaps are attributed to a host annotation


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _clip(start: float, end: float, window: Tuple[float, float]) -> Optional[Tuple[float, float]]:
    start, end = max(start, window[0]), min(end, window[1])
    return (start, end) if end > start else None


def load_planes(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """{plane name: {line name: [(event name, start ns, duration ns), ...]}}."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Tuple[str, float, float]]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for event in line.events:
                events.append((event.name, float(event.start_ns), float(event.duration_ns)))
    return planes


def op_stem(name: str) -> str:
    """`%_flash_backward.23 = (bf16[...]) custom-call(...)` -> `_flash_backward`: on a
    TPU an operation's event carries its whole HLO text; the stem is the name
    before ` = ` without the `%` and the numeric suffix, so that the instances of
    one kernel or fusion kind add up under one key."""
    return re.sub(r"[.\d]+$", "", name.split(" = ", 1)[0].lstrip("%")) or name


def reduce_planes(planes: Dict[str, Dict[str, List[Tuple[str, float, float]]]], top: int = 10,
                  host_spans: Iterable[Tuple[float, float, str]] = ()) -> Dict[str, Any]:
    """`host_spans`: (start s, end s, label) of the benchmark's own calls, in seconds
    from the start of the window annotation — they label the idle gaps beside the
    annotations in the trace, which lack every call that was open at either edge."""
    device_names = sorted(name for name in planes if DEVICE_PLANE.match(name))
    host_events = [
        (name, start, start + duration)
        for plane_name, lines in planes.items() if not DEVICE_PLANE.match(plane_name)
        for events in lines.values() for name, start, duration in events
    ]
    windows = [(s, e) for name, s, e in host_events if name == WINDOW_ANNOTATION]
    op_events = {
        device: [(name, s, s + d) for line in OP_LINES for name, s, d in planes[device].get(line, [])]
        for device in device_names
    }
    every = [event for events in op_events.values() for event in events]
    if windows:
        window = max(windows, key=lambda w: w[1] - w[0])
    elif every:
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    else:
        return {"devices": len(device_names), "window_s": 0.0, "busy_s": 0.0, "per_device_busy_s": [],
                "ops": {}, "gaps": [], "annotations": {}}

    ops: Dict[str, Dict[str, float]] = {}
    per_device_busy, gaps = [], []
    annotations = sorted({(s, e, name) for name, s, e in host_events
                          if name.startswith(ANNOTATION_PREFIX) and name != WINDOW_ANNOTATION}
                         | {(window[0] + s * 1e9, window[0] + e * 1e9, ANNOTATION_PREFIX + label)
                            for s, e, label in host_spans})
    for device in device_names:
        clipped = []
        for name, start, end in op_events[device]:
            inside = _clip(start, end, window)
            if inside is None:
                continue
            clipped.append(inside)
            entry = ops.setdefault(op_stem(name), {"seconds": 0.0, "count": 0})
            entry["seconds"] += (inside[1] - inside[0]) / 1e9 / len(device_names)
            entry["count"] += 1
        busy = _union(clipped)
        per_device_busy.append(sum(b - a for a, b in busy) / 1e9)
        edges = [window[0]] + [t for interval in busy for t in interval] + [window[1]]
        for gap_start, gap_end in zip(edges[0::2], edges[1::2]):
            if gap_end > gap_start:
                gaps.append((gap_end - gap_start, gap_start, device))
    labelled: Dict[str, float] = {}
    gaps.sort(reverse=True)
    short = sum(g[0] for g in gaps[LABELLED_GAPS:])
    if short:
        labelled[f"gaps beyond the {LABELLED_GAPS} longest"] = short / 1e9 / max(len(device_names), 1)
    for seconds_ns, gap_start, _device in gaps[:LABELLED_GAPS]:
        # what the host was doing when the device went idle: the innermost of the
        # benchmark's own annotations open at that moment
        open_now = [(e - s, name) for s, e, name in annotations if s <= gap_start < e]
        label = min(open_now)[1][len(ANNOTATION_PREFIX):] if open_now else "unlabelled"
        labelled[label] = labelled.get(label, 0.0) + seconds_ns / 1e9 / max(len(device_names), 1)
    annotation_seconds: Dict[str, float] = {}
    for s, e, name in annotations:
        inside = _clip(s, e, window)
        if inside:
            key = name[len(ANNOTATION_PREFIX):]
            annotation_seconds[key] = annotation_seconds.get(key, 0.0) + (inside[1] - inside[0]) / 1e9
    return {
        "devices": len(device_names),
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(per_device_busy) / max(len(per_device_busy), 1),
        "per_device_busy_s": per_device_busy,
        "ops": ops,
        "gaps": sorted(([label, seconds] for label, seconds in labelled.items()), key=lambda g: -g[1])[:top],
        "longest_gap_s": max((g[0] for g in gaps), default=0.0) / 1e9,
        "annotations": annotation_seconds,
    }


def reduce_trace(path: str, top: int = 10, host_spans: Iterable[Tuple[float, float, str]] = ()) -> Dict[str, Any]:
    return reduce_planes(load_planes(path), top=top, host_spans=host_spans)


def ops_matching(ops: Dict[str, Dict[str, float]], pattern: str) -> Dict[str, float]:
    """Seconds (averaged over devices) and event count of the operations whose stem
    (`op_stem`) matches `pattern` (a regular expression, searched)."""
    regex = re.compile(pattern)
    picked = [entry for name, entry in ops.items() if regex.search(name)]
    return {"seconds": sum(e["seconds"] for e in picked), "count": sum(e["count"] for e in picked)}


def breakdown(reduced: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    device_ops = sorted(([name, entry["seconds"]] for name, entry in reduced["ops"].items()), key=lambda o: -o[1])
    return {"device_ops": device_ops[:top], "idle_gaps": reduced["gaps"][:top]}
