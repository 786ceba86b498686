"""`perf/readers/idle_by_span.py`: the device's idle time attributed to the program's
`hivemind:` spans. Synthetic spans are spliced into the recorded cut
(`data/albert_swarm2_cut.xplane.pb`, see test_perf_trace_reduce.py); the expected
shares are worked out here gap by gap, by plain clipping, not by the code under test."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import trace_reduce as tr  # noqa: E402
from perf.readers import idle_by_span  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "albert_swarm2_cut.xplane.pb"
MS = 1e6  # ns


def _window_and_gaps(planes):
    [window] = [(s, s + d) for name, s, d in planes["/host:CPU"]["python3"] if name == tr.WINDOW_ANNOTATION]
    ops = sorted((max(s, window[0]), min(s + d, window[1])) for _n, s, d in planes["/device:TPU:0"]["XLA Ops"]
                 if s + d > window[0] and s < window[1])
    gaps, cursor = [], window[0]
    for start, end in ops:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    return window, gaps


def _idle_inside(gaps, intervals):
    """Idle ns inside the union of `intervals`, which the callers keep disjoint."""
    return sum(max(0.0, min(b, end) - max(a, start)) for a, b in gaps for start, end in intervals)


@pytest.fixture()
def spliced():
    """The recorded planes without the benchmark's own calls, with four program spans
    on two threads: a batch of 100 ms that holds an assemble of 30 ms and a step of
    40 ms, and on another thread a long span over the window's first 150 ms."""
    planes = tr.load_planes(str(RECORDED))
    window, gaps = _window_and_gaps(planes)
    t0 = window[0]
    spans = {"pool.batch": (t0 + 20 * MS, t0 + 120 * MS), "decode.assemble": (t0 + 30 * MS, t0 + 60 * MS),
             "decode.step": (t0 + 70 * MS, t0 + 110 * MS), "optimizer.step": (t0 - 5 * MS, t0 + 150 * MS)}
    planes["/host:CPU"]["python3"] = [e for e in planes["/host:CPU"]["python3"] if e[0] == tr.WINDOW_ANNOTATION]
    events = {name: ("hivemind:" + name, start, end - start) for name, (start, end) in spans.items()}
    planes["/host:CPU"]["executor"] = [events.pop("optimizer.step")]
    planes["/host:CPU"]["python3"] += list(events.values())
    return planes, window, gaps, spans


def test_the_cut_is_what_the_arithmetic_below_assumes(spliced):
    _planes, window, gaps, _spans = spliced
    assert window[1] - window[0] == pytest.approx(250 * MS)
    assert sum(b - a for a, b in gaps) == pytest.approx((0.25 - 0.17283520685) * 1e9, abs=2e3)


@pytest.mark.parametrize("labels, under, less", [
    # the shortest open span decides: assemble beats the batch and the long span around it
    (r"^decode\.(assemble|scatter)$", "decode.assemble", []),
    (r"^decode\.step$", "decode.step", []),
    # the batch is the shortest open span only where neither child is open
    (r"^pool\.batch$", "pool.batch", ["decode.assemble", "decode.step"]),
    # the long span of the other thread labels what no shorter span covers
    (r"^optimizer\.step$", "optimizer.step", ["pool.batch"]),
])
def test_idle_under_the_shortest_open_span(spliced, labels, under, less):
    planes, window, gaps, spans = spliced
    total = sum(b - a for a, b in gaps)
    clipped = (max(spans[under][0], window[0]), min(spans[under][1], window[1]))
    want = _idle_inside(gaps, [clipped]) - sum(_idle_inside(gaps, [spans[name]]) for name in less)
    assert idle_by_span.share(planes, labels=labels) == pytest.approx(100.0 * want / total, abs=1e-6)


def test_idle_outside_every_matching_span(spliced):
    planes, _window, gaps, spans = spliced
    total = sum(b - a for a, b in gaps)
    want = total - _idle_inside(gaps, [spans["pool.batch"]])
    assert idle_by_span.share(planes, outside=r"^pool\.batch$") == pytest.approx(100.0 * want / total, abs=1e-6)


def test_unlabelled_is_under_no_program_span_and_no_benchmark_call(spliced):
    planes, window, gaps, spans = spliced
    total = sum(b - a for a, b in gaps)
    covered_until = spans["optimizer.step"][1]  # the spans together cover the window's first 150 ms
    want = _idle_inside(gaps, [(covered_until, window[1])])
    assert idle_by_span.share(planes, unlabelled=True) == pytest.approx(100.0 * want / total, abs=1e-6)
    # one of the benchmark's own calls over the window's last 50 ms takes that much out
    planes["/host:CPU"]["python3"].append(("bench:peer0.loss_and_grad", window[1] - 50 * MS, 60 * MS))
    want -= _idle_inside(gaps, [(window[1] - 50 * MS, window[1])])
    assert idle_by_span.share(planes, unlabelled=True) == pytest.approx(100.0 * want / total, abs=1e-6)


def test_a_benchmark_call_cut_by_the_trace_edge_comes_from_the_runners_record(spliced):
    """`bench:` calls open at an edge of the trace are not in it; the runner's record of
    them on the process clock is placed by the calls that are in both."""
    planes, window, gaps, spans = spliced
    total = sum(b - a for a, b in gaps)
    planes["/host:CPU"]["python3"].append(("bench:peer0.batch", window[0] + 160 * MS, 10 * MS))
    offset_s = 1234.5  # process clock = trace clock / 1e9 + offset
    on_process_clock = lambda ns: ns / 1e9 + offset_s  # noqa: E731
    host_spans = [(on_process_clock(window[0] + 160 * MS), on_process_clock(window[0] + 170 * MS), "peer0.batch"),
                  (on_process_clock(window[0] + 200 * MS), on_process_clock(window[1] + 80 * MS), "peer0.optimizer_step")]
    want = _idle_inside(gaps, [(spans["optimizer.step"][1], window[0] + 160 * MS), (window[0] + 170 * MS, window[0] + 200 * MS)])
    got = idle_by_span.share(planes, unlabelled=True, host_spans=host_spans)
    assert got == pytest.approx(100.0 * want / total, abs=1e-3)


def test_every_device_plane_counts_with_its_own_idle_time(spliced):
    """A second chip that idles through the whole window: its idle seconds add to both
    sides of the share, moment by moment, whatever the first chip does meanwhile."""
    planes, window, gaps, spans = spliced
    first = sum(b - a for a, b in gaps)
    planes["/device:TPU:1"] = {"XLA Ops": []}
    whole = window[1] - window[0]
    under = spans["decode.assemble"][1] - spans["decode.assemble"][0]
    want = (_idle_inside(gaps, [spans["decode.assemble"]]) + under) / (first + whole)
    assert idle_by_span.share(planes, labels=r"^decode\.assemble$") == pytest.approx(100.0 * want, abs=1e-6)


def test_a_program_without_such_spans_reports_nothing():
    planes = tr.load_planes(str(RECORDED))  # the parent's trace: bench: calls only
    assert idle_by_span.share(planes, unlabelled=True) is None
    assert idle_by_span.share(planes, labels="^decode") is None
    assert idle_by_span.read({"trace": None}, unlabelled=True) is None
