"""The reduction from a profiler trace to busy, idle, kernel and collective time.

`data/albert_swarm2_cut.xplane.pb` is 0.25 s cut from this PR's first traced chip
run of `albert-base.swarm2` (TPU v5 lite; events kept from 2 ms before the window
to 2 ms after it, so that clipping is exercised; HLO texts shortened, statistics
dropped). The expected values were worked out from the protobuf by plain
arithmetic, not by the code under test."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import trace_reduce as tr  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "albert_swarm2_cut.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_trace(str(RECORDED))


@pytest.mark.parametrize("key, want", [("window_s", 0.25), ("busy_s", 0.17283520685), ("devices", 1)])
def test_recorded_trace_window_and_busy(recorded, key, want):
    assert recorded[key] == pytest.approx(want, abs=2e-6)


def test_recorded_trace_idle_share(recorded):
    assert 1 - recorded["busy_s"] / recorded["window_s"] == pytest.approx(0.3086591726, abs=1e-5)


@pytest.mark.parametrize("stem, seconds, count", [
    ("_flash_backward", 0.067916578266, 20), ("_flash_forward", 0.046340244376, 12),
    ("fusion", 0.021155949532, 161), ("convolution_add_fusion", 0.011072338594, 67), ("copy", 0.007019416878, 123),
])
def test_recorded_trace_kernel_times(recorded, stem, seconds, count):
    assert recorded["ops"][stem]["seconds"] == pytest.approx(seconds, abs=2e-6)
    assert recorded["ops"][stem]["count"] == count
    matched = tr.ops_matching(recorded["ops"], f"^{stem}$")
    assert matched["count"] == count


def test_recorded_trace_breakdown_and_gaps(recorded):
    top = tr.breakdown(recorded)
    assert [name for name, _ in top["device_ops"][:2]] == ["_flash_backward", "_flash_forward"]
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    # the device sat idle for the first 54 ms of this cut while both peers were inside Optimizer.step
    assert recorded["longest_gap_s"] == pytest.approx(0.054172776, abs=2e-6)
    assert sum(seconds for _, seconds in recorded["gaps"]) == pytest.approx(0.25 - recorded["busy_s"], abs=1e-6)
    assert recorded["annotations"]["peer0.optimizer_step"] > 0.1


def test_host_spans_label_gaps_the_trace_cannot():
    """A call that was open when the trace started is not in the trace; the runner's
    own record of it labels the gap."""
    reduced = tr.reduce_trace(str(RECORDED), host_spans=[(-3.0, 0.060, "swarm-round")])
    assert dict(reduced["gaps"])["swarm-round"] >= 0.054


def _planes(device_events, host_events=()):
    return {"/device:TPU:0": {"XLA Ops": list(device_events)}, "/host:CPU": {"main": list(host_events)}}


def test_union_of_overlapping_operations_counts_once():
    # two operations overlap for 2 ms inside a 10 ms window; a third runs alone
    planes = _planes([("%fusion.1 = f32[] fusion()", 1e6, 4e6), ("%all-reduce.7 = f32[] all-reduce()", 3e6, 4e6),
                      ("%fusion.2 = f32[] fusion()", 8e6, 1e6)],
                     [(tr.WINDOW_ANNOTATION, 0.0, 10e6), ("bench:wait", 7e6, 1e6)])
    reduced = tr.reduce_planes(planes)
    assert reduced["window_s"] == pytest.approx(0.010) and reduced["busy_s"] == pytest.approx(0.007)
    assert reduced["ops"]["fusion"] == {"seconds": pytest.approx(0.005), "count": 2}
    assert tr.ops_matching(reduced["ops"], "^(all-reduce|reduce-scatter)")["seconds"] == pytest.approx(0.004)
    assert dict(reduced["gaps"])["wait"] == pytest.approx(0.001)
    assert dict(reduced["gaps"])["unlabelled"] == pytest.approx(0.002)


def test_busy_is_averaged_over_device_planes_and_clipped_to_the_window():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("%a = op()", -5e6, 10e6)]},  # half of it before the window
        "/device:TPU:1": {"XLA Ops": [("%a = op()", 2e6, 2e6)], "Steps": [("step", 0.0, 10e6)]},
        "/host:CPU": {"main": [(tr.WINDOW_ANNOTATION, 0.0, 10e6)]},
    }
    reduced = tr.reduce_planes(planes)
    assert reduced["devices"] == 2 and reduced["per_device_busy_s"] == pytest.approx([0.005, 0.002])
    assert reduced["busy_s"] == pytest.approx(0.0035)
    assert reduced["ops"]["a"]["seconds"] == pytest.approx(0.0035)  # averaged over the chips


def test_a_trace_without_a_device_reduces_to_nothing():
    reduced = tr.reduce_planes({"/host:CPU": {"main": [(tr.WINDOW_ANNOTATION, 0.0, 1e6)]}})
    assert reduced["devices"] == 0 and reduced["busy_s"] == 0.0 and reduced["ops"] == {}


@pytest.mark.parametrize("name, stem", [
    ("%_flash_backward.23 = (bf16[384,512,64]{2,1,0}) custom-call(bf16[384,512,64] %bitcast.1470)", "_flash_backward"),
    ("%fusion.1204 = f32[16]{0} fusion(f32[16] %p)", "fusion"), ("%all-reduce.3 = f32[8] all-reduce()", "all-reduce"),
    ("%copy-done = f32[8] copy-done()", "copy-done"), ("plain_name", "plain_name"),
])
def test_op_stem(name, stem):
    assert tr.op_stem(name) == stem
