"""The per-layer metrics of ISSUE 37 over scripted observations: each reads what the
program now reports, and is left out (None, no exception) on a program that lacks it."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf import manifest as mf  # noqa: E402

ROUNDS = [
    {"purpose": "grads", "total_s": 0.7, "matchmaking_wait_s": 0.05, "encode_s": 0.2, "decode_s": 0.15,
     "reduce_s": 0.1, "loop_cpu_s": 0.5},
    {"purpose": "grads", "total_s": 0.9, "matchmaking_wait_s": 0.07, "encode_s": 0.4, "decode_s": 0.25,
     "reduce_s": 0.3, "loop_cpu_s": 0.6},
    {"purpose": "state", "total_s": 2.4, "matchmaking_wait_s": 0.3, "encode_s": 1.0, "decode_s": 1.0,
     "reduce_s": 1.0, "loop_cpu_s": 2.0},
]
OLD_ROUNDS = [{"purpose": "grads", "total_s": 0.7, "matchmaking_wait_s": 0.3}, {"purpose": "state", "total_s": 2.4}]


def _series(**phases):
    return {"type": "counter", "series": {f"phase={phase}": value for phase, value in phases.items()}}


COUNTERS = {
    "before": {
        "hivemind_wire_seconds_total": _series(seal=1.0, open=2.0, send_wait=0.5, encode=9.0, decode=1.0, reduce=0.5),
        "hivemind_wire_bytes_total": _series(seal=100e6, open=100e6, encode=1e9, decode=1e9, reduce=1e9),
        "hivemind_moe_runtime_handover_seconds_total": {"type": "counter", "series": {"_": 1.0}},
        "hivemind_moe_batches_total": {"type": "counter", "series": {"pool=a": 10.0, "pool=b": 10.0}},
    },
    "after": {
        "hivemind_wire_seconds_total": _series(seal=1.3, open=2.1, send_wait=0.9, encode=19.0, decode=3.0, reduce=0.7),
        "hivemind_wire_bytes_total": _series(seal=300e6, open=300e6, encode=2e9, decode=1.5e9, reduce=1.4e9),
        "hivemind_moe_runtime_handover_seconds_total": {"type": "counter", "series": {"_": 1.6}},
        "hivemind_moe_batches_total": {"type": "counter", "series": {"pool=a": 40.0, "pool=b": 40.0}},
    },
}
OLD_COUNTERS = {side: {"hivemind_moe_batches_total": COUNTERS[side]["hivemind_moe_batches_total"]} for side in COUNTERS}


def _read(name, obs):
    return mf.read_metric(mf.load_layer_metric(name), obs)


@pytest.mark.parametrize("name, want", [
    ("round_allreduce_ms.grads", 800.0), ("matchmaking_wait_ms.grads", 60.0), ("round_encode_ms.grads", 300.0),
    ("round_decode_ms.grads", 200.0), ("round_reduce_ms.grads", 200.0), ("round_loop_cpu_ms.grads", 550.0),
])
def test_round_metrics_read_the_gradient_rounds_alone(name, want):
    assert _read(name, {"rounds": ROUNDS}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["round_encode_ms.grads", "round_decode_ms.grads", "round_reduce_ms.grads",
                                  "round_loop_cpu_ms.grads"])
def test_round_metrics_are_left_out_on_a_program_without_the_fields(name):
    assert _read(name, {"rounds": OLD_ROUNDS}) is None and _read(name, {}) is None


@pytest.mark.parametrize("name, want", [
    ("wire_aead_ms_per_mb.train", 1.0), ("wire_aead_ms_per_mb.finetune", 1.0),  # 0.4 s over 400 MB
    ("wire_send_wait_ms_per_mb.train", 2.0), ("wire_send_wait_ms_per_mb.finetune", 2.0),  # 0.4 s over 200 MB sealed
    ("runtime_handover_ms_per_batch.finetune", 10.0),  # 0.6 s over 60 batches
    ("wire_codec_ms_per_mb.train", 8.0), ("wire_codec_ms_per_mb.finetune", 8.0),  # 10 + 2 s over 1,000 + 500 MB
    ("wire_reduce_ms_per_mb.train", 0.5),  # 0.2 s over 400 MB
])
def test_counter_metrics(name, want):
    assert _read(name, {"counters": COUNTERS}) == pytest.approx(want)
    assert _read(name, {"counters": OLD_COUNTERS}) is None and _read(name, {}) is None


def test_counter_ratio_present_wants_the_numerator_not_a_reading_of_zero():
    from perf.readers import counter_ratio, counter_ratio_present

    spec = mf.load_layer_metric("runtime_handover_ms_per_batch.finetune")["args"]
    assert counter_ratio.read({"counters": OLD_COUNTERS}, **spec) == 0.0  # what the plain reader would report
    assert counter_ratio_present.read({"counters": OLD_COUNTERS}, **spec) is None


@pytest.mark.parametrize("name", ["idle_wire_work_share.train", "idle_wire_work_share.finetune", "idle_round_wait_share.train"])
def test_trace_metrics_need_a_trace(name):
    assert _read(name, {}) is None


MS = 1e6


def _planes(*work):
    """Hand-made planes: 10 ms window, the device busy for the first 2 ms, a gradient round
    open from 2 to 10 ms on the stepping thread, and `work` on other threads."""
    return {
        "/device:TPU:0": {"XLA Ops": [("fusion", 0.0, 2 * MS)]},
        "/host:CPU": {
            "tracer": [("bench:window", 0.0, 10 * MS)],
            "stepper": [("hivemind:optimizer.step", 1 * MS, 9 * MS), ("hivemind:optimizer.grad_round", 2 * MS, 8 * MS)],
            **{f"thread{index}": [(f"hivemind:{name}", start * MS, length * MS)] for index, (name, start, length) in enumerate(work)},
        },
    }


def test_share_of_idle_time_in_the_finetune_cell_by_the_shortest_span_of_all():
    """A decode from 4 to 7 ms: of the 8 idle ms, 3 lie under wire work."""
    import re

    from perf.readers import idle_by_span

    work = mf.load_layer_metric("idle_wire_work_share.finetune")["args"]["labels"]
    assert idle_by_span.share(_planes(("wire.decode", 4, 3)), labels=work) == pytest.approx(100 * 3 / 8)
    for name in ("wire.encode", "wire.seal", "wire.open", "allreduce.reduce", "averager.load", "averager.collect"):
        assert re.search(work, name)
    assert not re.search(work, "allreduce.round")


def test_the_gradient_rounds_shares_do_not_see_the_state_rounds_work():
    """The state round's encode runs from 3 to 9 ms beside a gradient round whose own decode
    runs from 4 to 7: of the 8 idle ms the gradient round worked 3 and waited 5, whatever
    the state round had open; an AEAD frame (no purpose in its name) counts as work."""
    from perf.readers import idle_by_span, idle_by_span_among

    work = mf.load_layer_metric("idle_wire_work_share.train")["args"]
    wait = mf.load_layer_metric("idle_round_wait_share.train")["args"]
    planes = _planes(("wire.decode.grads", 4, 3), ("wire.encode.state", 3, 6))
    assert idle_by_span_among.share(planes, **work) == pytest.approx(100 * 3 / 8)
    assert idle_by_span_among.share(planes, **wait) == pytest.approx(100 * 5 / 8)
    # the shortest span of ALL is the state round's for 3 of those 5 ms: what the plain reader would answer
    assert idle_by_span.share(planes, labels=wait["labels"]) == pytest.approx(100 * 2 / 8)
    sealed = _planes(("wire.decode.grads", 4, 3), ("wire.encode.state", 3, 6), ("wire.seal", 8, 1))
    assert idle_by_span_among.share(sealed, **work) == pytest.approx(100 * 4 / 8)
    assert idle_by_span_among.share(sealed, **wait) == pytest.approx(100 * 4 / 8)
    for name in ("averager.load.grads", "averager.collect.grads", "allreduce.reduce.grads", "wire.encode.grads"):
        assert idle_by_span_among.share(_planes((name, 4, 3)), **work) == pytest.approx(100 * 3 / 8), name


def test_the_gradient_rounds_shares_are_left_out_on_a_program_without_purposes_in_its_names():
    """The parent commit has `optimizer.grad_round` and no work span: not measured, not 0 and not 100."""
    from perf.readers import idle_by_span_among

    for name in ("idle_wire_work_share.train", "idle_round_wait_share.train"):
        args = mf.load_layer_metric(name)["args"]
        assert idle_by_span_among.share(_planes(), **args) is None
        assert idle_by_span_among.share(_planes(("wire.encode.state", 3, 6), ("wire.seal", 8, 1)), **args) is None
