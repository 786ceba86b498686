"""`wire_frames_per_token.decode` (ISSUE 38) over scripted observations: the metric's file
loads, its reader gives frames sealed and opened over tokens returned, and it is left out
(None, no exception) on a program without the counter."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf import manifest as mf  # noqa: E402

NAME = "wire_frames_per_token.decode"
DECODE_CELLS = ["mistral-7b-span8.decode32", "olmoe-1b-7b-span4.decode32", "k-exaone-236b-span5.longgen32"]


def _frames(seal, opened):
    return {"type": "counter", "series": {"phase=seal": seal, "phase=open": opened}}


def _observation(before, after, tokens, **other):
    return {"counters": {"before": {"hivemind_wire_frames_total": _frames(*before), **other},
                         "after": {"hivemind_wire_frames_total": _frames(*after), **other}},
            "counts": {"tokens": tokens}}


def test_the_metric_is_declared_for_the_three_decode_cells_and_moves_their_rate():
    spec = mf.load_layer_metric(NAME)
    assert spec["name"] == NAME and spec["reader"] == "counter_ratio_present"
    entry = mf.by_name(mf.load_manifest()["per_layer"], NAME, "metric")
    assert entry["workloads"] == DECODE_CELLS and entry["moves"] == "decode_tokens_per_s"
    assert entry["source"] == "program_counter" and entry["layer"] == "averaging + wire" and entry["better"] == "lower"
    for cell in DECODE_CELLS:
        assert NAME in [metric["name"] for metric in mf.cell_metrics(mf.load_manifest(), cell, "per_layer")]


@pytest.mark.parametrize("before, after, tokens, want", [
    ((100.0, 200.0), (1100.0, 1200.0), 1000, 2.0),  # one frame each way a token: a unary call since PR 38
    ((0.0, 0.0), (2000.0, 4000.0), 1000, 6.0),  # four opened and two sealed a token: the protocol before
    ((50.0, 50.0), (1075.0, 1125.0), 1000, 2.1),  # the prefills' chunks and the DHT's own calls ride on top
])
def test_frames_sealed_and_opened_over_tokens_returned(before, after, tokens, want):
    assert mf.read_metric(mf.load_layer_metric(NAME), _observation(before, after, tokens)) == pytest.approx(want)


def test_left_out_on_a_program_without_the_counter_and_without_tokens():
    spec = mf.load_layer_metric(NAME)
    older = {"counters": {side: {"hivemind_wire_seconds_total": {"type": "counter", "series": {"phase=seal": 1.0}}}
                          for side in ("before", "after")}, "counts": {"tokens": 1000}}
    assert mf.read_metric(spec, older) is None and mf.read_metric(spec, {}) is None
    assert mf.read_metric(spec, _observation((0.0, 0.0), (10.0, 10.0), 0)) is None
