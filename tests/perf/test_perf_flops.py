"""The yardstick's arithmetic against hand-worked values, and the readers on
hand-made observations."""

import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import flops, manifest as mf  # noqa: E402
from perf.peaks import peak_for  # noqa: E402

ALBERT = mf.load_json(mf.PERF / "configs" / "albert-base.json")
MISTRAL = mf.load_json(mf.PERF / "configs" / "mistral-7b-span8.json")


def test_albert_flops_per_token_by_hand():
    # per layer 4 x 768^2 + 2 x 768 x 3072 = 7,077,888 MACs; attention 2 x 512 x 768 = 786,432;
    # 12 layers -> 94,371,840; head (768 x 128 + 128 x 30000) x 0.25 = 984,576; x 6
    assert flops.albert_flops_per_token(ALBERT["model"], 512, 0.25) == 6.0 * (94_371_840 + 984_576)
    assert flops.albert_flops_per_token(ALBERT["model"], 512, 0.25) == pytest.approx(572.1e6, rel=1e-3)


def test_albert_flops_match_the_programs_own_count():
    import importlib.util

    from hivemind_tpu.models import AlbertConfig

    spec = importlib.util.spec_from_file_location("repo_bench", ROOT / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.flops_per_token(AlbertConfig.base(), 512, 0.25) == flops.albert_flops_per_token(ALBERT["model"], 512, 0.25)


def test_block_parameters_and_flops_by_hand():
    # 2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 14336 = 218,103,808, + two norm scales of 4096
    assert flops.block_params(MISTRAL["model"]) == 218_103_808 + 8192
    # one token, context 1000: 2 x 218,103,808 + 4 x 1000 x 32 x 128
    assert flops.block_flops_per_token(MISTRAL["model"], 1000, backward=False) == 2 * 218_103_808 + 16_384_000
    assert flops.block_flops_per_token(MISTRAL["model"], 1000, backward=True) == 3 * (2 * 218_103_808 + 16_384_000)


def test_decode_bytes_by_hand():
    got = flops.block_decode_bytes(MISTRAL["model"], sessions=32, context=512, max_len=2048, param_itemsize=4, cache_itemsize=2)
    assert got["weights"] == (218_103_808 + 8192) * 4
    assert got["cache_read"] == 2 * 32 * 512 * 8 * 128 * 2
    assert got["stacked_copies"] == 2 * 2 * 32 * 2048 * 8 * 128 * 2  # 8.4 MB a session, in and out, keys and values


@pytest.mark.parametrize("causal, backward, want", [
    (False, False, 4 * 384 * 512 * 512 * 64), (True, False, 2 * 384 * 512 * 512 * 64),
    (False, True, 8 * 384 * 512 * 512 * 64),
])
def test_attention_flops_by_hand(causal, backward, want):
    assert flops.attention_flops(32, 12, 512, 512, 64, causal=causal, backward=backward) == want


def test_attention_bytes_and_roofline_by_hand():
    one = 32 * 12 * 512 * 64 * 2
    assert flops.attention_bytes(32, 12, 12, 512, 512, 64, 2, backward=False) == 4 * one
    assert flops.attention_bytes(32, 12, 12, 512, 512, 64, 2, backward=True) == 8 * one
    peak = peak_for("TPU v5 lite")
    needed = flops.roofline_seconds(4 * 384 * 512 * 512 * 64, 4 * one, peak)
    assert needed["bound"] == "compute" and needed["seconds"] == pytest.approx(130.8e-6, rel=1e-3)
    assert flops.roofline_seconds(1e6, 819e9, peak) == {"seconds": 1.0, "bound": "memory", "compute_s": 1e6 / 197e12, "memory_s": 1.0}


@pytest.mark.parametrize("kind, ok", [("TPU v5 lite", True), ("TPU v5e", True), ("cpu", False), ("TPU v9", False)])
def test_peaks_table_refuses_unknown_devices(kind, ok):
    if ok:
        assert peak_for(kind)["bf16_flops"] == 197e12 and peak_for(kind)["hbm_bytes_per_s"] == 819e9
    else:
        with pytest.raises(LookupError):
            peak_for(kind)


OBS = {
    "window_s": 10.0, "chips": 2, "setup_s": 40.0,
    "counts": {"tokens": 1000, "steps": 5},
    "samples": {"gap": [float(i) for i in range(1, 101)]},
    "counters": {
        "before": {"steps_total": {"series": {"path=batched": 10.0, "path=direct": 10.0}}, "bytes_total": {"series": {"_": 100.0}}},
        "after": {"steps_total": {"series": {"path=batched": 40.0, "path=direct": 20.0}}, "bytes_total": {"series": {"_": 2100.0}}},
    },
    "serving": [{"kind": "decode", "total_s": 0.010, "occupancy": 0.25}, {"kind": "decode", "total_s": 0.030, "occupancy": 0.75},
                {"kind": "forward", "total_s": 1.0, "queue_wait_s": 0.5}],
    "rounds": [{"group_size": 2, "total_s": 2.0}, {"group_size": 2, "total_s": 2.0}, {"group_size": 1, "total_s": 9.0}],
    "config": {"serving": {"max_batch_size": 16}},
    "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 12_500_000_000},
    "trace": {"devices": 1, "window_s": 4.0, "busy_s": 1.0,
              "ops": {"_flash_forward": {"seconds": 0.2, "count": 24}, "_flash_backward": {"seconds": 0.4, "count": 48},
                      "all-reduce": {"seconds": 0.1, "count": 4}, "fusion": {"seconds": 0.3, "count": 100}}},
}


@pytest.mark.parametrize("spec, want", [
    ({"reader": "rate", "args": {"count": "tokens"}}, 100.0),
    ({"reader": "rate", "args": {"count": "tokens", "per_chip": True}}, 50.0),
    ({"reader": "rate", "args": {"count": "absent"}}, None),
    ({"reader": "observed", "args": {"key": "setup_s"}}, 40.0),
    ({"reader": "percentile", "args": {"samples": "gap", "q": 95}}, 95.0),
    ({"reader": "percentile", "args": {"samples": "gap", "q": 50}}, 50.0),
    ({"reader": "percentile", "args": {"samples": "absent", "q": 50}}, None),
    ({"reader": "counter_ratio", "args": {"numerator": [{"metric": "steps_total", "series": "path=batched"}],
                                          "denominator": [{"metric": "steps_total"}], "scale": 100.0}}, 75.0),
    ({"reader": "counter_ratio", "args": {"numerator": [{"metric": "bytes_total"}], "per_count": "tokens", "scale": 0.001}}, 0.002),
    ({"reader": "ledger_stat", "args": {"ledger": "serving", "field": "total_s", "where": {"kind": "decode"}, "scale": 1000.0}}, 20.0),
    ({"reader": "ledger_stat", "args": {"ledger": "serving", "field": "queue_wait_s", "where": {"kind": "decode"}}}, None),
    ({"reader": "ledger_stat", "args": {"ledger": "serving", "field": "occupancy", "stat": "mean",
                                        "times_config": ["serving", "max_batch_size"]}}, 8.0),
    ({"reader": "median_gap", "args": {"samples": "gap", "ledger": "serving", "field": "total_s", "where": {"kind": "decode"}}}, 30.5),
    ({"reader": "allreduce_rate", "args": {"metric": "bytes_total"}}, 2000.0 / 4.0 / 1e6),
    ({"reader": "idle_share", "args": {}}, 75.0),
    ({"reader": "hbm_peak", "args": {}}, 12.5),
    ({"reader": "trace_ms_per_step", "args": {"pattern": "^_flash_", "step_pattern": "^_flash_forward$", "events_per_step": 12}}, 300.0),
    ({"reader": "trace_ms_per_step", "args": {"pattern": "^all-reduce", "step_pattern": "^_flash_forward$", "events_per_step": 12}}, 50.0),
    ({"reader": "trace_ms_per_step", "args": {"pattern": "^no_such_op$"}}, None),
])
def test_readers_on_hand_made_observations(spec, want):
    got = mf.read_metric(spec, OBS)
    assert got is None if want is None else got == pytest.approx(want)


def test_mfu_and_roofline_readers_by_hand():
    obs = {**OBS, "chips": 1, "config": ALBERT, "counts": {"tokens": 344_000 * 10}, "notes": []}
    per_token = flops.albert_flops_per_token(ALBERT["model"], 512, 0.25)
    assert mf.read_metric({"reader": "step_mfu", "args": {}}, obs) == pytest.approx(100 * per_token * 344_000 / 197e12)
    share = mf.read_metric({"reader": "flash_roofline", "args": {"pattern": "^_flash_", "step_pattern": "^_flash_forward$",
                                                                  "events_per_step_from_config": ["model", "num_hidden_layers"]}}, obs)
    # 2 traced steps x 12 layers x (130.8 us forward + 261.6 us backward at the roofline) over 0.6 s of kernel time
    assert share == pytest.approx(100 * 2 * 12 * (130.8e-6 + 261.6e-6) / 0.6, rel=1e-3)
    assert any("compute-bound" in note for note in obs["notes"])
    assert not math.isnan(share)
