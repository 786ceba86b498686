"""Traffic generators: the same seed gives the same schedule, another seed another
one, and every seed the same amount of work (the same sizes in another order)."""

import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import manifest as mf  # noqa: E402

WORKLOAD_FILES = sorted(path.stem for path in (mf.PERF / "workloads").glob("*.json"))
BIG_SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


def _schedule(name, seed, rehearsal=False):
    workload = mf.load_workload(name)
    traffic = {**workload["traffic"], **(workload.get("rehearsal_traffic", {}) if rehearsal else {})}
    return mf.plugin("traffic", traffic["generator"]).schedule(traffic, seed), traffic


@pytest.mark.parametrize("name", WORKLOAD_FILES)
@pytest.mark.parametrize("rehearsal", [False, True])
def test_same_seed_same_schedule_other_seed_other(name, rehearsal):
    first, _ = _schedule(name, BIG_SEED, rehearsal)
    again, _ = _schedule(name, BIG_SEED, rehearsal)
    other, _ = _schedule(name, BIG_SEED + 1, rehearsal)
    assert first == again
    assert first != other


def _sizes(schedule):
    """The multiset of the work's sizes, whatever order and seeds it is dealt in."""
    if "processes" not in schedule:
        return Counter(peer["kind"] for peer in schedule["peers"])
    return Counter(tuple(item[:2]) for process in schedule["processes"] for slot in process
                   for item in (slot if isinstance(slot[0], list) else [slot]))


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_every_seed_gives_the_same_sizes(name):
    assert _sizes(_schedule(name, 1)[0]) == _sizes(_schedule(name, BIG_SEED)[0])


def test_decode_sizes_follow_the_weights_and_fit_the_cache():
    schedule, traffic = _schedule("mistral-7b-span8.decode32", 7)
    config = mf.load_json(mf.PERF / "configs" / "mistral-7b-span8.json")
    pairs = [item for process in schedule["processes"] for slot in process for item in slot]
    assert len(schedule["processes"]) == traffic["processes"]
    assert all(len(process) == traffic["slots_per_process"] for process in schedule["processes"])
    total = len(pairs)
    for length, weight in zip(traffic["prompt_lengths"], traffic["prompt_weights"]):
        assert sum(p == length for p, _a, _s in pairs) == round(weight * total)
    answers = [a for _p, a, _s in pairs]
    assert min(answers) == traffic["answer_min"] and max(answers) == traffic["answer_max"]
    assert max(p + a for p, a, _s in pairs) <= config["serving"]["decode_max_len"]
    # the LRU cap counts sessions across block uids: the live ones must fit under it
    live = traffic["processes"] * traffic["slots_per_process"] * config["model"]["num_hidden_layers"]
    assert live <= config["serving"]["decode_max_sessions"]
