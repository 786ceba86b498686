"""Where a process's allocator gets blocks of 128 KiB and up (ISSUE 49):
``keep_large_blocks_on_heap`` sets glibc's two thresholds once, from ``P2P.create()``.
The setting is process-wide and cannot be undone, so every statement about a process
without it, or with another environment, is made in a child."""

import json
import platform
import subprocess
import sys

import pytest

from swarm_utils import cpu_child_env, stop_process, wait_for_children

pytestmark = pytest.mark.skipif(
    platform.libc_ver()[0] != "glibc", reason="the two thresholds are glibc's"
)

# what every child starts with: mallinfo2().hblks counts the blocks mapped for themselves.
# An array of exactly 32 MiB makes a block just over glibc's 32 MiB cap on its moving
# threshold, so without the setting EVERY such array is mapped, in every process.
_PRELUDE = r"""
import ctypes, json, threading
import numpy as np

class _Info(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in
                "arena ordblks smblks hblks hblkhd usmblks fsmblks uordblks fordblks keepcost".split()]

_libc = ctypes.CDLL(None)
_libc.mallinfo2.restype = _Info

def mapped_blocks():
    return _libc.mallinfo2().hblks

def blocks_seen_holding_arrays(threads, nbytes=32 << 20, count=10):
    # the most blocks mapped for themselves that any worker saw while it held an array
    seen = []
    def work():
        for _ in range(count):
            array = np.empty(nbytes, np.uint8)
            array[::4096] = 1
            seen.append(mapped_blocks())
            del array
    if threads == 0:
        work()
    else:
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for worker in workers:
            worker.start()
        for worker in workers:
            worker.join(60)
        assert not any(worker.is_alive() for worker in workers)
    assert len(seen) == count * max(threads, 1)
    return max(seen)

def gauges():
    from hivemind_tpu.telemetry import REGISTRY
    return {name: entry["series"]["_"] for name, entry in REGISTRY.snapshot().items()
            if name.startswith("hivemind_host_")}
"""


def run_child(tmp_path, body: str, timeout: float = 120.0, **environment) -> dict:
    """Run prelude + body as one child and return the JSON object of its last line."""
    script = tmp_path / "child.py"
    script.write_text(_PRELUDE + body)
    child = subprocess.Popen(
        [sys.executable, str(script)], stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(cpu_child_env(), **environment),
    )
    try:
        cut_short = wait_for_children([child], timeout)
    finally:
        stop_process(child)
    output = child.communicate(timeout=10)[0]
    assert not cut_short and child.returncode == 0, f"{cut_short}\n{output[-3000:]}"
    return json.loads(output.strip().splitlines()[-1])


@pytest.mark.parametrize("threads", [0, 3], ids=["main", "threads"])
def test_arrays_of_32_mib_stay_on_the_heap_after_the_call_and_not_before(tmp_path, threads):
    seen = run_child(tmp_path, f"""
from hivemind_tpu.utils.limits import keep_large_blocks_on_heap
before = mapped_blocks()
without = blocks_seen_holding_arrays({threads})
answer = keep_large_blocks_on_heap()
after = mapped_blocks()
with_it = blocks_seen_holding_arrays({threads})
print(json.dumps(dict(before=before, without=without, answer=answer, after=after, with_it=with_it,
                      at_the_end=mapped_blocks())))
""")
    assert seen["answer"] is True
    assert seen["without"] > seen["before"], seen  # an array in flight was a mapping of its own
    assert seen["with_it"] == seen["after"] == seen["at_the_end"], seen  # and is not any more


def test_the_call_is_idempotent_and_its_second_time_touches_nothing(tmp_path):
    seen = run_child(tmp_path, """
from hivemind_tpu.utils import limits
first = limits.keep_large_blocks_on_heap()
def gone(*args, **kwargs):
    raise AssertionError("the second call looked the library up again")
ctypes.CDLL = gone
second = limits.keep_large_blocks_on_heap()
held = blocks_seen_holding_arrays(0, count=2) == mapped_blocks()
print(json.dumps(dict(first=first, second=second, held=held, gauges=gauges())))
""")
    assert seen["first"] is True and seen["second"] is True and seen["held"]
    assert seen["gauges"]["hivemind_host_large_blocks_on_heap"] == 1.0


@pytest.mark.parametrize(
    "lookup",
    ["def lookup(*a, **k): raise OSError('no C library')", "def lookup(*a, **k): return object()"],
    ids=["no_library", "no_mallopt"],
)
def test_without_mallopt_the_call_says_no_and_a_peer_still_comes_up(tmp_path, lookup):
    seen = run_child(tmp_path, f"""
import asyncio
from hivemind_tpu.p2p import P2P
from hivemind_tpu.utils import limits
{lookup}
ctypes.CDLL = lookup
answers = [limits.keep_large_blocks_on_heap(), limits.keep_large_blocks_on_heap()]
async def peer():
    p2p = await P2P.create()
    listening = len(p2p.get_visible_maddrs()) > 0
    await p2p.shutdown()
    return listening
print(json.dumps(dict(answers=answers, listening=asyncio.run(peer()), gauges=gauges())))
""")
    assert seen["answers"] == [False, False] and seen["listening"]
    # no mallinfo2 either: the bytes' gauge is absent, not zero
    assert seen["gauges"] == {"hivemind_host_large_blocks_on_heap": 0.0}


@pytest.mark.parametrize(
    "environment",
    [
        {"MALLOC_MMAP_THRESHOLD_": "1048576"},
        {"MALLOC_TRIM_THRESHOLD_": "1048576"},
        {"GLIBC_TUNABLES": "glibc.malloc.mmap_threshold=1048576"},
    ],
    ids=["MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_", "GLIBC_TUNABLES"],
)
def test_a_threshold_the_environment_names_is_left_to_glibc(tmp_path, environment):
    seen = run_child(tmp_path, """
from hivemind_tpu.utils.limits import keep_large_blocks_on_heap
answer = keep_large_blocks_on_heap()
resting = mapped_blocks()
# the user's word still holds: 2 MiB is over every threshold named here (and over the
# 128 KiB at which naming the trim threshold alone pins the mapping threshold)
print(json.dumps(dict(answer=answer, resting=resting, gauges=gauges(),
                      holding_2_mib=blocks_seen_holding_arrays(0, nbytes=2 << 20, count=3),
                      holding_32_mib=blocks_seen_holding_arrays(0, count=3))))
""", **environment)
    assert seen["answer"] is False
    assert seen["gauges"]["hivemind_host_large_blocks_on_heap"] == 0.0
    # an array in flight is still a mapping of its own (how many blocks glibc maps beside
    # it is glibc's business, not the policy's)
    assert seen["holding_2_mib"] > seen["resting"] and seen["holding_32_mib"] > seen["resting"], seen


@pytest.mark.parametrize(
    "come_up",
    [
        "async def up():\n    p2p = await P2P.create()\n    await p2p.shutdown()\nasyncio.run(up())",
        "dht = DHT(start=True)\ndht.shutdown()",
    ],
    ids=["P2P.create", "DHT"],
)
def test_a_peer_that_came_up_says_so_on_its_gauges(tmp_path, come_up):
    seen = run_child(tmp_path, f"""
import asyncio
from hivemind_tpu.dht import DHT
from hivemind_tpu.p2p import P2P
{come_up}
resting = gauges()
array = np.empty(32 << 20, np.uint8)
print(json.dumps(dict(resting=resting, holding=gauges())))
""")
    assert seen["resting"]["hivemind_host_large_blocks_on_heap"] == 1.0
    # what start-up mapped, and a request's array does not add to it
    assert seen["holding"]["hivemind_host_mmapped_bytes"] == seen["resting"]["hivemind_host_mmapped_bytes"]
