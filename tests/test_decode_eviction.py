"""When the decode-session table is walked (ISSUE 44): eviction runs when a session can be evicted
(an add over the cap, or the earliest possible expiry come), a step asks in O(1), and whether a block
has concurrent streams is kept as its two latest stamps. `DecodeSessionManager` alone, no network,
on a clock the test moves (`time` as `decode_session` sees it; asyncio keeps the real one)."""

import asyncio
import functools
import random
import time as real_time

import numpy as np
import optax
import pytest

from hivemind_tpu.telemetry import REGISTRY
from swarm_utils import ManagerSharingPrograms, OneProgramBackend

HID = 16
CHAIN = ("evi.0", "evi.1")


class _Clock:
    """`time` for `decode_session`: `monotonic` is ``now``, which the test moves and every read nudges on
    (two reads never agree, as on the real clock); all else is the real module's."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self) -> float:
        self.now += 1e-5
        return self.now

    def __getattr__(self, name):
        return getattr(real_time, name)


@pytest.fixture
def clock(monkeypatch):
    from hivemind_tpu.moe.server import decode_session

    clock = _Clock()
    monkeypatch.setattr(decode_session, "time", clock)
    return clock


def _manager(uids=CHAIN, **kwargs):
    return ManagerSharingPrograms({uid: _backend(uid, seed) for seed, uid in enumerate(uids)}, **{"max_len": 32, "max_sessions": 64, **kwargs})


@functools.cache  # no test trains a block: each is built once a process, and its programs compiled once (`ManagerSharingPrograms`)
def _backend(uid, seed):
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    return OneProgramBackend(uid, CausalTransformerExpert(hidden_dim=HID, num_heads=4), optimizer=optax.sgd(1e-3),
                             sample_input=np.zeros((1, 4, HID), np.float32), max_batch_size=8, rng_seed=seed)


def _prompt(length=3):
    return np.ones((1, length, HID), np.float32)


TOKEN = np.ones((1, 1, HID), np.float32)


def _counts():
    passes, evictions = REGISTRY.get("hivemind_moe_decode_evict_passes_total"), REGISTRY.get("hivemind_moe_decode_session_evictions_total")
    return {"skipped": passes.labels("skipped").value, "ran": passes.labels("ran").value,
            "ttl": evictions.labels("ttl").value, "cap": evictions.labels("cap").value}


def _moved(before):
    return {key: value - before[key] for key, value in _counts().items()}


def _step_together(manager, chain, names):
    async def run():
        return await asyncio.gather(*(manager.decode_span_async(chain, name, TOKEN, False) for name in names))

    return asyncio.run(run())


def _names(manager):
    return {name for _uid, name in manager._sessions}


def _walked(manager, uid, now) -> bool:
    """`_concurrent_sessions` as it was: a walk of the table."""
    from hivemind_tpu.moe.server.decode_session import MERGE_RECENCY_S

    return sum(1 for (at, _name), session in manager._sessions.items() if at == uid and now - session.last_used < MERGE_RECENCY_S) > 1


# ------------------------------------------------------------------ TTL: lazily, at the next call after the deadline


@pytest.mark.parametrize("path", ["add", "direct_step", "cohort_step"])
def test_an_idle_session_goes_at_the_next_add_or_step_after_its_deadline(clock, path):
    """Both step paths and the add owe the same eviction: before the deadline they skip, after it the
    one pass drops the idle session and nothing else."""
    manager = _manager(session_ttl=10.0)
    for name in ("idle", "a", "b"):
        manager._decode_direct(CHAIN, name, _prompt(), True)
    _step_together(manager, CHAIN, ["a", "b"])  # the cohort's programs exist before anything is counted
    probe = {"add": lambda: manager._decode_direct(CHAIN, "fresh", _prompt(), True),
             "direct_step": lambda: asyncio.run(manager.decode_span_async(CHAIN, "a", TOKEN, False)),
             "cohort_step": lambda: _step_together(manager, CHAIN, ["a", "b"])}[path]

    started = clock.now  # "idle" was last used before this, so its deadline is 10 s on
    clock.now = started + 5.0
    manager._decode_direct(CHAIN, "b", TOKEN, False)  # neither recent nor expired when the deadline comes
    clock.now = started + 9.7
    for name in ("a",) if path == "direct_step" else ("a", "b"):  # a single stream takes the direct path, two a cohort
        manager._decode_direct(CHAIN, name, TOKEN, False)
    clock.now = started + 9.8
    before = _counts()
    probe()
    moved = _moved(before)
    assert moved["ran"] == moved["ttl"] == 0 and moved["skipped"] >= 2 and "idle" in _names(manager)

    clock.now = started + 10.01  # the probe is the first call after the deadline
    before = _counts()
    probe()
    moved = _moved(before)
    assert moved["ttl"] == len(CHAIN) and moved["cap"] == 0 and moved["ran"] == 1, moved
    assert _names(manager) == {"a", "b"} | ({"fresh"} if path == "add" else set())
    with pytest.raises(KeyError, match="reset=True"):
        manager._decode_direct(CHAIN, "idle", TOKEN, False)
    with pytest.raises(KeyError, match="reset=True"):
        asyncio.run(manager._submit_step(CHAIN, "idle", TOKEN, False))
    # the pass left the earliest possible expiry behind: the next calls skip again
    before = _counts()
    probe()
    assert _moved(before)["ran"] == 0


def test_the_pass_is_due_by_the_ttl_as_it_stands(clock):
    """The deadline is kept as the oldest use, not as a time: a TTL changed on a live manager counts at once."""
    manager = _manager(uids=CHAIN[:1], session_ttl=600.0)
    manager.decode(CHAIN[0], "s", _prompt(), True)
    clock.now += 5.0
    manager.session_ttl = 1.0
    before = _counts()
    manager.decode(CHAIN[0], "t", _prompt(), True)
    assert _moved(before) == {"skipped": 0, "ran": 1, "ttl": 1, "cap": 0} and _names(manager) == {"t"}
    assert REGISTRY.get("hivemind_moe_decode_sessions").value() == 1


# ------------------------------------------------------------------ the cap: at the add, oldest first, never a pinned one


@pytest.mark.parametrize("pin", ["pending", "in_flight"])
def test_the_cap_evicts_oldest_first_and_never_a_pinned_session(clock, pin):
    uid = CHAIN[0]
    manager = _manager(uids=(uid,), max_sessions=3)

    def opened(name):
        clock.now += 1.0
        manager.decode(uid, name, _prompt(), True)
        return manager._sessions[(uid, name)]

    def pinned(session, step):
        entry = (None, [session], TOKEN)
        with manager._lock:
            if pin == "in_flight":
                manager._pin_locked([entry], step)
            elif step > 0:
                manager._pending.setdefault((uid,), []).append(entry)
            else:
                manager._pending.pop((uid,))

    before = _counts()
    first, _second, _third = [opened(name) for name in ("s0", "s1", "s2")]
    assert _moved(before) == {"skipped": 3, "ran": 0, "ttl": 0, "cap": 0}  # within the cap nothing is walked
    pinned(first, +1)
    opened("s3")  # over by one: the oldest is pinned, so the one after it goes
    assert _names(manager) == {"s0", "s2", "s3"} and _moved(before)["cap"] == 1 and _moved(before)["ran"] == 1
    opened("s4")
    assert _names(manager) == {"s0", "s3", "s4"}
    pinned(first, -1)
    opened("s5")  # its pin gone, the oldest goes first
    assert _names(manager) == {"s3", "s4", "s5"} and _moved(before)["cap"] == 3
    assert REGISTRY.get("hivemind_moe_decode_sessions").value() == 3
    assert REGISTRY.get("hivemind_moe_decode_session_occupancy").value() == pytest.approx(1.0)
    # a step on a table AT its cap walks nothing
    before = _counts()
    manager.decode(uid, "s5", TOKEN, False)
    assert _moved(before) == {"skipped": 1, "ran": 0, "ttl": 0, "cap": 0}


def test_a_table_of_pinned_sessions_keeps_them_and_the_one_just_added(clock):
    uid = CHAIN[0]
    manager = _manager(uids=(uid,), max_sessions=2)
    for name in ("s0", "s1"):
        manager.decode(uid, name, _prompt(), True)
    with manager._lock:
        manager._pin_locked([(None, [session], TOKEN) for session in manager._sessions.values()], +1)
    before = _counts()
    manager.decode(uid, "s2", _prompt(), True)  # nothing may go: neither a pinned session nor the new one
    assert _names(manager) == {"s0", "s1", "s2"} and _moved(before) == {"skipped": 0, "ran": 1, "ttl": 0, "cap": 0}
    with manager._lock:
        manager._pin_locked([(None, [manager._sessions[(uid, name)]], TOKEN) for name in ("s0", "s1")], -1)
    manager.decode(uid, "s2", TOKEN, False)  # over its cap, the table is walked at a step too, as it always was
    assert _names(manager) == {"s1", "s2"} and _moved(before)["cap"] == 1


def test_a_prompt_through_a_chain_holds_the_cap_at_every_block(clock):
    manager = _manager(max_sessions=2 * len(CHAIN))
    for name in ("s0", "s1", "s2", "s3"):
        clock.now += 1.0
        manager._decode_direct(CHAIN, name, _prompt(), True)
        assert len(manager._sessions) <= manager.max_sessions
    assert _names(manager) == {"s2", "s3"}
    np.testing.assert_array_equal(manager._decode_direct(CHAIN, "s3", TOKEN, False).shape, (1, 1, HID))


# ------------------------------------------------------------------ a step walks nothing


class _Watched(dict):
    """The session table, counting every way of walking it."""

    walks = 0

    def _walk(name):  # noqa: N805
        def walk(self, *args):
            type(self).walks += 1
            return getattr(dict, name)(self, *args)

        return walk

    __iter__, items, values, keys = _walk("__iter__"), _walk("items"), _walk("values"), _walk("keys")


@pytest.mark.parametrize("table", [8, 512])
def test_a_step_on_a_table_of_any_size_walks_nothing(clock, monkeypatch, table):
    manager = _manager(max_sessions=2 * len(CHAIN) * (table + 2))
    for name in ("a", "b"):
        manager._decode_direct(CHAIN, name, _prompt(), True)
    _step_together(manager, CHAIN, ["a", "b"])
    for row in range(table):  # entries of other clients' sessions: in the table, taking no step
        for uid in CHAIN:
            manager._enter(uid, f"other{row}", 1, True)
    assert len(manager._sessions) == len(CHAIN) * (table + 2)

    class Watched(_Watched):
        walks = 0

    manager._sessions = Watched(manager._sessions)
    sampled = []
    monkeypatch.setattr(manager, "_sample_gauges_locked", lambda: sampled.append(1))
    batched = REGISTRY.get("hivemind_moe_decode_steps_total").labels("batched")
    before, steps_before, rounds = _counts(), batched.value, 5
    for _ in range(rounds):
        clock.now += 0.05
        _step_together(manager, CHAIN, ["a", "b"])
    assert batched.value - steps_before == rounds * 2 * len(CHAIN)  # they went as cohorts, through `_submit_step`
    assert _moved(before) == {"skipped": rounds * 2, "ran": 0, "ttl": 0, "cap": 0}
    assert Watched.walks == 0 and not sampled
    # the single stream's direct path too: one skip a block, no walk
    clock.now += 1.0
    before = _counts()
    asyncio.run(manager.decode_span_async(CHAIN, "a", TOKEN, False))
    assert _moved(before) == {"skipped": len(CHAIN), "ran": 0, "ttl": 0, "cap": 0} and Watched.walks == 0 and not sampled


# ------------------------------------------------------------------ concurrent streams, without the walk


@pytest.mark.parametrize("recent, quiet, expected", [(0, 0, False), (0, 3, False), (1, 0, False), (1, 3, False),
                                                     (2, 0, True), (2, 3, True), (3, 1, True)])
def test_concurrent_streams_are_told_from_the_two_latest_stamps(clock, recent, quiet, expected):
    """0, 1 and 2+ recently used sessions on a block, beside sessions that went quiet for longer than
    `MERGE_RECENCY_S`: the answer is the walk's, and the single stream keeps its direct path."""
    uid = CHAIN[0]
    manager = _manager(uids=(uid,))
    for row in range(quiet):
        manager.decode(uid, f"quiet{row}", _prompt(), True)
    clock.now += 1.0
    for row in range(recent):
        manager.decode(uid, f"recent{row}", _prompt(), True)
        clock.now += 0.01
    with manager._lock:
        assert manager._concurrent_sessions(uid) == _walked(manager, uid, clock.now) == expected
        assert manager._concurrent_sessions("no.such.block") is False
    if recent:
        direct = REGISTRY.get("hivemind_moe_decode_steps_total").labels("direct")
        before = direct.value
        asyncio.run(manager.decode_span_async((uid,), "recent0", TOKEN, False))
        # alone it is stepped by the direct path; with a second stream it waits for a cohort (of one row here,
        # which `_decode_batch` steps directly as well: either way one direct step)
        assert direct.value - before == 1
    clock.now += 1.0  # and all of them quiet
    with manager._lock:
        assert manager._concurrent_sessions(uid) is _walked(manager, uid, clock.now) is False


@pytest.mark.parametrize("seed", range(8))
def test_the_two_latest_stamps_answer_as_the_walk_does(clock, seed):
    """A random schedule of opens, steps (direct, and together through `decode_span_async`: a cohort, stamped
    as it resolves, where the block has concurrent streams; and one block's batched program alone, as in the
    middle of a cohort), idles, evictions by cap and by TTL, drops and a cleared table, over two blocks: after every
    operation, at every block, `_concurrent_sessions` equals the walk it replaces."""
    rng = random.Random(seed)
    manager = _manager(max_sessions=9, session_ttl=4.0)
    manager.clear_sessions()  # the gauges are the process's: whatever manager set them last
    names = [f"s{i}" for i in range(8)]
    answers = []

    def known(uid, batchable=False):
        return [name for at, name in manager._sessions if at == uid
                and (not batchable or 0 < manager._sessions[(uid, name)].index < manager.max_len - 1)]

    for _ in range(120):
        uid, action = rng.choice(CHAIN), rng.choice(["open", "open", "step", "step", "batch", "batch", "idle", "idle", "drop", "clear"])
        if action == "open":
            manager.decode(uid, rng.choice(names), _prompt(rng.choice([1, 2, 3])), True)
        elif action == "step" and known(uid, batchable=True):
            name = rng.choice(known(uid, batchable=True))
            expired = clock.now - manager._sessions[(uid, name)].last_used > manager.session_ttl
            try:
                manager.decode(uid, name, TOKEN, False)
            except KeyError:
                assert expired
            else:
                assert not expired
        elif action == "batch" and len(known(uid, batchable=True)) >= 2:
            rows = rng.sample(known(uid, batchable=True), rng.choice([2, 3, 3]) if len(known(uid, batchable=True)) > 2 else 2)
            if rng.random() < 0.5:  # a block's program of a cohort under way: its scatter, and no stamp under the lock yet
                outs = manager._decode_batch(uid, [(None, manager._sessions[(uid, name)], TOKEN) for name in rows])
                assert not [out for out in outs if isinstance(out, Exception)]
            elif all(clock.now - manager._sessions[(uid, name)].last_used < manager.session_ttl - 1.0 for name in rows):
                _step_together(manager, (uid,), rows)  # a cohort if they are recent (stamped as it resolves), else direct steps
        elif action == "idle":
            clock.now += rng.choice([0.01, 0.05, 0.1, 0.2, 0.3, 1.0, 5.0])
        elif action == "drop" and known(uid):
            with manager._lock:  # as a failed step drops its sessions
                manager._drop_locked([(uid, name) for name in rng.sample(known(uid), min(rng.choice([1, 1, 2]), len(known(uid))))])
        elif action == "clear" and rng.random() < 0.2:
            manager.clear_sessions()
        clock.now += rng.choice([0.0, 0.001, 0.02])
        with manager._lock:
            now = clock.now
            for at in CHAIN:
                assert manager._concurrent_sessions(at) == _walked(manager, at, now), (action, at, manager._recent.get(at))
                answers.append(_walked(manager, at, now))
            assert len(manager._sessions) <= manager.max_sessions
            assert REGISTRY.get("hivemind_moe_decode_sessions").value() == len(manager._sessions)
    assert True in answers and False in answers  # the schedule met both


def test_stamps_and_evictions_from_many_threads_leave_the_two_latest_true():
    """More threads than cores open, step and re-open sessions on two blocks of a table at its cap, under a
    short switch interval, for a bounded time: every stamp and every drop is made under the manager's lock,
    so afterwards each block's two latest are the table's two latest, stamp for stamp."""
    import os
    import sys
    import threading

    manager = _manager(max_sessions=12, session_ttl=600.0)
    for uid in CHAIN:  # the programs exist before the threads start
        manager.decode(uid, "warm", _prompt(), True)
        manager.decode(uid, "warm", TOKEN, False)
    manager.clear_sessions()
    workers, errors, until = (os.cpu_count() or 4) + 4, [], real_time.monotonic() + 1.5

    def work(number: int) -> None:
        rng = random.Random(number)
        try:
            while real_time.monotonic() < until:
                uid, name = rng.choice(CHAIN), f"w{number}-{rng.randrange(3)}"
                try:
                    manager.decode(uid, name, TOKEN, False)
                except (KeyError, ValueError):  # evicted by another thread's add, not opened yet, or full
                    manager.decode(uid, name, _prompt(), True)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(number,)) for number in range(workers)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(thread.is_alive() for thread in threads)
    with manager._lock:
        assert 0 < len(manager._sessions) <= manager.max_sessions
        for uid in CHAIN:
            used = {id(s): s.last_used for (at, _name), s in manager._sessions.items() if at == uid}
            first_id, first, second_id, second = manager._recent[uid]
            assert [first, second][:len(used)] == sorted(used.values(), reverse=True)[:2]
            assert first_id != second_id and [used[ident] for ident in (first_id, second_id)[:len(used)]] == [first, second][:len(used)]
        assert REGISTRY.get("hivemind_moe_decode_sessions").value() == len(manager._sessions)
