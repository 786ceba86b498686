"""ISSUE 3 resilience layer: RetryPolicy/Deadline semantics, circuit-breaker
state machine + telemetry, chaos-engine determinism and spec grammar, DHT
churn under injected rpc drops, the ad-hoc-retry lint, and a chaos-soak smoke.

Everything here is seeded and CPU-only; the multi-minute soak lives behind the
``slow`` marker (the ``chaos`` marker alone stays tier-1-safe)."""

import asyncio
import threading
import time

import numpy as np
import pytest

from hivemind_tpu.dht.node import Blacklist, DHTNode
from hivemind_tpu.resilience import (
    CHAOS,
    BreakerBoard,
    BreakerState,
    ChaosAbort,
    ChaosDrop,
    ChaosEngine,
    Deadline,
    DeadlineExceeded,
    RetryPolicy,
    reset_all_boards,
)
from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.utils.timed_storage import get_dht_time


# ---------------------------------------------------------------------- policy


async def test_deadline_budget_propagates():
    budget = Deadline(0.2)
    assert not budget.expired and 0.0 < budget.remaining() <= 0.2
    assert budget.remaining_or(0.05) <= 0.05  # per-step cap wins while budget is fat
    # a nested wait consumes the SHARED budget, not an independent timeout
    with pytest.raises(DeadlineExceeded):
        await budget.wait_for(asyncio.sleep(5.0))
    assert budget.expired and budget.remaining() == 0.0
    with pytest.raises(DeadlineExceeded):
        await budget.wait_for(asyncio.sleep(0.0))  # already spent: fails instantly
    assert Deadline(None).remaining() is None  # unlimited budget
    assert await Deadline(None).wait_for(_value(7)) == 7


async def _value(x):
    return x


async def test_retry_policy_async_retries_then_succeeds():
    calls = []

    async def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    policy = RetryPolicy(max_attempts=5, base_delay=0.001, name="test_site")
    assert await policy.execute(lambda: flaky()) == "ok"
    assert len(calls) == 3


async def test_retry_policy_respects_attempt_cap_and_predicate():
    policy = RetryPolicy(max_attempts=3, base_delay=0.001)

    attempts = []

    async def always_fails():
        attempts.append(1)
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        await policy.execute(lambda: always_fails())
    assert len(attempts) == 3

    # non-retryable types pass straight through
    picky = RetryPolicy(max_attempts=5, base_delay=0.001, retry_on=(ConnectionError,))

    async def type_error():
        attempts.append(1)
        raise TypeError("bug, not weather")

    attempts.clear()
    with pytest.raises(TypeError):
        await picky.execute(lambda: type_error())
    assert len(attempts) == 1

    # a spent deadline stops retries even under the attempt cap
    async def fails():
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        await RetryPolicy(max_attempts=100, base_delay=0.001).execute(
            lambda: fails(), deadline=Deadline(0.0)
        )


def test_retry_policy_sync_and_jitter_bounds():
    import random

    rng = random.Random(0)
    policy = RetryPolicy(base_delay=1.0, backoff=2.0, max_delay=3.0, jitter="none")
    assert [policy.delay(i) for i in range(4)] == [1.0, 2.0, 3.0, 3.0]
    equal = RetryPolicy(base_delay=1.6, backoff=1.0, jitter="equal")
    for _ in range(50):
        assert 0.8 <= equal.delay(0, rng) <= 1.6
    full = RetryPolicy(base_delay=1.0, jitter="full")
    for _ in range(50):
        assert 0.0 <= full.delay(0, rng) <= 1.0

    calls = []

    def flaky_sync():
        calls.append(1)
        if len(calls) < 2:
            raise OSError("transient")
        return 42

    sleeps = []
    result = RetryPolicy(max_attempts=3, base_delay=0.5, jitter="none").execute_sync(
        flaky_sync, sleep=sleeps.append
    )
    assert result == 42 and sleeps == [0.5]


# ---------------------------------------------------------------------- breaker


def test_breaker_trip_threshold_and_recovery():
    board = BreakerBoard("t_trip", failure_threshold=3, recovery_time=0.1, backoff_rate=2.0)
    board.register_failure("peer")
    board.register_failure("peer")
    assert "peer" not in board and board.state("peer") is BreakerState.CLOSED
    board.register_failure("peer")  # third consecutive failure trips it
    assert "peer" in board and board.state("peer") is BreakerState.OPEN
    assert board.trip_count("peer") == 1
    # a success anywhere before threshold resets the consecutive count
    board.register_success("peer")
    assert board.state("peer") is BreakerState.CLOSED and board.all_closed()
    board.register_failure("other")
    board.register_success("other")
    board.register_failure("other")
    board.register_failure("other")
    assert "other" not in board  # 2 failures after the reset: under threshold


def test_breaker_half_open_probe_success_and_failure():
    board = BreakerBoard("t_probe", failure_threshold=1, recovery_time=0.05, backoff_rate=2.0)
    board.register_failure("peer")
    assert board.state("peer") is BreakerState.OPEN and not board.allow("peer")
    time.sleep(0.06)
    assert board.state("peer") is BreakerState.HALF_OPEN
    assert "peer" not in board  # pure read: half-open is not banned
    assert board.allow("peer") and not board.allow("peer")  # one probe slot
    # probe FAILURE re-opens with a doubled window
    board.register_failure("peer")
    assert board.state("peer") is BreakerState.OPEN and board.trip_count("peer") == 2
    time.sleep(0.06)
    assert board.state("peer") is BreakerState.OPEN  # 0.1 s window now
    time.sleep(0.06)
    assert board.state("peer") is BreakerState.HALF_OPEN
    # probe SUCCESS closes and fully resets
    assert board.allow("peer")
    board.register_success("peer")
    assert board.state("peer") is BreakerState.CLOSED and board.all_closed()


def test_breaker_telemetry_emission():
    trips = REGISTRY.get("hivemind_breaker_trips_total")
    probes = REGISTRY.get("hivemind_breaker_probe_outcomes_total")
    tripped = REGISTRY.get("hivemind_breaker_tripped")
    board = BreakerBoard("t_telemetry", failure_threshold=1, recovery_time=0.05)
    trips_before = trips.value(board="t_telemetry")
    board.register_failure("a")
    board.register_failure("b")
    assert trips.value(board="t_telemetry") == trips_before + 2
    assert tripped.value(board="t_telemetry") == 2
    time.sleep(0.06)
    board.register_success("a")  # half-open probe success
    board.register_failure("b")  # half-open probe failure
    assert probes.value(board="t_telemetry", outcome="success") >= 1
    assert probes.value(board="t_telemetry", outcome="failure") >= 1
    assert tripped.value(board="t_telemetry") == 1
    board.clear()
    assert tripped.value(board="t_telemetry") == 0


def test_dht_blacklist_is_a_breaker_board():
    """The DHT Blacklist API (register_failure/success, `in`, ban_counter, clear)
    rides the shared breaker with its historical backoff semantics."""
    blacklist = Blacklist(base_time=0.1, backoff_rate=2.0)
    peer = "peer_id_stub"
    blacklist.register_failure(peer)
    assert peer in blacklist and blacklist.ban_counter.get(peer, 0) == 1
    # failures while banned do not escalate (historical semantics)
    blacklist.register_failure(peer)
    assert blacklist.ban_counter.get(peer) == 1
    blacklist.register_success(peer)
    assert peer not in blacklist and blacklist.ban_counter.get(peer, 0) == 0
    # base_time=0 disables banning entirely
    disabled = Blacklist(base_time=0.0)
    disabled.register_failure(peer)
    assert peer not in disabled
    blacklist.clear()


# ---------------------------------------------------------------------- chaos


async def test_chaos_spec_grammar_and_determinism():
    engine = ChaosEngine()
    engine.configure("seed=11;dht.rpc_find:drop:prob=0.4;p2p.unary.send:delay:delay=0.001:after=2")
    assert len(engine.rules) == 2 and engine.enabled

    async def decisions(e):
        out = []
        for _ in range(30):
            try:
                await e.inject("dht.rpc_find")
                out.append(0)
            except ChaosDrop:
                out.append(1)
        return out

    first = await decisions(engine)
    engine.configure("seed=11;dht.rpc_find:drop:prob=0.4;p2p.unary.send:delay:delay=0.001:after=2")
    second = await decisions(engine)
    assert first == second and 0 < sum(first) < 30  # seeded: identical, non-trivial
    engine.configure("seed=12;dht.rpc_find:drop:prob=0.4")
    third = await decisions(engine)
    assert third != first  # different seed, different schedule


async def test_chaos_after_times_scope_and_corrupt():
    engine = ChaosEngine()
    engine.reseed(3)
    engine.add_rule("allreduce.load", "abort", after=2, times=1, scope="victim")
    # wrong scope: never fires
    for _ in range(5):
        await engine.inject("allreduce.load", scope="healthy_peer")
    # right scope: skips 2, fires once, then is exhausted
    await engine.inject("allreduce.load", scope="the_victim_peer")
    await engine.inject("allreduce.load", scope="the_victim_peer")
    with pytest.raises(ChaosAbort):
        await engine.inject("allreduce.load", scope="the_victim_peer")
    await engine.inject("allreduce.load", scope="the_victim_peer")  # times=1 spent
    assert engine.stats() == {"allreduce.load:abort": 1}

    engine.clear()
    engine.add_rule("p2p.unary.send", "corrupt_payload")
    original = b"\x00" * 512
    corrupted = await engine.inject("p2p.unary.send", payload=original)
    assert corrupted != original and len(corrupted) == len(original)
    # non-byte payloads pass through corruption untouched
    assert await engine.inject("p2p.unary.send", payload={"not": "bytes"}) == {"not": "bytes"}


async def test_chaos_link_scope_directional_matching():
    """ISSUE 12: ``scope=link:<src>-><dst>`` rules fault exactly one direction
    of one link (wildcard ends supported); non-link call sites never match a
    link rule, and plain peer-substring rules still match link scopes because
    the link string carries both endpoint ids."""
    engine = ChaosEngine()
    engine.reseed(5)
    engine.add_rule("p2p.unary.send", "abort", scope="link:alice->bob")
    # matching direction fires
    with pytest.raises(ChaosAbort):
        await engine.inject("p2p.unary.send", scope="link:alice->bob")
    # reverse direction and other links do not
    await engine.inject("p2p.unary.send", scope="link:bob->alice")
    await engine.inject("p2p.unary.send", scope="link:alice->carol")
    # a non-link call site (plain peer scope) never matches a link rule
    await engine.inject("p2p.unary.send", scope="alice")
    assert engine.stats() == {"p2p.unary.send:abort": 1}

    engine.clear()
    engine.add_rule("p2p.unary.send", "abort", scope="link:*->bob*")
    with pytest.raises(ChaosAbort):
        await engine.inject("p2p.unary.send", scope="link:anyone->bob2")
    await engine.inject("p2p.unary.send", scope="link:bob2->anyone")  # into bob only
    assert engine.stats() == {"p2p.unary.send:abort": 1}

    # legacy substring rule composes: it hits both directions of the peer's links
    engine.clear()
    engine.add_rule("p2p.unary.send", "abort", scope="bob")
    with pytest.raises(ChaosAbort):
        await engine.inject("p2p.unary.send", scope="link:alice->bob")
    with pytest.raises(ChaosAbort):
        await engine.inject("p2p.unary.send", scope="link:bob->alice")


async def test_chaos_link_scope_grammar_survives_colons():
    """The HIVEMIND_CHAOS grammar splits on ':' — a link scope's own colon must
    re-join its key=value field instead of becoming an unknown key."""
    engine = ChaosEngine()
    engine.configure("seed=3;p2p.unary.send:drop:times=2:scope=link:src*->dst*")
    (rule,) = engine.rules
    assert rule.scope == "link:src*->dst*" and rule.times == 2
    with pytest.raises(ChaosDrop):
        await engine.inject("p2p.unary.send", scope="link:src1->dst9")
    await engine.inject("p2p.unary.send", scope="link:dst9->src1")  # wrong direction


async def test_chaos_throttle_is_byte_proportional():
    """ISSUE 11: the `throttle` action models a bandwidth-limited link — sleep
    time scales with the payload's wire size; payload-free points are no-ops."""
    import time as _time

    engine = ChaosEngine()
    engine.add_rule("allreduce.load", "throttle", rate=1_000_000.0)  # 1 MB/s
    started = _time.perf_counter()
    payload = b"\x00" * 100_000  # 0.1 s at 1 MB/s
    returned = await engine.inject("allreduce.load", payload=payload)
    elapsed = _time.perf_counter() - started
    assert returned is payload  # throttle never alters bytes
    assert 0.08 < elapsed < 1.0, elapsed
    started = _time.perf_counter()
    await engine.inject("allreduce.load")  # no payload: no sleep
    assert _time.perf_counter() - started < 0.05
    # grammar: rate is parseable from HIVEMIND_CHAOS specs
    engine.configure("allreduce.reduce:throttle:rate=2e6")
    assert engine.rules[0].rate == 2e6


async def test_chaos_bad_specs_rejected():
    engine = ChaosEngine()
    with pytest.raises(ValueError):
        engine.configure("dht.rpc_find")  # no action
    with pytest.raises(ValueError):
        engine.configure("dht.rpc_find:drop:bogus_key=1")
    with pytest.raises(AssertionError):
        engine.add_rule("dht.rpc_find", "explode")


# ------------------------------------------------------------ DHT churn + chaos


async def _launch_dht_swarm(n_peers: int, **kwargs):
    nodes = [await DHTNode.create(**kwargs)]
    first_maddrs = await nodes[0].get_visible_maddrs()
    rest = await asyncio.gather(
        *(DHTNode.create(initial_peers=[str(m) for m in first_maddrs], **kwargs) for _ in range(n_peers - 1))
    )
    nodes.extend(rest)
    return nodes


@pytest.mark.chaos
async def test_dht_store_get_under_rpc_drops():
    """store/get across a 4-node swarm stays correct with 20% of rpc_store and
    rpc_find calls dropped (seeded), and the blacklists the drops tripped all
    recover once the faults stop."""
    nodes = await _launch_dht_swarm(4, blacklist_time=0.3)
    try:
        CHAOS.clear()
        CHAOS.reseed(7)
        CHAOS.add_rule("dht.rpc_store", "drop", prob=0.2)
        CHAOS.add_rule("dht.rpc_find", "drop", prob=0.2)
        now = get_dht_time()
        # the layer's own retry policy IS the mechanism that makes ops succeed
        # under 20% drops: one attempt may legitimately miss (the only replica
        # holder's rpc_find dropped AND blacklisted it), so retries must outlast
        # the short blacklist window before the holder becomes reachable again
        op_retry = RetryPolicy(
            max_attempts=8, base_delay=0.4, backoff=1.0, jitter="equal", retry_on=(AssertionError,)
        )

        for i in range(8):
            async def store_once(i=i):
                assert await nodes[i % 4].store(f"chaos_key_{i}", f"value_{i}", now + 60)

            await op_retry.execute(lambda i=i: store_once(i))
        for i in range(8):
            async def get_once(i=i):
                result = await nodes[(i + 1) % 4].get(f"chaos_key_{i}", latest=True)
                assert result is not None and result.value == f"value_{i}", f"get {i} failed"

            await op_retry.execute(lambda i=i: get_once(i))
        injected = CHAOS.stats()
        assert injected.get("dht.rpc_store:drop", 0) + injected.get("dht.rpc_find:drop", 0) > 0
        CHAOS.clear()
        # recovery: EVERY node keeps issuing traffic until its tripped breakers
        # are probed back to closed (a breaker only closes on a probe success,
        # and probes only happen when that node itself makes requests)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if all(node.blacklist.all_closed() for node in nodes):
                break
            for j, node in enumerate(nodes):
                if not node.blacklist.all_closed():
                    await node.store(f"recovery_probe_{j}", j, get_dht_time() + 30)
            await asyncio.sleep(0.4)
        for i, node in enumerate(nodes):
            assert node.blacklist.all_closed(), (
                f"node {i} blacklist still tripped: {node.blacklist.tripped_keys()}"
            )
    finally:
        CHAOS.clear()
        await asyncio.gather(*(node.shutdown() for node in nodes))


# ------------------------------------------------------------------------- soak


@pytest.mark.chaos
def test_chaos_soak_smoke():
    """Tier-1-safe soak (seeded, CPU-only): 2 trainers + an MoE pair under the full
    default schedule; steps advance, breakers recover. Under six workers 30-52 s (PR 48's
    whole runs): 5 s of start-up, the 18 s schedule, and up to 29 s of tear-down, in which
    a trainer whose partner has stopped waits out its last gradient round and the state
    round its shutdown lets land (an ``averaging_timeout`` of 20 s each)."""
    from hivemind_tpu.hivemind_cli.run_chaos_soak import run_soak

    report = run_soak(n_peers=2, duration=18.0, seed=0, chaos_fraction=0.55, include_moe=True)
    assert report["checks"]["steps_advanced"], report
    assert report["checks"]["steps_advanced_after_chaos"], report
    assert report["checks"]["breakers_recovered"], report
    assert report["checks"]["faults_injected"], report
    assert report["checks"]["no_thread_errors"], report


@pytest.mark.chaos
def test_churn_soak_smoke():
    """Tier-1-safe churn soak (ISSUE 7; under six workers 36-72 s, PR 48's whole runs:
    the 32 s schedule and 1-39 s of tear-down, by where in their last rounds the
    trainers are when they are told to stop): 3 trainers under the default
    fault schedule (including state.download corruption/drops), one crash-killed
    mid-chaos — DHT yanked, no shutdown — and restarted against its crash-safe
    checkpoint directory. The verdict requires every restarted peer back at the
    tracker's global epoch and ZERO unverified/corrupt state adoptions."""
    from hivemind_tpu.hivemind_cli.run_chaos_soak import run_soak

    report = run_soak(
        n_peers=3, duration=32.0, seed=0, chaos_fraction=0.5,
        include_moe=False, churn=True, churn_kills=1,
    )
    assert report["checks"]["peers_restarted"], report
    assert report["checks"]["state_recovered"], report
    assert report["digest_failures_adopted"] == 0, report
    assert report["checks"]["digest_failures_adopted_zero"], report
    assert report["checks"]["steps_advanced_after_chaos"], report
    assert report["checks"]["no_thread_errors"], report

    # the crash held while the verdict was earned; once run_soak has returned, nothing of
    # the killed peer (nor of any other) runs here: no optimizer's worker thread, and on
    # the shared loop no averager's declare loop and no tracker's reporter or fetcher
    from hivemind_tpu.utils.loop import get_loop_runner

    async def pending_task_names():
        return sorted(task.get_name() for task in asyncio.all_tasks() if not task.done())

    names = get_loop_runner().run_coroutine(pending_task_names(), return_future=True).result(10)
    names += [thread.name for thread in threading.enumerate()]
    assert not [n for n in names if n.startswith(("averager.", "progress_tracker.", "hm_dpu", "hm_ckpt"))], names


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_soak_full():
    """The ISSUE 3 acceptance soak: 4 peers, every named injection point, strict
    recovery checks. Heavy — excluded from tier-1 (also runnable as
    ``python -m hivemind_tpu.hivemind_cli.run_chaos_soak``)."""
    from hivemind_tpu.hivemind_cli.run_chaos_soak import run_soak

    report = run_soak(n_peers=4, duration=60.0, seed=0, chaos_fraction=0.6, include_moe=True)
    assert report["ok"], report


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_soak_full_churn():
    """The ISSUE 7 acceptance soak: chaos + seeded churn; the full verdict
    (including state_recovered and digest_failures_adopted: 0) must hold
    (also runnable as ``python -m hivemind_tpu.hivemind_cli.run_chaos_soak --churn``)."""
    from hivemind_tpu.hivemind_cli.run_chaos_soak import run_soak

    report = run_soak(
        n_peers=4, duration=60.0, seed=0, chaos_fraction=0.6, include_moe=True, churn=True,
    )
    assert report["ok"], report


@pytest.fixture(autouse=True)
def _reset_resilience_state():
    yield
    CHAOS.clear()
    reset_all_boards()
