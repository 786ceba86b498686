"""Decode batching per span chain (ISSUE 28): the steps of different sessions that
wait on one chain of blocks form a cohort, which walks the chain in one executor
call, one batched device call a block. `DecodeSessionManager` alone, no network."""

import asyncio
import functools
import threading

import numpy as np
import optax
import pytest

from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.telemetry.tracing import add_span_listener, remove_span_listener
from swarm_utils import ManagerSharingPrograms, OneProgramBackend, decode_compiles as _compiles

HID = 16
CHAIN = ("coh.0", "coh.1", "coh.2")


def _manager(uids=CHAIN, block=None, hidden=HID, **kwargs):
    """A manager over one backend a uid; ``block()`` makes a uid's module (default: the dense causal block)."""
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    block = block or (lambda: CausalTransformerExpert(hidden_dim=hidden, num_heads=4))
    backends = {uid: _backend(uid, block(), hidden, seed) for seed, uid in enumerate(uids)}
    return ManagerSharingPrograms(backends, **{"max_len": 32, "max_sessions": 256, **kwargs})


@functools.cache  # no test trains a block: each is built once a process, and its programs compiled once (`ManagerSharingPrograms`)
def _backend(uid, module, hidden, seed):
    return OneProgramBackend(uid, module, optimizer=optax.sgd(1e-3), sample_input=np.zeros((1, 4, hidden), np.float32),
                             max_batch_size=8, rng_seed=seed)


def _prefill(manager, chain, names, rng, length=3, hidden=HID):
    """Each name twice, from one prompt: the session the cohort steps, and under
    "twin-<name>" the one the direct path steps for comparison."""
    for name in names:
        prompt = rng.randn(1, length, hidden).astype(np.float32)
        for session_id in (name, "twin-" + name):
            manager._decode_direct(chain, session_id, prompt, True)


def _counters():
    steps, calls = REGISTRY.get("hivemind_moe_decode_steps_total"), REGISTRY.get("hivemind_moe_decode_calls_total")
    return {"steps": steps.labels("batched").value, "calls": calls.labels("batched").value,
            "direct_calls": calls.labels("direct").value, "cohorts": REGISTRY.get("hivemind_moe_decode_cohorts_total").value()}


def _moved(before):
    return {key: value - before[key] for key, value in _counters().items()}


class _Spans:
    """The spans of this manager's blocks that finished inside the `with`."""

    def __init__(self, chain):
        self.chain, self.spans = chain, []

    def __enter__(self):
        add_span_listener(self.spans.append)
        return self

    def __exit__(self, *exc):
        remove_span_listener(self.spans.append)

    def cohorts(self):
        batches = [s for s in self.spans if s.name == "decode.batch" and s.attributes["uid"] in self.chain]
        parents = {s.parent_id for s in batches}
        return [s for s in self.spans if s.name == "decode.cohort" and s.span_id in parents], batches


def _step_together(manager, chain, tokens, timeout=60.0):
    """One token a session, all submitted in one loop tick; returns {name: output or exception}."""
    async def scenario():
        outs = await asyncio.wait_for(asyncio.gather(
            *(manager.decode_span_async(chain, name, token, False) for name, token in tokens.items()),
            return_exceptions=True), timeout)
        return dict(zip(tokens, outs))

    return asyncio.run(scenario())


def test_decode_reads_no_environment_and_takes_no_option_that_nobody_sets():
    """Every request takes the path the code chooses from what it observes (rows,
    reset, recency): nothing outside the process and no caller can choose another."""
    import inspect

    from hivemind_tpu.moe.server import decode_session

    assert list(inspect.signature(decode_session.DecodeSessionManager.__init__).parameters) == [
        "self", "backends", "max_len", "session_ttl", "max_sessions"]
    source = inspect.getsource(decode_session)
    assert "environ" not in source and "getenv" not in source
    assert (decode_session.FLUSH_WINDOW_S, decode_session.MERGE_RECENCY_S) == (0.002, 0.25)


@pytest.mark.parametrize("rows", [2, 6])
def test_a_cohort_is_one_batched_call_a_block(rows):
    manager, rng = _manager(), np.random.RandomState(rows)
    names = [f"s{i}" for i in range(rows)]
    _prefill(manager, CHAIN, names, rng)
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in names}
    before = _counters()
    with _Spans(CHAIN) as seen:
        outs = _step_together(manager, CHAIN, tokens)
    assert _moved(before) == {"steps": 3 * rows, "calls": 3, "direct_calls": 0, "cohorts": 1}
    cohorts, batches = seen.cohorts()
    assert [(c.attributes["rows"], c.attributes["chain_len"]) for c in cohorts] == [(rows, 3)]
    assert [(b.attributes["uid"], b.attributes["rows"]) for b in batches] == [(uid, rows) for uid in CHAIN]
    for name, token in tokens.items():
        np.testing.assert_allclose(outs[name], manager._decode_direct(CHAIN, "twin-" + name, token, False), rtol=1e-5, atol=1e-5)
    assert manager._in_flight == {} and not manager._pending.get(CHAIN)


@pytest.mark.parametrize("waiting, cohorts", [(1, [1]), (3, [3]), (5, [4, 1]), (8, [8]), (11, [8, 3]), (12, [12]),
                                              (17, [16, 1]), (23, [16, 7]), (24, [24]), (32, [32]), (40, [32, 8])])
def test_a_cohort_fills_its_bucket_or_takes_the_one_below(waiting, cohorts):
    """Rows past a full bucket wait for the next cohort unless they fill more than
    half of what the next bucket adds: a program costs by its bucket."""
    from hivemind_tpu.moe.server.decode_session import _cohort_rows

    taken = []
    while waiting:
        taken.append(_cohort_rows(waiting))
        waiting -= taken[-1]
    assert taken == cohorts


@pytest.mark.parametrize("waiting, active, taken", [
    (31, 32, 16), (32, 32, 16), (24, 32, 16), (26, 32, 16), (16, 32, 16), (15, 32, 15), (8, 32, 8), (1, 32, 1),  # 32 sessions: 16 + 16
    (24, 24, 16), (17, 17, 16), (16, 16, 16), (12, 16, 12), (12, 12, 12), (3, 4, 3),  # 16 or fewer under way: as it was
    (48, 64, 32), (60, 64, 32), (33, 33, 32), (31, 0, 31), (24, 0, 24)])
def test_a_cohort_leaves_half_of_many_rows_to_the_next(waiting, active, taken):
    """With more than 16 rows under way on a chain (waiting, or launched and not yet
    answered) a cohort takes at most the bucket that holds half of them: 31 waiting beside 1
    in flight become 16 + 15, where 31 + 1 would go on alternating (ISSUE 43)."""
    from hivemind_tpu.moe.server.decode_session import _cohort_rows

    assert _cohort_rows(waiting, active) == taken


def test_twenty_four_sessions_travel_as_sixteen_and_eight():
    """24 steps that wait together are two cohorts, 16 rows then 8, and no program of 32."""
    manager, rng = _manager(CHAIN[:1]), np.random.RandomState(24)
    chain, names = CHAIN[:1], [f"s{i}" for i in range(24)]
    _prefill(manager, chain, names, rng)
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in names}
    before = _counters()
    with _Spans(chain) as seen:
        outs = _step_together(manager, chain, tokens)
    assert _moved(before) == {"steps": 24, "calls": 2, "direct_calls": 0, "cohorts": 2}
    assert [(b.attributes["rows"], b.attributes["bucket"]) for b in seen.cohorts()[1]] == [(16, 16), (8, 8)]
    for name, token in tokens.items():
        np.testing.assert_allclose(outs[name], manager._decode_direct(chain, "twin-" + name, token, False), rtol=1e-5, atol=1e-5)
    assert manager._in_flight == {} and not manager._pending.get(chain)


@pytest.mark.parametrize("late, waits", [(4, True), (12, True), (16, False)])
def test_a_short_cohort_waits_for_the_one_on_the_device(late, waits, monkeypatch):
    """With more than 16 rows under way, a cohort short of the bucket that holds half of them is
    not launched while another cohort is still unanswered: that one's rows cannot come back before
    it is, the others' are on their way, and a program costs by its bucket (ISSUE 50: a walk whose
    dispatches got three times shorter outran its clients and ran buckets of 16 for 8 to 12 rows).
    A full half bucket that waits is launched beside the cohort on the device, as ever."""
    from hivemind_tpu.moe.server import decode_session

    chain = CHAIN[:1]
    manager, rng = _manager(chain), np.random.RandomState(late)
    names = [f"s{i}" for i in range(16 + late)]
    _prefill(manager, chain, names, rng)
    for group in (names[:16], names[16:]):  # both buckets' programs compiled before anything is timed
        warm = {name: rng.randn(1, 1, HID).astype(np.float32) for name in group}
        assert not any(isinstance(out, Exception) for out in _step_together(manager, chain, warm).values())
        for name, token in warm.items():  # the twins keep step
            manager._decode_direct(chain, "twin-" + name, token, False)
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in names}
    launching, launch, answering, answer = threading.Event(), threading.Event(), threading.Event(), threading.Event()
    real_batch, real_host = manager._decode_batch, decode_session._Output.host

    def first_launch_held(uid, entries, **how):
        if len(entries) == 16 and not launch.is_set():
            launching.set()
            launch.wait(10)
        return real_batch(uid, entries, **how)

    def first_answer_held(self):
        if self.rows == 16 and not answer.is_set():
            answering.set()
            answer.wait(10)
        return real_host(self)

    manager._decode_batch = first_launch_held
    monkeypatch.setattr(decode_session._Output, "host", first_answer_held)
    before = _counters()

    async def scenario():
        loop = asyncio.get_running_loop()
        steps = [asyncio.create_task(manager.decode_span_async(chain, name, tokens[name], False)) for name in names[:16]]
        await loop.run_in_executor(None, launching.wait, 10)
        steps += [asyncio.create_task(manager.decode_span_async(chain, name, tokens[name], False)) for name in names[16:]]
        await asyncio.sleep(0.01)  # they find a live drainer and only enqueue
        launch.set()
        await loop.run_in_executor(None, answering.wait, 10)
        await asyncio.sleep(0.3)  # the first cohort is launched and unanswered: what has the drainer done with the rest?
        launched_meanwhile = _moved(before)["cohorts"]
        answer.set()
        return launched_meanwhile, await asyncio.wait_for(asyncio.gather(*steps), 60.0)

    launched_meanwhile, outs = asyncio.run(scenario())
    assert launched_meanwhile == (1 if waits else 2)
    assert _moved(before) == {"steps": 16 + late, "calls": 2, "direct_calls": 0, "cohorts": 2}
    for name, out in zip(names, outs):
        np.testing.assert_allclose(out, manager._decode_direct(chain, "twin-" + name, tokens[name], False), rtol=1e-5, atol=1e-5)
    assert manager._in_flight == {} and not manager._pending.get(chain)


def test_rows_past_a_full_bucket_are_the_next_cohort():
    manager, rng = _manager(), np.random.RandomState(7)
    names = [f"s{i}" for i in range(5)]
    _prefill(manager, CHAIN, names, rng)
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in names}
    before = _counters()
    with _Spans(CHAIN) as seen:
        outs = _step_together(manager, CHAIN, tokens)
    # four rows in the bucket of four, then the fifth alone: a lone row takes the per-session program
    assert _moved(before) == {"steps": 3 * 4, "calls": 3, "direct_calls": 3, "cohorts": 2}
    assert [c.attributes["rows"] for c in seen.cohorts()[0]] == [4, 1]
    for name, token in tokens.items():
        np.testing.assert_allclose(outs[name], manager._decode_direct(CHAIN, "twin-" + name, token, False), rtol=1e-5, atol=1e-5)
    assert manager._in_flight == {} and not manager._pending.get(CHAIN)


@pytest.mark.parametrize("rows, batched_rows", [(2, 2), (3, 3), (5, 4)])
def test_a_cohort_over_two_sparse_expert_blocks(rows, batched_rows):
    """The OLMoE cell's path: a chain of two `olmoe_block`s. One batched call a block,
    every row equal to the per-session path, and the routed pairs counted by live
    rows: 3 rows ride a bucket of 4 (its padding row routes and is not counted),
    the 5th of 5 rows is the next cohort and takes the per-session program."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, top_k, chain = 32, 2, ("moe.0", "moe.1")
    manager = _manager(chain, hidden=hidden, block=lambda: name_to_block["olmoe_block"](
        hidden, num_heads=4, num_experts=8, experts_per_token=top_k, expert_inner=16))
    rng = np.random.RandomState(rows)
    names = [f"s{i}" for i in range(rows)]
    _prefill(manager, chain, names, rng, hidden=hidden)
    tokens = {name: rng.randn(1, 1, hidden).astype(np.float32) for name in names}
    pairs = REGISTRY.get("hivemind_moe_routed_pairs_total")
    before, pairs_before = _counters(), {path: pairs.labels(path).value for path in ("batched", "direct")}
    with _Spans(chain) as seen:
        outs = _step_together(manager, chain, tokens)
    lone = rows - batched_rows
    assert _moved(before) == {"steps": 2 * batched_rows, "calls": 2, "direct_calls": 2 * lone, "cohorts": 1 + lone}
    assert [(b.attributes["uid"], b.attributes["rows"]) for b in seen.cohorts()[1]] == (
        [(uid, batched_rows) for uid in chain] + [(uid, 1) for uid in chain] * lone)
    assert {path: pairs.labels(path).value - was for path, was in pairs_before.items()} == {
        "batched": 2 * batched_rows * top_k, "direct": 2 * lone * top_k}
    for name, token in tokens.items():
        np.testing.assert_allclose(outs[name], manager._decode_direct(chain, "twin-" + name, token, False), rtol=2e-2, atol=2e-2)
    assert manager._in_flight == {} and not manager._pending.get(chain)


def test_the_chain_of_one_is_decode_async():
    manager, rng = _manager(), np.random.RandomState(1)
    uid = CHAIN[0]
    _prefill(manager, (uid,), ["a", "b"], rng)
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in ("a", "b")}
    before = _counters()

    async def scenario():
        return await asyncio.gather(*(manager.decode_async(uid, name, token, False) for name, token in tokens.items()))

    with _Spans((uid,)) as seen:
        outs = dict(zip(tokens, asyncio.run(scenario())))
    assert _moved(before) == {"steps": 2, "calls": 1, "direct_calls": 0, "cohorts": 1}
    assert [(c.attributes["rows"], c.attributes["chain_len"]) for c in seen.cohorts()[0]] == [(2, 1)]
    assert list(manager._drainers) == [(uid,)]
    for name, token in tokens.items():
        np.testing.assert_allclose(outs[name], manager.decode(uid, "twin-" + name, token, reset=False), rtol=1e-5, atol=1e-5)


def test_a_row_that_fails_mid_chain_leaves_the_cohort():
    """Block 2 of 3 finds one session full: that row gets the error, block 3 never
    sees it, the others finish as if it had not been there, and no pin stays."""
    manager, rng = _manager(), np.random.RandomState(3)
    names = ["s0", "s1", "s2"]
    _prefill(manager, CHAIN, names, rng)
    manager._sessions[(CHAIN[1], "s1")].index = manager.max_len
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in names}
    before = _counters()
    with _Spans(CHAIN) as seen:
        outs = _step_together(manager, CHAIN, tokens)
    assert isinstance(outs["s1"], ValueError) and "full" in str(outs["s1"])
    assert _moved(before) == {"steps": 3 + 2 + 2, "calls": 3, "direct_calls": 0, "cohorts": 1}
    assert [b.attributes["rows"] for b in seen.cohorts()[1]] == [3, 2, 2]
    assert [manager._sessions[(uid, "s1")].index for uid in CHAIN] == [4, manager.max_len, 3]
    for name in ("s0", "s2"):
        np.testing.assert_allclose(outs[name], manager._decode_direct(CHAIN, "twin-" + name, tokens[name], False), rtol=1e-5, atol=1e-5)
    assert manager._in_flight == {} and not manager._pending.get(CHAIN)
    assert not any(session.lock.locked() for session in manager._sessions.values())


def test_a_program_that_fails_on_the_device_drops_the_sessions_it_was_handed_to(monkeypatch):
    """A cohort hands each block's new caches to the sessions while the program
    still runs. If a program then fails, what those sessions point at cannot be
    read: the rows fail and their sessions go, so that the clients re-prefill."""
    from hivemind_tpu.moe.server import decode_session

    manager, rng = _manager(), np.random.RandomState(8)
    _prefill(manager, CHAIN, ["s0", "s1"], rng)
    settle, calls = decode_session._Output.settle, []

    def lost_on_the_second_wait(self, span=None):
        calls.append(self)
        if len(calls) == 2:
            raise RuntimeError("device lost")
        return settle(self, span)

    monkeypatch.setattr(decode_session._Output, "settle", lost_on_the_second_wait)
    outs = _step_together(manager, CHAIN, {name: rng.randn(1, 1, HID).astype(np.float32) for name in ("s0", "s1")})
    monkeypatch.undo()
    assert all(isinstance(out, RuntimeError) and "device lost" in str(out) for out in outs.values())
    assert {key[1] for key in manager._sessions} == {"twin-s0", "twin-s1"}
    assert manager._in_flight == {} and not any(session.lock.locked() for session in manager._sessions.values())
    with pytest.raises(KeyError, match="unknown or expired"):
        asyncio.run(manager.decode_span_async(CHAIN, "s0", rng.randn(1, 1, HID).astype(np.float32), False))


def _failed_steps():
    return REGISTRY.get("hivemind_moe_decode_session_evictions_total").labels("failed_step").value


def test_a_program_that_fails_at_its_result_drops_its_batch_and_the_blocks_before_it(monkeypatch):
    """Every batched step donates the rows' caches (ISSUE 50), so a failed one cannot give them
    back. Block 0's program fails when its result is awaited, which the walk does once block 1
    is dispatched: exactly the cohort's sessions at blocks 0 and 1 leave the table (block 1's
    were handed outputs of a program fed by the one that failed), their futures get the error,
    each is counted `reason="failed_step"`; their sessions at block 2, which no program was
    handed, stay as they were, the other clients' sessions step on through the same programs,
    and a dropped client's next continuation gets the unknown-session KeyError and re-prefills."""
    from hivemind_tpu.moe.server import decode_session

    manager, rng = _manager(), np.random.RandomState(50)
    cohort, others = ["s0", "s1", "s2"], ["o0", "o1", "o2"]
    _prefill(manager, CHAIN, cohort + others, rng)
    untouched = {name: manager._sessions[(CHAIN[2], name)] for name in cohort}
    leaves_before = {name: session.leaves for name, session in untouched.items()}
    settle, calls = decode_session._Output.settle, []

    def lost_on_the_first_wait(self, span=None):
        calls.append(self)
        if len(calls) == 1:
            raise RuntimeError("device lost")
        return settle(self, span)

    before = _failed_steps()
    monkeypatch.setattr(decode_session._Output, "settle", lost_on_the_first_wait)
    outs = _step_together(manager, CHAIN, {name: rng.randn(1, 1, HID).astype(np.float32) for name in cohort})
    monkeypatch.undo()
    assert all(isinstance(out, RuntimeError) and "device lost" in str(out) for out in outs.values())
    assert _failed_steps() - before == 2 * len(cohort)
    gone = {(uid, name) for uid in CHAIN[:2] for name in cohort}
    assert not gone & set(manager._sessions)
    for name, session in untouched.items():  # never reached: as they were, and readable
        assert manager._sessions[(CHAIN[2], name)] is session and session.index == 3
        assert session.leaves is leaves_before[name] and not any(leaf.is_deleted() for leaf in session.leaves)
    assert {name for _uid, name in manager._sessions} >= set(others) | {"twin-" + name for name in cohort + others}
    assert manager._in_flight == {} and not any(session.lock.locked() for session in manager._sessions.values())
    tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in others}
    outs = _step_together(manager, CHAIN, tokens)  # the same bucket's programs, other rows
    for name, token in tokens.items():
        np.testing.assert_allclose(outs[name], manager._decode_direct(CHAIN, "twin-" + name, token, False), rtol=1e-5, atol=1e-5)
    token = rng.randn(1, 1, HID).astype(np.float32)
    with pytest.raises(KeyError, match="unknown or expired"):
        asyncio.run(manager.decode_span_async(CHAIN, "s0", token, False))
    prompt = rng.randn(1, 3, HID).astype(np.float32)
    for name in ("s0", "twin-s0"):  # the client re-prefills the whole chain, block 2's stale session replaced with the rest
        manager._decode_direct(CHAIN, name, prompt, True)
    assert manager._sessions[(CHAIN[2], "s0")] is not untouched["s0"]
    np.testing.assert_allclose(manager._decode_direct(CHAIN, "s0", token, False), manager._decode_direct(CHAIN, "twin-s0", token, False),
                               rtol=1e-5, atol=1e-5)


def test_a_program_that_fails_at_its_dispatch_after_it_took_the_caches_drops_them_too():
    """The program of block 1 is handed its rows' caches and then raises at the dispatch itself:
    its batch's sessions hold deleted buffers and go, and so do the same rows' sessions at
    block 0, whose step has ended while the chain's has not; block 2 is never reached. A
    session's own step that fails counts the same reason."""
    manager, rng = _manager(), np.random.RandomState(51)
    names = ["s0", "s1"]
    _prefill(manager, CHAIN, names, rng)
    real = manager._batched_fn(CHAIN[1], 2)

    def lost_after_the_dispatch(*args):
        real(*args)
        raise RuntimeError("device lost")

    before = _failed_steps()
    manager._batched_fns[(CHAIN[1], 2)] = lost_after_the_dispatch
    outs = _step_together(manager, CHAIN, {name: rng.randn(1, 1, HID).astype(np.float32) for name in names})
    manager._batched_fns[(CHAIN[1], 2)] = real
    assert all(isinstance(out, RuntimeError) and "device lost" in str(out) for out in outs.values())
    assert _failed_steps() - before == 2 * len(names)
    assert {(uid, name) for uid, name in manager._sessions if name in names} == {(CHAIN[2], name) for name in names}
    assert manager._in_flight == {} and not any(session.lock.locked() for session in manager._sessions.values())

    def broken(*_args):
        raise RuntimeError("device fault")

    before = _failed_steps()
    manager._step_fns[(CHAIN[0], 1, 1)] = broken
    with pytest.raises(RuntimeError, match="device fault"):
        manager.decode(CHAIN[0], "twin-s0", rng.randn(1, 1, HID).astype(np.float32), reset=False)
    assert _failed_steps() - before == 1 and (CHAIN[0], "twin-s0") not in manager._sessions


def test_cancelling_the_drainer_mid_cohort_cancels_every_pending_future():
    chain = CHAIN[:2]
    manager, rng = _manager(chain), np.random.RandomState(4)
    _prefill(manager, chain, ["s0", "s1", "late"], rng)
    token = rng.randn(1, 1, HID).astype(np.float32)
    entered, release, real_batch = threading.Event(), threading.Event(), manager._decode_batch

    def stuck_at_the_second_block(uid, entries, **how):
        if uid == chain[1]:
            entered.set()
            release.wait(10)
        return real_batch(uid, entries, **how)

    manager._decode_batch = stuck_at_the_second_block

    async def scenario():
        loop = asyncio.get_running_loop()
        steps = [asyncio.create_task(manager.decode_span_async(chain, name, token, False)) for name in ("s0", "s1")]
        await loop.run_in_executor(None, entered.wait, 10)
        # arrives while the cohort runs: it finds a live drainer and only enqueues
        steps.append(asyncio.create_task(manager.decode_span_async(chain, "late", token, False)))
        await asyncio.sleep(0.01)
        assert [len(entry[1]) for entry in manager._pending[chain]] == [2]
        assert len(manager._in_flight) == 4  # two sessions a row, two rows in the cohort
        drainer = manager._drainers[chain]
        drainer.cancel()
        with pytest.raises(asyncio.CancelledError):
            await drainer
        release.set()
        for step in steps:
            with pytest.raises(asyncio.CancelledError):
                await step
        assert manager._in_flight == {} and not manager._pending.get(chain)

    asyncio.run(scenario())


def test_chains_that_share_blocks_neither_deadlock_nor_mix_rows():
    """[b0, b1, b2] beside [b1, b2], stepping concurrently: each chain batches among
    its own sessions, and they meet only at the session locks."""
    whole, tail = CHAIN, CHAIN[1:]
    manager, rng = _manager(), np.random.RandomState(5)
    _prefill(manager, whole, ["w0", "w1", "w2"], rng)
    _prefill(manager, tail, ["t0", "t1"], rng, length=4)
    chain_of = {"w0": whole, "w1": whole, "w2": whole, "t0": tail, "t1": tail}
    before = _counters()

    async def scenario(tokens):
        return await asyncio.wait_for(asyncio.gather(
            *(manager.decode_span_async(chain_of[name], name, token, False) for name, token in tokens.items())), 60.0)

    for _round in range(3):
        tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in chain_of}
        outs = dict(zip(tokens, asyncio.run(scenario(tokens))))
        for name, token in tokens.items():
            want = manager._decode_direct(chain_of[name], "twin-" + name, token, False)
            np.testing.assert_allclose(outs[name], want, rtol=1e-5, atol=1e-5)
    assert set(manager._drainers) == {whole, tail}
    assert _moved(before) == {"steps": 3 * (3 * 3 + 2 * 2), "calls": 3 * (3 + 2), "direct_calls": 3 * (3 * 3 + 2 * 2), "cohorts": 6}
    assert manager._in_flight == {} and not any(session.lock.locked() for session in manager._sessions.values())


def test_chains_that_share_blocks_never_hand_one_throwaway_cache_to_two_programs():
    """[b0, b1, b2] beside [b1, b2], three rows each, so that BOTH chains' programs pad their
    bucket of four at the shared blocks, from threads of their own and under a short switch
    interval: a throwaway cache is out of the block's store while a program holds it (it is
    donated), so no two calls can be handed the same one; every output equals its twin's, and
    afterwards the store holds live arrays only, as many bytes as its gauge says."""
    import sys

    whole, tail, steps = CHAIN, CHAIN[1:], 5
    manager, rng = _manager(), np.random.RandomState(52)
    _prefill(manager, whole, ["w0", "w1", "w2"], rng)
    _prefill(manager, tail, ["t0", "t1", "t2"], rng, length=4)
    chain_of = {"w0": whole, "w1": whole, "w2": whole, "t0": tail, "t1": tail, "t2": tail}
    tokens = {name: rng.randn(steps, 1, 1, HID).astype(np.float32) for name in chain_of}

    async def stream(name):
        return [await manager.decode_span_async(chain_of[name], name, tokens[name][step], False) for step in range(steps)]

    async def scenario():
        return await asyncio.wait_for(asyncio.gather(*(stream(name) for name in chain_of)), 120.0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        outs = dict(zip(chain_of, asyncio.run(scenario())))
    finally:
        sys.setswitchinterval(interval)
    for name, chain in chain_of.items():
        for step in range(steps):
            want = manager._decode_direct(chain, "twin-" + name, tokens[name][step], False)
            np.testing.assert_allclose(outs[name][step], want, rtol=1e-5, atol=1e-5)
    kept = [row for rows in manager._padding_rows.values() for row in rows]
    assert kept and not any(leaf.is_deleted() for row in kept for leaf in row)
    assert len({id(leaf) for row in kept for leaf in row}) == sum(len(row) for row in kept)
    assert manager._padding_bytes == sum(leaf.nbytes for row in kept for leaf in row)
    assert manager._in_flight == {} and not any(session.lock.locked() for session in manager._sessions.values())


@pytest.fixture(scope="module")
def warmed():
    """A two-block manager after the warm-up of `perf/runners/block_server.py`
    `warm_decode`, at tiny sizes: per block a prefill of each prompt length, the
    single-session step, every full bucket and one short of the largest through
    `_decode_batch`; then the table cleared."""
    chain, slots, lengths = CHAIN[:2], 8, [3, 6]
    manager = _manager(chain)
    buckets = [2**k for k in range(1, slots.bit_length())]
    token, shortest = np.zeros((1, 1, HID), np.float32), min(lengths)
    for uid in chain:
        for length in lengths:
            manager.decode(uid, f"warm-len{length}", np.zeros((1, length, HID), np.float32), reset=True)
        manager.decode(uid, f"warm-len{shortest}", token, reset=False)
        names = [f"warm-row{i}" for i in range(max(buckets))]
        for name in names:
            manager.decode(uid, name, np.zeros((1, shortest, HID), np.float32), reset=True)
        for rows in buckets + [max(buckets) - 1]:
            entries = [(None, manager._sessions[(uid, name)], token) for name in names[:rows]]
            assert not [out for out in manager._decode_batch(uid, entries) if isinstance(out, Exception)]
        with manager._lock:
            manager._sessions.clear()
    return manager, chain, lengths


@pytest.mark.parametrize("rows", [2, 3, 4, 5, 6, 7, 8])
def test_a_warmed_server_compiles_nothing_for_a_cohort(warmed, rows):
    """Every program a cohort runs is one the benchmark's warm-up reaches: the
    `(uid, bucket)` batched programs, called with host arrays for the activations
    and write positions and the sessions' own caches."""
    from hivemind_tpu.moe.server.decode_session import _cohort_rows

    manager, chain, lengths = warmed
    rng = np.random.RandomState(rows)
    names = [f"r{rows}-{i}" for i in range(rows)]
    _prefill(manager, chain, names, rng, length=lengths[rows % 2])
    compiles, programs = _compiles(), (len(manager._batched_fns), len(manager._step_fns))
    for _step in range(2):  # fresh from the prefill, then with caches a cohort handed back
        tokens = {name: rng.randn(1, 1, HID).astype(np.float32) for name in names}
        before = _counters()
        outs = _step_together(manager, chain, tokens)
        first = _cohort_rows(rows)
        batched = [taken for taken in (first, rows - first) if taken > 1]  # a lone row takes the per-session program
        assert _moved(before) == {"steps": 2 * sum(batched), "calls": 2 * len(batched),
                                  "direct_calls": 2 * (rows - sum(batched)), "cohorts": 1 + (rows > first)}
        assert _compiles() == compiles and (len(manager._batched_fns), len(manager._step_fns)) == programs
        for name, token in tokens.items():
            np.testing.assert_allclose(outs[name], manager._decode_direct(chain, "twin-" + name, token, False), rtol=1e-5, atol=1e-5)


def test_closed_loop_sessions_under_a_short_switch_interval_keep_their_streams():
    """More sessions than cores, each sending its next token when the last came
    back, so that cohorts are launched while others are fetched and answered: every
    session's every token must equal its twin's on the direct path, and nothing
    may stay pinned, pending or locked."""
    import sys

    sessions, steps = 24, 6
    manager, rng = _manager(), np.random.RandomState(9)
    names = [f"s{i}" for i in range(sessions)]
    _prefill(manager, CHAIN, names, rng)
    tokens = rng.randn(sessions, steps, 1, 1, HID).astype(np.float32)
    before = _counters()

    async def stream(i):
        return [await manager.decode_span_async(CHAIN, names[i], tokens[i, step], False) for step in range(steps)]

    async def scenario():
        return await asyncio.wait_for(asyncio.gather(*(stream(i) for i in range(sessions))), 120.0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        outs = asyncio.run(scenario())
    finally:
        sys.setswitchinterval(interval)
    moved = _moved(before)
    assert moved["steps"] + moved["direct_calls"] == 3 * sessions * steps  # every token crossed every block once
    for i, name in enumerate(names):
        for step in range(steps):
            want = manager._decode_direct(CHAIN, "twin-" + name, tokens[i, step], False)
            np.testing.assert_allclose(outs[i][step], want, rtol=1e-5, atol=1e-5)
    assert manager._in_flight == {} and not manager._pending.get(CHAIN)
    assert not any(session.lock.locked() for session in manager._sessions.values())
    assert all(session.index == 3 + steps for session in manager._sessions.values())


def test_a_prefill_is_one_chain_on_the_device():
    """A prompt walks the chain's blocks on the device (ISSUE 34): ONE upload (the
    padded chunk) and ONE fetch (the last block's output, its real positions) whatever
    the chain's length, one `decode.direct` a block, every session advanced, and the
    output is what block-by-block calls give: past the first block the padded tail
    holds what the block before made of the padding, which no real position sees."""
    manager = _manager()
    rng = np.random.RandomState(34)
    prompt = rng.randn(1, 5, HID).astype(np.float32)  # pads to 8 positions
    moved = REGISTRY.get("hivemind_device_transfer_bytes_total")
    level = lambda: np.array([moved.labels(direction).value for direction in ("host_to_device", "device_to_host")])
    before = level()
    with _Spans(CHAIN) as seen:
        chained = manager._decode_direct(CHAIN, "chained", prompt, True)
    assert list(level() - before) == [8 * HID * 4, 5 * HID * 4]
    assert [s.attributes["uid"] for s in seen.spans if s.name == "decode.direct"] == list(CHAIN)
    prefilled = lambda: (REGISTRY.get("hivemind_moe_decode_prefill_positions_total").value(),
                         REGISTRY.get("hivemind_moe_decode_prefill_seconds_total").value())
    positions, seconds = prefilled()
    stepwise = prompt
    for uid in CHAIN:
        stepwise = manager.decode(uid, "stepwise", stepwise, True)
    assert chained.shape == (1, 5, HID)
    assert prefilled()[0] - positions == 3 * 8 and prefilled()[1] > seconds  # the padded chunk, once a block; a step counts nothing
    np.testing.assert_allclose(chained, stepwise, rtol=1e-5, atol=1e-6)
    token = rng.randn(1, 1, HID).astype(np.float32)
    before = level()
    chained = manager._decode_direct(CHAIN, "chained", token, False)
    assert list(level() - before) == [HID * 4, HID * 4]
    for uid in CHAIN:
        token = manager.decode(uid, "stepwise", token, False)
    np.testing.assert_allclose(chained, token, rtol=1e-5, atol=1e-6)
    assert all(session.index == 6 for session in manager._sessions.values())
    assert prefilled()[0] - positions == 3 * 8
    with pytest.raises(KeyError, match="unknown or expired"):
        manager._decode_direct(CHAIN, "never-opened", token, False)
