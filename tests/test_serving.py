"""Serving-path observability (ISSUE 9): per-request attribution records,
bounded-queue load-shed + breaker feedback, decode-session saturation metrics,
expert scorecards, the ``GET /serving`` endpoint and the ``hivemind-top
--serving`` board — including the two-peer end-to-end test that drives real
``rpc_forward`` / ``rpc_decode`` traffic."""

import asyncio
import json
import sys
import threading
import time
import urllib.request
import uuid

import numpy as np
import optax
import pytest

from hivemind_tpu.telemetry import REGISTRY, MetricsExporter
from hivemind_tpu.telemetry.serving import (
    SCORECARDS,
    SERVING_LEDGER,
    SERVING_SPAN,
    ExpertScorecards,
    ServingLedger,
    is_overload_error,
)
from hivemind_tpu.telemetry.tracing import Span
from swarm_utils import wait_for_experts

HID = 16


def _finished_span(name=SERVING_SPAN, duration=0.1, events=(), **attributes) -> Span:
    span = Span(name, attributes=dict(attributes))
    span.start -= duration
    for event_name, event_attrs in events:
        span.add_event(event_name, **event_attrs)
    span.end = time.perf_counter()
    return span


# ---------------------------------------------------------------- ledger units


def test_serving_ledger_assembles_records_from_spans():
    ledger = ServingLedger()
    ledger.on_span(_finished_span(
        duration=0.3, expert="e.0", kind="forward", peer="srv", client="cliA",
        batch=4, occupancy=0.5, pool="e.0_forward",
        queue_wait_s=0.25, assembly_s=0.001, compute_s=0.04, serialize_s=0.002,
    ))
    ledger.on_span(_finished_span(
        duration=0.02, expert="e.1", kind="decode", peer="srv", client="cliB",
        compute_s=0.018,
    ))
    # a non-serving span is ignored (one failed name compare)
    ledger.on_span(_finished_span(name="allreduce.round", duration=9.0))
    records = ledger.records()
    assert len(records) == 2
    first = records[0]
    assert first["expert"] == "e.0" and first["kind"] == "forward"
    assert first["client"] == "cliA" and first["batch"] == 4
    assert first["queue_wait_s"] == pytest.approx(0.25)
    assert first["compute_s"] == pytest.approx(0.04)
    assert first["occupancy"] == 0.5 and first["pool"] == "e.0_forward"
    assert first["queue_wait_s"] > first["compute_s"]  # decomposition readable

    experts = ledger.expert_stats()
    assert set(experts) == {"e.0", "e.1"}
    assert experts["e.0"]["requests"] == 1 and "p95_s" in experts["e.0"]
    clients = ledger.client_stats()
    assert clients["cliA"]["requests"] == 1 and clients["cliB"]["requests"] == 1
    # slowest exemplars: the 0.3 s forward leads
    assert ledger.slowest()[0]["expert"] == "e.0"
    summary = ledger.summary()
    assert summary["requests"] == 2 and summary["sheds"] == 0
    assert summary["phases"]["queue_wait_s"]["p95"] >= 0.25
    assert summary["batch_occupancy"]["mean"] == pytest.approx(0.5)
    snapshot = ledger.snapshot()
    assert snapshot["totals"]["requests"] == 2
    assert "e.0" in snapshot["experts"]


def test_serving_ledger_classifies_sheds_and_errors():
    ledger = ServingLedger()
    ledger.on_span(_finished_span(
        duration=0.001, expert="e.0", kind="forward", client="cliA",
        events=[("error", {"type": "ServerOverloadedError"})],
    ))
    ledger.on_span(_finished_span(
        duration=0.001, expert="e.0", kind="forward", client="cliA",
        events=[("error", {"type": "KeyError"})],
    ))
    summary = ledger.summary()
    assert summary["requests"] == 2 and summary["errors"] == 2 and summary["sheds"] == 1
    assert summary["experts"]["e.0"]["sheds"] == 1
    assert ledger.records()[0]["error"] == "ServerOverloadedError"


def test_serving_ledger_bounds_client_cardinality():
    """Client ids are remote-controlled: cycling identities must not grow the
    table without bound."""
    ledger = ServingLedger(max_clients=8)
    for index in range(50):
        ledger.on_span(_finished_span(expert="e.0", client=f"cli-{index}"))
    assert len(ledger.client_stats()) <= 8


def test_scorecards_classify_outcomes():
    cards = ExpertScorecards()
    cards.record("e.0", 0.01, ok=True)
    cards.record("e.0", 0.02, ok=True, kind="backward")
    cards.record("e.0", 0.5, ok=False, error=RuntimeError("ServerOverloadedError: full"))
    cards.record("e.0", 1.0, ok=False, error=asyncio.CancelledError())
    cards.record("e.0", 0.1, ok=False, error=ValueError("boom"))
    card = cards.card("e.0")
    assert card["requests"] == 5 and card["ok"] == 2
    assert card["sheds"] == 1 and card["timeouts"] == 1 and card["failures"] == 1
    assert card["success_rate"] == pytest.approx(0.4)
    assert card["p95_s"] >= card["p50_s"] > 0
    assert card["kinds"] == {"forward": 4, "backward": 1}
    assert is_overload_error(RuntimeError("ServerOverloadedError: full"))
    assert not is_overload_error(ValueError("fine"))


@pytest.mark.parametrize("span_len, pool", [(None, "e.0_forward"), (8, "e.0..e.7_forward")])
def test_serving_record_names_the_span_and_its_one_pool(span_len, pool):
    """A span request is one task in the pool of its chain: the record carries the
    chain's length and that pool; a single block's record has no ``span_len``."""
    ledger = ServingLedger()
    extra = {} if span_len is None else {"span_len": span_len}
    ledger.on_span(_finished_span(duration=0.3, expert="e.0", kind="backward", peer="srv", client="cliA", batch=4,
                                  occupancy=1.0, pool=pool, queue_wait_s=0.1, compute_s=0.15, stage_s=0.03, **extra))
    [record] = ledger.records()
    assert record.get("span_len") == span_len and record["pool"] == pool and record["occupancy"] == 1.0
    assert record["stage_s"] == pytest.approx(0.03) and record["stage_s"] <= record["compute_s"]


# ---------------------------------------------------------------- pool units


async def test_request_through_two_pools_accrues_phases_and_keeps_the_worst_occupancy_pool():
    from hivemind_tpu.moe.server.task_pool import TaskPool
    from hivemind_tpu.telemetry.tracing import trace

    full = TaskPool(lambda x: [x], "occ_full", max_batch_size=2, blocks=3)
    thin = TaskPool(lambda x: [x], "occ_thin", max_batch_size=8)
    blocks = REGISTRY.get("hivemind_moe_pool_blocks_total")
    before = {name: blocks.labels(name).value for name in ("occ_full", "occ_thin")}
    x = np.zeros((2, 4), np.float32)
    with trace(SERVING_SPAN, kind="forward", expert="occ.0", client="cli", peer="srv") as span:
        for pool in (full, thin, full):
            pending = asyncio.ensure_future(pool.submit_task(x))
            await asyncio.sleep(0.01)
            pool.process_batch(pool.pop_batch())
            await asyncio.wait_for(pending, timeout=10)
    assert (span.attributes["pool"], span.attributes["occupancy"]) == ("occ_thin", 0.25)
    assert span.attributes["queue_wait_s"] >= 0.03 and span.attributes["compute_s"] > 0  # three hops, summed
    # every batch counts the blocks its pool walks
    assert blocks.labels("occ_full").value - before["occ_full"] == 6
    assert blocks.labels("occ_thin").value - before["occ_thin"] == 1



async def test_task_pool_deque_semantics_and_phase_stamps():
    from hivemind_tpu.moe.server.task_pool import TaskPool

    pool = TaskPool(lambda x: [x * 2], "unit_pool", max_batch_size=8)
    inputs = [np.full((2, 4), float(i), np.float32) for i in range(3)]
    submits = [asyncio.create_task(pool.submit_task(x)) for x in inputs]
    await asyncio.sleep(0.01)
    assert pool.queue_size == 3
    assert pool.priority < float("inf")

    batch = pool.pop_batch()
    # oldest-first drain (deque popleft), all three fit in max_batch_size=8
    assert [float(t.args[0][0, 0]) for t in batch] == [0.0, 1.0, 2.0]
    assert all(t.popped_pc is not None for t in batch)
    assert pool.queue_size == 0 and pool.priority == float("inf")

    pool.process_batch(batch)
    results = await asyncio.gather(*submits)
    for x, [out] in zip(inputs, results):
        np.testing.assert_array_equal(out, x * 2)
    # phase stamps: compute/assembly/occupancy shared per batch
    assert all(t.compute_s is not None and t.assembly_s is not None for t in batch)
    assert all(t.occupancy == pytest.approx(6 / 8) for t in batch)


async def test_task_pool_bounded_queue_sheds():
    from hivemind_tpu.moe.server.task_pool import ServerOverloadedError, TaskPool

    pool = TaskPool(lambda x: [x], "shed_pool", max_batch_size=4, max_queue_size=1)
    shed_counter = REGISTRY.get("hivemind_moe_shed_total").labels("shed_pool")
    sheds_before = shed_counter.value
    first = asyncio.create_task(pool.submit_task(np.zeros((1, 2), np.float32)))
    await asyncio.sleep(0.01)
    with pytest.raises(ServerOverloadedError, match="shed"):
        await pool.submit_task(np.zeros((1, 2), np.float32))
    assert shed_counter.value == sheds_before + 1
    # depth gauge sampled on submit: the queued (not shed) task is visible
    assert REGISTRY.get("hivemind_moe_pool_queue_depth").labels("shed_pool").value == 1
    batch = pool.pop_batch()
    pool.process_batch(batch)
    await first


async def test_process_batch_validates_output_leading_dim():
    """Satellite: a process_func returning the wrong leading batch dim used to
    silently mis-slice per-task outputs — now the whole batch fails loudly."""
    from hivemind_tpu.moe.server.task_pool import TaskPool

    pool = TaskPool(lambda x: [x[:1]], "bad_pool", max_batch_size=8)
    submits = [
        asyncio.create_task(pool.submit_task(np.zeros((2, 3), np.float32)))
        for _ in range(2)
    ]
    await asyncio.sleep(0.01)
    batch = pool.pop_batch()
    with pytest.raises(ValueError, match="leading") as excinfo:
        pool.process_batch(batch)
    assert "4 samples" in str(excinfo.value)  # descriptive: expected batch size named
    pool.fail_batch(batch, excinfo.value)  # what the Runtime does with the raise
    for submit in submits:
        with pytest.raises(ValueError, match="mis-slice"):
            await submit


# ------------------------------------------------------- decode session limits


def _decode_backend(uid="lim.0"):
    from hivemind_tpu.moe import ModuleBackend
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    module = CausalTransformerExpert(hidden_dim=HID, num_heads=4)
    return {uid: ModuleBackend(
        uid, module, optimizer=optax.sgd(1e-3),
        sample_input=np.zeros((1, 4, HID), np.float32), max_batch_size=8,
    )}


def test_decode_session_cap_eviction_and_counters():
    """Satellite: decode_max_sessions overflow was untested. The LRU cap must
    evict the oldest session (continuations on it then raise), and the new
    occupancy/eviction metrics must record it."""
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

    manager = DecodeSessionManager(_decode_backend(), max_len=32, max_sessions=2)
    evictions = REGISTRY.get("hivemind_moe_decode_session_evictions_total")
    cap_before = evictions.labels("cap").value
    rng = np.random.RandomState(0)
    prompts = {name: rng.randn(1, 3, HID).astype(np.float32) for name in ("s1", "s2", "s3")}
    for name in ("s1", "s2", "s3"):
        manager.decode("lim.0", name, prompts[name], reset=True)
        time.sleep(0.002)  # distinct last_used ordering
    # the cap (2) is enforced on the next decode's eviction sweep: s1 (oldest) dies
    step = rng.randn(1, 1, HID).astype(np.float32)
    manager.decode("lim.0", "s3", step, reset=False)
    assert set(k[1] for k in manager._sessions) == {"s2", "s3"}
    assert evictions.labels("cap").value == cap_before + 1
    assert REGISTRY.get("hivemind_moe_decode_sessions").value() == 2
    assert REGISTRY.get("hivemind_moe_decode_session_occupancy").value() == pytest.approx(1.0)
    with pytest.raises(KeyError, match="reset=True"):
        manager.decode("lim.0", "s1", step, reset=False)


def test_decode_session_ttl_eviction_and_reset_semantics():
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

    manager = DecodeSessionManager(
        _decode_backend(), max_len=32, max_sessions=8, session_ttl=0.1
    )
    evictions = REGISTRY.get("hivemind_moe_decode_session_evictions_total")
    resets = REGISTRY.get("hivemind_moe_decode_session_resets_total")
    ttl_before = evictions.labels("ttl").value
    resets_before = resets.value()
    rng = np.random.RandomState(1)
    prompt = rng.randn(1, 4, HID).astype(np.float32)

    out_first = manager.decode("lim.0", "ttl-session", prompt, reset=True)
    session = manager._sessions[("lim.0", "ttl-session")]
    assert session.index == 4
    # reset on the SAME id rebuilds the cache from scratch: index restarts and
    # the prefill output is bit-identical to the first (deterministic)
    out_reset = manager.decode("lim.0", "ttl-session", prompt, reset=True)
    np.testing.assert_array_equal(out_first, out_reset)
    assert manager._sessions[("lim.0", "ttl-session")].index == 4
    assert resets.value() == resets_before + 2

    time.sleep(0.15)  # past the TTL
    manager.decode("lim.0", "fresh", prompt, reset=True)  # sweep runs here
    assert ("lim.0", "ttl-session") not in manager._sessions
    # >=: the first decode's jit compile can itself exceed the tiny TTL, making
    # an earlier sweep evict once already — at least the final eviction counted
    assert evictions.labels("ttl").value >= ttl_before + 1
    steps = REGISTRY.get("hivemind_moe_decode_steps_total")
    assert steps.labels("direct").value >= 3


# ---------------------------------------------- the vmapped batch is one program (ISSUE 25)


def _warmed_manager(uid="lim.0", slots=8):
    """A manager warmed as `perf/runners/block_server.py` `warm_decode` warms a
    block: sessions prefilled, every full bucket and one short of the largest
    stepped through `_decode_batch`, then the sessions cleared."""
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

    manager = DecodeSessionManager(_decode_backend(uid), max_len=32, max_sessions=64)
    buckets = [2**k for k in range(1, slots.bit_length())]
    token, prompt = np.zeros((1, 1, HID), np.float32), np.zeros((1, 3, HID), np.float32)
    manager.decode(uid, "warm-len", prompt, reset=True)
    manager.decode(uid, "warm-len", token, reset=False)
    names = [f"warm-row{i}" for i in range(max(buckets))]
    for name in names:
        manager.decode(uid, name, prompt, reset=True)
    for rows in buckets + [max(buckets) - 1]:
        entries = [(None, manager._sessions[(uid, name)], token) for name in names[:rows]]
        assert not [o for o in manager._decode_batch(uid, entries) if isinstance(o, Exception)]
    with manager._lock:
        manager._sessions.clear()
    return manager


def _prefilled(manager, uid, names, rng):
    for name in names:
        manager.decode(uid, name, rng.randn(1, 3, HID).astype(np.float32), reset=True)
    return [manager._sessions[(uid, name)] for name in names]


def _batched_compiles() -> int:
    from hivemind_tpu.telemetry.device import COMPILE_TRACKER

    return COMPILE_TRACKER.counts().get("decode_session.batched_step", 0)


@pytest.mark.parametrize("rows", [2, 5, 8])
def test_warmed_decode_batch_dispatches_nothing_per_session(rows, monkeypatch):
    """A warmed vmapped batch is ONE dispatched program: stacking the rows' caches
    and handing the new ones out happen inside it, so the eager primitives around
    it (each a dispatch of its own) number the same, none, whatever the rows. The
    one exception is not a step's: the first batch that pads its bucket by more
    positions than any before it makes the throwaway caches it lacks (ISSUE 50: a
    position each, the program donates them), as a prefill makes a session's."""
    from jax._src import core as jax_core

    uid = "lim.0"
    manager = _warmed_manager(uid)
    sessions = _prefilled(manager, uid, [f"s{i}" for i in range(rows)], np.random.RandomState(rows))
    token = np.random.RandomState(0).randn(1, 1, HID).astype(np.float32)
    eager = []
    process_primitive = jax_core.EvalTrace.process_primitive

    def counting(self, primitive, args, params):
        eager.append(primitive.name)
        return process_primitive(self, primitive, args, params)

    steps = REGISTRY.get("hivemind_moe_decode_steps_total")
    batched_before = steps.labels("batched").value
    monkeypatch.setattr(jax_core.EvalTrace, "process_primitive", counting)
    manager._fresh_caches(manager.backends[uid], 1)
    a_cache, eager[:] = len(eager), []
    padding = {2: 0, 5: 3, 8: 0}[rows]  # the warm-up padded by one
    manager._decode_batch(uid, [(None, session, token) for session in sessions])
    assert len(eager) == a_cache * max(padding - 1, 0), f"{len(eager)} eager primitives around a first batch of {rows} rows"
    eager[:] = []
    results = manager._decode_batch(uid, [(None, session, token) for session in sessions])
    monkeypatch.undo()
    assert eager == [], f"{len(eager)} eager primitives around a batch of {rows} rows: {sorted(set(eager))}"
    assert steps.labels("batched").value == batched_before + 2 * rows
    assert all(isinstance(out, np.ndarray) and out.shape == (1, 1, HID) for out in results)
    assert all(session.index == 5 and session.cache_k.shape[0] == 1 for session in sessions)


def test_decode_batch_program_is_keyed_by_the_bucket_alone():
    """Live counts the warm-up never ran (3, 5, 6 in the bucket of 8), with rows
    fresh from a prefill and rows a batched step has already handed their caches:
    no new program; and the batched path computes what the direct path computes."""
    uid = "lim.0"
    manager = _warmed_manager(uid)
    fns_before, compiles_before = len(manager._batched_fns), _batched_compiles()
    rng = np.random.RandomState(25)
    prompts = rng.randn(6, 1, 3, HID).astype(np.float32)
    tokens = rng.randn(3, 6, 1, 1, HID).astype(np.float32)
    for name in [f"b{i}" for i in range(6)] + [f"d{i}" for i in range(6)]:
        manager.decode(uid, name, prompts[int(name[1:])], reset=True)
    batched = [manager._sessions[(uid, f"b{i}")] for i in range(6)]
    direct = [manager._sessions[(uid, f"d{i}")] for i in range(6)]

    # 3 fresh rows; then 5 (three stepped, two fresh); then 6 (five stepped, one fresh)
    outs = {}
    for step, rows in enumerate((3, 5, 6)):
        results = manager._decode_batch(uid, [(None, batched[i], tokens[step, i]) for i in range(rows)])
        for i, out in enumerate(results):
            assert not isinstance(out, Exception), out
            outs[step, i] = out
    assert len(manager._batched_fns) == fns_before
    assert _batched_compiles() == compiles_before, "a live count inside a warmed bucket compiled a program"

    for step, rows in enumerate((3, 5, 6)):
        for i in range(rows):
            want = manager.decode(uid, f"d{i}", tokens[step, i], reset=False)
            np.testing.assert_allclose(outs[step, i], want, rtol=1e-5, atol=1e-5)
    for got, want in zip(batched, direct):
        assert got.index == want.index
        for a, b in ((got.cache_k, want.cache_k), (got.cache_v, want.cache_v)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32)[:, :got.index], np.asarray(b, np.float32)[:, :got.index], rtol=1e-5, atol=1e-5)


def test_failed_decode_batch_leaves_every_session_intact(monkeypatch):
    """The batched program does not donate the rows' caches (CHANGES.md, PR 25): a
    step that raises, at dispatch or when its output is read, leaves every session
    where it was, and the same tokens then step as if nothing had happened."""
    uid = "lim.0"
    manager = _warmed_manager(uid, slots=4)
    rng = np.random.RandomState(4)
    sessions = _prefilled(manager, uid, ["f0", "f1", "f2"], rng)
    twins = _prefilled(manager, uid, ["t0", "t1", "t2"], np.random.RandomState(4))
    tokens = rng.randn(3, 1, 1, HID).astype(np.float32)

    def poisoned(params, *args):
        raise RuntimeError("device lost")

    monkeypatch.setattr(manager, "_batched_fn", lambda uid, stack: poisoned)
    with pytest.raises(RuntimeError, match="device lost"):
        manager._decode_batch(uid, [(None, session, token) for session, token in zip(sessions, tokens)])
    monkeypatch.undo()

    assert all(manager._sessions[(uid, f"f{i}")] is session for i, session in enumerate(sessions))
    assert all(session.index == 3 and not session.lock.locked() for session in sessions)
    assert not any(cache.is_deleted() for session in sessions for cache in (session.cache_k, session.cache_v))
    results = manager._decode_batch(uid, [(None, session, token) for session, token in zip(sessions, tokens)])
    for i, out in enumerate(results):
        np.testing.assert_allclose(out, manager.decode(uid, f"t{i}", tokens[i], reset=False), rtol=1e-5, atol=1e-5)
    assert all(session.index == twin.index == 4 for session, twin in zip(sessions, twins))


# ------------------------------------------------------------------ end-to-end


def test_two_peer_serving_attribution_shed_breaker_and_board(capsys):
    """The acceptance test: real rpc_forward/rpc_decode traffic between two DHT
    peers. Asserts (a) a ServingLedger record decomposes queue-wait vs compute
    with queue-wait dominating when the pool is artificially stalled, (b) a
    shed request increments hivemind_moe_shed_total AND trips the client-side
    expert breaker, (c) GET /serving and `hivemind-top --serving --frames 1
    --no-ansi` render the board."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteExpert, RemoteSequential, Server
    from hivemind_tpu.moe.client.call_many import EXPERT_BREAKERS
    from hivemind_tpu.moe.expert_uid import ExpertInfo
    from hivemind_tpu.telemetry import TelemetryPublisher
    from hivemind_tpu.telemetry.tracing import RECORDER

    SERVING_LEDGER.clear()
    SCORECARDS.clear()
    server = Server.create(
        expert_uids=["sobs.0", "sobs.1"], expert_cls="causal_transformer",
        hidden_dim=HID, start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        rng = np.random.RandomState(0)

        # --- rpc_decode traffic through a real KV session -------------------
        pipe = RemoteSequential(client_dht, "sobs.", 1)
        session = uuid.uuid4().hex
        hidden = rng.randn(1, 5, HID).astype(np.float32)
        pipe.decode_step(hidden[:, :4], session, reset=True)
        pipe.decode_step(hidden[:, 4:5], session)
        decode_records = [r for r in SERVING_LEDGER.records() if r["kind"] == "decode"]
        assert decode_records, SERVING_LEDGER.records()
        assert decode_records[-1]["expert"] == "sobs.0"
        assert decode_records[-1]["client"] == str(client_dht.peer_id)
        assert decode_records[-1]["compute_s"] > 0
        # the record joined the CALLER's trace: the client-side p2p.call span
        # of an rpc_decode shares its trace id with a serving record
        client_traces = {
            f"{span.trace_id:016x}" for span in RECORDER.snapshot()
            if span.name == "p2p.call:ConnectionHandler.rpc_decode"
        }
        assert any(r["trace"] in client_traces for r in decode_records)

        # --- rpc_forward with an artificially stalled runtime ---------------
        # occupy the single drain executor with a slow batch on sobs.1, then
        # request sobs.0: its task sits in the queue behind the slow batch, so
        # queue-wait must dominate its decomposition
        slow_pool = server.handler.chain_pool("forward", ["sobs.1"])
        original_process = slow_pool.process_func

        def slow_process(*args):
            time.sleep(1.0)
            return original_process(*args)

        slow_pool.process_func = slow_process
        info0 = ExpertInfo("sobs.0", server.dht.peer_id)
        info1 = ExpertInfo("sobs.1", server.dht.peer_id)
        expert0 = RemoteExpert(info0, client_dht.node.p2p)
        expert1 = RemoteExpert(info1, client_dht.node.p2p)
        x = rng.randn(1, 4, HID).astype(np.float32)

        slow_thread = threading.Thread(target=lambda: expert1.forward_np(x))
        slow_thread.start()
        time.sleep(0.4)  # let the slow batch reach the device executor
        expert0.forward_np(x)  # queues behind the 1.0 s batch
        slow_thread.join(timeout=15)
        stalled = [
            r for r in SERVING_LEDGER.records()
            if r["kind"] == "forward" and r["expert"] == "sobs.0"
        ]
        assert stalled, SERVING_LEDGER.records()
        record = stalled[-1]
        assert record["queue_wait_s"] > 0.3, record
        assert record["queue_wait_s"] > record["compute_s"], record
        slow_pool.process_func = original_process

        # --- load-shed: bounded queue -> typed error -> client breaker ------
        shed_total = REGISTRY.get("hivemind_moe_shed_total")
        sheds_before = shed_total.labels("sobs.0_forward").value
        server.handler.chain_pool("forward", ["sobs.0"]).max_queue_size = 0  # shed everything
        for _ in range(2):  # EXPERT_BREAKERS failure_threshold == 2
            with pytest.raises(Exception, match="ServerOverloadedError"):
                expert0.forward_np(x)
        assert shed_total.labels("sobs.0_forward").value == sheds_before + 2
        assert "sobs.0" in EXPERT_BREAKERS, "sheds did not trip the expert breaker"
        card = SCORECARDS.card("sobs.0")
        assert card is not None and card["sheds"] >= 2
        server.handler.chain_pool("forward", ["sobs.0"]).max_queue_size = 1024
        shed_records = [r for r in SERVING_LEDGER.records() if r.get("error")]
        assert any(r["error"] == "ServerOverloadedError" for r in shed_records)

        # --- GET /serving ----------------------------------------------------
        exporter = MetricsExporter(port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/serving", timeout=5
            ).read()
        finally:
            exporter.shutdown()
        doc = json.loads(body)
        assert doc["summary"]["requests"] >= 4
        assert doc["summary"]["sheds"] >= 2
        assert "sobs.0" in doc["experts"]
        assert "sobs.0" in doc["scorecards"]
        assert doc["records"][0]["client"] == str(client_dht.peer_id)

        # --- hivemind-top --serving --frames 1 --no-ansi ---------------------
        from hivemind_tpu.hivemind_cli import run_top

        publisher = TelemetryPublisher(
            server.dht, "serving_test_telemetry", interval=60.0, start=False
        )
        assert publisher.publish_once()
        assert "serving" in publisher.last_published, publisher.last_published.keys()
        argv_before = sys.argv
        sys.argv = [
            "hivemind-top",
            "--initial_peers", *[str(m) for m in server.dht.get_visible_maddrs()],
            "--key", "serving_test_telemetry",
            "--frames", "1", "--no-ansi", "--serving",
        ]
        try:
            run_top.main()
        finally:
            sys.argv = argv_before
        out = capsys.readouterr().out
        assert "serving board" in out, out
        assert "sobs.0" in out, out
        assert "SHEDS" in out or "slowest requests" in out, out
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


# ------------------------------------------------------------- render fallback


def test_serving_board_renders_and_survives_malformed_snapshot():
    """Pure render: QPS delta column, saturation lines, malformed peer row."""
    from hivemind_tpu.hivemind_cli.run_top import render_serving_board

    now = time.time()
    records = {
        "peerA": {
            "serving": {
                "totals": {"requests": 120, "errors": 3, "sheds": 2},
                "experts": {
                    "lb.0": {"requests": 100, "p95_s": 0.04, "sheds": 2},
                    "lb.1": {"requests": 20, "p95_s": 0.01},
                },
                "saturation": {
                    "queue_depth": {"pool=lb.0_forward": 7},
                    "runtime_utilization": {"_": 0.93},
                    "decode_session_occupancy": {"_": 0.5},
                    "sheds": 2,
                },
                "scorecards": {
                    "far.9": {"requests": 10, "success_rate": 0.5, "timeouts": 3,
                              "sheds": 2, "failures": 0},
                },
                "slowest": [
                    {"expert": "lb.0", "kind": "forward", "client": "cliX",
                     "total_s": 0.31, "queue_wait_s": 0.28, "compute_s": 0.02},
                ],
            },
        },
        "peerEvil": {"serving": {"experts": "nope", "saturation": 3}},
        "peerWeird": {"serving": ["not", "a", "dict"]},  # present but unparseable
    }
    board, state = render_serving_board(records, now=now, ansi=False)
    assert "serving board" in board and "lb.0" in board
    assert "SHEDS 2" in board and "runtime util 93%" in board
    assert "decode sessions 50% full" in board
    assert "far.9" in board and "ok=50%" in board
    assert "queue_wai" in board or "queue" in board  # phase decomposition shown
    assert ("peerA", "lb.0") in state
    # second frame: QPS from the request-count delta (100 -> 150 over 10 s)
    records["peerA"]["serving"]["experts"]["lb.0"]["requests"] = 150
    board2, _ = render_serving_board(
        records,
        prev_requests={key: (value[0], value[1] - 10.0) for key, value in state.items()},
        now=now, ansi=False,
    )
    assert "5.0" in board2  # 50 requests over 10 s
    # the malformed peers get flagged rows, never a dead board — including the
    # non-dict section and a peer whose parse failed mid-way (whose partial
    # rows must be rolled back, not shown alongside the malformed flag)
    assert board.count("<malformed serving section>") == 2, board
    from hivemind_tpu.telemetry.serving import collect_swarm_serving

    data = collect_swarm_serving(records)
    assert sorted(data["malformed"]) == ["peerEvil", "peerWeird"]
    assert all(peer == "peerA" for peer, _uid, _stats in data["experts"])

    from hivemind_tpu.telemetry.monitor import SwarmMonitor, aggregate_swarm_view

    monitor = SwarmMonitor.__new__(SwarmMonitor)
    monitor.publish_interval = 30.0
    view = aggregate_swarm_view(
        {"peerA": {"time": now, "metrics": {}, **records["peerA"]}}
    )
    report = monitor.render_report(view)
    assert "serving board" in report and "lb.0" in report
    assert "slowest requests" in report


def test_shrink_prefers_serving_section_over_metric_label_detail():
    """Regression (seen in-suite): a label-bloated registry used to push the
    serving/ledger sections out of the DHT snapshot budget while full per-label
    metric series survived. The shrink now compacts metric families (totals
    preserved swarm-wide) BEFORE dropping attribution sections."""
    from hivemind_tpu.telemetry.monitor import _shrink_to_fit
    from hivemind_tpu.utils.serializer import MSGPackSerializer

    metrics = {
        f"hivemind_bloated_family_{i}": {
            "type": "counter",
            "series": {f"peer=verylongpeeridentifier-{j:04d}": float(j) for j in range(200)},
        }
        for i in range(12)
    }
    serving = {
        "totals": {"requests": 10, "errors": 0, "sheds": 1},
        "experts": {"lb.0": {"requests": 10, "p95_s": 0.05, "sheds": 1}},
    }
    snapshot = {"time": 0.0, "metrics": metrics, "serving": serving,
                "ledger": {"stragglers": {"peerX": {"rounds_slowest": 2, "excess_s": 0.5}}}}
    assert len(MSGPackSerializer.dumps(snapshot)) > 48 * 1024  # genuinely oversized
    shrunk = _shrink_to_fit(dict(snapshot))
    assert len(MSGPackSerializer.dumps(shrunk)) <= 48 * 1024
    assert shrunk["serving"]["experts"]["lb.0"]["sheds"] == 1
    assert shrunk["ledger"]["stragglers"]["peerX"]["rounds_slowest"] == 2
    # label detail paid the bill: families compacted to one aggregate series
    assert any(f.get("compacted") for f in shrunk["metrics"].values())
