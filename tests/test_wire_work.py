"""The round and the request from inside (ISSUE 37): work spans where the wire's bytes are
worked on (`wire.encode` / `wire.decode` on executor threads, `wire.seal` / `wire.open` as
annotations on the AEAD pool, `allreduce.reduce` on the loop, `averager.load` /
`averager.collect`), the counters at the same boundaries, and the round record's
`encode_s` / `decode_s` / `reduce_s` / `loop_cpu_s`.

One profiler session and one real two-peer round serve the module; everything else is
scripted or runs on objects built without a network."""

import asyncio
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from hivemind_tpu.averaging import DecentralizedAverager
from hivemind_tpu.p2p.crypto_channel import _OFFLOAD_THRESHOLD, SecureChannel
from hivemind_tpu.telemetry import LEDGER, RECORDER, REGISTRY, tracing
from hivemind_tpu.telemetry.ledger import _MAX_PENDING_ROUNDS, RoundLedger
from hivemind_tpu.telemetry.tracing import (
    add_span_listener,
    finish_span,
    remove_span_listener,
    start_span,
    trace_work,
)
from hivemind_tpu.telemetry.wire import WORK_SPAN_BYTES, count_work, wire_work

from swarm_utils import launch_dht_swarm, shutdown_all

ROOT = Path(__file__).resolve().parents[1]
WORK_SPANS = ("wire.encode", "wire.decode", "allreduce.reduce")


class _Grads(DecentralizedAverager):
    round_purpose = "grads"


class _State(DecentralizedAverager):
    round_purpose = "state"


def _wire(kind, phase):
    metric = REGISTRY.get(f"hivemind_wire_{kind}_total")
    return metric.labels(phase=phase).value


def _channel_pair():
    """Two ends' ciphers without a socket: what one seals the other opens."""
    keys = (bytes(range(32)), bytes(range(32, 64)))
    return (SecureChannel(None, None, keys[0], keys[1], None), SecureChannel(None, None, keys[1], keys[0], None))


def _nonce(counter=0):
    import struct

    return struct.pack("<4xQ", counter)


# ------------------------------------------------------------------ one real round, traced


@pytest.fixture(scope="module")
def round_capture(tmp_path_factory):
    """Two peers, each a "grads" and a "state" averager on one DHT node, all four stepping
    at once inside one profiler session: the spans, the records as the listeners got them,
    and the capture's `hivemind:` events by thread line."""
    sys.path.insert(0, str(ROOT))
    from perf.trace_reduce import find_xplane

    out = SimpleNamespace(spans=[], records=[])
    on_record = lambda kind, record: out.records.append(record) if kind == "round" else None  # noqa: E731
    dhts = launch_dht_swarm(2)
    averagers = []
    for index, dht in enumerate(dhts):
        for kind, prefix, numel in ((_Grads, "wiregrads", 600_000), (_State, "wirestate", 400_000)):
            tensors = [np.full(numel, float(index), np.float32), np.full(numel // 2, float(index), np.float32)]
            averagers.append(kind(tensors, dht, prefix=prefix, start=True, target_group_size=2,
                                  min_matchmaking_time=1.0, request_timeout=1.0))
    out.peers = [str(dht.peer_id) for dht in dhts]
    phase_sum = REGISTRY.get("hivemind_averaging_allreduce_phase_seconds").labels(phase="total")
    logdir = tmp_path_factory.mktemp("xplane")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    add_span_listener(out.spans.append)
    LEDGER.add_record_listener(on_record)
    out.recorder_before, out.phase_total_before = len(RECORDER), phase_sum.sum
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        controls = [averager.step(wait=False, timeout=30) for averager in averagers]
        for control in controls:
            control.result(timeout=60)
        for averager in averagers:
            with averager.get_tensors() as tensors:
                assert np.allclose(tensors[0], 0.5)
        time.sleep(0.3)  # the exchange that outlives its round, and work that outlives it
    finally:
        jax.profiler.stop_trace()
        remove_span_listener(out.spans.append)
        LEDGER.remove_record_listener(on_record)
        out.phase_total = phase_sum.sum - out.phase_total_before
        shutdown_all(averagers, dhts)
    path = find_xplane(str(logdir))
    assert path is not None, "the profiler wrote no .xplane.pb"
    # thread by thread: `load_planes` merges the lines that share a name, and every Python thread's does
    from jax.profiler import ProfileData

    out.lines = {(plane.name, index): [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                                       if e.name.startswith("hivemind:")]
                 for plane in ProfileData.from_file(path).planes if not plane.name.startswith("/device")
                 for index, line in enumerate(plane.lines)}
    return out


def _first_records(capture):
    """A round's record as the listeners FIRST got it (a late exchange sends it again)."""
    first = {}
    for record in capture.records:
        first.setdefault((record["peer"], record["round"]), record)
    return list(first.values())


def test_round_record_says_what_the_round_was_made_of(round_capture):
    records = _first_records(round_capture)
    assert {(r["peer"], r["purpose"]) for r in records} == {(p, k) for p in round_capture.peers for k in ("grads", "state")}
    for record in records:
        for field in ("encode_s", "decode_s", "reduce_s"):
            assert record[field] > 0, (field, record)  # on the record when the listeners fire
        assert 0 <= record["loop_cpu_s"] <= record["total_s"] + 1e-3, record


def test_work_spans_are_children_of_their_round_from_executor_threads(round_capture):
    rounds = {s.span_id: s for s in round_capture.spans if s.name == "allreduce.round"}
    assert len(rounds) == 4
    for name in WORK_SPANS:
        spans = [s for s in round_capture.spans if s.name == name]
        assert spans and all(s.parent_id in rounds for s in spans), name
        for span in spans:
            owner = rounds[span.parent_id]
            assert span.attributes["purpose"] == owner.attributes["purpose"] and span.attributes["bytes"] >= WORK_SPAN_BYTES
            assert span.attributes["peer"] == owner.attributes["peer"]  # how the ledger knows a round's work
            # the codec runs on an executor thread, the reducer's numpy on the loop itself
            assert (span.thread_id == owner.thread_id) == (name == "allreduce.reduce"), (name, span.thread_id)
    # the record's seconds are the children's, summed by parent
    for record in _first_records(round_capture):
        [owner] = [s for s in rounds.values() if s.attributes["peer"] == record["peer"]
                   and s.attributes["purpose"] == record["purpose"]]
        total = sum(s.duration for s in round_capture.spans if s.name == "wire.encode" and s.parent_id == owner.span_id)
        assert record["encode_s"] <= total + 1e-5  # later copies of the record hold what ended after it closed


def test_each_averager_of_a_peer_gets_its_own_matchmaking_wait(round_capture):
    """A peer's "grads" and "state" averagers matchmake at once (since PR 36 every epoch):
    each round's record carries the wait of its own averager's span."""
    waits = {(s.attributes["peer"], s.attributes["purpose"]): round(s.duration, 6)
             for s in round_capture.spans if s.name == "averaging.matchmaking"}
    assert len(waits) == 4
    for record in _first_records(round_capture):
        assert record["matchmaking_wait_s"] == waits[(record["peer"], record["purpose"])], record


@pytest.mark.parametrize("first_closed", ["grads", "state"])
def test_matchmaking_wait_is_paired_by_peer_and_purpose(first_closed):
    """Scripted, and failing on the tree before: both averagers' matchmaking ends before
    either round closes, so a table keyed by peer alone holds one wait for two rounds."""
    ledger = RoundLedger()
    lengths = {"grads": 0.05, "state": 0.3}
    for purpose, seconds in lengths.items():
        span = start_span("averaging.matchmaking", peer="me", purpose=purpose)
        span.start -= seconds
        span.set("outcome", "assembled")
        finish_span(span)
        ledger.on_span(span)
    for purpose in (first_closed, "state" if first_closed == "grads" else "grads"):
        round_span = start_span("allreduce.round", peer="me", group_size=2, rank=0, purpose=purpose)
        finish_span(round_span)
        ledger.on_span(round_span)
    for record in ledger.records():
        assert record["matchmaking_wait_s"] == pytest.approx(lengths[record["purpose"]], abs=5e-3), record


def test_collect_and_load_spans_carry_bytes_and_purpose(round_capture):
    rounds = {s.span_id for s in round_capture.spans if s.name == "allreduce.round"}
    collects = [s for s in round_capture.spans if s.name == "averager.collect"]
    assert len(collects) == 8 and all(s.parent_id in rounds for s in collects)  # two tensors an averager
    assert {s.attributes["purpose"] for s in collects} == {"grads", "state"}
    assert sorted({s.attributes["bytes"] for s in collects}) == [800_000, 1_200_000, 1_600_000, 2_400_000]


def test_phase_histogram_and_round_span_are_one_pair_of_clock_reads(round_capture):
    rounds = [s for s in round_capture.spans if s.name == "allreduce.round"]
    assert round_capture.phase_total == pytest.approx(sum(s.duration for s in rounds), abs=1e-9)


def test_capture_holds_the_work_annotations_properly_nested(round_capture):
    """`hivemind:wire.encode.<purpose>` from an executor thread, nested inside nothing it
    does not end within; a work span says whose it is in the annotation's NAME, where a
    trace reader can see it; seal and open (a frame knows no purpose) are annotations
    too, though they open no Span."""
    purposes = ("grads", "state")
    found = {name: 0 for name in [f"{work}.{purpose}" for work in WORK_SPANS + ("averager.collect",) for purpose in purposes]
             + ["wire.seal", "wire.open"]}
    loop_lines = {key for key, events in round_capture.lines.items()
                  if any(n.startswith("hivemind:allreduce.reduce.") for n, _s, _e in events)}
    for key, events in round_capture.lines.items():
        for name, start, end in events:
            short = name[len("hivemind:"):]
            assert short not in WORK_SPANS, f"{short} without its averager's purpose"
            if short not in found:
                continue
            found[short] += 1
            if not short.startswith(("allreduce.reduce", "averager.collect")):
                assert key not in loop_lines, f"{short} ran on the event loop's thread"
            for other, other_start, other_end in events:  # same thread: disjoint or properly nested
                if (other, other_start, other_end) != (name, start, end) and other_start <= start < other_end:
                    assert end <= other_end, (name, other)
    assert all(found.values()), found
    assert not any(s.name in ("wire.seal", "wire.open") for s in round_capture.spans)


# ------------------------------------------------------------------ what is frequent stays light


def test_small_frame_moves_the_counters_and_leaves_the_recorder_alone():
    sender, receiver = _channel_pair()
    frame = b"t" * 8192  # a decode token
    before = (len(RECORDER), _wire("seconds", "seal"), _wire("bytes", "seal"), _wire("seconds", "open"), _wire("bytes", "open"))
    sealed = sender._seal(_nonce(), frame, (), len(frame))
    assert receiver._open(_nonce(), sealed) == frame
    after = (len(RECORDER), _wire("seconds", "seal"), _wire("bytes", "seal"), _wire("seconds", "open"), _wire("bytes", "open"))
    assert after[0] == before[0]
    assert after[1] > before[1] and after[3] > before[3]
    assert after[2] - before[2] == len(frame) and after[4] - before[4] == len(sealed)


def test_large_frame_is_annotated_and_still_opens_no_span(monkeypatch):
    calls = []

    class Annotation:
        def __init__(self, name):
            calls.append(name)

        def __enter__(self):
            calls.append("enter")

        def __exit__(self, *exc):
            calls.append("exit")

    monkeypatch.setitem(sys.modules, "jax", SimpleNamespace(profiler=SimpleNamespace(TraceAnnotation=Annotation)))
    sender, receiver = _channel_pair()
    header, body = b"h" * 9, b"b" * _OFFLOAD_THRESHOLD
    recorded = len(RECORDER)
    sealed = sender._seal(_nonce(), header, (body,), len(header) + len(body))
    assert receiver._open(_nonce(), sealed) == header + body
    assert calls == ["hivemind:wire.seal", "enter", "exit", "hivemind:wire.open", "enter", "exit"]
    assert len(RECORDER) == recorded
    assert WORK_SPAN_BYTES == _OFFLOAD_THRESHOLD  # one size at which work stops being small


def test_wire_work_counts_small_work_and_spans_large_work():
    seen = []
    add_span_listener(seen.append)
    try:
        before = (_wire("seconds", "decode"), _wire("bytes", "decode"))
        with wire_work("decode", 100, purpose="grads") as nothing:
            pass
        assert (_wire("bytes", "decode") - before[1], seen, nothing) == (100, [], None)
        with wire_work("decode", WORK_SPAN_BYTES, purpose="grads") as span:
            pass
        assert [(s.name, s.attributes) for s in seen] == [("wire.decode", {"bytes": WORK_SPAN_BYTES, "purpose": "grads"})]
        assert seen == [span]
        assert _wire("seconds", "decode") > before[0] and _wire("bytes", "decode") - before[1] == 100 + WORK_SPAN_BYTES
    finally:
        remove_span_listener(seen.append)


def test_count_work_takes_seconds_already_measured():
    before = (_wire("seconds", "encode"), _wire("bytes", "encode"))
    count_work("encode", 0.25, 1000)
    assert (_wire("seconds", "encode") - before[0], _wire("bytes", "encode") - before[1]) == (pytest.approx(0.25), 1000)


def test_handlers_inline_codec_calls_feed_the_counters_from_the_handlers_own_clock():
    """A decode token's request and response: the seconds the handler measures for its
    serving ledger ARE the wire counter's (no second pair of clock reads on the loop
    thread), and no span is built."""
    from hivemind_tpu.compression import NoCompression, serialize_tensor
    from hivemind_tpu.moe.server.connection_handler import ConnectionHandler

    token = serialize_tensor(np.ones((1, 1, 2048), np.float32))
    handler = SimpleNamespace(activation_codec=NoCompression())
    handler._serialize_outputs = lambda outputs: ConnectionHandler._serialize_outputs(handler, outputs)
    before = {phase: (_wire("seconds", phase), _wire("bytes", phase)) for phase in ("decode", "encode")}
    recorded = len(RECORDER)

    async def scenario():
        arrays, seconds = await ConnectionHandler._deserialize_request(handler, [token])
        await ConnectionHandler._respond(handler, arrays)
        return arrays, seconds

    [array], seconds = asyncio.new_event_loop().run_until_complete(scenario())
    assert array.shape == (1, 1, 2048) and len(RECORDER) == recorded
    assert _wire("seconds", "decode") - before["decode"][0] == pytest.approx(seconds, abs=1e-12)
    assert _wire("bytes", "decode") - before["decode"][1] == len(token.buffer)
    assert _wire("seconds", "encode") > before["encode"][0] and _wire("bytes", "encode") - before["encode"][1] == array.nbytes


def test_a_sync_span_with_a_purpose_says_so_in_its_annotation(monkeypatch):
    names = []

    class Annotation:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            pass

    monkeypatch.setitem(sys.modules, "jax", SimpleNamespace(profiler=SimpleNamespace(TraceAnnotation=Annotation)))
    with tracing.trace_sync("averager.load", purpose="grads") as span:
        pass
    with tracing.trace_sync("optimizer.update"):
        pass
    assert names == ["hivemind:averager.load.grads", "hivemind:optimizer.update"]
    assert span.name == "averager.load"  # the span, the recorder and the ledgers keep the plain name


def test_trace_work_levels(monkeypatch):
    seen = []
    sink = lambda seconds, nbytes: seen.append((seconds, nbytes))  # noqa: E731
    with trace_work("twork.span", 7, sink, step=3) as span:
        time.sleep(0.01)
    assert span.name == "twork.span" and span.attributes == {"bytes": 7, "step": 3}
    assert seen == [(span.end - span.start, 7)]  # the span's own length, not a second pair of reads
    recorded = len(RECORDER)
    with trace_work("twork.annotation", 8, sink, trace_work.ANNOTATION) as nothing:
        pass
    with trace_work("twork.sink", 9, sink, trace_work.COUNT) as nothing_either:
        pass
    assert nothing is None and nothing_either is None and len(RECORDER) == recorded
    assert [nbytes for _s, nbytes in seen] == [7, 8, 9] and all(seconds >= 0 for seconds, _n in seen)
    with pytest.raises(KeyError):
        with trace_work("twork.raises", 1, sink):
            raise KeyError("x")
    assert seen[-1][1] == 1  # the sink hears of work that raised too


def test_with_tracing_off_no_new_site_allocates_a_span(monkeypatch):
    """HIVEMIND_TRACE=0: the counters go on counting; no Span is built at any new site, in
    a real round (codec on executors, reducer on the loop, collect, the channel's frames)
    nor in the server's codec calls."""
    from hivemind_tpu.compression import NoCompression, serialize_tensor
    from hivemind_tpu.moe.server.connection_handler import ConnectionHandler

    built = []
    plain_init = tracing.Span.__init__

    def counting_init(self, name, *args, **kwargs):
        built.append(name)
        plain_init(self, name, *args, **kwargs)

    monkeypatch.setattr(tracing, "enabled", False)
    monkeypatch.setattr(tracing.Span, "__init__", counting_init)
    before = {phase: _wire("seconds", phase) for phase in ("encode", "decode", "seal", "open", "reduce")}
    dhts = launch_dht_swarm(2)
    averagers = [_Grads([np.full(300_000, float(i), np.float32)], dht, prefix="wireoff", start=True,
                        target_group_size=2, min_matchmaking_time=1.0, request_timeout=1.0) for i, dht in enumerate(dhts)]
    try:
        for control in [averager.step(wait=False, timeout=30) for averager in averagers]:
            control.result(timeout=60)
        with averagers[0].get_tensors() as tensors:
            assert np.allclose(tensors[0], 0.5)
    finally:
        shutdown_all(averagers, dhts)
    big = np.ones((64, 1024), np.float32)
    handler = SimpleNamespace(activation_codec=NoCompression())
    handler._serialize_outputs = lambda outputs: ConnectionHandler._serialize_outputs(handler, outputs)
    serialized = ConnectionHandler._serialize_traced(handler, [big], big.nbytes)
    [back] = ConnectionHandler._deserialize_off_loop(serialized, sum(len(t.buffer) for t in serialized))
    assert np.array_equal(back, big) and serialize_tensor(big).buffer == serialized[0].buffer
    assert built == []
    for phase, value in before.items():
        assert _wire("seconds", phase) > value, phase


# ------------------------------------------------------------------ the counters that await


def test_send_wait_counts_the_wait_for_the_writers_credit():
    """More frames than the channel keeps in flight, behind a writer that drains slowly:
    the senders past the sixteenth wait, and the counter holds their seconds."""
    written = []

    class SlowWriter:
        def write(self, data):
            written.append(len(data))

        async def drain(self):
            await asyncio.sleep(0.01)

        def close(self):
            pass

    async def scenario():
        channel = SecureChannel(None, SlowWriter(), bytes(32), bytes(range(32)), None)
        before = _wire("seconds", "send_wait")
        await channel.send(b"x" * 100)  # credit in hand: no wait, no clock read
        assert _wire("seconds", "send_wait") == before
        await asyncio.gather(*(channel.send(b"y" * 1000) for _ in range(40)))
        waited = _wire("seconds", "send_wait") - before
        channel.close()
        return waited

    waited = asyncio.new_event_loop().run_until_complete(scenario())
    assert waited >= 0.01, waited
    assert len(written) >= 25


def test_runtime_divides_the_time_between_two_batches():
    """`wait`: no pool holds a task. `handover`: a pool holds one, the executor is free,
    the batch is not handed over yet. Together they are the time between two batches."""
    import optax

    from hivemind_tpu.moe import ModuleBackend
    from hivemind_tpu.moe.server.connection_handler import ConnectionHandler
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert
    from hivemind_tpu.moe.server.runtime import Runtime

    def counted(name):
        return REGISTRY.get(name).labels().value

    async def scenario():
        uid = "wirework.0"
        backend = ModuleBackend(uid, CausalTransformerExpert(hidden_dim=16, num_heads=4), optimizer=optax.sgd(1e-3),
                                sample_input=np.zeros((1, 4, 16), np.float32), max_batch_size=2)
        handler = ConnectionHandler({uid: backend}, activation_compression="none")
        runtime = Runtime(handler.all_pools(), stats_report_interval=None)
        handler.on_new_pool = runtime.add_pool
        runtime.start()
        try:
            x = np.zeros((2, 4, 16), np.float32)
            await handler.chain_pool("forward", [uid]).submit_task(x)  # compiles; not counted below
            before = {name: counted(name) for name in (WAIT, HANDOVER)}
            began = time.perf_counter()
            await asyncio.sleep(0.05)  # starved: no pool holds a task
            pool = handler.chain_pool("forward", [uid])
            await asyncio.gather(*(pool.submit_task(x) for _ in range(3)))  # three batches of two rows, back to back
            elapsed = time.perf_counter() - began
            return {name: counted(name) - before[name] for name in before}, elapsed
        finally:
            runtime.shutdown()
            await asyncio.sleep(0)

    WAIT, HANDOVER = "hivemind_moe_runtime_wait_seconds_total", "hivemind_moe_runtime_handover_seconds_total"
    moved, elapsed = asyncio.new_event_loop().run_until_complete(scenario())
    assert moved[WAIT] >= 0.04  # until the first task arrived, and no longer
    assert 0 < moved[HANDOVER] < elapsed - moved[WAIT]  # three hand-overs, none of them the starvation
    assert moved[WAIT] + moved[HANDOVER] <= elapsed


# ------------------------------------------------------------------ the ledger's bookkeeping


def _work_span(parent, name, seconds, **attributes):
    span = start_span(name, parent=parent, bytes=WORK_SPAN_BYTES, **attributes)
    span.start -= seconds
    finish_span(span)
    return span


def test_work_that_outlives_its_round_lands_on_the_live_record():
    ledger = RoundLedger()
    heard = []
    ledger.add_record_listener(lambda kind, record: heard.append(record))
    round_span = start_span("allreduce.round", peer="me", group_size=2, rank=0, purpose="grads")
    round_span.set("loop_cpu_s", 0.002)
    for name, seconds in (("wire.encode", 0.02), ("wire.encode", 0.03), ("wire.decode", 0.01), ("allreduce.reduce", 0.005)):
        ledger.on_span(_work_span(round_span, name, seconds, peer="me"))
    round_span.start -= 0.1
    finish_span(round_span)
    ledger.on_span(round_span)
    [record] = heard
    assert record["encode_s"] == pytest.approx(0.05, abs=2e-3) and record["decode_s"] == pytest.approx(0.01, abs=2e-3)
    assert record["reduce_s"] == pytest.approx(0.005, abs=2e-3) and record["loop_cpu_s"] == 0.002
    ledger.on_span(_work_span(round_span, "wire.encode", 0.04, peer="me"))  # a delta still being encoded for the partner
    assert len(heard) == 1  # no copy goes out for it alone
    assert ledger.records()[0]["encode_s"] == pytest.approx(0.09, abs=3e-3)
    assert ledger.summary()["encode_s"]["mean"] == pytest.approx(0.09, abs=3e-3)


def test_work_under_a_parent_that_is_no_round_is_not_kept_and_evicts_no_round():
    """A client's call that decodes a streamed response is a child of the call's span and
    names no peer: the ledger does not keep it, so a process that both calls and averages
    loses no open round's sums to it."""
    ledger = RoundLedger()
    round_span = start_span("allreduce.round", peer="me", group_size=2, rank=0, purpose="grads")
    ledger.on_span(_work_span(round_span, "wire.encode", 0.02, peer="me"))
    for _ in range(3 * _MAX_PENDING_ROUNDS):
        ledger.on_span(_work_span(start_span("moe.call"), "wire.decode", 0.001))
    assert list(ledger._pending_work) == [round_span.span_id]
    finish_span(round_span)
    ledger.on_span(round_span)
    assert ledger.records()[0]["encode_s"] == pytest.approx(0.02, abs=2e-3) and not ledger._pending_work
    # rounds that never close are bounded too
    for _ in range(3 * _MAX_PENDING_ROUNDS):
        ledger.on_span(_work_span(start_span("allreduce.round"), "wire.decode", 0.001, peer="me"))
    assert len(ledger._pending_work) <= _MAX_PENDING_ROUNDS
    ledger.clear()
    assert not ledger._pending_work
