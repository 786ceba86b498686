"""Distributed tracing (ISSUE 4): span propagation across a real two-peer
protobuf RPC, ring-buffer eviction, chaos events landing on the correct span,
the ``/trace`` endpoint round-tripping valid Chrome trace JSON, and the
end-to-end attribution demo (a chaos delay injected into one peer's DHT RPC is
visible in that peer's exported trace, under the caller's trace)."""

import asyncio
import json
import time
import urllib.request

import pytest

from hivemind_tpu.resilience import CHAOS, BreakerBoard
from hivemind_tpu.telemetry import (
    RECORDER,
    MetricsExporter,
    SpanRecorder,
    build_peer_snapshot,
    current_span,
    finish_span,
    render_chrome_trace,
    start_span,
    trace,
)
from hivemind_tpu.telemetry.tracing import pack_context, unpack_context


# ------------------------------------------------------------------ span core


def test_span_nesting_parent_child_and_events():
    RECORDER.clear()
    with trace("outer", peer="A") as outer:
        assert current_span() is outer
        outer.add_event("checkpoint", step=3)
        with trace("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
        assert current_span() is outer
    assert current_span() is None
    spans = {s.name: s for s in RECORDER.snapshot()}
    assert set(spans) == {"outer", "inner"}
    assert spans["outer"].end is not None and spans["outer"].duration >= 0
    assert [(n, a) for _t, n, a in spans["outer"].events] == [("checkpoint", {"step": 3})]


def test_detached_span_parents_to_current():
    RECORDER.clear()
    with trace("op") as op:
        detached = start_span("stream")
        assert current_span() is op, "start_span must not install"
        assert detached.parent_id == op.span_id and detached.trace_id == op.trace_id
        finish_span(detached)
    assert any(s.name == "stream" for s in RECORDER.snapshot())


def test_context_wire_format_roundtrip_and_malformed():
    span = start_span("x")
    ctx = unpack_context(pack_context(span))
    assert ctx == (span.trace_id, span.span_id)
    assert unpack_context(None) is None
    assert unpack_context(b"short") is None
    assert unpack_context(b"\x00" * 16) is None  # zero ids = no context
    assert pack_context(None) is None


def test_ring_buffer_evicts_oldest_at_capacity():
    recorder = SpanRecorder(capacity=8)
    for i in range(20):
        span = start_span(f"s{i}")
        finish_span(span, recorder)
    assert len(recorder) == 8
    assert recorder.dropped == 12
    names = [s.name for s in recorder.snapshot()]
    assert names == [f"s{i}" for i in range(12, 20)], "oldest must be evicted first"


def test_slow_span_side_ring_and_threshold():
    recorder = SpanRecorder(capacity=8)
    recorder.slow_threshold = 0.01
    fast = start_span("fast")
    finish_span(fast, recorder)
    slow = start_span("slow")
    slow.add_event("chaos.delay", point="dht.rpc_store")
    time.sleep(0.02)
    finish_span(slow, recorder)
    assert [s.name for s in recorder.slow_spans()] == ["slow"]
    assert "events" in recorder.slow_spans()[0].summary()


def test_tracing_disabled_is_noop():
    from hivemind_tpu.telemetry import tracing

    RECORDER.clear()
    tracing.enabled = False
    try:
        with trace("invisible") as span:
            assert span is None and current_span() is None
        assert start_span("also_invisible") is None
        finish_span(None)  # must not raise
    finally:
        tracing.enabled = True
    assert len(RECORDER) == 0


# ------------------------------------------------------------------ cross-peer


async def _two_peers():
    from hivemind_tpu.p2p import P2P

    alice = await P2P.create()
    bob = await P2P.create()
    for maddr in bob.get_visible_maddrs():
        alice.add_peer_addr(bob.peer_id, maddr.with_peer_id(bob.peer_id))
    return alice, bob


async def test_handler_span_joins_callers_trace_over_real_rpc():
    RECORDER.clear()
    alice, bob = await _two_peers()

    async def handler(request: bytes, context) -> bytes:
        return b"ack:" + request

    await bob.add_protobuf_handler("trace.echo", handler)
    try:
        with trace("client.op", peer=str(alice.peer_id)) as root:
            response = await alice.call_protobuf_handler(bob.peer_id, "trace.echo", b"ping")
        assert response == b"ack:ping"
    finally:
        await alice.shutdown()
        await bob.shutdown()

    spans = {s.name: s for s in RECORDER.snapshot()}
    call = spans["p2p.call:trace.echo"]
    handle = spans["p2p.handle:trace.echo"]
    assert call.trace_id == root.trace_id and call.parent_id == root.span_id
    # the server-side handler span is a CHILD of the remote caller's span:
    # trace context crossed the wire on the OPEN frame
    assert handle.trace_id == root.trace_id
    assert handle.parent_id == call.span_id
    assert handle.attributes["peer"] == str(bob.peer_id)
    assert handle.attributes["remote"] == str(alice.peer_id)


async def test_streaming_rpc_span_propagates_context():
    RECORDER.clear()
    alice, bob = await _two_peers()

    async def handler(requests, context):
        async for message in requests:
            yield b"echo:" + message

    await bob.add_protobuf_handler("trace.stream", handler, stream_input=True, stream_output=True)
    try:
        with trace("client.stream_op", peer=str(alice.peer_id)) as root:
            async def _requests():
                yield b"a"
                yield b"b"

            received = [
                message
                async for message in alice.iterate_protobuf_handler(
                    bob.peer_id, "trace.stream", _requests()
                )
            ]
        assert received == [b"echo:a", b"echo:b"]
    finally:
        await alice.shutdown()
        await bob.shutdown()

    spans = {s.name: s for s in RECORDER.snapshot()}
    stream_span = spans["p2p.stream:trace.stream"]
    handle = spans["p2p.handle:trace.stream"]
    assert stream_span.trace_id == root.trace_id and stream_span.parent_id == root.span_id
    assert handle.trace_id == root.trace_id and handle.parent_id == stream_span.span_id


async def test_chaos_injection_lands_on_the_injected_call_span():
    RECORDER.clear()
    alice, bob = await _two_peers()

    async def handler(request: bytes, context) -> bytes:
        return request

    await bob.add_protobuf_handler("trace.chaos", handler)
    CHAOS.clear()
    CHAOS.add_rule("p2p.unary.send", "delay", delay=0.01, scope=str(alice.peer_id))
    try:
        with trace("client.chaos_op", peer=str(alice.peer_id)):
            await alice.call_protobuf_handler(bob.peer_id, "trace.chaos", b"x")
    finally:
        CHAOS.clear()
        await alice.shutdown()
        await bob.shutdown()

    spans = {s.name: s for s in RECORDER.snapshot()}
    call = spans["p2p.call:trace.chaos"]
    events = [(name, attrs) for _t, name, attrs in call.events or ()]
    assert ("chaos.delay", {"point": "p2p.unary.send"}) in events
    # the fault hit the CALL span, not its parent or the server handler
    assert not spans["client.chaos_op"].events
    assert not spans["p2p.handle:trace.chaos"].events


# ------------------------------------------------------------------ export


def _validate_chrome_trace(doc):
    """A structurally valid Chrome trace-event file (the subset Perfetto and
    chrome://tracing require to load it)."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    assert doc["traceEvents"], "trace must not be empty"
    for event in doc["traceEvents"]:
        assert isinstance(event["name"], str)
        assert event["ph"] in ("X", "i", "M")
        assert isinstance(event["pid"], int) and isinstance(event["tid"], int)
        if event["ph"] == "X":
            assert isinstance(event["ts"], (int, float)) and isinstance(event["dur"], (int, float))
            assert event["dur"] >= 0
        elif event["ph"] == "i":
            assert isinstance(event["ts"], (int, float))
    return doc


def test_render_chrome_trace_pid_per_peer_and_instants():
    RECORDER.clear()
    with trace("op_a", peer="peerA") as span_a:
        span_a.add_event("chaos.drop", point="dht.rpc_find")
    with trace("op_b", peer="peerB"):
        pass
    doc = _validate_chrome_trace(render_chrome_trace(RECORDER.snapshot()))
    process_names = {
        event["args"]["name"]: event["pid"]
        for event in doc["traceEvents"]
        if event["ph"] == "M" and event["name"] == "process_name"
    }
    assert set(process_names) == {"peer peerA", "peer peerB"}
    by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert by_name["op_a"]["pid"] != by_name["op_b"]["pid"], "one row per peer"
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["chaos.drop"]
    assert instants[0]["pid"] == by_name["op_a"]["pid"]
    # span args carry ids so parentage is greppable from the JSON alone
    assert by_name["op_a"]["args"]["trace_id"] == f"{span_a.trace_id:016x}"


def test_trace_endpoint_roundtrips_valid_chrome_trace_json():
    RECORDER.clear()
    with trace("http.visible", peer="exporter-test"):
        pass
    exporter = MetricsExporter(port=0)
    try:
        body = urllib.request.urlopen(f"http://127.0.0.1:{exporter.port}/trace", timeout=5).read()
    finally:
        exporter.shutdown()
    doc = _validate_chrome_trace(json.loads(body))
    assert any(e["name"] == "http.visible" for e in doc["traceEvents"])


# ------------------------------------------------------------------ acceptance


def test_e2e_chaos_delay_attribution_across_swarm():
    """ISSUE 4 acceptance: HIVEMIND_CHAOS-style rule injects a delay into one
    DHT RPC on ONE peer of a multi-peer swarm; the exported /trace JSON
    contains a span on that peer, under the caller's trace, carrying the chaos
    event — and the JSON is a valid Chrome trace-event file."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.utils.timed_storage import get_dht_time

    first = DHT(start=True)
    maddrs = [str(m) for m in first.get_visible_maddrs()]
    second = DHT(initial_peers=maddrs, start=True)
    third = DHT(initial_peers=maddrs, start=True)
    victim = str(second.peer_id)
    RECORDER.clear()
    CHAOS.configure(f"dht.rpc_store:delay:delay=0.05:scope={victim}")
    exporter = MetricsExporter(port=0)
    try:
        assert second.store("e2e_key", "e2e_value", expiration_time=get_dht_time() + 60)
        CHAOS.clear()
        assert first.get("e2e_key").value == "e2e_value"
        body = urllib.request.urlopen(f"http://127.0.0.1:{exporter.port}/trace", timeout=5).read()
    finally:
        CHAOS.clear()
        exporter.shutdown()
        for dht in (first, second, third):
            dht.shutdown()

    doc = _validate_chrome_trace(json.loads(body))
    events = doc["traceEvents"]
    # 1) the injected delay is visible as an instant event in the trace
    chaos_instants = [e for e in events if e["ph"] == "i" and e["name"] == "chaos.delay"]
    assert chaos_instants, "injected fault must appear in the exported trace"
    owner_span_id = chaos_instants[0]["args"]["span_id"]
    # 2) it sits on the victim peer's dht.store span...
    spans = {e["args"]["span_id"]: e for e in events if e["ph"] == "X"}
    owner = spans[owner_span_id]
    assert owner["name"] == "dht.store" and owner["args"]["peer"] == victim
    # 3) ...whose trace also contains the cross-peer handler span (the caller's
    # trace reached the remote peer through the RPC envelope)
    trace_id = owner["args"]["trace_id"]
    same_trace = [e for e in spans.values() if e["args"]["trace_id"] == trace_id]
    names = {e["name"] for e in same_trace}
    assert "p2p.call:DHTProtocol.rpc_store" in names
    assert "p2p.handle:DHTProtocol.rpc_store" in names
    handle = next(e for e in same_trace if e["name"] == "p2p.handle:DHTProtocol.rpc_store")
    call = next(e for e in same_trace if e["name"] == "p2p.call:DHTProtocol.rpc_store")
    assert handle["args"]["parent_id"] == call["args"]["span_id"]
    # 4) the victim's pid row differs from the remote store target's row
    assert handle["pid"] != owner["pid"]


# ------------------------------------------------------------------ monitor


def test_peer_snapshot_carries_breakers_and_slow_spans():
    RECORDER.clear()
    RECORDER.slow_threshold = 0.005
    board = BreakerBoard("snapshot_test_board", failure_threshold=1, recovery_time=60.0)
    board.register_failure("bad-peer")
    with trace("sluggish.op", peer="me"):
        time.sleep(0.01)
    snapshot = build_peer_snapshot()
    assert snapshot["breakers"]["snapshot_test_board"]["tripped"] == ["bad-peer"]
    assert any(s["name"] == "sluggish.op" for s in snapshot["slow_spans"])
    assert any(s["name"] == "sluggish.op" for s in snapshot["recent_spans"])

    from hivemind_tpu.telemetry.monitor import SwarmMonitor, aggregate_swarm_view

    monitor = SwarmMonitor.__new__(SwarmMonitor)  # no DHT needed for rendering
    snapshot["peer_id"] = "deadbeef"
    view = aggregate_swarm_view({"deadbeef": snapshot})
    report = monitor.render_report(view)
    assert "DEGRADED" in report and "snapshot_test_board" in report and "sluggish.op" in report
    timeline = monitor.render_timeline({"deadbeef": snapshot})
    assert "sluggish.op" in timeline and "trace " in timeline
    board.clear()


def test_unified_trace_span_emits_telemetry_span():
    from hivemind_tpu.telemetry.tracing import trace_sync

    RECORDER.clear()
    with trace_sync("unified.step", step=7):
        assert current_span() is not None and current_span().name == "unified.step"
    recorded = [s for s in RECORDER.snapshot() if s.name == "unified.step"]
    assert recorded and recorded[0].attributes["step"] == 7
