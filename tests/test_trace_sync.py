"""The program's own spans on the device trace's clock (ISSUE 24): `trace_sync`
writes a sync span into a profiler capture's host plane as `hivemind:<name>`; the
decode path, the pool path and the epoch transition open such spans where the work
happens and deliver the same time as ledger fields and counters.

One profiler session serves the whole module (Python tracer off, as
`perf/runtime.Tracer` has it): every scenario runs inside it, the tests read the
capture. No test needs a live swarm round."""

import asyncio
import subprocess
import sys
import time
import uuid
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import optax
import pytest

import jax

from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.telemetry import tracing
from hivemind_tpu.telemetry.ledger import LEDGER, RoundLedger
from hivemind_tpu.telemetry.serving import SERVING_LEDGER, SERVING_SPAN
from hivemind_tpu.telemetry.tracing import Span, add_span_listener, remove_span_listener, trace, trace_sync

ROOT = Path(__file__).resolve().parents[1]
HID = 16


def _backends(*uids):
    from hivemind_tpu.moe import ModuleBackend
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    return {uid: ModuleBackend(uid, CausalTransformerExpert(hidden_dim=HID, num_heads=4), optimizer=optax.sgd(1e-3),
                               sample_input=np.zeros((1, 4, HID), np.float32), max_batch_size=8) for uid in uids}


def _counter(name, *labels):
    return REGISTRY.get(name).labels(*labels).value


async def _crossing_an_await():
    with trace("tsync.crosses_await"):
        await asyncio.sleep(0.01)


async def _decode_two_sessions(uid, client):
    """Two sessions prefilled, then one concurrent token each: a vmapped batch of two."""
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

    manager = DecodeSessionManager(_backends(uid), max_len=32, max_sessions=8)
    rng = np.random.RandomState(0)
    for name in ("a", "b"):
        manager.decode(uid, name, rng.randn(1, 3, HID).astype(np.float32), reset=True)
    token = rng.randn(1, 1, HID).astype(np.float32)

    async def one_token(name):
        with trace(SERVING_SPAN, kind="decode", expert=uid, client=client, peer="srv", batch=1):
            return await manager.decode_async(uid, name, token, False)

    outs = await asyncio.gather(one_token("a"), one_token("b"))
    assert all(out.shape == (1, 1, HID) for out in outs)


async def _forward_through_the_handler(uid, next_uid, client):
    """One rpc_forward for the span of two blocks through ConnectionHandler -> TaskPool ->
    Runtime -> ModuleBackend, then one streamed request for the first block alone; no network."""
    from hivemind_tpu.compression import serialize_tensor
    from hivemind_tpu.moe.server.connection_handler import ConnectionHandler
    from hivemind_tpu.moe.server.runtime import Runtime
    from hivemind_tpu.proto import runtime_pb2
    from hivemind_tpu.utils.serializer import MSGPackSerializer

    handler = ConnectionHandler(_backends(uid, next_uid), activation_compression="none")
    runtime = Runtime(handler.all_pools(), stats_report_interval=None)
    handler.on_new_pool = runtime.add_pool  # the span's pool is made on its first request
    runtime.start()
    try:
        await asyncio.sleep(0.05)  # the drain loop finds its pools empty and starts to wait
        x = np.random.RandomState(1).randn(3, 4, HID).astype(np.float32)
        request = runtime_pb2.ExpertRequest(uid=uid, tensors=[serialize_tensor(x)],
                                            metadata=MSGPackSerializer.dumps({"uids": [uid, next_uid]}))
        context = SimpleNamespace(local_id="srv", remote_id=client)
        response = await handler.rpc_forward(request, context)
        assert response.nbytes > x.nbytes  # the output of 3 x 4 x HID float32, uncompressed, and its framing

        async def streamed():  # the same expert through the streaming RPC, two rows in one message
            yield runtime_pb2.ExpertRequest(uid=uid, tensors=[serialize_tensor(x[:2])])

        chunks = [chunk async for chunk in handler.rpc_forward_stream(streamed(), context)]
        assert sum(chunk.nbytes for chunk in chunks) > x[:2].nbytes
    finally:
        runtime.shutdown()
        await asyncio.sleep(0)


def _one_epoch_transition(run_id):
    """A lone Optimizer closes one epoch: no swarm round is attempted, the local
    gradients are applied — the phases of the transition are there either way."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer

    dht = DHT(start=True)
    opt = Optimizer(dht=dht, run_id=run_id, target_batch_size=8, batch_size_per_step=8,
                    params={"w": np.zeros((64, 64), np.float32)}, optimizer=optax.sgd(0.1), matchmaking_time=0.5,
                    tracker_opts=dict(min_refresh_period=0.2, default_refresh_period=0.3), verbose=False)
    try:
        deadline = time.monotonic() + 30.0
        while opt.local_epoch == 0 and time.monotonic() < deadline:
            opt.step({"w": np.ones((64, 64), np.float32)})
            time.sleep(0.05)
        assert opt.local_epoch >= 1, "the lone peer never closed its epoch"
        return str(dht.peer_id)
    finally:
        opt.shutdown()
        dht.shutdown()


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Every scenario, run once inside ONE profiler session: the spans the telemetry
    recorded, the ledgers' records, and the capture's `hivemind:` events."""
    sys.path.insert(0, str(ROOT))
    from perf.trace_reduce import find_xplane, load_planes

    tag = uuid.uuid4().hex[:8]
    out = SimpleNamespace(tag=tag, spans=[], epochs=[], uid=f"tsync{tag}.0", next_uid=f"tsync{tag}.1", client=f"cli-{tag}")
    record = lambda kind, entry: out.epochs.append(entry) if kind == "epoch" else None  # noqa: E731
    add_span_listener(out.spans.append)
    LEDGER.add_record_listener(record)
    logdir = tmp_path_factory.mktemp("xplane")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    out.counters_before = REGISTRY.snapshot()
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        with trace_sync("tsync.outer", step=7) as outer:
            time.sleep(0.02)
            with trace_sync("tsync.inner") as inner:
                time.sleep(0.01)
        out.outer, out.inner = outer, inner
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(_crossing_an_await())
            loop.run_until_complete(_decode_two_sessions(out.uid, out.client))
            loop.run_until_complete(_forward_through_the_handler(out.uid, out.next_uid, out.client))
        finally:
            loop.close()
        out.peer = _one_epoch_transition(f"tsync_{tag}")
    finally:
        jax.profiler.stop_trace()
        remove_span_listener(out.spans.append)
        LEDGER.remove_record_listener(record)
    out.counters_after = REGISTRY.snapshot()
    path = find_xplane(str(logdir))
    assert path is not None, "the profiler wrote no .xplane.pb"
    out.events = [(name, start, duration) for plane, lines in load_planes(path).items() if not plane.startswith("/device")
                  for events in lines.values() for name, start, duration in events if name.startswith("hivemind:")]
    out.serving = [r for r in SERVING_LEDGER.records() if r["client"] == out.client]
    return out


def _events(captured, name):
    return [(start, duration) for event, start, duration in captured.events if event == "hivemind:" + name]


def _children(captured, parent_name):
    parents = {span.span_id for span in captured.spans if span.name == parent_name}
    return {span.name for span in captured.spans if span.parent_id in parents}


# --------------------------------------------------------- (a) one instrument, two timelines


def test_sync_span_lies_in_the_capture_on_the_same_interval(captured):
    [(outer_start, outer_ns)], [(inner_start, inner_ns)] = _events(captured, "tsync.outer"), _events(captured, "tsync.inner")
    assert abs(outer_ns / 1e9 - captured.outer.duration) < 1e-3
    assert abs(inner_ns / 1e9 - captured.inner.duration) < 1e-3
    # one clock offset fits both: the intervals agree, not only their lengths
    assert abs((inner_start - outer_start) / 1e9 - (captured.inner.start - captured.outer.start)) < 1e-3
    assert captured.inner.parent_id == captured.outer.span_id and captured.outer.attributes["step"] == 7


def test_span_that_crosses_an_await_opens_no_annotation(captured):
    assert any(span.name == "tsync.crosses_await" for span in captured.spans)
    assert not _events(captured, "tsync.crosses_await")
    # nor do the async spans of the program: the request span, the p2p handler's
    assert any(span.name == SERVING_SPAN for span in captured.spans) and not _events(captured, SERVING_SPAN)


def test_annotation_is_entered_and_left_under_the_span_name(monkeypatch):
    """The stand-in twin of the capture test: what `trace_sync` asks of jax's profiler."""
    calls = []

    class Annotation:
        def __init__(self, name):
            calls.append(("init", name))

        def __enter__(self):
            calls.append(("enter",))

        def __exit__(self, *exc):
            calls.append(("exit", exc[0]))

    monkeypatch.setitem(sys.modules, "jax", SimpleNamespace(profiler=SimpleNamespace(TraceAnnotation=Annotation)))
    with pytest.raises(KeyError):
        with trace_sync("tsync.standin"):
            raise KeyError("x")
    assert calls == [("init", "hivemind:tsync.standin"), ("enter",), ("exit", KeyError)]
    calls.clear()
    monkeypatch.setattr(tracing, "enabled", False)  # HIVEMIND_TRACE=0: no span, no annotation
    with trace_sync("tsync.off") as span:
        assert span is None
    assert not calls


def test_importing_telemetry_does_not_import_jax():
    code = ("import sys; import hivemind_tpu.telemetry as t\n"
            "with t.trace_sync('no.jax') as span: pass\n"
            "assert span is not None and 'jax' not in sys.modules, sorted(m for m in sys.modules if m.startswith('jax'))")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# --------------------------------------------------------- (b) the decode path


def test_decode_batch_opens_its_three_phases(captured):
    batches = [span for span in captured.spans if span.name == "decode.batch" and span.attributes["uid"] == captured.uid]
    assert [(b.attributes["rows"], b.attributes["bucket"]) for b in batches] == [(2, 2)]
    assert _children(captured, "decode.batch") >= {"decode.assemble", "decode.step", "decode.scatter"}
    for name in ("decode.batch", "decode.assemble", "decode.step", "decode.scatter", "decode.direct"):
        assert _events(captured, name), f"{name} is not in the capture"
    prefills = [s for s in captured.spans if s.name == "decode.direct" and s.attributes["uid"] == captured.uid]
    assert sorted(s.attributes["chunk_len"] for s in prefills) == [4, 4]  # 3 positions, padded to a power of two


@pytest.mark.parametrize("phase", ["assemble", "step", "scatter"])
def test_decode_phase_counters_advance(captured, phase):
    def seconds(snapshot):
        return snapshot.get("hivemind_moe_decode_phase_seconds_total", {}).get("series", {}).get(f"phase={phase}", 0.0)

    spent = seconds(captured.counters_after) - seconds(captured.counters_before)
    [span] = [s for s in captured.spans if s.name == "decode." + phase]
    assert spent > 0 and spent == pytest.approx(span.duration, abs=2e-3)


def test_decode_call_counters_tell_the_paths_apart(captured):
    def calls(snapshot, path):
        return snapshot.get("hivemind_moe_decode_calls_total", {}).get("series", {}).get(f"path={path}", 0.0)

    assert calls(captured.counters_after, "batched") - calls(captured.counters_before, "batched") == 1
    assert calls(captured.counters_after, "direct") - calls(captured.counters_before, "direct") == 2


def test_decode_record_divides_into_queue_wait_and_compute(captured):
    records = [r for r in captured.serving if r["kind"] == "decode"]
    assert len(records) == 2
    for record in records:
        assert record["queue_wait_s"] > 0 and record["compute_s"] > 0
        assert record["queue_wait_s"] + record["compute_s"] == pytest.approx(record["total_s"], rel=0.05)


# --------------------------------------------------------- (c) the pool path


def test_pool_batch_opens_stage_in_device_and_fetch(captured):
    batch, streamed = [span for span in captured.spans if span.name == "pool.batch"]
    assert batch.attributes["pool"] == f"{captured.uid}..{captured.next_uid}_forward"  # the span's own pool
    assert streamed.attributes["pool"] == f"{captured.uid}_forward"
    assert (batch.attributes["rows"], batch.attributes["tasks"], batch.attributes["blocks"]) == (3, 1, 2)
    assert (streamed.attributes["rows"], streamed.attributes["blocks"]) == (2, 1)
    assert _children(captured, "pool.batch") == {"backend.stage_in", "backend.device", "backend.fetch"}
    # one upload, one program a block, one fetch
    under = [span.name for span in captured.spans if span.parent_id == batch.span_id]
    assert sorted(under) == ["backend.device", "backend.device", "backend.fetch", "backend.stage_in"]
    assert [s.attributes["uid"] for s in captured.spans if s.name == "backend.device" and s.parent_id == batch.span_id] == [
        captured.uid, captured.next_uid]
    for name in ("pool.batch", "backend.stage_in", "backend.device", "backend.fetch"):
        assert _events(captured, name), f"{name} is not in the capture"


def test_forward_record_carries_staging_and_deserialization(captured):
    [record] = [r for r in captured.serving if r["kind"] == "forward"]
    [batch] = [s for s in captured.spans if s.name == "pool.batch" and s.attributes["rows"] == 3]
    staged = sum(s.duration for s in captured.spans if s.name in ("backend.stage_in", "backend.fetch") and s.parent_id == batch.span_id)
    assert record["stage_s"] == pytest.approx(staged, abs=2e-3) and 0 < record["stage_s"] <= record["compute_s"]
    assert record["span_len"] == 2 and record["pool"] == batch.attributes["pool"]  # one pool for the whole span
    assert record["deserialize_s"] > 0 and record["serialize_s"] > 0
    def waited(snapshot):  # a counter nobody has moved yet has no series
        return snapshot.get("hivemind_moe_runtime_wait_seconds_total", {}).get("series", {}).get("_", 0.0)

    # the drain loop starved from its start until the request arrived
    assert waited(captured.counters_after) - waited(captured.counters_before) >= 0.04


def test_streamed_request_carries_the_same_phases(captured):
    """A request over the unary payload cap goes by the streaming RPC (a fine-tuning request
    of the benchmark does): its record divides like a unary one's."""
    [record] = [r for r in captured.serving if r["kind"] == "forward_stream"]
    for phase in ("deserialize_s", "queue_wait_s", "compute_s", "stage_s", "serialize_s"):
        assert record[phase] > 0, (phase, record)
    assert record["deserialize_s"] + record["queue_wait_s"] + record["compute_s"] + record["serialize_s"] <= record["total_s"]


# --------------------------------------------------------- (d) the epoch transition


def test_epoch_record_divides_the_transition(captured):
    [epoch] = [e for e in captured.epochs if e["peer"] == captured.peer][:1]
    phases = epoch["grad_round_s"] + epoch["update_s"] + epoch["state_round_s"]
    assert epoch["update_s"] > 0 and epoch["state_round_s"] == 0.0  # a lone peer averages no state
    assert phases == pytest.approx(epoch["transition_s"], rel=0.05)
    mine = [s for s in captured.spans if (s.attributes or {}).get("peer") == captured.peer]
    steps = {s.span_id for s in mine if s.name == "optimizer.step"}
    for name in ("optimizer.grad_round", "optimizer.update"):
        assert any(s.name == name and s.parent_id in steps for s in mine), f"no {name} under optimizer.step"
    for name in ("optimizer.step", "optimizer.accumulate", "optimizer.grad_round", "optimizer.update", "state.device_get"):
        assert _events(captured, name), f"{name} is not in the capture"


@pytest.mark.parametrize("purpose", ["grads", "state", None])
def test_round_record_says_which_averager_owned_the_round(purpose):
    """Scripted spans, as tests/test_device_telemetry.py scripts its own: the round
    span's `purpose` attribute reaches the round record."""
    ledger = RoundLedger()
    attributes = {"peer": "p0", "group_size": 2, "rank": 0, **({"purpose": purpose} if purpose else {})}
    span = Span("allreduce.round", attributes=attributes)
    span.end = span.start + 0.5
    ledger.on_span(span)
    [record] = ledger.records()
    assert record.get("purpose") == purpose and record["total_s"] == pytest.approx(0.5)


def test_the_two_averagers_of_an_optimizer_name_their_rounds():
    from hivemind_tpu.averaging import DecentralizedAverager
    from hivemind_tpu.optim.grad_averager import GradientAverager
    from hivemind_tpu.optim.slice_optimizer import _SliceStateAverager
    from hivemind_tpu.optim.state_averager import TrainingStateAverager

    assert DecentralizedAverager.round_purpose is None
    assert GradientAverager.round_purpose == "grads"
    assert TrainingStateAverager.round_purpose == _SliceStateAverager.round_purpose == "state"
