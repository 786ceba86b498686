"""A span request is one chain on the device (ISSUE 30): one pool task, one executor
call that walks the span's blocks (`forward_chain` / `backward_chain`), one upload
and one fetch a request. `ConnectionHandler` -> `TaskPool` -> `Runtime` ->
`ModuleBackend`, no network; twin backends of the same seeds take the per-block
calls that the chain is held to, bit for bit."""

import asyncio
import subprocess
import sys
import threading
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import optax
import pytest

from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.telemetry.serving import SERVING_LEDGER
from hivemind_tpu.telemetry.tracing import add_span_listener, remove_span_listener

ROOT = Path(__file__).resolve().parents[1]
HID = 16


def _backends(prefix, blocks=3, max_batch_size=8):
    from hivemind_tpu.moe import ModuleBackend
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    return {f"{prefix}.{i}": ModuleBackend(
        f"{prefix}.{i}", CausalTransformerExpert(hidden_dim=HID, num_heads=4), optimizer=optax.sgd(1e-2),
        sample_input=np.zeros((1, 4, HID), np.float32), max_batch_size=max_batch_size, rng_seed=i) for i in range(blocks)}


class _Served:
    """A handler and its runtime on a loop of their own; `forward` / `backward` are
    the unary RPCs with the chain in the request's metadata, decoded back to numpy."""

    def __init__(self, backends, client="chain-client"):
        from hivemind_tpu.moe.server.connection_handler import ConnectionHandler
        from hivemind_tpu.moe.server.runtime import Runtime

        self.backends, self.loop = backends, asyncio.new_event_loop()
        self.handler = ConnectionHandler(backends, activation_compression="none")
        self.context = SimpleNamespace(local_id="srv", remote_id=client)

        async def start():
            self.runtime = Runtime(self.handler.all_pools(), stats_report_interval=None)
            self.handler.on_new_pool = self.runtime.add_pool
            self.runtime.start()

        self.loop.run_until_complete(start())

    def _call(self, rpc, uids, tensors):
        from hivemind_tpu.compression import deserialize_tensor, serialize_tensor
        from hivemind_tpu.proto import runtime_pb2
        from hivemind_tpu.utils.serializer import MSGPackSerializer

        metadata = MSGPackSerializer.dumps({"uids": list(uids)}) if len(uids) > 1 else b""
        request = runtime_pb2.ExpertRequest(uid=uids[0], tensors=[serialize_tensor(t) for t in tensors], metadata=metadata)
        response = self.loop.run_until_complete(asyncio.wait_for(rpc(request, self.context), timeout=120))
        return [deserialize_tensor(t) for t in runtime_pb2.ExpertResponse.FromString(response.join()).tensors]

    def forward(self, uids, x):
        return self._call(self.handler.rpc_forward, uids, [x])[0]

    def backward(self, uids, x, grad):
        return self._call(self.handler.rpc_backward, uids, [x, grad])[0]

    def close(self):
        self.runtime.shutdown()
        self.loop.run_until_complete(asyncio.sleep(0))
        self.loop.close()


@pytest.fixture
def served(request):
    out = _Served(_backends(f"chain{request.node.name.replace('[', '_').replace(']', '')}"))
    yield out
    out.close()


def _per_block(backends, x, grad=None):
    """What the parent did at the numpy entry points: every block's output through
    host float32 to the next; for a gradient the forward sweep, then the blocks in reverse."""
    chain = list(backends.values())
    inputs = [x]
    for backend in chain[:-1] if grad is not None else chain:
        inputs.append(backend.forward(inputs[-1])[0])
    if grad is None:
        return inputs[-1]
    for backend, block_input in zip(reversed(chain), reversed(inputs)):
        [grad] = backend.backward(block_input, grad)
    return grad


def _counter(name, pool):
    return REGISTRY.get(name).labels(pool).value


def _params_equal(a, b):
    import jax

    return all(np.array_equal(x, y) for x, y in zip(jax.tree_util.tree_leaves(a.params), jax.tree_util.tree_leaves(b.params)))


@pytest.mark.parametrize("rows", [4, 3])  # a full bucket, and one with a padding row
def test_span_forward_equals_per_block_calls_bit_for_bit(served, rows):
    twins = _backends("twin")
    x = np.random.RandomState(rows).randn(rows, 4, HID).astype(np.float32)
    got = served.forward(list(served.backends), x)
    assert got.shape == x.shape and np.array_equal(got, _per_block(twins, x))
    assert all(backend.update_count == 0 for backend in served.backends.values())


@pytest.mark.parametrize("rows", [4, 3])
def test_span_backward_equals_per_block_calls_and_steps_every_optimizer_once(served, rows):
    twins = _backends("twin")
    rng = np.random.RandomState(10 + rows)
    x, grad = rng.randn(rows, 4, HID).astype(np.float32), rng.randn(rows, 4, HID).astype(np.float32)
    got = served.backward(list(served.backends), x, grad)
    assert np.array_equal(got, _per_block(twins, x, grad))
    assert [backend.update_count for backend in served.backends.values()] == [1, 1, 1]
    for mine, twin in zip(served.backends.values(), twins.values()):
        assert _params_equal(mine, twin), f"{mine.name} stepped to other parameters than its per-block twin"
    # and the blocks serve from the parameters they stepped to
    assert np.array_equal(served.forward(list(served.backends), x), _per_block(twins, x))


def _spans_of(request):
    """The spans one request closes, its `pool.batch` among them. The batch hands its
    callers their results INSIDE its span, so the answer can be here before the span has
    closed on the runtime's thread: the listener says when it has."""
    spans, batch_closed = [], threading.Event()

    def listener(span):
        spans.append(span)
        if span.name == "pool.batch":
            batch_closed.set()

    add_span_listener(listener)
    try:
        request()
        assert batch_closed.wait(timeout=60), "the request's pool.batch span never closed"
    finally:
        remove_span_listener(listener)
    return spans


def test_span_request_is_one_batch_that_walks_the_chain(served):
    uids = list(served.backends)
    pool_name = f"{uids[0]}..{uids[-1]}_forward"
    spans = _spans_of(lambda: served.forward(uids, np.ones((2, 4, HID), np.float32)))
    assert _counter("hivemind_moe_batches_total", pool_name) == 1
    assert _counter("hivemind_moe_pool_blocks_total", pool_name) == len(uids)
    for uid in uids:  # no block's own pool saw anything
        assert _counter("hivemind_moe_batches_total", f"{uid}_forward") == 0
    [batch] = [s for s in spans if s.name == "pool.batch"]
    assert (batch.attributes["pool"], batch.attributes["blocks"], batch.attributes["rows"]) == (pool_name, 3, 2)
    under = [s for s in spans if s.parent_id == batch.span_id]
    assert [s.name for s in under].count("backend.stage_in") == 1 and [s.name for s in under].count("backend.fetch") == 1
    assert [s.attributes["uid"] for s in under if s.name == "backend.device"] == uids
    [record] = [r for r in SERVING_LEDGER.records() if r["expert"] == uids[0] and r["kind"] == "forward"]
    assert record["span_len"] == 3 and record["pool"] == pool_name and record["occupancy"] == 2 / 8
    staged = sum(s.duration for s in under if s.name in ("backend.stage_in", "backend.fetch"))
    assert record["stage_s"] == pytest.approx(staged, abs=2e-3) and 0 < record["stage_s"] <= record["compute_s"]


def test_backward_walk_is_a_forward_sweep_then_the_blocks_in_reverse(served):
    uids = list(served.backends)
    ones = np.ones((2, 4, HID), np.float32)
    spans = _spans_of(lambda: served.backward(uids, ones, ones))
    [batch] = [s for s in spans if s.name == "pool.batch"]
    assert batch.attributes["pool"] == f"{uids[0]}..{uids[-1]}_backward" and batch.attributes["blocks"] == 3
    walked = [(s.attributes["sweep"], s.attributes["uid"]) for s in spans if s.name == "backend.device"]
    assert walked == [("forward", uids[0]), ("forward", uids[1])] + [("backward", uid) for uid in reversed(uids)]
    assert _counter("hivemind_moe_pool_blocks_total", f"{uids[0]}..{uids[-1]}_backward") == 3


def test_walk_runs_one_program_ahead_forward_and_none_in_the_reverse_sweep(monkeypatch):
    """What bounds the device memory a walk holds: a forward sweep dispatches block k+1
    while block k runs (and has waited for block k-1); the reverse sweep waits for a
    block's input gradient, and with it for the block's new parameters, before it
    dispatches the block before."""
    import jax

    from hivemind_tpu.moe.server import module_backend

    backends = list(_backends("ahead").values())
    events, labels = [], {}

    def spy(backend, method, label):
        real = getattr(backend, method)

        def dispatched(*args):
            outputs, routing = real(*args)
            labels[id(outputs[0])] = label
            events.append(f"dispatch {label}")
            return outputs, routing

        monkeypatch.setattr(backend, method, dispatched)

    for index, backend in enumerate(backends):
        spy(backend, "forward_on_device", f"f{index}")
        spy(backend, "backward_on_device", f"b{index}")
    real_wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: (events.append(f"wait {labels[id(x[0])]}"), real_wait(x))[1])
    x = np.ones((2, 4, HID), np.float32)
    module_backend.forward_chain(backends, x)
    assert events == ["dispatch f0", "dispatch f1", "wait f0", "dispatch f2", "wait f1", "wait f2"]
    events.clear()
    module_backend.backward_chain(backends, x, x)
    assert events == ["dispatch f0", "dispatch f1", "wait f0", "dispatch b2", "wait b2",
                      "dispatch b1", "wait b1", "dispatch b0", "wait b0"]


def test_transfers_cross_once_each_way(served):
    from hivemind_tpu.telemetry.device import transfer_totals

    uids = list(served.backends)
    x = np.ones((4, 4, HID), np.float32)
    before = transfer_totals()
    served.forward(uids, x)
    after = transfer_totals()
    assert after["host_to_device"] - before["host_to_device"] == x.nbytes
    assert after["device_to_host"] - before["device_to_host"] == x.nbytes
    served.backward(uids, x, x)
    final = transfer_totals()
    assert final["host_to_device"] - after["host_to_device"] == 2 * x.nbytes
    assert final["device_to_host"] - after["device_to_host"] == x.nbytes


def test_chain_of_one_is_a_single_uid_request(served):
    """No `uids` in the metadata: the block's own pool, made with the backend, one block a batch."""
    uid = list(served.backends)[1]
    twin = _backends("twin")["twin.1"]
    rng = np.random.RandomState(5)
    x, grad = rng.randn(3, 4, HID).astype(np.float32), rng.randn(3, 4, HID).astype(np.float32)
    assert np.array_equal(served.forward([uid], x), twin.forward(x)[0])
    assert np.array_equal(served.backward([uid], x, grad), twin.backward(x, grad)[0])
    assert [b.update_count for b in served.backends.values()] == [0, 1, 0]
    for direction in ("forward", "backward"):
        assert _counter("hivemind_moe_batches_total", f"{uid}_{direction}") == 1
        assert _counter("hivemind_moe_pool_blocks_total", f"{uid}_{direction}") == 1
        assert served.handler.chain_pool(direction, [uid]).blocks == 1
    [record] = [r for r in SERVING_LEDGER.records() if r["expert"] == uid and r["kind"] == "forward"]
    assert "span_len" not in record and record["pool"] == f"{uid}_forward"


def test_failure_mid_chain_fails_the_request_and_nothing_is_retried(served):
    """The middle block's backward raises: the request fails with it; the block behind
    it in the chain has stepped once, it and the block before it have not, and the
    pool runs nothing again on its own."""
    uids = list(served.backends)
    first, middle, last = served.backends.values()
    calls = []

    def broken(xs, grads):
        calls.append(1)
        raise FloatingPointError("the middle block's backward failed")

    middle.backward_on_device = broken
    failures = REGISTRY.get("hivemind_moe_batch_failures_total")
    pool_name = f"{uids[0]}..{uids[-1]}_backward"
    x = np.ones((2, 4, HID), np.float32)
    with pytest.raises(FloatingPointError, match="middle block"):
        served.backward(uids, x, x)
    served.loop.run_until_complete(asyncio.sleep(0.2))  # a retry would have run by now
    assert [first.update_count, middle.update_count, last.update_count] == [0, 0, 1] and len(calls) == 1
    assert failures.labels(pool_name).value == 1 and _counter("hivemind_moe_batches_total", pool_name) == 0
    assert served.handler.chain_pool("backward", uids).queue_size == 0
    del middle.backward_on_device  # the chain serves again, and steps each block once more
    served.backward(uids, x, x)
    assert [first.update_count, middle.update_count, last.update_count] == [1, 1, 2]


def test_unknown_or_mismatched_chains_are_refused_before_any_pool_is_made(served):
    uids = list(served.backends)
    pools = len(served.handler.all_pools())
    x = np.ones((1, 4, HID), np.float32)
    with pytest.raises(KeyError):
        served.forward([uids[0], "nowhere.7"], x)
    with pytest.raises(KeyError):
        served.forward(["nowhere.7"], x)
    with pytest.raises(ValueError, match="takes 1 tensors, got 2"):
        served._call(served.handler.rpc_forward, uids, [x, x])
    assert len(served.handler.all_pools()) == pools == 2 * len(uids)


def test_pools_are_bounded_by_the_consecutive_chains_of_a_server():
    served = _Served(_backends("bound", blocks=2))
    try:
        a, b = served.backends
        x = np.ones((1, 4, HID), np.float32)
        for uids in ([a, b], [b, a]):  # 4 single-block pools + these two: n(n+1) = 6
            served.forward(uids, x)
        with pytest.raises(ValueError, match="too many distinct span chains"):
            served.forward([a, b, a], x)
        assert len(served.handler.all_pools()) == 6 == len(served.runtime.pools)
    finally:
        served.close()


def test_warm_up_by_the_numpy_entry_points_covers_the_chain(served):
    """The benchmark's contract (`perf/runners/block_server.warm_finetune`): after
    `backend.forward(x)` and `backend.backward(x, x)` on each block with float32 numpy of
    one bucket, a span forward and backward of that bucket compile nothing."""
    import jax

    from hivemind_tpu.telemetry.device import COMPILE_TRACKER

    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: events.append(event) if event.endswith("backend_compile_duration") else None)
    warm = np.zeros((4, 4, HID), np.float32)
    for backend in served.backends.values():
        backend.forward(warm)
        backend.backward(warm, warm)
    tracked, fired = COMPILE_TRACKER.total(), len(events)
    assert tracked >= 2 * len(served.backends)  # the watch sees these jits compile
    rng = np.random.RandomState(3)
    x, grad = rng.randn(4, 4, HID).astype(np.float32), rng.randn(4, 4, HID).astype(np.float32)
    uids = list(served.backends)
    served.forward(uids, x)
    served.backward(uids, x, grad)
    served.forward(uids[1:], x[:3])  # another chain, a bucket with a padding row
    assert COMPILE_TRACKER.total() == tracked and len(events) == fired


def test_fine_tune_cell_rehearses_correct():
    """The benchmark's fine-tune cell at toy sizes on the CPU: exit code 3 says that every
    request came back, the reference check held and nothing compiled inside the window."""
    done = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--workload", "mistral-7b-span8.finetune",
                           "--trace", "1", "--seconds", "4"], cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert done.returncode == 3, done.stderr[-3000:]
    assert "inside it 0" in done.stderr
    for metric in ("pool_batches_per_request.finetune", "transfer_mb_per_request.finetune", "staging_ms.finetune"):
        assert metric in done.stderr.splitlines()[-1], done.stderr[-600:]
