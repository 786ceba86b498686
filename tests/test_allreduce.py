"""Partitioning, reduction, load balancing, and AllReduceRunner with hand-built
groups over real localhost transport (scope: reference tests/test_allreduce.py)."""

import asyncio
from typing import Dict, List

import numpy as np
import pytest

from hivemind_tpu.averaging.allreduce import AllReduceRunner, AveragingMode
from hivemind_tpu.averaging.load_balancing import hagenbach_bischoff, load_balance_peers
from hivemind_tpu.averaging.partition import TensorPartContainer, TensorPartReducer
from hivemind_tpu.compression import Float16Compression
from hivemind_tpu.p2p import P2P, P2PContext
from hivemind_tpu.proto import averaging_pb2


def make_tensors(seed=0):
    rng = np.random.RandomState(seed)
    return [
        rng.randn(1000).astype(np.float32),
        rng.randn(32, 16).astype(np.float32),
        rng.randn(7).astype(np.float32),
    ]


async def test_part_container_roundtrip():
    tensors = make_tensors()
    total = sum(t.size for t in tensors)
    counts = [total // 2, total - total // 2]
    container = TensorPartContainer(tensors, counts, part_size_bytes=800)

    # feeding back zero deltas reproduces... zero deltas per tensor
    for peer_index in range(2):
        parts = container.get_raw_input_parts(peer_index)
        assert sum(p.size for p in parts) == counts[peer_index]
        for part_index, part in enumerate(parts):
            container.register_processed_part(peer_index, part_index, part * 0.5)  # delta = half

    deltas = [d async for d in container.iterate_output_tensors()]
    flat_input = np.concatenate([t.reshape(-1) for t in tensors])
    flat_delta = np.concatenate([d.reshape(-1) for d in deltas])
    assert np.allclose(flat_delta, flat_input * 0.5, atol=1e-6)
    for tensor, delta in zip(tensors, deltas):
        assert delta.shape == tensor.shape


async def test_part_container_compressed_stream():
    tensors = make_tensors(1)
    total = sum(t.size for t in tensors)
    container = TensorPartContainer(tensors, [total], compression=Float16Compression(), part_size_bytes=1000)
    from hivemind_tpu.compression import deserialize_tensor

    restored = []
    async for serialized in container.iterate_input_parts_for(0):
        restored.append(deserialize_tensor(serialized))
    flat = np.concatenate([r.reshape(-1) for r in restored])
    original = np.concatenate([t.reshape(-1) for t in tensors])
    assert np.allclose(flat, original, atol=1e-2)


async def test_part_container_failed_reducer():
    tensors = make_tensors(2)
    total = sum(t.size for t in tensors)
    container = TensorPartContainer(tensors, [total // 3, total - total // 3], part_size_bytes=512)
    container.register_failed_reducer(0)
    for part_index, part in enumerate(container.get_raw_input_parts(1)):
        container.register_processed_part(1, part_index, np.ones_like(part))
    deltas = [d async for d in container.iterate_output_tensors()]
    flat_delta = np.concatenate([d.reshape(-1) for d in deltas])
    assert np.all(flat_delta[: total // 3] == 0)  # failed span keeps local values
    assert np.all(flat_delta[total // 3 :] == 1)
    assert container.failed_size == total // 3


async def test_reducer_weighted_average():
    reducer = TensorPartReducer([(10,), (5,)], num_senders=3)
    parts = [np.full(10, float(i)) for i in range(3)]

    results = await asyncio.gather(
        *(reducer.accumulate_part(i, 0, parts[i], weight=i + 1) for i in range(3))
    )
    expected = (parts[0] * 1 + parts[1] * 2 + parts[2] * 3) / 6
    for result in results:
        assert np.allclose(result, expected)


async def test_reducer_sender_failure_shrinks_denominator():
    reducer = TensorPartReducer([(4,)], num_senders=3)
    task0 = asyncio.create_task(reducer.accumulate_part(0, 0, np.full(4, 1.0), weight=1))
    task1 = asyncio.create_task(reducer.accumulate_part(1, 0, np.full(4, 3.0), weight=1))
    await asyncio.sleep(0.05)
    assert not task0.done()  # waiting for sender 2
    reducer.on_sender_failed(2)
    result = await asyncio.wait_for(task0, timeout=2)
    assert np.allclose(result, 2.0)  # average of survivors only
    assert np.allclose(await task1, 2.0)


def test_load_balancing():
    counts = load_balance_peers(1000, [1.0, 1.0, 1.0, 1.0])
    assert sum(counts) == 1000 and max(counts) - min(counts) <= 1

    counts = load_balance_peers(1000, [10.0, 1.0])
    assert sum(counts) == 1000 and counts[0] > counts[1]

    counts = load_balance_peers(1000, [1.0, None, 1.0, 0])  # two clients
    assert sum(counts) == 1000 and counts[1] == 0 and counts[3] == 0

    counts = load_balance_peers(1000, [7.0, None])
    assert counts == (1000, 0)

    with pytest.raises(ValueError):
        load_balance_peers(100, [None, None])

    assert list(hagenbach_bischoff(10, np.array([0.5, 0.3, 0.2]))) == [5, 3, 2]


@pytest.mark.parametrize("module, loads_scipy_optimize", [
    ("hivemind_tpu.averaging.load_balancing", True), ("hivemind_tpu.moe.server.server", False)])
def test_the_lp_solver_is_imported_when_the_averager_is_and_only_then(module, loads_scipy_optimize):
    """`scipy.optimize` takes a second or more to import. The averager pays it as
    it loads: inside `optimize_parts_lp` it fell on the event loop in the middle of
    a process's first round, which that stall lost to PROTOCOL_VIOLATION. A block
    server never loads the averager, and its start-up must not pay either."""
    import subprocess
    import sys

    code = f"import sys, {module}; sys.exit(0 if ('scipy.optimize' in sys.modules) == {loads_scipy_optimize} else 7)"
    run = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:] or f"scipy.optimize loaded: {not loads_scipy_optimize}"


class _AllreduceHarness:
    """Minimal averager stand-in: registers rpc_aggregate_part per peer and routes
    streams to that peer's runner."""

    def __init__(self, p2p: P2P):
        self.p2p = p2p
        self.runner = None

    async def register(self):
        async def rpc_aggregate_part(requests, context: P2PContext):
            first = await requests.__anext__()
            assert self.runner is not None
            async for message in self.runner.handle_aggregate_stream(first, requests, context):
                yield message

        await self.p2p.add_protobuf_handler(
            "DecentralizedAverager.rpc_aggregate_part",
            rpc_aggregate_part,
            averaging_pb2.AveragingData,
            stream_input=True,
            stream_output=True,
        )

    def get_stub(self, peer_id):
        harness_p2p = self.p2p

        class _Stub:
            def rpc_aggregate_part(self, requests, timeout=None):
                return harness_p2p.iterate_protobuf_handler(
                    peer_id, "DecentralizedAverager.rpc_aggregate_part", requests, averaging_pb2.AveragingData
                )

        return _Stub()


async def run_allreduce_group(n_peers: int, modes: List[AveragingMode], counts_override=None, weights=None):
    """Build a real group over localhost TCP and run one full all-reduce."""
    p2ps = [await P2P.create() for _ in range(n_peers)]
    for i, p2p in enumerate(p2ps):
        for other in p2ps[:i]:
            await p2p.connect(other.get_visible_maddrs()[0])
    harnesses = [_AllreduceHarness(p) for p in p2ps]
    for harness in harnesses:
        await harness.register()

    peer_tensors = {i: make_tensors(seed=i) for i in range(n_peers)}
    total = sum(t.size for t in peer_tensors[0])
    if counts_override is None:
        reducers = [i for i, m in enumerate(modes) if m != AveragingMode.CLIENT]
        base = total // len(reducers)
        counts = [0] * n_peers
        for j, i in enumerate(reducers):
            counts[i] = base + (total - base * len(reducers) if j == 0 else 0)
    else:
        counts = counts_override
    weights = weights or [1.0 if m != AveragingMode.AUX else 0.0 for m in modes]
    ordered_peer_ids = [p.peer_id for p in p2ps]

    group_id = b"test-group-0123"
    runners = []
    for i in range(n_peers):
        runner = AllReduceRunner(
            p2p=p2ps[i],
            group_id=group_id,
            tensors=peer_tensors[i] if modes[i] != AveragingMode.AUX else peer_tensors[0],
            ordered_peer_ids=ordered_peer_ids,
            peer_element_counts=counts,
            modes=modes,
            get_stub=harnesses[i].get_stub,
            weight=weights[i],
            sender_timeout=5.0,
            reducer_timeout=10.0,
        )
        harnesses[i].runner = runner
        runners.append(runner)

    async def run_one(i):
        deltas = [d async for d in runners[i].run()]
        return deltas

    all_deltas = await asyncio.gather(*(run_one(i) for i in range(n_peers)))
    for p2p in p2ps:
        await p2p.shutdown()
    return peer_tensors, all_deltas, weights


async def test_allreduce_two_nodes():
    modes = [AveragingMode.NODE, AveragingMode.NODE]
    peer_tensors, all_deltas, weights = await run_allreduce_group(2, modes)
    expected = [
        np.mean([peer_tensors[i][k] for i in range(2)], axis=0) for k in range(3)
    ]
    for i in range(2):
        for k in range(3):
            averaged = peer_tensors[i][k] + all_deltas[i][k].reshape(peer_tensors[i][k].shape)
            assert np.allclose(averaged, expected[k], atol=1e-5), f"peer {i} tensor {k}"


async def test_allreduce_four_nodes_weighted():
    modes = [AveragingMode.NODE] * 4
    weights = [1.0, 2.0, 3.0, 4.0]
    peer_tensors, all_deltas, _ = await run_allreduce_group(4, modes, weights=weights)
    total_w = sum(weights)
    expected = [
        sum(peer_tensors[i][k] * weights[i] for i in range(4)) / total_w for k in range(3)
    ]
    for i in range(4):
        for k in range(3):
            averaged = peer_tensors[i][k] + all_deltas[i][k].reshape(peer_tensors[i][k].shape)
            assert np.allclose(averaged, expected[k], atol=1e-4), f"peer {i} tensor {k}"


async def test_allreduce_client_and_aux_modes():
    # peer0: NODE, peer1: CLIENT (sends only, reduces nothing), peer2: AUX (reduces only)
    modes = [AveragingMode.NODE, AveragingMode.CLIENT, AveragingMode.AUX]
    total = sum(t.size for t in make_tensors())
    counts = [total // 2, 0, total - total // 2]
    peer_tensors, all_deltas, _ = await run_allreduce_group(3, modes, counts_override=counts)
    # only NODE and CLIENT contribute data (AUX weight 0); both should get the average
    expected = [
        np.mean([peer_tensors[0][k], peer_tensors[1][k]], axis=0) for k in range(3)
    ]
    for i in (0, 1):
        for k in range(3):
            averaged = peer_tensors[i][k] + all_deltas[i][k].reshape(peer_tensors[i][k].shape)
            assert np.allclose(averaged, expected[k], atol=1e-5), f"peer {i} tensor {k}"
    assert all_deltas[2] == []  # aux yields nothing
