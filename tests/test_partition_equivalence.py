"""Wire-equivalence and bit-identity guarantees for the zero-copy averaging data
path (ISSUE 6): the view-based ``TensorPartContainer`` must serialize byte-identical
parts to the old concat-everything implementation for every codec, the in-place
``TensorPartReducer`` must produce bit-identical averages, and a real two-peer
all-reduce must match an op-by-op numpy replay of the wire pipeline exactly."""

import asyncio
import os
import sys

import numpy as np
import pytest

from hivemind_tpu.averaging.allreduce import AllReduceRunner, AveragingMode
from hivemind_tpu.averaging.partition import (
    TensorPartContainer,
    TensorPartReducer,
    compute_span_part_sizes,
)
from hivemind_tpu.compression import (
    CompressionType,
    deserialize_tensor,
    get_codec,
    serialize_tensor,
)
from hivemind_tpu.proto import runtime_pb2


ALL_CODECS = sorted(runtime_pb2.CompressionType.values())


def _equivalence_tensors():
    """Mixed shapes/dtypes, with values beyond the fp16 range so the FLOAT16 clip
    path is exercised (an unclipped in-place bug would change bytes here)."""
    rng = np.random.RandomState(7)
    return [
        rng.randn(1111).astype(np.float32) * 1e5,  # exceeds FP16_MAX: clip must fire
        rng.randn(64, 32).astype(np.float32),
        rng.randn(501).astype(np.float64),  # conversion-copy (private) path
        rng.randn(3, 5, 7).astype(np.float32),
    ]


@pytest.mark.parametrize("compression_type", ALL_CODECS)
async def test_wire_equivalence_every_codec(compression_type):
    """Container-serialized parts must be byte-identical to serializing slices of
    the naive concatenated fp32 stream — across part boundaries that straddle
    tensors, for every registered codec."""
    codec = get_codec(compression_type)
    tensors = _equivalence_tensors()
    originals = [t.copy() for t in tensors]
    total = sum(t.size for t in tensors)
    counts = [total // 3, total // 5, total - total // 3 - total // 5]
    part_size_bytes = 1024  # small parts: many boundary-straddling cases

    # the reference construction the refactor replaced: one concatenated fp32 flat
    flat = np.concatenate([t.reshape(-1).astype(np.float32) for t in tensors])
    expected_spans = []
    offset = 0
    for count in counts:
        for size in compute_span_part_sizes(count, part_size_bytes):
            expected_spans.append((offset, offset + size))
            offset += size

    container = TensorPartContainer(tensors, counts, compression=codec, part_size_bytes=part_size_bytes)
    produced = []
    for peer_index in range(len(counts)):
        async for serialized in container.iterate_input_parts_for(peer_index):
            produced.append(serialized)

    assert len(produced) == len(expected_spans)
    for (start, stop), actual in zip(expected_spans, produced):
        expected = serialize_tensor(flat[start:stop].copy(), codec)
        assert actual.SerializeToString() == expected.SerializeToString(), (
            f"codec {compression_type}: part [{start}:{stop}) bytes diverged"
        )
    # in-place compression must never have leaked into caller-owned tensors
    for tensor, original in zip(tensors, originals):
        assert np.array_equal(tensor, original), "container mutated an input tensor"


async def test_reducer_in_place_average_bit_identical():
    """np.add/np.multiply/np.divide with out= must reproduce the naive
    ``(acc + p*w) / total`` bit for bit, including the weighted path."""
    rng = np.random.RandomState(3)
    parts = [rng.randn(1000).astype(np.float32) for _ in range(3)]
    weights = [0.3, 1.0, 2.5]

    reducer = TensorPartReducer([(1000,)], num_senders=3)
    results = await asyncio.gather(
        *(reducer.accumulate_part(i, 0, parts[i], weight=weights[i]) for i in range(3))
    )
    naive = np.zeros(1000, np.float32)
    for part, weight in zip(parts, weights):
        naive += part * weight
    naive = naive / sum(weights)
    for result in results:
        assert np.array_equal(result, naive), "in-place reduction diverged bitwise"


async def test_reducer_late_part_cannot_corrupt_resolved_average():
    """The accumulator IS the result after the in-place divide: a laggard whose
    part arrives after resolution (its denominator already shrunk) must not
    mutate the average other senders already received."""
    reducer = TensorPartReducer([(4,)], num_senders=2)
    early = asyncio.create_task(reducer.accumulate_part(0, 0, np.full(4, 2.0, np.float32)))
    await asyncio.sleep(0.01)
    reducer.on_sender_failed(1)
    resolved = await asyncio.wait_for(early, timeout=2)
    assert np.array_equal(resolved, np.full(4, 2.0, np.float32))
    snapshot = resolved.copy()
    late = await reducer.accumulate_part(1, 0, np.full(4, 99.0, np.float32))
    assert np.array_equal(late, snapshot), "late part mutated the resolved average"
    assert np.array_equal(resolved, snapshot)


async def test_prefetch_knob_is_wired():
    """ISSUE 6 satellite: the container's prefetch arg used to be accepted and
    dropped (iterate_input_parts_for hardcoded 4); it must be stored and the
    runner must plumb its own prefetch through."""
    tensors = [np.zeros(64, np.float32)]
    container = TensorPartContainer(tensors, [64], prefetch=2)
    assert container.prefetch == 2
    with pytest.raises(AssertionError):
        TensorPartContainer(tensors, [64], prefetch=0)


def _replay_two_peer_allreduce(flats, counts, codec_type, part_size_bytes):
    """Op-by-op numpy replay of the two-peer wire pipeline: what each peer's
    per-part deltas must be, bit for bit."""
    codec = get_codec(codec_type)

    def wire_roundtrip(part):
        return deserialize_tensor(serialize_tensor(part.copy(), codec))

    deltas = [np.empty_like(flats[0]) for _ in range(2)]
    offset = 0
    for owner, count in enumerate(counts):
        for size in compute_span_part_sizes(count, part_size_bytes):
            start, stop = offset, offset + size
            local = flats[owner][start:stop]            # loopback: raw fp32
            remote_sender = 1 - owner
            remote = wire_roundtrip(flats[remote_sender][start:stop])  # via the wire
            acc = np.zeros(size, np.float32)
            acc += local  # 2 senders: fp32 addition is commutative, order-free
            acc += remote
            averaged = acc / 2.0
            deltas[owner][start:stop] = averaged - local
            # the delta to the remote sender rides the wire (and is codec-rounded)
            deltas[remote_sender][start:stop] = wire_roundtrip(averaged - remote)
            offset = stop
    return deltas


@pytest.mark.parametrize("codec_type", [CompressionType.NONE, CompressionType.FLOAT16])
async def test_two_peer_allreduce_bit_identical_to_replay(codec_type):
    """A real two-peer all-reduce over localhost transport produces deltas that
    match the numpy replay of the exact wire pipeline — no copies, reorderings,
    or in-place tricks may perturb a single bit."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_allreduce import _AllreduceHarness

    part_size_bytes = 600  # several parts per span
    rng = np.random.RandomState(11)
    n = 800
    flats = [rng.randn(n).astype(np.float32) * 3.0 for _ in range(2)]
    counts = [n // 2, n - n // 2]
    codec = get_codec(codec_type)

    from hivemind_tpu.p2p import P2P

    p2ps = [await P2P.create() for _ in range(2)]
    await p2ps[1].connect(p2ps[0].get_visible_maddrs()[0])
    harnesses = [_AllreduceHarness(p) for p in p2ps]
    for harness in harnesses:
        await harness.register()
    try:
        runners = []
        for i in range(2):
            runner = AllReduceRunner(
                p2p=p2ps[i],
                group_id=b"equivalence-group",
                tensors=[flats[i].copy()],
                ordered_peer_ids=[p.peer_id for p in p2ps],
                peer_element_counts=counts,
                modes=[AveragingMode.NODE, AveragingMode.NODE],
                get_stub=harnesses[i].get_stub,
                compression=codec,
                part_size_bytes=part_size_bytes,
                sender_timeout=10.0,
                reducer_timeout=20.0,
            )
            harnesses[i].runner = runner
            runners.append(runner)

        async def run_one(i):
            return [d async for d in runners[i].run()]

        all_deltas = await asyncio.gather(*(run_one(i) for i in range(2)))
    finally:
        for p2p in p2ps:
            await p2p.shutdown()

    expected = _replay_two_peer_allreduce(flats, counts, codec_type, part_size_bytes)
    for i in range(2):
        got = all_deltas[i][0].reshape(-1)
        assert np.array_equal(got, expected[i]), (
            f"peer {i} deltas diverged from the wire replay (codec {codec_type}); "
            f"max abs diff {np.max(np.abs(got - expected[i]))}"
        )


async def _run_runner_group(flats, counts, links_by_peer, part_size_bytes=600):
    """A real localhost all-reduce with hand-built per-peer link maps
    (ISSUE 11); returns each peer's resulting vector."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_allreduce import _AllreduceHarness

    from hivemind_tpu.averaging.residual import ResidualStore
    from hivemind_tpu.compression import Float16Compression
    from hivemind_tpu.p2p import P2P

    n_peers = len(flats)
    p2ps = [await P2P.create() for _ in range(n_peers)]
    for i, p2p in enumerate(p2ps):
        for other in p2ps[:i]:
            await p2p.connect(other.get_visible_maddrs()[0])
    harnesses = [_AllreduceHarness(p) for p in p2ps]
    for harness in harnesses:
        await harness.register()
    try:
        runners = []
        for i in range(n_peers):
            runner = AllReduceRunner(
                p2p=p2ps[i],
                group_id=b"tier-interop-group",
                tensors=[flats[i].copy()],
                ordered_peer_ids=[p.peer_id for p in p2ps],
                peer_element_counts=counts,
                modes=[AveragingMode.NODE] * n_peers,
                get_stub=harnesses[i].get_stub,
                compression=Float16Compression(),
                part_size_bytes=part_size_bytes,
                sender_timeout=10.0,
                reducer_timeout=20.0,
                links=links_by_peer[i],
                residuals=ResidualStore(),
            )
            harnesses[i].runner = runner
            runners.append(runner)

        async def run_one(i):
            return [d async for d in runners[i].run()]

        all_deltas = await asyncio.gather(*(run_one(i) for i in range(n_peers)))
    finally:
        for p2p in p2ps:
            await p2p.shutdown()
    return [flats[i] + all_deltas[i][0].reshape(-1) for i in range(n_peers)]


async def test_two_peer_quantized_allreduce_within_tolerance():
    """The lossy-tier analog of the bit-identity replay: a uniform8 link with
    error feedback (absolute_part delta leg) lands every peer within
    quantization distance of the true average."""
    from hivemind_tpu.averaging.wire_codec import WireLink

    rng = np.random.RandomState(13)
    n = 4000
    flats = [rng.randn(n).astype(np.float32) for _ in range(2)]
    counts = [n // 2, n - n // 2]
    q8 = WireLink.for_tier("uniform8")
    results = await _run_runner_group(flats, counts, [{1: q8}, {0: q8}])
    true_average = (flats[0] + flats[1]) / 2
    for i, result in enumerate(results):
        assert np.abs(result - true_average).max() < 0.05, f"peer {i} diverged"


async def test_mixed_tier_group_interop():
    """ISSUE 11 satellite: an 8-bit peer in an fp16 group reduces correctly —
    one link runs uniform8 (both directions, with EF) while the other two stay
    float16; every peer still lands on the average within tolerance."""
    from hivemind_tpu.averaging.wire_codec import WireLink

    rng = np.random.RandomState(17)
    n = 3000
    flats = [rng.randn(n).astype(np.float32) for _ in range(3)]
    counts = [1000, 1000, 1000]
    q8, fp16 = WireLink.for_tier("uniform8"), WireLink.for_tier("float16")
    # peer 2 is the "slow WAN" peer: its links run 8-bit; peers 0<->1 stay fp16
    links_by_peer = [
        {1: fp16, 2: q8},
        {0: fp16, 2: q8},
        {0: q8, 1: q8},
    ]
    results = await _run_runner_group(flats, counts, links_by_peer)
    true_average = np.mean(flats, axis=0)
    for i, result in enumerate(results):
        assert np.abs(result - true_average).max() < 0.05, f"peer {i} diverged"
    # the fp16-only link kept its classic delta path: peers 0 and 1 agree on
    # each other's spans to fp16 precision
    assert np.abs(results[0][:2000] - results[1][:2000]).max() < 2e-3
