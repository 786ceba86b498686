"""Tests for the utils layer — scope mirrors reference tests/test_util_modules.py:
serializer ext types, TimedStorage semantics, streaming split/combine, PerformanceEMA,
asyncio helpers, loop runner (MPFuture equivalent), tensor descriptors, crypto."""

import asyncio
import time

import numpy as np
import pytest

from hivemind_tpu.utils import (
    MSGPackSerializer,
    PerformanceEMA,
    TensorDescriptor,
    BatchTensorDescriptor,
    TimedStorage,
    achain,
    aiter_with_timeout,
    amap_in_executor,
    as_aiter,
    azip,
    combine_from_streaming,
    get_dht_time,
    nested_flatten,
    nested_map,
    nested_pack,
    split_for_streaming,
)
from hivemind_tpu.utils.crypto import Ed25519PrivateKey, RSAPrivateKey
from hivemind_tpu.utils.loop import LoopRunner


def test_msgpack_serializer_roundtrip():
    for obj in [
        {"a": 1, "b": [2, 3], "c": (4, 5, (6,))},
        b"raw bytes",
        "string",
        12345,
        3.14,
        None,
        [1, "two", b"three", (4, 5)],
        {1: "int keys allowed"},
    ]:
        assert MSGPackSerializer.loads(MSGPackSerializer.dumps(obj)) == obj


def test_msgpack_tuple_vs_list_preserved():
    data = MSGPackSerializer.dumps({"t": (1, 2), "l": [1, 2]})
    restored = MSGPackSerializer.loads(data)
    assert restored["t"] == (1, 2) and isinstance(restored["t"], tuple)
    assert restored["l"] == [1, 2] and isinstance(restored["l"], list)


def test_msgpack_ext_serializable():
    @MSGPackSerializer.ext_serializable(0x7A)
    class Pair:
        def __init__(self, a, b):
            self.a, self.b = a, b

        def packb(self):
            return MSGPackSerializer.dumps([self.a, self.b])

        @classmethod
        def unpackb(cls, data):
            return cls(*MSGPackSerializer.loads(data))

        def __eq__(self, other):
            return self.a == other.a and self.b == other.b

    restored = MSGPackSerializer.loads(MSGPackSerializer.dumps({"p": Pair(1, "x")}))
    assert restored["p"] == Pair(1, "x")


def test_timed_storage_basic():
    storage = TimedStorage()
    now = get_dht_time()
    assert storage.store("key", "value", now + 10)
    assert storage.get("key").value == "value"
    assert "key" in storage and len(storage) == 1
    # stale write rejected
    assert not storage.store("key", "older", now + 5)
    assert storage.get("key").value == "value"
    # fresher write wins
    assert storage.store("key", "newer", now + 20)
    assert storage.get("key").value == "newer"
    # expired values vanish
    assert storage.store("fleeting", "gone", now + 0.05)
    time.sleep(0.1)
    assert storage.get("fleeting") is None
    assert "fleeting" not in storage


def test_timed_storage_maxsize_evicts_soonest():
    storage = TimedStorage(maxsize=2)
    now = get_dht_time()
    storage.store("a", 1, now + 100)
    storage.store("b", 2, now + 50)
    storage.store("c", 3, now + 200)
    assert "b" not in storage  # soonest-to-expire evicted
    assert "a" in storage and "c" in storage


def test_timed_storage_top_and_freeze():
    storage = TimedStorage()
    now = get_dht_time()
    storage.store("late", 1, now + 100)
    storage.store("early", 2, now + 10)
    key, entry = storage.top()
    assert key == "early" and entry.value == 2
    storage.store("gone", 3, now + 0.05)
    with storage.freeze():
        time.sleep(0.1)
        assert "gone" in storage  # frozen: no eviction
    assert "gone" not in storage


def test_streaming_split_combine():
    data = bytes(range(256)) * 100
    chunks = list(split_for_streaming(data, chunk_size_bytes=1000))
    assert all(len(c) <= 1000 for c in chunks)
    assert combine_from_streaming(chunks) == data
    assert list(split_for_streaming(b"", 10)) == [b""]


def test_performance_ema():
    ema = PerformanceEMA(alpha=0.5)
    ema.update(10, interval=1.0)  # 10 samples/sec
    assert abs(ema.samples_per_second - 10.0) < 1e-6
    ema.update(10, interval=1.0)
    assert abs(ema.samples_per_second - 10.0) < 1e-6
    with ema.pause():
        time.sleep(0.05)
    ema.update(20, interval=1.0)
    assert ema.samples_per_second > 10.0


def test_nested():
    structure = {"b": [1, (2, 3)], "a": 4}
    flat = list(nested_flatten(structure))
    assert flat == [4, 1, 2, 3]  # dict keys sorted
    packed = nested_pack(flat, structure)
    assert packed == {"a": 4, "b": [1, (2, 3)]}
    doubled = nested_map(lambda x: x * 2, structure)
    assert doubled == {"a": 8, "b": [2, (4, 6)]}


async def test_async_iterators():
    assert [x async for x in as_aiter(1, 2, 3)] == [1, 2, 3]
    assert [x async for x in achain(as_aiter(1), as_aiter(2, 3))] == [1, 2, 3]
    assert [x async for x in azip(as_aiter(1, 2), as_aiter("a", "b", "c"))] == [(1, "a"), (2, "b")]
    squared = [x async for x in amap_in_executor(lambda v: v * v, as_aiter(1, 2, 3))]
    assert squared == [1, 4, 9]

    async def slow_iter():
        yield 1
        await asyncio.sleep(10)
        yield 2

    with pytest.raises(asyncio.TimeoutError):
        _ = [x async for x in aiter_with_timeout(slow_iter(), timeout=0.1)]


def test_loop_runner_sync_and_future():
    runner = LoopRunner("test-loop")

    async def compute(x):
        await asyncio.sleep(0.01)
        return x * 2

    assert runner.run_coroutine(compute(21)) == 42
    future = runner.run_coroutine(compute(10), return_future=True)
    assert future.result(timeout=5) == 20

    async def fail():
        raise ValueError("boom")

    with pytest.raises(ValueError):
        runner.run_coroutine(fail())
    runner.shutdown()


def test_tensor_descriptor():
    arr = np.zeros((4, 8), dtype=np.float32)
    descr = TensorDescriptor.from_array(arr)
    assert descr.shape == (4, 8) and descr.dtype == "float32"
    assert descr.numel == 32 and descr.nbytes == 128
    zeros = descr.make_zeros()
    assert zeros.shape == (4, 8) and zeros.dtype == np.float32

    restored = MSGPackSerializer.loads(MSGPackSerializer.dumps(descr))
    assert restored == descr

    batch = BatchTensorDescriptor.from_array(arr)
    assert batch.shape == (0, 8)
    assert batch.with_batch_size(16).shape == (16, 8)
    assert batch.make_dummy().shape[0] == 3


def test_tensor_descriptor_bfloat16():
    import jax.numpy as jnp

    arr = jnp.zeros((2, 3), dtype=jnp.bfloat16)
    descr = TensorDescriptor.from_array(arr)
    assert descr.dtype == "bfloat16" and descr.itemsize == 2
    zeros = descr.make_zeros("jax")
    assert str(zeros.dtype) == "bfloat16"


@pytest.mark.parametrize("key_type", [Ed25519PrivateKey, RSAPrivateKey])
def test_crypto_sign_verify(key_type):
    key = key_type()
    public = key.get_public_key()
    signature = key.sign(b"hello swarm")
    assert public.verify(b"hello swarm", signature)
    assert not public.verify(b"tampered", signature)
    assert not public.verify(b"hello swarm", b"garbage-signature")
    # serialization round trip
    restored_pub = type(public).from_bytes(public.to_bytes())
    assert restored_pub.verify(b"hello swarm", signature)
    restored_priv = key_type.from_bytes(key.to_bytes())
    assert public.verify(b"again", restored_priv.sign(b"again"))


def test_process_wide_key_singleton():
    k1 = Ed25519PrivateKey.process_wide()
    k2 = Ed25519PrivateKey.process_wide()
    assert k1 is k2


def test_profiling_hooks():
    """device_memory_stats / tracked_jit (the profiler half lives in
    test_tracing.py: trace_sync puts spans on the XLA trace's timeline)."""
    import jax.numpy as jnp
    from hivemind_tpu.telemetry.device import COMPILE_TRACKER
    from hivemind_tpu.utils.profiling import device_memory_stats, tracked_jit

    stats = device_memory_stats()
    assert isinstance(stats, dict)  # may be empty on CPU

    double = tracked_jit(lambda x: x * 2, site="test_util_modules.double")
    before = COMPILE_TRACKER.counts().get("test_util_modules.double", 0)
    assert float(double(jnp.ones(3)).sum()) == 6.0  # first call compiles
    assert float(double(jnp.ones(3)).sum()) == 6.0  # cache hit: not counted
    assert float(double(jnp.ones(5)).sum()) == 10.0  # new shape compiles
    assert COMPILE_TRACKER.counts()["test_util_modules.double"] == before + 2
