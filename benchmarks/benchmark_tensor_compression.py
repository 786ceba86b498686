"""Per-codec wall time on a 10M-element tensor (parity: reference
benchmarks/benchmark_tensor_compression.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import json
import time

import numpy as np


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # host-only benchmark: pinned, and the result says so
    jax.devices()

    from hivemind_tpu.compression import CompressionType, deserialize_tensor, serialize_tensor

    tensor = np.random.randn(10_000_000).astype(np.float32)
    results = {}
    for name in ["NONE", "FLOAT16", "MEANSTD_16BIT", "UNIFORM_8BIT", "QUANTILE_8BIT", "BLOCKWISE_8BIT"]:
        ct = getattr(CompressionType, name)
        serialize_tensor(tensor, ct)  # warmup (jit)
        start = time.perf_counter()
        serialized = serialize_tensor(tensor, ct)
        compress_ms = (time.perf_counter() - start) * 1000
        start = time.perf_counter()
        restored = deserialize_tensor(serialized)
        extract_ms = (time.perf_counter() - start) * 1000
        results[name] = {
            "compress_ms": round(compress_ms, 1),
            "extract_ms": round(extract_ms, 1),
            "wire_mb": round(len(serialized.buffer) / 1e6, 2),
            "rel_error": round(float(np.abs(restored - tensor).mean() / np.abs(tensor).mean()), 5),
        }

    print(json.dumps({
        "metric": "compression_throughput_10m",
        "value": results["BLOCKWISE_8BIT"]["compress_ms"],
        "unit": "ms",
        "device": {"platform": "cpu", "pinned": "host-only benchmark"},
        "extra": results,
    }))


if __name__ == "__main__":
    main()
