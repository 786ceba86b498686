"""DHT store/get benchmark (parity: reference benchmarks/benchmark_dht.py — baselines
store 14.9ms/key, get 6.6ms/key at 1024 peers)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import argparse
import json
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num_peers", type=int, default=16)
    parser.add_argument("--num_keys", type=int, default=200)
    parser.add_argument("--expiration", type=float, default=300.0)
    parser.add_argument("--max_connections", type=int, default=0,
                        help="per-node connection-manager cap (bounds fds at scale; 0 = unlimited)")
    parser.add_argument("--batch_size", type=int, default=64,
                        help="keys per store_many/get_many call (reference benchmarks batch 64)")
    parser.add_argument("--declare_storm", action="store_true",
                        help="expert declare-storm mode (ISSUE 13 / ROADMAP item 5 "
                             "follow-up): declare a full expert grid through "
                             "store_many's shared-traversal batching and report "
                             "traversals saved, store RPC count, and leaf recall")
    parser.add_argument("--grid", default="storm.[0:16].[0:16]",
                        help="declare-storm expert grid pattern (all cells declared)")
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")  # host-only benchmark: pinned, and the result says so
    jax.devices()

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.utils.timed_storage import get_dht_time

    if args.declare_storm:
        return declare_storm(args)

    p2p_opts = {"max_connections": args.max_connections} if args.max_connections else {}
    first = DHT(start=True, **p2p_opts)
    maddrs = [str(m) for m in first.get_visible_maddrs()]
    dhts = [first] + [
        DHT(initial_peers=maddrs, start=True, **p2p_opts)
        for _ in range(args.num_peers - 1)
    ]

    # batched like the reference benchmark (batch 64): one store_many/get_many call
    # runs the per-key beam searches CONCURRENTLY on the node's event loop
    store_ok = get_ok = 0
    batches = [list(range(i, min(i + args.batch_size, args.num_keys)))
               for i in range(0, args.num_keys, args.batch_size)]

    start = time.perf_counter()
    for batch_index, batch in enumerate(batches):
        writer = dhts[batch_index % len(dhts)]
        expiration = get_dht_time() + args.expiration

        async def _store(_dht, node, batch=batch, expiration=expiration):
            return await node.store_many(
                [f"bench_key_{i}" for i in batch], list(batch), expiration
            )

        result = writer.run_coroutine(_store)
        store_ok += sum(bool(v) for v in result.values())
    store_time = time.perf_counter() - start

    start = time.perf_counter()
    for batch_index, batch in enumerate(batches):
        reader = dhts[(batch_index + 7) % len(dhts)]

        async def _get(_dht, node, batch=batch):
            return await node.get_many([f"bench_key_{i}" for i in batch])

        found = reader.run_coroutine(_get)
        get_ok += sum(
            1 for i in batch
            if found.get(f"bench_key_{i}") is not None and found[f"bench_key_{i}"].value == i
        )
    get_time = time.perf_counter() - start

    print(json.dumps({
        "metric": "dht_store_get_latency",
        "value": round(store_time / args.num_keys * 1000, 3),
        "unit": "ms/store",
        "device": {"platform": "cpu", "pinned": "host-only benchmark"},
        "extra": {
            "peers": args.num_peers, "keys": args.num_keys,
            "store_ms": round(store_time / args.num_keys * 1000, 3),
            "get_ms": round(get_time / args.num_keys * 1000, 3),
            "store_success": store_ok / args.num_keys,
            "get_success": get_ok / args.num_keys,
        },
    }))
    for dht in dhts:
        dht.shutdown()


def declare_storm(args):
    """Declare every cell of an expert grid (leaf + all prefixes per uid — the
    bulk-republish shape every serving peer emits each update period) and
    surface the PR 12 ``store_many`` shared-traversal batching in the DHT
    benchmark proper: traversals saved, store RPCs issued, wall time, and the
    part that keeps the optimization honest — leaf AND prefix recall read back
    through the real resolution path (the naive version of this batching
    sharded prefix dicts and collapsed recall; the witness fallback is what
    this mode regression-checks at benchmark scale)."""
    import itertools
    import re

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe.server.dht_handler import declare_experts, get_experts
    from hivemind_tpu.telemetry import REGISTRY

    # expand "storm.[0:16].[0:16]" into every grid cell
    blocks = args.grid.split(".")
    dims = []
    for block in blocks[1:]:
        match = re.fullmatch(r"\[(\d+):(\d+)\]", block)
        assert match, f"declare-storm grid blocks must be [lo:hi], got {block!r}"
        dims.append(range(int(match.group(1)), int(match.group(2))))
    uids = [
        ".".join([blocks[0], *map(str, cell)]) for cell in itertools.product(*dims)
    ]

    p2p_opts = {"max_connections": args.max_connections} if args.max_connections else {}
    first = DHT(start=True, **p2p_opts)
    maddrs = [str(m) for m in first.get_visible_maddrs()]
    dhts = [first] + [
        DHT(initial_peers=maddrs, start=True, **p2p_opts)
        for _ in range(args.num_peers - 1)
    ]

    def metric_total(name, label=None):
        metric = REGISTRY.get(name)
        if metric is None:
            return 0.0
        total = 0.0
        for key, child in metric.series():
            if label is None or label in key:
                total += getattr(child, "count", None) or child.value
        return total

    def snapshot():
        return {
            "traversals_saved": metric_total("hivemind_dht_store_traversals_saved_total"),
            "store_rpcs": metric_total("hivemind_dht_rpc_latency_seconds", "store"),
            "find_rpcs": metric_total("hivemind_dht_rpc_latency_seconds", "find"),
        }

    before = snapshot()
    start = time.perf_counter()
    declare_experts(dhts[0], uids, expiration_time=get_dht_time_() + args.expiration)
    declare_seconds = time.perf_counter() - start
    after = snapshot()

    # recall through the real resolution path, from a DIFFERENT peer
    reader = dhts[-1]
    found = get_experts(reader, uids)
    leaf_recall = sum(info is not None for info in found) / len(uids)
    # prefix recall: every first-dimension prefix must resolve its coordinate
    # dict (this is what the witness fallback protects — see dht/node.py)
    async def _prefix_coords(_dht, node):
        prefixes = [blocks[0]] if len(dims) == 1 else [
            f"{blocks[0]}.{i}" for i in dims[0]
        ]
        found = await node.get_many(prefixes)
        ok = 0
        for prefix in prefixes:
            entry = found.get(prefix)
            if entry is not None and isinstance(entry.value, dict) and entry.value:
                ok += 1
        return ok / len(prefixes)

    prefix_recall = reader.run_coroutine(_prefix_coords)

    print(json.dumps({
        "metric": "dht_declare_storm",
        "value": round(len(uids) / declare_seconds, 1),
        "unit": "experts_declared/s",
        "device": {"platform": "cpu", "pinned": "host-only benchmark"},
        "extra": {
            "peers": args.num_peers, "experts": len(uids), "grid": args.grid,
            "declare_seconds": round(declare_seconds, 3),
            "store_traversals_saved": after["traversals_saved"] - before["traversals_saved"],
            "store_rpcs": after["store_rpcs"] - before["store_rpcs"],
            "find_rpcs": after["find_rpcs"] - before["find_rpcs"],
            "leaf_recall": round(leaf_recall, 4),
            "prefix_recall": round(prefix_recall, 4),
        },
    }))
    failures = []
    if leaf_recall < 0.99:
        failures.append(f"leaf recall {leaf_recall}")
    if prefix_recall < 0.99:
        failures.append(f"prefix recall {prefix_recall}")
    for dht in dhts:
        dht.shutdown()
    if failures:
        raise SystemExit(f"declare-storm recall below bar: {failures}")


def get_dht_time_():
    from hivemind_tpu.utils.timed_storage import get_dht_time

    return get_dht_time()


if __name__ == "__main__":
    main()
