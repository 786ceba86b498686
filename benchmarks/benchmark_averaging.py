"""Butterfly all-reduce benchmark (parity: reference benchmarks/benchmark_averaging.py
— 16 peers, groups of 4, ~8.6M params). Reports rounds, success rate, and the driver
north-star: effective GB/s per peer."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import argparse
import json
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num_peers", type=int, default=8)
    parser.add_argument("--target_group_size", type=int, default=4)
    parser.add_argument("--num_rounds", type=int, default=3)
    parser.add_argument("--num_params", type=int, default=1_000_000)
    parser.add_argument("--compression", default="FLOAT16",
                        help="wire codec: a CompressionType name (FLOAT16, NONE, ...) or a "
                             "wire-tier alias (none/float16/uniform8/blockwise8, case-"
                             "insensitive). The 8-bit tiers negotiate per-link error "
                             "feedback automatically (ISSUE 11)")
    parser.add_argument("--part_size_bytes", type=int, default=None,
                        help="pre-compression part size (default: the library default, "
                             "2 MiB — measured fastest on loopback; clamped to the mux cap)")
    parser.add_argument("--min_matchmaking_time", type=float, default=2.0,
                        help="leader's group-collection window; on loopback the group "
                             "fills (and begins early) well before 1s, so the floor is "
                             "pure overhead — lower it when benchmarking bandwidth")
    parser.add_argument("--simulated_link_mbps", type=float, default=None,
                        help="throttle every tensor-part/delta payload to this per-link "
                             "bandwidth via the chaos engine's byte-proportional `throttle` "
                             "action — the WAN regime the quantized tiers exist for. "
                             "Unthrottled loopback is latency-bound, so wire-codec wins "
                             "are only representative under a link budget")
    parser.add_argument("--smoke", action="store_true",
                        help="tier-1-safe regression mode: tiny swarm + payload, exits "
                             "nonzero unless every round succeeds (wired into tests so "
                             "throughput-path breakage fails loudly)")
    args = parser.parse_args()
    if args.smoke:
        args.num_peers, args.target_group_size = 2, 2
        args.num_rounds, args.num_params = 1, 10_000
        args.min_matchmaking_time = 0.5

    import jax

    jax.config.update("jax_platforms", "cpu")  # host-only benchmark: pinned, and the result says so
    jax.devices()

    from hivemind_tpu.averaging import DecentralizedAverager
    from hivemind_tpu.compression import CompressionType, get_codec
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.telemetry import LEDGER, REGISTRY, watchdog_summary

    first = DHT(start=True)
    maddrs = [str(m) for m in first.get_visible_maddrs()]
    dhts = [first] + [DHT(initial_peers=maddrs, start=True) for _ in range(args.num_peers - 1)]
    # wire-tier aliases (uniform8 etc.) map onto the enum; enum names pass through
    tier_aliases = {"none": "NONE", "float16": "FLOAT16", "uniform8": "UNIFORM_8BIT",
                    "blockwise8": "BLOCKWISE_8BIT", "meanstd16": "MEANSTD_16BIT",
                    "quantile8": "QUANTILE_8BIT"}
    compression_name = tier_aliases.get(args.compression.lower(), args.compression.upper())
    codec = get_codec(getattr(CompressionType, compression_name))
    if args.simulated_link_mbps:
        from hivemind_tpu.resilience import CHAOS

        rate_bytes_s = args.simulated_link_mbps * 125_000.0
        CHAOS.add_rule("allreduce.load", "throttle", rate=rate_bytes_s)
        CHAOS.add_rule("allreduce.reduce", "throttle", rate=rate_bytes_s)
    averager_kwargs = {}
    if args.part_size_bytes is not None:
        averager_kwargs["part_size_bytes"] = args.part_size_bytes
    averagers = []
    for i, dht in enumerate(dhts):
        rng = np.random.RandomState(i)
        tensors = [rng.randn(args.num_params).astype(np.float32)]
        averagers.append(
            DecentralizedAverager(
                tensors, dht, prefix="bench", start=True,
                target_group_size=args.target_group_size,
                min_matchmaking_time=args.min_matchmaking_time, compression=codec,
                initial_group_bits="" if args.num_peers <= args.target_group_size else "0",
                **averager_kwargs,
            )
        )

    successes = attempts = 0
    start = time.perf_counter()
    for round_index in range(args.num_rounds):
        controls = [a.step(wait=False, timeout=60) for a in averagers]
        for control in controls:
            attempts += 1
            try:
                control.result(timeout=90)
                successes += 1
            except Exception:
                pass
    elapsed = time.perf_counter() - start

    bytes_per_peer_round = args.num_params * 4 * 2  # send + receive one vector's worth
    gbps_per_peer = bytes_per_peer_round * args.num_rounds / elapsed / 1e9
    print(json.dumps({
        "metric": "averaging_gbps_per_peer",
        "value": round(gbps_per_peer, 4),
        "unit": "GB/s/peer",
        "device": {"platform": "cpu", "pinned": "host-only benchmark"},
        "extra": {
            "peers": args.num_peers, "rounds": args.num_rounds,
            "params": args.num_params, "success_rate": successes / max(attempts, 1),
            "compression": compression_name.lower(),
            "simulated_link_mbps": args.simulated_link_mbps,
            "seconds_per_round": round(elapsed / args.num_rounds, 3),
            # the registry saw every matchmaking/all-reduce/DHT event of this
            # swarm: embed it so BENCH artifacts carry the per-phase breakdown
            # (VERDICT r5: five rounds of artifacts had none)
            "telemetry": REGISTRY.snapshot(),
            # per-round attribution (ISSUE 8): rounds, mean/p95 phase durations
            # and straggler scores from the ledger, plus event-loop stall count
            # and max lag — a regressed headline number then names its cause
            "attribution": {"ledger": LEDGER.summary(), "watchdog": watchdog_summary()},
        },
    }))
    for averager in averagers:
        averager.shutdown()
    for dht in dhts:
        dht.shutdown()
    if args.smoke and successes != attempts:
        sys.exit(f"smoke mode: only {successes}/{attempts} averaging steps succeeded")


if __name__ == "__main__":
    main()
