"""Per-step overhead of the SliceOptimizer decision broadcast, and what the
skip-count thinning buys (VERDICT r4 next-round #8).

Measures µs/step of `SliceOptimizer.step` on the virtual mesh with a trivial
gradient tree, far from any epoch boundary (the steady-state hot path), for
``max_broadcast_skip`` 0 vs N. On a single process the device broadcast itself
is cheap — the point is the CONTROL-PATH cost (tracker report + decision build +
collective dispatch) that thinning removes; on a real multi-host mesh the
skipped broadcast also removes a host round-trip per step.

Device telemetry (ISSUE 19) is armed for the whole run and doubles as a
regression guard: the steady-state loop must trigger ZERO recompiles after
warmup (a recompile storm here is a silent 1000x step-time bug), and a short
two-peer local-updates probe must produce a nonzero comm/compute overlap
efficiency from real optimizer steps (the ROADMAP item 2 yardstick).

Prints one JSON line."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import argparse
import json
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num_devices", type=int, default=8)
    parser.add_argument("--steps", type=int, default=400)
    parser.add_argument("--max_broadcast_skip", type=int, default=8)
    parser.add_argument("--no_blackbox", action="store_true",
                        help="skip the spool-armed measurement (ISSUE 17: the "
                             "black-box recorder must not move the hot path "
                             "out of its band)")
    parser.add_argument("--no_overlap_probe", action="store_true",
                        help="skip the two-peer overlap-efficiency probe "
                             "(ISSUE 19: real optimizer steps must emit a "
                             "nonzero comm/compute overlap ratio)")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform, describe_devices

    add_platform_arg(parser)
    args = parser.parse_args()
    flags = os.environ.get("XLA_FLAGS", "")
    if args.platform == "cpu" and "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.num_devices}"
        ).strip()
    apply_platform(args)

    import threading

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer
    from hivemind_tpu.telemetry.device import (
        COMPILE_TRACKER,
        STEP_TIMELINE,
        arm_device_telemetry,
        device_snapshot,
    )

    # armed for the whole benchmark: the band below must hold WITH telemetry on
    arm_device_telemetry()

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))

    def measure(max_skip: int) -> dict:
        opt = SliceOptimizer(
            mesh=mesh,
            params={"w": jax.device_put(np.zeros((8, 128), np.float32), sharding)},
            optimizer=optax.sgd(0.1), dht_factory=lambda: DHT(start=True),
            run_id=f"step_overhead_{max_skip}",
            # huge target: the loop below never reaches a boundary — pure hot path
            target_batch_size=1 << 30, batch_size_per_step=1,
            max_broadcast_skip=max_skip,
        )
        g = {"w": jax.device_put(np.ones((8, 128), np.float32), sharding)}
        try:
            for _ in range(20):  # warm the jits + the step-time EMA
                opt.step(g, batch_size=1)
            # past warmup every compile is a recompile-storm bug: the tracker
            # must not move during the measured loop (ISSUE 19 guard)
            compiles_before = COMPILE_TRACKER.total()
            # measure the CONTROL PATH alone (grads=None skips the jitted
            # accumulate, whose ~1 ms dispatch would swamp the decision cost)
            start = time.perf_counter()
            skipped = 0
            for _ in range(args.steps):
                if opt._skip_remaining > 0:
                    skipped += 1
                opt.step(None)
            elapsed = time.perf_counter() - start
            steady_state_compiles = COMPILE_TRACKER.total() - compiles_before
            assert steady_state_compiles == 0, (
                f"recompile storm in the steady-state loop: {steady_state_compiles} "
                f"compiles after warmup (sites: {COMPILE_TRACKER.counts()})"
            )
            return {
                "us_per_step": round(elapsed / args.steps * 1e6, 1),
                "skipped_fraction": round(skipped / args.steps, 3),
                "steady_state_compiles": steady_state_compiles,
            }
        finally:
            opt.shutdown()

    def measure_overlap() -> dict:
        """Two peers doing REAL optimizer steps (local updates + delayed state
        averaging, the canonical overlapped config): the background averaging
        round must overlap recorded compute, yielding a nonzero ratio."""
        import jax.numpy as jnp

        rng = np.random.RandomState(0)
        features = rng.randn(128, 4).astype(np.float32)
        targets = features @ rng.randn(4).astype(np.float32)

        from hivemind_tpu.optim import Optimizer

        first = DHT(start=True)
        maddrs = [str(m) for m in first.get_visible_maddrs()]
        dhts = [first, DHT(initial_peers=maddrs, start=True)]
        errors = []

        def run_peer(index, dht):
            try:
                opt = Optimizer(
                    dht=dht, run_id="overlap_probe", target_batch_size=32,
                    params={"w": jnp.zeros(4, jnp.float32)}, optimizer=optax.sgd(0.1),
                    batch_size_per_step=16, matchmaking_time=1.0, averaging_timeout=30,
                    average_state_every=1, target_group_size=2, verbose=False,
                    use_local_updates=True, delay_state_averaging=True,
                    tracker_opts=dict(min_refresh_period=0.3, default_refresh_period=0.5),
                )
                loss_grad = jax.jit(jax.value_and_grad(
                    lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2)
                ))
                local = np.random.RandomState(index)
                for _ in range(80):
                    if opt.local_epoch >= 3:
                        break
                    idx = local.choice(len(features), 16)
                    _, grads = loss_grad(opt.params, features[idx], targets[idx])
                    opt.step(grads)
                    time.sleep(0.1)
                opt.shutdown()
            except Exception as e:
                errors.append((index, repr(e)))

        threads = [threading.Thread(target=run_peer, args=(i, d)) for i, d in enumerate(dhts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        for dht in dhts:
            dht.shutdown()
        assert not errors, f"overlap probe peer failures: {errors}"
        summary = STEP_TIMELINE.overlap_summary()
        assert summary.get("rounds"), "no averaging round landed in the step timeline"
        best = max(r["overlap_ratio"] for r in STEP_TIMELINE.records())
        assert best > 0, (
            f"overlap efficiency is zero across {summary['rounds']} round(s): "
            "comm never overlapped recorded compute"
        )
        return {**summary, "best": best, "steps": len(STEP_TIMELINE.steps())}

    with_broadcast = measure(0)
    thinned = measure(args.max_broadcast_skip)
    spooled = None
    if not args.no_blackbox:
        # same hot path with the flight recorder armed: span finishes now fan
        # out to the spool writer's listener. The append is a buffered msgpack
        # pack + flush off the span's own lock, so the step stays in-band.
        import tempfile

        from hivemind_tpu.telemetry.blackbox import arm_blackbox, disarm_blackbox, read_spool

        with tempfile.TemporaryDirectory(prefix="slice_step_spool_") as spool_dir:
            arm_blackbox(spool_dir, peer="bench", metrics_interval=None)
            try:
                spooled = measure(0)
            finally:
                disarm_blackbox()
            _, spool_stats = read_spool(spool_dir)
            spooled["spool_frames"] = spool_stats["frames"]
    overlap = None if args.no_overlap_probe else measure_overlap()
    device = device_snapshot()
    print(json.dumps({
        "metric": "slice_step_decision_overhead_us",
        "value": with_broadcast["us_per_step"],
        "unit": "us/step (broadcast every step)",
        "device": describe_devices(),
        "extra": {
            "thinned_us_per_step": thinned["us_per_step"],
            "thinned_skipped_fraction": thinned["skipped_fraction"],
            "spooled_us_per_step": (spooled or {}).get("us_per_step"),
            "spool_frames": (spooled or {}).get("spool_frames"),
            "max_broadcast_skip": args.max_broadcast_skip,
            "num_devices": args.num_devices,
            "steps": args.steps,
            "steady_state_compiles": with_broadcast["steady_state_compiles"],
            "overlap": overlap,
            "device": {
                "compiles": (device.get("compiles") or {}).get("total", 0),
                "compile_seconds": (device.get("compiles") or {}).get("seconds", 0.0),
                "storms": (device.get("compiles") or {}).get("storms", 0),
                "transfer_bytes": device.get("transfer_bytes"),
            },
            "note": "single-process mesh: measures the control path; a real "
                    "multi-host mesh additionally saves one host round-trip "
                    "per skipped step",
        },
    }))


if __name__ == "__main__":
    main()
