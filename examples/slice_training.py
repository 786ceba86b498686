"""Multi-host slice training: a whole TPU slice (several hosts, one jax process
each) trains as ONE swarm peer.

This is the end-to-end recipe for the two-tier communication backend
(SURVEY §5, docs/design_notes.md "multi-host slices"):

- every process runs the SAME jitted train step over the shared ``Mesh`` —
  gradients ride ICI via pjit/shard_map exactly as in any SPMD program;
- the model averages with the REST OF THE SWARM (other slices, GPU peers,
  volunteer laptops) through :class:`SliceAverager`: process 0 alone talks to the
  DHT/matchmaking/all-reduce, the other hosts join only mesh collectives.

The flow is the local-SGD family (reference use_local_updates): local optax steps
between swarm rounds, parameters averaged every ``--steps_per_round``.

Launch one process per host, e.g. a 2-process CPU rehearsal of a v4-32 topology:

    python examples/slice_training.py --platform cpu --devices_per_proc 4 \
        --num_processes 2 --process_id 0 --coordinator 127.0.0.1:9911 &
    python examples/slice_training.py --platform cpu --devices_per_proc 4 \
        --num_processes 2 --process_id 1 --coordinator 127.0.0.1:9911

Process 0 additionally accepts ``--initial_peers`` (the swarm to join) and
prints its own DHT address for others. On a real slice drop --devices_per_proc
(the chips are discovered) and run one process per host.
"""

from __future__ import annotations

import argparse
import os


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--run_id", default="slice_demo")
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0 for jax.distributed.initialize "
                             "(omit for single-process)")
    parser.add_argument("--num_processes", type=int, default=1)
    parser.add_argument("--process_id", type=int, default=0)
    parser.add_argument("--devices_per_proc", type=int, default=0,
                        help=">0: force that many virtual CPU devices (rehearsal)")
    parser.add_argument("--initial_peers", nargs="*", default=[],
                        help="swarm bootstrap (used by process 0 only)")
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--steps_per_round", type=int, default=20)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--learning_rate", type=float, default=0.05)
    parser.add_argument("--target_group_size", type=int, default=2)
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform

    add_platform_arg(parser)
    args = parser.parse_args()
    if args.devices_per_proc > 0:
        # a rehearsal of several hosts on one machine: virtual CPU devices, never the
        # chip — the processes of one machine cannot share it
        args.platform = "cpu"
        print(f"rehearsal: {args.devices_per_proc} virtual devices, platform pinned to cpu", flush=True)
        # replace (not prepend) any inherited device-count flag: with duplicates
        # XLA honors the last one, so an inherited value would win
        kept = [
            flag for flag in os.environ.get("XLA_FLAGS", "").split()
            if not flag.startswith("--xla_force_host_platform_device_count")
        ]
        os.environ["XLA_FLAGS"] = " ".join(
            kept + [f"--xla_force_host_platform_device_count={args.devices_per_proc}"]
        )
    apply_platform(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if args.coordinator:
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    from hivemind_tpu.averaging import SliceAverager
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.utils.logging import get_logger

    logger = get_logger(f"slice_trainer.p{jax.process_index()}")

    devices = np.array(jax.devices())
    mesh = Mesh(devices.reshape(-1), ("dp",))
    logger.info(f"mesh: {devices.size} devices across {jax.process_count()} processes")

    # a toy regression model, dp-sharded batch, replicated params — the slice's
    # ICI carries the gradient psum exactly as a real model's would
    rng = np.random.RandomState(0)  # SAME init on every process (replicated params)
    params = {
        "w": jax.device_put(
            rng.randn(args.dim, args.dim).astype(np.float32) * 0.1,
            NamedSharding(mesh, P()),
        ),
        "b": jax.device_put(np.zeros(args.dim, np.float32), NamedSharding(mesh, P())),
    }
    target_w = np.eye(args.dim, dtype=np.float32)  # learn the identity map

    optimizer = optax.adam(args.learning_rate)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def dht_factory():
        dht = DHT(initial_peers=args.initial_peers, start=True)
        for maddr in dht.get_visible_maddrs():
            logger.info(f"swarm members can join via: --initial_peers {maddr}")
        return dht

    slice_avg = SliceAverager(
        params, mesh, dht_factory,
        prefix=f"{args.run_id}_params", start=True,
        target_group_size=args.target_group_size, min_matchmaking_time=1.0,
    )

    batch_sharding = NamedSharding(mesh, P("dp"))
    data_rng = np.random.RandomState(100 + jax.process_index())
    assert args.batch_size % jax.process_count() == 0, (
        f"batch_size {args.batch_size} must divide evenly across "
        f"{jax.process_count()} processes"
    )
    local_rows = args.batch_size // jax.process_count()
    assert local_rows and local_rows % len(mesh.local_devices) == 0, (
        "per-process batch must tile the local devices"
    )
    global_shape = (args.batch_size, args.dim)
    for step in range(1, args.steps + 1):
        # each process feeds ITS OWN rows of the global batch (data parallelism
        # across hosts); the global array is assembled from process-local shards
        x_host = data_rng.randn(local_rows, args.dim).astype(np.float32)
        y_host = x_host @ target_w
        x = jax.make_array_from_process_local_data(batch_sharding, x_host, global_shape)
        y = jax.make_array_from_process_local_data(batch_sharding, y_host, global_shape)
        params, opt_state, loss = train_step(params, opt_state, x, y)
        if step % args.steps_per_round == 0:
            slice_avg.device_tree = params
            ok = slice_avg.step(timeout=30)
            if ok:
                params = slice_avg.device_tree
                # adam moments describe the pre-average trajectory; restarting
                # them after adopting the swarm average is the stable choice for
                # this demo (delta-rule integration lives in the full Optimizer)
                opt_state = optimizer.init(params)
            logger.info(f"step {step} loss {float(loss):.5f} swarm_round_ok={ok}")
        elif step % 10 == 0:
            logger.info(f"step {step} loss {float(loss):.5f}")

    final = float(loss)
    logger.info(f"done: final loss {final:.5f}")
    slice_avg.shutdown()
    print(f"FINAL_LOSS {jax.process_index()} {final}", flush=True)


if __name__ == "__main__":
    main()
