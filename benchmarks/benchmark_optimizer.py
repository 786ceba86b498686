"""Collaborative optimizer harness (parity: reference benchmarks/benchmark_optimizer.py
— MLP peers, target_batch_size epochs, convergence check)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import argparse
import json
import threading
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num_peers", type=int, default=2)
    parser.add_argument("--target_batch_size", type=int, default=128)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--max_epochs", type=int, default=4)
    parser.add_argument("--hidden", type=int, default=64)
    mode = parser.add_mutually_exclusive_group()
    mode.add_argument("--dpu", action="store_true",
                      help="Delayed Parameter Updates: epoch transitions run in the "
                           "background, training continues during averaging")
    mode.add_argument("--local_updates", action="store_true",
                      help="async local-SGD: apply every step locally, average state "
                           "in the background with the delta rule so concurrent "
                           "steps survive")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform, describe_devices

    add_platform_arg(parser)
    args = parser.parse_args()
    apply_platform(args)
    import jax

    jax.devices()

    import jax.numpy as jnp
    import optax

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer

    rng = np.random.RandomState(0)
    true_w = rng.randn(args.hidden).astype(np.float32)
    X = rng.randn(1024, args.hidden).astype(np.float32)
    y = X @ true_w

    @jax.jit
    def loss_and_grad(params, xx, yy):
        fn = lambda p: jnp.mean((xx @ p["w"] - yy) ** 2)
        return jax.value_and_grad(fn)(params)

    first = DHT(start=True)
    maddrs = [str(m) for m in first.get_visible_maddrs()]
    dhts = [first] + [DHT(initial_peers=maddrs, start=True) for _ in range(args.num_peers - 1)]
    results = {}

    def peer_loop(index):
        mode_opts = {}
        if args.dpu:
            mode_opts["delay_optimizer_step"] = True
        if args.local_updates:
            # the canonical local-SGD combination (optim/optimizer.py docstring):
            # background state averaging + delta rule to protect concurrent steps
            mode_opts.update(
                use_local_updates=True, delta_rule_averaging=True, delay_state_averaging=True
            )
        opt = Optimizer(
            dht=dhts[index], run_id="bench_opt", target_batch_size=args.target_batch_size,
            params={"w": jnp.zeros(args.hidden)}, optimizer=optax.sgd(0.2),
            batch_size_per_step=args.batch_size, matchmaking_time=1.5,
            target_group_size=args.num_peers,
            tracker_opts=dict(min_refresh_period=0.3), **mode_opts,
        )
        local = np.random.RandomState(index)
        first_loss = last_loss = None
        steps = 0
        while opt.local_epoch < args.max_epochs and steps < 200:
            idx = local.choice(len(X), args.batch_size)
            loss, grads = loss_and_grad(opt.params, X[idx], y[idx])
            first_loss = first_loss if first_loss is not None else float(loss)
            last_loss = float(loss)
            opt.step(grads)
            steps += 1
            time.sleep(0.2)
        results[index] = (first_loss, last_loss, opt.local_epoch)
        opt.shutdown()

    start = time.perf_counter()
    threads = [threading.Thread(target=peer_loop, args=(i,)) for i in range(args.num_peers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start

    print(json.dumps({
        "metric": "optimizer_loss_reduction",
        "value": round(min(r[0] / max(r[1], 1e-9) for r in results.values()), 2),
        "unit": "x",
        "device": describe_devices(),
        "extra": {
            "peers": args.num_peers, "seconds": round(elapsed, 1),
            "mode": "dpu" if args.dpu else ("local_updates" if args.local_updates else "sync"),
            "per_peer": {str(k): {"first": round(v[0], 4), "last": round(v[1], 4), "epoch": v[2]} for k, v in results.items()},
        },
    }))
    for dht in dhts:
        dht.shutdown()


if __name__ == "__main__":
    main()
