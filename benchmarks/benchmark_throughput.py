"""MoE server throughput (parity: reference benchmarks/benchmark_throughput.py —
baselines 28,581 samples/s fwd+bwd, 97,604 fwd-only on a GTX 1080 Ti)."""

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
    parser.add_argument("--num_experts", type=int, default=4)
    parser.add_argument("--hidden_dim", type=int, default=1024)
    parser.add_argument("--num_clients", type=int, default=8)
    parser.add_argument("--batches_per_client", type=int, default=8)
    parser.add_argument("--batch_size", type=int, default=512)
    parser.add_argument("--backward", action="store_true", help="also run backward passes")
    parser.add_argument("--expert_cls", default="ffn",
                        help="registered expert class; input shape comes from its "
                             "registry schema (block classes take [batch, seq, hid])")
    parser.add_argument("--decode_clients", type=int, default=0,
                        help=">0: measure KV-session decoding — this many concurrent "
                             "1-token streams through one block (continuous batching)")
    parser.add_argument("--decode_steps", type=int, default=64,
                        help="tokens per decode client")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform, describe_devices

    add_platform_arg(parser)
    args = parser.parse_args()

    apply_platform(args)
    import jax

    jax.devices()

    import optax

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteExpert, Server, get_experts

    uids = [f"bench_expert.{i}" for i in range(args.num_experts)]
    server = Server.create(
        expert_uids=uids, expert_cls=args.expert_cls, hidden_dim=args.hidden_dim,
        max_batch_size=8192, start=True, optim_factory=lambda: optax.sgd(1e-3),
    )
    from hivemind_tpu.moe.server.layers import name_to_input

    # the registry schema defines each class's input shape; swap in batch_size
    sample = name_to_input[args.expert_cls](args.batch_size, args.hidden_dim)
    assert not isinstance(sample, tuple), "multi-input expert classes are not benchmarked here"
    sample_shape = sample.shape
    time.sleep(1.0)
    client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
    infos = get_experts(client_dht, uids)
    assert all(info is not None for info in infos), "experts not discoverable"
    experts = [RemoteExpert(info, client_dht.node.p2p) for info in infos]

    if args.decode_clients:
        # continuous-batching decode: N clients each own a KV session on ONE block
        # and step one token at a time; concurrent steps merge into vmapped device
        # calls server-side (A/B with HIVEMIND_TPU_DECODE_BATCHING=0)
        import uuid

        block = experts[0]
        prompt, hid = 8, args.hidden_dim
        sessions = [uuid.uuid4().hex for _ in range(args.decode_clients)]
        rng = np.random.RandomState(0)
        prompts = rng.randn(args.decode_clients, 1, prompt, hid).astype(np.float32)
        for session, chunk in zip(sessions, prompts):
            block.decode_np(chunk, session, reset=True)
        token = rng.randn(1, 1, hid).astype(np.float32)
        done = [0] * args.decode_clients
        errors = []

        # untimed warmup round: trigger the batched-step compiles (pow2 buckets)
        # so short measured runs aren't dominated by jit time
        warmup = [threading.Thread(target=block.decode_np, args=(token, s, False))
                  for s in sessions]
        for t in warmup:
            t.start()
        for t in warmup:
            t.join()

        def decode_loop(index: int):
            try:
                for _ in range(args.decode_steps):
                    block.decode_np(token, sessions[index], reset=False)
                    done[index] += 1
            except Exception as e:
                errors.append((index, repr(e)))

        start = time.perf_counter()
        threads = [threading.Thread(target=decode_loop, args=(i,))
                   for i in range(args.decode_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        manager = server.handler.decode_sessions
        print(json.dumps({
            "metric": "moe_decode_tokens_per_sec_aggregate",
            "value": round(sum(done) / elapsed, 1),
            "unit": "tokens/s",
            "device": describe_devices(),
            "extra": {
                "decode_clients": args.decode_clients, "steps_per_client": args.decode_steps,
                "hidden_dim": args.hidden_dim, "expert_cls": args.expert_cls,
                "batching": manager.batching_enabled,
                "batched_signatures": sorted(s for _, s in manager._batched_fns),
                "errors": errors[:3],
            },
        }))
        client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()
        return

    processed = [0] * args.num_clients
    errors = []

    def client_loop(index: int):
        rng = np.random.RandomState(index)
        try:
            for b in range(args.batches_per_client):
                x = rng.randn(*sample_shape).astype(np.float32)
                expert = experts[(index + b) % len(experts)]
                out = expert.forward_np(x)[0]
                if args.backward:
                    expert.backward_np(x, np.ones_like(out))
                processed[index] += args.batch_size
        except Exception as e:
            errors.append((index, repr(e)))

    start = time.perf_counter()
    threads = [threading.Thread(target=client_loop, args=(i,)) for i in range(args.num_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start

    total = sum(processed)
    print(json.dumps({
        "metric": "moe_server_samples_per_sec" + ("_fwd_bwd" if args.backward else "_fwd"),
        "value": round(total / elapsed, 1),
        "unit": "samples/s",
        "device": describe_devices(),
        "extra": {
            "experts": args.num_experts, "clients": args.num_clients,
            "hidden_dim": args.hidden_dim, "expert_cls": args.expert_cls,
            "errors": errors[:3],
        },
    }))
    client_dht.shutdown()
    server.shutdown()
    server.dht.shutdown()


if __name__ == "__main__":
    main()
