"""Petals-style Llama block serving from a real checkpoint (BASELINE config #5):
synthesizes an HF-layout sharded safetensors checkpoint at the requested shape
(or uses --checkpoint), loads it into llama_block backends (optionally int8
weight-only), serves over RPC, and measures KV-cache decode tok/s through
RemoteSequential."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # repo root

import argparse
import json
import tempfile
import threading
import time
from pathlib import Path

import numpy as np


def _is_shed(error: BaseException) -> bool:
    """True when the terminal error (or anything on its cause chain — decode
    failover wraps the typed shed in a RuntimeError) is a server load-shed."""
    from hivemind_tpu.telemetry.serving import is_overload_error

    seen = set()
    while error is not None and id(error) not in seen:
        seen.add(id(error))
        if is_overload_error(error):
            return True
        error = error.__cause__ or error.__context__
    return False


def synthesize_checkpoint(path: Path, hidden: int, heads: int, kv_heads: int,
                          inner: int, layers: int) -> None:
    from safetensors.numpy import save_file

    rng = np.random.RandomState(0)
    (path / "config.json").write_text(json.dumps({
        "hidden_size": hidden, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "intermediate_size": inner,
        "num_hidden_layers": layers, "rope_theta": 10000.0,
    }))
    head_dim = hidden // heads
    weight_map = {}
    scale = 1.0 / np.sqrt(hidden)
    for layer in range(layers):
        prefix = f"model.layers.{layer}."
        tensors = {
            prefix + "self_attn.q_proj.weight": rng.randn(heads * head_dim, hidden) * scale,
            prefix + "self_attn.k_proj.weight": rng.randn(kv_heads * head_dim, hidden) * scale,
            prefix + "self_attn.v_proj.weight": rng.randn(kv_heads * head_dim, hidden) * scale,
            prefix + "self_attn.o_proj.weight": rng.randn(hidden, hidden) * scale,
            prefix + "mlp.gate_proj.weight": rng.randn(inner, hidden) * scale,
            prefix + "mlp.up_proj.weight": rng.randn(inner, hidden) * scale,
            prefix + "mlp.down_proj.weight": rng.randn(hidden, inner) * scale,
            prefix + "input_layernorm.weight": np.ones(hidden),
            prefix + "post_attention_layernorm.weight": np.ones(hidden),
        }
        shard = f"model-{layer:05d}-of-{layers:05d}.safetensors"
        save_file({k: v.astype(np.float32) for k, v in tensors.items()}, path / shard)
        weight_map.update({name: shard for name in tensors})
    (path / "model.safetensors.index.json").write_text(json.dumps({"weight_map": weight_map}))


def run_multi_client(args, checkpoint: Path) -> None:
    """Skewed multi-tenant load generator (ISSUE 13): one HOT client decoding
    flat-out + N paced background clients, each with its own DHT identity (the
    server attributes and rate-limits per client id). Optional second replica
    of every block (multi-value DHT records; clients balance/hedge/fail over)
    and a mid-run crash-kill of that replica. Emits per-client tok/s and p99
    step latency; ANY non-shed client-visible failure voids the run (exit 1),
    and with --client_rate armed a shed on a BACKGROUND client (the hot tenant
    eating someone else's budget) also voids it."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from hivemind_tpu.moe.server.llama_loader import load_llama_blocks
    from hivemind_tpu.moe.server.server import Server
    from hivemind_tpu.telemetry import REGISTRY
    from hivemind_tpu.utils.platform import describe_devices

    backends, config = load_llama_blocks(checkpoint, uid_prefix="lb.")
    num_blocks = len(backends)
    dht_primary = DHT(start=True)
    maddrs = [str(m) for m in dht_primary.get_visible_maddrs()]
    server_primary = Server(
        dht_primary, backends, decode_max_len=args.decode_max_len,
        activation_compression=args.activation_compression,
        client_rate=args.client_rate, client_burst=args.client_burst,
    )
    server_primary.run_in_background(await_ready=True)
    dht_replica = server_replica = None
    if args.replicas == 2:
        backends_replica, _config = load_llama_blocks(checkpoint, uid_prefix="lb.")
        dht_replica = DHT(initial_peers=maddrs, start=True)
        server_replica = Server(
            dht_replica, backends_replica, decode_max_len=args.decode_max_len,
            activation_compression=args.activation_compression,
            client_rate=args.client_rate, client_burst=args.client_burst,
        )
        server_replica.run_in_background(await_ready=True)
    time.sleep(1.0)

    rng = np.random.RandomState(1)
    hidden = rng.randn(1, args.prompt + args.generate, config.hidden_size).astype(np.float32)
    specs = [{"name": "hot", "interval": 0.0}] + [
        {"name": f"bg{i}", "interval": args.background_interval}
        for i in range(args.multi_client)
    ]
    stop = threading.Event()
    report = {}
    killed = {"at": None}

    def run_client(spec):
        client_dht = DHT(initial_peers=maddrs, start=True)
        pipe = RemoteSequential(client_dht, "lb.", num_blocks)
        latencies, failures = [], []
        tokens = sheds = episodes = 0
        started = time.perf_counter()
        try:
            while not stop.is_set():
                episodes += 1
                session = f"{spec['name']}_{episodes}"
                try:
                    pipe.decode_step(hidden[:, : args.prompt], session, reset=True)
                except Exception as e:
                    if _is_shed(e):
                        sheds += 1
                        time.sleep(0.1)
                        continue
                    failures.append(repr(e))
                    break
                for t in range(args.generate):
                    if stop.is_set():
                        break
                    pos = args.prompt + t
                    step_start = time.perf_counter()
                    try:
                        pipe.decode_step(hidden[:, pos : pos + 1], session)
                    except Exception as e:
                        if _is_shed(e):
                            sheds += 1
                            time.sleep(0.1)
                            break  # bucket dry: restart a fresh episode when refilled
                        failures.append(repr(e))
                        break
                    latencies.append(time.perf_counter() - step_start)
                    tokens += 1
                    if spec["interval"]:
                        time.sleep(spec["interval"])
                else:
                    pipe.close_decode_session(session)
                    continue
                pipe.close_decode_session(session)
                if failures:
                    break
        finally:
            elapsed = max(time.perf_counter() - started, 1e-9)
            entry = {
                "tokens": tokens,
                "tok_s": round(tokens / elapsed, 2),
                "episodes": episodes,
                "sheds": sheds,
                "failures": failures,
            }
            if latencies:
                entry["p50_ms"] = round(float(np.percentile(latencies, 50)) * 1e3, 1)
                entry["p99_ms"] = round(float(np.percentile(latencies, 99)) * 1e3, 1)
            report[spec["name"]] = entry
            client_dht.shutdown()

    def run_killer():
        delay = args.kill_replica_at * args.multi_duration
        if stop.wait(delay):
            return
        killed["at"] = round(delay, 2)
        print(f"# crash-killing replica 2 at t={delay:.1f}s", file=sys.stderr)
        dht_replica.shutdown()  # the power cord: transport dies, no shutdown

    client_threads = [threading.Thread(target=run_client, args=(spec,)) for spec in specs]
    threads = list(client_threads)
    if args.kill_replica_at and dht_replica is not None:
        threads.append(threading.Thread(target=run_killer))
    for thread in threads:
        thread.start()
    time.sleep(args.multi_duration)
    stop.set()
    for thread in threads:
        thread.join(timeout=60)
    # a client wedged past the join timeout never wrote its report entry, and
    # the verdicts below only inspect entries that exist — a hung client must
    # be a hard failure, not a vacuous pass
    hung = [
        spec["name"] for spec, thread in zip(specs, client_threads)
        if thread.is_alive() or spec["name"] not in report
    ]

    def metric_series(name):
        metric = REGISTRY.get(name)
        if metric is None:
            return {}
        return {",".join(k) or "_": round(c.value, 1) for k, c in metric.series()}

    total_tok_s = round(sum(entry.get("tok_s", 0.0) for entry in report.values()), 2)
    background = [entry for name, entry in report.items() if name != "hot"]
    extra = {
        "clients": report,
        "hot_tok_s": report.get("hot", {}).get("tok_s"),
        "background_tok_s_mean": round(
            sum(e.get("tok_s", 0.0) for e in background) / max(len(background), 1), 2
        ),
        "background_p99_ms_max": max(
            (e.get("p99_ms", 0.0) for e in background), default=None
        ),
        "replicas": args.replicas,
        "killed_replica_at_s": killed["at"],
        "client_rate": args.client_rate,
        "hedges": metric_series("hivemind_moe_hedge_total"),
        "replica_failovers": sum(metric_series("hivemind_moe_replica_failover_total").values()),
        "admission_sheds": sum(metric_series("hivemind_moe_admission_shed_total").values()),
        "layers": num_blocks, "hidden": config.hidden_size,
        "prompt": args.prompt, "generate": args.generate,
        "duration_s": args.multi_duration, "smoke": args.smoke,
    }
    print(json.dumps({
        "metric": "llama_multi_client_decode",
        "device": describe_devices(),
        "value": total_tok_s,
        "unit": "tok/s",
        "extra": extra,
    }))
    # teardown before verdicts so a failing run still cleans up
    for server in (server_primary, server_replica):
        if server is not None:
            server.shutdown()
    for dht in (dht_primary,) + ((dht_replica,) if killed["at"] is None and dht_replica is not None else ()):
        dht.shutdown()

    if hung:
        raise SystemExit(f"client thread(s) hung or unreported (run void): {hung}")
    hard_failures = {
        name: entry["failures"] for name, entry in report.items() if entry["failures"]
    }
    if hard_failures:
        raise SystemExit(f"client-visible request failures (run void): {hard_failures}")
    if args.client_rate and any(entry.get("sheds") for entry in background):
        raise SystemExit(
            "fair-share violated: background clients were shed while the hot "
            f"client saturated its bucket: {report}"
        )
    if not all(entry.get("tokens") for entry in report.values()):
        raise SystemExit(f"a client decoded zero tokens (run void): {report}")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--checkpoint", default=None, help="existing HF-layout dir")
    parser.add_argument("--hidden_dim", type=int, default=1024)
    parser.add_argument("--num_heads", type=int, default=8)
    parser.add_argument("--num_kv_heads", type=int, default=8)
    parser.add_argument("--inner", type=int, default=2816)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--int8", action="store_true")
    parser.add_argument("--prompt", type=int, default=16)
    parser.add_argument("--generate", type=int, default=48)
    parser.add_argument("--decode_max_len", type=int, default=128)
    parser.add_argument("--activation_compression", default="float16",
                        help="serving wire dtype for the A/B ('none' = "
                             "bit-identical fp32 wire; see docs/benchmarks.md)")
    parser.add_argument("--smoke", action="store_true",
                        help="tier-1-safe regression mode: tiny model, exits "
                             "nonzero if any request fails or the serving "
                             "wire-bytes counters did not move (wired into "
                             "tests so serving data-path breakage fails loudly)")
    parser.add_argument("--multi_client", type=int, default=0,
                        help="skewed multi-tenant mode (ISSUE 13): one HOT client "
                             "decoding flat-out plus this many paced background "
                             "clients, each on its own DHT identity; emits "
                             "per-client tok/s and p99 step latency")
    parser.add_argument("--multi_duration", type=float, default=20.0,
                        help="multi-client mode: traffic window in seconds")
    parser.add_argument("--background_interval", type=float, default=0.08,
                        help="background clients' pause between decode steps")
    parser.add_argument("--replicas", type=int, default=1, choices=(1, 2),
                        help="servers hosting the SAME blocks (replica set "
                             "declared multi-value in the DHT; clients balance, "
                             "hedge and fail over across them)")
    parser.add_argument("--kill_replica_at", type=float, default=0.0,
                        help="crash-kill the second replica at this fraction of "
                             "the multi-client window (0 = never); requires "
                             "--replicas 2. Zero client-visible failures required")
    parser.add_argument("--client_rate", type=float, default=None,
                        help="server-side fair-share admission budget "
                             "(tokens/s per client); the hot client saturates "
                             "its bucket, background clients must be unaffected")
    parser.add_argument("--client_burst", type=float, default=None,
                        help="admission burst ceiling (default 2s of "
                             "--client_rate). Size it to cover the longest "
                             "session re-prefill (prompt+generate): a replica "
                             "death mid-session replays the whole retained "
                             "history in one admission draw, and a burst below "
                             "that sheds the innocent client's recovery")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform, describe_devices

    add_platform_arg(parser)
    args = parser.parse_args()
    apply_platform(args)
    if args.smoke:
        args.hidden_dim, args.num_heads, args.num_kv_heads = 64, 4, 4
        args.inner, args.layers = 128, 1
        args.prompt, args.generate = 4, 4
        args.multi_duration = min(args.multi_duration, 8.0)

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from hivemind_tpu.moe.server.llama_loader import load_llama_blocks
    from hivemind_tpu.moe.server.server import Server
    from hivemind_tpu.telemetry.device import (
        COMPILE_TRACKER,
        arm_device_telemetry,
        device_snapshot,
    )

    # device telemetry rides every serving benchmark (ISSUE 19): steady-state
    # decode must never recompile, and the extras carry the compile/transfer
    # summary so bench.py lands it under telemetry.device
    arm_device_telemetry()

    with tempfile.TemporaryDirectory() as tmp:
        if args.checkpoint:
            checkpoint = Path(args.checkpoint)
        else:
            checkpoint = Path(tmp)
            synthesize_checkpoint(
                checkpoint, args.hidden_dim, args.num_heads, args.num_kv_heads,
                args.inner, args.layers,
            )
        if args.multi_client:
            return run_multi_client(args, checkpoint)
        load_start = time.perf_counter()
        backends, config = load_llama_blocks(
            checkpoint, uid_prefix="lb.",
            weight_quantization="int8" if args.int8 else None,
        )
        load_seconds = time.perf_counter() - load_start
        resident_mb = sum(b.param_bytes() for b in backends.values()) / 1e6
        # planning accuracy (VERDICT r3 #8): the capacity planner's input vs reality
        from hivemind_tpu.moe.server.llama_loader import (
            decode_cache_bytes, plan_block_capacity, predict_block_param_bytes,
        )

        predicted_block = predict_block_param_bytes(
            config, "int8" if args.int8 else None
        )
        measured_block = next(iter(backends.values())).param_bytes()
        cache_bytes = decode_cache_bytes(config, batch=1, max_len=args.decode_max_len)
        plan_16gb = plan_block_capacity(
            predicted_block, hbm_bytes=16 * 1024**3,
            decode_sessions=8, cache_bytes_per_session_block=cache_bytes,
        )

        dht = DHT(start=True)
        server = Server(dht, backends, decode_max_len=args.decode_max_len,
                        activation_compression=args.activation_compression)
        client_dht = None
        try:
            server.run_in_background(await_ready=True)
            time.sleep(1.0)
            client_dht = DHT(initial_peers=[str(m) for m in dht.get_visible_maddrs()], start=True)
            pipe = RemoteSequential(client_dht, "lb.", len(backends))

            rng = np.random.RandomState(1)
            hidden = rng.randn(1, args.prompt + args.generate, config.hidden_size).astype(np.float32)
            pipe.decode_step(hidden[:, : args.prompt], "warm", reset=True)  # compile
            pipe.decode_step(hidden[:, args.prompt : args.prompt + 1], "warm")

            # wire accounting (ISSUE 10): serving payload bytes over the timed
            # window, client side only (the server's mirror totals would double
            # count this in-process A/B) — bytes-per-token is the headline the
            # fp16 wire dtype halves vs fp32
            from hivemind_tpu.telemetry import REGISTRY
            from hivemind_tpu.telemetry.serving import SERVING_LEDGER

            def client_wire_bytes():
                out = {}
                for name, field in (("hivemind_moe_bytes_sent_total", "sent"),
                                    ("hivemind_moe_bytes_received_total", "received")):
                    metric = REGISTRY.get(name)
                    if metric is not None:
                        out[field] = metric.labels("client").value
                return out

            wire_before = client_wire_bytes()
            compiles_before = COMPILE_TRACKER.total()
            start = time.perf_counter()
            pipe.decode_step(hidden[:, : args.prompt], "bench", reset=True)
            for t in range(args.generate):
                pos = args.prompt + t
                try:
                    pipe.decode_step(hidden[:, pos : pos + 1], "bench")
                except Exception as e:
                    # ANY failed request voids the run: a tok/s computed over
                    # partially-failed steps would record an inflated A/B
                    raise SystemExit(f"decode step {t} failed (run void): {e!r}")
            elapsed = time.perf_counter() - start
            wire_after = client_wire_bytes()
            wire_delta = {
                key: wire_after.get(key, 0.0) - wire_before.get(key, 0.0)
                for key in wire_after
            }
            # per generated token, each way (the prefill rides the first step)
            wire_per_token = {
                key: round(value / max(args.generate, 1), 1)
                for key, value in wire_delta.items()
            }
            if args.smoke and not all(wire_delta.get(k, 0) > 0 for k in ("sent", "received")):
                raise SystemExit(f"smoke mode: serving wire-bytes counters did not move: {wire_delta}")
            # recompile-storm guard (ISSUE 19): the warm session compiled both
            # the prefill and single-token shapes, so the timed window must be
            # compile-free — a nonzero delta is a silent tok/s regression
            steady_state_compiles = COMPILE_TRACKER.total() - compiles_before
            if args.smoke and steady_state_compiles:
                raise SystemExit(
                    f"smoke mode: {steady_state_compiles} recompile(s) in the "
                    f"steady-state decode window (sites: {COMPILE_TRACKER.counts()})"
                )
            device = device_snapshot()
            # serving attribution rides the artifact (ISSUE 9): the server ran
            # in-process, so the global ledger holds every request's phase
            # decomposition — bench.py lands this under telemetry.serving
            print(json.dumps({
                "metric": "llama_checkpoint_decode",
                "device": describe_devices(),
                "value": round(args.generate / elapsed, 1),
                "unit": "tok/s",
                "extra": {
                    "layers": len(backends), "hidden": config.hidden_size,
                    "inner": config.intermediate_size,
                    "int8": args.int8, "resident_mb": round(resident_mb, 1),
                    "load_seconds": round(load_seconds, 2),
                    "per_block_load_seconds": round(load_seconds / max(len(backends), 1), 2),
                    "predicted_block_mb": round(predicted_block / 1e6, 1),
                    "measured_block_mb": round(measured_block / 1e6, 1),
                    "prediction_error_pct": round(
                        100.0 * abs(predicted_block - measured_block) / max(measured_block, 1), 2
                    ),
                    "planned_blocks_16gb_8sessions": plan_16gb,
                    "prompt": args.prompt, "generated": args.generate,
                    "prefill_included_tok_s": round((args.prompt + args.generate) / elapsed, 1),
                    "activation_compression": args.activation_compression,
                    "smoke": args.smoke,
                    # client-side serving payload bytes over the timed window,
                    # per generated token (the fp16-vs-fp32 wire A/B headline)
                    "wire_bytes_per_token": wire_per_token,
                    "serving": SERVING_LEDGER.summary(),
                    "steady_state_compiles": steady_state_compiles,
                    "device": {
                        "compiles": (device.get("compiles") or {}).get("total", 0),
                        "compile_seconds": (device.get("compiles") or {}).get("seconds", 0.0),
                        "storms": (device.get("compiles") or {}).get("storms", 0),
                        "transfer_bytes": device.get("transfer_bytes"),
                    },
                },
            }))
        finally:
            if client_dht is not None:
                client_dht.shutdown()
            server.shutdown()
            dht.shutdown()


if __name__ == "__main__":
    main()
