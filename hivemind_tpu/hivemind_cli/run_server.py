"""Run a MoE expert server (capability parity: reference
hivemind/hivemind_cli/run_server.py)."""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from hivemind_tpu.moe import Server
from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def main():
    parser = argparse.ArgumentParser(description="Run a hivemind_tpu MoE expert server")
    parser.add_argument("--num_experts", type=int, default=None)
    parser.add_argument("--expert_uids", nargs="*", default=None, help="explicit expert uids")
    parser.add_argument("--expert_pattern", default=None, help="e.g. 'ffn.[0:16].[0:16]'")
    parser.add_argument("--expert_cls", default="ffn", help="registered expert class")
    parser.add_argument("--hidden_dim", type=int, default=1024)
    parser.add_argument("--expert_kwargs", default=None,
                        help="JSON dict forwarded to the expert class, e.g. "
                             "'{\"num_kv_heads\": 2}' for GQA llama_block")
    parser.add_argument("--decode_max_len", type=int, default=256,
                        help="KV-cache decode session capacity (prompt + generated "
                             "tokens) per client session")
    parser.add_argument("--decode_max_sessions", type=int, default=64,
                        help="LRU cap on concurrent KV-cache decode sessions "
                             "(occupancy/evictions are gauged — see "
                             "docs/observability.md 'Serving')")
    parser.add_argument("--max_queue_size", type=int, default=1024,
                        help="bounded task-pool queue: submits past this many "
                             "waiting tasks are SHED with ServerOverloadedError "
                             "(counted in hivemind_moe_shed_total) instead of "
                             "queueing unboundedly toward client timeouts")
    parser.add_argument("--activation_compression", default="float16",
                        help="wire dtype for expert activations/grads on the "
                             "serving RPC path (float16 halves wire bytes; "
                             "'none' = bit-identical fp32). Published in expert "
                             "info + DHT declarations so clients negotiate the "
                             "same codec for requests")
    parser.add_argument("--client_rate", type=float, default=None,
                        help="fair-share admission (ISSUE 13): per-client token "
                             "budget in samples/s — a hot client past its bucket "
                             "is shed (typed ClientOverBudgetError, counted in "
                             "hivemind_moe_admission_shed_total) while other "
                             "clients keep flowing. Default: off")
    parser.add_argument("--client_burst", type=float, default=None,
                        help="token-bucket burst ceiling (default 2s of --client_rate)")
    parser.add_argument("--replica_slots", type=int, default=0,
                        help="acquire up to this many hot experts from other "
                             "servers (rpc_replica_state transfer, then served + "
                             "declared here as extra replicas)")
    parser.add_argument("--replicate_hot_experts", action="store_true",
                        help="advertise this server's hot experts (ServingLedger "
                             "QPS/occupancy thresholds) under replica_wanted.* so "
                             "servers with --replica_slots pick them up")
    parser.add_argument("--replication_watch_grids", nargs="*", default=None,
                        help="grid roots to scan for replica_wanted adverts "
                             "(default: the roots of this server's own experts)")
    parser.add_argument("--custom_module_path", default=None,
                        help="path to a .py file whose @register_expert_class "
                             "decorators run before the server starts (capability "
                             "parity: reference custom_experts.py add_custom_models)")
    parser.add_argument("--max_batch_size", type=int, default=4096)
    parser.add_argument("--initial_peers", nargs="*", default=[])
    parser.add_argument("--checkpoint_dir", default=None)
    parser.add_argument("--llama_checkpoint", default=None,
                        help="serve a real (sharded) HF-layout Llama checkpoint: "
                             "decoder layers load into llama_block backends "
                             "(BASELINE config #5 Petals-style block server)")
    parser.add_argument("--llama_layers", default=None,
                        help="'start:stop' layer range of --llama_checkpoint to "
                             "serve (default: HBM-budgeted from the start, or all "
                             "when the platform reports no memory limit)")
    parser.add_argument("--llama_uid_prefix", default="llama.")
    parser.add_argument("--mesh_devices", type=int, default=0,
                        help="serve each block MESH-SHARDED over this many local "
                             "devices (params + KV caches as NamedSharding arrays; "
                             "0 = single-device serving). The HBM plan uses the "
                             "probe block's MEASURED per-device residency, so "
                             "blocks one chip cannot hold fit when they shard")
    parser.add_argument("--weight_quantization", choices=["int8"], default=None,
                        help="serve blocks int8 weight-only via the blockwise "
                             "codec (4x less resident HBM; inference-only)")
    parser.add_argument("--decode_sessions_budget", type=int, default=8,
                        help="concurrent decode sessions the HBM plan reserves "
                             "KV-cache space for")
    parser.add_argument("--learning_rate", type=float, default=1e-3)
    parser.add_argument("--max_connections", type=int, default=0,
                        help="connection-manager high water for the DHT peer "
                             "(0 = unlimited; bounds fds at swarm scale)")
    parser.add_argument("--increase_file_limit", action="store_true",
                        help="raise RLIMIT_NOFILE for many concurrent connections")
    parser.add_argument("--metrics-port", "--metrics_port", type=int, default=None,
                        dest="metrics_port",
                        help="serve Prometheus text exposition at "
                             "http://<metrics_host>:PORT/metrics (0 = auto-pick)")
    parser.add_argument("--metrics_host", default="127.0.0.1",
                        help="bind host of the metrics endpoint (0.0.0.0 for "
                             "remote scrapers)")
    parser.add_argument("--telemetry_key", default=None,
                        help="publish this server's telemetry snapshot to the DHT "
                             "under this key every --telemetry_interval seconds")
    parser.add_argument("--telemetry_interval", type=float, default=30.0)
    parser.add_argument("--blackbox_dir", default=None,
                        help="crash-durable flight-recorder spool directory: "
                             "finished spans, round/serving ledger records and "
                             "metric snapshots are appended as msgpack frames "
                             "readable post-mortem with hivemind-blackbox (see "
                             "docs/observability.md 'Black-box flight recorder')")
    parser.add_argument("--no_device_telemetry", action="store_false", dest="device_telemetry",
                        help="disable device-side observability (jit compile tracking, "
                             "HBM/leak sampling on the watchdog tick, transfer counters; "
                             "docs/observability.md 'Device telemetry'); on by default")
    from hivemind_tpu.utils.platform import add_platform_arg, apply_platform, describe_devices

    add_platform_arg(parser)
    args = parser.parse_args()
    apply_platform(args)
    # this is where the serving process meets its devices: say which it got
    logger.info(f"devices: {json.dumps(describe_devices())}")

    if args.increase_file_limit:
        from hivemind_tpu.utils.limits import increase_file_limit

        increase_file_limit()

    if args.custom_module_path:
        import importlib.util
        import sys

        spec = importlib.util.spec_from_file_location("hivemind_custom_experts", args.custom_module_path)
        if spec is None or spec.loader is None:
            raise RuntimeError(f"cannot load {args.custom_module_path}")
        module = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = module  # classes' __module__ must resolve (pickling etc.)
        spec.loader.exec_module(module)  # runs the @register_expert_class decorators
        logger.info(f"loaded custom expert module {args.custom_module_path}")

    import optax

    if args.llama_checkpoint:
        server = _serve_llama_checkpoint(args)
        _run_forever(server, _start_telemetry(args, server.dht))
        return
    if args.mesh_devices:
        raise SystemExit(
            "--mesh_devices is only supported with --llama_checkpoint serving; "
            "the registry-expert path would silently ignore it"
        )

    from hivemind_tpu.dht import DHT

    # construct the DHT here so --max_connections reaches its transport
    dht = DHT(initial_peers=args.initial_peers, start=True,
              max_connections=args.max_connections)
    server = Server.create(
        num_experts=args.num_experts,
        expert_uids=args.expert_uids,
        expert_pattern=args.expert_pattern,
        expert_cls=args.expert_cls,
        hidden_dim=args.hidden_dim,
        expert_kwargs=json.loads(args.expert_kwargs) if args.expert_kwargs else None,
        max_batch_size=args.max_batch_size,
        dht=dht,
        checkpoint_dir=Path(args.checkpoint_dir) if args.checkpoint_dir else None,
        decode_max_len=args.decode_max_len,
        decode_max_sessions=args.decode_max_sessions,
        max_queue_size=args.max_queue_size,
        activation_compression=args.activation_compression,
        client_rate=args.client_rate,
        client_burst=args.client_burst,
        replica_slots=args.replica_slots,
        replicate_hot_experts=args.replicate_hot_experts,
        replication_watch_grids=args.replication_watch_grids,
        optim_factory=lambda: optax.adam(args.learning_rate),
        start=True,
    )
    _run_forever(server, _start_telemetry(args, dht))


def _serve_llama_checkpoint(args) -> Server:
    """BASELINE config #5: serve a real checkpoint's decoder layers, choosing how
    many fit this chip when no explicit range is given."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe.server.llama_loader import (
        LlamaCheckpointConfig,
        decode_cache_bytes,
        device_hbm_bytes,
        load_llama_blocks,
        plan_block_capacity,
    )

    mesh = None
    if args.mesh_devices:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        if args.mesh_devices < 1:
            raise ValueError(f"--mesh_devices must be >= 1, got {args.mesh_devices}")
        devices = jax.local_devices()[: args.mesh_devices]
        if len(devices) < args.mesh_devices:
            raise RuntimeError(
                f"--mesh_devices {args.mesh_devices} but only {len(devices)} local devices"
            )
        mesh = Mesh(np.array(devices).reshape(len(devices)), ("tp",))

    config = LlamaCheckpointConfig.load(args.llama_checkpoint)
    if args.llama_layers:
        start, _, stop = args.llama_layers.partition(":")
        layers = range(int(start or 0), int(stop or config.num_hidden_layers))
    else:
        layers = range(config.num_hidden_layers)
        hbm = device_hbm_bytes()
        if hbm is not None:
            # measure one real block, then plan with KV-cache headroom
            probe, _ = load_llama_blocks(
                args.llama_checkpoint, layers=[0], uid_prefix="_probe.",
                weight_quantization=args.weight_quantization, mesh=mesh,
            )
            probe_backend = next(iter(probe.values()))
            # mesh serving: plan from the MEASURED per-device residency, not an
            # assumed 1/mesh fraction — kernels whose last dim does not divide
            # the mesh REPLICATE (leaf_spec), and only the probe knows how much
            block_bytes = (
                probe_backend.param_bytes_per_device() if mesh is not None
                else probe_backend.param_bytes()
            )
            del probe, probe_backend  # release before the real load fills the plan
            fit = plan_block_capacity(
                block_bytes,
                hbm_bytes=hbm,
                decode_sessions=args.decode_sessions_budget,
                # conservative: budget FULL per-session caches on every chip
                # (cache sharding is also divisibility-dependent)
                cache_bytes_per_session_block=decode_cache_bytes(
                    config, batch=1, max_len=args.decode_max_len
                ),
            )
            layers = range(min(fit, config.num_hidden_layers))
            logger.info(
                f"HBM plan: {block_bytes / 1e6:.0f} MB/block resident per chip "
                f"({'mesh of ' + str(args.mesh_devices) if mesh is not None else 'single device'}), "
                f"{hbm / 1e9:.1f} GB/chip → serving {len(layers)} of "
                f"{config.num_hidden_layers} layers"
            )
    backends, _config = load_llama_blocks(
        args.llama_checkpoint,
        layers=layers,
        uid_prefix=args.llama_uid_prefix,
        weight_quantization=args.weight_quantization,
        max_batch_size=args.max_batch_size,
        mesh=mesh,
    )
    dht = DHT(initial_peers=args.initial_peers, start=True,
              max_connections=args.max_connections)
    server = Server(
        dht, backends, decode_max_len=args.decode_max_len,
        # the HBM plan reserved KV space for exactly this many sessions: cap the
        # session manager to it so the reservation is real, not advisory
        decode_max_sessions=args.decode_sessions_budget,
        max_queue_size=args.max_queue_size,
        activation_compression=args.activation_compression,
        client_rate=args.client_rate,
        client_burst=args.client_burst,
    )
    server.run_in_background(await_ready=True)
    return server


def _start_telemetry(args, dht):
    """Optional metrics endpoint + DHT snapshot publisher (docs/observability.md);
    returns the components to shut down, or an empty tuple."""
    from hivemind_tpu.telemetry import ensure_watchdog
    from hivemind_tpu.utils.loop import get_loop_runner

    # server + DHT already armed the loop watchdog; stay loud if it is disabled
    if ensure_watchdog(get_loop_runner().loop) is None:
        logger.warning("event-loop watchdog disabled (HIVEMIND_WATCHDOG=0): stalls will be silent")
    components = []
    if getattr(args, "device_telemetry", True):
        import types

        from hivemind_tpu.telemetry.device import arm_device_telemetry, disarm_device_telemetry

        arm_device_telemetry()
        components.append(types.SimpleNamespace(shutdown=disarm_device_telemetry))
    if getattr(args, "blackbox_dir", None):
        import types

        from hivemind_tpu.telemetry.blackbox import arm_blackbox, disarm_blackbox

        arm_blackbox(args.blackbox_dir, peer=str(dht.peer_id))
        logger.info(f"black-box recorder armed: spooling to {args.blackbox_dir}")
        # disarm (not just close) at shutdown so the global slot is freed for
        # whatever arms next in this process
        components.append(types.SimpleNamespace(shutdown=disarm_blackbox))
    if args.metrics_port is not None:
        from hivemind_tpu.telemetry import MetricsExporter

        components.append(MetricsExporter(port=args.metrics_port, host=args.metrics_host))
    if args.telemetry_key:
        from hivemind_tpu.telemetry import TelemetryPublisher

        components.append(
            TelemetryPublisher(dht, args.telemetry_key, interval=args.telemetry_interval)
        )
    return tuple(components)


def _run_forever(server: Server, telemetry=()) -> None:
    for maddr in server.dht.get_visible_maddrs():
        logger.info(f"listening: {maddr}")
    logger.info(f"serving {len(server.backends)} experts: {sorted(server.backends)[:8]}…")
    try:
        while True:
            time.sleep(60)
    except KeyboardInterrupt:
        logger.info("shutting down")
        for component in telemetry:
            component.shutdown()
        server.shutdown()
        server.dht.shutdown()


if __name__ == "__main__":
    main()
