"""Swarm-wide telemetry (ISSUE 2 + 4): a zero-dependency, thread-safe metrics
registry with a Prometheus text exporter, DHT-published peer snapshots, and
distributed tracing with a per-process flight recorder.

- :mod:`~hivemind_tpu.telemetry.registry` — Counter / Gauge / Histogram with
  labels; the process-wide :data:`REGISTRY` all layers record into.
- :mod:`~hivemind_tpu.telemetry.tracing` — cross-peer spans, the
  :data:`~hivemind_tpu.telemetry.tracing.RECORDER` ring buffer, and
  Chrome-trace/Perfetto export.
- :mod:`~hivemind_tpu.telemetry.exporter` — ``GET /metrics`` + ``GET /trace``
  over stdlib HTTP (``--metrics-port`` in run_server.py / run_dht.py).
- :mod:`~hivemind_tpu.telemetry.monitor` — per-peer DHT snapshot publisher and
  the swarm-wide aggregation view (now incl. breaker states + slow spans).
- :mod:`~hivemind_tpu.telemetry.ledger` — the per-round attribution ledger
  (ISSUE 8): one structured record per averaging round / optimizer epoch with
  per-peer straggler scores, served at ``GET /ledger``.
- :mod:`~hivemind_tpu.telemetry.watchdog` — event-loop lag probe with stall
  stack capture and executor-queue-depth gauges.
- :mod:`~hivemind_tpu.telemetry.serving` — the serving-path attribution layer
  (ISSUE 9): one record per expert request decomposed into queue-wait /
  batch-assembly / device-compute / serialize, per-expert quantiles, per-client
  attribution, plus client-side expert scorecards; served at ``GET /serving``.

- :mod:`~hivemind_tpu.telemetry.blackbox` — the black-box flight recorder
  (ISSUE 17): crash-durable on-disk telemetry spools (segment-rotated msgpack
  frames) fed from the span/ledger hooks, read back by ``hivemind-blackbox``
  and ``hivemind-top --from-spool`` for cross-peer post-mortems.

- :mod:`~hivemind_tpu.telemetry.device` — device-side observability
  (ISSUE 19): the jit compile tracker + recompile-storm detector, device
  memory/leak/transfer telemetry sampled by the watchdog tick, and the
  compute / comm lanes of the Perfetto exports.

See docs/observability.md for the metric catalog and the span catalog.
"""

from hivemind_tpu.telemetry.device import (
    COMPILE_TRACKER,
    MEMORY_MONITOR,
    DeviceMemoryMonitor,
    JitCompileTracker,
    add_device_listener,
    arm_device_telemetry,
    device_snapshot,
    device_telemetry_armed,
    disarm_device_telemetry,
    record_transfer,
    remove_device_listener,
    reset_device_telemetry,
    span_lane,
)
from hivemind_tpu.telemetry.blackbox import (
    BlackBox,
    SpoolWriter,
    active_blackbox,
    arm_blackbox,
    disarm_blackbox,
    read_spool,
)
from hivemind_tpu.telemetry.exporter import MetricsExporter, render_prometheus
from hivemind_tpu.telemetry.ledger import LEDGER, RoundLedger
from hivemind_tpu.telemetry.serving import (
    SCORECARDS,
    SERVING_LEDGER,
    ExpertScorecards,
    ServingLedger,
    is_overload_error,
)
from hivemind_tpu.telemetry.watchdog import (
    EventLoopWatchdog,
    ensure_watchdog,
    watchdog_summary,
)
from hivemind_tpu.telemetry.tracing import (
    RECORDER,
    Span,
    SpanRecorder,
    current_span,
    finish_span,
    render_chrome_trace,
    set_slow_span_threshold,
    start_span,
    trace,
    trace_sync,
    trace_work,
)
from hivemind_tpu.telemetry.monitor import (
    DEFAULT_TELEMETRY_KEY,
    SwarmMonitor,
    TelemetryPublisher,
    aggregate_swarm_view,
    build_peer_snapshot,
    fetch_swarm_telemetry,
)
from hivemind_tpu.telemetry.registry import (
    DEFAULT_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)

__all__ = [
    "REGISTRY",
    "RECORDER",
    "COMPILE_TRACKER",
    "MEMORY_MONITOR",
    "JitCompileTracker",
    "DeviceMemoryMonitor",
    "add_device_listener",
    "remove_device_listener",
    "arm_device_telemetry",
    "disarm_device_telemetry",
    "device_telemetry_armed",
    "device_snapshot",
    "record_transfer",
    "reset_device_telemetry",
    "span_lane",
    "BlackBox",
    "SpoolWriter",
    "read_spool",
    "arm_blackbox",
    "disarm_blackbox",
    "active_blackbox",
    "LEDGER",
    "RoundLedger",
    "SERVING_LEDGER",
    "SCORECARDS",
    "ServingLedger",
    "ExpertScorecards",
    "is_overload_error",
    "EventLoopWatchdog",
    "ensure_watchdog",
    "watchdog_summary",
    "DEFAULT_BUCKETS",
    "DEFAULT_TELEMETRY_KEY",
    "Span",
    "SpanRecorder",
    "trace",
    "trace_sync",
    "trace_work",
    "current_span",
    "start_span",
    "finish_span",
    "render_chrome_trace",
    "set_slow_span_threshold",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsExporter",
    "render_prometheus",
    "TelemetryPublisher",
    "SwarmMonitor",
    "build_peer_snapshot",
    "fetch_swarm_telemetry",
    "aggregate_swarm_view",
]
