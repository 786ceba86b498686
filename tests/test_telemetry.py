"""The telemetry subsystem (ISSUE 2): registry semantics (labels, histogram
buckets, concurrent increments), the Prometheus exporter scrape round-trip, the
DHT snapshot publish/aggregate path, and a real two-peer run asserting that the
matchmaking / all-reduce / optimizer instrumentation actually advances."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from hivemind_tpu.telemetry import (
    REGISTRY,
    MetricsExporter,
    MetricsRegistry,
    TelemetryPublisher,
    aggregate_swarm_view,
    build_peer_snapshot,
    fetch_swarm_telemetry,
    render_prometheus,
)

from swarm_utils import launch_dht_swarm, shutdown_all


# ------------------------------------------------------------------ registry


def test_counter_labels_and_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("rpc_calls_total", "calls", ("handler", "side"))
    c.inc(handler="ping", side="server")
    c.inc(2.0, handler="ping", side="server")
    c.labels("find", "client").inc()
    assert c.value(handler="ping", side="server") == 3.0
    assert c.value(handler="find", side="client") == 1.0
    # same name returns the same metric object; wrong type/labels assert
    assert reg.counter("rpc_calls_total", "calls", ("handler", "side")) is c
    with pytest.raises(AssertionError):
        reg.gauge("rpc_calls_total")
    with pytest.raises(AssertionError):
        reg.counter("rpc_calls_total", "calls", ("handler",))


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("epoch")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value() == 4.0


def test_gauge_reads_its_function_when_looked_at():
    reg = MetricsRegistry()
    kept = [3.0]
    reg.gauge("kept_elsewhere", "read where it is kept").set_function(lambda: kept[0])
    assert reg.gauge("kept_elsewhere").value() == 3.0
    kept[0] = 7.0  # nobody told the gauge
    assert reg.snapshot()["kept_elsewhere"]["series"]["_"] == 7.0
    assert "kept_elsewhere 7" in render_prometheus(reg)


def test_histogram_buckets_are_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", ("op",), buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v, op="get")
    child = h.labels(op="get")
    buckets, total, count = child.snapshot()
    assert buckets == [1, 2, 3]  # cumulative: le=0.01 -> 1, le=0.1 -> 2, le=1.0 -> 3
    assert count == 4
    assert abs(total - 5.555) < 1e-9
    text = render_prometheus(reg)
    assert 'lat_bucket{op="get",le="+Inf"} 4' in text
    assert 'lat_count{op="get"} 4' in text


def test_histogram_timer_context():
    reg = MetricsRegistry()
    h = reg.histogram("span", "span", ("what",))
    with h.time(what="sleep"):
        pass
    assert h.labels(what="sleep").count == 1


def test_concurrent_increments_are_lossless():
    reg = MetricsRegistry()
    c = reg.counter("spins_total", "spins", ("worker",))
    h = reg.histogram("spin_lat", "lat")

    def spin(worker):
        child = c.labels(worker)
        hchild = h.labels()
        for _ in range(5000):
            child.inc()
            c.inc(worker="shared")  # un-cached path: exercises get-or-create
            hchild.observe(0.001)

    threads = [threading.Thread(target=spin, args=(str(i),)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert c.value(worker="shared") == 8 * 5000
    assert sum(c.value(worker=str(i)) for i in range(8)) == 8 * 5000
    assert h.labels().count == 8 * 5000


# ------------------------------------------------------------------ exporter


def test_exporter_scrape_roundtrip():
    reg = MetricsRegistry()
    reg.counter("demo_total", "demo", ("kind",)).inc(kind="x")
    reg.gauge("demo_gauge", "demo").set(1.5)
    reg.histogram("demo_seconds", "demo").observe(0.2)
    exporter = MetricsExporter(port=0, registry=reg)
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5).read().decode()
        assert "# TYPE demo_total counter" in body
        assert 'demo_total{kind="x"} 1' in body
        assert "demo_gauge 1.5" in body
        assert "demo_seconds_count 1" in body
        snapshot = json.loads(urllib.request.urlopen(f"{base}/metrics.json", timeout=5).read())
        assert snapshot["demo_total"]["series"]["kind=x"] == 1
        assert snapshot["demo_seconds"]["series"]["_"]["count"] == 1
        assert urllib.request.urlopen(f"{base}/healthz", timeout=5).read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        exporter.shutdown()


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("esc_total", "esc", ("name",)).inc(name='we"ird\\peer\nid')
    text = render_prometheus(reg)
    assert 'esc_total{name="we\\"ird\\\\peer\\nid"} 1' in text


# ------------------------------------------------------------------ snapshots / aggregation


def test_snapshot_and_swarm_aggregation_without_network():
    reg = MetricsRegistry()
    reg.counter("work_total", "w").inc(7)
    reg.gauge("epoch", "e").set(3)
    reg.histogram("lat", "l").observe(0.5)
    snap_a = build_peer_snapshot(reg, extras={"peer_id": "peerA"})
    snap_b = build_peer_snapshot(reg, extras={"peer_id": "peerB"})
    view = aggregate_swarm_view({"peerA": snap_a, "peerB": snap_b})
    assert view["num_peers"] == 2
    assert view["metrics"]["work_total"]["total"] == 14
    assert view["metrics"]["epoch"]["min"] == view["metrics"]["epoch"]["max"] == 3
    assert view["metrics"]["lat"]["total"] == 2  # histogram counts sum
    assert abs(view["metrics"]["lat"]["sum"] - 1.0) < 1e-9
    assert set(view["peers"]) == {"peerA", "peerB"}


# ------------------------------------------------------------------ end-to-end


def _counter_total(name: str) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    total = 0.0
    for _key, child in metric.series():
        total += getattr(child, "value", 0.0) or getattr(child, "count", 0.0)
    return total


def _histogram_count(name: str) -> int:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0
    return sum(child.count for _key, child in metric.series())


def _run_one_moe_batch():
    import asyncio

    from hivemind_tpu.moe.server.runtime import Runtime
    from hivemind_tpu.moe.server.task_pool import TaskPool

    async def run():
        pool = TaskPool(lambda x: x * 2, name="telemetry_e2e_pool", max_batch_size=16)
        runtime = Runtime([pool], stats_report_interval=None)
        runtime.start()
        try:
            await asyncio.wait_for(pool.submit_task(np.ones((2, 3), np.float32)), timeout=10)
        finally:
            runtime.shutdown()

    asyncio.run(run())


def _run_one_slice_epoch_transition():
    import jax
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    opt = SliceOptimizer(
        mesh=mesh,
        params={"w": jax.device_put(np.zeros((8, 4), np.float32), sharding)},
        optimizer=optax.sgd(0.1),
        dht_factory=lambda: DHT(start=True),
        run_id="telemetry_e2e_slice",
        target_batch_size=1 << 30,
        batch_size_per_step=1,
    )
    try:
        opt.step({"w": jax.device_put(np.ones((8, 4), np.float32), sharding)}, batch_size=1)
        opt.force_epoch_transition(num_peers=1)
    finally:
        opt.shutdown()


def test_two_peer_run_advances_cross_layer_counters():
    """Two real peers over a real DHT: one averaging round plus progress
    reporting must advance the p2p, DHT, matchmaking, all-reduce and optimizer
    metrics — and the DHT-published snapshots must aggregate into a swarm view."""
    from hivemind_tpu.averaging import DecentralizedAverager
    from hivemind_tpu.optim.progress_tracker import ProgressTracker

    before = {
        "p2p_rpc": _histogram_count("hivemind_p2p_rpc_latency_seconds"),
        "dht_rpc": _histogram_count("hivemind_dht_rpc_latency_seconds"),
        "dht_op": _histogram_count("hivemind_dht_operation_latency_seconds"),
        "matchmaking": _counter_total("hivemind_averaging_matchmaking_rounds_total"),
        "allreduce": _histogram_count("hivemind_averaging_allreduce_phase_seconds"),
    }

    dhts = launch_dht_swarm(2)
    averagers = [
        DecentralizedAverager(
            [np.full(16, float(i), np.float32)], dht, prefix="telemetry_e2e", start=True,
            target_group_size=2, min_matchmaking_time=1.0, request_timeout=1.0,
        )
        for i, dht in enumerate(dhts)
    ]
    trackers = []
    publishers = []
    try:
        controls = [a.step(wait=False, timeout=30) for a in averagers]
        results = [c.result(timeout=60) for c in controls]
        assert all(r is not None for r in results)

        trackers = [ProgressTracker(dht, "telemetry_e2e_run", target_batch_size=1000) for dht in dhts]
        for epoch, tracker in enumerate(trackers):
            tracker.report_local_progress(epoch, 123)

        # every layer moved
        assert _histogram_count("hivemind_p2p_rpc_latency_seconds") > before["p2p_rpc"]
        assert _histogram_count("hivemind_dht_rpc_latency_seconds") > before["dht_rpc"]
        assert _histogram_count("hivemind_dht_operation_latency_seconds") > before["dht_op"]
        assert _counter_total("hivemind_averaging_matchmaking_rounds_total") > before["matchmaking"]
        assert _histogram_count("hivemind_averaging_allreduce_phase_seconds") > before["allreduce"]
        assert REGISTRY.get("hivemind_optim_local_samples_accumulated").value() == 123
        assert REGISTRY.get("hivemind_dht_routing_table_size").value() >= 1

        # layer 5: one MoE runtime batch so the scrape carries all five layers
        _run_one_moe_batch()
        # layer 4 counter: one deterministic slice epoch transition
        _run_one_slice_epoch_transition()

        # acceptance criterion: GET /metrics serves valid exposition with at
        # least one counter sample from every layer
        exporter = MetricsExporter(port=0)
        try:
            page = urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics", timeout=5
            ).read().decode()
        finally:
            exporter.shutdown()
        for counter_sample in (
            'hivemind_p2p_rpc_bytes_total{',               # layer 1
            'hivemind_dht_operation_latency_seconds_count{',  # layer 2
            'hivemind_averaging_matchmaking_rounds_total{',   # layer 3
            'hivemind_optim_epoch_transitions_total{',        # layer 4
            'hivemind_moe_batches_total{',                    # layer 5
        ):
            assert counter_sample in page, f"{counter_sample} missing from scrape"
        for family in (
            "hivemind_p2p_rpc_latency_seconds",
            "hivemind_dht_rpc_latency_seconds",
            "hivemind_optim_local_epoch",
        ):
            assert page.count(f"# TYPE {family}") == 1

        # DHT-published snapshots aggregate into the swarm view
        publishers = [
            TelemetryPublisher(dht, "telemetry_e2e_swarm", interval=30.0, start=False)
            for dht in dhts
        ]
        for publisher in publishers:
            assert publisher.publish_once()
        records = fetch_swarm_telemetry(dhts[0], "telemetry_e2e_swarm")
        assert len(records) == 2
        view = aggregate_swarm_view(records)
        assert view["num_peers"] == 2
        assert "hivemind_p2p_rpc_latency_seconds" in view["metrics"]
    finally:
        for publisher in publishers:
            publisher.shutdown()
        for tracker in trackers:
            tracker.shutdown()
        shutdown_all(averagers, dhts)


def test_moe_runtime_metrics_advance():
    """The Runtime's registry counters replace its private _stats dict."""
    import asyncio

    from hivemind_tpu.moe.server.runtime import Runtime
    from hivemind_tpu.moe.server.task_pool import TaskPool

    before_batches = _counter_total("hivemind_moe_batches_total")
    before_samples = _counter_total("hivemind_moe_samples_total")

    async def run():
        pool = TaskPool(lambda x: x * 2, name="telemetry_pool", max_batch_size=16)
        runtime = Runtime([pool], stats_report_interval=None)
        runtime.start()
        try:
            out = await asyncio.wait_for(pool.submit_task(np.ones((4, 3), np.float32)), timeout=10)
            assert np.allclose(out[0], 2.0)
        finally:
            runtime.shutdown()

    asyncio.run(run())
    assert _counter_total("hivemind_moe_batches_total") == before_batches + 1
    assert _counter_total("hivemind_moe_samples_total") == before_samples + 4
    assert REGISTRY.get("hivemind_moe_batch_latency_seconds").labels(pool="telemetry_pool").count >= 1
