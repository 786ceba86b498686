"""Device-side observability (ISSUE 19): compile tracking via tracked_jit and
recompile storms, watchdog-sampled device memory, the compute / comm lanes of
the Perfetto export, snapshot/spool integration, and the hivemind-top device
board."""

import threading
import time

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from hivemind_tpu.optim import Optimizer
from hivemind_tpu.telemetry import watchdog as telemetry_watchdog
from hivemind_tpu.telemetry.blackbox import BlackBox
from hivemind_tpu.telemetry.device import (
    COMPILE_TRACKER,
    MEMORY_MONITOR,
    JitCompileTracker,
    add_device_listener,
    arm_device_telemetry,
    compact_device_snapshot,
    device_snapshot,
    device_telemetry_armed,
    record_transfer,
    remove_device_listener,
    reset_device_telemetry,
    span_lane,
    transfer_totals,
)
from hivemind_tpu.telemetry.ledger import LEDGER
from hivemind_tpu.telemetry.monitor import _shrink_to_fit
from hivemind_tpu.telemetry.tracing import render_chrome_trace
from hivemind_tpu.utils.profiling import tracked_jit
from hivemind_tpu.utils.serializer import MSGPackSerializer

from swarm_utils import launch_dht_swarm


# ----------------------------------------------------------- compile tracking


def test_tracked_jit_counts_compiles_not_cache_hits():
    @tracked_jit(site="test.add_one")
    def add_one(x):
        return x + 1

    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(add_one(x)), np.arange(8) + 1)
    assert COMPILE_TRACKER.counts().get("test.add_one") == 1

    add_one(x + 5)  # same abstract signature: cache hit, NOT a compile
    assert COMPILE_TRACKER.counts().get("test.add_one") == 1

    add_one(jnp.arange(16, dtype=jnp.float32))  # new shape: recompile
    assert COMPILE_TRACKER.counts().get("test.add_one") == 2

    summary = COMPILE_TRACKER.summary()
    site = summary["sites"]["test.add_one"]
    assert site["count"] == 2 and site["seconds"] >= 0.0
    assert "float32" in site["signature"]  # the shape detail lives here, not in labels
    assert summary["last"]["site"] == "test.add_one"


def test_forced_recompiles_detect_a_storm_once_per_window():
    tracker = JitCompileTracker(storm_threshold=3, storm_window_s=60.0)
    for _ in range(10):  # one churning site, well past the threshold
        tracker.record_compile("moe.forward", duration_s=0.01, signature="f32[?]")
    assert tracker.storm_count() == 1, "a storm fires once per window, not per compile"
    assert tracker.counts()["moe.forward"] == 10
    assert tracker.summary()["storms"] == 1


def test_jax_monitoring_events_accrue_but_never_storm():
    tracker = JitCompileTracker(storm_threshold=2, storm_window_s=60.0)
    for _ in range(8):
        tracker.record_jax_event("/jax/compilation/backend_compile_time", 0.005)
    assert tracker.counts() == {"jax": 8}
    assert tracker.storm_count() == 0, "unattributed backend events are storm-exempt"
    assert tracker.total() == 0, "steady-state mark counts tracked sites only"
    assert tracker.total(include_jax_events=True) == 8


def test_compile_records_reach_device_listeners():
    events = []

    def listener(kind, record):
        events.append((kind, record))

    add_device_listener(listener)
    try:
        COMPILE_TRACKER.record_compile("test.listener_site", duration_s=0.02)
    finally:
        remove_device_listener(listener)
    kinds = [k for k, _ in events]
    assert "compile" in kinds
    record = dict(events[kinds.index("compile")][1])
    assert record["site"] == "test.listener_site" and record["count"] == 1


# ------------------------------------------------------------- device memory


def test_watchdog_tick_samples_live_device_memory():
    # retain a live device array across the sample: jax.live_arrays() only
    # sees buffers that have not been GC'd
    retained = jnp.ones((64, 64), dtype=jnp.float32)
    retained.block_until_ready()
    arm_device_telemetry()
    try:
        assert device_telemetry_armed()
        telemetry_watchdog._run_tick_samplers()
        sample = MEMORY_MONITOR.last_sample
        assert sample is not None and sample["total_bytes"] >= retained.nbytes
        assert sample["buffers"] >= 1 and sample["devices"]
        entry = next(iter(sample["devices"].values()))
        assert entry["peak_bytes"] >= entry["bytes"] > 0
    finally:
        reset_device_telemetry()
    del retained


def test_memory_sampler_is_inert_without_jax_in_the_process():
    # the monitor reads sys.modules and must never import jax itself: a
    # process that has not touched jax pays nothing for the sampler
    assert MEMORY_MONITOR.sample(modules={}) is None
    assert MEMORY_MONITOR.last_sample is None


def test_leak_heuristic_fires_on_monotonic_growth_then_resets():
    leaks = []

    def listener(kind, record):
        if kind == "leak":
            leaks.append(record)

    add_device_listener(listener)
    try:
        growth = MEMORY_MONITOR.leak_min_growth // 4
        buffers = []
        for _ in range(MEMORY_MONITOR.leak_samples):
            buffers.append(jnp.ones(growth // 4, dtype=jnp.float32))  # 4 B/elem
            buffers[-1].block_until_ready()
            MEMORY_MONITOR.sample()
        assert MEMORY_MONITOR.leak_count() == 1, "strict growth across the window"
        assert leaks and leaks[0]["growth_bytes"] >= MEMORY_MONITOR.leak_min_growth
        # the trend restarts after firing: the very next sample cannot re-fire
        MEMORY_MONITOR.sample()
        assert MEMORY_MONITOR.leak_count() == 1
    finally:
        remove_device_listener(listener)
    del buffers


def test_record_transfer_accounts_both_directions():
    before = transfer_totals()
    record_transfer(1000, "host_to_device")
    record_transfer(250, "device_to_host")
    record_transfer(0, "host_to_device")  # no-op, not an error
    after = transfer_totals()
    assert after["host_to_device"] - before["host_to_device"] == 1000
    assert after["device_to_host"] - before["device_to_host"] == 250
    with pytest.raises(ValueError):
        record_transfer(1, "sideways")


# -------------------------------------------------------------------- lanes


def test_span_lane_classification():
    assert span_lane("optimizer.update") == "compute"
    assert span_lane("allreduce.round") == "comm"
    assert span_lane("allreduce.peer_exchange") == "comm"  # child: comm LANE only
    assert span_lane("dht.store") is None


def test_two_peer_round_fills_both_lanes_and_the_records_the_benchmark_reads():
    """One real local-updates run: the optimizer.update spans land on the compute
    lane and the state round's spans on the comm lane of the Perfetto export, and
    the round and epoch records carry the fields the benchmark's ledger metrics
    read (perf/layer_metrics: state_round_ms, state_round_wait_ms,
    matchmaking_wait_ms, round_allreduce_ms)."""
    closed = {"round": [], "epoch": []}

    def on_record(kind, record):
        closed[kind].append(record)

    LEDGER.add_record_listener(on_record)
    rng = np.random.RandomState(0)
    features = rng.randn(128, 4).astype(np.float32)
    targets = features @ rng.randn(4).astype(np.float32)

    dhts = launch_dht_swarm(2)
    errors = []

    def run_peer(index, dht):
        try:
            opt = Optimizer(
                dht=dht, run_id="lanes_test", target_batch_size=32,
                params={"w": jnp.zeros(4, jnp.float32)}, optimizer=optax.sgd(0.1),
                batch_size_per_step=16, matchmaking_time=1.0, averaging_timeout=30,
                average_state_every=1, target_group_size=2, verbose=False,
                use_local_updates=True,
                tracker_opts=dict(min_refresh_period=0.3, default_refresh_period=0.5),
            )
            loss_grad = jax.jit(jax.value_and_grad(
                lambda p, x, y: jnp.mean((x @ p["w"] - y) ** 2)
            ))
            local = np.random.RandomState(index)
            for _ in range(40):
                if opt.local_epoch >= 2:
                    break
                idx = local.choice(len(features), 16)
                _, grads = loss_grad(opt.params, features[idx], targets[idx])
                opt.step(grads)
                time.sleep(0.2)
            opt.shutdown()
        except Exception as e:
            import traceback

            errors.append((index, e, traceback.format_exc()))

    threads = [threading.Thread(target=run_peer, args=(i, d)) for i, d in enumerate(dhts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    try:
        assert not errors, f"peer failures: {errors}"
        events = render_chrome_trace()["traceEvents"]
        lanes = {}
        for event in events:
            lane = (event.get("args") or {}).get("lane")
            if lane is not None:
                lanes.setdefault(lane, set()).add(event["name"])
        assert "optimizer.update" in lanes["compute"]
        assert {"allreduce.round", "averaging.matchmaking"} <= lanes["comm"]
        state_rounds = [r for r in closed["round"] if r.get("purpose") == "state"]
        assert state_rounds, closed["round"]
        for record in state_rounds:
            assert record["group_size"] == 2 and record["total_s"] > 0
        assert any("matchmaking_wait_s" in record for record in state_rounds)
        # the epoch that launches a round hands it over; the next one carries its length
        landed = [r for r in closed["epoch"] if r["state_round_s"] > 0]
        assert landed, closed["epoch"]
        assert all(record["state_round_wait_s"] >= 0 for record in landed)
    finally:
        LEDGER.remove_record_listener(on_record)
        for dht in dhts:
            dht.shutdown()


# ------------------------------------------------- snapshot / spool / boards


def _fat_device_section():
    return {
        "compiles": {
            "total": 40, "seconds": 12.5, "storms": 1,
            "sites": {
                f"site.{i}": {"count": 4, "seconds": 1.0, "signature": "x" * 200}
                for i in range(16)
            },
            "last": {"site": "site.0", "count": 4, "dur_s": 0.5, "signature": "x" * 200},
        },
        "memory": {
            "devices": {
                f"cpu:{i}": {"bytes": 1 << 20, "buffers": 100, "peak_bytes": 1 << 21}
                for i in range(8)
            },
            "total_bytes": 8 << 20,
            "buffers": 800,
        },
        "transfer_bytes": {"host_to_device": 123456, "device_to_host": 654321},
    }


def test_device_snapshot_is_empty_when_nothing_happened():
    reset_device_telemetry()
    assert device_snapshot() == {}


def test_device_snapshot_surfaces_activity():
    COMPILE_TRACKER.record_compile("test.site", duration_s=0.1)
    record_transfer(512, "host_to_device")
    snapshot = device_snapshot()
    assert snapshot["compiles"]["sites"]["test.site"]["count"] == 1
    assert snapshot["transfer_bytes"]["host_to_device"] >= 512


def test_shrink_to_fit_compacts_then_drops_the_device_section():
    device = _fat_device_section()
    base = {"time": 1.0, "peer": "p0", "metrics": {}}

    # generous budget: compaction suffices — headline numbers survive
    compact_budget = len(MSGPackSerializer.dumps(
        {**base, "device": compact_device_snapshot(device), "truncated": True}
    )) + 16
    shrunk = _shrink_to_fit({**base, "device": device}, max_bytes=compact_budget)
    assert shrunk["truncated"] is True
    assert shrunk["device"]["compiles"]["total"] == 40
    assert "sites" not in shrunk["device"]["compiles"]
    assert shrunk["device"]["memory"] == {"total_bytes": 8 << 20, "buffers": 800}
    assert shrunk["device"]["transfer_bytes"]["device_to_host"] == 654321
    assert len(MSGPackSerializer.dumps(shrunk)) <= compact_budget

    # brutal budget: the device section goes before the core health record
    tiny_budget = len(MSGPackSerializer.dumps({**base, "truncated": True})) + 8
    shrunk = _shrink_to_fit({**base, "device": device}, max_bytes=tiny_budget)
    assert "device" not in shrunk
    assert len(MSGPackSerializer.dumps(shrunk)) <= tiny_budget


def test_device_frames_spool_past_the_peer_filter_and_memory_is_throttled(tmp_path):
    from hivemind_tpu.hivemind_cli.run_blackbox import read_spool

    # peer_filter targets another peer: device telemetry is process-scoped
    # (one jit cache, one HBM pool), so device frames must bypass it
    box = BlackBox(tmp_path, peer_filter="someone_else", metrics_interval=None)
    try:
        COMPILE_TRACKER.record_compile("test.spooled", duration_s=0.05)
        memory_record = {"total_bytes": 1024, "buffers": 2, "devices": {}}
        box._on_device_record("memory", memory_record)
        box._on_device_record("memory", memory_record)  # inside the 5 s throttle
    finally:
        box.close()
    frames, _stats = read_spool(tmp_path)
    device_frames = [f for f in frames if f["k"] == "device"]
    kinds = [f["d"]["kind"] for f in device_frames]
    assert kinds.count("compile") == 1
    assert kinds.count("memory") == 1, "memory frames throttle to one per 5 s"
    compile_frame = next(f for f in device_frames if f["d"]["kind"] == "compile")
    assert compile_frame["d"]["site"] == "test.spooled"


def test_run_blackbox_aggregates_device_frames_into_postmortem_and_snapshot(tmp_path):
    from hivemind_tpu.hivemind_cli.run_blackbox import (
        load_spools,
        reconstruct_final_round,
        spool_snapshot,
    )
    from hivemind_tpu.hivemind_cli.run_top import render_device_board

    box = BlackBox(tmp_path, peer="p0", metrics_interval=None)
    try:
        COMPILE_TRACKER.record_compile("test.victim_site", duration_s=0.2)
        box._on_device_record(
            "memory", {"total_bytes": 4096, "buffers": 3, "devices": {}}
        )
        box._on_device_record("storm", {"site": "test.victim_site", "count": 7})
        # a frame as a peer from before PR 47 spooled it: the readers pass over it
        box.writer.append("device", {"kind": "overlap", "span": "allreduce.round", "overlap_ratio": 0.6})
    finally:
        box.close()

    spools = load_spools([tmp_path])
    frames = spools["p0"]["frames"]
    post = reconstruct_final_round(frames, spools["p0"]["stats"])
    assert post["device"]["compiles"]["total"] >= 1
    assert post["device"]["compiles"]["storms"] == 1
    assert post["device"]["last_compile"]["site"] == "test.victim_site"
    assert post["device"]["memory"]["total_bytes"] == 4096
    assert "overlap" not in post["device"]

    snapshot = spool_snapshot(spools["p0"])
    assert snapshot["device"]["compiles"]["total"] >= 1
    board = render_device_board({"p0": snapshot}, ansi=False)
    assert "p0" in board and "test.victim_site" in board
    # ... and so does the board, over such a peer's published section
    snapshot["device"]["overlap"] = {"rounds": 9, "last": 0.8, "mean": 0.7}
    assert render_device_board({"p0": snapshot}, ansi=False) == board


def test_device_board_renders_live_snapshot_shape():
    from hivemind_tpu.hivemind_cli.run_top import render_device_board

    records = {
        "peerA": {"device": _fat_device_section()},
        "peerB": {"device": {}},  # inactive peer: no row
        "peerC": {"device": {"compiles": "garbage"}},  # malformed: flagged row
    }
    board = render_device_board(records, ansi=False)
    assert "peerA" in board
    assert "peerB" not in board
    assert "malformed device section" in board
    assert "site.0" in board  # hot compile sites
    assert "recompile-storm" in board  # storms surface as alerts


def test_monitor_snapshot_includes_device_section_when_active():
    from hivemind_tpu.telemetry.monitor import build_peer_snapshot

    reset_device_telemetry()
    snapshot = build_peer_snapshot()
    assert "device" not in snapshot, "inactive device telemetry publishes nothing"

    COMPILE_TRACKER.record_compile("test.published", duration_s=0.01)
    snapshot = build_peer_snapshot()
    assert snapshot["device"]["compiles"]["sites"]["test.published"]["count"] == 1
