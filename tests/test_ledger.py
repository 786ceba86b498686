"""Round ledger (ISSUE 8): record assembly from a real two-peer averaging
round, straggler scoring, the DHT snapshot size budget, the ``GET /ledger``
round-trip, epoch rollups, and the ``hivemind-top`` / epoch-timeline renders."""

import json
import threading
import time
import urllib.request

import numpy as np

from hivemind_tpu.averaging import DecentralizedAverager
from hivemind_tpu.telemetry import LEDGER, MetricsExporter
from hivemind_tpu.telemetry.ledger import RoundLedger
from hivemind_tpu.telemetry.tracing import finish_span, start_span, trace

from swarm_utils import launch_dht_swarm, shutdown_all


def _synthetic_round(ledger: RoundLedger, exchanges, local_reduce_s=0.001, matchmaking=True):
    """Feed one round's spans straight into a ledger: exchanges is a list of
    (remote, seconds)."""
    if matchmaking:
        with trace("averaging.matchmaking", peer="me") as span:
            span.set("outcome", "assembled")
    round_span = start_span("allreduce.round", peer="me", group_size=len(exchanges) + 1, rank=0)
    local = start_span("allreduce.local_reduce", parent=round_span, peer="me")
    local.start -= local_reduce_s  # backdate instead of sleeping
    finish_span(local)
    for remote, seconds in exchanges:
        exchange = start_span("allreduce.peer_exchange", parent=round_span, peer="me", remote=remote)
        exchange.start -= seconds
        finish_span(exchange)
        ledger.on_span(exchange)
    ledger.on_span(local)
    if matchmaking:
        ledger.on_span(span)
    # the round wall time covers its phases: backdate like the children
    round_span.start -= max((seconds for _remote, seconds in exchanges), default=0.0) + local_reduce_s
    finish_span(round_span)
    ledger.on_span(round_span)


# ------------------------------------------------------------------ assembly


def test_record_assembly_from_real_two_peer_round():
    """The global LEDGER assembles records from the spans a REAL two-peer
    all-reduce produces — phases, partner attribution, matchmaking wait."""
    LEDGER.clear()
    # a round's record closes with its span, on the averager's loop, and an exchange that
    # was mid-cancellation then is attached to it later: the test waits for the records
    # it asserts on (counted as they are published), not for a time to have passed
    published = threading.Event()

    def on_record(kind, record):
        published.set()

    LEDGER.add_record_listener(on_record)

    def both_rounds_closed_and_one_attributed():
        records = LEDGER.records()
        return len({record["peer"] for record in records}) == 2 and any("slowest_peer" in r for r in records)

    dhts = launch_dht_swarm(2)
    averagers = []
    for i, dht in enumerate(dhts):
        tensors = [np.full(64, float(i), np.float32)]
        averagers.append(
            DecentralizedAverager(
                tensors, dht, prefix="ledgertest", start=True, target_group_size=2,
                min_matchmaking_time=1.0, request_timeout=1.0,
            )
        )
    try:
        controls = [a.step(wait=False, timeout=30) for a in averagers]
        for control in controls:
            control.result(timeout=60)
        with averagers[0].get_tensors() as tensors:
            assert np.allclose(tensors[0], 0.5)
        while not both_rounds_closed_and_one_attributed():
            assert published.wait(timeout=60), f"the ledger published no further record: {LEDGER.records()}"
            published.clear()
        records = LEDGER.records()
        # both peers live in this process: one record per peer's round (an
        # exchange span may still be mid-cancellation when its round closes, so
        # attribution is asserted on the records that carry it)
        assert len(records) >= 2, records
        peer_ids = {str(a.peer_id) for a in averagers}
        assert {record["peer"] for record in records} == peer_ids
        for record in records:
            assert record["group_size"] == 2
            assert record["total_s"] > 0
        attributed = [record for record in records if "slowest_peer" in record]
        assert attributed, records
        for record in attributed:
            # the one exchange partner is the OTHER peer
            assert record["slowest_peer"] in peer_ids - {record["peer"]}
            assert record["slowest_s"] > 0
        assert any("local_reduce_s" in record for record in records), records
        assert any("matchmaking_wait_s" in record for record in records), records
        scores = LEDGER.straggler_scores()
        assert set(scores) <= peer_ids and scores
        assert all(score["rounds_slowest"] >= 1 for score in scores.values())
    finally:
        LEDGER.remove_record_listener(on_record)
        shutdown_all(averagers, dhts)


def test_straggler_scoring_names_the_slow_partner():
    ledger = RoundLedger()
    for _ in range(3):
        _synthetic_round(ledger, [("slowpoke", 0.5), ("fast1", 0.01), ("fast2", 0.012)])
    _synthetic_round(ledger, [("fast1", 0.02), ("fast2", 0.01)])
    scores = ledger.straggler_scores()
    worst = next(iter(scores))
    assert worst == "slowpoke"
    assert scores["slowpoke"]["rounds_slowest"] == 3
    # excess is measured over the round's median exchange, so ~0.49/round
    assert scores["slowpoke"]["excess_s"] > 1.0
    assert scores["fast1"]["rounds_slowest"] == 1  # slowest of the last round
    records = ledger.records()
    assert len(records) == 4
    assert records[0]["slowest_peer"] == "slowpoke"
    assert records[0]["exchange_spread_s"] > 0.4
    summary = ledger.summary()
    assert summary["rounds"] == 4
    assert summary["total_s"]["p95"] >= summary["total_s"]["mean"]
    assert "slowpoke" in summary["stragglers"]


def test_epoch_rollup_carries_rounds_and_straggler():
    ledger = RoundLedger()
    _synthetic_round(ledger, [("laggard", 0.2), ("quick", 0.01)])
    _synthetic_round(ledger, [("laggard", 0.3), ("quick", 0.02)])
    entry = ledger.record_epoch(7, peer="me", averaged_ok=True, num_peers=3)
    assert entry["epoch"] == 7 and entry["rounds"] == 2
    assert entry["straggler"] == "laggard"
    assert entry["round_s"] > 0.5
    # the rollup window resets: the next epoch only sees its own rounds
    entry2 = ledger.record_epoch(8, peer="me", averaged_ok=False, num_peers=3)
    assert entry2["rounds"] == 0 and "straggler" not in entry2
    assert [e["epoch"] for e in ledger.epochs()] == [7, 8]


def test_epoch_windows_are_per_peer():
    """Several optimizers share one process (and this singleton) in soaks:
    peer A's transition must consume only A's rounds, not B's."""
    ledger = RoundLedger()

    def _round_for(peer, remote, seconds):
        round_span = start_span("allreduce.round", peer=peer, group_size=2, rank=0)
        exchange = start_span("allreduce.peer_exchange", parent=round_span, peer=peer, remote=remote)
        exchange.start -= seconds
        finish_span(exchange)
        ledger.on_span(exchange)
        round_span.start -= seconds
        finish_span(round_span)
        ledger.on_span(round_span)

    _round_for("peerA", "slowX", 0.2)
    _round_for("peerB", "slowY", 0.3)
    _round_for("peerA", "slowX", 0.1)
    entry_a = ledger.record_epoch(4, peer="peerA")
    assert entry_a["rounds"] == 2 and entry_a["straggler"] == "slowX"
    entry_b = ledger.record_epoch(4, peer="peerB")
    assert entry_b["rounds"] == 1 and entry_b["straggler"] == "slowY"
    assert abs(entry_b["round_s"] - 0.3) < 0.05


def test_late_exchange_retroattaches_and_reattributes():
    """The slowest partner's exchange span usually finishes AFTER its round's
    record closed (its delta completes the round output while the stream close
    is still in flight): the ledger must fold it in and move the round's
    straggler credit — otherwise it would drop exactly the peer it exists to
    name."""
    ledger = RoundLedger()
    round_span = start_span("allreduce.round", peer="me", group_size=3, rank=0)
    fast = start_span("allreduce.peer_exchange", parent=round_span, peer="me", remote="fast")
    fast.start -= 0.01
    finish_span(fast)
    ledger.on_span(fast)
    finish_span(round_span)
    ledger.on_span(round_span)
    assert ledger.records()[0]["slowest_peer"] == "fast"  # best knowledge so far
    # the true straggler's span lands after the round already closed
    late = start_span("allreduce.peer_exchange", parent=round_span, peer="me", remote="laggard")
    late.start -= 0.4
    late.add_event("retry")
    finish_span(late)
    ledger.on_span(late)
    record = ledger.records()[0]
    assert record["slowest_peer"] == "laggard" and record["slowest_s"] > 0.3
    assert len(record["exchanges"]) == 2
    assert record["events"]["retry"] == 1
    scores = ledger.straggler_scores()
    assert scores["laggard"]["rounds_slowest"] == 1
    assert scores["fast"]["rounds_slowest"] == 0  # its interim credit was retracted
    assert scores["fast"]["total_s"] > 0  # but its exchange time still counts


def test_concurrent_rounds_do_not_cross_contaminate():
    """Two interleaved rounds (grad + state averager share one process): each
    record only contains its own round's exchanges, keyed by parent span."""
    ledger = RoundLedger()
    round_a = start_span("allreduce.round", peer="me", group_size=2, rank=0)
    round_b = start_span("allreduce.round", peer="me", group_size=2, rank=1)
    for parent, remote, seconds in ((round_a, "peerA", 0.1), (round_b, "peerB", 0.2)):
        exchange = start_span("allreduce.peer_exchange", parent=parent, peer="me", remote=remote)
        exchange.start -= seconds
        finish_span(exchange)
        ledger.on_span(exchange)
    for round_span in (round_b, round_a):
        finish_span(round_span)
        ledger.on_span(round_span)
    records = {r["rank"]: r for r in ledger.records()}
    assert records[0]["slowest_peer"] == "peerA"
    assert records[1]["slowest_peer"] == "peerB"


# ------------------------------------------------------------------ budget


def test_snapshot_respects_dht_size_budget():
    from hivemind_tpu.telemetry.monitor import _shrink_to_fit
    from hivemind_tpu.utils.serializer import MSGPackSerializer

    ledger = RoundLedger()
    for index in range(200):
        _synthetic_round(ledger, [(f"peer-{index % 17}-{'x' * 40}", 0.01 + index * 1e-4)])
    compact = ledger.snapshot()
    # the compact view is bounded regardless of history length
    assert len(compact["records"]) <= 8
    assert len(compact["stragglers"]) <= 5
    assert all("exchanges" not in record for record in compact["records"])

    snapshot = {"time": 0.0, "metrics": {}, "ledger": compact}
    for budget in (4096, 1024, 256):
        shrunk = _shrink_to_fit(dict(snapshot), max_bytes=budget)
        assert len(MSGPackSerializer.dumps(shrunk)) <= budget
    # at a tight budget the bulky records go before the straggler scores do,
    # and at the tightest the whole ledger section is dropped, never a crash
    shrunk = _shrink_to_fit(dict(snapshot), max_bytes=1024)
    ledger_part = shrunk.get("ledger")
    assert ledger_part is None or "records" not in ledger_part or shrunk.get("truncated")


# ------------------------------------------------------------------ endpoint


def test_ledger_endpoint_roundtrip():
    ledger = RoundLedger()
    _synthetic_round(ledger, [("slowpoke", 0.25), ("quick", 0.01)])
    ledger.record_epoch(3, peer="me", averaged_ok=True, num_peers=2)
    exporter = MetricsExporter(port=0, ledger=ledger)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/ledger", timeout=5
        ).read()
    finally:
        exporter.shutdown()
    doc = json.loads(body)
    assert doc["records"][0]["slowest_peer"] == "slowpoke"
    assert doc["records"][0]["exchanges"][0]["remote"] == "slowpoke"  # raw, not compacted
    assert doc["straggler_scores"]["slowpoke"]["rounds_slowest"] == 1
    assert doc["epochs"][0]["epoch"] == 3 and doc["epochs"][0]["straggler"] == "slowpoke"
    assert doc["summary"]["rounds"] == 1


# ------------------------------------------------------------------ rendering


def _monitor_fixture_records():
    """Two-peer snapshot fixture: one healthy, one stale straggler-victimized
    peer with a stalled loop — every dashboard column has something to show."""
    now = time.time()
    healthy = {
        "peer_id": "peerHealthy",
        "time": now - 2.0,
        "metrics": {
            "hivemind_optim_local_epoch": {"type": "gauge", "series": {"_": 12}},
            "hivemind_optim_local_samples_accumulated": {"type": "gauge", "series": {"_": 640}},
            "hivemind_event_loop_lag_seconds": {
                "type": "histogram", "series": {"loop=hmtpu-loop": {"count": 100, "sum": 0.05}},
            },
        },
        "ledger": {
            "stragglers": {"peerStale": {"rounds_slowest": 4, "excess_s": 1.25, "total_s": 3.0}},
            "records": [{"round": 1, "slowest_peer": "peerStale", "total_s": 0.5, "group_size": 2}],
            "epochs": [
                {"epoch": 11, "peer": "peerHealthy", "rounds": 2, "round_s": 0.9,
                 "straggler": "peerStale", "averaged_ok": True},
                {"epoch": 12, "peer": "peerHealthy", "rounds": 1, "round_s": 0.4, "averaged_ok": True},
            ],
        },
    }
    stale = {
        "peer_id": "peerStale",
        "time": now - 500.0,  # way past 3x any sane publish interval
        "metrics": {
            "hivemind_optim_local_epoch": {"type": "gauge", "series": {"_": 9}},
            "hivemind_event_loop_stalls_total": {"type": "counter", "series": {"loop=hmtpu-loop": 2}},
        },
        "watchdog": {
            "loops": ["hmtpu-loop"], "stalls": 2, "max_lag_s": 1.7,
            "last_stall": {"time": now - 510.0, "loop": "hmtpu-loop", "blocked_s_at_capture": 1.5},
        },
        "breakers": {"dht_blacklist": {"num_tripped": 1, "tripped": ["peerGone"]}},
        "slow_spans": [{"name": "allreduce.round", "dur_ms": 9000.0, "events": ["error"]}],
    }
    return {"peerHealthy": healthy, "peerStale": stale}


def test_run_top_render_smoke():
    from hivemind_tpu.hivemind_cli.run_top import render_frame

    records = _monitor_fixture_records()
    frame, samples = render_frame(records, publish_interval=30.0, ansi=False)
    assert "hivemind-top" in frame and "2 peer(s)" in frame
    assert "peerHealthy" in frame and "peerStale" in frame
    assert "STALE" in frame and "LOOP-STALLED" in frame and "BREAKERS" in frame
    assert "stragglers" in frame and "slowest in    4 round(s)" in frame
    assert "recent alerts" in frame and "allreduce.round" in frame
    assert "peerHealthy" in samples  # samples gauge captured for the rate column
    # second frame computes the samples/s column from the delta
    records["peerHealthy"]["metrics"]["hivemind_optim_local_samples_accumulated"]["series"]["_"] = 740
    frame2, _ = render_frame(
        records, publish_interval=30.0, ansi=False,
        prev_samples={k: (v[0], v[1] - 10.0) for k, v in samples.items()},
    )
    assert "10.0" in frame2  # 100 samples over 10 s
    # ANSI mode prefixes the clear-screen control sequence
    ansi_frame, _ = render_frame(records, publish_interval=30.0, ansi=True)
    assert ansi_frame.startswith("\x1b[2J\x1b[H")


def test_renders_survive_malformed_peer_snapshot():
    """Snapshots come from the DHT: one buggy/hostile peer must get a flagged
    row, not kill every operator's dashboard or report."""
    from hivemind_tpu.hivemind_cli.run_top import render_frame
    from hivemind_tpu.telemetry.monitor import SwarmMonitor, aggregate_swarm_view

    records = _monitor_fixture_records()
    records["peerEvil"] = {
        "time": "not-a-number",
        "metrics": "nope",
        "ledger": {"epochs": [{"epoch": None}, {"epoch": 3, "rounds": "many", "round_s": {}}],
                   "stragglers": {"x": {"rounds_slowest": "NaNish"}}},
        "watchdog": [],
    }
    frame, _ = render_frame(records, publish_interval=30.0, ansi=False)
    assert "<malformed snapshot>" in frame
    assert "peerHealthy" in frame  # healthy peers still render fully

    monitor = SwarmMonitor.__new__(SwarmMonitor)
    report = monitor.render_report(aggregate_swarm_view(
        {k: v for k, v in records.items() if isinstance(v.get("time"), (int, float)) or k == "peerEvil"}
    ))
    assert "epoch timeline" in report  # healthy entries survive
    assert "<malformed ledger entry>" in report or "epoch 3" in report


def test_render_report_epoch_timeline_and_stale_flag():
    from hivemind_tpu.telemetry.monitor import SwarmMonitor, aggregate_swarm_view

    monitor = SwarmMonitor.__new__(SwarmMonitor)
    monitor.publish_interval = 30.0
    view = aggregate_swarm_view(_monitor_fixture_records())
    report = monitor.render_report(view)
    assert "STALE" in report, report
    assert "epoch timeline" in report and "epoch 11" in report
    assert "slowest=peerStale" in report
    assert "WATCHDOG: 2 event-loop stall(s)" in report
    assert "straggler seen: peerStale" in report
    # the raw ledger/watchdog dicts must not be dumped inline on the peer line
    peer_line = next(line for line in report.splitlines() if "peerHealthy" in line and "peer " in line)
    assert "stragglers" not in peer_line
