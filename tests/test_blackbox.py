"""Black-box flight recorder (ISSUE 17): spool durability, post-mortem
reconstruction, skew-corrected merge, and sim virtual-time determinism.

The durability tests attack the on-disk format the way crashes do — torn
tails, bit flips, concurrent writers, restarts over a corpse — and assert the
reader degrades frame-by-frame instead of losing the spool. The sim test pins
the headline ISSUE 17 property: two same-seed scenario runs leave
bit-identical ``ledger_round`` frame streams in every peer's spool.
"""

import json
import struct
import threading

import pytest

from hivemind_tpu.hivemind_cli.run_blackbox import (
    estimate_skew,
    load_spools,
    main as blackbox_main,
    merge_timeline,
    reconstruct_final_round,
    render_spool_chrome_trace,
    spool_snapshot,
)
from hivemind_tpu.hivemind_cli.run_top import render_frame
from hivemind_tpu.sim import run_scenario
from hivemind_tpu.telemetry.blackbox import (
    READ_SKIPPED,
    BlackBox,
    SpoolWriter,
    arm_blackbox,
    disarm_blackbox,
    read_spool,
)
from hivemind_tpu.telemetry.ledger import RoundLedger
from hivemind_tpu.telemetry.registry import MetricsRegistry
from hivemind_tpu.telemetry.tracing import finish_span, start_span, trace

_FRAME_HEADER = struct.Struct(">II")


# ------------------------------------------------------------- spool durability


def test_rotation_under_concurrent_writers(tmp_path):
    """Many threads hammering one writer: every frame lands exactly once, in a
    frame-aligned segment, across however many rotations that forces."""
    writer = SpoolWriter(tmp_path, peer="p0", segment_bytes=4096, retention_segments=64)
    n_threads, per_thread = 8, 200

    def _pound(worker: int) -> None:
        for i in range(per_thread):
            writer.append("span", {"name": f"w{worker}", "i": i})

    threads = [threading.Thread(target=_pound, args=(w,)) for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    writer.close()

    frames, stats = read_spool(tmp_path)
    assert stats["torn_tail"] == 0 and stats["corrupt"] == 0
    assert stats["segments"] > 1, "4KiB segments must have rotated"
    assert len(list(tmp_path.glob("spool-*.open"))) == 0, "close() publishes the tail"
    spans = [f for f in frames if f["k"] == "span"]
    assert len(spans) == n_threads * per_thread
    # exactly-once per (worker, i): no frame lost or duplicated by rotation races
    seen = {(f["d"]["name"], f["d"]["i"]) for f in spans}
    assert len(seen) == n_threads * per_thread
    headers = [f for f in frames if f["k"] == "header"]
    assert len(headers) == stats["segments"], "every segment starts with a header"


def test_torn_tail_is_truncated_and_counted(tmp_path):
    """A kill-9 mid-frame leaves a half-written tail: the reader keeps every
    complete frame and counts the tear instead of exploding."""
    writer = SpoolWriter(tmp_path, peer="p0")
    for i in range(5):
        writer.append("span", {"i": i})
    # simulate the crash: close the fd without publishing, then tear the tail
    with writer._lock:
        writer._file.close()
        writer._file = None
    (open_seg,) = tmp_path.glob("spool-*.open")
    open_seg.write_bytes(open_seg.read_bytes()[:-7])  # mid-payload tear

    frames, stats = read_spool(tmp_path)
    assert stats["torn_tail"] == 1
    assert stats["corrupt"] == 0
    spans = [f["d"]["i"] for f in frames if f["k"] == "span"]
    assert spans == [0, 1, 2, 3], "all complete frames survive; only the torn one is lost"


def test_retention_cap_bounds_the_spool(tmp_path):
    writer = SpoolWriter(tmp_path, peer="p0", segment_bytes=2048, retention_segments=2)
    for i in range(400):
        writer.append("span", {"i": i, "pad": "x" * 64})
    writer.close()
    segments = sorted(tmp_path.glob("spool-*.seg"))
    assert len(segments) == 2, "oldest segments must be deleted past the cap"
    frames, _stats = read_spool(tmp_path)
    spans = [f["d"]["i"] for f in frames if f["k"] == "span"]
    # the survivors are the NEWEST frames, still contiguous and in order
    assert spans == list(range(spans[0], 400))


def test_corrupt_frame_is_skipped_frame_aligned(tmp_path):
    """A bit flip inside one payload: that frame dies (crc), every later frame
    still reads — the length header kept the stream aligned."""
    writer = SpoolWriter(tmp_path, peer="p0")
    for i in range(6):
        writer.append("span", {"i": i})
    writer.close()
    (seg,) = tmp_path.glob("spool-*.seg")
    raw = bytearray(seg.read_bytes())
    # walk to the 3rd frame (header frame + spans 0,1) and flip a payload byte
    offset = 0
    for _ in range(3):
        length, _crc = _FRAME_HEADER.unpack_from(raw, offset)
        offset += _FRAME_HEADER.size + length
    length, _crc = _FRAME_HEADER.unpack_from(raw, offset)
    raw[offset + _FRAME_HEADER.size + 2] ^= 0xFF
    seg.write_bytes(bytes(raw))

    skipped_before = READ_SKIPPED.value(reason="crc")
    frames, stats = read_spool(tmp_path)
    assert stats["corrupt"] == 1 and stats["torn_tail"] == 0
    assert READ_SKIPPED.value(reason="crc") == skipped_before + 1
    spans = [f["d"]["i"] for f in frames if f["k"] == "span"]
    assert spans == [0, 1, 3, 4, 5], "only the flipped frame is lost"


def test_restart_publishes_the_previous_incarnations_open_segment(tmp_path):
    """A restarted peer spooling into the same directory must not clobber its
    pre-crash evidence: the leftover .open is promoted to .seg and segment
    numbering continues past it."""
    first = SpoolWriter(tmp_path, peer="p0")
    first.append("span", {"life": 1})
    with first._lock:  # die without publishing
        first._file.close()
        first._file = None
    assert len(list(tmp_path.glob("spool-*.open"))) == 1

    second = SpoolWriter(tmp_path, peer="p0")
    second.append("span", {"life": 2})
    second.close()

    assert len(list(tmp_path.glob("spool-*.open"))) == 0
    frames, stats = read_spool(tmp_path)
    assert stats["segments"] == 2 and stats["torn_tail"] == 0
    lives = [f["d"]["life"] for f in frames if f["k"] == "span"]
    assert lives == [1, 2]


# ------------------------------------------------- listeners and post-mortem


def test_blackbox_spools_spans_and_reconstructs_the_crash_site(tmp_path):
    box = BlackBox(tmp_path, peer="p0", metrics_interval=None)
    with trace("optimizer.step", peer="p0"):
        pass
    # the operation the peer "dies inside": started, never finished
    start_span("averaging.allreduce", peer="p0")
    box.writer.append("ledger_round", {"round": 7, "slowest_peer": "pX", "peer": "p0"})
    box.abandon()  # kill-9 semantics: .open stays behind, unpublished

    assert len(list(tmp_path.glob("spool-*.open"))) == 1
    frames, stats = read_spool(tmp_path)
    kinds = [f["k"] for f in frames]
    assert kinds.count("span_start") == 2 and kinds.count("span") == 1

    post = reconstruct_final_round(frames, stats)
    assert post["reconstructed"] is True
    assert post["final_round"]["round"] == 7
    assert post["last_in_flight"]["name"] == "averaging.allreduce"
    assert post["open_spans"] == 1
    assert post["last_span"]["name"] == "optimizer.step"


def test_peer_filter_scopes_a_shared_telemetry_plane(tmp_path):
    """Multi-peer harnesses (soak, sim) arm one box per peer on one process:
    only frames attributable to the filtered peer may land in its spool."""
    box = BlackBox(tmp_path, peer_filter="pA", metrics_interval=None)
    try:
        with trace("dht.store", peer="pA"):
            pass
        with trace("dht.store", peer="pB"):
            pass
        with trace("dht.store"):  # no peer attribute at all
            pass
    finally:
        box.close()
    frames, _stats = read_spool(tmp_path)
    spans = [f for f in frames if f["k"] in ("span", "span_start")]
    assert spans, "the filtered peer's spans must spool"
    assert all(f["d"]["attrs"]["peer"] == "pA" for f in spans)


def test_arm_blackbox_is_idempotent_per_directory(tmp_path):
    try:
        box = arm_blackbox(tmp_path / "a", peer="p0", metrics_interval=None)
        assert arm_blackbox(tmp_path / "a", metrics_interval=None) is box
        other = arm_blackbox(tmp_path / "b", peer="p0", metrics_interval=None)
        assert other is not box
        assert box._closed, "re-arming a new directory closes the old box"
    finally:
        disarm_blackbox()


def test_closed_writer_swallows_late_listener_fires(tmp_path):
    box = BlackBox(tmp_path, peer="p0", metrics_interval=None)
    box.close()
    box.writer.append("span", {"late": True})  # must be a no-op, not a crash
    frames, _stats = read_spool(tmp_path)
    assert all(f["k"] == "header" for f in frames)


# --------------------------------------------------------- cross-peer merging


def _spoolset(*peers):
    """Synthetic load_spools() shape: {peer: {"frames", "stats", "header"}}."""
    return {
        peer: {"frames": frames, "stats": {"frames": len(frames), "segments": 1,
                                           "torn_tail": 0, "corrupt": 0},
               "header": {"peer": peer, "clock": "wall"}}
        for peer, frames in peers
    }


def test_skew_estimate_restores_cross_peer_causality():
    """Peer B's clock runs 10s behind: its child span 'starts before' the
    remote parent that caused it. The estimator must shift B forward until
    causality holds again."""
    parent = {"t": 100.0, "k": "span", "d": {"name": "rpc", "trace": "t1",
                                             "span": "aaaa", "start": 100.0, "dur_s": 1.0}}
    child = {"t": 90.2, "k": "span", "d": {"name": "handle", "trace": "t1", "span": "bbbb",
                                           "parent": "aaaa", "start": 90.2, "dur_s": 0.5}}
    spools = _spoolset(("A", [parent]), ("B", [child]))
    offsets = estimate_skew(spools)
    assert offsets["A"] == 0.0
    assert offsets["B"] == pytest.approx(9.8)

    merged = merge_timeline(spools, offsets)
    times = {f["peer"]: f["t"] for f in merged}
    assert times["B"] >= times["A"], "corrected child may not precede its parent"


def test_merge_timeline_last_window_anchors_on_the_victim():
    frames_a = [{"t": t, "k": "span", "d": {"span": f"a{t}", "start": t}} for t in (10.0, 50.0)]
    frames_b = [{"t": t, "k": "span", "d": {"span": f"b{t}", "start": t}} for t in (12.0, 30.0)]
    spools = _spoolset(("A", frames_a), ("B", frames_b))
    # victim B died at t=30: the window must end there, not at A's t=50
    merged = merge_timeline(spools, {"A": 0.0, "B": 0.0}, last_s=20.0, victim="B")
    assert [f["t"] for f in merged] == [10.0, 12.0, 30.0]


def test_chrome_export_marks_the_crash_site_in_flight():
    merged = [
        {"t": 1.0, "peer": "A", "k": "span",
         "d": {"name": "step", "trace": "t1", "span": "s1", "start": 1.0, "dur_s": 0.25}},
        {"t": 1.5, "peer": "A", "k": "span_start",
         "d": {"name": "allreduce", "trace": "t1", "span": "s2", "start": 1.5}},
    ]
    doc = render_spool_chrome_trace(merged)
    events = {e.get("name"): e for e in doc["traceEvents"]}
    assert events["step"]["ph"] == "X" and events["step"]["dur"] > 0
    assert events["allreduce"]["ph"] == "i", "unfinished span renders as an instant"
    assert events["allreduce"]["args"]["in_flight"] is True
    assert events["process_name"]["args"]["name"] == "peer A"


def test_spool_snapshot_feeds_the_dashboard(tmp_path):
    """hivemind-top --from-spool: a spool renders as a dashboard frame with
    straggler attribution recomputed from the spooled rounds."""
    box = BlackBox(tmp_path, peer="p0", metrics_interval=None)
    box.writer.append("ledger_round", {
        "round": 1, "peer": "p0", "slowest_peer": "pSlow",
        "exchanges": [{"peer": "pSlow", "dur_s": 2.0}, {"peer": "pFast", "dur_s": 0.5},
                      {"peer": "pMid", "dur_s": 0.6}],
    })
    with trace("optimizer.step", peer="p0"):
        pass
    box.snapshot_metrics()
    box.close()

    spools = load_spools([tmp_path])
    snapshot = spool_snapshot(spools["p0"])
    assert snapshot["ledger"]["records"][0]["round"] == 1
    scores = snapshot["ledger"]["stragglers"]["pSlow"]
    assert scores["rounds_slowest"] == 1 and scores["excess_s"] == pytest.approx(1.4)
    assert "metrics" in snapshot and snapshot["slow_spans"]

    frame, _samples = render_frame({"p0": snapshot}, now=snapshot["time"], ansi=False)
    assert "p0" in frame


def test_cli_end_to_end(tmp_path, capsys):
    spool_dir = tmp_path / "peerA"
    box = BlackBox(spool_dir, peer="peerA", metrics_interval=None)
    with trace("dht.store", peer="peerA"):
        pass
    start_span("averaging.allreduce", peer="peerA")
    box.writer.append("ledger_round", {"round": 3, "peer": "peerA", "slowest_peer": "pX"})
    box.abandon()

    assert blackbox_main([str(spool_dir), "--victim", "peerA", "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    post = report["postmortem"]["peerA"]
    assert post["final_round"]["round"] == 3
    assert post["last_in_flight"]["name"] == "averaging.allreduce"

    out = tmp_path / "trace.json"
    assert blackbox_main([str(spool_dir), "--format", "chrome", "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "averaging.allreduce" in names and "dht.store" in names


# --------------------------------------------------- sim virtual-time spools


def test_sim_same_seed_spools_are_bit_identical(tmp_path):
    """ISSUE 17 acceptance: a seeded sim scenario with per-peer spools leaves
    bit-identical virtual-time ``ledger_round`` frame streams (straggler
    attribution included) across two same-seed runs."""
    params = dict(peers=24, regions=2, keys=40, churn_fraction=0.15, probe_samples=10,
                  matchmaking_peers=8, matchmaking_rounds=1)
    first = run_scenario("dht_churn", seed=33, blackbox_root=str(tmp_path / "one"), **params)
    second = run_scenario("dht_churn", seed=33, blackbox_root=str(tmp_path / "two"), **params)

    ledger = first.summary["matchmaking"]["ledger"]
    assert ledger["rounds"] > 0, "the cohort must have produced virtual-time rounds"
    assert first.digest() == second.digest(), "the ledger summary rides the digest"

    one = sorted(p.name for p in (tmp_path / "one").iterdir())
    two = sorted(p.name for p in (tmp_path / "two").iterdir())
    assert one == two and len(one) == 8, "one spool per cohort peer"
    compared_rounds = 0
    for name in one:
        frames_one, stats_one = read_spool(tmp_path / "one" / name)
        frames_two, stats_two = read_spool(tmp_path / "two" / name)
        assert stats_one["torn_tail"] == 0 and stats_one["corrupt"] == 0
        rounds_one = [f for f in frames_one if f["k"] == "ledger_round"]
        rounds_two = [f for f in frames_two if f["k"] == "ledger_round"]
        # full frames — virtual timestamps included — must match bit for bit
        assert rounds_one == rounds_two
        compared_rounds += len(rounds_one)
        # virtual clock: frame timestamps are sim-time (epoch-magnitude anchor)
        assert all(f["t"] >= 1e9 for f in rounds_one)
    assert compared_rounds > 0, "at least one peer must have spooled its rounds"
