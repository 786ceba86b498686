"""bench.py names the device behind every number and has no way to report one it
did not take: no CPU fallback, no default peak, no child whose failure is skipped.
The host-side drivers it starts are exercised here in their --smoke modes."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH_PATH = os.path.join(_REPO, "bench.py")
_spec = importlib.util.spec_from_file_location("bench", _BENCH_PATH)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


class _Device:
    platform = "tpu"

    def __init__(self, device_kind):
        self.device_kind = device_kind


def test_peak_flops_knows_the_v5e_and_refuses_an_unknown_device():
    assert bench.peak_flops(_Device("TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no published peak for device_kind 'TPU v99'"):
        bench.peak_flops(_Device("TPU v99"))
    with pytest.raises(ValueError):
        bench.peak_flops(_Device("cpu"))


def test_bench_has_no_path_around_a_missing_chip():
    """The device measurement fails on this CPU host — exit code non-zero, no JSON
    result on stdout — and the source holds none of the old escape hatches."""
    run = subprocess.run(
        [sys.executable, "-c", "import bench; bench.measure_main()"], cwd=_REPO, timeout=240,
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode != 0
    assert "jax found platform 'cpu'" in run.stderr
    assert not any(line.startswith("{") for line in run.stdout.splitlines())
    source = open(_BENCH_PATH).read()
    for gone in ("force_cpu", "tpu_unavailable", "fallback", "_tpu_probe", "compact_result", "_host_control"):
        assert gone not in source, gone


def test_a_failed_host_driver_fails_the_run(tmp_path, monkeypatch):
    """A child that exits non-zero, or prints no result, raises — it used to return
    None and let the run exit 0 with a hole in it."""
    scripts = tmp_path / "benchmarks"
    scripts.mkdir()
    (scripts / "crashes.py").write_text("import sys; print('{\"value\": 1}'); sys.exit(4)")
    (scripts / "silent.py").write_text("print('nothing to see')")
    (scripts / "works.py").write_text(
        "import json, os; print(json.dumps({'value': 2, 'platform': os.environ['JAX_PLATFORMS']}))"
    )
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    with pytest.raises(RuntimeError, match="exited with code 4"):
        bench._run_host_driver("crashes.py", [], timeout=60)
    with pytest.raises(RuntimeError, match="printed no result"):
        bench._run_host_driver("silent.py", [], timeout=60)
    # and the child that works was pinned to the CPU: this process owns the chip
    assert bench._run_host_driver("works.py", [], timeout=60) == {"value": 2, "platform": "cpu"}


def test_bench_artifact_embeds_telemetry_snapshot():
    """ISSUE 2: every BENCH artifact carries a telemetry snapshot — the bench
    process's registry plus the averaging swarm's (shipped via its JSON extra)."""
    from hivemind_tpu.telemetry import REGISTRY

    REGISTRY.counter("bench_emission_probe_total", "test counter").inc(5)
    averaging = {
        "value": 0.61,
        "extra": {"telemetry": {"hivemind_averaging_matchmaking_rounds_total": {
            "type": "counter", "series": {"outcome=assembled": 8}}}},
    }
    try:
        section = bench.telemetry_section(averaging)
    finally:
        REGISTRY.unregister("bench_emission_probe_total")  # keep the global registry clean
    assert section["bench_process"]["metrics"]["bench_emission_probe_total"]["series"]["_"] == 5
    assert section["averaging_swarm"]["hivemind_averaging_matchmaking_rounds_total"]["series"][
        "outcome=assembled"] == 8
    json.dumps(section)  # the artifact is one JSON line: the section must serialize


def test_telemetry_section_survives_missing_averaging():
    section = bench.telemetry_section(None)
    assert "bench_process" in section or "error" in section
    assert "averaging_swarm" not in section


def test_bench_artifact_embeds_ledger_and_watchdog_attribution():
    """ISSUE 8: the averaging swarm's ledger + watchdog rollup rides the BENCH
    artifact, so a perf regression carries attribution (rounds, per-phase
    mean/p95, straggler scores, stall count, max loop lag), not just the
    headline number."""
    averaging = {
        "value": 0.3,
        "extra": {
            "telemetry": {},
            "attribution": {
                "ledger": {
                    "rounds": 12,
                    "total_s": {"mean": 0.8, "p95": 1.4},
                    "matchmaking_wait_s": {"mean": 0.4, "p95": 0.9},
                    "stragglers": {"peerX": {"rounds_slowest": 7, "excess_s": 2.1}},
                },
                "watchdog": {"loops": ["hmtpu-loop"], "stalls": 0, "max_lag_s": 0.004},
            },
        },
    }
    section = bench.telemetry_section(averaging)
    assert section["attribution"]["ledger"]["rounds"] == 12
    assert section["attribution"]["ledger"]["total_s"]["p95"] == 1.4
    assert section["attribution"]["watchdog"]["stalls"] == 0
    assert section["attribution"]["ledger"]["stragglers"]["peerX"]["rounds_slowest"] == 7


def test_benchmark_averaging_smoke_uniform8():
    """ISSUE 11: the quantized averaging tier end-to-end in --smoke mode —
    2 peers negotiate uniform8 links (with error-feedback residuals) through
    the real DHT + matchmaking + butterfly path; any failed step exits nonzero,
    so a quantized-wire regression fails tier-1 loudly. Mirrors the fp16 smoke
    in test_partition_equivalence.py (bench.py's `_averaging_gbps_q8` runs the
    same codec at the full 4-peer/4M config)."""
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "benchmark_averaging.py",
    )
    run = subprocess.run(
        [sys.executable, script, "--smoke", "--compression", "uniform8"],
        timeout=180,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, f"smoke benchmark failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}"
    payload = next(line for line in run.stdout.splitlines() if line.startswith("{"))
    result = json.loads(payload)
    assert result["extra"]["success_rate"] == 1.0
    assert result["extra"]["compression"] == "uniform_8bit"


def test_benchmark_llama_serving_smoke():
    """ISSUE 10: the serving data path end-to-end (checkpoint load + Server +
    RemoteSequential KV-cache decode over real RPC) — --smoke exits nonzero on
    any failed request or if the serving wire-bytes counters did not move, so a
    compressed-RPC/batching regression fails tier-1 loudly (mirrors the
    benchmark_averaging smoke pattern)."""
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "benchmark_llama_serving.py",
    )
    run = subprocess.run(
        [sys.executable, script, "--smoke", "--platform", "cpu"],
        timeout=240,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, f"smoke benchmark failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}"
    payload = next(line for line in run.stdout.splitlines() if line.startswith("{"))
    result = json.loads(payload)
    assert result["metric"] == "llama_checkpoint_decode"
    # any failed request exits nonzero before the JSON prints (asserted above)
    wire = result["extra"]["wire_bytes_per_token"]
    assert wire["sent"] > 0 and wire["received"] > 0
    # the default A/B config rides fp16 activations on the wire
    assert result["extra"]["activation_compression"] == "float16"


def test_benchmark_llama_multi_client_smoke():
    """ISSUE 13: the skewed multi-client load generator end to end — one hot
    client + a background client over TWO replicas with fair-share admission
    armed and one replica crash-killed mid-run. --smoke exits nonzero on any
    non-shed client-visible failure, on a background-client shed (fair-share
    violated), or on a client decoding zero tokens."""
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "benchmark_llama_serving.py",
    )
    run = subprocess.run(
        [sys.executable, script, "--smoke", "--multi_client", "1", "--replicas", "2",
         "--kill_replica_at", "0.5", "--client_rate", "40", "--platform", "cpu"],
        timeout=300,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, f"smoke benchmark failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}"
    payload = next(line for line in run.stdout.splitlines() if line.startswith("{"))
    result = json.loads(payload)
    assert result["metric"] == "llama_multi_client_decode"
    clients = result["extra"]["clients"]
    assert set(clients) == {"hot", "bg0"}
    for name, entry in clients.items():
        assert entry["failures"] == [], (name, entry)
        assert entry["tokens"] > 0 and "p99_ms" in entry, (name, entry)
    # the kill actually happened and the replica set was real
    assert result["extra"]["killed_replica_at_s"] is not None
    assert result["extra"]["replicas"] == 2
    # background client untouched by the hot client's saturation
    assert clients["bg0"]["sheds"] == 0


def test_benchmark_swarm_sim_smoke():
    """ISSUE 12: the swarm simulator end-to-end in --smoke mode — a ~100-peer
    composite (DHT fan-out under churn + link-scoped chaos, matchmaking
    convergence across a partition, beam search vs oracle) plus a
    same-seed-twice determinism double-run; any failed invariant exits nonzero,
    so a sim/transport regression fails tier-1 loudly (mirrors the averaging
    and serving smoke patterns)."""
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "benchmark_swarm_sim.py",
    )
    run = subprocess.run(
        [sys.executable, script, "--smoke", "--seed", "17"],
        timeout=420,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode == 0, f"smoke benchmark failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}"
    payload = next(line for line in run.stdout.splitlines() if line.startswith("{"))
    result = json.loads(payload)
    assert result["metric"] == "swarm_sim_peers"
    assert result["value"] >= 90  # ~100 peers simulated across the composite
    assert result["extra"]["deterministic"] is True
    assert result["extra"]["recall_at_beam"] >= 0.95
    assert result["extra"]["failures"] == []


def test_bench_artifact_embeds_serving_attribution():
    """ISSUE 9: the llama-serving swarm's per-request attribution summary rides
    the BENCH artifact under telemetry.serving — per-expert p50/p95, phase
    decomposition, batch occupancy, shed count."""
    serving = {
        "value": 19.0,
        "extra": {
            "serving": {
                "requests": 98, "errors": 0, "sheds": 0,
                "phases": {
                    "total_s": {"mean": 0.05, "p50": 0.04, "p95": 0.11},
                    "compute_s": {"mean": 0.03, "p50": 0.03, "p95": 0.06},
                },
                "batch_occupancy": {"mean": 0.002, "p50": 0.002, "p95": 0.002},
                "experts": {"lb.0": {"requests": 49, "p95_s": 0.06, "p50_s": 0.04}},
            },
        },
    }
    section = bench.telemetry_section(None, serving)
    assert section["serving"]["requests"] == 98
    assert section["serving"]["experts"]["lb.0"]["p95_s"] == 0.06
    assert section["serving"]["phases"]["compute_s"]["p95"] == 0.06
    # missing serving stays absent, never a crash
    assert "serving" not in bench.telemetry_section(None, None)
