"""`python -m perf.run` on a machine without a TPU: exits non-zero, names the
platform it found, prints no result. And the benchmark alone (BENCHMARK.json and the
files under `paths`, no program) cannot run at all."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [cell["name"] for cell in MANIFEST["workloads"]]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run(args, cwd=ROOT, timeout=180):
    return subprocess.run([sys.executable, "-m", "perf.run", *args], cwd=cwd, env=ENV, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cpu_is_refused_without_a_result(trace):
    done = _run(["--workload", CELLS[0], "--seed", str(2**31 + 5), "--seconds", "1", "--trace", trace])
    assert done.returncode == 2
    assert "'cpu'" in done.stderr and "nothing to measure" in done.stderr
    assert done.stdout.strip() == ""


def test_unknown_cell_is_refused():
    done = _run(["--workload", "no-such.cell", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_benchmark_without_the_program_exits_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for base in MANIFEST["paths"]:
        shutil.copytree(ROOT / base, tmp_path / base, ignore=shutil.ignore_patterns("__pycache__"))
    env = {key: value for key, value in ENV.items() if key != "PYTHONPATH"}
    done = subprocess.run([sys.executable, "-m", "perf.run", "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                           "--trace", "0", "--rehearse-cpu"], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=180)
    assert done.returncode not in (0, 3) and done.stdout.strip() == ""
