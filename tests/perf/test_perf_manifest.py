"""The benchmark's data: `BENCHMARK.json` and every file it names parse, keep to the
contract's names, units and limits, and point at files, readers and cells that exist."""

import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import manifest as mf  # noqa: E402

MANIFEST = mf.load_manifest()
CELLS = [cell["name"] for cell in MANIFEST["workloads"]]
CONFIGS = [config["name"] for config in MANIFEST["configs"]]
END_TO_END = {metric["name"]: metric for metric in MANIFEST["end_to_end"]}
PER_LAYER = {metric["name"]: metric for metric in MANIFEST["per_layer"]}
WORKLOAD_FILES = sorted(path.stem for path in (mf.PERF / "workloads").glob("*.json"))
LAYER_METRIC_FILES = sorted(path.stem for path in (mf.PERF / "layer_metrics").glob("*.json"))
CONFIG_FILES = sorted(path.stem for path in (mf.PERF / "configs").glob("*.json"))
WIDTH_KEY = re.compile(r"(hidden|intermediate|latent|state|proj\w*|embedding|head)_size$|(_dim|_rank)$|expansion|experts_per_tok")
ONE_LINE = lambda text: 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text  # noqa: E731


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["perf", "tests/perf"]
    assert 1 <= len(MANIFEST["command"]) <= 32 and all(ONE_LINE(word) for word in MANIFEST["command"])
    assert not any(word.startswith("/") or ".." in word for word in MANIFEST["command"])
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    assert 1 <= len(CONFIGS) <= 24 and len(set(CONFIGS)) == len(CONFIGS)
    assert len(set(END_TO_END) | set(PER_LAYER)) == len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"])
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    four = sum(cell["chips"] == 4 for cell in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    # a full check: 2 + 14 x cells runs of run_seconds + 60 s, 180 s a cell to compile, 1200 s spare, with all 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_entry_and_file(name):
    entry = mf.by_name(MANIFEST["configs"], name, "configuration")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert mf.NAME_RE.match(name) and ONE_LINE(entry["source"]) and ONE_LINE(entry["why"])
    assert entry["source"].startswith("https://")
    assert entry["file"].startswith("perf/") and re.fullmatch(r"[A-Za-z0-9_.\-/]+", entry["file"])
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert mf.NAME_RE.match(key) and not WIDTH_KEY.search(key), f"{key}: a width may never be reduced"
    config = mf.load_config(MANIFEST, name)
    assert config["name"] == name and config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    assert set(config["reduced_why"]) == set(entry["reduced"])
    assert (mf.PERF / "runners" / f"{config['runner']}.py").exists()
    assert "rehearsal" in config and "assumed" in config and "deployment" in config and "tolerances" in config
    assert any(cell["config"] == name for cell in MANIFEST["workloads"]), "every configuration is used by some cell"
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(entry["file"]) == 1


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_every_configuration_file_parses(name):
    config = mf.load_json(mf.PERF / "configs" / f"{name}.json")
    assert config["name"] == name and isinstance(config["model"], dict)
    merged = mf.rehearsal_config(config)
    assert merged["model"].keys() >= config["model"].keys()


@pytest.mark.parametrize("name", CELLS)
def test_cell_entry(name):
    cell = mf.by_name(MANIFEST["workloads"], name, "cell")
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(mf.NAME_RE.match(cell[key]) for key in ("name", "config", "traffic"))
    assert cell["config"] in CONFIGS and cell["chips"] in (1, 4) and ONE_LINE(cell["why"])
    assert name == f"{cell['config']}.{cell['traffic']}"
    workload = mf.load_workload(name)
    assert workload["config"] == cell["config"] and workload["chips"] == cell["chips"] and workload["why"] == cell["why"]
    reported = [metric["name"] for metric in mf.cell_metrics(MANIFEST, name, "end_to_end")]
    assert "setup_s" in reported and len(reported) >= 2
    assert sorted(workload["end_to_end"]) == sorted(reported), "the cell's file gives a reader for each of its end-to-end metrics"
    assert mf.cell_metrics(MANIFEST, name, "per_layer"), "every cell reports at least one per-layer metric"


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_every_workload_file_parses(name):
    """Cells kept as data but not (yet) in the manifest are held to the same form."""
    workload = mf.load_workload(name)
    assert workload["name"] == name and mf.NAME_RE.match(name) and ONE_LINE(workload["why"])
    assert (mf.PERF / "configs" / f"{workload['config']}.json").exists()
    assert (mf.PERF / "traffic" / f"{workload['traffic']['generator']}.py").exists()
    for metric, spec in workload["end_to_end"].items():
        assert metric in END_TO_END and (mf.PERF / "readers" / f"{spec['reader']}.py").exists()


@pytest.mark.parametrize("name", sorted(END_TO_END))
def test_end_to_end_metric(name):
    metric = END_TO_END[name]
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert mf.NAME_RE.match(name) and mf.UNIT_RE.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_per_layer_metric(name):
    metric = PER_LAYER[name]
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert mf.NAME_RE.match(name) and mf.UNIT_RE.match(metric["unit"]) and metric["better"] in ("lower", "higher")
    assert metric["source"] in mf.SOURCES and ONE_LINE(metric["layer"])
    assert metric["moves"] in END_TO_END and metric["moves"] != "setup_s"
    if name.endswith("_roofline") or "mfu" in name:
        assert metric["unit"] == "%"
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert mf.metric_in_cell(END_TO_END[metric["moves"]], cell), f"{metric['moves']} is not reported in {cell}"
    spec = mf.load_layer_metric(name)
    assert spec["name"] == name and (mf.PERF / "readers" / f"{spec['reader']}.py").exists()


@pytest.mark.parametrize("name", LAYER_METRIC_FILES)
def test_every_layer_metric_file_parses(name):
    spec = mf.load_layer_metric(name)
    assert spec["name"] == name and mf.NAME_RE.match(name) and isinstance(spec.get("args", {}), dict)
    assert callable(mf.plugin("readers", spec["reader"]).read)


def test_layers_are_the_ones_perf_md_lists():
    text = (ROOT / "PERF.md").read_text()
    for layer in {metric["layer"] for metric in MANIFEST["per_layer"]}:
        assert f"| {layer} |" in text, f"PERF.md section 3 has no layer {layer!r}"


def test_run_py_names_no_configuration_cell_or_metric():
    source = (mf.PERF / "run.py").read_text()
    for name in CELLS + CONFIGS + list(END_TO_END) + list(PER_LAYER) + WORKLOAD_FILES:
        assert name not in source, f"perf/run.py names {name!r}"


def test_files_under_paths_are_named_from_allowed_characters():
    for base in MANIFEST["paths"]:
        for path in (ROOT / base).rglob("*"):
            if "__pycache__" in path.parts:
                continue
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", str(path.relative_to(ROOT))), path
