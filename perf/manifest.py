"""Where the benchmark's data lives and how it is found by name.

`BENCHMARK.json` (repo root) lists configurations, cells (`workloads`) and metrics.
Everything that belongs to ONE of them sits in a file of its own, found by that name:

    perf/configs/<configuration>.json       sizes as run, source, cuts, rehearsal sizes
    perf/workloads/<cell>.json              traffic generator + parameters, end-to-end readers
    perf/layer_metrics/<metric>.json        the per-layer metric's reader + arguments
    perf/runners/<runner>.py                builds the system under test (named by the configuration)
    perf/traffic/<generator>.py             turns a cell's parameters + seed into a schedule
    perf/readers/<reader>.py                one number out of a run's observations

A later PR adds files and entries; nothing here names a configuration, a cell or a
metric."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
PERF = ROOT / "perf"
MANIFEST_PATH = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as handle:
        return json.load(handle)


def load_manifest() -> Dict[str, Any]:
    return load_json(MANIFEST_PATH)


def by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json (has: {[e['name'] for e in entries]})")


def load_workload(name: str) -> Dict[str, Any]:
    return load_json(PERF / "workloads" / f"{name}.json")


def load_config(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    return load_json(ROOT / by_name(manifest["configs"], name, "configuration")["file"])


def load_layer_metric(name: str) -> Dict[str, Any]:
    return load_json(PERF / "layer_metrics" / f"{name}.json")


def metric_in_cell(entry: Dict[str, Any], cell: str) -> bool:
    """A metric with no `workloads` key is reported in every cell."""
    return "workloads" not in entry or cell in entry["workloads"]


def cell_metrics(manifest: Dict[str, Any], cell: str, kind: str) -> List[Dict[str, Any]]:
    return [entry for entry in manifest[kind] if metric_in_cell(entry, cell)]


def rehearsal_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with its `rehearsal` block laid over it (toy sizes, CPU)."""
    merged = json.loads(json.dumps(config))
    for section, values in config.get("rehearsal", {}).items():
        merged.setdefault(section, {}).update(values)
    return merged


def plugin(kind: str, name: str):
    """`perf/<kind>/<name>.py`, imported by name; a new one is a new file."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"perf.{kind}.{name}")


def read_metric(spec: Dict[str, Any], observations: Dict[str, Any]) -> Optional[float]:
    """Run one reader (`{"reader": name, "args": {...}}`) over a run's observations.
    A reader that finds nothing to read returns None and the metric is left out."""
    value = plugin("readers", spec["reader"]).read(observations, **spec.get("args", {}))
    return None if value is None else float(value)
