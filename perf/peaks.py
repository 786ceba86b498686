"""The table of published peaks (`perf/peaks.json`), keyed by `device_kind`."""

from __future__ import annotations

from typing import Any, Dict

from perf.manifest import PERF, load_json


def peak_for(device_kind: str) -> Dict[str, Any]:
    kind = device_kind.lower()
    for key, row in load_json(PERF / "peaks.json")["devices"].items():
        if key in kind:
            return row
    raise LookupError(f"no published peak for device_kind {device_kind!r} in perf/peaks.json; "
                      f"add the row with its source")
