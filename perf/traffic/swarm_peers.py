"""Trainer traffic: which peers train together and what data each draws.

Parameters (the cell's `traffic`): `peers` (each `{"kind": "optimizer" | "slice"}`),
`target_group_size`, `warm_epochs` (epochs closed before the window), `trace_seconds`.
The work is fixed by the configuration (every step is the same size); the seed
changes the weights' and each peer's data stream only."""

from __future__ import annotations

from typing import Any, Dict


def schedule(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    base = (int(seed) * 1_000_003) % (2**31 - 1)
    return {
        "init_seed": base,
        "peers": [
            {"kind": peer["kind"], "data_seed": (base + 7919 * (index + 1)) % (2**31 - 1)}
            for index, peer in enumerate(params["peers"])
        ],
        "target_group_size": params["target_group_size"],
        "warm_epochs": params.get("warm_epochs", 1),
    }
