"""Closed-loop fine-tuning requests: each slot sends the forward and then the
backward of `sequences` x `positions` hidden states through the whole span
(`RemoteSequential.__call__` under `jax.vjp`), and at once sends the next.

Parameters: `processes` x `slots_per_process` slots, `sequences`, `positions`,
`lead_seconds` (the slots start that long before the window, uncounted). Every
request is the same size; the seed changes the tensors only."""

from __future__ import annotations

import random
import time
from typing import Any, Dict, List

SERVER_PATH = "forward_backward"  # which of the server's paths this traffic takes: warmed and checked, no other


def schedule(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    rng = random.Random(int(seed))
    shape = [params["sequences"], params["positions"]]
    return {"processes": [
        [[shape[0], shape[1], rng.randrange(2**31)] for _ in range(params["slots_per_process"])]
        for _ in range(params["processes"])
    ]}


def drive_slot(pipe, plan: List[int], ctx: Dict[str, Any], out: Dict[str, Any]) -> None:
    import jax
    import numpy as np

    from perf.runtime import float16_exact

    sequences, positions, tensor_seed = plan
    begin, end, hidden, tag = ctx["begin"], ctx["end"], ctx["hidden"], ctx["tag"]
    rng = np.random.default_rng(tensor_seed)
    x = float16_exact(rng.standard_normal((sequences, positions, hidden), dtype=np.float32))
    grad = float16_exact(rng.standard_normal((sequences, positions, hidden), dtype=np.float32))
    tokens = sequences * positions
    inside = lambda moment: begin <= moment <= end  # noqa: E731
    while time.monotonic() < end:
        sent = time.monotonic()
        try:
            y, pullback = jax.vjp(pipe, x)
            y = np.asarray(y)
            forwarded = time.monotonic()
            (grad_x,) = pullback(grad)
            grad_x = np.asarray(grad_x)
            done = time.monotonic()
            if not (np.isfinite(y).all() and np.isfinite(grad_x).all()):
                raise FloatingPointError(f"{tag}: a non-finite output or gradient")
            # a forward pass is a third of a request's FLOPs and a backward pass two
            # thirds: a request cut by an edge of the window counts by the part inside
            out["tokens"] += tokens * (inside(forwarded) / 3.0 + 2.0 * inside(done) / 3.0)
            if inside(done):
                out["request_ms"].append(1000.0 * (done - sent))
                out["completed"] += 1
        except Exception as e:  # a request that raised or was shed counts as failed
            if inside(time.monotonic()):
                out["failed"] += 1
                out["errors"].append(repr(e)[:200])
            time.sleep(0.05)
        out["attempted"] = out["completed"] + out["failed"]


def new_result() -> Dict[str, Any]:
    return {"request_ms": [], "tokens": 0, "attempted": 0, "failed": 0, "completed": 0, "errors": []}
