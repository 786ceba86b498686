"""Routing counts of sparse expert layers, from what a block's program hands out.

A block with an expert layer sows the experts it chose into `ROUTING_COLLECTION`,
one ``[batch, seq, k]`` int32 leaf per expert-layer call; the serving paths apply
every block with that collection mutable and return it from their jits beside the
block's output. Rows and positions that only pad a bucket are part of the program
but not of the traffic: the caller says how many rows and positions are live, and
only those are counted, here on the host. A block without experts hands out an
empty collection and nothing is counted.

A layer that holds a share of the experts (`ops.sparse_experts.routed_swiglu_held`)
chooses among all of them and computes the pairs whose expert it holds: the caller
names the held range, the pairs chosen are counted as before, the held ones beside
them, and the experts hit are then the held ones (the others' weights are not here
to be read)."""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

from hivemind_tpu.moe.server.layers.common import ATTENDED_COLLECTION, ROUTING_COLLECTION
from hivemind_tpu.telemetry import REGISTRY as _TELEMETRY

__all__ = ["ATTENDED_COLLECTION", "ROUTING_COLLECTION", "ROUTER_TAPS", "SELECTION_TAPS", "held_range", "record_attended", "record_routing"]

_PATH_HELP = "by serving path (batched / direct = decode sessions, pool = TaskPool forward and backward)"
_LAYER_CALLS = _TELEMETRY.counter(
    "hivemind_moe_expert_layer_calls_total", f"sparse expert-layer calls served, {_PATH_HELP}", ("path",))
_ROUTED_PAIRS = _TELEMETRY.counter(
    "hivemind_moe_routed_pairs_total",
    f"(token, expert) pairs routed: live tokens x experts per token, padding excluded, {_PATH_HELP}", ("path",))
_EXPERTS_HIT = _TELEMETRY.counter(
    "hivemind_moe_experts_hit_total",
    f"distinct experts with at least one live token, summed over expert-layer calls, {_PATH_HELP}", ("path",))
_EXPERT_MAX_PAIRS = _TELEMETRY.counter(
    "hivemind_moe_expert_max_pairs_total",
    f"live tokens of the fullest expert, summed over expert-layer calls, {_PATH_HELP}", ("path",))
_HELD_PAIRS = _TELEMETRY.counter(
    "hivemind_moe_held_pairs_total",
    f"routed (token, expert) pairs whose expert this server holds, i.e. the pairs it computed: all of them "
    f"for a layer that holds every expert, {_PATH_HELP}", ("path",))


def held_range(module) -> Optional[Tuple[int, int]]:
    """The experts ``[lo, hi)`` that a block's expert layer holds, as the block says
    (``held_experts``); None for a block that holds all it routes over, or has none."""
    return getattr(module, "held_experts", None)


def record_routing(routing, path: str, span=None, rows: Optional[int] = None,
                   positions: Optional[int] = None, held: Optional[Tuple[int, int]] = None) -> None:
    """Count one call's routing onto the `hivemind_moe_*{path}` counters and, as
    ``experts_hit``, ``pairs`` and ``held_pairs``, onto ``span`` (the call's
    `decode.batch` / `decode.direct` / `pool.batch`). ``rows`` / ``positions``: how
    many leading rows and positions of each leaf are live (None = all). ``held``:
    the range ``(lo, hi)`` of experts the layer holds (`held_range`; None = all):
    ``experts_hit`` and the fullest expert are then taken among those. For a chain
    of blocks, ``routing`` and ``held`` are lists, one entry a block."""
    per_block = zip(routing, held) if isinstance(held, list) else [(routing, held)]
    leaves = [(leaf, block_held) for block, block_held in per_block for leaf in jax.tree_util.tree_leaves(block)]
    if not leaves:
        return
    pairs = held_pairs = hit = fullest = 0
    for leaf, block_held in leaves:
        chosen = np.asarray(leaf)[:rows, :positions].reshape(-1)
        pairs += chosen.size
        if block_held is not None:
            chosen = chosen[(chosen >= block_held[0]) & (chosen < block_held[1])]
        per_expert = np.bincount(chosen)
        held_pairs += chosen.size
        hit += int(np.count_nonzero(per_expert))
        fullest += int(per_expert.max(initial=0))
    _LAYER_CALLS.inc(len(leaves), path=path)
    _ROUTED_PAIRS.inc(pairs, path=path)
    _EXPERTS_HIT.inc(hit, path=path)
    _EXPERT_MAX_PAIRS.inc(fullest, path=path)
    _HELD_PAIRS.inc(held_pairs, path=path)
    if span is not None:
        span.set("experts_hit", hit)
        span.set("pairs", pairs)
        span.set("held_pairs", held_pairs)


_POSITIONS_ATTENDED = _TELEMETRY.counter(
    "hivemind_moe_sparse_positions_attended_total",
    "positions that the queries of blocks with selected (block-sparse) attention attended in decode sessions: "
    "one query a live row a step a sparse block (a prompt chunk: one a real position), padding excluded; a query "
    "that attended densely (a session short of the block's dense length) counts all it had seen")
_POSITIONS_CACHED = _TELEMETRY.counter(
    "hivemind_moe_sparse_positions_cached_total",
    "positions that those queries had seen, their own included: what dense attention would have read")


# whoever wants the blocks that the served programs' queries selected (a check against a
# reference) appends a callable here and takes it off again: it is handed, for every call
# of a sparse block on a decode path, the live part of what the block sowed as ``chosen``,
# ``[rows, positions, kv_heads, topk]`` int32 on the host, in the order the calls settle.
# While the list is empty nothing of it leaves the device
SELECTION_TAPS: List[Callable[[np.ndarray], None]] = []
# the same for what the served programs' ROUTERS saw and chose: handed, for every call on a
# decode path of a block that sows them (``router_input`` ``[rows, positions, hidden]`` and
# ``router_choice`` ``[rows, positions, k]``), the live part of both on the host
ROUTER_TAPS: List[Callable[[np.ndarray, np.ndarray], None]] = []


def record_attended(attended, rows: Optional[int] = None, positions: Optional[int] = None) -> None:
    """Count what a call's block sowed into `ATTENDED_COLLECTION` as ``attended``: ``[2,
    batch, seq]`` leaves, the positions each query attended and the positions it had seen.
    ``rows`` / ``positions``: the live leading rows and positions (None = all). What it
    sowed as ``chosen`` goes to the `SELECTION_TAPS`, and what it sowed as ``router_input`` and
    ``router_choice`` to the `ROUTER_TAPS`, if there are any. A block that sows nothing counts
    nothing."""
    for leaf in attended.get("attended", ()):
        live = np.asarray(leaf)[:, :rows, :positions]
        _POSITIONS_ATTENDED.inc(int(live[0].sum()))
        _POSITIONS_CACHED.inc(int(live[1].sum()))
    if SELECTION_TAPS:
        for leaf in attended.get("chosen", ()):
            for tap in SELECTION_TAPS:
                tap(np.asarray(leaf)[:rows, :positions])
    if ROUTER_TAPS:
        for seen, chose in zip(attended.get("router_input", ()), attended.get("router_choice", ())):
            for tap in ROUTER_TAPS:
                tap(np.asarray(seen)[:rows, :positions], np.asarray(chose)[:rows, :positions])
