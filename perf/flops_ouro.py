"""Parameters, cache bytes and the least bytes of a batched decode step of Ouro-2.6B's blocks: the
yardstick's arithmetic for `looped_step_roofline`, and the counts that the configuration's cut is
reckoned from (`model`: the configuration's `model` section, the published keys).

A looped model runs its stack `total_ut_steps` times a token with the same weights, every pass on a
cache of its own: the PARAMETERS do not grow with the passes, the CACHE does (a position is cached
once a pass a block), and a token's device work is `total_ut_steps` programs a block.

Counted for one batched program of one block (`jit_batched_step_looped`: one pass of one position for
each of its rows), as what has to move through HBM once: the block's weights (float32, read once for
all the rows), the keys and values that the live rows attended in the cache of their own pass (the
manager's count of positions, `hivemind_moe_looped_positions_attended_total`, at `position_bytes` a
position), and the rows' activations in and out. It is a LOWER bound of what the program reads: a
padding row's cache, the slots past a row's position that a program reads and masks, the new position's
write and every intermediate are left out, so a share over 100 % would be a fault of the count. The
FLOPs (2 a weight a row, 4 x heads x head_dim a position attended) are counted beside the bytes; at
these rows the step is memory-bound by a wide margin."""

from __future__ import annotations

from typing import Any, Dict


def block_params(model: Dict[str, Any]) -> int:
    """q, k, v, o at heads x head_dim; gate, up, down at the SwiGLU's width; four norm scales; no bias: 51,388,416."""
    hidden, width = model["hidden_size"], model["num_attention_heads"] * model["head_dim"]
    kv_width = model["num_key_value_heads"] * model["head_dim"]
    return 2 * hidden * width + 2 * hidden * kv_width + 3 * hidden * model["intermediate_size"] + 4 * hidden


def model_params(model: Dict[str, Any], layers: int) -> int:
    """``layers`` blocks (the published 48; the loop adds none), embedding and head untied, the final norm and
    the exit gate (a hidden -> 1 linear with bias): 2.668 B at the published depth."""
    hidden = model["hidden_size"]
    return layers * block_params(model) + 2 * model["vocab_size"] * hidden + hidden + (hidden + 1)


def position_bytes(model: Dict[str, Any], cache_itemsize: int = 2) -> int:
    """A cached position at one block at ONE pass: a key and a value for every KV head, bf16: 8,192 B."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * cache_itemsize


def position_bytes_all_passes(model: Dict[str, Any], cache_itemsize: int = 2) -> int:
    """A cached position at one block: once a pass, 32,768 B."""
    return model["total_ut_steps"] * position_bytes(model, cache_itemsize)


def session_cache_bytes(model: Dict[str, Any], max_len: int, cache_itemsize: int = 2) -> int:
    """What one session pins at one block: `total_ut_steps` caches of ``max_len`` slots."""
    return max_len * position_bytes_all_passes(model, cache_itemsize)


def step_bytes(programs: float, rows: float, positions: float, model: Dict[str, Any], weight_itemsize: int = 4,
               activation_itemsize: int = 4) -> float:
    """Least bytes of ``programs`` batched steps that held ``rows`` live rows in all, which attended ``positions``
    cached positions in all: the weights once a program, the attended keys and values, a row's hidden state in and out."""
    weights = programs * block_params(model) * weight_itemsize
    return weights + positions * position_bytes(model) + rows * 2 * model["hidden_size"] * activation_itemsize


def step_flops(rows: float, positions: float, model: Dict[str, Any]) -> float:
    """2 a weight a row (the norms' scales aside), and for every attended position a score and a mix over every head."""
    matmuls = block_params(model) - 4 * model["hidden_size"]
    return 2.0 * rows * matmuls + 4.0 * positions * model["num_attention_heads"] * model["head_dim"]
