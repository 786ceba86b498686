"""Operations and bytes of MiniCPM-SALA's two mixers in a decode step, one row (one
session's one position) at one block — the yardstick's arithmetic for
`lightning_step_roofline` and `sparse_attend_roofline`.

Counted: what the algorithm requires. A lightning step reads its recurrent state once
and writes it once; a sparse step reads the `topk` selected blocks of keys and of
values once. Not counted: the projections, norms and the MLP around the mixer (the
block's weights: `decode_program_ms.*` holds them), the joining and splitting of the
sessions' caches around a batched program, the selection's own read of the compressed
keys (`sparse_select` is a scope of its own)."""

from __future__ import annotations

from typing import Any, Dict


def lightning_step_flops(model: Dict[str, Any]) -> float:
    """Per row: decay and rank-one update of the state (2 a state element) and the
    query's product with it (2 a state element)."""
    heads, dim = model["lightning_nh"], model["lightning_head_dim"]
    return 4.0 * heads * dim * dim


def lightning_step_bytes(model: Dict[str, Any], state_itemsize: int = 4, activation_itemsize: int = 2) -> float:
    """Per row: the state read once and written once; q, k, v in and the output out."""
    heads, dim = model["lightning_nh"], model["lightning_head_dim"]
    return 2.0 * heads * dim * dim * state_itemsize + 4.0 * heads * dim * activation_itemsize


def sparse_attend_flops(model: Dict[str, Any]) -> float:
    """Per row: scores and weighted values over the `topk` blocks' positions, every query head."""
    sparse = model["sparse_config"]
    positions = sparse["topk"] * sparse["block_size"]
    return 2.0 * 2.0 * positions * model["num_attention_heads"] * model["head_dim"]


def sparse_attend_bytes(model: Dict[str, Any], cache_itemsize: int = 2) -> float:
    """Per row: the `topk` selected blocks of keys and of values, every key-value head,
    read once; the query in and the context out."""
    sparse = model["sparse_config"]
    positions = sparse["topk"] * sparse["block_size"]
    selected = 2.0 * positions * model["num_key_value_heads"] * model["head_dim"] * cache_itemsize
    return selected + 2.0 * model["num_attention_heads"] * model["head_dim"] * cache_itemsize
