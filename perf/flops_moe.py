"""Operations and bytes of a sparse expert layer (SwiGLU experts, top-k routing),
computed from what was routed — the yardstick's arithmetic for `moe_experts_roofline`.

Counted: what the algorithm requires for the (token, expert) pairs that were routed
and the experts that were hit. Not counted: rows a grouped matmul's tile holds for
another group (masked work), the sort and the gathers around the matmuls, an expert
nobody chose."""

from __future__ import annotations

from typing import Any, Dict


def expert_params(hidden: int, width: int) -> int:
    """Parameters of one SwiGLU expert: gate, up and down projections, no biases."""
    return 3 * hidden * width


def expert_layer_flops(pairs: float, hidden: int, width: int) -> float:
    """FLOPs of the three matmuls for `pairs` routed (token, expert) pairs: each pair
    is one token through one expert, 2 FLOPs a multiply-accumulate."""
    return 2.0 * pairs * expert_params(hidden, width)


def expert_layer_bytes(experts_hit: float, pairs: float, hidden: int, width: int,
                       weight_itemsize: int, activation_itemsize: int) -> float:
    """Bytes that must move once: the weights of every expert that was hit, at the
    size the program reads them, plus each pair's input row, its two inner rows
    (written, then read for the product) and its output row."""
    weights = experts_hit * expert_params(hidden, width) * weight_itemsize
    activations = pairs * (2 * hidden + 4 * width) * activation_itemsize
    return weights + activations


def moe_block_params(model: Dict[str, Any]) -> int:
    """Parameters of one OLMoE-style block: attention's four matrices, the experts,
    the router, and four norm scales (attention, query, key, ffn)."""
    h, heads, kv = model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"]
    head_dim = h // heads
    q, kv_width = heads * head_dim, kv * head_dim
    attention = h * q + 2 * h * kv_width + q * h
    experts = model["num_experts"] * expert_params(h, model["intermediate_size"])
    return attention + experts + h * model["num_experts"] + (2 * h + q + kv_width)


def moe_block_params_per_token(model: Dict[str, Any]) -> int:
    """Parameters one token touches: all but the experts it was not routed to."""
    idle = (model["num_experts"] - model["num_experts_per_tok"]) * expert_params(model["hidden_size"], model["intermediate_size"])
    return moe_block_params(model) - idle
