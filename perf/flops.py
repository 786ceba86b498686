"""Operations and bytes computed from shapes — the yardstick's arithmetic.

`albert_flops_per_token` is a copy of `bench.flops_per_token` (sound: it counts
what the masked-only loss path executes); the original stays in `bench.py` until
a later PR deletes it with that script. Everything here counts what the algorithm
requires: recomputed work does not count."""

from __future__ import annotations

from typing import Any, Dict


def albert_flops_per_token(model: Dict[str, Any], seq_len: int, head_fraction: float = 1.0) -> float:
    """Forward + backward FLOPs per token = 6 x multiply-accumulates per token.

    `head_fraction`: the MLM head (transform + tied decoder) runs only on this
    fraction of positions under the masked-only loss."""
    h, i, layers = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    e, vocab = model["embedding_size"], model["vocab_size"]
    per_layer = 4 * h * h + 2 * h * i  # q, k, v, out projections + the two FFN matrices
    attention_quadratic = 2 * seq_len * h  # QK^T and PV, per token
    head = h * e + e * vocab
    return 6.0 * (layers * (per_layer + attention_quadratic) + head_fraction * head)


def attention_flops(batch: int, heads: int, q_len: int, kv_len: int, head_dim: int,
                    causal: bool, backward: bool) -> float:
    """FLOPs the attention core needs: QK^T and PV forward; dV, dP, dQ and dK
    backward, twice the forward's. The scores a flash kernel recomputes in its
    backward are not counted. Causal attention needs half of the square."""
    matmuls = 2 * (2 * batch * heads * q_len * kv_len * head_dim)
    if causal:
        matmuls *= 0.5
    return matmuls * (2.0 if backward else 1.0)


def attention_bytes(batch: int, heads: int, kv_heads: int, q_len: int, kv_len: int, head_dim: int,
                    itemsize: int, backward: bool) -> float:
    """Bytes the attention core must move once: read q, k, v, write o (forward);
    read q, k, v, o, do and write dq, dk, dv (backward). Row statistics are small
    beside them and left out."""
    q = batch * heads * q_len * head_dim * itemsize
    kv = batch * kv_heads * kv_len * head_dim * itemsize
    if backward:
        return 3 * q + 2 * kv + (q + 2 * kv)
    return 2 * q + 2 * kv


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take, and which bound binds."""
    compute, memory = flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory), "bound": "compute" if compute >= memory else "memory",
            "compute_s": compute, "memory_s": memory}


def block_params(model: Dict[str, Any]) -> int:
    """Parameters of one Llama-family decoder block (no biases; two norm scales)."""
    h, inner = model["hidden_size"], model["intermediate_size"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    q = model["num_attention_heads"] * model["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * inner + 2 * h


def block_flops_per_token(model: Dict[str, Any], context: int, backward: bool) -> float:
    """FLOPs one block needs for one token attending to `context` positions
    (causal training at sequence S: pass context = S / 2, the mean)."""
    matmul_params = block_params(model) - 2 * model["hidden_size"]
    attention = 2 * 2 * context * model["num_attention_heads"] * model["head_dim"]
    return (2 * matmul_params + attention) * (3.0 if backward else 1.0)


def block_decode_bytes(model: Dict[str, Any], sessions: int, context: int, max_len: int,
                       param_itemsize: int, cache_itemsize: int) -> Dict[str, float]:
    """Bytes one block must read for one step of `sessions` single-token rows: its
    weights once, each session's keys and values up to `context`. `stacked_copies`
    is what the program's batched step moves besides (two copies of every session's
    whole cache of `max_len`, in and out) — not required by the algorithm."""
    row = model["num_key_value_heads"] * model["head_dim"] * cache_itemsize
    return {
        "weights": float(block_params(model) * param_itemsize),
        "cache_read": float(2 * sessions * context * row),
        "stacked_copies": float(2 * 2 * sessions * max_len * row),
    }
