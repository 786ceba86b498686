"""Plain float32 reference of the ALBERT masked-LM loss the trainer optimises.

Straightforward `jax.numpy`, no kernels, no flax, independent of
`hivemind_tpu.models.albert`; it reads only that model's parameter tree (names as
in Lan et al. 2019 / HF `AlbertForMaskedLM`: factorised embedding, ONE shared
post-layer-norm block applied `num_hidden_layers` times, tied decoder).

Departures from the published model, shared by both sides: no token-type embedding
and no pooler (the repo's model has neither); layer-norm epsilon 1e-6 (flax's
default; HF's config says 1e-12); `gelu_new` (the tanh form); the loss is taken
over at most `budget` masked positions per row, the first ones (the trainer's
masked-only head, `masked_loss_fraction` x sequence length)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)))


def encode(params, input_ids, num_layers: int, num_heads: int):
    seq = input_ids.shape[1]
    x = params["word_embeddings"]["embedding"][input_ids] + params["position_embeddings"][None, :seq]
    x = _dense(_layer_norm(x, params["embedding_norm"]), params["embedding_projection"])
    layer = params["shared_layer"]
    batch, _, hidden = x.shape
    head_dim = hidden // num_heads
    for _ in range(num_layers):
        q, k, v = (_dense(x, layer[name]).reshape(batch, seq, num_heads, head_dim) for name in ("query", "key", "value"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(head_dim))
        context = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v).reshape(batch, seq, hidden)
        x = _layer_norm(x + _dense(context, layer["attention_out"]), layer["attention_norm"])
        x = _layer_norm(x + _dense(_gelu_new(_dense(x, layer["ffn_up"])), layer["ffn_down"]), layer["ffn_norm"])
    return x


def mlm_loss(params, batch, num_layers: int, num_heads: int, budget: int):
    """Mean cross-entropy over the first `budget` masked positions of each row."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params)
        hidden = encode(params, batch["input_ids"], num_layers, num_heads)
        transformed = _layer_norm(_gelu_new(_dense(hidden, params["mlm_transform"])), params["mlm_norm"])
        logits = transformed @ params["word_embeddings"]["embedding"].T + params["mlm_bias"]
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        label_ll = jnp.take_along_axis(log_probs, batch["labels"][..., None], axis=-1)[..., 0]
        mask = batch["mlm_mask"]
        counted = (mask & (jnp.cumsum(mask, axis=1) <= budget)).astype(jnp.float32)
        return -(label_ll * counted).sum() / jnp.maximum(counted.sum(), 1.0)


def loss_and_grad(params, batch, num_layers: int, num_heads: int, budget: int):
    return jax.value_and_grad(mlm_loss)(params, batch, num_layers, num_heads, budget)
