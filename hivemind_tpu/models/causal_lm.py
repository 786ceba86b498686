"""Decoder-only causal LM — the GPT-family training counterpart to the ALBERT MLM
flagship (the reference's example recipe covers only ALBERT; causal pretraining is
the other model family users expect from a collaborative-training framework, and the
serving side already ships causal/llama blocks — moe/server/layers/common.py).

TPU-first: bf16 compute with fp32 params, pre-norm blocks whose parameter names
match ``parallel/mesh.py``'s TP sharding rules, and a pluggable attention core —
plain causal attention on one chip, CAUSAL ring attention over the ``sp`` mesh axis
for long contexts (shards are contiguous sequence chunks in rank order; see
``parallel/ring_attention.ring_attention(causal=True)``)."""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp



@dataclasses.dataclass(frozen=True)
class CausalLMConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 1024
    dtype: Any = jnp.bfloat16
    remat: bool = False  # checkpoint each layer (see AlbertConfig.remat)
    mesh: Optional[Any] = None  # sp>1 switches to causal ring attention

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @classmethod
    def base(cls, **overrides) -> "CausalLMConfig":
        return cls(**overrides)

    @classmethod
    def tiny(cls, **overrides) -> "CausalLMConfig":
        defaults = dict(
            vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=128, max_position=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


def _causal_attention_core(config: CausalLMConfig, q, k, v):
    from hivemind_tpu.parallel.ring_attention import mesh_attention_core

    return mesh_attention_core(config.mesh, q, k, v, causal=True)


class DecoderLayer(nn.Module):
    """One pre-norm decoder block: causal attention + gelu FFN. Parameter names
    (query/key/value/attention_out/ffn_up/ffn_down) match the mesh TP rules."""

    config: CausalLMConfig

    @nn.compact
    def __call__(self, hidden: jax.Array) -> jax.Array:
        cfg = self.config
        batch, seq, _ = hidden.shape
        dense = partial(nn.Dense, dtype=cfg.dtype, param_dtype=jnp.float32)
        normed = nn.LayerNorm(dtype=cfg.dtype, name="attention_norm")(hidden)
        q = dense(cfg.hidden_size, name="query")(normed).reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        k = dense(cfg.hidden_size, name="key")(normed).reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        v = dense(cfg.hidden_size, name="value")(normed).reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        context = _causal_attention_core(cfg, q, k, v)
        hidden = hidden + dense(cfg.hidden_size, name="attention_out")(context.reshape(batch, seq, -1))
        normed = nn.LayerNorm(dtype=cfg.dtype, name="ffn_norm")(hidden)
        up = dense(cfg.intermediate_size, name="ffn_up")(normed)
        return hidden + dense(cfg.hidden_size, name="ffn_down")(jax.nn.gelu(up))


class CausalLM(nn.Module):
    config: CausalLMConfig

    def setup(self):
        cfg = self.config
        self.word_embeddings = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32,
            name="word_embeddings",
        )
        self.position_embeddings = self.param(
            "position_embeddings", nn.initializers.normal(0.02),
            (cfg.max_position, cfg.hidden_size), jnp.float32,
        )
        layer_cls = nn.remat(DecoderLayer) if cfg.remat else DecoderLayer
        self.layers = [layer_cls(cfg, name=f"layer_{i}") for i in range(cfg.num_layers)]
        self.final_norm = nn.LayerNorm(dtype=cfg.dtype, name="final_norm")

    def __call__(self, input_ids: jax.Array) -> jax.Array:
        """Returns next-token logits [batch, seq, vocab] (fp32 for a stable softmax;
        decoder = transposed embedding — weight tying)."""
        cfg = self.config
        seq = input_ids.shape[1]
        x = self.word_embeddings(input_ids) + self.position_embeddings[None, :seq].astype(cfg.dtype)
        for layer in self.layers:
            x = layer(x)
        x = self.final_norm(x)
        return self.word_embeddings.attend(x).astype(jnp.float32)


def causal_lm_loss(logits: jax.Array, input_ids: jax.Array) -> jax.Array:
    """Next-token cross-entropy: position t predicts token t+1 (the last position
    has no target and is dropped)."""
    log_probs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    targets = input_ids[:, 1:]
    token_ll = jnp.take_along_axis(log_probs, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(token_ll)


def make_train_step(config: CausalLMConfig, optimizer):
    """A jittable (params, opt_state, batch) -> (loss, params, opt_state) step;
    ``batch``: dict(input_ids)."""
    import optax

    model = CausalLM(config)

    def loss_fn(params, batch):
        return causal_lm_loss(model.apply({"params": params}, batch["input_ids"]), batch["input_ids"])

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return loss, params, opt_state

    return model, train_step


def make_synthetic_lm_batch(rng: jax.Array, config: CausalLMConfig, batch_size: int, seq_len: int):
    """Deterministic synthetic token stream for tests."""
    input_ids = jax.random.randint(rng, (batch_size, seq_len), 0, config.vocab_size)
    return {"input_ids": input_ids}
