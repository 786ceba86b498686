"""ALBERT-style masked-LM — the flagship collaborative-pretraining model
(capability parity: the reference's examples/albert recipe targets HF ALBERT on
torch; this is an own flax implementation, TPU-first: bf16 compute, layer-shared
encoder on the MXU, pluggable attention core that switches to ring attention when the
mesh has a sequence-parallel axis).

ALBERT signature features: factorized embeddings (vocab → embedding_size →
hidden_size) and cross-layer parameter sharing (one transformer block applied
num_layers times)."""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp



@dataclasses.dataclass(frozen=True)
class AlbertConfig:
    vocab_size: int = 30000
    embedding_size: int = 128
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    dtype: Any = jnp.bfloat16
    remat: bool = False  # checkpoint each shared-layer application (see setup)
    # sequence parallelism: when mesh is set and its 'sp' axis > 1, attention runs as
    # ring attention sharded over the sequence (mask support: full sequences only)
    mesh: Optional[Any] = None

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads

    @classmethod
    def base(cls, **overrides) -> "AlbertConfig":
        return cls(**overrides)

    @classmethod
    def tiny(cls, **overrides) -> "AlbertConfig":
        defaults = dict(
            vocab_size=1024, embedding_size=32, hidden_size=64, num_layers=2,
            num_heads=4, intermediate_size=128, max_position=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


def _attention_core(config: AlbertConfig, q, k, v, mask):
    from hivemind_tpu.parallel.ring_attention import mesh_attention_core

    return mesh_attention_core(config.mesh, q, k, v, mask=mask)


class AlbertLayer(nn.Module):
    """One shared transformer block (post-layernorm, gelu FFN)."""

    config: AlbertConfig

    @nn.compact
    def __call__(self, hidden: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
        cfg = self.config
        batch, seq, _ = hidden.shape
        dense = partial(nn.Dense, dtype=cfg.dtype, param_dtype=jnp.float32)
        q = dense(cfg.hidden_size, name="query")(hidden).reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        k = dense(cfg.hidden_size, name="key")(hidden).reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        v = dense(cfg.hidden_size, name="value")(hidden).reshape(batch, seq, cfg.num_heads, cfg.head_dim)
        context = _attention_core(cfg, q, k, v, mask)
        attn_out = dense(cfg.hidden_size, name="attention_out")(context.reshape(batch, seq, -1))
        hidden = nn.LayerNorm(dtype=cfg.dtype, name="attention_norm")(hidden + attn_out)
        up = dense(cfg.intermediate_size, name="ffn_up")(hidden)
        down = dense(cfg.hidden_size, name="ffn_down")(jax.nn.gelu(up))
        return nn.LayerNorm(dtype=cfg.dtype, name="ffn_norm")(hidden + down)


class AlbertForMaskedLM(nn.Module):
    config: AlbertConfig

    def setup(self):
        cfg = self.config
        self.word_embeddings = nn.Embed(
            cfg.vocab_size, cfg.embedding_size, dtype=cfg.dtype, param_dtype=jnp.float32,
            name="word_embeddings",
        )
        self.position_embeddings = self.param(
            "position_embeddings",
            nn.initializers.normal(0.02),
            (cfg.max_position, cfg.embedding_size),
            jnp.float32,
        )
        self.embedding_norm = nn.LayerNorm(dtype=cfg.dtype, name="embedding_norm")
        self.embedding_projection = nn.Dense(
            cfg.hidden_size, dtype=cfg.dtype, param_dtype=jnp.float32, name="embedding_projection"
        )
        # remat: recompute each shared-layer application's activations in the backward
        # pass instead of keeping them in HBM for the whole step — buys batch size when
        # the step is memory-bound (the classic single-chip MFU lever). The module name
        # is pinned so the parameter tree is identical either way.
        layer_cls = nn.remat(AlbertLayer) if cfg.remat else AlbertLayer
        self.shared_layer = layer_cls(cfg, name="shared_layer")
        self.mlm_transform = nn.Dense(
            cfg.embedding_size, dtype=cfg.dtype, param_dtype=jnp.float32, name="mlm_transform"
        )
        self.mlm_norm = nn.LayerNorm(dtype=cfg.dtype, name="mlm_norm")
        self.mlm_bias = self.param("mlm_bias", nn.initializers.zeros, (cfg.vocab_size,), jnp.float32)

    def encode(self, input_ids: jax.Array, attention_mask: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        seq = input_ids.shape[1]
        x = self.word_embeddings(input_ids) + self.position_embeddings[None, :seq].astype(cfg.dtype)
        x = self.embedding_projection(self.embedding_norm(x))
        for _ in range(cfg.num_layers):  # cross-layer parameter sharing
            x = self.shared_layer(x, attention_mask)
        return x

    def _mlm_logits(self, hidden: jax.Array) -> jax.Array:
        transformed = self.mlm_norm(jax.nn.gelu(self.mlm_transform(hidden)))
        logits = self.word_embeddings.attend(transformed)  # tied decoder
        return logits.astype(jnp.float32) + self.mlm_bias

    def __call__(self, input_ids: jax.Array, attention_mask: Optional[jax.Array] = None) -> jax.Array:
        """Returns MLM logits [batch, seq, vocab] (float32 for a stable softmax)."""
        return self._mlm_logits(self.encode(input_ids, attention_mask))

    def loss_masked_only(
        self, input_ids: jax.Array, labels: jax.Array, mlm_mask: jax.Array, budget: int
    ) -> jax.Array:
        """MLM loss computed ONLY at masked positions (up to ``budget`` per row).

        The full-logits path materializes fp32 [batch, seq, vocab] — ~2 GB at
        batch 32 × seq 512 × vocab 30k — yet only ~15% of positions carry loss.
        Gathering those positions first shrinks the decoder matmul and the softmax
        by seq/budget (≈4× at budget=seq/4) in both passes: the single biggest
        single-chip throughput lever for this model. ``budget`` must be static
        (XLA shapes); rows with more masked positions than the budget contribute
        their first ``budget`` ones (at 15% masking, budget seq/4 is ≈ +6σ above
        the binomial mean, so truncation is virtually never hit)."""
        hidden = self.encode(input_ids)
        order = jnp.argsort(~mlm_mask, axis=1, stable=True)[:, :budget]  # masked first
        selected_mask = jnp.take_along_axis(mlm_mask, order, axis=1)
        selected_hidden = jnp.take_along_axis(hidden, order[..., None], axis=1)
        selected_labels = jnp.take_along_axis(labels, order, axis=1)
        logits = self._mlm_logits(selected_hidden)  # [batch, budget, vocab]
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        label_ll = jnp.take_along_axis(log_probs, selected_labels[..., None], axis=-1)[..., 0]
        mask = selected_mask.astype(jnp.float32)
        return -(label_ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def mlm_loss(logits: jax.Array, labels: jax.Array, mlm_mask: jax.Array) -> jax.Array:
    """Masked cross-entropy: mlm_mask selects the positions that were masked out."""
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    label_ll = jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]
    mask = mlm_mask.astype(jnp.float32)
    return -(label_ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def make_mlm_loss_fn(model: "AlbertForMaskedLM", masked_loss_fraction: Optional[float] = None):
    """``loss(params, batch) -> scalar`` for dict(input_ids, labels, mlm_mask).

    :param masked_loss_fraction: compute the MLM head only on this fraction of
        positions per row (the masked ones — see ``loss_masked_only``). Opt-in:
        rows with more masked positions than ``fraction * seq`` contribute only
        the first that many, so callers must size it above their masking rate
        (0.25 gives ≈+6σ headroom over 15% masking at seq 512). None = exact
        full-logits objective."""

    def loss_fn(params, batch):
        if masked_loss_fraction is not None:
            budget = max(1, int(batch["input_ids"].shape[1] * masked_loss_fraction))
            return model.apply(
                {"params": params}, batch["input_ids"], batch["labels"], batch["mlm_mask"],
                budget, method=AlbertForMaskedLM.loss_masked_only,
            )
        logits = model.apply({"params": params}, batch["input_ids"])
        return mlm_loss(logits, batch["labels"], batch["mlm_mask"])

    return loss_fn


def make_train_step(config: AlbertConfig, optimizer, masked_loss_fraction: Optional[float] = None):
    """A jittable (params, opt_state, batch) -> (loss, params, opt_state) step.
    ``batch``: dict(input_ids, labels, mlm_mask). See ``make_mlm_loss_fn`` for
    ``masked_loss_fraction`` (None keeps the exact full-logits objective)."""
    import optax

    model = AlbertForMaskedLM(config)
    loss_fn = make_mlm_loss_fn(model, masked_loss_fraction)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return loss, params, opt_state

    return model, train_step


def make_synthetic_mlm_batch(rng: jax.Array, config: AlbertConfig, batch_size: int, seq_len: int):
    """Deterministic synthetic MLM data for the benchmark and tests (15% masking)."""
    ids_key, mask_key = jax.random.split(rng)
    labels = jax.random.randint(ids_key, (batch_size, seq_len), 0, config.vocab_size)
    mlm_mask = jax.random.bernoulli(mask_key, 0.15, (batch_size, seq_len))
    mask_token = jnp.asarray(config.vocab_size - 1)
    input_ids = jnp.where(mlm_mask, mask_token, labels)
    return {"input_ids": input_ids, "labels": labels, "mlm_mask": mlm_mask}
