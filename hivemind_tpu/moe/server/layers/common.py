"""Built-in expert blocks + registry (capability parity: reference
hivemind/moe/server/layers/common.py:18-31 'ffn', transformer encoder block, 'nop';
custom_experts.py:35 register_expert_class)."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

name_to_block: Dict[str, Callable] = {}
name_to_input: Dict[str, Callable] = {}


def register_expert_class(name: str, sample_input: Callable[[int, int], np.ndarray]):
    """Register a flax module factory under ``name``; ``sample_input(batch, hid)``
    builds a schema-defining dummy input."""

    def decorator(factory):
        assert name not in name_to_block, f"expert class {name!r} already registered"
        name_to_block[name] = factory
        name_to_input[name] = sample_input
        return factory

    return decorator


class FeedforwardExpert(nn.Module):
    """hid -> 4*hid -> hid feedforward with layernorm (the reference's benchmark
    'ffn' expert shape)."""

    hidden_dim: int

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.hidden_dim * 4, dtype=jnp.bfloat16, param_dtype=jnp.float32)(x)
        h = jax.nn.gelu(h)
        h = nn.Dense(self.hidden_dim, dtype=jnp.bfloat16, param_dtype=jnp.float32)(h)
        return nn.LayerNorm(dtype=jnp.bfloat16)(x + h).astype(jnp.float32)


class TransformerExpert(nn.Module):
    """One post-norm transformer encoder block operating on [batch, seq, hid]."""

    hidden_dim: int
    num_heads: int = 8

    @nn.compact
    def __call__(self, x):
        from hivemind_tpu.ops.pallas_attention import attention_auto

        batch, seq, hid = x.shape
        head_dim = hid // self.num_heads
        dense = lambda n, name: nn.Dense(n, dtype=jnp.bfloat16, param_dtype=jnp.float32, name=name)
        q = dense(hid, "query")(x).reshape(batch, seq, self.num_heads, head_dim)
        k = dense(hid, "key")(x).reshape(batch, seq, self.num_heads, head_dim)
        v = dense(hid, "value")(x).reshape(batch, seq, self.num_heads, head_dim)
        attn = dense(hid, "attention_out")(attention_auto(q, k, v).reshape(batch, seq, hid))
        x = nn.LayerNorm(dtype=jnp.bfloat16)(x + attn)
        h = dense(4 * hid, "ffn_up")(x)
        h = dense(hid, "ffn_down")(jax.nn.gelu(h))
        return nn.LayerNorm(dtype=jnp.bfloat16)(x + h).astype(jnp.float32)


def _decode_attention(q, k_new, v_new, cache_k, cache_v, index, groups: int = 1):
    """Shared KV-cache attention step for decoder blocks.

    Writes ``k_new``/``v_new`` into the caches at ``index`` (dynamic), then attends
    the chunk's queries over every cached position the session has produced so far.
    Valid for the two session shapes: prefill (``index == 0``, chunk length L,
    causal within the chunk) and incremental (chunk length 1, attends everything
    ≤ index). ``groups`` > 1 repeats the (grouped-query) KV heads to match q at
    attention time — caches stay in the compact kv_heads layout.
    Returns (context, cache_k, cache_v)."""
    from hivemind_tpu.parallel.ring_attention import plain_attention

    batch, new_len = q.shape[0], q.shape[1]
    max_len = cache_k.shape[1]
    cache_k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype), (0, index, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype), (0, index, 0, 0))
    expand = (lambda t: jnp.repeat(t, groups, axis=2)) if groups > 1 else (lambda t: t)
    if new_len == 1:
        mask = (jnp.arange(max_len) <= index)[None, :]  # [1, max_len] key-validity
        context = plain_attention(
            q, expand(cache_k), expand(cache_v),
            mask=jnp.broadcast_to(mask, (batch, max_len)),
        )
    else:
        # prefill chunk at the session start: plain causal attention over the chunk
        # is exact (the cache holds nothing before index 0)
        context = plain_attention(q, expand(k_new), expand(v_new), causal=True)
    return context, cache_k, cache_v


class CausalTransformerExpert(nn.Module):
    """One pre-norm DECODER block on [batch, seq, hid]: causal attention + gelu ffn.
    The building block for pipelined autoregressive models over the swarm
    (RemoteSequential): causality means right-padded prefixes are exact — real
    positions never attend to the padding after them — so clients can decode with
    a fixed schema sequence length and read the logits at the true last position.

    Decode sessions: calling with ``(cache_k, cache_v, index)`` runs one KV-cache
    step — O(seq) per token instead of the O(seq²) right-padded recompute — and
    returns ``(y, cache_k, cache_v)``; see ``moe/server/decode_session.py``."""

    hidden_dim: int
    num_heads: int = 8

    def init_decode_cache(self, batch: int, max_len: int):
        head_dim = self.hidden_dim // self.num_heads
        shape = (batch, max_len, self.num_heads, head_dim)
        return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None):
        from hivemind_tpu.ops.pallas_attention import attention_auto

        batch, seq, hid = x.shape
        head_dim = hid // self.num_heads
        dense = lambda n, name: nn.Dense(n, dtype=jnp.bfloat16, param_dtype=jnp.float32, name=name)
        normed = nn.LayerNorm(dtype=jnp.bfloat16, name="attention_norm")(x)
        q = dense(hid, "query")(normed).reshape(batch, seq, self.num_heads, head_dim)
        k = dense(hid, "key")(normed).reshape(batch, seq, self.num_heads, head_dim)
        v = dense(hid, "value")(normed).reshape(batch, seq, self.num_heads, head_dim)
        if cache_k is None:
            attn = attention_auto(q, k, v, causal=True).reshape(batch, seq, hid)
        else:
            context, cache_k, cache_v = _decode_attention(q, k, v, cache_k, cache_v, index)
            attn = context.reshape(batch, seq, hid)
        x = x + dense(hid, "attention_out")(attn)
        normed = nn.LayerNorm(dtype=jnp.bfloat16, name="ffn_norm")(x)
        h = dense(4 * hid, "ffn_up")(normed)
        y = (x + dense(hid, "ffn_down")(jax.nn.gelu(h))).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: jax.Array, theta: float = 10000.0, offset=0) -> jax.Array:
    """Rotary position embedding over [batch, seq, heads, head_dim] (head_dim even).
    ``offset`` (may be traced) shifts positions — decode sessions rotate the new
    token at its absolute position in the sequence."""
    seq, dim = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    positions = offset + jnp.arange(seq, dtype=jnp.float32)
    angles = positions[:, None] * freqs[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)  # [seq, dim]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return x * cos + _rotate_half(x) * sin


class LlamaBlockExpert(nn.Module):
    """One Llama-family decoder block on [batch, seq, hid]: pre-RMSNorm, rotary
    position embeddings, causal attention with optional grouped-query KV heads, and
    a SwiGLU MLP. This is the block shape Petals serves for Llama models (the
    BASELINE 'Petals-style Llama-7B block server' config): stack N of these under
    ``RemoteSequential`` and decoding is exact with right-padded fixed schemas, same
    as ``CausalTransformerExpert``. RoPE makes positions intrinsic to the block, so
    the client does not ship position ids."""

    hidden_dim: int
    num_heads: int = 8
    num_kv_heads: int = 0  # 0 = multi-head (Llama-7B); set lower for GQA (Llama-70B style)
    rope_theta: float = 10000.0
    ffn_inner: int = 0  # 0 = the 8/3 rule below; real checkpoints set intermediate_size
    rms_eps: float = 1e-6  # real checkpoints set rms_norm_eps (Llama-2: 1e-5)
    # set when the block is served sharded over a device mesh (MeshModuleBackend):
    # the fused attention kernel must then run per shard (mesh_attention_core)
    mesh: Optional[Any] = None

    def init_decode_cache(self, batch: int, max_len: int):
        kv_heads = self.num_kv_heads or self.num_heads
        shape = (batch, max_len, kv_heads, self.hidden_dim // self.num_heads)
        return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None):
        from hivemind_tpu.parallel.ring_attention import mesh_attention_core

        batch, seq, hid = x.shape
        heads = self.num_heads
        kv_heads = self.num_kv_heads or heads
        assert heads % kv_heads == 0, (heads, kv_heads)
        head_dim = hid // heads
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32, name=name
        )
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="attention_norm")(x)
        q = dense(heads * head_dim, "query")(normed).reshape(batch, seq, heads, head_dim)
        k = dense(kv_heads * head_dim, "key")(normed).reshape(batch, seq, kv_heads, head_dim)
        v = dense(kv_heads * head_dim, "value")(normed).reshape(batch, seq, kv_heads, head_dim)
        offset = 0 if cache_k is None else index  # decode: rotate at absolute position
        q = apply_rope(q, self.rope_theta, offset)
        k = apply_rope(k, self.rope_theta, offset)
        if cache_k is None:
            if kv_heads != heads:  # grouped-query: each KV head serves heads/kv_heads queries
                k = jnp.repeat(k, heads // kv_heads, axis=2)
                v = jnp.repeat(v, heads // kv_heads, axis=2)
            attn = mesh_attention_core(self.mesh, q, k, v, causal=True).reshape(batch, seq, hid)
        else:
            context, cache_k, cache_v = _decode_attention(
                q, k, v, cache_k, cache_v, index, groups=heads // kv_heads
            )
            attn = context.reshape(batch, seq, hid)
        x = x + dense(hid, "attention_out")(attn)
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
        inner = self.ffn_inner or -(-8 * hid // 3 // 8) * 8  # 8/3*hid rounded up to 8
        gate = dense(inner, "ffn_gate")(normed)
        up = dense(inner, "ffn_up")(normed)
        y = (x + dense(hid, "ffn_down")(jax.nn.silu(gate) * up)).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)


class NopExpert(nn.Module):
    """Identity with a dummy parameter (reference 'nop' expert for transport tests)."""

    hidden_dim: int

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, ())
        return x * scale


register_expert_class("ffn", lambda batch, hid: np.zeros((batch, hid), np.float32))(FeedforwardExpert)
register_expert_class("transformer", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(TransformerExpert)
register_expert_class("causal_transformer", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(CausalTransformerExpert)
register_expert_class("llama_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(LlamaBlockExpert)
register_expert_class("nop", lambda batch, hid: np.zeros((batch, hid), np.float32))(NopExpert)
