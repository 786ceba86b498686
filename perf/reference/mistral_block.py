"""Plain float32 reference of one Mistral-7B decoder block and of a span of them.

Straightforward `jax.numpy` after the published architecture (Jiang et al. 2023;
HF `MistralDecoderLayer`): pre-RMS-norm, rotary position embedding in the
rotate-half layout, grouped-query causal attention, SwiGLU MLP, no biases. No
kernels, no cache, no batching, independent of the program's `LlamaBlockExpert`;
it reads only that block's parameter tree. v0.3 has no sliding window."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(jnp.concatenate([angles, angles], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([angles, angles], -1))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def block(params, x, num_heads: int, num_kv_heads: int, rope_theta: float, rms_eps: float):
    batch, seq, hidden = x.shape
    head_dim = params["query"]["kernel"].shape[1] // num_heads
    normed = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    q = (normed @ params["query"]["kernel"]).reshape(batch, seq, num_heads, head_dim)
    k = (normed @ params["key"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    v = (normed @ params["value"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    group = num_heads // num_kv_heads
    q = q.reshape(batch, seq, num_kv_heads, group, head_dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    context = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v)
    x = x + context.reshape(batch, seq, num_heads * head_dim) @ params["attention_out"]["kernel"]
    normed = _rms_norm(x, params["ffn_norm"]["scale"], rms_eps)
    gated = jax.nn.silu(normed @ params["ffn_gate"]["kernel"]) * (normed @ params["ffn_up"]["kernel"])
    return x + gated @ params["ffn_down"]["kernel"]


def span(all_params, x, **sizes):
    """The blocks of `all_params` (a list of parameter trees) applied in order."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        for params in all_params:
            x = block(jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params), x, **sizes)
        return x


def span_input_grad(all_params, x, grad_out, **sizes):
    """Output of the span and the gradient of <output, grad_out> with respect to x."""
    out, vjp = jax.vjp(lambda xx: span(all_params, xx, **sizes), x)
    return out, vjp(grad_out.astype(jnp.float32))[0]
