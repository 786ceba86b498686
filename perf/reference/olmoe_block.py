"""Plain float32 reference of one OLMoE-1B-7B decoder block and of a span of them.

Straightforward `jax.numpy` after the published architecture (Muennighoff et al.
2024, "OLMoE: Open Mixture-of-Experts Language Models"; HF `OlmoeDecoderLayer`):

    n = RMSNorm(x)
    h = x + W_o . Attn(rope(q_norm(W_q n)), rope(k_norm(W_k n)), W_v n)
    m = RMSNorm(h);  p = softmax(W_r m) over all experts, float32
    y = h + sum_{e in top-k(p)} p_e . W_down,e( silu(W_gate,e m) * W_up,e m )

`q_norm` / `k_norm` are RMS norms with a learned scale over the whole projected
width, applied before the split into heads; rotary embedding in the rotate-half
layout; causal softmax attention, scale 1/sqrt(head size); the k largest router
probabilities are used as they are, not renormalised (`norm_topk_prob: false`); no
shared expert, no biases. Every expert is computed densely for every token and
masked by its router weight. No kernels, no cache, no batching, independent of the
program's `OlmoeBlockExpert`; it reads only that block's parameter tree. The one
departure from the published model: the weights are random, drawn from the seed."""

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


def route(params, m, experts_per_token: int):
    """Router probabilities over all experts and, per token, the chosen experts
    and a dense [.., experts] matrix holding p_e for the chosen and 0 elsewhere."""
    probs = jax.nn.softmax(m @ params["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, experts_per_token)
    chosen = jax.nn.one_hot(top_e, probs.shape[-1], dtype=probs.dtype).sum(-2)
    return probs * chosen, top_e


def chosen_experts(params, m, experts_per_token: int):
    """The experts the published router chooses for the router inputs ``m``
    ([.., hidden], any dtype), in float32 at the highest matmul precision. A program's
    routing is held against it on the program's OWN router inputs (teacher-forced):
    the inputs' rounding is then shared, and only the router's arithmetic can differ."""
    with jax.default_matmul_precision("highest"):
        return route(_float32(params), m.astype(jnp.float32), experts_per_token)[1]


def block(params, x, num_heads: int, num_kv_heads: int, experts_per_token: int, rope_theta: float, rms_eps: float,
          return_routing: bool = False, route=route):
    """``route``: the routing rule; anything but the published one (`route` above) is
    a deliberately WRONG reference, for showing what the tolerances refuse.
    ``return_routing``: also return ``(m, top_e)``, the router's input and the
    experts chosen, ``[batch, seq, hidden]`` and ``[batch, seq, k]``."""
    batch, seq, hidden = x.shape
    head_dim = params["query"]["kernel"].shape[1] // num_heads
    normed = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    q = _rms_norm(normed @ params["query"]["kernel"], params["query_norm"]["scale"], rms_eps)
    k = _rms_norm(normed @ params["key"]["kernel"], params["key_norm"]["scale"], rms_eps)
    v = (normed @ params["value"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    q = _rope(q.reshape(batch, seq, num_heads, head_dim), rope_theta)
    k = _rope(k.reshape(batch, seq, num_kv_heads, head_dim), rope_theta)
    group = num_heads // num_kv_heads
    q = q.reshape(batch, seq, num_kv_heads, group, head_dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(float(head_dim))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    context = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v)
    h = x + context.reshape(batch, seq, num_heads * head_dim) @ params["attention_out"]["kernel"]
    m = _rms_norm(h, params["ffn_norm"]["scale"], rms_eps)
    weights, top_e = route(params, m, experts_per_token)  # the argument, by default the function above

    def one_expert(total, expert):  # every expert on every token, masked by its router weight
        w_gate, w_up, w_down, weight = expert
        out = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
        return total + weight[..., None] * out, None

    per_expert = (params["experts_gate"], params["experts_up"], params["experts_down"], jnp.moveaxis(weights, -1, 0))
    y = h + jax.lax.scan(one_expert, jnp.zeros_like(h), per_expert)[0]
    return (y, (m, top_e)) if return_routing else y


def _float32(params):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params)


def span(all_params, x, **sizes):
    """The blocks of `all_params` (a list of parameter trees) applied in order."""
    with jax.default_matmul_precision("highest"):
        x = x.astype(jnp.float32)
        for params in all_params:
            x = block(_float32(params), x, **sizes)
        return x


def span_with_routing(all_params, x, **sizes):
    """`span`, and each block's ``(m, top_e)`` beside it (`block`'s ``return_routing``)."""
    with jax.default_matmul_precision("highest"):
        x, routing = x.astype(jnp.float32), []
        for params in all_params:
            x, routed = block(_float32(params), x, return_routing=True, **sizes)
            routing.append(routed)
        return x, routing


def span_input_grad(all_params, x, grad_out, **sizes):
    """Output of the span and the gradient of <output, grad_out> with respect to x."""
    out, vjp = jax.vjp(lambda xx: span(all_params, xx, **sizes), x)
    return out, vjp(grad_out.astype(jnp.float32))[0]
