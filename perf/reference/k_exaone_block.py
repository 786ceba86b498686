"""Plain float32 reference of K-EXAONE-236B-A23B decoder blocks and of a span of them.

Straightforward `jax.numpy` after the model's published `config.json` (`model_type`
`exaone_moe`, LGAI-EXAONE/K-EXAONE-236B-A23B); `x` is `[batch, T, hidden]`, block i:

    n = RMSNorm(x)
    q = W_q n  -> heads of head_dim (heads * head_dim need not be hidden);  k = W_k n, v = W_v n -> kv heads
    q, k <- RMSNorm over each head's values (one learned scale of head_dim each)
    sliding block (`layer_types[i] = sliding_attention`): q, k <- rope(theta, rotate-half);
        position t attends s with t - window < s <= t
    full block: causal over all positions, no rotary embedding
    h = x + W_o Attn(q, k, v)      softmax, scale 1/sqrt(head_dim), each KV head serves heads / kv_heads query heads
    m = RMSNorm(h)
    dense block (`mlp_layer_types[i] = dense`):  y = h + W_down( silu(W_gate m) * W_up m )
    sparse block:
        s = sigmoid(W_r m) over all experts, float32
        C = the k largest of s + b           (b: a per-expert selection bias; it picks and does not weigh)
        w_e = scale * s_e / sum_{c in C} s_c (`norm_topk_prob`, `routed_scaling_factor`)
        y = h + Shared(m) + sum_{e in C} w_e Expert_e(m);  Shared and every Expert_e a SwiGLU

The window is an explicit [T, T] mask; every expert the parameters hold is computed
densely for every token and masked by its weight. No kernels, no cache, no batching,
independent of the program's `ExaoneMoeBlockExpert`: it reads only that block's
parameter tree (a tree with `ffn_gate` is a dense block, one with `router` a sparse one).

Departures from the published model, all of them:

- the weights are random, drawn from the seed; the selection bias `b` is drawn too
  (a trained one balances the load), wide enough to change some picks;
- four conventions of the family that `config.json` does not spell out are ASSUMED
  and taken by the program's block alike: the per-head RMS norm of q and k
  (EXAONE-4); no rotary embedding on full-attention blocks (EXAONE-4's global
  layers); pre-norm placement (norm, sublayer, residual), as in this repo's other
  decoder blocks; the DeepSeek-V3 reading of `scoring_func: sigmoid` with `n_group`,
  `topk_group`, `routed_scaling_factor`: the bias enters the choice only, and
  `n_group` 1 / `topk_group` 1 make group limiting a no-op;
- THE HELD SHARE: the parameters may hold only the experts `[held_lo, held_lo + held)`
  (`experts_gate` is `[held, hidden, width]`) of those the router chooses among.
  The router keeps all its outputs; a pair whose expert is not held adds nothing, here
  as in the program: what it would add is another chip's to compute and to send.
  With every expert held (`held_lo` 0) this is the uncut layer."""

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


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def route(params, m, experts_per_token: int, scale: float):
    """The chosen experts per token and a dense [.., experts] matrix that holds
    ``scale * s_e / sum of the chosen s`` for the chosen and 0 elsewhere."""
    scores = jax.nn.sigmoid(m @ params["router"])
    _, top_e = jax.lax.top_k(scores + params["router_bias"], experts_per_token)
    picked = scores * jax.nn.one_hot(top_e, scores.shape[-1], dtype=scores.dtype).sum(-2)
    return scale * picked / picked.sum(-1, keepdims=True), top_e


def chosen_experts(params, m, experts_per_token: int):
    """The experts the published router chooses for the router inputs ``m``
    ([.., hidden], any dtype), in float32 at the highest matmul precision. A program's
    routing is held against it on the program's OWN router inputs (teacher-forced):
    the inputs' rounding is then shared, and only the router's arithmetic can differ."""
    with jax.default_matmul_precision("highest"):
        return route(_float32(params), m.astype(jnp.float32), experts_per_token, 1.0)[1]


def block(params, x, *, window: int, rope: bool, num_heads: int, num_kv_heads: int, head_dim: int,
          experts_per_token: int, routed_scale: float, held_lo: int, rope_theta: float, rms_eps: float,
          return_routing: bool = False, route=route, shared: bool = True, absent_left_out: bool = True):
    """One block. ``window`` 0 = full attention; ``rope``: whether q and k are rotated
    (the model: exactly on the sliding blocks). ``route``, ``shared`` = False and
    ``absent_left_out`` = False (a pair routed elsewhere computed by the held expert
    at its number mod held, where the layer leaves it out) make deliberately WRONG
    references, for showing what the limits refuse. ``return_routing``: also return
    ``(m, top_e)``, the router's input and the experts chosen (None for a dense block)."""
    batch, seq, _hidden = x.shape
    normed = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    q = (normed @ params["query"]["kernel"]).reshape(batch, seq, num_heads, head_dim)
    k = (normed @ params["key"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    v = (normed @ params["value"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    q = _rms_norm(q, params["query_norm"]["scale"], rms_eps)
    k = _rms_norm(k, params["key_norm"]["scale"], rms_eps)
    if rope:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    q = q.reshape(batch, seq, num_kv_heads, num_heads // num_kv_heads, head_dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(float(head_dim))
    t, s = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    seen = (s <= t) & (t - s < jnp.where(window > 0, window, seq))  # window 0: every earlier position
    scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
    context = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v)
    h = x + context.reshape(batch, seq, num_heads * head_dim) @ params["attention_out"]["kernel"]
    m = _rms_norm(h, params["ffn_norm"]["scale"], rms_eps)
    if "ffn_gate" in params:
        y = h + _swiglu(m, *(params[f"ffn_{name}"]["kernel"] for name in ("gate", "up", "down")))
        return (y, (m, None)) if return_routing else y
    weights, top_e = route(params, m, experts_per_token, routed_scale)  # the argument, by default the function above
    held = params["experts_gate"].shape[0]
    if absent_left_out:
        weights = weights[..., held_lo:held_lo + held]
    else:
        weights = jnp.roll(weights, -held_lo, -1).reshape(*weights.shape[:-1], -1, held).sum(-2)

    def one_expert(total, expert):  # every held expert on every token, masked by its weight
        w_gate, w_up, w_down, weight = expert
        return total + weight[..., None] * _swiglu(m, w_gate, w_up, w_down), None

    per_expert = (params["experts_gate"], params["experts_up"], params["experts_down"], jnp.moveaxis(weights, -1, 0))
    y = h + jax.lax.scan(one_expert, jnp.zeros_like(h), per_expert)[0]
    if shared:
        y = y + _swiglu(m, *(params[f"shared_{name}"]["kernel"] for name in ("gate", "up", "down")))
    return (y, (m, top_e)) if return_routing else y


def _float32(params):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params)


def span_with_routing(all_params, x, layers, **sizes):
    """The blocks of ``all_params`` (a list of parameter trees) applied in order;
    ``layers`` gives each block's ``{"window", "rope"}``. Returns the output and each
    block's ``(m, top_e)`` (`block`'s ``return_routing``)."""
    with jax.default_matmul_precision("highest"):
        x, routing = x.astype(jnp.float32), []
        for params, layer in zip(all_params, layers):
            x, routed = block(_float32(params), x, return_routing=True, **layer, **sizes)
            routing.append(routed)
        return x, routing


def span(all_params, x, layers, **sizes):
    return span_with_routing(all_params, x, layers, **sizes)[0]
