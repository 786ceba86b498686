"""Plain float32 reference of GigaChat3.1-702B-A36B decoder blocks and of a span of them.

Straightforward `jax.numpy` after the model's published `config.json` (`model_type`
`deepseek_v3`, ai-sage/GigaChat3.1-702B-A36B) and, where that does not spell a convention
out, DeepSeek-V3's modeling code, which `model_type` names. `x` is `[batch, T, hidden]`;
RMSNorm eps 1e-6, no biases anywhere, pre-norm, SiLU.

**Attention, every block** (multi-head latent attention). h = RMSNorm(x).

    c_q = RMSNorm(h W_qa)               `q_lora_rank` 1536, one learned scale of 1536
    q   = c_q W_qb -> 64 heads of 192 = q_nope (128, `qk_nope_head_dim`) | q_pe (64, `qk_rope_head_dim`)
    h W_kva -> 576 = c (512, `kv_lora_rank`) | k_pe (64);  c <- RMSNorm(c) (one learned scale of 512)
    k_pe is ONE key for all 64 heads
    q_pe, k_pe <- rope at the absolute position: pairs (2i, 2i + 1) rotated by t * inv_freq_i, YaRN frequencies:
        inv_freq_i = (1 - r_i) theta^(-2i/64) / factor + r_i theta^(-2i/64)
        r_i = 1 - clip((i - lo) / (hi - lo), 0, 1)
        lo, hi = floor, ceil of 64 ln(original / (beta 2 pi)) / (2 ln theta) at beta_fast, beta_slow, clamped to [0, 63]
        cos and sin times yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)   (1 here)
    c W_kvb -> per head k_nope (128) | v (192, `v_head_dim`: NOT DeepSeek-V3's 128; the config wins)
    score_{t,s} = (q_nope . k_nope + q_pe . k_pe) * 192^(-1/2) * m^2,   m = yarn_mscale(factor, mscale_all_dim) = 0.1 ln 64 + 1
    causal softmax in float32;  o = sum_s p v -> 64 x 192 = 12,288;  x <- x + o W_o

Keys and values are expanded for EVERY position (no cache, no absorption of `W_kvb`
into the query); the queries are taken in blocks (`query_block`) so that the scores of
4,692 positions fit a device. Nothing else is blocked, cached or batched.

**MLP.** m = RMSNorm(x). A tree with `ffn_gate` is a dense block (the model's blocks 0-2,
`first_k_dense_replace` 3): x <- x + W_down(silu(W_gate m) * W_up m), width 18,432. One with
`router` is a sparse block (3-63):

    s = sigmoid(m W_r) over 256 experts, float32
    choice on s + b (b: a per-expert bias, `topk_method` `noaux_tc`: it picks and does not weigh):
        `n_group` 8 groups of 32; a group's score the sum of its 2 largest s + b; the `topk_group` 4 best groups kept;
        the `num_experts_per_tok` 8 largest s + b among the kept groups' 128 experts
    w_e = 2.5 * s_e / sum of the chosen s          (`norm_topk_prob`, `routed_scaling_factor`)
    x <- x + Shared(m) + sum_e w_e Expert_e(m)     each a SwiGLU of 2,048; `n_shared_experts` 1

Every expert the parameters hold is computed densely for every token and masked by its
weight. Independent of the program's `DeepseekV3BlockExpert`: it reads only that block's
parameter tree.

Departures from the published model and assumptions, all of them (the configuration file
`perf/configs/gigachat-702b-a36b-span5.json` lists the same under `assumed`, and the
program's block takes the same):

- the weights are random, drawn from the seed; the selection bias `b` is drawn too (a
  trained one balances the load), at a standard deviation of 0.1, which changes picks;
- ASSUMED from DeepSeek-V3's code: the rotary pairs are interleaved (2i, 2i + 1) (its
  Hugging Face port permutes them to halves first, on q and k alike: the same scores);
  the softmax scale carries m^2; the latent c is normed BEFORE it is kept and expanded;
  the ramp's formula above; an expert outside the kept groups cannot be chosen
  (masked to -inf, as DeepSeek's own inference code does; the Hugging Face port masks
  to 0, which differs only where a kept expert's s + b is negative);
- THE HELD SHARE: the parameters may hold only the experts `[held_lo, held_lo + held)`
  (`experts_gate` is `[held, hidden, width]`) of those the router chooses among. The
  router keeps all its outputs and all of the choice above; a pair whose expert is not
  held adds nothing, here as in the program: what it would add is another chip's to
  compute and to send. With every expert held (`held_lo` 0) this is the uncut layer;
- `num_nextn_predict_layers` (the multi-token-prediction module after block 63),
  `vocab_size`, `tie_word_embeddings` act on the embedding and the head, which live on
  the client; `ep_size` 1 is a checkpoint's layout: nothing here reads them."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int, beta_fast: float, beta_slow: float, yarn: bool = True):
    """The rotary frequencies of the ``dim / 2`` pairs: plain ``theta^(-2i/dim)`` below
    ``lo``, divided by ``factor`` above ``hi``, a linear ramp between."""
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not yarn:
        return plain
    turns = lambda beta: dim * math.log(original / (beta * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(turns(beta_fast)), 0), min(math.ceil(turns(beta_slow)), dim - 1)
    r = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - r) * plain / factor + r * plain


def _rope(x, inv_freq, amplitude: float, pairs: str):
    """``x`` ``[batch, T, .., dim]`` rotated at positions 0..T-1. ``pairs``: ``interleaved``
    (2i, 2i + 1), the model's; ``halves`` (i, i + dim / 2) makes a wrong reference."""
    seq = x.shape[1]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = angles.reshape((1, seq) + (1,) * (x.ndim - 3) + (-1,))
    cos, sin = jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude
    if pairs == "interleaved":
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def route(params, m, experts_per_token: int, scale: float, n_group: int = 1, topk_group: int = 1, *,
          group_best: int = 2, bias_weighs: bool = False, rounded: bool = False):
    """A dense ``[.., experts]`` matrix that holds ``scale * s_e / sum of the chosen s``
    for the chosen experts and 0 elsewhere, and the chosen experts ``[.., k]``. The
    keyword arguments make deliberately WRONG references (every default is the model's):
    ``group_best`` 1 scores a group by its one best; ``bias_weighs``: the bias also
    weighs; ``rounded``: the router's matmul in ONE bf16 pass (operands rounded to bf16,
    exact products, float32 sums: what a TPU makes of float32 at default precision)."""
    router = params["router"]
    if rounded:
        m, router = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (m, router))
    scores = jax.nn.sigmoid(m @ router)
    biased = scores + params["router_bias"]
    choice = biased
    if n_group > 1:
        experts = biased.shape[-1]
        grouped = biased.reshape(*biased.shape[:-1], n_group, experts // n_group)
        group_score = jax.lax.top_k(grouped, group_best)[0].sum(-1)
        _, kept = jax.lax.top_k(group_score, topk_group)
        in_kept = jax.nn.one_hot(kept, n_group, dtype=jnp.bool_).any(-2)
        choice = jnp.where(in_kept[..., None], grouped, -jnp.inf).reshape(biased.shape)
    _, top_e = jax.lax.top_k(choice, experts_per_token)
    picked = (biased if bias_weighs else scores) * jax.nn.one_hot(top_e, scores.shape[-1], dtype=scores.dtype).sum(-2)
    return scale * picked / picked.sum(-1, keepdims=True), top_e


def chosen_experts(params, m, experts_per_token: int, n_group: int, topk_group: int):
    """The experts the published router chooses for the router inputs ``m`` ([.., hidden],
    any dtype), in float32 at the highest matmul precision. A program's routing is held
    against it on the program's OWN router inputs (teacher-forced): the inputs' rounding
    is then shared, and only the router's arithmetic can differ."""
    with jax.default_matmul_precision("highest"):
        return route(_float32(params), m.astype(jnp.float32), experts_per_token, 1.0, n_group, topk_group)[1]


def attention(params, h, *, num_heads: int, qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int, rms_eps: float,
              rope, query_block: int = 512, yarn: bool = True, softmax_mscale: bool = True, scale_dim: int = 0,
              latent_norm: bool = True, query_norm: bool = True, pairs: str = "interleaved"):
    """``o W_o`` for the normed input ``h`` ``[batch, T, hidden]``. ``rope``: the published
    `rope_theta` and `rope_scaling` as a dict (``theta``, ``factor``, ``original``,
    ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``). The keyword arguments
    after it make WRONG references (every default is the model's): plain rope without
    YaRN, m^2 left out of the scale, another width under the scale's root, the latent or
    the query latent without its norm, rotate-half pairs."""
    batch, seq, _hidden = h.shape
    heads, nope, roped, v_dim = num_heads, qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    c_q = h @ params["query_down"]["kernel"]
    if query_norm:
        c_q = _rms_norm(c_q, params["query_latent_norm"]["scale"], rms_eps)
    q = (c_q @ params["query_up"]["kernel"]).reshape(batch, seq, heads, nope + roped)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    down = h @ params["kv_down"]["kernel"]
    c, k_pe = down[..., :-roped], down[..., -roped:]
    if latent_norm:
        c = _rms_norm(c, params["kv_latent_norm"]["scale"], rms_eps)
    inv_freq = yarn_inv_freq(roped, rope["theta"], rope["factor"], rope["original"], rope["beta_fast"], rope["beta_slow"], yarn)
    amplitude = yarn_mscale(rope["factor"], rope["mscale"]) / yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    q_pe, k_pe = _rope(q_pe, inv_freq, amplitude, pairs), _rope(k_pe, inv_freq, amplitude, pairs)
    expanded = (c @ params["kv_up"]).reshape(batch, seq, heads, nope + v_dim)  # keys and values of EVERY position
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    scale = float(scale_dim or nope + roped) ** -0.5
    if softmax_mscale:
        scale *= yarn_mscale(rope["factor"], rope["mscale_all_dim"]) ** 2

    blocks = -(-seq // query_block)
    pad = lambda t: jnp.pad(t, ((0, 0), (0, blocks * query_block - seq), (0, 0), (0, 0)))
    q_nope, q_pe = pad(q_nope), pad(q_pe)

    def one_block(start):  # the queries start .. start + query_block against every position
        take = lambda t: jax.lax.dynamic_slice_in_dim(t, start, query_block, axis=1)
        scores = (jnp.einsum("bqhd,bshd->bhqs", take(q_nope), k_nope) + jnp.einsum("bqhd,bsd->bhqs", take(q_pe), k_pe)) * scale
        t, s = start + jnp.arange(query_block)[:, None], jnp.arange(seq)[None, :]
        scores = jnp.where(s <= t, scores, -jnp.inf)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    context = jax.lax.map(one_block, jnp.arange(blocks) * query_block)  # [blocks, batch, query_block, heads, v_dim]
    context = jnp.moveaxis(context, 0, 1).reshape(batch, blocks * query_block, heads * v_dim)[:, :seq]
    return context @ params["attention_out"]["kernel"]


def block(params, x, *, experts_per_token: int, routed_scale: float, n_group: int, topk_group: int, held_lo: int,
          rms_eps: float, return_routing: bool = False, shared: bool = True, absent_left_out: bool = True,
          route_knobs=(), **attention_sizes):
    """One block. ``route_knobs`` (pairs of `route`'s keyword arguments), ``shared`` =
    False and ``absent_left_out`` = False (a pair routed elsewhere computed by the held
    expert at its number mod held, where the layer leaves it out) make deliberately WRONG
    references, as the keyword arguments of `attention` among ``attention_sizes`` do.
    ``return_routing``: also return ``(m, top_e)``, the router's input and the experts
    chosen (None for a dense block)."""
    h = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    x = x + attention(params, h, rms_eps=rms_eps, **attention_sizes)
    m = _rms_norm(x, params["ffn_norm"]["scale"], rms_eps)
    if "ffn_gate" in params:
        y = x + _swiglu(m, *(params[f"ffn_{name}"]["kernel"] for name in ("gate", "up", "down")))
        return (y, (m, None)) if return_routing else y
    weights, top_e = route(params, m, experts_per_token, routed_scale, n_group, topk_group, **dict(route_knobs))
    held = params["experts_gate"].shape[0]
    if absent_left_out:
        weights = weights[..., held_lo:held_lo + held]
    else:
        weights = jnp.roll(weights, -held_lo, -1).reshape(*weights.shape[:-1], -1, held).sum(-2)

    def one_expert(total, expert):  # every held expert on every token, masked by its weight
        w_gate, w_up, w_down, weight = expert
        return total + weight[..., None] * _swiglu(m, w_gate, w_up, w_down), None

    per_expert = (params["experts_gate"], params["experts_up"], params["experts_down"], jnp.moveaxis(weights, -1, 0))
    y = x + jax.lax.scan(one_expert, jnp.zeros_like(x), per_expert)[0]
    if shared:
        y = y + _swiglu(m, *(params[f"shared_{name}"]["kernel"] for name in ("gate", "up", "down")))
    return (y, (m, top_e)) if return_routing else y


def _float32(params):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params)


def span_with_routing(all_params, x, **sizes):
    """The blocks of ``all_params`` (a list of parameter trees) applied in order. Returns
    the output and each block's ``(m, top_e)`` (`block`'s ``return_routing``)."""
    with jax.default_matmul_precision("highest"):
        x, routing = x.astype(jnp.float32), []
        for params in all_params:
            x, routed = block(_float32(params), x, return_routing=True, **sizes)
            routing.append(routed)
        return x, routing


def span(all_params, x, **sizes):
    return span_with_routing(all_params, x, **sizes)[0]
