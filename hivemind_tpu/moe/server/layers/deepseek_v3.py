"""DeepSeek-V3-family decoder blocks (`model_type: deepseek_v3`; the equations and every
assumption are in `perf/reference/gigachat_block.py`, written for GigaChat3.1-702B-A36B,
whose `v_head_dim` is 192): multi-head latent attention in every block, then a dense
SwiGLU MLP (``mlp="dense"``: the model's leading `first_k_dense_replace` blocks) or a
sparse expert layer (``mlp="sparse"``), chosen per block by whoever builds the span.

- **Attention.** The query goes through a normed low-rank latent (``q_lora_rank``); keys
  and values are expanded from ONE normed latent a position (``kv_lora_rank`` values)
  beside ONE rotated key that all heads share (``qk_rope_head_dim`` values, YaRN rotary on
  interleaved pairs). A decode session keeps exactly that: one array ``[batch, max_len,
  kv_lora_rank + qk_rope_head_dim]`` bf16 (`decode_cache_kind` ``latent``; 1,152 B a
  position at 512 + 64). One array and not two: a step then writes one row and reads one
  array a session, and on a TPU the array's default layout puts the positions on the lanes
  (576 is no multiple of 128, 16,384 is), so neither part is padded.
  A STEP takes the absorbed form (`ops.latent_attention.latent_step`: ``W_kvb``'s key half
  into the query, its value half onto the output, the latent read where it lies, never
  expanded); a CHUNK (the pool's forward, a session's prompt or a further chunk of it:
  ``decode_takes_chunks``) takes the expanded form (`latent_chunk`: keys and queries in
  blocks under a running softmax). Nothing switches between them but what the call is.
  A batched step takes the rows' caches APART (`decode_rows_apart`): a row's latent is
  written into and attended from its own array; the rows' projections, the absorption and
  the MLP see the rows together.
- **Sparse layer.** Sigmoid router over ``num_experts`` in float32 at the highest matmul
  precision, a per-expert selection bias that picks and does not weigh, GROUP-LIMITED:
  ``n_group`` groups, a group's score the sum of its two best, the ``topk_group`` best
  groups kept, the ``experts_per_token`` best of their experts chosen
  (`ops.sparse_experts.route_sigmoid_top_k`); the chosen scores renormalised and scaled
  by ``routed_scale``; one shared SwiGLU expert for every token; and the routed experts
  ``[held_lo, held_lo + held)`` that THIS server holds (``held`` = 0: all of them). The
  router keeps its ``num_experts`` outputs; a pair routed to an expert held elsewhere adds
  nothing here (`routed_swiglu_held`), as under expert parallelism before the exchange.

The chosen experts are sown into `ROUTING_COLLECTION`; `held_experts` tells the serving
paths which of them were computed here. On a decode path a sparse block also sows what
its router saw and chose into `ATTENDED_COLLECTION` (``router_input``, ``router_choice``):
outputs that stay on the device unless a check against a reference taps them
(`routing_stats.ROUTER_TAPS`), so that such a check reads the router of the SERVED
programs, step and chunk alike. The positions a step attended are the manager's to count,
from the rows' indices (`hivemind_moe_latent_positions_attended_total`).

This module and `ops/latent_attention.py` are imported when a block is built."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from hivemind_tpu.moe.server.layers.common import ATTENDED_COLLECTION, ROUTING_COLLECTION, _plain_dense
from hivemind_tpu.ops import latent_attention as latent_ops

DENSE, SPARSE = "dense", "sparse"


class DeepseekV3BlockExpert(nn.Module):
    hidden_dim: int
    mlp: str = SPARSE
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128  # DeepSeek-V3's; GigaChat3.1 publishes 192
    rope_theta: float = 10000.0
    rope_factor: float = 40.0  # `rope_scaling` (YaRN): factor, original_max_position_embeddings, beta_fast, beta_slow, mscale, mscale_all_dim
    rope_original: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    ffn_inner: int = 18432  # a dense block's width
    num_experts: int = 256  # the router's outputs
    experts_per_token: int = 8
    n_group: int = 8
    topk_group: int = 4
    expert_inner: int = 2048  # the shared expert's and every routed expert's width
    held_lo: int = 0
    held: int = 0
    routed_scale: float = 2.5

    decode_cache_kind = "latent"  # names the block's decode programs and its caches in the telemetry
    decode_takes_chunks = True  # a chunk of more than one position may continue a session
    # a cache of ``max_len`` slots of which a step writes one and reads the rest where it lies
    decode_rows_apart = True

    @property
    def held_experts(self):
        """``(lo, hi)`` of the routed experts computed here; None where all are, or none exist."""
        if self.mlp == DENSE or self.held in (0, self.num_experts):
            return None
        return self.held_lo, self.held_lo + self.held

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * latent_ops.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    def init_decode_cache(self, batch: int, max_len: int):
        """The session's cache as a TREE of one leaf: the normed latent beside the rotated shared key."""
        return (jnp.zeros((batch, max_len, self.kv_lora_rank + self.qk_rope_head_dim), jnp.bfloat16),)

    def _attention(self, normed, cache, index):
        batch, seq, _hid = normed.shape
        heads, nope, roped, rank = self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim, self.kv_lora_rank
        query_latent = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="query_latent_norm")(
            _plain_dense(self.q_lora_rank, "query_down")(normed))
        q = _plain_dense(heads * (nope + roped), "query_up")(query_latent).reshape(batch, seq, heads, nope + roped)
        down = _plain_dense(rank + roped, "kv_down")(normed)
        latent = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="kv_latent_norm")(down[..., :rank])
        # [rank, heads, nope | v]: every head's key and value weights over the latent
        w_kvb = self.param("kv_up", nn.initializers.lecun_normal(), (rank, heads * (nope + self.v_head_dim)), jnp.float32)
        w_kvb = w_kvb.reshape(rank, heads, nope + self.v_head_dim)
        w_k, w_v = w_kvb[..., :nope], w_kvb[..., nope:]
        inv_freq = latent_ops.yarn_inv_freq(roped, self.rope_theta, self.rope_factor, self.rope_original,
                                            self.rope_beta_fast, self.rope_beta_slow)
        amplitude = (latent_ops.yarn_mscale(self.rope_factor, self.rope_mscale)
                     / latent_ops.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))
        offset = jnp.asarray(0 if index is None else index, jnp.int32)
        positions = offset[..., None] + jnp.arange(seq)  # [seq], or [rows, seq] in a batched step
        q_nope, q_pe = q[..., :nope], latent_ops.rope_interleaved(q[..., nope:], positions, inv_freq, amplitude)
        new = jnp.concatenate([latent, latent_ops.rope_interleaved(down[..., rank:], positions, inv_freq, amplitude)], axis=-1)
        if cache is not None and seq == 1:
            context, cache = latent_ops.latent_step(q_nope[:, 0], q_pe[:, 0], new, cache, index, w_k, w_v, self.softmax_scale)
            context = context[:, None]
        else:
            # a whole chunk: the pool's forward (the chunk is all there is, in a cache of its own that is
            # not kept), a session's prompt, or a further chunk of it, written where the session ends
            holds = new.astype(jnp.bfloat16) if cache is None else jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), (0, index, 0))
            context = latent_ops.latent_chunk(q_nope, q_pe, holds, 0 if cache is None else index, w_k, w_v, self.softmax_scale)
            cache = None if cache is None else holds
        return _plain_dense(self.hidden_dim, "attention_out")(context.reshape(batch, seq, heads * self.v_head_dim)), cache

    @nn.compact
    def __call__(self, x, cache=None, index=None):
        """``x`` alone: the block on a whole sequence (the pool's forward). With a session:
        ``(x, cache, index)`` -> ``(y, cache)``; ``index`` a scalar (one session's chunk or
        step) or ``[rows]`` with ``cache`` the tuple of the rows' own arrays (a batched step)."""
        from hivemind_tpu.ops.sparse_experts import route_sigmoid_top_k, routed_swiglu_held

        assert self.mlp in (DENSE, SPARSE), self.mlp
        batch, seq, hid = x.shape
        attended, cache = self._attention(nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="attention_norm")(x), cache, index)
        x = x + attended
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
        swiglu = lambda prefix, width: _plain_dense(hid, prefix + "_down")(
            jax.nn.silu(_plain_dense(width, prefix + "_gate")(normed)) * _plain_dense(width, prefix + "_up")(normed))
        if self.mlp == DENSE:
            y = (x + swiglu("ffn", self.ffn_inner)).astype(jnp.float32)
            return y if cache is None else (y, cache)
        per_expert = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        held, inner = self.held or self.num_experts, self.expert_inner
        router = self.param("router", nn.initializers.lecun_normal(), (hid, self.num_experts), jnp.float32)
        # a checkpoint brings its own; a seeded one is drawn wide enough to change some picks
        bias = self.param("router_bias", nn.initializers.normal(0.1), (self.num_experts,), jnp.float32)
        w_gate = self.param("experts_gate", per_expert, (held, hid, inner), jnp.float32)
        w_up = self.param("experts_up", per_expert, (held, hid, inner), jnp.float32)
        w_down = self.param("experts_down", per_expert, (held, inner, hid), jnp.float32)
        tokens = normed.reshape(batch * seq, hid)  # the call's rows together
        weights, top_e = route_sigmoid_top_k(tokens, router, bias, self.experts_per_token, self.routed_scale,
                                             n_group=self.n_group, topk_group=self.topk_group)
        self.sow(ROUTING_COLLECTION, "expert_choice", top_e.reshape(batch, seq, -1))
        self.sow(ATTENDED_COLLECTION, "router_input", normed)  # a decode path's alone, and there only a tapped check fetches them
        self.sow(ATTENDED_COLLECTION, "router_choice", top_e.reshape(batch, seq, -1))
        with jax.named_scope("moe_experts"):
            routed = routed_swiglu_held(tokens, weights, top_e, w_gate, w_up, w_down, self.held_lo)
        y = (x + swiglu("shared", inner) + routed.reshape(batch, seq, hid)).astype(jnp.float32)
        return y if cache is None else (y, cache)
