"""GraniteMoeHybrid-family blocks without experts (`model_type: granitemoehybrid` with
`num_local_experts` 0; the equations and every assumption are in
`perf/reference/granite_h_block.py`, written for ibm-granite/granite-4.0-h-micro): one class,
two kinds, chosen per block by ``kind`` as the model's `layer_types` names them. EVERY block
is a mixer AND a gated MLP under two residuals scaled by ``residual_multiplier`` (``r``)::

    h = x + r * Mixer(RMSNorm1(x))          y = h + r * W_out (silu(g) * v),  [g | v] = W_in RMSNorm2(h)

- ``"mamba"``: `nemotron_h_block`'s Mamba-2 mixer, inherited and not copied (the same
  projection to ``[z | xBC | dt]``, convolution with bias, `ops/ssm.py`'s step and scan, skip
  term, gate BEFORE the group norm), here over ONE group (the norm runs over all ``mamba_heads *
  mamba_head_dim`` values) and in sub-chunks of ``chunk_size`` = 256. The session's tree, its
  `decode_cache_kind` ``ssm``, `decode_rows_apart` and `decode_takes_length` are that mixer's.
- ``"attention"``: grouped-query causal softmax attention, no bias, NO position embedding
  (`position_embedding_type` ``nope``), heads of 64, scores scaled by ``attention_multiplier``
  (1/64) and not by ``head_dim ** -0.5`` (1/8). The cache step and the chunk are the shared
  `common._grouped_cache_step` and `nemotron_h.attend_chunk`, which scale by ``head_dim ** -0.5``
  themselves: the queries are scaled by ``attention_multiplier * head_dim ** 0.5`` (0.125 at the
  published sizes, a power of two: the published arithmetic to the bit in bf16) before either, so
  neither function changes. Caches ``[batch, kv_heads, slots, head_dim]`` bf16, `decode_cache_kind`
  ``full``. On a v5e an array ``[1, 8, 12288, 64]`` bf16 lies with its SLOTS as the minor axis
  (``{2,3,1,0:T(8,128)(2,1)}``, read off the chip's compiler: `tests/test_tpu_compile.py`), so
  the 64-wide heads are not padded to a lane row of 128 and the logical bytes are the physical.

What is SHARED with `nemotron_h.py`, by subclassing its block: the mixer's body (`_mamba`),
the session trees (`init_decode_cache`), the seeded state-space initialisers, `attend_chunk`,
the jitted one-row step. Nothing of that file was moved or given an argument, so the
Nemotron cell's lowered programs are the text they were. Its own: the two scaled residuals,
the second norm and the MLP (`shared_mlp` in a lowered program), the attention's scale
(`nope_attend`: the scores to the context). The LatentMoE kind is refused here.

This module, `nemotron_h.py` and `ops/ssm.py` are imported when a block is built."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from hivemind_tpu.moe.server.layers.common import _grouped_cache_step, _plain_dense
from hivemind_tpu.moe.server.layers.nemotron_h import ATTENTION, MAMBA, NemotronHBlockExpert, attend_chunk


class GraniteHBlockExpert(NemotronHBlockExpert):
    # the published sizes of granite-4.0-h-micro where they differ from the parent's defaults
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    ssm_groups: int = 1
    chunk_size: int = 256
    num_kv_heads: int = 8
    head_dim: int = 64
    # every block's second half, and the two multipliers
    ffn_inner: int = 8192
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625

    def _attention(self, normed, cache, index):
        from hivemind_tpu.ops.attention import attention_auto

        batch, seq, _hid = normed.shape
        heads, kv_heads, dim = self.num_heads, self.num_kv_heads, self.head_dim
        assert heads % kv_heads == 0, (heads, kv_heads)
        q = _plain_dense(heads * dim, "query")(normed).reshape(batch, seq, heads, dim)
        k = _plain_dense(kv_heads * dim, "key")(normed).reshape(batch, seq, kv_heads, dim)
        v = _plain_dense(kv_heads * dim, "value")(normed).reshape(batch, seq, kv_heads, dim)
        with jax.named_scope("nope_attend"):
            # every attention below scales by dim ** -0.5: what is left of `attention_multiplier` goes onto the queries
            q = q * jnp.asarray(self.attention_multiplier * dim**0.5, q.dtype)
            if cache is None:  # the pool's forward: the chunk is all there is
                repeat = lambda t: jnp.repeat(t, heads // kv_heads, axis=2)
                context = attention_auto(q, repeat(k), repeat(v), causal=True).reshape(batch, seq, heads * dim)
            elif seq == 1:
                rows = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (batch,))
                context, *cache = _grouped_cache_step(q, k, v, *cache, rows)
            else:  # a session's chunk, its first or a later one: written where the session ends, attended with what it holds
                write = lambda held, new: jax.lax.dynamic_update_slice(held, jnp.swapaxes(new, 1, 2).astype(held.dtype), (0, 0, index, 0))
                cache = (write(cache[0], k), write(cache[1], v))
                context = attend_chunk(q, *cache, index)
        return _plain_dense(self.hidden_dim, "attention_out")(context), (None if cache is None else tuple(cache))

    @nn.compact
    def __call__(self, x, *session):
        """``x`` alone: the block on a whole sequence (the pool's forward). With a session:
        ``(x, *cache, index[, length])`` -> ``(y, *cache)``, two leaves either kind (a mixer's
        window and state, which alone takes ``length``; an attention block's keys and values)."""
        if self.kind not in (MAMBA, ATTENTION):  # the parent's third kind: this family has no expert layer of that form
            raise ValueError(f"a granite_h_block is a mixer or an attention block, not {self.kind!r}")
        cache = tuple(session[:2]) if session else None
        index = session[2] if session else None
        length = session[3] if len(session) > 3 else None
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="norm")(x)
        if self.kind == MAMBA:
            out, cache = self._mamba(normed, cache, length)
        else:
            out, cache = self._attention(normed, cache, index)
        h = x + self.residual_multiplier * out
        with jax.named_scope("shared_mlp"):
            normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="mlp_norm")(h)
            gate, up = jnp.split(_plain_dense(2 * self.ffn_inner, "mlp_in")(normed), 2, axis=-1)  # [g | v]
            out = _plain_dense(self.hidden_dim, "mlp_out")(jax.nn.silu(gate) * up)
        y = (h + self.residual_multiplier * out).astype(jnp.float32)
        return y if cache is None else (y, *cache)
