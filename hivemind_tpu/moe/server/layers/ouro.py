"""The decoder block of a LOOPED language model (`model_type: ouro`, ByteDance's Ouro LoopLM;
the equations are in `perf/reference/ouro_block.py`): the model's stack of blocks runs
``total_ut_steps`` times a token with the SAME weights, a pass taking the (normed) output of
the pass before it, and each pass attends the keys and values that THAT pass wrote.

The block is the Llama family's attention and SwiGLU with SANDWICH normalisation: a second
RMS norm on each sublayer's OUTPUT before the residual, beside the usual one on its input::

    a = W_o Attn(rope(W_q N1(x)), rope(W_k N1(x)), W_v N1(x))      h = x + N2(a)
    m = W_down (silu(W_gate N3(h)) * W_up N3(h))                    y = h + N4(m)

no bias anywhere, rotary embedding over the whole head at the absolute position. Its attention
half is the Llama-family blocks' own (`common._rope_attention_half`, told to norm its output before
the residual), so it runs their `apply_rope`, `_cache_attention` and prefill core.

**The loop is not here.** The block says how many passes its decode sessions hold
(`decode_passes` = ``total_ut_steps``); `DecodeSessionManager` keeps that many cache pairs a
session and hands a step the pair of the pass its request named (``loop_pass``), so the block
sees ONE cache and never learns which pass it is: every pass runs the same programs. The norm
between two passes, the exit gate and the head are the client's (`RemoteSequential.decode_step`)."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from hivemind_tpu.moe.server.layers.common import _empty_kv_cache, _plain_dense, _rope_attention_half


class OuroBlockExpert(nn.Module):
    """One Ouro decoder block on [batch, seq, hid]; see the module's docstring. ``head_dim`` is
    ``hidden_dim // num_heads`` (Ouro-2.6B: 2048 / 16 = 128, its published head size); one that
    says otherwise fails loudly and is not served at another shape."""

    hidden_dim: int
    num_heads: int = 16
    num_kv_heads: int = 0  # 0 = as many as query heads (Ouro-2.6B)
    ffn_inner: int = 5632
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    head_dim: int = 0  # 0 = hidden_dim // num_heads; anything else must equal it
    total_ut_steps: int = 4  # how many times a token runs the stack: the passes a decode session holds

    decode_cache_kind = "looped"
    decode_rows_apart = True  # caches of ``max_len`` slots: a batched step takes the rows' own arrays (`_grouped_cache_step`)

    @property
    def decode_passes(self) -> int:
        """The cache pairs and positions a decode session of this block holds: one a pass of the loop."""
        return self.total_ut_steps

    def init_decode_cache(self, batch: int, max_len: int):
        """ONE pass's ``(cache_k, cache_v)``; the manager asks once a pass."""
        return _empty_kv_cache(batch, max_len, self.num_kv_heads or self.num_heads, self.hidden_dim // self.num_heads)

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None):
        hid = x.shape[-1]
        with jax.named_scope("loop_attention"):
            x, cache_k, cache_v = _rope_attention_half(
                x, cache_k, cache_v, index, heads=self.num_heads, kv_heads=self.num_kv_heads or self.num_heads,
                rope_theta=self.rope_theta, rms_eps=self.rms_eps, head_dim=self.head_dim, out_norm=True,
            )
        with jax.named_scope("loop_mlp"):
            normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
            gated = jax.nn.silu(_plain_dense(self.ffn_inner, "ffn_gate")(normed)) * _plain_dense(self.ffn_inner, "ffn_up")(normed)
            out = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_out_norm")(_plain_dense(hid, "ffn_down")(gated))
            y = (x + out).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)
