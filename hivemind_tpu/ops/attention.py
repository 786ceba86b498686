"""The attention core on ONE device and the choice of it: the plain einsum core,
`flash_applies` (whether the fused Pallas kernel serves a call) and `attention_auto`
(the dispatch). Operands sharded over a mesh go through
`parallel.ring_attention.mesh_attention_core`, which runs a core per shard."""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp


def plain_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
) -> jax.Array:
    """Single-device attention core with the same [B, T, H, D] convention.

    :param mask: optional [B, T] key-validity mask
    :param causal: lower-triangular masking (decoder blocks); position t attends
        only to positions <= t, so right-padding never leaks into real positions
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    neg = jnp.finfo(scores.dtype).min
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :], scores, neg)
    if causal:
        # offset so queries align to the END of the key sequence: incremental
        # decode (q_len=1 vs cached k_len) sees all past keys, not just key 0
        q_len, k_len = scores.shape[-2], scores.shape[-1]
        tri = jnp.tril(jnp.ones((q_len, k_len), bool), k=k_len - q_len)
        scores = jnp.where(tri[None, None], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_forced() -> bool:
    """HIVEMIND_TPU_FORCE_FLASH=1 selects the flash kernels regardless of the
    CURRENT backend — for AOT workflows (jax.export platforms=["tpu"]) where the
    trace happens on a CPU host but the artifact targets a TPU."""
    return os.environ.get("HIVEMIND_TPU_FORCE_FLASH", "0") == "1"


def flash_applies(q, k, mask=None) -> bool:
    """Whether the fused kernel serves this call: full unmasked sequences on a TPU
    (or an AOT trace for one). q_len != k_len (cached incremental decode) needs
    plain_attention's end-aligned causal mask; the kernel assumes square
    self-attention."""
    return (
        mask is None
        and q.shape[1] == k.shape[1]
        and (jax.default_backend() == "tpu" or _flash_forced())
    )


def attention_auto(q, k, v, mask=None, causal: bool = False):
    """Backend dispatch for the attention core on ONE device: fused Pallas kernel
    where `flash_applies` (both directions are fused kernels), reference einsum
    path elsewhere."""
    if flash_applies(q, k, mask):
        from hivemind_tpu.ops.pallas_attention import flash_attention

        return flash_attention(q, k, v, causal)
    return plain_attention(q, k, v, mask=mask, causal=causal)
