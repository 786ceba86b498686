"""Multi-head latent attention over ONE compressed array a position (DeepSeek-V2/V3's MLA):
a session's cache holds, per position, the normed latent ``c`` (``rank`` values, which every
head's key AND value are expanded from by ``W_kvb``) beside the rotated key ``k_pe`` that all
heads share (``rope`` values): ``[batch, slots, rank + rope]`` bf16, 1,152 B a position at
512 + 64 where 64 heads of 192 + 192 would keep 49,152 B.

Two forms over the same cache, chosen by what the call is:

- **a step absorbs** (`latent_step`): ``W_kvb``'s key half goes into the query
  (``q' = q_nope W_K^T``, 128 -> rank a head) and its value half onto the output
  (``o = u W_V``), so that a row's attention is ``[heads, rank + rope] x [rank + rope, n]``
  over the latent AS IT LIES: the heads are the matmul's rows, and no key or value of
  the cache is ever expanded (``heads x n x 320`` values a row);
- **a chunk expands** (`latent_chunk`): the cached latents are expanded once, a block of
  keys at a time, and a chunk's queries attend them with heads of 192, keys in blocks
  and queries in blocks under a running softmax, so that no ``[queries, keys, heads]``
  score array is ever whole (2,048 x 14,336 x 64 in float32 is 7.5 GB).

Plain `jax.numpy` / `lax` under the named scopes ``latent_absorb``, ``latent_attend``
and ``latent_expand``, which the benchmark's trace reduction reads.

YaRN rotary frequencies and DeepSeek's interleaved rotation are here too: they belong
to this attention's shared key and to nothing else in the tree."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int, beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN: a pair that turns more than
    ``beta_fast`` times within the ``original`` context keeps ``theta^(-2i/dim)``, one that
    turns less than ``beta_slow`` times is slowed by ``factor``, and a linear ramp lies
    between (float32, computed on the host: they are constants of the block)."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turns = lambda beta: dim * math.log(original / (beta * 2 * math.pi)) / (2 * math.log(theta))
    lo, hi = max(math.floor(turns(beta_fast)), 0), min(math.ceil(turns(beta_slow)), dim - 1)
    keep = 1.0 - np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - keep) * plain / factor + keep * plain).astype(np.float32)


def rope_interleaved(x: jax.Array, positions: jax.Array, inv_freq, amplitude: float = 1.0) -> jax.Array:
    """Rotate the pairs ``(2i, 2i + 1)`` of ``x`` ``[batch, seq, .., dim]`` by ``positions *
    inv_freq[i]``; ``positions`` ``[seq]`` or ``[batch, seq]`` (each row of a batched step at
    its own). The angles and the rotation are float32; the result has ``x``'s dtype."""
    angles = jnp.asarray(positions, jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    angles = angles.reshape((-1,) + angles.shape[-2:-1] + (1,) * (x.ndim - 3) + angles.shape[-1:])  # [batch or 1, seq, 1.., dim / 2]
    cos, sin = jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude
    a, b = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape).astype(x.dtype)


def _step_rows(q, new, cache, index):
    """``q`` ``[rows, heads, width]`` (absorbed and scaled), ``new`` ``[rows, 1, width]``,
    ``cache`` ``[rows, slots, width]``, every row at the write position ``index`` (a scalar):
    write the position, attend the slots ``<= index`` where they lie. The weighted sum runs
    over the whole width (the shared key's 64 columns ride along and are dropped by the
    caller): a slice of the cache's minor axis would be a copy of it."""
    cache = jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), (0, index, 0))
    with jax.named_scope("latent_attend"):
        scores = jnp.einsum("rhw,rsw->rhs", q.astype(cache.dtype), cache, preferred_element_type=jnp.float32)
        live = jnp.arange(cache.shape[1]) <= index
        scores = jnp.where(live[None, None, :], scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(cache.dtype)
        mixed = jnp.einsum("rhs,rsw->rhw", probs, cache, preferred_element_type=jnp.float32)
    return mixed, cache


# one row of a batched step, traced ONCE for all the rows, buckets and blocks of one shape
_step_row = jax.jit(_step_rows)


def latent_step(q_nope, q_pe, new, cache, index, w_k, w_v, scale: float):
    """One position a row in the absorbed form. ``q_nope`` ``[rows, heads, nope]``, ``q_pe``
    ``[rows, heads, rope]`` (rotated), ``new`` ``[rows, 1, rank + rope]`` (the position's normed
    latent beside its rotated shared key), ``w_k`` ``[rank, heads, nope]`` and ``w_v`` ``[rank,
    heads, v]`` the two halves of ``W_kvb``. ``cache``: an array ``[rows, slots, rank + rope]``
    with ``index`` a scalar (a session's own step), or the TUPLE of the rows' own arrays
    ``[1, slots, rank + rope]`` with ``index`` ``[rows]`` (a batched step of a block that says
    `decode_rows_apart`): each row is then written and attended where it lies. Returns
    (context ``[rows, heads, v]``, the cache in the form it came in)."""
    rank = w_k.shape[0]
    with jax.named_scope("latent_absorb"):
        absorbed = jnp.einsum("rhd,chd->rhc", q_nope, w_k.astype(q_nope.dtype))  # W_K into the query: 128 -> rank a head
    q = jnp.concatenate([absorbed, q_pe], axis=-1) * jnp.asarray(scale, absorbed.dtype)
    if isinstance(cache, (tuple, list)):
        steps = [_step_row(q[row:row + 1], new[row:row + 1], cache[row], index[row]) for row in range(len(cache))]
        mixed, cache = zip(*steps)
        mixed = jnp.concatenate(mixed)
    else:
        mixed, cache = _step_rows(q, new, cache, index)
    with jax.named_scope("latent_absorb"):
        context = jnp.einsum("rhc,chv->rhv", mixed[..., :rank].astype(q_nope.dtype), w_v.astype(q_nope.dtype))  # W_V onto the output
    return context, cache


def latent_chunk(q_nope, q_pe, cache, index, w_k, w_v, scale: float, key_block: int = 1024, query_block: int = 512):
    """A chunk's queries against the cache that already holds the chunk, in the expanded
    form. ``q_nope`` ``[batch, seq, heads, nope]``, ``q_pe`` ``[batch, seq, heads, rope]`` (rotated,
    the query at row i at position ``index + i``), ``cache`` ``[batch, slots, rank + rope]``.
    The key blocks ``0 .. ceil((index + seq) / key_block)`` are walked in order; each is
    expanded once (``latent_expand``) and attended by the chunk's queries, a block of them
    at a time, under a running softmax in float32 (``latent_attend``). The last block of a
    cache whose slots are no multiple of ``key_block`` is taken from the cache's end and the
    positions the block before it held are masked. Returns ``[batch, seq, heads, v]``."""
    batch, seq, heads, _nope = q_nope.shape
    rank, v_dim = w_k.shape[0], w_v.shape[-1]
    slots, dtype = cache.shape[1], cache.dtype
    key_block, query_block = min(key_block, slots), min(query_block, seq)
    query_blocks = -(-seq // query_block)
    padded = query_blocks * query_block
    in_blocks = lambda t: jnp.moveaxis(jnp.pad(t, ((0, 0), (0, padded - seq)) + ((0, 0),) * (t.ndim - 2)).reshape(
        (batch, query_blocks, query_block) + t.shape[2:]), 1, 0)
    q_nope, q_pe = in_blocks((q_nope * jnp.asarray(scale, q_nope.dtype)).astype(dtype)), in_blocks((q_pe * jnp.asarray(scale, q_pe.dtype)).astype(dtype))
    q_at = index + jnp.arange(padded).reshape(query_blocks, query_block)
    w_k, w_v = w_k.astype(dtype), w_v.astype(dtype)
    lowest = jnp.finfo(jnp.float32).min

    def one_key_block(block, state):
        start = jnp.minimum(block * key_block, slots - key_block)
        held = jax.lax.dynamic_slice_in_dim(cache, start, key_block, axis=1)
        at = start + jnp.arange(key_block)
        fresh = at >= block * key_block  # a last block taken from the cache's end repeats positions of the one before
        with jax.named_scope("latent_expand"):
            k_nope = jnp.einsum("bsc,chd->bshd", held[..., :rank], w_k)
            values = jnp.einsum("bsc,chd->bshd", held[..., :rank], w_v)
        k_pe = held[..., rank:]

        def one_query_block(queries):
            qn, qp, at_q, top, total, mixed = queries
            with jax.named_scope("latent_attend"):
                scores = (jnp.einsum("bqhd,bshd->bhqs", qn, k_nope, preferred_element_type=jnp.float32)
                          + jnp.einsum("bqhd,bsd->bhqs", qp, k_pe, preferred_element_type=jnp.float32))
                seen = ((at[None, :] <= at_q[:, None]) & fresh[None, :])[None, None]
                new_top = jnp.maximum(top, jnp.where(seen, scores, lowest).max(-1))
                weights = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
                shrink = jnp.exp(top - new_top)
                total = total * shrink + weights.sum(-1)
                mixed = mixed * jnp.moveaxis(shrink, 1, 2)[..., None] + jnp.einsum(
                    "bhqs,bshd->bqhd", weights.astype(dtype), values, preferred_element_type=jnp.float32)
            return new_top, total, mixed

        return jax.lax.map(one_query_block, (q_nope, q_pe, q_at, *state))

    state = (jnp.full((query_blocks, batch, heads, query_block), lowest, jnp.float32),
             jnp.zeros((query_blocks, batch, heads, query_block), jnp.float32),
             jnp.zeros((query_blocks, batch, query_block, heads, v_dim), jnp.float32))
    key_blocks = (index + seq + key_block - 1) // key_block
    _top, total, mixed = jax.lax.fori_loop(0, key_blocks, one_key_block, state)
    context = mixed / jnp.moveaxis(total, 2, 3)[..., None]
    return jnp.moveaxis(context, 0, 1).reshape(batch, padded, heads, v_dim)[:, :seq].astype(dtype)
