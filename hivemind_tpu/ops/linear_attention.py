"""Lightning (linear) attention with a per-head scalar decay: the one-position update
of a recurrent state, and the chunked scan that brings a whole chunk of positions
through the same recurrence.

Per head j, with a decay ``lambda_j`` in (0, 1) and a state ``S`` ``[dim, dim]`` kept
in float32:

    S_t = lambda_j S_{t-1} + k_t^T v_t         o_t = q_t S_t / sqrt(dim)

A chunk of C positions is exact in exact arithmetic as

    O = ((Q K^T) * D) V / sqrt(dim) + diag(lambda^(1..C)) Q S_prev / sqrt(dim),   D_ts = lambda^(t-s) for s <= t
    S_next = lambda^C S_prev + sum_s lambda^(C-s) k_s^T v_s

and `lightning_scan` runs it over sub-chunks, every power of the decay formed as
``exp((t - s) log lambda)`` with t >= s: at most 1, so that ``lambda^(-C)`` (which
overflows float32 for the fastest heads within a few dozen positions) is never formed.
Plain `jax.numpy` / `lax`; the scopes `lightning_step` and `lightning_scan` name the
two in a lowered program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

SUB_CHUNK = 256  # positions a sub-chunk: [heads, 256, 256] float32 of decays is 8 MB at 32 heads


def lightning_log_decay(heads: int) -> jax.Array:
    """``log lambda_j`` = ``-2^(-8 (j + 1) / heads)`` (Lightning Attention-2's slopes)."""
    return -(2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads))


def lightning_step(q, k, v, state, log_decay):
    """One position a row: ``q``, ``k``, ``v`` ``[rows, heads, dim]``, ``state`` ``[rows,
    heads, dim, dim]`` float32 (read once, written once). Returns (o ``[rows, heads, dim]``
    float32, state)."""
    with jax.named_scope("lightning_step"):
        dim = q.shape[-1]
        k, v, q = (t.astype(jnp.float32) for t in (k, v, q))
        state = jnp.exp(log_decay)[None, :, None, None] * state + k[..., :, None] * v[..., None, :]
        o = (q[..., :, None] * state).sum(-2) * dim**-0.5  # a matrix-vector product a head: the state's bytes bound it
        return o, state


def lightning_scan(q, k, v, state, log_decay, length=None, sub_chunk: int = SUB_CHUNK):
    """A chunk of positions: ``q``, ``k``, ``v`` ``[batch, seq, heads, dim]``, ``state``
    ``[batch, heads, dim, dim]`` float32 as the chunk finds it. ``length`` (may be traced):
    how many leading positions are real; the padding after them neither decays the
    state nor adds to it (its outputs are whatever they are and are cut off by the
    caller). Returns (o ``[batch, seq, heads, dim]`` float32, the state after the last
    real position)."""
    with jax.named_scope("lightning_scan"):
        batch, seq, heads, dim = q.shape
        length = seq if length is None else length
        size = min(sub_chunk, seq)
        chunks = -(-seq // size)
        pad = ((0, 0), (0, chunks * size - seq), (0, 0), (0, 0))
        split = lambda t: jnp.moveaxis(jnp.pad(t, pad).reshape(batch, chunks, size, heads, dim), 1, 0)
        real = (jnp.arange(chunks * size) < length).reshape(chunks, size)
        lower = jnp.tril(jnp.ones((size, size), bool))

        def one_chunk(state, inputs):
            q, k, v, real = inputs  # [batch, size, heads, dim]; real [size]
            # a_t: log of the decay gathered from the sub-chunk's start to position t (padding gathers none)
            a = jnp.cumsum(real.astype(jnp.float32))[None, :] * log_decay[:, None]  # [heads, size]
            k = jnp.where(real[None, :, None, None], k, 0)
            decays = jnp.exp(jnp.where(lower[None], a[:, :, None] - a[:, None, :], -jnp.inf))  # [heads, t, s], each <= 1
            scores = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32) * decays[None]
            within = jnp.einsum("bhts,bshe->bthe", scores.astype(v.dtype), v, preferred_element_type=jnp.float32)
            carried = jnp.einsum("bthd,bhde->bthe", q.astype(jnp.float32), state) * jnp.exp(a).T[None, :, :, None]
            to_end = jnp.exp(a[:, -1:] - a).T[None, :, :, None]  # lambda^(real positions after s), [1, size, heads, 1]
            state = jnp.exp(a[:, -1])[None, :, None, None] * state + jnp.einsum(
                "bshd,bshe->bhde", (k.astype(jnp.float32) * to_end).astype(v.dtype), v, preferred_element_type=jnp.float32)
            return state, (within + carried) * dim**-0.5

        state, o = jax.lax.scan(one_chunk, state, (split(q), split(k), split(v), real))
        return jnp.moveaxis(o, 0, 1).reshape(batch, chunks * size, heads, dim)[:, :seq], state
