"""The Mamba-2 (SSD) state-space recurrence: the one-position step of a decode session and
the chunked scan that brings a whole prompt chunk through the same recurrence, each with
the short causal convolution that feeds it.

Per head h of ``P`` values, with a state ``S`` ``[P, N]`` kept in float32, a step size
``dt_t > 0`` that the INPUT gives (after its softplus), a decay rate ``A_h < 0``, and the
``B_t``, ``C_t`` ``[N]`` of the head's group (``H / G`` consecutive heads read one group):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t          y_t = S_t C_t + D_h x_t

A chunk of L positions is exact in exact arithmetic as

    Y = ((C B^T) * M) X + diag(exp(a)) C S_prev,   M_ts = exp(a_t - a_s) dt_s for s <= t,   a_t = sum_{r <= t} dt_r A
    S_next = exp(a_L) S_prev + sum_s exp(a_L - a_s) dt_s x_s (x) B_s

and `ssd_scan` runs it over sub-chunks of ``chunk`` positions (the model's `chunk_size`),
every decay formed as ``exp`` of a NON-POSITIVE difference of the cumulative sum (t >= s),
so that ``exp(-a_s)``, which overflows float32 within a sub-chunk for the fastest heads, is
never formed. A position past ``length`` (right-padding) has dt = 0: it neither decays the
state nor adds to it. The convolution is depthwise over the last ``K`` positions; a
session keeps the last ``K - 1`` input rows (`conv_step` rolls them, `conv_chunk` cuts them
from the last REAL rows, so that no padded row enters the window).

What `ops/linear_attention.lightning_scan` shares with this is the outline (sub-chunks
under a `lax.scan`, decays from a cumulative sum); the bodies differ in every operand: a
lightning head's decay is one constant and its cumulative sum a count, its keys are its
own and not a group's, and it has no step size on the input and no skip term. One body for
both would change MiniCPM-SALA's program, so each keeps its own.

Plain `jax.numpy` / `lax`; the scopes `ssm_conv`, `ssm_step` and `ssm_scan` name the
parts in a lowered program."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def conv_step(new, window, weight, bias):
    """One position a row through the depthwise causal convolution: ``new`` ``[rows,
    channels]`` (the position's input), ``window`` ``[rows, K - 1, channels]`` (the rows'
    last ``K - 1`` inputs, oldest first), ``weight`` ``[K, channels]``, ``bias``
    ``[channels]``. Returns (silu of the convolution ``[rows, channels]`` float32, the
    window rolled by one, in its own dtype)."""
    with jax.named_scope("ssm_conv"):
        seen = jnp.concatenate([window, new[:, None].astype(window.dtype)], axis=1)  # [rows, K, channels]
        out = bias.astype(jnp.float32) + (seen.astype(jnp.float32) * weight.astype(jnp.float32)[None]).sum(1)
        return jax.nn.silu(out), seen[:, 1:]


def conv_chunk(new, window, weight, bias, length=None):
    """A chunk of positions through the same convolution: ``new`` ``[batch, seq,
    channels]``, ``window`` as in `conv_step` (zeros before a stream's first position).
    ``length`` (may be traced): how many leading positions are real. Returns (silu of the
    convolution ``[batch, seq, channels]`` float32, the window after the last REAL
    position: the padding after it never enters)."""
    with jax.named_scope("ssm_conv"):
        seq, taps = new.shape[1], weight.shape[0]
        seen = jnp.concatenate([window, new.astype(window.dtype)], axis=1)  # position t's inputs are rows t .. t + K - 1
        out = bias.astype(jnp.float32) + sum(
            seen[:, tap:tap + seq].astype(jnp.float32) * weight[tap].astype(jnp.float32) for tap in range(taps))
        window = jax.lax.dynamic_slice_in_dim(seen, seq if length is None else length, taps - 1, axis=1)
        return jax.nn.silu(out), window


def _by_group(t, groups: int, axis: int = 1):
    """``t`` with its head axis (``axis``) split as ``[G, H / G]``: a group's heads side by side."""
    return t.reshape(t.shape[:axis] + (groups, t.shape[axis] // groups) + t.shape[axis + 1:])


def ssd_step(x, b, c, dt, a, d, state):
    """One position a row: ``x`` ``[rows, H, P]``, ``b``, ``c`` ``[rows, G, N]``, ``dt``
    ``[rows, H]`` (positive: after its softplus), ``a`` ``[H]`` (negative), ``d`` ``[H]``,
    ``state`` ``[rows, H, P, N]`` float32 (read once, written once). The state carries the
    position: nothing here depends on where a row stands. Returns (y ``[rows, H, P]``
    float32, state)."""
    with jax.named_scope("ssm_step"):
        rows, heads, dim = x.shape
        groups = b.shape[1]
        x, b, c, dt = (t.astype(jnp.float32) for t in (x, b, c, dt))
        grouped = _by_group(state, groups)  # [rows, G, H / G, P, N]: a group's B and C are read by its heads where they lie
        decay = jnp.exp(dt * a)
        fed = _by_group((dt[..., None] * x)[..., None], groups) * b[:, :, None, None, :]
        grouped = _by_group(decay[..., None, None], groups) * grouped + fed
        y = (grouped * c[:, :, None, None, :]).sum(-1).reshape(rows, heads, dim)  # a matrix-vector product a head: the state's bytes bound it
        return y + d[None, :, None] * x, grouped.reshape(state.shape)


def ssd_scan(x, b, c, dt, a, d, state, length=None, chunk: int = 128):
    """A chunk of positions: ``x`` ``[batch, seq, H, P]``, ``b``, ``c`` ``[batch, seq, G,
    N]``, ``dt`` ``[batch, seq, H]`` (positive), ``a``, ``d`` ``[H]``, ``state`` ``[batch, H,
    P, N]`` float32 as the chunk finds it. ``length`` (may be traced): how many leading
    positions are real; the padding after them has dt = 0 (its outputs are whatever they
    are and are cut off by the caller). Returns (y ``[batch, seq, H, P]`` float32, the state
    after the last real position)."""
    with jax.named_scope("ssm_scan"):
        batch, seq, heads, dim = x.shape
        groups, per_group = b.shape[2], heads // b.shape[2]
        size = min(chunk, seq)
        chunks = -(-seq // size)
        split = lambda t: jnp.moveaxis(jnp.pad(t, ((0, 0), (0, chunks * size - seq)) + ((0, 0),) * (t.ndim - 2)).reshape(
            (batch, chunks, size) + t.shape[2:]), 1, 0)
        real = jnp.arange(chunks * size) < (seq if length is None else length)
        dt = jnp.where(real[None, :seq, None], dt.astype(jnp.float32), 0.0)  # padding neither decays nor feeds
        lower = jnp.tril(jnp.ones((size, size), bool))

        def one_chunk(state, inputs):
            x, b, c, dt = inputs  # [batch, size, ...]
            gathered = jnp.cumsum(dt * a, axis=1)  # a_t [batch, size, H]: non-positive, falling
            by_head = jnp.moveaxis(gathered, 1, 2)  # [batch, H, size]
            decays = jnp.exp(jnp.where(lower, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))  # [batch, H, t, s], each <= 1
            fed = x.astype(jnp.float32) * dt[..., None]  # dt_s x_s
            scores = jnp.einsum("btgn,bsgn->bgts", c, b, preferred_element_type=jnp.float32)  # a group's, shared by its heads
            mixed = jnp.repeat(scores, per_group, axis=1) * decays
            within = jnp.einsum("bhts,bshp->bthp", mixed.astype(x.dtype), fed.astype(x.dtype), preferred_element_type=jnp.float32)
            grouped = _by_group(state, groups)
            carried = jnp.einsum("btgn,bgkpn->btgkp", c.astype(jnp.float32), grouped).reshape(batch, size, heads, dim)
            carried = carried * jnp.exp(gathered)[..., None]
            to_end = jnp.exp(gathered[:, -1:] - gathered)  # exp(a_L - a_s) [batch, size, H]
            added = jnp.einsum("bsgkp,bsgn->bgkpn", _by_group((fed * to_end[..., None]).astype(x.dtype), groups, axis=2), b,
                               preferred_element_type=jnp.float32)
            state = jnp.exp(gathered[:, -1])[..., None, None] * state + added.reshape(state.shape)
            return state, within + carried

        state, y = jax.lax.scan(one_chunk, state, (split(x), split(b), split(c), split(dt)))
        y = jnp.moveaxis(y, 0, 1).reshape(batch, chunks * size, heads, dim)[:, :seq]
        return y + d[None, None, :, None] * x.astype(jnp.float32), state
