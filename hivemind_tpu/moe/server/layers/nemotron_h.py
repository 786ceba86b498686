"""NemotronH-family blocks (`model_type: nemotron_h`; the equations and every assumption
are in `perf/reference/nemotron_h_block.py`, written for NVIDIA-Nemotron-3-Super-120B-A12B):
one class, three kinds, chosen per block by ``kind`` as the model's
`hybrid_override_pattern` names them. A block here is ONE residual, ``y = x + f(RMSNorm(x))``:
a mixer OR a feed-forward part, where every other served block is both.

- ``"mamba"`` (`M`): a Mamba-2 mixer. One input projection to ``[z | xBC | dt]``, a
  depthwise causal convolution of ``conv_kernel`` taps over ``xBC``, the state-space
  recurrence (`ops/ssm.py`) over ``mamba_heads`` heads whose ``B`` and ``C`` are shared by
  groups, a skip term, the gate ``silu(z)`` BEFORE a group RMS norm, the output projection.
  A decode session keeps TWO arrays: the last ``conv_kernel - 1`` rows of ``xBC``
  ``[batch, conv_kernel - 1, channels]`` bf16 (a state with a time axis of the kernel's
  width) and the recurrent state ``[batch, heads, head_dim, state]`` float32, which a step
  rewrites whole; `decode_cache_kind` is ``ssm``. In a batched step the rows' caches come
  APART (`decode_rows_apart`): the windows are joined for one convolution, each row's state is
  stepped where it lies. The state carries the position: ``index`` is not read. A chunk comes right-padded with its real positions as ``length``
  (`decode_takes_length`): the padding has a step size of zero and stays out of the window.
- ``"attention"`` (`*`): grouped-query causal softmax attention, no bias, NO position
  embedding (the state-space blocks carry position), caches ``[batch, kv_heads, slots,
  head_dim]`` bf16 as `exaone_moe_block` keeps them; `decode_cache_kind` is ``full``. A step
  is `common._grouped_cache_step` (the rows' caches APART); a chunk writes its keys and
  values where the session ends and attends cache and chunk together, a block of keys at a
  time under a running softmax, so that a prompt's later chunk is a prefill's equal.
- ``"experts"`` (`E`): LatentMoE. The router (sigmoid, a selection bias that picks and does
  not weigh, the picked scores renormalised and scaled: `route_sigmoid_top_k`) and the
  shared expert work on the full hidden; the routed experts, non-gated ``down(relu(up
  l)^2)``, work on a latent ``l = W_dn u`` of ``latent_dim`` values, and ``W_up`` brings
  their weighted sum back. The block holds the routed experts ``[held_lo, held_lo + held)``
  (``held`` = 0: all); ``W_up`` of a share's partial sum is that share's part of the layer
  (linear: the shares add up). It keeps NOTHING between calls: `init_decode_cache` returns
  an EMPTY tree, `decode_cache_kind` is ``stateless``, and in a decode chain it is called as
  ``(x, index)`` and returns ``(y,)``.

All three take a session's prompt in chunks (`decode_takes_chunks`). The chosen experts are
sown into `ROUTING_COLLECTION`; on a decode path what the router saw and chose into
`ATTENDED_COLLECTION` (``router_input``, ``router_choice``), for a check that taps them.

This module and `ops/ssm.py` are imported when a block is built."""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from hivemind_tpu.moe.server.layers.common import ATTENDED_COLLECTION, ROUTING_COLLECTION, _grouped_cache_step, _plain_dense
from hivemind_tpu.ops import ssm

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
CACHE_KINDS = {MAMBA: "ssm", ATTENTION: "full", EXPERTS: "stateless"}


def _initial_a_log(key, shape, dtype=jnp.float32):
    """``A`` uniform in [1, 16] (the family's `A_init_range`), kept as its logarithm."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _initial_dt_bias(low: float, high: float, floor: float):
    """The step size log-uniform in [low, high], floored, and inverted through softplus."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (jnp.log(high) - jnp.log(low)) + jnp.log(low))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def _conv_uniform(key, shape, dtype=jnp.float32):
    """A depthwise convolution's weights and bias, uniform in +-1 / sqrt(taps) (``shape[0]`` taps for the weights)."""
    bound = 4 ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def attend_chunk(q, cache_k, cache_v, index, key_block: int = 512):
    """A chunk's queries against the caches that already hold the chunk: ``q`` ``[batch, seq,
    heads, dim]`` (row i at position ``index + i``), the caches ``[batch, kv_heads, slots,
    dim]``. The key blocks ``0 .. ceil((index + seq) / key_block)`` are walked in order under
    a running softmax in float32, each KV head against its own ``heads / kv_heads`` queries
    where the cache lies. Returns ``[batch, seq, heads * dim]``."""
    batch, seq, heads, dim = q.shape
    kv_heads, slots = cache_k.shape[1], cache_k.shape[2]
    key_block = min(key_block, slots)
    grouped = (q * jnp.asarray(dim**-0.5, q.dtype)).astype(cache_k.dtype).reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    at_q = index + jnp.arange(seq)
    lowest = jnp.finfo(jnp.float32).min

    def one_key_block(block, state):
        top, total, mixed = state
        start = jnp.minimum(block * key_block, slots - key_block)  # a last block is taken from the caches' end
        keys = jax.lax.dynamic_slice_in_dim(cache_k, start, key_block, axis=2)
        values = jax.lax.dynamic_slice_in_dim(cache_v, start, key_block, axis=2)
        at = start + jnp.arange(key_block)
        seen = (at[None, :] <= at_q[:, None]) & (at >= block * key_block)[None, :]  # ... and repeats no position of the block before
        scores = jnp.einsum("bqkgd,bksd->bkgqs", grouped, keys, preferred_element_type=jnp.float32)
        new_top = jnp.maximum(top, jnp.where(seen, scores, lowest).max(-1))
        weights = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        mixed = mixed * shrink[..., None] + jnp.einsum("bkgqs,bksd->bkgqd", weights.astype(values.dtype), values,
                                                       preferred_element_type=jnp.float32)
        return new_top, total * shrink + weights.sum(-1), mixed

    state = (jnp.full((batch, kv_heads, heads // kv_heads, seq), lowest, jnp.float32),
             jnp.zeros((batch, kv_heads, heads // kv_heads, seq), jnp.float32),
             jnp.zeros((batch, kv_heads, heads // kv_heads, seq, dim), jnp.float32))
    _top, total, mixed = jax.lax.fori_loop(0, (index + seq + key_block - 1) // key_block, one_key_block, state)
    context = (mixed / total[..., None]).astype(cache_v.dtype)  # [batch, kv_heads, group, seq, dim]
    return jnp.moveaxis(context, 3, 1).reshape(batch, seq, heads * dim)


# one row of a batched step, traced ONCE for all the rows, buckets and blocks of one shape (as `common._grouped_cache_step_row`)
_ssd_step_row = jax.jit(ssm.ssd_step)


class NemotronHBlockExpert(nn.Module):
    hidden_dim: int
    kind: str = MAMBA
    rms_eps: float = 1e-5
    # a Mamba-2 mixer
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001  # these three shape the INITIAL step-size bias of seeded weights, and nothing else
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # an attention block
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # a LatentMoE layer
    num_experts: int = 512  # the router's outputs
    experts_per_token: int = 22
    latent_dim: int = 1024
    expert_inner: int = 2688
    shared_inner: int = 5376
    held_lo: int = 0
    held: int = 0
    routed_scale: float = 5.0

    decode_takes_chunks = True  # a chunk of more than one position may continue a session, whatever the kind

    @property
    def decode_cache_kind(self) -> str:
        """Names the block's decode programs and its caches in the telemetry."""
        return CACHE_KINDS[self.kind]

    @property
    def decode_takes_length(self) -> bool:
        """The mixer's state and window must keep a chunk's padding out; the attention's padded tail lies past ``index``."""
        return self.kind == MAMBA

    @property
    def decode_rows_apart(self) -> bool:
        """Whether a batched step takes each cache leaf as the tuple of the rows' own arrays: the
        attention's ``max_len`` slots (12.6 MB a session at 12,288), of which a step writes one, and
        the mixer's state, 4.19 MB a session that a step rewrites whole: joined, 16 rows are 67 MB
        copied in and out around the step, and the batched program took 0.99 ms where it takes 0.81
        with each row's state stepped where it lies (PERF.md section 6, PR 51). An expert layer has
        no cache to take apart."""
        return self.kind in (ATTENTION, MAMBA)

    @property
    def held_experts(self):
        """``(lo, hi)`` of the routed experts computed here; None where all are, or none exist."""
        if self.kind != EXPERTS or self.held in (0, self.num_experts):
            return None
        return self.held_lo, self.held_lo + self.held

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def init_decode_cache(self, batch: int, max_len: int):
        """The session's cache as a TREE (a tuple of arrays, batch axis first): the window and
        the state, keys and values, or NOTHING (an expert layer keeps no cache)."""
        assert self.kind in CACHE_KINDS, self.kind
        if self.kind == MAMBA:
            return (jnp.zeros((batch, self.conv_kernel - 1, self.conv_channels), jnp.bfloat16),
                    jnp.zeros((batch, self.mamba_heads, self.mamba_head_dim, self.ssm_state), jnp.float32))
        if self.kind == ATTENTION:
            shape = (batch, self.num_kv_heads, max_len, self.head_dim)
            return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)
        return ()

    def _mamba(self, normed, cache, length):
        batch, seq, _hid = normed.shape
        heads, dim, groups, width = self.mamba_heads, self.mamba_head_dim, self.ssm_groups, self.ssm_state
        inner, channels = self.mamba_inner, self.conv_channels
        projected = _plain_dense(inner + channels + heads, "in_proj")(normed)  # [z | xBC | dt]
        z, xbc, dt = projected[..., :inner], projected[..., inner:inner + channels], projected[..., inner + channels:]
        conv_weight = self.param("conv_weight", _conv_uniform, (self.conv_kernel, channels), jnp.float32)
        conv_bias = self.param("conv_bias", _conv_uniform, (channels,), jnp.float32)
        a = -jnp.exp(self.param("A_log", _initial_a_log, (heads,), jnp.float32))
        dt_bias = self.param("dt_bias", _initial_dt_bias(self.time_step_min, self.time_step_max, self.time_step_floor), (heads,), jnp.float32)
        d = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)  # the family's time_step_limit is (0, inf): no clamp
        parts = lambda t: (t[..., :inner].reshape(t.shape[:-1] + (heads, dim)),
                           t[..., inner:inner + groups * width].reshape(t.shape[:-1] + (groups, width)),
                           t[..., inner + groups * width:].reshape(t.shape[:-1] + (groups, width)))
        if cache is not None and seq == 1:
            # a batched step (`decode_rows_apart`): the leaves are the tuples of the rows' own arrays. The windows (61 KB
            # a row) are joined for one convolution and split again; each row's STATE is stepped where it lies
            apart = isinstance(cache[1], (tuple, list))
            mixed, window = ssm.conv_step(xbc[:, 0], jnp.concatenate(cache[0]) if apart else cache[0], conv_weight, conv_bias)
            if apart:
                x, b, c = parts(mixed)
                steps = [_ssd_step_row(x[row:row + 1], b[row:row + 1], c[row:row + 1], dt[row:row + 1, 0], a, d, cache[1][row])
                         for row in range(len(cache[1]))]
                y, state = zip(*steps)
                y, window = jnp.concatenate(y), tuple(jnp.split(window, len(state)))
            else:  # a session's own step: arrays
                y, state = ssm.ssd_step(*parts(mixed), dt[:, 0], a, d, cache[1])
            y = y[:, None]
        else:  # a chunk: the pool's forward (nothing before it, nothing kept), or a session's prompt chunk
            window, state = self.init_decode_cache(batch, 0) if cache is None else cache
            mixed, window = ssm.conv_chunk(xbc, window, conv_weight, conv_bias, length)
            y, state = ssm.ssd_scan(*parts(mixed), dt, a, d, state, length, chunk=self.chunk_size)
        gated = y.reshape(batch, seq, groups, inner // groups) * jax.nn.silu(z.astype(jnp.float32)).reshape(batch, seq, groups, inner // groups)
        scale = self.param("gate_norm", nn.initializers.ones, (inner,), jnp.float32)
        gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + self.rms_eps)  # over each group's values
        out = _plain_dense(self.hidden_dim, "out_proj")((gated.reshape(batch, seq, inner) * scale).astype(jnp.bfloat16))
        return out, (None if cache is None else (window, state))

    def _attention(self, normed, cache, index):
        from hivemind_tpu.ops.attention import attention_auto

        batch, seq, _hid = normed.shape
        heads, kv_heads, dim = self.num_heads, self.num_kv_heads, self.head_dim
        assert heads % kv_heads == 0, (heads, kv_heads)
        q = _plain_dense(heads * dim, "query")(normed).reshape(batch, seq, heads, dim)
        k = _plain_dense(kv_heads * dim, "key")(normed).reshape(batch, seq, kv_heads, dim)
        v = _plain_dense(kv_heads * dim, "value")(normed).reshape(batch, seq, kv_heads, dim)
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

    def _experts(self, normed, decoding: bool):
        from hivemind_tpu.ops.sparse_experts import route_sigmoid_top_k, routed_mlp_held

        batch, seq, hid = normed.shape
        per_expert = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        held, inner, latent_dim = self.held or self.num_experts, self.expert_inner, self.latent_dim
        router = self.param("router", nn.initializers.lecun_normal(), (hid, self.num_experts), jnp.float32)
        # a checkpoint brings its own; a seeded one is drawn wide enough to change some picks
        bias = self.param("router_bias", nn.initializers.normal(0.1), (self.num_experts,), jnp.float32)
        w_up = self.param("experts_up", per_expert, (held, latent_dim, inner), jnp.float32)
        w_down = self.param("experts_down", per_expert, (held, inner, latent_dim), jnp.float32)
        tokens = normed.reshape(batch * seq, hid)  # the call's rows together
        weights, top_e = route_sigmoid_top_k(tokens, router, bias, self.experts_per_token, self.routed_scale)  # over the FULL hidden
        self.sow(ROUTING_COLLECTION, "expert_choice", top_e.reshape(batch, seq, -1))
        if decoding:  # a decode path's alone, and there only a tapped check fetches them
            self.sow(ATTENDED_COLLECTION, "router_input", normed)
            self.sow(ATTENDED_COLLECTION, "router_choice", top_e.reshape(batch, seq, -1))
        latent = _plain_dense(latent_dim, "latent_down")(normed).reshape(batch * seq, latent_dim)
        with jax.named_scope("moe_experts"):
            routed = routed_mlp_held(latent, weights, top_e, w_up, w_down, self.held_lo, "relu2")
        shared = _plain_dense(hid, "shared_down")(jnp.square(jax.nn.relu(_plain_dense(self.shared_inner, "shared_up")(normed))))
        return _plain_dense(hid, "latent_up")(routed.reshape(batch, seq, latent_dim)) + shared

    @nn.compact
    def __call__(self, x, *session):
        """``x`` alone: the block on a whole sequence (the pool's forward). With a session:
        ``(x, *cache, index[, length])`` -> ``(y, *cache)``, the cache's leaves in the order
        `init_decode_cache` gave them: two for a mixer (which alone takes ``length``), two
        for an attention block, NONE for an expert layer (``(x, index)`` -> ``(y,)``)."""
        assert self.kind in CACHE_KINDS, self.kind
        leaves = 0 if self.kind == EXPERTS else 2
        cache = tuple(session[:leaves]) if session else None
        index = session[leaves] if session else None
        length = session[leaves + 1] if len(session) > leaves + 1 else None
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="norm")(x)
        if self.kind == MAMBA:
            out, cache = self._mamba(normed, cache, length)
        elif self.kind == ATTENTION:
            out, cache = self._attention(normed, cache, index)
        else:
            out = self._experts(normed, decoding=bool(session))
        y = (x + out).astype(jnp.float32)
        return y if cache is None else (y, *cache)
