"""Built-in expert blocks + registry (capability parity: reference
hivemind/moe/server/layers/common.py:18-31 'ffn', transformer encoder block, 'nop';
custom_experts.py:35 register_expert_class)."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

name_to_block: Dict[str, Callable] = {}
name_to_input: Dict[str, Callable] = {}


def register_expert_class(name: str, sample_input: Callable[[int, int], np.ndarray]):
    """Register a flax module factory under ``name``; ``sample_input(batch, hid)``
    builds a schema-defining dummy input."""

    def decorator(factory):
        assert name not in name_to_block, f"expert class {name!r} already registered"
        name_to_block[name] = factory
        name_to_input[name] = sample_input
        return factory

    return decorator


class FeedforwardExpert(nn.Module):
    """hid -> 4*hid -> hid feedforward with layernorm (the reference's benchmark
    'ffn' expert shape)."""

    hidden_dim: int

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.hidden_dim * 4, dtype=jnp.bfloat16, param_dtype=jnp.float32)(x)
        h = jax.nn.gelu(h)
        h = nn.Dense(self.hidden_dim, dtype=jnp.bfloat16, param_dtype=jnp.float32)(h)
        return nn.LayerNorm(dtype=jnp.bfloat16)(x + h).astype(jnp.float32)


class TransformerExpert(nn.Module):
    """One post-norm transformer encoder block operating on [batch, seq, hid]."""

    hidden_dim: int
    num_heads: int = 8

    @nn.compact
    def __call__(self, x):
        from hivemind_tpu.ops.attention import attention_auto

        batch, seq, hid = x.shape
        head_dim = hid // self.num_heads
        dense = lambda n, name: nn.Dense(n, dtype=jnp.bfloat16, param_dtype=jnp.float32, name=name)
        q = dense(hid, "query")(x).reshape(batch, seq, self.num_heads, head_dim)
        k = dense(hid, "key")(x).reshape(batch, seq, self.num_heads, head_dim)
        v = dense(hid, "value")(x).reshape(batch, seq, self.num_heads, head_dim)
        attn = dense(hid, "attention_out")(attention_auto(q, k, v).reshape(batch, seq, hid))
        x = nn.LayerNorm(dtype=jnp.bfloat16)(x + attn)
        h = dense(4 * hid, "ffn_up")(x)
        h = dense(hid, "ffn_down")(jax.nn.gelu(h))
        return nn.LayerNorm(dtype=jnp.bfloat16)(x + h).astype(jnp.float32)


def _each_row_apart(row_step, q, k_new, v_new, cache_k, cache_v, index):
    """A batched step of a block that says `decode_rows_apart`, at its cache step:
    ``cache_k`` / ``cache_v`` are the tuples of the rows' own arrays (``[1, ...]`` each),
    ``q``, ``k_new``, ``v_new`` hold a row a session and ``index`` ``[rows]`` their write
    positions. ``row_step(q, k_new, v_new, cache_k, cache_v, index=)`` is called once a
    row on that row's slices (``index`` ``[1]``) and its own caches as they lie; the rows'
    contexts are stacked (a few KB a row) and the caches go back as tuples, so that no
    cache is joined or split around the step (what is left is the row's new array)."""
    steps = [row_step(q[row:row + 1], k_new[row:row + 1], v_new[row:row + 1], cache_k[row], cache_v[row], index=index[row:row + 1])
             for row in range(len(cache_k))]
    contexts, cache_k, cache_v = zip(*steps)
    return jnp.concatenate(contexts), cache_k, cache_v


def _grouped_cache_step(q, k_new, v_new, cache_k, cache_v, index):
    """One position a row through caches kept ``[rows, kv_heads, slots, dim]``: row
    r writes its key and value at slot ``index[r] mod slots`` and attends over the
    slots written so far, the queries of a KV head grouped against that head's
    cache as it lies (no copy of the cache at query width). With as many slots as
    the session may have positions the cache is the whole past; with ``window``
    slots it is a ring that holds exactly the positions ``index - window < s <=
    index``, so ONE validity rule serves both: slot j is live iff ``j <= index``
    (a ring that has wrapped is live everywhere). ``q`` ``[rows, 1, heads, dim]``,
    ``k_new``, ``v_new`` ``[rows, 1, kv_heads, dim]``, ``index`` ``[rows]``.
    ``cache_k`` / ``cache_v`` as the TUPLES of the rows' own arrays (``[1, ...]`` each:
    a batched step of a block that says `decode_rows_apart`) are stepped row by row
    where they lie (`_each_row_apart` of `_grouped_cache_step_row`, traced once for all
    of them) and go back as tuples. Returns (context ``[rows, 1, heads * dim]``, cache_k, cache_v)."""
    if isinstance(cache_k, (tuple, list)):
        return _each_row_apart(_grouped_cache_step_row, q, k_new, v_new, cache_k, cache_v, index)
    rows, _, heads, dim = q.shape
    kv_heads, slots = cache_k.shape[1], cache_k.shape[2]

    def write(cache, new):  # [rows, kv_heads, slots, dim] <- [rows, 1, kv_heads, dim], row r at slot index[r] mod slots
        new, slot = jnp.swapaxes(new, 1, 2).astype(cache.dtype), index % slots
        if rows == 1:  # nothing to map over: a plain update at one slot, where the vmap below makes a scatter
            return jax.lax.dynamic_update_slice(cache, new, (0, 0, slot[0], 0))
        return jax.vmap(lambda cache, new, slot: jax.lax.dynamic_update_slice(cache, new, (0, slot, 0)))(cache, new, slot)

    cache_k, cache_v = write(cache_k, k_new), write(cache_v, v_new)
    grouped = q.reshape(rows, kv_heads, heads // kv_heads, dim).astype(cache_k.dtype)
    scores = jnp.einsum("rkgd,rksd->rkgs", grouped, cache_k, preferred_element_type=jnp.float32) * dim**-0.5
    live = jnp.arange(slots)[None, :] <= index[:, None]
    scores = jnp.where(live[:, None, None, :], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
    context = jnp.einsum("rkgs,rksd->rkgd", probs, cache_v)
    return context.reshape(rows, 1, heads * dim), cache_k, cache_v


# one row of a batched step, traced ONCE for all the rows, buckets and blocks of one shape (a bucket
# of 32 holds 32 calls of one function, not 32 copies of its text: the programs are jitted per uid
# and bucket, and set-up pays their tracing)
_grouped_cache_step_row = jax.jit(_grouped_cache_step)


def _prefill_into_cache(cache, new, length):
    """A session's first chunk into its cache: ``new`` ``[batch, seq, kv_heads, dim]``
    (right-padded; ``length`` positions are real) into ``cache`` ``[batch, kv_heads,
    slots, dim]``. A cache that holds the chunk takes all of it (the padded tail
    lies past ``index`` and is overwritten by the steps). A ring shorter than the
    chunk takes the last ``slots`` REAL positions, each at its position mod slots:
    slot j gets the largest position p < length with p = j (mod slots)."""
    seq, slots = new.shape[1], cache.shape[2]
    new = jnp.swapaxes(new, 1, 2).astype(cache.dtype)
    if seq <= slots:
        return jax.lax.dynamic_update_slice(cache, new, (0, 0, 0, 0))
    slot = jnp.arange(slots)
    position = slot + slots * ((length - 1 - slot) // slots)  # negative where no real position lands on the slot
    taken = jnp.take(new, jnp.clip(position, 0, seq - 1), axis=2)
    return jnp.where((position >= 0)[None, None, :, None], taken, cache)


def _empty_kv_cache(batch: int, slots: int, kv_heads: int, head_dim: int):
    """(cache_k, cache_v) of the blocks of this file: bf16, one head's slots together
    (``[batch, kv_heads, slots, head_dim]``), so that a step attends a KV head's queries
    over that head's cache as it lies (`_grouped_cache_step`)."""
    shape = (batch, kv_heads, slots, head_dim)
    return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)


def _cache_attention(q, k_new, v_new, cache_k, cache_v, index):
    """The cache half of a decoder block that keeps every position it has seen
    (`causal_transformer` and the Llama family): caches ``[batch, kv_heads, max_len,
    dim]``, ``q`` ``[batch, seq, heads, dim]``, ``k_new``, ``v_new`` ``[batch, seq,
    kv_heads, dim]``; how many queries a KV head serves is read from those shapes.
    Valid for the two session shapes. One position (``index`` a scalar, or a vector
    of the rows' own positions beside their caches as tuples) is a
    `_grouped_cache_step`. A chunk is the session's prefill (``index == 0``: the
    cache holds nothing before it): it is written with `_prefill_into_cache`, its
    padded tail where the steps will overwrite it, and plain causal attention within
    the chunk is exact. Returns (context ``[batch, seq, heads * dim]``,
    cache_k, cache_v)."""
    batch, seq, heads, dim = q.shape
    if seq == 1:
        rows = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (batch,))
        return _grouped_cache_step(q, k_new, v_new, cache_k, cache_v, rows)
    from hivemind_tpu.ops.attention import plain_attention

    cache_k, cache_v = _prefill_into_cache(cache_k, k_new, seq), _prefill_into_cache(cache_v, v_new, seq)
    groups = heads // k_new.shape[2]
    expand = (lambda t: jnp.repeat(t, groups, axis=2)) if groups > 1 else (lambda t: t)
    context = plain_attention(q, expand(k_new), expand(v_new), causal=True)
    return context.reshape(batch, seq, heads * dim), cache_k, cache_v


class CausalTransformerExpert(nn.Module):
    """One pre-norm DECODER block on [batch, seq, hid]: causal attention + gelu ffn.
    The building block for pipelined autoregressive models over the swarm
    (RemoteSequential): causality means right-padded prefixes are exact — real
    positions never attend to the padding after them — so clients can decode with
    a fixed schema sequence length and read the logits at the true last position.

    Decode sessions: calling with ``(cache_k, cache_v, index)`` runs one KV-cache
    step — O(seq) per token instead of the O(seq²) right-padded recompute — and
    returns ``(y, cache_k, cache_v)``; see ``moe/server/decode_session.py``."""

    hidden_dim: int
    num_heads: int = 8

    # a batched step writes one position of a cache of ``max_len`` slots and reads the rest
    # where it lies: the rows' caches come as tuples, unjoined (`_grouped_cache_step`)
    decode_rows_apart = True

    def init_decode_cache(self, batch: int, max_len: int):
        return _empty_kv_cache(batch, max_len, self.num_heads, self.hidden_dim // self.num_heads)

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None):
        from hivemind_tpu.ops.attention import attention_auto

        batch, seq, hid = x.shape
        head_dim = hid // self.num_heads
        dense = lambda n, name: nn.Dense(n, dtype=jnp.bfloat16, param_dtype=jnp.float32, name=name)
        normed = nn.LayerNorm(dtype=jnp.bfloat16, name="attention_norm")(x)
        q = dense(hid, "query")(normed).reshape(batch, seq, self.num_heads, head_dim)
        k = dense(hid, "key")(normed).reshape(batch, seq, self.num_heads, head_dim)
        v = dense(hid, "value")(normed).reshape(batch, seq, self.num_heads, head_dim)
        if cache_k is None:
            attn = attention_auto(q, k, v, causal=True).reshape(batch, seq, hid)
        else:
            attn, cache_k, cache_v = _cache_attention(q, k, v, cache_k, cache_v, index)
        x = x + dense(hid, "attention_out")(attn)
        normed = nn.LayerNorm(dtype=jnp.bfloat16, name="ffn_norm")(x)
        h = dense(4 * hid, "ffn_up")(normed)
        y = (x + dense(hid, "ffn_down")(jax.nn.gelu(h))).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: jax.Array, theta: float = 10000.0, offset=0) -> jax.Array:
    """Rotary position embedding over [batch, seq, heads, head_dim] (head_dim even).
    ``offset`` (may be traced) shifts positions — decode sessions rotate the new
    token at its absolute position in the sequence; a vector of offsets gives each
    row of the batch its own (the rows of a batched decode step)."""
    seq, dim = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    positions = jnp.asarray(offset, jnp.float32)[..., None] + jnp.arange(seq, dtype=jnp.float32)
    angles = positions[..., None] * freqs
    angles = jnp.concatenate([angles, angles], axis=-1)  # [seq, dim] or [batch, seq, dim]
    cos = jnp.cos(angles)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., :, None, :].astype(x.dtype)
    return x * cos + _rotate_half(x) * sin


def _plain_dense(features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32, name=name)


def _rope_attention_half(x, cache_k, cache_v, index, *, heads: int, kv_heads: int, rope_theta: float,
                         rms_eps: float, mesh=None, qk_norm: bool = False, head_dim: int = 0, out_norm: bool = False):
    """The attention half of the Llama-family blocks, called inside a block's
    compact ``__call__`` (its submodules become the block's): pre-RMSNorm, q / k / v
    projections without bias, optional RMS norms over the whole projected query and
    key widths (``qk_norm``: OLMoE), rotary embedding, causal attention with
    grouped KV heads (over the decode cache when one is given), output projection,
    an RMS norm on that projection's output where the family has one (``out_norm``: the
    sandwich norm of `ouro_block`), residual. The head size is hidden / heads; a ``head_dim`` that says otherwise
    (a checkpoint whose heads are not hidden / heads wide) fails here, loudly,
    and is not served at another shape. Returns (x + attention, cache_k, cache_v)."""
    from hivemind_tpu.parallel.ring_attention import mesh_attention_core

    batch, seq, hid = x.shape
    assert heads % kv_heads == 0, (heads, kv_heads)
    assert hid % heads == 0 and head_dim in (0, hid // heads), (
        f"head size {head_dim or 'hidden / heads'} with hidden {hid} and {heads} heads: "
        f"these blocks serve heads of hidden / heads only"
    )
    head_dim = hid // heads
    normed = nn.RMSNorm(epsilon=rms_eps, dtype=jnp.bfloat16, name="attention_norm")(x)
    q = _plain_dense(heads * head_dim, "query")(normed)
    k = _plain_dense(kv_heads * head_dim, "key")(normed)
    v = _plain_dense(kv_heads * head_dim, "value")(normed).reshape(batch, seq, kv_heads, head_dim)
    if qk_norm:
        q = nn.RMSNorm(epsilon=rms_eps, dtype=jnp.bfloat16, name="query_norm")(q)
        k = nn.RMSNorm(epsilon=rms_eps, dtype=jnp.bfloat16, name="key_norm")(k)
    q = q.reshape(batch, seq, heads, head_dim)
    k = k.reshape(batch, seq, kv_heads, head_dim)
    offset = 0 if cache_k is None else index  # decode: rotate at absolute position
    q = apply_rope(q, rope_theta, offset)
    k = apply_rope(k, rope_theta, offset)
    if cache_k is None:
        if kv_heads != heads:  # grouped-query: each KV head serves heads/kv_heads queries
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
        attn = mesh_attention_core(mesh, q, k, v, causal=True).reshape(batch, seq, hid)
    else:
        attn, cache_k, cache_v = _cache_attention(q, k, v, cache_k, cache_v, index)
    out = _plain_dense(hid, "attention_out")(attn)
    if out_norm:
        out = nn.RMSNorm(epsilon=rms_eps, dtype=jnp.bfloat16, name="attention_out_norm")(out)
    return x + out, cache_k, cache_v


class LlamaBlockExpert(nn.Module):
    """One Llama-family decoder block on [batch, seq, hid]: pre-RMSNorm, rotary
    position embeddings, causal attention with optional grouped-query KV heads, and
    a SwiGLU MLP. This is the block shape Petals serves for Llama models (the
    BASELINE 'Petals-style Llama-7B block server' config): stack N of these under
    ``RemoteSequential`` and decoding is exact with right-padded fixed schemas, same
    as ``CausalTransformerExpert``. RoPE makes positions intrinsic to the block, so
    the client does not ship position ids."""

    hidden_dim: int
    num_heads: int = 8
    num_kv_heads: int = 0  # 0 = multi-head (Llama-7B); set lower for GQA (Llama-70B style)
    rope_theta: float = 10000.0
    ffn_inner: int = 0  # 0 = the 8/3 rule below; real checkpoints set intermediate_size
    rms_eps: float = 1e-6  # real checkpoints set rms_norm_eps (Llama-2: 1e-5)
    head_dim: int = 0  # 0 = hidden_dim // num_heads; anything else must equal it (asserted)
    # set when the block is served sharded over a device mesh (MeshModuleBackend):
    # the fused attention kernel must then run per shard (mesh_attention_core)
    mesh: Optional[Any] = None

    decode_rows_apart = True  # caches of ``max_len`` slots: a batched step takes the rows' own arrays (`_grouped_cache_step`)

    def init_decode_cache(self, batch: int, max_len: int):
        return _empty_kv_cache(batch, max_len, self.num_kv_heads or self.num_heads, self.hidden_dim // self.num_heads)

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None):
        hid = x.shape[-1]
        x, cache_k, cache_v = _rope_attention_half(
            x, cache_k, cache_v, index, heads=self.num_heads, kv_heads=self.num_kv_heads or self.num_heads,
            rope_theta=self.rope_theta, rms_eps=self.rms_eps, mesh=self.mesh, head_dim=self.head_dim,
        )
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
        inner = self.ffn_inner or -(-8 * hid // 3 // 8) * 8  # 8/3*hid rounded up to 8
        gate = _plain_dense(inner, "ffn_gate")(normed)
        up = _plain_dense(inner, "ffn_up")(normed)
        y = (x + _plain_dense(hid, "ffn_down")(jax.nn.silu(gate) * up)).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)


# the collection a block sows its routing into (flax `sow`): one [batch, seq, k]
# int32 leaf of chosen experts per expert-layer call. The serving paths apply
# every block with this collection mutable and count the live rows on the host
# (`moe/server/routing_stats.py`); a block without experts sows nothing.
ROUTING_COLLECTION = "routing"
# the collection a block whose steps attend a SELECTION of what they cached sows into,
# by name: ``attended``, one ``[2, batch, seq]`` int32 leaf a call: the positions each
# query attended (all it had seen, where it attended densely) and the positions it had
# seen, its own included, which the decode paths count for the live rows onto
# `hivemind_moe_sparse_positions_*_total`; ``chosen``, ``[batch, seq, kv_heads, k]`` int32:
# the blocks each query selected (-1: none), which stay on the device unless a check
# against a reference asks for them (`routing_stats.SELECTION_TAPS`).
ATTENDED_COLLECTION = "attended"


class OlmoeBlockExpert(nn.Module):
    """One OLMoE decoder block on [batch, seq, hid] (Muennighoff et al. 2024, HF
    `OlmoeDecoderLayer`): `LlamaBlockExpert`'s attention half (pre-RMSNorm, rotary
    embedding, causal attention, the same `(cache_k, cache_v)` decode cache) with
    RMS norms over the whole projected query and key widths before the split into
    heads, then a sparse expert layer in place of the MLP: float32 router over
    ``num_experts`` SwiGLU experts of width ``expert_inner``, the
    ``experts_per_token`` largest router probabilities used as they are (not
    renormalised), no shared expert, no biases.

    The expert layer sees every token of the call together (`ops/sparse_experts`),
    so in a batched decode step each chosen expert's weights are read once for all
    the sessions' rows. The chosen experts are sown into `ROUTING_COLLECTION`.

    ``head_dim`` is ``hidden_dim // num_heads``, as in `LlamaBlockExpert`; OLMoE's
    2048 / 16 = 128 is its published head size. ``head_dim``, where given, must say
    the same (asserted in `_rope_attention_half`)."""

    hidden_dim: int
    num_heads: int = 16
    num_kv_heads: int = 0  # 0 = as many as query heads (OLMoE-1B-7B)
    num_experts: int = 64
    experts_per_token: int = 8
    expert_inner: int = 1024  # one expert's width (OLMoE's `intermediate_size`)
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    head_dim: int = 0  # 0 = hidden_dim // num_heads; anything else must equal it

    decode_rows_apart = True  # as `LlamaBlockExpert`

    def init_decode_cache(self, batch: int, max_len: int):
        return _empty_kv_cache(batch, max_len, self.num_kv_heads or self.num_heads, self.hidden_dim // self.num_heads)

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None):
        from hivemind_tpu.ops.sparse_experts import route_top_k, routed_swiglu

        batch, seq, hid = x.shape
        x, cache_k, cache_v = _rope_attention_half(
            x, cache_k, cache_v, index, heads=self.num_heads, kv_heads=self.num_kv_heads or self.num_heads,
            rope_theta=self.rope_theta, rms_eps=self.rms_eps, qk_norm=True, head_dim=self.head_dim,
        )
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
        per_expert = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        experts, inner = self.num_experts, self.expert_inner
        router = self.param("router", nn.initializers.lecun_normal(), (hid, experts), jnp.float32)
        w_gate = self.param("experts_gate", per_expert, (experts, hid, inner), jnp.float32)
        w_up = self.param("experts_up", per_expert, (experts, hid, inner), jnp.float32)
        w_down = self.param("experts_down", per_expert, (experts, inner, hid), jnp.float32)
        tokens = normed.reshape(batch * seq, hid)  # the call's rows together
        top_p, top_e = route_top_k(tokens, router, self.experts_per_token)
        self.sow(ROUTING_COLLECTION, "expert_choice", top_e.reshape(batch, seq, -1))
        with jax.named_scope("moe_experts"):
            routed = routed_swiglu(tokens, top_p, top_e, w_gate, w_up, w_down)
        y = (x + routed.reshape(batch, seq, hid)).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)


def _banded_attention(q, k, v, window: int):
    """Causal attention in which position t sees the positions s with
    ``t - window < s <= t``, on a whole chunk: ``q`` ``[batch, seq, heads, dim]``,
    ``k``, ``v`` ``[batch, seq, kv_heads, dim]`` (each KV head serves ``heads /
    kv_heads`` consecutive query heads, not repeated). The chunk is cut into blocks
    of ``window`` queries, each held against its own and the previous block's keys:
    O(seq * 2 window) scores, in float32, where the masked square takes O(seq^2)."""
    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[2]
    blocks = -(-seq // window)
    pad = ((0, 0), (0, blocks * window - seq), (0, 0), (0, 0))
    q = jnp.pad(q, pad).reshape(batch, blocks, window, kv_heads, heads // kv_heads, dim)

    def behind_previous(t):  # [batch, blocks, 2 window, kv_heads, dim]
        t = jnp.pad(t, pad).reshape(batch, blocks, window, kv_heads, dim)
        previous = jnp.pad(t, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
        return jnp.concatenate([previous, t], axis=2)

    keys, values = behind_previous(k), behind_previous(v)
    scores = jnp.einsum("bcqkgd,bcskd->bckgqs", q, keys, preferred_element_type=jnp.float32) * dim**-0.5
    # query a of a block is position c*window + a, key b of its pair of blocks (c-1)*window + b
    a, b = jnp.arange(window)[:, None], jnp.arange(2 * window)[None, :]
    seen = (b > a) & (b <= a + window)
    seen = seen[None] & ((b >= window)[None] | (jnp.arange(blocks) > 0)[:, None, None])  # no block before the first
    scores = jnp.where(seen[None, :, None, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    context = jnp.einsum("bckgqs,bcskd->bcqkgd", probs, values)
    return context.reshape(batch, blocks * window, heads, dim)[:, :seq]


class ExaoneMoeBlockExpert(nn.Module):
    """One K-EXAONE decoder block on [batch, seq, hid] (`model_type: exaone_moe`,
    LG AI Research 2026; the equations are in `perf/reference/k_exaone_block.py`):
    pre-RMSNorm; q / k / v without bias at a head size that is GIVEN (``num_heads *
    head_dim`` need not be the hidden size); an RMS norm over each head's values of
    q and k (one learned scale of ``head_dim`` each); causal softmax attention, each
    KV head serving ``num_heads / num_kv_heads`` consecutive query heads; then a
    SwiGLU MLP or a sparse expert layer. What kind of block it is follows from its
    own sizes:

    - ``window`` > 0: a sliding-window block. Rotary embedding (rotate-half) on q and
      k, position t attends ``t - window < s <= t``, and a decode session keeps a RING
      of ``window`` slots. ``window`` = 0: a full-attention block, causal over all
      positions, NO rotary embedding (the family's convention for its global
      layers), a cache of ``max_len`` slots.
    - ``ffn_inner`` > 0: a dense SwiGLU MLP of that width (the leading dense
      blocks). ``ffn_inner`` = 0: the sparse layer: sigmoid router over
      ``num_experts`` with a per-expert selection bias that picks and does not weigh,
      the ``experts_per_token`` picked scores renormalised and scaled by
      ``routed_scale`` (`ops.sparse_experts.route_sigmoid_top_k`), one shared SwiGLU
      expert of width ``expert_inner`` for every token, and the routed experts
      ``[held_lo, held_lo + held)`` of width ``expert_inner`` that THIS server holds
      (``held`` = 0: all of them). The router keeps its ``num_experts`` outputs; a
      pair routed to an expert held elsewhere adds nothing here
      (`routed_swiglu_held`), as under expert parallelism before the exchange.

    Decode caches are kept ``[batch, kv_heads, slots, head_dim]`` in bf16, so that a
    step attends per KV head over the cache as it lies. The chosen experts (over
    all ``num_experts``) are sown into `ROUTING_COLLECTION`; `held_experts` tells the
    serving paths which of them were computed here."""

    hidden_dim: int
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    window: int = 0
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    ffn_inner: int = 0
    num_experts: int = 128
    experts_per_token: int = 8
    expert_inner: int = 2048
    held_lo: int = 0
    held: int = 0
    routed_scale: float = 2.5

    # a prefill chunk comes right-padded to a power of two: `DecodeSessionManager`
    # hands a block that says so the number of real positions (``length``), which a
    # ring needs to keep the padding out
    decode_takes_length = True

    @property
    def decode_cache_kind(self) -> str:
        """Names the block's decode programs and its caches in the telemetry."""
        return "window" if self.window else "full"

    @property
    def decode_rows_apart(self) -> bool:
        """Whether a batched step takes each cache as the tuple of the rows' own arrays: a
        full-attention block's ``max_len`` slots (33.5 MB a session at 4,096), of which a
        step writes one and reads the rest where it lies. A ring of ``window`` slots
        (0.5 MB) stays joined: the join costs less than an operation a row."""
        return not self.window

    @property
    def held_experts(self):
        """``(lo, hi)`` of the routed experts computed here; None where all are, or none exist."""
        if self.ffn_inner or self.held in (0, self.num_experts):
            return None
        return self.held_lo, self.held_lo + self.held

    def init_decode_cache(self, batch: int, max_len: int):
        return _empty_kv_cache(batch, self.window or max_len, self.num_kv_heads, self.head_dim)

    def _attention_half(self, x, cache_k, cache_v, index, length):
        from hivemind_tpu.ops.attention import attention_auto

        batch, seq, _hid = x.shape
        heads, kv_heads, dim = self.num_heads, self.num_kv_heads, self.head_dim
        assert heads % kv_heads == 0, (heads, kv_heads)
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="attention_norm")(x)
        q = _plain_dense(heads * dim, "query")(normed).reshape(batch, seq, heads, dim)
        k = _plain_dense(kv_heads * dim, "key")(normed).reshape(batch, seq, kv_heads, dim)
        v = _plain_dense(kv_heads * dim, "value")(normed).reshape(batch, seq, kv_heads, dim)
        q = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="query_norm")(q)  # over each head's values
        k = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="key_norm")(k)
        if self.window:
            offset = 0 if cache_k is None else index  # decode: rotate at the absolute position
            q, k = apply_rope(q, self.rope_theta, offset), apply_rope(k, self.rope_theta, offset)
        if cache_k is not None and seq == 1:
            rows = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (batch,))
            context, cache_k, cache_v = _grouped_cache_step(q, k, v, cache_k, cache_v, rows)
        else:
            # a whole chunk: the pool's forward, or a session's prefill (it starts the
            # session: the cache holds nothing before it)
            if cache_k is not None:
                length = seq if length is None else length
                cache_k, cache_v = _prefill_into_cache(cache_k, k, length), _prefill_into_cache(cache_v, v, length)
            if self.window:
                context = _banded_attention(q, k, v, self.window)
            else:
                repeat = lambda t: jnp.repeat(t, heads // kv_heads, axis=2)
                context = attention_auto(q, repeat(k), repeat(v), causal=True)
            context = context.reshape(batch, seq, heads * dim)
        return x + _plain_dense(self.hidden_dim, "attention_out")(context), cache_k, cache_v

    @nn.compact
    def __call__(self, x, cache_k=None, cache_v=None, index=None, length=None):
        from hivemind_tpu.ops.sparse_experts import route_sigmoid_top_k, routed_swiglu_held

        batch, seq, hid = x.shape
        x, cache_k, cache_v = self._attention_half(x, cache_k, cache_v, index, length)
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
        swiglu = lambda prefix, width: _plain_dense(hid, prefix + "_down")(
            jax.nn.silu(_plain_dense(width, prefix + "_gate")(normed)) * _plain_dense(width, prefix + "_up")(normed))
        if self.ffn_inner:
            y = (x + swiglu("ffn", self.ffn_inner)).astype(jnp.float32)
            return y if cache_k is None else (y, cache_k, cache_v)
        per_expert = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        held, inner = self.held or self.num_experts, self.expert_inner
        router = self.param("router", nn.initializers.lecun_normal(), (hid, self.num_experts), jnp.float32)
        # a checkpoint brings its own; a seeded one is drawn wide enough to change some picks
        bias = self.param("router_bias", nn.initializers.normal(0.1), (self.num_experts,), jnp.float32)
        w_gate = self.param("experts_gate", per_expert, (held, hid, inner), jnp.float32)
        w_up = self.param("experts_up", per_expert, (held, hid, inner), jnp.float32)
        w_down = self.param("experts_down", per_expert, (held, inner, hid), jnp.float32)
        tokens = normed.reshape(batch * seq, hid)  # the call's rows together
        weights, top_e = route_sigmoid_top_k(tokens, router, bias, self.experts_per_token, self.routed_scale)
        self.sow(ROUTING_COLLECTION, "expert_choice", top_e.reshape(batch, seq, -1))
        with jax.named_scope("moe_experts"):
            routed = routed_swiglu_held(tokens, weights, top_e, w_gate, w_up, w_down, self.held_lo)
        y = (x + swiglu("shared", inner) + routed.reshape(batch, seq, hid)).astype(jnp.float32)
        return y if cache_k is None else (y, cache_k, cache_v)


class NopExpert(nn.Module):
    """Identity with a dummy parameter (reference 'nop' expert for transport tests)."""

    hidden_dim: int

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, ())
        return x * scale


register_expert_class("ffn", lambda batch, hid: np.zeros((batch, hid), np.float32))(FeedforwardExpert)
register_expert_class("transformer", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(TransformerExpert)
register_expert_class("causal_transformer", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(CausalTransformerExpert)
register_expert_class("llama_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(LlamaBlockExpert)
register_expert_class("olmoe_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(OlmoeBlockExpert)
register_expert_class("exaone_moe_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))(ExaoneMoeBlockExpert)
register_expert_class("nop", lambda batch, hid: np.zeros((batch, hid), np.float32))(NopExpert)


@register_expert_class("minicpm_sala_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))
def _minicpm_sala_block(hidden_dim: int, **kwargs):
    """`layers/minicpm_sala.py`'s block, loaded when one is built: a process that only
    reads the registry (a trainer) imports nothing of it."""
    from hivemind_tpu.moe.server.layers.minicpm_sala import MiniCPMSalaBlockExpert

    return MiniCPMSalaBlockExpert(hidden_dim, **kwargs)


@register_expert_class("deepseek_v3_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))
def _deepseek_v3_block(hidden_dim: int, **kwargs):
    """`layers/deepseek_v3.py`'s block (multi-head latent attention, then a dense MLP or a
    group-limited sparse expert layer), loaded when one is built, as `minicpm_sala_block` is."""
    from hivemind_tpu.moe.server.layers.deepseek_v3 import DeepseekV3BlockExpert

    return DeepseekV3BlockExpert(hidden_dim, **kwargs)


@register_expert_class("nemotron_h_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))
def _nemotron_h_block(hidden_dim: int, **kwargs):
    """`layers/nemotron_h.py`'s block (ONE residual a block: a Mamba-2 mixer, a grouped-query attention
    without position embedding, or a LatentMoE layer that keeps no cache, by its ``kind``), loaded when
    one is built, as `minicpm_sala_block` is."""
    from hivemind_tpu.moe.server.layers.nemotron_h import NemotronHBlockExpert

    return NemotronHBlockExpert(hidden_dim, **kwargs)


@register_expert_class("ouro_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))
def _ouro_block(hidden_dim: int, **kwargs):
    """`layers/ouro.py`'s block (a looped model's: the Llama family's attention and SwiGLU between sandwich
    norms, its decode sessions holding a cache pair for each of ``total_ut_steps`` passes), loaded when one is
    built, as `minicpm_sala_block` is."""
    from hivemind_tpu.moe.server.layers.ouro import OuroBlockExpert

    return OuroBlockExpert(hidden_dim, **kwargs)


@register_expert_class("granite_h_block", lambda batch, hid: np.zeros((batch, 64, hid), np.float32))
def _granite_h_block(hidden_dim: int, **kwargs):
    """`layers/granite_h.py`'s block (a Mamba-2 mixer or a grouped-query attention without position embedding, by
    its ``kind``, AND a gated MLP, under two residuals scaled by ``residual_multiplier``; the mixer's body is
    `nemotron_h_block`'s), loaded when one is built, as `minicpm_sala_block` is."""
    from hivemind_tpu.moe.server.layers.granite_h import GraniteHBlockExpert

    return GraniteHBlockExpert(hidden_dim, **kwargs)
