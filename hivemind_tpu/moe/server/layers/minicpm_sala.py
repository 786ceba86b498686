"""MiniCPM-SALA decoder blocks (`model_type: minicpm_sala`, openbmb 2026; the equations
and every assumption are in `perf/reference/minicpm_sala_block.py`): one class, two
mixers, chosen per block by ``mixer`` as the model's `mixer_types` names them.

- ``"lightning-attn"``: linear attention with a per-head decay. A decode session keeps
  ONE array, the recurrent state ``[batch, heads, head_dim, head_dim]`` float32, which a
  step UPDATES (constant in the session's length); `decode_cache_kind` is ``lightning``.
- ``"minicpm4"``: InfLLM-V2 block-sparse attention. A session keeps THREE arrays that it
  appends to: keys and values ``[batch, kv_heads, max_len, head_dim]`` bf16 and the
  compressed keys ``[batch, kv_heads, max_len / kernel_stride, head_dim]`` bf16 that the
  selection reads (a kernel's mean is written when its last position arrives);
  `decode_cache_kind` is ``sparse``. While a session holds fewer than ``dense_len``
  positions a step attends all of them; from there on it reads the compressed keys,
  selects ``topk`` blocks and gathers them. In a batched step it takes the rows' caches
  APART (`decode_rows_apart`: each leaf the tuple of the rows' own arrays): a row's key
  and value are written into, and its chosen blocks gathered from, its own arrays, so
  the whole caches of a batch (a GB at 32 rows of 32,768 slots) are never joined.
  Beside the positions each query attended (`ATTENDED_COLLECTION` ``attended``) it sows
  the blocks each query selected (``chosen``: ``[batch, seq, kv_heads, topk]`` int32, -1
  for none: a query in the dense mode, a block that does not exist), from a chunk and
  from a step alike: what a check against a reference compares.

Both take a session's prompt in CHUNKS (``decode_takes_chunks``): a chunk of more than
one position may continue a session, the lightning state carries over by construction
and the sparse block attends the chunk against its cache; a chunk comes right-padded to
a power of two with its real positions as ``length`` (``decode_takes_length``).

The mixers' own code (`ops/linear_attention.py`, `ops/block_sparse_attention.py`) is
imported when a block is first applied."""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from hivemind_tpu.moe.server.layers.common import ATTENDED_COLLECTION, _plain_dense, apply_rope

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


class MiniCPMSalaBlockExpert(nn.Module):
    hidden_dim: int
    mixer: str = LIGHTNING
    num_heads: int = 32
    num_kv_heads: int = 2  # the sparse mixer's; a lightning block has as many key-value heads as heads
    head_dim: int = 128
    ffn_inner: int = 16384
    rope_theta: float = 10000.0  # the lightning mixer's (`lightning_use_rope`); the sparse mixer has none (`attn_use_rope: false`)
    rms_eps: float = 1e-6
    residual_scale: float = 1.4 / math.sqrt(32)  # scale_depth / sqrt(the PUBLISHED num_hidden_layers)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    decode_takes_length = True
    decode_takes_chunks = True

    @property
    def decode_cache_kind(self) -> str:
        """Names the block's decode programs and its caches in the telemetry."""
        return "lightning" if self.mixer == LIGHTNING else "sparse"

    @property
    def decode_rows_apart(self) -> bool:
        """Whether a batched step takes each cache leaf as the tuple of the rows' own arrays."""
        return self.mixer == SPARSE

    @property
    def sparse_config(self):
        from hivemind_tpu.ops.block_sparse_attention import SparseConfig

        return SparseConfig(self.kernel_size, self.kernel_stride, self.block_size, self.topk, self.init_blocks,
                            self.window_size, self.dense_len)

    def init_decode_cache(self, batch: int, max_len: int):
        """The session's cache as a TREE (a tuple of arrays, batch axis first)."""
        assert self.mixer in (LIGHTNING, SPARSE), self.mixer
        if self.mixer == LIGHTNING:
            return (jnp.zeros((batch, self.num_heads, self.head_dim, self.head_dim), jnp.float32),)
        self.sparse_config.check(max_len)
        empty = lambda slots: jnp.zeros((batch, self.num_kv_heads, slots, self.head_dim), jnp.bfloat16)
        return empty(max_len), empty(max_len), empty(max_len // self.kernel_stride)  # three arrays: a step donates each

    def _heads(self, normed, kv_heads: int):
        batch, seq, _hid = normed.shape
        heads, dim = self.num_heads, self.head_dim
        q = _plain_dense(heads * dim, "query")(normed).reshape(batch, seq, heads, dim)
        k = _plain_dense(kv_heads * dim, "key")(normed).reshape(batch, seq, kv_heads, dim)
        v = _plain_dense(kv_heads * dim, "value")(normed).reshape(batch, seq, kv_heads, dim)
        q = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="query_norm")(q)  # over each head's values
        k = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="key_norm")(k)
        return q, k, v

    def _lightning(self, normed, cache, index, length):
        from hivemind_tpu.ops.linear_attention import lightning_log_decay, lightning_scan, lightning_step

        batch, seq, _hid = normed.shape
        q, k, v = self._heads(normed, self.num_heads)
        offset = 0 if index is None else index  # rotate at the absolute position
        q, k = apply_rope(q, self.rope_theta, offset), apply_rope(k, self.rope_theta, offset)
        log_decay = lightning_log_decay(self.num_heads)
        if cache is not None and seq == 1:
            o, state = lightning_step(q[:, 0], k[:, 0], v[:, 0], cache[0], log_decay)
            o = o[:, None]
        else:  # a chunk: the pool's forward (no state before it, none kept), or a session's prompt chunk
            state = jnp.zeros((batch, self.num_heads, self.head_dim, self.head_dim), jnp.float32) if cache is None else cache[0]
            o, state = lightning_scan(q, k, v, state, log_decay, length)
        o = o.reshape(batch, seq, self.num_heads * self.head_dim)
        o = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="output_norm")(o)  # over the whole width
        return o, (None if cache is None else (state,))

    def _sparse(self, normed, cache, index, length):
        from hivemind_tpu.ops import block_sparse_attention as sparse_ops

        batch, seq, _hid = normed.shape
        heads, kv_heads, dim, config = self.num_heads, self.num_kv_heads, self.head_dim, self.sparse_config
        assert heads % kv_heads == 0, (heads, kv_heads)
        q, k, v = self._heads(normed, kv_heads)
        grouped = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)
        if cache is not None and seq == 1:
            rows = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (batch,))
            context, attended, chosen, cache = self._sparse_step(sparse_ops, config, grouped[:, 0], k, v, cache, rows)
            context, chosen, seen = context[:, None], chosen[:, None], rows + 1
        else:
            kept = cache
            if cache is None:  # the pool's forward: the chunk is all there is, in a cache of its own that is not kept
                slots = -(-seq // config.block_size) * config.block_size
                cache, index = self.init_decode_cache(batch, slots), 0
            length = seq if length is None else length
            reach = config.kernel_size // config.kernel_stride
            first = jnp.maximum(index // config.kernel_stride - reach + 1, 0)  # the first kernel the chunk's positions touch

            def one_row(grouped, k, v, cache_k, cache_v, compressed):
                write = lambda cache, new: jax.lax.dynamic_update_slice(cache, jnp.swapaxes(new, 0, 1).astype(cache.dtype), (0, index, 0))
                cache_k, cache_v = write(cache_k, k), write(cache_v, v)
                compressed = sparse_ops.write_compressed(compressed, cache_k, first, -(-seq // config.kernel_stride) + reach + 1, config)
                context, attended, chosen = sparse_ops.sparse_prefill(grouped, cache_k, cache_v, compressed, index, config)
                return context, attended, chosen, (cache_k, cache_v, compressed)

            context, attended, chosen, cache = jax.vmap(one_row)(grouped, k, v, *cache)
            real = jnp.arange(seq)[None, :] < length
            attended, seen = jnp.where(real, attended, 0), jnp.where(real, index + 1 + jnp.arange(seq)[None, :], 0)
            cache = None if kept is None else cache
        # [2, batch, seq]: the positions each query attended, and those it had seen (its own included)
        self.sow(ATTENDED_COLLECTION, "attended", jnp.stack([jnp.broadcast_to(t, attended.shape).astype(jnp.int32).reshape(batch, seq)
                                                              for t in (attended, seen)]))
        self.sow(ATTENDED_COLLECTION, "chosen", chosen)  # [batch, seq, kv_heads, topk]
        return context.reshape(batch, seq, heads * dim), cache

    def _sparse_step(self, sparse_ops, config, grouped, k, v, cache, rows):
        """One position a row, each row on its OWN arrays: ``cache``'s leaves are tuples of
        the rows' arrays ``[1, ...]`` (a batched step, `decode_rows_apart`) or arrays whose
        rows are taken apart here (a session's own step: one row, nothing moves). Write the
        key, the value and the kernel that the position completes; then each row in its own
        mode. The dense mode reads the caches' first ``dense_len`` slots, so it runs only
        when some row of the step is in it (`lax.cond`), and it only READS
        (`ops.block_sparse_attention.dense_attend`, the chunk path's own): a conditional
        that also wrote the caches, as `common._grouped_cache_step` does, would copy them
        into the branch whether it runs or not. Returns (context, positions attended,
        blocks chosen ``[rows, kv_heads, topk]`` with -1 for none, the new leaves in the form
        they came in)."""
        count = rows.shape[0]
        apart = isinstance(cache[0], (tuple, list))
        own = cache if apart else tuple(tuple(leaf[row:row + 1] for row in range(count)) for leaf in cache)
        write = lambda cache, new, slot: jax.lax.dynamic_update_slice(cache, jnp.swapaxes(new, 1, 2).astype(cache.dtype), (0, 0, slot, 0))
        cache_k = tuple(write(own[0][row], k[row:row + 1], rows[row]) for row in range(count))
        cache_v = tuple(write(own[1][row], v[row:row + 1], rows[row]) for row in range(count))
        # position t completes the kernel that ends at it: (t + 1 - kernel_size) / stride, when that is a whole number >= 0
        done, rest = jnp.divmod(rows + 1 - config.kernel_size, config.kernel_stride)
        due = (rest == 0) & (done >= 0)
        compressed = tuple(sparse_ops.write_kernel(own[2][row][0], cache_k[row][0], done[row], due[row], config)[None] for row in range(count))
        dense = rows + 1 < config.dense_len
        extent = min(config.dense_len, cache_k[0].shape[2])
        one_dense = lambda q, cache_k, cache_v, position: sparse_ops.dense_attend(q[None], cache_k, cache_v, position[None], extent)[0]
        first = lambda caches: jnp.concatenate([cache[:, :, :extent] for cache in caches])
        in_dense = jax.lax.cond(jnp.any(dense), lambda: jax.vmap(one_dense)(grouped, first(cache_k), first(cache_v), rows),
                                lambda: jnp.zeros(grouped.shape, cache_v[0].dtype))
        chosen, exists = sparse_ops.select_rows(grouped, jnp.concatenate(compressed), rows, config)
        in_sparse, attended = sparse_ops.attend_rows(grouped, [cache[0] for cache in cache_k], [cache[0] for cache in cache_v],
                                                     chosen, exists, rows, config)
        context = jnp.where(dense[:, None, None, None], in_dense, in_sparse).reshape(count, self.num_heads * self.head_dim)
        chosen = jnp.where(exists & ~dense[:, None, None], chosen, -1)
        new = (cache_k, cache_v, compressed)
        return context, jnp.where(dense, rows + 1, attended), chosen, new if apart else tuple(jnp.concatenate(leaf) for leaf in new)

    @nn.compact
    def __call__(self, x, *session):
        """``x`` alone: the block on a whole sequence (the pool's forward). With a
        session: ``(x, *cache, index[, length])`` -> ``(y, *cache)``, the cache's leaves in
        the order `init_decode_cache` gave them."""
        assert self.mixer in (LIGHTNING, SPARSE), self.mixer
        leaves = 1 if self.mixer == LIGHTNING else 3
        cache = tuple(session[:leaves]) if session else None
        index = session[leaves] if session else None
        length = session[leaves + 1] if len(session) > leaves + 1 else None
        scale = self.residual_scale
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="attention_norm")(x)
        mix = self._lightning if self.mixer == LIGHTNING else self._sparse
        mixed, cache = mix(normed, cache, index, length)
        mixed = mixed * jax.nn.sigmoid(_plain_dense(mixed.shape[-1], "gate")(normed))
        x = x + scale * _plain_dense(self.hidden_dim, "attention_out")(mixed)
        normed = nn.RMSNorm(epsilon=self.rms_eps, dtype=jnp.bfloat16, name="ffn_norm")(x)
        inner = jax.nn.silu(_plain_dense(self.ffn_inner, "ffn_gate")(normed)) * _plain_dense(self.ffn_inner, "ffn_up")(normed)
        y = (x + scale * _plain_dense(self.hidden_dim, "ffn_down")(inner)).astype(jnp.float32)
        return y if cache is None else (y, *cache)
