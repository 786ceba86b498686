"""Block-sparse attention over a session's cache, selected through compressed keys
(InfLLM-V2 as MiniCPM4 publishes it): a query scores the means of overlapping kernels
of keys (`kernel_size` positions every `kernel_stride`), the scores of the kernels that
overlap a block of `block_size` positions give the block's, the first `init_blocks` and
the `window_size / block_size` blocks that end at the query's own are forced, and the
query attends the positions ``s <= t`` of the `topk` best blocks, forced ones included;
while fewer than `dense_len` positions are seen it attends all of them.

`sparse_select` and `sparse_attend` are ONE query's; `select_rows` / `attend_rows` take
a leading axis of queries, each with its own caches (the rows of a batched step: the
keys and values a SEQUENCE of the rows' own arrays, gathered where they lie before
anything joins them) or all on one session's (a chunk's queries), under the scopes
`sparse_select` and `sparse_attend` that name the two in a lowered program (a scope
opened inside a `jax.vmap` is lost from the operations' names, so the scopes lie
around it). A
cache of keys or values is ``[kv_heads, slots, dim]``, the compressed keys ``[kv_heads,
slots / kernel_stride, dim]`` (kernel m at slot m, written when it completes), a query
``[kv_heads, group, dim]`` (the query heads of a key-value head together: ONE selection
a key-value head). The selection reads the compressed keys and the attention GATHERS
the chosen blocks (the cache viewed ``[slots / block_size, block_size, dim]``): neither
reads the whole cache. Selection scores are float32. Plain `jax.numpy` / `lax`."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SparseConfig(NamedTuple):
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def check(self, slots: int) -> None:
        assert self.kernel_size % self.kernel_stride == 0 and self.block_size % self.kernel_stride == 0, self
        assert slots % self.block_size == 0, f"a cache of {slots} slots is no whole number of blocks of {self.block_size}"


def write_compressed(compressed, cache_k, first, count: int, config: SparseConfig):
    """The means of the kernels ``first .. first + count - 1`` (``first`` may be traced),
    taken from ``cache_k`` as it lies, into their slots of ``compressed``; a kernel past
    the last slot is dropped. A kernel that is not complete yet gets the mean of what
    its positions hold now: nobody reads it before it completes, and it is written
    again then."""
    kernel = first + jnp.arange(count)
    positions = kernel[:, None] * config.kernel_stride + jnp.arange(config.kernel_size)[None, :]
    keys = jnp.take(cache_k, jnp.clip(positions, 0, cache_k.shape[1] - 1), axis=1)  # [kv_heads, count, kernel_size, dim]
    means = keys.astype(jnp.float32).mean(2).astype(compressed.dtype)
    return compressed.at[:, kernel].set(means, mode="drop")


def write_kernel(compressed, cache_k, kernel, due, config: SparseConfig):
    """ONE kernel's mean (``kernel`` traced) into its slot of ``compressed``, if it is
    ``due``, else nothing: a step's form of `write_compressed`. The kernel's positions are
    cut out of ``cache_k`` as one slice: a gather along the slots, as `write_compressed`
    makes for many kernels, has a TPU lay the whole cache out slots-first, two copies of
    it a step."""
    kv_heads, slots, dim = cache_k.shape
    kernel = jnp.clip(kernel, 0, compressed.shape[1] - 1)
    start = jnp.minimum(kernel * config.kernel_stride, slots - config.kernel_size)
    keys = jax.lax.dynamic_slice(cache_k, (0, start, 0), (kv_heads, config.kernel_size, dim))
    mean = keys.astype(jnp.float32).mean(1, keepdims=True).astype(compressed.dtype)
    held = jax.lax.dynamic_slice(compressed, (0, kernel, 0), (kv_heads, 1, dim))
    return jax.lax.dynamic_update_slice(compressed, jnp.where(due, mean, held), (0, kernel, 0))


def sparse_select(q, compressed, position, config: SparseConfig):
    """The blocks the query at ``position`` selects: (block numbers ``[kv_heads, k]``,
    whether each exists ``[kv_heads, k]``), k = min(topk, blocks of the cache)."""
    kv_heads, kernels, dim = compressed.shape
    ratio, reach = config.block_size // config.kernel_stride, config.kernel_size // config.kernel_stride
    blocks = kernels // ratio
    seen = position + 1
    complete = jnp.arange(kernels) * config.kernel_stride + config.kernel_size <= seen
    scores = jnp.einsum("kgd,kmd->kgm", q.astype(compressed.dtype), compressed, preferred_element_type=jnp.float32) * dim**-0.5
    scores = jnp.where(complete[None, None], scores, -jnp.inf)
    probs = jnp.where(complete[None, None], jax.nn.softmax(scores, axis=-1), 0.0).sum(1)  # one score a key-value head
    probs = jnp.where(complete[None], probs, -jnp.inf)
    # block b overlaps the kernels b ratio - (reach - 1) .. b ratio + ratio - 1
    padded = jnp.pad(probs, ((0, 0), (reach - 1, 0)), constant_values=-jnp.inf)
    by_block = jnp.stack([padded[:, offset:offset + ratio * blocks:ratio] for offset in range(ratio + reach - 1)]).max(0)
    own = position // config.block_size
    index = jnp.arange(blocks)
    forced = (index < config.init_blocks) | ((index > own - config.window_size // config.block_size) & (index <= own))
    by_block = jnp.where(forced[None], jnp.inf, by_block)
    by_block = jnp.where((index <= own)[None], by_block, -jnp.inf)
    best, chosen = jax.lax.top_k(by_block, min(config.topk, blocks))
    return chosen, best > -jnp.inf


def gather_blocks(cache, chosen, size: int):
    """The ``chosen`` blocks ``[kv_heads, k]`` of ``size`` positions out of a cache
    ``[kv_heads, slots, dim]`` viewed ``[kv_heads, blocks, size, dim]``, as ``[kv_heads,
    k size, dim]``: this gather reads the cache where it lies (one whose slices are cut
    out of ``[slots, dim]`` makes a TPU copy the whole cache into another layout first)."""
    kv_heads, slots, dim = cache.shape
    return jnp.take_along_axis(cache.reshape(kv_heads, slots // size, size, dim), chosen[:, :, None, None], axis=1).reshape(kv_heads, -1, dim)


def attend_gathered(q, keys, values, chosen, exists, position, config: SparseConfig):
    """Softmax attention of the query at ``position`` over the positions ``s <= position``
    of the ``chosen`` blocks, whose ``keys`` and ``values`` are already gathered
    (`gather_blocks`). Returns (context ``[kv_heads, group, dim]`` in the values' dtype, how
    many positions one key-value head attended)."""
    kv_heads, _picked, dim = keys.shape
    size = config.block_size
    at = (chosen[:, :, None] * size + jnp.arange(size)[None, None, :]).reshape(kv_heads, -1)
    seen = (at <= position) & jnp.repeat(exists, size, axis=1)
    scores = jnp.einsum("kgd,ksd->kgs", q.astype(keys.dtype), keys, preferred_element_type=jnp.float32) * dim**-0.5
    scores = jnp.where(seen[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    return jnp.einsum("kgs,ksd->kgd", probs, values), seen[0].sum()


def sparse_attend(q, cache_k, cache_v, chosen, exists, position, config: SparseConfig):
    """`attend_gathered` over the chosen blocks gathered from the caches ``[kv_heads, slots, dim]``."""
    size = config.block_size
    return attend_gathered(q, gather_blocks(cache_k, chosen, size), gather_blocks(cache_v, chosen, size), chosen, exists, position, config)


def select_rows(q, compressed, positions, config: SparseConfig, shared: bool = False):
    """`sparse_select` for the queries ``q`` ``[rows, ...]`` at ``positions`` ``[rows]``, each on
    its own compressed keys ``[rows, ...]`` (``shared``: all on one session's)."""
    with jax.named_scope("sparse_select"):
        return jax.vmap(lambda q, compressed, position: sparse_select(q, compressed, position, config),
                        in_axes=(0, None if shared else 0, 0))(q, compressed, positions)


def attend_rows(q, cache_k, cache_v, chosen, exists, positions, config: SparseConfig, shared: bool = False):
    """`sparse_attend` for the queries ``q`` ``[rows, ...]``: each over its own caches, ``cache_k``
    and ``cache_v`` a sequence of the rows' arrays (each row's blocks are gathered from its own
    array, so the whole caches are never joined), or (``shared``) all over one session's."""
    with jax.named_scope("sparse_attend"):
        if shared:
            return jax.vmap(lambda q, chosen, exists, position: sparse_attend(q, cache_k, cache_v, chosen, exists, position, config))(
                q, chosen, exists, positions)
        gathered = lambda caches: jnp.stack([gather_blocks(cache, chosen[row], config.block_size) for row, cache in enumerate(caches)])
        return jax.vmap(lambda q, keys, values, chosen, exists, position: attend_gathered(q, keys, values, chosen, exists, position, config))(
            q, gathered(cache_k), gathered(cache_v), chosen, exists, positions)


def dense_attend(q, cache_k, cache_v, positions, extent: int):
    """Causal softmax attention of the queries ``q`` ``[queries, kv_heads, group, dim]`` at
    ``positions`` over the first ``extent`` slots of the caches (the dense mode of a
    chunk: every query of it has seen fewer than ``extent`` positions)."""
    dim = q.shape[-1]
    keys, values = cache_k[:, :extent], cache_v[:, :extent]
    scores = jnp.einsum("qkgd,ksd->qkgs", q.astype(keys.dtype), keys, preferred_element_type=jnp.float32) * dim**-0.5
    seen = jnp.arange(extent)[None, :] <= positions[:, None]
    scores = jnp.where(seen[:, None, None, :], scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("qkgs,ksd->qkgd", jax.nn.softmax(scores, axis=-1).astype(values.dtype), values)


def sparse_prefill(q, cache_k, cache_v, compressed, index, config: SparseConfig, query_block: int = 128):
    """A chunk of queries ``q`` ``[seq, kv_heads, group, dim]`` at the positions ``index ..
    index + seq - 1`` against caches that already hold the chunk's own keys, values and
    compressed keys: each query in the mode its own position puts it in, its own
    selection, its own gather — O(seq * topk * block_size) scores a head, in blocks of
    ``query_block`` queries so that the gathered keys of one block (``query_block * topk *
    block_size`` positions a key-value head) fit beside the model. Returns (context
    ``[seq, kv_heads, group, dim]``, the positions each query attended ``[seq]``: all it
    had seen in the dense mode; the blocks each query selected ``[seq, kv_heads, k]``: -1
    for a block that does not exist, and throughout in the dense mode)."""
    seq, kv_heads, group, dim = q.shape
    size = min(query_block, seq)
    blocks = -(-seq // size)
    q = jnp.pad(q, ((0, blocks * size - seq), (0, 0), (0, 0), (0, 0))).reshape(blocks, size, kv_heads, group, dim)
    positions = index + jnp.arange(blocks * size).reshape(blocks, size)
    extent = min(config.dense_len, cache_k.shape[1])

    picks = min(config.topk, cache_k.shape[1] // config.block_size)

    def in_sparse_mode(q, positions):
        chosen, exists = select_rows(q, compressed, positions, config, shared=True)
        return (*attend_rows(q, cache_k, cache_v, chosen, exists, positions, config, shared=True), jnp.where(exists, chosen, -1))

    def one_block(inputs):
        q, positions = inputs
        sparse = positions + 1 >= config.dense_len
        nothing = lambda: jnp.zeros(q.shape, cache_v.dtype)
        in_dense = jax.lax.cond(jnp.any(~sparse), lambda: dense_attend(q, cache_k, cache_v, positions, extent), nothing)
        in_sparse, attended, chosen = jax.lax.cond(
            jnp.any(sparse), lambda: in_sparse_mode(q, positions),
            lambda: (nothing(), jnp.zeros(size, jnp.int32), jnp.full((size, kv_heads, picks), -1, jnp.int32)))
        return (jnp.where(sparse[:, None, None, None], in_sparse, in_dense), jnp.where(sparse, attended, positions + 1),
                jnp.where(sparse[:, None, None], chosen, -1))

    context, attended, chosen = jax.lax.map(one_block, (q, positions))
    return (context.reshape(blocks * size, kv_heads, group, dim)[:seq], attended.reshape(-1)[:seq],
            chosen.reshape(blocks * size, kv_heads, picks)[:seq])
