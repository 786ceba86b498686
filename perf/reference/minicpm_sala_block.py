"""Plain float32 reference of MiniCPM-SALA decoder blocks and of a span of them.

Straightforward `jax.numpy` after the model's published `config.json` (`model_type`
`minicpm_sala`, openbmb/MiniCPM-SALA) and the conventions of its two parent families
(Lightning Attention-2 for the `lightning-attn` blocks, InfLLM-V2 as MiniCPM4
publishes it for the `minicpm4` blocks). `x` is `[batch, T, hidden]`; both kinds:

    h = RMSNorm(x)                                  eps 1e-6, no biases anywhere
    x <- x + a * Mixer(h)                           a = scale_depth / sqrt(num_hidden_layers) = 1.4 / sqrt(32):
    x <- x + a * W_down( silu(W_gate m) * W_up m )  m = RMSNorm(x); the PUBLISHED depth, whatever the span holds

**Lightning block** (`mixer_types[i] = lightning-attn`). q, k, v = h W_q, h W_k, h W_v, 32 heads of
128 each; q, k <- RMSNorm over each head's values (one learned scale of 128 each); q, k <- rope(theta
10,000, rotate-half, absolute position); per head j, position by position, in float32:

    S_t = lambda_j S_{t-1} + k_t^T v_t         S_0 = 0, `[128, 128]`
    o_t = q_t S_t / sqrt(128)
    lambda_j = exp(-2^(-8 (j + 1) / heads))

    o <- RMSNorm over the 4,096 concatenated outputs (one learned scale of 4,096) * sigmoid(h W_g)
    Mixer(h) = o W_o

It is written as the recurrence itself (a `lax.scan` over the positions), not as the
chunked form a program would run.

**Sparse block** (`mixer_types[i] = minicpm4`). q 32 heads of 128, k, v 2 heads of 128 (16 query heads
a key-value head); the same per-head RMS norms; NO rotary embedding (`attn_use_rope: false`). For the
query at position t, with n = t + 1 positions seen:

    n < dense_len:  causal softmax attention over all n positions
    else:           c_m = mean(k[stride m : stride m + kernel_size])   the complete kernels only (stride m + kernel_size <= n)
                    p_{j,m} = softmax_m(q_j . c_m / sqrt(128))          per query head j
                    r_m = sum of p_{j,m} over the 16 heads of the group  one score a key-value head
                    block b (positions block_size b ... block_size (b + 1) - 1) scores the max of r_m over the kernels that overlap it
                    block 0 (`init_blocks`) and the window_size / block_size blocks that end at the query's own: forced (+inf)
                    the `topk` best blocks, forced ones included, are selected
                    causal softmax attention, scale 1 / sqrt(128), over the positions s <= t of the selected blocks

    o <- o * sigmoid(h W_g);  Mixer(h) = o W_o

The queries are taken in blocks (`query_block`) so that the scores of 9,000 positions
fit a device; nothing else is blocked, cached or batched. Independent of the program's
`MiniCPMSalaBlockExpert`: it reads only that block's parameter tree (a tree with
`output_norm` is a lightning block).

Departures from the published model and assumptions, all of them (the configuration
file `perf/configs/minicpm-sala-span8.json` lists the same under `assumed`, and the
program's block takes the same):

- the weights are random, drawn from the seed;
- `config.json` gives no decay: lambda_j = exp(-2^(-8 (j + 1) / heads)) is Lightning
  Attention-2's; MiniMax-01's per-layer factor (1 - layer / (layers - 1) + 1e-5 in the
  exponent) is NOT applied, the same decay in every block: a checkpoint's code would
  settle it (`per_layer_decay` below makes the wrong reference that applies it);
- the output norm is one RMS norm over the whole width (not per head), and the
  lightning gate is computed from the NORMED input h;
- `config.json` has no `sparse_config`: MiniCPM4's published one is taken
  (`kernel_size` 32, `kernel_stride` 16, `block_size` 64, `topk` 64, `init_blocks` 1,
  `window_size` 2048, `dense_len` 8192); the 64 selected blocks INCLUDE the forced
  ones; the sparse block's gate is sigmoid(h W_g) on the attention output before W_o;
- `qk_norm` is taken to hold in both kinds of block;
- `mup_denominator`, `scale_emb`, `dim_model_base` act on the embedding and the head,
  which live on the client: nothing here reads them."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(jnp.concatenate([angles, angles], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([angles, angles], -1))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def decay(heads: int, layer: int = 0, layers: int = 0):
    """lambda_j per head. ``layers`` > 0 applies MiniMax-01's per-layer factor (a WRONG
    reference here: the model as assumed has the same decay in every block)."""
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)
    if layers:
        slope = slope * (1.0 - layer / (layers - 1) + 1e-5)
    return jnp.exp(-slope)


def lightning_mixer(params, h, *, heads: int, head_dim: int, rope_theta: float, rms_eps: float,
                    lam=None, output_gate: bool = True, output_norm: bool = True):
    batch, seq, _hidden = h.shape
    q = (h @ params["query"]["kernel"]).reshape(batch, seq, heads, head_dim)
    k = (h @ params["key"]["kernel"]).reshape(batch, seq, heads, head_dim)
    v = (h @ params["value"]["kernel"]).reshape(batch, seq, heads, head_dim)
    q = _rope(_rms_norm(q, params["query_norm"]["scale"], rms_eps), rope_theta)
    k = _rope(_rms_norm(k, params["key_norm"]["scale"], rms_eps), rope_theta)
    lam = decay(heads) if lam is None else lam

    def one_position(state, qkv):  # state [batch, heads, head_dim, head_dim]
        q_t, k_t, v_t = qkv
        state = lam[None, :, None, None] * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state) / math.sqrt(head_dim)

    by_position = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = jax.lax.scan(one_position, jnp.zeros((batch, heads, head_dim, head_dim), jnp.float32),
                        (by_position(q), by_position(k), by_position(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(batch, seq, heads * head_dim)
    if output_norm:
        o = _rms_norm(o, params["output_norm"]["scale"], rms_eps)
    if output_gate:
        o = o * jax.nn.sigmoid(h @ params["gate"]["kernel"])
    return o @ params["attention_out"]["kernel"]


def selected_blocks(q, k, positions, *, kernel_size: int, kernel_stride: int, block_size: int, topk: int,
                    init_blocks: int, window_size: int, force_window: bool = True):
    """Which blocks the queries ``q`` ``[Q, kv_heads, group, dim]`` at ``positions`` ``[Q]``
    select among the keys ``k`` ``[T, kv_heads, dim]``: a boolean ``[Q, kv_heads, blocks]``."""
    seq, dim = k.shape[0], k.shape[-1]
    kernels = max((seq - kernel_size) // kernel_stride + 1, 0)
    blocks = -(-seq // block_size)
    starts = jnp.arange(kernels) * kernel_stride
    within = starts[:, None] + jnp.arange(kernel_size)[None, :]  # [kernels, kernel_size]
    compressed = k[within].mean(1) if kernels else jnp.zeros((0,) + k.shape[1:], k.dtype)  # [kernels, kv_heads, dim]
    seen = positions[:, None] + 1  # n of each query
    complete = (starts[None, :] + kernel_size) <= seen  # [Q, kernels]
    scores = jnp.einsum("qkgd,mkd->qkgm", q, compressed) / math.sqrt(dim)
    scores = jnp.where(complete[:, None, None, :], scores, -jnp.inf)
    # a query with no complete kernel has nothing to score: its blocks are the forced ones
    probs = jnp.where(complete[:, None, None, :], jax.nn.softmax(scores, axis=-1), 0.0).sum(2)  # [Q, kv_heads, kernels]
    block_start = jnp.arange(blocks) * block_size
    overlaps = ((starts[None, :] + kernel_size > block_start[:, None])
                & (starts[None, :] < block_start[:, None] + block_size))  # [blocks, kernels]
    usable = overlaps[None] & complete[:, None, :]  # [Q, blocks, kernels]
    block_scores = jnp.where(usable[:, None], probs[:, :, None, :], -jnp.inf).max(-1) if kernels else \
        jnp.full((q.shape[0], q.shape[1], blocks), -jnp.inf)
    own = positions // block_size  # the query's own block
    index = jnp.arange(blocks)[None, :]
    forced = index < init_blocks
    if force_window:
        forced = forced | ((index > own[:, None] - window_size // block_size) & (index <= own[:, None]))
    exists = index <= own[:, None]
    block_scores = jnp.where(forced[:, None, :], jnp.inf, block_scores)
    block_scores = jnp.where(exists[:, None, :], block_scores, -jnp.inf)
    _, chosen = jax.lax.top_k(block_scores, min(topk, blocks))
    picked = jax.nn.one_hot(chosen, blocks, dtype=jnp.bool_).any(-2)
    return picked & (block_scores > -jnp.inf)


def sparse_mixer(params, h, *, heads: int, kv_heads: int, head_dim: int, rms_eps: float, kernel_size: int,
                 kernel_stride: int, block_size: int, topk: int, init_blocks: int, window_size: int, dense_len: int,
                 rope_theta: float = 0.0, force_window: bool = True, output_gate: bool = True, query_block: int = 512,
                 return_selection: bool = False):
    """``rope_theta`` > 0 (rotary embedding in a sparse block), ``force_window`` = False,
    ``output_gate`` = False and other ``topk`` / ``dense_len`` make WRONG references."""
    batch, seq, _hidden = h.shape
    group = heads // kv_heads
    q = (h @ params["query"]["kernel"]).reshape(batch, seq, heads, head_dim)
    k = (h @ params["key"]["kernel"]).reshape(batch, seq, kv_heads, head_dim)
    v = (h @ params["value"]["kernel"]).reshape(batch, seq, kv_heads, head_dim)
    q = _rms_norm(q, params["query_norm"]["scale"], rms_eps)
    k = _rms_norm(k, params["key_norm"]["scale"], rms_eps)
    if rope_theta:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    q = q.reshape(batch, seq, kv_heads, group, head_dim)
    blocks = -(-seq // block_size)
    padded = -(-seq // query_block) * query_block
    positions = jnp.arange(padded).reshape(-1, query_block)
    key_block = jnp.arange(seq) // block_size

    def one_stream(q, k, v):
        q = jnp.pad(q, ((0, padded - seq), (0, 0), (0, 0), (0, 0))).reshape(-1, query_block, kv_heads, group, head_dim)

        def one_query_block(args):
            q, at = args
            picked = selected_blocks(q, k, at, kernel_size=kernel_size, kernel_stride=kernel_stride, block_size=block_size,
                                     topk=topk, init_blocks=init_blocks, window_size=window_size, force_window=force_window)
            sparse = (at + 1 >= dense_len)[:, None, None]
            picked = jnp.where(sparse, picked, True)  # the dense mode attends every block
            seen = picked[:, :, key_block] & (jnp.arange(seq)[None, None, :] <= at[:, None, None])  # [Q, kv_heads, T]
            scores = jnp.einsum("qkgd,skd->qkgs", q, k) / math.sqrt(head_dim)
            scores = jnp.where(seen[:, :, None, :], scores, -jnp.inf)
            return jnp.einsum("qkgs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v), picked & sparse

        context, picked = jax.lax.map(one_query_block, (q, positions))
        return context.reshape(padded, heads * head_dim)[:seq], picked.reshape(padded, kv_heads, blocks)[:seq]

    o, picked = jax.vmap(one_stream)(q, k, v)
    if output_gate:
        o = o * jax.nn.sigmoid(h @ params["gate"]["kernel"])
    out = o @ params["attention_out"]["kernel"]
    return (out, picked) if return_selection else out


def block(params, x, *, alpha: float, rms_eps: float, lightning: dict, sparse: dict, return_selection: bool = False):
    """One block; its kind is read off the parameter tree. ``lightning`` / ``sparse``:
    the keyword arguments of `lightning_mixer` / `sparse_mixer`. ``return_selection``:
    also the blocks each query selected in sparse mode (``[batch, T, kv_heads, blocks]``
    booleans, all False for a query in dense mode; None for a lightning block)."""
    h = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    if "output_norm" in params:
        mixed, picked = lightning_mixer(params, h, rms_eps=rms_eps, **lightning), None
    else:
        mixed, picked = sparse_mixer(params, h, rms_eps=rms_eps, return_selection=True, **sparse)
    x = x + alpha * mixed
    m = _rms_norm(x, params["ffn_norm"]["scale"], rms_eps)
    y = x + alpha * ((jax.nn.silu(m @ params["ffn_gate"]["kernel"]) * (m @ params["ffn_up"]["kernel"])) @ params["ffn_down"]["kernel"])
    return (y, picked) if return_selection else y


def _float32(params):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params)


def span_with_selection(all_params, x, **sizes):
    """The blocks of ``all_params`` (a list of parameter trees) applied in order: the
    output and each block's selection (`block`'s ``return_selection``)."""
    with jax.default_matmul_precision("highest"):
        x, selections = x.astype(jnp.float32), []
        for params in all_params:
            x, picked = block(_float32(params), x, return_selection=True, **sizes)
            selections.append(picked)
        return x, selections


def span(all_params, x, **sizes):
    return span_with_selection(all_params, x, **sizes)[0]
