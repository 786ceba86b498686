"""Pallas TPU flash-attention kernel — the fused hot op behind the serving path.

The reference has no attention kernel at all (its device math is plain torch ops;
SURVEY §2.0); attention here is the TPU-first capability layer's hot op: MoE
transformer/causal/llama experts and the flagship model all funnel through one
attention core (`ops/attention.py`). This kernel fuses the
whole softmax(QKᵀ)·V pipeline into VMEM-block passes with ONLINE softmax, so logits
never round-trip through HBM and VMEM stays O(block_q·block_k) regardless of
sequence length.

Operands: q, k, v and the cotangent enter every product in the dtype they arrive in
(bf16 from ALBERT and the served blocks, float32 from a float32 caller) with float32
accumulation; scores, the running max and sum, the log-sum-exp, delta and every
accumulator are float32; probabilities and dS are cast to the operands' dtype only
for the product that consumes them. Nothing is narrowed below its input dtype.

Tiles: `_tiles` picks (block_q, block_k) from what a call can observe — sequence
length, head size, operand itemsize, `causal` — under `_VMEM_BUDGET`, by the least
estimated time (grid steps at a measured cost each, plus the score entries computed).
A whole row of keys is one step where it fits (512 x 512 at ALBERT's shape: the KV
sweep and its carry are one step); causal calls skip the blocks above the diagonal,
so past one tile they take tiles that leave blocks to skip (1,024 causal: 512 x 512,
three of four computed). Lengths pad to the next multiple of 128 only, and tiles
divide the padded length.

Layout: the kernels read q, k, v (and write the context and the gradients) where they
lie, as [batch, seq, heads·head_dim] — no transpose to a head-major copy. A grid step
takes one LANE GROUP of them: the fewest whole heads that fill whole 128-lane tiles
(two heads of 64, one of 128). Where a group holds several heads, each head's products
run on the group's operands with the other heads' lanes zeroed: contracting over 128
lanes of which 64 are zero costs the matrix unit what 64 do, and the outputs come out
lane-dense.

Forward: grid = (batch, lane groups, seq/block_q, seq/block_k) — the KV loop is the
LAST (fastest-varying) grid dimension, and the online-softmax carry (running row max,
row sum, output accumulator) lives in VMEM scratch that persists across those grid
steps; the carry is initialized on the first KV block and the normalized output is
written on the last. In causal mode, KV blocks entirely above the diagonal skip their
matmuls via `pl.when` and are not fetched (their block index repeats the last needed
one); the mask is applied only in blocks that straddle the diagonal or hold tail
padding, and matches `plain_attention` exactly.

Row statistics cross HBM in their own width: the forward writes the log-sum-exp as
[batch, lane groups, heads a group, seq] float32 (one lane-major row a head), and the
backward reads it and delta the same way.

Differentiation: `flash_attention` carries a `jax.custom_vjp` with ONE fused backward
kernel: grid (batch, lane groups, kv block, query block), scores computed TRANSPOSED
(K·Qᵀ, so the saved row statistics broadcast along sublanes as they lie and dV = Pᵀ·dO
and dK = dSᵀ·Q are plain products); dK and dV accumulate in scratch over the query
sweep, dQ in a whole-row float32 scratch over both sweeps. Probabilities are
recomputed per tile from the saved log-sum-exp (`p = exp(s − lse)`, no max carry
needed), once for all three gradients, so score matrices never materialize in HBM
in either direction. On non-TPU backends the kernels run in interpret mode for the
test suite; `ops.attention.attention_auto` dispatches per backend."""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # a tile's minor dimension; lengths pad to a multiple of it
_NEG_INF = -1e30  # large-but-finite: keeps fully-masked rows NaN-free
# What one grid step of the backward kernel (the larger of the two) may hold in VMEM
# by the estimate of `_step_bytes`; Mosaic is given `_VMEM_LIMIT` so that its own
# temporaries have room (a v5e core has 128 MiB, 16 MiB of it scoped by default).
_VMEM_BUDGET = 8 * 2**20
_VMEM_LIMIT = 32 * 2**20
# The tile rule's table, measured on a v5e (PERF.md §6, PR 33): what a grid step costs
# before any arithmetic, and what the kernels take per entry of a large score tile (the
# forward's 512 x 512 tile in 2.3 us; the vector unit's passes over the float32 scores
# set it, not the matrix unit, so head size and dtype move it little). At the cells'
# shapes the step dominates: causal 4 x 512 x 32 x 128 forward took 0.81 / 0.40 / 0.29 ms
# in tiles of 128 / 256 / 512, although a 512 tile computes the whole square where
# 128-wide tiles compute 10 of 16.
_STEP_US = 0.35
_ENTRY_US = 2.3 / (512 * 512)

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_NN = (((1,), (0,)), ((), ()))  # a · b
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b


class Tiles(NamedTuple):
    padded: int  # sequence length the kernels see
    block_q: int
    block_k: int


def _step_bytes(block_q: int, block_k: int, head_dim: int, itemsize: int) -> int:
    """VMEM one backward grid step needs: four float32 score-shaped tiles (sᵀ, pᵀ, dpᵀ,
    dsᵀ) and three in the operands' dtype (pᵀ, dsᵀ and dsᵀ transposed for dQ); a lane
    group's q, dO, k, v in and dK, dV out, double-buffered; the float32 dK and dV
    accumulators."""
    width = math.lcm(head_dim, _LANES)  # `_lane_width`, the usual case
    scores = block_q * block_k * (4 * 4 + 3 * itemsize)
    operands = 2 * (2 * block_q + 4 * block_k) * width * itemsize
    return scores + operands + 2 * block_k * width * 4


def _tiles(seq: int, head_dim: int, itemsize: int, causal: bool) -> Tiles:
    """The one tile rule. Lengths pad to the next multiple of 128; candidates are the
    pairs of multiples of 128 that divide the padded length and fit `_VMEM_BUDGET`
    (128 x 128 always may); the pair with the least estimated time wins: every grid
    step costs `_STEP_US`, every entry of a computed score tile `_ENTRY_US`, and a
    causal call computes only the tiles that reach the diagonal. So a bidirectional
    row of 512 keys is one step, and a causal call takes the largest tile that fits
    until the skipped tiles outweigh the steps. Ties go to the squarer pair, then to
    the wider key block."""
    padded = -(-seq // _LANES) * _LANES
    sizes = [t for t in range(_LANES, padded + 1, _LANES) if padded % t == 0]

    def estimate(block_q, block_k):
        num_q, num_k = padded // block_q, padded // block_k
        computed = num_q * num_k
        if causal:
            computed = sum(min(num_k, (qi * block_q + block_q - 1) // block_k + 1) for qi in range(num_q))
        return num_q * num_k * _STEP_US + computed * block_q * block_k * _ENTRY_US

    fitting = [
        (block_q, block_k) for block_q in sizes for block_k in sizes
        if block_q == block_k == _LANES or _step_bytes(block_q, block_k, head_dim, itemsize) <= _VMEM_BUDGET
    ]
    block_q, block_k = min(fitting, key=lambda t: (estimate(*t), -min(t), -t[1]))
    return Tiles(padded, block_q, block_k)


def _block_mask(shape, q_axis: int, q_start, kv_start, seq_len: int, causal: bool):
    """Validity of a score tile's entries: keys inside the sequence (tail padding) and,
    in causal mode, not after their query. `q_axis` is the tile's query dimension."""
    # rank-2 iotas: Mosaic rejects rank-1 lax.iota (pallas_guide: common pitfalls)
    kv_positions = kv_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = kv_positions < seq_len
    if causal:
        q_positions = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        mask &= kv_positions <= q_positions
    return mask


def _for_needed_blocks(step, q_start, kv_start, block_q, block_k, seq_len, padded, causal):
    """Run `step(masked)` for this (query block, KV block) pair: not at all where the
    block lies above the diagonal, with the mask only where the block straddles the
    diagonal or holds tail padding."""
    if block_q == block_k == padded:  # the call's only tile: what it needs is known at trace time
        return step(causal or padded != seq_len)
    needed, masked = True, False
    if causal:
        needed = kv_start <= q_start + block_q - 1
        masked = kv_start + block_k - 1 > q_start
    if padded != seq_len:
        masked |= kv_start + block_k > seq_len
    if masked is False:  # no block of this call needs a mask, and none is skipped
        step(False)
    else:
        pl.when(needed & masked)(partial(step, True))
        pl.when(needed & jnp.logical_not(masked))(partial(step, False))


def _lane_width(heads: int, head_dim: int) -> int:
    """Lanes of the [batch, seq, heads·head_dim] operands a grid step takes, as they lie:
    the fewest whole heads that fill whole 128-lane tiles (two heads of 64, one of 128),
    else all of them (a block may always span its array)."""
    width = math.lcm(head_dim, _LANES)
    return width if (heads * head_dim) % width == 0 else heads * head_dim


def _head_lanes(width: int, head_dim: int, head: int):
    """[1, width] mask of the lanes that hold `head` of a step's heads; None where the
    step holds one head."""
    if width == head_dim:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim == head


def _only(lanes, x):
    """`x` with every lane outside `lanes` zeroed: as an operand of a product it confines
    the product to one head — contracting over 128 lanes of which 64 are zero costs the
    matrix unit what contracting over 64 does, and the other head's output lanes stay 0."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref, lse_ref, max_ref, sum_ref, acc_ref,
    *, head_dim: int, seq_len: int, padded: int, causal: bool,
):
    """One (query block, KV block) grid step for the heads of one lane group; carry
    persists in scratch refs."""
    q_index, kv_index = pl.program_id(2), pl.program_id(3)
    num_kv = pl.num_programs(3)
    block_q, block_k, width = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]
    heads = width // head_dim
    q_start, kv_start = q_index * block_q, kv_index * block_k
    scale = head_dim ** -0.5

    @pl.when(kv_index == 0)
    def _init():
        max_ref[:] = jnp.full_like(max_ref, _NEG_INF)
        sum_ref[:] = jnp.zeros_like(sum_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _accumulate(masked: bool):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]  # [block, width], in the dtype they arrived in
        mask = _block_mask((block_q, block_k), 0, q_start, kv_start, seq_len, causal) if masked else None
        for head in range(heads):
            lanes = _head_lanes(width, head_dim, head)
            scores = jax.lax.dot_general(_only(lanes, q), k, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                scores = jnp.where(mask, scores, _NEG_INF)
            row_max = max_ref[head]  # [block_q, 1]
            new_max = jnp.maximum(row_max, jnp.max(scores, axis=-1, keepdims=True))
            correction = jnp.exp(row_max - new_max)
            probs = jnp.exp(scores - new_max)
            update = jax.lax.dot_general(
                probs.astype(v.dtype), _only(lanes, v), _NN, preferred_element_type=jnp.float32
            )
            acc_ref[:] = acc_ref[:] * (correction if lanes is None else jnp.where(lanes, correction, 1.0)) + update
            sum_ref[head] = sum_ref[head] * correction + jnp.sum(probs, axis=-1, keepdims=True)
            max_ref[head] = new_max

    _for_needed_blocks(_accumulate, q_start, kv_start, block_q, block_k, seq_len, padded, causal)

    @pl.when(kv_index == num_kv - 1)
    def _finalize():
        norm = None
        for head in range(heads):
            lanes = _head_lanes(width, head_dim, head)
            row_sum = jnp.maximum(sum_ref[head], 1e-30)
            norm = 1.0 / row_sum if lanes is None else jnp.where(lanes, 1.0 / row_sum, 0.0 if norm is None else norm)
            # log-sum-exp per query row: what ring attention needs to merge softmax
            # statistics across sequence shards without re-materializing the scores.
            # The column of row statistics leaves as one lane-major row: broadcast
            # across a tile's lanes, transpose, keep the first row.
            lse = jnp.broadcast_to(max_ref[head] + jnp.log(row_sum), (block_q, _LANES))
            lse_ref[0, 0, head:head + 1, :] = jnp.transpose(lse)[:1]
        out_ref[0] = (acc_ref[:] * norm).astype(out_ref.dtype)


def _as_rows(x, padded: int):
    """[batch, seq, heads, d] -> [batch, padded, heads·d]: the operands as they lie (no
    transpose), zero-padded along the sequence."""
    batch, seq, heads, head_dim = x.shape
    x = x.reshape(batch, seq, heads * head_dim)
    return jnp.pad(x, ((0, 0), (0, padded - seq), (0, 0))) if padded != seq else x


def _last_needed_kv(q_index, tiles: Tiles):
    """Last KV block a causal query block needs."""
    return (q_index * tiles.block_q + tiles.block_q - 1) // tiles.block_k


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


@partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_forward(q, k, v, causal: bool = False, interpret: bool = False):
    """q, k, v: [batch, seq, heads, head_dim] → context of the same shape."""
    batch, seq, heads, head_dim = q.shape
    tiles = _tiles(seq, head_dim, q.dtype.itemsize, causal)
    padded, block_q, block_k = tiles
    width = _lane_width(heads, head_dim)
    groups, heads_a_step = heads * head_dim // width, width // head_dim

    def kv_index(b, g, qi, ki):  # a skipped block is not fetched: repeat the last needed one
        return (b, jnp.minimum(ki, _last_needed_kv(qi, tiles)) if causal else ki, g)

    q_spec = pl.BlockSpec((1, block_q, width), lambda b, g, qi, ki: (b, qi, g))
    kv_spec = pl.BlockSpec((1, block_k, width), kv_index)
    out, lse = pl.pallas_call(
        partial(_flash_kernel, head_dim=head_dim, seq_len=seq, padded=padded, causal=causal),
        grid=(batch, groups, padded // block_q, padded // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, heads_a_step, block_q), lambda b, g, qi, ki: (b, g, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, padded, heads * head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, groups, heads_a_step, padded), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads_a_step, block_q, 1), jnp.float32),  # running row max
            pltpu.VMEM((heads_a_step, block_q, 1), jnp.float32),  # running row sum
            pltpu.VMEM((block_q, width), jnp.float32),  # output accumulator
        ],
        compiler_params=_compiler_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(*(_as_rows(x, padded) for x in (q, k, v)))
    return out[:, :seq].reshape(q.shape), lse.reshape(batch, heads, padded)[:, :, :seq]


def flash_attention_lse(q, k, v, causal: bool = False, interpret: bool = False):
    """Fused attention that ALSO returns the per-row log-sum-exp ([batch, heads,
    seq], fp32) — the statistic ring attention needs to merge shard outputs:
    ``merged = Σ_i out_i · exp(lse_i − logaddexp_i(lse))``. Forward-only (no
    custom_vjp): callers that differentiate wrap the whole construction (see
    `parallel.ring_attention.ring_flash_attention`)."""
    return _flash_forward(q, k, v, causal=causal, interpret=interpret)


def _flash_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_acc_ref, dk_acc_ref, dv_acc_ref, *, head_dim: int, seq_len: int, padded: int, causal: bool,
):
    """One (KV block, query block) grid step of the fused backward: recompute the
    tile's probabilities TRANSPOSED from the saved log-sum-exp, then dV += Pᵀ·dO,
    dK += dSᵀ·Q (scratch over the query sweep) and dQ += dS·K (whole-row scratch)."""
    kv_index, q_index = pl.program_id(2), pl.program_id(3)
    num_kv, num_q = pl.num_programs(2), pl.num_programs(3)
    block_q, block_k, width = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]
    heads = width // head_dim
    q_start, kv_start = q_index * block_q, kv_index * block_k
    q_rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
    scale = head_dim ** -0.5

    @pl.when((kv_index == 0) & (q_index == 0))
    def _init_dq():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when(q_index == 0)
    def _init_dkv():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def _accumulate(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        mask = _block_mask((block_k, block_q), 1, q_start, kv_start, seq_len, causal) if masked else None
        for head in range(heads):
            lanes = _head_lanes(width, head_dim, head)
            q_h, do_h = _only(lanes, q), _only(lanes, do)
            scores = jax.lax.dot_general(k, q_h, _NT, preferred_element_type=jnp.float32) * scale
            if masked:  # masked p underflows to 0
                scores = jnp.where(mask, scores, _NEG_INF)
            # [block_k, block_q]; lse and delta are [1, block_q] rows, float32
            p = jnp.exp(scores - lse_ref[0, 0, head:head + 1, :])  # exact probs: lse already holds the row max
            dp = jax.lax.dot_general(v, do_h, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, 0, head:head + 1, :]) * scale).astype(q.dtype)
            dv_acc_ref[:] += jax.lax.dot_general(p.astype(do.dtype), do_h, _NN, preferred_element_type=jnp.float32)
            dk_acc_ref[:] += jax.lax.dot_general(ds, q_h, _NN, preferred_element_type=jnp.float32)
            dq_acc_ref[q_rows, :] += jax.lax.dot_general(ds, _only(lanes, k), _TN, preferred_element_type=jnp.float32)

    _for_needed_blocks(_accumulate, q_start, kv_start, block_q, block_k, seq_len, padded, causal)

    @pl.when(q_index == num_q - 1)
    def _finalize_dkv():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)

    @pl.when((kv_index == num_kv - 1) & (q_index == num_q - 1))
    def _finalize_dq():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


@partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_backward(q, k, v, out, lse, grad_out, causal: bool = False, interpret: bool = False):
    """Fused flash backward from the saved (out, lse) residuals."""
    batch, seq, heads, head_dim = q.shape
    tiles = _tiles(seq, head_dim, q.dtype.itemsize, causal)
    padded, block_q, block_k = tiles
    width = _lane_width(heads, head_dim)
    groups, heads_a_step = heads * head_dim // width, width // head_dim

    def row_stats(x):  # [batch, heads, seq] float32 -> [batch, groups, heads a step, padded]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, padded - seq))) if padded != seq else x
        return x.reshape(batch, groups, heads_a_step, padded)

    # delta_i = Σ_d dOut·Out — one elementwise reduce; padded rows are zero (they
    # are padded with zeros), so they contribute nothing to dK/dV in the sweep
    delta = jnp.sum(grad_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [batch, seq, heads]

    def q_index(ki, qi):  # a skipped block is not fetched: repeat the first needed one
        return jnp.maximum(qi, (ki * block_k) // block_q) if causal else qi

    q_spec = pl.BlockSpec((1, block_q, width), lambda b, g, ki, qi: (b, q_index(ki, qi), g))
    kv_spec = pl.BlockSpec((1, block_k, width), lambda b, g, ki, qi: (b, ki, g))
    row_spec = pl.BlockSpec((1, 1, heads_a_step, block_q), lambda b, g, ki, qi: (b, g, 0, q_index(ki, qi)))
    dq, dk, dv = pl.pallas_call(
        partial(_flash_bwd_kernel, head_dim=head_dim, seq_len=seq, padded=padded, causal=causal),
        grid=(batch, groups, padded // block_k, padded // block_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((1, padded, width), lambda b, g, ki, qi: (b, 0, g)), kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((batch, padded, heads * head_dim), x.dtype) for x in (q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((padded, width), jnp.float32),
            pltpu.VMEM((block_k, width), jnp.float32),
            pltpu.VMEM((block_k, width), jnp.float32),
        ],
        compiler_params=_compiler_params("parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(
        *(_as_rows(x, padded) for x in (q, k, v, grad_out)),
        row_stats(lse), row_stats(jnp.transpose(delta, (0, 2, 1))),
    )
    return tuple(x[:, :seq].reshape(q.shape) for x in (dq, dk, dv))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = False, interpret: bool = False):
    """Fused flash attention on [batch, seq, heads, head_dim] (full sequences; for
    padded batches use the mask-capable `plain_attention`). Backward is fused too
    (one kernel from the saved log-sum-exp — see module docstring)."""
    return _flash_forward(q, k, v, causal=causal, interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, interpret, residuals, grad_out):
    q, k, v, out, lse = residuals  # lse: [batch, heads, seq] as returned by _flash_forward
    return _flash_backward(
        q, k, v, out, lse, grad_out.astype(q.dtype), causal=causal, interpret=interpret
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)
