"""Pallas TPU flash-attention kernel — the fused hot op behind the serving path.

The reference has no attention kernel at all (its device math is plain torch ops;
SURVEY §2.0); attention here is the TPU-first capability layer's hot op: MoE
transformer/causal/llama experts and the flagship model all funnel through one
attention core (`parallel/ring_attention.plain_attention`). This kernel fuses the
whole softmax(QKᵀ)·V pipeline into VMEM-block passes with ONLINE softmax, so logits
never round-trip through HBM and VMEM stays O(BLOCK_Q·BLOCK_K) regardless of
sequence length.

Layout: grid = (batch·heads, seq/BLOCK_Q, seq/BLOCK_K) — the KV loop is the LAST
(fastest-varying) grid dimension, and the online-softmax carry (running row max,
row sum, output accumulator) lives in VMEM scratch that persists across those grid
steps; the carry is initialized on the first KV block and the normalized output is
written on the last. Only one (1, BLOCK_Q, d) query tile and one (1, BLOCK_K, d)
KV tile are resident per step. In causal mode, KV blocks entirely above the
diagonal skip their matmuls via `pl.when` (half the FLOPs of the naive sweep);
masking within straddling blocks matches `plain_attention` exactly.

Differentiation: `flash_attention` carries a `jax.custom_vjp` with FUSED backward
kernels (the standard two-pass scheme): the forward saves (out, lse) as O(seq)
residuals, then dQ comes from one kernel sweeping KV blocks per query block and
(dK, dV) from a second kernel sweeping query blocks per KV block — probabilities
are recomputed per tile from the saved log-sum-exp (`p = exp(s − lse)`, no max
carry needed), so score matrices never materialize in HBM in either direction.
On non-TPU backends the kernels run in interpret mode for the test suite;
`attention_auto` dispatches per backend."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
# Row statistics (max / sum / lse / delta) are carried with a 128-wide minor dim:
# Mosaic requires the last two dims of every block to tile onto (8, 128) lanes,
# so a [BLOCK_Q] column vector is broadcast across _LANES and read back from
# lane 0 (the official TPU flash kernel stores l/m the same way,
# jax/experimental/pallas/ops/tpu/flash_attention.py MIN_BLOCK_SIZE).
_LANES = 128
_NEG_INF = -1e30  # large-but-finite: keeps fully-masked rows NaN-free


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref, lse_ref, max_ref, sum_ref, acc_ref, *, seq_len: int, causal: bool
):
    """One (query block, KV block) grid step; carry persists in scratch refs."""
    q_index, kv_index = pl.program_id(1), pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_index == 0)
    def _init():
        max_ref[:] = jnp.full_like(max_ref, _NEG_INF)
        sum_ref[:] = jnp.zeros_like(sum_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_start = kv_index * BLOCK_K
    # in causal mode, blocks entirely above the diagonal contribute nothing
    block_needed = (not causal) or (kv_start <= q_index * BLOCK_Q + BLOCK_Q - 1)

    @pl.when(block_needed)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [BLOCK_Q, d]
        k = k_ref[0].astype(jnp.float32)  # [BLOCK_K, d]
        v = v_ref[0].astype(jnp.float32)
        scale = q.shape[-1] ** -0.5
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BLOCK_Q, BLOCK_K]
        # rank-2 iotas: Mosaic rejects rank-1 lax.iota (pallas_guide: common pitfalls)
        kv_positions = kv_start + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_Q, BLOCK_K), 1)
        mask = kv_positions < seq_len  # guard the tail-padding block
        if causal:
            q_positions = q_index * BLOCK_Q + jax.lax.broadcasted_iota(
                jnp.int32, (BLOCK_Q, BLOCK_K), 0
            )
            mask &= kv_positions <= q_positions
        scores = jnp.where(mask, scores, _NEG_INF)

        row_max = max_ref[:, 0]
        block_max = jnp.max(scores, axis=-1)
        new_max = jnp.maximum(row_max, block_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(scores - new_max[:, None])
        acc_ref[:] = acc_ref[:] * correction[:, None] + jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        new_sum = sum_ref[:, 0] * correction + jnp.sum(probs, axis=-1)
        sum_ref[:] = jnp.broadcast_to(new_sum[:, None], sum_ref.shape)
        max_ref[:] = jnp.broadcast_to(new_max[:, None], max_ref.shape)

    @pl.when(kv_index == num_kv - 1)
    def _finalize():
        out = acc_ref[:] / jnp.maximum(sum_ref[:, 0], 1e-30)[:, None]
        out_ref[0] = out.astype(out_ref.dtype)
        # log-sum-exp per query row: what ring attention needs to merge softmax
        # statistics across sequence shards without re-materializing the scores
        lse = max_ref[:, 0] + jnp.log(jnp.maximum(sum_ref[:, 0], 1e-30))
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


@partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_forward(q, k, v, causal: bool = False, interpret: bool = False):
    """q, k, v: [batch, seq, heads, head_dim] → context of the same shape."""
    batch, seq, heads, head_dim = q.shape

    def to_bh(x, block):  # [batch*heads, ceil(seq/block)*block, head_dim]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(batch * heads, seq, head_dim)
        pad = (-seq) % block
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    qb = to_bh(q, BLOCK_Q)
    kb, vb = to_bh(k, BLOCK_K), to_bh(v, BLOCK_K)
    out, lse = pl.pallas_call(
        partial(_flash_kernel, seq_len=seq, causal=causal),
        grid=(batch * heads, qb.shape[1] // BLOCK_Q, kb.shape[1] // BLOCK_K),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, head_dim), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, BLOCK_K, head_dim), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, BLOCK_K, head_dim), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_Q, head_dim), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, BLOCK_Q, _LANES), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, qb.shape[1], head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch * heads, qb.shape[1], _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, _LANES), jnp.float32),  # running row max
            pltpu.VMEM((BLOCK_Q, _LANES), jnp.float32),  # running row sum
            pltpu.VMEM((BLOCK_Q, head_dim), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qb, kb, vb)
    out = out[:, :seq].reshape(batch, heads, seq, head_dim)
    lse = lse[:, :seq, 0].reshape(batch, heads, seq)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def flash_attention_lse(q, k, v, causal: bool = False, interpret: bool = False):
    """Fused attention that ALSO returns the per-row log-sum-exp ([batch, heads,
    seq], fp32) — the statistic ring attention needs to merge shard outputs:
    ``merged = Σ_i out_i · exp(lse_i − logaddexp_i(lse))``. Forward-only (no
    custom_vjp): callers that differentiate wrap the whole construction (see
    `parallel.ring_attention.ring_flash_attention`)."""
    return _flash_forward(q, k, v, causal=causal, interpret=interpret)


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, kv_start, q_start, seq_len, causal):
    """Shared per-tile math of both backward kernels: recompute probabilities from
    the saved log-sum-exp and return (p, ds) for this (query, KV) tile pair."""
    q = q_ref[0].astype(jnp.float32)  # [BLOCK_Q, d]
    k = k_ref[0].astype(jnp.float32)  # [BLOCK_K, d]
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0]  # [BLOCK_Q] fp32 (lane 0 of the 128-wide carry)
    delta = delta_ref[0][:, 0]  # [BLOCK_Q] fp32, rowsum(dout * out)
    scale = q.shape[-1] ** -0.5
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    kv_positions = kv_start + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_Q, BLOCK_K), 1)
    mask = kv_positions < seq_len  # tail-padding guard; masked p underflows to 0
    if causal:
        q_positions = q_start + jax.lax.broadcasted_iota(jnp.int32, (BLOCK_Q, BLOCK_K), 0)
        mask &= kv_positions <= q_positions
    scores = jnp.where(mask, scores, _NEG_INF)
    p = jnp.exp(scores - lse[:, None])  # exact probs: lse already holds the row max
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return q, k, do, p, ds


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref, *, seq_len, causal
):
    """dQ pass: grid (batch·heads, q_blocks, kv_blocks) — for each query block,
    sweep KV blocks accumulating dQ = Σ dS·K in VMEM scratch."""
    q_index, kv_index = pl.program_id(1), pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(kv_index == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    kv_start = kv_index * BLOCK_K
    block_needed = (not causal) or (kv_start <= q_index * BLOCK_Q + BLOCK_Q - 1)

    @pl.when(block_needed)
    def _accumulate():
        _q, k, _do, _p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            kv_start=kv_start, q_start=q_index * BLOCK_Q, seq_len=seq_len, causal=causal,
        )
        dq_acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kv_index == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref, *, seq_len, causal
):
    """dK/dV pass: grid (batch·heads, kv_blocks, q_blocks) — for each KV block,
    sweep query blocks accumulating dV = Σ Pᵀ·dO and dK = Σ dSᵀ·Q in scratch."""
    kv_index, q_index = pl.program_id(1), pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(q_index == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    kv_start = kv_index * BLOCK_K
    # blocks strictly above the diagonal see no probability mass in causal mode
    block_needed = (not causal) or (q_index * BLOCK_Q + BLOCK_Q - 1 >= kv_start)

    @pl.when(block_needed)
    def _accumulate():
        q, _k, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            kv_start=kv_start, q_start=q_index * BLOCK_Q, seq_len=seq_len, causal=causal,
        )
        dv_acc_ref[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc_ref[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(q_index == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


@partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_backward(q, k, v, out, lse, grad_out, causal: bool = False, interpret: bool = False):
    """Fused two-pass flash backward from the saved (out, lse) residuals."""
    batch, seq, heads, head_dim = q.shape

    def to_bh(x, block):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(batch * heads, seq, head_dim)
        pad = (-seq) % block
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def from_bh(x):
        return jnp.transpose(x[:, :seq].reshape(batch, heads, seq, head_dim), (0, 2, 1, 3))

    qb, dob, outb = to_bh(q, BLOCK_Q), to_bh(grad_out, BLOCK_Q), to_bh(out, BLOCK_Q)
    kb, vb = to_bh(k, BLOCK_K), to_bh(v, BLOCK_K)
    padded_q = qb.shape[1]
    # delta_i = Σ_d dOut·Out — one elementwise reduce; padded rows are zero (dob
    # is zero-padded), so they contribute nothing to dK/dV in the sweep
    deltab = jnp.sum(dob.astype(jnp.float32) * outb.astype(jnp.float32), axis=-1)
    lseb = lse.reshape(batch * heads, seq)  # lse arrives as [batch, heads, seq]
    pad = padded_q - seq
    if pad:
        lseb = jnp.pad(lseb, ((0, 0), (0, pad)))
    # 128-lane broadcast of the row statistics (see _LANES)
    lseb = jnp.broadcast_to(lseb[:, :, None], (*lseb.shape, _LANES))
    deltab = jnp.broadcast_to(deltab[:, :, None], (*deltab.shape, _LANES))

    num_q, num_kv = padded_q // BLOCK_Q, kb.shape[1] // BLOCK_K
    q_spec = pl.BlockSpec((1, BLOCK_Q, head_dim), lambda bh, qi, ki: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, BLOCK_K, head_dim), lambda bh, qi, ki: (bh, ki, 0))
    row_spec = pl.BlockSpec((1, BLOCK_Q, _LANES), lambda bh, qi, ki: (bh, qi, 0))
    dq = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, seq_len=seq, causal=causal),
        grid=(batch * heads, num_q, num_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, BLOCK_Q, head_dim), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * heads, padded_q, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK_Q, head_dim), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lseb, deltab)
    # second pass: grid transposed — (bh, kv block, q block), q fastest-varying
    q_spec_t = pl.BlockSpec((1, BLOCK_Q, head_dim), lambda bh, ki, qi: (bh, qi, 0))
    kv_spec_t = pl.BlockSpec((1, BLOCK_K, head_dim), lambda bh, ki, qi: (bh, ki, 0))
    row_spec_t = pl.BlockSpec((1, BLOCK_Q, _LANES), lambda bh, ki, qi: (bh, qi, 0))
    dk, dv = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, seq_len=seq, causal=causal),
        grid=(batch * heads, num_kv, num_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t, row_spec_t],
        out_specs=[
            pl.BlockSpec((1, BLOCK_K, head_dim), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, BLOCK_K, head_dim), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, kb.shape[1], head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch * heads, kb.shape[1], head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((BLOCK_K, head_dim), jnp.float32),
            pltpu.VMEM((BLOCK_K, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb, dob, lseb, deltab)
    return from_bh(dq), from_bh(dk), from_bh(dv)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = False, interpret: bool = False):
    """Fused flash attention on [batch, seq, heads, head_dim] (full sequences; for
    padded batches use the mask-capable `plain_attention`). Backward is fused too
    (two-pass kernels from the saved log-sum-exp — see module docstring)."""
    return _flash_forward(q, k, v, causal=causal, interpret=interpret)[0]


def _flash_fwd(q, k, v, causal, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, interpret, residuals, grad_out):
    q, k, v, out, lse = residuals
    # lse back to [bh, seq] layout happens inside _flash_backward; reshape here
    # keeps residuals in the public [batch, seq, heads, dim] convention
    lse_bhs = lse  # [batch, heads, seq] as returned by _flash_forward
    return _flash_backward(
        q, k, v, out, lse_bhs, grad_out.astype(q.dtype), causal=causal, interpret=interpret
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _flash_enabled() -> bool:
    import os

    return os.environ.get("HIVEMIND_TPU_FLASH_ATTENTION", "1") == "1"


def _flash_forced() -> bool:
    """HIVEMIND_TPU_FORCE_FLASH=1 selects the flash kernels regardless of the
    CURRENT backend — for AOT workflows (jax.export platforms=["tpu"]) where the
    trace happens on a CPU host but the artifact targets a TPU."""
    import os

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
        and _flash_enabled()
    )


def attention_auto(q, k, v, mask=None, causal: bool = False):
    """Backend dispatch for the attention core on ONE device: fused Pallas kernel
    where `flash_applies` (both directions are fused kernels — set
    HIVEMIND_TPU_FLASH_ATTENTION=0 to force the einsum core for A/B runs),
    reference einsum path elsewhere. Operands sharded over a mesh go through
    `parallel.ring_attention.mesh_attention_core`, which runs the kernel per shard."""
    if flash_applies(q, k, mask):
        return flash_attention(q, k, v, causal)
    from hivemind_tpu.parallel.ring_attention import plain_attention

    return plain_attention(q, k, v, mask=mask, causal=causal)
