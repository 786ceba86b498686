"""Ring attention: sequence/context parallelism over a mesh axis.

No reference equivalent (SURVEY §5: long-context is absent upstream; this is the
TPU-native capability layer). Keys/values rotate around the ``sp`` mesh axis via
``jax.lax.ppermute`` while each device keeps its local queries; softmax is merged
online (log-sum-exp carry), so memory stays O(seq_local²) and the full sequence never
materializes on one chip. Designed for use inside shard_map over a Mesh axis."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from hivemind_tpu.ops.attention import attention_auto, flash_applies

_NEG_INF = -1e30  # matches ops/pallas_attention: finite, so lse merges stay NaN-free


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str, causal: bool = False
) -> jax.Array:
    """Ring attention over the sequence-parallel mesh axis. All inputs are the
    LOCAL sequence shard: [batch, seq_local, heads, head_dim]. Must run inside
    shard_map with ``axis_name`` mapped over that axis.

    ``causal=True`` (decoder models): shards are contiguous sequence chunks in
    rank order, so the KV block received at ring step s originates from rank
    j = (i - s) mod P and contributes fully when j < i (every key precedes every
    local query), causally when j == i (the local diagonal block), and not at
    all when j > i (the whole block is in the future)."""
    axis_size = lax.psum(1, axis_name)
    my_rank = lax.axis_index(axis_name)
    batch, seq_local, heads, dim = q.shape
    # derive initial carries from q so they inherit its varying manual axes
    # (jax >= 0.9 shard_map rejects unvarying zeros as scan carries)
    zeros_bht = jnp.transpose(q[..., 0], (0, 2, 1)) * 0  # [B, H, T_local]
    row_max = zeros_bht - jnp.inf
    row_sum = zeros_bht
    acc = q * 0
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    tri = jnp.tril(jnp.ones((seq_local, seq_local), bool))  # loop-invariant

    def body(carry, step):
        k_cur, v_cur, row_max, row_sum, acc = carry
        scale = dim ** -0.5
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur) * scale
        if causal:
            source = (my_rank - step) % axis_size
            block_mask = (source < my_rank) | ((source == my_rank) & tri)
            scores = jnp.where(block_mask[None, None], scores, _NEG_INF)
        block_max = jnp.max(scores, axis=-1)
        new_max = jnp.maximum(row_max, block_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(scores - new_max[..., None])
        if causal:
            # a fully-masked block (future shard) leaves scores == new_max == NEG_INF
            # and exp(0) would contribute weight 1 — masked entries must stay 0
            probs = jnp.where(scores <= _NEG_INF / 2, 0.0, probs)
        acc_new = acc * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", probs, v_cur
        )
        row_sum_new = row_sum * correction + jnp.sum(probs, axis=-1)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, new_max, row_sum_new, acc_new), None

    (k_final, v_final, row_max, row_sum, acc), _ = lax.scan(
        body, (k, v, row_max, row_sum, acc), jnp.arange(axis_size)
    )
    return acc / row_sum.transpose(0, 2, 1)[..., None]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
    interpret: bool = False, causal: bool = False,
) -> jax.Array:
    """Ring attention with the Pallas flash kernel as the per-step core.

    Same contract as :func:`ring_attention` (incl. ``causal``), but each ring
    step runs the fused flash kernel (scores never leave VMEM) and the per-shard
    outputs are merged through their log-sum-exp statistics — peak memory drops
    from O(seq_local²) score blocks to O(seq_local·head_dim) accumulators, which
    is what makes long local shards viable. In causal mode the local (diagonal)
    block runs the kernel's causal path and future shards are excluded by
    forcing their lse to −∞ before the merge. Backward recomputes through the
    einsum ring (`jax.vjp(ring_attention)`), the same remat trade
    `flash_attention` makes on one chip."""
    return _ring_flash_forward(q, k, v, axis_name, interpret, causal)


def _ring_flash_forward(q, k, v, axis_name: str, interpret: bool, causal: bool):
    from hivemind_tpu.ops.pallas_attention import flash_attention_lse

    axis_size = lax.psum(1, axis_name)
    my_rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    # step 0 is always the LOCAL block (causal within it when causal=True — the
    # kernel's static causal flag cannot vary per scan step, so it runs outside)
    out_acc, lse_acc = flash_attention_lse(q, k, v, causal=causal, interpret=interpret)
    # accumulate in float32 regardless of the input dtype: the kernel's lse output
    # is float32, and lax.scan requires carry dtypes to be identical across steps
    # (bf16 inits would be promoted by the merge and fail tracing)
    out_acc = out_acc.astype(jnp.float32)
    k = lax.ppermute(k, axis_name, perm)
    v = lax.ppermute(v, axis_name, perm)

    def body(carry, step):
        k_cur, v_cur, out_acc, lse_acc = carry
        out_i, lse_i = flash_attention_lse(q, k_cur, v_cur, interpret=interpret)
        out_i = out_i.astype(jnp.float32)
        if causal:
            # source rank of this block; future shards contribute nothing
            source = (my_rank - step) % axis_size
            lse_i = jnp.where(source > my_rank, _NEG_INF, lse_i)
        new_lse = jnp.logaddexp(lse_acc, lse_i)
        w_old = jnp.exp(lse_acc - new_lse)
        w_new = jnp.exp(lse_i - new_lse)
        out_acc = (
            out_acc * jnp.transpose(w_old, (0, 2, 1))[..., None]
            + out_i * jnp.transpose(w_new, (0, 2, 1))[..., None]
        )
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, out_acc, new_lse), None

    if axis_size > 1:
        (_, _, out_acc, _), _ = lax.scan(
            body, (k, v, out_acc, lse_acc), jnp.arange(1, axis_size)
        )
    return out_acc.astype(q.dtype)


def _ring_flash_fwd(q, k, v, axis_name, interpret, causal):
    return _ring_flash_forward(q, k, v, axis_name, interpret, causal), (q, k, v)


def _ring_flash_bwd(axis_name, interpret, causal, residuals, grad_out):
    q, k, v = residuals
    _, vjp = jax.vjp(partial(ring_attention, axis_name=axis_name, causal=causal), q, k, v)
    return vjp(grad_out.astype(q.dtype))


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def mesh_attention_core(mesh, q, k, v, mask=None, causal: bool = False):
    """The shared attention dispatch for mesh-aware models. On a mesh of more than
    one device the fused kernel runs PER SHARD under shard_map (batch over ``dp``,
    heads over ``tp``): Mosaic kernels cannot be partitioned by GSPMD — jax refuses
    to lower a bare ``pallas_call`` on sharded operands ("Mosaic kernels cannot be
    automatically partitioned"). With ``sp`` > 1 the per-shard core is the
    (flash-)ring over the sequence axis. Without a mesh, with one device, or when
    the einsum core is selected, `attention_auto` decides (XLA partitions the
    einsum core by itself). ``mask`` (key-validity) is only supported off the
    ring: ring shards carry full sequences."""
    ring = mesh is not None and mesh.shape.get("sp", 1) > 1
    flash = flash_applies(q, k, mask)
    if ring:
        assert mask is None, "ring attention shards carry full sequences (no padding mask)"
        if flash:
            # flash core per ring step: scores stay in VMEM, shard outputs merge
            # via log-sum-exp
            def inner(q, k, v):
                return ring_flash_attention(q, k, v, "sp", False, causal)

        else:
            inner = partial(ring_attention, axis_name="sp", causal=causal)
    elif mesh is not None and mesh.size > 1 and flash:
        from hivemind_tpu.ops.pallas_attention import flash_attention

        def inner(q, k, v):
            return flash_attention(q, k, v, causal)

    else:
        return attention_auto(q, k, v, mask=mask, causal=causal)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(
        "dp" if "dp" in mesh.shape else None,
        "sp" if ring else None,
        "tp" if mesh.shape.get("tp", 1) > 1 else None,
        None,
    )
    # check_vma off for the flash cores: the varying-axes checker cannot see
    # through pallas_call outputs
    core = shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=not flash
    )
    return core(q, k, v)
