"""Int8 weight-only parameter storage for serving (BASELINE config #5, the
Petals-style block server: reference-era Petals serves Llama blocks with 8-bit
weights; here the storage codec is this repo's own blockwise absmax int8 —
`ops/pallas_quantization.py` on TPU, the fused jnp path on host).

A parameter pytree is converted leaf-by-leaf: float leaves above a size threshold
become :class:`QuantizedTensor` (int8 codes + per-block fp32 absmax, a registered
pytree node, 4x smaller resident than fp32), tiny leaves (norm scales, biases)
stay exact. ``dequantize_tree`` runs INSIDE the consumer's jit, so XLA keeps the
int8 resident in HBM and materializes bf16/fp32 weights transiently per use —
resident model memory divides by ~4 while matmuls still run on the MXU in bf16.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from hivemind_tpu.ops.pallas_quantization import (
    blockwise_dequantize_auto,
    blockwise_quantize_auto,
)

QUANT_BLOCK_SIZE = 4096
MIN_QUANT_SIZE = 4096  # leaves smaller than one block stay exact


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Blockwise-int8 weight: ``codes`` [n_blocks, block] int8 + ``absmax``
    [n_blocks] fp32, remembering the original shape/dtype/true size."""

    def __init__(self, codes, absmax, shape: Tuple[int, ...], dtype, size: int):
        self.codes, self.absmax = codes, absmax
        self.shape, self.dtype, self.size = tuple(shape), dtype, size

    def tree_flatten(self):
        return (self.codes, self.absmax), (self.shape, self.dtype, self.size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def nbytes(self) -> int:
        return int(self.codes.nbytes + self.absmax.nbytes)

    def dequantize(self, mesh=None, axis: Optional[str] = None):
        decode = lambda codes, absmax: blockwise_dequantize_auto(codes, absmax, QUANT_BLOCK_SIZE)
        if mesh is not None:
            rows = _row_axis(mesh, axis, self.codes.shape[0])
            decode = _per_shard(
                decode, mesh, in_specs=(PartitionSpec(rows, None), PartitionSpec(rows)),
                out_specs=PartitionSpec(rows),
            )
        flat = decode(self.codes, self.absmax)
        return flat[: self.size].reshape(self.shape).astype(self.dtype)

    def __repr__(self):
        return f"QuantizedTensor(shape={self.shape}, blocks={self.codes.shape[0]})"


def _is_quantized(leaf) -> bool:
    return isinstance(leaf, QuantizedTensor)


def _row_axis(mesh, axis: str, n_blocks: int) -> Optional[str]:
    """The mesh axis quantization blocks (rows) are distributed over, or None when
    the block count does not divide it (then every device holds, and works on,
    all rows)."""
    return axis if n_blocks % int(mesh.shape[axis]) == 0 else None


def _per_shard(fn, mesh, in_specs, out_specs):
    """Run ``fn`` once per device on that device's rows. Quantization blocks are
    independent, so this needs no communication — and it is the only way a Mosaic
    kernel runs on sharded operands at all: GSPMD cannot partition one, and jax
    refuses to lower a bare ``pallas_call`` whose operands are sharded."""
    from jax import shard_map

    # check_vma off: the varying-axes checker cannot see through pallas_call
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=8)
def _mesh_encoder(mesh, rows: Optional[str]):
    """The jitted per-shard encoder for blocks laid out ``[rows, None]`` on ``mesh``
    (cached so that loading many leaves compiles once per leaf shape, not per leaf)."""
    return jax.jit(
        _per_shard(
            lambda blocks: blockwise_quantize_auto(blocks.reshape(-1), QUANT_BLOCK_SIZE),
            mesh, in_specs=PartitionSpec(rows, None),
            out_specs=(PartitionSpec(rows, None), PartitionSpec(rows)),
        )
    )


def quantize_params(
    params: Any, min_size: int = MIN_QUANT_SIZE, mesh=None, axis: Optional[str] = None
) -> Any:
    """Float leaves with >= ``min_size`` elements become QuantizedTensor.

    With ``mesh``, every leaf goes from where it is (host memory for a checkpoint
    load) straight to its place on the mesh: blocks to be quantized are laid out
    row-sharded over ``axis`` and encoded per device, exact leaves replicate — no
    leaf ever exists whole on one device."""

    def convert(leaf):
        xp = np if isinstance(leaf, np.ndarray) else jnp
        # only float MATRICES quantize: 1-D leaves are norm scales/biases whose
        # exactness matters far more than their bytes (a 4096-wide RMSNorm scale
        # has size == one quant block, so a pure size test would catch it)
        if leaf.ndim < 2 or leaf.size < min_size or not jnp.issubdtype(leaf.dtype, jnp.floating):
            if mesh is None:
                return jnp.asarray(leaf)
            return jax.device_put(leaf, NamedSharding(mesh, PartitionSpec()))
        flat = xp.reshape(leaf, -1).astype(xp.float32)
        pad = (-flat.size) % QUANT_BLOCK_SIZE
        if pad:
            flat = xp.pad(flat, (0, pad))
        if mesh is None:
            codes, absmax = blockwise_quantize_auto(flat, QUANT_BLOCK_SIZE)
        else:
            blocks = xp.reshape(flat, (-1, QUANT_BLOCK_SIZE))
            rows = _row_axis(mesh, axis, blocks.shape[0])
            blocks = jax.device_put(blocks, NamedSharding(mesh, PartitionSpec(rows, None)))
            codes, absmax = _mesh_encoder(mesh, rows)(blocks)
        return QuantizedTensor(codes, absmax, leaf.shape, leaf.dtype, leaf.size)

    return jax.tree_util.tree_map(convert, params)


def dequantize_tree(params: Any, mesh=None, axis: Optional[str] = None) -> Any:
    """Materialize a quantized tree back to dense weights (call INSIDE jit).
    ``mesh``/``axis``: as given to `quantize_params`."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.dequantize(mesh, axis) if _is_quantized(leaf) else leaf,
        params,
        is_leaf=_is_quantized,
    )


def tree_param_bytes(params: Any) -> int:
    """Resident bytes of a (possibly quantized) parameter tree."""
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(params))
