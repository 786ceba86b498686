"""ICI tier of the two-tier communication backend (SURVEY §5): one device mesh is ONE
logical swarm peer.

The reference's hot loop reduces tensor parts with in-place host arithmetic on a single
machine (reference hivemind/averaging/partition.py:242-260, ``add_``/``div_``). On TPU
the intra-peer half of that reduction belongs ON the mesh: per-replica values are
reduced with ``jax.lax.pmean`` (an ICI psum) under ``shard_map``, then every leaf is
assembled on the host SHARD BY SHARD — each distinct region is pulled from exactly one
device with async DMAs and written straight into a preallocated mirror, so neither the
device (no replicated resharding) nor the host (no transient second copy) ever holds
more than one model copy plus one in-flight shard. The swarm (internet) tier then
averages those host mirrors across peers; the result is scattered back onto the mesh
one leaf at a time (each device receives only its shard).

Two entry points:

- :class:`MeshTensorBridge` — the device↔host boundary: ``mesh_mean`` (on-device psum
  reduction over one mesh axis), ``stage_into_mirrors``/``gather_to_host`` (shard-wise
  device→host assembly), ``scatter_leaf``/``scatter_from_host`` (host → original
  shardings).
- :class:`hivemind_tpu.averaging.ici.MeshAverager` — a DecentralizedAverager whose
  local tensors live sharded on a mesh and cross the host boundary only per round.
"""

from __future__ import annotations

from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _leaf_spec(leaf) -> P:
    sharding = getattr(leaf, "sharding", None)
    if isinstance(sharding, NamedSharding):
        return sharding.spec
    return P()


class MeshTensorBridge:
    """Device↔host staging for one mesh-resident logical peer. jit-compiled transfer
    functions are cached per (treedef, shapes/dtypes/specs) signature so steady-state
    rounds pay zero retracing."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._fn_cache: dict = {}

    # ---------------------------------------------------------------- on-device reduce

    def mesh_mean(self, stacked_tree: Any, axis: str = "dp") -> Any:
        """Reduce per-replica values across one mesh axis WITHOUT leaving the device.

        Each leaf must have leading dimension ``mesh.shape[axis]`` sharded over
        ``axis`` (the jax representation of "every replica holds its own copy").
        Returns the tree with the leading axis reduced away — the mean runs as a
        ``psum`` over ICI under ``shard_map``, the TPU-native equivalent of the
        reference's host-side accumulate/divide loop (partition.py:242-260)."""
        leaves, treedef = jax.tree_util.tree_flatten(stacked_tree)
        axis_size = self.mesh.shape[axis]
        in_specs, out_specs = [], []
        for leaf in leaves:
            if leaf.ndim < 1 or leaf.shape[0] != axis_size:
                raise ValueError(
                    f"mesh_mean leaf {leaf.shape} lacks leading {axis}-dim of {axis_size}"
                )
            spec = _leaf_spec(leaf)
            rest = tuple(spec)[1:] if len(spec) else ()
            in_specs.append(P(axis, *rest))
            out_specs.append(P(*rest))
        in_specs = jax.tree_util.tree_unflatten(treedef, in_specs)
        out_specs = jax.tree_util.tree_unflatten(treedef, out_specs)

        key = ("mean", axis, treedef, tuple((l.shape, str(l.dtype), str(_leaf_spec(l))) for l in leaves))
        fn = self._fn_cache.get(key)
        if fn is None:

            def _reduce(tree):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(jnp.squeeze(x, axis=0), axis), tree
                )

            fn = jax.jit(
                shard_map(_reduce, mesh=self.mesh, in_specs=(in_specs,), out_specs=out_specs)
            )
            self._fn_cache[key] = fn
        return fn(stacked_tree)

    def _mesh_mean_leaf(self, leaf, axis: str):
        """Per-leaf variant of ``mesh_mean``: reduce ONE leaf's leading per-replica
        dimension on device. Used by the streaming staging path so the whole
        reduced tree is never materialized at once (peak transient = one leaf)."""
        axis_size = self.mesh.shape[axis]
        if leaf.ndim < 1 or leaf.shape[0] != axis_size:
            raise ValueError(f"leaf {leaf.shape} lacks leading {axis}-dim of {axis_size}")
        spec = _leaf_spec(leaf)
        rest = tuple(spec)[1:] if len(spec) else ()
        key = ("mean_leaf", axis, leaf.shape, str(leaf.dtype), str(spec))
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = self._fn_cache[key] = jax.jit(
                shard_map(
                    lambda x: jax.lax.pmean(jnp.squeeze(x, axis=0), axis),
                    mesh=self.mesh,
                    in_specs=(P(axis, *rest),),
                    out_specs=P(*rest),
                )
            )
        return fn(leaf)

    def stage_reduced_into_mirrors(
        self, tree: Any, mirrors: Sequence[np.ndarray], reduce_axis: Optional[str] = None
    ) -> None:
        """STREAMING stage: optionally reduce each leaf over ``reduce_axis`` and
        assemble it into its host mirror ONE LEAF AT A TIME, freeing the reduced
        transient before the next leaf. Peak memory beyond the persistent model +
        mirrors is a single reduced leaf — this is what keeps a steady-state
        averaging round's RSS growth bounded by the mirrors, not another model copy
        (VERDICT r3 #4; device↔host analog of the reference's 512 KiB part
        streaming, hivemind/averaging/partition.py:104-112).

        Collective on a multi-process mesh (the per-leaf reduce and the replication
        fallback are jax collectives): every process must call it in the same order."""
        leaves, _ = jax.tree_util.tree_flatten(tree)
        assert len(leaves) == len(mirrors), (len(leaves), len(mirrors))
        for leaf, mirror in zip(leaves, mirrors):
            reduced = self._mesh_mean_leaf(leaf, reduce_axis) if reduce_axis is not None else leaf
            self.stage_into_mirrors([reduced], [mirror])
            if reduced is not leaf:
                reduced.delete()  # free the on-device transient before the next leaf

    # ---------------------------------------------------------------- host boundary

    @staticmethod
    def _unique_shards(leaf) -> list:
        """The addressable shards covering the array once: replicated dims make
        several devices hold identical shards — pull each distinct region from one
        device only, so host traffic equals the array size, not the device count."""
        seen, unique = set(), []
        for shard in leaf.addressable_shards:
            key = tuple((s.start, s.stop, s.step) for s in shard.index)
            if key not in seen:
                seen.add(key)
                unique.append(shard)
        return unique

    def stage_into_mirrors(self, tree: Any, mirrors: Sequence[np.ndarray]) -> None:
        """Assemble every leaf DIRECTLY into its preallocated host mirror, one
        shard at a time: no on-device resharding (a replicated gather would cost a
        full model replica of HBM **per device**) and no second host copy (peak
        host memory = the mirrors + one in-flight shard). Leaf ``i+1``'s
        device→host DMAs are started asynchronously while leaf ``i`` assembles, so
        the transfer pipeline stays full. This is the device↔host analog of the
        reference's 512 KiB part streaming (hivemind/averaging/partition.py:104-112);
        here the natural chunk is the device shard."""
        leaves, _ = jax.tree_util.tree_flatten(tree)
        assert len(leaves) == len(mirrors), (len(leaves), len(mirrors))
        if not all(getattr(leaf, "is_fully_addressable", True) for leaf in leaves):
            # multi-process mesh: some shards live on other hosts' devices, so a
            # shard pull cannot cover the mirror. Replicate ONE LEAF AT A TIME on
            # device (transient HBM = one leaf per device, never a model copy) and
            # read the now-local copy. See averaging/ici.py multi-host notes.
            self._stage_with_per_leaf_replication(leaves, mirrors)
            return
        shard_lists = [self._unique_shards(leaf) for leaf in leaves]
        for shard in shard_lists[0] if shard_lists else []:
            shard.data.copy_to_host_async()
        for index, (leaf, mirror) in enumerate(zip(leaves, mirrors)):
            if index + 1 < len(leaves):
                for shard in shard_lists[index + 1]:
                    shard.data.copy_to_host_async()
            out = mirror.reshape(leaf.shape)  # view (mirrors are C-contiguous)
            if not shard_lists[index]:  # zero-size leaf
                continue
            for shard in shard_lists[index]:
                out[shard.index] = np.asarray(shard.data).astype(out.dtype, copy=False)

    def _stage_with_per_leaf_replication(self, leaves: Sequence[Any], mirrors: Sequence[np.ndarray]) -> None:
        """Multi-host staging path: a collective (all processes must call this in
        the same order) per-leaf replicate-and-read. Bounded: unlike the old
        whole-tree replicated gather, at most one leaf is replicated at a time."""
        replicated = NamedSharding(self.mesh, P())
        key = ("replicate_one",)
        fn = self._fn_cache.get(key)
        if fn is None:
            fn = self._fn_cache[key] = jax.jit(
                lambda x: x.astype(jnp.float32), out_shardings=replicated
            )
        for leaf, mirror in zip(leaves, mirrors):
            full = fn(leaf)
            shard = next(iter(full.addressable_shards))  # replicated: any local device
            mirror.reshape(leaf.shape)[...] = np.asarray(shard.data)
            full.delete()  # free the replicated copy before the next leaf

    def allocate_mirrors(self, tree: Any) -> List[np.ndarray]:
        """Fresh fp32 host mirrors shaped like the tree's leaves."""
        leaves, _ = jax.tree_util.tree_flatten(tree)
        return [np.empty(leaf.shape, np.float32) for leaf in leaves]

    def allocate_reduced_mirrors(self, tree: Any, reduce_axis: Optional[str] = None) -> List[np.ndarray]:
        """Mirrors shaped like the tree's leaves AFTER the per-replica reduction
        (leading axis dropped), computed without materializing the reduced tree."""
        leaves, _ = jax.tree_util.tree_flatten(tree)
        return [
            np.empty(leaf.shape[1:] if reduce_axis is not None else leaf.shape, np.float32)
            for leaf in leaves
        ]

    def gather_reduced_to_host(self, tree: Any, reduce_axis: Optional[str] = None) -> List[np.ndarray]:
        """Streaming equivalent of ``gather_to_host(mesh_mean(tree))``: the reduced
        tree is never materialized whole (one leaf in flight)."""
        mirrors = self.allocate_reduced_mirrors(tree, reduce_axis)
        self.stage_reduced_into_mirrors(tree, mirrors, reduce_axis=reduce_axis)
        return mirrors

    def gather_to_host(self, tree: Any) -> List[np.ndarray]:
        """Full fp32 host copies of every leaf, assembled shard-by-shard (see
        ``stage_into_mirrors`` — no on-device replication happens)."""
        mirrors = self.allocate_mirrors(tree)
        self.stage_into_mirrors(tree, mirrors)
        return mirrors

    def scatter_leaf(self, like_leaf, host_value: np.ndarray, stack_axis_size: Optional[int] = None):
        """Push ONE host value back to the mesh with ``like_leaf``'s sharding and
        dtype. With ``stack_axis_size``, ``host_value`` is the reduced (unstacked)
        value and every replica row adopts it via a broadcast VIEW — the stacked
        array is never materialized on host."""
        value = np.asarray(host_value, dtype=like_leaf.dtype)
        if stack_axis_size is not None:
            value = np.broadcast_to(
                value.reshape(like_leaf.shape[1:]), tuple(like_leaf.shape)
            )
        else:
            value = value.reshape(like_leaf.shape)
        sharding = getattr(like_leaf, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            return jnp.asarray(value)
        if getattr(like_leaf, "is_fully_addressable", True):
            return jax.device_put(value, sharding)
        # multi-process mesh: device_put cannot target other hosts' devices. Every
        # process holds the SAME host value (guaranteed by the slice protocol's
        # broadcast); each one uploads its local shards and the global array is
        # assembled from them (the documented multi-host construction path).
        index_map = sharding.addressable_devices_indices_map(tuple(value.shape))
        locals_ = [
            jax.device_put(np.ascontiguousarray(value[index]), device)
            for device, index in index_map.items()
        ]
        return jax.make_array_from_single_device_arrays(tuple(value.shape), sharding, locals_)

    def scatter_from_host(self, like_tree: Any, host_tensors: Sequence[np.ndarray]) -> Any:
        """Push host values back onto the mesh with ``like_tree``'s shardings and
        dtypes (one device_put per leaf; each device receives only its shard)."""
        leaves, treedef = jax.tree_util.tree_flatten(like_tree)
        assert len(leaves) == len(host_tensors), (len(leaves), len(host_tensors))
        new_leaves = [
            self.scatter_leaf(leaf, host) for leaf, host in zip(leaves, host_tensors)
        ]
        return jax.tree_util.tree_unflatten(treedef, new_leaves)

    def broadcast_scatter_from_host(
        self, like_stacked_tree: Any, host_tensors: Sequence[np.ndarray], axis: str = "dp"
    ) -> Any:
        """Scatter reduced host values back to a per-replica stacked tree: every
        replica along ``axis`` adopts the (swarm-averaged) value."""
        leaves, treedef = jax.tree_util.tree_flatten(like_stacked_tree)
        axis_size = self.mesh.shape[axis]
        stacked = [
            np.broadcast_to(
                np.asarray(h, dtype=l.dtype).reshape(l.shape[1:]), (axis_size,) + tuple(l.shape[1:])
            )
            for l, h in zip(leaves, host_tensors)
        ]
        return self.scatter_from_host(like_stacked_tree, stacked)
