"""ModuleBackend: one expert = a flax module + optax optimizer behind jitted apply
functions (capability parity: reference hivemind/moe/server/module_backend.py:19-200).

TPU-first: instead of the reference's dynamic torch batches, inputs are padded to
power-of-two buckets so XLA compiles one executable per bucket; backward re-derives
the forward under jax.vjp and applies the optimizer update in the same jitted call
(the reference's on_backward semantics, module_backend.py:156-165).

A backend has two levels of entry. `forward_on_device` / `backward_on_device` take
and return device arrays of one bucket and wait for nothing; `forward` / `backward`
are the numpy entry points around them (stage in, the device-level call, fetch and
slice). A span request walks several co-located backends between ONE stage-in and
ONE fetch (`forward_chain` / `backward_chain`, at the end of this file): the numpy
entry points are chains of one."""

from __future__ import annotations

import contextlib
import functools
import threading
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hivemind_tpu.compression import CompressionType
from hivemind_tpu.moe.server.routing_stats import ROUTING_COLLECTION, held_range, record_routing
from hivemind_tpu.telemetry.device import record_transfer
from hivemind_tpu.telemetry.serving import accrue_span_phase
from hivemind_tpu.telemetry.tracing import current_span, trace_sync as _trace_sync
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.profiling import tracked_jit
from hivemind_tpu.utils.tensor_descr import BatchTensorDescriptor

logger = get_logger(__name__)


@contextlib.contextmanager
def _staging(name: str):
    """A host<->device staging span of one batch (`backend.stage_in`, `backend.fetch`);
    its seconds accrue as ``stage_s`` onto the span around it — the TaskPool's
    ``pool.batch``, which hands them to the batch's requests."""
    with _trace_sync(name) as span:
        yield
    if span is not None:  # accrued after the span closed: onto its parent
        accrue_span_phase("stage_s", span.duration)


def bucket_batch_size(n: int, max_batch_size: int) -> int:
    """Next power of two ≥ n (capped): static shapes for XLA."""
    bucket = 1
    while bucket < n:
        bucket *= 2
    return min(bucket, max(max_batch_size, n))


class ModuleBackend:
    """See module docstring: one expert's weights, optimizer state and jitted
    programs; the unit that a span chain walks on the device.

    :param module: a flax module; __call__ may take SEVERAL input arrays and return
        one array or a tuple of arrays (nested expert schemas, reference
        module_backend.py:68-74)
    :param optimizer: optax transformation applied on every backward batch
    :param sample_input: schema-defining input WITH batch dim (single-input experts)
    :param sample_inputs: schema-defining inputs for multi-input experts
    :param weight_quantization: ``"int8"`` stores the expert's weights with the
        repo's blockwise absmax codec (4x less resident memory; dense bf16/fp32
        weights are materialized transiently inside the jit). Serving-only: the
        backend refuses backward calls (the Petals-style Llama-7B block server of
        BASELINE config #5 serves frozen pretrained blocks).
    """

    def __init__(
        self,
        name: str,
        module,
        *,
        optimizer,
        sample_input: Optional[np.ndarray] = None,
        sample_inputs: Optional[Sequence[np.ndarray]] = None,
        max_batch_size: int = 4096,
        rng_seed: int = 0,
        weight_quantization: Optional[str] = None,
    ):
        assert (sample_input is None) != (sample_inputs is None), (
            "provide exactly one of sample_input / sample_inputs"
        )
        if sample_inputs is None:
            sample_inputs = (sample_input,)
        assert weight_quantization in (None, "int8"), weight_quantization
        self.name, self.module, self.optimizer = name, module, optimizer
        self.max_batch_size = max_batch_size
        self.weight_quantization = weight_quantization
        from hivemind_tpu.ops.quantized_params import dequantize_tree, quantize_params

        placement = self._codec_placement()
        self._quantize = functools.partial(quantize_params, **placement)
        # the dense weights of a (possibly int8-stored) parameter tree — traced INSIDE
        # the serving jits (forward here, the decode-session steps), so the dense
        # copies are transient; identity for plain trees
        self.dense_params = dense_params = functools.partial(dequantize_tree, **placement)
        samples = tuple(jnp.asarray(np.asarray(s)[:1]) for s in sample_inputs)
        self.params, self.opt_state = self._init_state(samples, rng_seed)
        self._state_lock = threading.Lock()
        self.update_count = 0

        # shapes only: nothing runs, so a mesh backend needs no eager shard_map and
        # no backend pays a whole forward pass to learn its output schema
        sample_out = jax.eval_shape(module.apply, {"params": self.params}, *samples)
        if weight_quantization is not None:
            self.params = self._quantize(self.params)
        outs = tuple(sample_out) if isinstance(sample_out, (tuple, list)) else (sample_out,)
        self.num_inputs, self.num_outputs = len(samples), len(outs)
        outputs_are_tuple = isinstance(sample_out, (tuple, list))
        self.forward_schema = tuple(
            BatchTensorDescriptor.from_array(np.asarray(s)) for s in sample_inputs
        )
        self.outputs_schema = tuple(BatchTensorDescriptor.from_array(o) for o in outs)

        def _as_tuple(value):
            return tuple(value) if isinstance(value, (tuple, list)) else (value,)

        # tracked_jit (ISSUE 19): per-bucket compiles show up on the compile
        # tracker (sites are fixed strings — expert names would explode label
        # cardinality; the signature on the compile record carries the shape).
        # Neither closure may capture ``self``: a jitted function is a C++ object
        # the garbage collector does not look through, so the cycle backend ->
        # jitted closure -> backend would pin the expert's weights on the device
        # for the life of the process. Both hand out what the module sowed into
        # ROUTING_COLLECTION (a sparse expert layer's chosen experts; empty otherwise).
        @tracked_jit(site="module_backend.forward")
        def _forward(params, *xs):
            out, routing = module.apply({"params": dense_params(params)}, *xs, mutable=[ROUTING_COLLECTION])
            return _as_tuple(out), routing

        @tracked_jit(site="module_backend.backward")
        def _backward(params, opt_state, xs, grad_outs):
            import optax

            apply = lambda p, xx: module.apply({"params": p}, *xx, mutable=[ROUTING_COLLECTION])
            out, vjp, routing = jax.vjp(apply, params, tuple(xs), has_aux=True)
            cotangent = _as_tuple(grad_outs) if outputs_are_tuple else grad_outs[0]
            grad_params, grad_xs = vjp(cotangent)
            updates, new_opt_state = optimizer.update(grad_params, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return grad_xs, new_params, new_opt_state, routing

        self._jit_forward, self._jit_backward = _forward, _backward

    # ------------------------------------------------------------------ execution

    def _init_state(self, samples, rng_seed: int):
        """Create (params, opt_state); subclasses control placement (the mesh
        backend lands state directly under its shardings)."""
        params = self.module.init(jax.random.PRNGKey(rng_seed), *samples)["params"]
        opt_state = self.optimizer.init(params) if self.weight_quantization is None else None
        return params, opt_state

    def _codec_placement(self) -> Dict[str, Any]:
        """Where the int8 codec runs, as keyword arguments of `quantize_params` /
        `dequantize_tree`: nothing to say on one device; a mesh backend names its
        mesh, as it controls placement in `_init_state`."""
        return {}

    def snapshot_params(self):
        """The current parameter pytree under the state lock (for read-only use by
        auxiliary executors, e.g. decode sessions)."""
        with self._state_lock:
            return self.params

    def load_params(self, params) -> None:
        """Replace the expert's weights (e.g. with a pretrained checkpoint's). The
        tree must match the init schema. Quantized backends re-encode to int8;
        trainable ones restart optimizer statistics for the new weights."""
        with self._state_lock:
            if self.weight_quantization is not None:
                self.params = self._quantize(params)
            else:
                self.params = jax.tree_util.tree_map(jnp.asarray, params)
                self.opt_state = self.optimizer.init(self.params)

    def param_bytes(self) -> int:
        """Resident bytes of this expert's weights (int8 codes count, not the
        transient dense copies) — the HBM budgeting input."""
        from hivemind_tpu.ops.quantized_params import tree_param_bytes

        with self._state_lock:
            return tree_param_bytes(self.params)

    def check_trainable(self) -> None:
        if self.weight_quantization is not None:
            raise RuntimeError(
                f"expert {self.name!r} serves int8 weight-only (inference-only): "
                f"backward/training is not supported on quantized weights"
            )

    def forward_on_device(self, *xs):
        """The jitted forward on device arrays of one bucket: dispatched, not waited
        for. Returns (outputs, routing), both still on the device."""
        return self._jit_forward(self.snapshot_params(), *xs)

    def backward_on_device(self, xs, grad_outs):
        """The jitted backward on device arrays of one bucket, dispatched and not
        waited for; the expert's parameters and optimizer state are swapped for the
        program's new ones and ``update_count`` steps, under the state lock.
        Returns (input gradients, routing), both still on the device."""
        self.check_trainable()
        with self._state_lock:
            grad_xs, new_params, new_opt_state, routing = self._jit_backward(
                self.params, self.opt_state, tuple(xs), tuple(grad_outs)
            )
            self.params, self.opt_state = new_params, new_opt_state
            self.update_count += 1
        return grad_xs, routing

    def forward(self, *inputs: np.ndarray) -> List[np.ndarray]:
        """Inference on a concatenated batch (no parameter updates): a chain of one."""
        return forward_chain((self,), *inputs)

    def backward(self, *tensors: np.ndarray) -> List[np.ndarray]:
        """Gradients wrt every input; ALSO applies one optimizer update to the expert
        (reference on_backward: the server trains on every backward call).
        ``tensors`` = the forward inputs followed by one grad per output. A chain of one."""
        return backward_chain((self,), *tensors)

    # ------------------------------------------------------------------ metadata/state

    def get_info(self) -> Dict[str, Any]:
        return dict(
            forward_schema=list(self.forward_schema),
            outputs_schema=list(self.outputs_schema),
            max_batch_size=self.max_batch_size,
            updates=self.update_count,
        )

    def state_dict(self) -> bytes:
        import flax.serialization

        with self._state_lock:
            # quantized backends serialize the dense form (msgpack cannot carry the
            # QuantizedTensor nodes); load_state_dict re-encodes, so the round-trip
            # is exact for int8 serving
            return flax.serialization.to_bytes(
                {
                    "params": self._dense_snapshot(),
                    "opt_state": self.opt_state if self.opt_state is not None else {},
                    "updates": self.update_count,
                }
            )

    def _dense_snapshot(self):
        """Dense weights for (de)serialization — one jitted program, so a mesh
        backend's per-shard decoders compile together. Rare (checkpoint / replica
        transfer), hence no compile tracking."""
        if self.weight_quantization is None:
            return self.params
        return jax.jit(self.dense_params)(self.params)  # lint: allow(jit-in-hot-path)

    def load_state_dict(self, blob: bytes) -> None:
        import flax.serialization

        with self._state_lock:
            template = {
                "params": self._dense_snapshot(),
                "opt_state": self.opt_state if self.opt_state is not None else {},
                "updates": 0,
            }
            restored = flax.serialization.from_bytes(template, blob)
            if self.weight_quantization is not None:
                self.params = self._quantize(restored["params"])
            else:
                self.params = restored["params"]
                self.opt_state = restored["opt_state"]
            self.update_count = int(restored["updates"])


# ---------------------------------------------------------------------- span chains
#
# A request names a chain of co-located blocks (one block is a chain of one). Its
# tensors cross to the device once, every block's program runs on the previous
# block's output where it lies, and the chain's result crosses back once. Activations
# stay float32 between blocks and every block runs the very jits that its numpy entry
# points run, so a client receives what per-block calls would have sent. Rows that
# only pad the bucket are zeros on the way in and carry whatever the blocks make of
# them from there on: no live row reads them, and their output gradients are zero at
# every block, so no optimizer step does either.

# How many programs a forward sweep may have dispatched beyond the one the device is
# running. One keeps the device fed through the host's dispatch; more would only hold
# more programs' outputs and temporaries at once. The reverse sweep runs none ahead:
# it waits for a block's input gradient, and with it for the block's new parameters
# (handed back beside the old ones), before it dispatches the block before, so a walk
# holds what a per-block call holds plus the saved block inputs. One ahead there read
# the same memory peak and the same rate on the chip (PERF.md §6, PR 30): not taken.
_RUN_AHEAD = 1


def _stage_in(tensors: Sequence[np.ndarray], max_batch_size: int) -> Tuple[List[jnp.ndarray], int]:
    """Widen to float32, pad the rows to their bucket and hand over for upload;
    counts the bytes that cross. Returns the device arrays and the live rows."""
    staged, rows = [], tensors[0].shape[0]
    bucket = bucket_batch_size(rows, max_batch_size)
    for tensor in tensors:
        batch = np.asarray(tensor, np.float32)
        if bucket != rows:
            batch = np.pad(batch, [(0, bucket - rows)] + [(0, 0)] * (batch.ndim - 1))
        staged.append(jnp.asarray(batch))
    record_transfer(sum(int(x.nbytes) for x in staged), "host_to_device")
    return staged, rows


def _fetch(arrays, rows: int) -> List[np.ndarray]:
    with _staging("backend.fetch"):
        results = [np.asarray(a)[:rows] for a in arrays]
    record_transfer(sum(r.nbytes for r in results), "device_to_host")
    return results


def _dispatched(in_flight: Deque, outputs) -> None:
    """``outputs`` belong to the forward program just dispatched: wait until the
    device is at most `_RUN_AHEAD` programs behind it."""
    in_flight.append(outputs)
    while len(in_flight) > _RUN_AHEAD:
        jax.block_until_ready(in_flight.popleft())


def forward_chain(backends: Sequence[ModuleBackend], *inputs: np.ndarray) -> List[np.ndarray]:
    """``inputs`` through every block of ``backends`` in turn: one upload, one
    program a block, one fetch. Under the `pool.batch` span around it: one
    `backend.stage_in`, one `backend.device` a block (the dispatch of its program and
    the wait for the program before it; for the last block also the wait for the
    chain's end), one `backend.fetch`."""
    assert len(inputs) == backends[0].num_inputs, (len(inputs), backends[0].num_inputs)
    with _staging("backend.stage_in"):
        current, rows = _stage_in(inputs, min(b.max_batch_size for b in backends))
    in_flight, routings, helds = deque(), [], []
    for backend in backends:
        with _trace_sync("backend.device", uid=backend.name):
            current, routing = backend.forward_on_device(*current)
            routings.append(routing)
            helds.append(held_range(backend.module))
            _dispatched(in_flight, current)
            if backend is backends[-1]:
                jax.block_until_ready(current)
    results = _fetch(current, rows)
    record_routing(routings, "pool", current_span(), rows=rows, held=helds)  # onto the pool.batch span around this call
    return results


def backward_chain(backends: Sequence[ModuleBackend], *tensors: np.ndarray) -> List[np.ndarray]:
    """Gradients wrt the chain's inputs, and one optimizer step of every block.
    ``tensors`` = the first block's inputs followed by one gradient per output of the
    last. A forward sweep over all blocks but the last keeps each block's input on the
    device; the reverse sweep runs each block's backward on it, waits for the input
    gradient and hands it on. Every block steps under its own lock, last block first,
    as per-block calls would. A block that fails ends the walk there: the blocks behind
    it in the chain have stepped, it and the blocks before it have not."""
    num_inputs = backends[0].num_inputs
    assert len(tensors) == num_inputs + backends[-1].num_outputs, (
        len(tensors), num_inputs, backends[-1].num_outputs,
    )
    for backend in backends:  # before any block has stepped
        backend.check_trainable()
    with _staging("backend.stage_in"):
        max_batch_size = min(b.max_batch_size for b in backends)
        current, rows = _stage_in(tensors[:num_inputs], max_batch_size)
        grads, _ = _stage_in(tensors[num_inputs:], max_batch_size)
    in_flight, routings, helds, block_inputs = deque(), [], [], []
    for backend in backends[:-1]:
        block_inputs.append(current)
        with _trace_sync("backend.device", uid=backend.name, sweep="forward"):
            current, routing = backend.forward_on_device(*current)
            routings.append(routing)
            helds.append(held_range(backend.module))
            _dispatched(in_flight, current)
    block_inputs.append(current)
    for backend in reversed(backends):
        with _trace_sync("backend.device", uid=backend.name, sweep="backward"):
            grads, routing = backend.backward_on_device(block_inputs.pop(), grads)
            routings.append(routing)
            helds.append(held_range(backend.module))
            jax.block_until_ready(grads)
    results = _fetch(grads, rows)
    record_routing(routings, "pool", current_span(), rows=rows, held=helds)
    return results
