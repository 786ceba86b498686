"""Gradient accumulation + swarm averaging (capability parity: reference
hivemind/optim/grad_averager.py).

jax-first design: gradients arrive as pytrees/lists of jax arrays from the user's
jitted step; accumulators are HOST buffers (network-adjacent — all-reduce data must
reach the host anyway), so accumulate is a device→host add, not a torch .grad swap.
Three buffer roles as in the reference (grad_averager.py:23-29): live gradients
(user's), local accumulators, and the averager's shared averaged-gradient tensors."""

from __future__ import annotations

import contextlib
from typing import Iterable, Iterator, List, Optional, Sequence

import numpy as np

from hivemind_tpu.averaging.averager import DecentralizedAverager
from hivemind_tpu.averaging.control import StepControl
from hivemind_tpu.compression.base import as_numpy
from hivemind_tpu.dht import DHT
from hivemind_tpu.telemetry.tracing import trace_sync as _sync_span
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.timed_storage import DHTExpiration, get_dht_time

logger = get_logger(__name__)


class GradientAverager(DecentralizedAverager):
    """Accumulates local gradients toward a virtual large batch, then averages the
    accumulated gradients with a group of peers.

    :param tensor_shapes_like: list/pytree leaves of arrays defining gradient shapes
    :param local_updates: if True, peers apply updates locally and this averager is
        used only for state averaging (reference use_local_updates)
    """

    round_purpose = "grads"

    def __init__(
        self,
        tensors_like: Sequence,
        *,
        dht: DHT,
        prefix: str,
        reuse_grad_buffers: bool = False,
        accumulate_grads_on_host: bool = True,
        **kwargs,
    ):
        self.reuse_grad_buffers = reuse_grad_buffers
        templates = [as_numpy(t) for t in tensors_like]
        # accumulate_grads_on_host=False skips the host accumulator allocation (a
        # full model copy) for callers that stage gradients straight into the
        # shared tensors — e.g. SliceOptimizer, whose accumulation lives on device
        self._grad_accumulators: Optional[List[np.ndarray]] = (
            [np.zeros(t.shape, np.float32) for t in templates]
            if accumulate_grads_on_host
            else None
        )
        self.local_samples_accumulated = 0
        self.local_times_accumulated = 0
        self._new_averaged_grads = False
        super().__init__(
            averaged_tensors=[np.zeros(t.shape, np.float32) for t in templates],
            dht=dht,
            prefix=prefix,
            **kwargs,
        )

    def accumulate_grads_(self, grads: Iterable, batch_size: int) -> None:
        """Add one microbatch's gradients (jax or numpy arrays, already averaged over
        the microbatch) scaled by its size (reference grad_averager.py:129-148)."""
        grads = list(grads)
        assert self._grad_accumulators is not None, (
            "this averager was built with accumulate_grads_on_host=False — "
            "gradients are staged externally into the shared tensors"
        )
        assert len(grads) == len(self._grad_accumulators), (
            f"got {len(grads)} gradient tensors, expected {len(self._grad_accumulators)}"
        )
        # the device->host pull of every microbatch's gradients (and the wait for
        # the step that computes them) happens here
        with _sync_span("optimizer.accumulate", peer=str(self.peer_id)):
            for accumulator, grad in zip(self._grad_accumulators, grads):
                accumulator += np.asarray(as_numpy(grad), dtype=np.float32) * batch_size
        self.local_samples_accumulated += batch_size
        self.local_times_accumulated += 1

    def schedule_step(self, scheduled_time: Optional[DHTExpiration] = None, **kwargs) -> StepControl:
        """Begin matchmaking early; the accumulated gradients are loaded and the
        all-reduce triggered later, by step(control=...) (reference
        grad_averager.py:163-184). Bypasses this class's step override: accumulators
        must NOT be loaded yet."""
        assert kwargs.get("weight") is None, "weight is set automatically at trigger time"
        return DecentralizedAverager.step(
            self, scheduled_time=scheduled_time, wait=False, require_trigger=True, **kwargs
        )

    def step(
        self,
        weight: Optional[float] = None,
        control: Optional[StepControl] = None,
        reset_accumulators: bool = True,
        load_accumulators: bool = True,
        wait: bool = True,
        timeout: Optional[float] = None,
        **kwargs,
    ):
        """Average the accumulated gradients with the group; fills the shared
        averaged-gradient buffers (reference grad_averager.py:163-201).

        :param load_accumulators: stage the live accumulators into the shared buffers
            now. Delayed (DPU) updates stage them at schedule time instead and pass
            False, so gradients of the NEXT epoch accumulating concurrently cannot
            leak into the in-flight round."""
        if control is None:
            control = super().step(weight=weight, wait=False, require_trigger=True, timeout=timeout, **kwargs)
        elif weight is not None:
            control.weight = weight
        if load_accumulators:
            self.load_accumulators_into_averager_()
            if control.weight == 1.0 and self.local_samples_accumulated > 0:
                control.weight = self.local_samples_accumulated
            if reset_accumulators:
                self.reset_accumulated_grads_()
        control.allow_allreduce()
        return control.result(timeout) if wait else control

    def load_accumulators_into_averager_(self) -> None:
        """Normalize accumulators by sample count and copy into the shared tensors
        (reference grad_averager.py:203-210)."""
        assert self._grad_accumulators is not None, (
            "accumulate_grads_on_host=False: stage into the shared tensors directly"
        )
        denominator = max(self.local_samples_accumulated, 1)
        with self.get_tensors() as tensors:
            with _sync_span("averager.load", bytes=sum(t.nbytes for t in tensors), **self._work_attributes()):
                for tensor, accumulator in zip(tensors, self._grad_accumulators):
                    np.divide(accumulator, denominator, out=tensor)
        self._new_averaged_grads = True

    def reset_accumulated_grads_(self) -> None:
        if self._grad_accumulators is not None:
            for accumulator in self._grad_accumulators:
                accumulator.fill(0.0)
        self.local_samples_accumulated = 0
        self.local_times_accumulated = 0

    @contextlib.contextmanager
    def use_averaged_gradients(self) -> Iterator[List[np.ndarray]]:
        """Access the averaged gradients after a successful step
        (reference grad_averager.py:221-235 swaps param.grad; here we just expose the
        buffers — the jax caller feeds them to its optax update)."""
        self._new_averaged_grads = False
        with self.get_tensors() as tensors:
            yield tensors

    def averaged_grads_as_jax(self):
        import jax.numpy as jnp

        with self.get_tensors() as tensors:
            return [jnp.asarray(t) for t in tensors]
