"""TrainingStateAverager: owns the optax optimizer + parameters and periodically
averages them with peers (capability parity: reference hivemind/optim/state_averager.py).

jax-first: the canonical train state (params + optax state) lives as device arrays;
the optimizer update is a jitted pure function. The reference's CPU-offload machinery
(offload_optimizer / reuse_tensors, state_averager.py:37-120) has no analog here —
host staging IS the transport path: averaging rounds device_get the state, all-reduce
it over the network, and device_put it back. Epoch-keyed schedules come for free:
optax schedules see the update count, and one optimizer step == one epoch."""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from hivemind_tpu.averaging.averager import DecentralizedAverager
from hivemind_tpu.compression.base import as_numpy
from hivemind_tpu.dht import DHT
from hivemind_tpu.optim.recovery import _STATE_RESTORES
from hivemind_tpu.telemetry.device import record_transfer
from hivemind_tpu.telemetry.tracing import trace_sync as _sync_span
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.profiling import tracked_jit

logger = get_logger(__name__)


class TrainingStateAverager(DecentralizedAverager):
    """Averages model parameters (and optionally optimizer statistics) across peers.

    :param optimizer: an optax.GradientTransformation
    :param params: the initial parameter pytree (jax arrays or numpy)
    :param average_opt_statistics: also average float optimizer-state leaves (e.g.
        Adam's mu/nu) so joining peers inherit momentum
    :param extra_tensors: additional arrays averaged and shared with state downloads
    :param delta_rule_averaging: apply each averaging round's result as a DELTA
        (average − pre-round snapshot) onto the CURRENT state instead of overwriting
        it, so optimizer steps taken concurrently with the round are not clobbered
        (reference state_averager.py:73-74). ``Optimizer`` always sets it: its
        state rounds land behind the next epoch's steps
    """

    round_purpose = "state"

    def __init__(
        self,
        *,
        dht: DHT,
        optimizer,
        params: Any,
        prefix: str,
        average_opt_statistics: bool = True,
        extra_tensors: Sequence = (),
        delta_rule_averaging: bool = False,
        count_equals_epoch: bool = True,
        **kwargs,
    ):
        import jax

        self.optax_optimizer = optimizer
        self.delta_rule_averaging = delta_rule_averaging
        self.count_equals_epoch = count_equals_epoch
        params_flat, self._params_treedef = jax.tree_util.tree_flatten(params)
        self._params_flat = [jax.numpy.asarray(p) for p in params_flat]
        self.opt_state = optimizer.init(jax.tree_util.tree_unflatten(self._params_treedef, self._params_flat))
        self.average_opt_statistics = average_opt_statistics
        self.extra_tensors = [np.array(as_numpy(t), copy=True) for t in extra_tensors]
        self.local_epoch = 0
        self._state_lock = threading.Lock()

        opt_leaves, self._opt_treedef = jax.tree_util.tree_flatten(self.opt_state)
        self._averaged_opt_indices = [
            i
            for i, leaf in enumerate(opt_leaves)
            if average_opt_statistics
            and hasattr(leaf, "dtype")
            and np.issubdtype(np.asarray(leaf).dtype, np.floating)
            and np.asarray(leaf).ndim >= 1
        ]

        @tracked_jit(site="state_averager.apply")
        def _apply(params_flat, opt_state, grads_flat):
            params_tree = jax.tree_util.tree_unflatten(self._params_treedef, params_flat)
            grads_tree = jax.tree_util.tree_unflatten(self._params_treedef, grads_flat)
            updates, new_opt_state = optimizer.update(grads_tree, opt_state, params_tree)
            import optax

            new_params = optax.apply_updates(params_tree, updates)
            return jax.tree_util.tree_flatten(new_params)[0], new_opt_state

        self._jitted_apply = _apply

        averaged = self._host_state_tensors()
        super().__init__(averaged_tensors=averaged, dht=dht, prefix=prefix, **kwargs)

    # ------------------------------------------------------------------ state access

    @property
    def params(self) -> Any:
        import jax

        return jax.tree_util.tree_unflatten(self._params_treedef, self._params_flat)

    @property
    def params_flat(self) -> List:
        return list(self._params_flat)

    def _opt_leaves(self) -> list:
        import jax

        return jax.tree_util.tree_flatten(self.opt_state)[0]

    def _host_state_tensors(self) -> List[np.ndarray]:
        """The averageable view: params + chosen optimizer statistics + extras."""
        with _sync_span("state.device_get"):
            tensors = [np.asarray(as_numpy(p), dtype=np.float32) for p in self._params_flat]
            opt_leaves = self._opt_leaves()
            tensors += [np.asarray(as_numpy(opt_leaves[i]), dtype=np.float32) for i in self._averaged_opt_indices]
            tensors += [np.asarray(t, dtype=np.float32) for t in self.extra_tensors]
        # host staging IS the transport path (module docstring): every round
        # device_gets the whole averageable state — the d2h side of ISSUE 19's
        # transfer accounting on the averaging boundary
        record_transfer(sum(t.nbytes for t in tensors), "device_to_host")
        return tensors

    def _load_host_state_tensors(self, tensors: List[np.ndarray]) -> None:
        """Inverse of _host_state_tensors: write averaged values back to the device
        state, preserving original dtypes."""
        with self._state_lock:
            self._write_host_state_tensors(tensors)

    def _write_host_state_tensors(self, tensors: List[np.ndarray]) -> None:
        """The upload itself; the caller holds ``_state_lock``. Uploads only — no
        program is compiled here, because a round may land in the middle of training."""
        import jax
        import jax.numpy as jnp

        n_params = len(self._params_flat)
        n_opt = len(self._averaged_opt_indices)
        assert len(tensors) >= n_params + n_opt, "state tensor count mismatch"
        record_transfer(sum(int(t.nbytes) for t in tensors), "host_to_device")
        with _sync_span("state.load"):
            self._params_flat = [
                jnp.asarray(tensor, dtype=p.dtype)
                for tensor, p in zip(tensors[:n_params], self._params_flat)
            ]
            opt_leaves = self._opt_leaves()
            for slot, tensor in zip(self._averaged_opt_indices, tensors[n_params : n_params + n_opt]):
                opt_leaves[slot] = jnp.asarray(tensor, dtype=np.asarray(opt_leaves[slot]).dtype)
            self.opt_state = jax.tree_util.tree_unflatten(self._opt_treedef, opt_leaves)
            for extra, tensor in zip(self.extra_tensors, tensors[n_params + n_opt :]):
                np.copyto(extra, tensor.reshape(extra.shape))

    # ------------------------------------------------------------------ optimization

    def apply_optimizer_step(self, grads: Any) -> None:
        """One jitted optax update. ``grads`` may be a pytree matching params, or a
        flat list of arrays (e.g. the averaged-gradient buffers)."""
        import jax

        if isinstance(grads, (list, tuple)) and len(grads) == len(self._params_flat):
            grads_flat = [
                jax.numpy.asarray(g, dtype=p.dtype) for g, p in zip(grads, self._params_flat)
            ]
        else:
            grads_flat = [
                jax.numpy.asarray(g, dtype=p.dtype)
                for g, p in zip(jax.tree_util.tree_flatten(grads)[0], self._params_flat)
            ]
        with self._state_lock:
            self._params_flat, self.opt_state = self._jitted_apply(
                self._params_flat, self.opt_state, grads_flat
            )

    def do_averaging_round(self, timeout: Optional[float] = None, **kwargs) -> bool:
        """Stage state to host, average with the group, load it back. Returns True on
        success (reference state_averager averaging_round path).

        With ``delta_rule_averaging``, the result lands as ``current + (average −
        snapshot)`` in ONE critical section with :meth:`apply_optimizer_step`: an
        optimizer step that ran while the round was in flight survives the landing,
        whenever it ran (reference state_averager.py:73-74,595-612)."""
        with self._state_lock:  # parameters and statistics of the same update
            snapshot = self._host_state_tensors()
        with self.get_tensors() as tensors:
            with _sync_span("averager.load", bytes=sum(t.nbytes for t in tensors), **self._work_attributes()):
                for tensor, fresh in zip(tensors, snapshot):
                    np.copyto(tensor, fresh)
        try:
            result = self.step(timeout=timeout, wait=True, **kwargs)
        except Exception as e:
            logger.warning(f"state averaging round failed: {e!r}")
            return False
        if result is None:
            return False
        with self.get_tensors() as tensors:
            averaged = [t.copy() for t in tensors]
        if not self.delta_rule_averaging:
            self._load_host_state_tensors(averaged)
            return True
        for delta, before in zip(averaged, snapshot):
            delta -= before
        with self._state_lock:
            # the pulled tensors may be read-only views of device buffers: add into ours
            merged = [np.add(current, delta, out=delta) for current, delta in zip(self._host_state_tensors(), averaged)]
            self._write_host_state_tensors(merged)
        return True

    # ------------------------------------------------------------------ schedules

    def replay_schedule_to_epoch(self, epoch: int) -> None:
        """Fast-forward optax step counters to ``epoch`` so epoch-keyed schedules
        (LR warmup/decay) resume at the right point after adopting a peer's params
        (reference state_averager.py:700-704 replays scheduler.step() local_epoch
        times; optax counters jump directly). Only scalar integer leaves whose field
        is named ``count`` are touched — the optax convention for step counters.

        Valid ONLY under the collaborative convention one optimizer step == one
        epoch; local-updates peers take many steps per epoch, so their counters are
        preserved (gated by ``count_equals_epoch``)."""
        if not self.count_equals_epoch:
            return
        self._set_opt_counts([epoch])

    @staticmethod
    def _is_count_leaf(key_path, leaf) -> bool:
        return bool(
            key_path
            and getattr(key_path[-1], "name", None) == "count"
            and hasattr(leaf, "dtype")
            and np.issubdtype(np.asarray(leaf).dtype, np.integer)
            and np.asarray(leaf).ndim == 0
        )

    def _set_opt_counts(self, values: Sequence[int]) -> None:
        """Overwrite the optax count leaves in flatten order; a single value is
        broadcast to every counter."""
        import jax
        import jax.numpy as jnp

        with self._state_lock:
            flat, _ = jax.tree_util.tree_flatten_with_path(self.opt_state)
            new_leaves, index = [], 0
            for key_path, leaf in flat:
                if self._is_count_leaf(key_path, leaf):
                    value = values[index] if index < len(values) else values[-1]
                    new_leaves.append(jnp.asarray(value, dtype=leaf.dtype))
                    index += 1
                else:
                    new_leaves.append(leaf)
            self.opt_state = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self.opt_state), new_leaves
            )

    # ------------------------------------------------------------------ checkpointing

    def state_dict(self) -> dict:
        """Serializable snapshot: epoch + every averaged tensor (params, chosen opt
        statistics, extras) — the user-level checkpoint the reference embeds the
        epoch into (reference optimizer.py:719-727)."""
        with self._state_lock:
            tensors = self._host_state_tensors()
        return {
            "epoch": int(self.local_epoch),
            "tensors": tensors,
            # counters saved explicitly: local-updates peers take many optimizer
            # steps per epoch, so counts cannot be reconstructed from the epoch
            "opt_counts": self._get_opt_counts(),
        }

    def _get_opt_counts(self) -> List[int]:
        import jax

        return [
            int(leaf)
            for key_path, leaf in jax.tree_util.tree_flatten_with_path(self.opt_state)[0]
            if self._is_count_leaf(key_path, leaf)
        ]

    def load_state_dict(self, state: dict) -> None:
        expected = len(self._params_flat) + len(self._averaged_opt_indices) + len(self.extra_tensors)
        tensors = state["tensors"]
        if len(tensors) != expected:
            raise ValueError(f"checkpoint has {len(tensors)} tensors, expected {expected}")
        self._load_host_state_tensors([np.asarray(t, dtype=np.float32) for t in tensors])
        self.local_epoch = int(state["epoch"])
        counts = state.get("opt_counts")
        if counts:
            self._set_opt_counts(list(counts))
        else:
            self.replay_schedule_to_epoch(self.local_epoch)

    # ------------------------------------------------------------------ state sharing

    async def _get_current_state(self) -> Tuple[Any, List[np.ndarray]]:
        metadata = {"epoch": self.local_epoch}
        return metadata, self._host_state_tensors()

    def load_full_state_from_peers(
        self, timeout: Optional[float] = None, min_epoch: Optional[int] = None
    ) -> bool:
        """Download params/opt-state/epoch from the swarm and adopt them
        (reference load_state_from_peers path, state_averager.py:658-698).

        ``min_epoch`` (normally the progress tracker's global epoch) is enforced
        at the donor's MANIFEST: a donor whose epoch is behind it is rejected
        before any tensor bytes move, so catching up can never adopt state staler
        than the swarm's published progress (ISSUE 7 — the old path adopted any
        donor's epoch via ``max()`` with no freshness validation)."""
        future = self._runner.run_coroutine(
            self._load_state_from_peers_async(timeout, min_epoch=min_epoch), return_future=True
        )
        try:
            # small slack over the coroutine's own deadline so the in-loop
            # timeout (which preserves partial verification state) fires first
            result = future.result(None if timeout is None else timeout + 10.0)
        except Exception as e:
            logger.warning(f"state download did not complete: {e!r}")
            return False
        if result is None:
            return False
        expected = len(self._params_flat) + len(self._averaged_opt_indices) + len(self.extra_tensors)
        if len(result.tensors) != expected:
            logger.warning(f"donor sent {len(result.tensors)} tensors, expected {expected}; ignoring")
            return False
        self._load_host_state_tensors(result.tensors)
        # adopted tensors owe nothing to our pre-download quantization errors:
        # carrying the old error-feedback residuals forward would "compensate"
        # state we no longer hold (ISSUE 11)
        self._wire_residuals.reset()
        # the verified manifest's epoch is authoritative; a legacy (unverified)
        # stream falls back to the msgpack metadata it shipped
        donor_epoch = int(result.epoch)
        if not result.verified and isinstance(result.metadata, dict) and "epoch" in result.metadata:
            donor_epoch = max(donor_epoch, int(result.metadata["epoch"]))
        self.local_epoch = max(self.local_epoch, donor_epoch)
        # int step counters are not averaged tensors: fast-forward them so LR
        # schedules resume at the adopted epoch rather than restarting warmup
        self.replay_schedule_to_epoch(self.local_epoch)
        _STATE_RESTORES.inc(source="swarm")
        logger.info(
            f"adopted peer state at epoch {self.local_epoch} "
            f"({'digest-verified' if result.verified else 'UNVERIFIED legacy stream'})"
        )
        return True
