"""SliceOptimizer: the FULL collaborative ``Optimizer`` semantics — target_batch_size
epochs, swarm gradient averaging, progress tracker, periodic state averaging, and
``load_state_from_peers`` — running on a (possibly multi-host) jax device mesh, where
the whole mesh/slice is ONE swarm peer.

This joins the two halves of the TPU-native design (VERDICT r3 next-round #1): the
reference's flagship training API (reference hivemind/optim/optimizer.py:32-790 +
grad_averager.py:18-239) and the slice tier (`averaging/slice.py`, where previously
only local-SGD *parameter* averaging could ride a multi-host mesh).

Division of labor:

- **Every process** (the SPMD contract: all processes call every method at the same
  points): holds its shards of params / optax state / the on-device gradient
  accumulator; joins the collective staging, broadcast, and update phases.
- **Process 0** (the network process) exclusively owns the DHT, the
  ``ProgressTracker``, matchmaking (including the reference's pre-scheduled
  gradient-averaging groups), the butterfly all-reduce, and state sharing. Non-zero
  processes never construct any networking object — the same structural guarantee
  as ``SliceAverager``.

TPU-first choices:

- **Gradient accumulation stays on device.** ``step(grads)`` adds into a sharded
  fp32 accumulator tree with a jitted donated add — no per-microbatch device→host
  transfer. Gradients cross the host boundary ONCE per epoch, at averaging time,
  through :class:`MeshTensorBridge` (shard-wise staging).
- **The optax update is collective.** Parameters and optimizer state never leave
  the mesh: the final (swarm-averaged or local) gradients are scattered back to
  the params' shardings and one jitted donated update advances every shard.
- **Decisions are broadcast, not re-derived.** Whether to catch up, whether the
  swarm is ready for an epoch, and whether averaging succeeded are known only on
  process 0; a small decision vector is broadcast each step
  (``multihost_utils.broadcast_one_to_all``) so every process takes the same
  branch — control flow divergence across processes is a hang, not an error.

Wire compatibility: the slice peer matchmakes under the same prefixes
(``{run_id}_grad_averager``, ``{run_id}_state``) with the same tensor schemas as
host-resident :class:`hivemind_tpu.optim.Optimizer` peers, so slices, GPU boxes and
laptops share one swarm. Its advertised bandwidth is the slice's aggregate egress
(host count × base), as in :class:`MeshAverager`.

Gradient compression composes: ``grad_averager_factory`` accepts e.g.
``PowerSGDGradientAverager`` — the rank-r P/Q phases run on the staged host
gradients on process 0, wire-compatible with host PowerSGD peers in the same run.

Comm/compute overlap (the DPU analog, reference optimizer.py:87-88,131-132 +
state_averager.py:478-574): with ``delay_grad_averaging=True`` the swarm gradient
round runs on a BACKGROUND thread of process 0 while every process keeps
stepping into a fresh accumulator — the mesh never stalls for the round's
matchmaking + allreduce. The collective contract survives because the round's
LIFECYCLE is replicated, not its execution: the launch happens at a collective
step (every process stages the epoch's gradients and remembers the pending
round), completion is announced through the per-step decision broadcast, and
the adoption (scatter + optax update + state phase) happens at the next step
boundary on every process — one epoch stale, exactly the reference's DPU
semantics.

Deviations from the host Optimizer (documented, not silent): no
``use_local_updates`` mode (use ``SliceAverager`` for the local-SGD family),
and no aux/client modes (a slice is by definition a full NODE peer).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hivemind_tpu.averaging.averager import DecentralizedAverager
from hivemind_tpu.averaging.control import StepControl
from hivemind_tpu.compression import CompressionBase, Float16Compression
from hivemind_tpu.optim.chronic import ChronicFailureTracking
from hivemind_tpu.optim.grad_averager import GradientAverager
from hivemind_tpu.optim.progress_tracker import ProgressTracker
from hivemind_tpu.parallel.ici import MeshTensorBridge
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.profiling import tracked_jit
from hivemind_tpu.utils.timed_storage import get_dht_time

logger = get_logger(__name__)

# layer-4 telemetry (docs/observability.md). The skipped-steps child is bound
# once: it increments on the broadcast-free hot path.
from hivemind_tpu.telemetry import REGISTRY as _TELEMETRY
from hivemind_tpu.telemetry.ledger import LEDGER as _LEDGER
from hivemind_tpu.telemetry.ledger import EpochPhases as _EpochPhases

_C_SKIPPED_STEPS = _TELEMETRY.counter(
    "hivemind_optim_skipped_broadcast_steps_total",
    "steps that skipped the per-step decision broadcast (thinning)",
).labels()
_C_EPOCH_TRANSITIONS = _TELEMETRY.counter(
    "hivemind_optim_epoch_transitions_total", "slice epoch transitions", ("kind",)
)
_C_POISONED_ROUNDS = _TELEMETRY.counter(
    "hivemind_optim_poisoned_averager_rounds_total",
    "delayed rounds whose thread outlived its join timeout, poisoning the grad averager",
).labels()


def _broadcast(value: np.ndarray) -> np.ndarray:
    """Broadcast one host array from process 0 to all processes (device collective)."""
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.broadcast_one_to_all(value))


class _SliceStateAverager(DecentralizedAverager):
    """State-sharing endpoint of a slice peer: serves the staged state mirrors with
    the slice's current epoch as metadata (the canonical state lives sharded on the
    mesh; mirrors are refreshed at every epoch transition, so downloads are at most
    one epoch stale — a joiner adopts them and catches up through the tracker)."""

    round_purpose = "state"

    def __init__(self, *args, epoch_fn, **kwargs):
        self._epoch_fn = epoch_fn
        super().__init__(*args, **kwargs)

    async def _get_current_state(self) -> Tuple[Any, List[np.ndarray]]:
        return {"epoch": int(self._epoch_fn())}, self._snapshot_tensors()


class SliceOptimizer(ChronicFailureTracking):
    """See module docstring.

    :param mesh: the global Mesh (possibly spanning several processes/hosts)
    :param params: the initial parameter pytree, sharded over ``mesh``
    :param optimizer: an optax.GradientTransformation (same on every peer)
    :param dht_factory: zero-arg callable building the network process's DHT;
        called ONLY on process 0
    :param run_id: swarm identifier — must match the host peers' ``run_id``
    :param target_batch_size: global samples per virtual epoch (swarm-wide)
    :param batch_size_per_step: default GLOBAL samples per ``step`` call (every
        process passes the same number — the global microbatch, not its shard)
    :param average_state_every: run a parameter/opt-state averaging round every N
        epochs (reference average_state_every)
    :param average_opt_statistics: also average floating optimizer-state leaves
        (must match the host peers' setting or the state schemas diverge)
    :param delay_grad_averaging: overlap the swarm gradient round with training
        (the reference's delayed parameter updates): the round runs on a process-0
        background thread while the whole mesh keeps stepping; the averaged
        update is adopted collectively at the next step boundary, one epoch
        stale. See the module docstring.
    :param max_broadcast_skip: thin the per-step decision broadcast: while the
        tracker's ETA to the next epoch is far, process 0 announces how many
        upcoming steps may skip the collective entirely (every process counts
        down the same number, so lockstep holds). 0 disables thinning; skipping
        never happens near a boundary, during a pending round, or while chronic.
    """

    _chronic_peer_noun = "slice"

    def __init__(
        self,
        *,
        mesh,
        params: Any,
        optimizer,
        dht_factory,
        run_id: str,
        target_batch_size: int,
        batch_size_per_step: Optional[int] = None,
        average_state_every: int = 1,
        average_opt_statistics: bool = True,
        delay_grad_averaging: bool = False,
        max_broadcast_skip: int = 8,
        matchmaking_time: float = 5.0,
        averaging_timeout: float = 60.0,
        load_state_timeout: float = 60.0,
        grad_compression: CompressionBase = Float16Compression(),
        state_averaging_compression: CompressionBase = Float16Compression(),
        target_group_size: Optional[int] = None,
        min_group_size: int = 2,
        bandwidth: Optional[float] = None,
        grad_averager_factory=None,
        chronic_failure_threshold: int = 5,
        verbose: bool = False,
        **averager_opts,
    ):
        self.mesh = mesh
        self.run_id = run_id
        self.target_batch_size = target_batch_size
        self.batch_size_per_step = batch_size_per_step
        self.average_state_every = max(int(average_state_every), 1)
        self.delay_grad_averaging = delay_grad_averaging
        self.max_broadcast_skip = max(int(max_broadcast_skip), 0)
        self.matchmaking_time = matchmaking_time
        self.averaging_timeout = averaging_timeout
        self.load_state_timeout = load_state_timeout
        self.verbose = verbose
        self.process_index = jax.process_index()
        self.is_network_process = self.process_index == 0
        self.bridge = MeshTensorBridge(mesh)
        self._optax_optimizer = optimizer
        self._step_lock = threading.Lock()

        # -------- device state (every process) --------
        self.params = params
        self.opt_state = jax.jit(optimizer.init)(params)
        self._params_leaves, self._params_treedef = jax.tree_util.tree_flatten(params)
        opt_leaves, self._opt_treedef = jax.tree_util.tree_flatten(self.opt_state)
        # same selection rule as TrainingStateAverager (host peers): floating,
        # ndim>=1 — the schemas must agree or slices cannot group with host peers.
        # dtype/ndim read from attributes: a multi-process global array cannot be
        # np.asarray'd from one process.
        self._averaged_opt_indices = [
            i
            for i, leaf in enumerate(opt_leaves)
            if average_opt_statistics
            and hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and getattr(leaf, "ndim", 0) >= 1
        ]
        self._accum = self._jit_zeros_like()(params)
        self._samples = 0
        self.local_epoch = 0
        self.scheduled_grads: Optional[StepControl] = None
        # delayed-round state, REPLICATED on every process (set and cleared only
        # at collective steps, so `self._pending is not None` is identical
        # everywhere — the in-flight check never needs its own collective)
        self._pending: Optional[dict] = None
        self._bg_thread: Optional[threading.Thread] = None  # process 0 only
        self._bg_outcome: Optional[dict] = None  # process 0 only
        # a delayed-round thread that outlived its join timeout still owns the
        # grad averager's shared tensors: until it is confirmed dead the averager
        # is POISONED and must not be reused (silent data race otherwise)
        self._poisoned_bg_thread: Optional[threading.Thread] = None  # process 0 only
        # broadcast thinning, also replicated: process 0 announces a skip count in
        # the decision vector; every process counts the same number down
        self._skip_remaining = 0
        self._deferred_network_error: Optional[BaseException] = None
        self._step_time_ema: Optional[float] = None
        self._last_step_time: Optional[float] = None
        # chronic-degradation tracking (host Optimizer parity, optimizer.py:100-136):
        # epochs that fell back to local gradients count; past the threshold the
        # condition escalates to ERROR and matchmaking backs off exponentially.
        # Tracked consistently on EVERY process — the outcome flag is broadcast.
        self.chronic_failure_threshold = chronic_failure_threshold
        self._consecutive_failed_rounds = 0

        import optax

        def _accumulate(acc, grads, scale):
            return jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) * scale, acc, grads
            )

        def _apply(params_, opt_state_, grads_):
            updates, new_state = optimizer.update(grads_, opt_state_, params_)
            return optax.apply_updates(params_, updates), new_state

        def _normalize(acc, inv_scale):
            return jax.tree_util.tree_map(lambda a: a * inv_scale, acc)

        # tracked_jit (ISSUE 19): these three are the slice's hottest device
        # calls — a retrace here (e.g. a dtype drift in the grads tree) must
        # surface on the compile tracker, not hide as a slow step
        self._jit_accumulate = tracked_jit(
            _accumulate, site="slice_optimizer.accumulate", donate_argnums=(0,)
        )
        self._jit_apply = tracked_jit(_apply, site="slice_optimizer.apply", donate_argnums=(0, 1))
        self._jit_normalize = tracked_jit(_normalize, site="slice_optimizer.normalize")

        # -------- networking (process 0 only) --------
        self.dht = None
        self.grad_averager: Optional[DecentralizedAverager] = None
        self.state_averager: Optional[_SliceStateAverager] = None
        self.tracker: Optional[ProgressTracker] = None
        if self.is_network_process:
            self.dht = dht_factory()
            num_hosts = len({d.process_index for d in mesh.devices.flat})
            slice_bandwidth = bandwidth if bandwidth is not None else 1.0e8 * max(num_hosts, 1)
            common = dict(
                dht=self.dht,
                start=True,
                target_group_size=target_group_size,
                min_group_size=min_group_size,
                min_matchmaking_time=matchmaking_time,
                bandwidth=slice_bandwidth,
                **averager_opts,
            )
            grad_templates = [
                np.zeros(leaf.shape, np.float32) for leaf in self._params_leaves
            ]
            # grad_averager_factory (API parity with the host Optimizer): e.g.
            # PowerSGDGradientAverager for rank-r compressed swarm rounds — the
            # P/Q phases run on the staged host gradients on process 0, so the
            # slice interoperates with host PowerSGD peers on the same run_id.
            # The factory must accept (templates, dht=..., prefix=..., ...).
            # When it resolves to a GradientAverager subclass (class or
            # functools.partial of one), host accumulators are skipped — the
            # slice accumulates on device and stages directly, so they would be
            # a wasted model copy of host RAM.
            factory = grad_averager_factory if grad_averager_factory is not None else DecentralizedAverager
            factory_class = factory if isinstance(factory, type) else getattr(factory, "func", None)
            extra_opts = (
                {"accumulate_grads_on_host": False}
                if isinstance(factory_class, type) and issubclass(factory_class, GradientAverager)
                else {}
            )
            self.grad_averager = factory(
                grad_templates,
                prefix=f"{run_id}_grad_averager",
                compression=grad_compression,
                **extra_opts,
                **common,
            )
            self.grad_averager.round_purpose = "grads"  # whichever class the factory built
            state_templates = [
                np.zeros(leaf.shape, np.float32) for leaf in self._state_leaves()
            ]
            self.state_averager = _SliceStateAverager(
                state_templates,
                prefix=f"{run_id}_state",
                compression=state_averaging_compression,
                state_compression=state_averaging_compression,
                epoch_fn=lambda: self.local_epoch,
                **common,
            )
            self.tracker = ProgressTracker(self.dht, run_id, target_batch_size)

    def _epoch_phases(self, epoch: int) -> _EpochPhases:
        """The clock of one epoch transition, labelled as the host Optimizer labels its own."""
        peer = str(self.dht.peer_id) if self.dht is not None else f"proc{self.process_index}"
        return _EpochPhases(peer=peer, epoch=epoch)

    # ------------------------------------------------------------------ device trees

    def _jit_zeros_like(self):
        fn = getattr(self, "_zeros_fn", None)
        if fn is None:
            fn = self._zeros_fn = tracked_jit(
                lambda tree: jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), tree
                ),
                site="slice_optimizer.zeros_like",
            )
        return fn

    def _state_leaves(self) -> List:
        """Params + selected optimizer statistics, in the host peers' flatten order
        (params first, then stats — matching TrainingStateAverager's schema)."""
        opt_leaves = jax.tree_util.tree_flatten(self.opt_state)[0]
        return list(self._params_leaves) + [opt_leaves[i] for i in self._averaged_opt_indices]

    def _refresh_param_leaves(self) -> None:
        self._params_leaves = jax.tree_util.tree_flatten(self.params)[0]

    # ------------------------------------------------------------------ main entry

    @property
    def ready_to_update_epoch(self) -> bool:
        """Meaningful on the network process; followers learn it via the broadcast."""
        return bool(self.tracker is not None and self.tracker.ready_to_update_epoch)

    def step(self, grads: Any = None, batch_size: Optional[int] = None) -> Any:
        """Accumulate one (global) microbatch of sharded gradients; when the swarm
        reaches ``target_batch_size``, run the collective epoch transition
        (synchronously, or — with ``delay_grad_averaging`` — launch the swarm
        round in the background and adopt it at a later step boundary). Every
        process of the slice must call this at the same point with the same
        ``batch_size`` (the global microbatch size). Returns the parameter tree."""
        with self._step_lock:
            batch_size = batch_size if batch_size is not None else (self.batch_size_per_step or 1)
            if grads is not None:
                self._accum = self._jit_accumulate(
                    self._accum, grads, jnp.float32(batch_size)
                )
                self._samples += batch_size
            self._observe_step_time()

            # thinned step: process 0 announced this many broadcast-free steps;
            # every process counts the SAME number down, so lockstep holds with
            # zero collectives on the hot path. Process 0 still does its local
            # networking — but an error there is deferred to the next broadcast
            # step (raising here would desync the skip countdown).
            if self._skip_remaining > 0:
                self._skip_remaining -= 1
                _C_SKIPPED_STEPS.inc()
                if self.is_network_process and self._deferred_network_error is None:
                    try:
                        assert self.tracker is not None
                        self.tracker.report_local_progress(self.local_epoch, self._samples)
                        if self._pending is None:
                            self._maybe_schedule_gradient_averaging()
                    except BaseException as e:
                        self._deferred_network_error = e
                return self.params

            # process 0 decides; everyone else adopts the decision (one small
            # device broadcast per step — control flow must not diverge). The
            # decision vector carries an ERROR flag in slot 4: if process 0's
            # networking raises (DHT shutdown, tracker store failure), it still
            # broadcasts — with the flag set — so every process raises in
            # lockstep instead of the followers parking forever in the
            # collective (advisor r4 medium finding). Slots 5-6 announce a
            # pending background round's completion; slot 7 the next skip count.
            in_flight = self._pending is not None
            network_error: Optional[BaseException] = None
            if self.is_network_process:
                try:
                    if self._deferred_network_error is not None:
                        network_error = self._deferred_network_error
                        self._deferred_network_error = None
                        raise network_error
                    assert self.tracker is not None
                    self.tracker.report_local_progress(self.local_epoch, self._samples)
                    if not in_flight:
                        self._maybe_schedule_gradient_averaging()
                    # one-epoch grace (reference optimizer.py:654-672): global ==
                    # local + 1 is normal network asynchrony — the tracker
                    # reports us ready and we transition ourselves onto the
                    # global epoch; only a 2+ gap downloads state
                    catch_up = self.local_epoch < self.tracker.global_epoch - 1
                    ready = self.tracker.ready_to_update_epoch
                    round_done = round_ok = 0.0
                    if in_flight and self._bg_thread is not None:
                        if ready and self._bg_thread.is_alive():
                            # the NEXT boundary arrived while the round is still
                            # in flight: staleness is capped at one epoch — wait
                            # the round out (its own timeouts bound this)
                            self._bg_thread.join(timeout=self.averaging_timeout + 30.0)
                        if not self._bg_thread.is_alive():
                            round_done = 1.0
                            round_ok = 1.0 if (self._bg_outcome or {}).get("ok") else 0.0
                    elif in_flight:
                        # solo-swarm pending (no thread): adopt immediately
                        round_done, round_ok = 1.0, 0.0
                    decision = np.asarray(
                        [
                            1.0 if catch_up else 0.0,
                            1.0 if ready else 0.0,
                            float(self.tracker.global_epoch),
                            float(self.tracker.global_progress.num_peers),
                            0.0,
                            round_done,
                            round_ok,
                            float(self._suggest_skip(catch_up, ready, in_flight)),
                        ],
                        np.float32,
                    )
                except BaseException as e:
                    network_error = e
                    decision = np.asarray(
                        [0.0, 0.0, -1.0, -1.0, 1.0, 0.0, 0.0, 0.0], np.float32
                    )
            else:
                decision = np.zeros(8, np.float32)
            decision = _broadcast(decision)
            if decision[4] >= 0.5:
                if network_error is not None:
                    raise network_error
                raise RuntimeError(
                    "the slice's network process failed during its decision phase; "
                    "raising in lockstep (see process 0's traceback for the cause)"
                )
            catch_up, ready = decision[0] >= 0.5, decision[1] >= 0.5
            global_epoch, num_peers = int(decision[2]), int(decision[3])
            round_done, round_ok = decision[5] >= 0.5, decision[6] >= 0.5
            self._skip_remaining = max(int(decision[7]), 0)

            if catch_up:
                # local_epoch already counts a launched delayed round (the epoch
                # advances at LAUNCH, reference optimizer.py:131-132), so being
                # behind here is genuine — drop the pending round and download
                self._discard_pending()
                self._collective_catch_up(global_epoch)
                return self.params
            if in_flight:
                if round_done:
                    self._finish_delayed_epoch(round_ok)
                return self.params
            if ready:
                if self.delay_grad_averaging and num_peers > 1:
                    self._begin_delayed_epoch(num_peers, global_epoch)
                else:
                    self._collective_epoch_update(num_peers, global_epoch)
            return self.params

    def _observe_step_time(self) -> None:
        """EMA of the wall time between step() calls (used to size the skip)."""
        now = get_dht_time()
        if self._last_step_time is not None:
            dt = max(now - self._last_step_time, 1e-6)
            self._step_time_ema = (
                dt if self._step_time_ema is None else 0.8 * self._step_time_ema + 0.2 * dt
            )
        self._last_step_time = now

    def _suggest_skip(self, catch_up: bool, ready: bool, in_flight: bool) -> int:
        """How many upcoming steps may skip the decision broadcast (network
        process only). Never skips when anything needs low-latency signaling:
        a boundary is near (in step-time terms), a round is pending, we are
        behind, or rounds are chronically failing."""
        if (
            self.max_broadcast_skip <= 0
            or catch_up
            or ready
            or in_flight
            or self.chronic_averaging_failure
            or self._step_time_ema is None
        ):
            return 0
        assert self.tracker is not None
        progress = self.tracker.global_progress
        eta = progress.eta_next_epoch - get_dht_time()
        # stay broadcast-per-step inside the pre-scheduling window so the group
        # forms at full cadence, and keep a 2x step-time safety margin
        if eta <= max(self.matchmaking_time * 2, 4 * self._step_time_ema):
            return 0
        # additionally cap by the locally-known samples remaining to the target:
        # the ETA extrapolates the swarm's PAST rate, so a swarm speed-up (new
        # peers joining mid-window) can close the epoch well before it — the
        # sample count cannot be outrun the same way, and with the same 2x
        # margin our own contribution can cover at most half the known gap
        # before the next broadcast re-checks
        per_step = max(int(self.batch_size_per_step or 1), 1)
        remaining_samples = max(progress.target_batch_size - progress.samples_accumulated, 0)
        steps_to_target = int(remaining_samples // (2 * per_step))
        return min(self.max_broadcast_skip, int(eta / (2 * self._step_time_ema)), steps_to_target)

    # ------------------------------------------------------------------ delayed rounds

    def _begin_delayed_epoch(self, num_peers: int, global_epoch: int = 0) -> None:
        """COLLECTIVE: stage this epoch's normalized gradients to identical host
        copies on every process, remember the pending round, reset the on-device
        accumulator (training continues into the NEXT epoch), ADVANCE the epoch
        (reference DPU semantics, optimizer.py:131-132 — the epoch counts the
        launched round; only the parameter update is delayed; advancing here
        also resets the tracker so ``ready`` cannot re-fire into an immediate
        blocking join), and — network process only — launch the swarm round on
        a background thread."""
        _C_EPOCH_TRANSITIONS.inc(kind="delayed_launch")
        inv = jnp.float32(1.0 / max(self._samples, 1))
        normalized = self._jit_normalize(self._accum, inv)
        scratch = self.bridge.gather_to_host(normalized)
        # the round runs beside the next epoch's steps, so this clock's phases add up
        # to less than its transition_s (launch to adoption)
        phases = self._epoch_phases(max(self.local_epoch + 1, global_epoch))
        self._pending = {"scratch": scratch, "num_peers": num_peers, "phases": phases}
        # weight 0 is correct for a peer with nothing accumulated (the grace rule
        # can transition an empty peer): its zero buffers must not dilute the
        # group average — matches the host Optimizer (optimizer.py:379-383)
        weight = float(self._samples)
        self._accum = self._jit_zeros_like()(self.params)
        self._samples = 0
        # a rejoining peer lands ON the global epoch, not past it
        self.local_epoch = max(self.local_epoch + 1, global_epoch)
        if not self.is_network_process:
            return
        assert self.tracker is not None
        self.tracker.update_epoch(self.local_epoch)
        control = None if self._scheduled_control_invalid() else self.scheduled_grads
        self.scheduled_grads = None
        outcome: dict = {"ok": False}
        self._bg_outcome = outcome

        def run_round() -> None:
            # writing the average back into process 0's scratch is race-free:
            # the adoption step reads it only after joining this thread
            with phases.phase("grad_round"):
                outcome["ok"] = self._run_swarm_round(scratch, weight, control)

        self._bg_thread = threading.Thread(
            target=run_round, name="slice-delayed-round", daemon=True
        )
        self._bg_thread.start()

    def _finish_delayed_epoch(self, round_ok: bool) -> None:
        """COLLECTIVE: adopt the background round's outcome — averaged gradients
        if it succeeded (per-leaf broadcast from process 0), the staged local
        gradients otherwise — then run the shared update + state phase tail.
        The CURRENT accumulator (next epoch's partial progress) is untouched."""
        pending = self._pending
        assert pending is not None
        self._pending = None
        scratch = pending["scratch"]
        num_peers = pending["num_peers"]
        if self.is_network_process and self._bg_thread is not None:
            self._bg_thread.join(timeout=5.0)  # decision said done; near-instant
        averaged_ok = bool(round_ok)
        if averaged_ok:
            # process 0's scratch already holds the group average (written by the
            # background round before it finished)
            for i in range(len(scratch)):
                scratch[i] = _broadcast(np.ascontiguousarray(scratch[i]))
        self._bg_thread = None
        self._bg_outcome = None
        self._apply_epoch_tail(
            scratch, averaged_ok, num_peers, pending["phases"],
            reset_accumulator=False, advance_epoch=False,
        )

    def _discard_pending(self) -> None:
        """Drop an in-flight delayed round (all processes; the catch-up path is
        about to replace the state it would have updated). Process 0 waits the
        background thread out so the averager is free for the state download; a
        thread that survives the join timeout POISONS the grad averager — its
        buffers are not reused until the thread is confirmed dead (a wedged round
        writing into tensors a new round is reading is a silent data race)."""
        if self._pending is None:
            return
        self._pending = None
        if self.is_network_process and self._bg_thread is not None:
            self._bg_thread.join(timeout=self.averaging_timeout + 30.0)
            if self._bg_thread.is_alive():
                self._poisoned_bg_thread = self._bg_thread
                _C_POISONED_ROUNDS.inc()
                logger.error(
                    "a discarded delayed averaging round did not terminate within "
                    f"{self.averaging_timeout + 30.0:.0f}s; the grad averager is POISONED — "
                    "swarm gradient rounds degrade to local gradients until the round "
                    "thread is confirmed dead (see "
                    "hivemind_optim_poisoned_averager_rounds_total)"
                )
        self._bg_thread = None
        self._bg_outcome = None

    def _grad_averager_poisoned(self) -> bool:
        """True while a timed-out delayed-round thread may still touch the grad
        averager's buffers; self-clears once the thread is confirmed dead."""
        thread = self._poisoned_bg_thread
        if thread is None:
            return False
        if thread.is_alive():
            return True
        self._poisoned_bg_thread = None
        logger.warning(
            "the poisoned delayed-round thread has terminated; grad averager "
            "buffers are safe to reuse again"
        )
        return False

    # ------------------------------------------------------------------ scheduling

    # chronic counter/backoff/log members come from ChronicFailureTracking

    def _maybe_schedule_gradient_averaging(self) -> None:
        """Pre-schedule matchmaking so the group is formed when the swarm hits the
        target (reference optimizer.py:559-567). Network process only, no collective."""
        assert self.tracker is not None and self.grad_averager is not None
        if self.chronic_averaging_failure:
            # pre-scheduling re-declares in the DHT at full cadence every step;
            # under chronic failure only the (backed-off) step-time path matchmakes
            return
        if self._grad_averager_poisoned():
            return  # a wedged round still owns the averager's buffers
        eta = self.tracker.global_progress.eta_next_epoch - get_dht_time()
        if eta <= self.matchmaking_time * 2 and self._scheduled_control_invalid():
            scheduled_time = get_dht_time() + max(eta, 1e-2)
            if isinstance(self.grad_averager, GradientAverager):
                # its step() override hardcodes require_trigger; use the dedicated
                # scheduling entry point (same as the host Optimizer)
                self.scheduled_grads = self.grad_averager.schedule_step(
                    scheduled_time=scheduled_time, timeout=self.averaging_timeout
                )
            else:
                self.scheduled_grads = self.grad_averager.step(
                    scheduled_time=scheduled_time,
                    timeout=self.averaging_timeout,
                    require_trigger=True,
                    wait=False,
                )
            logger.debug(f"pre-scheduled slice gradient averaging in {eta:.1f}s")

    def _scheduled_control_invalid(self) -> bool:
        control = self.scheduled_grads
        return control is None or control.done() or control.cancelled

    # ------------------------------------------------------------------ epoch transition

    def _run_swarm_round(self, scratch: List[np.ndarray], weight: float, control) -> bool:
        """Network process only; the ONE swarm-gradient-round implementation shared
        by the synchronous and delayed paths: stage ``scratch`` into the shared
        tensors, run the round (pre-claimed ``control`` or a fresh step), and on
        success write the group average back INTO ``scratch``. Never raises —
        every failure (staging included) degrades to False so the caller's flag
        broadcast keeps the mesh in lockstep (advisor r4 medium finding), and a
        claimed control is cancelled so matched groupmates are not stranded."""
        try:
            assert self.grad_averager is not None
            if self._grad_averager_poisoned():
                # refusing to touch the shared tensors IS the fix: the wedged
                # thread may still be writing them (loud log already emitted)
                if control is not None and not control.done():
                    with contextlib.suppress(Exception):
                        control.cancel()
                return False
            with self.grad_averager.get_tensors() as tensors:
                for tensor, fresh in zip(tensors, scratch):
                    np.copyto(tensor, fresh)
            if isinstance(self.grad_averager, GradientAverager):
                # one call covers scheduled and unscheduled (the host Optimizer's
                # DPU path, optimizer.py:430-436); gradients are ALREADY staged
                # in the shared tensors, so the host accumulators must not
                # overwrite them
                result = self.grad_averager.step(
                    control=control,
                    weight=weight,
                    timeout=self.averaging_timeout,
                    load_accumulators=False,
                    scheduled_time=(
                        get_dht_time() + self._matchmaking_delay() if control is None else None
                    ),
                )
            elif control is not None:
                control.weight = weight
                control.allow_allreduce()
                result = control.result(self.averaging_timeout)
            else:
                result = self.grad_averager.step(
                    weight=weight,
                    timeout=self.averaging_timeout,
                    scheduled_time=get_dht_time() + self._matchmaking_delay(),
                )
            if result is None:
                return False
            with self.grad_averager.get_tensors() as tensors:
                for mirror, tensor in zip(scratch, tensors):
                    np.copyto(mirror, tensor)
            return True
        except Exception as e:
            if control is not None and not control.done():
                with contextlib.suppress(Exception):
                    control.cancel()
            logger.warning(f"slice gradient averaging failed ({e!r}); applying local gradients")
            return False

    def _collective_epoch_update(self, num_peers: int, global_epoch: int = 0) -> None:
        """The slice analog of reference _update_global_epoch (optimizer.py:438-509):
        stage → swarm-average (p0) → broadcast → collective optax update → state round."""

        _C_EPOCH_TRANSITIONS.inc(kind="synchronous")
        phases = self._epoch_phases(max(self.local_epoch + 1, global_epoch))
        with phases.phase("grad_round"):  # staging, the swarm round, adopting its outcome
            scratch, averaged_ok = self._collective_grad_round(num_peers)
        self._apply_epoch_tail(
            scratch, averaged_ok, num_peers, phases, reset_accumulator=True, global_epoch=global_epoch
        )

    def _collective_grad_round(self, num_peers: int) -> Tuple[List[np.ndarray], Optional[bool]]:
        """Phases A-C of the synchronous transition: every process's host copy of the
        epoch's gradients (swarm-averaged if the round succeeded) and the round's
        outcome (None = no round attempted)."""
        # phase A (collective): normalize the on-device accumulator and stage it to
        # identical full host copies on EVERY process (per-leaf bounded staging).
        # These doubles as the local-gradient fallback: if the swarm round fails,
        # every process already holds the same local average — no broadcast needed.
        inv = jnp.float32(1.0 / max(self._samples, 1))
        normalized = self._jit_normalize(self._accum, inv)
        scratch = self.bridge.gather_to_host(normalized)

        # phase B (network process): the swarm round
        averaged_ok: Optional[bool] = None  # None = no round attempted (solo swarm)
        if num_peers > 1:
            averaged_ok = False
            if self.is_network_process:
                # claim the pre-scheduled control BEFORE the round: if staging
                # fails, the control must still be consumed (and cancelled), not
                # left live to block re-scheduling and strand its matched
                # groupmates until the averaging timeout
                control = None if self._scheduled_control_invalid() else self.scheduled_grads
                self.scheduled_grads = None
                # weight 0 for a peer with nothing accumulated (see
                # _begin_delayed_epoch / host optimizer.py:379-383)
                averaged_ok = self._run_swarm_round(scratch, float(self._samples), control)

            # phase C (collective): adopt the round outcome
            flag = _broadcast(np.asarray([1.0 if averaged_ok else 0.0], np.float32))
            averaged_ok = bool(flag[0] >= 0.5)
            if averaged_ok:
                for i in range(len(scratch)):
                    scratch[i] = _broadcast(np.ascontiguousarray(scratch[i]))
        return scratch, averaged_ok

    def _apply_epoch_tail(
        self,
        scratch: List[np.ndarray],
        averaged_ok: Optional[bool],
        num_peers: int,
        phases: _EpochPhases,
        reset_accumulator: bool,
        advance_epoch: bool = True,
        global_epoch: int = 0,
    ) -> None:
        """The shared end of every epoch transition (synchronous and delayed).

        phase D (collective): scatter the final gradients back to the params'
        shardings and run ONE jitted donated update — params/opt state never
        left the mesh. phase E (collective): record the round outcome, refresh
        the state mirrors, run the periodic state round, advance the epoch.
        ``reset_accumulator=False`` / ``advance_epoch=False`` on the delayed
        path: the accumulator already holds the NEXT epoch's partial progress,
        and the epoch was counted at launch — this tail only lands the update."""
        next_epoch = (
            max(self.local_epoch + 1, global_epoch) if advance_epoch else self.local_epoch
        )
        with phases.phase("update"):
            grads_tree = jax.tree_util.tree_unflatten(
                self._params_treedef,
                [
                    self.bridge.scatter_leaf(leaf, value)
                    for leaf, value in zip(self._params_leaves, scratch)
                ],
            )
            self.params, self.opt_state = self._jit_apply(self.params, self.opt_state, grads_tree)
            self._refresh_param_leaves()
        if reset_accumulator:
            self._accum = self._jit_zeros_like()(self.params)
            self._samples = 0

        # record the grad-round outcome FIRST (reference order, optimizer.py:384-388):
        # the state phase's matchmaking delay must see the recovered counter
        self._record_round_outcome(averaged_ok)
        with phases.phase("state_round"):  # the mirrors' refresh, and the swarm round when one is due
            self._collective_state_phase(next_epoch, num_peers)

        self.local_epoch = next_epoch
        if self.is_network_process:
            assert self.tracker is not None and self.state_averager is not None
            self.state_averager.state_sharing_priority = next_epoch
            # the slice is ONE swarm peer: its network process closes the epoch's record
            _LEDGER.record_epoch(
                next_epoch, peer=str(self.dht.peer_id), averaged_ok=averaged_ok,
                num_peers=num_peers, **phases.fields(),
            )
            if advance_epoch:
                self.tracker.update_epoch(next_epoch)
        if self.verbose:
            logger.info(
                f"[proc {self.process_index}] slice transitioned to epoch {next_epoch} "
                f"(averaged={averaged_ok}, peers={num_peers})"
            )

    def _refresh_state_mirrors(self) -> List[np.ndarray]:
        """COLLECTIVE: stage current params+opt-stats to every process's host
        copies and (network process) into the state averager's mirrors, so state
        downloads serve fresh tensors. Returns the per-process host copies."""
        state_scratch = self.bridge.gather_to_host(self._state_leaves())
        if self.is_network_process:
            try:
                assert self.state_averager is not None
                with self.state_averager.get_tensors() as tensors:
                    for tensor, fresh in zip(tensors, state_scratch):
                        np.copyto(tensor, fresh)
            except Exception as e:
                # non-fatal: the download mirrors stay one epoch staler; raising
                # here would strand the followers at the next collective
                logger.warning(f"failed to refresh state mirrors: {e!r}")
        return state_scratch

    def _collective_state_phase(self, next_epoch: int, num_peers: int) -> None:
        """Stage params+opt-stats to the state mirrors; every ``average_state_every``
        epochs additionally average them with the swarm and adopt the result."""
        state_scratch = self._refresh_state_mirrors()

        run_round = num_peers > 1 and next_epoch % self.average_state_every == 0
        if not run_round:
            return
        ok = False
        if self.is_network_process:
            # round + averaged-result readback both precede the flag broadcast,
            # under one guard (same hang-proofing as the gradient phase)
            try:
                assert self.state_averager is not None
                ok = (
                    self.state_averager.step(
                        timeout=self.averaging_timeout,
                        scheduled_time=get_dht_time() + self._matchmaking_delay(),
                    )
                    is not None
                )
                if ok:
                    with self.state_averager.get_tensors() as tensors:
                        for mirror, tensor in zip(state_scratch, tensors):
                            np.copyto(mirror, tensor)
            except Exception as e:
                ok = False
                logger.warning(f"slice state averaging failed: {e!r}")
        flag = _broadcast(np.asarray([1.0 if ok else 0.0], np.float32))
        if not bool(flag[0] >= 0.5):
            return
        for i in range(len(state_scratch)):
            state_scratch[i] = _broadcast(np.ascontiguousarray(state_scratch[i]))
        self._adopt_state_tensors(state_scratch)

    # ------------------------------------------------------------------ catch-up

    def _collective_catch_up(self, global_epoch: int) -> bool:
        """We are behind the swarm: process 0 downloads a donor's state, then the
        whole slice adopts it collectively (broadcast + shard upload) — the
        reference load_state_from_peers path (optimizer.py:655-717), landing on
        every process's shards. Returns True when a donor's state was adopted."""
        # header = [ok, epoch]: the epoch is broadcast on BOTH outcomes — on the
        # failure path every process must adopt the SAME epoch (process 0's view
        # can differ from a follower's argument, and divergent epochs desync the
        # collective schedule of later phases)
        _C_EPOCH_TRANSITIONS.inc(kind="catch_up")
        header = np.asarray([0.0, float(global_epoch)], np.float32)
        tensors: Optional[List[np.ndarray]] = None
        if self.is_network_process:
            assert self.state_averager is not None
            logger.info(
                f"slice epoch {self.local_epoch} is behind the swarm ({global_epoch}); downloading state"
            )
            state_leaves = self._state_leaves()
            try:
                result = self.state_averager.load_state_from_peers(timeout=self.load_state_timeout)
            except Exception as e:
                logger.warning(f"state download failed: {e!r}")
                result = None
            if result is not None:
                metadata, downloaded = result
                # count AND per-leaf sizes must match BEFORE broadcasting ok=1: a
                # shape-mismatched donor failing mid-adoption would leave the
                # followers parked in a leaf broadcast forever
                shapes_ok = len(downloaded) == len(state_leaves) and all(
                    np.asarray(t).size == int(np.prod(leaf.shape))
                    for t, leaf in zip(downloaded, state_leaves)
                )
                if shapes_ok:
                    tensors = [np.asarray(t, np.float32) for t in downloaded]
                    epoch = (
                        int(metadata["epoch"])
                        if isinstance(metadata, dict) and "epoch" in metadata
                        else global_epoch
                    )
                    header = np.asarray([1.0, float(max(epoch, global_epoch))], np.float32)
                else:
                    logger.warning(
                        f"donor state does not match our schema "
                        f"({len(downloaded)} tensors vs {len(state_leaves)} expected); ignoring"
                    )
        header = _broadcast(header)
        ok, adopted_epoch = bool(header[0] >= 0.5), int(header[1])
        if not ok:
            # could not download: every process adopts the BROADCAST epoch so we
            # stop re-triggering and stay in collective lockstep
            # (reference optimizer.py:481-482 fallback)
            self.local_epoch = max(self.local_epoch, adopted_epoch)
            return False

        # collective adoption: per-leaf broadcast from process 0, then every
        # process uploads its local shards (same fabric path as SliceAverager)
        state_leaves = self._state_leaves()
        adopted: List[np.ndarray] = []
        for i, leaf in enumerate(state_leaves):
            value = tensors[i] if tensors is not None else np.zeros(leaf.shape, np.float32)
            adopted.append(_broadcast(np.ascontiguousarray(value.reshape(leaf.shape))))
        self._adopt_checkpoint(adopted, adopted_epoch)
        logger.info(f"[proc {self.process_index}] slice adopted swarm state at epoch {adopted_epoch}")
        return True

    def _adopt_checkpoint(self, tensors: List[np.ndarray], epoch: int) -> None:
        """Shared adoption tail for catch-up and checkpoint restore (COLLECTIVE):
        validate against the state schema BEFORE touching anything (a half-restored
        optimizer — new params, stale Adam moments — is worse than an error), write
        the sharded device state, fast-forward counters/epoch, reset accumulation,
        and restage the state mirrors so downloads immediately serve the adopted
        state at its true epoch rather than init-time zeros."""
        state_leaves = self._state_leaves()
        if len(tensors) != len(state_leaves) or any(
            int(np.asarray(t).size) != int(np.prod(leaf.shape))
            for t, leaf in zip(tensors, state_leaves)
        ):
            raise ValueError(
                f"checkpoint tensors do not match the state schema "
                f"({len(tensors)} tensors vs {len(state_leaves)} leaves)"
            )
        self._adopt_state_tensors(tensors)
        self._set_opt_counts(epoch)
        self.local_epoch = epoch
        self._accum = self._jit_zeros_like()(self.params)
        self._samples = 0
        if self.is_network_process:
            assert self.state_averager is not None and self.tracker is not None
            # the adopted host tensors ARE the new state: restage the download
            # mirrors from them directly — no redundant device→host gather of
            # what was just scattered
            with self.state_averager.get_tensors() as mirrors:
                for mirror, tensor, leaf in zip(mirrors, tensors, state_leaves):
                    np.copyto(mirror, np.asarray(tensor, np.float32).reshape(leaf.shape))
            self.state_averager.state_sharing_priority = epoch
            self.tracker.report_local_progress(epoch, 0)

    def _adopt_state_tensors(self, host_tensors: List[np.ndarray]) -> None:
        """Write host values (identical on every process) into the sharded device
        state: params first, then the selected optimizer-statistic leaves."""
        n_params = len(self._params_leaves)
        new_param_leaves = [
            self.bridge.scatter_leaf(leaf, value)
            for leaf, value in zip(self._params_leaves, host_tensors[:n_params])
        ]
        self.params = jax.tree_util.tree_unflatten(self._params_treedef, new_param_leaves)
        self._refresh_param_leaves()
        opt_leaves = jax.tree_util.tree_flatten(self.opt_state)[0]
        for slot, value in zip(self._averaged_opt_indices, host_tensors[n_params:]):
            opt_leaves[slot] = self.bridge.scatter_leaf(opt_leaves[slot], value)
        self.opt_state = jax.tree_util.tree_unflatten(self._opt_treedef, opt_leaves)

    def _set_opt_counts(self, epoch: int) -> None:
        """Fast-forward optax integer step counters to the adopted epoch so LR
        schedules resume correctly (collaborative convention: one update == one
        epoch; reference state_averager.py:700-704)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.opt_state)
        new_leaves = []
        for key_path, leaf in flat:
            is_count = bool(
                key_path
                and getattr(key_path[-1], "name", None) == "count"
                and hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.integer)
                and getattr(leaf, "ndim", None) == 0
            )
            if is_count:
                new_leaves.append(
                    self.bridge.scatter_leaf(leaf, np.asarray(epoch, leaf.dtype))
                )
            else:
                new_leaves.append(leaf)
        self.opt_state = jax.tree_util.tree_unflatten(self._opt_treedef, new_leaves)

    # ------------------------------------------------------------------ lifecycle

    def state_dict(self) -> dict:
        """User-level checkpoint with the epoch embedded (API parity with
        ``Optimizer.state_dict``, reference optimizer.py:719-727). COLLECTIVE:
        every process must call it (the gather is a mesh collective on a
        multi-process mesh); every process returns the same full host tensors.
        Takes the step lock so a checkpoint can never capture a torn mid-epoch
        state (params advanced but epoch not yet). With ``delay_grad_averaging``
        a checkpoint taken while a round is in flight captures the pre-update
        params at the CURRENT epoch — consistent, one round behind (the pending
        gradients are accumulator-external state, exactly as between boundaries
        in synchronous mode). NOTE: the lock covers
        concurrent threads WITHIN one process only — on a multi-process mesh all
        collective calls (step/checkpoint/restore) must come from one thread per
        process in the same order, or the processes' collectives mismatch."""
        with self._step_lock:
            tensors = self.bridge.gather_to_host(self._state_leaves())
            return {"epoch": int(self.local_epoch), "tensors": tensors}

    def load_state_dict(self, state: dict) -> None:
        """Restore a checkpoint onto the sharded device state. COLLECTIVE: every
        process must call it with the same checkpoint. Takes the step lock — a
        restore racing a training step in another thread would swap the param
        tree under it (single-process protection only; see ``state_dict``'s
        multi-process ordering note). An in-flight delayed round is discarded:
        its staged gradients were computed against the state being replaced, and
        landing them on the restored params would silently corrupt it."""
        with self._step_lock:
            self._discard_pending()
            self._adopt_checkpoint(
                [np.asarray(t, np.float32) for t in state["tensors"]], int(state["epoch"])
            )

    def force_epoch_transition(self, num_peers: int = 1) -> None:
        """Run the collective epoch transition NOW with whatever has accumulated —
        the deterministic alternative to waiting for the tracker's async fetch
        (tests, drills, graceful drain before shutdown). COLLECTIVE: every process
        must call it; ``num_peers`` > 1 additionally attempts the swarm rounds.
        A pending delayed round is finished FIRST (process 0 waits it out and
        broadcasts the outcome), so no staged epoch is ever lost to a drain."""
        with self._step_lock:
            if self._pending is not None:
                ok = 0.0
                if self.is_network_process:
                    if self._bg_thread is not None:
                        self._bg_thread.join(timeout=self.averaging_timeout + 30.0)
                        if not self._bg_thread.is_alive() and (self._bg_outcome or {}).get("ok"):
                            ok = 1.0
                flag = _broadcast(np.asarray([ok], np.float32))
                self._finish_delayed_epoch(bool(flag[0] >= 0.5))
            self._collective_epoch_update(num_peers)

    def load_state_from_peers(self, timeout: Optional[float] = None) -> bool:
        """Explicit collective state download (every process must call this).
        Takes the step lock like every other public collective entry point — a
        concurrent ``step`` in another thread must not interleave with the
        catch-up and tear the param tree (advisor r4 finding)."""
        del timeout  # the network process uses self.load_state_timeout
        with self._step_lock:
            self._discard_pending()  # the download replaces what the round would update
            epoch_target = self.local_epoch
            if self.is_network_process and self.tracker is not None:
                epoch_target = max(epoch_target, self.tracker.global_epoch)
            return self._collective_catch_up(epoch_target)

    def shutdown(self) -> None:
        if self._bg_thread is not None:
            self._bg_thread.join(timeout=self.averaging_timeout + 30.0)
            self._bg_thread = None
        if self.tracker is not None:
            self.tracker.shutdown()
        if self.scheduled_grads is not None:
            self.scheduled_grads.cancel()
        if self.grad_averager is not None:
            self.grad_averager.shutdown()
        if self.state_averager is not None:
            self.state_averager.shutdown()
        if self.dht is not None:
            self.dht.shutdown()

    def __repr__(self):
        return (
            f"SliceOptimizer(run_id={self.run_id!r}, epoch={self.local_epoch}, "
            f"proc={self.process_index}, network={self.is_network_process})"
        )
