"""SliceOptimizer scheduling guards (ISSUE 2 satellites): the broadcast-skip
window is capped by locally-known samples remaining to target_batch_size, and a
delayed round whose thread outlives its join timeout poisons the grad averager
(loud log + telemetry counter) instead of silently racing its buffers."""

import threading

import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hivemind_tpu.dht import DHT
from hivemind_tpu.optim import SliceOptimizer
from hivemind_tpu.optim.progress_tracker import GlobalTrainingProgress
from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.utils.timed_storage import get_dht_time


@pytest.fixture
def slice_opt():
    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    opt = SliceOptimizer(
        mesh=mesh,
        params={"w": jax.device_put(np.zeros((8, 4), np.float32), NamedSharding(mesh, P("dp")))},
        optimizer=optax.sgd(0.1),
        dht_factory=lambda: DHT(start=True),
        run_id="guards_test",
        target_batch_size=4096,
        batch_size_per_step=16,
        max_broadcast_skip=8,
    )
    try:
        yield opt
    finally:
        opt.shutdown()


def _set_global_progress(opt, samples_accumulated: int, eta_s: float = 1000.0) -> None:
    opt.tracker.global_progress = GlobalTrainingProgress(
        global_epoch=0,
        samples_accumulated=samples_accumulated,
        target_batch_size=opt.target_batch_size,
        num_peers=2,
        num_clients=0,
        eta_next_epoch=get_dht_time() + eta_s,
        next_fetch_time=get_dht_time() + eta_s,
    )


def test_suggest_skip_capped_by_remaining_samples(slice_opt):
    slice_opt._step_time_ema = 0.01  # far from the boundary in step-time terms

    # plenty of samples remaining: the ETA term dominates, full skip granted
    _set_global_progress(slice_opt, samples_accumulated=0)
    assert slice_opt._suggest_skip(False, False, False) == 8

    # 32 samples remaining at 16/step with the 2x margin -> at most 1 skip,
    # even though the (stale) ETA still claims the boundary is ~1000s away
    _set_global_progress(slice_opt, samples_accumulated=4064)
    assert slice_opt._suggest_skip(False, False, False) == 1

    # target already reached locally: no broadcast-free steps at all
    _set_global_progress(slice_opt, samples_accumulated=4096)
    assert slice_opt._suggest_skip(False, False, False) == 0

    # anything needing low-latency signaling still disables the skip entirely
    _set_global_progress(slice_opt, samples_accumulated=0)
    assert slice_opt._suggest_skip(True, False, False) == 0
    assert slice_opt._suggest_skip(False, True, False) == 0
    assert slice_opt._suggest_skip(False, False, True) == 0


def _poison_counter() -> float:
    metric = REGISTRY.get("hivemind_optim_poisoned_averager_rounds_total")
    return metric.value() if metric is not None else 0.0


def test_timed_out_discard_poisons_grad_averager(slice_opt):
    release = threading.Event()
    wedged = threading.Thread(target=release.wait, daemon=True)
    wedged.start()
    slice_opt._pending = {"scratch": [], "num_peers": 2}
    slice_opt._bg_thread = wedged
    slice_opt.averaging_timeout = -30.0  # join timeout (averaging_timeout + 30) == 0

    before = _poison_counter()
    slice_opt._discard_pending()
    assert slice_opt._bg_thread is None and slice_opt._pending is None
    assert slice_opt._grad_averager_poisoned()
    assert _poison_counter() == before + 1

    # while poisoned: rounds refuse the shared buffers (degrade to local)...
    assert slice_opt._run_swarm_round([np.zeros(4, np.float32)], 1.0, None) is False
    # ...and pre-scheduling declines to claim a control
    slice_opt._maybe_schedule_gradient_averaging()
    assert slice_opt.scheduled_grads is None

    # once the thread is confirmed dead the poison clears itself
    release.set()
    wedged.join(timeout=5.0)
    assert not slice_opt._grad_averager_poisoned()


def test_clean_discard_does_not_poison(slice_opt):
    done = threading.Thread(target=lambda: None)
    done.start()
    done.join(30)
    assert not done.is_alive()
    slice_opt._pending = {"scratch": [], "num_peers": 2}
    slice_opt._bg_thread = done
    before = _poison_counter()
    slice_opt._discard_pending()
    assert not slice_opt._grad_averager_poisoned()
    assert _poison_counter() == before
