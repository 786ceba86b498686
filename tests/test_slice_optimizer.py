"""The FULL collaborative Optimizer on a multi-host slice (VERDICT r3 next-round #1):
TWO REAL ``jax.distributed`` processes form ONE mesh and train as ONE swarm peer with
the complete reference semantics — target_batch_size epochs, swarm gradient
averaging, progress tracker, periodic state averaging — in lockstep with a plain
host-resident ``Optimizer`` peer. A fresh slice then joins late and adopts the
swarm's state via the collective download path: the donor tensors must land on BOTH
processes' device shards (reference hivemind/optim/optimizer.py:32-790 semantics).

Only process 0 owns any networking; process 1 asserts it never constructs a DHT.
"""

import os
import subprocess
import sys

import pytest
from swarm_utils import run_jax_workers

_WORKER = r"""
import os, sys, threading, time

proc_id = int(sys.argv[1])
port = sys.argv[2]
dpu_mode = len(sys.argv) > 3 and sys.argv[3] == "dpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=proc_id
)
import numpy as np
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hivemind_tpu.dht import DHT
from hivemind_tpu.optim import Optimizer, SliceOptimizer

devices = np.array(jax.devices()).reshape(8)
mesh = Mesh(devices, ("dp",))

rng = np.random.RandomState(3)
w0 = rng.randn(8, 16).astype(np.float32) * 0.1
b0 = np.zeros(16, np.float32)
params = {
    "w": jax.device_put(w0, NamedSharding(mesh, P("dp"))),
    "b": jax.device_put(b0, NamedSharding(mesh, P())),
}
LR, TARGET = 0.1, 64
opt = optax.sgd(LR)
common_av = dict(target_group_size=2, min_group_size=2,
                 matchmaking_time=2.0, averaging_timeout=40.0)

host_dht = host_opt = None
if proc_id == 0:
    boot = DHT(start=True)
    maddrs = [str(m) for m in boot.get_visible_maddrs()]
    host_dht = DHT(initial_peers=maddrs, start=True)
    host_opt = Optimizer(
        dht=host_dht, run_id="slice_full_opt", params={"w": jnp.asarray(w0), "b": jnp.asarray(b0)},
        optimizer=opt, target_batch_size=TARGET, batch_size_per_step=16, **common_av,
    )
    dht_factory = lambda: boot
else:
    dht_factory = lambda: (_ for _ in ()).throw(
        AssertionError("dht_factory called on a non-network process")
    )

slice_opt = SliceOptimizer(
    mesh=mesh, params=params, optimizer=opt, dht_factory=dht_factory,
    run_id="slice_full_opt", target_batch_size=TARGET, batch_size_per_step=16,
    load_state_timeout=30.0, delay_grad_averaging=dpu_mode,
    **(common_av if proc_id == 0 else {}),
)
if proc_id != 0:
    # the structural claim: followers own NO networking objects at all
    assert slice_opt.dht is None and slice_opt.grad_averager is None
    assert slice_opt.state_averager is None and slice_opt.tracker is None

# deterministic gradients: slice contributes 1.0/2.0, host peer 3.0/4.0 — with
# equal sample weights the swarm average is w:2.0, b:3.0 per epoch, so after E
# epochs BOTH peers must hold exactly w0 - LR*2*E / b0 - LR*3*E (the large-batch
# equivalence the reference promises, optimizer.py:63-69)
g_slice = {
    "w": jax.device_put(np.full((8, 16), 1.0, np.float32), NamedSharding(mesh, P("dp"))),
    "b": jax.device_put(np.full(16, 2.0, np.float32), NamedSharding(mesh, P())),
}
g_host = {"w": jnp.full((8, 16), 3.0), "b": jnp.full(16, 4.0)}

EPOCHS = 2
stop = threading.Event()
def host_loop():
    # the host peer must stop at the SAME epoch as the slice: if it advanced solo,
    # the late joiner below would adopt a further-evolved state than expected
    while not stop.is_set() and host_opt.local_epoch < EPOCHS:
        host_opt.step(g_host, batch_size=16)
        time.sleep(0.25)

host_thread = None
if proc_id == 0:
    host_thread = threading.Thread(target=host_loop, daemon=True)
    host_thread.start()
deadline = time.monotonic() + 240
steps_while_pending = 0
while slice_opt.local_epoch < EPOCHS and time.monotonic() < deadline:
    # count BEFORE stepping: only a step ENTERED with a round already in flight
    # proves overlap (the launching step itself always leaves _pending set)
    entered_pending = slice_opt._pending is not None
    slice_opt.step(g_slice, batch_size=16)
    if entered_pending:
        steps_while_pending += 1
    time.sleep(0.25)
assert slice_opt.local_epoch >= EPOCHS, f"[{proc_id}] stuck at epoch {slice_opt.local_epoch}"
if dpu_mode:
    # drain the last in-flight round so every counted epoch's update landed
    drain = time.monotonic() + 90
    while slice_opt._pending is not None and time.monotonic() < drain:
        slice_opt.step(None)
        time.sleep(0.25)
    assert slice_opt._pending is None, f"[{proc_id}] pending round never adopted"
    # the overlap is real on BOTH processes: training steps ran while a swarm
    # round was in flight (the synchronous mode blocks inside the round)
    assert steps_while_pending >= 1, f"[{proc_id}] no overlap observed"
epochs_done = slice_opt.local_epoch

# weighted-by-samples group averaging (reference semantics — with the r5 grace
# rule a trailing peer transitions EARLY with its actual accumulated weight, so
# per-epoch applied gradients land BETWEEN the two peers' constants rather than
# at the equal-weight midpoint): every epoch's update must sit inside the
# [min(grads), max(grads)] envelope, and both peers must hold the SAME state
lo_w, hi_w = w0 - LR * 3.0 * epochs_done - 5e-3, w0 - LR * 1.0 * epochs_done + 5e-3
lo_b, hi_b = b0 - LR * 4.0 * epochs_done - 5e-3, b0 - LR * 2.0 * epochs_done + 5e-3

def check_shards_range(arr, lo, hi):
    assert arr.addressable_shards, "process holds no shards"
    for shard in arr.addressable_shards:
        data = np.asarray(shard.data)
        assert (data >= lo[shard.index]).all() and (data <= hi[shard.index]).all(), (
            data, lo[shard.index], hi[shard.index]
        )

def check_shards_match(arr, full, atol):
    assert arr.addressable_shards, "process holds no shards"
    for shard in arr.addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data), full[shard.index], rtol=0, atol=atol)

# every process verifies ITS shards: together both processes cover the arrays
check_shards_range(slice_opt.params["w"], lo_w, hi_w)
check_shards_range(slice_opt.params["b"], lo_b, hi_b)
assert slice_opt.params["w"].sharding.spec == P("dp")
print(f"TRAIN_OK_{proc_id} epochs={epochs_done}", flush=True)

if proc_id == 0:
    # the host peer adopted the same weighted group averages: equal state
    settle = time.monotonic() + 60
    while host_opt.local_epoch < epochs_done and time.monotonic() < settle:
        time.sleep(0.25)
    hw = np.asarray(jax.device_get(host_opt.params["w"]))
    check_shards_match(slice_opt.params["w"], hw, 5e-2)

# ---- late joiner: a FRESH slice (epoch 0) catches up through the tracker and
# adopts a donor's state COLLECTIVELY — the download must land on both
# processes' shards (VERDICT r3 done-bar)
if proc_id == 0:
    fresh_factory = lambda: DHT(initial_peers=maddrs, start=True)
else:
    fresh_factory = lambda: (_ for _ in ()).throw(AssertionError("follower built a DHT"))

fresh = SliceOptimizer(
    mesh=mesh,
    params={
        "w": jax.device_put(np.zeros((8, 16), np.float32), NamedSharding(mesh, P("dp"))),
        "b": jax.device_put(np.zeros(16, np.float32), NamedSharding(mesh, P())),
    },
    optimizer=opt, dht_factory=fresh_factory,
    run_id="slice_full_opt", target_batch_size=TARGET, batch_size_per_step=16,
    load_state_timeout=30.0, **(common_av if proc_id == 0 else {}),
)
deadline = time.monotonic() + 120
while fresh.local_epoch < epochs_done and time.monotonic() < deadline:
    fresh.step(None)  # no grads: pure catch-up through the tracker decision
    time.sleep(0.5)
assert fresh.local_epoch >= epochs_done, f"[{proc_id}] late joiner stuck at {fresh.local_epoch}"
# the joiner adopted the DONOR's state: its shards equal the trained slice's
# (mirrors are refreshed at every transition, and training has stopped)
for name in ("w", "b"):
    donor_full = np.zeros(fresh.params[name].shape, np.float32)
    for shard in slice_opt.params[name].addressable_shards:
        donor_full[shard.index] = np.asarray(shard.data)
    # each process checks ITS joiner shards against ITS donor shards (same mesh
    # layout on both optimizers, so the local shard indices coincide)
    for shard in fresh.params[name].addressable_shards:
        np.testing.assert_allclose(
            np.asarray(shard.data), donor_full[shard.index], rtol=0, atol=5e-3
        )
print(f"JOIN_OK_{proc_id} epoch={fresh.local_epoch}", flush=True)

stop.set()
if host_thread is not None:
    host_thread.join(timeout=60)
fresh.shutdown()
slice_opt.shutdown()
if proc_id == 0:
    host_opt.shutdown(); host_dht.shutdown()
print(f"SLICE_OPT_OK_{proc_id}", flush=True)
"""


def _assert_two_process_slice_trains(tmp_path, mode: str):
    for i, (code, out) in enumerate(run_jax_workers(_WORKER, tmp_path, [mode])):
        assert code == 0, f"worker {i} exited {code}:\n{out[-4000:]}"
        for marker in ("TRAIN_OK", "JOIN_OK", "SLICE_OPT_OK"):
            assert f"{marker}_{i}" in out, out[-4000:]


def test_full_optimizer_on_two_process_slice(tmp_path):
    _assert_two_process_slice_trains(tmp_path, "sync")


def test_full_optimizer_on_two_process_slice_dpu(tmp_path):
    """The DELAYED (DPU) path under real multihost collectives: the same
    two-process worker with ``delay_grad_averaging=True`` — the launch/adopt
    lifecycle, the 8-slot decision broadcast, and the catch-up interplay must
    hold with a genuinely separate follower process (the single-process DPU
    tests cannot catch a cross-process collective-ordering divergence). Both
    workers additionally assert steps ran while a round was in flight."""
    _assert_two_process_slice_trains(tmp_path, "dpu")


def test_slice_collaborative_example_single_process():
    """The recipe in examples/slice_collaborative_training.py runs end to end on a
    single-process virtual mesh: a solo swarm still advances epochs (no round is
    attempted below 2 peers; local gradients apply) and the script exits cleanly."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the example sets its own device-count flag
    result = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "slice_collaborative_training.py"),
         "--platform", "cpu", "--devices_per_proc", "4", "--steps", "24",
         "--target_batch_size", "64", "--batch_size", "32", "--dim", "16"],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert result.returncode == 0, (result.stdout + result.stderr)[-3000:]
    combined = result.stdout + result.stderr
    assert "done: epoch" in combined, combined[-2000:]
    final_epoch = int(combined.rsplit("done: epoch", 1)[1].strip().split()[0])
    assert final_epoch >= 5, combined[-2000:]


def test_slice_optimizer_state_dict_roundtrip():
    """Checkpoint parity with the host Optimizer (reference optimizer.py:719-727):
    state_dict embeds the epoch and every averaged tensor (params + adam mu/nu);
    load_state_dict restores them onto the sharded device state and fast-forwards
    the optax counters, so one identical post-restore epoch update matches the
    original run exactly."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    boot = DHT(start=True)
    opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.ones((8, 4), np.float32), sharding)},
        optimizer=optax.adam(0.1), dht_factory=lambda: boot,
        run_id="ckpt_rt", target_batch_size=8, batch_size_per_step=8,
    )
    fresh = None
    try:
        g = {"w": jnp.full((8, 4), 1.0)}
        deadline = time.monotonic() + 90
        while opt.local_epoch < 3 and time.monotonic() < deadline:
            opt.step(g, batch_size=8)
            time.sleep(0.2)
        assert opt.local_epoch >= 3
        checkpoint = opt.state_dict()
        assert checkpoint["epoch"] == opt.local_epoch
        assert len(checkpoint["tensors"]) == 3  # params + adam mu + nu
        trained = np.asarray(jax.device_get(opt.params["w"]))

        # a DIFFERENT run_id: the restore target must not share a swarm with the
        # original — otherwise the original's tracker records can flip the
        # restored peer into the catch-up path mid-comparison (it would download
        # state instead of applying its gradient, making the adam assertion
        # vacuous) and 2-peer trackers would try real averaging rounds
        fresh = SliceOptimizer(
            mesh=mesh, params={"w": jax.device_put(np.zeros((8, 4), np.float32), sharding)},
            optimizer=optax.adam(0.1),
            dht_factory=lambda: DHT(
                initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True
            ),
            run_id="ckpt_rt_restored", target_batch_size=8, batch_size_per_step=8,
        )
        fresh.load_state_dict(checkpoint)
        assert fresh.local_epoch == checkpoint["epoch"]
        np.testing.assert_allclose(
            np.asarray(jax.device_get(fresh.params["w"])), trained, atol=1e-6
        )
        # adam statistics restored: one identical (solo, local-gradient) epoch
        # update on both sides must produce identical params. Exactly ONE
        # transition each: if step() already fired it via the tracker, forcing a
        # second would apply a spurious zero-grad adam update
        for instance in (opt, fresh):
            before = instance.local_epoch
            instance.step(g, batch_size=8)
            if instance.local_epoch == before:
                instance.force_epoch_transition()
        np.testing.assert_allclose(
            np.asarray(jax.device_get(fresh.params["w"])),
            np.asarray(jax.device_get(opt.params["w"])), atol=1e-6,
        )
    finally:
        if fresh is not None:
            fresh.shutdown()
        opt.shutdown()


def test_slice_optimizer_with_powersgd_interoperates_with_host_peer():
    """PowerSGD gradient compression on the slice tier: a SliceOptimizer with a
    PowerSGDGradientAverager factory trains in lockstep with a host Optimizer
    peer using the same factory. Constant gradients are exactly rank-1, so the
    factorized rounds are lossless and both peers must land on the exact
    large-batch average — and on each other."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer, PowerSGDGradientAverager, SliceOptimizer

    import functools

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    LR, TARGET = 0.1, 32
    # a partial (not a lambda) lets SliceOptimizer see the class and skip the
    # host accumulator allocation (its accumulation lives on device)
    factory = functools.partial(PowerSGDGradientAverager, averager_rank=1)

    boot = DHT(start=True)
    slice_opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 16), np.float32), sharding)},
        optimizer=optax.sgd(LR), dht_factory=lambda: boot,
        run_id="psgd_slice", target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=1.5, averaging_timeout=40.0,
        grad_averager_factory=factory,
    )
    q_seed = np.array(slice_opt.grad_averager._qs[0])  # warm-start Q before any round
    host_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    host_opt = Optimizer(
        dht=host_dht, run_id="psgd_slice", params={"w": jnp.zeros((8, 16))},
        optimizer=optax.sgd(LR), target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=1.5, averaging_timeout=40.0,
        grad_averager_factory=factory,
    )
    g_slice = {"w": jax.device_put(np.full((8, 16), 2.0, np.float32), sharding)}
    g_host = {"w": jnp.full((8, 16), 4.0)}
    EPOCHS = 2
    stop = threading.Event()

    def host_loop():
        while not stop.is_set() and host_opt.local_epoch < EPOCHS:
            host_opt.step(g_host, batch_size=8)
            time.sleep(0.2)

    thread = threading.Thread(target=host_loop, daemon=True)
    thread.start()
    try:
        deadline = time.monotonic() + 180
        while slice_opt.local_epoch < EPOCHS and time.monotonic() < deadline:
            slice_opt.step(g_slice, batch_size=8)
            time.sleep(0.2)
        assert slice_opt.local_epoch >= EPOCHS, f"stuck at {slice_opt.local_epoch}"
        epochs = slice_opt.local_epoch
        # the slice loop exits the moment IT transitions; let the host finish its
        # own epoch-2 transition before comparing (its thread stops itself there)
        settle = time.monotonic() + 60
        while host_opt.local_epoch < epochs and time.monotonic() < settle:
            time.sleep(0.2)
        stop.set()
        thread.join(timeout=60)
        assert host_opt.local_epoch >= epochs, f"host stuck at {host_opt.local_epoch}"
        # the device-side accumulation path really skipped the host buffers
        assert slice_opt.grad_averager._grad_accumulators is None
        sw = np.asarray(jax.device_get(slice_opt.params["w"]))
        hw = np.asarray(jax.device_get(host_opt.params["w"]))
        # both peers ADOPT the same factorized group average every epoch, so they
        # must agree exactly — regardless of how the sample split landed; the
        # value itself sits between the all-slice and all-host extremes (the
        # weighted mean of grads 2.0 and 4.0)
        np.testing.assert_allclose(sw, hw, atol=5e-3)
        assert (-LR * 4.0 * epochs - 5e-3) <= sw[0, 0] <= (-LR * 2.0 * epochs + 5e-3), sw[0, 0]
        # the compressed rounds really happened: a successful P/Q round replaces
        # the warm-start Q (seeded 0xC0FFEE) with the orthogonalized average
        assert not np.allclose(slice_opt.grad_averager._qs[0], q_seed), (
            "warm-start Q unchanged: no factorized round ever completed"
        )
    finally:
        stop.set()
        thread.join(timeout=60)
        slice_opt.shutdown()
        host_opt.shutdown()
        host_dht.shutdown()


def test_delay_grad_averaging_overlaps_training():
    """The slice-tier DPU analog (VERDICT r4 next-round #1): with
    ``delay_grad_averaging=True`` and a deliberately SLOW swarm round (2 s of
    injected latency inside the averager), the slice (a) keeps stepping while the
    round is in flight — synchronous mode would complete zero steps there — and
    (b) still reaches epoch lockstep with a host Optimizer peer on the exact
    same group averages: final params equal across peers and bounded by the
    all-slice / all-host gradient extremes (one-epoch-stale adoption loses no
    gradients and double-applies none)."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.averaging.averager import DecentralizedAverager
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer, SliceOptimizer

    ROUND_LATENCY = 2.0

    class SlowAverager(DecentralizedAverager):
        def step(self, *args, wait=True, **kwargs):
            if wait:  # only the blocking round call, not schedule-style dispatch
                time.sleep(ROUND_LATENCY)
            return super().step(*args, wait=wait, **kwargs)

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    LR, TARGET = 0.1, 256
    boot = DHT(start=True)
    slice_opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 16), np.float32), sharding)},
        optimizer=optax.sgd(LR), dht_factory=lambda: boot,
        run_id="dpu_slice", target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=4.0, averaging_timeout=60.0,
        delay_grad_averaging=True, grad_averager_factory=SlowAverager,
    )
    # force every round through the (slowed) blocking step call: pre-scheduled
    # controls would bypass the injection and blur the A/B
    slice_opt._maybe_schedule_gradient_averaging = lambda: None
    host_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    host_opt = Optimizer(
        dht=host_dht, run_id="dpu_slice", params={"w": jnp.zeros((8, 16))},
        optimizer=optax.sgd(LR), target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=4.0, averaging_timeout=60.0,
    )
    g_slice = {"w": jax.device_put(np.full((8, 16), 1.0, np.float32), sharding)}
    g_host = {"w": jnp.full((8, 16), 3.0)}
    EPOCHS = 2
    stop = threading.Event()

    def host_loop():
        while not stop.is_set() and host_opt.local_epoch < EPOCHS:
            host_opt.step(g_host, batch_size=8)
            time.sleep(0.1)

    thread = threading.Thread(target=host_loop, daemon=True)
    thread.start()
    steps_while_pending = 0
    try:
        deadline = time.monotonic() + 240
        while slice_opt.local_epoch < EPOCHS and time.monotonic() < deadline:
            # count BEFORE stepping: only a step ENTERED with a round already in
            # flight proves overlap (the launch itself always sets _pending)
            entered_pending = slice_opt._pending is not None
            slice_opt.step(g_slice, batch_size=8)
            if entered_pending:
                steps_while_pending += 1
            time.sleep(0.02)
        assert slice_opt.local_epoch >= EPOCHS, f"stuck at {slice_opt.local_epoch}"
        epochs = slice_opt.local_epoch
        # (a) the overlap: training steps completed while a swarm round was in
        # flight (in synchronous mode this count is structurally zero — step()
        # blocks inside the round)
        assert steps_while_pending >= 3, steps_while_pending
        # the epoch advances at LAUNCH (reference DPU semantics); drain the last
        # in-flight round so every counted epoch's update has landed
        drain = time.monotonic() + 120
        while slice_opt._pending is not None and time.monotonic() < drain:
            slice_opt.step(None)
            time.sleep(0.1)
        assert slice_opt._pending is None, "pending round never completed"
        settle = time.monotonic() + 90
        while host_opt.local_epoch < epochs and time.monotonic() < settle:
            time.sleep(0.2)
        stop.set()
        thread.join(timeout=60)
        assert host_opt.local_epoch >= epochs, f"host stuck at {host_opt.local_epoch}"
        # (b) both peers hold the SAME adopted group averages
        sw = np.asarray(jax.device_get(slice_opt.params["w"]))
        hw = np.asarray(jax.device_get(host_opt.params["w"]))
        np.testing.assert_allclose(sw, hw, atol=5e-3)
        assert (-LR * 3.0 * epochs - 5e-3) <= sw[0, 0] <= (-LR * 1.0 * epochs + 5e-3), sw[0, 0]
    finally:
        stop.set()
        thread.join(timeout=60)
        slice_opt.shutdown()
        host_opt.shutdown()
        host_dht.shutdown()


def test_broadcast_thinning_preserves_lockstep_and_transitions():
    """Per-step broadcast thinning (VERDICT r4 next-round #8): far from the epoch
    boundary, process 0 announces skip counts and subsequent steps run ZERO
    collectives — strictly fewer broadcasts than steps — yet the epoch
    transition still fires and applies the right update. Near the boundary the
    skip shrinks to 0 (the pre-scheduling window is honored)."""
    import time

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import hivemind_tpu.optim.slice_optimizer as slice_mod
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer

    broadcasts = {"count": 0}
    real_broadcast = slice_mod._broadcast

    def counting_broadcast(value):
        broadcasts["count"] += 1
        return real_broadcast(value)

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 4), np.float32), sharding)},
        optimizer=optax.sgd(0.1), dht_factory=lambda: DHT(start=True),
        run_id="thinned_bcast", target_batch_size=512, batch_size_per_step=8,
        max_broadcast_skip=4,
    )
    slice_mod._broadcast = counting_broadcast
    g = {"w": jax.device_put(np.ones((8, 4), np.float32), sharding)}
    try:
        steps = 0
        deadline = time.monotonic() + 120
        while opt.local_epoch < 1 and time.monotonic() < deadline:
            opt.step(g, batch_size=8)
            steps += 1
            time.sleep(0.05)
        assert opt.local_epoch >= 1, "no epoch transition under thinning"
        # decision broadcasts are a strict subset of steps (the transition itself
        # adds non-decision collectives, so compare against a thinning margin)
        assert broadcasts["count"] < steps, (broadcasts["count"], steps)
        assert opt._step_time_ema is not None
        # the solo local-gradient update really applied
        w = np.asarray(jax.device_get(opt.params["w"]))
        np.testing.assert_allclose(w, -0.1 * 1.0 * opt.local_epoch, atol=1e-5)
    finally:
        slice_mod._broadcast = real_broadcast
        opt.shutdown()


@pytest.mark.slow  # ~60 s; state_dict round-tripping stays covered in ~4 s by
# test_slice_optimizer_state_dict_roundtrip and
# test_optimizer_dpu.py::test_state_dict_roundtrip_with_schedule_replay
def test_load_state_dict_discards_pending_delayed_round():
    """A checkpoint restore during an in-flight delayed round must DISCARD the
    round: its staged gradients were computed against the replaced state, and
    landing them on the restored params would silently corrupt the checkpoint
    (review finding on the r5 DPU work). The restore wins; the next steps train
    from exactly the checkpoint."""
    import time

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer
    from hivemind_tpu.optim.progress_tracker import ProgressTracker

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    TARGET = 16
    boot = DHT(start=True)
    opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 4), np.float32), sharding)},
        optimizer=optax.sgd(0.1), dht_factory=lambda: boot,
        run_id="restore_vs_pending", target_batch_size=TARGET, batch_size_per_step=8,
        delay_grad_averaging=True, matchmaking_time=1.0, averaging_timeout=30.0,
    )
    ghost_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    ghost = ProgressTracker(ghost_dht, "restore_vs_pending", TARGET)
    try:
        checkpoint = opt.state_dict()  # the all-zeros epoch-0 state
        ghost.report_local_progress(0, TARGET)  # num_peers=2: delayed rounds engage
        g = {"w": jax.device_put(np.ones((8, 4), np.float32), sharding)}
        deadline = time.monotonic() + 60
        while opt._pending is None and time.monotonic() < deadline:
            opt.step(g, batch_size=8)
            time.sleep(0.1)
        assert opt._pending is not None, "no delayed round ever launched"

        opt.load_state_dict(checkpoint)
        assert opt._pending is None, "restore left the stale round pending"
        assert opt.local_epoch == checkpoint["epoch"]
        np.testing.assert_allclose(
            np.asarray(jax.device_get(opt.params["w"])), 0.0, atol=1e-6
        )
        # the next step must NOT adopt ghost-round leftovers onto the restore
        opt.step(None)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(opt.params["w"])), 0.0, atol=1e-6
        )
    finally:
        ghost.shutdown()
        ghost_dht.shutdown()
        opt.shutdown()


def test_thinned_steps_defer_network_errors_to_next_broadcast():
    """An error in process 0's networking DURING a skipped (collective-free) step
    must not raise there — that would desync the skip countdown across processes
    — but at the NEXT broadcast step, via the error-flagged decision vector."""
    import jax
    import numpy as np
    import optax
    import pytest
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 4), np.float32), sharding)},
        optimizer=optax.sgd(0.1), dht_factory=lambda: DHT(start=True),
        run_id="thinned_defer", target_batch_size=1 << 30, batch_size_per_step=1,
        max_broadcast_skip=4,
    )
    g = {"w": jax.device_put(np.ones((8, 4), np.float32), sharding)}
    try:
        deadline_steps = 200
        while opt._skip_remaining == 0 and deadline_steps:
            opt.step(g, batch_size=1)
            deadline_steps -= 1
        assert opt._skip_remaining > 0, "thinning never engaged"

        def boom(*args, **kwargs):
            raise OSError("injected during a skipped step")

        opt.tracker.report_local_progress = boom
        skipped_without_raise = 0
        with pytest.raises(OSError, match="injected during a skipped step"):
            for _ in range(opt._skip_remaining + 1):
                before = opt._skip_remaining
                opt.step(g, batch_size=1)
                if before > 0:
                    skipped_without_raise += 1  # skipped steps swallow + defer
        assert skipped_without_raise >= 1
    finally:
        opt.shutdown()


def test_network_process_failure_raises_in_lockstep_not_hangs():
    """Advisor r4 medium finding: if process 0's networking raises inside step()'s
    decision phase (DHT store failure, tracker shutdown), it must STILL broadcast
    — with the error flag set — so followers raise in lockstep instead of parking
    forever in the collective. On one process we can assert the p0 half: the
    original exception propagates (after the sentinel broadcast) rather than
    being swallowed or skipping the broadcast."""
    import jax
    import numpy as np
    import optax
    import pytest
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.ones((8, 4), np.float32), sharding)},
        optimizer=optax.sgd(0.1), dht_factory=lambda: DHT(start=True),
        run_id="sentinel_bcast", target_batch_size=64, batch_size_per_step=4,
    )
    try:
        g = {"w": jax.device_put(np.ones((8, 4), np.float32), sharding)}
        opt.step(g, batch_size=4)  # sanity: a healthy step works

        def boom(*args, **kwargs):
            raise OSError("injected: dht store failed")

        opt.tracker.report_local_progress = boom
        with pytest.raises(OSError, match="injected: dht store failed"):
            opt.step(g, batch_size=4)
    finally:
        opt.shutdown()


def test_one_swarm_all_four_roles():
    """The reference's heterogeneity story end-to-end WITH a slice in the group
    (VERDICT r4 next-round #5; reference allreduce.py:26-29 + optimizer.py:147-148):
    one run_id carries a SliceOptimizer peer, a host NODE, a firewalled CLIENT,
    and an AUX reducer. All four advance epochs in lockstep; the client joins
    rounds send-only (its averagers run client_mode — never dialable, never a
    leader); the aux peer owns no data (no params, weight-0 contributions,
    schema bootstrapped from the swarm)."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer, SliceOptimizer

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    LR, TARGET, EPOCHS = 0.1, 72, 2
    common = dict(
        run_id="four_roles", target_batch_size=TARGET,
        # the last peer to reach EPOCHS finds no partner for its state round and waits this out (ROADMAP D13)
        target_group_size=4, matchmaking_time=2.5, averaging_timeout=20.0,
    )
    boot = DHT(start=True)
    maddrs = [str(m) for m in boot.get_visible_maddrs()]
    slice_opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 16), np.float32), sharding)},
        optimizer=optax.sgd(LR), dht_factory=lambda: boot,
        batch_size_per_step=8, **common,
    )
    node_dht = DHT(initial_peers=maddrs, start=True)
    node_opt = Optimizer(
        dht=node_dht, params={"w": jnp.zeros((8, 16))}, optimizer=optax.sgd(LR),
        batch_size_per_step=8, **common,
    )
    client_dht = DHT(initial_peers=maddrs, start=True)
    client_opt = Optimizer(
        dht=client_dht, params={"w": jnp.zeros((8, 16))}, optimizer=optax.sgd(LR),
        batch_size_per_step=8, client_mode=True, **common,
    )
    aux_dht = DHT(initial_peers=maddrs, start=True)
    aux_opt = Optimizer(dht=aux_dht, load_state_timeout=60.0, **common, auxiliary=True)

    # per-role structure: the client's averager is client_mode (sends-only, never
    # a leader/dialable); the aux peer owns NO model state of its own
    assert client_opt.grad_averager.client_mode
    assert aux_opt.auxiliary and aux_opt.state_averager is None  # owns no model state
    with aux_opt.grad_averager.get_tensors() as aux_tensors:
        assert sorted(tuple(t.shape) for t in aux_tensors) == [(8, 16)]  # bootstrapped schema

    stop = threading.Event()
    g_node = {"w": jnp.full((8, 16), 2.0)}
    g_client = {"w": jnp.full((8, 16), 3.0)}

    def data_loop(opt, grads):
        while not stop.is_set() and opt.local_epoch < EPOCHS:
            opt.step(grads, batch_size=8)
            time.sleep(0.15)

    def aux_loop():
        while not stop.is_set() and aux_opt.local_epoch < EPOCHS:
            aux_opt.step()
            time.sleep(0.2)

    threads = [
        threading.Thread(target=data_loop, args=(node_opt, g_node), daemon=True),
        threading.Thread(target=data_loop, args=(client_opt, g_client), daemon=True),
        threading.Thread(target=aux_loop, daemon=True),
    ]
    for thread in threads:
        thread.start()
    g_slice = {"w": jax.device_put(np.full((8, 16), 1.0, np.float32), sharding)}
    try:
        deadline = time.monotonic() + 240
        while slice_opt.local_epoch < EPOCHS and time.monotonic() < deadline:
            slice_opt.step(g_slice, batch_size=8)
            time.sleep(0.1)
        assert slice_opt.local_epoch >= EPOCHS, f"slice stuck at {slice_opt.local_epoch}"
        # every role advances with the swarm (the aux's epoch is the tracker's)
        settle = time.monotonic() + 120
        peers = {"node": node_opt, "client": client_opt, "aux": aux_opt}
        while time.monotonic() < settle and any(
            p.local_epoch < EPOCHS for p in peers.values()
        ):
            time.sleep(0.2)
        for name, peer in peers.items():
            assert peer.local_epoch >= EPOCHS, f"{name} stuck at {peer.local_epoch}"
        for peer in (slice_opt, node_opt, client_opt):
            for leaf in jax.tree_util.tree_leaves(peer.params):
                assert np.isfinite(np.asarray(jax.device_get(leaf))).all()
    finally:
        stop.set()
        for thread in threads:
            thread.join(timeout=60)
        slice_opt.shutdown()
        node_opt.shutdown()
        client_opt.shutdown()
        aux_opt.shutdown()
        for dht in (node_dht, client_dht, aux_dht):
            dht.shutdown()


def test_slice_degrades_to_local_grads_and_recovers_on_groupmate_churn():
    """Churn for the slice tier (VERDICT r4 next-round #6, reference bar
    tests/test_allreduce_fault_tolerance.py:22-120): a groupmate that reports
    progress but VANISHES before the round leaves the slice's matchmaking empty —
    the epoch still transitions on local gradients and the chronic counter moves;
    when a real host peer replaces it, the next round succeeds and the counter
    resets."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer, SliceOptimizer
    from hivemind_tpu.optim.progress_tracker import ProgressTracker

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    LR, TARGET = 0.1, 32
    boot = DHT(start=True)
    slice_opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 16), np.float32), sharding)},
        optimizer=optax.sgd(LR), dht_factory=lambda: boot,
        run_id="churn_slice", target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=1.0, averaging_timeout=10.0,
    )
    ghost_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    ghost = ProgressTracker(ghost_dht, "churn_slice", TARGET)
    g_slice = {"w": jax.device_put(np.full((8, 16), 1.0, np.float32), sharding)}
    host_opt = host_dht = None
    try:
        # phase 1: the ghost reports a full batch of progress, then never shows up
        # for the round — the slice must transition on LOCAL gradients
        ghost.report_local_progress(0, TARGET)
        deadline = time.monotonic() + 90
        while slice_opt.local_epoch < 1 and time.monotonic() < deadline:
            slice_opt.step(g_slice, batch_size=8)
            time.sleep(0.1)
        assert slice_opt.local_epoch >= 1, "no epoch transition after groupmate vanished"
        assert slice_opt.consecutive_failed_averaging_rounds >= 1, (
            "the failed round must move the chronic counter"
        )
        w = np.asarray(jax.device_get(slice_opt.params["w"]))
        np.testing.assert_allclose(w, -LR * 1.0, atol=1e-5)  # exactly the local update

        # phase 2: a real host peer replaces the ghost; the next round succeeds
        ghost.shutdown()
        host_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
        host_opt = Optimizer(
            dht=host_dht, run_id="churn_slice", params={"w": jnp.asarray(w)},
            optimizer=optax.sgd(LR), target_batch_size=TARGET, batch_size_per_step=8,
            # as the slice's: the host's last round, after the slice has stopped, waits this out (ROADMAP D13)
            target_group_size=2, matchmaking_time=1.5, averaging_timeout=10.0,
        )
        target_epoch = slice_opt.local_epoch + 1
        stop = threading.Event()
        g_host = {"w": jnp.full((8, 16), 3.0)}

        def host_loop():
            while not stop.is_set() and host_opt.local_epoch < target_epoch + 5:
                host_opt.step(g_host, batch_size=8)
                time.sleep(0.15)

        thread = threading.Thread(target=host_loop, daemon=True)
        thread.start()
        # run until a round actually SUCCEEDS (counter resets); allow a couple of
        # epochs of slack for mistimed first windows on one contended core
        deadline = time.monotonic() + 150
        while time.monotonic() < deadline and not (
            slice_opt.local_epoch >= target_epoch
            and slice_opt.consecutive_failed_averaging_rounds == 0
        ):
            slice_opt.step(g_slice, batch_size=8)
            time.sleep(0.1)
        stop.set()
        thread.join(timeout=60)
        assert slice_opt.local_epoch >= target_epoch, "no recovery round"
        assert slice_opt.consecutive_failed_averaging_rounds == 0, (
            "a successful round must reset the chronic counter"
        )
        # the successful rounds really averaged: with the host's larger gradient
        # (3.0 vs 1.0) in the mix, the slice moved FURTHER than local-only would
        w2 = np.asarray(jax.device_get(slice_opt.params["w"]))
        local_only = w - LR * 1.0 * (slice_opt.local_epoch - 1)
        assert w2[0, 0] < local_only[0, 0] - 1e-4, (w2[0, 0], local_only[0, 0])
    finally:
        import contextlib

        with contextlib.suppress(Exception):
            ghost.shutdown()
        if host_opt is not None:
            host_opt.shutdown()
        if host_dht is not None:
            host_dht.shutdown()
        slice_opt.shutdown()


def test_slice_survives_groupmate_dying_mid_allreduce():
    """A host groupmate that dies MID-ALLREDUCE (sends one part, then its sends
    abort — Fault.FAIL_SENDING from the fault matrix, now armed through the
    first-class chaos engine): the slice's epoch still transitions without
    hanging, parameters stay finite, and after the faulty peer heals (rules
    cleared) a later round completes with both peers converging."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from test_allreduce_fault_tolerance import Fault, arm_fault

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer, SliceOptimizer
    from hivemind_tpu.resilience import CHAOS

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    LR, TARGET = 0.1, 32
    boot = DHT(start=True)
    # every averager in a group must agree on part_size_bytes (partitioning is
    # part of the wire contract); 64-byte parts make FAIL_SENDING strike
    # mid-stream rather than after the whole tensor
    slice_opt = SliceOptimizer(
        mesh=mesh, params={"w": jax.device_put(np.zeros((8, 16), np.float32), sharding)},
        optimizer=optax.sgd(LR), dht_factory=lambda: boot,
        run_id="midreduce_slice", target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=1.5, averaging_timeout=20.0,
        part_size_bytes=64, sender_timeout=3.0, reducer_timeout=6.0,
    )
    host_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    host_opt = Optimizer(
        dht=host_dht, run_id="midreduce_slice", params={"w": jnp.zeros((8, 16))},
        optimizer=optax.sgd(LR), target_batch_size=TARGET, batch_size_per_step=8,
        target_group_size=2, matchmaking_time=1.5, averaging_timeout=20.0,
        grad_averager_opts=dict(sender_timeout=3.0, reducer_timeout=6.0, part_size_bytes=64),
        state_averager_opts=dict(part_size_bytes=64, sender_timeout=3.0, reducer_timeout=6.0),
    )
    # the host peer's sends abort after the first part (scoped to its peer id:
    # the slice's own traffic through the shared engine stays clean)
    arm_fault(Fault.FAIL_SENDING, str(host_dht.peer_id))
    g_slice = {"w": jax.device_put(np.full((8, 16), 1.0, np.float32), sharding)}
    g_host = {"w": jnp.full((8, 16), 3.0)}
    stop = threading.Event()
    EPOCHS = 3

    def host_loop():
        while not stop.is_set() and host_opt.local_epoch < EPOCHS + 5:
            host_opt.step(g_host, batch_size=8)
            time.sleep(0.15)

    thread = threading.Thread(target=host_loop, daemon=True)
    thread.start()
    try:
        # epoch 1 under a mid-allreduce death: must complete, not hang
        deadline = time.monotonic() + 120
        while slice_opt.local_epoch < 1 and time.monotonic() < deadline:
            slice_opt.step(g_slice, batch_size=8)
            time.sleep(0.1)
        assert slice_opt.local_epoch >= 1, "slice hung on a groupmate dying mid-allreduce"
        w1 = np.asarray(jax.device_get(slice_opt.params["w"]))
        assert np.isfinite(w1).all()

        # the groupmate heals: run until a post-heal round SUCCEEDS (the counter
        # resets), allowing a couple of epochs of slack for mistimed windows
        CHAOS.clear()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and not (
            slice_opt.local_epoch >= EPOCHS
            and slice_opt.consecutive_failed_averaging_rounds == 0
        ):
            slice_opt.step(g_slice, batch_size=8)
            time.sleep(0.1)
        assert slice_opt.local_epoch >= EPOCHS, f"stuck at {slice_opt.local_epoch}"
        settle = time.monotonic() + 60
        while host_opt.local_epoch < slice_opt.local_epoch and time.monotonic() < settle:
            time.sleep(0.2)
        stop.set()
        thread.join(timeout=60)
        assert slice_opt.consecutive_failed_averaging_rounds == 0
        sw = np.asarray(jax.device_get(slice_opt.params["w"]))
        hw = np.asarray(jax.device_get(host_opt.params["w"]))
        np.testing.assert_allclose(sw, hw, atol=5e-3)
    finally:
        CHAOS.clear()
        stop.set()
        thread.join(timeout=60)
        slice_opt.shutdown()
        host_opt.shutdown()
        host_dht.shutdown()


def test_slice_state_download_fails_over_when_donor_dies_mid_stream():
    """The state donor dies mid-download while a slice catches up: the truncated
    stream (fewer tensors than the schema) must fail over IN-LOOP to the next
    donor — the slice adopts the healthy donor's state at the advertised epoch,
    never a half-written one (VERDICT r4 next-round #6, second scenario)."""
    import time

    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hivemind_tpu.averaging.averager import DecentralizedAverager
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import SliceOptimizer
    from hivemind_tpu.optim.progress_tracker import ProgressTracker

    DONOR_EPOCH = 3

    class HealthyDonor(DecentralizedAverager):
        async def _get_current_state(self):
            return {"epoch": DONOR_EPOCH}, self._snapshot_tensors()

    class TruncatingDonor(DecentralizedAverager):
        async def _get_current_state(self):
            # dies after streaming the first tensor: a clean early end-of-stream,
            # exactly what a SIGKILLed donor's socket close looks like post-frame
            return {"epoch": DONOR_EPOCH}, self._snapshot_tensors()[:1]

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    TARGET = 32
    boot = DHT(start=True)
    params = {
        "b": jax.device_put(np.zeros(16, np.float32), NamedSharding(mesh, P())),
        "w": jax.device_put(np.zeros((8, 16), np.float32), sharding),
    }
    slice_opt = SliceOptimizer(
        mesh=mesh, params=params, optimizer=optax.sgd(0.1), dht_factory=lambda: boot,
        run_id="donor_churn", target_batch_size=TARGET, batch_size_per_step=8,
        load_state_timeout=20.0,
    )
    state_templates = [np.zeros(leaf.shape, np.float32) for leaf in slice_opt._state_leaves()]
    donor_values = [np.full(t.shape, 7.0, np.float32) for t in state_templates]

    faulty_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    faulty = TruncatingDonor(
        [np.array(v) for v in donor_values], faulty_dht,
        prefix="donor_churn_state", start=True, declare_state_period=1.0,
    )
    healthy_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    healthy = HealthyDonor(
        [np.array(v) for v in donor_values], healthy_dht,
        prefix="donor_churn_state", start=True, declare_state_period=1.0,
    )
    # the faulty donor advertises the HIGHER priority, so it is tried first
    faulty.state_sharing_priority = DONOR_EPOCH + 5
    healthy.state_sharing_priority = DONOR_EPOCH
    ghost = ProgressTracker(healthy_dht, "donor_churn", TARGET)
    try:
        ghost.report_local_progress(DONOR_EPOCH, 0)
        time.sleep(3.0)  # let the re-declared priorities + progress land in the DHT
        g = {k: jax.device_put(np.ones(v.shape, np.float32), v.sharding) for k, v in params.items()}
        deadline = time.monotonic() + 90
        while slice_opt.local_epoch < DONOR_EPOCH and time.monotonic() < deadline:
            slice_opt.step(g, batch_size=8)
            time.sleep(0.2)
        assert slice_opt.local_epoch == DONOR_EPOCH, slice_opt.local_epoch
        # the adopted tensors are the HEALTHY donor's, not a truncated mix
        for leaf in jax.tree_util.tree_leaves(slice_opt.params):
            np.testing.assert_allclose(np.asarray(jax.device_get(leaf)), 7.0, atol=1e-5)
    finally:
        ghost.shutdown()
        faulty.shutdown()
        healthy.shutdown()
        faulty_dht.shutdown()
        healthy_dht.shutdown()
        slice_opt.shutdown()


def test_slice_chronic_failure_counter_and_backoff():
    """Host-Optimizer parity (optimizer.py:100-136): consecutive failed swarm
    rounds escalate to chronic failure, matchmaking lead time backs off
    exponentially (capped 8x), pre-scheduling is suppressed while chronic, and
    one success resets everything. Pure unit math — no network."""
    from hivemind_tpu.optim import SliceOptimizer

    opt = SliceOptimizer.__new__(SliceOptimizer)
    opt.matchmaking_time = 2.0
    opt.chronic_failure_threshold = 3
    opt._consecutive_failed_rounds = 0
    opt.is_network_process = True

    assert not opt.chronic_averaging_failure
    assert opt._matchmaking_delay() == 2.0
    opt._record_round_outcome(None)  # solo swarm: neither failure nor recovery
    assert opt.consecutive_failed_averaging_rounds == 0

    for _ in range(3):
        opt._record_round_outcome(False)
    assert opt.chronic_averaging_failure
    assert opt._matchmaking_delay() == 4.0  # 2.0 * 2^1
    opt._record_round_outcome(False)
    assert opt._matchmaking_delay() == 8.0
    for _ in range(10):
        opt._record_round_outcome(False)
    assert opt._matchmaking_delay() == 16.0  # capped at 8x

    opt._record_round_outcome(True)  # recovery resets
    assert opt.consecutive_failed_averaging_rounds == 0
    assert not opt.chronic_averaging_failure
    assert opt._matchmaking_delay() == 2.0
