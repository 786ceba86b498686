"""Round-2 optimizer features: Delayed Parameter Updates (background epoch
transitions), delta-rule state averaging, aux-peer schema bootstrap, user-level
checkpointing with schedule replay, and the one-epoch-grace reload rule
(VERDICT r1 items 4, 5, 7, 8)."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from hivemind_tpu.dht import DHT
from hivemind_tpu.optim import GradientAverager, Optimizer, TrainingStateAverager

from swarm_utils import launch_dht_swarm


def _toy_problem(seed=0):
    rng = np.random.RandomState(seed)
    true_w = rng.randn(8).astype(np.float32)
    features = rng.randn(256, 8).astype(np.float32)
    targets = features @ true_w

    @jax.jit
    def loss_and_grad(params, x, y):
        def loss_fn(p):
            return jnp.mean((x @ p["w"] - y) ** 2)

        return jax.value_and_grad(loss_fn)(params)

    return features, targets, loss_and_grad


# The DPU tests train until four epochs have closed: a bound in epochs, with a deadline of
# its own in steps. A round that a loaded host makes miss its 12 s `averaging_timeout`
# falls back to local gradients and costs the test those seconds, not its verdict (80
# steps of 0.25 s, as it was, ended the loop before one such round had); so does the last
# round of the peer whose partner has closed its fourth epoch and left (ROADMAP D13: with
# 30 s that was 60 s of a test in some runs, ISSUE 53).
_MOST_STEPS = 400


def test_dpu_overlapped_convergence():
    """delay_optimizer_step=True: step() must return while an epoch transition is
    still in flight, training must keep going meanwhile, and the loss must drop.
    The first transition of each peer is held at a gate the test owns until the
    stepping thread has seen step() return and stepped once more: the order of the
    two is the test's, not the scheduler's."""
    features, targets, loss_and_grad = _toy_problem()
    dhts = launch_dht_swarm(2)
    results, errors = {}, []
    steps_beside_a_transition = [0, 0]

    def run_peer(index: int, dht: DHT):
        try:
            params = {"w": jnp.zeros(8, jnp.float32)}
            opt = Optimizer(
                dht=dht, run_id="dpu_test", target_batch_size=64,
                params=params, optimizer=optax.sgd(0.3),
                batch_size_per_step=16, matchmaking_time=1.5, averaging_timeout=12,
                average_state_every=1, target_group_size=2,
                delay_optimizer_step=True,
                tracker_opts=dict(min_refresh_period=0.3, default_refresh_period=0.5),
            )
            gate = threading.Event()
            transition = opt._delayed_epoch_update

            def held_at_the_gate(*args):
                gate.wait(timeout=60)
                return transition(*args)

            opt._delayed_epoch_update = held_at_the_gate
            rng_local = np.random.RandomState(index)
            first_loss = last_loss = None

            def one_step():
                nonlocal first_loss, last_loss
                idx = rng_local.choice(len(features), 16)
                loss, grads = loss_and_grad(opt.params, features[idx], targets[idx])
                first_loss = first_loss if first_loss is not None else float(loss)
                last_loss = float(loss)
                opt.step(grads)

            for _ in range(_MOST_STEPS):
                if opt.local_epoch >= 4:
                    break
                one_step()
                if opt._pending_update is not None and not gate.is_set():
                    # step() returned with its transition in flight (it is at the gate),
                    # and training goes on beside it
                    assert not opt._pending_update.done()
                    one_step()
                    steps_beside_a_transition[index] += 1
                    gate.set()
                time.sleep(0.25)
            results[index] = (first_loss, last_loss, opt.local_epoch)
            opt.shutdown()
        except Exception as e:
            import traceback

            errors.append((index, e, traceback.format_exc()))

    threads = [threading.Thread(target=run_peer, args=(i, d)) for i, d in enumerate(dhts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    try:
        assert not errors, f"peer failures: {errors}"
        assert len(results) == 2
        assert steps_beside_a_transition == [1, 1], "a peer never had a transition in flight"
        for index, (first_loss, last_loss, epoch) in results.items():
            assert epoch >= 2, f"peer {index} stuck at epoch {epoch}"
            assert last_loss < first_loss / 5, (
                f"peer {index}: loss {first_loss:.4f} -> {last_loss:.4f} did not converge"
            )
    finally:
        for dht in dhts:
            dht.shutdown()


def test_delta_rule_preserves_concurrent_steps():
    """Deterministic delta-rule check: an optimizer step applied WHILE the averaging
    round is in flight must survive (result = current + average − snapshot)."""
    dht = DHT(start=True)
    try:
        params = {"w": jnp.full((4,), 10.0, jnp.float32)}
        averager = TrainingStateAverager(
            dht=dht, optimizer=optax.sgd(1.0), params=params, prefix="deltarule",
            start=True, delta_rule_averaging=True, average_opt_statistics=False,
        )

        fake_average = np.full((4,), 8.0, np.float32)  # pretend the group averaged to 8

        def fake_step(self_unused=None, timeout=None, wait=True, **kwargs):
            # concurrent local update lands mid-round: params 10 -> 6 (sgd lr=1, grad=4)
            averager.apply_optimizer_step({"w": jnp.full((4,), 4.0, jnp.float32)})
            with averager.get_tensors() as tensors:
                tensors[0][...] = fake_average
            return {}

        averager.step = fake_step
        assert averager.do_averaging_round(timeout=5)
        # delta rule: 6 + (8 − 10) = 4; plain overwrite would clobber the local step to 8
        np.testing.assert_allclose(np.asarray(averager.params["w"]), 4.0, atol=1e-6)
        averager.shutdown()
    finally:
        dht.shutdown()


def test_aux_peer_schema_bootstrap():
    """An auxiliary peer with ZERO model knowledge learns the gradient schema from
    the swarm (VERDICT r1 item 7)."""
    dhts = launch_dht_swarm(2)
    worker = aux = None
    try:
        params = {"w": jnp.zeros((6, 3), jnp.float32), "b": jnp.zeros(3, jnp.float32)}
        worker = Optimizer(
            dht=dhts[0], run_id="auxboot", target_batch_size=64,
            params=params, optimizer=optax.sgd(0.1), batch_size_per_step=16,
            matchmaking_time=1.0,
        )
        aux = Optimizer(
            dht=dhts[1], run_id="auxboot", target_batch_size=64,
            auxiliary=True, matchmaking_time=1.0, load_state_timeout=60,
        )
        assert aux.grad_averager is not None
        with aux.grad_averager.get_tensors() as tensors:
            shapes = sorted(tuple(t.shape) for t in tensors)
        assert shapes == sorted([(6, 3), (3,)])
        # matching schema hash means the aux peer can actually join groups
        assert aux.grad_averager.schema_hash == worker.grad_averager.schema_hash
    finally:
        for opt in (aux, worker):
            if opt is not None:
                opt.shutdown()
        for dht in dhts:
            dht.shutdown()


def test_state_dict_roundtrip_with_schedule_replay():
    """Checkpoint embeds the epoch; restoring replays optax step counters so LR
    schedules resume correctly (VERDICT r1 item 8)."""
    dht = DHT(start=True)
    try:
        schedule = optax.linear_schedule(0.0, 1.0, transition_steps=10)
        make_opt = lambda: optax.chain(optax.scale_by_adam(), optax.scale_by_schedule(schedule))
        params = {"w": jnp.ones((5,), jnp.float32)}

        source = Optimizer(
            dht=dht, run_id="ckpt_src", target_batch_size=64,
            params=params, optimizer=make_opt(), batch_size_per_step=16,
        )
        for _ in range(3):
            source.state_averager.apply_optimizer_step({"w": jnp.full((5,), 0.1, jnp.float32)})
        source.state_averager.local_epoch = 3
        checkpoint = source.state_dict()
        assert checkpoint["epoch"] == 3

        restored = Optimizer(
            dht=dht, run_id="ckpt_dst", target_batch_size=64,
            params=params, optimizer=make_opt(), batch_size_per_step=16,
        )
        restored.load_state_dict(checkpoint)
        assert restored.local_epoch == 3
        for mine, theirs in zip(
            restored.state_averager._host_state_tensors(),
            source.state_averager._host_state_tensors(),
        ):
            np.testing.assert_allclose(mine, theirs, atol=1e-6)
        # optax step counters were fast-forwarded to the epoch
        counts = [
            np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(restored.state_averager.opt_state)[0]
            if path and getattr(path[-1], "name", None) == "count"
        ]
        assert counts and all(c == 3 for c in counts)
        source.shutdown()
        restored.shutdown()
    finally:
        dht.shutdown()


def test_one_epoch_grace_reload_rule():
    """Peers trailing by exactly one epoch must NOT redownload state — in EVERY
    mode (reference optimizer.py:654-672: the first peer to see enough samples
    transitions and restarts the count, so global == local + 1 is normal network
    asynchrony and the tracker reports the trailing peer ready to transition
    itself). Two or more epochs behind must reload."""
    dht = DHT(start=True)
    opt = None
    try:
        params = {"w": jnp.zeros((2,), jnp.float32)}
        opt = Optimizer(
            dht=dht, run_id="grace", target_batch_size=64,
            params=params, optimizer=optax.sgd(0.1), delay_optimizer_step=True,
        )
        opt.tracker.shutdown()
        opt.tracker = SimpleNamespace(global_epoch=1, shutdown=lambda: None)
        assert opt.local_epoch == 0
        assert not opt._should_load_state_from_peers()  # one behind: grace
        opt.tracker.global_epoch = 2
        assert opt._should_load_state_from_peers()  # two behind: reload
        # an in-flight background transition suppresses reload entirely
        opt._pending_update = SimpleNamespace(done=lambda: False)
        assert not opt._should_load_state_from_peers()
        opt._pending_update = None
        # non-DPU peers get the SAME one-epoch grace (r5 reference-parity fix:
        # the old strict rule made sync peers discard progress and download
        # state whenever a groupmate merely transitioned first)
        opt.delay_optimizer_step = False
        opt.tracker.global_epoch = 1
        assert not opt._should_load_state_from_peers()
        opt.tracker.global_epoch = 2
        assert opt._should_load_state_from_peers()
    finally:
        if opt is not None:
            opt.shutdown()
        dht.shutdown()


def test_local_updates_with_delayed_state_averaging():
    """Local SGD: with use_local_updates the state rounds run on the background thread
    (as every state round does) while local steps continue, and land by the delta
    rule; peers converge and stay in sync."""
    features, targets, loss_and_grad = _toy_problem(seed=4)
    dhts = launch_dht_swarm(2)
    results, errors = {}, []

    def run_peer(index: int, dht: DHT):
        try:
            params = {"w": jnp.zeros(8, jnp.float32)}
            opt = Optimizer(
                dht=dht, run_id="localsgd", target_batch_size=64,
                params=params, optimizer=optax.sgd(0.2),
                batch_size_per_step=16, matchmaking_time=1.5, averaging_timeout=12,
                average_state_every=1, target_group_size=2,
                use_local_updates=True,
                tracker_opts=dict(min_refresh_period=0.3, default_refresh_period=0.5),
            )
            rng_local = np.random.RandomState(index)
            first_loss = last_loss = None
            for _ in range(_MOST_STEPS):
                if opt.local_epoch >= 4:
                    break
                idx = rng_local.choice(len(features), 16)
                loss, grads = loss_and_grad(opt.params, features[idx], targets[idx])
                first_loss = first_loss if first_loss is not None else float(loss)
                last_loss = float(loss)
                opt.step(grads)
                time.sleep(0.25)
            results[index] = (first_loss, last_loss, opt.local_epoch, np.asarray(opt.params["w"]))
            opt.shutdown()
        except Exception:
            import traceback

            errors.append((index, traceback.format_exc()))

    threads = [threading.Thread(target=run_peer, args=(i, d)) for i, d in enumerate(dhts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    try:
        assert not errors, f"peer failures: {errors}"
        assert len(results) == 2
        for index, (first_loss, last_loss, epoch, _w) in results.items():
            assert epoch >= 2, f"peer {index} stuck at epoch {epoch}"
            assert last_loss < first_loss / 5, (
                f"peer {index}: loss {first_loss:.4f} -> {last_loss:.4f} did not converge"
            )
        w0, w1 = results[0][3], results[1][3]
        assert np.allclose(w0, w1, atol=0.25), f"peers diverged: {np.abs(w0 - w1).max()}"
    finally:
        for dht in dhts:
            dht.shutdown()


def test_powersgd_with_dpu_convergence():
    """The recipe's two throughput flags COMBINED: PowerSGD low-rank gradient
    compression inside Delayed Parameter Updates — compressed chained-phase
    averaging rounds run on the background thread while training continues."""
    from hivemind_tpu.optim import PowerSGDGradientAverager

    features, targets, loss_and_grad = _toy_problem()
    dhts = launch_dht_swarm(2)
    results, errors = {}, []

    def run_peer(index: int, dht: DHT):
        try:
            # w as a matrix so PowerSGD actually compresses (vectors pass raw)
            params = {"w": jnp.zeros((8, 1), jnp.float32)}
            opt = Optimizer(
                dht=dht, run_id="psgd_dpu_test", target_batch_size=64,
                params=params, optimizer=optax.sgd(0.3),
                batch_size_per_step=16, matchmaking_time=1.5, averaging_timeout=12,
                average_state_every=1, target_group_size=2,
                delay_optimizer_step=True,
                grad_averager_factory=PowerSGDGradientAverager,
                grad_averager_opts={"averager_rank": 4},
                tracker_opts=dict(min_refresh_period=0.3, default_refresh_period=0.5),
            )
            rng_local = np.random.RandomState(index)
            first_loss = last_loss = None
            for _ in range(_MOST_STEPS):
                if opt.local_epoch >= 4:
                    break
                idx = rng_local.choice(len(features), 16)
                loss, grads = loss_and_grad(
                    {"w": opt.params["w"][:, 0]}, features[idx], targets[idx]
                )
                first_loss = first_loss if first_loss is not None else float(loss)
                last_loss = float(loss)
                opt.step({"w": grads["w"][:, None]})
                time.sleep(0.25)
            results[index] = (first_loss, last_loss, opt.local_epoch)
            opt.shutdown()
        except Exception as e:
            import traceback

            errors.append((index, e, traceback.format_exc()))

    threads = [threading.Thread(target=run_peer, args=(i, d)) for i, d in enumerate(dhts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    try:
        assert not errors, f"peer failures: {errors}"
        assert len(results) == 2
        for index, (first_loss, last_loss, epoch) in results.items():
            assert epoch >= 2, f"peer {index} stuck at epoch {epoch}"
            assert last_loss < first_loss / 5, (
                f"peer {index}: loss {first_loss:.4f} -> {last_loss:.4f} did not converge"
            )
    finally:
        for dht in dhts:
            dht.shutdown()


# ------------------------------------------------ the state round behind the next epoch's steps
#
# Two CPU peers with seeded gradients: in epoch e BOTH peers feed GRADS[e] at every step, so
# the averaged gradient is GRADS[e] whatever the weights and however many steps a peer took,
# and a plain optax LAMB loop over GRADS is what both must hold in the end. Peer 0's state
# rounds are stretched past the next gradient round, so its transitions find one in flight.

_EPOCHS = 4
_GRADS = np.random.RandomState(7).randn(_EPOCHS + 1, 24).astype(np.float32)
_W0 = np.random.RandomState(8).randn(24).astype(np.float32)


@pytest.fixture(scope="module")
def background_rounds():
    from hivemind_tpu.telemetry.ledger import LEDGER

    dhts = launch_dht_swarm(2)
    seen = SimpleNamespace(
        peers=[str(dht.peer_id) for dht in dhts], rounds=[], epochs=[], closing=[{}, {}],
        open_now=[0, 0], most_open=[0, 0], final=[None, None], errors=[],
    )

    def on_record(kind, record):
        (seen.rounds if kind == "round" else seen.epochs).append(dict(record))

    def state_rounds_of(index):
        return [r for r in seen.rounds if r.get("purpose") == "state" and r["peer"] == seen.peers[index]]

    def run_peer(index, dht):
        try:
            opt = Optimizer(
                dht=dht, run_id="behind_steps", target_batch_size=32, batch_size_per_step=16,
                params={"w": jnp.asarray(_W0)}, optimizer=optax.lamb(0.05),
                matchmaking_time=1.0, averaging_timeout=30, average_state_every=1, target_group_size=2,
                tracker_opts=dict(min_refresh_period=0.2, default_refresh_period=0.3),
            )
            plain_round = opt.state_averager.do_averaging_round

            def watched_round(**kwargs):
                seen.open_now[index] += 1
                seen.most_open[index] = max(seen.most_open[index], seen.open_now[index])
                try:
                    time.sleep(0.3)  # a group of two forms at once: keep the record behind the step's return
                    done = plain_round(**kwargs)
                    if index == 0:
                        time.sleep(3.5)  # still out when the next transition comes for it
                    return done
                finally:
                    seen.open_now[index] -= 1

            opt.state_averager.do_averaging_round = watched_round
            deadline = time.monotonic() + 150
            while opt.local_epoch < _EPOCHS and time.monotonic() < deadline:
                epoch = opt.local_epoch
                opt.step({"w": jnp.asarray(_GRADS[epoch])})
                if opt.local_epoch > epoch:
                    in_flight = opt._state_round is not None and not opt._state_round.done()
                    seen.closing[index][opt.local_epoch] = (len(state_rounds_of(index)), in_flight)
                time.sleep(0.05)
            opt._wait_for_state_round(60)
            seen.final[index] = (opt.local_epoch, np.asarray(opt.params["w"]), len(state_rounds_of(index)))
            opt.shutdown()
        except Exception:
            import traceback

            seen.errors.append((index, traceback.format_exc()))

    LEDGER.add_record_listener(on_record)
    threads = [threading.Thread(target=run_peer, args=(i, d)) for i, d in enumerate(dhts)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads), "a peer did not finish"
        assert not seen.errors, f"peer failures: {seen.errors}"
        yield seen
    finally:
        LEDGER.remove_record_listener(on_record)
        for dht in dhts:
            dht.shutdown()


def test_closing_step_returns_before_its_state_round(background_rounds):
    """(a) The step that closes epoch n returns while the round it launched is in flight:
    the ledger then holds the n-1 state rounds that landed, not the n-th. The round's own
    length reaches the NEXT epoch record, beside the seconds that transition waited."""
    seen = background_rounds
    for index, peer in enumerate(seen.peers):
        assert sorted(seen.closing[index]) == list(range(1, _EPOCHS + 1))
        for epoch, (recorded, in_flight) in seen.closing[index].items():
            assert in_flight and recorded == epoch - 1, (index, epoch, recorded, in_flight)
        records = {e["epoch"]: e for e in seen.epochs if e["peer"] == peer}
        assert records[1]["state_round_s"] == 0.0 and records[1]["state_round_wait_s"] == 0.0
        for epoch in range(2, _EPOCHS + 1):
            assert records[epoch]["state_round_s"] > 0.3, records[epoch]
            assert records[epoch]["state_round_wait_s"] >= 0.0
            # what the transition itself took no longer holds the round, only the wait for it
            spent = sum(records[epoch][k] for k in ("grad_round_s", "update_s", "state_round_wait_s"))
            assert spent == pytest.approx(records[epoch]["transition_s"], abs=0.25)


def test_background_rounds_match_a_plain_lamb_loop(background_rounds):
    """(b) After N epochs and a wait for the last round both peers hold what the same
    gradients give through plain optax LAMB with an average after every step (the peers
    start equal, so the average of two plain loops is the loop), within the fp16 wire."""
    optimizer = optax.lamb(0.05)
    params = {"w": jnp.asarray(_W0)}
    state = optimizer.init(params)
    smallest_update = np.inf
    for epoch in range(_EPOCHS):
        updates, state = optimizer.update({"w": jnp.asarray(_GRADS[epoch])}, state, params)
        smallest_update = min(smallest_update, float(jnp.abs(updates["w"]).max()))
        params = optax.apply_updates(params, updates)
    want = np.asarray(params["w"])
    # set from the dtype: one fp16 rounding (2**-11 of the value) a gradient round and a state
    # round an epoch, doubled for what LAMB's trust ratio makes of a rounded gradient
    tolerance = 2 * (2 * _EPOCHS) * 2.0**-11 * float(np.abs(want).max())
    assert smallest_update > 3 * tolerance  # a lost or doubled update lands far outside it
    for epoch, mine, _rounds in background_rounds.final:
        assert epoch == _EPOCHS
        np.testing.assert_allclose(mine, want, atol=tolerance)
    np.testing.assert_allclose(background_rounds.final[0][1], background_rounds.final[1][1], atol=tolerance)


def test_transition_waits_for_the_round_in_flight(background_rounds):
    """(d) Never two in flight, never one skipped: a transition that finds the last round
    still out waits for it, so the rounds recorded equal the epochs closed."""
    seen = background_rounds
    assert seen.most_open == [1, 1]
    for index, peer in enumerate(seen.peers):
        epoch, _params, rounds = seen.final[index]
        assert rounds == epoch == _EPOCHS, (index, rounds, epoch)
    waits = [e["state_round_wait_s"] for e in seen.epochs if e["peer"] == seen.peers[0] and e["epoch"] >= 2]
    # peer 0's rounds outlast a gradient round (on a loaded machine not every one)
    assert len(waits) == _EPOCHS - 1 and max(waits) > 0.5, waits


def test_step_between_the_merges_takes_survives_the_landing():
    """(c) The landing reads the current state, adds the round's delta and writes the sum
    in ONE critical section with apply_optimizer_step. Here an optimizer step arrives
    just when the landing has read the current state: a merge that takes the lock
    twice (read, then write) lets it in between and then overwrites it."""
    dht = DHT(start=True)
    averager = None
    try:
        averager = TrainingStateAverager(
            dht=dht, optimizer=optax.sgd(1.0), params={"w": jnp.full((4,), 10.0, jnp.float32)},
            prefix="atomic_landing", start=True, delta_rule_averaging=True, average_opt_statistics=False,
        )

        def fake_step(timeout=None, wait=True, **kwargs):
            with averager.get_tensors() as tensors:
                tensors[0][...] = 8.0  # the group averaged 10 and 6
            return {}

        averager.step = fake_step
        pull, pulls, stepper = averager._host_state_tensors, [], []

        def pull_then_step():
            tensors = pull()
            pulls.append(len(pulls))
            if len(pulls) == 2:  # the first pull is the snapshot, the second the landing's read
                thread = threading.Thread(
                    target=averager.apply_optimizer_step, args=({"w": jnp.full((4,), 3.0, jnp.float32)},)
                )
                thread.start()
                thread.join(0.5)  # held out by the landing's lock, or through already
                stepper.append(thread)
            return tensors

        averager._host_state_tensors = pull_then_step
        assert averager.do_averaging_round(timeout=5)
        stepper[0].join(10)
        assert not stepper[0].is_alive()
        # 10 + (8 - 10) landed, then the step: 8 - 3 = 5. A two-take merge reads 10, lets
        # the step write 7, then writes 8 over it.
        np.testing.assert_allclose(np.asarray(averager.params["w"]), 5.0, atol=1e-6)
    finally:
        if averager is not None:
            averager.shutdown()
        dht.shutdown()
