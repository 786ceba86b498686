"""ISSUE 7: crash-safe state recovery — verified/resumable/striped state sync,
stale-donor rejection, shutdown retraction, and the local checkpoint store
(scope: reference averager.py:628-651 load_state_from_peers, hardened)."""

import asyncio
import time

import numpy as np
import pytest

from hivemind_tpu.averaging import DecentralizedAverager
from hivemind_tpu.averaging.state_sync import (
    _STATE_SYNC_DIGEST_FAILURES,
    _STATE_SYNC_FAILOVERS,
    _STATE_SYNC_STALE_DONORS,
    DigestMismatch,
    ManifestMismatch,
    StaleDonor,
    StateAssembly,
    StateUnavailable,
    _list_donor_candidates,
    _split_for_striping,
    _stream_from_donor,
    _try_striped_fetch,
    build_state_manifest,
)
from hivemind_tpu.compression import serialize_tensor, split_tensor_for_streaming
from hivemind_tpu.compression.base import NoCompression
from hivemind_tpu.optim.recovery import LocalCheckpointStore
from hivemind_tpu.proto import averaging_pb2, runtime_pb2
from hivemind_tpu.resilience import CHAOS, Deadline

from swarm_utils import launch_dht_swarm, shutdown_all


# ------------------------------------------------------------------ helpers


def _state_tensors(seed: int, n: int = 2):
    rng = np.random.RandomState(seed)
    return [rng.randn(123).astype(np.float32), rng.randn(3, 5).astype(np.float32)][:n]


def _serialized_state(tensors):
    return [serialize_tensor(t, NoCompression()) for t in tensors]


def _manifest_for(serialized, epoch=0, schema_hash="test-schema"):
    return build_state_manifest(serialized, schema_hash=schema_hash, epoch=epoch)


class _ScriptedStub:
    """An in-memory donor: serves a scripted manifest + chunk stream, records the
    ``have_tensors`` of every request, optionally dies after N chunk messages."""

    def __init__(self, serialized, manifest, *, fail_after_chunks=None, chunk_bytes=200):
        self.serialized = serialized
        self.manifest = manifest
        self.fail_after_chunks = fail_after_chunks
        self.chunk_bytes = chunk_bytes
        self.requests = []

    def rpc_download_state(self, request, timeout=None):
        self.requests.append(request)

        async def _gen():
            yield averaging_pb2.DownloadData(manifest=self.manifest)
            if request.manifest_only:
                return
            have = set(request.have_tensors)
            sent = 0
            for index, tensor in enumerate(self.serialized):
                if index in have:
                    continue
                for chunk in split_tensor_for_streaming(tensor, self.chunk_bytes):
                    if self.fail_after_chunks is not None and sent >= self.fail_after_chunks:
                        raise ConnectionError("scripted donor died mid-stream")
                    sent += 1
                    yield averaging_pb2.DownloadData(tensor_part=chunk, tensor_index=index)

        return _gen()


# ------------------------------------------------------------------ assembly units


def test_assembly_verifies_tensors_and_rejects_corruption():
    tensors = _state_tensors(0)
    serialized = _serialized_state(tensors)
    manifest = _manifest_for(serialized)
    assembly = StateAssembly()
    assembly.pin_manifest(manifest, "donor")

    # a flipped byte is caught at the tensor boundary, nothing is adopted
    corrupt = runtime_pb2.Tensor()
    corrupt.CopyFrom(serialized[0])
    payload = bytearray(corrupt.buffer)
    payload[7] ^= 0xFF
    corrupt.buffer = bytes(payload)
    with pytest.raises(DigestMismatch):
        assembly.feed(0, corrupt)
    assert 0 not in assembly.verified and assembly.digest_failures == 1

    # the same index recovers with the genuine bytes (failover donor)
    assembly.feed(0, serialized[0])
    assembly.feed(1, serialized[1])
    assert assembly.complete()
    result = assembly.result(["donor"])
    assert result.verified
    for got, want in zip(result.tensors, tensors):
        assert np.array_equal(got, want.astype(np.float32))


def test_assembly_rejects_stale_epoch_schema_and_unavailable():
    serialized = _serialized_state(_state_tensors(0))
    stale_before = _STATE_SYNC_STALE_DONORS.value()

    assembly = StateAssembly(min_epoch=5)
    with pytest.raises(StaleDonor):
        assembly.pin_manifest(_manifest_for(serialized, epoch=3), "old-donor")
    assert _STATE_SYNC_STALE_DONORS.value() == stale_before + 1
    assembly.pin_manifest(_manifest_for(serialized, epoch=5), "fresh-donor")  # boundary OK

    with pytest.raises(ManifestMismatch):
        StateAssembly(schema_hash="ours").pin_manifest(
            _manifest_for(serialized, schema_hash="theirs"), "donor"
        )
    with pytest.raises(ManifestMismatch):
        StateAssembly(expected_tensors=5).pin_manifest(_manifest_for(serialized), "donor")
    with pytest.raises(StateUnavailable):
        StateAssembly().pin_manifest(
            averaging_pb2.StateManifest(state_unavailable=True), "donor"
        )


def test_assembly_repin_on_divergent_failover_but_not_for_stripes():
    serialized_a = _serialized_state(_state_tensors(0))
    serialized_b = _serialized_state(_state_tensors(1))
    assembly = StateAssembly()
    assembly.pin_manifest(_manifest_for(serialized_a), "a")
    assembly.feed(0, serialized_a[0])
    assert list(assembly.verified) == [0]

    # a striping donor must match bit-for-bit
    with pytest.raises(ManifestMismatch):
        assembly.pin_manifest(_manifest_for(serialized_b), "b", allow_repin=False)
    assert list(assembly.verified) == [0]  # untouched

    # a failover donor with a different VALID state resets the assembly
    assembly.pin_manifest(_manifest_for(serialized_b), "b")
    assert not assembly.verified
    assembly.feed(0, serialized_b[0])
    assembly.feed(1, serialized_b[1])
    assert assembly.complete()


def test_stream_resume_continues_from_last_verified_tensor():
    """The headline resume guarantee: after donor A dies mid-stream, the request
    to donor B names exactly the already-verified tensors so only the missing
    ones travel again — and the final state is bitwise identical."""
    tensors = _state_tensors(3)
    serialized = _serialized_state(tensors)
    manifest = _manifest_for(serialized)
    # tensor 0 is 492 bytes → 3 chunks at 200 B; die right after it completes
    donor_a = _ScriptedStub(serialized, manifest, fail_after_chunks=3)
    donor_b = _ScriptedStub(serialized, manifest)
    assembly = StateAssembly()

    async def _run():
        with pytest.raises(ConnectionError):
            await _stream_from_donor(
                donor_a, assembly, "donor-a", want=None, deadline=Deadline(10)
            )
        assert list(assembly.verified) == [0], "tensor 0 must survive the donor's death"
        await _stream_from_donor(donor_b, assembly, "donor-b", want=None, deadline=Deadline(10))

    asyncio.run(_run())
    assert list(donor_b.requests[0].have_tensors) == [0], (
        "the failover request must resume after the last verified tensor"
    )
    assert assembly.complete()
    for got, want in zip(assembly.result(["a", "b"]).tensors, tensors):
        assert np.array_equal(got, want.astype(np.float32))


def test_divergent_failover_donor_completes_without_livelock(monkeypatch):
    """Regression: the failover request's have_tensors is computed against the
    OLD manifest; when the new donor's (valid, divergent) manifest re-pins the
    assembly, the donor was told to skip tensors the repin just discarded. One
    immediate same-donor retry with the fresh have-set must complete the
    download — not fail over in circles against an actively-training donor."""
    import hivemind_tpu.averaging.state_sync as state_sync_module
    from hivemind_tpu.averaging.state_sync import download_state_verified

    tensors_a, tensors_b = _state_tensors(0), _state_tensors(1)
    serialized_a, serialized_b = _serialized_state(tensors_a), _serialized_state(tensors_b)
    # donor A completes tensor 0 (3 chunks at 200 B), then dies mid-stream
    stubs = {
        "a": _ScriptedStub(serialized_a, _manifest_for(serialized_a), fail_after_chunks=3),
        "b": _ScriptedStub(serialized_b, _manifest_for(serialized_b)),
    }

    async def _fake_candidates(dht, prefix, exclude_peer_id):
        return ["a", "b"]

    monkeypatch.setattr(state_sync_module, "_list_donor_candidates", _fake_candidates)

    result = asyncio.run(
        download_state_verified(
            None, None, "livelock", lambda p2p, donor, namespace: stubs[str(donor)],
            timeout=10,
        )
    )
    assert result is not None and result.verified
    for got, want in zip(result.tensors, tensors_b):
        assert np.array_equal(got, want.astype(np.float32))
    # donor B saw the inverted request first (skip tensor 0, verified under A's
    # manifest); tensor 1 still landed and re-verified under B's re-pinned
    # manifest, so the immediate same-donor retry re-requests ONLY tensor 0
    payload_requests = [r for r in stubs["b"].requests if not r.manifest_only]
    assert [list(r.have_tensors) for r in payload_requests] == [[0], [1]]


def _big_state(n_tensors=8, floats_each=1 << 18):
    rng = np.random.RandomState(42)
    return [rng.randn(floats_each).astype(np.float32) for _ in range(n_tensors)]


def test_striped_fetch_downloads_disjoint_halves_concurrently():
    """Two donors with bit-identical manifests each carry roughly half the
    missing bytes; the merged assembly is complete and bitwise correct."""
    tensors = _big_state()  # 8 x 1 MiB: far past MIN_STRIPE_BYTES
    serialized = _serialized_state(tensors)
    manifest = _manifest_for(serialized)
    stubs = {
        "a": _ScriptedStub(serialized, manifest, chunk_bytes=1 << 20),
        "b": _ScriptedStub(serialized, manifest, chunk_bytes=1 << 20),
    }
    assembly = StateAssembly()
    assembly.pin_manifest(manifest, "a")

    async def _run():
        return await _try_striped_fetch(
            assembly, "a", ["b"],
            get_stub=lambda p2p, donor, namespace: stubs[str(donor)],
            p2p=None, prefix="striped", deadline=Deadline(30),
            max_stripes=2, used_donors=[],
        )

    assert asyncio.run(_run()) is True
    assert assembly.complete()
    for got, want in zip(assembly.result(["a", "b"]).tensors, tensors):
        assert np.array_equal(got, want)
    # the LAST request each stub saw is the payload fetch (b's first was the
    # manifest probe); their have-sets must partition the tensors disjointly
    want_a = set(range(len(tensors))) - set(stubs["a"].requests[-1].have_tensors)
    want_b = set(range(len(tensors))) - set(stubs["b"].requests[-1].have_tensors)
    assert want_a and want_b and not (want_a & want_b)
    assert want_a | want_b == set(range(len(tensors)))


def test_striped_fetch_survives_one_stripe_dying():
    """A stripe donor dying mid-transfer loses only its own share: the other
    stripe's tensors stay verified and the failover loop finishes the rest."""
    tensors = _big_state()
    serialized = _serialized_state(tensors)
    manifest = _manifest_for(serialized)
    dying = _ScriptedStub(serialized, manifest, chunk_bytes=1 << 20, fail_after_chunks=1)
    healthy = _ScriptedStub(serialized, manifest, chunk_bytes=1 << 20)
    stubs = {"a": healthy, "b": dying}
    assembly = StateAssembly()
    assembly.pin_manifest(manifest, "a")

    async def _run():
        return await _try_striped_fetch(
            assembly, "a", ["b"],
            get_stub=lambda p2p, donor, namespace: stubs[str(donor)],
            p2p=None, prefix="striped", deadline=Deadline(30),
            max_stripes=2, used_donors=[],
        )

    assert asyncio.run(_run()) is True
    healthy_share = set(range(len(tensors))) - set(healthy.requests[-1].have_tensors)
    assert healthy_share <= set(assembly.verified), "the surviving stripe must be intact"
    assert not assembly.complete(), "the dead stripe's share is still missing"
    for index in assembly.verified:
        assert np.array_equal(assembly.verified[index], tensors[index])


def test_split_for_striping_is_balanced_and_complete():
    rng = np.random.RandomState(0)
    tensors = [rng.randn(n).astype(np.float32) for n in (1000, 10, 500, 300, 7, 900)]
    serialized = _serialized_state(tensors)
    assembly = StateAssembly()
    assembly.pin_manifest(_manifest_for(serialized), "donor")
    stripes = _split_for_striping(assembly, 2)
    flat = sorted(index for stripe in stripes for index in stripe)
    assert flat == list(range(len(tensors))), "every tensor assigned exactly once"
    loads = [
        sum(int(assembly.manifest.tensors[i].num_bytes) for i in stripe) for stripe in stripes
    ]
    assert max(loads) <= 2 * min(loads), f"stripes badly unbalanced: {loads}"


# ------------------------------------------------------------------ checkpoints


def test_checkpoint_store_roundtrip_retention_and_digest(tmp_path):
    store = LocalCheckpointStore(tmp_path, keep_last=2)
    states = {
        epoch: {
            "epoch": epoch,
            "tensors": [t * epoch for t in _state_tensors(0)],
            "opt_counts": [epoch],
        }
        for epoch in (1, 2, 3)
    }
    for epoch in (1, 2, 3):
        store.save(states[epoch])
    assert len(store.checkpoints()) == 2, "retention must prune beyond keep_last"
    loaded = store.load_latest()
    assert loaded["epoch"] == 3 and loaded["opt_counts"] == [3]
    for got, want in zip(loaded["tensors"], states[3]["tensors"]):
        assert np.array_equal(got, want)


def test_checkpoint_kill_during_save_leaves_previous_loadable(tmp_path):
    """kill -9 atomicity: a crash at ANY point of a save leaves the previous
    checkpoint adoptable — a torn temp file is invisible, and a torn final file
    is rejected by its digest."""
    store = LocalCheckpointStore(tmp_path, keep_last=3)
    good = {"epoch": 7, "tensors": _state_tensors(1), "opt_counts": []}
    store.save(good)

    # crash BEFORE the rename: only a temp file exists for epoch 8 (aged so the
    # sweep treats it as a dead process's leftovers, not a live writer's file)
    import os

    torn_tmp = tmp_path / ".state-save-killed9.tmp"
    torn_tmp.write_bytes(b"half a checkpoint")
    old = 1e9
    os.utime(torn_tmp, (old, old))
    # crash that somehow tore the published bytes: valid name, wrong digest
    fake = tmp_path / f"state-e{8:012d}-{'ab' * 16}.ckpt.npz"
    fake.write_bytes(b"torn npz bytes")

    loaded = store.load_latest()
    assert loaded is not None and loaded["epoch"] == 7
    for got, want in zip(loaded["tensors"], good["tensors"]):
        assert np.array_equal(got, np.asarray(want))
    store.prune()
    assert not torn_tmp.exists(), "interrupted temp files are swept"


# ------------------------------------------------------------------ real-swarm paths


def _make_averagers(dhts, prefix="recovtest", seeds=None, **kwargs):
    averagers = []
    for index, dht in enumerate(dhts):
        tensors = _state_tensors(seeds[index] if seeds else index)
        averagers.append(
            DecentralizedAverager(
                tensors, dht, prefix=prefix, start=True,
                min_matchmaking_time=1.0, request_timeout=1.0,
                declare_state_period=0.5, **kwargs,
            )
        )
    return averagers


def _download_rich(averager, timeout=25, min_epoch=None):
    future = averager._runner.run_coroutine(
        averager._load_state_from_peers_async(timeout, min_epoch=min_epoch), return_future=True
    )
    return future.result(timeout + 10)


def test_corrupt_donor_fails_over_without_adopting_bad_state():
    """A donor whose every payload is corrupted in flight must never poison the
    receiver: digests reject it, the download fails over, and the adopted state
    is bitwise the clean donor's snapshot."""
    dhts = launch_dht_swarm(3)
    averagers = _make_averagers(dhts)
    corrupt_donor, clean_donor, receiver = averagers
    corrupt_donor.state_sharing_priority = 10.0  # tried first
    clean_donor.state_sharing_priority = 1.0
    receiver.allow_state_sharing = False
    digest_before = _STATE_SYNC_DIGEST_FAILURES.value(site="download")
    failover_before = _STATE_SYNC_FAILOVERS.value()
    try:
        time.sleep(1.5)  # let declarations propagate
        CHAOS.add_rule(
            "state.download.send", "corrupt_payload", scope=str(corrupt_donor.peer_id)
        )
        result = _download_rich(receiver, timeout=25)
        assert result is not None and result.verified
        with clean_donor.get_tensors() as donor_tensors:
            for got, want in zip(result.tensors, donor_tensors):
                assert np.array_equal(got, want.astype(np.float32)), (
                    "adopted state must be bitwise the clean donor's snapshot"
                )
        with corrupt_donor.get_tensors() as bad_tensors:
            assert not all(
                np.array_equal(got, want.astype(np.float32))
                for got, want in zip(result.tensors, bad_tensors)
            ), "the corrupt donor's state must not have been adopted"
        assert _STATE_SYNC_DIGEST_FAILURES.value(site="download") > digest_before
        assert _STATE_SYNC_FAILOVERS.value() > failover_before
    finally:
        CHAOS.clear()
        shutdown_all(averagers, dhts)


def test_truncated_stream_fails_over_to_next_donor():
    """A donor dying mid-stream (stream ends early / errors) must not yield a
    truncated adoption: the receiver fails over and lands on complete state."""
    dhts = launch_dht_swarm(3)
    averagers = _make_averagers(dhts)
    dying_donor, healthy_donor, receiver = averagers
    dying_donor.state_sharing_priority = 10.0
    healthy_donor.state_sharing_priority = 1.0
    receiver.allow_state_sharing = False
    try:
        time.sleep(1.5)
        # first chunk passes, everything after is eaten: a classic mid-stream death
        CHAOS.add_rule(
            "state.download.send", "drop", after=1, scope=str(dying_donor.peer_id)
        )
        result = _download_rich(receiver, timeout=25)
        assert result is not None and result.verified
        assert len(result.tensors) == 2, "a truncated stream must never be adopted"
        with healthy_donor.get_tensors() as donor_tensors:
            for got, want in zip(result.tensors, donor_tensors):
                assert np.array_equal(got, want.astype(np.float32))
    finally:
        CHAOS.clear()
        shutdown_all(averagers, dhts)


class _EpochAverager(DecentralizedAverager):
    """Test donor that advertises a fixed epoch in its state metadata."""

    def __init__(self, *args, epoch=0, **kwargs):
        self._test_epoch = epoch
        super().__init__(*args, **kwargs)

    async def _get_current_state(self):
        return {"epoch": self._test_epoch}, self._snapshot_tensors()


def test_stale_epoch_donor_is_rejected():
    """A donor whose manifest epoch is behind the required minimum (the tracker's
    global epoch at the call site) is rejected at the manifest — the fresh donor
    wins even when the stale one has better priority."""
    dhts = launch_dht_swarm(3)
    shared = _state_tensors(0)
    stale = _EpochAverager(
        [t.copy() for t in shared], dhts[0], prefix="staletest", start=True, epoch=3,
        min_matchmaking_time=1.0, request_timeout=1.0, declare_state_period=0.5,
    )
    fresh_tensors = _state_tensors(9)
    fresh = _EpochAverager(
        fresh_tensors, dhts[1], prefix="staletest", start=True, epoch=7,
        min_matchmaking_time=1.0, request_timeout=1.0, declare_state_period=0.5,
    )
    receiver = _EpochAverager(
        [t.copy() for t in shared], dhts[2], prefix="staletest", start=True, epoch=0,
        min_matchmaking_time=1.0, request_timeout=1.0, declare_state_period=0.5,
        allow_state_sharing=False,
    )
    stale.state_sharing_priority = 10.0
    fresh.state_sharing_priority = 1.0
    stale_before = _STATE_SYNC_STALE_DONORS.value()
    try:
        time.sleep(1.5)
        result = _download_rich(receiver, timeout=25, min_epoch=5)
        assert result is not None and result.verified
        assert result.epoch == 7, "only the fresh donor may be adopted"
        for got, want in zip(result.tensors, fresh_tensors):
            assert np.array_equal(got, want.astype(np.float32))
        assert _STATE_SYNC_STALE_DONORS.value() > stale_before
    finally:
        shutdown_all([stale, fresh, receiver], dhts)


def test_sharing_disabled_is_explicit_not_truncation():
    """A donor that declared state but turned sharing off answers with an explicit
    state_unavailable manifest; the download returns None instead of adopting an
    empty stream as state."""
    dhts = launch_dht_swarm(2)
    averagers = _make_averagers(dhts, seeds=[0, 1])
    donor, receiver = averagers
    try:
        time.sleep(1.5)  # declared while sharing was on
        donor._allow_state_sharing = False  # raw flag: the declaration stays live
        result = _download_rich(receiver, timeout=6)
        assert result is None
    finally:
        shutdown_all(averagers, dhts)


def test_shutdown_retracts_state_declaration():
    """ISSUE 7 satellite: a cleanly-departed donor must not cost joiners a dial —
    its ``all_averagers`` record is tombstoned at shutdown."""
    dhts = launch_dht_swarm(2)
    averagers = _make_averagers(dhts, prefix="retracttest")
    retiring, survivor = averagers
    try:
        time.sleep(1.5)

        async def _candidates(_dht, _node):
            return await _list_donor_candidates(_dht, "retracttest", None)

        before = dhts[1].run_coroutine(_candidates)
        assert retiring.peer_id in before, "donor must be declared before shutdown"
        retiring.shutdown()
        time.sleep(0.5)  # let the tombstone replicate
        after = dhts[1].run_coroutine(_candidates)
        assert retiring.peer_id not in after, "shutdown must retract the declaration"
        assert survivor.peer_id in after, "the live donor must remain declared"
    finally:
        survivor.shutdown()
        for dht in dhts:
            dht.shutdown()


# ------------------------------------------------------------------ optimizer integration


def test_optimizer_checkpoint_restore_cycle(tmp_path):
    """The restore order's local leg: a solo trainer checkpoints on its epoch
    cadence; a restarted process adopts the newest checkpoint bitwise — no swarm
    download needed."""
    import optax

    import jax.numpy as jnp

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer

    dht = DHT(start=True)
    try:
        def make_opt(d):
            return Optimizer(
                dht=d, run_id="ckpt_cycle", target_batch_size=32,
                params={"w": jnp.zeros(8, jnp.float32)}, optimizer=optax.sgd(0.1),
                batch_size_per_step=32, matchmaking_time=0.5, averaging_timeout=10,
                checkpoint_dir=tmp_path, checkpoint_every=1,
                tracker_opts=dict(min_refresh_period=0.2, default_refresh_period=0.3),
            )

        opt = make_opt(dht)
        rng = np.random.RandomState(0)
        grads_tree = {"w": jnp.asarray(rng.randn(8).astype(np.float32))}
        for _ in range(3):  # solo swarm: every full batch advances the epoch
            opt.step(grads_tree)
            time.sleep(0.1)
        saved_epoch = opt.local_epoch
        saved_state = opt.state_dict()
        assert saved_epoch >= 1, "the solo trainer must have advanced epochs"
        assert store_nonempty(tmp_path)
        opt.shutdown()

        # "reboot": same checkpoint dir, fresh everything else
        dht2 = DHT(start=True)
        try:
            restarted = make_opt(dht2)
            assert restarted.local_epoch == saved_epoch
            for got, want in zip(
                restarted.state_averager._host_state_tensors(), saved_state["tensors"]
            ):
                assert np.array_equal(got, np.asarray(want, dtype=np.float32))
            restarted.shutdown()
        finally:
            dht2.shutdown()
    finally:
        dht.shutdown()


def store_nonempty(path) -> bool:
    return bool(LocalCheckpointStore(path).checkpoints())


def test_epoch_adopted_without_state_is_loud_and_counted(tmp_path, monkeypatch):
    """ISSUE 7 satellite: when the download fails, fast-forwarding the epoch
    number is an emergency, not business as usual — counted and logged at ERROR."""
    import optax

    import jax.numpy as jnp

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.optim import Optimizer
    from hivemind_tpu.optim.optimizer import _EPOCH_ADOPTED_WITHOUT_STATE
    from hivemind_tpu.optim.progress_tracker import ProgressTracker

    dht = DHT(start=True)
    opt = Optimizer(
        dht=dht, run_id="adopt_test", target_batch_size=64,
        params={"w": jnp.zeros(4, jnp.float32)}, optimizer=optax.sgd(0.1),
        batch_size_per_step=16, matchmaking_time=0.5,
        tracker_opts=dict(min_refresh_period=0.2, default_refresh_period=0.3),
    )
    try:
        opt.state_averager.load_full_state_from_peers = lambda **kwargs: False
        # the swarm's epoch as the tracker reports it: set on the property, because the
        # tracker's own thread replaces `global_progress` with what it fetched (this lone
        # peer's epoch 0) every 0.2 s, between a write to it and the catch-up's read
        monkeypatch.setattr(ProgressTracker, "global_epoch", property(lambda self: 5))
        before = _EPOCH_ADOPTED_WITHOUT_STATE.value()
        opt._catch_up_with_swarm()
        assert opt.local_epoch == 5, "the epoch number is still adopted (anti-livelock)"
        assert _EPOCH_ADOPTED_WITHOUT_STATE.value() == before + 1
    finally:
        opt.shutdown()
        dht.shutdown()
