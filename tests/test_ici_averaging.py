"""ICI tier: MeshTensorBridge collectives and MeshAverager in a real swarm
(SURVEY §5 two-tier communication backend; VERDICT r1 item 3).

A peer whose state is sharded over the 8-device virtual CPU mesh joins a swarm round
with a plain host-resident peer; the averages must match the numpy path exactly."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from hivemind_tpu.averaging import DecentralizedAverager, MeshAverager
from hivemind_tpu.dht import DHT
from hivemind_tpu.parallel import MeshTensorBridge, make_mesh

from swarm_utils import launch_dht_swarm


def test_bridge_gather_scatter_roundtrip():
    mesh = make_mesh(dp=2, tp=2, sp=2)
    bridge = MeshTensorBridge(mesh)
    rng = np.random.RandomState(0)
    host = {
        "a": rng.randn(8, 16).astype(np.float32),
        "b": rng.randn(4, 4, 4).astype(np.float32),
    }
    tree = {
        "a": jax.device_put(host["a"], NamedSharding(mesh, P("dp", "tp"))),
        "b": jax.device_put(host["b"], NamedSharding(mesh, P("sp", None, None))),
    }
    gathered = bridge.gather_to_host(tree)
    flat_host = [host["a"], host["b"]]  # tree_flatten orders dict leaves by sorted key
    for got, expected in zip(gathered, flat_host):
        np.testing.assert_array_equal(got, expected)

    # scatter modified values back; shardings must be preserved
    modified = [t + 1.0 for t in gathered]
    new_tree = bridge.scatter_from_host(tree, modified)
    np.testing.assert_array_equal(np.asarray(new_tree["a"]), host["a"] + 1.0)
    assert new_tree["a"].sharding.spec == P("dp", "tp")


def test_staging_runs_no_xla_and_pulls_each_region_once():
    """VERDICT r2 weak #3 regression: host staging must be pure shard pulls — no
    jit/XLA computation (the old replicated-gather cost a full model replica of
    device memory PER DEVICE), and each distinct region must be fetched from
    exactly one device even when the sharding replicates it across many."""
    mesh = make_mesh(dp=2, tp=2, sp=2)
    bridge = MeshTensorBridge(mesh)
    rng = np.random.RandomState(2)
    host = {
        "sharded": rng.randn(8, 16).astype(np.float32),
        "replicated": rng.randn(5, 3).astype(np.float32),  # every device holds it all
        "mixed": rng.randn(4, 6).astype(np.float32),  # sharded over dp, replicated over tp/sp
    }
    tree = {
        "sharded": jax.device_put(host["sharded"], NamedSharding(mesh, P("dp", "tp"))),
        "replicated": jax.device_put(host["replicated"], NamedSharding(mesh, P())),
        "mixed": jax.device_put(host["mixed"], NamedSharding(mesh, P("dp", None))),
    }

    # every distinct region exactly once: 4 for P(dp, tp), 1 for replicated, 2 for P(dp)
    assert len(bridge._unique_shards(tree["sharded"])) == 4
    assert len(bridge._unique_shards(tree["replicated"])) == 1
    assert len(bridge._unique_shards(tree["mixed"])) == 2

    import unittest.mock

    mirrors = bridge.allocate_mirrors(tree)
    with unittest.mock.patch.object(
        jax, "jit", side_effect=AssertionError("staging must not launch XLA computations")
    ):
        bridge.stage_into_mirrors(tree, mirrors)
    flat_host = [jax.tree_util.tree_flatten(host)[0][i] for i in range(3)]
    for got, expected in zip(mirrors, flat_host):
        np.testing.assert_array_equal(got, expected)

    # bf16 leaves are upcast into the fp32 mirrors shard-by-shard
    bf16 = jax.device_put(
        jnp.asarray(host["sharded"], jnp.bfloat16), NamedSharding(mesh, P("dp", None))
    )
    [mirror] = bridge.gather_to_host([bf16])
    assert mirror.dtype == np.float32
    np.testing.assert_allclose(mirror, host["sharded"], atol=0.01, rtol=0.01)


def test_bridge_mesh_mean_is_psum_mean():
    """Per-replica stacks reduce on-device (pmean under shard_map) to the numpy mean."""
    mesh = make_mesh(dp=4, tp=2)
    bridge = MeshTensorBridge(mesh)
    rng = np.random.RandomState(1)
    stacked_host = rng.randn(4, 6, 8).astype(np.float32)  # leading dim = dp replicas
    stacked = jax.device_put(stacked_host, NamedSharding(mesh, P("dp", "tp", None)))
    reduced = bridge.mesh_mean({"g": stacked}, axis="dp")["g"]
    assert reduced.shape == (6, 8)
    np.testing.assert_allclose(np.asarray(reduced), stacked_host.mean(axis=0), rtol=1e-6)


def _launch_swarm_pair(mesh_tree, host_tensors, prefix, **mesh_kwargs):
    first, second = launch_dht_swarm(2)
    common = dict(
        prefix=prefix, start=True, target_group_size=2,
        min_matchmaking_time=1.0, request_timeout=1.0,
        sender_timeout=5.0, reducer_timeout=10.0,
    )
    mesh = mesh_kwargs.pop("mesh")
    mesh_peer = MeshAverager(mesh_tree, mesh, first, **mesh_kwargs, **common)
    host_peer = DecentralizedAverager(host_tensors, second, **common)
    return first, second, mesh_peer, host_peer


def test_mesh_peer_joins_swarm_round():
    """8-device mesh peer + host peer: post-round device shards hold the exact
    cross-peer average and the host peer sees the mesh peer's contribution."""
    mesh = make_mesh(dp=2, tp=2, sp=2)
    rng = np.random.RandomState(2)
    w_host = rng.randn(8, 32).astype(np.float32)
    b_host = rng.randn(64).astype(np.float32)
    tree = {
        "w": jax.device_put(w_host, NamedSharding(mesh, P("dp", "tp"))),
        "b": jax.device_put(b_host, NamedSharding(mesh, P("sp"))),
    }
    peer_w = rng.randn(8, 32).astype(np.float32)
    peer_b = rng.randn(64).astype(np.float32)

    first = second = mesh_peer = host_peer = None
    try:
        # host list must follow the mesh peer's flatten order (dict keys sorted: b, w)
        first, second, mesh_peer, host_peer = _launch_swarm_pair(
            tree, [peer_b, peer_w], "ici_round", mesh=mesh
        )
        controls = [a.step(wait=False, timeout=30) for a in (mesh_peer, host_peer)]
        for control in controls:
            assert control.result(timeout=60) is not None

        expected_w = (w_host + peer_w) / 2.0
        expected_b = (b_host + peer_b) / 2.0
        averaged = mesh_peer.device_tree
        assert averaged["w"].sharding.spec == P("dp", "tp")
        # the ICI staging path adds ZERO error: device shards are bit-identical to
        # the peer's own post-round host mirrors (the numpy path)
        with mesh_peer.get_tensors() as mirrors:
            np.testing.assert_array_equal(np.asarray(averaged["b"]), mirrors[0])
            np.testing.assert_array_equal(np.asarray(averaged["w"]), mirrors[1])
        # and the round itself converged to the cross-peer mean (delta application
        # costs at most 1 ulp, same as host-resident peers)
        np.testing.assert_allclose(np.asarray(averaged["w"]), expected_w, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(averaged["b"]), expected_b, rtol=1e-6, atol=1e-7)
        with host_peer.get_tensors() as tensors:
            np.testing.assert_allclose(tensors[0], expected_b, rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(tensors[1], expected_w, rtol=1e-6, atol=1e-7)
    finally:
        for obj in (mesh_peer, host_peer, first, second):
            if obj is not None:
                obj.shutdown()


def test_mesh_peer_local_reduce_axis():
    """Per-dp-replica gradients: the swarm sees the ICI mean; afterwards every
    replica adopts the swarm average (broadcast scatter)."""
    mesh = make_mesh(dp=2, tp=2, sp=2)
    rng = np.random.RandomState(3)
    stacked_host = rng.randn(2, 12, 4).astype(np.float32)  # [dp, ...]
    tree = {"g": jax.device_put(stacked_host, NamedSharding(mesh, P("dp", "tp", None)))}
    ici_mean = stacked_host.mean(axis=0)
    peer_g = rng.randn(12, 4).astype(np.float32)

    first = second = mesh_peer = host_peer = None
    try:
        first, second, mesh_peer, host_peer = _launch_swarm_pair(
            tree, [peer_g], "ici_grad", mesh=mesh, local_reduce_axis="dp"
        )
        controls = [a.step(wait=False, timeout=30) for a in (mesh_peer, host_peer)]
        for control in controls:
            assert control.result(timeout=60) is not None

        expected = (ici_mean + peer_g) / 2.0
        averaged = np.asarray(mesh_peer.device_tree["g"])
        assert averaged.shape == (2, 12, 4)
        for replica in range(2):
            np.testing.assert_allclose(averaged[replica], expected, rtol=1e-6, atol=1e-7)
        with host_peer.get_tensors() as tensors:
            np.testing.assert_allclose(tensors[0], expected, rtol=1e-6, atol=1e-7)
    finally:
        for obj in (mesh_peer, host_peer, first, second):
            if obj is not None:
                obj.shutdown()


def test_mesh_peer_fresh_state_staged_per_round():
    """The mesh tree can change between rounds; _pre_allreduce must stage the CURRENT
    device values, not the construction-time snapshot."""
    mesh = make_mesh(dp=2, tp=2, sp=2)
    zeros = np.zeros((16,), np.float32)
    tree = {"x": jax.device_put(zeros, NamedSharding(mesh, P("dp")))}
    peer_x = np.full((16,), 4.0, np.float32)

    first = second = mesh_peer = host_peer = None
    try:
        first, second, mesh_peer, host_peer = _launch_swarm_pair(
            tree, [peer_x], "ici_fresh", mesh=mesh
        )
        # user updates the device tree after construction (e.g. a local train step)
        ones = np.full((16,), 2.0, np.float32)
        mesh_peer.device_tree = {"x": jax.device_put(ones, NamedSharding(mesh, P("dp")))}

        controls = [a.step(wait=False, timeout=30) for a in (mesh_peer, host_peer)]
        for control in controls:
            assert control.result(timeout=60) is not None
        np.testing.assert_array_equal(
            np.asarray(mesh_peer.device_tree["x"]), np.full((16,), 3.0, np.float32)
        )
    finally:
        for obj in (mesh_peer, host_peer, first, second):
            if obj is not None:
                obj.shutdown()


@pytest.mark.slow  # ~60 s subprocess benchmark; the staging LOGIC is covered
# sub-second by the test_mesh_peer_* tests above — this only re-measures RSS
def test_streaming_staging_memory_bar_100m_params():
    """The 100M-param ICI staging round must grow RSS by at most 1.5x the model
    size (VERDICT r3 #4): per-leaf streaming reduce+stage never materializes the
    reduced tree whole, and steady-state rounds reuse persistent mirrors. Run in a
    fresh subprocess so this process's earlier high-water mark cannot mask (or
    fake) the measurement (the worker is tests/ici_staging_worker.py)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the worker sets its own device-count flag
    result = subprocess.run(
        [sys.executable, os.path.join(repo, "tests", "ici_staging_worker.py"),
         "--num_params", "100000000", "--num_rounds", "2", "--platform", "cpu"],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    record = json.loads(result.stdout.strip().splitlines()[-1])
    model_gb = record["extra"]["model_gb"]
    growth_gb = record["extra"]["rss_growth_during_rounds_gb"]
    assert growth_gb <= 1.5 * model_gb, (
        f"staging rounds grew RSS by {growth_gb} GB against a {model_gb} GB model "
        f"(> 1.5x bar): whole-tree transients are back"
    )
