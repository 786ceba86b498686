"""The north-star integration: a peer TRAINS the flagship model sharded over a
dp×tp×sp mesh (ring attention, tensor-parallel kernels) and averages its parameters
with a swarm peer through the ICI bridge — sharded compute below, swarm collective
above, one host staging per round (SURVEY §5 two-tier backend, BASELINE.md)."""

import numpy as np
import optax

import jax

from hivemind_tpu.averaging import DecentralizedAverager, MeshAverager
from hivemind_tpu.models import AlbertConfig, make_synthetic_mlm_batch, make_train_step
from hivemind_tpu.parallel import batch_sharding, make_mesh, params_shardings

from swarm_utils import launch_dht_swarm, shutdown_all


def test_sharded_training_with_swarm_averaging():
    mesh = make_mesh(dp=2, tp=2, sp=2)
    config = AlbertConfig.tiny(mesh=mesh)
    optimizer = optax.adamw(1e-3)
    model, train_step = make_train_step(config, optimizer, masked_loss_fraction=0.25)

    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, batch_size=4, seq_len=32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    params = jax.device_put(params, params_shardings(params, mesh))
    opt_state = optimizer.init(params)
    batch = jax.device_put(batch, batch_sharding(mesh))

    with mesh:
        step = jax.jit(train_step)
        for _ in range(2):  # local sharded training before the swarm round
            loss, params, opt_state = step(params, opt_state, batch)
    assert np.isfinite(float(loss))

    dhts = launch_dht_swarm(2)
    mesh_peer = host_peer = None
    try:
        common = dict(
            prefix="ici_train", start=True, target_group_size=2,
            min_matchmaking_time=1.0, request_timeout=1.0,
        )
        mesh_peer = MeshAverager(params, mesh, dhts[0], **common)
        # the "other pod": host-resident parameters with the same schema
        rng = np.random.RandomState(7)
        host_leaves = [
            np.asarray(leaf, np.float32) + rng.randn(*leaf.shape).astype(np.float32) * 0.01
            for leaf in jax.tree_util.tree_leaves(params)
        ]
        host_peer = DecentralizedAverager([t.copy() for t in host_leaves], dhts[1], **common)

        trained_leaves = [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(params)]
        controls = [a.step(wait=False, timeout=30) for a in (mesh_peer, host_peer)]
        for control in controls:
            assert control.result(timeout=60) is not None

        # both sides converged to the cross-pod average
        averaged_tree = mesh_peer.device_tree
        averaged_leaves = jax.tree_util.tree_leaves(averaged_tree)
        with host_peer.get_tensors() as host_now:
            for mine, theirs, trained, host_orig in zip(
                averaged_leaves, host_now, trained_leaves, host_leaves
            ):
                expected = (trained + host_orig) / 2.0
                np.testing.assert_allclose(np.asarray(mine), expected, rtol=1e-4, atol=1e-5)
                np.testing.assert_allclose(theirs, expected, rtol=1e-4, atol=1e-5)

        # the averaged tree kept its shardings: training continues sharded
        q_kernel = averaged_tree["shared_layer"]["query"]["kernel"]
        assert "tp" in str(q_kernel.sharding.spec)
        with mesh:
            loss2, _params, _opt_state = jax.jit(train_step)(averaged_tree, opt_state, batch)
        assert np.isfinite(float(loss2))
    finally:
        shutdown_all([obj for obj in (mesh_peer, host_peer) if obj is not None], dhts)
