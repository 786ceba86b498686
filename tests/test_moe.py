"""MoE end-to-end: server + remote expert numerics vs local module, gradients through
RPC, beam search over a real swarm, mixture forward, checkpoints
(scope: reference tests/test_moe.py + test_expert_backend.py + test_connection_handler.py)."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from hivemind_tpu.dht import DHT
from hivemind_tpu.moe import (
    ExpertInfo,
    ModuleBackend,
    RemoteExpert,
    RemoteMixtureOfExperts,
    RemoteSwitchMixtureOfExperts,
    Server,
    declare_experts,
    get_experts,
    is_valid_uid,
    split_uid,
)
from hivemind_tpu.moe.client.beam_search import MoEBeamSearcher
from hivemind_tpu.moe.server.layers import FeedforwardExpert, name_to_block
from hivemind_tpu.utils.timed_storage import get_dht_time
from swarm_utils import wait_for_experts

HID = 32


def assert_same_to_bf16_rounding(got, want, err_msg=""):
    """A position STEPPED through a decode cache against the same position computed from a chunk
    (a full forward, a prefill, a re-prefill after a failover). Since ISSUE 52 a step of every
    block that keeps keys and values sums its scores in float32 (`common._grouped_cache_step`)
    where a chunk's attention rounds them to bf16, so the two agree to the rounding of the bf16
    activations and no longer bit for bit: within 2e-2 of the largest value, the tolerance the
    other decode tests hold a served block to (3e-3 to 1e-2 read here, at 16 hidden values; a
    wrong mask, slot or rotary offset shows at 1e-1 and more). Chunk against chunk stays exact."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and np.abs(got - want).max() <= 2e-2 * np.abs(want).max(), (
        err_msg, float(np.abs(got - want).max()), float(np.abs(want).max()))


def test_expert_uid_utils():
    assert is_valid_uid("ffn.0.3") and is_valid_uid("expert.5")
    assert not is_valid_uid("ffn.") and not is_valid_uid("ffn") and not is_valid_uid("ffn.01")
    assert split_uid("ffn.5.12") == ("ffn.5.", 12)


def test_module_backend_numerics():
    module = FeedforwardExpert(HID)
    backend = ModuleBackend(
        "test.0", module, optimizer=optax.sgd(1e-2),
        sample_input=np.zeros((4, HID), np.float32), max_batch_size=64,
    )
    x = np.random.RandomState(0).randn(5, HID).astype(np.float32)
    out = backend.forward(x)[0]
    expected = jax.jit(module.apply)({"params": backend.params}, x)
    assert np.allclose(out, np.asarray(expected), atol=2e-2)  # bf16 compute tolerance

    # backward returns input grads AND trains the expert
    params_before = [np.asarray(l).copy() for l in jax.tree_util.tree_leaves(backend.params)]
    grad_out = np.ones_like(out)
    grad_in = backend.backward(x, grad_out)[0]
    assert grad_in.shape == x.shape and np.isfinite(grad_in).all()
    params_after = [np.asarray(l) for l in jax.tree_util.tree_leaves(backend.params)]
    assert any(not np.array_equal(a, b) for a, b in zip(params_before, params_after))
    assert backend.update_count == 1

    # state round trip
    blob = backend.state_dict()
    backend.load_state_dict(blob)
    assert backend.update_count == 1


def make_server(dht=None, uids=("ffn_test.0.0", "ffn_test.0.1", "ffn_test.1.0", "ffn_test.1.1")):
    return Server.create(
        expert_uids=list(uids), expert_cls="ffn", hidden_dim=HID,
        dht=dht, start=True, max_batch_size=256,
        optim_factory=lambda: optax.sgd(1e-3),
    )


def test_remote_expert_forward_backward():
    server = make_server()
    try:
        wait_for_experts(server.dht, ["ffn_test.0.0"])
        infos = get_experts(server.dht, ["ffn_test.0.0"])
        assert infos[0] is not None
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        expert = RemoteExpert(infos[0], client_dht.node.p2p)
        # info fetch
        assert expert.info["max_batch_size"] == 256

        x = jnp.asarray(np.random.RandomState(0).randn(3, HID), jnp.float32)
        out = expert(x)
        backend = server.backends["ffn_test.0.0"]
        expected = jax.jit(backend.module.apply)({"params": backend.params}, x)
        assert np.allclose(np.asarray(out), np.asarray(expected), atol=2e-2)

        # gradients flow through the RPC (and train the server-side expert)
        def loss_fn(xx):
            return jnp.sum(expert(xx) ** 2)

        grads = jax.grad(loss_fn)(x)
        assert grads.shape == x.shape and bool(jnp.isfinite(grads).all())
        assert backend.update_count >= 1
        client_dht.shutdown()
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_beam_search_finds_best_experts():
    server = make_server()
    try:
        import time
        time.sleep(1.0)
        searcher = MoEBeamSearcher(server.dht, "ffn_test.", grid_size=(2, 2))
        # score dimension 0: prefer row 1; dimension 1: prefer col 0
        grid_scores = [np.array([0.0, 5.0], np.float32), np.array([3.0, 0.0], np.float32)]
        found = searcher.find_best_experts(grid_scores, beam_size=3)
        assert found, "beam search found nothing"
        assert found[0].uid == "ffn_test.1.0"  # argmax of score sums
        uids = [info.uid for info in found]
        assert uids == sorted(uids, key=lambda u: -sum(
            grid_scores[d][int(c)] for d, c in enumerate(u.split(".")[1:])
        ))
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_remote_mixture_of_experts():
    server = make_server()
    try:
        import time
        time.sleep(1.0)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        moe = RemoteMixtureOfExperts(
            dht=client_dht, in_features=HID, grid_size=(2, 2),
            uid_prefix="ffn_test.", k_best=2, k_min=1,
        )
        x = jnp.asarray(np.random.RandomState(1).randn(5, HID), jnp.float32)
        out = moe(x)
        assert out.shape == (5, HID)
        assert bool(jnp.isfinite(out).all())

        switch = RemoteSwitchMixtureOfExperts(
            dht=client_dht, in_features=HID, grid_size=(2, 2), uid_prefix="ffn_test.",
        )
        out_switch = switch(x)
        assert out_switch.shape == (5, HID)
        assert any(u.sum() > 0 for u in switch.grid_utilization)
        client_dht.shutdown()
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_background_server_contextmanager():
    from hivemind_tpu.moe import background_server

    with background_server(
        expert_uids=["bgctx.0"], expert_cls="nop", hidden_dim=8,
        optim_factory=lambda: optax.sgd(1e-3),
    ) as (dht, server):
        assert dht.is_alive and "bgctx.0" in server.backends
        out = server.backends["bgctx.0"].forward(np.ones((2, 8), np.float32))[0]
        assert out.shape == (2, 8)
    assert not dht.is_alive  # context exit shuts everything down


def test_checkpoints_roundtrip(tmp_path):
    from hivemind_tpu.moe.server.checkpoints import load_experts, store_experts

    module = FeedforwardExpert(HID)
    backend = ModuleBackend(
        "ck.0", module, optimizer=optax.sgd(1e-2),
        sample_input=np.zeros((2, HID), np.float32),
    )
    x = np.random.randn(4, HID).astype(np.float32)
    backend.backward(x, np.ones((4, HID), np.float32))  # mutate params
    store_experts({"ck.0": backend}, tmp_path)

    fresh = ModuleBackend(
        "ck.0", FeedforwardExpert(HID), optimizer=optax.sgd(1e-2),
        sample_input=np.zeros((2, HID), np.float32), rng_seed=99,
    )
    assert load_experts({"ck.0": fresh}, tmp_path) == 1
    old_leaf = jax.tree_util.tree_leaves(backend.params)[0]
    new_leaf = jax.tree_util.tree_leaves(fresh.params)[0]
    assert np.allclose(np.asarray(old_leaf), np.asarray(new_leaf))
    assert fresh.update_count == 1


def test_multi_tensor_expert_backend_and_remote():
    """Experts with several inputs AND several outputs work locally and over RPC
    (reference module_backend.py:68-74 nested schemas)."""
    import flax.linen as nn

    class TwoInTwoOut(nn.Module):
        hid: int

        @nn.compact
        def __call__(self, x, y):
            h = nn.Dense(self.hid)(x) + y
            return h, jnp.tanh(h)

    backend = ModuleBackend(
        "multi.0", TwoInTwoOut(HID), optimizer=optax.sgd(1e-3),
        sample_inputs=[np.zeros((2, HID), np.float32), np.zeros((2, HID), np.float32)],
        max_batch_size=64,
    )
    assert backend.num_inputs == 2 and backend.num_outputs == 2
    rng = np.random.RandomState(0)
    x, y = rng.randn(3, HID).astype(np.float32), rng.randn(3, HID).astype(np.float32)
    out1, out2 = backend.forward(x, y)
    ref1, ref2 = jax.jit(backend.module.apply)({"params": backend.params}, x, y)
    assert np.allclose(out1, np.asarray(ref1), atol=1e-4)
    assert np.allclose(out2, np.asarray(ref2), atol=1e-4)
    grads = backend.backward(x, y, np.ones_like(out1), np.ones_like(out2))
    assert len(grads) == 2 and grads[0].shape == x.shape and grads[1].shape == y.shape
    assert backend.update_count == 1

    # over RPC: schemas travel through rpc_info, both passes work, grads flow to
    # EVERY input
    dht = DHT(start=True)
    # exact-numerics fixture: wire precision is covered by the compressed-RPC
    # equivalence suite (test_serving_compression.py)
    server = Server(dht, {"multi.0": backend}, activation_compression="none")
    try:
        server.run_in_background(await_ready=True)
        client_dht = DHT(initial_peers=[str(m) for m in dht.get_visible_maddrs()], start=True)
        expert = RemoteExpert(ExpertInfo("multi.0", dht.peer_id), client_dht.node.p2p)
        r_out1, r_out2 = expert(jnp.asarray(x), jnp.asarray(y))
        # the local backward above trained the expert: compare against CURRENT params
        now1, now2 = backend.forward(x, y)
        assert np.allclose(np.asarray(r_out1), now1, atol=1e-4)
        assert np.allclose(np.asarray(r_out2), now2, atol=1e-4)

        def loss_fn(xx, yy):
            a, b = expert(xx, yy)
            return jnp.sum(a ** 2) + jnp.sum(b ** 2)

        gx, gy = jax.grad(loss_fn, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(y))
        assert gx.shape == x.shape and gy.shape == y.shape
        assert bool(jnp.isfinite(gx).all()) and bool(jnp.isfinite(gy).all())
        assert bool((jnp.abs(gy) > 0).any())
        client_dht.shutdown()
    finally:
        server.shutdown()
        dht.shutdown()


def test_call_many_masks_dead_experts():
    """RemoteCallMany: a dead expert is masked out (k_min still satisfied), gradients
    flow through the survivors, and k_min violations raise."""
    from hivemind_tpu.moe.client.call_many import RemoteCallMany

    server = make_server()
    try:
        wait_for_experts(server.dht, ["ffn_test.0.0", "ffn_test.0.1"])
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        infos = get_experts(server.dht, ["ffn_test.0.0", "ffn_test.0.1"])
        good = [RemoteExpert(info, client_dht.node.p2p) for info in infos]
        dead = RemoteExpert(ExpertInfo("ffn_test.9.9", server.dht.peer_id), client_dht.node.p2p)

        x = jnp.asarray(np.random.RandomState(2).randn(4, HID), jnp.float32)
        rows = [[good[0], dead], [good[1], dead], [good[0], good[1]], [good[1], dead]]
        rcm = RemoteCallMany(rows, k_min=1, backward_k_min=1, forward_timeout=20)
        outputs, alive = rcm(x)
        alive = np.asarray(alive)
        assert outputs.shape == (4, 2, HID)
        assert alive[:, 0].all() and alive[2, 1] and not alive[0, 1] and not alive[3, 1]

        def loss_fn(xx):
            out, live = RemoteCallMany(rows, k_min=1, forward_timeout=20)(xx)
            return jnp.sum(out ** 2)

        grads = jax.grad(loss_fn)(x)
        assert grads.shape == x.shape and bool(jnp.isfinite(grads).all())

        # k_min=2 with only one live expert on a row must raise
        rcm_strict = RemoteCallMany([[good[0], dead]], k_min=2, forward_timeout=10)
        with pytest.raises(Exception):
            jax.block_until_ready(rcm_strict(x[:1])[0])
        client_dht.shutdown()
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_deterministic_dropout_expert():
    """det_dropout: the mask is a second input; forward/backward see the same mask
    over RPC and the mask gates the gradient (reference layers/dropout.py)."""
    server = Server.create(
        expert_uids=["drop.0"], expert_cls="det_dropout", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-3),
    )
    try:
        import time
        time.sleep(0.5)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        expert = RemoteExpert(ExpertInfo("drop.0", server.dht.peer_id), client_dht.node.p2p)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(3, 16), jnp.float32)
        mask = jnp.asarray((rng.rand(3, 16) > 0.2), jnp.float32)
        out = expert(x, mask)
        backend = server.backends["drop.0"]
        expected = jax.jit(backend.module.apply)({"params": backend.params}, x, mask)
        assert np.allclose(np.asarray(out), np.asarray(expected), atol=2e-2)

        # gradient wrt x must be zero exactly where the mask dropped the input
        grads = jax.grad(lambda xx: jnp.sum(expert(xx, mask) ** 2))(x)
        dropped = np.asarray(mask) == 0
        assert np.allclose(np.asarray(grads)[dropped], 0.0, atol=1e-6)
        assert np.abs(np.asarray(grads)[~dropped]).max() > 0
        client_dht.shutdown()
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_remote_sequential_pipeline():
    """Petals-style pipelining: a 3-block model served across TWO servers runs and
    backpropagates end-to-end through chained remote calls; killing the server of a
    block and re-declaring it elsewhere fails over transparently."""
    from hivemind_tpu.moe import RemoteSequential

    # server A hosts blocks 0 and 2, server B hosts block 1 (split pipeline)
    server_a = Server.create(
        expert_uids=["blk.0", "blk.2"], expert_cls="transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-3),
    )
    dht_b = DHT(initial_peers=[str(m) for m in server_a.dht.get_visible_maddrs()], start=True)
    server_b = Server.create(
        expert_uids=["blk.1"], expert_cls="transformer", hidden_dim=16,
        dht=dht_b, start=True, optim_factory=lambda: optax.sgd(1e-3),
    )
    client_dht = None
    try:
        import time
        wait_for_experts(server_a.dht, ["blk.0", "blk.1", "blk.2"])
        client_dht = DHT(initial_peers=[str(m) for m in server_a.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "blk.", 3, update_period=2.0)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 16), jnp.float32)

        out = pipe(x)
        assert out.shape == x.shape
        # matches running the three backends locally in order
        expected = x
        for uid, backend_server in (("blk.0", server_a), ("blk.1", server_b), ("blk.2", server_a)):
            backend = backend_server.backends[uid]
            expected = jax.jit(backend.module.apply)({"params": backend.params}, expected)
        assert np.allclose(np.asarray(out), np.asarray(expected), atol=5e-2)

        # gradients flow through the WHOLE pipeline (and train every block server)
        grads = jax.grad(lambda xx: jnp.sum(pipe(xx) ** 2))(x)
        assert grads.shape == x.shape and bool(jnp.isfinite(grads).all())
        assert server_b.backends["blk.1"].update_count >= 1

        # failover: block 1 moves to a new server; the stale cached route must heal
        server_b.shutdown()
        dht_b.shutdown()
        replacement = Server.create(
            expert_uids=["blk.1"], expert_cls="transformer", hidden_dim=16,
            dht=DHT(initial_peers=[str(m) for m in server_a.dht.get_visible_maddrs()], start=True),
            start=True, optim_factory=lambda: optax.sgd(1e-3),
        )
        try:
            time.sleep(2.5)  # cached resolution expires (update_period) + declare
            out2 = pipe(x)
            assert out2.shape == x.shape and bool(jnp.isfinite(out2).all())
        finally:
            replacement.shutdown()
            replacement.dht.shutdown()
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server_a.shutdown()
        server_a.dht.shutdown()


def test_switch_grid_dropout():
    """grid_dropout masks grid coordinates with -inf gating scores: routing avoids
    dropped coordinates; dropout 1.0 is a no-op (reference switch_moe.py:84-98)."""
    server = make_server()
    try:
        import time
        time.sleep(1.0)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        switch = RemoteSwitchMixtureOfExperts(
            dht=client_dht, in_features=HID, grid_size=(2, 2), uid_prefix="ffn_test.",
            grid_dropout=0.75,
        )
        # force a deterministic mask: keep only row 0 (dim 0) and column 1 (dim 1)
        class _FixedRng:
            def __init__(self):
                self.masks = [np.array([0.0, 1.0]), np.array([1.0, 0.0])]  # < 0.75 keeps

            def uniform(self, low, high, size):
                return np.full(size, 1.0, np.float32)  # no jitter

            def rand(self, size):
                return self.masks.pop(0)

        switch._jitter_rng = _FixedRng()
        x = jnp.asarray(np.random.RandomState(3).randn(4, HID), jnp.float32)
        out = switch(x)
        assert out.shape == (4, HID) and bool(jnp.isfinite(out).all())
        # with rows {0} and cols {1} kept, the only routable expert is 0.1
        utilization_rows, utilization_cols = switch.grid_utilization
        assert utilization_rows[0] > utilization_rows[1]
        assert utilization_cols[1] > utilization_cols[0]
        client_dht.shutdown()
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_causal_block_pipeline_decode():
    """Causal decoder blocks over RemoteSequential: positions only depend on their
    prefix (changing the suffix leaves earlier outputs bit-identical THROUGH the
    RPC), which makes fixed-schema right-padded autoregressive decoding exact."""
    from hivemind_tpu.moe import RemoteSequential

    server = Server.create(
        expert_uids=["cblk.0", "cblk.1"], expert_cls="causal_transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "cblk.", 2)

        rng = np.random.RandomState(0)
        prefix = rng.randn(1, 64, 16).astype(np.float32)
        variant = prefix.copy()
        variant[:, 10:] = rng.randn(1, 54, 16)  # different suffix from position 10

        out_a = np.asarray(pipe(jnp.asarray(prefix)))
        out_b = np.asarray(pipe(jnp.asarray(variant)))
        # causality through two remote blocks: positions < 10 are identical
        np.testing.assert_array_equal(out_a[:, :10], out_b[:, :10])
        assert np.abs(out_a[:, 10:] - out_b[:, 10:]).max() > 0  # suffix does differ
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_llama_block_gqa_causality_and_rope():
    """LlamaBlockExpert (RMSNorm + RoPE + GQA + SwiGLU): causal, GQA head wiring
    sound, and RoPE gives relative-position-consistent attention (a pure shift of
    content into later positions preserves causality of the earlier ones)."""
    from hivemind_tpu.moe.server.layers.common import LlamaBlockExpert

    block = LlamaBlockExpert(hidden_dim=16, num_heads=4, num_kv_heads=2)
    rng = np.random.RandomState(0)
    x = rng.randn(2, 32, 16).astype(np.float32)
    apply = jax.jit(block.apply)
    params = jax.jit(block.init)(jax.random.PRNGKey(0), x)
    out = np.asarray(apply(params, x))
    assert out.shape == x.shape and np.isfinite(out).all()

    # GQA params: key/value project to kv_heads*head_dim = 8, query to 16
    kernels = jax.tree_util.tree_map(lambda a: a.shape, params)["params"]
    assert kernels["key"]["kernel"] == (16, 8)
    assert kernels["query"]["kernel"] == (16, 16)

    # causality: perturbing the suffix leaves the prefix outputs bit-identical
    y = x.copy()
    y[:, 20:] = rng.randn(2, 12, 16)
    out_y = np.asarray(apply(params, y))
    np.testing.assert_array_equal(out[:, :20], out_y[:, :20])
    assert np.abs(out[:, 20:] - out_y[:, 20:]).max() > 0

    # RoPE pin: q·k after rotation depends only on the RELATIVE position, and the
    # rotation is not the identity. Broadcasting one content vector to every
    # position makes apply_rope(x)[0, p, 0] the rotation of that vector at p.
    from hivemind_tpu.moe.server.layers.common import apply_rope

    cq, ck = rng.randn(8).astype(np.float32), rng.randn(8).astype(np.float32)
    rq = np.asarray(apply_rope(jnp.broadcast_to(jnp.asarray(cq), (1, 16, 1, 8))))[0, :, 0]
    rk = np.asarray(apply_rope(jnp.broadcast_to(jnp.asarray(ck), (1, 16, 1, 8))))[0, :, 0]
    scores = rq @ rk.T  # [i, j] = rot(cq, i) . rot(ck, j)
    for shift in (1, 5):
        np.testing.assert_allclose(
            scores[:-shift, :-shift], scores[shift:, shift:], rtol=1e-4, atol=1e-4
        )
    assert np.abs(scores - float(cq @ ck)).max() > 0.1  # identity rope would be flat


def test_llama_block_pipeline_decode():
    """Llama-family blocks served over RemoteSequential (the BASELINE 'Petals-style
    Llama block server' config): prefix outputs are exact through the RPC, so
    right-padded fixed-schema autoregressive decoding works unchanged."""
    from hivemind_tpu.moe import RemoteSequential

    server = Server.create(
        expert_uids=["lblk.0", "lblk.1"], expert_cls="llama_block", hidden_dim=16,
        expert_kwargs={"num_heads": 4, "num_kv_heads": 2},  # GQA through the serving path
        start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "lblk.", 2)

        rng = np.random.RandomState(1)
        prefix = rng.randn(1, 64, 16).astype(np.float32)
        variant = prefix.copy()
        variant[:, 7:] = rng.randn(1, 57, 16)

        out_a = np.asarray(pipe(jnp.asarray(prefix)))
        out_b = np.asarray(pipe(jnp.asarray(variant)))
        np.testing.assert_array_equal(out_a[:, :7], out_b[:, :7])
        assert np.abs(out_a[:, 7:] - out_b[:, 7:]).max() > 0
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_beam_search_negative_caching():
    """Dead prefixes (grid cells with no declared experts) land in the negative
    cache after one search (reference beam_search.py:60-74,152-160), and cached
    searches still rank live experts correctly."""
    server = make_server()  # declares ffn_test.{0,1}.{0,1}
    try:
        import time
        time.sleep(1.0)
        searcher = MoEBeamSearcher(server.dht, "ffn_test.", grid_size=(4, 2))
        grid_scores = [
            np.array([0.0, 1.0, 10.0, 10.0], np.float32),  # rows 2..3 score best but are dead
            np.array([3.0, 0.0], np.float32),
        ]
        found = searcher.find_best_experts(grid_scores, beam_size=4)
        # rows 2..3 score best but are dead: the beam never proposes them because
        # the DHT prefix dictionary only lists coordinates that were declared
        assert found and found[0].uid == "ffn_test.1.0"
        assert all(info.uid.split(".")[1] in ("0", "1") for info in found)

        # a prefix tree with NO experts at all gets negative-cached after one miss
        ghost = MoEBeamSearcher(server.dht, "ghost.", grid_size=(2, 2))
        assert ghost.find_best_experts([np.ones(2, np.float32)] * 2, beam_size=2) == []
        assert len(ghost._negative_cache) > 0, "dead prefix was not negative-cached"
        assert ghost.find_best_experts([np.ones(2, np.float32)] * 2, beam_size=2) == []

        # the live searcher's second query (now possibly cache-assisted) must
        # still rank live experts identically
        again = searcher.find_best_experts(grid_scores, beam_size=4)
        assert [i.uid for i in again] == [i.uid for i in found]
    finally:
        server.shutdown()
        server.dht.shutdown()


def test_decode_cache_matches_full_forward():
    """KV-cache decode (prefill + per-token steps) against the full causal forward for
    both decoder block families (GQA caches stay compact): the prefill bit-identical, the
    steps to bf16 rounding (`assert_same_to_bf16_rounding`)."""
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert, LlamaBlockExpert

    rng = np.random.RandomState(0)
    for cls, kwargs in (
        (CausalTransformerExpert, dict(num_heads=4)),
        (LlamaBlockExpert, dict(num_heads=4, num_kv_heads=2)),
    ):
        block = cls(hidden_dim=16, **kwargs)
        x = jnp.asarray(rng.randn(2, 12, 16).astype(np.float32))
        apply = jax.jit(block.apply)  # a program a shape, not one an operation
        params = jax.jit(block.init)(jax.random.PRNGKey(0), x)
        full = np.asarray(apply(params, x))

        cache_k, cache_v = block.init_decode_cache(batch=2, max_len=32)
        y, cache_k, cache_v = apply(params, x[:, :5], cache_k, cache_v, 0)
        np.testing.assert_array_equal(np.asarray(y), full[:, :5])
        assert cache_k.shape == cache_v.shape == (2, kwargs.get("num_kv_heads", 4), 32, 4)
        for t in range(5, 12):
            y, cache_k, cache_v = apply(params, x[:, t:t + 1], cache_k, cache_v, t)
            assert_same_to_bf16_rounding(y, full[:, t:t + 1], f"position {t}")


def test_decode_sessions_over_rpc():
    """Petals-style incremental decoding through the swarm: per-session KV caches
    on the serving peer, driven by RemoteSequential.decode_step — outputs match
    the right-padded full-recompute pipeline exactly, per generated position."""
    import uuid
    from hivemind_tpu.moe import RemoteSequential

    server = Server.create(
        expert_uids=["dblk.0", "dblk.1"], expert_cls="llama_block", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
        # exact decode-vs-recompute math is the subject: bit-exact wire (fp16
        # wire tolerance is covered by test_serving_compression.py)
        activation_compression="none",
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "dblk.", 2)

        rng = np.random.RandomState(3)
        hidden = rng.randn(1, 9, 16).astype(np.float32)  # prompt 6 + 3 decode steps
        session = uuid.uuid4().hex

        # session path: prefill the 6-token prompt, then three 1-token steps
        out_prefill = pipe.decode_step(hidden[:, :6], session, reset=True)
        step_outs = [pipe.decode_step(hidden[:, t:t + 1], session) for t in range(6, 9)]

        # reference path: right-padded full recompute at schema length 64
        padded = np.zeros((1, 64, 16), np.float32)
        padded[:, :9] = hidden
        full = np.asarray(pipe(jnp.asarray(padded)))

        np.testing.assert_allclose(out_prefill, full[:, :6], rtol=1e-5, atol=1e-5)
        for offset, out in enumerate(step_outs):
            assert_same_to_bf16_rounding(out, full[:, 6 + offset:7 + offset])

        # a fresh session with the same id on ANOTHER input must reset cleanly
        out_reset = pipe.decode_step(hidden[:, :6], session, reset=True)
        np.testing.assert_allclose(out_reset, out_prefill, rtol=1e-6, atol=1e-6)

        # a continuation on an UNKNOWN session must raise, never silently prefill
        with pytest.raises(RuntimeError, match="no pinned route"):
            pipe.decode_step(hidden[:, :1], "never-prefilled")
        from hivemind_tpu.p2p.p2p import P2PHandlerError

        block0 = pipe._block(0)
        with pytest.raises(P2PHandlerError, match="unknown or expired"):
            block0.decode_np(hidden[:, :1], "server-side-unknown", reset=False)
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_decode_span_execution_across_two_servers():
    """A 4-block pipeline split over TWO servers pins two 2-block spans: each
    per-token RPC chains the co-located blocks server-side, and the decoded
    positions still match the right-padded full recompute exactly."""
    import uuid
    from hivemind_tpu.moe import RemoteSequential

    server_a = Server.create(
        expert_uids=["span.0", "span.1"], expert_cls="causal_transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
        activation_compression="none",  # exact span-vs-recompute math is the subject
    )
    server_b = Server.create(
        expert_uids=["span.2", "span.3"], expert_cls="causal_transformer", hidden_dim=16,
        dht=None, start=True, optim_factory=lambda: optax.sgd(1e-4),
        initial_peers=[str(m) for m in server_a.dht.get_visible_maddrs()],
        activation_compression="none",
    )
    client_dht = None
    try:
        wait_for_experts(server_a.dht, [f"span.{i}" for i in range(4)])
        client_dht = DHT(initial_peers=[str(m) for m in server_a.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "span.", 4)

        rng = np.random.RandomState(11)
        hidden = rng.randn(1, 7, 16).astype(np.float32)
        session = uuid.uuid4().hex
        out_prefill = pipe.decode_step(hidden[:, :5], session, reset=True)
        route = pipe._decode_routes[session]["route"]
        assert [len(span) for _block, span in route] == [2, 2], route  # two 2-block spans
        step_outs = [pipe.decode_step(hidden[:, t:t + 1], session) for t in (5, 6)]

        padded = np.zeros((1, 64, 16), np.float32)
        padded[:, :7] = hidden
        full = np.asarray(pipe(jnp.asarray(padded)))
        np.testing.assert_allclose(out_prefill, full[:, :5], rtol=1e-5, atol=1e-5)
        for offset, out in enumerate(step_outs):
            assert_same_to_bf16_rounding(out, full[:, 5 + offset:6 + offset])

        # training across the span boundary: gradients flow through both servers'
        # spans (client recovers the boundary activation with one forward sweep)
        # and every block's server-side optimizer steps
        counts_before = [
            server_a.backends["span.0"].update_count, server_a.backends["span.1"].update_count,
            server_b.backends["span.2"].update_count, server_b.backends["span.3"].update_count,
        ]
        grads = jax.grad(lambda xx: jnp.sum(pipe(xx) ** 2))(jnp.asarray(padded))
        assert grads.shape == padded.shape and bool(jnp.isfinite(grads).all())
        counts_after = [
            server_a.backends["span.0"].update_count, server_a.backends["span.1"].update_count,
            server_b.backends["span.2"].update_count, server_b.backends["span.3"].update_count,
        ]
        assert all(after == before + 1 for before, after in zip(counts_before, counts_after)), (
            counts_before, counts_after,
        )
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        for server in (server_b, server_a):
            server.shutdown()
            server.dht.shutdown()


def test_decode_failover_mid_generation_matches_uninterrupted_run():
    """Transparent decode-session failover (VERDICT r3 #3, Petals-class): one of two
    block servers dies MID-GENERATION and a replacement (same uid, same seed-0
    weights) takes over; the client re-prefills it from the retained input history
    and the emitted positions are IDENTICAL to an uninterrupted run — the caller
    never passes reset=True."""
    import uuid
    from hivemind_tpu.moe import RemoteSequential

    server_a = Server.create(
        expert_uids=["fo.0"], expert_cls="causal_transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    maddrs = [str(m) for m in server_a.dht.get_visible_maddrs()]
    server_b = Server.create(
        expert_uids=["fo.1"], expert_cls="causal_transformer", hidden_dim=16,
        dht=None, start=True, optim_factory=lambda: optax.sgd(1e-4), initial_peers=maddrs,
    )
    client_dht = server_b2 = None
    try:
        wait_for_experts(server_a.dht, ["fo.0", "fo.1"])
        client_dht = DHT(initial_peers=maddrs, start=True)
        pipe = RemoteSequential(client_dht, "fo.", 2, max_retries=4)

        rng = np.random.RandomState(5)
        hidden = rng.randn(1, 8, 16).astype(np.float32)
        prompt, steps = 4, 4

        # reference: uninterrupted generation
        ref_session = uuid.uuid4().hex
        ref = [pipe.decode_step(hidden[:, :prompt], ref_session, reset=True)]
        ref += [pipe.decode_step(hidden[:, t:t + 1], ref_session) for t in range(prompt, prompt + steps)]

        # failover run: same inputs; kill server_b after two generated positions
        session = uuid.uuid4().hex
        outs = [pipe.decode_step(hidden[:, :prompt], session, reset=True)]
        outs += [pipe.decode_step(hidden[:, t:t + 1], session) for t in (prompt, prompt + 1)]

        server_b.shutdown()
        server_b.dht.shutdown()
        server_b2 = Server.create(  # same uid + default rng_seed=0 => same weights
            expert_uids=["fo.1"], expert_cls="causal_transformer", hidden_dim=16,
            dht=None, start=True, optim_factory=lambda: optax.sgd(1e-4), initial_peers=maddrs,
        )
        wait_for_experts(client_dht, ["fo.1"], served_by=server_b2.dht.peer_id)  # the replacement's record

        outs += [pipe.decode_step(hidden[:, t:t + 1], session) for t in (prompt + 2, prompt + 3)]

        for i, (expected, got) in enumerate(zip(ref, outs)):
            if i < 3:  # before the failover both runs took the same path: exact
                np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5, err_msg=f"position group {i} differs before the failover")
            else:  # after it the stepped positions were re-prefilled as a chunk
                assert_same_to_bf16_rounding(got, expected, f"position group {i} diverged after failover")
        # the route really did move to the replacement peer
        new_route = pipe._decode_routes[session]["route"]
        assert any(
            block.peer_id == server_b2.dht.peer_id for block, _span in new_route
        ), "failover did not re-pin onto the replacement server"
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        for server in (server_b2, server_a):
            if server is not None:
                server.shutdown()
                server.dht.shutdown()


def test_decode_failover_with_span_groups():
    """Failover across SPAN-grouped routes: two servers each hosting a 2-block
    span; the second dies mid-generation and the replacement (same uids, seed-0
    weights) is re-prefilled THROUGH the span RPC — emitted positions identical
    to the uninterrupted run, and the recovered route still groups 2+2."""
    import uuid
    from hivemind_tpu.moe import RemoteSequential

    server_a = Server.create(
        expert_uids=["fs.0", "fs.1"], expert_cls="causal_transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    maddrs = [str(m) for m in server_a.dht.get_visible_maddrs()]
    server_b = Server.create(
        expert_uids=["fs.2", "fs.3"], expert_cls="causal_transformer", hidden_dim=16,
        dht=None, start=True, optim_factory=lambda: optax.sgd(1e-4), initial_peers=maddrs,
    )
    client_dht = server_b2 = None
    try:
        wait_for_experts(server_a.dht, [f"fs.{i}" for i in range(4)])
        client_dht = DHT(initial_peers=maddrs, start=True)
        pipe = RemoteSequential(client_dht, "fs.", 4, max_retries=4)

        rng = np.random.RandomState(9)
        hidden = rng.randn(1, 7, 16).astype(np.float32)
        prompt = 4

        ref_session = uuid.uuid4().hex
        ref = [pipe.decode_step(hidden[:, :prompt], ref_session, reset=True)]
        ref += [pipe.decode_step(hidden[:, t:t + 1], ref_session) for t in range(prompt, 7)]

        session = uuid.uuid4().hex
        outs = [pipe.decode_step(hidden[:, :prompt], session, reset=True)]
        outs.append(pipe.decode_step(hidden[:, prompt:prompt + 1], session))
        assert [len(span) for _b, span in pipe._decode_routes[session]["route"]] == [2, 2]

        server_b.shutdown()
        server_b.dht.shutdown()
        server_b = None  # intentionally dead: keep it out of the finally sweep
        server_b2 = Server.create(
            expert_uids=["fs.2", "fs.3"], expert_cls="causal_transformer", hidden_dim=16,
            dht=None, start=True, optim_factory=lambda: optax.sgd(1e-4), initial_peers=maddrs,
        )
        wait_for_experts(client_dht, ["fs.2", "fs.3"], served_by=server_b2.dht.peer_id)
        outs += [pipe.decode_step(hidden[:, t:t + 1], session) for t in (prompt + 1, prompt + 2)]

        for i, (expected, got) in enumerate(zip(ref, outs)):
            if i < 2:  # before the failover both runs took the same path: exact
                np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5, err_msg=f"position group {i} differs before the failover")
            else:  # after it the stepped position was re-prefilled as a chunk
                assert_same_to_bf16_rounding(got, expected, f"position group {i} diverged after span failover")
        assert [len(span) for _b, span in pipe._decode_routes[session]["route"]] == [2, 2]
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        for server in (server_b2, server_b, server_a):
            if server is not None:
                server.shutdown()
                server.dht.shutdown()


def test_span_fallback_for_span_unaware_server():
    """Mixed-swarm capability negotiation: when a server does not advertise
    span_support (an older build would run only the head block and silently
    return a wrong result), the client must fall back to per-block calls."""
    from hivemind_tpu.moe import RemoteSequential

    server = Server.create(
        expert_uids=["nospan.0", "nospan.1"], expert_cls="causal_transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "nospan.", 2)

        # this server DOES advertise span support: grouping forms one 2-block span
        groups = pipe._grouped_range(0, 2)
        assert [len(uids) for _head, uids in groups] == [2], groups
        peer_id = groups[0][0].peer_id

        # a span-unaware peer (negative capability cache, as _peer_supports_spans
        # records after probing an older server's rpc_info) falls back to
        # per-block grouping — and the pipeline still computes correctly
        pipe._span_support[peer_id] = False
        groups = pipe._grouped_range(0, 2)
        assert [len(uids) for _head, uids in groups] == [1, 1], groups
        assert all(head.span is None for head, _uids in groups)
        x = jnp.asarray(np.random.RandomState(5).randn(1, 64, 16), jnp.float32)
        out = pipe(x)
        assert out.shape == x.shape and bool(jnp.isfinite(out).all())
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_span_forward_retry_restarts_from_original_input():
    """Regression: a mid-chain failure must retry from the ORIGINAL input, not the
    partially-advanced activation — otherwise the blocks that already ran are
    silently applied twice and the custom_vjp primal is corrupted on exactly the
    failover path the retry exists for."""
    from hivemind_tpu.moe import RemoteSequential

    pipe = RemoteSequential.__new__(RemoteSequential)
    pipe.max_retries = 2
    calls = {"attempt": 0}

    class FakeHead:
        def __init__(self, add, fail_once):
            self.add, self.fail_once = add, fail_once

        def forward_np(self, x):
            if self.fail_once and calls["attempt"] == 0:
                calls["attempt"] += 1
                raise ConnectionError("peer died mid-chain")
            return (x + self.add,)

    def grouped_range(start, stop, force=False):
        return [(FakeHead(1.0, fail_once=False), ["b.0"]),
                (FakeHead(10.0, fail_once=True), ["b.1"])]

    pipe._grouped_range = grouped_range
    out = pipe._span_forward(0, 2, np.zeros((1,), np.float32))
    # first attempt applied +1 then died; a buggy retry would re-apply +1 (out=12)
    assert float(out[0]) == 11.0, out


def test_drain_cancellation_releases_pins_and_unblocks_callers():
    """Killing the drainer mid-batch (server shutdown, loop teardown) must drop the
    eviction pins, cancel stranded caller futures, and leave sessions evictable —
    a leaked pin makes a session permanently un-evictable (round-3 advisor,
    decode_session.py:252)."""
    import asyncio
    import threading
    import uuid

    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    module = CausalTransformerExpert(hidden_dim=16, num_heads=4)
    backend = ModuleBackend(
        "pin.0", module, optimizer=optax.sgd(1e-3),
        sample_input=np.zeros((1, 4, 16), np.float32), max_batch_size=8,
    )
    manager = DecodeSessionManager({"pin.0": backend}, max_len=32)
    rng = np.random.RandomState(0)
    sid = uuid.uuid4().hex
    manager.decode("pin.0", sid, rng.randn(1, 4, 16).astype(np.float32), reset=True)
    # a second recently-active session keeps the continuous-batching (drainer)
    # path engaged — a lone stream routes onto the direct path since ISSUE 10
    manager.decode("pin.0", uuid.uuid4().hex, rng.randn(1, 4, 16).astype(np.float32), reset=True)

    release, entered = threading.Event(), threading.Event()

    def stuck_batch(uid, entries, **how):
        entered.set()
        release.wait(10)
        raise RuntimeError("batch aborted")

    manager._decode_batch = stuck_batch

    async def scenario():
        step = asyncio.create_task(
            manager.decode_async("pin.0", sid, rng.randn(1, 1, 16).astype(np.float32), False)
        )
        await asyncio.get_running_loop().run_in_executor(None, entered.wait, 10)
        drainer = manager._drainers[("pin.0",)]
        drainer.cancel()
        with pytest.raises(asyncio.CancelledError):
            await drainer
        release.set()
        with pytest.raises(asyncio.CancelledError):
            await step
        assert manager._in_flight == {}, "eviction pins leaked after drain cancellation"

    asyncio.run(scenario())


def test_decode_continuous_batching_many_clients():
    """Concurrent single-token steps from MANY client sessions are merged into one
    vmapped device call (continuous batching) — every client's tokens must match
    the sequential unbatched path bit-for-bit in fp32 tolerance."""
    import uuid
    from concurrent.futures import ThreadPoolExecutor

    from hivemind_tpu.moe import RemoteSequential

    server = Server.create(
        expert_uids=["cbat.0"], expert_cls="causal_transformer", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
        # batched-vs-direct device math is the subject: bit-exact wire (fp16
        # wire tolerance is covered by test_serving_compression.py)
        activation_compression="none",
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "cbat.", 1)

        num_clients, prompt, steps = 5, 4, 3
        rng = np.random.RandomState(7)
        inputs = [rng.randn(1, prompt + steps, 16).astype(np.float32) for _ in range(num_clients)]

        # reference: each client decoded alone, sequentially (exercises the direct path
        # via fresh sessions; single calls still batch trivially with themselves)
        expected = []
        for hidden in inputs:
            session = uuid.uuid4().hex
            pipe.decode_step(hidden[:, :prompt], session, reset=True)
            expected.append([
                pipe.decode_step(hidden[:, t:t + 1], session)
                for t in range(prompt, prompt + steps)
            ])

        # concurrent: all clients step in lockstep from threads, so their 1-token
        # requests pile into the same flush windows server-side
        sessions = [uuid.uuid4().hex for _ in range(num_clients)]
        for hidden, session in zip(inputs, sessions):
            pipe.decode_step(hidden[:, :prompt], session, reset=True)
        manager = server.handler.decode_sessions
        fns_before = len(manager._batched_fns)

        def one_step(args):
            client, t = args
            return client, pipe.decode_step(inputs[client][:, t:t + 1], sessions[client])

        with ThreadPoolExecutor(num_clients) as pool:
            for t in range(prompt, prompt + steps):
                outs = dict(pool.map(one_step, [(c, t) for c in range(num_clients)]))
                for client in range(num_clients):
                    np.testing.assert_allclose(
                        outs[client], expected[client][t - prompt], rtol=1e-5, atol=1e-5,
                    )
        assert len(manager._batched_fns) > fns_before, "no batched step was ever compiled"
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_decode_prefill_streams_over_unary_cap():
    """A prefill chunk above the 2 MiB unary split streams through
    rpc_decode_stream and still matches the session's incremental math."""
    import uuid
    from hivemind_tpu.moe import RemoteSequential

    server = Server.create(
        expert_uids=["big.0"], expert_cls="causal_transformer", hidden_dim=512,
        decode_max_len=1200, start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "big.", 1)
        rng = np.random.RandomState(0)
        prompt = rng.randn(1, 1100, 512).astype(np.float32)  # 2.25 MB > unary cap
        session = uuid.uuid4().hex
        out = pipe.decode_step(prompt, session, reset=True)
        assert out.shape == (1, 1100, 512) and np.isfinite(out).all()
        # one incremental token afterwards proves the streamed prefill seeded the cache
        nxt = pipe.decode_step(prompt[:, :1], session)
        assert nxt.shape == (1, 1, 512) and np.isfinite(nxt).all()
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_custom_cached_block_steps_batched():
    """A registered block with cache code of its own (not `_grouped_cache_step`) served
    through the batched step: `layers/__init__.py`'s contract. In a session's own
    call ``index`` is a scalar, in a batched step a vector, one write position a row;
    the block vmaps its per-row cache code over that vector itself. Its output is a
    running mean of the session's inputs, which a wrong position shows at once."""
    import flax.linen as nn

    from hivemind_tpu.moe import register_expert_class
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager
    from hivemind_tpu.moe.server.module_backend import ModuleBackend

    @register_expert_class("running_mean_test_block", lambda batch, hid: np.zeros((batch, 8, hid), np.float32))
    class RunningMeanBlock(nn.Module):
        hidden_dim: int

        def init_decode_cache(self, batch: int, max_len: int):
            # cache_k: the inputs seen so far; cache_v: how many there are, by position
            return jnp.zeros((batch, max_len, self.hidden_dim), jnp.float32), jnp.zeros((batch, max_len), jnp.float32)

        @nn.compact
        def __call__(self, x, cache_k=None, cache_v=None, index=None):
            scale = self.param("scale", nn.initializers.ones, ())
            if cache_k is None:
                return x * scale

            def one_session(x, seen, count, index):  # x [new_len, hid] written at a scalar index
                seen = jax.lax.dynamic_update_slice(seen, x, (index, 0))
                count = jax.lax.dynamic_update_slice(count, jnp.ones(x.shape[0]), (index,))
                sums = jnp.cumsum(seen * count[:, None], axis=0)
                means = sums / jnp.maximum(jnp.cumsum(count), 1.0)[:, None]
                return jax.lax.dynamic_slice(means, (index, 0), x.shape) * scale, seen, count

            if jnp.ndim(index) == 1:  # a batched step: every row a session at its own position
                return jax.vmap(one_session)(x, cache_k, cache_v, index)
            return jax.vmap(one_session, in_axes=(0, 0, 0, None))(x, cache_k, cache_v, index)

    hid, lengths = 8, [2, 5, 3]
    backend = ModuleBackend("mean.0", name_to_block["running_mean_test_block"](hid), optimizer=optax.sgd(0.0),
                            sample_input=np.zeros((2, 8, hid), np.float32), max_batch_size=4)
    manager = DecodeSessionManager({"mean.0": backend}, max_len=16)
    x = np.random.RandomState(0).randn(len(lengths), 8, hid).astype(np.float32)
    want = np.cumsum(x, axis=1) / np.arange(1, 9)[None, :, None]
    for row, length in enumerate(lengths):
        out = manager.decode("mean.0", f"row{row}", x[row:row + 1, :length], reset=True)
        np.testing.assert_allclose(out, want[row:row + 1, :length], rtol=1e-5, atol=1e-6)
    for step in range(2):  # 3 rows in a bucket of 4: one padding row
        entries = [(None, manager._sessions[("mean.0", f"row{row}")], x[row:row + 1, length + step:length + step + 1])
                   for row, length in enumerate(lengths)]
        for row, (out, length) in enumerate(zip(manager._decode_batch("mean.0", entries), lengths)):
            assert not isinstance(out, Exception), out
            np.testing.assert_allclose(out, want[row:row + 1, length + step:length + step + 1], rtol=1e-5, atol=1e-6)
    assert list(manager._batched_fns) == [("mean.0", 4)]  # the uid's view; the program behind it is its kind's
    assert [key[1:] for key in manager._programs if key[1] == "batched"] == [("batched", 4)]
