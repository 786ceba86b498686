"""Mesh-sharded block serving (VERDICT r4 next-round #4): a served block whose
params + KV caches are NamedSharding global arrays over a device mesh, behind the
UNCHANGED Server/RemoteSequential path — clients get token-identical generations
whether one device or the whole mesh answers. Re-designed reference role: the
single-CUDA-device executor of hivemind/moe/server/runtime.py:22-199."""


import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec

from hivemind_tpu.dht import DHT
from hivemind_tpu.moe.server.llama_loader import (
    LlamaCheckpointConfig,
    decode_cache_bytes,
    load_llama_blocks,
    plan_block_capacity,
    predict_block_param_bytes,
)
from hivemind_tpu.moe.server.mesh_backend import MeshModuleBackend
from hivemind_tpu.moe.server.server import Server

from test_llama_loader import HID, LAYERS, _write_checkpoint
from swarm_utils import wait_for_experts


def _tp_mesh() -> Mesh:
    devices = np.array(jax.devices())
    return Mesh(devices.reshape(len(devices)), ("tp",))


def test_mesh_backend_shards_params_and_caches(tmp_path):
    _write_checkpoint(tmp_path)
    mesh = _tp_mesh()
    backends, _config = load_llama_blocks(tmp_path, uid_prefix="mb.", mesh=mesh)
    backend = backends["mb.0"]
    assert isinstance(backend, MeshModuleBackend)

    # the big kernels really live distributed: each device holds 1/8th
    sharded_leaves = [
        leaf
        for leaf in jax.tree_util.tree_leaves(backend.params)
        if backend.leaf_spec(leaf) != PartitionSpec()
    ]
    assert sharded_leaves, "no parameter leaf was sharded"
    for leaf in sharded_leaves:
        shard = leaf.addressable_shards[0]
        assert shard.data.size == leaf.size // len(mesh.devices.flat)
    assert backend.param_bytes_per_device() < backend.param_bytes()

    # KV decode caches shard through the session-manager hook: 2 kv heads do not go round
    # 8 devices, so a head's 32 values do (and never the 32 slots, which a step's softmax spans)
    cache_k, cache_v = backend.module.init_decode_cache(2, 32)
    assert cache_k.shape == (2, 2, 32, 32)  # [batch, kv_heads, slots, head_dim]
    sharded_k, sharded_v = backend.shard_decode_cache(cache_k, cache_v)
    assert sharded_k.sharding.spec == sharded_v.sharding.spec == PartitionSpec(None, None, None, "tp")
    info = backend.get_info()
    assert info["mesh_devices"] == len(mesh.devices.flat)


@pytest.mark.parametrize("devices, block, sizes, spec", [
    (2, "llama_block", dict(num_heads=4, num_kv_heads=2), PartitionSpec(None, "tp", None, None)),  # the kv heads go round
    (4, "llama_block", dict(num_heads=4, num_kv_heads=2), PartitionSpec(None, None, None, "tp")),  # they do not: a head's values
    (4, "olmoe_block", dict(num_heads=4, num_experts=4, experts_per_token=2, expert_inner=32), PartitionSpec(None, "tp", None, None)),
    (2, "exaone_moe_block", dict(num_heads=4, num_kv_heads=2, head_dim=16, ffn_inner=64), PartitionSpec(None, "tp", None, None)),
    (8, "causal_transformer", dict(num_heads=4), PartitionSpec(None, None, None, "tp")),
])
def test_decode_caches_shard_over_the_kv_heads_axis(devices, block, sizes, spec):
    """`shard_decode_cache` on the caches each KV-cache block of `layers/common.py` makes (one
    layout since ISSUE 52: ``[batch, kv_heads, slots, head_dim]``, 48 slots here): the kv-heads
    axis where the mesh divides it, else a head's values, and the slots in no case (a step's
    softmax runs over them)."""
    import optax

    from hivemind_tpu.moe.server.layers import name_to_block, name_to_input

    mesh = Mesh(np.array(jax.devices()[:devices]), ("tp",))
    backend = MeshModuleBackend("m.0", name_to_block[block](HID, **sizes), mesh=mesh, optimizer=optax.sgd(0.0),
                                sample_input=name_to_input[block](2, HID), max_batch_size=4)
    for leaf in backend.shard_decode_cache(*backend.module.init_decode_cache(1, 48)):
        assert leaf.shape[2] == 48 and leaf.sharding.spec == spec


def test_mesh_sharded_server_is_token_identical_over_rpc(tmp_path):
    """The same checkpoint served twice from one process — once mesh-sharded,
    once single-device — through the same Server/RemoteSequential stack: greedy
    decode produces IDENTICAL tokens (GSPMD may reorder reductions, so hidden
    states match to tolerance and the argmax chain exactly)."""
    from hivemind_tpu.moe import RemoteSequential

    _write_checkpoint(tmp_path)
    mesh = _tp_mesh()
    backends_mesh, _ = load_llama_blocks(tmp_path, uid_prefix="meshed.", mesh=mesh)
    backends_single, _ = load_llama_blocks(tmp_path, uid_prefix="single.")
    dht = DHT(start=True)
    server = Server(dht, {**backends_mesh, **backends_single}, decode_max_len=64)
    client_dht = None
    try:
        server.run_in_background(await_ready=True)
        wait_for_experts(dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in dht.get_visible_maddrs()], start=True)
        rng = np.random.RandomState(5)
        prompt_len, steps = 6, 6
        hidden = rng.randn(1, prompt_len, HID).astype(np.float32)

        outputs = {}
        for prefix in ("meshed.", "single."):
            pipe = RemoteSequential(client_dht, prefix, LAYERS)
            chunks = [np.asarray(pipe.decode_step(hidden, f"tok_{prefix}", reset=True))]
            # greedy-style chain: each step feeds the previous step's output back,
            # so ANY divergence compounds — the strongest identity check the
            # hidden-state interface allows
            for _ in range(steps):
                chunks.append(
                    np.asarray(pipe.decode_step(chunks[-1][:, -1:], f"tok_{prefix}"))
                )
            outputs[prefix] = np.concatenate(chunks, axis=1)

        meshed, single = outputs["meshed."], outputs["single."]
        assert meshed.shape == single.shape
        # blocks COMPUTE in bf16 and GSPMD reorders reductions, and the feedback
        # chain compounds the epsilon across 6 steps — the norm check is loose;
        # the argmax chain below is the exact assertion
        rel_err = np.linalg.norm(meshed - single) / np.linalg.norm(single)
        assert rel_err < 3e-2, rel_err
        # token-identical: a greedy head reading either stream picks the same ids
        proj = rng.randn(HID, 64).astype(np.float32)  # a fixed surrogate LM head
        assert np.array_equal(
            np.argmax(meshed @ proj, axis=-1), np.argmax(single @ proj, axis=-1)
        )
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        dht.shutdown()


def test_mesh_sharded_sessions_share_one_batched_program(tmp_path):
    """ISSUE 25: the batched decode program stacks the rows' caches itself, so it
    takes mesh-sharded per-session caches and hands back caches a later step
    accepts; and it stays keyed by the bucket alone: rows fresh from a prefill,
    rows a batch has stepped and the padding pair all reach ONE program (left to
    the compiler, every program's outputs come back under another sharding)."""
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager
    from hivemind_tpu.telemetry.device import COMPILE_TRACKER

    _write_checkpoint(tmp_path)
    backends_mesh, _ = load_llama_blocks(tmp_path, uid_prefix="meshed.", mesh=_tp_mesh())
    backends_single, _ = load_llama_blocks(tmp_path, uid_prefix="single.")
    manager = DecodeSessionManager({**backends_mesh, **backends_single}, max_len=32)
    rng = np.random.RandomState(25)
    prompts = rng.randn(4, 1, 3, HID).astype(np.float32)
    tokens = rng.randn(4, 4, 1, 1, HID).astype(np.float32)
    outs = {}
    for uid in ("meshed.0", "single.0"):
        compiles_before = COMPILE_TRACKER.counts().get("decode_session.batched_step", 0)
        for i in range(4):
            manager.decode(uid, f"s{i}", prompts[i], reset=True)
        for step, rows in enumerate((3, 4, 3, 4)):  # the fourth row joins fresh from its prefill
            entries = [(None, manager._sessions[(uid, f"s{i}")], tokens[step, i]) for i in range(rows)]
            outs[uid, step] = np.stack(manager._decode_batch(uid, entries))
        assert COMPILE_TRACKER.counts()["decode_session.batched_step"] == compiles_before + 1
    cache = manager._sessions[("meshed.0", "s0")].cache_k
    assert cache.sharding == manager._dummy_rows("meshed.0")[0].sharding and not cache.sharding.is_fully_replicated
    for step in range(4):
        meshed, single = outs["meshed.0", step], outs["single.0", step]
        assert np.linalg.norm(meshed - single) / np.linalg.norm(single) < 3e-2


def test_hbm_planning_7b_mesh_pooling():
    """The regime the mesh tier exists for, at REAL 7B shapes: with a 600 MB
    per-chip budget one chip cannot hold even one fp32 block, but an 8-device
    mesh pools to several blocks — and the sharded per-device residency math
    confirms each chip holds 1/8th of a block."""
    config = LlamaCheckpointConfig(
        hidden_size=4096, num_attention_heads=32, num_key_value_heads=32,
        intermediate_size=11008, num_hidden_layers=32,
    )
    block = predict_block_param_bytes(config)
    assert block > 700 * 1024**2  # ~810 MB fp32: genuinely 7B-scale

    budget = 600 * 1024**2
    cache = decode_cache_bytes(config, batch=1, max_len=512)
    single = plan_block_capacity(
        block, hbm_bytes=budget, decode_sessions=2, cache_bytes_per_session_block=cache
    )
    pooled = plan_block_capacity(
        block, hbm_bytes=budget, decode_sessions=2, cache_bytes_per_session_block=cache,
        mesh_devices=8,
    )
    assert single == 0, single  # one chip: not even one block
    assert pooled >= 4, pooled  # the slice: several blocks


def test_mesh_int8_blocks_are_encoded_and_decoded_per_shard(tmp_path):
    """int8 weight-only serving on a mesh: every leaf goes from the checkpoint
    straight to its place on the mesh (codes and scales sharded by quantization
    block — never whole on one device), the codec runs per shard, and the block
    answers like the single-device int8 block."""
    from hivemind_tpu.ops.quantized_params import QuantizedTensor

    _write_checkpoint(tmp_path)
    # two devices: the toy attention kernels hold 4 and 2 quantization blocks
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    meshed, _ = load_llama_blocks(tmp_path, uid_prefix="mq.", mesh=mesh, weight_quantization="int8")
    single, _ = load_llama_blocks(tmp_path, uid_prefix="sq.", weight_quantization="int8")
    backend = meshed["mq.0"]

    quantized = [
        leaf for leaf in jax.tree_util.tree_leaves(
            backend.params, is_leaf=lambda leaf: isinstance(leaf, QuantizedTensor)
        ) if isinstance(leaf, QuantizedTensor)
    ]
    assert quantized, "no leaf was quantized"
    for leaf in quantized:
        assert set(leaf.codes.devices()) == set(mesh.devices.flat)
        if leaf.codes.shape[0] % len(mesh.devices.flat) == 0:
            assert leaf.codes.sharding.spec == PartitionSpec("tp", None)
            assert leaf.absmax.sharding.spec == PartitionSpec("tp")
    assert backend.param_bytes_per_device() < backend.param_bytes()
    # the resident bytes are the int8 store's, identical to one device's
    assert backend.param_bytes() == single["sq.0"].param_bytes()

    # per-shard encoding is the same arithmetic block by block: identical codes
    one_device = single["sq.0"].params["query"]["kernel"]
    np.testing.assert_array_equal(np.asarray(backend.params["query"]["kernel"].codes), np.asarray(one_device.codes))

    # bf16 compute; GSPMD reorders the reductions, so hidden states agree to a few bf16 steps
    x = np.random.RandomState(3).randn(2, 8, HID).astype(np.float32)
    np.testing.assert_allclose(backend.forward(x)[0], single["sq.0"].forward(x)[0], atol=6e-2)
    # the dense snapshot (checkpoints, replica transfer) decodes through the same path
    backend.load_state_dict(backend.state_dict())
    np.testing.assert_allclose(backend.forward(x)[0], single["sq.0"].forward(x)[0], atol=6e-2)
