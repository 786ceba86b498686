"""Llama-7B block-server readiness (VERDICT r2 next-round #8; BASELINE config #5):
real sharded HF-layout checkpoints load into llama_block backends, serve int8
weight-only through decode sessions, and per-block HBM accounting plans chip
capacity."""

import json
import time

import numpy as np
import optax
import pytest
from chip_smoke import synthesize_checkpoint
from hivemind_tpu.dht import DHT
from hivemind_tpu.moe.server.llama_loader import (
    LlamaCheckpointConfig,
    ShardedSafetensorsReader,
    _block_params_from_hf,
    decode_cache_bytes,
    load_llama_blocks,
    plan_block_capacity,
)
from hivemind_tpu.moe.server.server import Server
from swarm_utils import wait_for_experts

HID, HEADS, KV_HEADS, INNER, LAYERS = 128, 4, 2, 352, 2


def _write_checkpoint(tmp_path):
    """A tiny sharded HF-layout Llama checkpoint: 2 layers across 2 shard files."""
    synthesize_checkpoint(tmp_path, HID, HEADS, KV_HEADS, INNER, LAYERS)


def test_synthesized_checkpoint_reads_as_gqa_and_sharded(tmp_path):
    """The one writer of synthetic checkpoints (chip_smoke.py) against the loader's
    reader: a shard a layer behind an index, and a config that loads as GQA with the
    eps it was given."""
    synthesize_checkpoint(tmp_path, hidden=64, heads=8, kv_heads=2, inner=96, layers=3)
    index = json.loads((tmp_path / "model.safetensors.index.json").read_text())
    assert len(set(index["weight_map"].values())) == 3  # genuinely sharded
    config = LlamaCheckpointConfig.load(tmp_path)
    assert (config.num_attention_heads, config.num_key_value_heads) == (8, 2)  # GQA
    assert (config.num_hidden_layers, config.rms_norm_eps) == (3, 1e-5)
    reader = ShardedSafetensorsReader(tmp_path)
    assert sorted(reader.names()) == sorted(index["weight_map"])
    assert reader.get("model.layers.2.self_attn.k_proj.weight").shape == (2 * 8, 64)


def _local_reference(checkpoint_dir, x):
    """Apply the checkpoint's blocks directly in flax (the ground truth)."""
    import jax.numpy as jnp

    from hivemind_tpu.moe.server.layers import name_to_block

    config = LlamaCheckpointConfig.load(checkpoint_dir)
    reader = ShardedSafetensorsReader(checkpoint_dir)
    out = jnp.asarray(x)
    for layer in range(config.num_hidden_layers):
        module = name_to_block["llama_block"](
            config.hidden_size, num_heads=config.num_attention_heads,
            num_kv_heads=config.num_key_value_heads, rope_theta=config.rope_theta,
            ffn_inner=config.intermediate_size, rms_eps=config.rms_norm_eps,
        )
        params = _block_params_from_hf(reader, layer)
        out = module.apply({"params": params}, out)
    return np.asarray(out)


def test_sharded_checkpoint_loads_exactly(tmp_path):
    _write_checkpoint(tmp_path)
    backends, config = load_llama_blocks(tmp_path, uid_prefix="lt.")
    assert config.num_hidden_layers == LAYERS and set(backends) == {"lt.0", "lt.1"}

    x = np.random.RandomState(3).randn(2, 16, HID).astype(np.float32)
    served = x
    for layer in range(LAYERS):
        served = backends[f"lt.{layer}"].forward(served)[0]
    # weights load exactly; the block COMPUTES in bf16, so jitted-vs-eager
    # reduction orderings differ at bf16 epsilon (elementwise rtol is meaningless
    # for near-zero outputs — compare in relative L2)
    truth = _local_reference(tmp_path, x)
    rel_err = np.linalg.norm(served - truth) / np.linalg.norm(truth)
    assert rel_err < 5e-3, rel_err


def test_int8_serving_close_smaller_and_frozen(tmp_path):
    _write_checkpoint(tmp_path)
    fp32, _ = load_llama_blocks(tmp_path, uid_prefix="f.")
    int8, _ = load_llama_blocks(tmp_path, uid_prefix="q.", weight_quantization="int8")

    # 4x smaller residency (norm scales stay exact, so slightly above 1/4)
    fp32_bytes = sum(b.param_bytes() for b in fp32.values())
    int8_bytes = sum(b.param_bytes() for b in int8.values())
    assert int8_bytes < 0.30 * fp32_bytes, (int8_bytes, fp32_bytes)

    x = np.random.RandomState(5).randn(2, 16, HID).astype(np.float32)
    exact, quant = x, x
    for layer in range(LAYERS):
        exact = fp32[f"f.{layer}"].forward(exact)[0]
        quant = int8[f"q.{layer}"].forward(quant)[0]
    rel_err = np.linalg.norm(quant - exact) / np.linalg.norm(exact)
    assert rel_err < 0.05, rel_err

    # weight-only serving is frozen: training calls must refuse loudly
    grads = np.ones_like(x)
    with pytest.raises(RuntimeError, match="inference-only"):
        int8["q.0"].backward(x, grads)

    # state_dict round-trips through the dense form and re-encodes exactly
    before = int8["q.0"].forward(x)[0]
    blob = int8["q.0"].state_dict()
    int8["q.0"].load_state_dict(blob)
    np.testing.assert_allclose(int8["q.0"].forward(x)[0], before)


def test_int8_blocks_serve_decode_sessions_over_rpc(tmp_path):
    """The full BASELINE #5 shape: checkpoint -> int8 blocks -> Server ->
    RemoteSequential KV-cache decode; outputs match local fp32 ground truth and
    tok/s is recorded."""
    from hivemind_tpu.moe import RemoteSequential

    _write_checkpoint(tmp_path)
    backends, _config = load_llama_blocks(tmp_path, uid_prefix="ls.", weight_quantization="int8")
    dht = DHT(start=True)
    server = Server(dht, backends, decode_max_len=64)
    client_dht = None
    try:
        server.run_in_background(await_ready=True)
        wait_for_experts(dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "ls.", LAYERS)

        rng = np.random.RandomState(11)
        prompt_len, steps = 8, 8
        hidden = rng.randn(1, prompt_len + steps, HID).astype(np.float32)

        start = time.perf_counter()
        out = pipe.decode_step(hidden[:, :prompt_len], "sess", reset=True)
        step_outs = [
            pipe.decode_step(hidden[:, prompt_len + t : prompt_len + t + 1], "sess")
            for t in range(steps)
        ]
        elapsed = time.perf_counter() - start
        toks_per_s = (prompt_len + steps) / elapsed
        print(f"\nint8 llama decode over RPC: {toks_per_s:.1f} tok/s ({LAYERS} blocks)")

        served = np.concatenate([np.asarray(out)] + [np.asarray(s) for s in step_outs], axis=1)
        truth = _local_reference(tmp_path, hidden)
        rel_err = np.linalg.norm(served - truth) / np.linalg.norm(truth)
        assert rel_err < 0.05, rel_err
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        dht.shutdown()


def test_hbm_planning_7b_shapes():
    """At real Llama-7B shapes, int8 fits the whole model on a 16 GB chip with
    decode sessions; fp32 does not — the accounting that picks block counts."""
    config = LlamaCheckpointConfig(
        hidden_size=4096, num_attention_heads=32, num_key_value_heads=32,
        intermediate_size=11008, num_hidden_layers=32,
    )
    params_per_block = 4 * 4096 * 4096 + 3 * 4096 * 11008 + 2 * 4096
    fp32_block = params_per_block * 4
    int8_block = params_per_block * 1.03  # + per-4096-block fp32 absmax overhead

    cache = decode_cache_bytes(config, batch=1, max_len=2048)
    assert cache == 2 * 2 * 2048 * 4096  # bf16 K+V, full kv heads

    hbm = 16 * 1024**3
    fp32_fit = plan_block_capacity(
        int(fp32_block), hbm_bytes=hbm, decode_sessions=8, cache_bytes_per_session_block=cache
    )
    int8_fit = plan_block_capacity(
        int(int8_block), hbm_bytes=hbm, decode_sessions=8, cache_bytes_per_session_block=cache
    )
    # fp32 7B + 8×2048-token sessions: ~1.08 GB/block → a third of the model/chip;
    # int8 more than doubles capacity, and at 4 sessions the WHOLE model fits
    assert fp32_fit < config.num_hidden_layers // 2
    assert int8_fit > 2 * fp32_fit
    int8_fit_light = plan_block_capacity(
        int(int8_block), hbm_bytes=hbm, decode_sessions=4, cache_bytes_per_session_block=cache
    )
    assert int8_fit_light >= config.num_hidden_layers

    with pytest.raises(ValueError):
        plan_block_capacity(1, hbm_bytes=None, device=None)  # CPU reports no limit


def test_single_file_checkpoint_and_missing_tensor(tmp_path):
    """Single-file model.safetensors checkpoints load identically to sharded ones,
    and a truncated checkpoint fails with a clear KeyError naming the tensor."""
    from safetensors.numpy import save_file

    _write_checkpoint(tmp_path)
    sharded = ShardedSafetensorsReader(tmp_path)

    single_dir = tmp_path / "single"
    single_dir.mkdir()
    (single_dir / "config.json").write_text((tmp_path / "config.json").read_text())
    save_file({name: sharded.get(name) for name in sharded.names()},
              single_dir / "model.safetensors")

    backends, config = load_llama_blocks(single_dir, uid_prefix="sf.")
    assert len(backends) == LAYERS and config.hidden_size == HID
    x = np.random.RandomState(9).randn(1, 8, HID).astype(np.float32)
    out = x
    for layer in range(LAYERS):
        out = backends[f"sf.{layer}"].forward(out)[0]
    ref = _local_reference(tmp_path, x)
    assert np.linalg.norm(out - ref) / np.linalg.norm(ref) < 1e-2  # bf16 compute noise

    truncated = tmp_path / "truncated"
    truncated.mkdir()
    (truncated / "config.json").write_text((tmp_path / "config.json").read_text())
    partial = {n: sharded.get(n) for n in sharded.names() if "mlp.down_proj" not in n}
    save_file(partial, truncated / "model.safetensors")
    with pytest.raises(KeyError, match="mlp.down_proj"):
        load_llama_blocks(truncated, uid_prefix="tr.")

    with pytest.raises(FileNotFoundError):
        ShardedSafetensorsReader(tmp_path / "nowhere")


def test_greedy_generation_from_checkpoint_over_rpc(tmp_path):
    """BASELINE #5 end-to-end: token ids in, token ids out. The client loads the
    checkpoint's embedding/final-norm/LM-head; the decoder blocks serve remotely
    with KV-cache sessions; greedy generation matches a local full-model replay
    of the same sequence."""
    from safetensors.numpy import save_file

    from hivemind_tpu.moe import RemoteSequential
    from hivemind_tpu.moe.server.llama_loader import LlamaClientHead, generate_greedy

    VOCAB = 96
    _write_checkpoint(tmp_path)
    rng = np.random.RandomState(21)
    head_tensors = {
        "model.embed_tokens.weight": (rng.randn(VOCAB, HID) / np.sqrt(HID)).astype(np.float32),
        "model.norm.weight": np.ones(HID, np.float32),
        # separate (untied) head so the tied-fallback path is NOT what's tested here
        "lm_head.weight": (rng.randn(VOCAB, HID) / np.sqrt(HID)).astype(np.float32),
    }
    shard = "model-head.safetensors"
    save_file(head_tensors, tmp_path / shard)
    index_path = tmp_path / "model.safetensors.index.json"
    index = json.loads(index_path.read_text())
    index["weight_map"].update({name: shard for name in head_tensors})
    index_path.write_text(json.dumps(index))

    backends, _config = load_llama_blocks(tmp_path, uid_prefix="gen.")
    head = LlamaClientHead.load(tmp_path)
    assert head.vocab_size == VOCAB
    assert not np.array_equal(head.lm_head_matrix, head.embed_matrix)

    dht = DHT(start=True)
    server = Server(dht, backends, decode_max_len=64)
    client_dht = None
    try:
        server.run_in_background(await_ready=True)
        wait_for_experts(dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "gen.", LAYERS)

        prompt = rng.randint(0, VOCAB, size=(1, 6))
        generated = generate_greedy(head, pipe, prompt, max_new_tokens=8)
        assert generated.shape == (1, 14)
        assert np.array_equal(generated[:, :6], prompt)

        # local ground truth: full forward of the SERVED sequence through the
        # checkpoint blocks + head (teacher-forced replay, so positions check
        # independently). The served path computes in bf16 through a different
        # jit than the local one — a near-tied top-2 may flip, so accept the
        # generated token when its local logit is within bf16 noise of the max.
        hidden = _local_reference(tmp_path, head.embed(generated))
        local_logits = head.logits(hidden)
        for t in range(6, 14):
            position = local_logits[0, t - 1]
            best = float(np.max(position))
            chosen = float(position[int(generated[0, t])])
            tolerance = 2e-2 * max(abs(best), 1.0)
            assert best - chosen <= tolerance, (
                t, int(generated[0, t]), int(np.argmax(position)), best - chosen
            )
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        dht.shutdown()


def test_generation_across_two_servers(tmp_path):
    """The multi-server BASELINE #5 topology: each server hosts a layer RANGE of
    the same checkpoint (quickstart's --llama_layers story); the client chains
    them by uid and generates across both."""
    from safetensors.numpy import save_file

    from hivemind_tpu.moe import RemoteSequential
    from hivemind_tpu.moe.server.llama_loader import LlamaClientHead, generate_greedy

    VOCAB = 64
    _write_checkpoint(tmp_path)
    rng = np.random.RandomState(33)
    head_tensors = {
        "model.embed_tokens.weight": (rng.randn(VOCAB, HID) / np.sqrt(HID)).astype(np.float32),
        "model.norm.weight": np.ones(HID, np.float32),
    }
    save_file(head_tensors, tmp_path / "model-head.safetensors")
    index_path = tmp_path / "model.safetensors.index.json"
    index = json.loads(index_path.read_text())
    index["weight_map"].update({n: "model-head.safetensors" for n in head_tensors})
    index_path.write_text(json.dumps(index))

    backends_a, _config = load_llama_blocks(tmp_path, layers=[0], uid_prefix="sp.")
    backends_b, _config = load_llama_blocks(
        tmp_path, layers=[1], uid_prefix="sp.", weight_quantization="int8"
    )
    dht_a = DHT(start=True)
    server_a = Server(dht_a, backends_a, decode_max_len=64)
    dht_b = DHT(initial_peers=[str(m) for m in dht_a.get_visible_maddrs()], start=True)
    server_b = Server(dht_b, backends_b, decode_max_len=64)
    client_dht = None
    try:
        server_a.run_in_background(await_ready=True)
        server_b.run_in_background(await_ready=True)
        wait_for_experts(dht_a, [*server_a.backends, *server_b.backends])
        client_dht = DHT(initial_peers=[str(m) for m in dht_a.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "sp.", LAYERS)
        head = LlamaClientHead.load(tmp_path)
        assert np.array_equal(head.lm_head_matrix, head.embed_matrix)  # tied fallback

        prompt = rng.randint(0, VOCAB, size=(1, 4))
        generated = generate_greedy(head, pipe, prompt, max_new_tokens=5)
        assert generated.shape == (1, 9)
        assert np.array_equal(generated[:, :4], prompt)
        assert (generated >= 0).all() and (generated < VOCAB).all()
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server_b.shutdown()
        server_a.shutdown()
        dht_b.shutdown()
        dht_a.shutdown()


def test_predicted_block_bytes_match_measured_gqa(tmp_path):
    """plan_block_capacity's planning input must be trustworthy BEFORE weights
    load (VERDICT r3 #8): predict_block_param_bytes from config arithmetic alone
    must match the measured resident bytes of a loaded block within 10%, for both
    fp32 and int8, at a GQA shape (hidden 1024, 4 layers, kv_heads < heads,
    sharded index)."""
    from hivemind_tpu.moe.server.llama_loader import predict_block_param_bytes

    synthesize_checkpoint(tmp_path, hidden=1024, heads=8, kv_heads=2, inner=2816, layers=4)
    config = LlamaCheckpointConfig.load(tmp_path)

    for quantization in (None, "int8"):
        predicted = predict_block_param_bytes(config, quantization)
        backends, _ = load_llama_blocks(
            tmp_path, uid_prefix="pb.", weight_quantization=quantization, layers=[0]
        )
        measured = backends["pb.0"].param_bytes()
        assert abs(predicted - measured) <= 0.10 * measured, (
            f"{quantization}: predicted {predicted} vs measured {measured}"
        )
    # int8 must actually shrink the block ~4x
    assert predict_block_param_bytes(config, "int8") < 0.3 * predict_block_param_bytes(config)


def test_a_dropped_backend_releases_its_weights(tmp_path):
    """run_server's HBM plan loads one probe block, measures it and drops it before the
    real load. Dropping must free the device arrays there and then: a jitted closure
    that captured the backend would pin the block (a reference cycle through a jitted
    function is never collected)."""
    import jax

    _write_checkpoint(tmp_path)
    live_bytes = lambda: sum(array.nbytes for array in jax.live_arrays())
    for options in ({}, {"weight_quantization": "int8"}):
        before = live_bytes()
        probe, _config = load_llama_blocks(tmp_path, layers=[0], uid_prefix="_probe.", **options)
        backend = probe["_probe.0"]
        backend.forward(np.zeros((1, 8, HID), np.float32))
        assert live_bytes() - before >= backend.param_bytes()
        del probe, backend
        assert live_bytes() == before, options
