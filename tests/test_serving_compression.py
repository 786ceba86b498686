"""Compressed expert RPC (ISSUE 10): wire-splice byte identity, per-codec
round-trips through a REAL client→server forward/backward/decode, mixed-
compression swarm interop, and shed/breaker/scorecard behavior under
compression. The serving wire dtype defaults to fp16 (``none`` = bit-identical
fp32); every assertion here pins the contract the default relies on."""

import uuid

import numpy as np
import optax
import pytest

from hivemind_tpu.compression import (
    CompressionType,
    codec_name,
    expert_request_parts,
    expert_response_parts,
    get_codec,
    resolve_activation_codec,
    serialize_tensor,
    split_response_for_wire,
    split_tensor_for_streaming,
)
from hivemind_tpu.proto import runtime_pb2
from swarm_utils import wait_for_experts

HID = 16

ALL_CODEC_NAMES = tuple(k.lower() for k in runtime_pb2.CompressionType.keys())


# ------------------------------------------------------------- wire splicers


@pytest.mark.parametrize("name", ALL_CODEC_NAMES)
def test_wire_parts_byte_identical_to_protobuf(name):
    """The hand-spliced scatter-gather frames must be byte-identical to
    protobuf's own SerializeToString for every codec — the receive side parses
    them with the stock generated classes."""
    rng = np.random.RandomState(0)
    codec = resolve_activation_codec(name)
    for array in (
        rng.randn(3, 5).astype(np.float32),
        rng.randn(70000).astype(np.float32),  # multi-chunk when split
        np.array([], np.float32),
        np.float32(2.25),
    ):
        tensor = serialize_tensor(array, codec)
        request = runtime_pb2.ExpertRequest(
            uid="eq.0", tensors=[tensor, tensor], metadata=b"\x00meta"
        )
        assert (
            expert_request_parts("eq.0", [tensor, tensor], b"\x00meta").join()
            == request.SerializeToString()
        )
        # empty uid/metadata are omitted fields, exactly like protobuf
        assert (
            expert_request_parts("", [tensor]).join()
            == runtime_pb2.ExpertRequest(tensors=[tensor]).SerializeToString()
        )
        assert (
            expert_response_parts([tensor]).join()
            == runtime_pb2.ExpertResponse(tensors=[tensor]).SerializeToString()
        )
        # stream chunks: same frames the proto-built chunking emits
        expected_chunks = [
            runtime_pb2.ExpertResponse(tensors=[chunk]).SerializeToString()
            for chunk in split_tensor_for_streaming(tensor, 1024)
        ]
        assert [w.join() for w in split_response_for_wire(tensor, 1024)] == expected_chunks


def test_resolve_activation_codec_knob():
    assert resolve_activation_codec(None).compression_type == CompressionType.NONE
    assert resolve_activation_codec("FLOAT16") is get_codec(CompressionType.FLOAT16)
    assert codec_name(resolve_activation_codec("meanstd_16bit")) == "meanstd_16bit"
    with pytest.raises(ValueError, match="unknown activation compression"):
        resolve_activation_codec("bogus")


# ------------------------------------------------------- real RPC round trips


@pytest.fixture(scope="module")
def serving_pair():
    """One real server + client DHT shared by the round-trip tests (module
    scoped: server startup dominates the suite's runtime)."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import Server

    server = Server.create(
        expert_uids=["eq.0"], expert_cls="causal_transformer", hidden_dim=HID,
        start=True, optim_factory=lambda: optax.sgd(1e-4),
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(
            initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True
        )
        yield server, client_dht
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def _remote(server, client_dht, compression):
    from hivemind_tpu.moe import RemoteExpert
    from hivemind_tpu.moe.expert_uid import ExpertInfo

    return RemoteExpert(
        ExpertInfo("eq.0", server.dht.peer_id, compression), client_dht.node.p2p
    )


@pytest.mark.parametrize("name", ALL_CODEC_NAMES)
def test_codec_forward_roundtrip_through_real_rpc(serving_pair, name):
    """Every codec survives a real rpc_forward: NONE bitwise vs the local
    backend, 16-bit codecs within the documented tolerance, 8-bit codecs
    finite and correlated (they are lossy by design)."""
    server, client_dht = serving_pair
    server.handler.activation_codec = resolve_activation_codec(name)
    expert = _remote(server, client_dht, name)
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, HID).astype(np.float32)
    [out] = expert.forward_np(x)
    [local] = server.backends["eq.0"].forward(x)
    assert out.shape == local.shape and np.isfinite(out).all()
    if name == "none":
        np.testing.assert_array_equal(out, local)
    elif name in ("float16", "meanstd_16bit"):
        np.testing.assert_allclose(out, local, rtol=2e-2, atol=2e-2)
    else:  # 8-bit: lossy; the signal must still clearly be the same function
        correlation = np.corrcoef(out.ravel(), local.ravel())[0, 1]
        assert correlation > 0.95, correlation


def test_backward_and_decode_roundtrip_none_bitwise(serving_pair):
    """rpc_backward and rpc_decode under the NONE fallback are bit-identical to
    local execution (backward compares gradients BEFORE the optimizer step
    drifts the params; decode compares against a local session manager over the
    same backend)."""
    from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

    server, client_dht = serving_pair
    server.handler.activation_codec = resolve_activation_codec("none")
    expert = _remote(server, client_dht, "none")
    backend = server.backends["eq.0"]
    rng = np.random.RandomState(2)

    # decode: prefill + one continuation, bitwise vs a local manager
    session = uuid.uuid4().hex
    prompt = rng.randn(1, 4, HID).astype(np.float32)
    step = rng.randn(1, 1, HID).astype(np.float32)
    remote_prefill = expert.decode_np(prompt, session, reset=True)
    remote_step = expert.decode_np(step, session)
    local_mgr = DecodeSessionManager({"eq.0": backend}, max_len=256)
    local_prefill = local_mgr.decode("eq.0", "local", prompt, reset=True)
    local_step = local_mgr.decode("eq.0", "local", step, reset=False)
    np.testing.assert_array_equal(remote_prefill, local_prefill)
    np.testing.assert_array_equal(remote_step, local_step)

    # backward: compare gradients against a bit-equal local replay. The remote
    # call ALSO steps the expert's optimizer (by design), so replay locally on
    # a clone of the params first.
    import copy

    x = rng.randn(2, 4, HID).astype(np.float32)
    grad_out = rng.randn(2, 4, HID).astype(np.float32)
    params_before = copy.deepcopy(backend.params)
    opt_before = copy.deepcopy(backend.opt_state)
    [local_grad] = backend.backward(x, grad_out)
    backend.params, backend.opt_state = params_before, opt_before  # rewind the step
    [remote_grad] = expert.backward_np(x, grad_out)
    np.testing.assert_array_equal(remote_grad, local_grad)


def test_fp16_backward_within_tolerance(serving_pair):
    server, client_dht = serving_pair
    server.handler.activation_codec = resolve_activation_codec("float16")
    expert = _remote(server, client_dht, "float16")
    rng = np.random.RandomState(3)
    x = rng.randn(2, 4, HID).astype(np.float32)
    grad_out = rng.randn(2, 4, HID).astype(np.float32)
    import copy

    backend = server.backends["eq.0"]
    params_before = copy.deepcopy(backend.params)
    opt_before = copy.deepcopy(backend.opt_state)
    [local_grad] = backend.backward(x, grad_out)
    backend.params, backend.opt_state = params_before, opt_before
    [remote_grad] = expert.backward_np(x, grad_out)
    np.testing.assert_allclose(remote_grad, local_grad, rtol=5e-2, atol=5e-2)


def test_mixed_compression_swarm_interop(serving_pair):
    """A tensor self-describes its codec on the wire, so an fp16 client against
    a NONE server (and vice versa) interoperates — the designed mixed-swarm /
    rolling-upgrade posture."""
    server, client_dht = serving_pair
    rng = np.random.RandomState(4)
    x = rng.randn(2, 5, HID).astype(np.float32)
    [local] = server.backends["eq.0"].forward(x)

    # fp16 client → NONE server: request rides fp16, response rides fp32
    server.handler.activation_codec = resolve_activation_codec("none")
    fp16_client = _remote(server, client_dht, "float16")
    [out] = fp16_client.forward_np(x)
    np.testing.assert_allclose(out, local, rtol=2e-2, atol=2e-2)

    # NONE client → fp16 server: request exact, response rides fp16
    server.handler.activation_codec = resolve_activation_codec("float16")
    none_client = _remote(server, client_dht, "none")
    [out2] = none_client.forward_np(x)
    np.testing.assert_allclose(out2, local, rtol=2e-2, atol=2e-2)


def test_negotiation_follows_server_advertisement(serving_pair):
    """A client WITHOUT an explicit override negotiates the server's advertised
    codec: from the DHT declaration when present, else via rpc_info."""
    from hivemind_tpu.moe import RemoteExpert
    from hivemind_tpu.moe.expert_uid import ExpertInfo
    from hivemind_tpu.moe.server.dht_handler import get_experts
    from hivemind_tpu.utils.loop import get_loop_runner

    server, client_dht = serving_pair
    server.handler.activation_codec = resolve_activation_codec("float16")

    # DHT path: the periodic declaration carries the wire dtype
    [info] = get_experts(client_dht, ["eq.0"])
    assert info is not None and info.compression == "float16"
    expert = RemoteExpert(info, client_dht.node.p2p)
    codec = get_loop_runner().run_coroutine(expert._wire_codec())
    assert codec.compression_type == CompressionType.FLOAT16

    # rpc_info path: an ExpertInfo without compression falls back to rpc_info
    bare = RemoteExpert(ExpertInfo("eq.0", server.dht.peer_id), client_dht.node.p2p)
    codec = get_loop_runner().run_coroutine(bare._wire_codec())
    assert codec.compression_type == CompressionType.FLOAT16
    assert bare.info["activation_compression"] == "float16"


def test_shed_breaker_scorecard_unchanged_under_compression(serving_pair):
    """Load-shed semantics are orthogonal to the wire dtype: a full bounded
    queue sheds with the typed error across the RPC boundary, trips the expert
    breaker after two sheds, and lands on the client scorecard — all with fp16
    activations active."""
    from hivemind_tpu.moe.client.call_many import EXPERT_BREAKERS
    from hivemind_tpu.telemetry import REGISTRY
    from hivemind_tpu.telemetry.serving import SCORECARDS

    server, client_dht = serving_pair
    server.handler.activation_codec = resolve_activation_codec("float16")
    expert = _remote(server, client_dht, "float16")
    rng = np.random.RandomState(5)
    x = rng.randn(1, 4, HID).astype(np.float32)
    [warm] = expert.forward_np(x)  # route + schema warm, codec active
    assert np.isfinite(warm).all()

    shed_total = REGISTRY.get("hivemind_moe_shed_total")
    sheds_before = shed_total.labels("eq.0_forward").value
    pool = server.handler.chain_pool("forward", ["eq.0"])
    pool.max_queue_size = 0  # shed everything
    try:
        for _ in range(2):  # EXPERT_BREAKERS failure_threshold == 2
            with pytest.raises(Exception, match="ServerOverloadedError"):
                expert.forward_np(x)
    finally:
        pool.max_queue_size = 1024
    assert shed_total.labels("eq.0_forward").value == sheds_before + 2
    assert "eq.0" in EXPERT_BREAKERS, "sheds did not trip the expert breaker under fp16"
    card = SCORECARDS.card("eq.0")
    assert card is not None and card["sheds"] >= 2
