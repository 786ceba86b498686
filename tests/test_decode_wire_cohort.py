"""A batched decode step's rows cross the wire's dtype once a COHORT (ISSUE 57): under the
plain fp16 codec the handler hands the session manager a float16 view of a request's buffer,
the cohort's rows widen in the one join before the upload, the last output's rows take ONE
fp16 pass where they were fetched, and the handler frames their bytes. `ConnectionHandler`
over a `DecodeSessionManager`, no network: the bytes of a response are the codec's, byte for
byte, and no program sees another dtype."""

import asyncio
import functools
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hivemind_tpu.compression import (
    CompressionType,
    Float16Compression,
    deserialize_tensor,
    expert_response_parts,
    serialize_tensor,
)
from hivemind_tpu.compression.floating import to_half
from hivemind_tpu.moe.server import decode_session
from hivemind_tpu.moe.server.connection_handler import ConnectionHandler
from hivemind_tpu.proto import runtime_pb2
from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.utils.serializer import MSGPackSerializer
from swarm_utils import ManagerSharingPrograms, OneProgramBackend, decode_compiles as _compiles

HID = 16
CHAIN = ("wire.0", "wire.1")
CONTEXT = SimpleNamespace(local_id="srv", remote_id="wire-client")
FP16, NONE = CompressionType.FLOAT16, CompressionType.NONE

# what a float32 output can hold that the codec's clip and cast treat apart: beyond the halves'
# range either way, infinities, a NaN, both zeros, halves that are subnormal, values that underflow
_SPECIAL = np.array([7e4, -7e4, 3e38, -3e38, np.inf, -np.inf, np.nan, 0.0, -0.0, 65504.0, -65520.0, 6e-8, -6e-8,
                     2.0**-15, -(2.0**-24), 1e-9], np.float32)


@functools.cache  # no test trains a block: each is built once a process, and its programs compiled once
def _backend(uid, seed):
    from hivemind_tpu.moe.server.layers.common import CausalTransformerExpert

    return OneProgramBackend(uid, CausalTransformerExpert(hidden_dim=HID, num_heads=4), optimizer=optax.sgd(1e-3),
                             sample_input=np.zeros((1, 4, HID), np.float32), max_batch_size=8, rng_seed=seed)


def _handler(codec="float16"):
    """A handler of ``codec`` over `CHAIN`, whose session manager shares this file's programs."""
    backends = {uid: _backend(uid, seed) for seed, uid in enumerate(CHAIN)}
    handler = ConnectionHandler(backends, activation_compression=codec)
    handler.decode_sessions = ManagerSharingPrograms(backends, max_len=32, max_sessions=256)
    return handler, handler.decode_sessions


def _request(session_id, x, compression, reset=False):
    """One `rpc_decode` request through the whole of `CHAIN`."""
    metadata = {"session_id": session_id, "reset": reset, "uids": list(CHAIN)}
    return runtime_pb2.ExpertRequest(uid=CHAIN[0], tensors=[serialize_tensor(x, compression)],
                                     metadata=MSGPackSerializer.dumps(metadata))


def _tensor(response) -> runtime_pb2.Tensor:
    [tensor] = runtime_pb2.ExpertResponse.FromString(response.join()).tensors
    return tensor


def _same_tensor(got: runtime_pb2.Tensor, expected: runtime_pb2.Tensor) -> bool:
    return (got.buffer, tuple(got.size), got.dtype, got.compression) == (
        expected.buffer, tuple(expected.size), expected.dtype, expected.compression)


def _call(handler, requests):
    """The requests as concurrent `rpc_decode`s of one loop tick; the responses, in order."""
    async def scenario():
        return await asyncio.wait_for(asyncio.gather(*(handler.rpc_decode(request, CONTEXT) for request in requests)), 120.0)

    return asyncio.run(scenario())


def _prefill(handler, names, rng, compression=FP16):
    return _call(handler, [_request(name, rng.randn(1, 3, HID).astype(np.float32), compression, reset=True)
                           for name in names])


def _responses():
    counter = REGISTRY.get("hivemind_moe_decode_responses_total")
    return {at: counter.labels(at).value for at in ("cohort", "handler")}


def _moved(before):
    return {at: value - before[at] for at, value in _responses().items()}


class _Recorded:
    """What the manager makes and is handed from here to the test's end (``monkeypatch`` undoes it): every
    batched program's `_Output` (the chain's last given ``last_output`` as its ``y``), every array through the
    upload program, every answer of `decode_span_async`."""

    def __init__(self, manager, monkeypatch, last_output=None):
        self.outputs, self.uploads, self.answers = [], [], []
        made, upload, answer = decode_session._Output.__init__, manager._upload, manager.decode_span_async

        def output(this, y, *args, **kwargs):
            if last_output is not None and y.shape[0] == len(last_output):  # the chain's last program answers THIS
                y = jnp.asarray(last_output) if len(self.outputs) % len(CHAIN) == len(CHAIN) - 1 else y
            made(this, y, *args, **kwargs)
            self.outputs.append(this)

        def uploaded(xs):
            self.uploads.append(xs)
            return upload(xs)

        async def answered(*args, **kwargs):
            out = await answer(*args, **kwargs)
            self.answers.append(out)
            return out

        monkeypatch.setattr(decode_session._Output, "__init__", output)
        monkeypatch.setattr(manager, "_upload", uploaded)
        monkeypatch.setattr(manager, "decode_span_async", answered)


@pytest.mark.parametrize("rows, bucket", [(2, 2), (7, 8), (16, 16), (13, 16)])
def test_a_cohorts_responses_are_the_codecs_bytes(rows, bucket, monkeypatch):
    """(a), (f): whatever float32 the last program hands back, each row's response is
    `Float16Compression().compress(row)` byte for byte, and the cohort's rows count `cohort`."""
    handler, manager = _handler()
    rng = np.random.RandomState(rows)
    names = [f"s{i}" for i in range(rows)]
    _prefill(handler, names, rng)
    last = rng.randn(bucket, 1, HID).astype(np.float32)
    for row in range(bucket):
        last[row, 0, : HID // 2] = np.roll(_SPECIAL, row)[: HID // 2]
    seen = _Recorded(manager, monkeypatch, last_output=last)
    before = _responses()
    responses = _call(handler, [_request(name, rng.randn(1, 1, HID).astype(np.float32), FP16) for name in names])
    assert _moved(before) == {"cohort": rows, "handler": 0}
    assert [(o.rows, o.y.shape[0]) for o in seen.outputs] == [(rows, bucket)] * len(CHAIN)
    for row, response in enumerate(responses):
        expected = Float16Compression().compress(last[row:row + 1])
        assert _same_tensor(_tensor(response), expected), row
        assert response.join() == expert_response_parts([expected]).join()
        assert (expected.dtype, expected.compression) == ("float32", FP16)
    final = seen.outputs[-1]  # the wire copy is a second array: the float32 copy is as it was fetched
    assert final.in_half.dtype == np.float16 and final.in_half.shape == (rows, 1, HID)
    assert final.on_host.tobytes() == last.tobytes() and not np.shares_memory(final.in_half, final.on_host)
    assert all(o.in_half is None for o in seen.outputs[:-1])  # ONE pass a cohort, of the last output only


@pytest.mark.parametrize("float32_rows", [0, 1])
def test_half_rows_widen_in_the_one_join_before_the_upload(float32_rows, monkeypatch):
    """(b): the float32 `[bucket, 1, hidden]` that reaches the upload program holds what
    `deserialize_tensor` makes of each request, bit for bit, padding rows zero — also when a
    float32 row (a client that sent its token uncompressed) rides in the cohort; that row is
    answered in float32 and converted by its handler."""
    handler, manager = _handler()
    rng, rows, bucket = np.random.RandomState(5), 6, 8
    names = [f"s{i}" for i in range(rows)]
    _prefill(handler, names, rng)
    tokens = [np.roll(_SPECIAL, i).reshape(1, 1, HID) for i in range(rows)]
    compressions = [NONE] * float32_rows + [FP16] * (rows - float32_rows)
    requests = [_request(name, token, compression) for name, token, compression in zip(names, tokens, compressions)]
    seen = _Recorded(manager, monkeypatch)
    before = _responses()
    responses = _call(handler, requests)
    assert _moved(before) == {"cohort": rows - float32_rows, "handler": float32_rows}
    [xs] = seen.uploads  # one upload a cohort: the first block's; the second takes the first's output where it lies
    assert xs.dtype == np.float32 and xs.shape == (bucket, 1, HID)
    expected = np.concatenate([deserialize_tensor(request.tensors[0]) for request in requests])
    assert xs[:rows].tobytes() == expected.tobytes() and not xs[rows:].any()
    for response, answer, compression in zip(responses, seen.answers, compressions):
        assert answer.dtype == (np.float32 if compression == NONE else np.float16)
        assert _tensor(response).compression == FP16 and _tensor(response).dtype == "float32"
    final = seen.outputs[-1]
    for row in range(float32_rows):  # the float32 row's answer is the codec's own, made by the handler
        assert _same_tensor(_tensor(responses[row]), Float16Compression().compress(final.on_host[row:row + 1]))


@pytest.mark.parametrize("codec, request_compression", [("none", NONE), ("meanstd_16bit", CompressionType.MEANSTD_16BIT),
                                                        ("meanstd_16bit", FP16), ("none", FP16)])
def test_any_other_codec_converts_in_its_handler_as_before(codec, request_compression, monkeypatch):
    """(c): a server whose wire is not plain fp16 deserializes every request to float32 (an fp16
    request too) and serializes every float32 answer with its codec, a cohort's as any other."""
    handler, manager = _handler(codec)
    rng, names = np.random.RandomState(11), ["s0", "s1", "s2"]
    _prefill(handler, names, rng, request_compression)
    seen = _Recorded(manager, monkeypatch)
    before = _responses()
    responses = _call(handler, [_request(name, rng.randn(1, 1, HID).astype(np.float32), request_compression)
                                for name in names])
    assert _moved(before) == {"cohort": 0, "handler": len(names)}
    assert [o.rows for o in seen.outputs] == [3, 3] and all(o.in_half is None for o in seen.outputs)
    assert seen.uploads[0].dtype == np.float32
    for response, answer in zip(responses, seen.answers):
        assert answer.dtype == np.float32
        assert _same_tensor(_tensor(response), serialize_tensor(answer, handler.activation_codec))


def test_what_the_direct_path_answers_converts_in_its_handler(monkeypatch):
    """(c): a prefill, a reset of one position and a lone stream's token, under the fp16 codec: the
    request's halves go to `_decode_direct` as they lie, which widens its own on its executor
    thread, and the float32 answer is the codec's `compress`, made by the handler."""
    handler, manager = _handler()
    rng = np.random.RandomState(13)
    seen = _Recorded(manager, monkeypatch)
    before = _responses()
    steps = [(rng.randn(1, 5, HID).astype(np.float32), True), (rng.randn(1, 1, HID).astype(np.float32), False),
             (rng.randn(1, 1, HID).astype(np.float32), True), (np.roll(_SPECIAL, 3).reshape(1, 1, HID), False)]
    twin = ManagerSharingPrograms(manager.backends, max_len=32, max_sessions=256)
    for x, reset in steps:
        [response] = _call(handler, [_request("lone", x, FP16, reset)])
        [answer] = seen.answers[-1:]
        assert answer.dtype == np.float32 and _same_tensor(_tensor(response), Float16Compression().compress(answer))
        # and it is what the step makes of the float32 that the codec's `extract` gives
        expected = twin._decode_direct(CHAIN, "lone", deserialize_tensor(serialize_tensor(x, FP16)), reset)
        assert answer.tobytes() == expected.tobytes()
    assert _moved(before) == {"cohort": 0, "handler": len(steps)}
    assert not seen.outputs and not seen.uploads  # no cohort was formed


def test_an_answer_past_the_inline_threshold_counts_under_neither(monkeypatch):
    """A long prompt's answer is serialized on the executor, as ever: it was never the loop thread's,
    and the counter holds what that thread converts and what it was spared."""
    from hivemind_tpu.moe.server import connection_handler

    handler, _manager = _handler()
    x = np.random.RandomState(29).randn(1, 5, HID).astype(np.float32)
    monkeypatch.setattr(connection_handler, "_OFF_LOOP_CODEC_BYTES", x.nbytes)  # the answer's size: no longer under it
    before = _responses()
    [response] = _call(handler, [_request("long", x, NONE, reset=True)])
    assert _moved(before) == {"cohort": 0, "handler": 0}
    assert (_tensor(response).compression, tuple(_tensor(response).size)) == (FP16, (1, 5, HID))


def test_a_row_that_fails_mid_chain_leaves_the_others_answers_and_the_host_copy(monkeypatch):
    """(d): the second block finds one session full: that client gets the error, the others'
    responses are the codec's bytes of their rows, and the first block's float32 copy, which
    the rows that no longer line up were read from, stays as it was fetched."""
    handler, manager = _handler()
    rng, names = np.random.RandomState(17), ["s0", "s1", "s2"]
    _prefill(handler, names, rng)
    manager._sessions[(CHAIN[1], "s1")].index = manager.max_len
    last = np.stack([np.roll(_SPECIAL, row).reshape(1, HID) for row in range(2)])
    seen = _Recorded(manager, monkeypatch, last_output=last)

    async def scenario():
        return await asyncio.gather(*(handler.rpc_decode(_request(name, rng.randn(1, 1, HID).astype(np.float32), FP16), CONTEXT)
                                      for name in names), return_exceptions=True)

    before = _responses()
    responses = asyncio.run(scenario())
    assert isinstance(responses[1], ValueError) and "full" in str(responses[1])
    assert _moved(before) == {"cohort": 2, "handler": 0}
    first, final = seen.outputs
    assert (first.rows, final.rows) == (3, 2) and first.in_half is None
    assert first.on_host.tobytes() == np.asarray(first.y).tobytes()  # unclipped, unconverted
    [xs_first, xs_second] = seen.uploads  # the two rows left were read off the host copy and uploaded again
    assert xs_second.tobytes() == first.on_host[[0, 2]].tobytes()
    for row, response in zip(range(2), (responses[0], responses[2])):
        assert _same_tensor(_tensor(response), Float16Compression().compress(last[row:row + 1]))
    assert manager._in_flight == {} and not any(session.lock.locked() for session in manager._sessions.values())


def test_the_warm_ups_float32_rows_come_back_float32_and_a_half_cohort_compiles_nothing(monkeypatch):
    """(e): `manager._decode_batch(uid, entries)` as `perf/runners/block_server.warm_decode` calls
    it keeps its dtype in and out; and the cohort of fp16 requests that follows reaches the
    programs that call compiled: the upload and every block's program see float32 as before."""
    handler, manager = _handler()
    rng, rows = np.random.RandomState(19), 4
    names = [f"s{i}" for i in range(rows)]
    _prefill(handler, names, rng)
    token = np.zeros((1, 1, HID), np.float32)
    for uid in CHAIN:
        entries = [(None, manager._sessions[(uid, name)], token) for name in names]
        outs = manager._decode_batch(uid, entries)
        assert all(out.dtype == np.float32 and out.shape == (1, 1, HID) for out in outs)
    compiles, programs = _compiles(), (len(manager._batched_fns), len(manager._step_fns))
    _prefill(handler, names, rng)  # the warm-up left the blocks' positions apart: start the sessions over
    assert _compiles() == compiles
    before = _responses()
    responses = _call(handler, [_request(name, rng.randn(1, 1, HID).astype(np.float32), FP16) for name in names])
    assert _moved(before) == {"cohort": rows, "handler": 0} and len(responses) == rows
    assert _compiles() == compiles and (len(manager._batched_fns), len(manager._step_fns)) == programs


def test_a_half_row_alone_in_its_program_is_widened_for_the_sessions_own_step():
    """A cohort of which ONE row is live takes the per-session program, whose input is float32:
    the half row is widened for it and answered in float32 (its handler converts)."""
    _handler_unused, manager = _handler()
    rng, chain = np.random.RandomState(23), CHAIN[:1]
    for name in ("s0", "s1"):
        prompt = rng.randn(1, 3, HID).astype(np.float32)
        for session_id in (name, "twin-" + name):
            manager._decode_direct(chain, session_id, prompt, True)
    manager._sessions[(chain[0], "s1")].index = manager.max_len  # s1 is refused at the block: s0 is alone in the cohort
    token = to_half(np.roll(_SPECIAL, 1), False).reshape(1, 1, HID)  # as a request's buffer holds it
    compiles = _compiles()

    async def scenario():
        return await asyncio.gather(*(manager.decode_span_async(chain, name, token, False) for name in ("s0", "s1")),
                                    return_exceptions=True)

    out, error = asyncio.run(scenario())
    assert isinstance(error, ValueError) and "full" in str(error) and out.dtype == np.float32
    assert out.tobytes() == manager._decode_direct(chain, "twin-s0", token.astype(np.float32), False).tobytes()
    assert _compiles() == compiles
