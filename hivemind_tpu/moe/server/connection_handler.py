"""Expert RPC endpoints (capability parity: reference
hivemind/moe/server/connection_handler.py:22-177 — there N forked handler processes;
here one asyncio servicer feeding the task pools directly).

The unit of pooling is the span chain that a request names (ISSUE 30): a forward or
backward request for k consecutive co-located blocks is ONE task in the pool of that
direction and chain, and the pool's batch walks the k blocks on the device in one
executor call (`module_backend.forward_chain` / `backward_chain`): one upload and one
fetch a request. A request for one block is a chain of one on the same path.

Serving attribution (ISSUE 9): every expert RPC runs inside a ``serving.request``
span — a child of the ``p2p.handle:`` span, which already joined the remote
caller's trace via cross-peer propagation, so the request's phase decomposition
(queue-wait / batch-assembly / compute / staging stamped by the TaskPool — by
the DecodeSessionManager for decode steps, which bypass the pools — deserialize
and serialize stamped here) lands in the CALLER's trace and in the process-wide
:data:`~hivemind_tpu.telemetry.serving.SERVING_LEDGER`.

Serving data path (ISSUE 10, the PR 5 playbook applied to this layer):

- **Wire dtype**: responses are serialized with this server's configured
  activation codec (``--activation_compression``; default fp16, ``none`` =
  bit-identical). The choice is published in ``rpc_info`` (and on the DHT via
  the expert declarations), so clients negotiate the same dtype for requests.
- **Off-loop codecs**: request deserialization and response serialization run
  on the shared executor past a small inline threshold — the event-loop
  watchdog proved inline codecs stall RPC dispatch under load (the evidence
  was multi-MB payloads; a ~4 KB payload stays inline, where the executor
  hop would dominate). The ``serialize_s`` phase accrues the executor
  round-trip when off-loop (queue time included; see docs/observability.md).
- **A batched decode step converts nothing here** (ISSUE 57): under the fp16
  codec an ``rpc_decode`` request's row goes to the session manager as a float16
  VIEW of its buffer and a cohort's answer comes back as a float16 row, so the
  rows of a cohort change dtype together, once each way, on the threads that
  upload and fetch them (`decode_session._device_rows`, `_Output.wire`); this
  thread only frames bytes. ``hivemind_moe_decode_responses_total`` says how often,
  against the answers this thread still converts inline.
- **Scatter-gather responses**: responses leave as spliced
  :class:`~hivemind_tpu.utils.streaming.WireParts` frames — the tensor buffer
  rides into the AEAD as its own buffer instead of being copied into one
  ``SerializeToString`` blob; stream chunks are zero-copy memoryview slices,
  still serialized lazily one tensor at a time.
"""

from __future__ import annotations

import functools
import time
from typing import AsyncIterator, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from hivemind_tpu.compression import (
    CompressionType,
    Float16Compression,
    codec_name,
    deserialize_tensor,
    deserialize_tensor_stream,
    expert_response_parts,
    resolve_activation_codec,
    serialize_tensor,
    split_response_for_wire,
)
from hivemind_tpu.moe.expert_uid import IDEMPOTENT_CONNECTION_RPCS
from hivemind_tpu.moe.server.module_backend import ModuleBackend, backward_chain, forward_chain
from hivemind_tpu.moe.server.task_pool import TaskPool
from hivemind_tpu.p2p import P2P, P2PContext, ServicerBase
from hivemind_tpu.proto import runtime_pb2
from hivemind_tpu.telemetry.serving import (
    DECODE_RESPONSES,
    SERVING_SPAN,
    WIRE_BYTES_RECEIVED,
    WIRE_BYTES_SENT,
    accrue_span_phase,
)
from hivemind_tpu.telemetry.tracing import trace as _trace
from hivemind_tpu.telemetry.wire import count_work, wire_work
from hivemind_tpu.utils.asyncio_utils import run_in_executor
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.serializer import MSGPackSerializer
from hivemind_tpu.utils.streaming import WireParts

logger = get_logger(__name__)

_STREAM_CHUNK = 2**20  # 1 MiB chunks inside stream replies

# payloads below this encode/decode inline: the executor hop would dominate a
# few KB (same rationale and threshold as the client's _OFF_LOOP_CODEC_BYTES in
# moe/client/expert.py — the loop-stall evidence that motivated off-loop codecs
# came from MULTI-MB payloads). What still converts inline under it: the pools'
# small requests and, of the decode path, what `_decode_direct` answers (a short
# prefill, a reset, a lone stream) and every step of a server whose codec is not
# plain fp16. A BATCHED decode step under the fp16 codec converts nothing on the
# loop at all: its rows cross the wire's dtype with their cohort (ISSUE 57)
_OFF_LOOP_CODEC_BYTES = 256 * 1024

# what a pool's batch runs over its chain's backends, by the pool's direction
_CHAIN_WALKS = {"forward": forward_chain, "backward": backward_chain}

# cached metric children (one label value per role on this path)
_SERVER_BYTES_SENT = WIRE_BYTES_SENT.labels("server")
_SERVER_BYTES_RECEIVED = WIRE_BYTES_RECEIVED.labels("server")
_DTYPE_AT_COHORT, _DTYPE_AT_HANDLER = DECODE_RESPONSES.labels("cohort"), DECODE_RESPONSES.labels("handler")


def _is_half_of_float32(tensor: runtime_pb2.Tensor) -> bool:
    return tensor.compression == CompressionType.FLOAT16 and (tensor.dtype or "float32") == "float32"


def _half_view(tensor: runtime_pb2.Tensor) -> np.ndarray:
    """The halves of a FLOAT16 tensor as they lie in its buffer, in the tensor's shape: no copy, no cast."""
    return np.frombuffer(tensor.buffer, dtype=np.float16).reshape(tuple(tensor.size))


def _framed(serialized: List[runtime_pb2.Tensor], serialize_s: float) -> WireParts:
    """A unary response of ``serialized``, scatter-gather; the seconds they took onto the serving span."""
    accrue_span_phase("serialize_s", serialize_s)
    response = expert_response_parts(serialized)
    _SERVER_BYTES_SENT.inc(response.nbytes)
    return response


class ConnectionHandler(ServicerBase):
    # which RPCs may be retried on ambiguous connection loss — shared with the
    # client's direct call sites (expert.py), see expert_uid.py for the rationale
    _idempotent_rpcs = IDEMPOTENT_CONNECTION_RPCS

    def __init__(self, backends: Dict[str, ModuleBackend], decode_max_len: int = 256,
                 decode_max_sessions: int = 64, max_queue_size: int = 1024,
                 activation_compression: str = "float16",
                 client_rate: Optional[float] = None,
                 client_burst: Optional[float] = None):
        from hivemind_tpu.moe.server.decode_session import DecodeSessionManager

        self.backends = backends
        self.activation_codec = resolve_activation_codec(activation_compression)
        # plain fp16 (no subclass: a scaled codec ships statistics beside its halves): the one wire dtype whose
        # rows a decode cohort converts together, where this handler only frames their bytes
        self._wire_is_half = type(self.activation_codec) is Float16Compression
        # one pool per direction and span chain, made on the chain's first request
        # (a single block is a chain of one, made with its backend)
        self._pools: Dict[Tuple[str, Tuple[str, ...]], TaskPool] = {}
        self.on_new_pool: Optional[Callable[[TaskPool], None]] = None  # the Runtime's add_pool
        self._max_queue_size = max_queue_size
        self.decode_sessions = DecodeSessionManager(
            backends, max_len=decode_max_len, max_sessions=decode_max_sessions
        )
        # fair-share admission (ISSUE 13): per-client token buckets ahead of the
        # bounded queues — one hot tenant sheds at its own budget, typed exactly
        # like a queue shed, while other clients keep flowing. Opt-in.
        self.admission = None
        if client_rate:
            from hivemind_tpu.moe.server.admission import FairShareAdmission

            self.admission = FairShareAdmission(client_rate, burst=client_burst)
        for uid, backend in list(backends.items()):
            self.add_backend(uid, backend)

    def chain_pool(self, direction: str, uids: Sequence[str]) -> TaskPool:
        """The pool whose tasks are whole ``direction`` ("forward" / "backward")
        requests for the chain ``uids``, and whose batches walk the chain on the
        device in one executor call (`forward_chain` / `backward_chain`). Made on
        the chain's first request and handed to the Runtime (``on_new_pool``).
        Chains of consecutive co-located blocks number at most n(n+1)/2 a
        direction; a request for a new chain beyond that many pools is refused."""
        key = (direction, tuple(uids))
        pool = self._pools.get(key)
        if pool is None:
            if len(self._pools) >= len(self.backends) * (len(self.backends) + 1):
                raise ValueError(f"too many distinct span chains on this server ({len(self._pools)} pools)")
            chain = [self.backends[uid] for uid in uids]
            name = uids[0] if len(uids) == 1 else f"{uids[0]}..{uids[-1]}"
            pool = self._pools[key] = TaskPool(
                functools.partial(_CHAIN_WALKS[direction], chain), f"{name}_{direction}", blocks=len(chain),
                max_batch_size=min(backend.max_batch_size for backend in chain),
                max_queue_size=self._max_queue_size,
            )
            if self.on_new_pool is not None:
                self.on_new_pool(pool)
        return pool

    def add_backend(self, uid: str, backend: ModuleBackend) -> None:
        """Register a backend and make its single-block pools (a chain of one each
        way). At runtime (expert replication) the caller, Server.add_backend,
        re-declares; the pools reach the Runtime through ``on_new_pool``."""
        if ("forward", (uid,)) in self._pools:
            return
        self.backends[uid] = backend
        for direction in ("forward", "backward"):
            self.chain_pool(direction, (uid,))

    def _admit(self, context: P2PContext, tensors, kind: str) -> None:
        """Fair-share gate: draw this request's sample count from the calling
        client's token bucket (raises the typed ClientOverBudgetError shed).
        Runs inside the serving span so sheds stay attributed per client."""
        if self.admission is None:
            return
        cost = 1.0
        if tensors:
            first = tensors[0]
            if getattr(first, "ndim", 0):
                # samples, not requests: batching harder must not dodge the
                # budget. Decode steps are [batch, positions, hid] — charge
                # positions too (a prefill is prompt_len tokens of work).
                cost = float(first.shape[0])
                if getattr(first, "ndim", 0) >= 3:
                    cost *= float(first.shape[1])
        self.admission.admit(str(context.remote_id), cost, kind=kind)

    @property
    def activation_compression(self) -> str:
        """Canonical knob value of this server's wire dtype ("float16", "none", …)."""
        return codec_name(self.activation_codec)

    def all_pools(self) -> List[TaskPool]:
        return list(self._pools.values())

    @staticmethod
    def _serving_trace(kind: str, uid: str, context: P2PContext, tensors=None,
                       deserialize_s: Optional[float] = None) -> _trace:
        """The per-request serving span (ServingLedger assembles one record per
        finished span; see telemetry/serving.py). ``client`` is the remote
        caller — per-client attribution rides every record. ``deserialize_s``:
        what the unary RPCs spent turning wire tensors into numpy before the span
        could open (its attributes come from those tensors)."""
        attributes = {
            "kind": kind,
            "expert": uid,
            "peer": str(context.local_id),
            "client": str(context.remote_id),
        }
        if deserialize_s is not None:
            attributes["deserialize_s"] = round(deserialize_s, 6)
        if tensors:
            first = tensors[0]
            if getattr(first, "ndim", 0):
                attributes["batch"] = int(first.shape[0])
        return _trace(SERVING_SPAN, **attributes)

    # ------------------------------------------------------------------ RPCs

    async def rpc_info(self, request: runtime_pb2.ExpertUID, context: P2PContext) -> runtime_pb2.ExpertInfoResponse:
        backend = self.backends.get(request.uid)
        if backend is None:
            raise KeyError(f"unknown expert {request.uid!r}")
        info = backend.get_info()
        info["span_support"] = True  # clients only group co-located blocks if set
        # wire-dtype negotiation (ISSUE 10): clients serialize their request
        # activations with the server's declared codec (NONE stays bit-identical)
        info["activation_compression"] = self.activation_compression
        if self.decode_sessions.supports(request.uid):
            info["decode_max_len"] = self.decode_sessions.max_len
            # how many times a token runs this block, each pass on a cache of its own (a looped model's
            # `loop_pass`; 1 for every other block): a client reads it before it names a pass
            info["decode_passes"] = self.decode_sessions.block_passes(request.uid)
        return runtime_pb2.ExpertInfoResponse(serialized_info=MSGPackSerializer.dumps(info))

    def _span_uids(self, uid: str, metadata: bytes) -> List[str]:
        """Span execution: request metadata may name CONSECUTIVE co-located blocks
        (``{"uids": [...]}`` starting with the request uid) to run as one chain —
        one RPC, one pool task and one trip to the device and back per server
        instead of per block. A request without the key is a chain of one."""
        meta = MSGPackSerializer.loads(metadata) if metadata else {}
        uids = meta.get("uids") or [uid]
        if uids[0] != uid:
            raise ValueError(f"span uids must start with the request uid {uid!r}, got {uids!r}")
        if uid not in self.backends:
            raise KeyError(f"unknown expert {uid!r}")
        for prev, nxt in zip(uids, uids[1:]):
            prev_backend, next_backend = self.backends.get(prev), self.backends.get(nxt)
            if prev_backend is None or next_backend is None:
                raise KeyError(f"unknown expert in span: {prev!r} or {nxt!r}")
            if prev_backend.num_outputs != next_backend.num_inputs:
                raise ValueError(
                    f"span chain mismatch: {prev!r} outputs {prev_backend.num_outputs} "
                    f"tensors but {nxt!r} takes {next_backend.num_inputs}"
                )
        return uids

    async def _run_span(self, direction: str, uids: List[str], tensors: List[np.ndarray]) -> List[np.ndarray]:
        """One task for the whole chain: its pool's batch walks the blocks on the
        device. Forward: the first block's inputs in, the last block's outputs out.
        Backward: inputs and the last block's output gradients in, input gradients
        out; the batch recovers each block's inputs with a forward sweep, then
        backpropagates block by block in reverse (every block's backward also steps
        its optimizer — same semantics as per-block RPCs)."""
        expected = self.backends[uids[0]].num_inputs
        if direction == "backward":
            expected += self.backends[uids[-1]].num_outputs
        if len(tensors) != expected:
            raise ValueError(f"{direction} through {uids!r} takes {expected} tensors, got {len(tensors)}")
        return await self.chain_pool(direction, uids).submit_task(*tensors)

    # ------------------------------------------------------------------ codecs

    async def _deserialize_request(self, tensors, half_as_is: bool = False) -> Tuple[List[np.ndarray], float]:
        """Parse request tensors; big payloads decode off the event loop (the
        watchdog showed inline deserialization stalling dispatch under load),
        small ones inline (the executor hop would dominate them). Returns the
        arrays and the seconds it took (the serving span's ``deserialize_s``).
        ``half_as_is`` (``rpc_decode``, whose session manager widens a row where
        it joins it with its cohort's, or on the executor thread of its own step):
        where this server's wire is plain fp16, a small float32 tensor that came
        in it is handed on as a float16 VIEW of its buffer, unconverted."""
        started = time.perf_counter()
        tensor_list = list(tensors)
        nbytes = sum(len(t.buffer) for t in tensor_list)
        if nbytes < _OFF_LOOP_CODEC_BYTES:
            # a decode token's 8 KB, inline: the wire's counters take the seconds this
            # handler measures anyway, with no clock read of their own
            as_is = half_as_is and self._wire_is_half
            arrays = [_half_view(t) if as_is and _is_half_of_float32(t) else deserialize_tensor(t) for t in tensor_list]
            elapsed = time.perf_counter() - started
            count_work("decode", elapsed, nbytes)
            return arrays, elapsed
        arrays = await run_in_executor(self._deserialize_off_loop, tensor_list, nbytes)
        return arrays, time.perf_counter() - started

    @staticmethod
    def _deserialize_off_loop(tensors: List[runtime_pb2.Tensor], nbytes: int) -> List[np.ndarray]:
        with wire_work("decode", nbytes):  # a fine-tuning request's 33 MB: a `wire.decode` span
            return [deserialize_tensor(t) for t in tensors]

    def _serialize_outputs(self, outputs: List[np.ndarray]) -> List[runtime_pb2.Tensor]:
        # allow_inplace: each output row range is private to its task (views of
        # the fresh device-transfer batch), so the fp16 clip may reuse it
        return [serialize_tensor(o, self.activation_codec, None, True) for o in outputs]

    def _serialize_traced(self, outputs: List[np.ndarray], nbytes: int) -> List[runtime_pb2.Tensor]:
        with wire_work("encode", nbytes):
            return self._serialize_outputs(outputs)

    async def _respond(self, outputs: List[np.ndarray]) -> WireParts:
        """Serialize the response with the server's wire dtype (off-loop past
        the inline threshold), accrue the serialize phase onto the active
        serving span, and frame the tensors scatter-gather (buffers uncopied
        to the AEAD)."""
        start = time.perf_counter()
        nbytes = sum(int(getattr(o, "nbytes", 0)) for o in outputs)
        if nbytes < _OFF_LOOP_CODEC_BYTES:
            serialized = self._serialize_outputs(outputs)
            elapsed = time.perf_counter() - start
            count_work("encode", elapsed, nbytes)  # as in _deserialize_request
        else:
            serialized = await run_in_executor(self._serialize_traced, outputs, nbytes)
            elapsed = time.perf_counter() - start
        return _framed(serialized, elapsed)

    async def _respond_decode(self, output: np.ndarray) -> WireParts:
        """``rpc_decode``'s `_respond`. A row that a cohort answered is in the wire's
        dtype already (float16 under the plain fp16 codec: `_Output.wire` made it with
        its cohort's, the codec's own clip and cast): its bytes are framed as the
        codec frames them, a float32 tensor in FLOAT16, and nothing is converted on
        this thread. Any other answer (a prefill's, a reset's, a lone stream's; any
        other codec's) is serialized as every response is, and counted where that
        is inline, on this thread: a long prompt's answer was never converted here."""
        if not (self._wire_is_half and output.dtype == np.float16):
            if output.nbytes < _OFF_LOOP_CODEC_BYTES:
                _DTYPE_AT_HANDLER.inc()
            return await self._respond([output])
        _DTYPE_AT_COHORT.inc()
        start = time.perf_counter()
        serialized = runtime_pb2.Tensor(
            buffer=output.tobytes(), size=output.shape, dtype="float32", compression=CompressionType.FLOAT16
        )
        elapsed = time.perf_counter() - start
        count_work("encode", elapsed, 2 * output.nbytes)  # the float32 bytes the codec was handed before
        return _framed([serialized], elapsed)

    async def rpc_forward(self, request: runtime_pb2.ExpertRequest, context: P2PContext) -> runtime_pb2.ExpertResponse:
        _SERVER_BYTES_RECEIVED.inc(request.ByteSize())
        inputs, deserialize_s = await self._deserialize_request(request.tensors)
        with self._serving_trace("forward", request.uid, context, inputs, deserialize_s) as span:
            self._admit(context, inputs, "forward")
            uids = self._span_uids(request.uid, request.metadata)
            if span is not None and len(uids) > 1:
                span.set("span_len", len(uids))
            outputs = await self._run_span("forward", uids, inputs)
            return await self._respond(outputs)

    async def rpc_backward(self, request: runtime_pb2.ExpertRequest, context: P2PContext) -> runtime_pb2.ExpertResponse:
        _SERVER_BYTES_RECEIVED.inc(request.ByteSize())
        inputs, deserialize_s = await self._deserialize_request(request.tensors)
        with self._serving_trace("backward", request.uid, context, inputs, deserialize_s) as span:
            self._admit(context, inputs, "backward")
            uids = self._span_uids(request.uid, request.metadata)
            if span is not None and len(uids) > 1:
                span.set("span_len", len(uids))
            grads = await self._run_span("backward", uids, inputs)
            return await self._respond(grads)

    async def _run_decode(self, uid: str, metadata: bytes, tensors: List[np.ndarray], span=None) -> np.ndarray:
        meta = MSGPackSerializer.loads(metadata) if metadata else {}
        session_id = meta.get("session_id")
        if not session_id:
            raise ValueError("rpc_decode requires a session_id in request metadata")
        [x] = tensors
        # span execution: consecutive co-located pipeline blocks' session steps in
        # ONE rpc and ONE call into the session manager, which batches per span
        # chain: the steps of different clients that wait on this chain walk its
        # blocks together (a cohort), one batched device call a block. Decode
        # bypasses the pools: decode_span_async stamps the step's queue wait (the
        # flush window or the cohort before it) and compute onto the serving span
        uids = self._span_uids(uid, metadata)
        reset = bool(meta.get("reset", False))
        # which pass of a looped model's loop the step is (its blocks run `decode_passes` times a token, each
        # pass on a cache of its own); a client that knows no passes sends none and is served the first
        loop_pass = int(meta.get("loop_pass", 0))
        if span is not None:
            span.set("loop_pass", loop_pass)  # onto the ServingLedger's record of the request
        return await self.decode_sessions.decode_span_async(uids, str(session_id), x, reset, loop_pass)

    async def rpc_decode(self, request: runtime_pb2.ExpertRequest, context: P2PContext) -> runtime_pb2.ExpertResponse:
        """One KV-cache session step (decode_session.py). Metadata carries
        ``{"session_id": str, "reset": bool}`` and, from a client that walks a looped
        model's loop, ``"loop_pass": int`` (absent: 0); sessions bypass the batching
        pools — each holds its own per-client device cache, one a pass."""
        _SERVER_BYTES_RECEIVED.inc(request.ByteSize())
        tensors, deserialize_s = await self._deserialize_request(request.tensors, half_as_is=True)
        with self._serving_trace("decode", request.uid, context, tensors, deserialize_s) as span:
            self._admit(context, tensors, "decode")
            output = await self._run_decode(request.uid, request.metadata, tensors, span)
            return await self._respond_decode(output)

    async def rpc_replica_state(
        self, request: runtime_pb2.ExpertUID, context: P2PContext
    ) -> AsyncIterator[runtime_pb2.ExpertResponse]:
        """Expert replication transfer (ISSUE 13): stream this expert's
        construction spec + full ``state_dict`` blob to a peer acquiring a
        replica. First message carries msgpack metadata (spec, byte length,
        blake2b digest); the blob follows in 1 MiB chunks riding Tensor
        buffers. Backends without a ``replication_spec`` (e.g. checkpoint-
        loaded Llama blocks) refuse — they replicate by loading the same
        checkpoint, not over RPC."""
        import hashlib

        backend = self.backends.get(request.uid)
        if backend is None:
            raise KeyError(f"unknown expert {request.uid!r}")
        spec = getattr(backend, "replication_spec", None)
        if spec is None:
            raise ValueError(
                f"expert {request.uid!r} carries no replication spec; "
                f"replicate it from its source checkpoint instead"
            )
        blob = await run_in_executor(backend.state_dict)
        digest = hashlib.blake2b(blob, digest_size=16).hexdigest()
        yield runtime_pb2.ExpertResponse(
            metadata=MSGPackSerializer.dumps({
                "spec": dict(spec),
                "total_bytes": len(blob),
                "digest": digest,
            })
        )
        view = memoryview(blob)
        for offset in range(0, len(blob), _STREAM_CHUNK):
            chunk = bytes(view[offset:offset + _STREAM_CHUNK])
            _SERVER_BYTES_SENT.inc(len(chunk))
            yield runtime_pb2.ExpertResponse(
                tensors=[runtime_pb2.Tensor(buffer=chunk, dtype="uint8")]
            )

    # NOTE on the stream RPCs below: the serving span must not wrap a `yield`
    # (an async generator's body runs in its consumer's context), so it closes
    # before the first chunk leaves, and the response tensors serialize LAZILY,
    # one at a time — a multi-hundred-MB streamed response must never be
    # materialized whole. The FIRST tensor's turn is at once, so it is serialized
    # while the span is still open (`_serialize_head`): stream kinds carry the
    # `serialize_s` of that tensor (the whole response of a single-output expert),
    # and `deserialize_s` as the time `_collect_stream_with_metadata` spent past
    # waiting for the request's chunks.

    async def rpc_decode_stream(
        self, requests: AsyncIterator[runtime_pb2.ExpertRequest], context: P2PContext
    ) -> AsyncIterator[runtime_pb2.ExpertResponse]:
        """Streaming variant for prefill chunks over the unary payload cap."""
        with self._serving_trace("decode_stream", "?", context) as span:
            uid, metadata, tensors = await self._collect_stream_with_metadata(requests)
            if span is not None:
                span.set("expert", uid)
                if tensors and getattr(tensors[0], "ndim", 0):
                    span.set("batch", int(tensors[0].shape[0]))
            self._admit(context, tensors, "decode")
            output = await self._run_decode(uid, metadata, tensors, span)
            head = await self._serialize_head([output])
        async for message in self._stream_response(head, []):
            yield message

    async def rpc_forward_stream(
        self, requests: AsyncIterator[runtime_pb2.ExpertRequest], context: P2PContext
    ) -> AsyncIterator[runtime_pb2.ExpertResponse]:
        with self._serving_trace("forward_stream", "?", context) as span:
            uid, metadata, tensors = await self._collect_stream_with_metadata(requests)
            if span is not None:
                span.set("expert", uid)
                if tensors and getattr(tensors[0], "ndim", 0):
                    span.set("batch", int(tensors[0].shape[0]))
            self._admit(context, tensors, "forward")
            outputs = await self._run_span("forward", self._span_uids(uid, metadata), tensors)
            head = await self._serialize_head(outputs)
        async for message in self._stream_response(head, outputs[1:]):
            yield message

    async def rpc_backward_stream(
        self, requests: AsyncIterator[runtime_pb2.ExpertRequest], context: P2PContext
    ) -> AsyncIterator[runtime_pb2.ExpertResponse]:
        with self._serving_trace("backward_stream", "?", context) as span:
            uid, metadata, tensors = await self._collect_stream_with_metadata(requests)
            if span is not None:
                span.set("expert", uid)
                if tensors and getattr(tensors[0], "ndim", 0):
                    span.set("batch", int(tensors[0].shape[0]))
            self._admit(context, tensors, "backward")
            grads = await self._run_span("backward", self._span_uids(uid, metadata), tensors)
            head = await self._serialize_head(grads)
        async for message in self._stream_response(head, grads[1:]):
            yield message

    async def _collect_stream_with_metadata(self, requests: AsyncIterator[runtime_pb2.ExpertRequest]):
        """Collect a streamed request: uid + first message's metadata + tensors.
        Chunk reassembly/deserialization runs off-loop (one tensor at a time,
        as the chunks arrive); what it took past waiting for the chunks accrues
        onto the serving span as ``deserialize_s``."""
        uid = None
        metadata = b""
        started = time.perf_counter()
        waited = 0.0  # for the client's next chunk

        async def parts():
            nonlocal uid, metadata, waited
            asked = time.perf_counter()
            async for request in requests:
                waited += time.perf_counter() - asked
                _SERVER_BYTES_RECEIVED.inc(request.ByteSize())
                if uid is None and request.uid:
                    uid = request.uid
                if not metadata and request.metadata:
                    metadata = request.metadata
                yield list(request.tensors)
                asked = time.perf_counter()
            waited += time.perf_counter() - asked

        tensors = await deserialize_tensor_stream(parts(), off_loop=True)
        accrue_span_phase("deserialize_s", max(time.perf_counter() - started - waited, 0.0))
        if uid is None:
            # wire input from a remote peer: a proper error the client can read
            # (an assert would vanish under -O and crash as a bare AssertionError)
            raise ValueError("streamed expert request carried no expert uid")
        return uid, metadata, tensors

    async def _serialize_streamed(self, out: np.ndarray) -> runtime_pb2.Tensor:
        """One tensor of a streamed response in the server's wire dtype (off-loop
        past the inline threshold)."""
        nbytes = int(getattr(out, "nbytes", 0))
        if nbytes < _OFF_LOOP_CODEC_BYTES:
            return self._serialize_traced([out], nbytes)[0]
        return (await run_in_executor(self._serialize_traced, [out], nbytes))[0]

    async def _serialize_head(self, outputs: List[np.ndarray]) -> Optional[runtime_pb2.Tensor]:
        """The first tensor of a streamed response, serialized inside the serving
        span (see the NOTE above): its time is the span's ``serialize_s``."""
        if not outputs:
            return None
        start = time.perf_counter()
        head = await self._serialize_streamed(outputs[0])
        accrue_span_phase("serialize_s", time.perf_counter() - start)
        return head

    async def _stream_response(self, head: Optional[runtime_pb2.Tensor], rest: List[np.ndarray]):
        """Lazy streamed response: after the already-serialized ``head``, each
        tensor serializes only when its turn comes, and its chunks are zero-copy
        memoryview slices framed scatter-gather."""
        if head is not None:
            for chunk in split_response_for_wire(head, _STREAM_CHUNK):
                _SERVER_BYTES_SENT.inc(chunk.nbytes)
                yield chunk
        for out in rest:
            for chunk in split_response_for_wire(await self._serialize_streamed(out), _STREAM_CHUNK):
                _SERVER_BYTES_SENT.inc(chunk.nbytes)
                yield chunk
