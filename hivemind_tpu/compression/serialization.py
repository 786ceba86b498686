"""Serialization facade: one codec instance per CompressionType enum value
(capability parity: reference hivemind/compression/serialization.py:13-68), plus
the serving-path wire splicers (ISSUE 10): hand-encoded ``ExpertRequest`` /
``ExpertResponse`` frames whose multi-MB tensor buffers ride as separate
scatter-gather buffers (:class:`~hivemind_tpu.utils.streaming.WireParts`)
instead of being copied into one ``SerializeToString`` blob. The encodings are
byte-identical to protobuf's own (asserted in tests/test_serving_compression.py),
so the receive side parses them with the stock generated classes."""

from __future__ import annotations

from typing import Any, AsyncIterator, List, Optional, Sequence

import numpy as np

from hivemind_tpu.compression.base import (
    CompressionBase,
    CompressionInfo,
    CompressionType,
    NoCompression,
)
from hivemind_tpu.compression.floating import Float16Compression, ScaledFloat16Compression
from hivemind_tpu.compression.quantization import (
    BlockwiseQuantization,
    Quantile8BitQuantization,
    Uniform8BitQuantization,
)
from hivemind_tpu.proto import runtime_pb2
from hivemind_tpu.utils.streaming import WireParts

_CODECS = {
    CompressionType.NONE: NoCompression(),
    CompressionType.FLOAT16: Float16Compression(),
    CompressionType.MEANSTD_16BIT: ScaledFloat16Compression(),
    CompressionType.UNIFORM_8BIT: Uniform8BitQuantization(),
    CompressionType.QUANTILE_8BIT: Quantile8BitQuantization(),
    CompressionType.BLOCKWISE_8BIT: BlockwiseQuantization(),
}

for _value in runtime_pb2.CompressionType.values():
    assert _value in _CODECS, f"no codec registered for CompressionType={_value}"


def get_codec(compression_type: int) -> CompressionBase:
    return _CODECS[compression_type]


def resolve_activation_codec(name: Optional[str]) -> CompressionBase:
    """The serving wire dtype by knob value ("none", "float16", "meanstd_16bit",
    … — any CompressionType name, case-insensitive; None/"" = NONE)."""
    if not name:
        return _CODECS[CompressionType.NONE]
    try:
        # Value() rejects anything that is not an enum member — a plain getattr
        # would let remote-supplied names hit real enum-wrapper attributes and
        # escape as KeyError past callers' ValueError guards
        value = runtime_pb2.CompressionType.Value(str(name).upper())
    except ValueError:
        valid = ", ".join(k.lower() for k in runtime_pb2.CompressionType.keys())
        raise ValueError(f"unknown activation compression {name!r}; expected one of: {valid}") from None
    return _CODECS[value]


def codec_name(codec: CompressionBase) -> str:
    """Canonical lowercase knob value for a codec ("float16", "none", …)."""
    return runtime_pb2.CompressionType.Name(codec.compression_type).lower()


def serialize_tensor(
    array: Any,
    compression: CompressionBase | int = CompressionType.NONE,
    info: Optional[CompressionInfo] = None,
    allow_inplace: bool = False,
) -> runtime_pb2.Tensor:
    if isinstance(compression, int):
        compression = _CODECS[compression]
    return compression.compress(array, info, allow_inplace)


def deserialize_tensor(serialized: runtime_pb2.Tensor) -> np.ndarray:
    return _CODECS[serialized.compression].extract(serialized)


def deserialize_to_jax(serialized: runtime_pb2.Tensor):
    import jax.numpy as jnp

    return jnp.asarray(deserialize_tensor(serialized))


def _clone_tensor_metadata(source: runtime_pb2.Tensor) -> runtime_pb2.Tensor:
    """A Tensor message carrying every field of ``source`` EXCEPT its (possibly
    multi-MiB) payload — chunking helpers must never duplicate the buffer just to
    replace it (ISSUE 6 satellite: the old CopyFrom+overwrite did exactly that)."""
    return runtime_pb2.Tensor(
        size=source.size,
        dtype=source.dtype,
        requires_grad=source.requires_grad,
        compression=source.compression,
    )


async def deserialize_tensor_stream(
    stream: AsyncIterator[List[runtime_pb2.Tensor]], off_loop: bool = False
) -> List[np.ndarray]:
    """Reassemble tensors from a stream of chunked parts: each tensor arrives as its
    first message (with ``chunks`` = total count) followed by buffer-only continuation
    messages (reference serialization.py deserialize_tensor_stream).

    ``off_loop=True`` runs each completed tensor's join+decode in the shared
    executor — server handlers use it so a multi-MB prefill chunk cannot stall
    the event loop (ISSUE 10); chunks still decode one tensor at a time, as
    they complete."""
    from hivemind_tpu.telemetry.wire import wire_work
    from hivemind_tpu.utils.asyncio_utils import run_in_executor

    def _combine(chunk_parts: List[runtime_pb2.Tensor]) -> np.ndarray:
        # the join of the chunks is part of the decode: it copies every byte once more
        with wire_work("decode", sum(len(p.buffer) for p in chunk_parts)):
            combined = _clone_tensor_metadata(chunk_parts[0])
            combined.buffer = b"".join(p.buffer for p in chunk_parts)
            return deserialize_tensor(combined)

    tensors: List[np.ndarray] = []
    parts: List[runtime_pb2.Tensor] = []
    async for chunk_batch in stream:
        for chunk in chunk_batch:
            parts.append(chunk)
            total = parts[0].chunks or 1
            if len(parts) == total:
                tensors.append(await run_in_executor(_combine, parts) if off_loop else _combine(parts))
                parts = []
    if parts:
        raise ValueError(f"stream ended mid-tensor: got {len(parts)}/{parts[0].chunks} chunks")
    return tensors


def split_tensor_for_streaming(serialized: runtime_pb2.Tensor, chunk_size_bytes: int) -> List[runtime_pb2.Tensor]:
    """Split one serialized tensor into wire-sized chunk messages (the inverse of
    deserialize_tensor_stream's reassembly)."""
    from hivemind_tpu.utils.streaming import split_for_streaming

    buffers = list(split_for_streaming(serialized.buffer, chunk_size_bytes))
    first = _clone_tensor_metadata(serialized)
    first.buffer = buffers[0]
    first.chunks = len(buffers)
    out = [first]
    for extra in buffers[1:]:
        out.append(runtime_pb2.Tensor(buffer=extra))
    return out


# ------------------------------------------------------------------ wire splicers
#
# Hand-rolled protobuf framing for the serving hot path: concatenating encoded
# fields in field-number order is exactly what SerializeToString emits, so a
# Tensor can be framed as [buffer-field header][the buffer object itself]
# [metadata fields] with the (possibly multi-MB) buffer riding as ITS OWN
# scatter-gather part — never copied into a materialized message. Field
# numbers/tags below mirror proto/runtime.proto; byte-identity with protobuf's
# own encoder is pinned by tests.

_TENSOR_BUFFER_TAG = b"\x0a"  # Tensor.buffer = 1, wire type 2
_REQUEST_UID_TAG = b"\x0a"  # ExpertRequest.uid = 1
_REQUEST_TENSOR_TAG = b"\x12"  # ExpertRequest.tensors = 2
_REQUEST_METADATA_TAG = b"\x1a"  # ExpertRequest.metadata = 3
_RESPONSE_TENSOR_TAG = b"\x0a"  # ExpertResponse.tensors = 1
_RESPONSE_METADATA_TAG = b"\x12"  # ExpertResponse.metadata = 2


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        bits = value & 0x7F
        value >>= 7
        if value:
            out.append(bits | 0x80)
        else:
            out.append(bits)
            return bytes(out)


def _tensor_field_parts(serialized: runtime_pb2.Tensor, field_tag: bytes) -> List[bytes]:
    """Encode one Tensor as a length-delimited field of an outer message,
    splicing ``serialized.buffer`` in as a separate part (zero-copy)."""
    buffer = serialized.buffer
    meta = _clone_tensor_metadata(serialized)
    meta.chunks = serialized.chunks
    meta_bytes = meta.SerializeToString()
    if buffer:
        # protobuf emits fields in number order: buffer (field 1) precedes the
        # metadata fields (2..6), keeping the frame byte-identical to protobuf's
        inner = [_TENSOR_BUFFER_TAG + _varint(len(buffer)), buffer, meta_bytes]
    else:
        inner = [meta_bytes]
    inner_len = sum(len(part) for part in inner)
    return [field_tag + _varint(inner_len), *inner]


def expert_request_parts(
    uid: str, tensors: Sequence[runtime_pb2.Tensor], metadata: bytes = b""
) -> WireParts:
    """``ExpertRequest(uid=, tensors=, metadata=)`` as scatter-gather parts."""
    parts: List[Any] = []
    if uid:
        uid_bytes = uid.encode("utf-8")
        parts.append(_REQUEST_UID_TAG + _varint(len(uid_bytes)) + uid_bytes)
    for tensor in tensors:
        parts.extend(_tensor_field_parts(tensor, _REQUEST_TENSOR_TAG))
    if metadata:
        parts.append(_REQUEST_METADATA_TAG + _varint(len(metadata)) + metadata)
    return WireParts(*parts)


def expert_response_parts(
    tensors: Sequence[runtime_pb2.Tensor], metadata: bytes = b""
) -> WireParts:
    """``ExpertResponse(tensors=, metadata=)`` as scatter-gather parts."""
    parts: List[Any] = []
    for tensor in tensors:
        parts.extend(_tensor_field_parts(tensor, _RESPONSE_TENSOR_TAG))
    if metadata:
        parts.append(_RESPONSE_METADATA_TAG + _varint(len(metadata)) + metadata)
    return WireParts(*parts)


def split_response_for_wire(
    serialized: runtime_pb2.Tensor, chunk_size_bytes: int
) -> List[WireParts]:
    """One serialized tensor as a list of ``ExpertResponse`` stream-chunk frames
    (the wire-parts analog of ``split_tensor_for_streaming``): the buffer is
    sliced as zero-copy memoryviews, so a multi-hundred-MB streamed response is
    never re-materialized chunk by chunk."""
    view = memoryview(serialized.buffer)
    total_chunks = max(1, -(-len(view) // chunk_size_bytes)) if len(view) else 1
    first = _clone_tensor_metadata(serialized)
    first.chunks = total_chunks
    meta_bytes = first.SerializeToString()
    out: List[WireParts] = []
    for index in range(total_chunks):
        chunk = view[index * chunk_size_bytes : (index + 1) * chunk_size_bytes]
        inner: List[Any] = []
        if len(chunk):
            inner.extend([_TENSOR_BUFFER_TAG + _varint(len(chunk)), chunk])
        if index == 0:
            inner.append(meta_bytes)
        inner_len = sum(len(part) for part in inner)
        out.append(WireParts(_RESPONSE_TENSOR_TAG + _varint(inner_len), *inner))
    return out
