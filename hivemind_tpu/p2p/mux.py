"""Message-oriented stream multiplexing over one secure channel per peer pair.

The reference gets multiplexing from go-libp2p (yamux/mplex inside the daemon) plus a
persistent control connection for unary calls (p2p_daemon_bindings/control.py:172-311).
Here both collapse into one mechanism: lightweight in-process streams over a single
encrypted TCP connection. Frames are whole messages (an RPC message = one frame), which
removes the reference's 8-byte-header + marker reframing layer entirely.

Mux frame layout (inside the AEAD envelope): [u64 stream_id][u8 flags][payload].
The flags are a bit mask, and one frame may carry several; the receiver handles them
in the order OPEN, DATA, ERROR, CLOSE/RESET:

    OPEN   the open section: handler name utf-8, optionally followed by NUL + a 16-byte
           trace context (handler names never contain NUL). The trace context
           (telemetry/tracing.py pack_context) is how a server-side handler span becomes
           a child of the remote caller's span; absent when the caller has no active
           span, ignored when malformed.
    DATA   one message. In a frame that also says OPEN the payload is
           [u16 length of the open section][open section][message].
    ERROR  msgpack error info (sent as ERROR|CLOSE: an error is a side's last word).
    CLOSE  graceful end-of-stream from that side, after the frame's message if any.
    RESET  abort: the receiver stops sending and cancels the stream's handler mid-compute.

A frame is what the event loop pays a fixed price for (one AEAD call, one write to the
socket, one turn of the peer's read loop), so a unary call is TWO frames: the request
as OPEN|DATA|CLOSE, the answer as DATA|CLOSE (ERROR|CLOSE from a failed handler). A
stream whose caller knows its single request sends the same first frame; a request
iterator sends OPEN, then DATA..., then CLOSE, and a streaming handler DATA... then
CLOSE; every such frame alone stays valid input, there is no version to negotiate.

The end of a stream: once BOTH sides have half-closed (each has sent CLOSE and seen the
other's), the stream is complete and each peer forgets it on the spot — where it sees
the second half-close or sends it — with no further frame: its entry in ``_streams``,
its undrained credit, its handler-task entry. RESET is for a stream that is NOT
complete (a timeout, a cancellation, a hedge's losing request, a failed request
iterator); ``MuxStream.reset()`` on a complete stream sends nothing.
Flow control: per-stream inboxes are unbounded (the read loop never head-of-line-blocks
one stream on another), with a per-connection buffered-bytes cap as the memory backstop
— a peer that overruns it loses the connection, not the process. TCP backpressure plus
eager reads in the RPC layer keep buffers small in practice.
"""

from __future__ import annotations

import asyncio
import struct
import time
from enum import IntFlag
from typing import AsyncIterator, Awaitable, Callable, Dict, Optional, Union

# what receive() yields: bytes for locally-generated items, a zero-copy memoryview
# of the decrypted wire frame for DATA payloads
Message = Union[bytes, memoryview]

from hivemind_tpu.p2p.crypto_channel import SecureChannel
from hivemind_tpu.telemetry.tracing import unpack_context
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.asyncio_utils import spawn
from hivemind_tpu.utils.serializer import MSGPackSerializer

logger = get_logger(__name__)

_HEADER = struct.Struct(">QB")
_OPEN_LENGTH = struct.Struct(">H")  # in an OPEN|DATA frame: the open section's length

# one RPC message per frame; larger payloads must be chunked by the caller
# (parity: reference DEFAULT_MAX_MSG_SIZE, p2p_daemon_bindings/control.py:36-39)
MAX_MESSAGE_SIZE = 4 * 1024 * 1024


class Flags(IntFlag):
    OPEN = 1
    DATA = 2
    CLOSE = 4
    RESET = 8
    ERROR = 16


# the read loop tests a frame's bits as plain ints (an IntFlag's `&` builds an enum member: 0.75 us
# each, five a frame), and the two composites a unary call sends are built once
_OPEN, _DATA, _CLOSE, _RESET, _ERROR = (int(flag) for flag in (Flags.OPEN, Flags.DATA, Flags.CLOSE, Flags.RESET, Flags.ERROR))
_DATA_CLOSE, _ERROR_CLOSE, _OPEN_DATA_CLOSE = Flags.DATA | Flags.CLOSE, Flags.ERROR | Flags.CLOSE, Flags.OPEN | Flags.DATA | Flags.CLOSE


class StreamClosedError(ConnectionError):
    """The stream (or its connection) closed before the operation completed."""


class RemoteError(RuntimeError):
    """The remote handler raised an exception; carries its type name and message."""

    def __init__(self, type_name: str, message: str):
        super().__init__(f"{type_name}: {message}")
        self.type_name = type_name
        self.remote_message = message


_EOF = object()


def _check_message_size(total: int) -> None:
    if total > MAX_MESSAGE_SIZE:
        raise ValueError(
            f"message of {total} bytes exceeds MAX_MESSAGE_SIZE={MAX_MESSAGE_SIZE}; "
            f"split large tensors with utils.streaming.split_for_streaming"
        )


class MuxStream:
    """One bidirectional message stream. ``send``/``receive`` whole byte messages.

    Inboxes are unbounded so the connection read loop never head-of-line-blocks on a
    slow consumer; memory is bounded per connection (``MuxConnection.max_buffered_bytes``)
    — exceeding it kills the whole connection rather than stalling unrelated streams.
    """

    def __init__(self, conn: "MuxConnection", stream_id: int, handler_name: str):
        self._conn = conn
        self.stream_id = stream_id
        self.handler_name = handler_name
        self.trace_context = None  # (trace_id, span_id) from the remote OPEN, if any
        self._inbox: asyncio.Queue = asyncio.Queue()
        self._recv_closed = False
        self._send_closed = False  # this side has sent CLOSE (or the stream is reset)
        self._remote_closed = False  # the peer's CLOSE has been seen
        self._reset = False
        self._inbox_bytes = 0  # bytes currently debited against the connection cap

    @property
    def peer_id(self):
        return self._conn.peer_id

    async def send(self, message: bytes, *extra: bytes, close: bool = False) -> None:
        """Send one message; ``extra`` buffers travel scatter-gather with it as a
        single frame (a spliced protobuf's tensor buffers ride uncopied into the
        AEAD — the serving-path analog of the averaging framing). ``close``: this is
        the side's last message, and the half-close rides the same frame."""
        if self._send_closed or self._reset:
            raise StreamClosedError(f"stream {self.stream_id} is closed for sending")
        _check_message_size(len(message) + sum(len(part) for part in extra))
        if close:
            await self._send_last(_DATA_CLOSE, message, *extra)
        else:
            await self._conn.send_frame(self.stream_id, Flags.DATA, message, *extra)

    async def _send_last(self, flags: Flags, *payload: bytes) -> None:
        """This side's last frame, whatever else it carries: the half-close is sent."""
        self._send_closed = True
        try:
            await self._conn.send_frame(self.stream_id, flags, *payload)
        finally:
            self._half_closed()

    async def send_error(self, exc: BaseException) -> None:
        """A failed handler's last word: the error and the half-close in one frame."""
        if self._send_closed or self._reset:
            return
        payload = MSGPackSerializer.dumps({"type": type(exc).__name__, "message": str(exc)})
        await self._send_last(_ERROR_CLOSE, payload)

    async def close_send(self) -> None:
        """Half-close: no more messages from this side."""
        if not self._send_closed and not self._reset:
            try:
                await self._send_last(Flags.CLOSE, b"")
            except (ConnectionError, StreamClosedError):
                pass

    @property
    def is_complete(self) -> bool:
        """Both sides have half-closed: nothing more can cross, in either direction."""
        return self._send_closed and self._remote_closed and not self._reset

    def _half_closed(self) -> None:
        """One side's CLOSE has just been sent or seen: if it was the second, the stream
        ends here, on this peer, with no frame (module docstring)."""
        if self.is_complete:
            self._conn._forget_stream(self.stream_id)

    async def reset(self) -> None:
        """Abort a stream that is not complete: the peer stops sending and cancels its
        handler. On a complete stream there is nobody left to tell."""
        if not self._reset:
            complete = self.is_complete
            self._reset = True
            self._send_closed = True
            if not complete:
                try:
                    await self._conn.send_frame(self.stream_id, Flags.RESET, b"")
                except (ConnectionError, StreamClosedError):
                    pass
            self._push_eof()
            self._conn._forget_stream(self.stream_id)

    async def receive(self) -> Message:
        """Next message (bytes-like: may be a zero-copy memoryview of the wire
        frame); raises StreamClosedError at end-of-stream, RemoteError if the peer's
        handler failed."""
        if self._recv_closed:
            raise StreamClosedError(f"stream {self.stream_id}: receive side closed")
        item = await self._inbox.get()
        if isinstance(item, (bytes, bytearray, memoryview)) and self._inbox_bytes > 0:
            self._inbox_bytes -= len(item)
            self._conn._credit_bytes(len(item))
        if item is _EOF:
            self._recv_closed = True
            raise StreamClosedError(f"stream {self.stream_id} ended")
        if isinstance(item, RemoteError):
            self._recv_closed = True
            raise item
        return item

    async def __aiter__(self) -> AsyncIterator[Message]:
        while True:
            try:
                yield await self.receive()
            except StreamClosedError:
                return

    def iter_messages(self) -> AsyncIterator[Message]:
        return self.__aiter__()

    def _push(self, item) -> None:
        if isinstance(item, (bytes, bytearray, memoryview)):
            self._inbox_bytes += len(item)
        self._inbox.put_nowait(item)  # unbounded: never blocks the read loop

    def _push_eof(self) -> None:
        self._inbox.put_nowait(_EOF)

    def _return_credit(self) -> None:
        """Credit back all undrained inbox bytes (stream reset/forgotten)."""
        if self._inbox_bytes > 0:
            self._conn._credit_bytes(self._inbox_bytes)
            self._inbox_bytes = 0


class MuxConnection:
    """All streams between this node and one peer, over one SecureChannel."""

    def __init__(
        self,
        channel: SecureChannel,
        peer_id,
        is_initiator: bool,
        on_inbound_stream: Callable[[MuxStream], Awaitable[None]],
        max_buffered_bytes: int = 256 * 1024 * 1024,
    ):
        self._channel = channel
        self.peer_id = peer_id
        self._next_stream_id = 1 if is_initiator else 2
        self._streams: Dict[int, MuxStream] = {}
        self._on_inbound_stream = on_inbound_stream
        self._closed = False
        self._read_task: Optional[asyncio.Task] = None
        self._handler_tasks: set = set()
        # stream_id -> running inbound handler task: a peer's RESET cancels the
        # handler MID-COMPUTE (ISSUE 13 hedged requests: the losing server must
        # stop working on an answer nobody will read, not just fail its send)
        self._stream_handler_tasks: Dict[int, asyncio.Task] = {}
        self._buffered_bytes = 0
        self._max_buffered_bytes = max_buffered_bytes
        self.last_used = time.monotonic()  # LRU key for the connection manager

    def _credit_bytes(self, nbytes: int) -> None:
        self._buffered_bytes -= nbytes

    def start(self) -> None:
        self._read_task = spawn(self._read_loop(), name="mux.read_loop")

    @property
    def is_closed(self) -> bool:
        return self._closed

    async def open_stream(
        self, handler_name: str, trace_context: Optional[bytes] = None, request: Optional[tuple] = None
    ) -> MuxStream:
        """Open a stream to the peer's handler. ``request``: the buffers of the caller's
        ONE message — open section, message and half-close then leave as one frame,
        OPEN|DATA|CLOSE, and the stream comes back closed for sending."""
        if self._closed:
            raise StreamClosedError(f"connection to {self.peer_id} is closed")
        opening = [handler_name.encode("utf-8")]
        if trace_context is not None:
            opening += [b"\x00", trace_context]
        flags = Flags.OPEN
        if request is not None:
            _check_message_size(sum(len(part) for part in request))  # before an id is taken
            flags = _OPEN_DATA_CLOSE
            opening = [_OPEN_LENGTH.pack(sum(len(part) for part in opening)), *opening, *request]
        stream_id = self._next_stream_id
        self._next_stream_id += 2
        stream = MuxStream(self, stream_id, handler_name)
        stream._send_closed = request is not None
        self._streams[stream_id] = stream
        await self.send_frame(stream_id, flags, *opening)
        return stream

    @property
    def num_streams(self) -> int:
        return len(self._streams)

    async def send_frame(self, stream_id: int, flags: Flags, *payload: bytes) -> None:
        """Send one frame; the payload may arrive as several buffers which travel
        scatter-gather all the way into the AEAD (no header+payload concat here)."""
        if self._closed:
            raise StreamClosedError(f"connection to {self.peer_id} is closed")
        self.last_used = time.monotonic()
        try:
            await self._channel.send(_HEADER.pack(stream_id, int(flags)), *payload)
        except (ConnectionError, OSError, asyncio.IncompleteReadError) as e:
            await self._shutdown(e)
            raise StreamClosedError(f"connection to {self.peer_id} lost: {e}") from e

    async def _read_loop(self) -> None:
        error: Optional[BaseException] = None
        try:
            while True:
                frame = await self._channel.recv()
                stream_id, flags = _HEADER.unpack_from(frame)
                # zero-copy: DATA payloads ride to their consumer as a view of the
                # decrypted frame instead of re-materializing frame[9:] per message
                payload = memoryview(frame)[_HEADER.size :]
                await self._dispatch(stream_id, flags, payload)
        except (ConnectionError, OSError, asyncio.IncompleteReadError, EOFError) as e:
            error = e
        except asyncio.CancelledError:
            raise
        except Exception as e:
            logger.warning(f"connection to {self.peer_id}: read loop failed with {e!r}")
            error = e
        finally:
            await self._shutdown(error)

    async def _dispatch(self, stream_id: int, flags: int, payload) -> None:
        # ``payload`` is a memoryview into the decrypted frame; the open section and an
        # error materialize it, a message passes the view through. One frame may carry
        # several flags: OPEN, then DATA, then ERROR, then CLOSE / RESET
        self.last_used = time.monotonic()
        flags = int(flags)
        if flags & _OPEN:
            # a remote OPEN must use the REMOTE side's id parity and a fresh id: a
            # misbehaving peer reusing a local-parity or existing id would silently
            # replace a live stream in _streams, misrouting its responses and
            # orphaning its credit accounting. Refused before any payload it carries
            # is delivered to anybody
            if stream_id % 2 == self._next_stream_id % 2 or stream_id in self._streams:
                logger.warning(
                    f"connection to {self.peer_id}: rejecting OPEN with "
                    f"{'local-parity' if stream_id % 2 == self._next_stream_id % 2 else 'duplicate'} "
                    f"stream id {stream_id}"
                )
                await self.send_frame(stream_id, Flags.RESET, b"")
                return
            opening = payload
            if flags & _DATA:  # [u16 length][open section][message]
                if len(payload) < _OPEN_LENGTH.size:
                    raise ConnectionError("malformed OPEN|DATA frame: no length of the open section")
                message_at = _OPEN_LENGTH.size + _OPEN_LENGTH.unpack_from(payload)[0]
                if message_at > len(payload):
                    raise ConnectionError("malformed OPEN|DATA frame: the open section overruns it")
                opening, payload = payload[_OPEN_LENGTH.size : message_at], payload[message_at:]
            name_bytes, _nul, trace_raw = bytes(opening).partition(b"\x00")
            stream = MuxStream(self, stream_id, name_bytes.decode("utf-8", errors="replace"))
            if trace_raw:
                stream.trace_context = unpack_context(trace_raw)
            self._streams[stream_id] = stream
        else:
            stream = self._streams.get(stream_id)
            if stream is None:
                return  # already complete, reset or forgotten
        if flags & _DATA:
            self._buffered_bytes += len(payload)
            if self._buffered_bytes > self._max_buffered_bytes:
                logger.warning(
                    f"connection to {self.peer_id}: buffered {self._buffered_bytes} bytes "
                    f"exceeds cap; closing connection"
                )
                raise ConnectionError("per-connection buffer cap exceeded")
            stream._push(payload)
        if flags & _ERROR:
            try:
                info = MSGPackSerializer.loads(bytes(payload))
                stream._push(RemoteError(info.get("type", "RemoteError"), info.get("message", "")))
            except Exception:
                stream._push(RemoteError("RemoteError", "malformed error payload"))
        if flags & _RESET:
            stream._push_eof()
            # peer aborted: local side must stop sending immediately
            stream._reset = True
            stream._send_closed = True
            # ...and stop COMPUTING: a still-running inbound handler for
            # this stream is work nobody will read (a hedge's losing
            # request, an abandoned call). A handler that already finished
            # is no longer in the map — its completed response stands.
            handler_task = self._stream_handler_tasks.pop(stream_id, None)
            self._forget_stream(stream_id)
            if handler_task is not None and not handler_task.done():
                handler_task.cancel()
            return
        if flags & _CLOSE:
            stream._push_eof()
            stream._remote_closed = True
            stream._half_closed()  # the second half-close ends the stream here
        if flags & _OPEN:
            # the handler starts with its inbox already holding what the frame carried
            task = spawn(self._on_inbound_stream(stream), name="mux.inbound_stream")
            self._handler_tasks.add(task)
            self._stream_handler_tasks[stream_id] = task

            def _forget_handler(finished, *, stream_id=stream_id):
                self._handler_tasks.discard(finished)
                if self._stream_handler_tasks.get(stream_id) is finished:
                    self._stream_handler_tasks.pop(stream_id, None)

            task.add_done_callback(_forget_handler)

    def _forget_stream(self, stream_id: int) -> None:
        """The stream is complete or reset: its table entry, its undrained credit and its
        handler's entry go (what its inbox still holds stays readable, and the handler of
        a complete stream runs to its end)."""
        stream = self._streams.pop(stream_id, None)
        if stream is not None:
            stream._return_credit()
            self._stream_handler_tasks.pop(stream_id, None)

    async def _shutdown(self, error: Optional[BaseException]) -> None:
        if self._closed:
            return
        self._closed = True
        for stream in list(self._streams.values()):
            stream._push_eof()  # guaranteed: queue is unbounded
            stream._return_credit()
        self._streams.clear()
        self._channel.close()

    async def close(self) -> None:
        if self._read_task is not None:
            self._read_task.cancel()
            try:
                await self._read_task
            except (asyncio.CancelledError, Exception):
                pass
        await self._shutdown(None)
        # cancel AND await: a handler parked in an await only ends on a later loop
        # iteration, and would otherwise outlive the connection (and P2P.shutdown).
        # A handler may close its own connection: it cannot await itself.
        handlers = list(self._handler_tasks)
        for task in handlers:
            task.cancel()
        me = asyncio.current_task()
        await asyncio.gather(*(t for t in handlers if t is not me), return_exceptions=True)
        await self._channel.wait_closed()
