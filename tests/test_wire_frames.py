"""The price of a frame (ISSUE 38): a unary call crosses the wire in two frames, a stream
that both sides have half-closed ends by itself on both peers, RESET is left for what is
abandoned, the three-frame form stays valid input, and no frame asks the operating system
for the CPU count."""

import asyncio
import os
from typing import AsyncIterator, List, Tuple

import pytest

from hivemind_tpu.p2p import P2P, P2PContext, P2PHandlerError
from hivemind_tpu.p2p import crypto_channel
from hivemind_tpu.p2p.mux import _OPEN_LENGTH, Flags, MuxConnection, RemoteError, StreamClosedError
from hivemind_tpu.proto import test_pb2
from hivemind_tpu.telemetry import REGISTRY


class Wire:
    """A server and a client in this process, with every frame either of them sends on record."""

    def __init__(self):
        self.sent: List[Tuple[str, Flags]] = []  # ("client" | "server", flags), in sending order
        self.handler_started = asyncio.Event()
        self.handler_cancelled = asyncio.Event()

    async def __aenter__(self) -> "Wire":
        self.server, self.client = await P2P.create(), await P2P.create()

        async def square(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
            if request.number < 0:
                raise ValueError("negative")
            return test_pb2.TestResponse(number=request.number ** 2)

        async def slow(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
            self.handler_started.set()
            try:
                await asyncio.sleep(30)
            except asyncio.CancelledError:
                self.handler_cancelled.set()
                raise
            return test_pb2.TestResponse(number=0)

        async def count_up(request: test_pb2.TestRequest, context: P2PContext) -> AsyncIterator[test_pb2.TestResponse]:
            for number in range(request.number):
                yield test_pb2.TestResponse(number=number)

        async def total(requests: AsyncIterator[test_pb2.TestRequest], context: P2PContext) -> test_pb2.TestResponse:
            return test_pb2.TestResponse(number=sum([request.number async for request in requests]))

        await self.server.add_protobuf_handler("square", square, test_pb2.TestRequest)
        await self.server.add_protobuf_handler("slow", slow, test_pb2.TestRequest)
        await self.server.add_protobuf_handler("count_up", count_up, test_pb2.TestRequest, stream_output=True)
        await self.server.add_protobuf_handler("total", total, test_pb2.TestRequest, stream_input=True)
        await self.client.connect(self.server.get_visible_maddrs()[0])
        self.client_conn = self.client._connections[self.server.peer_id]
        for _ in range(100):  # the server registers the connection once its side of the handshake is through
            if self.client.peer_id in self.server._connections:
                break
            await asyncio.sleep(0.01)
        self.server_conn = self.server._connections[self.client.peer_id]
        for side, conn in (("client", self.client_conn), ("server", self.server_conn)):
            self._record(side, conn)
        return self

    def _record(self, side: str, conn: MuxConnection) -> None:
        send_frame = conn.send_frame

        async def recorded(stream_id, flags, *payload):
            self.sent.append((side, Flags(flags)))
            await send_frame(stream_id, flags, *payload)

        conn.send_frame = recorded

    async def __aexit__(self, *exc) -> None:
        await self.client.shutdown()
        await self.server.shutdown()

    async def call(self, name: str, number: int):
        return await self.client.call_protobuf_handler(
            self.server.peer_id, name, test_pb2.TestRequest(number=number), test_pb2.TestResponse
        )

    async def iterate(self, name: str, requests) -> List[int]:
        responses = self.client.iterate_protobuf_handler(self.server.peer_id, name, requests, test_pb2.TestResponse)
        return [response.number async for response in responses]

    async def settle(self) -> None:
        """Let the last frame sent reach the other side's read loop."""
        for _ in range(200):
            if not (self.client_conn.num_streams or self.server_conn.num_streams or self.server_conn._stream_handler_tasks):
                return
            await asyncio.sleep(0.005)


async def _numbers(*numbers: int):
    for number in numbers:
        yield test_pb2.TestRequest(number=number)


REQUEST, ANSWER, FAILURE = Flags.OPEN | Flags.DATA | Flags.CLOSE, Flags.DATA | Flags.CLOSE, Flags.ERROR | Flags.CLOSE


@pytest.mark.parametrize("number, answer", [(7, ANSWER), (-1, FAILURE)])
async def test_a_unary_call_puts_one_frame_each_way_on_the_channel(number, answer):
    async with Wire() as wire:
        if answer is ANSWER:
            assert (await wire.call("square", number)).number == number ** 2
        else:
            with pytest.raises(P2PHandlerError, match="ValueError: negative") as failed:
                await wire.call("square", number)
            assert isinstance(failed.value.__cause__, RemoteError) and failed.value.__cause__.type_name == "ValueError"
        await wire.settle()
        assert wire.sent == [("client", REQUEST), ("server", answer)]


async def test_the_frames_counter_counts_one_seal_and_one_open_a_frame():
    async with Wire() as wire:
        await wire.call("square", 1)  # the channels are warm: no handshake frame is left in flight
        await wire.settle()
        frames = lambda phase: REGISTRY.snapshot()["hivemind_wire_frames_total"]["series"][f"phase={phase}"]  # noqa: E731
        sealed, opened = frames("seal"), frames("open")
        for number in range(10):
            await wire.call("square", number)
        await wire.settle()
        # both peers count into this process's registry: two frames a call, each sealed once and opened once
        assert frames("seal") - sealed == 20 and frames("open") - opened == 20


CALLS = {
    "unary": lambda wire: wire.call("square", 3),
    "unary that fails": lambda wire: _expect_failure(wire.call("square", -3)),
    "stream out, one request": lambda wire: wire.iterate("count_up", test_pb2.TestRequest(number=3)),
    "stream in, request iterator": lambda wire: wire.iterate("total", _numbers(1, 2, 3)),
    "unknown handler": lambda wire: _expect_failure(wire.call("nobody", 1)),
}


async def _expect_failure(call) -> None:
    with pytest.raises(P2PHandlerError):
        await call


@pytest.mark.parametrize("calls", [1, 25])
@pytest.mark.parametrize("kind", list(CALLS))
async def test_finished_calls_leave_no_stream_behind_and_send_no_reset(kind, calls):
    async with Wire() as wire:
        for _ in range(calls):
            await CALLS[kind](wire)
        await wire.settle()
        assert wire.client_conn.num_streams == 0 and wire.server_conn.num_streams == 0
        assert not wire.server_conn._stream_handler_tasks and wire.server_conn._buffered_bytes == 0
        assert wire.client_conn._buffered_bytes == 0
        assert not any(flags & Flags.RESET for _side, flags in wire.sent), wire.sent
        if kind == "stream out, one request":  # the request as a unary call's; the handler's CLOSE after its last message
            assert wire.sent[:5] == [("client", REQUEST)] + [("server", Flags.DATA)] * 3 + [("server", Flags.CLOSE)]
        if kind == "stream in, request iterator":  # a request iterator keeps OPEN, DATA..., CLOSE
            assert wire.sent[:6] == [("client", Flags.OPEN)] + [("client", Flags.DATA)] * 3 + [("client", Flags.CLOSE), ("server", ANSWER)]


@pytest.mark.parametrize("how", ["timeout", "cancel", "stream abandoned"])
async def test_an_abandoned_call_still_sends_reset_and_the_remote_handler_stops_mid_compute(how):
    async with Wire() as wire:
        if how == "timeout":
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(wire.call("slow", 1), 0.3)
        elif how == "cancel":
            call = asyncio.ensure_future(wire.call("slow", 1))
            await asyncio.wait_for(wire.handler_started.wait(), 10)
            call.cancel()
            with pytest.raises(asyncio.CancelledError):
                await call
        else:
            responses = wire.client.iterate_protobuf_handler(
                wire.server.peer_id, "slow", test_pb2.TestRequest(number=1), test_pb2.TestResponse
            )
            first = asyncio.ensure_future(responses.__anext__())
            await asyncio.wait_for(wire.handler_started.wait(), 10)
            first.cancel()
            with pytest.raises(asyncio.CancelledError):
                await first
            await responses.aclose()
        await asyncio.wait_for(wire.handler_cancelled.wait(), 10)  # the server STOPPED computing
        await wire.settle()
        assert wire.sent == [("client", REQUEST), ("client", Flags.RESET)]
        assert wire.client_conn.num_streams == 0 and wire.server_conn.num_streams == 0


@pytest.mark.parametrize("then_reset", [False, True], ids=["as the protocol is now", "a peer that still sends RESET"])
async def test_open_data_close_as_three_frames_are_still_served(then_reset):
    async with Wire() as wire:
        stream = await wire.client_conn.open_stream("square")
        await stream.send(test_pb2.TestRequest(number=9).SerializeToString())
        await stream.close_send()
        response = test_pb2.TestResponse()
        response.ParseFromString(bytes(await stream.receive()))
        assert response.number == 81
        with pytest.raises(StreamClosedError):
            await stream.receive()
        if then_reset:  # what a peer of the older protocol sends last: a frame for a stream the server has forgotten
            await wire.client_conn.send_frame(stream.stream_id, Flags.RESET, b"")
        await wire.settle()
        assert [flags for side, flags in wire.sent if side == "client"][:3] == [Flags.OPEN, Flags.DATA, Flags.CLOSE]
        assert ("server", ANSWER) in wire.sent
        assert wire.client_conn.num_streams == 0 and wire.server_conn.num_streams == 0
        assert (await wire.call("square", 2)).number == 4  # the connection took no harm


def _request_payload(name: bytes, number: int) -> bytes:
    return _OPEN_LENGTH.pack(len(name)) + name + test_pb2.TestRequest(number=number).SerializeToString()


@pytest.mark.parametrize("which", ["local parity", "duplicate"])
async def test_a_request_frame_with_a_bad_id_is_refused_and_its_payload_delivered_to_nobody(which):
    async with Wire() as wire:
        conn = wire.server_conn  # the server's local ids are even (the client dialed)
        live = await conn.open_stream("square")  # a live stream of the server's own, inbox empty
        handlers = set(conn._handler_tasks)
        bad_id = live.stream_id if which == "duplicate" else conn._next_stream_id
        wire.sent.clear()
        await conn._dispatch(bad_id, REQUEST, _request_payload(b"square", 5))
        assert wire.sent == [("server", Flags.RESET)]
        assert conn._streams.get(live.stream_id) is live and live._inbox.empty() and not live._remote_closed
        assert (bad_id in conn._streams) == (which == "duplicate")
        assert set(conn._handler_tasks) == handlers and conn._buffered_bytes == 0
        await live.reset()
        # the same frame under a fresh id of the remote's parity is served
        await conn._dispatch(1001, REQUEST, memoryview(_request_payload(b"square", 5)))
        await wire.settle()
        assert ("server", ANSWER) in wire.sent and conn.num_streams == 0


async def test_a_handler_starts_with_the_message_and_the_end_of_stream_in_its_inbox():
    async with Wire() as wire:
        seen = []

        async def on_stream(stream):
            seen.append((stream.handler_name, stream._inbox.qsize(), stream._remote_closed, stream._send_closed))

        wire.server_conn._on_inbound_stream = on_stream
        await wire.server_conn._dispatch(2001, REQUEST, _request_payload(b"anything", 1))
        await asyncio.sleep(0.05)
        assert seen == [("anything", 2, True, False)]  # the message and the end-of-stream


@pytest.mark.parametrize("payload", [b"\x00", _OPEN_LENGTH.pack(500) + b"short"], ids=["no length", "length overruns"])
async def test_a_malformed_request_frame_costs_the_connection_not_the_process(payload):
    async with Wire() as wire:
        with pytest.raises(ConnectionError, match="malformed OPEN"):
            await wire.server_conn._dispatch(3001, Flags.OPEN | Flags.DATA, payload)
        assert 3001 not in wire.server_conn._streams


async def test_a_request_over_the_message_cap_takes_no_stream_id():
    from hivemind_tpu.p2p.mux import MAX_MESSAGE_SIZE

    async with Wire() as wire:
        next_id = wire.client_conn._next_stream_id
        with pytest.raises(ValueError, match="MAX_MESSAGE_SIZE"):
            await wire.client.call_protobuf_handler(wire.server.peer_id, "square", b"x" * (MAX_MESSAGE_SIZE + 1), bytes)
        assert wire.client_conn._next_stream_id == next_id and wire.client_conn.num_streams == 0 and not wire.sent


async def test_a_hundred_frames_ask_the_operating_system_for_the_cpu_count_zero_times(monkeypatch):
    asked = []
    monkeypatch.delenv("HIVEMIND_AEAD_THREADS", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: asked.append(1) or 8)
    async with Wire() as wire:
        for number in range(50):
            await wire.call("square", number)
        await wire.settle()
        assert len(wire.sent) == 100 and asked == []
    assert crypto_channel._aead_workers() == min(4, crypto_channel._CPU_COUNT) or crypto_channel._CPU_COUNT == 1


@pytest.mark.parametrize("configured, workers", [("0", 0), ("3", 3), ("-2", 0)])
def test_aead_threads_set_after_import_are_honoured(monkeypatch, configured, workers):
    monkeypatch.setenv("HIVEMIND_AEAD_THREADS", configured)
    assert crypto_channel._aead_workers() == workers
    executor = crypto_channel._get_aead_executor()
    assert (executor is None) if workers == 0 else (executor._max_workers == workers)
