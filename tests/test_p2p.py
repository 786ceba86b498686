"""Transport tests — scope mirrors reference tests/test_p2p_daemon.py +
test_p2p_servicer.py: lifecycle, identity, unary/stream handlers, errors,
cancellation, servicer reflection."""

import asyncio
from typing import AsyncIterator

import pytest

from hivemind_tpu.p2p import (
    P2P,
    Multiaddr,
    P2PContext,
    P2PHandlerError,
    PeerID,
    PeerNotFoundError,
    ServicerBase,
)
from hivemind_tpu.p2p.peer_id import base58_decode, base58_encode
from hivemind_tpu.proto import test_pb2


def test_base58_roundtrip():
    for data in [b"", b"\x00\x00abc", b"hello world", bytes(range(256))]:
        assert base58_decode(base58_encode(data)) == data
    with pytest.raises(ValueError):
        base58_decode("0OIl")  # excluded characters


def test_peer_id_and_multiaddr():
    from hivemind_tpu.utils.crypto import Ed25519PrivateKey

    key = Ed25519PrivateKey()
    pid = PeerID.from_private_key(key)
    assert PeerID.from_base58(pid.to_base58()) == pid
    maddr = Multiaddr.parse(f"/ip4/127.0.0.1/tcp/1234/p2p/{pid.to_base58()}")
    assert maddr.host == "127.0.0.1" and maddr.port == 1234 and maddr.peer_id == pid
    assert Multiaddr.parse(str(maddr)) == maddr
    with pytest.raises(ValueError):
        Multiaddr.parse("/udp/53")

    # reference vendored-multiaddr codec extras: unix + onion3 round-trip
    unix = Multiaddr.parse("/unix/tmp/sockets/p2p.sock")
    assert unix.host_proto == "unix" and unix.host == "/tmp/sockets/p2p.sock"
    assert Multiaddr.parse(str(unix)) == unix
    # ...including a pinned peer identity (hole-punch serialization reparses str)
    unix_pid = unix.with_peer_id(pid)
    assert Multiaddr.parse(str(unix_pid)) == unix_pid
    onion_host = "a" * 56
    onion = Multiaddr.parse(f"/onion3/{onion_host}:9443")
    assert onion.host_proto == "onion3" and onion.host == onion_host and onion.port == 9443
    assert Multiaddr.parse(str(onion)) == onion
    # protocols are part of identity: same host+port, different proto, distinct
    assert onion != Multiaddr.parse(f"/dns/{onion_host}/tcp/9443")
    # a path whose last segments merely LOOK base58 stays a path (only a real
    # sha2-256 multihash identity is stripped as /p2p/<id>)
    plain_path = Multiaddr.parse("/unix/var/run/p2p/sock")
    assert plain_path.host == "/var/run/p2p/sock" and plain_path.peer_id is None
    with pytest.raises(ValueError):
        Multiaddr.parse("/onion3/tooshort:1")


async def test_p2p_lifecycle_and_identity(tmp_path):
    ident = str(tmp_path / "id.key")
    p2p = await P2P.create(identity_path=ident)
    peer_id = p2p.peer_id
    maddrs = p2p.get_visible_maddrs()
    assert len(maddrs) == 1 and maddrs[0].peer_id == peer_id
    await p2p.shutdown()
    # identity persists across restarts
    p2p2 = await P2P.create(identity_path=ident)
    assert p2p2.peer_id == peer_id
    await p2p2.shutdown()


async def test_unary_handler_and_errors():
    server = await P2P.create()
    client = await P2P.create()

    async def square(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
        assert context.remote_id == client.peer_id
        return test_pb2.TestResponse(number=request.number**2)

    async def fail(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
        raise ValueError("deliberate failure")

    await server.add_protobuf_handler("square", square, test_pb2.TestRequest)
    await server.add_protobuf_handler("fail", fail, test_pb2.TestRequest)

    await client.connect(server.get_visible_maddrs()[0])
    response = await client.call_protobuf_handler(
        server.peer_id, "square", test_pb2.TestRequest(number=12), test_pb2.TestResponse
    )
    assert response.number == 144

    with pytest.raises(P2PHandlerError, match="deliberate failure"):
        await client.call_protobuf_handler(
            server.peer_id, "fail", test_pb2.TestRequest(number=1), test_pb2.TestResponse
        )
    with pytest.raises(P2PHandlerError, match="unknown handler"):
        await client.call_protobuf_handler(
            server.peer_id, "nonexistent", test_pb2.TestRequest(number=1), test_pb2.TestResponse
        )

    await client.shutdown()
    await server.shutdown()


async def test_streaming_handler_both_directions():
    server = await P2P.create()
    client = await P2P.create()

    async def partial_sums(
        requests: AsyncIterator[test_pb2.TestRequest], context: P2PContext
    ) -> AsyncIterator[test_pb2.TestResponse]:
        total = 0
        async for request in requests:
            total += request.number
            yield test_pb2.TestResponse(number=total)

    await server.add_protobuf_handler(
        "partial_sums", partial_sums, test_pb2.TestRequest, stream_input=True, stream_output=True
    )
    await client.connect(server.get_visible_maddrs()[0])

    async def gen():
        for i in [1, 2, 3, 4]:
            yield test_pb2.TestRequest(number=i)

    sums = [
        r.number
        async for r in client.iterate_protobuf_handler(
            server.peer_id, "partial_sums", gen(), test_pb2.TestResponse
        )
    ]
    assert sums == [1, 3, 6, 10]
    await client.shutdown()
    await server.shutdown()


async def test_dial_failures():
    client = await P2P.create()
    with pytest.raises((OSError, asyncio.TimeoutError, ConnectionError)):
        await client.connect("/ip4/127.0.0.1/tcp/1")  # nothing listening
    with pytest.raises(PeerNotFoundError):
        from hivemind_tpu.utils.crypto import Ed25519PrivateKey

        unknown = PeerID.from_private_key(Ed25519PrivateKey())
        await client.call_protobuf_handler(unknown, "x", b"", None)
    await client.shutdown()


async def test_wrong_expected_peer_rejected():
    from hivemind_tpu.p2p.crypto_channel import HandshakeError
    from hivemind_tpu.utils.crypto import Ed25519PrivateKey

    server = await P2P.create()
    client = await P2P.create()
    impostor = PeerID.from_private_key(Ed25519PrivateKey())
    bad_maddr = Multiaddr("127.0.0.1", server.listen_port, impostor)
    with pytest.raises(HandshakeError, match="dialed"):
        await client.connect(bad_maddr)
    await client.shutdown()
    await server.shutdown()


async def test_server_streaming_cancellation():
    server = await P2P.create()
    client = await P2P.create()
    served = asyncio.Event()
    cancelled = asyncio.Event()

    async def infinite(request: test_pb2.TestRequest, context: P2PContext) -> AsyncIterator[test_pb2.TestResponse]:
        try:
            n = 0
            while True:
                yield test_pb2.TestResponse(number=n)
                n += 1
                served.set()
                await asyncio.sleep(0.001)
        except (asyncio.CancelledError, ConnectionError):
            cancelled.set()
            raise

    await server.add_protobuf_handler("infinite", infinite, test_pb2.TestRequest, stream_output=True)
    await client.connect(server.get_visible_maddrs()[0])

    iterator = client.iterate_protobuf_handler(
        server.peer_id, "infinite", test_pb2.TestRequest(number=0), test_pb2.TestResponse
    )
    received = 0
    async for _ in iterator:
        received += 1
        if received >= 3:
            break  # closes the generator → resets the stream
    assert served.is_set()
    await client.shutdown()
    await server.shutdown()


async def test_large_messages():
    server = await P2P.create()
    client = await P2P.create()

    async def echo_len(request: bytes, context: P2PContext) -> bytes:
        return len(request).to_bytes(8, "big")

    await server.add_protobuf_handler("echo_len", echo_len, bytes)
    await client.connect(server.get_visible_maddrs()[0])
    payload = b"x" * (3 * 1024 * 1024)  # 3 MiB through the AEAD + mux path
    result = await client.call_protobuf_handler(server.peer_id, "echo_len", payload, bytes)
    assert int.from_bytes(result, "big") == len(payload)
    await client.shutdown()
    await server.shutdown()


class MathServicer(ServicerBase):
    async def rpc_square(self, request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
        return test_pb2.TestResponse(number=request.number**2)

    async def rpc_count(self, request: test_pb2.TestRequest, context: P2PContext) -> AsyncIterator[test_pb2.TestResponse]:
        for i in range(request.number):
            yield test_pb2.TestResponse(number=i)

    async def rpc_sum(self, requests: AsyncIterator[test_pb2.TestRequest], context: P2PContext) -> test_pb2.TestResponse:
        total = 0
        async for request in requests:
            total += request.number
        return test_pb2.TestResponse(number=total)

    async def rpc_slow_count(self, request: test_pb2.TestRequest, context: P2PContext) -> AsyncIterator[test_pb2.TestResponse]:
        for i in range(request.number):
            await asyncio.sleep(5)
            yield test_pb2.TestResponse(number=i)


async def test_servicer_reflection():
    specs = {s.method_name: s for s in MathServicer._collect_rpc_specs()}
    assert not specs["rpc_square"].stream_input and not specs["rpc_square"].stream_output
    assert not specs["rpc_count"].stream_input and specs["rpc_count"].stream_output
    assert specs["rpc_sum"].stream_input and not specs["rpc_sum"].stream_output
    assert specs["rpc_slow_count"].stream_output

    server = await P2P.create()
    client = await P2P.create()
    servicer = MathServicer()
    await servicer.add_p2p_handlers(server)
    await client.connect(server.get_visible_maddrs()[0])

    stub = MathServicer.get_stub(client, server.peer_id)
    assert (await stub.rpc_square(test_pb2.TestRequest(number=9))).number == 81
    counted = [r.number async for r in stub.rpc_count(test_pb2.TestRequest(number=4))]
    assert counted == [0, 1, 2, 3]

    async def gen():
        for i in range(5):
            yield test_pb2.TestRequest(number=i)

    assert (await stub.rpc_sum(gen())).number == 10

    with pytest.raises(asyncio.TimeoutError):
        async for _ in stub.rpc_slow_count(test_pb2.TestRequest(number=1), timeout=0.1):
            pass

    await client.shutdown()
    await server.shutdown()


async def test_servicer_namespaces():
    server = await P2P.create()
    client = await P2P.create()
    servicer_a, servicer_b = MathServicer(), MathServicer()
    await servicer_a.add_p2p_handlers(server, namespace="a")
    await servicer_b.add_p2p_handlers(server, namespace="b")
    await client.connect(server.get_visible_maddrs()[0])
    stub_a = MathServicer.get_stub(client, server.peer_id, namespace="a")
    assert (await stub_a.rpc_square(test_pb2.TestRequest(number=3))).number == 9
    stub_missing = MathServicer.get_stub(client, server.peer_id, namespace="missing")
    with pytest.raises(P2PHandlerError):
        await stub_missing.rpc_square(test_pb2.TestRequest(number=3))
    await client.shutdown()
    await server.shutdown()


async def test_servicer_wrapper_substitutes_the_bound_target():
    """`add_p2p_handlers(wrapper=...)`: the handlers registered are the wrapper's, under
    the servicer's names (the hook an authorizing wrapper would hang on)."""

    class Refusing:
        def __init__(self, servicer):
            self._servicer = servicer

        def __getattr__(self, name):
            method = getattr(self._servicer, name)

            async def guarded(request, context):
                if request.number < 0:
                    raise PermissionError("refused")
                return await method(request, context)

            return guarded

    server = await P2P.create()
    client = await P2P.create()
    servicer = MathServicer()
    await servicer.add_p2p_handlers(server, wrapper=Refusing(servicer))
    await client.connect(server.get_visible_maddrs()[0])
    stub = MathServicer.get_stub(client, server.peer_id)
    assert (await stub.rpc_square(test_pb2.TestRequest(number=3))).number == 9
    with pytest.raises(P2PHandlerError, match="refused"):
        await stub.rpc_square(test_pb2.TestRequest(number=-3))
    await client.shutdown()
    await server.shutdown()


async def test_mux_rejects_invalid_open_frames():
    """OPEN frames with local-parity or already-used stream ids must be RESET, not
    silently replace a live stream (ADVICE r1: stream hijack via id collision)."""
    from hivemind_tpu.p2p.mux import Flags

    server = await P2P.create()
    client = await P2P.create()
    try:
        async def echo(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
            return test_pb2.TestResponse(number=request.number)

        await server.add_protobuf_handler("echo", echo, test_pb2.TestRequest)
        await client.connect(server.get_visible_maddrs()[0])
        response = await client.call_protobuf_handler(
            server.peer_id, "echo", test_pb2.TestRequest(number=7), test_pb2.TestResponse
        )
        assert response.number == 7

        conn = client._connections[server.peer_id]
        # client is the initiator: its local ids are odd. A remote OPEN with an odd
        # id (wrong parity) must be rejected...
        local_parity_id = conn._next_stream_id  # odd, unused
        await conn._dispatch(local_parity_id, Flags.OPEN, b"echo")
        assert local_parity_id not in conn._streams
        # ...and so must an OPEN duplicating an id that is already live
        stream = await conn.open_stream("echo")
        before = conn._streams[stream.stream_id]
        await conn._dispatch(stream.stream_id, Flags.OPEN, b"echo")
        assert conn._streams[stream.stream_id] is before
        # valid remote-parity OPEN still works
        await conn._dispatch(1000, Flags.OPEN, b"echo")
        assert 1000 in conn._streams
    finally:
        await client.shutdown()
        await server.shutdown()


async def test_many_concurrent_streams_one_connection():
    """Stress the mux: many interleaved unary + streaming calls share ONE encrypted
    connection; every response routes to the right stream (race-detection parity:
    the reference exercises concurrency with real parallel calls)."""
    server = await P2P.create()
    client = await P2P.create()
    try:
        async def square(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
            await asyncio.sleep(0.001 * (request.number % 7))  # shuffle completion order
            return test_pb2.TestResponse(number=request.number ** 2)

        async def countdown(request: test_pb2.TestRequest, context: P2PContext):
            for value in range(request.number, 0, -1):
                yield test_pb2.TestResponse(number=value)

        await server.add_protobuf_handler("square", square, test_pb2.TestRequest)
        await server.add_protobuf_handler("countdown", countdown, test_pb2.TestRequest, stream_output=True)
        await client.connect(server.get_visible_maddrs()[0])

        async def one_unary(i):
            response = await client.call_protobuf_handler(
                server.peer_id, "square", test_pb2.TestRequest(number=i), test_pb2.TestResponse
            )
            return response.number

        async def one_stream(i):
            values = []
            async for response in client.iterate_protobuf_handler(
                server.peer_id, "countdown", test_pb2.TestRequest(number=i), test_pb2.TestResponse
            ):
                values.append(response.number)
            return values

        unary_results, stream_results = await asyncio.gather(
            asyncio.gather(*(one_unary(i) for i in range(50))),
            asyncio.gather(*(one_stream(i) for i in range(1, 11))),
        )
        assert list(unary_results) == [i ** 2 for i in range(50)]
        assert list(stream_results) == [list(range(i, 0, -1)) for i in range(1, 11)]
        # all of that rode exactly one connection
        assert len(client._connections) == 1
    finally:
        await client.shutdown()
        await server.shutdown()


async def test_identity_file_collision_detected(tmp_path):
    """Two live P2P instances must not share one identity file (capability parity:
    reference is_identity_taken, p2p_daemon.py): the second create() fails fast,
    and the identity becomes reusable once the holder shuts down."""
    path = str(tmp_path / "id.key")
    first = await P2P.create(identity_path=path)
    try:
        with pytest.raises(P2P.IdentityTakenError):
            await P2P.create(identity_path=path)
    finally:
        await first.shutdown()
    second = await P2P.create(identity_path=path)  # lock released on shutdown
    assert second.peer_id == first.peer_id  # same key file -> same identity
    await second.shutdown()


async def test_identity_file_readonly_and_failed_create(tmp_path):
    """A pre-provisioned read-only key file works (flock on a read-only fd), and a
    create() that fails AFTER taking the lock releases it for the next attempt."""
    import os

    path = str(tmp_path / "ro.key")
    P2P.generate_identity(path)
    os.chmod(path, 0o400)
    node = await P2P.create(identity_path=path)
    await node.shutdown()

    # occupy a port, then fail a create() bound to it: the lock must be released
    blocker = await P2P.create()
    busy_port = blocker.listen_port
    with pytest.raises(OSError):
        await P2P.create(identity_path=path, listen_port=busy_port)
    retry = await P2P.create(identity_path=path)  # identity is NOT stuck "taken"
    assert retry.peer_id == node.peer_id
    await retry.shutdown()
    await blocker.shutdown()


async def test_connection_manager_trims_idle_and_redials():
    """Reference parity (go-libp2p ConnManager): past the high water mark, idle
    stream-less connections close LRU-first; a trimmed peer is re-dialed
    transparently on the next call — this is what bounds fd usage at swarm scale."""
    hub = await P2P.create(max_connections=4)

    async def echo(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
        return test_pb2.TestResponse(number=request.number + 1)

    await hub.add_protobuf_handler("echo", echo, test_pb2.TestRequest)
    spokes = []
    try:
        for _ in range(8):
            spoke = await P2P.create()
            await spoke.connect(hub.get_visible_maddrs()[0])
            spokes.append(spoke)
        await asyncio.sleep(0.1)
        live = [c for c in hub._all_connections if not c.is_closed]
        assert len(live) <= 4, f"{len(live)} live connections past the cap"

        # every spoke can still call the hub: trimmed ones re-dial transparently
        # (echo is read-only, so the ambiguous-loss retry is explicitly allowed)
        for i, spoke in enumerate(spokes):
            response = await spoke.call_protobuf_handler(
                hub.peer_id, "echo", test_pb2.TestRequest(number=i), test_pb2.TestResponse,
                idempotent=True,
            )
            assert response.number == i + 1
    finally:
        for spoke in spokes:
            await spoke.shutdown()
        await hub.shutdown()


async def test_unary_retry_gated_on_idempotency():
    """A connection that dies after the request was sent is ambiguous — the handler
    may already have run. Idempotent calls retry on a fresh connection; calls with
    side effects fail loudly instead of risking a double-applied optimizer step or
    a double-advanced decode cache (round-3 advisor, p2p.py:549)."""
    server = await P2P.create()
    calls = {"n": 0}

    async def flaky(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
        calls["n"] += 1
        if calls["n"] == 1:
            # the handler DID run; the connection dies before the response arrives
            await server._connections[context.remote_id].close()
        return test_pb2.TestResponse(number=calls["n"])

    await server.add_protobuf_handler("flaky", flaky, test_pb2.TestRequest)
    client = await P2P.create()
    await client.connect(server.get_visible_maddrs()[0])
    try:
        response = await client.call_protobuf_handler(
            server.peer_id, "flaky", test_pb2.TestRequest(number=0), test_pb2.TestResponse,
            idempotent=True,
        )
        assert response.number == 2 and calls["n"] == 2  # retried: attempt 2 answered

        calls["n"] = 0
        with pytest.raises(P2PHandlerError, match="not marked idempotent"):
            await client.call_protobuf_handler(
                server.peer_id, "flaky", test_pb2.TestRequest(number=0), test_pb2.TestResponse
            )
        assert calls["n"] == 1  # ran exactly once — no silent second application
    finally:
        await client.shutdown()
        await server.shutdown()
