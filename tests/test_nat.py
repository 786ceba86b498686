"""NAT traversal: relay whoami (observed endpoint), AutoNAT-style dial-back probe,
and DCUtR-style hole punching upgrading a relayed connection to a direct one
(scope: reference p2p_daemon.py:84-147 AutoNAT/AutoRelay/DCUtR flags)."""

import asyncio

from hivemind_tpu.p2p import NATTraversal, P2P, P2PContext
from hivemind_tpu.p2p.relay import RelayClient
from hivemind_tpu.proto import test_pb2


async def test_relay_whoami(relay_daemon):
    p2p = await P2P.create()
    try:
        relay = RelayClient(p2p, "127.0.0.1", relay_daemon.port)
        host, port = await relay.whoami()
        assert host == "127.0.0.1" and 0 < port < 65536
    finally:
        await p2p.shutdown()


async def test_reachability_probe():
    alice = await P2P.create()
    bob = await P2P.create()
    try:
        await NATTraversal(bob).register_handlers()
        await alice.connect(bob.get_visible_maddrs()[0])
        nat_alice = NATTraversal(alice)
        # our real listener is reachable from bob
        reachable = await nat_alice.check_reachability(bob.peer_id)
        assert [str(m) for m in alice.get_visible_maddrs()] == reachable
        # a dead port is correctly reported unreachable
        dead = f"/ip4/127.0.0.1/tcp/1/p2p/{alice.peer_id.to_base58()}"
        reachable = await nat_alice.check_reachability(
            bob.peer_id, maddrs=[alice.get_visible_maddrs()[0], dead]
        )
        assert dead not in reachable and len(reachable) == 1
    finally:
        await alice.shutdown()
        await bob.shutdown()


async def test_hole_punch_upgrades_relayed_connection(relay_daemon):
    """Two peers talk only through the relay; hole punching swaps in a direct
    connection that keeps serving RPCs."""
    server = await P2P.create()
    client = await P2P.create()
    try:
        async def double(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
            return test_pb2.TestResponse(number=request.number * 2)

        await server.add_protobuf_handler("double", double, test_pb2.TestRequest)
        await NATTraversal(server).register_handlers()
        nat_client = NATTraversal(client)
        await nat_client.register_handlers()

        server_relay = await RelayClient.create(server, "127.0.0.1", relay_daemon.port)
        client_relay = RelayClient(client, "127.0.0.1", relay_daemon.port)
        await client_relay.dial(server.peer_id)
        relayed_conn = client._connections[server.peer_id]
        response = await client.call_protobuf_handler(
            server.peer_id, "double", test_pb2.TestRequest(number=5), test_pb2.TestResponse
        )
        assert response.number == 10

        # punch: both sides dial direct; the map entry must change connections
        assert await nat_client.hole_punch(server.peer_id)
        await asyncio.sleep(0.2)
        direct_conn = client._connections[server.peer_id]
        assert direct_conn is not relayed_conn and not direct_conn.is_closed
        response = await client.call_protobuf_handler(
            server.peer_id, "double", test_pb2.TestRequest(number=8), test_pb2.TestResponse
        )
        assert response.number == 16
        await server_relay.close()
    finally:
        await client.shutdown()
        await server.shutdown()
