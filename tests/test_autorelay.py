"""Auto-relay via the DHT (VERDICT r2 next-round #6; reference use_auto_relay,
hivemind/p2p/p2p_daemon.py:114-137): a NATed peer with ZERO relay configuration
diagnoses itself via AutoNAT dial-back, discovers an advertised relay in the DHT,
registers there, publishes its circuits — and a public peer dials it purely by
peer id through the installed resolver."""

import asyncio

from swarm_utils import start_relay_daemon, stop_process

from hivemind_tpu.dht import DHT
from hivemind_tpu.p2p import P2P, AutoRelay, P2PContext, advertise_relay
from hivemind_tpu.p2p.autorelay import RELAY_DHT_KEY, RELAYED_PEER_PREFIX
from hivemind_tpu.proto import test_pb2


def test_advertise_and_parse_relay_records(relay_daemon):
    _, port, pubkey_hex = relay_daemon
    dht = DHT(start=True)
    try:
        assert advertise_relay(dht, "127.0.0.1", port, pubkey_hex)
        record = dht.get(RELAY_DHT_KEY, latest=True)
        assert record is not None
        from hivemind_tpu.p2p.autorelay import _parse_relay_records

        relays = _parse_relay_records(record)
        assert ("127.0.0.1", port, pubkey_hex) in relays
    finally:
        dht.shutdown()


def test_natted_peer_zero_config_becomes_dialable(relay_daemon):
    _, port, pubkey_hex = relay_daemon

    async def scenario():
        # swarm bootstrap + a PUBLIC peer that serves the AutoNAT dial-back
        boot = DHT(start=True)
        maddrs = [str(m) for m in boot.get_visible_maddrs()]
        public_dht = DHT(initial_peers=maddrs, start=True)
        natted_dht = DHT(initial_peers=maddrs, start=True)

        # the relay operator advertises the daemon in the DHT — the ONLY place
        # relay coordinates exist in this test
        assert advertise_relay(boot, "127.0.0.1", port, pubkey_hex)

        public = await P2P.create()
        public_auto = await AutoRelay.create(public, public_dht)

        # "NATed": announces a dead port (like an unforwarded NAT mapping), so the
        # dial-back gets connection-refused and every direct dial fails fast
        import socket

        with socket.socket() as probe_sock:
            probe_sock.bind(("127.0.0.1", 0))
            dead_port = probe_sock.getsockname()[1]
        natted = await P2P.create(announce_port=dead_port, dial_timeout=1.0)

        async def echo(request: test_pb2.TestRequest, context: P2PContext) -> test_pb2.TestResponse:
            return test_pb2.TestResponse(number=request.number + 1)

        await natted.add_protobuf_handler("echo", echo, test_pb2.TestRequest)

        # the NATed peer can reach the public peer (outbound works behind NAT)
        await natted.connect(public.get_visible_maddrs()[0])
        natted_auto = await AutoRelay.create(natted, natted_dht, probe_via=public.peer_id)

        # self-diagnosis found no reachable address → registered + published
        assert natted_auto.relay_clients, "NATed peer did not register at any relay"
        published = natted_dht.get(RELAYED_PEER_PREFIX + natted.peer_id.to_base58(), latest=True)
        assert published is not None and published.value

        # a fresh public client knows ONLY the peer id: resolver finds the circuit
        client = await P2P.create(dial_timeout=1.0)
        client_auto = await AutoRelay.create(client, public_dht)
        response = await client.call_protobuf_handler(
            natted.peer_id, "echo", test_pb2.TestRequest(number=41), test_pb2.TestResponse
        )
        assert response.number == 42

        # second call rides the established relayed connection
        response = await client.call_protobuf_handler(
            natted.peer_id, "echo", test_pb2.TestRequest(number=99), test_pb2.TestResponse
        )
        assert response.number == 100

        for auto in (client_auto, natted_auto, public_auto):
            await auto.close()
        for node in (client, natted, public):
            await node.shutdown()
        for dht in (public_dht, natted_dht, boot):
            dht.shutdown()

    asyncio.run(asyncio.wait_for(scenario(), timeout=120))


def test_maintenance_replaces_dead_relay(relay_daemon, tmp_path):
    """Failure recovery: the relay a NATed peer registered at dies; a maintenance
    pass detects the dropped control line and re-registers at another advertised
    relay, republishing circuits (reference auto-relay keeps peers dialable
    through relay churn)."""
    _, port, pubkey_hex = relay_daemon

    async def scenario():
        # a second, short-lived relay the peer will register at FIRST
        victim, victim_port, victim_key = start_relay_daemon()
        try:
            dht = DHT(start=True)
            assert advertise_relay(dht, "127.0.0.1", victim_port, victim_key)
            natted = await P2P.create(dial_timeout=1.0)
            auto = await AutoRelay.create(natted, dht, max_relays=1, force_relay=True)
            assert set(auto.relay_clients) == {("127.0.0.1", victim_port)}

            # the registered relay dies; the survivor is advertised in its place
            stop_process(victim)
            assert advertise_relay(dht, "127.0.0.1", port, pubkey_hex)

            deadline = asyncio.get_event_loop().time() + 30
            while asyncio.get_event_loop().time() < deadline:
                await auto._maintenance_once()
                if ("127.0.0.1", port) in auto.relay_clients:
                    break
                await asyncio.sleep(0.5)
            assert set(auto.relay_clients) == {("127.0.0.1", port)}, auto.relay_clients

            published = dht.get(RELAYED_PEER_PREFIX + natted.peer_id.to_base58(), latest=True)
            assert published is not None
            endpoints = {c["endpoint"] for c in published.value}
            assert f"127.0.0.1:{port}" in endpoints

            await auto.close()
            await natted.shutdown()
            dht.shutdown()
        finally:
            stop_process(victim)

    asyncio.run(asyncio.wait_for(scenario(), timeout=120))
