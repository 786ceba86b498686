"""Run the native relay daemon AND advertise it in the swarm's DHT — the complete
relay-operator story for zero-config auto-relay (reference role: public peers
with relay enabled, p2p_daemon.py use_relay; here the relay is the C++ daemon
`hivemind_tpu/native/relay_daemon.cpp` and discovery rides `p2p/autorelay.py`).

    python -m hivemind_tpu.hivemind_cli.run_relay \
        --initial_peers /ip4/…/tcp/…/p2p/Qm… \
        --relay_port 34000 --announce_host 203.0.113.7

NATed peers then find this relay via `AutoRelay.create(p2p, dht)` with zero
relay configuration."""

from __future__ import annotations

import argparse
import subprocess
import time
from pathlib import Path

from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)

NATIVE_DIR = Path(__file__).parent.parent / "native"


def main():
    parser = argparse.ArgumentParser(description="Run + advertise a relay daemon")
    parser.add_argument("--initial_peers", nargs="*", default=[],
                        help="DHT bootstrap addrs (empty: starts a fresh swarm)")
    parser.add_argument("--relay_port", type=int, default=0, help="0 = ephemeral")
    parser.add_argument("--announce_host", default=None,
                        help="the relay endpoint advertised to the swarm (REQUIRED "
                             "for real deployments; defaults to loopback for local "
                             "testing only)")
    parser.add_argument("--identity_path", default="relay.key",
                        help="persistent relay Ed25519 identity file")
    parser.add_argument("--advertise_period", type=float, default=300.0,
                        help="re-advertise at this period (records expire at 2x)")
    args = parser.parse_args()

    if args.announce_host is None:
        args.announce_host = "127.0.0.1"
        logger.warning(
            "no --announce_host given: advertising LOOPBACK (127.0.0.1) — fine for "
            "local testing, useless to any peer on another machine"
        )

    from hivemind_tpu.p2p.native_transport import build_daemon_binary, read_daemon_banner

    # the shared helper serializes concurrent makes with an flock and treats a
    # missing toolchain as an error message (an operator CLI raises on it)
    binary, error = build_daemon_binary()
    if binary is None:
        raise RuntimeError(f"relay daemon unavailable under {NATIVE_DIR}: {error}")

    daemon = subprocess.Popen(
        [str(binary), str(args.relay_port), args.identity_path],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # a current daemon emits exactly two startup lines in one flush; the bounded
    # read guards a STALE prebuilt binary from before the two-line protocol —
    # hanging forever there would be worse than erroring. Anything unexpected is
    # an error: a crypto-capable relay advertised WITHOUT its identity would
    # silently downgrade every NATed peer to unpinned registration.
    banner = read_daemon_banner(daemon, timeout=10.0)
    if banner is None:
        returncode = daemon.poll()
        stderr_tail = ""
        if returncode is not None:  # died before announcing (e.g. port bound)
            stderr_tail = daemon.stderr.read()[-500:]
        daemon.kill()
        daemon.wait()
        raise RuntimeError(
            "relay daemon did not announce its two startup lines within 10s"
            + (f" (rc={returncode}): {stderr_tail}" if returncode is not None
               else " — a stale binary predates the protocol; rebuild (make -C native)")
        )
    first_line, identity_line = banner
    try:
        port = int(first_line.rsplit(" ", 1)[-1])
    except ValueError:
        daemon.kill()
        raise RuntimeError(f"unexpected relay daemon output: {first_line!r}") from None
    if identity_line.startswith("relay identity "):
        pubkey_hex = identity_line.rsplit(" ", 1)[-1]
        logger.info(f"relay daemon up on port {port} (identity {pubkey_hex[:16]}…)")
    elif identity_line == "relay encryption unavailable":
        pubkey_hex = ""
        logger.warning(
            f"relay daemon up on port {port} WITHOUT an identity (no libcrypto) — "
            f"peers cannot pin it and will refuse encrypted-control registration"
        )
    else:
        daemon.kill()
        raise RuntimeError(f"unexpected relay daemon output: {identity_line!r}")

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.p2p.autorelay import advertise_relay

    dht = DHT(initial_peers=args.initial_peers, start=True)
    for maddr in dht.get_visible_maddrs():
        logger.info(f"swarm members can bootstrap via: --initial_peers {maddr}")

    try:
        while True:
            if daemon.poll() is not None:
                raise RuntimeError(f"relay daemon exited with rc={daemon.returncode}")
            ok = advertise_relay(
                dht, args.announce_host, port, pubkey_hex, ttl=args.advertise_period * 2
            )
            logger.info(
                f"advertised {args.announce_host}:{port} in the DHT (stored={ok}); "
                f"next refresh in {args.advertise_period:.0f}s"
            )
            time.sleep(args.advertise_period)
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        daemon.kill()
        daemon.wait()
        dht.shutdown()


if __name__ == "__main__":
    main()
