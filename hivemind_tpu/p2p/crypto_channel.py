"""Authenticated encrypted channel over a TCP connection.

The reference delegates transport security to the Go daemon (TLS1.3 / noise inside
go-libp2p, hivemind/p2p/p2p_daemon.py:99). Here the equivalent is a Noise-style
XX-pattern handshake implemented with the ``cryptography`` primitives:

1. both sides exchange a plaintext hello: {ed25519 static pub, x25519 ephemeral pub,
   sig = Ed25519_sign(transcript_prefix || x25519_pub)}, proving static-key possession.
2. shared secret = X25519(own ephemeral, peer ephemeral); two ChaCha20-Poly1305 keys
   are derived with HKDF-SHA256 (one per direction), giving forward secrecy.
3. every subsequent frame is AEAD-sealed with a per-direction 64-bit counter nonce and
   the 4-byte length header as associated data.

Frame wire format: [u32 big-endian ciphertext length][ciphertext].

Data-plane parallelism: the reference's Go daemon spreads AEAD + IO over goroutines
(p2p_daemon.py:84-147 delegates the whole data path); a single asyncio thread doing
AEAD in-line caps the cross-pod tier at one core. Both directions are therefore
PIPELINED: ``send`` assigns the nonce and enqueues the seal, a writer task emits
ciphertexts strictly in nonce order; the reader task prefetches and unseals ahead of
``recv``. Frames above ``_OFFLOAD_THRESHOLD`` are sealed/opened in a shared thread
pool — ChaCha20-Poly1305 releases the GIL in OpenSSL, so on a multi-core host k
connections (or k queued frames of one connection) use k cores. On a single-core
host the pool is disabled (``HIVEMIND_AEAD_THREADS=0`` forces this; any other value
overrides the default ``min(4, cpu_count)``) and the pipeline still batches socket
writes. The CPU count behind that default is asked ONCE, at import (``_CPU_COUNT``):
every frame sent and received consults the pool's size, and a system call a frame is
what a server of small frames cannot afford (35 us each where a sandbox answers them:
a quarter of a decode cell's rate, PERF.md §6); the environment variable is still read
a frame, so a test or an operator may set it after import. In-flight frames are bounded both ways (send semaphore / bounded prefetch
queue), so memory stays capped and TCP backpressure propagates to callers.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import struct
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

try:
    from cryptography.exceptions import InvalidTag
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric.x25519 import X25519PrivateKey, X25519PublicKey
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
    from cryptography.hazmat.primitives.kdf.hkdf import HKDF
except ImportError:  # no cryptography wheel on this image: system libcrypto shim
    from hivemind_tpu.utils._libcrypto import (
        ChaCha20Poly1305,
        HKDF,
        InvalidTag,
        X25519PrivateKey,
        X25519PublicKey,
        hashes,
        serialization,
    )

from hivemind_tpu.telemetry.wire import WORK_SPAN_BYTES, add_send_wait, count_work, wire_work
from hivemind_tpu.utils.crypto import Ed25519PrivateKey, Ed25519PublicKey
from hivemind_tpu.utils.serializer import MSGPackSerializer
from hivemind_tpu.utils.asyncio_utils import spawn

MAX_FRAME_SIZE = 16 * 1024 * 1024  # hard cap on one encrypted frame
_HANDSHAKE_PREFIX = b"hivemind-tpu-noise-v1:"

# frames at least this large have their AEAD offloaded to the worker pool; smaller
# ones are sealed inline (executor hop costs more than the cipher call)
_OFFLOAD_THRESHOLD = 128 * 1024
_MAX_INFLIGHT_SEND = 16  # per channel; bounds sender memory at 16 frames
_RECV_PREFETCH = 8  # frames unsealed ahead of recv(); bounds receiver memory

_aead_executor: Optional[ThreadPoolExecutor] = None

# asked once: every frame sent and received passes _aead_workers(), and where a sandbox
# answers system calls os.cpu_count() is 35 us with the interpreter lock held (PERF.md §6, PR 37)
_CPU_COUNT = os.cpu_count() or 1


def _aead_workers() -> int:
    configured = os.environ.get("HIVEMIND_AEAD_THREADS")  # read a frame: tests set it after import
    if configured is not None:
        return max(0, int(configured))
    return min(4, _CPU_COUNT) if _CPU_COUNT > 1 else 0


def _get_aead_executor() -> Optional[ThreadPoolExecutor]:
    global _aead_executor
    workers = _aead_workers()
    if workers <= 0:
        return None
    if _aead_executor is None or _aead_executor._max_workers != workers:
        if _aead_executor is not None:
            _aead_executor.shutdown(wait=False)
        # hmtpu- prefix: the test thread sanitizer exempts the shared
        # process-lifetime executors by this naming convention
        _aead_executor = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="hmtpu-aead")
    return _aead_executor


class HandshakeError(RuntimeError):
    pass


class SecureChannel:
    """Length-prefixed AEAD frames over an asyncio stream pair. Use ``handshake`` to
    construct. ``send``/``recv`` exchange whole messages (frames)."""

    def __init__(
        self,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        send_key: bytes,
        recv_key: bytes,
        peer_public_key: Ed25519PublicKey,
    ):
        self._reader = reader
        self._writer = writer
        self._send_aead = ChaCha20Poly1305(send_key)
        self._recv_aead = ChaCha20Poly1305(recv_key)
        self._send_counter = 0
        self._recv_counter = 0
        self.peer_public_key = peer_public_key
        # ordered pipelines (see module docstring); tasks start lazily so a channel
        # that fails mid-handshake never spawns them without a closer
        self._send_queue: asyncio.Queue = asyncio.Queue()
        self._send_sem = asyncio.Semaphore(_MAX_INFLIGHT_SEND)
        self._send_error: Optional[BaseException] = None
        self._writer_task: Optional[asyncio.Task] = None
        self._recv_queue: asyncio.Queue = asyncio.Queue(maxsize=_RECV_PREFETCH)
        self._recv_error: Optional[BaseException] = None
        self._recv_stopped = False  # auth failure: no frame past it may be delivered
        self._reader_task: Optional[asyncio.Task] = None
        self._closed = False

    # ------------------------------------------------------------------ send side

    async def send(self, payload: bytes, *extra_buffers: bytes) -> None:
        """Seal and send one frame. The plaintext is the concatenation of all given
        buffers — scatter-gather: callers framing a header in front of a large
        payload pass both instead of concatenating (the AEAD walks the pieces;
        only the ciphertext output is a fresh buffer)."""
        if self._send_error is not None:
            raise self._send_failed()
        total_len = len(payload) + sum(len(buffer) for buffer in extra_buffers)
        # size check BEFORE the counter moves: raising after an increment would
        # desynchronize AEAD nonces and poison the whole connection
        if total_len + 16 > MAX_FRAME_SIZE:  # +16: poly1305 tag
            raise ValueError(f"frame too large: {total_len} > {MAX_FRAME_SIZE - 16}")
        # the only wait of a mux send (MuxStream.send -> send_frame -> here): for the in-flight
        # credit, which the writer loop hands back frame by frame; with credit in hand, no clock read
        waiting_since = time.perf_counter() if self._send_sem.locked() else None
        await self._send_sem.acquire()
        if waiting_since is not None:
            add_send_wait(time.perf_counter() - waiting_since)
        if self._send_error is not None:
            self._send_sem.release()
            raise self._send_failed()
        # no await between the counter assignment and the enqueue: nonce order and
        # wire order are decided atomically on the event loop
        nonce = struct.pack("<4xQ", self._send_counter)
        self._send_counter += 1
        executor = _get_aead_executor()
        if executor is not None and total_len >= _OFFLOAD_THRESHOLD:
            sealed = asyncio.get_running_loop().run_in_executor(
                executor, self._seal, nonce, payload, extra_buffers, total_len
            )
        else:
            sealed = self._seal(nonce, payload, extra_buffers, total_len)
        if self._writer_task is None:
            self._writer_task = spawn(self._writer_loop(), name="crypto_channel.writer_loop")
        self._send_queue.put_nowait(sealed)

    def _seal(self, nonce: bytes, payload: bytes, extra_buffers: Tuple[bytes, ...], total_len: int) -> bytes:
        # once a frame. A small one (a decode token's, some thousand a second, inline on the
        # event loop) is timed here and handed to the counters, as the serving handler does
        # with its inline codec calls: with a context manager a frame in its place,
        # mistral-7b-span8.decode32 read 2-7 % under the parent in nine pairs of nine
        # (PERF.md §6, PR 37). From the offload size up: the counters and the profiler's
        # annotation, no span
        if total_len < WORK_SPAN_BYTES:
            began = time.perf_counter()
            sealed = self._encrypt(nonce, payload, extra_buffers)
            count_work("seal", time.perf_counter() - began, total_len)
            return sealed
        with wire_work("seal", total_len, span=False):
            return self._encrypt(nonce, payload, extra_buffers)

    def _encrypt(self, nonce: bytes, payload: bytes, extra_buffers: Tuple[bytes, ...]) -> bytes:
        if not extra_buffers:
            return self._send_aead.encrypt(nonce, payload, None)
        encrypt_parts = getattr(self._send_aead, "encrypt_parts", None)
        if encrypt_parts is not None:
            return encrypt_parts(nonce, (payload, *extra_buffers), None)
        # cipher without multi-buffer support: one join is still cheaper than
        # making every caller concatenate ahead of the size check
        return self._send_aead.encrypt(nonce, b"".join((payload, *extra_buffers)), None)

    def _open(self, nonce: bytes, ciphertext: bytes) -> bytes:
        if len(ciphertext) < WORK_SPAN_BYTES:  # as in _seal
            began = time.perf_counter()
            opened = self._recv_aead.decrypt(nonce, ciphertext, None)
            count_work("open", time.perf_counter() - began, len(ciphertext))
            return opened
        with wire_work("open", len(ciphertext), span=False):
            return self._recv_aead.decrypt(nonce, ciphertext, None)

    def _send_failed(self) -> ConnectionError:
        error = self._send_error
        if isinstance(error, (ConnectionError, OSError)):
            return error  # type: ignore[return-value]
        return ConnectionError(f"secure channel send failed: {error!r}")

    def _fail_send(self, error: BaseException) -> None:
        if self._send_error is None:
            self._send_error = error
        # wake every sender parked on the in-flight semaphore
        for _ in range(_MAX_INFLIGHT_SEND):
            self._send_sem.release()

    async def _writer_loop(self) -> None:
        try:
            while True:
                sealed = await self._send_queue.get()
                if sealed is None:
                    return
                ciphertext = (await sealed) if asyncio.isfuture(sealed) else sealed
                header = struct.pack(">I", len(ciphertext))
                if len(ciphertext) >= _OFFLOAD_THRESHOLD:
                    # two writes skip the megabyte-scale header+body concat copy
                    self._writer.write(header)
                    self._writer.write(ciphertext)
                else:
                    self._writer.write(header + ciphertext)
                self._send_sem.release()
                # drain() is a no-op below the transport high-water mark; above it,
                # this is where TCP backpressure propagates: writer blocks → queue
                # fills → the in-flight semaphore parks the senders
                await self._writer.drain()
        except asyncio.CancelledError:
            raise
        except BaseException as e:
            self._fail_send(e)

    # ------------------------------------------------------------------ recv side

    async def recv(self) -> bytes:
        if self._reader_task is None:
            self._reader_task = spawn(self._reader_loop(), name="crypto_channel.reader_loop")
        while True:
            if self._recv_stopped or (self._recv_error is not None and self._recv_queue.empty()):
                raise self._recv_error
            opened = await self._recv_queue.get()
            if opened is None:  # reader loop ended; the stored error says why
                # one sentinel must serve EVERY concurrent recv(): re-enqueue it so
                # a second parked waiter wakes and raises too instead of hanging
                with contextlib.suppress(asyncio.QueueFull):
                    self._recv_queue.put_nowait(None)  # lint: single-writer — sentinel re-enqueue is idempotent
                if self._recv_error is not None:
                    raise self._recv_error
                continue
            try:
                return (await opened) if asyncio.isfuture(opened) else opened
            except HandshakeError:
                # the prefetch queue is FIFO, so frames behind the tampered one sit
                # behind this failure: stop delivery for good (a clean reader death
                # still drains prefetched VALID frames — only auth failure stops)
                self._recv_stopped = True
                raise
            except InvalidTag:
                # defensive: _open_offloaded normally converts + poisons already
                error = HandshakeError("AEAD authentication failed (corrupted or replayed frame)")
                self._recv_stopped = True
                self._poison(error)
                raise error

    def _poison(self, error: BaseException) -> None:
        """Fatal receive-side failure: kill BOTH directions and stop the reader.
        Authentication failure must be fatal regardless of frame size — nonces are
        counters, so if the channel survived one InvalidTag, later frames would
        still authenticate and an on-path attacker could selectively delete a
        frame by corrupting it."""
        if self._recv_error is None:
            self._recv_error = error
        self._fail_send(error)
        if self._reader_task is not None and not self._reader_task.done():
            self._reader_task.cancel()
        if self._writer_task is not None:
            self._send_queue.put_nowait(None)
        with contextlib.suppress(asyncio.QueueFull):
            self._recv_queue.put_nowait(None)

    async def _open_offloaded(self, future: "asyncio.Future[bytes]") -> bytes:
        try:
            return await future
        except InvalidTag:
            error = HandshakeError("AEAD authentication failed (corrupted or replayed frame)")
            self._poison(error)
            raise error from None

    async def _reader_loop(self) -> None:
        error: BaseException
        try:
            while True:
                header = await self._reader.readexactly(4)
                (length,) = struct.unpack(">I", header)
                if length > MAX_FRAME_SIZE:
                    raise HandshakeError(f"oversized frame: {length}")
                ciphertext = await self._reader.readexactly(length)
                nonce = struct.pack("<4xQ", self._recv_counter)
                self._recv_counter += 1  # lint: single-writer — sole reader loop owns the nonce
                executor = _get_aead_executor()
                if executor is not None and length >= _OFFLOAD_THRESHOLD:
                    # wrap the executor future so an InvalidTag poisons the channel
                    # the moment the decrypt finishes — even if recv() never awaits
                    # this particular frame
                    opened = asyncio.ensure_future(
                        self._open_offloaded(
                            asyncio.get_running_loop().run_in_executor(
                                executor, self._open, nonce, ciphertext
                            )
                        )
                    )
                    # mark a never-awaited failure as retrieved (recv may have
                    # already raised on an earlier frame and stopped consuming)
                    opened.add_done_callback(lambda t: t.cancelled() or t.exception())
                else:
                    try:
                        opened = self._open(nonce, ciphertext)
                    except InvalidTag:
                        raise HandshakeError(
                            "AEAD authentication failed (corrupted or replayed frame)"
                        )
                await self._recv_queue.put(opened)  # bounded: backpressures the socket
        except asyncio.CancelledError:
            raise
        except BaseException as e:
            error = e
        if self._recv_error is None:  # don't overwrite an earlier poison error
            self._recv_error = error
        # a dead connection must also stop the writer (it may be parked on its queue)
        self._fail_send(error)
        if self._writer_task is not None:
            self._send_queue.put_nowait(None)
        await self._recv_queue.put(None)  # wake a parked recv()

    # ------------------------------------------------------------------ lifecycle

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._fail_send(ConnectionError("secure channel closed"))
        if self._recv_error is None:
            self._recv_error = ConnectionError("secure channel closed")
        for task in (self._writer_task, self._reader_task):
            if task is not None:
                task.cancel()
        with contextlib.suppress(Exception):
            self._recv_queue.put_nowait(None)  # wake a parked recv()
        try:
            self._writer.close()
        except Exception:
            pass

    async def wait_closed(self) -> None:
        try:
            await self._writer.wait_closed()
        except Exception:
            pass


async def _send_plain(writer: asyncio.StreamWriter, payload: bytes) -> None:
    writer.write(struct.pack(">I", len(payload)) + payload)
    await writer.drain()


async def _recv_plain(reader: asyncio.StreamReader, max_size: int = 4096) -> bytes:
    header = await reader.readexactly(4)
    (length,) = struct.unpack(">I", header)
    if length > max_size:
        raise HandshakeError(f"oversized handshake frame: {length}")
    return await reader.readexactly(length)


class _NullAEAD:
    """Cipher stand-in for daemon-proxied channels: the LOCAL hop to the native
    data-plane proxy carries plaintext frames (loopback trust boundary — exactly
    the reference's unix-socket hop to its Go daemon, p2p_daemon.py:84-147); the
    daemon performs the real ChaCha20-Poly1305 with the keys handed over in the
    'K' upgrade frame. Wire format and security toward the REMOTE peer are
    unchanged."""

    @staticmethod
    def encrypt(nonce: bytes, data: bytes, aad) -> bytes:
        return data

    @staticmethod
    def encrypt_parts(nonce: bytes, parts, aad) -> bytes:
        return b"".join(parts)

    @staticmethod
    def decrypt(nonce: bytes, data: bytes, aad) -> bytes:
        return data


async def handshake(
    reader: asyncio.StreamReader,
    writer: asyncio.StreamWriter,
    identity: Ed25519PrivateKey,
    is_initiator: bool,
    announced_addrs: Optional[list] = None,
    timeout: float = 15.0,
    proxy_upgrade: bool = False,
) -> Tuple[SecureChannel, dict]:
    """Perform the mutual-authentication handshake. Returns (channel, peer_hello_extras)
    where extras carries the peer's announced listen addresses.

    ``proxy_upgrade``: the stream runs through the native daemon's data-plane
    proxy ('X' mode): after deriving keys, hand them to the daemon in a 'K' frame
    and switch this end to plaintext framing — the daemon seals/opens every
    subsequent frame (including the key-confirmation exchange) in C++."""

    async def _run() -> Tuple[SecureChannel, dict]:
        ephemeral = X25519PrivateKey.generate()
        eph_pub = ephemeral.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw
        )
        # the signature covers the ENTIRE hello payload (not just the ephemeral), so a
        # MITM cannot rewrite the announced addresses without failing verification
        static_pub = identity.get_public_key().to_bytes()
        addrs = [str(a) for a in (announced_addrs or [])]
        signed_payload = MSGPackSerializer.dumps([static_pub, eph_pub, addrs, 1])
        hello = {
            "payload": signed_payload,
            "sig": identity.sign(_HANDSHAKE_PREFIX + signed_payload),
        }
        await _send_plain(writer, MSGPackSerializer.dumps(hello))
        peer_hello_outer = MSGPackSerializer.loads(await _recv_plain(reader))

        peer_payload = peer_hello_outer["payload"]
        peer_static_bytes, peer_eph_bytes, peer_addrs, peer_version = MSGPackSerializer.loads(peer_payload)
        peer_static = Ed25519PublicKey.from_bytes(peer_static_bytes)
        if not peer_static.verify(_HANDSHAKE_PREFIX + peer_payload, peer_hello_outer["sig"]):
            raise HandshakeError("peer failed static key proof")
        peer_hello = {"static": peer_static_bytes, "ephemeral": peer_eph_bytes, "addrs": peer_addrs}

        peer_eph = X25519PublicKey.from_public_bytes(peer_hello["ephemeral"])
        shared = ephemeral.exchange(peer_eph)
        okm = HKDF(
            algorithm=hashes.SHA256(), length=64, salt=b"hivemind-tpu-hs", info=b"channel-keys"
        ).derive(shared)
        initiator_key, responder_key = okm[:32], okm[32:]
        send_key, recv_key = (
            (initiator_key, responder_key) if is_initiator else (responder_key, initiator_key)
        )
        channel = SecureChannel(reader, writer, send_key, recv_key, peer_static)
        if proxy_upgrade:
            # hand the channel keys (and current counters — the confirm below is
            # the first sealed frame each way) to the local daemon, then go
            # plaintext on this hop: the daemon does the AEAD from here on
            upgrade = (
                b"K" + send_key + recv_key
                + struct.pack("<Q", channel._send_counter)
                + struct.pack("<Q", channel._recv_counter)
            )
            await _send_plain(writer, upgrade)
            channel._send_aead = _NullAEAD()  # type: ignore[assignment]
            channel._recv_aead = _NullAEAD()  # type: ignore[assignment]
        # key confirmation: proves the peer holds the ephemeral private key, which a
        # replayed hello cannot (helloes alone are replayable — sig covers only the
        # static prefix + own ephemeral). Both sides send first, then verify.
        try:
            await channel.send(b"confirm")
            if await channel.recv() != b"confirm":
                raise HandshakeError("peer failed key confirmation")
        except BaseException:
            channel.close()  # reap the pipeline tasks the confirm exchange started
            raise
        return channel, {"addrs": peer_hello.get("addrs", []), "static": peer_hello["static"]}

    try:
        return await asyncio.wait_for(_run(), timeout=timeout)
    except (ValueError, KeyError, TypeError, IndexError, struct.error) as e:
        # a malformed/hostile hello (bad msgpack, wrong shapes, junk key bytes)
        # must read as a handshake failure the acceptor already handles — not
        # crash the per-connection task with an unretrieved msgpack error
        raise HandshakeError(f"malformed handshake from peer: {e!r}") from e
