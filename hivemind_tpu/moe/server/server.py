"""The expert server (capability parity: reference hivemind/moe/server/server.py:35-411).

Owns: a DHT peer, ModuleBackends, the batching Runtime, the RPC handler, a periodic
expert-declaration task, and optionally a CheckpointSaver — all asyncio components in
one process (the reference forks handlers and pools; SURVEY §1 'process model')."""

from __future__ import annotations

import asyncio
import contextlib
import random
import threading
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from hivemind_tpu.dht import DHT
from hivemind_tpu.moe.expert_uid import UID_DELIMITER, is_valid_prefix, is_valid_uid
from hivemind_tpu.moe.server.checkpoints import CheckpointSaver, load_experts
from hivemind_tpu.moe.server.connection_handler import ConnectionHandler
from hivemind_tpu.moe.server.dht_handler import declare_experts, get_experts
from hivemind_tpu.moe.server.layers import name_to_block, name_to_input
from hivemind_tpu.moe.server.module_backend import ModuleBackend
from hivemind_tpu.moe.server.runtime import Runtime
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.asyncio_utils import spawn
from hivemind_tpu.utils.loop import LoopRunner, get_loop_runner
from hivemind_tpu.utils.timed_storage import get_dht_time

logger = get_logger(__name__)


class Server:
    """Create with Server.create(...); call .run_in_background() / .shutdown()."""

    def __init__(
        self,
        dht: DHT,
        backends: Dict[str, ModuleBackend],
        *,
        update_period: float = 30.0,
        checkpoint_dir: Optional[Path] = None,
        decode_max_len: int = 256,
        decode_max_sessions: int = 64,
        max_queue_size: int = 1024,
        activation_compression: str = "float16",
        client_rate: Optional[float] = None,
        client_burst: Optional[float] = None,
        replica_slots: int = 0,
        replicate_hot_experts: bool = False,
        replication_policy=None,
        replication_watch_grids: Optional[Sequence[str]] = None,
        loop_runner: Optional[LoopRunner] = None,
    ):
        self.dht, self.backends = dht, backends
        self.update_period = update_period
        self.handler = ConnectionHandler(
            backends, decode_max_len=decode_max_len, decode_max_sessions=decode_max_sessions,
            max_queue_size=max_queue_size, activation_compression=activation_compression,
            client_rate=client_rate, client_burst=client_burst,
        )
        self.runtime = Runtime(self.handler.all_pools())
        self.handler.on_new_pool = self.runtime.add_pool  # a span chain's pool is made on its first request
        self.checkpoint_saver = (
            CheckpointSaver(backends, checkpoint_dir) if checkpoint_dir is not None else None
        )
        # hot-expert replication (ISSUE 13): advertise hot local experts and/or
        # acquire other servers' hot experts into spare replica slots
        self.replication = None
        if replicate_hot_experts or replica_slots > 0:
            from hivemind_tpu.moe.server.replication import ReplicationManager

            self.replication = ReplicationManager(
                self, replica_slots=replica_slots, policy=replication_policy,
                watch_grids=replication_watch_grids,
            )
        self._runner = loop_runner if loop_runner is not None else get_loop_runner()
        self._declare_task: Optional[asyncio.Task] = None
        self._ready = threading.Event()

    @classmethod
    def create(
        cls,
        *,
        num_experts: Optional[int] = None,
        expert_uids: Optional[Sequence[str]] = None,
        expert_pattern: Optional[str] = None,
        expert_cls: str = "ffn",
        hidden_dim: int = 1024,
        expert_kwargs: Optional[dict] = None,
        optim_factory=None,
        max_batch_size: int = 4096,
        initial_peers: Sequence[str] = (),
        dht: Optional[DHT] = None,
        checkpoint_dir: Optional[Path] = None,
        decode_max_len: int = 256,
        decode_max_sessions: int = 64,
        max_queue_size: int = 1024,
        activation_compression: str = "float16",
        client_rate: Optional[float] = None,
        client_burst: Optional[float] = None,
        replica_slots: int = 0,
        replicate_hot_experts: bool = False,
        replication_policy=None,
        replication_watch_grids: Optional[Sequence[str]] = None,
        start: bool = False,
        **backend_kwargs,
    ) -> "Server":
        """Build a server with experts from the layer registry; UIDs are either given
        or sampled from ``expert_pattern`` (e.g. 'ffn.[0:256].[0:256]') and
        deduplicated against the DHT (reference server.py:351-411).

        ``expert_kwargs`` are forwarded to the expert class constructor — e.g.
        ``expert_cls='llama_block', expert_kwargs={'num_kv_heads': 2}`` serves
        grouped-query Llama blocks."""
        import optax

        if dht is None:
            dht = DHT(initial_peers=initial_peers, start=True)
        if expert_uids is None:
            if num_experts is None and replica_slots > 0:
                expert_uids = []  # replica-only volunteer: starts empty, acquires hot experts
            else:
                assert num_experts is not None, "provide either expert_uids or num_experts"
                expert_uids = _generate_uids(num_experts, expert_pattern or f"expert.[0:{2**30}]", dht)
        optim_factory = optim_factory or (lambda: optax.adam(1e-3))

        backends = {}
        for uid in expert_uids:
            module = name_to_block[expert_cls](hidden_dim, **(expert_kwargs or {}))
            sample = name_to_input[expert_cls](4, hidden_dim)
            # multi-tensor experts (e.g. det_dropout) declare a tuple of inputs
            sample_kwargs = (
                {"sample_inputs": sample} if isinstance(sample, tuple) else {"sample_input": sample}
            )
            backends[uid] = ModuleBackend(
                uid, module, optimizer=optim_factory(), **sample_kwargs,
                max_batch_size=max_batch_size, **backend_kwargs,
            )
            # registry-built experts are replicable over rpc_replica_state: the
            # spec lets an acquiring server reconstruct the module before
            # loading the transferred state_dict (moe/server/replication.py)
            backends[uid].replication_spec = {
                "expert_cls": expert_cls, "hidden_dim": hidden_dim,
                "expert_kwargs": dict(expert_kwargs or {}),
                "max_batch_size": max_batch_size,
            }
        if checkpoint_dir is not None:
            loaded = load_experts(backends, checkpoint_dir)
            if loaded:
                logger.info(f"restored {loaded} experts from {checkpoint_dir}")
        server = cls(dht, backends, checkpoint_dir=checkpoint_dir, decode_max_len=decode_max_len,
                     decode_max_sessions=decode_max_sessions, max_queue_size=max_queue_size,
                     activation_compression=activation_compression,
                     client_rate=client_rate, client_burst=client_burst,
                     replica_slots=replica_slots, replicate_hot_experts=replicate_hot_experts,
                     replication_policy=replication_policy,
                     replication_watch_grids=replication_watch_grids)
        if start:
            server.run_in_background(await_ready=True)
        return server

    # ------------------------------------------------------------------ lifecycle

    def run_in_background(self, await_ready: bool = True, timeout: Optional[float] = None) -> None:
        future = self._runner.run_coroutine(self._start(), return_future=True)
        if await_ready:
            future.result(timeout)

    async def _start(self) -> None:
        # a stalled loop stops expert RPC dispatch AND batch draining at once:
        # arm the watchdog with the server (idempotent; the DHT shares the loop)
        from hivemind_tpu.telemetry.watchdog import ensure_watchdog

        ensure_watchdog(asyncio.get_event_loop())
        await self.handler.add_p2p_handlers(await self.dht.replicate_p2p())
        self.runtime.start()
        if self.checkpoint_saver is not None:
            self.checkpoint_saver.start()
        if self.replication is not None:
            self.replication.start()
        self._declare_task = spawn(self._declare_periodically(), name="server.declare_periodically")
        self._ready.set()

    async def add_backend(self, uid: str, backend: ModuleBackend) -> None:
        """Register an expert acquired at runtime (replication): handler pools
        + runtime + an immediate declaration, so clients resolve the grown
        replica set without waiting a full update period. Runs on the server
        loop (the ReplicationManager's)."""
        self.handler.add_backend(uid, backend)  # its pools reach the runtime through on_new_pool
        declare_experts(
            self.dht, [uid],
            expiration_time=get_dht_time() + self.update_period * 3,
            wait=False,
            compression=self.handler.activation_compression,
        )

    async def _declare_periodically(self) -> None:
        while True:
            with contextlib.suppress(Exception):
                declare_experts(
                    self.dht, list(self.backends.keys()),
                    expiration_time=get_dht_time() + self.update_period * 3,
                    wait=False,
                    # the declaration carries the wire dtype, so clients learn
                    # the negotiated codec from discovery alone (ISSUE 10)
                    compression=self.handler.activation_compression,
                )
            await asyncio.sleep(self.update_period)

    def shutdown(self) -> None:
        async def _stop():
            if self._declare_task is not None:
                self._declare_task.cancel()
            if self.replication is not None:
                self.replication.shutdown()
            self.runtime.shutdown()
            if self.checkpoint_saver is not None:
                self.checkpoint_saver.shutdown()
            with contextlib.suppress(Exception):
                await self.handler.remove_p2p_handlers(await self.dht.replicate_p2p())

        with contextlib.suppress(Exception):
            self._runner.run_coroutine(_stop(), return_future=True).result(5.0)

    def __enter__(self):
        if not self._ready.is_set():
            self.run_in_background(await_ready=True)
        return self

    def __exit__(self, *args):
        self.shutdown()


def _generate_uids(num_experts: int, expert_pattern: str, dht: DHT, attempts_per_expert: int = 10) -> List[str]:
    """Sample unique UIDs matching 'prefix.[0:N].[0:M]'-style patterns, skipping UIDs
    already claimed in the DHT (reference server.py:351-411)."""
    import re

    def sample_uid() -> str:
        out = []
        for block in expert_pattern.split(UID_DELIMITER):
            match = re.fullmatch(r"\[(\d+):(\d+)\]", block)
            out.append(str(random.randint(int(match.group(1)), int(match.group(2)) - 1)) if match else block)
        return UID_DELIMITER.join(out)

    chosen: List[str] = []
    attempts = 0
    while len(chosen) < num_experts and attempts < num_experts * attempts_per_expert:
        attempts += 1
        candidates = list({sample_uid() for _ in range(num_experts - len(chosen))} - set(chosen))
        if not candidates:
            continue
        existing = get_experts(dht, candidates)
        for uid, info in zip(candidates, existing):
            if info is None and is_valid_uid(uid):
                chosen.append(uid)
    assert len(chosen) >= num_experts, f"could only allocate {len(chosen)}/{num_experts} unique uids"
    return chosen[:num_experts]


@contextlib.contextmanager
def background_server(**kwargs):
    """Spin up a server for tests/benchmarks; yields (dht, server)
    (reference server.py:308-348)."""
    server = Server.create(start=True, **kwargs)
    try:
        yield server.dht, server
    finally:
        server.shutdown()
        server.dht.shutdown()
