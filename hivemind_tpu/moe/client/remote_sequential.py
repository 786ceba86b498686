"""RemoteSequential: run a model as a CHAIN of remote transformer blocks served by
swarm peers — pipelined model parallelism over the DHT (the Petals-style capability
layered on the DMoE stack; the reference README positions Petals as the downstream
project built exactly this way on hivemind, README.md:35-40, and SURVEY §7.10 lists
it as the capability layer above the expert server).

Blocks are ordinary experts named ``{prefix}{index}`` ("gpt_block.0", "gpt_block.1",
…): any :class:`hivemind_tpu.moe.Server` can host any subset of blocks and declares
them in the DHT. The client resolves each index lazily, chains the blocks'
``RemoteExpert`` calls — each differentiable via custom_vjp — so ``jax.grad`` flows
through the WHOLE pipeline, and every backward RPC also trains the server-side block
(ModuleBackend on_backward semantics). A failed block call triggers re-resolution
(a replacement server re-declaring the same uid takes over transparently)."""

from __future__ import annotations

import random
import threading
import time
import zlib
from typing import Dict, Optional

import jax

from hivemind_tpu.dht import DHT
from hivemind_tpu.moe.client.expert import RemoteExpert
from hivemind_tpu.moe.expert_uid import ExpertInfo
from hivemind_tpu.moe.server.dht_handler import get_experts
from hivemind_tpu.p2p import PeerID
from hivemind_tpu.resilience import RetryPolicy
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.loop import get_loop_runner

logger = get_logger(__name__)


class _ResilientBlock(RemoteExpert):
    """A RemoteExpert whose RPCs retry with DHT re-resolution INSIDE forward_np /
    backward_np — i.e. inside the pure_callback — so failover covers the backward
    pass of jax.grad and jitted execution, not just the eager forward dispatch."""

    def __init__(self, sequential: "RemoteSequential", index: int, info: ExpertInfo):
        super().__init__(info, sequential.p2p,
                         request_compression=sequential.request_compression)
        self._sequential = sequential
        self._index = index

    def _with_retries(self, operation):
        def on_retry(retry_index: int, error: BaseException) -> None:
            logger.warning(
                f"block {self.uid} via {self.peer_id} failed (attempt {retry_index + 1}): {error!r}"
            )
            fresh = self._sequential._resolve_info(self._index, force=True)
            self.expert_info = fresh
            with self._info_lock:
                self._info = None  # schema may differ on the new server

        try:
            return self._sequential.retry_policy.execute_sync(operation, on_retry=on_retry)
        except Exception as last_error:
            raise RuntimeError(f"block {self.uid} failed after retries") from last_error

    def forward_np(self, *xs):
        return self._with_retries(lambda: RemoteExpert.forward_np(self, *xs))

    def backward_np(self, *tensors):
        return self._with_retries(lambda: RemoteExpert.backward_np(self, *tensors))

    @property
    def info(self):
        # the schema fetch at dispatch time must fail over too
        return self._with_retries(lambda: RemoteExpert.info.fget(self))


class RemoteSequential:
    """See module docstring.

    :param prefix: block uid prefix incl. trailing delimiter, e.g. ``"gpt_block."``
    :param num_blocks: pipeline depth; block i is expert ``{prefix}{i}``
    :param update_period: re-resolve a cached block after this many seconds
    :param max_retries: per block call: failures before giving up (each retry
        re-resolves the uid from the DHT first)
    """

    def __init__(
        self,
        dht: DHT,
        prefix: str,
        num_blocks: int,
        *,
        update_period: float = 30.0,
        max_retries: int = 2,
        max_failover_history: int = 4096,
        request_compression: Optional[str] = None,
    ):
        self.dht, self.prefix, self.num_blocks = dht, prefix, num_blocks
        self.update_period, self.max_retries = update_period, max_retries
        # wire-dtype override for every block request; None = negotiate each
        # server's advertised codec (ISSUE 10)
        self.request_compression = request_compression
        # decode failover retains each session's input history for re-prefill; the
        # cap bounds client memory (past it, failover degrades to the pre-r4
        # raise-and-reset behavior for that session). 0 disables retention.
        self.max_failover_history = max_failover_history
        self.p2p = get_loop_runner().run_coroutine(dht.replicate_p2p())
        self._blocks: Dict[int, _ResilientBlock] = {}
        self._infos: Dict[int, ExpertInfo] = {}
        self._resolved_at: Dict[int, float] = {}
        self._span_support: Dict[object, bool] = {}  # peer_id -> server groups spans
        # session_id -> {"route": pinned block handles, "chunks": list of input
        # chunks retained for failover re-prefill (None = over the retention cap),
        # "later": the same for the passes after the first of a looped model's loop,
        # pass -> list (a model of one pass has none), "positions": retained position
        # count, all passes together, "chunked": the longest chunk of more than one
        # position that CONTINUED the session (0: the prompt came whole), "passes": the
        # blocks' `decode_passes`, asked of the servers when a call first names a pass}
        self._decode_routes: Dict[str, dict] = {}
        self.max_decode_routes = 256  # oldest pinned routes drop beyond this
        # seeded replica choice across route resolutions (ISSUE 13): fresh
        # clients spread over the replica set instead of all pinning the first
        # declared server, yet each client's choices replay deterministically
        self._route_rng = random.Random(zlib.crc32(f"{prefix}|{self.p2p.peer_id}".encode()))
        self._lock = threading.Lock()

    @property
    def retry_policy(self) -> RetryPolicy:
        """Every retry loop in this client shares one declared policy (ISSUE 3):
        short equal-jittered backoff — a replacement server needs a beat to
        re-declare the uid, and synchronized clients must not re-dial in
        lockstep. Derived lazily from ``max_retries`` so changing it (or tests
        building partial instances) stays honored."""
        policy = self.__dict__.get("_retry_policy")
        if policy is None or policy.max_attempts != self.max_retries + 1:
            policy = RetryPolicy(
                max_attempts=self.max_retries + 1,
                base_delay=0.25,
                backoff=2.0,
                max_delay=2.0,
                jitter="equal",
                name="remote_sequential",
            )
            self.__dict__["_retry_policy"] = policy
        return policy

    def __len__(self) -> int:
        return self.num_blocks

    def block_uid(self, index: int) -> str:
        return f"{self.prefix}{index}"

    def _resolve_info(self, index: int, force: bool = False) -> ExpertInfo:
        with self._lock:
            fresh_enough = time.monotonic() - self._resolved_at.get(index, -1e9) < self.update_period
            cached = self._infos.get(index)
            if not force and cached is not None and fresh_enough:
                return cached
        [info] = get_experts(self.dht, [self.block_uid(index)])
        if info is None:
            raise RuntimeError(f"no server declares block {self.block_uid(index)!r}")
        with self._lock:
            self._infos[index] = info
            self._resolved_at[index] = time.monotonic()
        return info

    def _block(self, index: int) -> _ResilientBlock:
        info = self._resolve_info(index)
        with self._lock:
            block = self._blocks.get(index)
            if block is None:
                block = self._blocks[index] = _ResilientBlock(self, index, info)
            elif block.expert_info != info:
                block.expert_info = info  # route refreshed by update_period
                with block._info_lock:
                    block._info = None
            return block

    def _call_block(self, index: int, x: jax.Array) -> jax.Array:
        return self._block(index)(x)

    def _peer_supports_spans(self, head: RemoteExpert) -> bool:
        """Capability negotiation for mixed swarms: a span-unaware server would run
        only the head block and silently return its output as the whole span's —
        so multi-block groups require the server to advertise span_support."""
        supported = self._span_support.get(head.peer_id)
        if supported is None:
            try:
                supported = bool(head.info.get("span_support"))
            except Exception:
                # transient info failure: assume no spans THIS grouping, but do not
                # cache the negative — a single failed fetch must not disable span
                # grouping for this peer for the process lifetime
                return False
            with self._lock:
                self._span_support[head.peer_id] = supported
        return supported

    def _select_block_replica(
        self, info: ExpertInfo, preferred: Optional[PeerID]
    ) -> ExpertInfo:
        """Pick this block's serving replica (ISSUE 13): breaker-open replicas
        are avoided (a killed server must drop out of fresh routes instantly),
        the PREVIOUS block's peer is kept when it also hosts this block (span
        grouping — one RPC per server, not per block), then the shared
        replica-health policy (expert.classify_replicas) decides, with a
        seeded-random pick while cold."""
        replicas = info.replica_set
        if len(replicas) == 1:
            return info
        from hivemind_tpu.moe.client.call_many import EXPERT_BREAKERS
        from hivemind_tpu.moe.client.expert import classify_replicas

        measured, cold, failing, banned = classify_replicas(
            info.uid, replicas, EXPERT_BREAKERS
        )
        live = [replica for _rate, _mean, replica in measured] + cold + failing
        pool = live if live else list(replicas)
        chosen = None
        if preferred is not None:
            for replica in pool:
                if replica.peer_id == preferred:
                    chosen = replica
                    break
        if chosen is None:
            if measured:
                chosen = measured[0][2]
            else:
                chosen = self._route_rng.choice(cold or pool)
        return ExpertInfo(info.uid, chosen.peer_id, chosen.compression, info.replicas)

    def _grouped_range(self, start: int, stop: int, force: bool = False):
        """Resolve blocks [start, stop) and group CONSECUTIVE same-peer blocks into
        spans: each group is one RPC (server chains the blocks — span execution).
        Replicated blocks prefer staying on the previous block's peer so spans
        survive replication (see _select_block_replica)."""
        blocks = []
        preferred: Optional[PeerID] = None
        for index in range(start, stop):
            chosen = self._select_block_replica(
                self._resolve_info(index, force=force), preferred
            )
            blocks.append(
                RemoteExpert(chosen, self.p2p, request_compression=self.request_compression)
            )
            preferred = chosen.peer_id
        groups = []
        for block in blocks:
            if (
                groups
                and groups[-1][0].peer_id == block.peer_id
                and self._peer_supports_spans(groups[-1][0])
            ):
                groups[-1][1].append(block.uid)
            else:
                groups.append((block, [block.uid]))
        for head, uids in groups:
            head.span = uids if len(uids) > 1 else None
        return groups

    def _span_forward(self, start: int, stop: int, x):
        """Each attempt restarts from the ORIGINAL input: a mid-chain failure would
        otherwise retry the whole range on a partially-advanced activation, silently
        double-applying the blocks that already ran (corrupting the custom_vjp
        primal on exactly the failover path the retry exists for)."""
        attempt_counter = [0]

        def one_attempt():
            force = attempt_counter[0] > 0
            attempt_counter[0] += 1
            current = x
            for head, _uids in self._grouped_range(start, stop, force=force):
                current = head.forward_np(current)[0]
            return current

        def on_retry(retry_index: int, error: BaseException) -> None:
            logger.warning(f"span forward [{start}, {stop}) failed (attempt {retry_index + 1}): {error!r}")

        try:
            return self.retry_policy.execute_sync(one_attempt, on_retry=on_retry)
        except Exception as last_error:
            raise RuntimeError(f"span forward [{start}, {stop}) failed after retries") from last_error

    def _span_backward(self, start: int, stop: int, x, grad):
        """Chained backward over the range. With one co-located span the server does
        everything in a single RPC; across several servers the boundary activations
        are recovered with one forward sweep first (the client keeps no residuals).

        Every backward RPC steps the serving blocks' optimizers, so a retry must
        NEVER replay a group whose backward already succeeded — progress is tracked
        as a shrinking [start, remaining) range and only the remainder is retried
        (forward sweeps are side-effect-free and safe to re-run)."""
        state = {"remaining": stop, "grad": grad, "attempt": 0}

        def one_attempt():
            force = state["attempt"] > 0
            state["attempt"] += 1
            if state["remaining"] <= start:
                return state["grad"]
            groups = self._grouped_range(start, state["remaining"], force=force)
            boundary_inputs, current = [], x
            for head, _uids in groups:
                boundary_inputs.append(current)
                if head is not groups[-1][0]:
                    current = head.forward_np(current)[0]
            for (head, uids), block_input in zip(reversed(groups), reversed(boundary_inputs)):
                state["grad"] = head.backward_np(block_input, state["grad"])[0]
                state["remaining"] -= len(uids)  # this group's optimizers have stepped
            return state["grad"]

        def on_retry(retry_index: int, error: BaseException) -> None:
            logger.warning(
                f"span backward [{start}, {state['remaining']}) failed (attempt {retry_index + 1}): {error!r}"
            )

        try:
            return self.retry_policy.execute_sync(one_attempt, on_retry=on_retry)
        except Exception as last_error:
            raise RuntimeError(f"span backward [{start}, {stop}) failed after retries") from last_error

    def __call__(self, x: jax.Array, start: int = 0, stop: Optional[int] = None) -> jax.Array:
        """Run blocks [start, stop) in order; differentiable end to end. Co-located
        consecutive blocks execute as server-side spans (one RPC per SERVER, not per
        block — both directions), with re-resolution retries inside the callbacks."""
        import numpy as np

        import jax.numpy as jnp

        stop = stop if stop is not None else self.num_blocks
        if start >= stop:
            return x
        out_schemas = self._block(stop - 1).info["outputs_schema"]
        assert len(out_schemas) == 1, "RemoteSequential chains single-tensor blocks"
        # blocks preserve batch and sequence dims; only the FEATURE dim follows the
        # server's schema (whose leading dims reflect its sample batch, not ours)
        out_struct = jax.ShapeDtypeStruct((*x.shape[:-1], out_schemas[0].shape[-1]), jnp.float32)
        sequential = self

        @jax.custom_vjp
        def remote_span(x):
            return jax.pure_callback(
                lambda a: np.asarray(
                    sequential._span_forward(start, stop, np.asarray(a)), np.float32
                ),
                out_struct,
                x,
            )

        def fwd(x):
            return remote_span(x), x

        def bwd(residual_x, g):
            grad_struct = jax.ShapeDtypeStruct(residual_x.shape, jnp.float32)
            grad = jax.pure_callback(
                lambda a, gg: np.asarray(
                    sequential._span_backward(start, stop, np.asarray(a), np.asarray(gg)),
                    np.float32,
                ),
                grad_struct,
                residual_x,
                g,
            )
            return (grad.astype(residual_x.dtype),)

        remote_span.defvjp(fwd, bwd)
        return remote_span(x)

    def decode_step(self, x, session_id: str, reset: bool = False, loop_pass: int = 0):
        """Chain one KV-cache decode-session step through every block: the prefill
        call (``reset=True``) seeds each block's session with the prompt chunk
        [batch, prompt_len, hid], later calls advance a single token
        [batch, 1, hid] — O(context) per token vs the O(context²) right-padded
        ``__call__`` decode. A long prompt may arrive in CHUNKS: a later call of more
        than one position continues the session where the last one ended, on servers
        whose blocks take chunks (``decode_takes_chunks``; any other block raises). Sessions are STICKY to the peers resolved at prefill
        (the periodic DHT re-resolution must not silently move a session to a
        cache-less peer), but a dead pinned peer fails over TRANSPARENTLY
        (VERDICT r3 #3, Petals-class behavior): the client retains each session's
        full input history, re-resolves the route, re-prefills every group on the
        replacement peers from that history, and continues the stream — the caller
        never sees a reset, and emitted positions are identical to an
        uninterrupted run (the re-prefill is deterministic).

        ``loop_pass``: which pass of a LOOPED model's loop the call is. Such a model's
        blocks run ``decode_passes`` times a token (what the servers' ``rpc_info`` says; a
        pass beyond it raises here), pass u on the cache that pass u wrote, and a token
        is that many walks of the route, each taking what the caller made of the walk
        before (the norm between the passes is the CALLER's: this class does not learn
        the model). The route is pinned at the reset of pass 0 and shared by the passes;
        a ``reset`` at a later pass starts that pass over inside the session. The
        failover history is kept A PASS (``max_failover_history`` bounds the positions of
        all of them together), and a failover rebuilds every pass's cache on the
        replacement peers from it, in pass order."""
        import numpy as np

        x = np.asarray(x, np.float32)
        if reset and not loop_pass:
            # pin the route with FRESH immutable handles: _ResilientBlock objects
            # are shared and re-pointed in place by the periodic re-resolution, so
            # pinning them would let the route silently move to a cache-less peer.
            # Consecutive blocks on the SAME peer form a span served by one RPC
            # (Petals-style span execution): per-token round-trips = #servers.
            route = self._grouped_range(0, self.num_blocks)
            with self._lock:
                # a reset REUSES the prior state's lock (atomically, under the
                # global lock): an in-flight step on the old state then finishes
                # before this reset's server-side prefill runs, so a failed old
                # step cannot fail over AFTER the reset and clobber the fresh
                # server sessions with the stale history
                prior = self._decode_routes.get(session_id)
                state = {
                    "route": route,
                    "chunks": [],
                    "later": {},
                    "positions": 0,
                    "chunked": 0,
                    "passes": None,
                    "lock": prior["lock"] if prior is not None else threading.Lock(),
                }
                self._decode_routes[session_id] = state
                while len(self._decode_routes) > self.max_decode_routes:
                    self._decode_routes.pop(next(iter(self._decode_routes)))  # oldest
        else:
            with self._lock:
                state = self._decode_routes.get(session_id)
            if state is None:
                raise RuntimeError(
                    f"decode session {session_id!r} has no pinned route here; "
                    f"start it with reset=True"
                )
        # the per-session lock serializes concurrent decode_steps on the SAME
        # session (advisor r4: an unguarded concurrent step could fail over with a
        # half-appended chunk list); different sessions still decode in parallel.
        # KV positions are inherently ordered, so serializing is the only sound
        # semantics for same-session concurrency anyway.
        with state["lock"]:
            if loop_pass:  # a call that names no pass asks nothing: it is served as it always was
                if state["passes"] is None:
                    state["passes"] = int(state["route"][0][0].info.get("decode_passes", 1))
                if not 0 <= loop_pass < state["passes"]:
                    raise ValueError(f"pass {loop_pass} of a pipeline whose blocks hold {state['passes']} pass(es) a session")
            # history retention: a LIST of chunks a pass (concatenated only at failover, so a
            # long generation costs O(1) per step, not an O(context) recopy), capped by
            # max_failover_history — past the cap, retention stops and a dead peer is
            # a hard error again (restart with reset=True), bounding client memory
            step_appended = False
            if not reset and x.shape[1] > 1:
                state["chunked"] = max(state["chunked"], x.shape[1])
            if state["chunks"] is not None:
                if reset and loop_pass:  # this pass starts over, and with it the passes that take it as their input
                    for later in [u for u in state["later"] if u >= loop_pass]:
                        state["positions"] -= sum(chunk.shape[1] for chunk in state["later"].pop(later))
                if self.max_failover_history and state["positions"] + x.shape[1] <= self.max_failover_history:
                    retained = state["later"].setdefault(loop_pass, []) if loop_pass else state["chunks"]
                    retained.append(x)
                    state["positions"] += x.shape[1]
                    step_appended = not reset
                else:  # retention disabled (cap 0), or over the cap: no failover for this session from here
                    state["chunks"], state["later"], state["positions"] = None, {}, 0
            try:
                out = x
                groups_advanced = 0
                for block, span in state["route"]:
                    out = block.decode_np(out, session_id, reset=reset, span=span, loop_pass=loop_pass)
                    groups_advanced += 1
            except Exception as e:
                from hivemind_tpu.telemetry.serving import is_overload_error

                if is_overload_error(e) and groups_advanced == 0:
                    # a typed shed (fair-share admission / bounded queue) is NOT
                    # a dead peer: the server session is intact and re-prefilling
                    # would only spend more of the very budget that ran out.
                    # Undo this step's history append so the caller can back off
                    # and retry the same step cleanly, and surface the shed.
                    # ONLY valid when no group advanced — a shed deeper in the
                    # pipeline means upstream groups already appended this step
                    # to their KV sessions, and a clean retry would double-feed
                    # them (silent divergence); that case falls through to the
                    # full re-prefill failover below, which rebuilds every
                    # group's cache consistently (or fails loudly).
                    if step_appended:
                        retained.pop()
                        state["positions"] -= x.shape[1]
                    raise
                if state["chunks"] is None:
                    raise  # history over the retention cap (or disabled): no failover
                logger.warning(
                    f"decode session {session_id!r} lost a pinned peer ({e!r}); "
                    f"failing over: re-resolving the route and re-prefilling from "
                    f"{state['positions']} retained positions of {1 + len(state['later'])} pass(es)"
                )
                try:
                    out = self._decode_failover(session_id, state, np.concatenate(state["chunks"], axis=1), loop_pass)
                except Exception:
                    # a FAILED failover leaves surviving servers' caches re-prefilled to
                    # an unknown point and this chunk already in the history: the
                    # session is unusable — forget it so a caller retry gets the
                    # explicit "start with reset=True" error instead of silent
                    # divergence
                    with self._lock:
                        self._decode_routes.pop(session_id, None)
                    raise
                if not reset:
                    out = out[:, -x.shape[1]:]  # the caller expects this step's positions only
        return out

    def _decode_failover(self, session_id: str, state: dict, history, loop_pass: int = 0) -> "np.ndarray":
        """Re-resolve the pipeline and re-prefill EVERY group from the retained
        input history (surviving groups simply rebuild identical caches; the
        replacement peer builds its first): ``history``, and then the later passes'
        of a looped model (``state["later"]``), pass by pass in the loop's order (a pass
        may not run ahead of the one before it). Each
        group's prefill output is the next group's input, so one sweep a pass both
        recovers the caches and computes the current step: what comes back is the
        output of ``loop_pass``, the pass of the call that failed. Retries with forced
        re-resolution (a replacement server may take a moment to re-declare the uid)."""
        import numpy as np

        histories = {0: history, **{u: np.concatenate(chunks, axis=1) for u, chunks in sorted(state.get("later", {}).items())}}

        def one_attempt():
            route = self._grouped_range(0, self.num_blocks, force=True)
            outs = []
            for u, history in histories.items():
                # a prompt that arrived in chunks is re-sent in chunks (no longer than the
                # longest the session sent: what its servers were shown to take), each chunk
                # through every group before the next; a prompt that came whole goes whole
                size = state["chunked"] or history.shape[1]
                for start in range(0, history.shape[1], size):
                    out = history[:, start:start + size]
                    for block, span in route:
                        out = block.decode_np(out, session_id, reset=start == 0, span=span, loop_pass=u)
                    if u == loop_pass:
                        outs.append(np.asarray(out, np.float32))
            state["route"] = route
            return np.concatenate(outs, axis=1)

        def on_retry(retry_index: int, error: BaseException) -> None:
            logger.warning(
                f"decode failover for {session_id!r} failed (attempt {retry_index + 1}): {error!r}"
            )

        try:
            return self.retry_policy.execute_sync(one_attempt, on_retry=on_retry)
        except Exception as last_error:
            raise RuntimeError(
                f"decode session {session_id!r} could not fail over after retries"
            ) from last_error

    def close_decode_session(self, session_id: str) -> None:
        """Forget a pinned decode route and its retained history (the server side
        expires by TTL/LRU)."""
        with self._lock:
            self._decode_routes.pop(session_id, None)

    def block_scorecards(self) -> Dict[str, dict]:
        """Per-block serving scorecards (ISSUE 9): this client's observed
        success rate / latency quantiles / timeouts / sheds for each pipeline
        block it has called — which block (and therefore which server) is
        degrading the pipeline, from the caller's side."""
        from hivemind_tpu.telemetry.serving import SCORECARDS

        cards = SCORECARDS.export()
        return {
            uid: cards[uid]
            for uid in (self.block_uid(index) for index in range(self.num_blocks))
            if uid in cards
        }

    def decode_capacity(self) -> Optional[int]:
        """The tightest ``decode_max_len`` across the pipeline's current servers
        (each advertises it via rpc_info), or None if a block lacks sessions."""
        capacities = [
            self._block(index).info.get("decode_max_len") for index in range(self.num_blocks)
        ]
        return None if any(c is None for c in capacities) else min(capacities)

    def __getitem__(self, index: int):
        """A callable handle to one block (e.g. for partial pipelines)."""
        if not (0 <= index < self.num_blocks):
            raise IndexError(index)
        return lambda x: self._call_block(index, x)

    def __repr__(self):
        return f"RemoteSequential({self.prefix!r}, {self.num_blocks} blocks)"
