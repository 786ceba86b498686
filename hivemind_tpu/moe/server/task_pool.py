"""Batching queues between RPC handlers and the device runtime (capability parity:
reference hivemind/moe/server/task_pool.py:59-256 — there a fork with shared-memory
transfer; here an asyncio queue in the single-process runtime).

Saturation semantics (ISSUE 9): the queue is BOUNDED — past ``max_queue_size``
waiting tasks a submit is *shed* with a typed :class:`ServerOverloadedError`
(counted in ``hivemind_moe_shed_total{pool}``; the client's expert breakers
recognize the type across the RPC boundary), so an overloaded server answers
"no, now" instead of queueing unboundedly toward a timeout. Queue depth and
oldest-task age are gauged on submit AND drain, each task is stamped with its
queue-wait / batch-assembly / device-compute phases (accrued onto the active
``serving.request`` span for the ServingLedger), and every batch observes the
occupancy it ran at (samples ÷ max_batch_size)."""

from __future__ import annotations

import asyncio
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, List, Optional, Sequence, Tuple

import numpy as np

from hivemind_tpu.telemetry import REGISTRY as _TELEMETRY
from hivemind_tpu.telemetry.serving import accrue_span_phase
from hivemind_tpu.telemetry.tracing import current_span
from hivemind_tpu.telemetry.tracing import trace_sync as _trace_sync
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.timed_storage import get_dht_time

logger = get_logger(__name__)

# saturation + phase metrics (docs/observability.md "Serving"): sampled on the
# submit/drain path, so the queue is visible while it GROWS, not only after a
# drain happens to run
_QUEUE_DEPTH = _TELEMETRY.gauge(
    "hivemind_moe_pool_queue_depth", "tasks waiting in a pool (sampled on submit and drain)",
    ("pool",),
)
_QUEUE_AGE = _TELEMETRY.gauge(
    "hivemind_moe_queue_age_seconds", "age of the oldest task waiting in a pool", ("pool",)
)
_QUEUE_WAIT = _TELEMETRY.histogram(
    "hivemind_moe_queue_wait_seconds", "submit-to-drain wait of one task", ("pool",)
)
_SHEDS = _TELEMETRY.counter(
    "hivemind_moe_shed_total",
    "tasks shed because the pool's bounded queue was full (ServerOverloadedError)",
    ("pool",),
)
_OCCUPANCY = _TELEMETRY.histogram(
    "hivemind_moe_batch_occupancy",
    "samples per device batch / max_batch_size (1.0 = the batch dimension is full)",
    ("pool",),
    buckets=(0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
)
# counted by the pool in its executor thread BEFORE a batch's futures resolve: a
# caller that has its result always finds its batch counted, whatever becomes of
# the Runtime's loop in between (a shutdown cancels it)
_BATCHES = _TELEMETRY.counter(
    "hivemind_moe_batches_total", "batches processed", ("pool",)
)
_SAMPLES = _TELEMETRY.counter(
    "hivemind_moe_samples_total", "samples processed", ("pool",)
)
_BLOCKS = _TELEMETRY.counter(
    "hivemind_moe_pool_blocks_total",
    "blocks that the pool's batches walked: a span chain's length for every batch",
    ("pool",),
)
_BATCH_LATENCY = _TELEMETRY.histogram(
    "hivemind_moe_batch_latency_seconds", "device time of one batch", ("pool",)
)
_CANCELLED_SKIPPED = _TELEMETRY.counter(
    "hivemind_moe_pool_cancelled_skipped_total",
    "queued tasks dropped at drain time because their caller already gave up "
    "(hedge loser cancelled through the mux, abandoned deadline) — compute saved",
    ("pool",),
)


class ServerOverloadedError(RuntimeError):
    """The pool's bounded queue is full: this request was shed. Clients should
    back off (the expert's circuit breaker counts sheds as failures)."""


# every live pool, so read-time consumers (the serving ledger's saturation
# view) can refresh the gauges on demand: during a FULL stall nothing submits
# or drains, and event-driven sampling alone would freeze the age gauge at its
# last pre-stall value — exactly when the operator needs it most
_LIVE_POOLS: "weakref.WeakSet[TaskPool]" = weakref.WeakSet()


def sample_all_pool_gauges() -> None:
    """Refresh depth/age gauges for every live pool (thread-safe best effort)."""
    for pool in list(_LIVE_POOLS):
        pool._sample_gauges()


@dataclass
class _Task:
    args: Tuple[np.ndarray, ...]
    future: asyncio.Future
    timestamp: float = field(default_factory=get_dht_time)
    # phase stamps (perf_counter; ISSUE 9 attribution): queue-wait is
    # submitted->popped, assembly/compute/occupancy are shared per device batch
    submitted_pc: float = field(default_factory=time.perf_counter)
    popped_pc: Optional[float] = None
    assembly_s: Optional[float] = None
    compute_s: Optional[float] = None
    stage_s: Optional[float] = None  # of compute_s: the backend's host<->device staging
    occupancy: Optional[float] = None

    @property
    def batch_size(self) -> int:
        return self.args[0].shape[0]


class TaskPool:
    """Collects tasks for one processing function; the Runtime drains the
    highest-priority pool (priority = oldest undispatched task, reference
    task_pool.py:169-176). A server's pools are keyed by direction and span chain
    (connection_handler.py): a task is one request for the whole chain, and
    ``process_func`` walks the chain's ``blocks`` on the device in one call."""

    def __init__(
        self,
        process_func: Callable[..., Sequence[np.ndarray]],
        name: str,
        *,
        max_batch_size: int = 4096,
        min_batch_size: int = 1,
        flush_timeout: float = 0.1,
        max_queue_size: int = 1024,
        blocks: int = 1,
    ):
        self.process_func = process_func
        self.name = name
        self.blocks = blocks  # how many blocks one batch walks (the span chain's length)
        self.max_batch_size = max_batch_size
        self.min_batch_size = min_batch_size
        self.flush_timeout = flush_timeout  # sub-min batches run anyway after this age
        self.max_queue_size = max_queue_size  # queued tasks beyond this are SHED
        # deque: submit appends right, drain pops left — O(1) per task where the
        # old list.pop(0) was O(n) under load; priority still reads [0] (oldest)
        self._queue: Deque[_Task] = deque()
        # reused batch-assembly buffers, keyed (arg index, bucket, trailing
        # shape, dtype) — see _batch_buffer
        self._batch_buffers: dict = {}
        self._task_added: Optional[asyncio.Event] = None
        # cached metric children (pool names are stable for the pool's lifetime)
        self._depth_gauge = _QUEUE_DEPTH.labels(name)
        self._age_gauge = _QUEUE_AGE.labels(name)
        self._wait_histogram = _QUEUE_WAIT.labels(name)
        self._shed_counter = _SHEDS.labels(name)
        self._occupancy_histogram = _OCCUPANCY.labels(name)
        self._cancelled_counter = _CANCELLED_SKIPPED.labels(name)
        self.batches_counter = _BATCHES.labels(name)
        self.samples_counter = _SAMPLES.labels(name)
        self._blocks_counter = _BLOCKS.labels(name)
        self.latency_histogram = _BATCH_LATENCY.labels(name)
        _LIVE_POOLS.add(self)

    def _event(self) -> asyncio.Event:
        if self._task_added is None:
            self._task_added = asyncio.Event()
        return self._task_added

    def _sample_gauges(self) -> None:
        self._depth_gauge.set(len(self._queue))
        try:
            # may run off-loop (sample_all_pool_gauges): guard the popleft race
            oldest = self._queue[0].timestamp
        except IndexError:
            oldest = None
        self._age_gauge.set(max(get_dht_time() - oldest, 0.0) if oldest is not None else 0.0)

    async def submit_task(self, *args: np.ndarray) -> Sequence[np.ndarray]:
        """Enqueue one task; resolves with its slice of the batched output.
        Sheds (ServerOverloadedError) when the bounded queue is full."""
        batch_size = args[0].shape[0]
        if batch_size > self.max_batch_size:
            raise ValueError(f"task of {batch_size} items exceeds max_batch_size={self.max_batch_size}")
        if len(self._queue) >= self.max_queue_size:
            self._shed_counter.inc()
            self._sample_gauges()
            raise ServerOverloadedError(
                f"pool {self.name!r} is overloaded: {len(self._queue)} tasks queued "
                f"(max_queue_size={self.max_queue_size}); request shed"
            )
        task = _Task(tuple(np.asarray(a) for a in args), asyncio.get_event_loop().create_future())
        self._queue.append(task)
        self._sample_gauges()
        self._event().set()
        outputs = await task.future
        # phase attribution onto the active serving.request span (ISSUE 9)
        if task.popped_pc is not None:
            queue_wait = max(task.popped_pc - task.submitted_pc, 0.0)
            self._wait_histogram.observe(queue_wait)
            accrue_span_phase("queue_wait_s", queue_wait)
        if task.assembly_s is not None:
            accrue_span_phase("assembly_s", task.assembly_s)
        if task.compute_s is not None:
            accrue_span_phase("compute_s", task.compute_s)
        if task.stage_s is not None:
            accrue_span_phase("stage_s", task.stage_s)
        if task.occupancy is not None:
            span = current_span()
            if span is not None:
                # a request that went through several pools keeps the
                # WORST-occupancy one (the under-filled batch is the lever a
                # reader wants named; a span chain is one pool, so one hop)
                previous = (span.attributes or {}).get("occupancy")
                if previous is None or task.occupancy < float(previous):
                    span.set("occupancy", task.occupancy)
                    span.set("pool", self.name)
        return outputs

    @property
    def queue_size(self) -> int:
        """Tasks currently waiting (telemetry: hivemind_moe_pool_queue_depth)."""
        return len(self._queue)

    @property
    def priority(self) -> float:
        """Lower is more urgent: timestamp of the oldest queued task. A queue below
        min_batch_size is deprioritized only until its oldest task exceeds
        flush_timeout — never starved (the reference flushes partial batches too)."""
        if not self._queue:
            return float("inf")
        total = sum(t.batch_size for t in self._queue)
        oldest = self._queue[0].timestamp
        if total < self.min_batch_size and get_dht_time() - oldest < self.flush_timeout:
            return float("inf")
        return oldest

    def pop_batch(self) -> List[_Task]:
        """Remove up to max_batch_size samples' worth of tasks. Tasks whose
        future is already done (the caller was cancelled — a hedge's losing
        request RESET through the mux, an abandoned deadline) are dropped here
        instead of burning a device-batch slot on an answer nobody will read."""
        batch, total = [], 0
        popped_at = time.perf_counter()
        while self._queue and total + self._queue[0].batch_size <= self.max_batch_size:
            task = self._queue.popleft()
            if task.future.done():
                self._cancelled_counter.inc()
                continue
            task.popped_pc = popped_at
            batch.append(task)
            total += task.batch_size
        self._sample_gauges()
        if self._task_added is not None and not self._queue:
            self._task_added.clear()
        return batch

    async def wait_for_tasks(self) -> None:
        await self._event().wait()

    def _batch_buffer(self, arg_index: int, bucket: int, sample: np.ndarray) -> np.ndarray:
        """The reusable batch-assembly buffer for one argument position at one
        power-of-two bucket size (ISSUE 10: the per-batch ``np.concatenate``
        allocated + copied every batch; now tasks write once into a buffer that
        matches the backend's one-executable-per-bucket jit cache, so the
        backend's own pad-to-bucket step becomes a no-op). Safe to reuse:
        batches run one at a time on the Runtime's executor, and process_func
        copies to device before the next batch overwrites it."""
        key = (arg_index, bucket, sample.shape[1:], sample.dtype.str)
        buffer = self._batch_buffers.get(key)
        if buffer is None:
            if len(self._batch_buffers) >= 32:
                # trailing shapes are request-controlled (e.g. per-client seq
                # lengths): bound retention — these are pure caches, so a clear
                # only costs the next batches one allocation each
                self._batch_buffers.clear()
            buffer = self._batch_buffers[key] = np.zeros(
                (bucket, *sample.shape[1:]), sample.dtype
            )
        return buffer

    def process_batch(self, tasks: List[_Task]) -> None:
        """Run process_func on the assembled batch; split outputs per task as
        zero-copy views. Called from the Runtime's executor thread via
        call_soon_threadsafe plumbing."""
        total = sum(t.batch_size for t in tasks)
        with _trace_sync("pool.batch", pool=self.name, rows=total, tasks=len(tasks), blocks=self.blocks) as span:
            self._process_batch(tasks, total, span)

    def _process_batch(self, tasks: List[_Task], total: int, span) -> None:
        from hivemind_tpu.moe.server.module_backend import bucket_batch_size

        num_args = len(tasks[0].args)
        assembly_start = time.perf_counter()
        if len(tasks) == 1:
            # single-task batch (the per-token decode/forward common case):
            # pass the task's own arrays straight through — zero copies here
            joined: List[np.ndarray] = list(tasks[0].args)
            batch_len = total
        else:
            # copy-free batching: one write per task into the reused bucket
            # buffer (vs concatenate-allocate + the backend's pad copy)
            batch_len = bucket_batch_size(total, self.max_batch_size)
            joined = []
            for i in range(num_args):
                buffer = self._batch_buffer(i, batch_len, tasks[0].args[i])
                offset = 0
                for task in tasks:
                    buffer[offset : offset + task.batch_size] = task.args[i]
                    offset += task.batch_size
                if offset < batch_len:
                    # stale rows from the previous batch must not leak into the
                    # padding (a backward pool's optimizer update sums over them)
                    buffer[offset:batch_len] = 0
                joined.append(buffer)
        compute_start = time.perf_counter()
        outputs = self.process_func(*joined)
        compute_end = time.perf_counter()
        if isinstance(outputs, np.ndarray):
            outputs = [outputs]
        # a process_func returning the wrong leading dim used to mis-slice:
        # some tasks silently received truncated/empty outputs — fail the whole
        # batch loudly instead (the Runtime routes this into fail_batch).
        # Outputs must cover the submitted batch; bucket-padded rows beyond
        # `total` are sliced away below and never reach a task.
        for index, out in enumerate(outputs):
            out_len = np.asarray(out).shape[0] if np.ndim(out) else 0
            if out_len not in (total, batch_len):
                raise ValueError(
                    f"pool {self.name!r}: process_func output {index} has leading "
                    f"dim {out_len} but the batch holds {total} samples "
                    f"({len(tasks)} tasks, padded to {batch_len}) — refusing to "
                    f"mis-slice per-task outputs"
                )
        assembly_s = compute_start - assembly_start
        compute_s = compute_end - compute_start
        # the backend's staging spans accrue their seconds onto the batch's span
        stage_s = (span.attributes or {}).get("stage_s") if span is not None else None
        occupancy = round(total / max(self.max_batch_size, 1), 4)
        self._occupancy_histogram.observe(occupancy)
        self.batches_counter.inc()
        self.samples_counter.inc(total)
        self._blocks_counter.inc(self.blocks)
        self.latency_histogram.observe(compute_end - assembly_start)
        offset = 0
        for task in tasks:
            size = task.batch_size
            task.assembly_s = assembly_s
            task.compute_s = compute_s
            task.stage_s = stage_s
            task.occupancy = occupancy
            task_out = [np.asarray(out[offset : offset + size]) for out in outputs]
            offset += size
            if not task.future.done():
                task.future.get_loop().call_soon_threadsafe(
                    lambda t=task, o=task_out: t.future.done() or t.future.set_result(o)
                )

    def fail_batch(self, tasks: List[_Task], exc: BaseException) -> None:
        for task in tasks:
            if not task.future.done():
                task.future.get_loop().call_soon_threadsafe(
                    lambda t=task: t.future.done() or t.future.set_exception(exc)
                )
