"""The device executor: drains task pools by priority and runs their jitted
processing functions (capability parity: reference hivemind/moe/server/runtime.py:22-199
— there a thread juggling fork pipes; here an asyncio task + executor thread so device
dispatch never blocks the event loop)."""

from __future__ import annotations

import asyncio
import time
from typing import Dict, Optional, Sequence, Tuple

from hivemind_tpu.moe.server.task_pool import TaskPool
from hivemind_tpu.utils.asyncio_utils import run_in_executor
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.asyncio_utils import spawn

logger = get_logger(__name__)

# layer-5 telemetry (docs/observability.md): batch failures and drain-loop
# utilization. Per-pool throughput and batch latency are counted by the pools
# (task_pool.py, with the queue gauges), so one scrape sees the same numbers the
# periodic log line reports
from hivemind_tpu.telemetry import REGISTRY as _TELEMETRY

_BATCH_FAILURES = _TELEMETRY.counter(
    "hivemind_moe_batch_failures_total", "batches whose processing function raised", ("pool",)
)
_UTILIZATION = _TELEMETRY.gauge(
    "hivemind_moe_runtime_utilization",
    "fraction of the drain loop's recent wall time spent processing batches "
    "(1.0 = the device executor never idles; sampled over ~5 s windows)",
)


_WAIT_SECONDS = _TELEMETRY.counter(
    "hivemind_moe_runtime_wait_seconds_total",
    "seconds the drain loop had no batch to give the device: from 'no pool holds a "
    "task' until one does (on a profiler trace this and the hand-over seconds are the "
    "idle time outside every pool.batch span)",
)
_HANDOVER_SECONDS = _TELEMETRY.counter(
    "hivemind_moe_runtime_handover_seconds_total",
    "seconds from 'a pool holds a task and the executor is free' to 'its batch is handed "
    "to the executor': the drain loop's own turn-around, on an event loop it shares with "
    "the handlers; with the wait seconds, the time between two batches",
)


def _totals(pool: TaskPool) -> Tuple[float, float, float]:
    """(batches, samples, seconds) the pool has counted; it counts them itself,
    before a batch's callers see their results (task_pool.py)."""
    return (pool.batches_counter.value, pool.samples_counter.value, pool.latency_histogram.sum)


class Runtime:
    def __init__(self, pools: Sequence[TaskPool], stats_report_interval: Optional[float] = 60.0):
        self.pools = list(pools)
        self.stats_report_interval = stats_report_interval
        self._task: Optional[asyncio.Task] = None
        # set by add_pool so the drain loop's wait wakes for pools registered
        # mid-wait (ISSUE 13 replication) without any polling timeout
        self._pools_changed = asyncio.Event()
        self._last_report = time.perf_counter()
        # drain-loop utilization (ISSUE 9): busy seconds over a rolling window —
        # 1.0 with growing queues means the device executor is the bottleneck;
        # low utilization with deep queues points at dispatch, not compute
        self._utilization_window = 5.0
        self._busy_s = 0.0
        self._busy_anchor = time.perf_counter()
        # cumulative (batches, samples, seconds) at the last report, per pool —
        # the pools' counters hold process-lifetime totals; the log line shows deltas.
        # Seeded from the CURRENT totals: the counters are process-global, so a
        # second Runtime reusing a pool name must not replay its predecessor's
        # work as one giant first interval.
        self._reported: Dict[str, Tuple[float, float, float]] = {
            pool.name: _totals(pool) for pool in self.pools
        }

    def start(self) -> None:
        self._task = spawn(self._run(), name="runtime.run")

    def add_pool(self, pool: TaskPool) -> None:
        """Register a pool created after start() (ISSUE 13 expert replication:
        a server acquires a hot expert at runtime). Runs on the runtime's own
        loop; `_pools_changed` wakes the drain wait so the new pool is picked
        up immediately."""
        if pool in self.pools:
            return
        self.pools.append(pool)
        self._pools_changed.set()
        self._reported.setdefault(pool.name, _totals(pool))

    async def _run(self) -> None:
        # the loop awaits here: counters, not annotations. Between two batches the executor
        # is free since `free_since`; the seconds until a pool holds a task are wait, the
        # seconds from then until the batch is handed over are hand-over
        starved_since: Optional[float] = None
        free_since = time.perf_counter()
        while True:
            if starved_since is None and not any(pool.queue_size for pool in self.pools):
                starved_since = time.perf_counter()
            if not self.pools:
                # a replica-slot server starts empty and gains pools at runtime
                self._pools_changed.clear()  # lint: single-writer — loop clears its own wake event
                await self._pools_changed.wait()
                continue
            self._pools_changed.clear()
            waiters = [asyncio.create_task(pool.wait_for_tasks()) for pool in self.pools]
            # a pool added mid-wait (add_pool) has no waiter in this set — its
            # event wakes the wait so the next iteration picks the new pool up
            # immediately, with no polling timeout on idle servers
            waiters.append(asyncio.create_task(self._pools_changed.wait()))
            try:
                await asyncio.wait(waiters, return_when=asyncio.FIRST_COMPLETED)
            finally:
                for waiter in waiters:
                    waiter.cancel()
            pool = min(self.pools, key=lambda p: p.priority)
            if pool.priority == float("inf"):
                self._account_busy(0.0)  # idle windows drive the gauge to 0
                await asyncio.sleep(0.001)
                continue
            batch = pool.pop_batch()
            if not batch:
                continue
            start = time.perf_counter()
            held_since = free_since
            if starved_since is not None:
                # the batch's oldest task ended the starvation (a task stamps its submission)
                held_since = max(min(task.submitted_pc for task in batch), starved_since)
                _WAIT_SECONDS.inc(held_since - starved_since)
                starved_since = None
            _HANDOVER_SECONDS.inc(start - held_since)
            try:
                await run_in_executor(pool.process_batch, batch)
            except Exception as e:
                logger.warning(f"pool {pool.name}: batch failed with {e!r}")
                _BATCH_FAILURES.inc(pool=pool.name)
                pool.fail_batch(batch, e)
                free_since = time.perf_counter()
                self._account_busy(free_since - start)
                continue
            free_since = time.perf_counter()
            self._account_busy(free_since - start)
            self._maybe_report_stats()

    def _account_busy(self, elapsed: float) -> None:
        """Utilization gauge: busy seconds / wall seconds over ~5 s windows."""
        self._busy_s += elapsed
        now = time.perf_counter()
        window = now - self._busy_anchor
        if window >= self._utilization_window:
            _UTILIZATION.set(round(min(self._busy_s / window, 1.0), 4))
            self._busy_s = 0.0
            self._busy_anchor = now

    def _maybe_report_stats(self) -> None:
        """StatsReporter parity (reference runtime.py:161-199): periodic per-pool
        batch size / throughput logging, computed as deltas over the registry's
        cumulative counters."""
        if self.stats_report_interval is None:
            return
        now = time.perf_counter()
        if now - self._last_report < self.stats_report_interval:
            return
        self._last_report = now
        for pool in sorted(self.pools, key=lambda pool: pool.name):
            name, totals = pool.name, _totals(pool)
            last = self._reported.get(name, (0.0, 0.0, 0.0))
            batches, samples, seconds = (t - l for t, l in zip(totals, last))
            self._reported[name] = totals
            if batches:
                logger.info(
                    f"[{name}] {int(batches)} batches, avg size {samples / batches:.1f}, "
                    f"{samples / max(seconds, 1e-9):.0f} samples/s device time"
                )
        try:
            from hivemind_tpu.utils.profiling import device_memory_stats

            memory = device_memory_stats()
            if memory.get("bytes_in_use"):
                used, limit = memory["bytes_in_use"], memory.get("bytes_limit", 0)
                logger.info(
                    f"[device] HBM {used / 2**30:.2f} GiB in use"
                    + (f" / {limit / 2**30:.2f} GiB" if limit else "")
                )
        except Exception:
            pass  # CPU backends expose no memory stats

    def shutdown(self) -> None:
        if self._task is not None:
            self._task.cancel()
