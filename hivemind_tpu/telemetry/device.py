"""Device-side observability (ISSUE 19): the accelerator's sibling of the
ledger/watchdog stack.

Every telemetry layer before this one watches the *host* — asyncio loops, wire
bytes, span trees — while the device was a black box: nothing counted jit
recompiles, live HBM or host↔device transfer cost. Two instruments fix that:

- :class:`JitCompileTracker` — fed by :func:`~hivemind_tpu.utils.profiling.tracked_jit`
  wrappers around every hot jit entry point (and by ``jax.monitoring`` compile
  events where the jaxlib exposes them). Records every compile's site, abstract
  signature, and duration; detects **recompile storms** (N compiles of one site
  inside a window → loud warning, exactly once per window) — the decode-bucket
  and batching paths are the known at-risk sites.
- :class:`DeviceMemoryMonitor` — live-buffer bytes / peak per device from
  ``jax.live_arrays()`` plus ``device.memory_stats()`` where available, sampled
  by the watchdog tick (never imports jax itself: a process that has not paid
  for a backend must not start paying because telemetry looked). A
  monotonic-growth heuristic flags suspected leaks across averaging rounds.

Counting (tracked_jit, :func:`record_transfer`) is always-on and hot-path
cheap; :func:`arm_device_telemetry` additionally hooks the watchdog memory
sampler and the ``jax.monitoring`` listener. Everything surfaces through
:func:`device_snapshot` (DHT peer snapshot / hivemind-top device board) and
through device listeners (the black-box spool's ``device`` frames).
"""

from __future__ import annotations

import math
import sys
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from hivemind_tpu.telemetry.registry import REGISTRY
from hivemind_tpu.telemetry import tracing as _tracing
from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_COMPILES = REGISTRY.counter(
    "hivemind_device_compiles_total",
    "jit compiles observed, by site (a tracked_jit label or 'jax' for "
    "unattributed jax.monitoring events)",
    ("site",),
)
_COMPILE_SECONDS = REGISTRY.histogram(
    "hivemind_device_compile_seconds",
    "wall seconds per observed jit compile (tracked_jit measures the whole "
    "triggering call: trace + lower + compile)",
    ("site",),
)
_STORMS = REGISTRY.counter(
    "hivemind_device_recompile_storms_total",
    "recompile storms detected: >= storm_threshold compiles of one site inside "
    "storm_window_s (fires once per window per site)",
    ("site",),
)
_MEMORY_BYTES = REGISTRY.gauge(
    "hivemind_device_memory_bytes",
    "live jax buffer bytes per device (from jax.live_arrays; each device counts "
    "the shard it holds, so a replicated array counts in full on every device)",
    ("device",),
)
_MEMORY_PEAK_BYTES = REGISTRY.gauge(
    "hivemind_device_memory_peak_bytes",
    "peak device memory per device: backend peak_bytes_in_use where the "
    "runtime exposes it (TPU/GPU), else the high-water mark of sampled live bytes",
    ("device",),
)
_LIVE_BUFFERS = REGISTRY.gauge(
    "hivemind_device_live_buffers",
    "live jax arrays per device at the last watchdog sample",
    ("device",),
)
_LEAKS = REGISTRY.counter(
    "hivemind_device_memory_leak_suspected_total",
    "times the monotonic-growth heuristic fired: live bytes grew on every one "
    "of leak_samples consecutive watchdog samples by >= leak_min_growth total",
)
_TRANSFER = REGISTRY.counter(
    "hivemind_device_transfer_bytes_total",
    "bytes crossing the host<->device boundary on instrumented hot paths "
    "(expert batch upload/download, decode KV steps, state averaging mirrors)",
    ("direction",),
)

# cached children: record_transfer sits on per-batch/per-token paths
_TRANSFER_H2D = _TRANSFER.labels(direction="host_to_device")
_TRANSFER_D2H = _TRANSFER.labels(direction="device_to_host")

_H2D = "host_to_device"
_D2H = "device_to_host"

# Prometheus counters are process-cumulative by contract, but device_snapshot()
# promises "empty when nothing device-side has happened" after a reset — so the
# snapshot view subtracts the baseline captured by reset_device_telemetry().
_TRANSFER_BASELINE = {_H2D: 0, _D2H: 0}

# device-record listeners: the black-box spool subscribes here so compile /
# storm / leak / memory records survive a crash as ``device`` frames
_DEVICE_LISTENERS: List[Callable[[str, Dict[str, Any]], None]] = []


def add_device_listener(listener: Callable[[str, Dict[str, Any]], None]) -> None:
    """Subscribe ``listener(kind, record)`` to device telemetry records. Kinds:
    ``compile`` | ``storm`` | ``memory`` | ``leak``."""
    if listener not in _DEVICE_LISTENERS:
        _DEVICE_LISTENERS.append(listener)


def remove_device_listener(listener: Callable[[str, Dict[str, Any]], None]) -> None:
    try:
        _DEVICE_LISTENERS.remove(listener)
    except ValueError:
        pass


def _notify(kind: str, record: Dict[str, Any]) -> None:
    for listener in list(_DEVICE_LISTENERS):
        try:
            listener(kind, record)
        except Exception as e:  # a broken subscriber must not break the hot path
            logger.warning(f"device listener failed on {kind}: {e!r}")


def record_transfer(nbytes: int, direction: str) -> None:
    """Account ``nbytes`` crossing the host↔device boundary. Direction is
    ``host_to_device`` or ``device_to_host``. One cached-child counter inc —
    cheap enough for per-batch and per-token call sites."""
    if nbytes <= 0:
        return
    if direction == _H2D:
        _TRANSFER_H2D.inc(nbytes)
    elif direction == _D2H:
        _TRANSFER_D2H.inc(nbytes)
    else:
        raise ValueError(f"unknown transfer direction {direction!r}")


def transfer_totals() -> Dict[str, int]:
    """Bytes transferred since the last :func:`reset_device_telemetry` (the raw
    ``hivemind_device_transfer_bytes_total`` counters never reset)."""
    return {
        _H2D: int(_TRANSFER_H2D.value) - _TRANSFER_BASELINE[_H2D],
        _D2H: int(_TRANSFER_D2H.value) - _TRANSFER_BASELINE[_D2H],
    }


# ------------------------------------------------------------------ compiles


class JitCompileTracker:
    """Process-wide compile ledger. ``tracked_jit`` wrappers report every cache
    miss here; ``jax.monitoring`` events (armed processes) accrue as the
    un-attributed ``jax`` site. Detects recompile storms: ``storm_threshold``
    compiles of one site within ``storm_window_s`` fires a loud warning and a
    counter — exactly once per window, so a runaway site cannot also flood the
    logs."""

    def __init__(self, storm_threshold: int = 5, storm_window_s: float = 60.0):
        self.storm_threshold = int(storm_threshold)
        self.storm_window_s = float(storm_window_s)
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._seconds: Dict[str, float] = {}
        self._signatures: Dict[str, str] = {}  # last abstract signature per site
        self._recent: Dict[str, deque] = {}  # site -> recent compile timestamps
        self._storm_fired_at: Dict[str, float] = {}
        self._storms = 0
        self._last: Optional[Dict[str, Any]] = None

    def record_compile(
        self, site: str, duration_s: float = 0.0, signature: Optional[str] = None
    ) -> None:
        now = _tracing.telemetry_time()
        storm = False
        with self._lock:
            self._counts[site] = self._counts.get(site, 0) + 1
            self._seconds[site] = self._seconds.get(site, 0.0) + float(duration_s)
            if signature:
                self._signatures[site] = signature
            recent = self._recent.get(site)
            if recent is None:
                recent = self._recent[site] = deque(maxlen=max(self.storm_threshold * 4, 16))
            recent.append(now)
            in_window = sum(1 for t in recent if now - t <= self.storm_window_s)
            if in_window >= self.storm_threshold:
                fired = self._storm_fired_at.get(site)
                if fired is None or now - fired >= self.storm_window_s:
                    self._storm_fired_at[site] = now
                    self._storms += 1
                    storm = True
            record = {
                "site": site,
                "count": self._counts[site],
                "dur_s": round(float(duration_s), 6),
                "signature": signature,
            }
            self._last = record
        _COMPILES.inc(site=site)
        _COMPILE_SECONDS.observe(float(duration_s), site=site)
        span = _tracing.current_span()
        if span is not None:
            span.add_event("device.compile", site=site, dur_ms=round(duration_s * 1e3, 3))
        if storm:
            _STORMS.inc(site=site)
            logger.warning(
                f"RECOMPILE STORM at jit site {site!r}: >= {self.storm_threshold} compiles "
                f"within {self.storm_window_s:.0f}s (total {self._counts[site]}; last "
                f"signature {signature!r}) — the abstract signature is churning; bucket "
                f"shapes or hoist the jit (docs/observability.md 'Device telemetry')"
            )
            _notify("storm", {"site": site, "count": self._counts[site]})
        _notify("compile", record)

    def record_jax_event(self, event: str, duration_s: float) -> None:
        """Un-attributed compile-flavored ``jax.monitoring`` event (e.g. backend
        compile time). Accrued under the reserved site ``jax`` — kept out of the
        per-site storm detector (one user-visible site can emit several backend
        events per compile)."""
        with self._lock:
            self._counts["jax"] = self._counts.get("jax", 0) + 1
            self._seconds["jax"] = self._seconds.get("jax", 0.0) + float(duration_s)
        _COMPILES.inc(site="jax")
        _COMPILE_SECONDS.observe(float(duration_s), site="jax")

    # ------------------------------------------------------------- inspection

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def total(self, include_jax_events: bool = False) -> int:
        """Cumulative compiles across sites (the benchmark steady-state mark).
        ``jax.monitoring`` backend events are excluded by default so the count
        matches 'distinct tracked_jit cache misses'."""
        with self._lock:
            return sum(
                count
                for site, count in self._counts.items()
                if include_jax_events or site != "jax"
            )

    def storm_count(self) -> int:
        with self._lock:
            return self._storms

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            sites = {
                site: {
                    "count": count,
                    "seconds": round(self._seconds.get(site, 0.0), 4),
                    **(
                        {"signature": self._signatures[site]}
                        if site in self._signatures
                        else {}
                    ),
                }
                for site, count in sorted(self._counts.items())
            }
            return {
                "total": sum(self._counts.values()),
                "seconds": round(sum(self._seconds.values()), 4),
                "storms": self._storms,
                "sites": sites,
                "last": dict(self._last) if self._last else None,
            }

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._seconds.clear()
            self._signatures.clear()
            self._recent.clear()
            self._storm_fired_at.clear()
            self._storms = 0
            self._last = None


COMPILE_TRACKER = JitCompileTracker()


# ------------------------------------------------------------------- memory


class DeviceMemoryMonitor:
    """Live/peak device memory, sampled from whatever jax state already exists.

    ``sample()`` NEVER imports jax or initializes a backend: it reads
    ``sys.modules`` (the same discipline as the watchdog's executor sampler) and
    walks ``jax.live_arrays()`` — so a lightweight process pays nothing, and a
    jax process pays one python loop per watchdog tick. Peak per device is the
    backend's ``peak_bytes_in_use`` where the runtime exposes one (TPU/GPU),
    else a host-side high-water mark of sampled live bytes (CPU).

    Leak heuristic: live bytes strictly grew on ``leak_samples`` consecutive
    samples AND the total growth exceeds ``leak_min_growth`` bytes → warn +
    counter, then restart the episode (no refiring every tick)."""

    def __init__(self, leak_samples: int = 8, leak_min_growth: int = 8 << 20):
        self.leak_samples = int(leak_samples)
        self.leak_min_growth = int(leak_min_growth)
        self._lock = threading.Lock()
        self._trend: deque = deque(maxlen=max(self.leak_samples, 2))
        self._peak: Dict[str, int] = {}
        self._leaks = 0
        self.last_sample: Optional[Dict[str, Any]] = None

    def sample(self, modules=None) -> Optional[Dict[str, Any]]:
        jax = (modules if modules is not None else sys.modules).get("jax")
        if jax is None:
            return None
        try:
            arrays = jax.live_arrays()
        except Exception:
            return None
        per_device: Dict[str, List[int]] = {}  # device -> [bytes, buffers]
        device_objs: Dict[str, Any] = {}
        for array in arrays:
            try:
                sharding = array.sharding
                devices = sharding.addressable_devices
                # what EACH device holds: its shard, which for a replicated array
                # is the whole array — not an even split of the global bytes
                shard_bytes = math.prod(sharding.shard_shape(array.shape)) * array.dtype.itemsize
            except Exception:
                continue  # deleted/donated buffers can race the walk
            for device in devices:
                key = str(device)
                entry = per_device.setdefault(key, [0, 0])
                entry[0] += shard_bytes
                entry[1] += 1
                device_objs.setdefault(key, device)
        snapshot: Dict[str, Any] = {"devices": {}, "total_bytes": 0, "buffers": 0}
        with self._lock:
            for key, (nbytes, buffers) in sorted(per_device.items()):
                stats = None
                try:
                    stats = device_objs[key].memory_stats()
                except Exception:
                    stats = None
                backend_peak = int((stats or {}).get("peak_bytes_in_use", 0))
                self._peak[key] = max(self._peak.get(key, 0), nbytes, backend_peak)
                entry = {"bytes": nbytes, "buffers": buffers, "peak_bytes": self._peak[key]}
                if stats and "bytes_in_use" in stats:
                    entry["backend_bytes_in_use"] = int(stats["bytes_in_use"])
                snapshot["devices"][key] = entry
                snapshot["total_bytes"] += nbytes
                snapshot["buffers"] += buffers
                _MEMORY_BYTES.set(nbytes, device=key)
                _MEMORY_PEAK_BYTES.set(self._peak[key], device=key)
                _LIVE_BUFFERS.set(buffers, device=key)
            self._trend.append(snapshot["total_bytes"])
            leak = (
                len(self._trend) == self._trend.maxlen
                and all(b > a for a, b in zip(self._trend, list(self._trend)[1:]))
                and self._trend[-1] - self._trend[0] >= self.leak_min_growth
            )
            if leak:
                self._leaks += 1
                growth = self._trend[-1] - self._trend[0]
                self._trend.clear()  # restart the episode: fire once, not every tick
            self.last_sample = snapshot
        if leak:
            _LEAKS.inc()
            logger.warning(
                f"suspected device memory leak: live buffer bytes grew monotonically "
                f"across {self.leak_samples} samples (+{growth} bytes, now "
                f"{snapshot['total_bytes']}) — check for caches pinned across "
                f"averaging rounds"
            )
            _notify("leak", {"growth_bytes": growth, "total_bytes": snapshot["total_bytes"]})
        return snapshot

    def leak_count(self) -> int:
        with self._lock:
            return self._leaks

    def reset(self) -> None:
        with self._lock:
            self._trend.clear()
            self._peak.clear()
            self._leaks = 0
            self.last_sample = None


MEMORY_MONITOR = DeviceMemoryMonitor()


# -------------------------------------------------------------------- lanes

COMPUTE_SPAN_NAMES = frozenset({"optimizer.update", "device.compute", "moe.forward", "moe.backward"})
# allreduce.round, averaging.matchmaking, averaging.aggregate and their children
_COMM_LANE_PREFIXES = ("allreduce.", "averaging.")


def span_lane(name: str) -> Optional[str]:
    """Perfetto lane for a span name: ``comm`` / ``compute`` / None (default
    lane). Used by the chrome-trace exports to render compute-vs-comm rows."""
    if name in COMPUTE_SPAN_NAMES:
        return "compute"
    if name.startswith(_COMM_LANE_PREFIXES):
        return "comm"
    return None


# ------------------------------------------------------------------ snapshot


def device_snapshot() -> Dict[str, Any]:
    """The ``device`` section of the DHT peer snapshot / hivemind-top board:
    compile totals per site, last memory sample, transfer totals. Empty dict
    when nothing device-side has happened (lightweight peers publish no device
    section at all)."""
    out: Dict[str, Any] = {}
    compiles = COMPILE_TRACKER.summary()
    if compiles["total"]:
        out["compiles"] = compiles
    memory = MEMORY_MONITOR.last_sample
    if memory:
        out["memory"] = memory
    if MEMORY_MONITOR.leak_count():
        out["leaks_suspected"] = MEMORY_MONITOR.leak_count()
    transfers = transfer_totals()
    if any(transfers.values()):
        out["transfer_bytes"] = transfers
    return out


def compact_device_snapshot(section: Dict[str, Any]) -> Dict[str, Any]:
    """Shrink a device section for snapshot budgets: drop per-site compile
    detail and the per-device memory map, keep the headline numbers."""
    out: Dict[str, Any] = {}
    compiles = section.get("compiles")
    if compiles:
        out["compiles"] = {
            "total": compiles.get("total"),
            "seconds": compiles.get("seconds"),
            "storms": compiles.get("storms"),
        }
    memory = section.get("memory")
    if memory:
        out["memory"] = {
            "total_bytes": memory.get("total_bytes"),
            "buffers": memory.get("buffers"),
        }
    for key in ("leaks_suspected", "transfer_bytes"):
        if key in section:
            out[key] = section[key]
    return out


# -------------------------------------------------------------------- arming

_MONITORING_INSTALLED = False
_ARMED = False


def _watchdog_sampler() -> None:
    MEMORY_MONITOR.sample()
    memory = MEMORY_MONITOR.last_sample
    if memory:
        _notify("memory", memory)


def _install_jax_monitoring() -> None:
    """Hook ``jax.monitoring`` compile-duration events into the tracker.
    Install-once per process: the trampoline stays registered and the tracker's
    reset() is what tests rely on."""
    global _MONITORING_INSTALLED
    if _MONITORING_INSTALLED:
        return
    jax = sys.modules.get("jax")
    if jax is None:
        return  # never import jax for telemetry's sake

    def _on_event(event: str, duration: float, **_kwargs) -> None:
        # trace, lowering and backend-compile time actually SPENT; not the
        # /jax/compilation_cache/ family, which reports time a cache hit saved
        if event.startswith("/jax/core/compile/"):
            COMPILE_TRACKER.record_jax_event(event, duration)

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    _MONITORING_INSTALLED = True


def arm_device_telemetry() -> None:
    """Turn on the sampled half of device telemetry: watchdog memory sampling +
    jax.monitoring compile events. The counting half (tracked_jit, transfers)
    is always-on. Idempotent."""
    global _ARMED
    from hivemind_tpu.telemetry import watchdog as _watchdog

    _install_jax_monitoring()
    _watchdog.add_tick_sampler(_watchdog_sampler)
    _ARMED = True


def disarm_device_telemetry() -> None:
    global _ARMED
    from hivemind_tpu.telemetry import watchdog as _watchdog

    _watchdog.remove_tick_sampler(_watchdog_sampler)
    _ARMED = False


def device_telemetry_armed() -> bool:
    return _ARMED


def reset_device_telemetry() -> None:
    """Test hygiene (conftest): zero the trackers and disarm the samplers, the
    device-side mirror of LEDGER.clear()/disarm_blackbox()."""
    disarm_device_telemetry()
    COMPILE_TRACKER.reset()
    MEMORY_MONITOR.reset()
    del _DEVICE_LISTENERS[:]
    _TRANSFER_BASELINE[_H2D] = int(_TRANSFER_H2D.value)
    _TRANSFER_BASELINE[_D2H] = int(_TRANSFER_D2H.value)
