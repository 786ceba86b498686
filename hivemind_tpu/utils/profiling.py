"""Profiling hooks over the jax/XLA profiler (fills the reference's tracing role:
hivemind/utils/performance_ema.py + the torch profiler hooks scattered through its
runtime; here the device timeline comes from XLA's own profiler, which captures
HBM traffic, fusion boundaries, and per-op device time — strictly more than the
reference's host-side timers).

- :func:`trace_span` — annotate a host-side region so it shows up on the XLA trace
  timeline (viewable in TensorBoard / Perfetto).
- :func:`profile_to` — capture a full device+host trace for a ``with`` block.
- :func:`device_memory_stats` — live HBM usage of a device (bytes in use / limit),
  the "am I about to OOM" probe for schedulers and monitors.
- :func:`tracked_jit` — ``jax.jit`` plus compile accounting: every cache miss is
  reported to the device-telemetry compile tracker with a site label and the
  triggering abstract signature (ISSUE 19).
- :class:`StepProfiler` — rolling tokens/s + achieved-FLOP/s estimator for training
  loops (PerformanceEMA under the hood), the number the training monitor reports.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict, Optional

from hivemind_tpu.utils.performance_ema import PerformanceEMA

# jax is imported lazily inside each hook: utils/__init__.py re-exports this module,
# and lightweight processes (DHT-only peers, CLIs) must not pay for — or claim — an
# accelerator backend just by importing hivemind_tpu.


@contextlib.contextmanager
def trace_span(name: str, **attributes):
    """Label a host-side region on BOTH timelines under one name: the XLA
    profiler trace (device view — HBM traffic, fusions, per-op device time) and
    the swarm telemetry tracer (host view — the flight recorder, ``/trace``
    Perfetto export, cross-peer parenting). One call site, two synchronized
    views; the shared name is what lets you line them up in Perfetto."""
    import jax

    from hivemind_tpu.telemetry.tracing import trace as _telemetry_trace

    with _telemetry_trace(name, **attributes):
        with jax.profiler.TraceAnnotation(name):
            yield


@contextlib.contextmanager
def profile_to(logdir: str):
    """Capture a device+host trace into ``logdir`` for the duration of the block
    (open with TensorBoard's profile plugin or Perfetto)."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def device_memory_stats(device=None) -> Dict[str, Any]:
    """Live memory statistics for one device; empty dict when the backend does not
    expose them (CPU)."""
    import jax

    device = device if device is not None else jax.devices()[0]
    return dict(device.memory_stats() or {})


def _abstract_signature(args, kwargs, limit: int = 16) -> Optional[str]:
    """Compact shape/dtype signature of a call's array leaves — computed only
    when a compile was actually observed, so the cost never hits a cache hit."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        parts = [
            f"{getattr(leaf, 'dtype', '?')}{list(getattr(leaf, 'shape', ()))}"
            for leaf in leaves[:limit]
            if hasattr(leaf, "shape")
        ]
        return ",".join(parts)[:200] or None
    except Exception:
        return None


def tracked_jit(fn=None, *, site: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` with compile accounting (ISSUE 19).

    Wraps the jitted callable so every cache miss — detected via a
    ``_cache_size()`` delta around the call — is reported to
    :data:`~hivemind_tpu.telemetry.device.COMPILE_TRACKER` under ``site``
    (default: the function's qualname), with the call's wall duration (trace +
    lower + compile + first run) and abstract signature. Cache hits pay one
    cache-size probe and one clock read, cheap enough for per-token decode
    paths; this is the sanctioned alternative the ``jit-in-hot-path`` lint rule
    points at for memoized-factory jits that legitimately live inside methods.

    Usable as ``tracked_jit(fn, site=..., donate_argnums=...)`` or as a bare
    decorator. The underlying jitted function stays reachable via
    ``wrapper.jitted`` (``lower()``/cache inspection)."""

    def wrap(fn):
        import jax

        from hivemind_tpu.telemetry.device import COMPILE_TRACKER

        label = site or getattr(fn, "__qualname__", None) or getattr(fn, "__name__", "jit")
        jitted = jax.jit(fn, **jit_kwargs)
        cache_size = jitted._cache_size

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            before = cache_size()
            started = time.perf_counter()
            out = jitted(*args, **kwargs)
            if cache_size() > before:
                COMPILE_TRACKER.record_compile(
                    label,
                    duration_s=time.perf_counter() - started,
                    signature=_abstract_signature(args, kwargs),
                )
            return out

        wrapper.jitted = jitted
        wrapper.site = label
        return wrapper

    return wrap if fn is None else wrap(fn)


class StepProfiler:
    """Rolling throughput for a training loop.

    >>> prof = StepProfiler(flops_per_token=flops)
    >>> for batch in data:
    ...     loss = train_step(batch)
    ...     prof.step(tokens=batch_tokens)
    >>> prof.tokens_per_second, prof.achieved_flops
    """

    def __init__(self, flops_per_token: Optional[float] = None, alpha: float = 0.1):
        self.flops_per_token = flops_per_token
        self.ema = PerformanceEMA(alpha=alpha)
        self.total_tokens = 0
        self._started = time.perf_counter()

    def step(self, tokens: int) -> None:
        self.total_tokens += tokens
        self.ema.update(tokens)

    @property
    def tokens_per_second(self) -> float:
        return self.ema.samples_per_second

    @property
    def achieved_flops(self) -> Optional[float]:
        if self.flops_per_token is None:
            return None
        return self.tokens_per_second * self.flops_per_token

    def mfu(self, peak_flops: float) -> Optional[float]:
        achieved = self.achieved_flops
        return None if achieved is None else achieved / peak_flops

    def summary(self) -> Dict[str, Any]:
        return {
            "tokens_per_second": round(self.tokens_per_second, 1),
            "total_tokens": self.total_tokens,
            "elapsed_s": round(time.perf_counter() - self._started, 3),
            "achieved_tflops": None
            if self.achieved_flops is None
            else round(self.achieved_flops / 1e12, 3),
        }


class JsonlMetricsSink:
    """Append metric records as JSON lines — the offline wandb-style sink shared
    by the flagship recipe's trainer and monitor. Non-finite floats serialize as
    null so every line stays strict JSON (jq/pandas-parsable)."""

    def __init__(self, path: Optional[str]):
        self._file = open(path, "a") if path else None

    def log(self, record: Dict[str, Any]) -> None:
        if self._file is None:
            return
        import json
        import math

        clean = {
            key: (None if isinstance(value, float) and not math.isfinite(value) else value)
            for key, value in record.items()
        }
        self._file.write(json.dumps(clean, allow_nan=False) + "\n")
        self._file.flush()

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None
