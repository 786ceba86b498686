"""Device-side helpers around jax (host spans on the XLA trace's timeline are
:class:`hivemind_tpu.telemetry.tracing.trace_sync`).

- :func:`device_memory_stats` — live HBM usage of a device (bytes in use / limit),
  the "am I about to OOM" probe for schedulers and monitors.
- :func:`tracked_jit` — ``jax.jit`` plus compile accounting: every cache miss is
  reported to the device-telemetry compile tracker with a site label and the
  triggering abstract signature (ISSUE 19).
- :class:`JsonlMetricsSink` — append metric records as JSON lines.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional

# jax is imported lazily inside each hook: utils/__init__.py re-exports this module,
# and lightweight processes (DHT-only peers, CLIs) must not pay for — or claim — an
# accelerator backend just by importing hivemind_tpu.


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
