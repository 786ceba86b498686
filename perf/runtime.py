"""What every runner needs around its window: the compile watch, the program's
counters and ledgers, peak memory, and the traced seconds."""

from __future__ import annotations

import contextlib
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from perf.manifest import ROOT
from perf.trace_reduce import WINDOW_ANNOTATION, find_xplane, reduce_trace

TRACE_DIR = ROOT / ".perf_run" / "trace"  # run-time files; listed in .gitignore


class CompileWatch:
    """Counts compilations: jax's own events (`/jax/core/compile/backend_compile_duration`)
    and the program's tracked jit sites (`COMPILE_TRACKER`). A measured window must
    see none: a compile there says the warm-up missed a shape."""

    def __init__(self):
        import jax

        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kwargs) -> None:
        if event.endswith("backend_compile_duration"):
            self.events += 1

    def count(self) -> int:
        from hivemind_tpu.telemetry.device import COMPILE_TRACKER

        return self.events + COMPILE_TRACKER.total()


def counters() -> Dict[str, Any]:
    from hivemind_tpu.telemetry import REGISTRY

    return REGISTRY.snapshot()


class LedgerTap:
    """Every record the program's ledgers close while the tap is open (the ledgers
    themselves keep only their newest few hundred)."""

    def __init__(self):
        from hivemind_tpu.telemetry.ledger import LEDGER
        from hivemind_tpu.telemetry.serving import SERVING_LEDGER

        self._ledgers = (LEDGER, SERVING_LEDGER)
        self.records: Dict[str, List[Dict[str, Any]]] = {"round": [], "epoch": [], "serving": []}
        self._lock = threading.Lock()
        for ledger in self._ledgers:
            ledger.add_record_listener(self._on_record)

    def _on_record(self, kind: str, record: Dict[str, Any]) -> None:
        with self._lock:
            self.records.setdefault(kind, []).append(record)

    def drain(self) -> Dict[str, List[Dict[str, Any]]]:
        with self._lock:
            taken, self.records = self.records, {"round": [], "epoch": [], "serving": []}
        return taken

    def close(self) -> None:
        for ledger in self._ledgers:
            ledger.remove_record_listener(self._on_record)


def memory_peak_bytes(devices, log=None) -> int:
    """Peak bytes on the fullest of `devices`, where the backend reports it: the peak
    of the arrays in use plus the peak the runtime reserved for programs'
    temporaries. On a TPU they are two pools (a train step's 6 GB of activations
    never show in `peak_bytes_in_use`); the two peaks need not coincide, so the sum
    is an upper bound."""
    peaks = []
    for device in devices:
        stats = device.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
        if log is not None:
            log(f"memory_stats of {device}: " + ", ".join(f"{key}={value}" for key, value in sorted(stats.items())))
    return max(peaks, default=0)


class Tracer:
    """Traces `seconds` of the window on a thread of its own, starting `after`
    seconds into it, and reduces the trace when asked. `mark(kind)` notes a unit of
    work that completed while the trace was on."""

    def __init__(self, seconds: float, after: float, log):
        self.seconds, self.after, self.log = seconds, after, log
        self.active = threading.Event()
        self.marks: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.traced_s, self.window_began = 0.0, 0.0

    def start(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        self._thread = threading.Thread(target=self._run, name="perf-tracer", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax

        time.sleep(self.after)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer taxes every call of the host path under test
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        began = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                self.window_began = time.monotonic()
                self.active.set()
                time.sleep(self.seconds)
                self.active.clear()
                self.traced_s = time.monotonic() - began
        finally:
            jax.profiler.stop_trace()

    def mark(self, kind: str, count: int = 1) -> None:
        if self.active.is_set():
            with self._lock:
                self.marks[kind] = self.marks.get(kind, 0) + count

    def finish(self) -> Dict[str, Any]:
        assert self._thread is not None
        self._thread.join(timeout=self.after + self.seconds + 120.0)
        path = find_xplane(str(TRACE_DIR))
        if path is None:
            self.log("no .xplane.pb was written: the traced metrics are left out")
            return {}
        started = time.monotonic()
        edge = self.window_began
        with _SPANS_LOCK:
            spans = [(s - edge, e - edge, label) for s, e, label in _SPANS if e > edge and s < edge + self.traced_s]
        reduced = reduce_trace(path, host_spans=spans)
        self.log(f"trace {Path(path).name}: {Path(path).stat().st_size / 1e6:.1f} MB, reduced in "
                 f"{time.monotonic() - started:.1f} s; window {reduced['window_s']:.3f} s, "
                 f"busy {reduced['busy_s']:.3f} s on {reduced['devices']} device plane(s)")
        return {"trace": reduced, "traced": {**self.marks, "seconds": self.traced_s}}


_SPANS: List = []  # (start, end, label) on time.monotonic, of the benchmark's own calls
_SPANS_LOCK = threading.Lock()


@contextlib.contextmanager
def annotate(label: str):
    """The benchmark's own span around a call into the program: in the profiler's
    trace, and on this process's clock (a call that is open when the trace starts
    or stops is missing from the trace). Labels the device's idle gaps."""
    import jax

    began = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation("bench:" + label):
            yield
    finally:
        with _SPANS_LOCK:
            _SPANS.append((began, time.monotonic(), label))


def float16_exact(array):
    """Round to what the float16 wire carries, so inputs reach the server unchanged."""
    import numpy as np

    return np.asarray(array, np.float16).astype(np.float32)


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-9))
