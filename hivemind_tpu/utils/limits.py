"""The process's OS-level settings: the file-descriptor limit (capability parity:
reference hivemind/utils/limits.py; swarm peers hold many sockets) and where the C
library's allocator gets large blocks (a peer that moves tensors allocates tens of MB
a request)."""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

from hivemind_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def increase_file_limit(new_soft: int = 2**15, new_hard: int = 2**15) -> None:
    """Best-effort bump of RLIMIT_NOFILE up to the allowed hard limit."""
    try:
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if hard == resource.RLIM_INFINITY:
            # never LOWER an unlimited hard limit (RLIM_INFINITY is -1: naive max()
            # would irreversibly clamp it)
            target_hard = resource.RLIM_INFINITY
            target_soft = max(soft, new_soft)
        else:
            target_hard = hard
            target_soft = min(max(soft, new_soft), hard)
        if target_soft > soft:
            resource.setrlimit(resource.RLIMIT_NOFILE, (target_soft, target_hard))
            logger.info(f"raised file limit: {soft} -> {target_soft}")
    except Exception as e:
        logger.warning(f"could not increase file limit: {e!r}")


# glibc's <malloc.h>
_M_TRIM_THRESHOLD = -1
_M_MMAP_THRESHOLD = -3
# mallopt takes an int: sixty times the largest array a request of the benchmark's
# fine-tune cell allocates, so no tensor this library moves is mapped for itself
_LARGEST_THRESHOLD = 2**31 - 1
# the two ways a user hands glibc the same two settings; glibc has read them by now
_MALLOC_VARIABLES = ("MALLOC_MMAP_THRESHOLD_", "MALLOC_TRIM_THRESHOLD_")
_MALLOC_TUNABLES = ("glibc.malloc.mmap_threshold", "glibc.malloc.trim_threshold")

_heap_policy_lock = threading.Lock()
_large_blocks_on_heap: Optional[bool] = None  # None until the first call has decided


class _Mallinfo2(ctypes.Structure):
    _fields_ = [
        (name, ctypes.c_size_t)
        for name in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd",
            "usmblks", "fsmblks", "uordblks", "fordblks", "keepcost",
        )
    ]


def _c_library() -> Optional[ctypes.CDLL]:
    """The C library this process is linked against, or None where ctypes finds none."""
    try:
        return ctypes.CDLL(None)
    except (OSError, TypeError):  # TypeError: Windows takes no None
        return None


def _environment_chose_thresholds() -> bool:
    tunables = os.environ.get("GLIBC_TUNABLES", "")
    return any(os.environ.get(name) for name in _MALLOC_VARIABLES) or any(
        name in tunables for name in _MALLOC_TUNABLES
    )


def _take_thresholds(libc: Optional[ctypes.CDLL]) -> bool:
    mallopt = getattr(libc, "mallopt", None)
    if mallopt is None:
        logger.debug("no glibc mallopt on this platform: the allocator is left as it is")
        return False
    if _environment_chose_thresholds():
        logger.debug("the environment sets malloc's thresholds: the allocator is left to it")
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    # the mapping threshold first, and the trim threshold only behind it: either call
    # ends glibc's moving of the mapping threshold, so the trim threshold alone would
    # pin it where it stands (128 KiB in a fresh process: every tensor mapped)
    if not mallopt(_M_MMAP_THRESHOLD, _LARGEST_THRESHOLD):
        logger.debug("mallopt refused the mapping threshold: the allocator is left as it is")
        return False
    if not mallopt(_M_TRIM_THRESHOLD, _LARGEST_THRESHOLD):
        logger.warning("mallopt took the mapping threshold and refused the trim threshold")
        return False
    return True


def keep_large_blocks_on_heap() -> bool:
    """Tell glibc's allocator to serve blocks of 128 KiB and up from the heap and to keep
    the heap, where it would map each for itself and unmap it when freed: on a host where
    a fresh mapping of a 17-34 MB array, its page faults and its unmapping cost several
    times the work done on the array, that is a third of a fine-tuning request (PERF.md).
    The price is resident memory held at its high-water mark.

    Process-wide and not undone; the first call decides and later calls return its answer.
    False, with nothing changed, without glibc's ``mallopt`` (musl, macOS, Windows) or where
    the environment already names either threshold (``MALLOC_MMAP_THRESHOLD_``,
    ``MALLOC_TRIM_THRESHOLD_``, ``GLIBC_TUNABLES``): those are the user's word to glibc.

    Gauges: ``hivemind_host_large_blocks_on_heap`` (the answer) and, with ``mallinfo2``,
    ``hivemind_host_mmapped_bytes`` (bytes in blocks mapped for themselves, read when the
    registry is)."""
    global _large_blocks_on_heap
    if _large_blocks_on_heap is not None:
        return _large_blocks_on_heap
    with _heap_policy_lock:
        if _large_blocks_on_heap is None:
            from hivemind_tpu.telemetry.registry import REGISTRY

            libc = _c_library()
            taken = _take_thresholds(libc)
            REGISTRY.gauge(
                "hivemind_host_large_blocks_on_heap",
                "1 where malloc keeps blocks of 128 KiB and up on the heap, 0 where the "
                "platform has no mallopt or the environment chose the thresholds",
            ).set(taken)
            mallinfo2 = getattr(libc, "mallinfo2", None)
            if mallinfo2 is not None:
                mallinfo2.argtypes = []
                mallinfo2.restype = _Mallinfo2
                REGISTRY.gauge(
                    "hivemind_host_mmapped_bytes",
                    "bytes malloc holds in blocks mapped for themselves (mallinfo2().hblkhd)",
                ).set_function(lambda: float(mallinfo2().hblkhd))
            _large_blocks_on_heap = taken
    return _large_blocks_on_heap
