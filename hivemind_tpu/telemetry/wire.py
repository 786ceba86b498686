"""Counters at the boundaries where the wire's bytes are worked on (ISSUE 37), and
the one call that opens a work span there.

``hivemind_wire_seconds_total{phase}`` / ``hivemind_wire_bytes_total{phase}``, and for the
two phases whose unit of work is a frame, ``hivemind_wire_frames_total{phase}``:

    encode     a tensor or tensor part serialized with its codec (executor threads);
               bytes: the array that went in
    decode     the inverse, chunk joins included; bytes: the buffer that went in
    seal/open  one frame through the AEAD (the ``hmtpu-aead`` pool, or inline when small);
               frames: one a call — what the event loop pays a fixed price for, whatever
               the frame carries (a unary call is two frames, one each way)
    reduce     the reducer's numpy: accumulate, divide, delta, the sender's store
    send_wait  seconds a frame's sender stood waiting for the channel's in-flight
               credit, which the writer hands back; seconds only — it awaits

``hivemind_wire_half_elements_total{range}``: elements the fp16 codec encoded, ``tiny`` where
0 < |x| < 2**-14 (the half is subnormal or underflows: the values numpy's own cast pays
thirty times for, and which send an array through the codec's integer path) and ``other``,
by the codec's own sampled estimate; arrays of :data:`WORK_SPAN_BYTES` and up.

Seconds are work summed over threads; seconds over bytes is what a megabyte costs in
that phase on this host (``docs/observability.md`` says what an operator reads off it).
The counters always count; what else a site leaves behind follows its size
(:data:`WORK_SPAN_BYTES`) and :class:`~hivemind_tpu.telemetry.tracing.trace_work`'s levels."""

from __future__ import annotations

from typing import Any, Optional

from hivemind_tpu.telemetry.registry import REGISTRY
from hivemind_tpu.telemetry.tracing import Span, trace_work

# under this size a site gets the counters alone: the size at which the channel already
# declines the executor hop (p2p/crypto_channel._OFFLOAD_THRESHOLD; a decode token is 8 KB)
WORK_SPAN_BYTES = 128 * 1024

_SECONDS = REGISTRY.counter(
    "hivemind_wire_seconds_total", "seconds of work on the wire's bytes, summed over threads", ("phase",)
)
_BYTES = REGISTRY.counter("hivemind_wire_bytes_total", "bytes that work was done on", ("phase",))
_FRAMES = REGISTRY.counter("hivemind_wire_frames_total", "frames sealed and opened by the channels", ("phase",))
_HALF_ELEMENTS = REGISTRY.counter(
    "hivemind_wire_half_elements_total", "elements the fp16 codec encoded, by the codec's sampled estimate", ("range",)
)
_TINY_ELEMENTS, _OTHER_ELEMENTS = _HALF_ELEMENTS.labels(range="tiny").inc, _HALF_ELEMENTS.labels(range="other").inc


def _phase(phase: str):
    """(span name, sink(seconds, bytes)): the reducer's numpy is the all-reduce's own work,
    not the codec's, and its span says so."""
    add_seconds, add_bytes = _SECONDS.labels(phase=phase).inc, _BYTES.labels(phase=phase).inc

    if phase in ("seal", "open"):
        add_frames = _FRAMES.labels(phase=phase).inc

        def sink(elapsed: float, size: int) -> None:
            add_seconds(elapsed)
            add_bytes(size)
            add_frames()

    else:

        def sink(elapsed: float, size: int) -> None:
            add_seconds(elapsed)
            add_bytes(size)

    return "allreduce.reduce" if phase == "reduce" else "wire." + phase, sink


_PHASES = {phase: _phase(phase) for phase in ("encode", "decode", "seal", "open", "reduce")}
_SEND_WAIT = _SECONDS.labels(phase="send_wait")


def wire_work(phase: str, nbytes: int, *, span: bool = True, parent: Optional[Span] = None,
              **attributes: Any) -> trace_work:
    """``with wire_work("decode", len(buffer), parent=round_span, purpose="grads"):`` —
    the phase's counters, and a ``wire.<phase>`` span (``allreduce.reduce`` for
    ``reduce``) unless the bytes are few. ``span=False``: the profiler's annotation in
    place of the span, for what happens once a frame."""
    name, sink = _PHASES[phase]
    if nbytes < WORK_SPAN_BYTES:  # some thousand frames a second take this way
        return trace_work(name, nbytes, sink, trace_work.COUNT)
    if not span:
        return trace_work(name, nbytes, sink, trace_work.ANNOTATION)
    return trace_work(name, nbytes, sink, trace_work.SPAN, parent, **attributes)


def count_work(phase: str, seconds: float, nbytes: int) -> None:
    """The phase's counters from seconds the caller has already measured at the same
    boundary (a serving handler's inline codec call, timed for its ledger): no second
    pair of clock reads."""
    _PHASES[phase][1](seconds, nbytes)


def add_send_wait(seconds: float) -> None:
    _SEND_WAIT.inc(seconds)


def count_half_elements(tiny: float, other: float) -> None:
    _TINY_ELEMENTS(tiny)
    _OTHER_ELEMENTS(other)
