"""Float16 codecs (capability parity: reference hivemind/compression/floating.py)."""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from hivemind_tpu.compression.base import (
    CompressionBase,
    CompressionInfo,
    CompressionType,
    as_numpy,
)
from hivemind_tpu.proto import runtime_pb2
from hivemind_tpu.telemetry.wire import WORK_SPAN_BYTES, count_half_elements

FP16_MAX = 65504.0

# ---------------------------------------------------------------- the two conversions
#
# numpy's own casts cost by the VALUES (numpy 2.0.2 on the chip machine's host, 13 cores with
# AVX2, one 2 MiB part of 524,288 elements alone in a quiet process; PERF.md §6, PR 45):
# float32 -> float16 takes 4.0 ns an element with its clip and its copy, and 76 ns more for
# every element whose half is subnormal or underflows (0 < |x| < 2**-14: a scalar branch
# that raises the floating-point status flag an element), 42 ms a part; float16 -> float32
# takes 1.2 ms a part and 5.3 on subnormal halves. Gradients averaged over thousands of
# tokens and an optimizer's moments are such values: 14-96 % of a live gradient part of
# ALBERT's, all of a part of LAMB's moments. Both conversions below give numpy's bits;
# what differs is the time.

_MAGNITUDE = np.uint32(0x7FFFFFFF)
_EXPONENT = np.uint32(0x7F800000)
_INFINITY = 0x7F800000
_FP16_MAX_BITS = np.uint32(0x477FE000)  # FP16_MAX
_MIN_NORMAL = np.uint32(113 << 23)  # 2**-14, the smallest normal half
_TINY_FLOAT32 = (_MAGNITUDE, np.uint32(1), _MIN_NORMAL)  # _sampled_share's: 0 < |x| < 2**-14
_ROUNDS_AT = np.uint32(13 << 23)  # a half keeps 10 of a float32's 23 mantissa bits
_ADDEND_OF_MIN_NORMAL = _MIN_NORMAL + _ROUNDS_AT

# The integer path works on pieces of this many elements, so that an array of any size
# costs two temporaries of a piece. One part of an averaging round is one piece, and not
# eight that would lie in the cache: each numpy call gives the interpreter lock away and
# asks for it back, and beside a busy event loop that way back cost half a millisecond a
# call — inside a live gradient round pieces of 65,536 read 48 ms a part where one piece
# reads 9 and both take 1.3 alone (chip host, PR 45).
_PIECE = 1 << 19

# The look reads at most this many elements: as many as numpy works on without giving the
# interpreter lock away (NPY_BEGIN_THREADS_THRESHOLDED), so it costs microseconds wherever
# it runs; the share it estimates is within 0.045 of the array's nineteen times in twenty.
_SAMPLE = 500

# The integer path is taken when the sampled share of tiny elements times what numpy's
# cast pays for one (_TINY_NS) exceeds what the integer path pays for any element
# (_INTEGER_NS: 1.36 ms a part with its copy, at every scale): a share above 3.4 %. Alone
# the integer path is the faster at any share; what numpy's three calls a part save beside
# a busy interpreter is about what the rule leaves out, numpy's own 4.0 ns an element. A
# wrong guess costs time, never a byte.
_TINY_NS = 76.0
_INTEGER_NS = 2.6


def _sampled_share(bits: np.ndarray, magnitude, one, below) -> float:
    """Share of 0 < (bits & magnitude) < below among a strided sample of a flat array of
    bit patterns: the look both conversions take at an array's values. ``one`` is 1 in the
    array's own type (a typed scalar is a microsecond cheaper than a Python int here)."""
    sample = bits[:: -(-bits.size // _SAMPLE)] & magnitude
    sample -= one  # zero is on numpy's fast path: 0 - 1 wraps out of the range
    return np.count_nonzero(sample < below - one) / sample.size


def _half_bits_by_integers(flat32: np.ndarray) -> Optional[np.ndarray]:
    """The uint16 bits of ``np.clip(flat32, -FP16_MAX, FP16_MAX).astype(np.float16)``
    without numpy's cast, at one cost whatever the values; None where a NaN is inside
    (numpy's cast decides its payload).

    |x| + 2**(e+13), e the exponent of |x| and at least -14, leaves in the sum's mantissa
    |x| rounded to nearest even at a half's last bit — at 2**-24 for every |x| under 2**-14,
    where a half is subnormal — so the sum's bits less the addend's are the half's
    significand, the implicit one with it, which carries into the exponent field as it must.
    No operand and no result is a float32 denormal unless the input is one."""
    out = np.empty(flat32.size, np.uint16)
    for start in range(0, flat32.size, _PIECE):
        bits = flat32[start : start + _PIECE].view(np.uint32)
        a = np.bitwise_and(bits, _MAGNITUDE)
        largest = a.max()
        if largest > _INFINITY:
            return None
        if largest > _FP16_MAX_BITS:
            np.minimum(a, _FP16_MAX_BITS, out=a)  # the clip
        c = np.bitwise_and(a, _EXPONENT)
        np.maximum(c, _MIN_NORMAL, out=c)
        np.add(c, _ROUNDS_AT, out=c)
        np.add(a.view(np.float32), c.view(np.float32), out=a.view(np.float32))
        np.subtract(a, c, out=a)
        np.subtract(c, _ADDEND_OF_MIN_NORMAL, out=c)
        np.right_shift(c, 13, out=c)
        np.add(a, c, out=a)  # the exponent field: e + 14, 0 for a subnormal half
        np.right_shift(bits, 16, out=c)
        np.bitwise_and(c, 0x8000, out=c)
        np.bitwise_or(a, c, out=a)
        out[start : start + _PIECE] = a
    return out


def to_half(array32: np.ndarray, inplace: bool) -> np.ndarray:
    """``np.clip(array32, -FP16_MAX, FP16_MAX).astype(np.float16)``, bit for bit, flat.
    Which way the array goes is decided by a look at its values; ``inplace`` lets numpy's
    way clip where the array lies."""
    flat32 = array32.reshape(-1)  # a copy, in C order, where the array is not contiguous
    if flat32.size == 0:
        return flat32.astype(np.float16)
    tiny = _sampled_share(flat32.view(np.uint32), *_TINY_FLOAT32)
    if array32.nbytes >= WORK_SPAN_BYTES:
        count_half_elements(tiny * flat32.size, (1.0 - tiny) * flat32.size)
    if tiny * _TINY_NS > _INTEGER_NS:
        half_bits = _half_bits_by_integers(flat32)
        if half_bits is not None:
            return half_bits.view(np.float16)
    clipped = np.clip(flat32, -FP16_MAX, FP16_MAX, out=flat32 if inplace and flat32.flags.writeable else None)
    return clipped.astype(np.float16)


# every half as numpy's cast makes it a float32, NaN payloads and all: a gather from this
# table decodes at one cost whatever the values (0.45 ms a part of 524,288 on the chip
# host, where numpy's cast takes 1.15 and 4.3-5.3 on subnormal halves: 7.9 ns a subnormal
# half). Three sizes of array, by what the table and a gather cost:
# - fewer halves than the table has entries: numpy's cast, as ever. Such an array would
#   pull more of a cold table through the cache than it has elements to convert (a decode
#   token's 2,048-4,096 values inside a live server: a tenth more decode seconds a byte
#   with the gather, PR 45), and numpy's worst on it is half a millisecond;
# - up to _GATHER halves: one gather, one call as numpy's cast is, faster at any values;
# - more: a call a gather where numpy's cast takes one, and calls are what costs beside
#   a busy interpreter (_PIECE), so a look decides: the gathers where the share of
#   subnormal halves costs numpy more (_SUBNORMAL_NS a half) than a gather costs
#   (_GATHER_NS an element), 11 %, else numpy's cast.
_HALF_AS_FLOAT32 = np.arange(1 << 16, dtype=np.uint16).view(np.float16).astype(np.float32)
_GATHER = 1 << 20  # elements a gather: its index temporary is 8 bytes an element
_SUBNORMAL_NS = 7.9
_GATHER_NS = 0.86
_SUBNORMAL_HALF = (np.uint16(0x7FFF), np.uint16(1), np.uint16(0x400))  # _sampled_share's: 0 < |h| < 2**-14


def from_half(buffer, count: int = -1) -> np.ndarray:
    """``np.frombuffer(buffer, np.float16, count).astype(np.float32)``, bit for bit."""
    half = np.frombuffer(buffer, dtype=np.float16, count=count)
    half_bits = half.view(np.uint16)
    if _HALF_AS_FLOAT32.size <= half.size <= _GATHER:
        return _HALF_AS_FLOAT32.take(half_bits)
    if half.size < _HALF_AS_FLOAT32.size or _sampled_share(half_bits, *_SUBNORMAL_HALF) * _SUBNORMAL_NS <= _GATHER_NS:
        return half.astype(np.float32)
    out = np.empty(half.size, np.float32)
    for start in range(0, half.size, _GATHER):
        # a uint16 cannot leave the table: "clip" only spares the copy that "raise" makes
        np.take(_HALF_AS_FLOAT32, half_bits[start : start + _GATHER], out=out[start : start + _GATHER], mode="clip")
    return out


class Float16Compression(CompressionBase):
    """Clamp to the fp16 range and cast (reference floating.py:10-40)."""

    compression_type = CompressionType.FLOAT16
    is_lossy = True

    def compress(self, array: Any, info: Optional[CompressionInfo] = None, allow_inplace: bool = False) -> runtime_pb2.Tensor:
        array = as_numpy(array)
        original_dtype = "bfloat16" if str(array.dtype) == "bfloat16" else array.dtype.name
        array32 = array.astype(np.float32, copy=False)
        # a dtype conversion already made array32 private; otherwise in-place needs
        # the caller's explicit permission (bit-identical either way — same values)
        private = True if array32 is not array else allow_inplace
        return runtime_pb2.Tensor(
            buffer=to_half(array32, private).tobytes(),
            size=array.shape,
            dtype=original_dtype,
            compression=self.compression_type,
        )

    def extract(self, serialized: runtime_pb2.Tensor) -> np.ndarray:
        from hivemind_tpu.utils.tensor_descr import numpy_dtype

        dtype = numpy_dtype(serialized.dtype or "float32")
        if dtype == np.float32:
            return from_half(serialized.buffer).reshape(tuple(serialized.size))
        half = np.frombuffer(serialized.buffer, dtype=np.float16)
        return half.astype(dtype).reshape(tuple(serialized.size))

    def estimate_compression_ratio(self, info: CompressionInfo) -> float:
        return 16.0 / (8 * (info.descriptor.itemsize if info.descriptor else 4))


class ScaledFloat16Compression(Float16Compression):
    """Normalize per last axis by mean/std, cast to fp16, and ship the fp32 stats
    alongside (reference floating.py:43-91, MEANSTD_16BIT)."""

    compression_type = CompressionType.MEANSTD_16BIT

    def compress(self, array: Any, info: Optional[CompressionInfo] = None, allow_inplace: bool = False) -> runtime_pb2.Tensor:
        array = as_numpy(array)
        original_dtype = "bfloat16" if str(array.dtype) == "bfloat16" else array.dtype.name
        array32 = array.astype(np.float32, copy=False)
        if array32.ndim == 0:
            array32 = array32.reshape(1)
            means = np.zeros(1, np.float32)
            stds = np.ones(1, np.float32)
            normalized = array32
        else:
            means = array32.mean(axis=-1, keepdims=True, dtype=np.float32)
            stds = array32.std(axis=-1, keepdims=True, dtype=np.float32) + 1e-6
            private = True if array32 is not array else allow_inplace
            if private and array32.flags.writeable:
                np.subtract(array32, means, out=array32)
                np.divide(array32, stds, out=array32)
                normalized = array32
            else:
                normalized = (array32 - means) / stds
        half = to_half(normalized, False)
        buffer = half.tobytes() + means.astype(np.float32).tobytes() + stds.astype(np.float32).tobytes()
        return runtime_pb2.Tensor(
            buffer=buffer,
            size=array.shape,
            dtype=original_dtype,
            compression=self.compression_type,
        )

    def extract(self, serialized: runtime_pb2.Tensor) -> np.ndarray:
        from hivemind_tpu.utils.tensor_descr import numpy_dtype

        shape = tuple(serialized.size)
        numel = int(np.prod(shape)) if shape else 1
        stats_shape = (*shape[:-1], 1) if shape else (1,)
        stats_count = int(np.prod(stats_shape))
        half_bytes = numel * 2
        means = np.frombuffer(serialized.buffer, dtype=np.float32, count=stats_count, offset=half_bytes)
        stds = np.frombuffer(
            serialized.buffer, dtype=np.float32, count=stats_count, offset=half_bytes + stats_count * 4
        )
        restored = from_half(serialized.buffer, numel).reshape(shape or (1,))
        restored = restored * stds.reshape(stats_shape) + means.reshape(stats_shape)
        out = restored.astype(numpy_dtype(serialized.dtype or "float32"))
        return out.reshape(shape) if shape else out.reshape(())
