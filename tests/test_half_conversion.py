"""The fp16 codec's two conversions (ISSUE 45): numpy's bits at a cost that does not follow
the values.

``float32 -> float16`` goes one of two ways by a look at the array (numpy's cast, or integer
arithmetic on the bits where the cast would take its per-element underflow branch),
``float16 -> float32`` is one gather from a table. The reference throughout is what the
parent commit did: ``np.clip(x, -65504, 65504).astype(np.float16)`` and
``half.astype(np.float32)``."""

import time

import ml_dtypes
import numpy as np
import pytest

from hivemind_tpu.compression import (
    CompressionType,
    Float16Compression,
    deserialize_tensor,
    serialize_tensor,
)
from hivemind_tpu.compression import floating
from hivemind_tpu.compression.floating import FP16_MAX, from_half, to_half
from hivemind_tpu.telemetry import REGISTRY
from hivemind_tpu.telemetry.wire import WORK_SPAN_BYTES

ALL_HALVES = np.arange(1 << 16, dtype=np.uint16)
PART = 1 << 19  # elements of one 2 MiB part of an averaging round


def parent_encode(array32: np.ndarray) -> np.ndarray:
    with np.errstate(all="ignore"):
        return np.clip(array32, -FP16_MAX, FP16_MAX).astype(np.float16)


def tiny_share(values: np.ndarray) -> float:
    return floating._sampled_share(values.view(np.uint32), *floating._TINY_FLOAT32)


def parent_decode(buffer: bytes) -> np.ndarray:
    return np.frombuffer(buffer, dtype=np.float16).astype(np.float32)


def halves_ties_and_neighbours() -> np.ndarray:
    """Every finite half, the midpoint to its upper neighbour (the tie), that midpoint one
    float32 ulp up and down, both signs."""
    positive = ALL_HALVES[ALL_HALVES < 0x7C00]
    value = positive.view(np.float16).astype(np.float64)
    above = (positive + 1).astype(np.uint16).view(np.float16).astype(np.float64)
    above[-1] = 65536.0  # past the largest half lies the tie with infinity
    tie = ((value + above) / 2).astype(np.float32)  # 12 significant bits: exact
    one_sign = np.concatenate([
        value.astype(np.float32), tie, np.nextafter(tie, np.float32(np.inf)), np.nextafter(tie, np.float32(-np.inf))
    ])
    return np.concatenate([one_sign, -one_sign])


def random_patterns() -> np.ndarray:
    """2**22 uint32 patterns as float32: float32 denormals, NaNs and infinities among them."""
    return np.random.default_rng(45).integers(0, 1 << 32, size=1 << 22, dtype=np.uint32).view(np.float32)


ENCODE_INPUTS = {"halves_ties_neighbours": halves_ties_and_neighbours, "random_patterns": random_patterns}
# the way an array goes: numpy's cast, the integer path whole or in pieces that do not
# divide the array, or whichever the look at the values chooses
WAYS = {"numpy": dict(_INTEGER_NS=float("inf")), "integers": dict(_INTEGER_NS=-1.0),
        "integers_in_pieces": dict(_INTEGER_NS=-1.0, _PIECE=1000), "as_decided": {}}


@pytest.fixture
def way(request, monkeypatch):
    for name, value in WAYS[request.param].items():
        monkeypatch.setattr(floating, name, value)
    return request.param


# ---------------------------------------------------------------------------- decode


@pytest.mark.parametrize("gather, subnormal_ns", [(1 << 20, 7.9), (1000, float("inf")), (1000, 0.0)],
                         ids=["one_gather", "gathers_of_1000", "numpys_cast"])
def test_decode_every_half(gather, subnormal_ns, monkeypatch):
    monkeypatch.setattr(floating, "_GATHER", gather)
    monkeypatch.setattr(floating, "_SUBNORMAL_NS", subnormal_ns)
    want, got = parent_decode(ALL_HALVES.tobytes()), from_half(ALL_HALVES.tobytes())
    assert got.dtype == np.float32 and got.shape == want.shape
    nan = np.isnan(want)
    assert nan.sum() == 2 * 1023
    assert np.array_equal(np.isnan(got), nan)
    assert np.array_equal(np.signbit(got), np.signbit(want))
    assert np.array_equal(got.view(np.uint32)[~nan], want.view(np.uint32)[~nan])


def test_an_array_smaller_than_the_table_is_numpys(monkeypatch):
    """A decode token's few thousand values never touch the table."""
    monkeypatch.setattr(floating, "_HALF_AS_FLOAT32", np.full_like(floating._HALF_AS_FLOAT32, -1))
    half = (np.random.default_rng(2).standard_normal(4096) * 1e-5).astype(np.float16)
    assert np.array_equal(from_half(half.tobytes()).view(np.uint32), half.astype(np.float32).view(np.uint32))
    assert (from_half(ALL_HALVES.tobytes()) == -1).all()


def test_decode_counts_from_the_buffers_start():
    buffer = ALL_HALVES[0x3C00:0x3C10].tobytes() + b"trailing statistics"[:16]
    assert np.array_equal(from_half(buffer, 16), parent_decode(buffer[:32]))
    assert from_half(b"").shape == (0,)


@pytest.mark.parametrize("scale, gathers", [(1.0, False), (1e-2, False), (1e-5, True), (3e-5, True)])
def test_an_array_of_many_gathers_takes_them_where_numpy_is_slow(scale, gathers, monkeypatch):
    """Activations of unit scale, a fine-tune request's 8 M among them, are decoded by
    numpy's one call as they always were; an array that one gather takes is gathered."""
    calls = []
    monkeypatch.setattr(floating, "_GATHER", 1 << 15)
    monkeypatch.setattr(np, "take", lambda *args, **kwargs: calls.append(1) or np.ndarray.take(*args, **kwargs))
    half = (np.random.default_rng(9).standard_normal(1 << 17) * scale).astype(np.float16)
    assert abs(floating._sampled_share(half.view(np.uint16), *floating._SUBNORMAL_HALF) - np.mean((half != 0) & (np.abs(half) < 2.0**-14))) < 0.06
    assert np.array_equal(from_half(half.tobytes()).view(np.uint32), half.astype(np.float32).view(np.uint32))
    assert len(calls) == (4 if gathers else 0)


# ---------------------------------------------------------------------------- encode


@pytest.mark.parametrize("inputs", sorted(ENCODE_INPUTS))
@pytest.mark.parametrize("way", sorted(WAYS), indirect=True)
def test_encode_gives_numpys_bits(inputs, way):
    values = ENCODE_INPUTS[inputs]()
    want = parent_encode(values).view(np.uint16)
    got = to_half(values, False).view(np.uint16)
    assert got.shape == want.shape
    differ = np.flatnonzero(got != want)
    assert differ.size == 0, (values[differ[:5]], got[differ[:5]], want[differ[:5]])


@pytest.mark.parametrize("values", [
    [np.nan, 1e-6, -1e-6], [np.inf, -np.inf, 7e-8], [65504.0, 65519.99, 65520.0, 1e30], [-0.0, 0.0, 2.0**-25, -(2.0**-25)],
    [2.0**-24, 1.5 * 2.0**-24, 2.5 * 2.0**-24, 2.0**-14, np.nextafter(np.float32(2.0**-14), np.float32(0))],
    [1e-40, -1e-45, 2.0**-126],
], ids=["nan", "infinities", "clipped", "signed_zeros", "subnormal_halves", "float32_denormals"])
def test_integer_path_on_the_edges(values):
    values = np.asarray(values, np.float32)
    got = floating._half_bits_by_integers(values)
    if np.isnan(values).any():
        assert got is None  # numpy's cast decides a NaN's payload
        got = to_half(values, False).view(np.uint16)
    assert np.array_equal(got, parent_encode(values).view(np.uint16))


def test_integer_path_leaves_its_input_alone():
    values = (np.random.default_rng(0).standard_normal(5000) * 1e5).astype(np.float32)
    before = values.copy()
    floating._half_bits_by_integers(values)
    assert np.array_equal(values, before)


# ---------------------------------------------------------------------------- the look


@pytest.mark.parametrize("scale, share", [(1.0, 0.0), (1e-2, 0.005), (1e-3, 0.049), (1e-4, 0.458), (1e-5, 1.0), (1e-40, 1.0), (0.0, 0.0)])
def test_the_look_estimates_the_tiny_share(scale, share):
    values = (np.random.default_rng(3).standard_normal(PART + 77) * scale).astype(np.float32)
    exact = np.mean((np.abs(values) > 0) & (np.abs(values) < 2.0**-14))
    assert abs(exact - share) < 0.01
    assert abs(tiny_share(values) - exact) < 0.06  # about a thousand samples
    assert abs(tiny_share(-values[:300]) - np.mean((values[:300] != 0) & (np.abs(values[:300]) < 2.0**-14))) < 1e-9


@pytest.mark.parametrize("scale, elements, integers", [
    (1.0, PART, False), (1e-5, PART, True), (1e-4, PART, True), (1.0, 4096, False), (1e-6, 4096, True), (0.0, PART, False),
])
def test_the_values_choose_the_way(scale, elements, integers, monkeypatch):
    """Activations of unit scale, a decode token's 4,096 among them, go through numpy's
    cast as they always did; small numbers do not, whatever the array's size."""
    calls = []
    inner = floating._half_bits_by_integers
    monkeypatch.setattr(floating, "_half_bits_by_integers", lambda flat: calls.append(flat.size) or inner(flat))
    values = (np.random.default_rng(5).standard_normal(elements) * scale).astype(np.float32)
    assert np.array_equal(to_half(values, False).view(np.uint16), parent_encode(values).view(np.uint16))
    assert bool(calls) == integers


def test_the_counter_takes_the_codecs_estimate():
    def series():
        got = REGISTRY.snapshot().get("hivemind_wire_half_elements_total", {}).get("series", {})
        return got.get("range=tiny", 0.0), got.get("range=other", 0.0)

    rng = np.random.default_rng(7)
    codec = Float16Compression()
    tiny0, other0 = series()
    codec.compress((rng.standard_normal(PART) * 1e-6).astype(np.float32))
    codec.compress(rng.standard_normal(PART).astype(np.float32))
    codec.compress((rng.standard_normal(WORK_SPAN_BYTES // 4 - 1) * 1e-6).astype(np.float32))  # under the floor
    tiny1, other1 = series()
    assert tiny1 - tiny0 == pytest.approx(PART, rel=0.01) and other1 - other0 == pytest.approx(PART, rel=0.01)
    mixed = np.concatenate([rng.standard_normal(PART // 2) * 1e-6, rng.standard_normal(PART // 2)]).astype(np.float32)
    codec.compress(rng.permutation(mixed))
    tiny2, other2 = series()
    assert tiny2 - tiny1 == pytest.approx(PART / 2, rel=0.15)
    assert (tiny2 - tiny1) + (other2 - other1) == pytest.approx(PART)


# ---------------------------------------------------------------------------- the wire


def _parent_float16(array):
    """Float16Compression.compress / extract as the parent commit had them."""
    array = np.asarray(array)
    array32 = array.astype(np.float32, copy=False)
    buffer = parent_encode(array32).tobytes()
    target = ml_dtypes.bfloat16 if str(array.dtype) == "bfloat16" else array.dtype
    return buffer, np.frombuffer(buffer, dtype=np.float16).astype(target).reshape(array.shape)


def _parent_scaled(array):
    """ScaledFloat16Compression.compress / extract as the parent commit had them."""
    array = np.asarray(array)
    array32 = array.astype(np.float32, copy=False)
    shape = array32.shape
    if array32.ndim == 0:
        array32 = array32.reshape(1)
        means, stds, normalized = np.zeros(1, np.float32), np.ones(1, np.float32), array32
    else:
        means = array32.mean(axis=-1, keepdims=True, dtype=np.float32)
        stds = array32.std(axis=-1, keepdims=True, dtype=np.float32) + 1e-6
        normalized = (array32 - means) / stds
    half = parent_encode(normalized)
    buffer = half.tobytes() + means.astype(np.float32).tobytes() + stds.astype(np.float32).tobytes()
    restored = half.astype(np.float32).reshape(shape or (1,)) * stds + means
    target = ml_dtypes.bfloat16 if str(array.dtype) == "bfloat16" else array.dtype
    return buffer, restored.astype(target).reshape(shape)


def _wire_arrays():
    rng = np.random.default_rng(11)
    normal = rng.standard_normal((384, 512)).astype(np.float32)
    mixed = normal.copy()
    mixed[::3] *= 1e-5
    mixed[1::3] *= 1e-9
    mixed[5] = 0.0
    mixed[7, :4] = [1e6, -1e6, 65519.0, -7e4]
    read_only = (normal * 1e-5).copy()
    read_only.flags.writeable = False
    return {
        "scale_1": normal, "scale_1e-4": normal * np.float32(1e-4), "scale_1e-6": normal * np.float32(1e-6),
        "scale_1e-9": normal * np.float32(1e-9), "all_zero": np.zeros((64, 128), np.float32), "mixed": mixed,
        "zero_d": np.float32(3e-7).reshape(()), "empty": np.zeros((0, 16), np.float32),
        "non_contiguous": (normal * np.float32(1e-5)).T[3:200:2], "read_only": read_only,
        "bfloat16": (normal[:64] * np.float32(1e-4)).astype(ml_dtypes.bfloat16),
    }


WIRE_ARRAYS = _wire_arrays()
PARENTS = {CompressionType.FLOAT16: _parent_float16, CompressionType.MEANSTD_16BIT: _parent_scaled}


@pytest.mark.parametrize("allow_inplace", [False, True], ids=["copy", "inplace"])
@pytest.mark.parametrize("name", sorted(WIRE_ARRAYS))
@pytest.mark.parametrize("codec", sorted(PARENTS), ids=["float16", "meanstd_16bit"])
def test_the_wire_is_the_parents_byte_for_byte(codec, name, allow_inplace):
    original = WIRE_ARRAYS[name]
    # a fresh array a case, where a copy keeps what the case is about
    array = original if name in ("non_contiguous", "read_only") else original.copy()
    before = np.array(array, copy=True)
    want_buffer, want_restored = PARENTS[codec](before)

    serialized = serialize_tensor(array, codec, allow_inplace=allow_inplace)
    assert serialized.buffer == want_buffer
    assert tuple(serialized.size) == original.shape
    assert serialized.dtype == ("bfloat16" if name == "bfloat16" else "float32")
    if not allow_inplace or name in ("read_only", "bfloat16"):
        assert np.asarray(array).tobytes() == before.tobytes()  # the caller's array

    restored = deserialize_tensor(serialized)
    assert restored.dtype == want_restored.dtype and restored.shape == want_restored.shape
    assert restored.tobytes() == want_restored.tobytes()


# ---------------------------------------------------------------------------- the cost


def _best_of_many(*calls):
    """Best of 40 of each call, taking turns so that a busy moment of the host falls on
    all of them (five were too few beside fourteen busy processes on eight cores)."""
    best = [float("inf")] * len(calls)
    for _ in range(40):
        for index, call in enumerate(calls):
            began = time.perf_counter()
            call()
            best[index] = min(best[index], time.perf_counter() - began)
    return best


def _part(scale):
    return (np.random.default_rng(13).standard_normal(PART) * scale).astype(np.float32)


@pytest.mark.parametrize("scale", [1e-4, 1e-5])
def test_encoding_small_numbers_costs_what_unit_scale_costs(scale):
    """A ratio, not a wall-clock bound: numpy's cast reads 11 x and 25-30 x here."""
    codec, unit, small = Float16Compression(), _part(1.0), _part(scale)
    unit_s, small_s = _best_of_many(lambda: codec.compress(unit), lambda: codec.compress(small))
    assert small_s < 4 * unit_s, (small_s, unit_s)


def test_encoding_unit_scale_costs_what_it_did():
    codec, unit = Float16Compression(), _part(1.0)
    ours, parents = _best_of_many(
        lambda: codec.compress(unit), lambda: np.clip(unit, -FP16_MAX, FP16_MAX).astype(np.float16).tobytes())
    assert ours < 1.3 * parents, (ours, parents)


@pytest.mark.parametrize("scale", [1e-4, 1e-5])
def test_decoding_small_numbers_costs_what_unit_scale_costs(scale):
    """numpy's cast reads 5 x and 8 x here."""
    codec = Float16Compression()
    unit, small = codec.compress(_part(1.0)), codec.compress(_part(scale))
    unit_s, small_s = _best_of_many(lambda: codec.extract(unit), lambda: codec.extract(small))
    assert small_s < 4 * unit_s, (small_s, unit_s)


def test_decoding_unit_scale_costs_what_it_did():
    codec = Float16Compression()
    unit = codec.compress(_part(1.0))
    ours, parents = _best_of_many(lambda: codec.extract(unit), lambda: parent_decode(unit.buffer).reshape(PART))
    assert ours < 1.3 * parents, (ours, parents)
