"""`ops/ssm.py`: the Mamba-2 recurrence as a one-position step and as a chunked scan, and
the short convolution that feeds it, against each other and against the plain reference's
position-by-position `lax.scan` (`perf/reference/nemotron_h_block.py`). CPU, float32: the
CPU multiplies float32 operands in float32, so the three agree to rounding."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from hivemind_tpu.ops import ssm  # noqa: E402
from perf.reference import nemotron_h_block as reference  # noqa: E402

HEADS, DIM, GROUPS, WIDTH, TAPS = 8, 4, 2, 6, 4
CHANNELS = HEADS * DIM + 2 * GROUPS * WIDTH

# each side of a comparison as ONE program a shape, not one an operation; inputs are drawn in numpy
ssd_scan, ssd_step = jax.jit(ssm.ssd_scan, static_argnames="chunk"), jax.jit(ssm.ssd_step)
conv_chunk, conv_step = jax.jit(ssm.conv_chunk), jax.jit(ssm.conv_step)
recurrence, causal_conv = jax.jit(reference.recurrence), jax.jit(reference.causal_conv)


def _inputs(seed: int, batch: int, seq: int):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    dt = np.log1p(np.exp(draw(batch, seq, HEADS) - 2.0))  # softplus
    a = -rng.uniform(1.0, 16.0, HEADS).astype(np.float32)
    d = (1.0 + 0.1 * rng.standard_normal(HEADS)).astype(np.float32)
    return draw(batch, seq, HEADS, DIM), draw(batch, seq, GROUPS, WIDTH), draw(batch, seq, GROUPS, WIDTH), dt, a, d


def _empty(batch: int):
    return np.zeros((batch, HEADS, DIM, WIDTH), np.float32)


def _by_steps(x, b, c, dt, a, d, state):
    outs = []
    for t in range(x.shape[1]):
        y, state = ssd_step(x[:, t], b[:, t], c[:, t], dt[:, t], a, d, state)
        outs.append(y)
    return np.stack(outs, axis=1), state


@pytest.mark.parametrize("seq,chunk", [(1, 4), (7, 4), (16, 4), (37, 8), (50, 128)])
def test_scan_equals_steps_equals_the_references_recurrence(seq, chunk):
    x, b, c, dt, a, d = _inputs(seq, 2, seq)
    scanned, scan_state = ssd_scan(x, b, c, dt, a, d, _empty(2), chunk=chunk)
    stepped, step_state = _by_steps(x, b, c, dt, a, d, _empty(2))
    plain, plain_state = recurrence(x, b, c, dt, a)
    plain = plain + d[:, None] * x
    np.testing.assert_allclose(scanned, stepped, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(stepped, plain, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(scan_state, step_state, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(step_state, plain_state, rtol=2e-4, atol=2e-5)


def test_a_head_reads_its_own_group():
    """Head h reads group floor(h / (H / G)): B and C of the other group do not reach it."""
    x, b, c, dt, a, d = _inputs(3, 1, 9)
    y, state = ssd_scan(x, b, c, dt, a, d, _empty(1), chunk=4)
    other = b.copy(), c.copy()
    other[0][:, :, 1] = other[1][:, :, 1] = 0.0
    y2, state2 = ssd_scan(x, *other, dt, a, d, _empty(1), chunk=4)
    first = HEADS // GROUPS
    np.testing.assert_allclose(y[:, :, :first], y2[:, :, :first], rtol=1e-6)
    np.testing.assert_allclose(state[:, :first], state2[:, :first], rtol=1e-6)
    assert float(np.abs(y[:, :, first:] - y2[:, :, first:]).max()) > 1e-3


@pytest.mark.parametrize("cut", [1, 5, 8, 19])
def test_a_scan_carries_its_state_from_chunk_to_chunk_and_into_steps(cut):
    x, b, c, dt, a, d = _inputs(11, 2, 24)
    whole, whole_state = ssd_scan(x, b, c, dt, a, d, _empty(2), chunk=8)
    head, state = ssd_scan(x[:, :cut], b[:, :cut], c[:, :cut], dt[:, :cut], a, d, _empty(2), chunk=8)
    tail, state = ssd_scan(x[:, cut:20], b[:, cut:20], c[:, cut:20], dt[:, cut:20], a, d, state, chunk=8)
    last, state = _by_steps(x[:, 20:], b[:, 20:], c[:, 20:], dt[:, 20:], a, d, state)
    np.testing.assert_allclose(np.concatenate([head, tail, last], axis=1), whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state, whole_state, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("length,padded", [(5, 8), (9, 16), (16, 16), (1, 4)])
def test_right_padding_stays_out_of_the_state(length, padded):
    """The padding after ``length`` has a step size of zero: the real positions' outputs and the
    state are those of the unpadded chunk, whatever the padding holds."""
    x, b, c, dt, a, d = _inputs(5, 2, padded)
    plain, plain_state = ssd_scan(x[:, :length], b[:, :length], c[:, :length], dt[:, :length], a, d, _empty(2), chunk=4)
    out, state = jax.jit(lambda n: ssm.ssd_scan(x, b, c, dt, a, d, _empty(2), n, chunk=4))(jnp.int32(length))
    np.testing.assert_allclose(out[:, :length], plain, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state, plain_state, rtol=2e-4, atol=2e-5)


def test_fast_heads_do_not_overflow():
    """A decay of exp(-16 x 0.7) a position: exp(-a_s) would overflow float32 within a sub-chunk; no decay above 1 is formed."""
    x, b, c, _dt, _a, d = _inputs(9, 1, 64)
    dt, a = np.full((1, 64, HEADS), 0.7, np.float32), np.full((HEADS,), -16.0, np.float32)
    out, state = ssd_scan(x, b, c, dt, a, d, _empty(1), chunk=32)
    stepped, step_state = _by_steps(x, b, c, dt, a, d, _empty(1))
    assert bool(np.isfinite(out).all()) and bool(np.isfinite(state).all())
    np.testing.assert_allclose(out, stepped, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(state, step_state, rtol=2e-4, atol=2e-5)


def _conv_inputs(seed: int, batch: int, seq: int):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    return draw(batch, seq, CHANNELS), draw(TAPS, CHANNELS) / 2, draw(CHANNELS) / 2


@pytest.mark.parametrize("seq", [1, 3, 4, 11])
def test_convolution_chunk_equals_steps_equals_shifted_adds(seq):
    new, weight, bias = _conv_inputs(seq, 2, seq)
    window = np.zeros((2, TAPS - 1, CHANNELS), np.float32)
    chunked, chunk_window = conv_chunk(new, window, weight, bias)
    outs, rolled = [], window
    for t in range(seq):
        out, rolled = conv_step(new[:, t], rolled, weight, bias)
        outs.append(out)
    np.testing.assert_allclose(chunked, np.stack(outs, axis=1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(chunked, causal_conv(new, weight, bias), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(chunk_window, rolled, rtol=1e-6)


@pytest.mark.parametrize("length,padded", [(5, 8), (2, 4), (1, 2), (8, 8)])
def test_the_window_is_cut_from_the_last_real_rows(length, padded):
    """A right-padded chunk leaves the window that the unpadded chunk leaves: its last ``K - 1`` REAL
    rows (with what the window held before, where the chunk is shorter than that), never a padded one."""
    new, weight, bias = _conv_inputs(7, 2, padded)
    before = _conv_inputs(8, 2, TAPS - 1)[0]
    plain, plain_window = conv_chunk(new[:, :length], before, weight, bias)
    out, window = jax.jit(lambda n: ssm.conv_chunk(new, before, weight, bias, n))(jnp.int32(length))
    np.testing.assert_allclose(out[:, :length], plain, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(window, plain_window, rtol=1e-6)
    kept = np.concatenate([before, new[:, :length]], axis=1)[:, -(TAPS - 1):]
    np.testing.assert_allclose(window, kept, rtol=1e-6)


def test_the_window_keeps_its_dtype():
    new, weight, bias = _conv_inputs(2, 1, 6)
    window = jnp.zeros((1, TAPS - 1, CHANNELS), jnp.bfloat16)
    _out, chunk_window = ssm.conv_chunk(new, window, weight, bias)
    _out, step_window = ssm.conv_step(new[:, 0], window, weight, bias)
    assert chunk_window.dtype == step_window.dtype == jnp.bfloat16 and chunk_window.shape == step_window.shape == window.shape


def test_the_scopes_name_the_parts_of_a_lowered_program():
    x, b, c, dt, a, d = _inputs(1, 1, 8)
    new, weight, bias = _conv_inputs(1, 1, 8)
    window = jnp.zeros((1, TAPS - 1, CHANNELS), jnp.float32)
    text = lambda fn, *args: jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert "ssm_scan" in text(lambda *args: ssm.ssd_scan(*args, chunk=4), x, b, c, dt, a, d, _empty(1))
    assert "ssm_step" in text(ssm.ssd_step, x[:, 0], b[:, 0], c[:, 0], dt[:, 0], a, d, _empty(1))
    assert "ssm_conv" in text(ssm.conv_chunk, new, window, weight, bias) and "ssm_conv" in text(ssm.conv_step, new[:, 0], window, weight, bias)
