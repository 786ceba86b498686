"""Parity checks of every Pallas kernel against float32 references — the chip-trust
gate.

The CPU test suite covers the kernels in interpret mode, but Mosaic compilation on
a real TPU is a different code path (layout inference, VMEM allocation, tiling and
dtype rules). `validate_kernels` runs the same checks either way: compiled
(``interpret=False``: `chip_smoke.py` phase K, at the shapes the main path feeds the
kernels) or interpreted (`tests/test_device_tpu.py`, small shapes).
Nothing is caught here: a kernel that does not compile raises its own error, and a
kernel that compiles but disagrees raises :class:`KernelCheckError`."""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class KernelCheckError(AssertionError):
    """A kernel compiled and ran but disagrees with its reference."""


class AttentionShape(NamedTuple):
    name: str
    batch: int
    seq: int
    heads: int
    head_dim: int
    causal: bool


# what the benchmark's cells feed the flash kernels (PERF.md §4): the ALBERT-base train
# step (32 sequences of 512, 12 heads x 64, bidirectional), a fine-tuning request through
# Mistral-7B blocks (4 x 512, 32 heads x 128, causal) and the longest prefill of the
# decode cell (one prompt of 1024: four causal tiles a side)
MAIN_PATH_ATTENTION = (
    AttentionShape("albert-base", 32, 512, 12, 64, False),
    AttentionShape("mistral-finetune", 4, 512, 32, 128, True),
    AttentionShape("mistral-prefill", 1, 1024, 32, 128, True),
)
MAIN_PATH_QUANT_SHAPE = (4096, 11008)  # one Llama-7B MLP kernel

# bf16 operands carry 8 significant bits (eps 2^-8 = 3.9e-3); the kernels accumulate
# in float32, so errors against the float32 reference stay within a few eps of the
# largest value
ATTENTION_TOLERANCE = 2e-2


def _max_rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-9))


def _require(condition: bool, message: str) -> None:
    if not condition:
        raise KernelCheckError(message)


def check_flash_attention(shape: AttentionShape, interpret: bool) -> Dict[str, float]:
    """Flash forward, dQ, dK and dV in bf16 against `plain_attention` in float32.
    Returns the max error of each, relative to the reference's largest value."""
    from hivemind_tpu.ops.attention import plain_attention
    from hivemind_tpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(0)
    dims = (shape.batch, shape.seq, shape.heads, shape.head_dim)
    q, k, v = (jnp.asarray(rng.randn(*dims), jnp.bfloat16) for _ in range(3))
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    weight = jnp.asarray(np.cos(np.arange(shape.head_dim)), jnp.float32)

    def fused_loss(q, k, v):
        out = flash_attention(q, k, v, shape.causal, interpret)
        return (out.astype(jnp.float32) * weight).sum()

    def exact_loss(q, k, v):
        return (plain_attention(q, k, v, causal=shape.causal) * weight).sum()

    fused = flash_attention(q, k, v, shape.causal, interpret)
    fused_grads = jax.grad(fused_loss, argnums=(0, 1, 2))(q, k, v)
    # float32 matmuls on a TPU default to one bf16 pass: the reference must not
    with jax.default_matmul_precision("float32"):
        exact = plain_attention(q32, k32, v32, causal=shape.causal)
        exact_grads = jax.grad(exact_loss, argnums=(0, 1, 2))(q32, k32, v32)

    _require(fused.shape == dims and fused.dtype == jnp.bfloat16,
             f"{shape.name}: flash output is {fused.dtype}{fused.shape}, expected bfloat16{dims}")
    errors = {"fwd": _max_rel_err(fused, exact)}
    for name, got, want in zip(("dq", "dk", "dv"), fused_grads, exact_grads):
        errors[name] = _max_rel_err(got, want)
    for name, err in errors.items():
        _require(np.isfinite(err) and err < ATTENTION_TOLERANCE,
                 f"{shape.name}: flash {name} differs from the float32 reference by {err:.3g} "
                 f"(tolerance {ATTENTION_TOLERANCE})")
    return errors


def check_blockwise_int8(shape: Tuple[int, int], interpret: bool) -> Dict[str, float]:
    """The blockwise int8 quantize / dequantize kernels on a ``shape`` float32
    matrix against numpy. Codes may differ from numpy's by one step where
    ``x * (127 / absmax)`` lands on a rounding boundary (the two divide in a
    different order); the decoder must reproduce ``codes * absmax / 127`` and the
    round trip must stay inside half a quantization step."""
    from hivemind_tpu.ops.pallas_quantization import (
        pallas_blockwise_dequantize,
        pallas_blockwise_quantize,
    )

    block = 4096
    x = np.random.RandomState(1).randn(int(np.prod(shape))).astype(np.float32)
    _require(x.size % block == 0, f"{shape} is not a whole number of {block}-blocks")
    codes, absmax = pallas_blockwise_quantize(jnp.asarray(x), block_size=block, interpret=interpret)
    restored = pallas_blockwise_dequantize(codes, absmax, block_size=block, interpret=interpret)
    codes, absmax, restored = np.asarray(codes), np.asarray(absmax), np.asarray(restored)

    blocks = x.reshape(-1, block)
    want_absmax = np.abs(blocks).max(axis=1)
    want_codes = np.clip(np.round(blocks * (127.0 / want_absmax)[:, None]), -127, 127)
    code_diff = np.abs(codes.astype(np.int32) - want_codes.astype(np.int32))
    half_step = want_absmax[:, None] / 127.0 / 2.0
    result = {
        "absmax_err": float(np.abs(absmax - want_absmax).max()),
        "codes_off_by_one_fraction": float((code_diff > 0).mean()),
        "decode_err": float(
            np.abs(restored.reshape(-1, block) - codes.astype(np.float32) * (absmax[:, None] / 127.0)).max()
        ),
        "roundtrip_over_half_step": float((np.abs(restored.reshape(-1, block) - blocks) / half_step).max()),
    }
    _require(codes.dtype == np.int8 and codes.shape == blocks.shape, f"codes are {codes.dtype}{codes.shape}")
    _require(result["absmax_err"] == 0.0, f"absmax differs from numpy by {result['absmax_err']:.3g}")
    _require(int(code_diff.max()) <= 1 and result["codes_off_by_one_fraction"] < 1e-4,
             f"int8 codes differ from numpy: max step {int(code_diff.max())}, "
             f"fraction {result['codes_off_by_one_fraction']:.3g}")
    _require(result["decode_err"] < 1e-5, f"decoder differs from codes*absmax/127 by {result['decode_err']:.3g}")
    _require(result["roundtrip_over_half_step"] < 1.01,
             f"round trip leaves {result['roundtrip_over_half_step']:.3f} half-steps of error")
    return result


def validate_kernels(
    interpret: bool,
    attention_shapes: Sequence[AttentionShape] = MAIN_PATH_ATTENTION,
    quant_shape: Tuple[int, int] = MAIN_PATH_QUANT_SHAPE,
) -> Dict[str, Any]:
    """Every ``pallas_call`` in the tree (flash forward, flash backward; blockwise int8
    quantize, dequantize) against its float32 reference. Returns the measured
    errors keyed by check; raises on the first kernel that fails."""
    report: Dict[str, Any] = {"backend": jax.default_backend(), "interpret": interpret}
    for shape in attention_shapes:
        report[f"flash[{shape.name}]"] = check_flash_attention(shape, interpret)
    report[f"blockwise_int8[{quant_shape[0]}x{quant_shape[1]}]"] = check_blockwise_int8(
        quant_shape, interpret
    )
    return report
