"""The interpret-mode twin of the chip's kernel check.

`python chip_smoke.py` (phase K) runs `ops.device_check.validate_kernels` with the
kernels compiled by Mosaic, at the main path's shapes, on the TPU — that is where
the chip is checked. This suite pins the CPU, so the same function runs here in
Pallas interpret mode at small shapes: it keeps the checks themselves honest."""

import pytest

from hivemind_tpu.ops.device_check import (
    AttentionShape,
    KernelCheckError,
    check_flash_attention,
    validate_kernels,
)

_SMALL = (
    AttentionShape("bidirectional", 1, 256, 2, 64, False),
    AttentionShape("causal-padded", 1, 64, 2, 128, True),
)


def test_validate_kernels_interpret_mode():
    report = validate_kernels(interpret=True, attention_shapes=_SMALL, quant_shape=(64, 4096))
    assert report["interpret"] is True
    for shape in _SMALL:
        assert set(report[f"flash[{shape.name}]"]) == {"fwd", "dq", "dk", "dv"}
    assert report["blockwise_int8[64x4096]"]["absmax_err"] == 0.0


def test_kernel_disagreement_raises(monkeypatch):
    """A kernel that compiles but is wrong must fail the check, not fill a field."""
    from hivemind_tpu.ops import pallas_attention

    real = pallas_attention.flash_attention
    monkeypatch.setattr(
        pallas_attention, "flash_attention", lambda q, k, v, causal, interpret: real(q, k, v, not causal, interpret)
    )
    with pytest.raises(KernelCheckError, match="differs from the float32 reference"):
        check_flash_attention(_SMALL[1], interpret=True)
