"""AOT-lower the Pallas kernels and the sharded train step for the TPU target on a
CPU-only host: `jax.export` with platforms=("tpu",) runs the Pallas lowering path —
kernel tiling rules, shape/layout checks, custom-call emission — without executing
anything. It stops at the Mosaic custom call: Mosaic's own passes (layout inference,
VMEM allocation) run only inside the TPU compile, and numerics only on a chip. Both
are `chip_smoke.py`'s job (phase K compiles and checks every kernel there)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from hivemind_tpu.ops.pallas_attention import flash_attention, flash_attention_lse
from hivemind_tpu.ops.pallas_quantization import (
    pallas_blockwise_dequantize,
    pallas_blockwise_quantize,
)


def _export_for_tpu(fn, *args):
    return export.export(jax.jit(fn), platforms=("tpu",))(*args)


def _assert_mosaic_lowered(exported):
    assert "tpu" in [p.lower() for p in exported.platforms]
    text = exported.mlir_module()
    assert "tpu_custom_call" in text or "mosaic" in text.lower(), (
        "the Pallas kernel did not lower through Mosaic for the TPU target"
    )


def test_flash_attention_forward_lowers_for_tpu():
    q = jax.ShapeDtypeStruct((2, 4, 256, 64), jnp.bfloat16)
    exported = _export_for_tpu(lambda a, b, c: flash_attention(a, b, c, causal=True), q, q, q)
    _assert_mosaic_lowered(exported)


def test_flash_attention_lse_lowers_for_tpu():
    q = jax.ShapeDtypeStruct((1, 2, 512, 64), jnp.float32)
    exported = _export_for_tpu(lambda a, b, c: flash_attention_lse(a, b, c), q, q, q)
    _assert_mosaic_lowered(exported)


def test_flash_attention_backward_lowers_for_tpu():
    q = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.float32)

    def loss(a, b, c):
        return jnp.sum(flash_attention(a, b, c, causal=True))

    exported = _export_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    _assert_mosaic_lowered(exported)


# the shapes the benchmark's cells feed the kernels (PERF.md §4): a tile the Pallas
# lowering refuses must fail here, not on the chip (Mosaic's own verdict on the same
# shapes: tests/test_tpu_compile.py)
CELL_SHAPES = {
    "albert-32x512x12x64-bidirectional": ((32, 512, 12, 64), False),
    "finetune-4x512x32x128-causal": ((4, 512, 32, 128), True),
}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_flash_attention_lowers_for_tpu_at_the_cells_shapes(cell, direction):
    shape, causal = CELL_SHAPES[cell]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(a, b, c):
        return jnp.sum(flash_attention(a, b, c, causal).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if direction == "backward" else (
        lambda a, b, c: flash_attention_lse(a, b, c, causal))
    exported = _export_for_tpu(fn, q, q, q)
    _assert_mosaic_lowered(exported)
    # one forward custom call a layer application (the benchmark counts steps by them)
    assert exported.mlir_module().count("tpu_custom_call") == (2 if direction == "backward" else 1)


def test_blockwise_quantization_kernels_lower_for_tpu():
    flat = jax.ShapeDtypeStruct((1 << 16,), jnp.float32)
    exported = _export_for_tpu(lambda x: pallas_blockwise_quantize(x, block_size=4096), flat)
    _assert_mosaic_lowered(exported)

    codes = jax.ShapeDtypeStruct((16, 4096), jnp.int8)
    absmax = jax.ShapeDtypeStruct((16,), jnp.float32)
    exported = _export_for_tpu(
        lambda c, a: pallas_blockwise_dequantize(c, a, block_size=4096), codes, absmax
    )
    _assert_mosaic_lowered(exported)


def test_sharded_albert_train_step_lowers_for_tpu():
    """The FULL flagship train step — dp×tp×sp sharded ALBERT MLM fwd+bwd+adamw —
    lowers for an 8-device TPU mesh from this CPU host: every collective, every
    sharding constraint, and the attention core pass TPU lowering."""
    import optax

    from hivemind_tpu.models import (
        AlbertConfig,
        make_synthetic_mlm_batch,
        make_train_step,
    )
    from hivemind_tpu.parallel import make_mesh, params_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(dp=2, tp=2, sp=2)
    config = AlbertConfig.tiny(mesh=mesh, num_heads=4)
    optimizer = optax.adamw(1e-4)
    model, train_step = make_train_step(config, optimizer, masked_loss_fraction=0.25)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, 8, 64)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    opt_state = jax.jit(optimizer.init)(params)

    shardings = params_shardings(params, mesh)
    params = jax.device_put(params, shardings)
    batch = jax.device_put(batch, NamedSharding(mesh, P("dp", "sp")))
    with mesh:
        exported = export.export(jax.jit(train_step), platforms=("tpu",))(
            params, opt_state, batch
        )
    assert "tpu" in [p.lower() for p in exported.platforms]
    assert exported.nr_devices == 8
    # the sharded step really carries cross-device communication for the mesh
    text = exported.mlir_module()
    assert "sharding" in text, "no sharding annotations survived lowering"


def test_sharded_train_step_with_flash_core_lowers_for_tpu(monkeypatch):
    """The composition that actually runs on a slice: the ring/flash attention
    core INSIDE the dp×tp×sp-sharded train step, exported for the TPU target
    (HIVEMIND_TPU_FORCE_FLASH overrides the backend gate for AOT workflows).
    The Mosaic custom call must survive into the sharded module."""
    import optax

    from hivemind_tpu.models import (
        AlbertConfig,
        make_synthetic_mlm_batch,
        make_train_step,
    )
    from hivemind_tpu.parallel import make_mesh, params_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(dp=2, tp=2, sp=2)
    # flash kernels tile (128, 128) blocks: use a flash-sized sequence
    config = AlbertConfig.tiny(mesh=mesh, num_heads=4, max_position=256)
    optimizer = optax.adamw(1e-4)
    model, train_step = make_train_step(config, optimizer, masked_loss_fraction=0.25)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, 8, 256)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    opt_state = jax.jit(optimizer.init)(params)
    params = jax.device_put(params, params_shardings(params, mesh))
    batch = jax.device_put(batch, NamedSharding(mesh, P("dp", "sp")))
    # force the flash core only for the export TRACE (init above runs eagerly on
    # the CPU backend, where a non-interpret pallas_call cannot execute)
    monkeypatch.setenv("HIVEMIND_TPU_FORCE_FLASH", "1")
    with mesh:
        exported = export.export(jax.jit(train_step), platforms=("tpu",))(
            params, opt_state, batch
        )
    assert exported.nr_devices == 8
    text = exported.mlir_module()
    assert "tpu_custom_call" in text or "mosaic" in text.lower(), (
        "the flash core did not ride the sharded train step into the TPU module"
    )


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_granite_decode_step_lowers_for_tpu(kind):
    """One position a row of a `granite_h_block` of each kind, as a batched decode program hands it over (the rows' cache
    leaves as tuples of their own arrays, a position a row), exported for the TPU target at the published widths and two
    rows: the block's own step, its jitted one-row functions inside, lowers without the chip."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden, max_len, rows = 2048, 256, 2
    module = name_to_block["granite_h_block"](hidden, kind=kind)
    params = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden), jnp.float32))["params"])
    cache = jax.eval_shape(lambda: module.init_decode_cache(1, max_len))
    step = lambda p, x, columns, index: module.apply({"params": p}, x, *columns, index)
    exported = _export_for_tpu(step, params, jax.ShapeDtypeStruct((rows, 1, hidden), jnp.float32), tuple((leaf,) * rows for leaf in cache),
                               jax.ShapeDtypeStruct((rows,), jnp.int32))
    assert "tpu" in [p.lower() for p in exported.platforms]
    text = exported.mlir_module()
    assert len(exported.out_avals) == 1 + 2 * rows and exported.out_avals[0].shape == (rows, 1, hidden)
    assert ("4352" in text) == (kind == "mamba")  # the mixer's convolution channels; the attention's text holds none


def test_lowering_rejects_non_tpu_execution():
    """Executing a TPU-exported artifact on this CPU host must fail loudly (the
    artifact is for the TPU target) — guards against silently grading CPU
    numbers as TPU results."""
    q = jax.ShapeDtypeStruct((1, 2, 128, 64), jnp.float32)
    exported = _export_for_tpu(lambda a, b, c: flash_attention(a, b, c), q, q, q)
    array = np.zeros((1, 2, 128, 64), np.float32)
    with pytest.raises(Exception):
        exported.call(array, array, array)
