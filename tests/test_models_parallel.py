"""Flagship model + parallel layer: ALBERT forward/loss, ring attention vs plain
attention equivalence, multi-device sharded training step on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hivemind_tpu.models import AlbertConfig, AlbertForMaskedLM, make_synthetic_mlm_batch, make_train_step, mlm_loss
from hivemind_tpu.parallel import make_mesh, params_shardings, plain_attention, ring_attention

# the comparisons' own side as ONE program a shape, not one an operation (ISSUE 53)
plain_attention = jax.jit(plain_attention, static_argnames="causal")


def test_albert_forward_and_shapes():
    config = AlbertConfig.tiny()
    model = AlbertForMaskedLM(config)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, batch_size=2, seq_len=16)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    logits = jax.jit(model.apply)({"params": params}, batch["input_ids"])
    assert logits.shape == (2, 16, config.vocab_size)
    assert logits.dtype == jnp.float32
    loss = jax.jit(mlm_loss)(logits, batch["labels"], batch["mlm_mask"])
    assert np.isfinite(float(loss)) and float(loss) > 0
    # parameter sharing: one layer's worth of encoder params regardless of depth
    deep = AlbertForMaskedLM(AlbertConfig.tiny(num_layers=6))
    deep_params = jax.jit(deep.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    count = lambda p: sum(x.size for x in jax.tree_util.tree_leaves(p))
    assert count(deep_params) == count(params)


def test_albert_training_reduces_loss():
    config = AlbertConfig.tiny()
    optimizer = optax.adam(1e-3)
    model, train_step = make_train_step(config, optimizer)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, batch_size=4, seq_len=32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    opt_state = jax.jit(optimizer.init)(params)
    step = jax.jit(train_step)
    first_loss = None
    for _ in range(30):
        loss, params, opt_state = step(params, opt_state, batch)
        first_loss = first_loss if first_loss is not None else float(loss)
    assert float(loss) < first_loss * 0.7, f"loss {first_loss} -> {float(loss)}"


def test_ring_attention_matches_plain():
    """Ring attention over the sp axis must reproduce single-device attention."""
    mesh = make_mesh(dp=1, tp=1, sp=4)
    batch, seq, heads, dim = 2, 32, 4, 8
    rng = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(key, (batch, seq, heads, dim), jnp.float32)
        for key in jax.random.split(rng, 3)
    )
    expected = plain_attention(q, k, v)

    from functools import partial
    from jax import shard_map

    spec = P(None, "sp", None, None)
    ring = shard_map(
        partial(ring_attention, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    with mesh:
        result = jax.jit(ring)(q, k, v)
    assert np.allclose(np.asarray(result), np.asarray(expected), atol=1e-4)


def test_causal_ring_attention_matches_plain():
    """CAUSAL ring attention over contiguous sequence shards == single-device causal
    attention: past shards contribute fully, the local shard causally, future shards
    not at all."""
    from functools import partial
    from jax import shard_map

    mesh = make_mesh(dp=1, tp=1, sp=4)
    batch, seq, heads, dim = 2, 32, 4, 8
    rng = jax.random.PRNGKey(2)
    q, k, v = (
        jax.random.normal(key, (batch, seq, heads, dim), jnp.float32)
        for key in jax.random.split(rng, 3)
    )
    expected = plain_attention(q, k, v, causal=True)

    spec = P(None, "sp", None, None)
    ring = shard_map(
        partial(ring_attention, axis_name="sp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    with mesh:
        result = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(result), np.asarray(expected), rtol=1e-4, atol=1e-5)


def test_causal_lm_trains_and_shards():
    """The decoder-only flagship: loss decreases on one chip, and the same step
    compiles and descends under a dp×tp×sp mesh with causal ring attention."""
    from hivemind_tpu.models import CausalLMConfig, make_causal_train_step, make_synthetic_lm_batch

    config = CausalLMConfig.tiny()
    optimizer = optax.adam(1e-3)
    model, train_step = make_causal_train_step(config, optimizer)
    batch = make_synthetic_lm_batch(jax.random.PRNGKey(0), config, 4, 32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    opt_state = jax.jit(optimizer.init)(params)
    step = jax.jit(train_step)
    first_loss = None
    for _ in range(25):
        loss, params, opt_state = step(params, opt_state, batch)
        first_loss = first_loss if first_loss is not None else float(loss)
    assert float(loss) < first_loss * 0.8, (first_loss, float(loss))

    mesh = make_mesh(dp=2, tp=2, sp=2)
    sharded_config = CausalLMConfig.tiny(mesh=mesh)
    model, train_step = make_causal_train_step(sharded_config, optimizer)
    batch = make_synthetic_lm_batch(jax.random.PRNGKey(0), sharded_config, 4, 32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    opt_state = jax.jit(optimizer.init)(params)
    params = jax.device_put(params, params_shardings(params, mesh))
    batch = jax.device_put(batch, NamedSharding(mesh, P("dp", "sp")))
    with mesh:
        step = jax.jit(train_step)
        loss1, params, opt_state = step(params, opt_state, batch)
        loss2, _, _ = step(params, opt_state, batch)
    assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)
    q_kernel = params["layer_0"]["query"]["kernel"]
    assert "tp" in str(q_kernel.sharding.spec)


def test_ring_flash_attention_matches_plain():
    """Flash-core ring attention (per-step Pallas kernel + log-sum-exp shard merge,
    interpret mode on CPU) must reproduce single-device attention, and its
    recompute-backward must match plain attention's gradients."""
    from functools import partial

    from jax import shard_map
    from hivemind_tpu.parallel.ring_attention import ring_flash_attention

    mesh = make_mesh(dp=1, tp=1, sp=4)
    batch, seq, heads, dim = 2, 512, 2, 16  # 128 per shard: one full flash block
    rng = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(key, (batch, seq, heads, dim), jnp.float32)
        for key in jax.random.split(rng, 3)
    )
    expected = plain_attention(q, k, v)

    spec = P(None, "sp", None, None)
    ring = shard_map(
        partial(ring_flash_attention, axis_name="sp", interpret=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,  # the varying-axes checker can't see through pallas_call outputs
    )
    with mesh:
        result = jax.jit(ring)(q, k, v)
    assert np.allclose(np.asarray(result), np.asarray(expected), atol=1e-4)

    # bf16 inputs (the flagship model's compute dtype) must trace and stay close:
    # the scan carries are fp32 regardless of input dtype
    q16, k16, v16 = (x.astype(jnp.bfloat16) for x in (q, k, v))
    with mesh:
        result16 = jax.jit(ring)(q16, k16, v16)
    assert result16.dtype == jnp.bfloat16
    assert np.allclose(
        np.asarray(result16, np.float32), np.asarray(expected), atol=0.05
    )

    # CAUSAL flash ring: local block via the kernel's causal path, future shards
    # excluded by lse = -inf before the merge
    causal_ring = shard_map(
        partial(ring_flash_attention, axis_name="sp", interpret=True, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    with mesh:
        causal_result = jax.jit(causal_ring)(q, k, v)
    assert np.allclose(
        np.asarray(causal_result), np.asarray(plain_attention(q, k, v, causal=True)), atol=1e-4
    )

    # gradients flow through the custom_vjp einsum-ring recompute
    def ring_loss(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def plain_loss(q, k, v):
        return jnp.sum(plain_attention(q, k, v) ** 2)

    with mesh:
        ring_grads = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    plain_grads = jax.jit(jax.grad(plain_loss, argnums=(0, 1, 2)))(q, k, v)
    for rg, pg in zip(ring_grads, plain_grads):
        np.testing.assert_allclose(np.asarray(rg), np.asarray(pg), rtol=1e-3, atol=1e-4)


def test_sharded_training_step_8_devices():
    """Full dp×tp×sp sharded train step on the virtual 8-device mesh — the same path
    the driver's dryrun_multichip exercises."""
    mesh = make_mesh(dp=2, tp=2, sp=2)
    config = AlbertConfig.tiny(mesh=mesh)
    optimizer = optax.sgd(1e-2)
    model, train_step = make_train_step(config, optimizer)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, batch_size=4, seq_len=32)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    opt_state = jax.jit(optimizer.init)(params)

    shardings = params_shardings(params, mesh)
    params = jax.device_put(params, shardings)
    batch_sharded = jax.device_put(
        batch, NamedSharding(mesh, P("dp", "sp"))
    )
    with mesh:
        step = jax.jit(train_step)
        loss, new_params, new_opt_state = step(params, opt_state, batch_sharded)
        loss2, _, _ = step(new_params, new_opt_state, batch_sharded)
    assert np.isfinite(float(loss)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss)  # sgd on the same batch must descend
    # tp sharding actually applied to attention kernels
    q_kernel = new_params["shared_layer"]["query"]["kernel"]
    assert "tp" in str(q_kernel.sharding.spec)


def test_masked_only_loss_equals_full_loss():
    """loss_masked_only with a sufficient budget equals the full-logits mlm_loss
    (the bench's throughput lever must not change the objective)."""
    from hivemind_tpu.models import AlbertConfig, AlbertForMaskedLM, make_synthetic_mlm_batch, mlm_loss

    config = AlbertConfig.tiny(max_position=64)
    model = AlbertForMaskedLM(config)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, 4, 64)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"][:1, :8])["params"]

    full = jax.jit(lambda params, batch: mlm_loss(
        model.apply({"params": params}, batch["input_ids"]), batch["labels"], batch["mlm_mask"]
    ))(params, batch)
    masked = jax.jit(lambda params, batch: model.apply(
        {"params": params}, batch["input_ids"], batch["labels"], batch["mlm_mask"], 32,
        method=AlbertForMaskedLM.loss_masked_only,
    ))(params, batch)
    np.testing.assert_allclose(float(masked), float(full), rtol=1e-5)

    # gradients agree too (the actual training signal), across EVERY parameter
    import optax
    from hivemind_tpu.models import make_train_step

    updated = {}
    for fraction in (0.5, None):
        _model, step = make_train_step(config, optax.sgd(0.1), masked_loss_fraction=fraction)
        opt_state = jax.jit(optax.sgd(0.1).init)(params)
        loss, new_params, _ = jax.jit(step)(params, opt_state, batch)
        updated[fraction] = new_params
    for masked_leaf, full_leaf in zip(
        jax.tree_util.tree_leaves(updated[0.5]), jax.tree_util.tree_leaves(updated[None])
    ):
        # bf16 compute: gathering positions before the head reorders reductions,
        # so per-element grads differ by bf16 noise (~1% rel), not exactly
        np.testing.assert_allclose(
            np.asarray(masked_leaf), np.asarray(full_leaf), rtol=0.05, atol=1e-4
        )


def test_remat_training_step_matches_plain():
    """remat=True must be numerically identical (same params, same math, only the
    backward-pass activation strategy changes) — it is purely a memory/batch lever."""
    import optax

    from hivemind_tpu.models import AlbertConfig, make_synthetic_mlm_batch, make_train_step

    results = {}
    for remat in (False, True):
        config = AlbertConfig.tiny(max_position=64, remat=remat)
        model, step = make_train_step(config, optax.sgd(0.1))
        batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, 4, 64)
        params = jax.jit(model.init)(jax.random.PRNGKey(1), batch["input_ids"][:1, :8])["params"]
        opt_state = jax.jit(optax.sgd(0.1).init)(params)
        loss, new_params, _ = jax.jit(step)(params, opt_state, batch)
        results[remat] = (float(loss), new_params)

    assert results[False][0] == results[True][0], "remat changed the loss"
    for plain_leaf, remat_leaf in zip(
        jax.tree_util.tree_leaves(results[False][1]), jax.tree_util.tree_leaves(results[True][1])
    ):
        # the recompute changes XLA fusion boundaries, so bf16 rounding in the
        # backward pass differs slightly; the training signal must still agree
        np.testing.assert_allclose(
            np.asarray(plain_leaf), np.asarray(remat_leaf), rtol=0.05, atol=1e-3
        )


def test_pallas_flash_attention_matches_plain():
    """Fused flash kernel (interpret mode on CPU) == reference einsum attention,
    bidirectional + causal, including a seq that is not a block multiple, and
    gradients flow through the custom_vjp recompute path."""
    import numpy as np
    from hivemind_tpu.ops.pallas_attention import flash_attention

    flash_attention = jax.jit(flash_attention, static_argnums=(3, 4))
    rng = np.random.RandomState(0)
    for seq in (128, 192, 320):  # 192/320: padded tail blocks + multi-block carry
        q, k, v = (
            jnp.asarray(rng.randn(2, seq, 4, 16).astype(np.float32)) for _ in range(3)
        )
        for causal in (False, True):
            fused = flash_attention(q, k, v, causal, True)
            exact = plain_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(fused), np.asarray(exact), rtol=2e-5, atol=2e-5)

    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 8).astype(np.float32)) for _ in range(3))
    loss_fused = lambda q: flash_attention(q, k, v, True, True).sum()
    loss_exact = lambda q: plain_attention(q, k, v, causal=True).sum()
    np.testing.assert_allclose(
        np.asarray(jax.jit(jax.grad(loss_fused))(q)), np.asarray(jax.jit(jax.grad(loss_exact))(q)),
        rtol=2e-5, atol=2e-5,
    )


def _flash_against_float32(seq, head_dim, causal, dtype):
    """How far (out, dq, dk, dv) lie from the float32 `plain_attention`'s, for the flash
    kernels (interpret mode) and for `plain_attention`, both fed `dtype` operands: the
    largest difference over the reference's largest value, and the l2 distance over the
    reference's l2 norm."""
    from hivemind_tpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(seq + head_dim)
    q, k, v = (jnp.asarray(rng.randn(1, seq, 2, head_dim), dtype) for _ in range(3))
    weight = jnp.asarray(np.cos(np.arange(head_dim)), jnp.float32)  # non-uniform cotangent

    def results(attention, *operands):
        loss = lambda q, k, v: (attention(q, k, v).astype(jnp.float32) * weight).sum()
        return jax.jit(lambda *operands: (attention(*operands), *jax.grad(loss, argnums=(0, 1, 2))(*operands)))(*operands)

    exact = results(lambda q, k, v: plain_attention(q, k, v, causal=causal),
                    *(x.astype(jnp.float32) for x in (q, k, v)))

    def distances(got):
        diffs = [(np.asarray(g, np.float32) - np.asarray(e), np.asarray(e)) for g, e in zip(got, exact)]
        return [(float(np.abs(d).max() / np.abs(e).max()), float(np.linalg.norm(d) / np.linalg.norm(e)))
                for d, e in diffs]

    fused = results(lambda q, k, v: flash_attention(q, k, v, causal, True), q, k, v)
    assert all(g.dtype == dtype and g.shape == q.shape for g in fused)
    return distances(fused), distances(results(lambda q, k, v: plain_attention(q, k, v, causal=causal), q, k, v))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "seq,head_dim,causal",
    [
        (512, 64, False),  # ALBERT: one whole-row tile
        (512, 128, True),  # fine-tuning through Mistral blocks
        (128, 128, True),  # the shortest prefill: one tile
        (1024, 128, True),  # the longest prefill: the carry and the skipped blocks
        (200, 64, True),  # tail padding inside a causal tile
        (640, 128, False),  # five 128s: a whole row of keys beside 128-row query blocks
    ],
)
def test_pallas_flash_tiles_match_float32_plain(seq, head_dim, causal, dtype):
    """Whatever tile the rule picks, forward and all three gradients agree with a
    float32 `plain_attention`: float32 operands to 1e-5 of the largest value (nothing
    was narrowed), bf16 operands no farther (l2) than `plain_attention` on the same
    bf16 operands (its largest single difference is its output's own rounding, which
    the two share)."""
    fused, plain = _flash_against_float32(seq, head_dim, causal, dtype)
    for name, (fused_max, fused_l2), (_plain_max, plain_l2) in zip(("out", "dq", "dk", "dv"), fused, plain):
        if dtype == jnp.float32:
            assert fused_max <= 1e-5, f"{name}: flash is {fused_max:.3g} of the largest value from float32"
        else:
            assert fused_l2 <= plain_l2, f"{name}: flash is {fused_l2:.3g} from float32, plain_attention {plain_l2:.3g}"


@pytest.mark.parametrize(
    "seq,head_dim,itemsize,causal,expected",
    [
        (512, 64, 2, False, (512, 512, 512)),  # ALBERT: the KV sweep is one step
        (512, 128, 2, True, (512, 512, 512)),  # causal: one step beats three tiles of four (measured)
        (128, 128, 2, True, (128, 128, 128)),
        (1024, 128, 2, True, (1024, 512, 512)),  # past one tile: three of four computed
        (640, 128, 2, False, (640, 128, 640)),  # a 640-token prompt does not pay for 1,024
        (640, 128, 2, True, (640, 128, 640)),  # 5 steps over the square beat 25 steps over 15 tiles
        (200, 64, 4, False, (256, 256, 256)),
        (512, 64, 4, False, (512, 256, 512)),  # float32 operands: a smaller tile under the same budget
        (8192, 128, 2, False, (8192, 512, 512)),  # past the budget the KV sweep and its carry come back
    ],
)
def test_pallas_flash_tile_rule(seq, head_dim, itemsize, causal, expected):
    from hivemind_tpu.ops import pallas_attention as pa

    tiles = pa._tiles(seq, head_dim, itemsize, causal)
    assert tuple(tiles) == expected
    assert tiles.padded - seq < 128 and tiles.padded % tiles.block_q == 0 and tiles.padded % tiles.block_k == 0
    smallest = tiles.block_q == tiles.block_k == 128
    assert smallest or pa._step_bytes(tiles.block_q, tiles.block_k, head_dim, itemsize) <= pa._VMEM_BUDGET


def test_pallas_flash_backward_kernels_match_plain_grads():
    """The FUSED two-pass backward (dQ / dK+dV kernels from the saved lse) must
    reproduce the einsum path's gradients for all inputs — bidirectional and
    causal, block-aligned and padded, with a non-uniform cotangent so dP/delta
    terms are actually exercised (VERDICT r2 item 7)."""
    import numpy as np
    from hivemind_tpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(1)
    w = jnp.asarray(np.cos(np.arange(16)), jnp.float32)  # non-uniform cotangent
    for causal in (False, True):
        for seq in (128, 200):
            q, k, v = (
                jnp.asarray(rng.randn(2, seq, 4, 16).astype(np.float32)) for _ in range(3)
            )
            loss_fused = lambda q, k, v: (flash_attention(q, k, v, causal, True) * w).sum()
            loss_exact = lambda q, k, v: (plain_attention(q, k, v, causal=causal) * w).sum()
            grads_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
            grads_exact = jax.jit(jax.grad(loss_exact, argnums=(0, 1, 2)))(q, k, v)
            for name, gf, ge in zip("qkv", grads_fused, grads_exact):
                np.testing.assert_allclose(
                    np.asarray(gf), np.asarray(ge), rtol=2e-4, atol=2e-5,
                    err_msg=f"d{name} causal={causal} seq={seq}",
                )
