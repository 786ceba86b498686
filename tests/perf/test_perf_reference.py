"""The plain float32 references against the program's own modules, at the
configurations' rehearsal sizes, on seeded random weights (CPU)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import manifest as mf  # noqa: E402
from perf.reference import albert as albert_reference  # noqa: E402
from perf.reference import mistral_block as block_reference  # noqa: E402

ALBERT = mf.rehearsal_config(mf.load_json(mf.PERF / "configs" / "albert-base.json"))
MISTRAL = mf.rehearsal_config(mf.load_json(mf.PERF / "configs" / "mistral-7b-span8.json"))


def _albert(dtype):
    from hivemind_tpu.models import AlbertForMaskedLM, make_mlm_loss_fn, make_synthetic_mlm_batch
    from perf.runners.trainer import _albert_config

    model_sizes, recipe = ALBERT["model"], ALBERT["recipe"]
    config = _albert_config(model_sizes, recipe["seq_len"], dtype=dtype)
    model = AlbertForMaskedLM(config)
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(3), config, 2, recipe["seq_len"])
    params = model.init(jax.random.PRNGKey(4), batch["input_ids"][:1, :8])["params"]
    return model, params, batch, make_mlm_loss_fn


@pytest.mark.parametrize("dtype, loss_tol, grad_tol", [(jnp.float32, 1e-4, 1e-3), (jnp.bfloat16, 5e-2, 5e-2)])
@pytest.mark.parametrize("fraction", [0.25, 0.05])
def test_albert_loss_and_gradient_against_reference(dtype, loss_tol, grad_tol, fraction):
    """In float32 the program must agree to rounding; in bf16 (what the cell runs)
    within the configuration's tolerance. fraction 0.05 makes the budget bite: rows
    with more masked positions than it count only their first ones, on both sides."""
    model, params, batch, make_mlm_loss_fn = _albert(dtype)
    recipe, sizes = ALBERT["recipe"], ALBERT["model"]
    loss, grads = jax.value_and_grad(make_mlm_loss_fn(model, fraction))(params, batch)
    budget = max(1, int(recipe["seq_len"] * fraction))
    want_loss, want_grads = albert_reference.loss_and_grad(
        params, batch, sizes["num_hidden_layers"], sizes["num_attention_heads"], budget)
    assert abs(float(loss) - float(want_loss)) <= loss_tol
    pairs = list(zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)))
    distance = np.sqrt(sum(float(jnp.sum((a - b) ** 2)) for a, b in pairs) / sum(float(jnp.sum(b**2)) for _, b in pairs))
    assert distance <= grad_tol


def _blocks(count=2):
    from hivemind_tpu.moe.server.layers import name_to_block
    from perf.runners.block_server import _block_kwargs, _reference_sizes

    sizes = MISTRAL["model"]
    module = name_to_block["llama_block"](sizes["hidden_size"], **_block_kwargs(sizes))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 24, sizes["hidden_size"])), jnp.float32)
    params = [module.init(jax.random.PRNGKey(10 + i), x[:1, :4])["params"] for i in range(count)]
    return module, params, x, _reference_sizes(sizes)


def test_block_span_forward_against_reference():
    module, params, x, sizes = _blocks()
    got = x
    for block_params in params:
        got = module.apply({"params": block_params}, got)
    want = block_reference.span(params, x, **sizes)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= MISTRAL["tolerances"]["forward_rel"]


def test_block_span_input_gradient_against_reference():
    module, params, x, sizes = _blocks()

    def program(xx):
        for block_params in params:
            xx = module.apply({"params": block_params}, xx)
        return xx

    grad_out = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape), jnp.float32)
    got = jax.vjp(program, x)[1](grad_out)[0]
    _out, want = block_reference.span_input_grad(params, x, grad_out, **sizes)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= MISTRAL["tolerances"]["backward_rel"]


def test_prefill_then_cached_steps_against_the_references_full_forward():
    """Prefill 16 positions, then 8 single-token steps through the block's cache, must
    agree with the reference's one full forward over the 24 positions."""
    module, params, x, sizes = _blocks(count=1)
    one = x[:1]
    cache_k, cache_v = module.init_decode_cache(1, 32)
    y, cache_k, cache_v = module.apply({"params": params[0]}, one[:, :16], cache_k, cache_v, jnp.int32(0))
    chunks = [y]
    for position in range(16, 24):
        y, cache_k, cache_v = module.apply({"params": params[0]}, one[:, position:position + 1], cache_k, cache_v, jnp.int32(position))
        chunks.append(y)
    got = jnp.concatenate(chunks, axis=1)
    want = block_reference.span(params, one, **sizes)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= MISTRAL["tolerances"]["decode_rel"]


def test_reference_rotary_position_matters():
    """A reference that ignored positions would pass a shifted sequence unchanged."""
    _module, params, x, sizes = _blocks(count=1)
    whole = block_reference.span(params, x[:1], **sizes)
    shifted = block_reference.span(params, x[:1, 4:], **sizes)
    assert float(jnp.abs(whole[:, 4:] - shifted).max()) > 1e-3


def test_published_sizes_give_the_published_parameter_count():
    from perf import flops

    published = mf.load_json(mf.PERF / "configs" / "mistral-7b-span8.json")["model"]
    assert published["num_attention_heads"] * published["head_dim"] == published["hidden_size"]
    assert flops.block_params(published) * 4 == pytest.approx(872.4e6, rel=1e-3)  # bytes of one float32 block
