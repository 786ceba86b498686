"""`granite_h_block` (ibm-granite/granite-4.0-h-micro's blocks: EVERY block a mixer, a Mamba-2
state-space mixer or a grouped-query attention without position embedding at a scale that is
not ``head_dim ** -0.5``, AND a gated MLP, under two residuals scaled by 0.22) against the
plain float32 reference `perf/reference/granite_h_block.py`, on every serving path: the
block's forward, `DecodeSessionManager` with a prompt that arrives in chunks of unequal
length (the last one padded) and then single-token steps batched at mixed positions, a
session id that is opened again (its state and window start from zero), and a chain of 20
uids (``mmmmmammmm`` twice) that more than 32 live sessions walk as two cohorts. Beside them
that the mixer's body is `nemotron_h_block`'s own, and that a trainer loads nothing of this
block. Small sizes, seeded weights, CPU; each backend is built once a process and its
programs compiled once (`swarm_utils`).

Tolerance, as a share of the largest value of the reference's output: the served arithmetic
(bf16 activations, float32 state and accumulation) reads 2e-3 to 8e-3 at these sizes."""

import asyncio
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from hivemind_tpu.moe.server.layers import name_to_block, name_to_input  # noqa: E402
from hivemind_tpu.moe.server.module_backend import ModuleBackend  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.reference import granite_h_block as reference  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402

HID, MAX_LEN = 64, 256
SMALL = dict(mamba_heads=8, mamba_head_dim=16, ssm_groups=1, ssm_state=16, conv_kernel=4, chunk_size=16, num_heads=4, num_kv_heads=2,
             head_dim=16, ffn_inner=96, residual_multiplier=0.22, attention_multiplier=1 / 32)
SIZES = dict(rms_eps=1e-5, residual_multiplier=0.22, mamba_heads=8, mamba_head_dim=16, ssm_groups=1, ssm_state=16, num_heads=4, num_kv_heads=2,
             head_dim=16, attention_multiplier=1 / 32)
KINDS = ("mamba", "attention")
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4  # the model's period of ten
SERVED_TOL = 2e-2


@functools.cache  # read-only in every test (the optimizer's rate is 0): built once a process
def make_backend(kind: str, uid="gh.0", seed=3, **overrides) -> ModuleBackend:
    module = name_to_block["granite_h_block"](HID, kind=kind, **{**SMALL, **overrides})
    return OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input["granite_h_block"](4, HID),
                             max_batch_size=8, rng_seed=seed)


reference_span = jax.jit(functools.partial(reference.span, **SIZES))  # ONE program a shape, not one an operation


@functools.cache
def applied(module):
    return jax.jit(module.apply)


def stream(seed: int, rows: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((rows, length, HID)).astype(np.float32)


def counter(name: str, **labels) -> float:
    series = REGISTRY.snapshot().get(name, {}).get("series", {})
    key = ",".join(f"{k}={v}" for k, v in labels.items())
    return float(series.get(key, 0.0)) if labels else float(sum(series.values()))


def full_forward(backend, x):
    return np.asarray(reference_span([backend.snapshot_params()], x))


def held_to_the_reference(got, want):
    assert rel_err(got, want) <= SERVED_TOL, rel_err(got, want)


@pytest.mark.parametrize("kind", KINDS)
def test_forward_matches_the_reference(kind):
    backend = make_backend(kind)
    x = stream(1, 2, 50)
    got = applied(backend.module)({"params": backend.snapshot_params()}, x)
    held_to_the_reference(got, full_forward(backend, x))


@pytest.mark.parametrize("knob,kind,gross", [("residual_multiplier", "mamba", True), ("root_scale", "attention", False), ("rope", "attention", False),
                                             ("halves_swapped", "mamba", True), ("norm_before_gate", "mamba", True), ("conv_bias", "mamba", True),
                                             ("skip", "mamba", True)])
def test_the_forward_is_none_of_the_wrong_references(knob, kind, gross):
    """Each knob of the reference that makes a wrong model: the served block's output holds none of its departure
    (`_departure_share`, the runner's measure: a program that computed it would read 1), and a gross one moves ONE
    block's output by far more than the served rounding. The two of the attention's scores are slight at these
    sizes, as they are at the published ones (seeded queries and keys at 1/64 make a nearly uniform softmax)."""
    from perf.runners.hybrid_moe_block_server import _departure_share

    backend = make_backend(kind)
    x = stream(1, 2, 50)
    got, right = applied(backend.module)({"params": backend.snapshot_params()}, x), full_forward(backend, x)
    wrong = {"residual_multiplier": 1.0, "conv_bias": False, "skip": False}.get(knob, True)
    other = jax.jit(functools.partial(reference.span, **{**SIZES, knob: wrong}))([backend.snapshot_params()], x)
    assert rel_err(other, right) > 1e-3 and abs(_departure_share([(got, right, other)])) < 0.3, (knob, rel_err(other, right))
    assert not gross or rel_err(got, other) > 2 * SERVED_TOL > 2 * rel_err(got, right), (knob, rel_err(got, other))


@pytest.mark.parametrize("kind", KINDS)
def test_chunked_prompt_then_batched_steps_at_mixed_positions_equal_the_full_forward(kind):
    """Three sessions whose prompts (50, 37, 20) arrive in chunks of 16, the last padded to a power of two, then ten
    steps in ONE batched program a step (a bucket of 4: one padding row): the reference's full forward, and what the
    rows' own steps (twin sessions, one program a session) give."""
    backend = make_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    prompts, steps = (50, 37, 20), 10
    x = stream(2, 3, 60)
    want = full_forward(backend, x)
    got, own = [[] for _ in prompts], [[] for _ in prompts]
    for row, length in enumerate(prompts):
        for start in range(0, length, 16):
            chunk = x[row:row + 1, start:min(start + 16, length)]
            got[row].append(manager.decode(backend.name, f"row{row}", chunk, reset=start == 0))
            manager.decode(backend.name, f"twin{row}", chunk, reset=start == 0)
    for step in range(steps):
        tokens = [x[row:row + 1, length + step:length + step + 1] for row, length in enumerate(prompts)]
        outs = manager._decode_batch(backend.name, [(None, manager._sessions[(backend.name, f"row{row}")], token) for row, token in enumerate(tokens)])
        assert not any(isinstance(out, Exception) for out in outs), outs
        for row, out in enumerate(outs):
            got[row].append(out)
            own[row].append(manager.decode(backend.name, f"twin{row}", tokens[row], reset=False))
    for row, length in enumerate(prompts):
        held_to_the_reference(np.concatenate(got[row], axis=1), want[row:row + 1, :length + steps])
        np.testing.assert_allclose(np.concatenate(got[row][-steps:], axis=1), np.concatenate(own[row], axis=1), rtol=2e-2, atol=2e-2)
    assert [manager._sessions[(backend.name, f"row{row}")].index for row in range(3)] == [length + steps for length in prompts]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("length,padded", [(5, 8), (16, 16)])
def test_a_right_padded_chunk_equals_the_unpadded_one_in_output_and_in_what_is_kept(kind, length, padded):
    module = make_backend(kind).module
    params = make_backend(kind).snapshot_params()
    x = stream(4, 1, 16 + padded)
    cache = module.init_decode_cache(1, MAX_LEN)
    takes = (lambda n: (jnp.int32(n),)) if module.decode_takes_length else (lambda n: ())
    padded_chunk = x[:, 16:].copy()
    padded_chunk[:, length:] = 7.0
    _y, *cache = applied(module)({"params": params}, x[:, :16], *cache, jnp.int32(0), *takes(16))
    plain, *plain_cache = applied(module)({"params": params}, x[:, 16:16 + length], *cache, jnp.int32(16), *takes(length))
    got, *got_cache = applied(module)({"params": params}, padded_chunk, *cache, jnp.int32(16), *takes(length))
    np.testing.assert_allclose(got[:, :length], plain, rtol=2e-2, atol=2e-2)
    if kind == "mamba":
        np.testing.assert_array_equal(np.asarray(got_cache[0], np.float32), np.asarray(plain_cache[0], np.float32))  # the window: the last REAL rows
        np.testing.assert_allclose(got_cache[1], plain_cache[1], rtol=1e-4, atol=1e-5)  # the state: padding neither decays nor feeds it
    else:
        for ours, theirs in zip(got_cache, plain_cache):
            np.testing.assert_array_equal(np.asarray(ours[:, :, :16 + length], np.float32), np.asarray(theirs[:, :, :16 + length], np.float32))


def test_a_session_id_opened_again_starts_from_a_zero_state_and_window():
    """A stale key is masked by ``index``; a stale STATE would be silently wrong. A slot's next session (the same id,
    ``reset``) after one that ran 40 positions gives what a first session gives, and keeps what it keeps."""
    backend = make_backend("mamba")
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    first, second = stream(11, 1, 40), stream(12, 1, 24)
    manager.decode(backend.name, "slot", first[:, :32], reset=True)
    for at in range(32, 40):
        manager.decode(backend.name, "slot", first[:, at:at + 1], reset=False)
    used = [np.asarray(leaf, np.float32) for leaf in manager._sessions[(backend.name, "slot")].leaves]
    assert all(np.abs(leaf).max() > 0 for leaf in used)
    again = [manager.decode(backend.name, "slot", second[:, :16], reset=True)] + [
        manager.decode(backend.name, "slot", second[:, at:at + 1], reset=False) for at in range(16, 24)]
    fresh = [manager.decode(backend.name, "never-used", second[:, :16], reset=True)] + [
        manager.decode(backend.name, "never-used", second[:, at:at + 1], reset=False) for at in range(16, 24)]
    np.testing.assert_array_equal(np.concatenate(again, axis=1), np.concatenate(fresh, axis=1))
    held_to_the_reference(np.concatenate(again, axis=1), full_forward(backend, second))
    for ours, theirs in zip(manager._sessions[(backend.name, "slot")].leaves, manager._sessions[(backend.name, "never-used")].leaves):
        np.testing.assert_array_equal(np.asarray(ours, np.float32), np.asarray(theirs, np.float32))
    window, state = backend.module.init_decode_cache(1, MAX_LEN)
    assert not np.asarray(window, np.float32).any() and not np.asarray(state).any()


def test_what_a_block_says_of_itself():
    mixer, attention = make_backend("mamba").module, make_backend("attention").module
    assert [m.decode_cache_kind for m in (mixer, attention)] == ["ssm", "full"]
    assert [bool(m.decode_takes_length) for m in (mixer, attention)] == [True, False]
    assert all(m.decode_takes_chunks and m.decode_rows_apart and m.held_experts is None for m in (mixer, attention))
    window, state = mixer.init_decode_cache(2, MAX_LEN)
    assert (window.shape, window.dtype, state.shape, state.dtype) == ((2, 3, 8 * 16 + 2 * 16), jnp.bfloat16, (2, 8, 16, 16), jnp.float32)
    keys, values = attention.init_decode_cache(1, MAX_LEN)
    assert keys.shape == values.shape == (1, 2, MAX_LEN, 16) and keys.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="a mixer or an attention block"):
        name_to_block["granite_h_block"](HID, kind="experts", **SMALL).init(jax.random.PRNGKey(0), jnp.zeros((1, 4, HID)))
    published = name_to_block["granite_h_block"](2048)
    assert (published.mamba_inner, published.conv_channels, published.chunk_size, published.attention_multiplier * published.head_dim**0.5) == (
        4096, 4352, 256, 0.125)


def test_parameter_counts_by_hand_and_the_seeded_ranges():
    count = lambda params: sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    inner, channels, mlp = 8 * 16, 8 * 16 + 2 * 16, 3 * HID * 96
    params = make_backend("mamba").snapshot_params()
    assert count(params) == HID * (inner + channels + 8) + 5 * channels + 3 * 8 + inner + inner * HID + mlp + 2 * HID
    assert count(make_backend("attention").snapshot_params()) == 2 * HID * 64 + 2 * HID * 32 + mlp + 2 * HID
    a, dt = np.exp(np.asarray(params["A_log"])), np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and dt.min() >= 1e-4 and dt.max() <= 0.1 + 1e-6 and (np.asarray(params["D"]) == 1.0).all()
    assert float(np.abs(np.asarray(params["conv_bias"])).max()) > 0.1  # drawn, so that a convolution without its bias departs


def test_the_mixer_is_nemotrons_own_body():
    """Shared by inheritance, not copied: the functions are the same objects, and `nemotron_h.py` reads nothing of this block."""
    from hivemind_tpu.moe.server.layers import granite_h, nemotron_h

    assert granite_h.GraniteHBlockExpert._mamba is nemotron_h.NemotronHBlockExpert._mamba
    assert granite_h.GraniteHBlockExpert.init_decode_cache is nemotron_h.NemotronHBlockExpert.init_decode_cache
    assert granite_h.attend_chunk is nemotron_h.attend_chunk and "granite" not in Path(nemotron_h.__file__).read_text()


@functools.cache
def chain_backends():
    """Twenty uids, ``mmmmmammmm`` twice. The eighteen mixers are ONE backend object and the two attention blocks
    another (a program is a function of its backend alone, so the chain compiles two sets of programs, not twenty);
    the sessions, the caches and the counters are a uid's own."""
    kinds = PERIOD * 2
    return {f"gh.{at}": make_backend(kind, uid=f"shared.{kind}", seed=7) for at, kind in enumerate(kinds)}, kinds


def test_forty_sessions_walk_a_chain_of_twenty_as_two_cohorts():
    """40 steps that wait together on the 20-block chain: a cohort of 32 and one of 8 (with more than 16 under way a
    cohort takes at most the bucket that holds half), 20 batched programs a cohort, each row's output the reference's
    span over its stream, and the state-space counter counts the live rows of the 18 mixers alone."""
    backends, kinds = chain_backends()
    chain = tuple(backends)
    manager = ManagerSharingPrograms(backends, max_len=64, max_sessions=4096)
    names = [f"s{i}" for i in range(40)]
    x = stream(13, 40, 9)
    for row, name in enumerate(names):
        manager._decode_direct(chain, name, x[row:row + 1, :8], True)
    cohorts, calls, rewritten = counter("hivemind_moe_decode_cohorts_total"), counter("hivemind_moe_decode_calls_total", path="batched"), counter(
        "hivemind_moe_ssm_state_bytes_total", path="batched")

    async def together():
        return await asyncio.wait_for(asyncio.gather(*(manager.decode_span_async(chain, name, x[row:row + 1, 8:9], False)
                                                       for row, name in enumerate(names))), 240.0)

    outs = asyncio.run(together())
    assert counter("hivemind_moe_decode_cohorts_total") - cohorts == 2
    assert counter("hivemind_moe_decode_calls_total", path="batched") - calls == 2 * 20
    mixer_row = manager._sessions[(chain[0], "s0")].nbytes
    assert mixer_row == 3 * (8 * 16 + 2 * 16) * 2 + 8 * 16 * 16 * 4
    assert counter("hivemind_moe_ssm_state_bytes_total", path="batched") - rewritten == 40 * kinds.count("mamba") * mixer_row
    assert sorted(rows for (_uid, rows) in manager._batched_fns) == sorted([8, 32] * 20)  # a view a uid a bucket ...
    assert len({id(program) for program in manager._batched_fns.values()}) == 2 * len(set(kinds)), "... onto ONE program a kind of block a bucket"
    want = np.asarray(reference_span([backends[uid].snapshot_params() for uid in chain], x))
    held_to_the_reference(np.concatenate(outs), want[:, 8:9])
    manager.clear_sessions()


def test_trainers_load_nothing_of_this_block():
    """A process that imports what `perf/runners/trainer.py` and `examples/albert/run_trainer.py` import holds
    none of the modules this block's PR added (nor the mixer's, which it shares): they load when a block is BUILT."""
    import os
    import subprocess

    code = """
import ast, importlib, sys
def imports_of(path):
    names = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            names |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            names.add(node.module)
    return sorted(name for name in names if name.split('.')[0] in ('hivemind_tpu', 'perf'))
for name in imports_of('perf/runners/trainer.py') + imports_of('examples/albert/run_trainer.py'):
    importlib.import_module(name)
from hivemind_tpu.moe.server.layers import name_to_block
assert 'granite_h_block' in name_to_block
added = ('hivemind_tpu.moe.server.layers.granite_h', 'hivemind_tpu.moe.server.layers.nemotron_h', 'hivemind_tpu.ops.ssm',
         'perf.reference.granite_h_block', 'perf.runners.granite_block_server', 'perf.flops_granite', 'perf.readers.granite_ssm')
held = [name for name in added if name in sys.modules]
assert not held, held
name_to_block['granite_h_block'](64, kind='attention')
held = [name for name in added[3:] if name in sys.modules]
assert not held and all(name in sys.modules for name in added[:3]), held
"""
    run = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert run.returncode == 0, run.stderr[-3000:]
