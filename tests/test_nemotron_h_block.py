"""`nemotron_h_block` (NVIDIA-Nemotron-3-Super-120B-A12B's blocks: a Mamba-2 mixer that keeps a
convolution window and a recurrent state, a grouped-query attention without position embedding,
a LatentMoE layer that keeps NOTHING) against the plain float32 reference
`perf/reference/nemotron_h_block.py`, on every serving path: the block's forward,
`DecodeSessionManager` with a prompt that arrives in chunks of unequal length (the last one
padded) and then single-token steps batched at mixed positions, and a chain ``mamba -> experts
-> attention`` served by one manager with an EMPTY cache tree in the middle. Beside them the
share test (the 8 shares of an expert layer add up to the uncut layer), the shared grouped body
of `ops/sparse_experts.py` (the older blocks' programs lower to the text they lowered to), and
that a trainer loads nothing of this block. Small sizes, seeded weights, CPU.

Tolerances, as a share of the largest value of the reference's output: the served arithmetic
(bf16 activations, float32 state and accumulation) reads 2e-3 to 1e-2 at these sizes; a
near-tie of the router that bf16 flips moves ONE position by an expert's whole output, so an
expert block is held to `SERVED_TOL` on all but a few positions (`positions_beyond`)."""

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

from hivemind_tpu.moe.server.decode_session import DecodeSessionManager  # noqa: E402
from hivemind_tpu.moe.server.layers import name_to_block, name_to_input  # noqa: E402
from hivemind_tpu.moe.server.module_backend import ModuleBackend  # noqa: E402
from hivemind_tpu.ops import sparse_experts  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.reference import nemotron_h_block as reference  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402

HID, MAX_LEN = 64, 256
SMALL = dict(mamba_heads=8, mamba_head_dim=16, ssm_groups=2, ssm_state=16, conv_kernel=4, chunk_size=16, num_heads=4, num_kv_heads=2,
             head_dim=16, num_experts=16, experts_per_token=4, latent_dim=32, expert_inner=48, shared_inner=96, held_lo=4, held=8)
SIZES = dict(rms_eps=1e-5, mamba_heads=8, mamba_head_dim=16, ssm_groups=2, ssm_state=16, num_heads=4, num_kv_heads=2, head_dim=16,
             experts_per_token=4, routed_scale=5.0, held_lo=4)
KINDS = ("mamba", "attention", "experts")
SERVED_TOL = 2e-2


@functools.cache  # read-only in every test (the optimizer's rate is 0): built once a process
def make_backend(kind: str, uid="nh.0", seed=3, **overrides) -> ModuleBackend:
    module = name_to_block["nemotron_h_block"](HID, kind=kind, **{**SMALL, **overrides})
    return OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input["nemotron_h_block"](4, HID),
                             max_batch_size=8, rng_seed=seed)


reference_span = jax.jit(functools.partial(reference.span, **SIZES))  # ONE program a shape, not one an operation


@functools.cache
def applied(module):
    return jax.jit(module.apply)


def stream(seed: int, rows: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((rows, length, HID)).astype(np.float32)


def positions_beyond(got, want, tolerance: float) -> float:
    """The share of positions whose largest difference passes ``tolerance`` of the largest value."""
    error = np.abs(np.asarray(got) - np.asarray(want)).max(-1) / np.abs(np.asarray(want)).max()
    return float((error > tolerance).mean())


def counter(name: str, **labels) -> float:
    series = REGISTRY.snapshot().get(name, {}).get("series", {})
    key = ",".join(f"{k}={v}" for k, v in labels.items())
    return float(series.get(key, 0.0)) if labels else float(sum(series.values()))


def full_forward(backend, x):
    return np.asarray(reference_span([backend.snapshot_params()], x))


def held_to_the_reference(got, want):
    assert positions_beyond(got, want, SERVED_TOL) <= 0.05, (positions_beyond(got, want, SERVED_TOL), rel_err(got, want))


@pytest.mark.parametrize("kind", KINDS)
def test_forward_matches_the_reference(kind):
    backend = make_backend(kind)
    x = stream(1, 2, 50)
    got = applied(backend.module)({"params": backend.snapshot_params()}, x)
    held_to_the_reference(got, full_forward(backend, x))


@pytest.mark.parametrize("kind", KINDS)
def test_chunked_prompt_then_batched_steps_at_mixed_positions_equal_the_full_forward(kind):
    """Three sessions whose prompts (50, 37, 20) arrive in chunks of 16, the last padded to a power
    of two, then ten steps in ONE batched program a step (a bucket of 4: one padding row)."""
    backend = make_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    prompts, steps = (50, 37, 20), 10
    x = stream(2, 3, 60)
    want = full_forward(backend, x)
    got = [[] for _ in prompts]
    for row, length in enumerate(prompts):
        for start in range(0, length, 16):
            got[row].append(manager.decode(backend.name, f"row{row}", x[row:row + 1, start:min(start + 16, length)], reset=start == 0))
    for step in range(steps):
        entries = [(None, manager._sessions[(backend.name, f"row{row}")], x[row:row + 1, length + step:length + step + 1])
                   for row, length in enumerate(prompts)]
        outs = manager._decode_batch(backend.name, entries)
        assert not any(isinstance(out, Exception) for out in outs), outs
        for row, out in enumerate(outs):
            got[row].append(out)
    for row, length in enumerate(prompts):
        held_to_the_reference(np.concatenate(got[row], axis=1), want[row:row + 1, :length + steps])
    assert [manager._sessions[(backend.name, f"row{row}")].index for row in range(3)] == [length + steps for length in prompts]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("length,padded", [(5, 8), (11, 16), (16, 16)])
def test_a_right_padded_chunk_equals_the_unpadded_one_in_output_and_in_both_states(kind, length, padded):
    """A session's second chunk, ``length`` real positions in a chunk of ``padded``, against the same
    chunk unpadded: the outputs of the real positions, and what the session keeps (the mixer's window and
    state: bit for bit what the unpadded chunk leaves; the attention's caches up to the real positions)."""
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
    assert len(got_cache) == len(plain_cache) == len(module.init_decode_cache(1, MAX_LEN))
    if kind == "mamba":
        np.testing.assert_array_equal(np.asarray(got_cache[0], np.float32), np.asarray(plain_cache[0], np.float32))  # the window: the last REAL rows
        np.testing.assert_allclose(got_cache[1], plain_cache[1], rtol=1e-4, atol=1e-5)  # the state: padding neither decays nor feeds it
    elif kind == "attention":
        for ours, theirs in zip(got_cache, plain_cache):
            np.testing.assert_array_equal(np.asarray(ours[:, :, :16 + length], np.float32), np.asarray(theirs[:, :, :16 + length], np.float32))


def test_an_expert_block_keeps_nothing_and_says_so():
    module = make_backend("experts").module
    assert module.init_decode_cache(1, MAX_LEN) == () and module.decode_cache_kind == "stateless" and module.held_experts == (4, 12)
    assert [make_backend(kind).module.decode_cache_kind for kind in KINDS] == ["ssm", "full", "stateless"]
    assert [bool(make_backend(kind).module.decode_takes_length) for kind in KINDS] == [True, False, False]
    assert all(make_backend(kind).module.decode_takes_chunks for kind in KINDS)
    window, state = make_backend("mamba").module.init_decode_cache(2, MAX_LEN)
    assert (window.shape, window.dtype, state.shape, state.dtype) == ((2, 3, 8 * 16 + 2 * 2 * 16), jnp.bfloat16, (2, 8, 16, 16), jnp.float32)
    keys, values = make_backend("attention").module.init_decode_cache(1, MAX_LEN)
    assert keys.shape == values.shape == (1, 2, MAX_LEN, 16) and keys.dtype == jnp.bfloat16
    assert make_backend("experts", held=0).module.held_experts is None and make_backend("mamba").module.held_experts is None


def test_seeded_ssm_weights_follow_the_published_ranges():
    params = make_backend("mamba").snapshot_params()
    a, dt = np.exp(np.asarray(params["A_log"])), np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and dt.min() >= 1e-4 and dt.max() <= 0.1 + 1e-6 and (np.asarray(params["D"]) == 1.0).all()
    assert float(np.abs(np.asarray(params["conv_bias"])).max()) > 0.1  # drawn, so that a convolution without its bias departs


def test_shares_add_up():
    """THE SHARE TEST: the parts of all 8 shares of an `E` layer (each holds 2 of 16 experts and applies
    ``W_up`` to its own partial sum), with the shared expert and nothing else counted once, add up to the uncut
    layer: in the reference exactly, in the served block to its rounding."""
    whole = make_backend("experts", held=0, held_lo=0)
    params = jax.tree_util.tree_map(np.asarray, whole.snapshot_params())  # a share is cut in numpy: no program a slice
    x = stream(6, 2, 24)
    sizes = {**SIZES, "held_lo": 0}
    shares = [{**params, "experts_up": params["experts_up"][lo:lo + 2], "experts_down": params["experts_down"][lo:lo + 2]} for lo in range(0, 16, 2)]
    experts = functools.partial(reference.experts, experts_per_token=4, routed_scale=5.0)

    @jax.jit  # the reference's uncut layer, its shared expert and its eight shares: one program
    def by_the_reference(params, shares, x):
        with jax.default_matmul_precision("highest"):
            u = reference._rms_norm(x, params["norm"]["scale"], 1e-5)
            parts = [experts(own, u, held_lo=2 * share, shared=False)[0] for share, own in enumerate(shares)]
            return experts(params, u, held_lo=0)[0], experts(params, u, held_lo=0, routed=False)[0], parts

    uncut, shared_once, parts = by_the_reference(params, shares, x)
    served_parts = []
    with jax.default_matmul_precision("highest"):
        for share, own in enumerate(shares):
            module = name_to_block["nemotron_h_block"](HID, kind="experts", **{**SMALL, "held_lo": 2 * share, "held": 2})
            assert module.held_experts == (2 * share, 2 * share + 2)
            served_parts.append(np.asarray(jax.jit(module.apply)({"params": own}, x)) - x - np.asarray(shared_once))
    np.testing.assert_allclose(sum(parts) + shared_once, uncut, rtol=1e-4, atol=1e-4)
    assert float(np.abs(np.asarray(parts[0])).max()) > 0 and float(np.abs(np.asarray(sum(parts[1:]))).max()) > 0  # no share is the layer
    held_to_the_reference(sum(served_parts) + np.asarray(shared_once), np.asarray(uncut))
    assert sizes["held_lo"] == 0 and np.asarray(jax.jit(functools.partial(reference.span, **sizes))([params], x)).shape == x.shape


def chain_manager(**kwargs):
    backends = {f"nh.{at}": make_backend(kind, uid=f"nh.{at}", seed=at) for at, kind in enumerate(("mamba", "experts", "attention"))}
    return ManagerSharingPrograms(backends, max_len=MAX_LEN, **kwargs), tuple(backends)


def test_a_chain_with_an_empty_tree_in_the_middle():
    """``mamba -> experts -> attention`` in one manager: the chain takes chunks, every block has a session
    (the expert block's is a position and a batch: no leaf, no byte), the cache gauges have NO series for a kind
    that holds nothing, a batched step counts the expert block's rows as ``caches=none``, the state-space
    counter counts the mixer's rows, and `clear_sessions` empties all of it."""
    manager, chain = chain_manager()
    manager.clear_sessions()
    assert all(manager.supports(uid) for uid in chain) and manager._takes_chunks(chain)
    x = stream(8, 2, 60)
    all_params = [manager.backends[uid].snapshot_params() for uid in chain]
    want = np.asarray(reference_span(all_params, x))
    none, rewritten, evicted = counter("hivemind_moe_decode_batched_rows_total", caches="none"), counter("hivemind_moe_ssm_state_bytes_total"), counter(
        "hivemind_moe_decode_session_evictions_total")
    got = [[manager._decode_direct(chain, f"s{row}", x[row:row + 1, :33], reset=True),
            manager._decode_direct(chain, f"s{row}", x[row:row + 1, 33:50], reset=False)] for row in range(2)]
    middle = manager._sessions[(chain[1], "s0")]
    assert middle.leaves == () and middle.nbytes == 0 and middle.batch == 1 and middle.index == 50 and middle.cache == ()
    for step in range(10):
        xs = [x[row:row + 1, 50 + step:51 + step] for row in range(2)]
        for uid in chain:
            xs = manager._decode_batch(uid, [(None, manager._sessions[(uid, f"s{row}")], out) for row, out in enumerate(xs)])
            assert not any(isinstance(out, Exception) for out in xs), xs
        for row in range(2):
            got[row].append(xs[row])
    for row in range(2):
        held_to_the_reference(np.concatenate(got[row], axis=1), want[row:row + 1])
    assert counter("hivemind_moe_decode_batched_rows_total", caches="none") - none == 20
    mixer = manager._sessions[(chain[0], "s0")]
    assert mixer.nbytes == 3 * (8 * 16 + 64) * 2 + 8 * 16 * 16 * 4 and counter("hivemind_moe_ssm_state_bytes_total") - rewritten == 20 * mixer.nbytes
    gauges = REGISTRY.snapshot()
    assert "kind=stateless" not in gauges["hivemind_moe_decode_cache_bytes"]["series"]
    assert "kind=stateless" not in gauges["hivemind_moe_decode_cache_entries"]["series"]
    assert gauges["hivemind_moe_decode_cache_entries"]["series"]["kind=ssm"] == 2 and len(manager._sessions) == 6
    assert manager._batched_fn(chain[0], 2).jitted.__name__ == "batched_step_ssm"
    assert manager._batched_fn(chain[1], 2).jitted.__name__ == "batched_step_stateless"
    assert manager._step_fn(chain[1], 1, 32).jitted.__name__ == "prefill_stateless_32"
    assert manager._padding(chain[1], 2) == [(), ()] and manager._dummy_rows(chain[1]) == ()
    manager.clear_sessions()
    assert not manager._sessions and REGISTRY.snapshot()["hivemind_moe_decode_cache_bytes"]["series"]["kind=ssm"] == 0
    assert counter("hivemind_moe_decode_session_evictions_total") == evicted


def test_sessions_without_a_leaf_are_evicted_like_any_other():
    manager, chain = chain_manager(max_sessions=6)
    x = stream(9, 1, 20)
    for name in ("a", "b"):
        manager._decode_direct(chain, name, x, reset=True)
    capped = counter("hivemind_moe_decode_session_evictions_total", reason="cap")
    manager._decode_direct(chain, "c", x, reset=True)  # 9 entries over a cap of 6: the three oldest go, the expert block's among them
    assert counter("hivemind_moe_decode_session_evictions_total", reason="cap") - capped == 3
    assert (chain[1], "a") not in manager._sessions and (chain[1], "c") in manager._sessions
    with pytest.raises(KeyError):
        manager._decode_direct(chain, "a", x[:, :1], reset=False)
    manager.clear_sessions()


def test_a_failed_step_after_the_empty_tree_drops_the_sessions_before_it(monkeypatch):
    """A cohort whose LAST block's program fails: the sessions of the blocks dispatched before it go, the expert
    block's (which held nothing a step could have taken) with the mixer's; the attention block's, never reached, stay."""
    manager, chain = chain_manager()
    x = stream(10, 2, 30)
    for row in range(2):
        manager._decode_direct(chain, f"s{row}", x[row:row + 1, :20], reset=True)

    def broken(*_args, **_kwargs):
        raise RuntimeError("device fault")

    monkeypatch.setitem(manager._batched_fns, (chain[2], 2), broken)
    failed = counter("hivemind_moe_decode_session_evictions_total", reason="failed_step")
    entries = [(None, [manager._sessions[(uid, f"s{row}")] for uid in chain], x[row:row + 1, 20:21]) for row in range(2)]
    results = manager._launch_cohort(chain, entries)()
    assert all(isinstance(result, RuntimeError) for result in results)
    assert counter("hivemind_moe_decode_session_evictions_total", reason="failed_step") - failed == 4
    assert all((uid, f"s{row}") not in manager._sessions for uid in chain[:2] for row in range(2))
    assert all((chain[2], f"s{row}") in manager._sessions for row in range(2))
    manager.clear_sessions()


# ---- the grouped body that gated and non-gated experts share --------------------------


def _grouped_swiglu_before(tokens, weights, flat, w_gate, w_up, w_down, elsewhere):
    """`ops/sparse_experts._grouped_swiglu` as it stood before non-gated experts shared its body (PR 50's text)."""
    count, k = weights.shape
    groups = w_gate.shape[0]
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=groups + elsewhere)[:groups].astype(jnp.int32)
    rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    rows = rounded(tokens)[order // k]
    gate = jax.lax.ragged_dot(rows, w_gate, sizes)
    up = jax.lax.ragged_dot(rows, w_up, sizes)
    down = jax.lax.ragged_dot(rounded(jax.nn.silu(gate) * up), w_down, sizes)
    if elsewhere:
        down = jnp.where((jnp.arange(count * k) < sizes.sum())[:, None], down, 0.0)
    per_pair = down[jnp.argsort(order)].reshape(count, k, -1)
    return jnp.einsum("tkh,tk->th", per_pair, weights.astype(jnp.float32))


OLDER = {
    "olmoe_block": dict(num_heads=4, num_experts=8, experts_per_token=2, expert_inner=32),
    "exaone_moe_block": dict(num_heads=4, num_kv_heads=2, head_dim=16, window=0, num_experts=16, experts_per_token=4, expert_inner=32, held_lo=4, held=4),
    "deepseek_v3_block": dict(mlp="sparse", num_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
                              num_experts=16, experts_per_token=4, n_group=4, topk_group=2, expert_inner=32, held_lo=4, held=4, rope_original=64),
    "minicpm_sala_block": dict(mixer="lightning-attn", num_heads=4, head_dim=16, ffn_inner=96),
}


def _batched_text(name: str) -> str:
    backend = OneProgramBackend("older.0", name_to_block[name](HID, **OLDER[name]), optimizer=optax.sgd(0.0),
                                sample_input=name_to_input[name](4, HID), max_batch_size=8, rng_seed=1)
    manager = DecodeSessionManager({"older.0": backend}, max_len=64)
    shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
    columns = tuple((leaf,) * 4 for leaf in shape(manager._dummy_rows("older.0")))
    return manager._batched_fn("older.0", 4).jitted.lower(shape(backend.snapshot_params()), jax.ShapeDtypeStruct((4, 1, HID), "float32"), columns,
                                                        jax.ShapeDtypeStruct((4,), "int32")).as_text()


@pytest.mark.parametrize("name", sorted(OLDER))
def test_the_older_blocks_batched_steps_lower_to_the_text_they_lowered_to(name, monkeypatch):
    after = _batched_text(name)
    monkeypatch.setattr(sparse_experts, "_grouped_mlp", lambda tokens, weights, flat, w_in, w_down, elsewhere, activation:
                        _grouped_swiglu_before(tokens, weights, flat, *w_in, w_down, elsewhere))
    assert _batched_text(name) == after and len(after) > 10_000


def test_non_gated_experts_are_two_grouped_matmuls_and_equal_a_loop():
    rng = np.random.default_rng(5)
    tokens, w_up, w_down = (jnp.asarray(rng.standard_normal(shape), jnp.float32) for shape in ((6, 8), (4, 8, 12), (4, 12, 8)))
    top_e = jnp.asarray(rng.integers(0, 8, (6, 3)), jnp.int32)  # over 8 experts, of which [2, 6) are held
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (6, 3)), jnp.float32)
    layer = lambda *args: sparse_experts.routed_mlp_held(*args, 2, "relu2")
    got = layer(tokens, weights, top_e, w_up, w_down)
    rounded = lambda t: np.asarray(jnp.asarray(t).astype(jnp.bfloat16).astype(jnp.float32))
    want = np.zeros((6, 8), np.float32)
    for token in range(6):
        for slot in range(3):
            expert = int(top_e[token, slot]) - 2
            if 0 <= expert < 4:
                inner = np.maximum(rounded(tokens[token]) @ np.asarray(w_up[expert]), 0.0) ** 2
                want[token] += float(weights[token, slot]) * (rounded(inner) @ np.asarray(w_down[expert]))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert str(jax.make_jaxpr(layer)(tokens, weights, top_e, w_up, w_down)).count("ragged_dot_general[") == 2
    with pytest.raises(AssertionError):
        sparse_experts.routed_mlp_held(tokens, weights, top_e, (w_up, w_up), w_down, 2, "relu2")  # a gate it does not have


def test_parameter_counts_by_hand():
    count = lambda params: sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    inner, channels = 8 * 16, 8 * 16 + 2 * 2 * 16
    assert count(make_backend("mamba").snapshot_params()) == HID * (inner + channels + 8) + 5 * channels + 3 * 8 + inner + inner * HID + HID
    assert count(make_backend("attention").snapshot_params()) == HID * (64 + 2 * 32) + 64 * HID + HID
    assert count(make_backend("experts").snapshot_params()) == HID * 16 + 16 + 2 * HID * 32 + 2 * HID * 96 + HID + 8 * 2 * 32 * 48


def test_trainers_load_nothing_of_this_block():
    """A process that imports what `perf/runners/trainer.py` and `examples/albert/run_trainer.py` import
    holds none of the modules this block's PR added: the block's own module and `ops/ssm.py` load when a
    block is BUILT (PR 32's regression was ALBERT's `setup_s`, a cell whose process never runs a block)."""
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
assert 'nemotron_h_block' in name_to_block
added = ('hivemind_tpu.moe.server.layers.nemotron_h', 'hivemind_tpu.ops.ssm', 'perf.reference.nemotron_h_block',
         'perf.runners.nemotron_block_server', 'perf.flops_nemotron', 'perf.readers.ssm_roofline', 'perf.readers.moe_roofline_latent')
held = [name for name in added if name in sys.modules]
assert not held, held
name_to_block['nemotron_h_block'](64, kind='experts')
held = [name for name in added[2:] if name in sys.modules]
assert not held and added[0] in sys.modules and added[1] in sys.modules, held
"""
    run = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert run.returncode == 0, run.stderr[-3000:]
