"""`ouro_block` (a LOOPED language model's decoder block: the Llama family's attention and SwiGLU between
sandwich norms, run `total_ut_steps` times a token, each pass on a cache of its own) against the plain
float32 reference `perf/reference/ouro_block.py`, at toy sizes on the CPU: the block's full forward (one
pass), a prompt and then one position at a time through FOUR per-pass caches against the reference's full
looped forward (hidden states, every pass), a batched step whose rows are at DIFFERENT passes and positions
against the same rows stepped alone, and the four wrong programs, which have to differ from the model by
more than the tolerance.

Tolerance, as a share of the largest value of the reference's output: 4e-2 for the served arithmetic (bf16
activations and matmuls, float32 accumulation) through two blocks and four passes against the float32
reference; the readings are 1.0e-2 at the first pass and 2.1e-2 to 2.3e-2 at the later ones (`-s` prints
them: a pass takes the rounding of the passes before it), and the wrong programs read 0.61 to 0.99."""

import functools
import sys
from pathlib import Path

import jax
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from hivemind_tpu.moe.server.layers import name_to_block, name_to_input  # noqa: E402
from perf.reference import ouro_block as reference  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402

HID, HEADS, INNER, PASSES, MAX_LEN = 64, 4, 96, 4, 48
KWARGS = dict(num_heads=HEADS, ffn_inner=INNER, rope_theta=1e6, rms_eps=1e-6, total_ut_steps=PASSES)
SIZES = dict(num_heads=HEADS, num_kv_heads=HEADS, rope_theta=1e6, rms_eps=1e-6)
CHAIN = ("loop.0", "loop.1")
SERVED_TOL = 4e-2
WRONG_BY = 2e-1  # what a wrong program has to differ by at the least: five times the tolerance


@functools.cache
def backends():
    return {uid: OneProgramBackend(uid, name_to_block["ouro_block"](HID, **KWARGS), optimizer=optax.sgd(0.0),
                                   sample_input=name_to_input["ouro_block"](2, HID), max_batch_size=4, rng_seed=7 + at)
            for at, uid in enumerate(CHAIN)}


def fresh_manager(**kwargs):
    return ManagerSharingPrograms(backends(), max_len=MAX_LEN, **kwargs)


def all_params():
    return [backends()[uid].snapshot_params() for uid in CHAIN]


@functools.cache
def final_norm() -> np.ndarray:
    """`F`'s scale: the client's, drawn away from 1 so that leaving `F` out, or a wrong scale, shows."""
    return (1.0 + 0.2 * np.random.default_rng(11).standard_normal(HID)).astype(np.float32)


def client_norm(x: np.ndarray) -> np.ndarray:
    """What a looped model's client does between two passes (numpy, float32)."""
    return (x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * final_norm()).astype(np.float32)


@functools.cache
def reference_program(entry: str = "span", **changed):
    """The reference's ``entry`` as ONE program a shape, not one an operation."""
    return jax.jit(functools.partial(getattr(reference, entry), **{**SIZES, **changed}))


def stream(seed: int, batch: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((batch, length, HID)).astype(np.float32)


def served_loop(manager, name: str, x: np.ndarray, prompt: int, passes: int = PASSES, between=client_norm, one_cache: bool = False):
    """One session through the loop as its client walks it: the prompt pass by pass, then one position at a
    time, each pass's (normed) output the next pass's input (``between``: a wrong client's identity). Every
    pass's output under `F`, ``[passes, 1, T, hidden]``. ``one_cache``: the manager is MADE to share one cache between the passes (after a pass's call the next
    pass's tree is set to the arrays this one left), which is the wrong program of that name."""
    outs = [[] for _ in range(passes)]
    for start, stop in [(0, prompt)] + [(t, t + 1) for t in range(prompt, x.shape[1])]:
        piece = x[:, start:stop]
        for u in range(passes):
            left = manager._decode_direct(CHAIN, name, piece, start == 0, u)
            outs[u].append(client_norm(left))
            piece = between(left)
            if one_cache:
                for uid in CHAIN:
                    session = manager._sessions[(uid, name)]
                    session.trees[(u + 1) % passes] = session.trees[u]
    return np.stack([np.concatenate(pieces, axis=1) for pieces in outs])


def test_the_block_is_the_references_block():
    """One block, one pass, the whole sequence: the pool's forward against the reference's block."""
    backend, x = backends()[CHAIN[0]], stream(1, 2, 24)
    got = backend.forward(x)[0]
    want = np.asarray(jax.jit(functools.partial(reference.block, **SIZES))(
        jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float32), backend.snapshot_params()), x))
    assert rel_err(got, want) <= SERVED_TOL / 2, rel_err(got, want)
    module = backend.module
    assert module.decode_passes == PASSES and module.decode_cache_kind == "looped" and module.decode_rows_apart
    cache_k, cache_v = module.init_decode_cache(1, MAX_LEN)  # ONE pass's pair: the manager asks once a pass
    assert cache_k.shape == cache_v.shape == (1, HEADS, MAX_LEN, HID // HEADS) and str(cache_k.dtype) == "bfloat16"


def test_four_per_pass_caches_give_the_references_looped_forward():
    """A prompt, then one position at a time, through four caches a block: every pass's hidden states are the
    reference's full looped forward's; the session is ONE entry a block holding four trees at one position."""
    manager, x, prompt = fresh_manager(), stream(2, 1, 30), 20
    got = served_loop(manager, "s", x, prompt)
    want = np.asarray(reference_program()(all_params(), final_norm(), x))
    errors = [rel_err(got[u], want[u]) for u in range(PASSES)]
    print("per-pass caches against the reference's looped forward, pass by pass:", errors)
    assert max(errors) <= SERVED_TOL, errors
    assert len(manager._sessions) == len(CHAIN)
    session = manager._sessions[(CHAIN[0], "s")]
    assert session.positions == [30] * PASSES and len(session.trees) == PASSES and session.nbytes == PASSES * session.row_bytes
    assert len({id(leaf) for tree in session.trees for leaf in tree}) == 2 * PASSES  # a pair a pass, none shared


def test_rows_at_different_passes_and_positions_share_a_batched_program():
    """Five sessions stopped at different passes of different positions step in ONE batched program a block
    (the same program at every pass: nothing compiles for a pass), and each row's output is what the same
    row gives when its session steps alone."""
    from hivemind_tpu.telemetry import REGISTRY

    manager, twins = fresh_manager(), fresh_manager()
    prompts, stopped_at = [9, 12, 15, 18, 21], [0, 1, 2, 3, 1]  # row r takes its next step at pass stopped_at[r]
    x = stream(3, 5, 24)
    inputs = []
    for row, (prompt, stop) in enumerate(zip(prompts, stopped_at)):
        for side in (manager, twins):
            piece = x[row:row + 1, :prompt]
            for u in range(PASSES):
                piece = client_norm(side._decode_direct(CHAIN, f"r{row}", piece, True, u))
            piece = x[row:row + 1, prompt:prompt + 1]
            for u in range(stop):  # the passes of the next position that came before the one it stops at
                piece = client_norm(side._decode_direct(CHAIN, f"r{row}", piece, False, u))
        inputs.append(piece)
    compiled = len(manager._batched_fns)
    steps = lambda: dict(REGISTRY.snapshot()["hivemind_moe_decode_pass_steps_total"]["series"])
    before = steps()
    outs = inputs
    for uid in CHAIN:
        entries = [(None, manager._sessions[(uid, f"r{row}")], outs[row], stopped_at[row]) for row in range(5)]
        outs = manager._decode_batch(uid, entries)
        assert not any(isinstance(out, Exception) for out in outs), outs
    assert len(manager._batched_fns) - compiled == len(CHAIN), "one program a block for the bucket, whatever the passes"
    one_tree = manager._sessions[(CHAIN[0], "r0")].row_bytes
    assert manager._padding_bytes == len(CHAIN) * 3 * one_tree, "the three padding rows of a bucket of eight keep ONE tree each"
    moved = {key: steps()[key] - before.get(key, 0.0) for key in steps()}
    assert moved == {"pass=0": 2.0, "pass=1": 4.0, "pass=2": 2.0, "pass=3": 2.0}, moved
    for row, stop in enumerate(stopped_at):
        alone = twins._decode_direct(CHAIN, f"r{row}", inputs[row], False, stop)
        assert rel_err(outs[row], alone) <= 1e-2, (row, rel_err(outs[row], alone))
        session = manager._sessions[(CHAIN[0], f"r{row}")]
        assert session.positions == [prompts[row] + (u <= stop) for u in range(PASSES)], (row, session.positions)


WRONG_PROGRAMS = {
    "one cache shared by the passes": dict(served=dict(one_cache=True), wrong=("span_through_one_cache", dict(prompt=20))),
    "F left out between the passes": dict(served=dict(between=lambda y: y), wrong=("span", dict(norm_between=False))),
    "the output norms left out (plain pre-norm)": dict(served=None, wrong=("span", dict(sandwich=False))),
    "three passes for four": dict(served=dict(passes=3), wrong=("span", dict(passes=3))),
}


@pytest.mark.parametrize("name", list(WRONG_PROGRAMS))
def test_a_wrong_program_differs_by_more_than_the_tolerance(name):
    """Each wrong program, as the reference has it, is further from the model than the tolerance lets pass, on
    the decoded positions and at the last pass; and where the SERVED path can be driven wrongly (a manager made
    to share one cache, a client that leaves `F` out or walks three passes), what it then serves is that wrong
    program and not the model."""
    case, x, prompt = WRONG_PROGRAMS[name], stream(2, 1, 30), 20
    want = np.asarray(reference_program()(all_params(), final_norm(), x))
    entry, changed = case["wrong"]
    wrong = np.asarray(reference_program(entry, **changed)(all_params(), final_norm(), x)) if entry == "span" else np.asarray(
        getattr(reference, entry)(all_params(), final_norm(), x, **changed, **SIZES))
    last = lambda outs: outs[-1][:, prompt:]  # what the head would read of the decoded positions (of three passes: the third's)
    wrong_last, want_last = last(wrong), last(want)
    departure = rel_err(wrong_last, want_last)
    print(f"the reference with {name}: {departure:.3f} of the largest value from the model")
    assert departure >= WRONG_BY, (name, departure)
    if case["served"] is not None:
        got = served_loop(fresh_manager(), "w", x, prompt, **case["served"])
        assert rel_err(last(got), wrong_last) <= SERVED_TOL, (name, rel_err(last(got), wrong_last))
        assert rel_err(last(got), want_last) >= WRONG_BY, (name, rel_err(last(got), want_last))


def test_the_stepwise_form_of_the_reference_is_its_full_forward():
    """`span_through_one_cache` is a cached form: with NO decoded position there is nothing for the passes to
    share, and it is the full forward of the prompt (what the wrong reference's departure is measured from)."""
    x = stream(5, 1, 12)
    whole = np.asarray(reference_program()(all_params(), final_norm(), x))
    cached = np.asarray(reference.span_through_one_cache(all_params(), final_norm(), x, prompt=12, **SIZES))
    assert cached.shape == whole.shape and rel_err(cached, whole) <= 1e-5
