"""A decode program is made once for each KIND of block, not once for each uid (ISSUE 61).

`DecodeSessionManager` looks a step, a prefill or a batched bucket up by what its block IS
(`_kind`: the flax module, how the backend makes dense parameters of the stored ones, where the
caches are placed) and the shape, and builds it only when no block of that kind has. Held here:
the blocks of a kind share ONE jitted object and the second block's first call compiles nothing;
the shared program lowers, for the second block, to the text that a manager of that block alone
lowers; a block that differs in a module field, in its quantization or in its caches' placement
keeps programs of its own, and so does one whose module cannot be hashed (the counter's `origin`
says which); a chain of two equal blocks decodes to the values that two managers of one block
each decode, bit for bit; and the programs hold no backend. Toy sizes, each backend built once."""

import dataclasses
import functools
import gc
import sys
import weakref
from pathlib import Path

import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from hivemind_tpu.moe.server.decode_session import DecodeSessionManager  # noqa: E402
from hivemind_tpu.moe.server.layers import name_to_block, name_to_input  # noqa: E402
from hivemind_tpu.moe.server.mesh_backend import MeshModuleBackend  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from swarm_utils import OneProgramBackend, decode_compiles  # noqa: E402

HID, MAX_LEN, BLOCK = 32, 32, "llama_block"
SIZES = dict(num_heads=4, num_kv_heads=2)
LlamaBlock = name_to_block[BLOCK]


class NotedBlock(LlamaBlock):
    """The block with a field that compares equal and cannot be hashed: its text is the block's."""

    notes: list = dataclasses.field(default_factory=list)


@functools.cache
def backend(uid: str, seed: int = 1, quantized: bool = False, mesh_of: int = 0, noted: bool = False, **sizes):
    """Read-only in every test (the optimizer's rate is 0): built once a process."""
    module = (NotedBlock if noted else LlamaBlock)(HID, **{**SIZES, **sizes})
    common = dict(optimizer=optax.sgd(0.0), sample_input=name_to_input[BLOCK](4, HID), max_batch_size=8, rng_seed=seed,
                  weight_quantization="int8" if quantized else None)
    if mesh_of:
        return MeshModuleBackend(uid, module, mesh=Mesh(np.array(jax.devices()[:mesh_of]), ("tp",)), **common)
    return OneProgramBackend(uid, module, **common)


def programs() -> dict:
    counter = REGISTRY.get("hivemind_moe_decode_programs_total")
    return {origin: counter.labels(origin).value for origin in ("built", "shared")}


def moved(before: dict) -> dict:
    return {origin: value - before[origin] for origin, value in programs().items()}


def stream(seed: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((1, length, HID)).astype(np.float32)


def walk(manager, uid: str):
    """A prefill, the session's own step and a batched bucket of two at ``uid``: what it handed back."""
    x = stream(0, 12)
    outs = [manager.decode(uid, name, x[:, :8], reset=True) for name in ("r0", "r1")]
    outs.append(manager.decode(uid, "r0", x[:, 8:9], reset=False))
    entries = [(None, manager._sessions[(uid, name)], x[:, 9:10]) for name in ("r0", "r1")]
    outs += manager._decode_batch(uid, entries)
    assert not any(isinstance(out, Exception) for out in outs), outs
    return outs


def test_the_second_block_of_a_kind_is_handed_the_firsts_programs_and_compiles_nothing():
    a, b = backend("a.0", seed=1), backend("a.1", seed=2)
    assert a.module == b.module and a.module is not b.module
    manager = DecodeSessionManager({"a.0": a, "a.1": b}, max_len=MAX_LEN)
    before, compiles = programs(), decode_compiles()
    first = walk(manager, "a.0")
    assert moved(before) == {"built": 3, "shared": 0} and decode_compiles() - compiles >= 3
    before, compiles = programs(), decode_compiles()
    second = walk(manager, "a.1")
    assert moved(before) == {"built": 0, "shared": 3} and decode_compiles() == compiles, "the second block's first calls compiled"
    assert manager._step_fn("a.0", 1, 8) is manager._step_fn("a.1", 1, 8)
    assert manager._step_fn("a.0", 1, 1) is manager._step_fn("a.1", 1, 1) is not manager._step_fn("a.0", 1, 8)
    assert manager._batched_fn("a.0", 2) is manager._batched_fn("a.1", 2)
    assert sorted(manager._step_fns) == [(uid, 1, length) for uid in ("a.0", "a.1") for length in (1, 8)], "a view a uid, as ever"
    assert sorted(manager._batched_fns) == [("a.0", 2), ("a.1", 2)] and len(manager._programs) == 3
    assert moved(before) == {"built": 0, "shared": 3}, "a program a uid already holds is looked up in its view: nothing is counted"
    assert not any(np.array_equal(one, other) for one, other in zip(first, second)), "other weights, other values"


def lowered(manager, uid: str, shape: str) -> str:
    shaped = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)  # noqa: E731
    params, row = shaped(manager.backends[uid].snapshot_params()), shaped(manager._dummy_rows(uid))
    index = jax.ShapeDtypeStruct((), "int32")
    if shape == "batched":
        return manager._batched_fn(uid, 4).jitted.lower(params, jax.ShapeDtypeStruct((4, 1, HID), "float32"), tuple((leaf,) * 4 for leaf in row),
                                                        jax.ShapeDtypeStruct((4,), "int32")).as_text()
    length = 1 if shape == "step" else 16
    return manager._step_fn(uid, 1, length).jitted.lower(params, jax.ShapeDtypeStruct((1, length, HID), "float32"), row, index).as_text()


@pytest.mark.parametrize("shape", ["step", "prefill", "batched"])
def test_the_shared_program_lowers_for_the_second_block_to_what_a_manager_of_that_block_alone_lowers(shape):
    a, b = backend("a.0", seed=1), backend("a.1", seed=2)
    both = DecodeSessionManager({"a.0": a, "a.1": b}, max_len=MAX_LEN)
    lowered(both, "a.0", shape)  # the first block builds it
    before = programs()
    shared = lowered(both, "a.1", shape)
    assert moved(before) == {"built": 0, "shared": 1}
    alone = lowered(DecodeSessionManager({"a.1": b}, max_len=MAX_LEN), "a.1", shape)
    assert shared == alone and "stablehlo" in shared


OTHERS = {  # what makes the second block another kind -> its backend
    "module_field": lambda: backend("c.0", num_kv_heads=4),
    "weight_quantization": lambda: backend("q.0", quantized=True),
    "cache_placement": lambda: backend("m.0", mesh_of=2),
}


@pytest.mark.parametrize("differs", sorted(OTHERS))
def test_a_block_of_another_kind_gets_programs_of_its_own(differs):
    a, other = backend("a.0", seed=1), OTHERS[differs]()
    manager = DecodeSessionManager({"a.0": a, other.name: other}, max_len=MAX_LEN)
    assert manager._kind("a.0") != manager._kind(other.name)
    if differs != "module_field":
        assert a.module == other.module
    before = programs()
    for uid in ("a.0", other.name):
        manager._step_fn(uid, 1, 1), manager._step_fn(uid, 1, 8), manager._batched_fn(uid, 2)
    assert moved(before) == {"built": 6, "shared": 0} and len(manager._programs) == 6
    assert manager._step_fn("a.0", 1, 1) is not manager._step_fn(other.name, 1, 1)
    assert manager._batched_fn("a.0", 2) is not manager._batched_fn(other.name, 2)
    walk(manager, other.name)  # and they run: each on its own parameters' tree and placement


def test_two_blocks_on_one_mesh_are_one_kind_and_the_caches_stay_where_the_backend_places_them():
    m, n = backend("m.0", mesh_of=2), backend("m.1", seed=2, mesh_of=2)
    manager = DecodeSessionManager({"m.0": m, "m.1": n}, max_len=MAX_LEN)
    assert manager._kind("m.0") == manager._kind("m.1") and manager._kind("m.0")[-1] is not None
    before = programs()
    walk(manager, "m.0"), walk(manager, "m.1")
    assert moved(before) == {"built": 3, "shared": 3}
    placed = manager._cache_shardings("m.1")
    assert all(leaf.sharding == where for leaf, where in zip(manager._sessions[("m.1", "r1")].leaves, placed))


def test_a_module_that_cannot_be_hashed_keeps_programs_of_its_own_and_the_counter_says_built():
    n, o = backend("n.0", seed=1, noted=True), backend("n.1", seed=2, noted=True)
    assert n.module == o.module
    with pytest.raises(TypeError):
        hash(n.module)
    manager = DecodeSessionManager({"n.0": n, "n.1": o}, max_len=MAX_LEN)
    assert manager._kind("n.0") is None
    before, compiles = programs(), decode_compiles()
    walk(manager, "n.0"), walk(manager, "n.1")
    assert moved(before) == {"built": 6, "shared": 0} and not manager._programs
    assert decode_compiles() - compiles >= 6 and manager._step_fn("n.0", 1, 1) is not manager._step_fn("n.1", 1, 1)


def test_a_chain_of_two_equal_blocks_decodes_bit_for_bit_what_a_manager_a_block_decodes():
    a, b = backend("a.0", seed=1), backend("a.1", seed=2)
    chain = ("a.0", "a.1")
    together = DecodeSessionManager({"a.0": a, "a.1": b}, max_len=MAX_LEN)
    apart = [DecodeSessionManager({"a.0": a}, max_len=MAX_LEN), DecodeSessionManager({"a.1": b}, max_len=MAX_LEN)]
    names, x = ("r0", "r1", "r2"), [stream(seed, 14) for seed in (3, 4, 5)]
    for row, name in enumerate(names):
        want = x[row][:, :8]
        for manager, uid in zip(apart, chain):
            want = manager.decode(uid, name, want, reset=True)
        np.testing.assert_array_equal(together._decode_direct(chain, name, x[row][:, :8], True), want)
    for step in range(8, 12):
        want = [x[row][:, step:step + 1] for row in range(3)]
        for manager, uid in zip(apart, chain):
            want = manager._decode_batch(uid, [(None, manager._sessions[(uid, name)], token) for name, token in zip(names, want)])
        entries = [(None, [together._sessions[(uid, name)] for uid in chain], x[row][:, step:step + 1]) for row, name in enumerate(names)]
        got = together._launch_cohort(chain, entries)()
        for one, other in zip(got, want):
            np.testing.assert_array_equal(one, other)
    np.testing.assert_array_equal(together._decode_direct(chain, "r0", x[0][:, 12:13], False),
                                  apart[1].decode("a.1", "r0", apart[0].decode("a.0", "r0", x[0][:, 12:13], reset=False), reset=False))
    assert len(together._programs) == 3 and len(together._step_fns) == 4 and len(together._batched_fns) == 2


def test_the_programs_hold_no_backend_one_dropped_with_its_sessions_is_collected():
    module = LlamaBlock(HID, **SIZES)
    make = lambda uid, seed: OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input[BLOCK](4, HID),  # noqa: E731
                                               max_batch_size=8, rng_seed=seed)
    manager = DecodeSessionManager({"w.0": make("w.0", 1), "w.1": make("w.1", 2)}, max_len=MAX_LEN)
    walk(manager, "w.0"), walk(manager, "w.1")
    gone, weights = weakref.ref(manager.backends["w.0"]), weakref.ref(jax.tree_util.tree_leaves(manager.backends["w.0"].params)[0])
    manager.clear_sessions()
    del manager.backends["w.0"]  # the block that BUILT every program of the kind
    gc.collect()
    assert gone() is None and weights() is None, "a program of the manager pins the backend that built it"
    walk(manager, "w.1")  # and the block that is left steps on, with the programs the other built
    assert manager._step_fn("w.1", 1, 1) is manager._programs[(manager._kind("w.1"), "step", 1, 1)]
