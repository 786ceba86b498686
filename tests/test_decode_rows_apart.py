"""The batched decode step on the rows' own caches (ISSUE 42): a dense-attention block
whose cache holds ``max_len`` slots says `decode_rows_apart`, and its batched program
updates and reads each row's own arrays, joining and splitting nothing. Here
`llama_block` (with grouped key-value heads, 8 of 32, and with as many as query heads)
and `causal_transformer` get the pair of tests that `tests/test_olmoe_block.py`
holds for `olmoe_block`: a padded bucket against each row's full forward through the
block itself, and the batched program against the per-session one. Then, for every
block of `layers/common.py`, what the program's text says: which blocks join their
rows' caches, and that a row's step is one function the rows share. Small sizes,
seeded weights; `tests/test_exaone_block.py` holds the same for K-EXAONE's two kinds
against its reference, `tests/test_tpu_compile.py` the program at published widths.

Since ISSUE 52 every block of `layers/common.py` that keeps keys and values keeps them
``[batch, kv_heads, max_len, head_dim]`` and steps through `_grouped_cache_step`: the
queries of a key-value head are held against that head's cache as it lies. Held here for
`llama_block`: a prompt and steps through the cache against the float32 reference
(`perf/reference/mistral_block.py`), and that the batched program makes no array of a cache's
length at QUERY width (the copy that `jnp.repeat` of the key-value heads made a row a step).

Since ISSUE 50 the batched program DONATES the rows' cache leaves, whatever the block: what
that means for a session's leaves, for the padding positions of a bucket (a throwaway cache
each, kept and counted by `hivemind_moe_decode_padding_cache_bytes`) and for the compiled
program's aliases is held here for every kind of cache the repo serves; what a FAILED step
leaves behind in a cohort is `tests/test_decode_cohort.py`'s."""

import functools
import re
import sys
import warnings
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
from perf.reference import mistral_block as llama_reference  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402

HID, MAX_LEN = 128, 32
DENSE = {  # name -> (class, sizes): the blocks whose batched step is held against their own forward
    "llama_block": ("llama_block", dict(num_heads=32, num_kv_heads=8)),  # Mistral's grouping: four query heads a key-value head
    "llama_block_ungrouped": ("llama_block", dict(num_heads=4)),  # as many key-value heads as query heads: a group of one
    "causal_transformer": ("causal_transformer", dict(num_heads=4)),
}
EXAONE = dict(num_heads=4, num_kv_heads=2, head_dim=16, ffn_inner=64)
EVERY = {  # name -> (class, sizes, what a batched program does with its rows' caches, the row step's name)
    "llama_block": (*DENSE["llama_block"], "apart", "_grouped_cache_step"),
    "causal_transformer": (*DENSE["causal_transformer"], "apart", "_grouped_cache_step"),
    "olmoe_block": ("olmoe_block", dict(num_heads=4, num_experts=4, experts_per_token=2, expert_inner=32), "apart", "_grouped_cache_step"),
    "exaone_full": ("exaone_moe_block", dict(window=0, **EXAONE), "apart", "_grouped_cache_step"),
    "exaone_window": ("exaone_moe_block", dict(window=8, **EXAONE), "joined", None),
}
SERVED_TOL = 2e-2  # bf16 activations on both sides; the cache path sums its scores in another order


_BACKENDS = {}  # read-only in every test (the optimizer's rate is 0): each built once a process


def make_backend(block: str, sizes: dict, uid="blk.0", seed=3, hidden=HID) -> ModuleBackend:
    key = (block, repr(sizes), uid, seed, hidden)
    if key not in _BACKENDS:
        _BACKENDS[key] = OneProgramBackend(uid, name_to_block[block](hidden, **sizes), optimizer=optax.sgd(0.0),
                                           sample_input=name_to_input[block](4, hidden), max_batch_size=8, rng_seed=seed)
    return _BACKENDS[key]


def stream(seed: int, batch: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((batch, length, HID)).astype(np.float32)


def rows_by_caches():
    rows = REGISTRY.get("hivemind_moe_decode_batched_rows_total")
    return rows.labels("apart").value, rows.labels("joined").value


def prefilled_rows(manager, uid, x, lengths):
    for row, length in enumerate(lengths):
        manager.decode(uid, f"row{row}", x[row:row + 1, :length], reset=True)
    return [manager._sessions[(uid, f"row{row}")] for row in range(len(lengths))]


@pytest.mark.parametrize("name", sorted(DENSE))
def test_batched_step_pads_a_bucket_and_matches_each_rows_full_forward(name):
    """7 sessions at different positions in a bucket of 8, three batched steps: each
    row against the block's own forward over that row's whole stream (no cache: causal
    attention over the chunk). The rows are counted as stepped apart, every session keeps
    arrays of its own, and the padding row's never become a session's."""
    from hivemind_tpu.telemetry.tracing import RECORDER

    block, sizes = DENSE[name]
    backend = make_backend(block, sizes)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    lengths = [3, 5, 8, 4, 11, 6, 9]
    x = stream(5, len(lengths), 16)
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    want = np.asarray(jax.jit(backend.module.apply)({"params": backend.params}, x))
    before = rows_by_caches()
    for step in range(3):
        entries = [(None, session, x[row:row + 1, length + step:length + step + 1])
                   for row, (session, length) in enumerate(zip(sessions, lengths))]
        for row, (out, length) in enumerate(zip(manager._decode_batch(backend.name, entries), lengths)):
            assert not isinstance(out, Exception), out
            assert out.shape == (1, 1, HID)
            assert rel_err(out, want[row:row + 1, length + step:length + step + 1]) <= SERVED_TOL * (
                np.abs(want).max() / np.abs(want[row, length + step]).max())
    assert rows_by_caches() == (before[0] + 3 * 7, before[1])
    kv_heads = sizes.get("num_kv_heads", sizes["num_heads"])
    assert all(session.index == length + 3 and session.cache_k.shape == (1, kv_heads, MAX_LEN, HID // sizes["num_heads"])
               for session, length in zip(sessions, lengths))
    held = {id(leaf) for session in sessions for leaf in session.leaves}
    assert len(held) == 2 * 7 and not held & {id(leaf) for leaf in manager._dummy_rows(backend.name)}
    assert list(manager._batched_fns) == [(backend.name, 8)], "the uid's view holds the bucket alone"
    assert manager._batched_fns[(backend.name, 8)] is manager._programs[(manager._kind(backend.name), "batched", 8)], "its program is its kind's"
    [span] = [s for s in RECORDER.snapshot() if s.name == "decode.batch" and (s.attributes or {}).get("uid") == backend.name][-1:]
    assert (span.attributes["caches"], span.attributes["bucket"], span.attributes["rows"]) == ("apart", 8, 7)


@pytest.mark.parametrize("name", sorted(DENSE))
def test_batched_step_equals_the_direct_step(name):
    """The same tokens through the batched program and through the per-session
    program: one cache step (`_grouped_cache_step` on one row, once a row), so the
    outputs and the caches agree to rounding."""
    backend = make_backend(*DENSE[name])
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    lengths = [4, 7, 5]
    x = stream(6, 3, 12)
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    twins = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    twin_sessions = prefilled_rows(twins, backend.name, x, lengths)
    for step in range(2):
        results = manager._decode_batch(backend.name, [(None, session, x[row:row + 1, length + step:length + step + 1])
                                                       for row, (session, length) in enumerate(zip(sessions, lengths))])
        for row, (out, length) in enumerate(zip(results, lengths)):
            want = twins.decode(backend.name, f"row{row}", x[row:row + 1, length + step:length + step + 1], reset=False)
            np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)
    for session, twin in zip(sessions, twin_sessions):
        for got, want in zip(session.leaves, twin.leaves):
            np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", sorted(EVERY))
def test_what_the_batched_programs_text_joins(name):
    """The program `_batched_fn` builds for a bucket of 4, lowered: a block that says
    `decode_rows_apart` has no concatenation that makes an array of the joined caches'
    shape, and its rows' cache step is ONE function of the module that every row calls
    (set-up traces a bucket of 32 once, not 32 times); a ring of ``window`` slots is
    joined as before. Its outputs are one array a leaf a session either way."""
    block, sizes, caches, row_step = EVERY[name]
    backend = make_backend(block, sizes)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    assert manager._rows_caches(backend.name) == caches
    rows, leaves = 4, manager._dummy_rows(backend.name)
    lowered = manager._batched_fn(backend.name, rows).jitted.lower(
        backend.params, jnp.zeros((rows, 1, HID), jnp.float32), tuple((leaf,) * rows for leaf in leaves), jnp.ones((rows,), jnp.int32))
    _y, new, _routing, _attended = lowered.out_info  # what the program hands back, as shapes
    assert [[(row.shape, row.dtype) for row in leaf] for leaf in new] == [[(leaf.shape, leaf.dtype)] * rows for leaf in leaves]
    text = lowered.as_text()
    joined = ["x".join(map(str, (rows,) + leaf.shape[1:])) for leaf in leaves]
    joins = [line for line in text.splitlines() if "stablehlo.concatenate" in line and any(f"tensor<{shape}x" in line.split("->")[-1] for shape in joined)]
    if caches == "joined":
        assert len(joins) == len(leaves), "one join a leaf"
        return
    assert not joins, joins
    assert len(re.findall(rf"func\.func private @{row_step}\(", text)) == 1, "the rows do not share one traced step"
    assert len(re.findall(rf"call @{row_step}\(", text)) == rows


# ---- ISSUE 52: the Llama-family blocks step through `_grouped_cache_step` ----------------------------------


@pytest.mark.parametrize("name", ["llama_block", "llama_block_ungrouped"])  # four query heads a key-value head, and one
def test_a_prompt_and_steps_through_the_cache_against_the_float32_reference(name):
    """A prompt of 11 positions (padded to 16: the tail lies in the cache past the session's end,
    where the steps overwrite it) and nine single steps through a session's cache, against the plain
    float32 reference's forward over the whole stream: the rotary offset, the head-major write of a
    chunk and of a position, and the grouping of the queries all show here."""
    block, sizes = DENSE[name]
    backend = make_backend(block, sizes)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    x = stream(4, 1, 20)
    chunks = [manager.decode(backend.name, "s", x[:, :11], reset=True)]
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(11, 20)]
    want = jax.jit(functools.partial(llama_reference.span, num_heads=sizes["num_heads"], num_kv_heads=sizes.get("num_kv_heads", sizes["num_heads"]),
                                     rope_theta=10000.0, rms_eps=1e-6))([backend.params], x)
    assert rel_err(np.concatenate(chunks, axis=1), want) <= SERVED_TOL
    session = manager._sessions[(backend.name, "s")]
    assert session.index == 20 and session.cache_k.dtype == session.cache_v.dtype == jnp.bfloat16


def every_array_made(jaxpr):
    """The shapes of all the arrays a jaxpr's equations produce, those of the functions it calls among them."""
    for equation in jaxpr.eqns:
        yield from (tuple(var.aval.shape) for var in equation.outvars if hasattr(var.aval, "shape"))
        for value in equation.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)  # a ClosedJaxpr holds one
                if hasattr(inner, "eqns"):
                    yield from every_array_made(inner)


def test_a_batched_step_makes_no_array_of_a_caches_length_at_query_width():
    """What a CPU run can count of ISSUE 52's copy: in the batched program of a `llama_block` with four
    query heads a key-value head, traced for a bucket of 4, NO equation produces an array of
    ``heads x max_len x head_dim`` values (the keys or values of a row repeated to query width: 2 x 16.8 MB a
    row a step at Mistral's sizes); the largest thing made from a cache is a cache (``kv_heads x max_len x
    head_dim``) or its scores. 48 slots, so that no weight matrix of the block has as many values."""
    block, sizes = DENSE["llama_block"]
    heads, kv_heads, max_len, rows = sizes["num_heads"], sizes["num_kv_heads"], 48, 4
    backend = make_backend(block, sizes)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=max_len)
    leaves = manager._dummy_rows(backend.name)
    assert [leaf.shape for leaf in leaves] == [(1, kv_heads, max_len, HID // heads)] * 2
    traced = jax.make_jaxpr(manager._batched_fn(backend.name, rows).jitted)(
        backend.params, jnp.zeros((rows, 1, HID), jnp.float32), tuple((leaf,) * rows for leaf in leaves), jnp.ones((rows,), jnp.int32))
    made = list(every_array_made(traced.jaxpr))
    at_query_width = heads * max_len * (HID // heads)
    assert any(int(np.prod(shape)) == kv_heads * max_len * (HID // heads) for shape in made), "the walk does not reach the rows' cache step"
    assert not [shape for shape in made if int(np.prod(shape)) >= at_query_width and max_len in shape], "a cache was copied at query width"


# ---- ISSUE 50: the batched program donates the rows' cache leaves -----------------------------------------

SALA = dict(num_heads=4, num_kv_heads=2, head_dim=16, ffn_inner=96, kernel_size=4, kernel_stride=2, block_size=8, topk=6,
            init_blocks=1, window_size=16, dense_len=64)
LATENT = dict(mlp="dense", num_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
              rope_theta=1e5, rope_factor=8.0, rope_original=32, ffn_inner=96)
CACHE_KINDS = {  # what a session keeps -> (class, hidden, sizes, what a batched program does with the rows' caches, leaves a session)
    "pair": ("llama_block", HID, DENSE["llama_block"][1], "apart", 2),
    "ring": ("exaone_moe_block", HID, dict(window=8, **EXAONE), "joined", 2),
    "sparse_tree": ("minicpm_sala_block", 64, dict(mixer="minicpm4", **SALA), "apart", 3),
    "lightning_state": ("minicpm_sala_block", 64, dict(mixer="lightning-attn", **SALA), "joined", 1),
    "latent": ("deepseek_v3_block", 64, LATENT, "apart", 1),
}


def kind_backend(kind: str, uid="blk.0"):
    block, hidden, sizes, caches, leaves = CACHE_KINDS[kind]
    return make_backend(block, sizes, uid, hidden=hidden), hidden, caches, leaves


def donated_bytes(path: str) -> float:
    return REGISTRY.get("hivemind_moe_decode_cache_bytes_donated_total").labels(path).value


def padding_gauge() -> float:
    return REGISTRY.get("hivemind_moe_decode_padding_cache_bytes").value()


def alias_count(compiled_text: str) -> int:
    """How many outputs the compiled program's `input_output_alias` ties to a parameter."""
    header = next(line for line in compiled_text.splitlines() if line.startswith("HloModule"))
    found = re.search(r"input_output_alias=\{(.*?)\}, entry_computation_layout", header)
    return len(re.findall(r"\{[\d, ]*\}: \(\d+, \{[\d, ]*\}, (?:may|must)-alias\)", found.group(1))) if found else 0


@pytest.mark.parametrize("kind", sorted(CACHE_KINDS))
def test_a_batched_step_takes_every_rows_leaves_and_hands_back_readable_ones(kind):
    """After a batched step every live session's former leaves are deleted buffers (the program
    was handed them for good) and its new ones can be read; the bytes handed over are counted
    from the shapes, the padding position's throwaway cache among them; the span says so."""
    from hivemind_tpu.telemetry.tracing import RECORDER

    backend, hidden, caches, leaves = kind_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    assert manager._rows_caches(backend.name) == caches
    lengths = [5, 9, 7]
    x = np.random.default_rng(1).standard_normal((3, 16, hidden)).astype(np.float32)
    before_direct = donated_bytes("direct")
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    assert donated_bytes("direct") - before_direct == sum(session.nbytes for session in sessions), "a prefill donates a fresh cache"
    for step in range(2):
        former = [session.leaves for session in sessions]
        assert all(len(row) == leaves and not any(leaf.is_deleted() for leaf in row) for row in former)
        before = donated_bytes("batched")
        outs = manager._decode_batch(backend.name, [(None, session, x[row:row + 1, length + step:length + step + 1])
                                                    for row, (session, length) in enumerate(zip(sessions, lengths))])
        assert not any(isinstance(out, Exception) for out in outs) and all(np.isfinite(out).all() for out in outs)
        assert all(leaf.is_deleted() for row in former for leaf in row), "a leaf outlived the program that was handed it"
        for session, was in zip(sessions, former):
            assert [(leaf.shape, leaf.dtype) for leaf in session.leaves] == [(leaf.shape, leaf.dtype) for leaf in was]
            assert all(np.isfinite(np.asarray(leaf, np.float32)).all() for leaf in session.leaves)
        # three live rows in a bucket of four: the fourth position's throwaway cache is handed over like a session's
        assert donated_bytes("batched") - before == 4 * sessions[0].nbytes
    [span] = [s for s in RECORDER.snapshot() if s.name == "decode.batch" and (s.attributes or {}).get("uid") == backend.name][-1:]
    assert span.attributes["donated"] is True and span.attributes["caches"] == caches
    assert not any(leaf.is_deleted() for leaf in manager._dummy_rows(backend.name)), "the throwaway row that is kept is the new one"


@pytest.mark.parametrize("kind", ["pair", "ring"])
def test_every_padding_position_has_a_cache_of_its_own(kind):
    """Five sessions in a bucket of eight: THREE padding positions, each a throwaway cache of
    its own (one array in two positions of a call cannot be donated). The batch runs, every row
    equals the same token through the per-session program, the throwaway rows are distinct
    arrays before the step and after it (the new ones: the old were handed over), the gauge
    holds three rows' bytes and no more after a second step, and `clear_sessions()` releases them."""
    backend, hidden, _caches, leaves = kind_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    twins = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    lengths = [4, 7, 5, 9, 6]
    x = np.random.default_rng(2).standard_normal((5, 16, hidden)).astype(np.float32)
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    prefilled_rows(twins, backend.name, x, lengths)
    row_bytes = sessions[0].nbytes
    seen = []
    for step in range(2):
        outs = manager._decode_batch(backend.name, [(None, session, x[row:row + 1, length + step:length + step + 1])
                                                    for row, (session, length) in enumerate(zip(sessions, lengths))])
        for row, (out, length) in enumerate(zip(outs, lengths)):
            want = twins.decode(backend.name, f"row{row}", x[row:row + 1, length + step:length + step + 1], reset=False)
            np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)
        kept = manager._padding_rows[backend.name]
        assert len(kept) == 3 and len({id(leaf) for row in kept for leaf in row}) == 3 * leaves
        assert not any(leaf.is_deleted() for row in kept for leaf in row)
        assert manager._padding_bytes == 3 * row_bytes
        seen.append(kept[:])
    assert all(leaf.is_deleted() for row in seen[0] for leaf in row), "the second step was handed the first one's throwaway rows"
    assert not {id(leaf) for row in seen[1] for leaf in row} & {id(leaf) for session in sessions for leaf in session.leaves}
    assert list(manager._batched_fns) == [(backend.name, 8)], "the uid's view holds the bucket alone"
    assert manager._batched_fns[(backend.name, 8)] is manager._programs[(manager._kind(backend.name), "batched", 8)], "its program is its kind's"
    manager._decode_batch(backend.name, [(None, session, x[row:row + 1, 12:13]) for row, session in enumerate(sessions[:3])])  # pads by one
    assert manager._padding_bytes == 3 * row_bytes and padding_gauge() == 3 * row_bytes, "the store keeps the largest padding a call has needed"
    manager.clear_sessions()
    assert manager._padding_rows == {} and manager._padding_bytes == 0 and padding_gauge() == 0
    assert manager._dummy_rows(backend.name)[0].shape == sessions[0].leaves[0].shape  # whoever lowers a program is handed a fresh row


@pytest.mark.parametrize("kind", sorted(CACHE_KINDS))
def test_the_compiled_batched_program_aliases_every_cache_leaf_of_every_row(kind):
    """The program `_batched_fn` builds for a bucket of 4, compiled (here by the CPU's compiler,
    which donates as the chip's does): every cache leaf of every row is aliased to an output,
    whether the block takes the rows' caches apart or joins them, and jax has no donated buffer
    that it could not use. A block whose step copied its cache argument before writing would
    show here as an alias short."""
    backend, hidden, _caches, leaves = kind_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    rows, row = 4, manager._dummy_rows(backend.name)
    assert len(row) == leaves
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = manager._batched_fn(backend.name, rows).jitted.lower(
            backend.params, jnp.zeros((rows, 1, hidden), jnp.float32), tuple((leaf,) * rows for leaf in row), jnp.ones((rows,), jnp.int32)).compile()
    assert not [str(w.message) for w in caught if "donated" in str(w.message).lower()]
    assert alias_count(compiled.as_text()) == leaves * rows
    assert alias_count(manager._step_fn(backend.name, 1, 1).jitted.lower(
        backend.params, jnp.zeros((1, 1, hidden), jnp.float32), row, jnp.int32(1),
        *((jnp.int32(1),) if manager._takes_length(backend.name) else ())).compile().as_text()) == leaves, "the per-session step"


def test_a_program_that_fails_after_it_took_the_caches_drops_its_sessions_and_its_padding():
    """`_decode_batch` alone (a benchmark's check, a chain of one): a program that was handed
    three sessions' caches and a throwaway row and then fails leaves deleted buffers behind.
    Those sessions leave the table, counted `reason="failed_step"`, the lost throwaway row
    leaves the gauge, sessions outside the batch step on, and the next batch of the same
    bucket runs with a fresh throwaway row."""
    backend = make_backend(*DENSE["llama_block"])
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    lengths = [4, 6, 5, 7, 3, 8]
    x = stream(9, len(lengths), 16)
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    entries = lambda rows, step: [(None, sessions[row], x[row:row + 1, lengths[row] + step:lengths[row] + step + 1]) for row in rows]  # noqa: E731
    assert not any(isinstance(out, Exception) for out in manager._decode_batch(backend.name, entries([0, 1, 2], 0)))
    assert manager._padding_bytes == sessions[0].nbytes
    real = manager._batched_fns[(backend.name, 4)]

    def lost_after_the_dispatch(*args):
        real(*args)
        raise RuntimeError("device lost")

    failed = REGISTRY.get("hivemind_moe_decode_session_evictions_total").labels("failed_step")
    before = failed.value
    manager._batched_fns[(backend.name, 4)] = lost_after_the_dispatch
    with pytest.raises(RuntimeError, match="device lost"):
        manager._decode_batch(backend.name, entries([0, 1, 2], 1))
    manager._batched_fns[(backend.name, 4)] = real
    assert failed.value - before == 3
    assert sorted(name for _uid, name in manager._sessions) == ["row3", "row4", "row5"]
    assert manager._padding_bytes == 0 and padding_gauge() == 0 and manager._padding_rows[backend.name] == []
    assert not any(session.lock.locked() for session in sessions)
    with pytest.raises(KeyError, match="unknown or expired"):
        manager.decode(backend.name, "row0", x[:1, 6:7], reset=False)
    outs = manager._decode_batch(backend.name, entries([3, 4, 5], 0))
    assert not any(isinstance(out, Exception) for out in outs) and [sessions[row].index for row in (3, 4, 5)] == [8, 4, 9]
    assert manager._padding_bytes == sessions[3].nbytes
    manager.decode(backend.name, "row0", x[:1, :4], reset=True)  # the client re-prefills, and steps on
    assert manager.decode(backend.name, "row0", x[:1, 4:5], reset=False).shape == (1, 1, HID)
