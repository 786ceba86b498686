"""The batched decode step on the rows' own caches (ISSUE 42): a dense-attention block
whose cache holds ``max_len`` slots says `decode_rows_apart`, and its batched program
updates and reads each row's own arrays, joining and splitting nothing. Here the two
blocks that have no reference of their own (`llama_block` with grouped key-value heads,
8 of 32, and `causal_transformer`) get the pair of tests that `tests/test_olmoe_block.py`
holds for `olmoe_block`: a padded bucket against each row's full forward through the
block itself, and the batched program against the per-session one. Then, for every
block of `layers/common.py`, what the program's text says: which blocks join their
rows' caches, and that a row's step is one function the rows share. Small sizes,
seeded weights; `tests/test_exaone_block.py` holds the same for K-EXAONE's two kinds
against its reference, `tests/test_tpu_compile.py` the program at published widths."""

import re
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from hivemind_tpu.moe.server.decode_session import DecodeSessionManager  # noqa: E402
from hivemind_tpu.moe.server.layers import name_to_block, name_to_input  # noqa: E402
from hivemind_tpu.moe.server.module_backend import ModuleBackend  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.runtime import rel_err  # noqa: E402

HID, MAX_LEN = 128, 32
DENSE = {  # the blocks without a reference of their own
    "llama_block": dict(num_heads=32, num_kv_heads=8),  # Mistral's grouping: four query heads a key-value head
    "causal_transformer": dict(num_heads=4),
}
EXAONE = dict(num_heads=4, num_kv_heads=2, head_dim=16, ffn_inner=64)
EVERY = {  # name -> (class, sizes, what a batched program does with its rows' caches, the row step's name)
    "llama_block": ("llama_block", DENSE["llama_block"], "apart", "_decode_attention"),
    "causal_transformer": ("causal_transformer", DENSE["causal_transformer"], "apart", "_decode_attention"),
    "olmoe_block": ("olmoe_block", dict(num_heads=4, num_experts=4, experts_per_token=2, expert_inner=32), "apart", "_decode_attention"),
    "exaone_full": ("exaone_moe_block", dict(window=0, **EXAONE), "apart", "_grouped_cache_step"),
    "exaone_window": ("exaone_moe_block", dict(window=8, **EXAONE), "joined", None),
}
SERVED_TOL = 2e-2  # bf16 activations on both sides; the cache path sums its scores in another order


def make_backend(block: str, sizes: dict, uid="blk.0", seed=3) -> ModuleBackend:
    return ModuleBackend(uid, name_to_block[block](HID, **sizes), optimizer=optax.sgd(0.0),
                         sample_input=name_to_input[block](4, HID), max_batch_size=8, rng_seed=seed)


def stream(seed: int, batch: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((batch, length, HID)).astype(np.float32)


def rows_by_caches():
    rows = REGISTRY.get("hivemind_moe_decode_batched_rows_total")
    return rows.labels("apart").value, rows.labels("joined").value


def prefilled_rows(manager, uid, x, lengths):
    for row, length in enumerate(lengths):
        manager.decode(uid, f"row{row}", x[row:row + 1, :length], reset=True)
    return [manager._sessions[(uid, f"row{row}")] for row in range(len(lengths))]


@pytest.mark.parametrize("block", sorted(DENSE))
def test_batched_step_pads_a_bucket_and_matches_each_rows_full_forward(block):
    """7 sessions at different positions in a bucket of 8, three batched steps: each
    row against the block's own forward over that row's whole stream (no cache: causal
    attention over the chunk). The rows are counted as stepped apart, every session keeps
    arrays of its own, and the padding row's never become a session's."""
    from hivemind_tpu.telemetry.tracing import RECORDER

    backend = make_backend(block, DENSE[block])
    manager = DecodeSessionManager({backend.name: backend}, max_len=MAX_LEN)
    lengths = [3, 5, 8, 4, 11, 6, 9]
    x = stream(5, len(lengths), 16)
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    want = np.asarray(backend.module.apply({"params": backend.params}, jnp.asarray(x)))
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
    kv_heads = DENSE[block].get("num_kv_heads", DENSE[block]["num_heads"])
    assert all(session.index == length + 3 and session.cache_k.shape == (1, MAX_LEN, kv_heads, HID // DENSE[block]["num_heads"])
               for session, length in zip(sessions, lengths))
    held = {id(leaf) for session in sessions for leaf in session.leaves}
    assert len(held) == 2 * 7 and not held & {id(leaf) for leaf in manager._dummy_rows(backend.name)}
    assert list(manager._batched_fns) == [(backend.name, 8)], "the batch's program is keyed by (uid, bucket) alone"
    [span] = [s for s in RECORDER.snapshot() if s.name == "decode.batch" and (s.attributes or {}).get("uid") == backend.name][-1:]
    assert (span.attributes["caches"], span.attributes["bucket"], span.attributes["rows"]) == ("apart", 8, 7)


@pytest.mark.parametrize("block", sorted(DENSE))
def test_batched_step_equals_the_direct_step(block):
    """The same tokens through the batched program and through the per-session
    program: one cache step (`_decode_attention`'s scalar form, once a row), so the
    outputs and the caches agree to rounding."""
    backend = make_backend(block, DENSE[block])
    manager = DecodeSessionManager({backend.name: backend}, max_len=MAX_LEN)
    lengths = [4, 7, 5]
    x = stream(6, 3, 12)
    sessions = prefilled_rows(manager, backend.name, x, lengths)
    twins = DecodeSessionManager({backend.name: backend}, max_len=MAX_LEN)
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
    manager = DecodeSessionManager({backend.name: backend}, max_len=MAX_LEN)
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
