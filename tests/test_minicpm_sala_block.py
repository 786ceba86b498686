"""`minicpm_sala_block` (MiniCPM-SALA's decoder blocks: a block-sparse attention mixer
that keeps keys, values and compressed keys, and a lightning linear-attention mixer
that keeps one recurrent state) against the plain float32 reference
`perf/reference/minicpm_sala_block.py`, on every serving path: the block's forward,
`DecodeSessionManager` with a prompt that arrives in chunks of unequal length (the last
one padded) and then single-token steps, the batched step with rows at different
positions (one of them crossing `dense_len` mid-answer), and the rehearsal
configuration's span through `Server` + `RemoteSequential`. Beside them what the cache
tree and the chunks changed in the manager: the four blocks that keep a `(cache_k,
cache_v)` pair refuse a continuation chunk as before, a chain takes one only if all
its blocks do, a failed step leaves no half-updated state. Small sizes, seeded weights.

Tolerances, as a share of the largest value of the reference's output: the served
arithmetic (bf16 activations, float32 accumulation, state and selection scores) reads
2e-3 to 8e-3 at these sizes; a near-tie of the selection that the bf16 rounding of q
and k flips moves ONE position by a sixth of its attention (2e-2), so a stream is held
to `SERVED_TOL` on all but a few positions (`positions_beyond`)."""

import math
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
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.reference import minicpm_sala_block as reference  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402

HID, HEADS, KV, DIM, INNER = 64, 4, 2, 16, 96
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6, init_blocks=1, window_size=16, dense_len=64)
COMMON = dict(num_heads=HEADS, num_kv_heads=KV, head_dim=DIM, ffn_inner=INNER, **SPARSE)
ALPHA = 1.4 / math.sqrt(32)
SIZES = dict(alpha=ALPHA, rms_eps=1e-6, lightning=dict(heads=HEADS, head_dim=DIM, rope_theta=10000.0),
             sparse=dict(heads=HEADS, kv_heads=KV, head_dim=DIM, **SPARSE))
MIXERS = {"sparse": "minicpm4", "lightning": "lightning-attn"}
SERVED_TOL = 1.2e-2
MAX_LEN = 256


@functools.cache  # read-only in every test (the optimizer's rate is 0): built once a process
def make_backend(kind: str, uid="sala.0", seed=3, **overrides) -> ModuleBackend:
    module = name_to_block["minicpm_sala_block"](HID, mixer=MIXERS[kind], **{**COMMON, **overrides})
    return OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input["minicpm_sala_block"](4, HID),
                             max_batch_size=8, rng_seed=seed)


reference_span = jax.jit(functools.partial(reference.span, **SIZES))  # ONE program a shape, not one an operation


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


@pytest.mark.parametrize("kind", ["sparse", "lightning"])
def test_forward_matches_the_reference(kind):
    """The block on a whole sequence (the pool's forward) against the reference's:
    120 positions, past `dense_len` 64 and past the 48 positions that 6 blocks of 8 cover."""
    backend = make_backend(kind)
    x = stream(1, 2, 120)
    want = reference_span([backend.params], x)
    got = jax.jit(backend.module.apply)({"params": backend.params}, x)
    assert rel_err(got, want) <= (5e-3 if kind == "lightning" else 5e-2)
    assert positions_beyond(got, want, SERVED_TOL) <= 0.05


@pytest.mark.parametrize("kind", ["sparse", "lightning"])
def test_chunked_prompt_then_steps_equal_the_full_forward(kind):
    """A prompt of 101 positions in chunks of 48, 37 and 16 (the second crosses
    `dense_len` and is padded to 64, the first to 64 too), then 40 single steps, through
    the manager: the same positions as the reference's one forward of 141."""
    backend = make_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    x = stream(2, 1, 141)
    chunks, at = [], 0
    for length in (48, 37, 16):
        chunks.append(manager.decode(backend.name, "s", x[:, at:at + length], reset=at == 0))
        at += length
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(at, 141)]
    got = np.concatenate(chunks, axis=1)
    want = reference_span([backend.params], x)
    assert got.shape == want.shape and positions_beyond(got, want, SERVED_TOL) <= 0.05
    session = manager._sessions[(backend.name, "s")]
    assert session.index == 141 and len(jax.tree_util.tree_leaves(session.cache)) == (3 if kind == "sparse" else 1)


@pytest.mark.parametrize("kind", ["sparse", "lightning"])
def test_batched_rows_at_different_positions(kind):
    """Three sessions step together in ONE batched program (a vector ``index``), each
    at its own position: row 1 starts under `dense_len` and crosses it mid-answer, so
    one program holds a row in the dense mode beside rows in the sparse mode; every
    row equals the reference's full forward of its own stream."""
    backend = make_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    lengths, steps = [90, 50, 70], 30
    x = stream(3, 3, max(lengths) + steps)
    got = [[manager.decode(backend.name, f"row{row}", x[row:row + 1, :length], reset=True)] for row, length in enumerate(lengths)]
    before = counter("hivemind_moe_decode_calls_total", path="batched")
    for step in range(steps):
        entries = [(None, manager._sessions[(backend.name, f"row{row}")], x[row:row + 1, length + step:length + step + 1])
                   for row, length in enumerate(lengths)]
        for row, out in enumerate(manager._decode_batch(backend.name, entries)):
            assert not isinstance(out, Exception), out
            got[row].append(out)
    assert counter("hivemind_moe_decode_calls_total", path="batched") - before == steps
    want = np.asarray(reference_span([backend.params], x))
    for row, length in enumerate(lengths):
        served = np.concatenate(got[row], axis=1)
        assert positions_beyond(served, want[row:row + 1, :length + steps], SERVED_TOL) <= 0.05, row


def test_chunked_scan_equals_the_recurrence():
    """`lightning_scan` over a chunk (sub-chunks of 8, a padded tail that neither decays
    the state nor adds to it) equals `lightning_step` position by position, in float32,
    outputs and the state it leaves; and two chunks in a row equal one."""
    from hivemind_tpu.ops.linear_attention import lightning_log_decay, lightning_scan, lightning_step

    lightning_scan = jax.jit(lightning_scan, static_argnames=("length", "sub_chunk"))  # a program a shape, not one an operation
    lightning_step = jax.jit(lightning_step)
    rng = np.random.default_rng(4)
    q, k, v = (rng.standard_normal((2, 40, HEADS, DIM)).astype(np.float32) for _ in range(3))
    log_decay = lightning_log_decay(HEADS)
    state, outs = np.zeros((2, HEADS, DIM, DIM), np.float32), []
    for t in range(29):
        o, state = lightning_step(q[:, t], k[:, t], v[:, t], state, log_decay)
        outs.append(o)
    want = np.stack(outs, axis=1)
    with jax.default_matmul_precision("highest"):
        got, got_state = lightning_scan(q, k, v, jnp.zeros_like(state), log_decay, length=29, sub_chunk=8)
        first, mid = lightning_scan(q[:, :16], k[:, :16], v[:, :16], jnp.zeros_like(state), log_decay, sub_chunk=8)
        second, end = lightning_scan(q[:, 16:32], k[:, 16:32], v[:, 16:32], mid, log_decay, length=13, sub_chunk=8)
    assert rel_err(got[:, :29], want) <= 1e-5 and rel_err(got_state, state) <= 1e-5
    assert rel_err(np.concatenate([first, second], 1)[:, :29], want) <= 1e-5 and rel_err(end, state) <= 1e-5
    # the fastest head's decay over a chunk of 4,096 underflows to 0 and never overflows: nothing here forms lambda^(-C)
    far, _ = lightning_scan(*(np.tile(t, (1, 8, 1, 1)) for t in (q, k, v)), jnp.zeros_like(state), log_decay, sub_chunk=64)
    assert bool(np.isfinite(far).all())


def test_sparse_mode_equals_dense_mode_while_the_blocks_cover_the_context():
    """With `dense_len` 8 the block selects from position 7 on; while 6 blocks of 8
    cover all the query has seen (48 positions) the selection is everything and the two
    modes agree; beyond, positions are left out and the outputs part."""
    backend = make_backend("sparse", dense_len=8)
    dense = name_to_block["minicpm_sala_block"](HID, mixer="minicpm4", **{**COMMON, "dense_len": 10**6})
    x = stream(5, 1, 120)
    sparse_out = jax.jit(backend.module.apply)({"params": backend.params}, x)
    dense_out = jax.jit(dense.apply)({"params": backend.params}, x)
    error = np.abs(np.asarray(sparse_out - dense_out)).max(-1)[0] / float(np.abs(dense_out).max())
    assert error[:48].max() <= 2e-3, error[:48].max()
    assert error[64:].max() >= 4 * error[:48].max() and (error[64:] > 2e-3).mean() > 0.5


def test_selection_counts_and_forced_blocks():
    """`sparse_select` at position 100 of a 256-slot cache: block 0 and the two blocks
    that end at the query's own (window 16 = 2 blocks) are chosen whatever the scores,
    no block after the query's own is, and 6 are chosen in all; `sparse_attend` attends
    the positions s <= t of those blocks and says how many."""
    from hivemind_tpu.ops import block_sparse_attention as ops

    config = ops.SparseConfig(**SPARSE)
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((KV, HEADS // KV, DIM)), jnp.bfloat16)
    cache_k, cache_v = (jnp.asarray(rng.standard_normal((KV, MAX_LEN, DIM)), jnp.bfloat16) for _ in range(2))
    compressed = jax.jit(lambda into, keys: ops.write_compressed(into, keys, 0, MAX_LEN // 2, config))(
        jnp.zeros((KV, MAX_LEN // 2, DIM), jnp.bfloat16), cache_k)  # each op ONE program, not one an operation
    chosen, exists = jax.jit(lambda *args: ops.sparse_select(*args, config))(q, compressed, jnp.int32(100))
    chosen = np.asarray(chosen)
    assert chosen.shape == (KV, 6) and bool(np.asarray(exists).all())
    for head in range(KV):
        assert {0, 11, 12} <= set(chosen[head]) and chosen[head].max() == 12 and len(set(chosen[head])) == 6
    _context, attended = jax.jit(lambda *args: ops.sparse_attend(*args, config))(q, cache_k, cache_v, chosen, exists, jnp.int32(100))
    assert int(attended) == 5 * 8 + 5  # five whole blocks and positions 96..100 of the query's own
    # a kernel's compressed key is the mean of its positions
    assert rel_err(compressed[:, 7], cache_k[:, 14:18].astype(jnp.float32).mean(1)) <= 1e-2


OLDER_BLOCKS = {
    "causal_transformer": dict(num_heads=4),
    "llama_block": dict(num_heads=4, num_kv_heads=2),
    "olmoe_block": dict(num_heads=4, num_experts=4, experts_per_token=2, expert_inner=32),
    "exaone_moe_block": dict(num_heads=4, num_kv_heads=2, head_dim=16, window=8, ffn_inner=64),
}


def older_backend(name: str, uid: str, **overrides) -> ModuleBackend:
    return OneProgramBackend(uid, name_to_block[name](HID, **{**OLDER_BLOCKS[name], **overrides}), optimizer=optax.sgd(0.0),
                             sample_input=name_to_input[name](4, HID), max_batch_size=8, rng_seed=1)


@pytest.mark.parametrize("kind", ["sparse", "lightning", *sorted(OLDER_BLOCKS), "exaone_moe_block/full"])
def test_a_failed_step_leaves_no_half_updated_state(kind, monkeypatch):
    """A per-session step DONATES the cache tree: one that fails drops the session (the
    next continuation gets the unknown-session KeyError and re-prefills). A batched step
    donates too since ISSUE 50, whether it joins the rows' caches or steps on them where
    they lie (`decode_rows_apart`: the sparse block, and since ISSUE 42 the four blocks that
    keep ``max_len`` slots); one that raises BEFORE it took anything (the stand-in here
    raises at once, as a bad shape at tracing does) leaves every session's tree and position
    as they were. One that fails after it took them: `tests/test_decode_rows_apart.py`."""
    if kind in MIXERS:
        backend = make_backend(kind)
    else:
        backend = older_backend(kind.split("/")[0], "older.0", **(dict(window=0) if kind.endswith("/full") else {}))
    assert backend.module.decode_rows_apart == (kind not in ("lightning", "exaone_moe_block"))
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    x = stream(7, 2, 80)
    for row in range(2):
        manager.decode(backend.name, f"row{row}", x[row:row + 1, :70], reset=True)
    sessions = [manager._sessions[(backend.name, f"row{row}")] for row in range(2)]
    held = [jax.tree_util.tree_leaves(session.cache) for session in sessions]

    def broken(*_args, **_kwargs):
        raise RuntimeError("device fault")

    monkeypatch.setitem(manager._batched_fns, (backend.name, 2), broken)
    entries = [(None, session, x[row:row + 1, 70:71]) for row, session in enumerate(sessions)]
    with pytest.raises(RuntimeError):
        manager._decode_batch(backend.name, entries)
    for session, leaves in zip(sessions, held):
        assert session.index == 70 and all(a is b for a, b in zip(jax.tree_util.tree_leaves(session.cache), leaves))
        assert not any(leaf.is_deleted() for leaf in leaves)
    monkeypatch.delitem(manager._batched_fns, (backend.name, 2))
    assert not any(isinstance(out, Exception) for out in manager._decode_batch(backend.name, entries))  # and they step on

    monkeypatch.setitem(manager._step_fns, (backend.name, 1, 1), broken)
    with pytest.raises(RuntimeError):
        manager.decode(backend.name, "row0", x[:1, 71:72], reset=False)
    assert (backend.name, "row0") not in manager._sessions and (backend.name, "row1") in manager._sessions
    monkeypatch.delitem(manager._step_fns, (backend.name, 1, 1))
    with pytest.raises(KeyError):
        manager.decode(backend.name, "row0", x[:1, 71:72], reset=False)


@pytest.mark.parametrize("name", sorted(OLDER_BLOCKS))
def test_blocks_that_keep_a_pair_refuse_a_continuation_chunk_as_before(name):
    """The four blocks with a `(cache_k, cache_v)` pair: a prefill, a single step, then a
    chunk of more than one position raises the ValueError it always raised and leaves the
    session where it was; the session's tree is the pair."""
    backend = older_backend(name, "old.0")
    manager = DecodeSessionManager({"old.0": backend}, max_len=64)
    x = stream(8, 1, 24)
    manager.decode("old.0", "s", x[:, :10], reset=True)
    manager.decode("old.0", "s", x[:, 10:11], reset=False)
    with pytest.raises(ValueError, match="only 1-token steps may follow the prefill"):
        manager.decode("old.0", "s", x[:, 11:19], reset=False)
    session = manager._sessions[("old.0", "s")]
    assert session.index == 11 and session.cache == (session.cache_k, session.cache_v)
    manager.decode("old.0", "s", x[:, 11:12], reset=False)


def test_a_chain_takes_chunks_only_if_all_its_blocks_do():
    sala, old = make_backend("lightning", uid="mix.0"), older_backend("llama_block", "mix.1")
    manager = ManagerSharingPrograms({"mix.0": sala, "mix.1": old}, max_len=64)
    x = stream(9, 1, 24)
    before = counter("hivemind_moe_decode_prefill_chunks_total")
    manager._decode_direct(("mix.0", "mix.1"), "s", x[:, :8], reset=True)
    with pytest.raises(ValueError, match="does not take a prompt in chunks"):
        manager._decode_direct(("mix.0", "mix.1"), "s", x[:, 8:16], reset=False)
    assert [manager._sessions[(uid, "s")].index for uid in ("mix.0", "mix.1")] == [8, 8]  # refused before any block ran
    manager._decode_direct(("mix.0",), "t", x[:, :8], reset=True)
    manager._decode_direct(("mix.0",), "t", x[:, 8:16], reset=False)
    assert counter("hivemind_moe_decode_prefill_chunks_total") - before == 1
    with pytest.raises(ValueError, match="is full"):
        manager._decode_direct(("mix.0",), "t", stream(9, 1, 60), reset=False)


def test_a_chunk_near_the_end_of_the_cache_is_padded_to_fit():
    """A continuation chunk comes padded to a power of two, but never past the cache's
    end: a padded tail that did not fit would be written shifted back over real positions."""
    backend = make_backend("sparse")
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=128)
    x = stream(10, 1, 128)
    chunks = [manager.decode(backend.name, "s", x[:, :96], reset=True), manager.decode(backend.name, "s", x[:, 96:119], reset=False)]
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(119, 128)]
    want = reference_span([backend.params], x)
    assert positions_beyond(np.concatenate(chunks, axis=1), want, SERVED_TOL) <= 0.05
    assert (backend.name, 1, 32) in manager._step_fns  # 23 positions: 32 slots were left, so padded to 32


def test_gauges_counters_and_program_names_by_kind():
    """Two sessions on a sparse and a lightning block: the cache gauges by kind (bytes
    over entries = one session's tree), the attended / cached counters of the sparse
    block (dense mode: all it had seen; sparse mode: 6 blocks' positions up to the
    query), and the kinds in the programs' names."""
    backends = {"k.0": make_backend("sparse", uid="k.0"), "k.1": make_backend("lightning", uid="k.1")}
    manager = ManagerSharingPrograms(backends, max_len=MAX_LEN)
    manager.clear_sessions()
    x = stream(11, 1, 100)
    attended, cached = (counter(f"hivemind_moe_sparse_positions_{name}_total") for name in ("attended", "cached"))
    manager._decode_direct(("k.0", "k.1"), "a", x[:, :40], reset=True)  # 40 queries in the dense mode: 1 + 2 + ... + 40
    assert counter("hivemind_moe_sparse_positions_attended_total") - attended == 820
    assert counter("hivemind_moe_sparse_positions_cached_total") - cached == 820
    manager._decode_direct(("k.0", "k.1"), "a", x[:, 40:99], reset=False)
    attended, cached = (counter(f"hivemind_moe_sparse_positions_{name}_total") for name in ("attended", "cached"))
    manager._decode_direct(("k.0", "k.1"), "a", x[:, 99:100], reset=False)  # position 99: blocks 0..12, 6 chosen, 4 positions of its own
    assert counter("hivemind_moe_sparse_positions_attended_total") - attended == 5 * 8 + 4
    assert counter("hivemind_moe_sparse_positions_cached_total") - cached == 100
    manager._decode_direct(("k.0", "k.1"), "b", x[:, :40], reset=True)
    gauges = REGISTRY.snapshot()
    sizes = {kind: gauges["hivemind_moe_decode_cache_bytes"]["series"][f"kind={kind}"]
             / gauges["hivemind_moe_decode_cache_entries"]["series"][f"kind={kind}"] for kind in ("sparse", "lightning")}
    assert sizes == {"sparse": 2 * KV * MAX_LEN * DIM * 2 + KV * (MAX_LEN // 2) * DIM * 2, "lightning": HEADS * DIM * DIM * 4}
    assert gauges["hivemind_moe_decode_cache_entries"]["series"]["kind=sparse"] == 2
    assert manager._step_fn("k.0", 1, 64).jitted.__name__ == "prefill_sparse_64"
    assert manager._step_fn("k.1", 1, 1).jitted.__name__ == "step_lightning"
    assert manager._batched_fn("k.0", 2).jitted.__name__ == "batched_step_sparse"
    assert manager._batched_fn("k.1", 2).jitted.__name__ == "batched_step_lightning"
    manager.clear_sessions()


def test_parameter_counts_by_hand():
    """At the published widths, from shapes alone: a sparse block 253.8 M, a lightning
    block 285.2 M, the span of eight 2,218.8 M; a session's trees at 32,768 slots 34.6 MB
    and 2.1 MB."""
    from perf import manifest as mf
    from perf.runners import sala_block_server as runner

    config = mf.load_json(mf.PERF / "configs" / "minicpm-sala-span8.json")
    hidden, counts, caches = config["model"]["hidden_size"], [], []
    for index in range(config["model"]["num_hidden_layers"]):
        module = name_to_block["minicpm_sala_block"](hidden, **runner.block_kwargs(config, index))
        shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 4, hidden), jnp.float32))["params"]
        counts.append(sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)))
        cache = jax.eval_shape(lambda module=module: module.init_decode_cache(1, config["serving"]["decode_max_len"]))
        caches.append(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(cache)))
    mlp, norms = 3 * 4096 * 16384, 2 * 4096 + 2 * 128
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + mlp + norms
    lightning = 5 * 4096 * 4096 + mlp + norms + 4096
    assert counts == [sparse] + [lightning] * 6 + [sparse]
    assert [round(count / 1e6, 1) for count in (sparse, lightning, sum(counts))] == [253.8, 285.2, 2218.9]  # 2,218.8 M of matrices and 0.05 M of norm scales
    assert [round(size / 1e6, 2) for size in (caches[0], caches[1])] == [34.6, 2.1]
    assert round((2 * caches[0] + 6 * caches[1]) * 32 / 1e9, 2) == 2.62


def test_span_through_server_and_remote_sequential_and_failover_in_chunks(one_program_backends):
    """The rehearsal configuration's span (sparse, six lightning, sparse), built as the
    runner builds it: a client's prompt in chunks and single-token steps over the wire
    against the reference; then the client's failover path re-sends the retained history
    IN CHUNKS (no longer than the longest it sent), rebuilds the sessions and returns
    the same positions."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from perf import manifest as mf
    from perf.runners import sala_block_server as runner

    config = mf.rehearsal_config(mf.load_json(mf.PERF / "configs" / "minicpm-sala-span8.json"))
    config["serving"]["activation_compression"] = "none"
    hidden, blocks = config["model"]["hidden_size"], config["model"]["num_hidden_layers"]
    server_dht = DHT(start=True)
    server = runner.build_server(config, 5, server_dht, name_to_block["minicpm_sala_block"])
    client_dht = None
    try:
        client_dht = DHT(initial_peers=[str(m) for m in server_dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, config["serving"]["uid_prefix"], blocks)
        x = np.random.default_rng(21).standard_normal((1, 170, hidden)).astype(np.float32)
        chunks = [pipe.decode_step(x[:, start:min(start + 64, 150)], "e2e", reset=start == 0) for start in range(0, 150, 64)]
        chunks += [pipe.decode_step(x[:, t:t + 1], "e2e") for t in range(150, 170)]
        got = np.concatenate(chunks, axis=1)
        params = [server.backends[f"{config['serving']['uid_prefix']}{i}"].snapshot_params() for i in range(blocks)]
        want = jax.jit(functools.partial(reference.span, **runner.reference_sizes(config)))(params, x)
        assert positions_beyond(got, want, 2 * SERVED_TOL) <= 0.05  # eight blocks

        state = pipe._decode_routes["e2e"]
        assert state["chunked"] == 64 and state["positions"] == 170
        sent = counter("hivemind_moe_decode_prefill_chunks_total")
        resets = counter("hivemind_moe_decode_session_resets_total")
        again = pipe._decode_failover("e2e", state, np.concatenate(state["chunks"], axis=1))
        assert counter("hivemind_moe_decode_prefill_chunks_total") - sent == 2  # 170 = 64 + 64 + 42: two continuations
        assert counter("hivemind_moe_decode_session_resets_total") - resets == blocks  # ONE reset a block, not one a chunk
        assert rel_err(again, got) <= 2 * SERVED_TOL  # the same positions, cut into other chunks: bf16 rounds them otherwise
        np.testing.assert_allclose(pipe.decode_step(x[:, 169:170] * 0 + 1, "e2e").shape, (1, 1, hidden))
        pipe.close_decode_session("e2e")
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server_dht.shutdown()


def test_trainers_load_nothing_of_this_block():
    """A process that imports what `perf/runners/trainer.py` and
    `examples/albert/run_trainer.py` import (they load `moe.server.layers` for the
    optimizer helpers, and so the block registry) holds none of the modules this block's
    PR added: the block's own module loads when a block is BUILT, its mixers' code when
    one is first applied (PR 32's regression was ALBERT's `setup_s`, a cell whose process
    never runs a block); and building a block still loads no mixer code."""
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
assert 'minicpm_sala_block' in name_to_block
added = ('hivemind_tpu.moe.server.layers.minicpm_sala', 'hivemind_tpu.ops.linear_attention',
         'hivemind_tpu.ops.block_sparse_attention', 'perf.reference.minicpm_sala_block', 'perf.runners.sala_block_server',
         'perf.traffic.long_sessions', 'perf.flops_sala', 'perf.readers.scope_roofline', 'perf.readers.counter_ratio_lead')
held = [name for name in added if name in sys.modules]
assert not held, held
name_to_block['minicpm_sala_block'](64, mixer='minicpm4')
held = [name for name in added[1:] if name in sys.modules]
assert not held and added[0] in sys.modules, held
"""
    run = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert run.returncode == 0, run.stderr[-3000:]
