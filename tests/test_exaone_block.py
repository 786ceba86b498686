"""`exaone_moe_block` (K-EXAONE's decoder blocks: per-head qk-norm attention at a
given head size, a sliding window with rope or full attention without, a dense MLP
or a sparse layer that holds a SHARE of the experts beside a shared expert) against
the plain float32 reference `perf/reference/k_exaone_block.py`, on every serving
path: `ModuleBackend.forward`, `DecodeSessionManager.decode` (a prefill longer than
the window and no power of two, then single-token steps until the ring has wrapped
twice), the batched step with rows at different positions, and a five-block span of
all three kinds through `Server` + `RemoteSequential`. Small sizes, seeded weights.

Tolerances, as a share of the largest value of the reference's output: the served
arithmetic (bf16 activations, float32 accumulation, float32 router) against the
float32 reference reads 3e-3 to 1.2e-2 at these sizes, and `SERVED_TOL` is 2.5e-2; a window
off by one reads 1e-1 and more, as do the other wrong layers
(`test_reference_tells_a_wrong_layer_apart`). The bf16 rounding of the router's INPUT
can flip a near-tie against the reference's own forward, and at these toy sizes one
flipped expert carries a large part of a token's output: the input streams are seeds
on which the served blocks route as the reference does (`clean_stream`)."""

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
from hivemind_tpu.moe.server.routing_stats import record_routing  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.reference import k_exaone_block as reference  # noqa: E402
from perf.runners import hybrid_moe_block_server as runner  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402

HID, HEADS, KV, DIM, WINDOW, DENSE, INNER = 96, 4, 2, 16, 8, 160, 48
EXPERTS, TOP_K, HELD, LO, SCALE = 16, 4, 4, 4, 2.5
KINDS = {  # name -> (the block's own sizes, what the reference is told of the kind)
    "dense/window": (dict(window=WINDOW, ffn_inner=DENSE), dict(window=WINDOW, rope=True)),
    "sparse/window": (dict(window=WINDOW), dict(window=WINDOW, rope=True)),
    "sparse/full": (dict(window=0), dict(window=0, rope=False)),
    "dense/full": (dict(window=0, ffn_inner=DENSE), dict(window=0, rope=False)),  # in no span: the kinds are independent
}
SPAN = ["dense/window", "sparse/window", "sparse/window", "sparse/full", "sparse/window"]  # blocks 0-4: L L L G L
COMMON = dict(num_heads=HEADS, num_kv_heads=KV, head_dim=DIM, num_experts=EXPERTS, experts_per_token=TOP_K,
              expert_inner=INNER, held_lo=LO, held=HELD, routed_scale=SCALE)
SIZES = dict(num_heads=HEADS, num_kv_heads=KV, head_dim=DIM, experts_per_token=TOP_K, routed_scale=SCALE, held_lo=LO,
             rope_theta=1e6, rms_eps=1e-5)
SERVED_TOL = 2.5e-2
COUNTERS = ("hivemind_moe_expert_layer_calls_total", "hivemind_moe_routed_pairs_total", "hivemind_moe_held_pairs_total",
            "hivemind_moe_experts_hit_total", "hivemind_moe_expert_max_pairs_total")


@functools.cache  # read-only in every test (the optimizer's rate is 0): built once a process
def make_backend(kind: str, uid="exa.0", seed=3, **overrides) -> ModuleBackend:
    module = name_to_block["exaone_moe_block"](HID, **{**COMMON, **KINDS[kind][0], **overrides})
    return OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input["exaone_moe_block"](4, HID),
                             max_batch_size=8, rng_seed=seed)


@functools.cache
def reference_program(kinds: tuple, entry: str = "span_with_routing", **changed):
    """The reference over blocks of ``kinds`` as ONE program a shape, not one an operation."""
    layers = [KINDS[kind][1] for kind in kinds]
    return jax.jit(lambda params, x: getattr(reference, entry)(params, x, layers, **{**SIZES, **changed}))


def stream(seed: int, batch: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((batch, length, HID)).astype(np.float32)


def want_of(backends, kinds, x):
    return reference_program(tuple(kinds))([b.params for b in backends], x)


def routes_as_the_reference(backends, kinds, x) -> bool:
    routing = runner.program_routing([b.module for b in backends], [b.params for b in backends], jnp.asarray(x))
    _, want = want_of(backends, kinds, x)
    return all((np.sort(np.asarray(got), -1) == np.sort(np.asarray(ref), -1)).all()
               for (_, got), (_, ref) in zip(routing, want) if ref is not None)


def clean_stream(backends, kinds, seed: int, batch: int, length: int) -> np.ndarray:
    """The first stream from ``seed`` on (deterministic) on which the served blocks
    route as the reference does in its own forward: no near-tie that bf16 flips."""
    for candidate in range(seed, seed + 40):
        x = stream(candidate, batch, length)
        if routes_as_the_reference(backends, kinds, x):
            return x
    raise AssertionError("forty streams in a row hold a near-tie that bf16 flips: the router's input is off")


def positions_beyond(got, want, tolerance: float) -> float:
    """Share of positions whose largest difference is over ``tolerance`` of the
    reference's largest value. The decode paths round a little otherwise than the
    forward that `clean_stream` looked at, so a near-tie may still flip there: that
    moves ONE position by a whole expert, where a wrong cache slot, mask or rotary
    offset moves every position after it."""
    beyond = np.abs(np.asarray(got) - np.asarray(want)).max(-1) > tolerance * np.abs(np.asarray(want)).max()
    return float(beyond.mean())


def counters(path: str):
    return {name: REGISTRY.get(name).labels(path).value for name in COUNTERS}


def delta(before, after):
    return {name.replace("hivemind_moe_", "").replace("_total", ""): after[name] - before[name] for name in COUNTERS}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_forward_against_reference(kind):
    backend = make_backend(kind)
    x = clean_stream([backend], [kind], 0, 3, 29)  # longer than three windows, no multiple of one
    before = counters("pool")
    got = backend.forward(x)[0]
    want, [(_, chosen)] = want_of([backend], [kind], x)
    assert rel_err(got, want) <= SERVED_TOL
    counted = delta(before, counters("pool"))
    if kind.startswith("dense"):
        assert counted["expert_layer_calls"] == 0 and backend.module.held_experts is None
        return
    chosen = np.asarray(chosen).reshape(-1)
    held = chosen[(chosen >= LO) & (chosen < LO + HELD)]
    assert backend.module.held_experts == (LO, LO + HELD)
    assert counted["expert_layer_calls"] == 1 and counted["routed_pairs"] == 3 * 29 * TOP_K
    assert counted["held_pairs"] == held.size and 0 < held.size < chosen.size
    assert counted["experts_hit"] == np.unique(held).size <= HELD, "experts held elsewhere were counted as hit"
    assert counted["expert_max_pairs"] == np.bincount(held).max()


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("prompt", [21, 5])  # longer than twice the window and no power of two; shorter than the window
def test_prefill_and_single_token_steps_against_full_forward(kind, prompt):
    """The prefill is padded to a power of two (32, 8): the ring must take the last
    8 REAL positions, not the padding. 20 steps after 21 wrap the ring twice more."""
    backend = make_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=64)
    x = clean_stream([backend], [kind], 4, 1, prompt + 20)
    chunks = [manager.decode(backend.name, "s", x[:, :prompt], reset=True)]
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(prompt, prompt + 20)]
    assert rel_err(np.concatenate(chunks, axis=1), want_of([backend], [kind], x)[0]) <= SERVED_TOL
    session = manager._sessions[(backend.name, "s")]
    slots = WINDOW if "window" in kind else 64
    assert session.cache_k.shape == session.cache_v.shape == (1, KV, slots, DIM) and session.index == prompt + 20


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_batched_step_with_rows_at_different_positions(kind):
    """7 sessions at different positions (some rings wrapped, some not yet full) in a
    bucket of 8, twenty batched steps: each row against the reference's full forward
    over that row's own stream; the padding row is part of the program, not of the counts.
    A full-attention block steps on the rows' own caches (`decode_rows_apart`: 64 slots
    here, 33.5 MB a session in the cell), a window block on its rings joined."""
    from hivemind_tpu.telemetry.tracing import RECORDER

    rows_counted = lambda caches: REGISTRY.get("hivemind_moe_decode_batched_rows_total").labels(caches).value
    caches, other = ("joined", "apart") if "window" in kind else ("apart", "joined")

    backend = make_backend(kind)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=64)
    lengths = [3, 21, 8, 13, 11, 6, 9]
    x = clean_stream([backend], [kind], 50, len(lengths), 41)
    for row, length in enumerate(lengths):
        manager.decode(backend.name, f"row{row}", x[row:row + 1, :length], reset=True)
    sessions = [manager._sessions[(backend.name, f"row{row}")] for row in range(len(lengths))]
    want = np.asarray(want_of([backend], [kind], x)[0])
    before, rows_before = counters("batched"), (rows_counted(caches), rows_counted(other))
    got = [[] for _ in lengths]
    for step in range(20):
        entries = [(None, session, x[row:row + 1, length + step:length + step + 1])
                   for row, (session, length) in enumerate(zip(sessions, lengths))]
        for row, out in enumerate(manager._decode_batch(backend.name, entries)):
            assert not isinstance(out, Exception), out
            got[row].append(out)
    got = np.concatenate([np.concatenate(outs, axis=1) for outs in got])
    steps = np.stack([want[row, length:length + 20] for row, length in enumerate(lengths)])
    assert positions_beyond(got, steps, SERVED_TOL) <= 0.03, "more than a flipped near-tie or four in 140 steps"
    counted = delta(before, counters("batched"))
    sparse = kind.startswith("sparse")
    assert counted["expert_layer_calls"] == 20 * sparse and counted["routed_pairs"] == 20 * 7 * TOP_K * sparse
    assert [key for key in manager._batched_fns] == [(backend.name, 8)]  # the uid's view onto its kind's program
    assert manager._batched_fns[(backend.name, 8)] is manager._programs[(manager._kind(backend.name), "batched", 8)]
    [span] = [s for s in RECORDER.snapshot() if s.name == "decode.batch" and (s.attributes or {}).get("uid") == backend.name][-1:]
    assert span.attributes["cache"] == kind.split("/")[1] and span.attributes["rows"] == 7
    assert backend.module.decode_rows_apart == (caches == "apart") and span.attributes["caches"] == caches
    assert (rows_counted(caches), rows_counted(other)) == (rows_before[0] + 20 * 7, rows_before[1]), "live rows, by the block's own word"
    assert all(leaf.shape == (1, KV, WINDOW if "window" in kind else 64, DIM) for session in sessions for leaf in session.leaves)
    if sparse:
        assert 0 < counted["held_pairs"] < counted["routed_pairs"]
        assert span.attributes["pairs"] == 7 * TOP_K and 0 <= span.attributes["held_pairs"] <= span.attributes["pairs"]


def test_programs_and_cache_gauges_carry_the_kind():
    """Two cache shapes in one manager: each block's programs are named by its kind, and
    the table's bytes and entries are told apart by it."""
    backends = {f"exa.{i}": make_backend(kind, uid=f"exa.{i}", seed=i) for i, kind in enumerate(["sparse/window", "sparse/full"])}
    manager = ManagerSharingPrograms(backends, max_len=64)
    x = stream(6, 1, 12)
    for session in ("a", "b"):
        for uid in backends:
            manager.decode(uid, session, x, reset=True)
    level = lambda name, kind: REGISTRY.get(name).labels(kind).value
    ring, full = 2 * KV * WINDOW * DIM * 2, 2 * KV * 64 * DIM * 2  # keys and values, bf16
    assert level("hivemind_moe_decode_cache_bytes", "window") == 2 * ring and level("hivemind_moe_decode_cache_entries", "window") == 2
    assert level("hivemind_moe_decode_cache_bytes", "full") == 2 * full and level("hivemind_moe_decode_cache_entries", "full") == 2
    assert manager._step_fn("exa.0", 1, 16).jitted.__name__ == "prefill_window_16"
    assert manager._step_fn("exa.1", 1, 1).jitted.__name__ == "step_full"
    assert manager._batched_fn("exa.0", 4).jitted.__name__ == "batched_step_window"
    manager.decode("exa.1", "a", x, reset=True)  # a session opened again replaces its entry
    assert level("hivemind_moe_decode_cache_entries", "full") == 2 and level("hivemind_moe_decode_cache_bytes", "full") == 2 * full
    manager.clear_sessions()  # as the benchmark does after its check: the table and its gauges start anew
    assert level("hivemind_moe_decode_cache_bytes", "window") == level("hivemind_moe_decode_cache_entries", "full") == 0
    manager.decode("exa.0", "c", x, reset=True)
    assert level("hivemind_moe_decode_cache_bytes", "window") == ring and level("hivemind_moe_decode_cache_entries", "full") == 0
    manager.session_ttl = 0.0
    with manager._lock:
        manager._evict_locked()
    assert level("hivemind_moe_decode_cache_bytes", "window") == level("hivemind_moe_decode_cache_entries", "window") == 0


@pytest.mark.parametrize("block_cls, name", [("llama_block", "step"), ("olmoe_block", "step")])
def test_other_blocks_programs_keep_their_names(block_cls, name):
    """`llama_block` and `olmoe_block` name no kind: their decode programs are `step`
    and `batched_step` as before this block came, and they are handed no length."""
    module = name_to_block[block_cls](HID, num_heads=HEADS)
    backend = OneProgramBackend("other.0", module, optimizer=optax.sgd(0.0), sample_input=name_to_input[block_cls](4, HID), max_batch_size=8)
    manager = DecodeSessionManager({"other.0": backend}, max_len=32)
    assert manager._step_fn("other.0", 1, 16).jitted.__name__ == manager._step_fn("other.0", 1, 1).jitted.__name__ == name
    assert manager._batched_fn("other.0", 4).jitted.__name__ == "batched_step"
    assert not manager._takes_length("other.0") and manager._cache_kind("other.0") == "full"


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide: one sparse block's uncut reference
    (all 16 experts) against 4 shares of 4 experts each. A share's output is residual +
    attention + shared expert + ITS experts' part; with the common part counted once,
    the shares' routed parts add up to the uncut layer. Both from the program's block
    (each share a block of its own, fed the uncut block's weights for its experts)
    and from the reference told each share."""
    whole = make_backend("sparse/window", held=EXPERTS, held_lo=0)
    params = jax.tree_util.tree_map(np.asarray, whole.params)  # a share is cut in numpy: no program a slice
    x = stream(7, 2, 19)
    kinds = ("sparse/window",)
    uncut = np.asarray(reference_program(kinds, "span", held_lo=0)([params], x))
    common = np.asarray(reference_program(kinds, "span", held_lo=0)(
        [{**params, **{f"experts_{n}": params[f"experts_{n}"][:0] for n in ("gate", "up", "down")}}], x))  # no expert held: all but the routed part
    routed_by_reference, routed_by_program = 0.0, 0.0
    for lo in range(0, EXPERTS, HELD):
        share = {**params, **{f"experts_{n}": params[f"experts_{n}"][lo:lo + HELD] for n in ("gate", "up", "down")}}
        routed_by_reference += np.asarray(reference_program(kinds, "span", held_lo=lo)([share], x)) - common
        module = name_to_block["exaone_moe_block"](HID, **{**COMMON, **KINDS["sparse/window"][0], "held_lo": lo})
        routed_by_program += np.asarray(jax.jit(module.apply)({"params": share}, x), np.float32) - common
    assert rel_err(common + routed_by_reference, uncut) <= 1e-5
    assert rel_err(common + routed_by_program, uncut) <= 4 * SERVED_TOL  # four shares' rounding, each against the float32 common part
    assert np.abs(routed_by_reference).max() > 0.1 * np.abs(uncut).max(), "the routed part is too small to tell"


def test_router_against_hand_made_scores():
    """Scores by hand, margins wide of any rounding: the bias picks (expert 3 enters
    by its bias, expert 0 leaves) and does not weigh (3's weight is from its bare
    score); the picked scores are renormalised and scaled by 2.5."""
    from hivemind_tpu.ops.sparse_experts import route_sigmoid_top_k

    logits = np.array([[2.0, 1.0, 0.0, -1.0, -3.0, -3.0]], np.float32)  # sigmoid: .881 .731 .5 .269 .047 .047
    bias = np.array([-0.5, 0.0, 0.0, 0.4, 0.0, 0.0], np.float32)  # biased: .381 .731 .5 .669 .047 .047 -> picks 1, 3, 2
    tokens, router = jnp.asarray([[1.0, 0, 0, 0, 0, 0]]), jnp.zeros((6, 6)).at[0].set(logits[0])  # tokens @ router = logits
    weights, top_e = route_sigmoid_top_k(tokens, router, jnp.asarray(bias), 3, 2.5)
    assert top_e.tolist() == [[1, 3, 2]]
    picked = 1 / (1 + np.exp(-logits[0, [1, 3, 2]]))
    np.testing.assert_allclose(weights[0], 2.5 * picked / picked.sum(), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(), 2.5, rtol=1e-6)
    dense, ref_e = reference.route({"router": router, "router_bias": jnp.asarray(bias)}, tokens, 3, 2.5)
    assert ref_e.tolist() == [[1, 3, 2]]
    np.testing.assert_allclose(np.asarray(dense)[0, [1, 3, 2]], weights[0], rtol=1e-6)
    assert float(dense[0, 0]) == 0.0, "the expert with the largest score was not picked: its bias took it out"


@pytest.mark.parametrize("held, want", [(None, dict(held_pairs=12, experts_hit=5, expert_max_pairs=4)),
                                        ((4, 8), dict(held_pairs=7, experts_hit=2, expert_max_pairs=4))])
def test_record_routing_with_and_without_a_held_range(held, want):
    """12 pairs on experts {1, 4 x4, 5 x3, 9, 9, 11 x2}: without a range every pair is
    computed here and every expert counts; with [4, 8) held, 7 pairs on 2 experts."""
    chosen = np.array([1, 4, 4, 4, 4, 5, 5, 5, 9, 9, 11, 11], np.int32).reshape(3, 1, 4)
    before = counters("direct")
    record_routing({"expert_choice": (chosen,)}, "direct", held=held)
    counted = delta(before, counters("direct"))
    assert counted["expert_layer_calls"] == 1 and counted["routed_pairs"] == 12
    assert {key: counted[key] for key in want} == want


@functools.lru_cache(maxsize=None)
def _span_params():
    """The five blocks' parameters, drawn once for the nine wrong layers."""
    return [make_backend(kind, uid=f"exa.{i}", seed=10 + i).params for i, kind in enumerate(SPAN)]


@pytest.mark.parametrize("fault", ["window_short", "window_long", "window_ignored", "rope_on_full", "bias_weighs",
                                   "not_renormalised", "scale_left_out", "shared_left_out", "absent_not_left_out"])
def test_reference_tells_a_wrong_layer_apart(fault):
    """What the limits must refuse, computed with the reference itself over the
    three-kind span, and held against the served tolerance."""
    params, x = _span_params(), stream(8, 2, 24)
    layers = [KINDS[kind][1] for kind in SPAN]
    [variant] = [v for name, (v, _) in runner.wrong_references(layers).items() if {
        "window_short": "one short", "window_long": "one long", "window_ignored": "window ignored", "rope_on_full": "rope on",
        "bias_weighs": "bias used", "not_renormalised": "not renormalised", "scale_left_out": "scale 2.5",
        "shared_left_out": "shared expert", "absent_not_left_out": "not left out"}[fault] in name]
    want, _ = runner.reference_span(params, jnp.asarray(x), layers, SIZES)
    got, _ = runner.reference_span(params, jnp.asarray(x), sizes=SIZES, **{"layers": layers, **variant})
    assert rel_err(got, want) > 2 * SERVED_TOL
    plain, _ = reference_program(tuple(SPAN))(params, x)
    assert rel_err(want, plain) <= 1e-5, "the runner's block-by-block reference is not the reference's span"


@pytest.mark.parametrize("window, told_apart", [(WINDOW, False), (WINDOW - 1, True), (WINDOW + 1, True)])
def test_departure_share_finds_a_program_with_the_window_off_by_one(window, told_apart):
    """The check's sharpest measure on a program that is really wrong: a block served
    with a window of 7 or 9 holds nearly all of the matching wrong reference's
    departure (and the rounding noise does not hide it), the right block nearly none."""
    backend = make_backend("sparse/window", window=window)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=64)
    x = stream(9, 1, 40)
    chunks = [manager.decode(backend.name, "s", x[:, :19], reset=True)]
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(19, 40)]
    got = np.concatenate(chunks, axis=1)
    layer = KINDS["sparse/window"][1]
    want, _ = runner.reference_span([backend.params], jnp.asarray(x), [layer], SIZES)
    for wrong_window in (WINDOW - 1, WINDOW + 1):
        wrong, _ = runner.reference_span([backend.params], jnp.asarray(x), [dict(layer, window=wrong_window)], SIZES)
        share = runner._departure_share([(got, want, wrong)])
        if told_apart and wrong_window == window:
            assert share > 0.8, share
        elif not told_apart:
            assert abs(share) < 0.1, share


def test_parameter_counts_by_hand():
    """At the published widths, from shapes alone (`jax.eval_shape`): attention 113.2 M,
    block 0 453.0 M, a sparse block with 8 held experts 453.8 M, the span of five 2,268 M."""
    from perf import manifest as mf

    config = mf.load_json(mf.PERF / "configs" / "k-exaone-236b-span5.json")
    hidden = config["model"]["hidden_size"]
    counts = []
    for index in range(config["model"]["num_hidden_layers"]):
        module = name_to_block["exaone_moe_block"](hidden, **runner.block_kwargs(config, index))
        shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 4, hidden), jnp.float32))["params"]
        counts.append(sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)))
    attention = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    norms = 2 * 6144 + 2 * 128
    assert attention == 113_246_208
    assert counts[0] == attention + 3 * 6144 * 18432 + norms  # 453.0 M
    assert counts[1] == attention + 9 * 3 * 6144 * 2048 + 6144 * 128 + 128 + norms  # 8 held + 1 shared, router, bias: 453.8 M
    assert counts[1:] == [counts[1]] * 4
    assert [round(count / 1e6, 1) for count in (attention, counts[0], counts[1], sum(counts))] == [113.2, 453.0, 453.8, 2268.1]


def test_five_block_span_through_server_and_remote_sequential(one_program_backends):
    """The rehearsal configuration's span (dense/window, sparse/window x2, sparse/full,
    sparse/window), built as the runner builds it, a client's prefill and single-token
    steps over the wire against the reference with the same held share."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from perf import manifest as mf

    config = mf.rehearsal_config(mf.load_json(mf.PERF / "configs" / "k-exaone-236b-span5.json"))
    config["serving"]["activation_compression"] = "none"
    hidden, blocks = config["model"]["hidden_size"], config["model"]["num_hidden_layers"]
    server_dht = DHT(start=True)
    server = runner.build_server(config, 5, server_dht, name_to_block["exaone_moe_block"])
    client_dht = None
    try:
        client_dht = DHT(initial_peers=[str(m) for m in server_dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, config["serving"]["uid_prefix"], blocks)
        x = np.random.default_rng(21).standard_normal((1, 38, hidden)).astype(np.float32)
        before = counters("direct")
        chunks = [pipe.decode_step(x[:, :21], "e2e", reset=True)]
        chunks += [pipe.decode_step(x[:, t:t + 1], "e2e") for t in range(21, 38)]
        pipe.close_decode_session("e2e")
        params = [server.backends[f"{config['serving']['uid_prefix']}{i}"].snapshot_params() for i in range(blocks)]
        want, routing = jax.jit(lambda params, x: reference.span_with_routing(
            params, x, runner.reference_layers(config), **runner.reference_sizes(config)))(params, x)
        assert [top_e is None for _, top_e in routing] == [True, False, False, False, False]
        assert positions_beyond(np.concatenate(chunks, axis=1), want, 2 * SERVED_TOL) <= 0.1  # five blocks; a flip moves a position
        counted = delta(before, counters("direct"))
        assert counted["routed_pairs"] == 4 * 38 * config["model"]["num_experts_per_tok"]
        assert 0 < counted["held_pairs"] < counted["routed_pairs"]
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server_dht.shutdown()


def test_trainers_load_nothing_of_this_block():
    """A process that imports what `perf/runners/trainer.py` and
    `examples/albert/run_trainer.py` import (they load `moe.server.layers` for the
    optimizer helpers, and so the block registry) holds none of the modules this
    block's PR added, and registering the block imported nothing: its expert layer
    and its attention kernels load when a block is first applied (PR 32's regression
    was ALBERT's `setup_s`, a cell whose process never runs a block)."""
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
assert 'exaone_moe_block' in name_to_block and 'olmoe_block' in name_to_block
added = ('perf.reference.k_exaone_block', 'perf.runners.hybrid_moe_block_server', 'perf.readers.gauge_ratio',
         'perf.readers.program_ms', 'perf.readers.moe_roofline_held')
lazy = ('hivemind_tpu.ops.sparse_experts', 'hivemind_tpu.ops.pallas_attention')
held = [name for name in added + lazy if name in sys.modules]
assert not held, held
"""
    run = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert run.returncode == 0, run.stderr[-3000:]
