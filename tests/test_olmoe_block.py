"""`olmoe_block` (OLMoE's decoder block: qk-norm attention + a sparse expert layer)
against the plain float32 reference `perf/reference/olmoe_block.py`, on every
serving path: `ModuleBackend.forward` / `backward`, `DecodeSessionManager.decode`
(prefill and single-token steps), the batched step with a padded bucket, and
`Server` + `RemoteSequential.decode_step` end to end. Small sizes, seeded weights.

Tolerances, as a share of the largest value of the reference's output:
- served arithmetic (bf16 activations, float32 accumulation, float32 router) against
  the float32 reference: 2e-2 for one or two blocks. bf16 rounding gives 3e-3 to
  9e-3 at these sizes; a renormalised top-k, a dropped expert or a wrong rotary
  offset in the cache gives 1e-1 and more (`test_reference_tells_a_wrong_layer_apart`).
- the router is float32: handed the block's own router inputs (its ffn norm's
  output, which is bf16), the reference's router chooses exactly the block's experts
  (`test_router_is_float32_on_the_blocks_own_inputs`), and `route_top_k` on float32
  inputs chooses exactly the reference's.
- the bf16 rounding of the router's INPUT can flip a near-tie against the float32
  reference's own forward, and at these toy sizes one flipped expert carries a
  quarter of a token's expert output: the input streams are seeds on which the
  served block routes as the reference does (`routes_as_the_reference`, asserted
  where a flip would decide the test)."""

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
from hivemind_tpu.moe.server.layers.common import ROUTING_COLLECTION  # noqa: E402
from hivemind_tpu.moe.server.module_backend import ModuleBackend  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.reference import olmoe_block as reference  # noqa: E402
from perf.runners.moe_block_server import _program_routing, _router_mismatch_share  # noqa: E402
from perf.runtime import rel_err  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend, wait_for_experts  # noqa: E402

HID, HEADS, EXPERTS, TOP_K, INNER = 128, 4, 8, 2, 64
KWARGS = dict(num_heads=HEADS, num_experts=EXPERTS, experts_per_token=TOP_K, expert_inner=INNER)
SIZES = dict(num_heads=HEADS, num_kv_heads=HEADS, experts_per_token=TOP_K, rope_theta=10000.0, rms_eps=1e-5)
SERVED_TOL = 2e-2
COUNTERS = ("hivemind_moe_expert_layer_calls_total", "hivemind_moe_routed_pairs_total",
            "hivemind_moe_experts_hit_total", "hivemind_moe_expert_max_pairs_total")


def fresh_backend(uid="olmoe.0", seed=3, **overrides) -> ModuleBackend:
    module = name_to_block["olmoe_block"](HID, **{**KWARGS, **overrides})
    return OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input["olmoe_block"](4, HID),
                             max_batch_size=8, rng_seed=seed)


make_backend = functools.cache(fresh_backend)  # for the tests that only read it: built once a process


@functools.cache
def reference_program(entry: str = "span", **changed):
    """The reference's ``entry`` as ONE program a shape, not one an operation."""
    return jax.jit(functools.partial(getattr(reference, entry), **{**SIZES, **changed}))


@functools.cache
def sown_routing(module):
    return jax.jit(functools.partial(module.apply, mutable=[ROUTING_COLLECTION]))


def stream(seed: int, batch: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((batch, length, HID)).astype(np.float32)


def chosen_by(backend, x) -> np.ndarray:
    _, sown = sown_routing(backend.module)({"params": backend.params}, x)
    [chosen] = jax.tree_util.tree_leaves(sown)
    return np.asarray(chosen)


def routes_as_the_reference(backend, x) -> bool:
    _, [(_, want)] = reference_program("span_with_routing")([backend.params], x)
    return bool((np.sort(chosen_by(backend, x), -1) == np.sort(np.asarray(want), -1)).all())


def counters(path: str):
    return {name: REGISTRY.get(name).labels(path).value for name in COUNTERS}


def delta(before, after):
    return {name.replace("hivemind_moe_", "").replace("_total", ""): after[name] - before[name] for name in COUNTERS}


def test_forward_against_reference():
    backend = make_backend()
    x = stream(0, 3, 20)
    before = counters("pool")
    got = backend.forward(x)[0]
    assert rel_err(got, reference_program()([backend.params], x)) <= SERVED_TOL
    counted = delta(before, counters("pool"))
    # 3 rows padded to the bucket of 4: the padding row is computed and not counted
    assert counted["expert_layer_calls"] == 1 and counted["routed_pairs"] == 3 * 20 * TOP_K
    per_expert = np.bincount(chosen_by(backend, x).reshape(-1), minlength=EXPERTS)  # the 3 live rows' own choices
    assert counted["experts_hit"] == np.count_nonzero(per_expert) and counted["expert_max_pairs"] == per_expert.max()


@pytest.mark.parametrize("seed", [1, 2, 11])  # 2 and 11 hold a near-tie that the bf16 rounding of the input flips
def test_router_is_float32_on_the_blocks_own_inputs(seed):
    """Teacher-forced, as the benchmark's check holds it: the reference's float32
    router on the block's own ffn-norm output chooses the block's experts exactly."""
    backend = make_backend()
    routing = _program_routing(backend.module, [backend.params], jnp.asarray(stream(seed, 2, 16)))
    [(m, top_e)] = routing
    assert m.dtype == jnp.bfloat16 and top_e.shape == (2, 16, TOP_K)
    assert _router_mismatch_share(reference, [backend.params], routing, TOP_K) == 0.0


@pytest.mark.parametrize("seed", [1, 2, 11])
def test_route_top_k_on_float32_inputs_equals_the_references_exactly(seed):
    from hivemind_tpu.ops.sparse_experts import route_top_k

    backend = make_backend()
    m = jnp.asarray(stream(seed, 2, 16)).reshape(-1, HID)
    top_p, top_e = route_top_k(m, backend.params["router"], TOP_K)
    weights, want = reference.route(backend.params, m, TOP_K)
    assert (np.asarray(top_e) == np.asarray(want)).all()
    np.testing.assert_allclose(top_p, np.take_along_axis(np.asarray(weights), np.asarray(want), -1), rtol=1e-6)
    assert float(top_p.sum(-1).max()) < 1.0  # used as they are, not renormalised


def test_input_gradient_through_module_backend():
    backend = fresh_backend()  # its update is counted
    x, grad = stream(3, 2, 16), stream(13, 2, 16)
    assert routes_as_the_reference(backend, x), "this stream holds a near-tie that bf16 flips: take another seed"
    before = counters("pool")
    got = backend.backward(x, grad)[0]
    _, want = reference_program("span_input_grad")([backend.params], x, grad)
    assert rel_err(got, want) <= 2 * SERVED_TOL  # a gradient passes every rounding twice
    assert delta(before, counters("pool"))["routed_pairs"] == 2 * 16 * TOP_K
    assert backend.update_count == 1


def test_span_chain_counts_every_blocks_routing_once():
    """A chain of two sparse blocks in one walk (ISSUE 30): the `path=pool` counters see
    every expert-layer call of the walk, live rows only, and the values are the
    per-block calls' own."""
    from hivemind_tpu.moe.server.module_backend import backward_chain, forward_chain

    chain = [fresh_backend("olmoe.0", seed=3), fresh_backend("olmoe.1", seed=4)]  # their updates are counted
    x, grad = stream(3, 3, 16), stream(13, 3, 16)
    before = counters("pool")
    [got] = forward_chain(chain, x)
    counted = delta(before, counters("pool"))
    assert counted["expert_layer_calls"] == 2 and counted["routed_pairs"] == 2 * 3 * 16 * TOP_K
    assert np.array_equal(got, chain[1].forward(chain[0].forward(x)[0])[0])
    before = counters("pool")
    [grad_x] = backward_chain(chain, x, grad)
    counted = delta(before, counters("pool"))  # one forward sweep over the first block, two backwards
    assert counted["expert_layer_calls"] == 3 and counted["routed_pairs"] == 3 * 3 * 16 * TOP_K
    assert grad_x.shape == x.shape and [backend.update_count for backend in chain] == [1, 1]


def test_prefill_and_single_token_steps_against_full_forward():
    backend = make_backend()
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=32)
    x = stream(4, 1, 20)
    before = counters("direct")
    chunks = [manager.decode(backend.name, "s", x[:, :11], reset=True)]  # 11 pads to 16
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(11, 20)]
    want = reference_program()([backend.params], x)
    assert rel_err(np.concatenate(chunks, axis=1), want) <= SERVED_TOL
    counted = delta(before, counters("direct"))
    assert counted["expert_layer_calls"] == 10
    assert counted["routed_pairs"] == 20 * TOP_K, "the prefill's padded positions were counted"


def _prefilled_rows(manager, uid, x, lengths):
    for row, length in enumerate(lengths):
        manager.decode(uid, f"row{row}", x[row:row + 1, :length], reset=True)
    return [manager._sessions[(uid, f"row{row}")] for row in range(len(lengths))]


def _rows_by_caches():
    rows = REGISTRY.get("hivemind_moe_decode_batched_rows_total")
    return rows.labels("apart").value, rows.labels("joined").value


@pytest.mark.parametrize("kv_heads", [HEADS, 2])  # as many key-value heads as heads (OLMoE-1B-7B), and grouped
def test_batched_step_pads_a_bucket_and_matches_each_rows_full_forward(kv_heads):
    """7 sessions at different positions in a bucket of 8, two batched steps: each
    row against the reference's full forward over that row's own stream; the
    padding row is part of the program, not of the counts. The rows' caches are
    stepped where they lie (`decode_rows_apart`): counted as such, and each session
    keeps arrays of its own."""
    from hivemind_tpu.telemetry.tracing import RECORDER

    backend = make_backend(num_kv_heads=kv_heads)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=32)
    lengths = [3, 5, 8, 4, 11, 6, 9]
    x = stream(5, len(lengths), 16)
    sessions = _prefilled_rows(manager, backend.name, x, lengths)
    want = np.asarray(reference_program(num_kv_heads=kv_heads)([backend.params], x))
    before, rows_before = counters("batched"), _rows_by_caches()
    for step in range(2):
        entries = [(None, session, x[row:row + 1, length + step:length + step + 1])
                   for row, (session, length) in enumerate(zip(sessions, lengths))]
        results = manager._decode_batch(backend.name, entries)
        for row, (out, length) in enumerate(zip(results, lengths)):
            assert not isinstance(out, Exception), out
            assert out.shape == (1, 1, HID)
            assert rel_err(out, want[row:row + 1, length + step:length + step + 1]) <= SERVED_TOL * (
                np.abs(want).max() / np.abs(want[row, length + step]).max())
    counted = delta(before, counters("batched"))
    assert counted["expert_layer_calls"] == 2
    assert counted["routed_pairs"] == 2 * 7 * TOP_K, "pairs are live rows x top-k: the padding row is not counted"
    assert TOP_K <= counted["experts_hit"] <= 2 * min(EXPERTS, 7 * TOP_K)
    assert all(session.index == length + 2 and session.cache_k.shape == (1, kv_heads, 32, HID // HEADS)
               for session, length in zip(sessions, lengths))
    assert len({id(leaf) for session in sessions for leaf in session.leaves}) == 2 * 7, "the padding row's arrays came back as a session's"
    assert _rows_by_caches() == (rows_before[0] + 2 * 7, rows_before[1]), "live rows of programs that left the caches apart"
    [key] = [k for k in manager._batched_fns]
    assert key == (backend.name, 8), "the uid's view holds the bucket alone"
    assert manager._batched_fns[key] is manager._programs[(manager._kind(backend.name), "batched", 8)], "its program is its kind's"
    spans = [s for s in RECORDER.snapshot() if s.name == "decode.batch" and (s.attributes or {}).get("uid") == backend.name]
    assert spans and spans[-1].attributes["pairs"] == 7 * TOP_K and 1 <= spans[-1].attributes["experts_hit"] <= EXPERTS
    assert spans[-1].attributes["caches"] == "apart" and spans[-1].attributes["bucket"] == 8


@pytest.mark.parametrize("kv_heads", [HEADS, 2])
def test_batched_step_equals_the_direct_step(kv_heads):
    """The same tokens through the batched program and through the per-session
    program: one block code, so the outputs agree to rounding."""
    backend = make_backend(num_kv_heads=kv_heads)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=32)
    lengths = [4, 7, 5]
    x = stream(6, 3, 12)
    sessions = _prefilled_rows(manager, backend.name, x, lengths)
    twins = ManagerSharingPrograms({backend.name: backend}, max_len=32)
    _prefilled_rows(twins, backend.name, x, lengths)
    results = manager._decode_batch(
        backend.name, [(None, session, x[row:row + 1, length:length + 1]) for row, (session, length) in enumerate(zip(sessions, lengths))])
    for row, (out, length) in enumerate(zip(results, lengths)):
        want = twins.decode(backend.name, f"row{row}", x[row:row + 1, length:length + 1], reset=False)
        np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)


def test_expert_work_follows_the_routed_pairs():
    """The layer's matmul rows are tokens x top-k (the sorted pairs), not tokens x
    experts: the jaxpr's ragged dots take [pairs, ...] operands and there are three."""
    from hivemind_tpu.ops.sparse_experts import route_top_k, routed_swiglu

    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.standard_normal((10, HID)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((HID, EXPERTS)), jnp.float32)
    weights = [jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
               for shape in ((EXPERTS, HID, INNER), (EXPERTS, HID, INNER), (EXPERTS, INNER, HID))]

    def layer(tokens):
        top_p, top_e = route_top_k(tokens, router, TOP_K)
        return routed_swiglu(tokens, top_p, top_e, *weights)

    ragged = [eqn for eqn in jax.make_jaxpr(layer)(tokens).jaxpr.eqns if "ragged_dot" in eqn.primitive.name]
    assert len(ragged) == 3
    assert all(eqn.invars[0].aval.shape[0] == 10 * TOP_K for eqn in ragged)


@pytest.mark.parametrize("fault", ["renormalised", "dropped_expert", "all_bf16"])
def test_reference_tells_a_wrong_layer_apart(fault):
    """What the tolerances must refuse, computed with the reference itself: top-k
    weights renormalised and the weakest chosen expert dropped (over the served
    tolerance), and the whole block in bf16 (far over float32's rounding, 2e-5)."""
    backend = make_backend()
    x = jnp.asarray(stream(8, 2, 24))
    want = np.asarray(reference_program()([backend.params], x))
    params = backend.params
    if fault == "all_bf16":
        got = reference_program("block")(jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.bfloat16), params), x.astype(jnp.bfloat16))
        assert rel_err(got, want) > 50 * 2e-5
        return
    def wrong_route(params, m, experts_per_token):
        weights, top_e = reference.route(params, m, experts_per_token)
        if fault == "renormalised":
            return weights / weights.sum(-1, keepdims=True), top_e
        weakest = jnp.where(weights > 0, weights, jnp.inf).min(-1, keepdims=True)
        return jnp.where(weights == weakest, 0.0, weights), top_e

    got = jax.jit(functools.partial(reference.span, route=wrong_route, **SIZES))([params], x)
    assert rel_err(got, want) > SERVED_TOL


@pytest.mark.parametrize("block_cls, kwargs", [("olmoe_block", KWARGS), ("llama_block", dict(num_heads=HEADS))])
def test_a_head_size_that_is_not_hidden_over_heads_fails_loudly(block_cls, kwargs):
    x = jnp.zeros((1, 4, HID), jnp.float32)
    jax.jit(name_to_block[block_cls](HID, **kwargs, head_dim=HID // HEADS).init)(jax.random.PRNGKey(0), x)  # 32: as derived
    with pytest.raises(AssertionError, match="hidden / heads"):
        jax.jit(name_to_block[block_cls](HID, **kwargs, head_dim=64).init)(jax.random.PRNGKey(0), x)


def test_served_end_to_end_through_server_and_remote_sequential():
    """`Server.create(expert_cls="olmoe_block", expert_kwargs=...)`, two blocks, a
    client's prefill and single-token steps over the wire against the reference."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential, Server

    server = Server.create(
        expert_uids=["olmoe.0", "olmoe.1"], expert_cls="olmoe_block", expert_kwargs=KWARGS, hidden_dim=HID,
        start=True, optim_factory=lambda: optax.sgd(0.0), decode_max_len=32, activation_compression="none",
    )
    client_dht = None
    try:
        wait_for_experts(server.dht, server.backends)
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        pipe = RemoteSequential(client_dht, "olmoe.", 2)
        x = stream(9, 1, 14)
        before = counters("direct")
        chunks = [pipe.decode_step(x[:, :9], "e2e", reset=True)]
        chunks += [pipe.decode_step(x[:, t:t + 1], "e2e") for t in range(9, 14)]
        pipe.close_decode_session("e2e")
        params = [server.backends[f"olmoe.{i}"].snapshot_params() for i in range(2)]
        want = reference_program()(params, x)
        assert rel_err(np.concatenate(chunks, axis=1), want) <= SERVED_TOL
        assert delta(before, counters("direct"))["routed_pairs"] == 2 * 14 * TOP_K
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()
