"""`deepseek_v3_block` (GigaChat3.1-702B-A36B's decoder blocks: multi-head latent attention
over one compressed array a position, YaRN rotary on a shared key, then a dense MLP or a
sparse expert layer behind a group-limited sigmoid router) against the plain float32
reference `perf/reference/gigachat_block.py`, on every serving path: the block's forward,
`DecodeSessionManager` with a prompt that arrives in chunks of unequal length (the last one
padded) and then single-token steps across `original_max_position_embeddings`, the batched
step with rows at different positions (a vector ``index``, the rows' caches apart), and the
rehearsal configuration's span through `Server` + `RemoteSequential`. Beside them the two
attention forms on one cache in float32, the group-limited choice against a loop, the
ungrouped router's program unchanged, the shares that add up to the uncut layer, a failed
step, the telemetry, and the import guard. Small sizes, seeded weights.

Tolerances, as a share of the largest value of the reference's output: the served
arithmetic (bf16 activations, float32 accumulation and router) reads 3e-3 to 7e-3 at these
sizes on a dense block; a near-tie of the router that the bf16 rounding of its INPUT flips
moves ONE position by a whole expert, so a sparse block's stream is held to `SERVED_TOL` on
all but a few positions (`positions_beyond`)."""

import functools
import os
import subprocess
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
from swarm_utils import ManagerSharingPrograms, OneProgramBackend  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from perf.reference import gigachat_block as reference  # noqa: E402
from perf.runtime import rel_err  # noqa: E402

HID, HEADS, Q_RANK, RANK, NOPE, ROPED, V_DIM = 64, 4, 24, 16, 8, 8, 12
ROPE = dict(theta=100000.0, factor=8.0, original=32, beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
EXPERTS, GROUPS, KEPT, PICKS, HELD_LO, HELD = 16, 4, 2, 4, 4, 4
KWARGS = dict(num_heads=HEADS, q_lora_rank=Q_RANK, kv_lora_rank=RANK, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPED, v_head_dim=V_DIM,
              rope_theta=ROPE["theta"], rope_factor=ROPE["factor"], rope_original=ROPE["original"], ffn_inner=96, num_experts=EXPERTS,
              experts_per_token=PICKS, n_group=GROUPS, topk_group=KEPT, expert_inner=32, held_lo=HELD_LO, held=HELD)
SIZES = dict(num_heads=HEADS, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPED, v_head_dim=V_DIM, rms_eps=1e-6, rope=ROPE,
             experts_per_token=PICKS, routed_scale=2.5, n_group=GROUPS, topk_group=KEPT, held_lo=HELD_LO, query_block=32)
SERVED_TOL = 2e-2
MAX_LEN = 256
NAME = "deepseek_v3_block"


@functools.cache  # read-only in every test (the optimizer's rate is 0): built once a process
def make_backend(mlp: str, uid="giga.0", seed=3, **overrides) -> ModuleBackend:
    module = name_to_block[NAME](HID, mlp=mlp, **{**KWARGS, **overrides})
    return OneProgramBackend(uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input[NAME](4, HID), max_batch_size=8, rng_seed=seed)


@functools.cache
def reference_program(entry: str = "span", **changed):
    """The reference's ``entry`` as ONE program a shape, not one an operation."""
    return jax.jit(functools.partial(getattr(reference, entry), **{**SIZES, **changed}))


@functools.cache
def forward_program(module):
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


@pytest.mark.parametrize("mlp", ["dense", "sparse"])
def test_forward_matches_the_reference(mlp):
    """The block on a whole sequence (the pool's forward, the expanded form in blocks of
    keys and queries) against the reference's: 120 positions, past the original context of 32."""
    backend = make_backend(mlp)
    x = stream(1, 2, 120)
    want = reference_program()([backend.params], x)
    got = forward_program(backend.module)({"params": backend.params}, x)
    assert positions_beyond(got, want, SERVED_TOL) <= (0.0 if mlp == "dense" else 0.05)


@pytest.mark.parametrize("mlp", ["dense", "sparse"])
def test_chunked_prompt_then_steps_equal_the_full_forward(mlp):
    """A prompt of 101 positions in chunks of 48, 37 and 16 (padded to 64, 64 and 16; the
    first crosses the original context of 32), then 40 single steps (the absorbed form),
    through the manager with a scalar ``index``: the same positions as the reference's one
    forward of 141. The session keeps ONE array of 16 + 8 values a position."""
    backend = make_backend(mlp)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    x = stream(2, 1, 141)
    chunks, at = [], 0
    for length in (48, 37, 16):
        chunks.append(manager.decode(backend.name, "s", x[:, at:at + length], reset=at == 0))
        at += length
    chunks += [manager.decode(backend.name, "s", x[:, t:t + 1], reset=False) for t in range(at, 141)]
    got = np.concatenate(chunks, axis=1)
    want = reference_program()([backend.params], x)
    assert got.shape == want.shape and positions_beyond(got, want, SERVED_TOL) <= (0.0 if mlp == "dense" else 0.05)
    session = manager._sessions[(backend.name, "s")]
    [leaf] = jax.tree_util.tree_leaves(session.cache)
    assert session.index == 141 and leaf.shape == (1, MAX_LEN, RANK + ROPED) and leaf.dtype == jnp.bfloat16


def test_router_taps_get_what_the_served_routers_saw_and_chose():
    """A check against a reference appends to `ROUTER_TAPS` and is handed, call by call, what the
    router of the SERVED program saw and chose: a padded chunk's real positions, a session's own
    step, a batched step's live rows (the padding row of the bucket of four left out). The
    reference's float32 router on those inputs picks the same experts, and the choices of chunk and
    steps together are those of the reference's one forward but for near-ties. A dense block hands
    nothing over, and with no tap nothing is fetched."""
    from hivemind_tpu.moe.server.routing_stats import ROUTER_TAPS

    taken = []
    tap = lambda seen, chose: taken.append((seen, chose))
    for mlp, calls in (("dense", 0), ("sparse", 3)):
        backend = make_backend(mlp)
        manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
        x = stream(11, 3, 40)
        ROUTER_TAPS.append(tap)
        try:
            manager.decode(backend.name, "row0", x[:1, :37], reset=True)  # padded to 64: 37 real positions
            manager.decode(backend.name, "row0", x[:1, 37:38], reset=False)  # the session's own step
            for row in (1, 2):
                manager.decode(backend.name, f"row{row}", x[row:row + 1, :38], reset=True)
            del taken[calls and 2:]  # keep row 0's chunk and step
            entries = [(None, manager._sessions[(backend.name, f"row{row}")], x[row:row + 1, 38:39]) for row in range(3)]
            assert not any(isinstance(out, Exception) for out in manager._decode_batch(backend.name, entries))
        finally:
            ROUTER_TAPS.remove(tap)
        assert len(taken) == calls
        manager.decode(backend.name, "row0", x[:1, 39:40], reset=False)  # no tap: nothing more is handed over
        assert len(taken) == calls
    (chunk_m, chunk_e), (step_m, step_e), (rows_m, rows_e) = taken
    assert chunk_m.shape == (1, 37, HID) and chunk_e.shape == (1, 37, PICKS) and step_m.shape == (1, 1, HID)
    assert rows_m.shape == (3, 1, HID) and rows_e.shape == (3, 1, PICKS)
    router = {"router": backend.params["router"], "router_bias": backend.params["router_bias"]}
    seen = np.concatenate([chunk_m, step_m, rows_m[:1]], axis=1)
    chose = np.concatenate([chunk_e, step_e, rows_e[:1]], axis=1)
    own = np.asarray(reference.chosen_experts(router, jnp.asarray(seen), PICKS, GROUPS, KEPT))
    assert (np.sort(own, -1) == np.sort(chose, -1)).all()  # the served router is the float32 one
    _out, [(_m, want)] = reference_program("span_with_routing")([backend.params], x[:1, :39])
    assert (np.sort(np.asarray(want), -1) != np.sort(chose, -1)).any(-1).mean() <= 0.1


@pytest.mark.parametrize("mlp", ["dense", "sparse"])
def test_batched_rows_at_different_positions(mlp):
    """Three sessions step together in ONE batched program (a vector ``index``, each leaf
    the tuple of the rows' own arrays), each at its own position, one under the original
    context and two past it: every row equals the reference's full forward of its stream,
    the rows are counted `caches=apart`, and each session keeps an array of its own."""
    backend = make_backend(mlp)
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    lengths, steps = [90, 20, 70], 30
    x = stream(3, 3, max(lengths) + steps)
    got = [[manager.decode(backend.name, f"row{row}", x[row:row + 1, :length], reset=True)] for row, length in enumerate(lengths)]
    before = counter("hivemind_moe_decode_calls_total", path="batched")
    apart = counter("hivemind_moe_decode_batched_rows_total", caches="apart")
    attended = counter("hivemind_moe_latent_positions_attended_total", path="batched")
    for step in range(steps):
        entries = [(None, manager._sessions[(backend.name, f"row{row}")], x[row:row + 1, length + step:length + step + 1])
                   for row, length in enumerate(lengths)]
        for row, out in enumerate(manager._decode_batch(backend.name, entries)):
            assert not isinstance(out, Exception), out
            got[row].append(out)
    assert counter("hivemind_moe_decode_calls_total", path="batched") - before == steps
    assert counter("hivemind_moe_decode_batched_rows_total", caches="apart") - apart == 3 * steps
    # a row a step: its write position + 1; the padding row of the bucket of four is not counted
    assert counter("hivemind_moe_latent_positions_attended_total", path="batched") - attended == sum(
        length + step + 1 for length in lengths for step in range(steps))
    want = np.asarray(reference_program()([backend.params], x))
    for row, length in enumerate(lengths):
        served = np.concatenate(got[row], axis=1)
        assert positions_beyond(served, want[row:row + 1, :length + steps], SERVED_TOL) <= (0.0 if mlp == "dense" else 0.05), row
    arrays = [manager._sessions[(backend.name, f"row{row}")].leaves[0] for row in range(3)]
    assert len({id(array) for array in arrays}) == 3 and all(array.shape == (1, MAX_LEN, RANK + ROPED) for array in arrays)


def test_the_absorbed_step_equals_expanded_attention_over_the_same_cache():
    """In float32 the two forms are the same numbers: `latent_step` (W_kvb's key half in the
    query, its value half on the output, the latent attended where it lies) against
    `latent_chunk` (the cache's latents expanded, heads of NOPE + ROPED) for one query at
    the session's end, over one cache; and a cache whose slots are no multiple of the
    key block is walked to its end without a position counted twice."""
    from hivemind_tpu.ops import latent_attention as ops

    rng = np.random.default_rng(4)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    latent_step = jax.jit(ops.latent_step)  # each side of a comparison ONE program, not one an operation
    latent_chunk = jax.jit(ops.latent_chunk, static_argnames="key_block")
    rows, slots, held = 2, 100, 73
    cache = draw(rows, slots, RANK + ROPED)
    cache[:, held:] = 7.0  # what lies past the session's end is never read
    q_nope, q_pe, new = draw(rows, HEADS, NOPE), draw(rows, HEADS, ROPED), draw(rows, 1, RANK + ROPED)
    w_k, w_v = draw(RANK, HEADS, NOPE), draw(RANK, HEADS, V_DIM)
    with jax.default_matmul_precision("highest"):
        absorbed, written = latent_step(q_nope, q_pe, new, cache, jnp.int32(held), w_k, w_v, 0.3)
        for key_block in (1024, 32):  # the whole cache at once; four blocks, the last taken from the cache's end
            expanded = latent_chunk(q_nope[:, None], q_pe[:, None], written, jnp.int32(held), w_k, w_v, 0.3, key_block=key_block)
            assert rel_err(absorbed, expanded[:, 0]) <= 1e-5, key_block
        # against the definition: every head's keys and values from the latents, plain softmax
        @jax.jit
        def by_the_definition(written):
            c, k_pe = written[:, :held + 1, :RANK], written[:, :held + 1, RANK:]
            scores = (jnp.einsum("rhd,rshd->rhs", q_nope, jnp.einsum("rsc,chd->rshd", c, w_k)) + jnp.einsum("rhd,rsd->rhs", q_pe, k_pe)) * 0.3
            return jnp.einsum("rhs,rshv->rhv", jax.nn.softmax(scores, -1), jnp.einsum("rsc,chv->rshv", c, w_v))

        plain = by_the_definition(written)
    assert rel_err(absorbed, plain) <= 1e-5
    assert np.array_equal(np.asarray(written[:, held]), np.asarray(new[:, 0])) and float(written[0, held + 1, 0]) == 7.0
    # the rows' arrays apart, each at its own position: the same numbers row by row
    apart, arrays = latent_step(q_nope, q_pe, new, (cache[:1], cache[1:]), np.array([held, held], np.int32), w_k, w_v, 0.3)
    assert isinstance(arrays, tuple) and rel_err(apart, absorbed) <= 1e-6


def test_yarn_frequencies_by_hand():
    """At the published sizes: lo 8 and hi 19, the first eight pairs as plain rope, the pairs
    from 19 on slowed 64 times, a ramp between; the softmax scale carries m^2 = 1.4159^2."""
    from hivemind_tpu.ops import latent_attention as ops

    got = ops.yarn_inv_freq(64, 100000.0, 64.0, 4096, 32.0, 1.0)
    plain = 100000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(got[:9], plain[:9], rtol=1e-6) and np.allclose(got[19:], plain[19:] / 64.0, rtol=1e-6)
    assert np.allclose(got[10], plain[10] * ((9 / 11) + (2 / 11) / 64.0), rtol=1e-5)  # r = 1 - (10 - 8) / (19 - 8)
    assert np.allclose(got, np.asarray(reference.yarn_inv_freq(64, 100000.0, 64.0, 4096, 32.0, 1.0)), rtol=1e-6)
    assert ops.yarn_mscale(64.0, 1.0) == pytest.approx(1.4159, abs=1e-4) and ops.yarn_mscale(1.0, 1.0) == 1.0
    block = name_to_block[NAME](7168, v_head_dim=192, rope_theta=100000.0, rope_factor=64.0)
    assert block.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 4, 8)), jnp.float32)
    turned = ops.rope_interleaved(x, jnp.array([[5, 6, 7], [0, 1, 2]]), got[:4])
    assert np.allclose(turned[1, 0], x[1, 0]) and np.allclose(jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert np.allclose(turned[0, 1, 2, 0], x[0, 1, 2, 0] * np.cos(6 * got[0]) - x[0, 1, 2, 1] * np.sin(6 * got[0]), atol=1e-5)


def _loop_choice(biased, n_group: int, topk_group: int, k: int):
    """The group-limited choice written as loops over one token's ``score + bias``; ties go
    to the lower number, as `lax.top_k` breaks them."""
    size = len(biased) // n_group
    best = lambda values, count: sorted(range(len(values)), key=lambda i: (-values[i], i))[:count]
    group_scores = [sum(sorted(biased[g * size:(g + 1) * size], reverse=True)[:2]) for g in range(n_group)]
    kept = set(best(group_scores, topk_group))
    allowed = [value if i // size in kept else -np.inf for i, value in enumerate(biased)]
    return best(allowed, k)


def test_group_limited_choice_equals_a_loop_ties_included():
    from hivemind_tpu.ops.sparse_experts import route_sigmoid_top_k

    rng = np.random.default_rng(5)
    tokens, experts, n_group, topk_group, k = 40, 32, 8, 3, 5
    # logits on a coarse grid, so that experts and whole groups tie
    logits = rng.integers(-3, 4, size=(tokens, experts)).astype(np.float32)
    logits[0] = 0.0  # the scores tie everywhere: the bias alone picks
    bias = (rng.integers(-1, 2, size=experts) * 0.25).astype(np.float32)
    weights, top_e = route_sigmoid_top_k(jnp.asarray(logits), jnp.eye(experts, dtype=jnp.float32), jnp.asarray(bias), k, 2.5,
                                         n_group=n_group, topk_group=topk_group)
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    for token in range(tokens):
        want = _loop_choice(list(np.float32(jax.nn.sigmoid(logits[token])) + bias), n_group, topk_group, k)
        assert list(np.asarray(top_e[token])) == want, token
        picked = scores[token, want]
        assert np.allclose(np.asarray(weights[token]), 2.5 * picked / picked.sum(), rtol=1e-5)  # the bias picks and does not weigh
    _weights, flat = route_sigmoid_top_k(jnp.zeros((1, experts)), jnp.eye(experts, dtype=jnp.float32), jnp.zeros(experts), k, 2.5,
                                         n_group=n_group, topk_group=topk_group)
    assert list(np.asarray(flat[0])) == [0, 1, 2, 3, 4]  # every expert and every group ties: the lowest numbers win
    # the reference's rule, written another way again, chooses the same
    params = {"router": jnp.eye(experts, dtype=jnp.float32), "router_bias": jnp.asarray(bias)}
    _dense, ref_e = reference.route(params, jnp.asarray(logits), k, 2.5, n_group, topk_group)
    assert np.array_equal(np.sort(np.asarray(ref_e), -1), np.sort(np.asarray(top_e), -1))


def test_one_group_is_the_ungrouped_router_and_lowers_to_the_same_text():
    """`n_group` 1 / `topk_group` 1 (K-EXAONE) traces nothing of the group limiting: the
    program text of `route_sigmoid_top_k` as `exaone_moe_block` calls it is the text of the
    function as it stood before it learned of groups, and so is K-EXAONE's batched program."""
    from hivemind_tpu.ops import sparse_experts
    from hivemind_tpu.ops.sparse_experts import route_sigmoid_top_k

    def as_it_stood(tokens, router, bias, k, scale):
        logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        picked = jnp.take_along_axis(scores, top_e, axis=-1)
        return scale * picked / picked.sum(-1, keepdims=True), top_e

    shapes = (jax.ShapeDtypeStruct((8, 64), jnp.bfloat16), jax.ShapeDtypeStruct((64, 16), jnp.float32), jax.ShapeDtypeStruct((16,), jnp.float32))
    text = lambda fn: jax.jit(lambda tokens, router, bias: fn(tokens, router, bias, 4, 2.5)).lower(*shapes).as_text()
    assert text(route_sigmoid_top_k) == text(as_it_stood)
    assert text(lambda *args: route_sigmoid_top_k(*args, n_group=1, topk_group=1)) == text(as_it_stood)
    assert text(lambda *args: route_sigmoid_top_k(*args, n_group=4, topk_group=2)) != text(as_it_stood)

    # K-EXAONE's batched program, with the router as it is and as it stood
    def batched_text():
        module = name_to_block["exaone_moe_block"](HID, num_heads=4, num_kv_heads=2, head_dim=16, window=8, num_experts=16,
                                                   experts_per_token=4, expert_inner=32, held_lo=4, held=4)
        backend = OneProgramBackend("exa.0", module, optimizer=optax.sgd(0.0), sample_input=name_to_input["exaone_moe_block"](4, HID),
                                    max_batch_size=8, rng_seed=1)
        manager = DecodeSessionManager({"exa.0": backend}, max_len=64)
        shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
        columns = tuple((leaf,) * 4 for leaf in shape(manager._dummy_rows("exa.0")))
        return manager._batched_fn("exa.0", 4).jitted.lower(shape(backend.snapshot_params()), jax.ShapeDtypeStruct((4, 1, HID), "float32"),
                                                            columns, jax.ShapeDtypeStruct((4,), "int32")).as_text()

    now = batched_text()
    original = sparse_experts.route_sigmoid_top_k
    sparse_experts.route_sigmoid_top_k = as_it_stood
    try:
        assert batched_text() == now
    finally:
        sparse_experts.route_sigmoid_top_k = original


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: a toy layer of 32 routed experts in 4 groups, shared by 32 chips of one
    expert each. The parts that all 32 shares give, with what every chip computes alike
    (attention, the shared expert) counted once, add up to what the uncut reference gives
    for the whole layer: in the reference exactly, in the program to the served rounding."""
    experts = 32
    sizes = dict(experts_per_token=4, n_group=4, topk_group=2)
    whole = make_backend("sparse", num_experts=experts, held_lo=0, held=0)
    params = jax.tree_util.tree_map(np.asarray, whole.params)  # a share is cut in numpy: no program a slice
    x = stream(6, 1, 40)
    uncut = reference_program(**sizes, held_lo=0)([params], x)
    # what every share computes alike: the layer with no routed expert's output
    no_routed = {**params, "experts_down": np.zeros_like(params["experts_down"])}
    common = reference_program(**sizes, held_lo=0)([no_routed], x)
    share_of = lambda lo: {**params, **{name: params[name][lo:lo + 1] for name in ("experts_gate", "experts_up", "experts_down")}}
    shares = [share_of(lo) for lo in range(experts)]

    @jax.jit  # the 32 shares' layers in one program
    def parts_reference(shares, x, common):
        return sum(reference.span([share], x, **{**SIZES, **sizes, "held_lo": lo}) - common for lo, share in enumerate(shares))

    assert rel_err(common + parts_reference(shares, x, common), uncut) <= 1e-5
    assert float(jnp.abs(uncut - common).max() / jnp.abs(uncut).max()) > 0.05  # the routed experts are a real part of the layer
    # the program's shares: what they compute alike is taken from the program too (its rounding would count 32 times)
    alike = forward_program(whole.module)({"params": no_routed}, x)

    @jax.jit
    def parts_program(shares, x, alike):
        total = 0.0
        for lo, share in enumerate(shares):
            module = name_to_block[NAME](HID, mlp="sparse", **{**KWARGS, "num_experts": experts, "held_lo": lo, "held": 1})
            assert module.held_experts == (lo, lo + 1)
            total = total + (module.apply({"params": share}, x) - alike)
        return total

    served = alike + parts_program(shares, x, alike)
    assert positions_beyond(served, uncut, 3e-2) <= 0.05
    assert rel_err(served, forward_program(whole.module)({"params": params}, x)) <= 1e-2  # and to the program's own uncut layer
    assert whole.module.held_experts is None and make_backend("dense").module.held_experts is None


def test_a_failed_step_leaves_no_half_updated_state(monkeypatch):
    """A per-session step DONATES the latent cache: one that fails drops the session (the next
    continuation gets the unknown-session KeyError and re-prefills). A batched step steps on
    the rows' own arrays and donates them too (ISSUE 50); one that raises before it took
    anything (the stand-in here raises at once) leaves every session's array and position
    as they were."""
    backend = make_backend("sparse")
    assert backend.module.decode_rows_apart and backend.module.decode_takes_chunks and backend.module.decode_cache_kind == "latent"
    manager = ManagerSharingPrograms({backend.name: backend}, max_len=MAX_LEN)
    x = stream(7, 2, 80)
    for row in range(2):
        manager.decode(backend.name, f"row{row}", x[row:row + 1, :70], reset=True)
    sessions = [manager._sessions[(backend.name, f"row{row}")] for row in range(2)]
    held = [session.leaves for session in sessions]

    def broken(*_args, **_kwargs):
        raise RuntimeError("device fault")

    monkeypatch.setitem(manager._batched_fns, (backend.name, 2), broken)
    entries = [(None, session, x[row:row + 1, 70:71]) for row, session in enumerate(sessions)]
    with pytest.raises(RuntimeError):
        manager._decode_batch(backend.name, entries)
    for session, leaves in zip(sessions, held):
        assert session.index == 70 and session.leaves[0] is leaves[0] and not leaves[0].is_deleted()
    monkeypatch.delitem(manager._batched_fns, (backend.name, 2))
    assert not any(isinstance(out, Exception) for out in manager._decode_batch(backend.name, entries))  # and they step on

    monkeypatch.setitem(manager._step_fns, (backend.name, 1, 1), broken)
    with pytest.raises(RuntimeError):
        manager.decode(backend.name, "row0", x[:1, 71:72], reset=False)
    assert (backend.name, "row0") not in manager._sessions and (backend.name, "row1") in manager._sessions
    monkeypatch.delitem(manager._step_fns, (backend.name, 1, 1))
    with pytest.raises(KeyError):
        manager.decode(backend.name, "row0", x[:1, 71:72], reset=False)


def test_gauges_counters_and_program_names_say_latent():
    """Two sessions on a dense and a sparse block: the cache gauges under kind `latent` (bytes
    over entries = one array of max_len x 24 bf16), the positions a session's own steps
    attended counted `path=direct` (chunks are not counted), the held-share routing counters
    of the sparse block, and the kind in the programs' names."""
    backends = {"g.0": make_backend("dense", uid="g.0"), "g.1": make_backend("sparse", uid="g.1")}
    manager = ManagerSharingPrograms(backends, max_len=MAX_LEN)
    manager.clear_sessions()
    x = stream(11, 1, 100)
    direct, pairs, held = (counter(name, path="direct") for name in (
        "hivemind_moe_latent_positions_attended_total", "hivemind_moe_routed_pairs_total", "hivemind_moe_held_pairs_total"))
    manager._decode_direct(("g.0", "g.1"), "a", x[:, :40], reset=True)
    manager._decode_direct(("g.0", "g.1"), "a", x[:, 40:99], reset=False)
    assert counter("hivemind_moe_latent_positions_attended_total", path="direct") == direct  # a chunk expands: not this counter's
    manager._decode_direct(("g.0", "g.1"), "a", x[:, 99:100], reset=False)
    assert counter("hivemind_moe_latent_positions_attended_total", path="direct") - direct == 2 * 100  # position 99 at both blocks
    assert counter("hivemind_moe_routed_pairs_total", path="direct") - pairs == 100 * PICKS  # the sparse block alone, padding out
    assert 0 < counter("hivemind_moe_held_pairs_total", path="direct") - held < 100 * PICKS
    manager._decode_direct(("g.0", "g.1"), "b", x[:, :40], reset=True)
    gauges = REGISTRY.snapshot()
    entries = gauges["hivemind_moe_decode_cache_entries"]["series"]["kind=latent"]
    assert entries == 4 and gauges["hivemind_moe_decode_cache_bytes"]["series"]["kind=latent"] / entries == MAX_LEN * (RANK + ROPED) * 2
    assert manager._step_fn("g.0", 1, 64).jitted.__name__ == "prefill_latent_64"
    assert manager._step_fn("g.1", 1, 1).jitted.__name__ == "step_latent"
    assert manager._batched_fn("g.1", 2).jitted.__name__ == "batched_step_latent"
    manager.clear_sessions()


def test_parameter_counts_by_hand():
    """At the published widths, from shapes alone: attention 132.6 M, the dense block 528.9 M,
    a sparse block with 8 held experts 530.8 M, the span of five 2,652 M = 10.61 GB at 4 bytes;
    a session's array at 12,288 slots (the issue's lever) 14.16 MB, 32 sessions on five blocks 2.26 GB."""
    from perf import manifest as mf
    from perf.runners import latent_moe_block_server as runner

    config = mf.load_json(mf.PERF / "configs" / "gigachat-702b-a36b-span5.json")
    hidden, counts, caches = config["model"]["hidden_size"], [], []
    for index in range(config["model"]["num_hidden_layers"]):
        module = name_to_block[NAME](hidden, **runner.block_kwargs(config, index))
        shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 4, hidden), jnp.float32))["params"]
        counts.append(sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)))
        cache = jax.eval_shape(lambda module=module: module.init_decode_cache(1, config["serving"]["decode_max_len"]))
        caches.append(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(cache)))
    attention = 7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 20480 + 12288 * 7168
    norms = 2 * 7168 + 1536 + 512
    dense = attention + 3 * 7168 * 18432 + norms
    sparse = attention + 9 * 3 * 7168 * 2048 + 7168 * 256 + 256 + norms
    assert counts == [dense] + [sparse] * 4
    assert [round(count / 1e6, 2) for count in (attention, dense, sparse, sum(counts))] == [132.58, 528.96, 530.79, 2652.13]
    assert round(4 * sum(counts) / 1e9, 2) == 10.61
    assert caches == [12288 * 576 * 2] * 5 and round(caches[0] / 1e6, 2) == 14.16 and round(32 * sum(caches) / 1e9, 2) == 2.26


def test_span_through_server_and_remote_sequential(one_program_backends):
    """The rehearsal configuration's span (the dense block 2 and the sparse blocks 3-6, 4 of 16
    experts held inside one group), built as the runner builds it: a client's prompt in
    chunks and single-token steps over the wire against the reference, past the original
    context of 64."""
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from perf import manifest as mf
    from perf.runners import latent_moe_block_server as runner

    config = mf.rehearsal_config(mf.load_json(mf.PERF / "configs" / "gigachat-702b-a36b-span5.json"))
    config["serving"]["activation_compression"] = "none"
    hidden, blocks = config["model"]["hidden_size"], config["model"]["num_hidden_layers"]
    assert [runner.block_kwargs(config, index)["mlp"] for index in range(blocks)] == ["dense"] + ["sparse"] * 4
    server_dht = DHT(start=True)
    server = runner.build_server(config, 5, server_dht, name_to_block[NAME])
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
        assert positions_beyond(got, want, 5e-2) <= 0.1  # five blocks, four routers
        pipe.close_decode_session("e2e")
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server_dht.shutdown()


def test_trainers_load_nothing_of_this_block():
    """A process that imports what `perf/runners/trainer.py` and
    `examples/albert/run_trainer.py` import (they load `moe.server.layers` for the optimizer
    helpers, and so the block registry) holds none of the modules this block's PR added: the
    block's own module and `ops/latent_attention.py` load when a block is BUILT (PR 32's
    regression was ALBERT's `setup_s`, a cell whose process never runs a block)."""
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
assert 'deepseek_v3_block' in name_to_block
added = ('hivemind_tpu.moe.server.layers.deepseek_v3', 'hivemind_tpu.ops.latent_attention', 'perf.reference.gigachat_block',
         'perf.runners.latent_moe_block_server', 'perf.flops_mla', 'perf.readers.latent_roofline')
held = [name for name in added if name in sys.modules]
assert not held, held
name_to_block['deepseek_v3_block'](64)
assert 'hivemind_tpu.moe.server.layers.deepseek_v3' in sys.modules and 'hivemind_tpu.ops.latent_attention' in sys.modules
print('ok')
"""
    run = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert run.returncode == 0 and run.stdout.strip().endswith("ok"), run.stderr[-3000:]
