"""The PASS AXIS of a decode session (ISSUE 56): a looped model's blocks run `decode_passes` times a token, each
pass on a cache of its own. The manager's side — ONE entry of the table a session a block whatever its passes,
to the cap, the TTL, `clear_sessions`, a failed step and the gauges; a request names its pass, a pass out of
range or out of order raises and donates nothing; a cohort takes rows whatever their pass; a chain whose blocks
disagree is refused — and the wire's and the client's: `loop_pass` in the metadata, the ledger's record, a
failover between two passes of a token, an old client against the new server and the reverse. And what must
NOT move: the programs of the Llama-family blocks lower to the text they lowered to on the parent commit."""

import asyncio
import functools
import hashlib
import sys
import uuid
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from hivemind_tpu.dht import DHT  # noqa: E402
from hivemind_tpu.moe import RemoteSequential, Server  # noqa: E402
from hivemind_tpu.moe.server.decode_session import DecodeSessionManager  # noqa: E402
from hivemind_tpu.moe.server.layers import name_to_block, name_to_input  # noqa: E402
from hivemind_tpu.telemetry import REGISTRY  # noqa: E402
from hivemind_tpu.utils.serializer import MSGPackSerializer  # noqa: E402
from swarm_utils import ManagerSharingPrograms, OneProgramBackend, wait_for_experts  # noqa: E402

HID, PASSES, MAX_LEN = 32, 4, 24
LOOPED = dict(num_heads=2, ffn_inner=48, total_ut_steps=PASSES)
CHAIN = ("p.0", "p.1")


def backend_of(uid: str, cls: str = "ouro_block", seed: int = 3, **kwargs):
    return OneProgramBackend(uid, name_to_block[cls](HID, **kwargs), optimizer=optax.sgd(0.0), sample_input=name_to_input[cls](2, HID),
                             max_batch_size=4, rng_seed=seed)


@functools.cache
def looped_backends():
    return {uid: backend_of(uid, seed=3 + at, **LOOPED) for at, uid in enumerate(CHAIN)}


def fresh_manager(**kwargs):
    return ManagerSharingPrograms(looped_backends(), max_len=MAX_LEN, **kwargs)


def counter(name: str, **labels) -> float:
    series = REGISTRY.snapshot().get(name, {}).get("series", {})
    key = ",".join(f"{k}={v}" for k, v in labels.items()) or "_"
    return float(series.get(key, 0.0))


def stream(seed: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((1, length, HID)).astype(np.float32)


def prompt_all_passes(manager, name: str, length: int = 6, seed: int = 1) -> None:
    x = stream(seed, length)
    for u in range(PASSES):
        x = manager._decode_direct(CHAIN, name, x, True, u)


def held_arrays(manager, name: str):
    return [leaf for uid in CHAIN for tree in manager._sessions[(uid, name)].trees for leaf in tree]


# ---- one entry, four trees ----------------------------------------------------------------------


def test_a_session_of_four_passes_is_one_entry_and_the_gauges_read_four_trees():
    manager = fresh_manager(max_sessions=8)
    resets = counter("hivemind_moe_decode_session_resets_total")
    prompt_all_passes(manager, "a")
    assert len(manager._sessions) == len(CHAIN), "one entry a block, not one a pass"
    session = manager._sessions[(CHAIN[0], "a")]
    one_tree = 2 * 2 * MAX_LEN * (HID // 2) * 2  # keys and values, 2 heads, bf16
    assert session.row_bytes == one_tree and session.nbytes == PASSES * one_tree and len(session.trees) == PASSES
    assert session.positions == [6] * PASSES and session.index == 6 and session.leaves is session.trees[0]
    assert counter("hivemind_moe_decode_cache_bytes", kind="looped") == len(CHAIN) * PASSES * one_tree
    assert counter("hivemind_moe_decode_cache_entries", kind="looped") == len(CHAIN)
    assert counter("hivemind_moe_decode_sessions") == len(CHAIN)
    # a reset of pass 0 makes the entry; a reset of a later pass finds it: one count a block a call
    assert counter("hivemind_moe_decode_session_resets_total") - resets == len(CHAIN) * PASSES
    manager.clear_sessions()
    assert not manager._sessions and counter("hivemind_moe_decode_cache_bytes", kind="looped") == 0
    assert counter("hivemind_moe_decode_cache_entries", kind="looped") == 0


def test_the_cap_counts_a_session_once_and_an_eviction_takes_all_four_trees():
    manager = fresh_manager(max_sessions=2 * len(CHAIN))  # room for TWO sessions of four passes (eight trees a block)
    evicted = counter("hivemind_moe_decode_session_evictions_total", reason="cap")
    prompt_all_passes(manager, "a")
    prompt_all_passes(manager, "b")
    assert len(manager._sessions) == 2 * len(CHAIN) and counter("hivemind_moe_decode_session_evictions_total", reason="cap") == evicted
    prompt_all_passes(manager, "c")  # the oldest goes, whole: its blocks' entries, each with four trees
    assert counter("hivemind_moe_decode_session_evictions_total", reason="cap") - evicted == len(CHAIN)
    assert {name for _uid, name in manager._sessions} == {"b", "c"}
    assert counter("hivemind_moe_decode_cache_entries", kind="looped") == 2 * len(CHAIN)
    with pytest.raises(KeyError, match="unknown or expired"):  # every pass of it went: none can be stepped alone
        manager._decode_direct(CHAIN, "a", stream(2, 1), False, 2)
    manager.clear_sessions()


def test_the_ttl_takes_the_whole_entry():
    manager = fresh_manager()
    prompt_all_passes(manager, "a")
    expired = counter("hivemind_moe_decode_session_evictions_total", reason="ttl")
    manager.session_ttl = 0.0
    with pytest.raises(KeyError, match="unknown or expired"):
        manager._decode_direct(CHAIN, "a", stream(2, 1), False, 0)
    assert counter("hivemind_moe_decode_session_evictions_total", reason="ttl") - expired == len(CHAIN) and not manager._sessions


def test_a_failed_step_of_one_pass_drops_the_entry_with_all_four_trees(monkeypatch):
    manager = fresh_manager()
    for name in ("a", "b"):
        prompt_all_passes(manager, name)
    token = stream(4, 1)
    for name in ("a", "b"):  # both at pass 2 of the next position
        x = token
        for u in range(2):
            x = manager._decode_direct(CHAIN, name, x, False, u)
    real = manager._batched_fn(CHAIN[0], 2)

    def fails_after_it_took_the_caches(*args):
        real(*args)
        raise RuntimeError("device fault")

    monkeypatch.setitem(manager._batched_fns, (CHAIN[0], 2), fails_after_it_took_the_caches)
    failed = counter("hivemind_moe_decode_session_evictions_total", reason="failed_step")
    entries = [(None, manager._sessions[(CHAIN[0], name)], token, 2) for name in ("a", "b")]
    with pytest.raises(RuntimeError, match="device fault"):
        manager._decode_batch(CHAIN[0], entries)
    assert counter("hivemind_moe_decode_session_evictions_total", reason="failed_step") - failed == 2
    assert all((CHAIN[0], name) not in manager._sessions for name in ("a", "b")), "the other three passes' trees went with the entry"
    assert counter("hivemind_moe_decode_cache_entries", kind="looped") == 2  # the second block's, which the step never reached
    manager.clear_sessions()


# ---- a request names its pass -------------------------------------------------------------------


@pytest.mark.parametrize("loop_pass, error", [(PASSES, "whose sessions hold 4"), (-1, "whose sessions hold 4")])
def test_a_pass_out_of_range_raises_and_donates_nothing(loop_pass, error):
    manager = fresh_manager()
    prompt_all_passes(manager, "a")
    before, donated = held_arrays(manager, "a"), counter("hivemind_moe_decode_cache_bytes_donated_total", path="direct")
    with pytest.raises(ValueError, match=error):
        manager._decode_direct(CHAIN, "a", stream(2, 1), False, loop_pass)
    with pytest.raises(ValueError, match=error):
        asyncio.run(manager.decode_span_async(CHAIN, "a", stream(2, 1), False, loop_pass))
    after = held_arrays(manager, "a")
    assert all(a is b and not a.is_deleted() for a, b in zip(before, after))
    assert counter("hivemind_moe_decode_cache_bytes_donated_total", path="direct") == donated
    manager.clear_sessions()


def test_a_pass_out_of_order_raises_and_donates_nothing():
    """Pass u of a position takes pass u-1 of it as its input: a call that would carry a later pass past the
    pass before it is refused, on the direct path and as a row of a batched program, and nothing is donated."""
    manager = fresh_manager()
    for name in ("a", "b"):
        prompt_all_passes(manager, name)
    before = {name: held_arrays(manager, name) for name in ("a", "b")}
    with pytest.raises(ValueError, match="past the 6 that pass 1 holds"):
        manager._decode_direct(CHAIN, "a", stream(2, 1), False, 2)
    assert manager._sessions[(CHAIN[0], "a")].positions == [6] * PASSES
    # as rows of one batched program: "a" steps pass 2 ahead of pass 1 (refused), "b" steps pass 0 (served alone)
    entries = [(None, manager._sessions[(CHAIN[0], "a")], stream(2, 1), 2), (None, manager._sessions[(CHAIN[0], "b")], stream(3, 1), 0)]
    refused, served = manager._decode_batch(CHAIN[0], entries)
    assert isinstance(refused, ValueError) and "past the 6 that pass 1 holds" in str(refused) and served.shape == (1, 1, HID)
    assert all(a is b and not a.is_deleted() for a, b in zip(before["a"], held_arrays(manager, "a")))
    assert manager._sessions[(CHAIN[0], "b")].positions == [7, 6, 6, 6]
    # a prompt that arrives pass by pass stays inside the rule; one that starts at a later pass does not
    with pytest.raises(KeyError, match="unknown or expired"):
        manager._decode_direct(CHAIN, "c", stream(5, 4), True, 1)  # no entry yet: a later pass's reset makes none
    manager.clear_sessions()


def test_a_reset_of_a_later_pass_starts_that_pass_over_and_leaves_the_entry():
    manager = fresh_manager()
    prompt_all_passes(manager, "a")
    session = manager._sessions[(CHAIN[0], "a")]
    others = [session.trees[u] for u in (0, 1, 3)]
    manager._decode_direct(CHAIN, "a", stream(7, 5), True, 2)  # pass 2 re-prefilled with five positions
    assert manager._sessions[(CHAIN[0], "a")] is session and session.positions == [6, 6, 5, 6]
    assert [session.trees[u] for u in (0, 1, 3)] == others and not any(leaf.is_deleted() for tree in others for leaf in tree)
    manager.clear_sessions()


def test_a_chain_whose_blocks_disagree_on_their_passes_is_refused_when_it_is_made():
    backends = {"m.0": looped_backends()[CHAIN[0]], "m.1": backend_of("m.1", "llama_block", num_heads=2, ffn_inner=48)}
    manager = DecodeSessionManager(backends, max_len=MAX_LEN)
    with pytest.raises(ValueError, match="disagree on decode_passes"):
        manager._decode_direct(("m.0", "m.1"), "s", stream(1, 4), True)
    assert not manager._sessions, "refused before a session was made"
    assert manager._chain_passes(("m.0",)) == PASSES and manager._chain_passes(("m.1",)) == 1


def test_a_block_of_one_pass_is_served_as_ever_and_refuses_a_later_pass():
    backend = backend_of("one.0", "llama_block", num_heads=2, ffn_inner=48)
    manager = DecodeSessionManager({"one.0": backend}, max_len=MAX_LEN)
    first = counter("hivemind_moe_decode_pass_steps_total", **{"pass": 0})
    manager.decode("one.0", "s", stream(1, 5), reset=True)
    manager.decode("one.0", "s", stream(2, 1), reset=False)
    session = manager._sessions[("one.0", "s")]
    assert len(session.trees) == 1 and session.nbytes == session.row_bytes and session.index == 6
    assert counter("hivemind_moe_decode_pass_steps_total", **{"pass": 0}) - first == 1  # the step; a prompt is not counted
    with pytest.raises(ValueError, match="whose sessions hold 1 pass"):
        manager.decode("one.0", "s", stream(2, 1), reset=False, loop_pass=1)


# ---- cohorts mix passes ---------------------------------------------------------------------------


def test_a_cohort_takes_rows_whatever_their_pass():
    """Four sessions at four different passes of their next position, submitted together: ONE cohort, one
    batched program a block, and `hivemind_moe_decode_cohort_passes_total` counts the four distinct passes."""
    manager = fresh_manager()
    tokens = {}
    for row in range(PASSES):
        prompt_all_passes(manager, f"r{row}", seed=10 + row)
        x = stream(20 + row, 1)
        for u in range(row):
            x = manager._decode_direct(CHAIN, f"r{row}", x, False, u)
        tokens[row] = x
    cohorts, passes = counter("hivemind_moe_decode_cohorts_total"), counter("hivemind_moe_decode_cohort_passes_total")
    calls = counter("hivemind_moe_decode_calls_total", path="batched")

    async def together():
        return await asyncio.gather(*(manager.decode_span_async(CHAIN, f"r{row}", tokens[row], False, row) for row in range(PASSES)))

    outs = asyncio.run(together())
    assert all(out.shape == (1, 1, HID) and np.isfinite(out).all() for out in outs)
    assert counter("hivemind_moe_decode_cohorts_total") - cohorts == 1
    assert counter("hivemind_moe_decode_cohort_passes_total") - passes == PASSES
    assert counter("hivemind_moe_decode_calls_total", path="batched") - calls == len(CHAIN)
    for row in range(PASSES):
        assert manager._sessions[(CHAIN[0], f"r{row}")].positions == [6 + (u <= row) for u in range(PASSES)]
    manager.clear_sessions()


# ---- the wire and the client -----------------------------------------------------------------------


@pytest.fixture(scope="module")
def looped_swarm():
    """A server of two looped blocks, and a second one (same uids, same seed: the same weights) that takes over."""
    make = lambda **kwargs: Server.create(expert_uids=["lp.0", "lp.1"], expert_cls="ouro_block", hidden_dim=HID, expert_kwargs=LOOPED,
                                          decode_max_len=MAX_LEN, start=True, optim_factory=lambda: optax.sgd(0.0), **kwargs)
    server = make()
    maddrs = [str(m) for m in server.dht.get_visible_maddrs()]
    anchor = DHT(initial_peers=maddrs, start=True)  # keeps the swarm's addresses alive when the first server dies
    anchor_maddrs = [str(m) for m in anchor.get_visible_maddrs()]
    client_dht = DHT(initial_peers=maddrs + anchor_maddrs, start=True)
    state = {"server": server, "make": lambda: make(dht=None, initial_peers=anchor_maddrs), "client_dht": client_dht, "servers": [server]}
    try:
        wait_for_experts(client_dht, ["lp.0", "lp.1"])
        yield state
    finally:
        client_dht.shutdown()
        for each in state["servers"]:
            each.shutdown()
            each.dht.shutdown()
        anchor.shutdown()


def final_norm(x: np.ndarray) -> np.ndarray:
    return (x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)).astype(np.float32)


def walk(pipe, session: str, x: np.ndarray, reset: bool):
    """One position (or the prompt) through the loop as the client walks it: every pass's normed output."""
    outs = []
    for u in range(PASSES):
        x = final_norm(pipe.decode_step(x, session, reset=reset, loop_pass=u))
        outs.append(x)
    return outs


def test_the_client_walks_the_loop_and_the_ledger_names_each_pass(looped_swarm):
    from hivemind_tpu.telemetry.serving import SERVING_LEDGER

    pipe = RemoteSequential(looped_swarm["client_dht"], "lp.", 2)
    assert pipe._block(0).info["decode_passes"] == PASSES and pipe.decode_capacity() == MAX_LEN
    records = []
    listener = lambda kind, record: records.append(record) if kind == "serving" and record.get("kind") == "decode" else None
    SERVING_LEDGER.add_record_listener(listener)
    try:
        session = uuid.uuid4().hex
        x = stream(31, 8)
        walk(pipe, session, x[:, :6], True)
        walk(pipe, session, x[:, 6:7], False)
    finally:
        SERVING_LEDGER.remove_record_listener(listener)
    assert [record["loop_pass"] for record in records] == [0, 1, 2, 3] * 2
    manager = looped_swarm["server"].handler.decode_sessions
    assert manager._sessions[("lp.0", session)].positions == [7] * PASSES and len(pipe._decode_routes[session]["later"]) == PASSES - 1
    with pytest.raises(ValueError, match="hold 4 pass"):  # refused on the client: nothing is sent
        pipe.decode_step(x[:, 7:8], session, loop_pass=PASSES)
    assert manager._sessions[("lp.0", session)].positions == [7] * PASSES
    pipe.close_decode_session(session)


def test_an_old_client_is_served_the_first_pass_and_the_first_pass_sends_what_it_always_sent(looped_swarm, monkeypatch):
    """A client that knows no passes sends no `loop_pass`: the new server serves it pass 0 of every call (a looped
    block then behaves as a block of one pass: one cache is used). And a new client's call at pass 0 puts NO key
    on the wire, so a server of the parent's protocol sees what it always saw."""
    from hivemind_tpu.moe.client import expert as client_expert

    sent = []
    real = client_expert.MSGPackSerializer.dumps
    monkeypatch.setattr(client_expert.MSGPackSerializer, "dumps", staticmethod(lambda meta: sent.append(meta) or real(meta)))
    pipe = RemoteSequential(looped_swarm["client_dht"], "lp.", 2)
    session = uuid.uuid4().hex
    x = stream(33, 7)
    pipe.decode_step(x[:, :5], session, reset=True)  # as an old client calls it: no pass
    pipe.decode_step(x[:, 5:6], session)
    pipe.decode_step(x[:, 6:7], session, loop_pass=0)
    decode_metas = [meta for meta in sent if isinstance(meta, dict) and "session_id" in meta]
    assert len(decode_metas) == 3 and not any("loop_pass" in meta for meta in decode_metas)
    manager = looped_swarm["server"].handler.decode_sessions
    assert manager._sessions[("lp.0", session)].positions == [7, 0, 0, 0]
    pipe.decode_step(x[:, :5], session, reset=True, loop_pass=1)
    assert any(meta.get("loop_pass") == 1 for meta in sent if isinstance(meta, dict))
    pipe.close_decode_session(session)


def test_a_new_client_against_a_block_of_one_pass():
    """The reverse: a server whose blocks hold one pass (every block before ISSUE 56) says `decode_passes` 1, a call
    that names no pass or pass 0 is served as ever, and a later pass raises on the CLIENT."""
    server = Server.create(expert_uids=["op.0"], expert_cls="llama_block", hidden_dim=HID, expert_kwargs=dict(num_heads=2, ffn_inner=48),
                           decode_max_len=MAX_LEN, start=True, optim_factory=lambda: optax.sgd(0.0))
    client_dht = None
    try:
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        wait_for_experts(client_dht, ["op.0"])
        pipe = RemoteSequential(client_dht, "op.", 1)
        assert pipe._block(0).info["decode_passes"] == 1
        x = stream(35, 6)
        first = pipe.decode_step(x[:, :5], "s", reset=True, loop_pass=0)
        again = pipe.decode_step(x[:, :5], "t", reset=True)
        np.testing.assert_array_equal(first, again)
        pipe.decode_step(x[:, 5:6], "s")
        with pytest.raises(ValueError, match="hold 1 pass"):
            pipe.decode_step(x[:, 5:6], "s", loop_pass=1)
        assert server.handler.decode_sessions._sessions[("op.0", "s")].index == 6
    finally:
        if client_dht is not None:
            client_dht.shutdown()
        server.shutdown()
        server.dht.shutdown()


def test_a_peer_lost_between_two_passes_of_a_token_is_rebuilt_pass_by_pass(looped_swarm):
    """The pinned server dies between pass 1 and pass 2 of a token: the client re-prefills the replacement pass by
    pass from its per-pass histories (pass 0 and 1 with the token, pass 2 with it as the failed call's, pass 3
    without), the caller sees no reset, and every output equals an uninterrupted run's."""
    pipe = RemoteSequential(looped_swarm["client_dht"], "lp.", 2, max_retries=4)
    x = stream(37, 9)
    prompt = 6
    ref_session, session = uuid.uuid4().hex, uuid.uuid4().hex
    ref = [walk(pipe, ref_session, x[:, :prompt], True)] + [walk(pipe, ref_session, x[:, t:t + 1], False) for t in range(prompt, 9)]

    outs = [walk(pipe, session, x[:, :prompt], True), walk(pipe, session, x[:, prompt:prompt + 1], False)]
    token, got = x[:, prompt + 1:prompt + 2], []
    for u in range(2):  # passes 0 and 1 of the next token on the first server
        token = final_norm(pipe.decode_step(token, session, loop_pass=u))
        got.append(token)
    first = looped_swarm["server"]
    first.shutdown()
    first.dht.shutdown()
    replacement = looped_swarm["make"]()
    looped_swarm["servers"][:] = [replacement]
    looped_swarm["server"] = replacement
    wait_for_experts(looped_swarm["client_dht"], ["lp.0", "lp.1"], served_by=replacement.dht.peer_id)
    for u in range(2, PASSES):  # pass 2 fails over, pass 3 continues on the replacement
        token = final_norm(pipe.decode_step(token, session, loop_pass=u))
        got.append(token)
    outs.append(got)
    outs.append(walk(pipe, session, x[:, prompt + 2:prompt + 3], False))

    for position, (want_passes, got_passes) in enumerate(zip(ref, outs)):
        for u, (want, have) in enumerate(zip(want_passes, got_passes)):
            # after the failover the stepped positions were re-prefilled as a chunk: bf16 rounds them otherwise
            np.testing.assert_allclose(have, want, rtol=0, atol=3e-2 if position >= 2 else 1e-5, err_msg=f"position group {position}, pass {u}")
    manager = replacement.handler.decode_sessions
    assert manager._sessions[("lp.0", session)].positions == [prompt + 3] * PASSES
    assert any(block.peer_id == replacement.dht.peer_id for block, _span in pipe._decode_routes[session]["route"])
    pipe.close_decode_session(session)


# ---- what must not move ------------------------------------------------------------------------------

# sha256 (first 16 hex digits) of the StableHLO text that `jit(...).lower(...).as_text()` gives for the batched step at a
# bucket of 4 and for the prefill of 16 positions at the sizes below, read on the PARENT commit of ISSUE 56 (6b8827b) with
# this container's jax: the pass axis goes through the code every decode cell runs, and `ouro_block` shares the blocks'
# pieces, so neither may change what the Llama-family blocks compile. A change that MEANS to change a block's program
# reads the new digests off this test's failure message.
PARENT_PROGRAMS = {
    "llama_block": (dict(num_heads=4, num_kv_heads=2, ffn_inner=96), "d1e92b55206c847d", "2fdbafb545bcea71"),
    "olmoe_block": (dict(num_heads=4, num_experts=8, experts_per_token=2, expert_inner=32), "519d34cf51764034", "7c9cd2c59973ac65"),
    "exaone_moe_block": (dict(num_heads=4, num_kv_heads=2, head_dim=16, window=0, ffn_inner=96), "df5677746b8bd292", "10a7e004eb2fbc4b"),
}


@pytest.mark.parametrize("name", list(PARENT_PROGRAMS))
def test_the_llama_family_blocks_lower_to_the_text_they_lowered_to(name):
    kwargs, batched_digest, prefill_digest = PARENT_PROGRAMS[name]
    hidden, max_len = 64, 32
    backend = OneProgramBackend("b", name_to_block[name](hidden, **kwargs), optimizer=optax.sgd(0.0), sample_input=name_to_input[name](2, hidden),
                                max_batch_size=4, rng_seed=1)
    manager = DecodeSessionManager({"b": backend}, max_len=max_len)
    shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
    row, params = shape(manager._dummy_rows("b")), shape(backend.snapshot_params())
    batched = manager._batched_fn("b", 4).jitted.lower(params, jax.ShapeDtypeStruct((4, 1, hidden), jnp.float32), tuple((leaf,) * 4 for leaf in row),
                                                       jax.ShapeDtypeStruct((4,), jnp.int32)).as_text()
    length = (jax.ShapeDtypeStruct((), jnp.int32),) if manager._takes_length("b") else ()
    prefill = manager._step_fn("b", 1, 16).jitted.lower(params, jax.ShapeDtypeStruct((1, 16, hidden), jnp.float32), row,
                                                        jax.ShapeDtypeStruct((), jnp.int32), *length).as_text()
    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]
    assert (digest(batched), digest(prefill)) == (batched_digest, prefill_digest), (name, digest(batched), digest(prefill))
