"""A span of sparse-expert decoder blocks behind the block server: what
`perf/runners/block_server.py` does for dense blocks, for a configuration whose block
takes other sizes (experts, experts per token) and is held to another reference
(`perf/reference/olmoe_block.py`). The server, the load generators, the warm-up and the
window are the same; `build_server`, `check_against_reference` and `run` are this
file's own because that file builds and checks `llama_block` by name and may not be
edited by the PR that adds this configuration (PERF.md section 7: a `benchmark` issue
folds the two by letting the configuration name its block kwargs and its reference).

The block class is resolved before a DHT or a client process starts: a program that
lacks it (a parent commit) fails at once."""

from __future__ import annotations

import time
from typing import Any, Dict, List

from perf import runtime
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators, warm_decode


def _block_kwargs(model: Dict[str, Any]) -> Dict[str, Any]:
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
                num_experts=model["num_experts"], experts_per_token=model["num_experts_per_tok"],
                expert_inner=model["intermediate_size"], rope_theta=float(model["rope_theta"]),
                rms_eps=model["rms_norm_eps"], head_dim=model["hidden_size"] // model["num_attention_heads"])


def _reference_sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
                experts_per_token=model["num_experts_per_tok"], rope_theta=float(model["rope_theta"]),
                rms_eps=model["rms_norm_eps"])


def build_server(config: Dict[str, Any], seed: int, dht, block_factory):
    """What `Server.create` does for `expert_cls`, with each block's weights drawn
    on the device from its own seed and a frozen (`sgd(0.0)`) optimizer."""
    import optax

    from hivemind_tpu.moe import Server
    from hivemind_tpu.moe.server.layers import name_to_input
    from hivemind_tpu.moe.server.module_backend import ModuleBackend

    model, serving = config["model"], config["serving"]
    backends = {}
    for index in range(model["num_hidden_layers"]):
        uid = f"{serving['uid_prefix']}{index}"
        backends[uid] = ModuleBackend(
            uid, block_factory(model["hidden_size"], **_block_kwargs(model)), optimizer=optax.sgd(0.0),
            sample_input=name_to_input[serving["expert_cls"]](4, model["hidden_size"]),
            max_batch_size=serving["max_batch_size"], rng_seed=(int(seed) * 64 + index) % (2**31 - 1),
        )
    server = Server(dht, backends, decode_max_len=serving["decode_max_len"],
                    decode_max_sessions=serving["decode_max_sessions"],
                    activation_compression=serving["activation_compression"])
    server.run_in_background(await_ready=True)
    return server


def _mismatch_share(got: List, want: List) -> float:
    """Share of (token, slot) pairs whose expert is not in the other side's set for
    that token, over all blocks; each a [batch, seq, k] array of chosen experts."""
    import numpy as np

    differing = total = 0
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        differing += int((~(a[..., :, None] == b[..., None, :]).any(-1)).sum())
        total += a.size
    return differing / max(total, 1)


def _router_mismatch_share(reference, all_params, routing, experts_per_token: int) -> float:
    """The router alone, teacher-forced: ``routing`` is, per block, the router's input
    as the side under test computed it and the experts that side chose from it; the
    reference's float32 router is handed the SAME input. The rounding of the input is
    then shared, and what is left is the router's own arithmetic: a float32 router
    reads about 0, a router whose matmul is one bf16 pass a measurable share."""
    import jax

    choose = jax.jit(reference.chosen_experts, static_argnums=2)
    want = [choose({"router": params["router"]}, m, experts_per_token) for params, (m, _) in zip(all_params, routing)]
    return _mismatch_share([top_e for _, top_e in routing], want)


def _program_routing(module, all_params, x) -> List:
    """The program's blocks chained on ``x``: per block ``(m, top_e)``, the router's
    input as the program computed it (its ffn norm's output) and the experts it chose."""
    import jax

    from hivemind_tpu.moe.server.routing_stats import ROUTING_COLLECTION

    apply = jax.jit(lambda p, x: module.apply(
        {"params": p}, x, mutable=[ROUTING_COLLECTION, "intermediates"],
        capture_intermediates=lambda submodule, _method: submodule.name == "ffn_norm"))
    routing = []
    for params in all_params:
        x, state = apply(params, x)
        [m] = jax.tree_util.tree_leaves(state["intermediates"])
        [top_e] = jax.tree_util.tree_leaves(state[ROUTING_COLLECTION])
        routing.append((m, top_e))
    return routing


def _rms_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).mean() / (want**2).mean()))


def _wrong_references(reference, sizes) -> Dict[str, Any]:
    """What the limits must refuse, each a variant of the reference itself,
    ``(run(all_params, x) -> (out, routing), router_alone)``: the two nearest
    precisions below the configuration's float32 router (its matmul in one bf16 pass,
    which is what a TPU makes of float32 operands at default precision; every value
    and the router in bf16), float8 values, the top-k weights renormalised, the
    weakest chosen expert dropped. The first two differ from the served arithmetic in
    the router alone (``router_alone``): only the teacher-forced measure refuses them."""
    import jax
    import jax.numpy as jnp

    def in_dtype(cast):
        def run(all_params, x):
            x, routing = cast(x), []
            for params in all_params:
                x, routed = reference.block(jax.tree_util.tree_map(cast, params), cast(x), return_routing=True, **sizes)
                routing.append(routed)
            return x, routing
        return run

    def with_route(route):
        return lambda all_params, x: reference.span_with_routing(all_params, x, route=route, **sizes)

    def reweighted(wrong):
        return with_route(lambda params, m, k: wrong(*reference.route(params, m, k)))

    def one_bf16_pass(params, m, k):  # bf16-valued operands, exact products, float32 sums
        rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
        return reference.route({"router": rounded(params["router"])}, rounded(m), k)

    def drop_weakest(weights, top_e):
        weakest = jnp.where(weights > 0, weights, jnp.inf).min(-1, keepdims=True)
        return jnp.where(weights == weakest, 0.0, weights), top_e

    return {
        "the router's matmul in one bf16 pass": (with_route(one_bf16_pass), True),
        "all bf16, router too": (in_dtype(lambda t: t.astype(jnp.bfloat16)), True),
        "float8 weights and block inputs": (in_dtype(lambda t: t.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)), False),
        "top-k renormalised": (reweighted(lambda weights, top_e: (weights / weights.sum(-1, keepdims=True), top_e)), False),
        "weakest chosen expert dropped": (reweighted(drop_weakest), False),
    }


def _readings_for_the_record(reference, all_params, streams, want, want_routing, sizes, tolerances, rehearse, log) -> List[str]:
    """Every wrong reference of `_wrong_references` on the check's streams, logged with
    the four measures; limits that let one pass are at fault. A rehearsal's few hundred
    pairs hold too few near-ties to tell a router's precision, so there the limits are
    not faulted for the two that differ in the router alone."""
    import jax
    import jax.numpy as jnp

    k, faults = sizes["experts_per_token"], []
    want_choices = [top_e for _, top_e in want_routing]
    for name, (run, router_alone) in _wrong_references(reference, sizes).items():
        out, routing = jax.jit(run)(all_params, jnp.asarray(streams))
        largest, rms = runtime.rel_err(out, want), _rms_err(out, want)
        routed = _mismatch_share([top_e for _, top_e in routing], want_choices)
        router = _router_mismatch_share(reference, all_params, routing, k)
        passes = (largest <= tolerances["decode_rel"] and rms <= tolerances["decode_rms_rel"]
                  and routed <= tolerances["routing_mismatch_share"] and router <= tolerances["router_mismatch_share"])
        log(f"for the record, the reference with {name}: {largest:.2e} of the largest value, {rms:.2e} rms, "
            f"{routed:.4%} of pairs routed otherwise, {router:.4%} on its own router inputs: "
            f"{'inside' if passes else 'outside'} the limits")
        if passes and not (rehearse and router_alone):
            faults.append(f"the limits let a reference with {name} pass")
    return faults


def check_against_reference(server, client_dht, config, seed, rehearse, log) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full
    forward: (1) one session's prefill and single-token steps through the span over
    the wire; (2) the same stream decoded as one of 8 sessions at different positions
    that step in the same batched programs, every row compared; (3) the experts the
    program's blocks choose on the 8 streams: against the reference's own choices, and
    against the reference's router on the program's own router inputs
    (`_router_mismatch_share`). Outputs are held to two measures: the largest
    difference as a share of the largest value (a near-tie that bf16 flips moves ONE
    token by a whole expert, and sets this one) and the rms difference over the rms
    value (which a flip barely moves and a term dropped at every token moves a lot).
    Beside them `_readings_for_the_record`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential
    from perf.reference import olmoe_block as reference

    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    prefill, steps, others = (16, 4, 3) if rehearse else (128, 16, 7)
    hidden, blocks = model["hidden_size"], model["num_hidden_layers"]
    uids = [f"{serving['uid_prefix']}{i}" for i in range(blocks)]
    all_params = [server.backends[uid].snapshot_params() for uid in uids]
    sizes = _reference_sizes(model)
    manager = server.handler.decode_sessions
    rng = np.random.default_rng(seed)
    streams = runtime.float16_exact(rng.standard_normal((1 + others, prefill + steps, hidden), dtype=np.float32))
    faults = []

    want, want_routing = jax.jit(lambda p, x: reference.span_with_routing(p, x, **sizes))(all_params, jnp.asarray(streams))
    want = np.asarray(want)

    # (1) over the wire, one session
    pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
    chunks = [pipe.decode_step(streams[:1, :prefill], "reference-check", reset=True)]
    for position in range(prefill, prefill + steps):
        chunks.append(pipe.decode_step(streams[:1, position:position + 1], "reference-check"))
    pipe.close_decode_session("reference-check")
    single = np.concatenate(chunks, axis=1)
    single_err, single_rms = runtime.rel_err(single, want[:1]), _rms_err(single, want[:1])
    log(f"reference check: prefill {prefill} + {steps} steps through the cache, {single_err:.2e} of the largest value, {single_rms:.2e} rms")
    if not (single_err <= tolerances["decode_rel"] and single_rms <= tolerances["decode_rms_rel"]):
        faults.append(f"prefill {prefill} + {steps} steps through the cache is {single_err:.2e} of the largest value and "
                      f"{single_rms:.2e} rms from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

    # (2) the batched program: row 0 is that stream, the others start from shorter prompts
    prompts = [prefill - (prefill // 16) * row for row in range(1 + others)]
    got = [[] for _ in prompts]
    for row, length in enumerate(prompts):
        x = streams[row:row + 1, :length]
        for uid in uids:
            x = manager.decode(uid, f"reference-row{row}", x, reset=True)
        got[row].append(x)
    for step in range(steps):
        xs = [streams[row:row + 1, length + step:length + step + 1] for row, length in enumerate(prompts)]
        for uid in uids:
            entries = [(None, manager._sessions[(uid, f"reference-row{row}")], x) for row, x in enumerate(xs)]
            xs = manager._decode_batch(uid, entries)
            raised = [out for out in xs if isinstance(out, Exception)]
            if raised:
                raise raised[0]
        for row, x in enumerate(xs):
            got[row].append(x)
    scale = np.abs(want).max()
    rows = [(np.concatenate(got[row], axis=1), want[row:row + 1, :length + steps]) for row, length in enumerate(prompts)]
    batched_err = max(float(np.abs(out - ref).max() / scale) for out, ref in rows)
    batched_rms = max(_rms_err(out, ref) for out, ref in rows)
    log(f"reference check: {1 + others} sessions at positions {prompts} stepping {steps} times in the same batched "
        f"programs, {batched_err:.2e} of the largest value, {batched_rms:.2e} rms (worst row of each)")
    if not (batched_err <= tolerances["decode_rel"] and batched_rms <= tolerances["decode_rms_rel"]):
        faults.append(f"{1 + others} sessions in one batched program are {batched_err:.2e} of the largest value and "
                      f"{batched_rms:.2e} rms from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

    # (3) routing: the program's blocks, chained, on every stream
    routing = _program_routing(server.backends[uids[0]].module, all_params, jnp.asarray(streams))
    mismatch = _mismatch_share([top_e for _, top_e in routing], [top_e for _, top_e in want_routing])
    log(f"reference check: {mismatch:.4%} of (token, slot) pairs over {blocks} blocks chose an expert outside the reference's set")
    if not mismatch <= tolerances["routing_mismatch_share"]:
        faults.append(f"{mismatch:.4%} of (token, slot) pairs differ from the reference's routing, over "
                      f"{tolerances['routing_mismatch_share']:.2%}")
    router = _router_mismatch_share(reference, all_params, routing, sizes["experts_per_token"])
    log(f"reference check: on the program's own router inputs, {router:.4%} of {routing[0][1].size * blocks} pairs chose an "
        f"expert that the reference's float32 router does not")
    if not router <= tolerances["router_mismatch_share"]:
        faults.append(f"{router:.4%} of pairs differ from the float32 router on the same inputs, over "
                      f"{tolerances['router_mismatch_share']:.3%}: the program's router is not computed in float32")

    faults += _readings_for_the_record(reference, all_params, streams, want, want_routing, sizes, tolerances, rehearse, log)
    with manager._lock:  # the check's caches leave the device before the window
        manager._sessions.clear()
    return faults


def run(*, config, workload, chips, seed, seconds, trace, rehearse, started, log) -> Dict[str, Any]:
    import jax

    from hivemind_tpu.moe.server.layers import name_to_block

    model = config["model"]
    block_factory = name_to_block[config["serving"]["expert_cls"]]  # before any DHT or client: a program without it stops here

    from hivemind_tpu.dht import DHT

    traffic = workload["traffic"]
    if rehearse:  # the toy block's cache is short: the cell's rehearsal lengths fit it
        traffic = {**traffic, **workload.get("rehearsal_traffic", {})}
    generator = plugin("traffic", traffic["generator"])
    if generator.SERVER_PATH != "decode":
        raise ValueError(f"this runner warms and checks decode sessions only, not {generator.SERVER_PATH!r}")
    plan = generator.schedule(traffic, seed)
    watch, tap = runtime.CompileWatch(), runtime.LedgerTap()
    devices = jax.devices()[:chips]

    server_dht = DHT(start=True)
    maddrs = [str(m) for m in server_dht.get_visible_maddrs()]
    client_dht = DHT(initial_peers=maddrs, start=True)
    loadgen = None
    server = None
    try:
        built = time.monotonic()
        server = build_server(config, seed, server_dht, block_factory)
        log(f"{model['num_hidden_layers']} blocks hidden {model['hidden_size']} / {model['num_attention_heads']} heads / "
            f"{model['num_experts']} experts of {model['intermediate_size']}, {model['num_experts_per_tok']} a token, on the "
            f"device in {time.monotonic() - built:.1f} s")
        # the clients start now and connect while this process compiles
        lead = float(traffic.get("lead_seconds", 0.0))
        loadgen = LoadGenerators(traffic["generator"], plan, config, maddrs, lead_seconds=lead, drain_seconds=120.0)
        warm = time.monotonic()
        warm_decode(server, config, traffic, log)
        log(f"warm-up took {time.monotonic() - warm:.1f} s; {watch.count()} compilations so far")
        faults = check_against_reference(server, client_dht, config, seed, rehearse, log)
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (slots already at work, uncounted) is set-up
        setup_s = begin - started
        loadgen.go(begin, begin + seconds)
        if tracer is not None:
            tracer.start()
        time.sleep(max(begin - time.monotonic(), 0.0))  # the lead-in's records and counts are not the window's
        tap.drain()
        compiles_before, counters_before = watch.count(), runtime.counters()
        results = loadgen.collect(timeout=lead + seconds + 240.0)
        compiles_after, counters_after = watch.count(), runtime.counters()
        records = tap.drain()
        traced = tracer.finish() if tracer is not None else {}
        memory_peak = runtime.memory_peak_bytes(devices, log)
    finally:
        if loadgen is not None:
            loadgen.stop()
        tap.close()
        if server is not None:
            server.shutdown()
        client_dht.shutdown()
        server_dht.shutdown()

    slots = [slot for result in results for slot in result["slots"]]
    samples: Dict[str, List[float]] = {}
    for slot in slots:
        for key, value in slot.items():
            if isinstance(value, list) and key != "errors":
                samples.setdefault(key, []).extend(value)
    attempted, failed = sum(s["attempted"] for s in slots), sum(s["failed"] for s in slots)
    tokens, completed = sum(s["tokens"] for s in slots), sum(s["completed"] for s in slots)
    stuck = sum(result["stuck_slots"] for result in results)
    for error in sorted({e for s in slots for e in s["errors"]})[:5]:
        log(f"client error: {error}")
    serving = [r for r in records["serving"] if "error" not in r]
    shed = [r for r in records["serving"] if "error" in r]
    if compiles_after != compiles_before:
        faults.append(f"{compiles_after - compiles_before} compilation(s) inside the window: the warm-up missed a shape")
    if stuck:
        faults.append(f"{stuck} client slot(s) never returned")
    if not tokens:
        faults.append("no work completed inside the window")
    log(f"window {seconds:.1f} s: {attempted} attempted, {completed} completed, {failed} failed, {tokens} tokens; "
        f"{len(serving)} requests served, {len(shed)} ended in an error on the server; set-up {setup_s:.1f} s")
    for fault in faults:
        log(f"FAULT: {fault}")
    return {
        "correct": not faults,
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "window_s": seconds,
        "counts": {"tokens": tokens, "sessions": completed, "requests": completed},
        "samples": samples,
        "counters": {"before": counters_before, "after": counters_after},
        "serving": serving,
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
