"""A span whose blocks differ in kind behind the block server: what
`perf/runners/moe_block_server.py` does for a span of identical sparse blocks, for a
configuration that names each block's kind (`layer_types`, `mlp_layer_types`: window
or full attention, dense MLP or sparse experts) and holds a SHARE of each layer's
experts (`share`: the router's outputs, the first held expert; `num_experts` counts
the held). Each block is built with its own kwargs, and the whole is held to another
reference (`perf/reference/k_exaone_block.py`, given the same share). The server, the
load generators, the warm-up and the window are `block_server.py`'s.

`correct` is decided by what the served path produced: `check_against_reference`.
Beside the measures of the OLMoE cell (largest and rms difference, routing, the
router teacher-forced) stands one that the bf16 noise of the served arithmetic cannot
blur: for each WRONG reference (the window off by one, the bias weighing, ...) the
share of that reference's departure from the right one that is found in the served
output, position by position (`_departure_share`): about 0 for a program that
computes the model, about 1 for a program that computes the wrong thing, whatever
the noise between them. A plain run computes the three wrong references that only this
measure can tell from the served rounding; a traced run and a rehearsal compute all
eleven (each is a full-precision forward of every stream: 3 s of set-up apiece).

After a traced window the runner also sums the device time of the program's own
jitted programs by name (`programs` in the observations; the profiler's `XLA
Modules` line), which the reduced trace (`perf/trace_reduce.py`, operations only)
does not keep: `decode_program_ms.*` and `prefill_ms_per_1k_positions` read it; and
it reads the program's counters when the trace starts and when it stops
(`counters_traced`), so that `moe_experts_roofline.kexaone` takes the work and the
kernel time from the same seconds.

The block class is resolved before a DHT or a client process starts: a program that
lacks it (a parent commit) fails at once."""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

from perf import runtime
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators, warm_decode
from perf.runners.moe_block_server import _mismatch_share, _rms_err

MODULE_LINE = "XLA Modules"  # the device plane's line of whole programs, one event a run of a jitted function


def block_kwargs(config: Dict[str, Any], index: int) -> Dict[str, Any]:
    """Block ``index``'s own sizes: its kind from the configuration's per-layer lists."""
    model, share = config["model"], config["share"]
    sliding = model["layer_types"][index] == "sliding_attention"
    dense = model["mlp_layer_types"][index] == "dense"
    return dict(
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        window=model["sliding_window"] if sliding else 0, rope_theta=float(model["rope_parameters"]["rope_theta"]),
        rms_eps=model["rms_norm_eps"], ffn_inner=model["intermediate_size"] if dense else 0,
        num_experts=share["router_outputs"], experts_per_token=model["num_experts_per_tok"],
        expert_inner=model["moe_intermediate_size"], held_lo=share["held_lo"], held=model["num_experts"],
        routed_scale=model["routed_scaling_factor"],
    )


def reference_layers(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per block what the reference is told of its kind: the window (0 = full) and
    whether q and k are rotated (the model: exactly on the sliding blocks)."""
    model = config["model"]
    windows = [model["sliding_window"] if kind == "sliding_attention" else 0
               for kind in model["layer_types"][:model["num_hidden_layers"]]]
    return [{"window": window, "rope": window > 0} for window in windows]


def reference_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    model = config["model"]
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
                head_dim=model["head_dim"], experts_per_token=model["num_experts_per_tok"],
                routed_scale=model["routed_scaling_factor"], held_lo=config["share"]["held_lo"],
                rope_theta=float(model["rope_parameters"]["rope_theta"]), rms_eps=model["rms_norm_eps"])


def build_server(config: Dict[str, Any], seed: int, dht, block_factory):
    """What `Server.create` does for `expert_cls`, each block with its own kwargs, its
    weights drawn on the device from its own seed, and a frozen (`sgd(0.0)`) optimizer."""
    import optax

    from hivemind_tpu.moe import Server
    from hivemind_tpu.moe.server.layers import name_to_input
    from hivemind_tpu.moe.server.module_backend import ModuleBackend

    model, serving = config["model"], config["serving"]
    backends = {}
    for index in range(model["num_hidden_layers"]):
        uid = f"{serving['uid_prefix']}{index}"
        backends[uid] = ModuleBackend(
            uid, block_factory(model["hidden_size"], **block_kwargs(config, index)), optimizer=optax.sgd(0.0),
            sample_input=name_to_input[serving["expert_cls"]](4, model["hidden_size"]),
            max_batch_size=serving["max_batch_size"], rng_seed=(int(seed) * 64 + index) % (2**31 - 1),
        )
    server = Server(dht, backends, decode_max_len=serving["decode_max_len"],
                    decode_max_sessions=serving["decode_max_sessions"],
                    activation_compression=serving["activation_compression"])
    server.run_in_background(await_ready=True)
    return server


# ---- the reference, one jitted program a kind of block ------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_block(dtype: str, **static):
    """The reference's block under jit with ``static`` fixed, every parameter and the
    input rounded to ``dtype``: the window is a traced argument, so that the right
    window and the wrong ones are one program."""
    import jax

    from perf.reference import k_exaone_block as reference

    def run(params, x, window):
        with jax.default_matmul_precision("highest"):
            cast = lambda leaf: leaf.astype(dtype)
            return reference.block(jax.tree_util.tree_map(cast, params), cast(x), window=window,
                                   return_routing=True, **static)

    return jax.jit(run)


def reference_span(all_params, x, layers, sizes, dtype="float32", **variant):
    """`k_exaone_block.span_with_routing` block by block, each under `_jitted_block`:
    the output and each block's ``(m, top_e)``. ``variant``: the static arguments of
    `k_exaone_block.block` that make a wrong reference; ``dtype``: every parameter
    and every block's input rounded to it (float32: as they are)."""
    import jax.numpy as jnp

    routing = []
    for params, layer in zip(all_params, layers):
        x, routed = _jitted_block(dtype, rope=layer["rope"], **sizes, **variant)(params, x, jnp.int32(layer["window"]))
        routing.append(routed)
    return x, routing


@functools.lru_cache(maxsize=None)  # one function a variant: it is part of `_jitted_block`'s key
def _knobbed_route(bias_weighs: bool = False, renormalised: bool = True, scaled: bool = True, rounded: bool = False):
    """The reference's routing rule with one thing wrong (every default is the
    model's): the bias also weighs, the chosen scores are not renormalised, the scale
    is left out; ``rounded``: the router's matmul in ONE bf16 pass (operands rounded to
    bf16, exact products, float32 sums: what a TPU makes of float32 at default precision)."""
    import jax
    import jax.numpy as jnp

    def route(params, m, experts_per_token, scale):
        router = params["router"]
        if rounded:
            m, router = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (m, router))
        scores = jax.nn.sigmoid(m @ router)
        biased = scores + params["router_bias"]
        _, top_e = jax.lax.top_k(biased, experts_per_token)
        picked = (biased if bias_weighs else scores) * jax.nn.one_hot(top_e, scores.shape[-1], dtype=scores.dtype).sum(-2)
        weights = picked / picked.sum(-1, keepdims=True) if renormalised else picked
        return (scale if scaled else 1.0) * weights, top_e

    return route


NEAR_THE_ROUNDING = ("the window one short (127 of 128)", "the window one long (129 of 128)",
                     "the selection bias used as a weight")


def wrong_references(layers, every: bool = True) -> Dict[str, Any]:
    """What the limits must refuse: name -> (keyword arguments of `reference_span`,
    whether it differs from the served arithmetic in the router's precision alone).
    Each departs from the model in ONE thing (every default of `_knobbed_route` and
    of the reference's block is the model's). Without ``every``, the three that move
    the output by less than the served rounding does (`NEAR_THE_ROUNDING`): the plain
    limits cannot tell a program that computes one of them, only `_departure_share`
    can, so they decide `correct` in every run; the other eight fall outside the plain
    limits by a wide margin and are the limits' own evidence."""
    with_windows = lambda change: [dict(layer, window=change(layer["window"])) for layer in layers]
    references = {
        "the window ignored (full attention on every block)": (dict(layers=with_windows(lambda w: 0)), False),
        "the window one short (127 of 128)": (dict(layers=with_windows(lambda w: w - 1 if w else 0)), False),
        "the window one long (129 of 128)": (dict(layers=with_windows(lambda w: w + 1 if w else 0)), False),
        "rope on the full-attention blocks": (dict(layers=[dict(layer, rope=True) for layer in layers]), False),
        "the selection bias used as a weight": (dict(route=_knobbed_route(bias_weighs=True)), False),
        "the chosen scores not renormalised": (dict(route=_knobbed_route(renormalised=False)), False),
        "the scale 2.5 left out": (dict(route=_knobbed_route(scaled=False)), False),
        "the shared expert left out": (dict(shared=False), False),
        "pairs routed elsewhere not left out (every chosen expert computed, by the held expert at its number mod held)":
            (dict(absent_left_out=False), False),
        "the router's matmul in one bf16 pass": (dict(route=_knobbed_route(rounded=True)), True),
        "all bf16, router too": (dict(dtype="bfloat16"), True),
    }
    return references if every else {name: references[name] for name in NEAR_THE_ROUNDING}


def _router_mismatch_share(all_params, routing, experts_per_token: int) -> float:
    """The router alone, teacher-forced (as in the OLMoE cell): per sparse block, the
    reference's float32 router is handed the router input that the side under test
    computed, and its picks are held against that side's. The rounding of the input
    is then shared, and what is left is the router's own arithmetic."""
    import jax

    from perf.reference import k_exaone_block as reference

    choose = jax.jit(reference.chosen_experts, static_argnums=2)
    sparse = [(params, m, top_e) for params, (m, top_e) in zip(all_params, routing) if top_e is not None]
    want = [choose({"router": params["router"], "router_bias": params["router_bias"]}, m, experts_per_token)
            for params, m, _ in sparse]
    return _mismatch_share([top_e for _, _, top_e in sparse], want)


def _choices(routing) -> List:
    return [top_e for _, top_e in routing if top_e is not None]


def _departure_share(pieces) -> float:
    """How much of a wrong reference's departure from the right one is in the served
    outputs. ``pieces``: ``(got, want, wrong)`` arrays ``[.., hidden]`` of the same
    positions. Per position, the projection of ``got - want`` on ``wrong - want``
    over the latter's square; then the MEDIAN over the half of the positions where
    the departure is largest. A program that computes the model reads its rounding
    noise's chance overlap with the departure, about 0; one that computes the wrong
    thing about 1, whatever the noise beside it. The median, because a near-tie of
    the router that the served rounding flips is often flipped by the wrong
    reference's own perturbation too: those few positions move by a whole expert on
    both sides, and would carry a mean."""
    import numpy as np

    flat = lambda a: np.asarray(a, np.float64).reshape(-1, np.shape(a)[-1])
    error = np.concatenate([flat(got) - flat(want) for got, want, _ in pieces])
    departure = np.concatenate([flat(wrong) - flat(want) for _, want, wrong in pieces])
    size = (departure * departure).sum(-1)
    largest = size >= np.median(size)
    return float(np.median((error * departure).sum(-1)[largest] / np.maximum(size[largest], 1e-30)))


@functools.lru_cache(maxsize=None)
def _routing_apply(module):
    import jax

    from hivemind_tpu.moe.server.routing_stats import ROUTING_COLLECTION

    return jax.jit(lambda p, x: module.apply(
        {"params": p}, x, mutable=[ROUTING_COLLECTION, "intermediates"],
        capture_intermediates=lambda submodule, _method: submodule.name == "ffn_norm"))


def program_routing(modules, all_params, x) -> List:
    """The program's blocks chained on ``x`` (their forward without a cache): per
    block ``(m, top_e)``, the router's input as the program computed it (its ffn
    norm's output) and the experts it chose (None for a dense block)."""
    import jax

    from hivemind_tpu.moe.server.routing_stats import ROUTING_COLLECTION

    routing = []
    for module, params in zip(modules, all_params):
        x, state = _routing_apply(module)(params, x)
        [m] = jax.tree_util.tree_leaves(state["intermediates"])
        chosen = jax.tree_util.tree_leaves(state.get(ROUTING_COLLECTION, {}))
        routing.append((m, chosen[0] if chosen else None))
    return routing


def check_against_reference(server, client_dht, config, seed, rehearse, log, every_wrong_reference=True) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full
    forward with the same held share, of what the served path produced: 8 streams of
    ``prompt + steps`` positions; (1) stream 0's prefill (a prompt that is no power of
    two and longer than twice the window, so the ring takes the last window of REAL
    positions) and single-token steps through the span over the wire; (2) all 8 as
    sessions at different positions that step in the same batched programs (the ring
    wraps, the two cache shapes walk one chain); (3) the experts the program's blocks
    choose, against the reference's own and against the reference's router on the
    program's own router inputs. Then the wrong references of `wrong_references` (all
    of them, or with ``every_wrong_reference`` off the three near the rounding):
    its own readings on these measures, and how much of its departure the served
    outputs hold (`_departure_share`). One that differs from the served arithmetic in
    the router's precision alone has to fall outside the routing limits; of every
    other the served outputs must hold little (a program that computed it would hold
    all of it, whatever the rounding noise)."""
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential

    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    prompt, steps, rows = (20, 12, 4) if rehearse else (320, 192, 8)
    hidden, blocks = model["hidden_size"], model["num_hidden_layers"]
    uids = [f"{serving['uid_prefix']}{i}" for i in range(blocks)]
    all_params = [server.backends[uid].snapshot_params() for uid in uids]
    layers, sizes, k = reference_layers(config), reference_sizes(config), model["num_experts_per_tok"]
    manager = server.handler.decode_sessions
    rng = np.random.default_rng(seed)
    streams = runtime.float16_exact(rng.standard_normal((rows, prompt + steps, hidden), dtype=np.float32))
    faults = []

    want, want_routing = reference_span(all_params, jnp.asarray(streams), layers, sizes)
    want = np.asarray(want)

    # (1) over the wire, one session
    pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
    chunks = [pipe.decode_step(streams[:1, :prompt], "reference-check", reset=True)]
    for position in range(prompt, prompt + steps):
        chunks.append(pipe.decode_step(streams[:1, position:position + 1], "reference-check"))
    pipe.close_decode_session("reference-check")
    single = np.concatenate(chunks, axis=1)
    single_err, single_rms = runtime.rel_err(single, want[:1]), _rms_err(single, want[:1])
    log(f"reference check: prefill {prompt} + {steps} steps through the caches, {single_err:.2e} of the largest value, {single_rms:.2e} rms")
    if not (single_err <= tolerances["decode_rel"] and single_rms <= tolerances["decode_rms_rel"]):
        faults.append(f"prefill {prompt} + {steps} steps through the caches is {single_err:.2e} of the largest value and "
                      f"{single_rms:.2e} rms from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

    # (2) the batched programs: row 0 is that stream, the others start from shorter prompts
    prompts = [prompt - max(prompt // 40, 1) * row for row in range(rows)]
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
    served = [(np.concatenate(got[row], axis=1), slice(0, length + steps)) for row, length in enumerate(prompts)]
    batched_err = max(float(np.abs(out - want[row, span]).max() / scale) for row, (out, span) in enumerate(served))
    batched_rms = max(_rms_err(out, want[row:row + 1, span]) for row, (out, span) in enumerate(served))
    log(f"reference check: {rows} sessions at positions {prompts} stepping {steps} times in the same batched "
        f"programs, {batched_err:.2e} of the largest value, {batched_rms:.2e} rms (worst row of each)")
    if not (batched_err <= tolerances["decode_rel"] and batched_rms <= tolerances["decode_rms_rel"]):
        faults.append(f"{rows} sessions in one batched program are {batched_err:.2e} of the largest value and "
                      f"{batched_rms:.2e} rms from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

    # (3) routing: the program's blocks, chained, on every stream
    modules = [server.backends[uid].module for uid in uids]
    routing = program_routing(modules, all_params, jnp.asarray(streams))
    mismatch = _mismatch_share(_choices(routing), _choices(want_routing))
    pairs = sum(top_e.size for top_e in _choices(routing))
    log(f"reference check: {mismatch:.4%} of {pairs} (token, slot) pairs chose an expert outside the reference's set")
    if not mismatch <= tolerances["routing_mismatch_share"]:
        faults.append(f"{mismatch:.4%} of (token, slot) pairs differ from the reference's routing, over "
                      f"{tolerances['routing_mismatch_share']:.2%}")
    router = _router_mismatch_share(all_params, routing, k)
    log(f"reference check: on the program's own router inputs, {router:.4%} of {pairs} pairs chose an expert that the "
        f"reference's float32 router does not")
    if not router <= tolerances["router_mismatch_share"]:
        faults.append(f"{router:.4%} of pairs differ from the float32 router on the same inputs, over "
                      f"{tolerances['router_mismatch_share']:.3%}: the program's router is not computed in float32")

    # every wrong reference: it must fail a limit, and the served outputs must hold little of its departure.
    # A rehearsal's few hundred pairs hold too few near-ties to tell a router's precision: not faulted there
    for name, (variant, router_alone) in wrong_references(layers, every_wrong_reference).items():
        out, wrong_routing = reference_span(all_params, jnp.asarray(streams), sizes=sizes, **{"layers": layers, **variant})
        out = np.asarray(out, np.float32)
        largest, rms = runtime.rel_err(out, want), _rms_err(out, want)
        routed = _mismatch_share(_choices(wrong_routing), _choices(want_routing))
        own_router = _router_mismatch_share(all_params, wrong_routing, k)
        holds = max(abs(_departure_share([(single, want[:1], out[:1])])),  # over the wire; in the batched programs
                    abs(_departure_share([(o, want[row:row + 1, span], out[row:row + 1, span]) for row, (o, span) in enumerate(served)])))
        outside = (largest > tolerances["decode_rel"] or rms > tolerances["decode_rms_rel"]
                   or routed > tolerances["routing_mismatch_share"] or own_router > tolerances["router_mismatch_share"])
        log(f"for the record, the reference with {name}: {largest:.2e} of the largest value, {rms:.2e} rms, {routed:.4%} of "
            f"pairs routed otherwise, {own_router:.4%} on its own router inputs: {'outside' if outside else 'inside'} "
            f"those limits; the served outputs hold {holds:.3f} of its departure")
        if router_alone:  # the served arithmetic differs from it in the router alone: the routing limits have to tell
            if not outside and not rehearse:
                faults.append(f"the limits let a reference with {name} pass")
        elif not holds <= tolerances["departure_share"]:  # a program that computes it reads 1 here, whatever the noise
            faults.append(f"the served outputs hold {holds:.3f} of the departure of a reference with {name}, over "
                          f"{tolerances['departure_share']}: the program computes that, not the model")
    manager.clear_sessions()  # the check's caches leave the device before the window
    return faults


def program_seconds(trace_dir) -> Dict[str, Dict[str, float]]:
    """Device seconds and runs of every jitted program in the newest trace under
    ``trace_dir``, by the program's name without its id (`jit_batched_step_window`),
    averaged over the device planes. Empty where no trace or no such line is found."""
    from perf.trace_reduce import DEVICE_PLANE, find_xplane, load_planes

    path = find_xplane(str(trace_dir))
    if path is None:
        return {}
    planes = {name: lines for name, lines in load_planes(path).items() if DEVICE_PLANE.match(name)}
    programs: Dict[str, Dict[str, float]] = {}
    for lines in planes.values():
        for name, _start, duration in lines.get(MODULE_LINE, []):
            entry = programs.setdefault(name.split("(", 1)[0], {"seconds": 0.0, "count": 0.0})
            entry["seconds"] += duration / 1e9 / len(planes)
            entry["count"] += 1.0 / len(planes)
    return programs


class _TraceEdges:
    """The program's counters when the trace goes on and when it goes off, taken on a
    thread of its own (`runtime.Tracer.active` is set for exactly the traced seconds)."""

    def __init__(self, tracer):
        import threading

        self._tracer, self._before, self._after = tracer, None, None
        self._thread = threading.Thread(target=self._watch, name="perf-trace-edges", daemon=True)
        self._thread.start()

    def _watch(self) -> None:
        self._tracer.active.wait()
        self._before = runtime.counters()
        while self._tracer.active.is_set():
            time.sleep(0.01)
        self._after = runtime.counters()

    def counters(self) -> Dict[str, Any]:
        self._thread.join(timeout=5.0)
        return {"before": self._before, "after": self._after} if self._after is not None else {}


def _percentiles(values, points=(50, 90, 95, 99)) -> str:
    import numpy as np

    return " / ".join(f"{np.percentile(values, q):.1f}" for q in points) if len(values) else "-"


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
        kinds = [f"{'dense' if kw['ffn_inner'] else 'sparse'}/{'window' if kw['window'] else 'full'}"
                 for kw in (block_kwargs(config, index) for index in range(model["num_hidden_layers"]))]
        log(f"{len(kinds)} blocks ({', '.join(kinds)}) hidden {model['hidden_size']} / {model['num_attention_heads']} x "
            f"{model['head_dim']} heads / {model['num_key_value_heads']} kv / window {model['sliding_window']} / experts "
            f"{model['num_experts']} held of {config['share']['router_outputs']}, {model['num_experts_per_tok']} a token, on the "
            f"device in {time.monotonic() - built:.1f} s")
        # the clients start now and connect while this process compiles
        lead = float(traffic.get("lead_seconds", 0.0))
        loadgen = LoadGenerators(traffic["generator"], plan, config, maddrs, lead_seconds=lead, drain_seconds=120.0)
        warm = time.monotonic()
        warm_decode(server, config, traffic, log)
        server.handler.decode_sessions.clear_sessions()  # `warm_decode` empties the table itself: the gauges too
        log(f"warm-up took {time.monotonic() - warm:.1f} s; {watch.count()} compilations so far")
        checked = time.monotonic()
        faults = check_against_reference(server, client_dht, config, seed, rehearse, log,
                                         every_wrong_reference=bool(trace) or rehearse)
        log(f"the reference check took {time.monotonic() - checked:.1f} s")
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (slots already at work, uncounted) is set-up
        setup_s = begin - started
        loadgen.go(begin, begin + seconds)
        edges = _TraceEdges(tracer) if tracer is not None else None
        if tracer is not None:
            tracer.start()
        time.sleep(max(begin - time.monotonic(), 0.0))  # the lead-in's records and counts are not the window's
        tap.drain()
        compiles_before, counters_before = watch.count(), runtime.counters()
        results = loadgen.collect(timeout=lead + seconds + 240.0)
        compiles_after, counters_after = watch.count(), runtime.counters()
        records = tap.drain()
        traced = tracer.finish() if tracer is not None else {}
        programs = program_seconds(runtime.TRACE_DIR) if traced else {}
        counters_traced = edges.counters() if traced else {}
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
    log(f"window {seconds:.1f} s: {attempted} attempted, {completed} completed, {failed} failed, {tokens} tokens, "
        f"{len(samples.get('ttft_ms', []))} prefills; {len(serving)} requests served, {len(shed)} ended in an error on the "
        f"server; set-up {setup_s:.1f} s")
    # what tells a slow run's cause in a plain run's log: how the sessions travelled (rows a batched program,
    # cohorts), the gaps' distribution, each prefill's time to its first token
    from perf.readers.counter_ratio import delta

    moved = {"counters": {"before": counters_before, "after": counters_after}}
    programs_run, rows_run = (delta(moved, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"})
                              for name in ("calls", "steps"))
    cohorts = delta(moved, {"metric": "hivemind_moe_decode_cohorts_total"})
    log(f"window: {cohorts:.0f} cohorts, {programs_run:.0f} batched programs of {rows_run / max(programs_run, 1):.2f} rows; "
        f"gap ms p50 / p90 / p95 / p99 {_percentiles(samples.get('token_gap_ms', []))}, largest "
        f"{max(samples.get('token_gap_ms', [0.0])):.0f}; ttft ms {sorted(round(t) for t in samples.get('ttft_ms', []))}; "
        f"server ms a decode request p50 / p90 / p95 / p99 "
        f"{_percentiles([1e3 * r['total_s'] for r in serving if r.get('kind') == 'decode' and 'total_s' in r])}")
    for name, entry in sorted(programs.items(), key=lambda item: -item[1]["seconds"])[:12]:
        log(f"traced program {name}: {entry['count']:.0f} runs, {entry['seconds'] * 1e3:.1f} ms")
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
        "programs": programs,
        **({"counters_traced": counters_traced} if counters_traced else {}),
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
