"""A span of GraniteMoeHybrid blocks without experts (ibm-granite/granite-4.0-h-micro) behind
the block server: EVERY block a mixer and a gated MLP under two scaled residuals, the mixer a
Mamba-2 state-space mixer (`mamba`) or a grouped-query attention without position embedding
(`attention`) by the block's entry of `layer_types`; prompts that arrive in chunks; a chain of
20 uids. The whole is held to `perf/reference/granite_h_block.py`.

Nothing here is copied that could be imported: the load generators are `block_server.py`'s;
the programs' device time by name, the counters at the trace's edges, the share of a wrong
reference's departure and the log's percentiles `hybrid_moe_block_server.py`'s; the rms
`moe_block_server.py`'s; the warm-up of chunked prompts, the check's prompts and widths and the
scope of an instruction `sala_block_server.py`'s; the check's shape, the recurrent state's
error and dtype, the limits' verdict, the compiler's staging copies and the device time by
named scope in programs of several names and buckets `nemotron_block_server.py`'s. Its own,
because theirs do not fit and a file the benchmark has may not be edited: `build_server`
(theirs call their own module's `block_kwargs`); the reference's jitted block and the wrong
references (another model's); `batched_programs` (theirs reads its own module's scopes: this
span's programs also hold `shared_mlp` and `nope_attend`); the check, which has no router to
hold and holds the residual multiplier, the attention's scale and the MLP's halves instead.

`correct` is decided by what the served path produced (`check_against_reference`): at the
published widths, against the float32 reference at the highest matmul precision, stream by
stream: the span's output hidden states (largest and rms difference), the recurrent state of
the FIRST mixer after the last position (block 0: its input is the stream itself), the dtype
the served sessions keep their states in, and for each WRONG reference how much of its
departure the served outputs hold.

The lead-in of this runner's cell holds every prefill, so the runner reads the program's
counters when the lead-in starts (`counters_lead`); it reads them again at the trace's edges
(`counters_traced`), and after a traced window it sums the device time of the batched
programs' operations by named scope (`scopes`: `ssm_conv`, `ssm_step`, `shared_mlp`,
`nope_attend`, and `ssm_staging`: the compiler's own copies of a row's state into on-chip
memory). `param_bytes` is what one block of each kind keeps on the device, by its arrays'
own dtypes.

The block class is resolved before a DHT or a client process starts: a program that lacks
it (a parent commit) fails at once."""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional

from perf import runtime
from perf.flops_granite import head_dim  # `assumed.head_dim`: hidden / heads, config.json has none
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators
from perf.runners.hybrid_moe_block_server import _departure_share, _percentiles, _TraceEdges, program_seconds
from perf.runners.latent_moe_block_server import _frozen
from perf.runners.moe_block_server import _rms_err
from perf.runners.nemotron_block_server import PADDING, _state_err, check_shape, instruction_scopes, judge, scope_seconds, state_dtype_faults
from perf.runners.sala_block_server import check_prompts, check_widths, filler_prompt, scope_of_instructions, warm_decode

SCOPES = ("shared_mlp", "nope_attend")  # beside `nemotron_block_server.SCOPES` (`ssm_conv`, `ssm_step`, `ssm_scan`) and its staging copies


def kinds(config: Dict[str, Any]) -> List[str]:
    """The kind of each block of the span: the (cut) `layer_types`."""
    model = config["model"]
    assert len(model["layer_types"]) == model["num_hidden_layers"] and set(model["layer_types"]) <= {"mamba", "attention"}, model["layer_types"]
    return list(model["layer_types"])


def block_kwargs(config: Dict[str, Any], index: int) -> Dict[str, Any]:
    """Block ``index``'s own sizes: its kind by `layer_types`, every size from the published keys."""
    model = config["model"]
    assert model["mamba_expand"] * model["hidden_size"] == model["mamba_n_heads"] * model["mamba_d_head"], "expand x hidden is not heads x head_dim"
    assert model["num_local_experts"] == 0 and model["position_embedding_type"] == "nope", "this runner builds no expert and no rotary"
    return dict(
        kind=kinds(config)[index], rms_eps=model["rms_norm_eps"],
        mamba_heads=model["mamba_n_heads"], mamba_head_dim=model["mamba_d_head"], ssm_groups=model["mamba_n_groups"],
        ssm_state=model["mamba_d_state"], conv_kernel=model["mamba_d_conv"], chunk_size=model["mamba_chunk_size"],
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"], head_dim=head_dim(model),
        ffn_inner=model["shared_intermediate_size"], residual_multiplier=model["residual_multiplier"],
        attention_multiplier=model["attention_multiplier"],
    )


def reference_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keyword arguments of the reference's `block`, as the configuration has them."""
    model = config["model"]
    return dict(
        rms_eps=model["rms_norm_eps"], residual_multiplier=model["residual_multiplier"], mamba_heads=model["mamba_n_heads"],
        mamba_head_dim=model["mamba_d_head"], ssm_groups=model["mamba_n_groups"], ssm_state=model["mamba_d_state"],
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"], head_dim=head_dim(model),
        attention_multiplier=model["attention_multiplier"],
    )


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


def cohort_rows(slots: int) -> int:
    """The most rows a batched program of this traffic holds: with more than 16 sessions under way a
    cohort takes at most the bucket that holds half of them (the program's own rule, read and not copied)."""
    from hivemind_tpu.moe.server.decode_session import HALVED_ABOVE, _half_bucket

    return _half_bucket(slots) if slots > HALVED_ABOVE else 1 << (slots - 1).bit_length()


# ---- the reference, one jitted program a variant and a kind --------------------------


@functools.lru_cache(maxsize=None)
def _jitted_block(frozen_sizes):
    """The reference's block under jit with its sizes fixed (``frozen_sizes``: the hashable form
    of `block`'s keyword arguments), in float32 at the highest matmul precision."""
    import jax

    from perf.reference import granite_h_block as reference

    sizes = dict(frozen_sizes)

    def run(params, x):
        with jax.default_matmul_precision("highest"):
            cast = lambda leaf: leaf.astype("float32")
            return reference.block(jax.tree_util.tree_map(cast, params), cast(x), return_state=True, **sizes)

    return jax.jit(run)


def reference_span(all_params, x, sizes, **variant):
    """`granite_h_block.span_with_states` block by block, each under `_jitted_block`, on ``x``
    ``[streams, T, hidden]``: the output and each block's last recurrent state (None for an
    attention block). ``variant``: the keyword arguments of the reference's `block` that make a
    wrong reference."""
    states = []
    for params in all_params:
        x, state = _jitted_block(_frozen({**sizes, **variant}))(params, x)
        states.append(state)
    return x, states


# the wrong references that are read in EVERY run, plain ones too: those that move the output by little, so that every
# run says how far each stands from the limit that has to refuse it (the state in bf16: what no limit can tell, beside
# the dtype the check reads; the padding that leaks into the state and the two of the attention's scores, which two
# blocks in twenty compute under residuals x 0.22). The gross ones (every other: 30 times over a limit) are a traced run's
EVERY_RUN = ("the state kept in bf16", "a rotary embedding on the attention blocks", "the scores scaled by 1/8 and not by 1/64",
             "padding that decays and feeds the state")


def wrong_references(every: bool = True) -> Dict[str, Any]:
    """What the check must refuse: name -> (keyword arguments of `reference_span`, how it is told:
    ``"departure"`` (the served outputs must hold little of its departure), `PADDING` (a departure,
    read on the one served row whose chunk was padded: `check_against_reference` fills in where and
    by how many rows), or ``"dtype"`` (a state rounded to bf16 at every step moves the output by less
    than bf16 ACTIVATIONS do: no limit can tell it, so the check reads what the served sessions HOLD,
    `state_dtype_faults`; its readings are logged for the record). Each departs from the model in ONE thing."""
    references = {
        "the state kept in bf16": (dict(state_dtype="bfloat16"), "dtype"),
        "a rotary embedding on the attention blocks": (dict(rope=True), "departure"),
        "the scores scaled by 1/8 and not by 1/64": (dict(root_scale=True), "departure"),
        "the residual multiplier 1 and not 0.22": (dict(residual_multiplier=1.0), "departure"),
        "the norm before the gate": (dict(norm_before_gate=True), "departure"),
        "the convolution without its bias": (dict(conv_bias=False), "departure"),
        "the MLP's gate and up halves swapped": (dict(halves_swapped=True), "departure"),
        "the skip term D x left out": (dict(skip=False), "departure"),
        "padding that decays and feeds the state": (dict(padding="state"), PADDING),
    }
    return references if every else {name: references[name] for name in EVERY_RUN}


def _by_stream(run, streams):
    """``run`` on each stream ``[1, T, hidden]`` in turn; the outputs joined along the stream
    axis, and so each mixer's last state (an attention block's place holds None)."""
    import numpy as np

    outs, states = [], []
    for row in range(len(streams)):
        out, state = run(streams[row:row + 1])
        outs.append(np.asarray(out, np.float32))
        states.append([None if part is None else np.asarray(part) for part in state])
    return np.concatenate(outs), [None if parts[0] is None else np.concatenate(parts) for parts in zip(*states)]


def _mixer_states(states) -> List:
    return [state for state in states if state is not None]


def check_against_reference(server, client_dht, config, seed, rehearse, log, slots: int, every_wrong_reference=True) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full
    forward, of what the served path produced: streams of ``prompt + steps`` positions;
    (1) stream 0's prompt in chunks and single-token steps through the span over the wire,
    and the recurrent states its session ends with; (2) all streams as sessions at different
    positions (prompts of different lengths, the last chunk padded) that step in the same
    batched programs, at every bucket the window runs (`check_widths` of the largest cohort:
    short filler sessions pad the larger ones). Then the wrong references of
    `wrong_references` (all of them, or with ``every_wrong_reference`` off those of
    `EVERY_RUN`): each one's own readings, and how much of its departure the served outputs
    hold (`_departure_share`)."""
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential

    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    prompt, steps, rows = check_shape(rehearse)
    chunk = serving["prompt_chunk"]
    hidden, blocks = model["hidden_size"], model["num_hidden_layers"]
    uids = [f"{serving['uid_prefix']}{i}" for i in range(blocks)]
    all_params = [server.backends[uid].snapshot_params() for uid in uids]
    sizes = reference_sizes(config)
    manager = server.handler.decode_sessions
    rng = np.random.default_rng(seed)
    streams = runtime.float16_exact(rng.standard_normal((rows, prompt + steps, hidden), dtype=np.float32))
    faults = []

    reference = lambda count, **variant: _by_stream(lambda x: reference_span(all_params, jnp.asarray(x), **{"sizes": sizes, **variant}),
                                                    streams[:count])
    want, want_states = reference(rows)
    mixers = [uid for uid, kind in zip(uids, kinds(config)) if kind == "mamba"]
    last_states = lambda name: [np.asarray(manager._sessions[(uid, name)].leaves[1]) for uid in mixers]

    # (1) over the wire, one session, the prompt in chunks
    pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
    pieces = []
    for start in list(range(0, prompt, chunk)) + list(range(prompt, prompt + steps)):
        stop = min(start + chunk, prompt) if start < prompt else start + 1
        pieces.append(pipe.decode_step(streams[:1, start:stop], "reference-check", reset=start == 0))
    wire_states = last_states("reference-check")
    faults += state_dtype_faults(wire_states)
    pipe.close_decode_session("reference-check")
    single = np.concatenate(pieces, axis=1)
    ours = {"decode_rel": runtime.rel_err(single, want[:1]), "decode_rms_rel": _rms_err(single, want[:1]),
            **_state_err(wire_states, [state[:1] for state in _mixer_states(want_states)])}
    log(f"reference check: a prompt of {prompt} in chunks of {chunk} + {steps} steps through the caches, {ours['decode_rel']:.2e} of the "
        f"largest value, {ours['decode_rms_rel']:.2e} rms; the mixers' last states {ours['state_rms_rel']:.2e} rms, the first mixer's "
        f"{ours['first_state_rms_rel']:.2e}")
    faults += [f"a prompt of {prompt} in chunks + {steps} steps through the caches, against the reference's full forward: {fault}"
               for fault in judge(ours, tolerances)]

    # (2) the batched programs: row 0 is that stream, the others start from shorter prompts
    prompts = check_prompts(prompt, rows)
    got = [[] for _ in prompts]
    for row, length in enumerate(prompts):
        for start in range(0, length, chunk):
            got[row].append(manager._decode_direct(tuple(uids), f"reference-row{row}", streams[row:row + 1, start:min(start + chunk, length)],
                                                   reset=start == 0))
    widths = check_widths(rows, slots)
    names = [f"reference-row{row}" for row in range(rows)] + [f"reference-filler{at}" for at in range(widths[-1] - rows)]
    for name in names[rows:]:
        manager._decode_direct(tuple(uids), name, np.zeros((1, filler_prompt(prompt, chunk), hidden), np.float32), reset=True)
    token = np.zeros((1, 1, hidden), np.float32)
    for step in range(steps):
        width = widths[step * len(widths) // steps]
        xs = [streams[row:row + 1, length + step:length + step + 1] for row, length in enumerate(prompts)] + [token] * (width - rows)
        for uid in uids:
            entries = [(None, manager._sessions[(uid, name)], x) for name, x in zip(names, xs)]
            xs = manager._decode_batch(uid, entries)
            raised = [out for out in xs if isinstance(out, Exception)]
            if raised:
                raise raised[0]
        for row in range(rows):
            got[row].append(xs[row])
    row0_states = last_states("reference-row0")  # row 0 ends where its stream ends: the reference's last state is its own
    manager.clear_sessions()  # the check's caches leave the device before the wrong references are computed, and the window
    scale = np.abs(want).max()
    served = [(np.concatenate(got[row], axis=1), slice(0, length + steps)) for row, length in enumerate(prompts)]
    batched = {"decode_rel": max(float(np.abs(out - want[row, span]).max() / scale) for row, (out, span) in enumerate(served)),
               "decode_rms_rel": max(_rms_err(out, want[row:row + 1, span]) for row, (out, span) in enumerate(served)),
               **_state_err(row0_states, [state[:1] for state in _mixer_states(want_states)])}
    log(f"reference check: {rows} sessions at positions {prompts} stepping {steps} times in the same batched programs of "
        f"{widths} rows, {batched['decode_rel']:.2e} of the largest value, {batched['decode_rms_rel']:.2e} rms (worst row of each); "
        f"row 0's last states {batched['state_rms_rel']:.2e} rms, the first mixer's {batched['first_state_rms_rel']:.2e}")
    faults += [f"{rows} sessions in one batched program, against the reference's full forward: {fault}" for fault in judge(batched, tolerances)]

    # every wrong reference, on the first two streams: the served outputs must hold little of its departure
    few = min(rows, 2)
    right_few = [state[:few] for state in _mixer_states(want_states)]
    padded_by = (1 << ((prompts[1] - 1) % chunk).bit_length()) - ((prompts[1] - 1) % chunk + 1)  # the padding of row 1's last chunk
    for name, (variant, told) in wrong_references(every_wrong_reference).items():
        if told == PADDING:
            variant = dict(padding=(variant["padding"], prompts[1], max(padded_by, 1)))
        out, wrong_states = reference(few, **variant)
        theirs = {"decode_rel": runtime.rel_err(out, want[:few]), "decode_rms_rel": _rms_err(out, want[:few]),
                  **_state_err(_mixer_states(wrong_states), right_few)}
        if told == PADDING:  # row 1 alone was padded there, and only what follows its prompt can hold the departure
            after = slice(prompts[1], prompts[1] + steps)
            holds = abs(_departure_share([(served[1][0][:, after], want[1:2, after], out[1:2, after])]))
        else:
            pieces = [(o, want[row:row + 1, span], out[row:row + 1, span]) for row, (o, span) in list(enumerate(served))[:few]]
            holds = max(abs(_departure_share([(single, want[:1], out[:1])])), abs(_departure_share(pieces)))
        outside = judge(theirs, tolerances)
        log(f"for the record, the reference with {name}: {theirs['decode_rel']:.2e} of the largest value, {theirs['decode_rms_rel']:.2e} rms, "
            f"last states {theirs['state_rms_rel']:.2e} rms (the first mixer's {theirs['first_state_rms_rel']:.2e}): "
            f"{'outside' if outside else 'inside'} those limits; the served outputs hold {holds:.3f} of its departure")
        if told != "dtype" and not holds <= tolerances["departure_share"]:  # a program that computes it reads 1 here, whatever the noise
            faults.append(f"the served outputs hold {holds:.3f} of the departure of a reference with {name}, over "
                          f"{tolerances['departure_share']}: the program computes that, not the model")
    return faults


# ---- device time by named scope: the programs' texts ------------------------------------


def batched_programs(server, buckets: List[int], log) -> Dict[str, List[Dict[str, Optional[str]]]]:
    """A batched decode program's name -> every instruction's scope at every bucket of ``buckets``
    (blocks of one kind run ONE text a bucket), read off the compiled programs' own texts (after the
    window: the compilations are reads of the cache the warm-up filled, and no part of a
    measurement): `nemotron_block_server.instruction_scopes` (the state-space scopes and the
    staging copies of a row's state), with this span's own `SCOPES` laid over it."""
    import jax

    manager = server.handler.decode_sessions
    found: Dict[str, List[Dict[str, Optional[str]]]] = {}
    for uid, backend in server.backends.items():
        name = f"jit_batched_step_{backend.module.decode_cache_kind}"
        if name in found:
            continue
        found[name] = []
        for rows in buckets:
            try:
                shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
                columns = tuple((leaf,) * rows for leaf in shape(manager._dummy_rows(uid)))
                xs = jax.ShapeDtypeStruct((rows, 1, backend.module.hidden_dim), "float32")
                text = manager._batched_fn(uid, rows).jitted.lower(shape(backend.snapshot_params()), xs, columns,
                                                                   jax.ShapeDtypeStruct((rows,), "int32")).compile().as_text()
                state_bytes = {leaf.nbytes for leaf in manager._dummy_rows(uid)} if name.endswith("_ssm") else ()
                found[name].append({**instruction_scopes(text, state_bytes), **scope_of_instructions(text, SCOPES)})
            except Exception as e:  # a program whose text cannot be had: the scopes' metrics are left out
                log(f"{name} ({uid}, {rows} rows): no program text to read the scopes from ({e!r})")
                return {}
        scoped = [scope for scope in found[name][-1].values() if scope]
        log(f"{name} at {buckets} rows: at the last, {len(scoped)} of {len(found[name][-1])} instructions lie in a named scope ({sorted(set(scoped))})")
    return found


def param_bytes(server) -> Dict[str, float]:
    """What ONE block of each cache kind keeps on the device for its parameters, by its arrays' own dtypes."""
    import jax

    found: Dict[str, float] = {}
    for backend in server.backends.values():
        found.setdefault(backend.module.decode_cache_kind,
                         float(sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(backend.snapshot_params()))))
    return found


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
    if traffic["chunk"] != config["serving"]["prompt_chunk"]:
        raise ValueError("the traffic's chunk is not the configuration's prompt_chunk: the reference check would warm other programs")
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
        span_kinds = kinds(config)
        log(f"{model['num_hidden_layers']} blocks ({''.join(kind[0] for kind in span_kinds)}: the model's {model['first_block']}-"
            f"{model['first_block'] + model['num_hidden_layers'] - 1}) hidden {model['hidden_size']}, each a mixer and an MLP of "
            f"{model['shared_intermediate_size']} under residuals x {model['residual_multiplier']}; mixers of {model['mamba_n_heads']} heads of "
            f"{model['mamba_d_head']} with a state of {model['mamba_d_state']} in {model['mamba_n_groups']} group; attention "
            f"{model['num_attention_heads']} / {model['num_key_value_heads']} heads of {head_dim(model)} at {model['attention_multiplier']}, "
            f"on the device in {time.monotonic() - built:.1f} s")
        # the clients start now and connect while this process compiles
        lead = float(traffic.get("lead_seconds", 0.0))
        loadgen = LoadGenerators(traffic["generator"], plan, config, maddrs, lead_seconds=lead, drain_seconds=120.0)
        warm = time.monotonic()
        prompt, _steps, rows = check_shape(rehearse)
        slots_total = traffic["processes"] * traffic["slots_per_process"]
        most = cohort_rows(slots_total)  # no program of this traffic holds more rows: larger buckets are neither warmed nor checked
        warm_decode(server, config, {**traffic, "processes": 1, "slots_per_process": most},
                    check_prompts(prompt, rows) + [filler_prompt(prompt, traffic["chunk"])], log)
        log(f"warm-up took {time.monotonic() - warm:.1f} s; {watch.count()} compilations so far")
        checked = time.monotonic()
        faults = check_against_reference(server, client_dht, config, seed, rehearse, log, most,
                                         every_wrong_reference=bool(trace) or rehearse)
        server.handler.decode_sessions.clear_sessions()
        check_seconds = time.monotonic() - checked
        log(f"the reference check took {check_seconds:.1f} s")
        runtime.memory_peak_bytes(devices, log)  # for the log: whether the check or the served traffic sets the run's peak
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (every prompt, uncounted) is set-up
        # the check is the benchmark's own work, and several wrong references longer in a traced run: its seconds
        # are no part of what a deployment waits for before it serves
        setup_s = begin - started - check_seconds
        counters_lead = runtime.counters()
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
        buckets = [2**k for k in range(1, most.bit_length())]  # those `warm_decode` compiled
        scopes = scope_seconds(runtime.TRACE_DIR, batched_programs(server, buckets, log), log) if traced else {}
        weights = param_bytes(server)
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
    prefills = sorted(samples.get("prefill_s", []))
    log(f"window {seconds:.1f} s: {attempted} attempted, {completed} completed, {failed} failed, {tokens} tokens; {len(serving)} "
        f"requests served, {len(shed)} ended in an error on the server; set-up {setup_s:.1f} s; the lead-in's {len(prefills)} prompts "
        f"took {prefills[0] if prefills else 0:.1f} to {prefills[-1] if prefills else 0:.1f} s each, of {lead:.0f} s of lead-in")
    from perf.readers.counter_ratio import delta

    moved = {"counters": {"before": counters_before, "after": counters_after}}
    programs_run, rows_run = (delta(moved, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"})
                              for name in ("calls", "steps"))
    cohorts = delta(moved, {"metric": "hivemind_moe_decode_cohorts_total"})
    rewritten = delta(moved, {"metric": "hivemind_moe_ssm_state_bytes_total", "series": "path=batched"})
    log(f"window: {cohorts:.0f} cohorts, {programs_run:.0f} batched programs of {rows_run / max(programs_run, 1):.2f} rows, {rewritten / 1e9:.1f} GB "
        f"of state rewritten; gap ms p50 / p90 / p95 / p99 {_percentiles(samples.get('token_gap_ms', []))}, "
        f"largest {max(samples.get('token_gap_ms') or [0.0]):.0f}; server ms a decode request p50 / p90 / p95 / p99 "
        f"{_percentiles([1e3 * r['total_s'] for r in serving if r.get('kind') == 'decode' and 'total_s' in r])}")
    for name, entry in sorted(programs.items(), key=lambda item: -item[1]["seconds"])[:12]:
        log(f"traced program {name}: {entry['count']:.0f} runs, {entry['seconds'] * 1e3:.1f} ms")
    for name, entry in sorted(scopes.items()):
        log(f"traced scope {name}: {entry['count']:.0f} operations in {entry['runs']:.0f} programs, {entry['seconds'] * 1e3:.1f} ms")
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
        "counters_lead": {"before": counters_lead, "after": counters_before},
        **({"counters_traced": counters_traced} if counters_traced else {}),
        "serving": serving,
        "programs": programs,
        "scopes": scopes,
        "param_bytes": weights,
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
