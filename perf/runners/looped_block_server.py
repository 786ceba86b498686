"""A span of a LOOPED language model's blocks (Ouro-2.6B: `ouro_block`) behind the block server: the
blocks run `total_ut_steps` times a token, each pass on a cache pair of its own, so a served session
holds that many caches a block under ONE entry of the session table, a request names its pass
(`loop_pass`), and a token is that many dependent walks of the span with the model's final norm `F`
between them, on the client (`perf/traffic/looped_sessions.py`, whose `final_norm` / `apply_final_norm`
the check uses too: the run's clients and its check share one `F`). The whole is held to
`perf/reference/ouro_block.py`.

Nothing here is copied that could be imported: the load generators are `block_server.py`'s; the programs'
device time by name, the counters at the trace's edges, the share of a wrong reference's departure and the
log's percentiles `hybrid_moe_block_server.py`'s; the rms `moe_block_server.py`'s; the warm-up, the
check's widths and the scopes of a traced program's operations `sala_block_server.py`'s. Its own:
`build_server` (theirs call their own module's `block_kwargs`) and the check, which walks the LOOP.

`correct` is decided by what the served path produced (`check_against_reference`): at the published
widths, against the float32 reference at the highest matmul precision, EVERY pass's output of 8 streams
(prompts of the traffic's lengths, then `steps` single positions): stream 0 over the wire
(`RemoteSequential.decode_step(.., loop_pass=u)`), all 8 in the batched programs with the rows
STAGGERED so that the rows of one program are at different passes of different positions; that the
served sessions hold `total_ut_steps` cache trees a block under one entry; and for each WRONG reference
(one cache shared by the passes, `F` left out between passes, the output norms left out, three passes for
four, float8 values) that the limits refuse it or that the served outputs hold little of its departure.

The lead-in of this runner's cell holds every prefill, so the runner reads the program's counters when
the lead-in starts (`counters_lead`); it reads them again at the trace's edges (`counters_traced`).

The block class is resolved before a DHT or a client process starts: a program that lacks it (a parent
commit) fails at once."""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

from perf import runtime
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators
from perf.runners.hybrid_moe_block_server import _departure_share, _percentiles, _TraceEdges, program_seconds
from perf.runners.moe_block_server import _rms_err
from perf.runners.sala_block_server import check_widths, scope_of_instructions, scope_seconds, warm_decode

SCOPES = ("loop_attention", "loop_mlp")
BATCHED_PROGRAM = "jit_batched_step_looped"


def block_kwargs(model: Dict[str, Any]) -> Dict[str, Any]:
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
                ffn_inner=model["intermediate_size"], rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
                total_ut_steps=model["total_ut_steps"])


def reference_sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
                rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"])


def build_server(config: Dict[str, Any], seed: int, dht, block_factory):
    """What `Server.create` does for `expert_cls`, each block's weights drawn on the device from its own
    seed, and a frozen (`sgd(0.0)`) optimizer."""
    import optax

    from hivemind_tpu.moe import Server
    from hivemind_tpu.moe.server.layers import name_to_input
    from hivemind_tpu.moe.server.module_backend import ModuleBackend

    model, serving = config["model"], config["serving"]
    backends = {}
    for index in range(model["num_hidden_layers"]):
        uid = f"{serving['uid_prefix']}{index}"
        backends[uid] = ModuleBackend(
            uid, block_factory(model["hidden_size"], **block_kwargs(model)), optimizer=optax.sgd(0.0),
            sample_input=name_to_input[serving["expert_cls"]](4, model["hidden_size"]),
            max_batch_size=serving["max_batch_size"], rng_seed=(int(seed) * 64 + index) % (2**31 - 1),
        )
    server = Server(dht, backends, decode_max_len=serving["decode_max_len"],
                    decode_max_sessions=serving["decode_max_sessions"],
                    activation_compression=serving["activation_compression"])
    server.run_in_background(await_ready=True)
    return server


# ---- the reference, one jitted program a kind of block --------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_block(sandwich: bool, frozen_sizes):
    """The reference's block under jit (its sizes fixed): the loop around it stays the reference's own."""
    import jax

    from perf.reference import ouro_block as reference

    return jax.jit(functools.partial(reference.block, sandwich=sandwich, **dict(frozen_sizes)))


def compiled_block(sizes, cast=None):
    """`reference.block` as the reference's loops take it (``apply_block``): one compiled program a block; ``cast``
    rounds every block's input (a lower precision)."""
    frozen = tuple(sorted(sizes.items()))

    def block(params, x, sandwich=True, **_sizes):
        return _jitted_block(sandwich, frozen)(params, x if cast is None else cast(x))

    return block


def reference_loop(all_params, scale, x, sizes, cast=None, **variant):
    """`reference.span` on ``x`` ``[streams, T, hidden]``, stream by stream so that it fits: every pass's output,
    ``[passes, streams, T, hidden]``. ``variant``: the keyword arguments that make a wrong reference; ``cast``: a
    rounding of the weights and of every block's input (a lower precision)."""
    import jax
    import numpy as np

    from perf.reference import ouro_block as reference

    if cast is not None:
        all_params = jax.tree_util.tree_map(cast, all_params)
    block = compiled_block(sizes, cast)
    outs = [np.asarray(reference.span(all_params, scale, x[row:row + 1], apply_block=block, **sizes, **variant)) for row in range(len(x))]
    return np.concatenate(outs, axis=1)


def wrong_references(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the check must refuse: name -> (how it is computed, how it is told). ``"departure"``: the served outputs
    must hold little of its departure from the model (whether the plain limits refuse it too is logged);
    ``"precision"``: it differs from the served arithmetic in a precision alone, which the served rounding's chance
    overlap cannot tell, so it has to fall outside a limit; ``"record"``: logged only (the
    served matmuls round the float32 weights to bf16 on the way in, as every block of `layers/common.py` does, so
    a reference with bf16 weights is NEARER the served outputs than the float32 one: no limit can refuse it)."""
    import jax.numpy as jnp

    float8 = lambda t: jnp.asarray(t).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    bf16 = lambda t: jnp.asarray(t).astype(jnp.bfloat16).astype(jnp.float32)
    return {
        "one cache shared by the passes": ("one_cache", "departure"),
        "F left out between the passes": (dict(norm_between=False), "departure"),
        "the output norms left out (plain pre-norm)": (dict(sandwich=False), "departure"),
        "three passes for four": ("three_passes", "departure"),
        "float8 weights and block inputs": (dict(cast=float8), "precision"),
        "bf16 weights and block inputs": (dict(cast=bf16), "record"),
    }


def check_shape(rehearse: bool):
    """(steps, streams) of the reference check; the prompts are the traffic's."""
    return (12, 4) if rehearse else (128, 8)


def judge(readings: Dict[str, float], tolerances: Dict[str, Any]) -> List[str]:
    said = {"decode_rel": "of the largest value", "decode_rms_rel": "rms"}
    return [f"{readings[name]:.3e} {what}, over {tolerances[name]}" for name, what in said.items() if not readings[name] <= tolerances[name]]


def _readings(got, want) -> Dict[str, float]:
    """The worst pass's: ``got`` / ``want`` ``[passes, .., T, hidden]``."""
    return {"decode_rel": max(runtime.rel_err(got[u], want[u]) for u in range(len(want))),
            "decode_rms_rel": max(_rms_err(got[u], want[u]) for u in range(len(want)))}


def check_against_reference(server, client_dht, config, traffic, seed, rehearse, log, every_wrong_reference=True) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full looped forward, of what
    the served path produced: streams of ``prompt + steps`` positions, the prompts the traffic's lengths in turn;
    (1) stream 0 over the wire, its prompt and its steps each through `passes` calls with `F` between them; (2) all
    streams as sessions in the batched programs, row r starting r mod `passes` calls late, so that the rows of one
    program are at different passes (and, across a token's end, different positions), with filler sessions beside
    them up to the bucket half the window's slots fill; (3) the sessions hold `passes` trees a block under one
    entry. Then the wrong references (all of `wrong_references`, or with ``every_wrong_reference`` off the float8
    one alone), on the first two streams."""
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential
    from perf.reference import ouro_block as reference

    generator = plugin("traffic", traffic["generator"])
    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    steps, rows = check_shape(rehearse)
    passes, hidden, blocks = model["total_ut_steps"], model["hidden_size"], model["num_hidden_layers"]
    uids = tuple(f"{serving['uid_prefix']}{i}" for i in range(blocks))
    all_params = [server.backends[uid].snapshot_params() for uid in uids]
    sizes = reference_sizes(model)
    manager = server.handler.decode_sessions
    scale = generator.final_norm(generator.norm_seed(seed), hidden)
    norm = lambda y: generator.apply_final_norm(y, scale)
    prompts = [traffic["prompt_lengths"][row % len(traffic["prompt_lengths"])] for row in range(rows)]
    longest = max(prompts) + steps
    rng = np.random.default_rng(seed)
    streams = runtime.float16_exact(rng.standard_normal((rows, longest, hidden), dtype=np.float32))
    for row, prompt in enumerate(prompts):  # causal: the zeros past a stream's end change nothing before it
        streams[row, prompt + steps:] = 0.0
    faults = []

    want = reference_loop(all_params, scale, streams, sizes, passes=passes)  # [passes, rows, longest, hidden]
    upto = lambda outs, row: outs[:, row:row + 1, :prompts[row] + steps]

    # (1) over the wire, one session: the prompt, then single positions, each through the loop
    pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
    wire = [[] for _ in range(passes)]
    for start, stop in [(0, prompts[0])] + [(t, t + 1) for t in range(prompts[0], prompts[0] + steps)]:
        x = streams[:1, start:stop]
        for u in range(passes):
            x = norm(pipe.decode_step(x, "reference-check", reset=start == 0, loop_pass=u))
            wire[u].append(x)
    held = {uid: manager._sessions.get((uid, "reference-check")) for uid in uids}
    for uid, session in held.items():
        if session is None or len(session.trees) != passes or session.positions != [prompts[0] + steps] * passes:
            faults.append(f"the served session at {uid} holds {None if session is None else (len(session.trees), session.positions)}, "
                          f"not {passes} trees at position {prompts[0] + steps} under one entry")
    if len(manager._sessions) != blocks:
        faults.append(f"one session of {passes} passes is {len(manager._sessions)} entries of the table, not one a block ({blocks})")
    pipe.close_decode_session("reference-check")
    single = np.stack([np.concatenate(pieces, axis=1) for pieces in wire])
    ours = _readings(single, upto(want, 0))
    log(f"reference check: a prompt of {prompts[0]} + {steps} steps, {passes} passes each with the norm between, over the wire: "
        f"{ours['decode_rel']:.2e} of the largest value, {ours['decode_rms_rel']:.2e} rms (the worst pass); one entry a block holding "
        f"{passes} trees")
    faults += [f"a prompt of {prompts[0]} + {steps} steps through the loop over the wire, against the reference's looped forward: {fault}"
               for fault in judge(ours, tolerances)]

    # (2) the batched programs: the rows staggered by a call each, so that one program's rows are at different passes
    names = [f"reference-row{row}" for row in range(rows)]
    got = [[[] for _ in range(passes)] for _ in range(rows)]
    for row, name in enumerate(names):
        x = streams[row:row + 1, :prompts[row]]
        for u in range(passes):
            x = norm(manager._decode_direct(uids, name, x, True, u))
            got[row][u].append(x)
    widths = check_widths(rows, max(traffic["processes"] * traffic["slots_per_process"] // 2, rows))
    fillers = [f"reference-filler{at}" for at in range(widths[-1] - rows)]
    for name in fillers:
        manager._decode_direct(uids, name, np.zeros((1, min(prompts) // 4, hidden), np.float32), True, 0)
    token = np.zeros((1, 1, hidden), np.float32)
    carried: Dict[int, Any] = {}  # a row -> its next call's input, where that is a pass's output
    mixed = 0
    ticks = steps * passes + passes - 1
    for tick in range(ticks):
        live = [(row, tick - row % passes) for row in range(rows) if 0 <= tick - row % passes < steps * passes]
        width = widths[tick * len(widths) // ticks]
        beside = fillers[:max(width - len(live), 2 - len(live), 0)]  # a program of one row is the session's own step
        xs = [carried[row] if call % passes else streams[row:row + 1, prompts[row] + call // passes:prompts[row] + call // passes + 1]
              for row, call in live] + [token] * len(beside)
        stepped = [(names[row], call % passes) for row, call in live] + [(name, 0) for name in beside]
        mixed += len({loop_pass for _name, loop_pass in stepped[:len(live)]}) > 1
        for uid in uids:
            entries = [(None, manager._sessions[(uid, name)], x, loop_pass) for (name, loop_pass), x in zip(stepped, xs)]
            xs = manager._decode_batch(uid, entries)
            raised = [out for out in xs if isinstance(out, Exception)]
            if raised:
                raise raised[0]
        for (row, call), out in zip(live, xs):
            carried[row] = norm(out)
            got[row][call % passes].append(carried[row])
    served = [np.stack([np.concatenate(pieces, axis=1) for pieces in got[row]]) for row in range(rows)]  # a row: [passes, 1, T_r, hidden]
    manager.clear_sessions()  # the check's caches leave the device before the wrong references are computed, and the window
    each = [_readings(served[row], upto(want, row)) for row in range(rows)]
    batched = {name: max(reading[name] for reading in each) for name in each[0]}
    log(f"reference check: {rows} sessions from prompts {prompts} stepping {steps} positions of {passes} passes in the same batched programs "
        f"of {widths} rows, the rows a call apart ({mixed} of {ticks} programs held rows of different passes): {batched['decode_rel']:.2e} of "
        f"the largest value, {batched['decode_rms_rel']:.2e} rms (the worst pass of the worst row)")
    faults += [f"{rows} sessions at different passes in one batched program, against the reference's looped forward: {fault}"
               for fault in judge(batched, tolerances)]
    if not mixed:
        faults.append("no batched program of the check held rows of different passes")

    # the wrong references, on the first two streams: each must fall outside a limit, or the served outputs hold little of it
    few = min(rows, 2)
    decoded = lambda outs, row: outs[-1][:, prompts[row]:prompts[row] + steps]  # what the head reads of the decoded positions
    for name, (how, told) in wrong_references(model).items():
        if not every_wrong_reference and told != "precision":
            continue
        if how == "three_passes":  # the third pass's output where the fourth's is due
            wrong = np.concatenate([want[:passes - 1], want[passes - 2:passes - 1]])[:, :few]
        elif how == "one_cache":
            wrong = np.zeros_like(want[:, :few])
            for row in range(few):
                out = reference.span_through_one_cache(all_params, scale, streams[row:row + 1, :prompts[row] + steps], prompt=prompts[row], passes=passes,
                                                       apply_block=compiled_block(sizes), **sizes)
                wrong[:, row:row + 1, :prompts[row] + steps] = np.asarray(out)
        else:
            wrong = reference_loop(all_params, scale, streams[:few], sizes, passes=passes, **how)
        theirs = [_readings(upto(wrong, row), upto(want, row)) for row in range(few)]
        theirs = {key: max(reading[key] for reading in theirs) for key in theirs[0]}
        pieces = [(decoded(served[row], row), decoded(upto(want, row), row), decoded(upto(wrong, row), row)) for row in range(few)]
        holds = max(abs(_departure_share([(decoded(single, 0), decoded(upto(want, 0), 0), decoded(upto(wrong, 0), 0))])),
                    abs(_departure_share(pieces)))
        outside = judge(theirs, tolerances)
        log(f"for the record, the reference with {name}: {theirs['decode_rel']:.2e} of the largest value, {theirs['decode_rms_rel']:.2e} rms "
            f"(the worst pass): {'outside' if outside else 'inside'} the limits; the served outputs hold {holds:.3f} of its departure")
        if told == "precision":  # the served arithmetic shares its kind of rounding: only the limits can tell it
            if not outside and not rehearse:
                faults.append(f"the limits let a reference with {name} pass")
        elif told == "departure" and not holds <= tolerances["departure_share"]:  # a program that computes it reads 1 here, whatever the noise
            faults.append(f"the served outputs hold {holds:.3f} of the departure of a reference with {name}, over "
                          f"{tolerances['departure_share']}: the program computes that, not the model")
    return faults


def batched_program_scopes(server, rows: int, log) -> Dict[str, Dict[str, str]]:
    """The scopes (`SCOPES`) of the instructions of the batched decode program at the bucket of ``rows``, read off
    the compiled program's own text (after the window: a read of the cache the warm-up filled)."""
    import jax

    manager = server.handler.decode_sessions
    uid, backend = next(iter(server.backends.items()))
    try:
        shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
        columns = tuple((leaf,) * rows for leaf in shape(manager._dummy_rows(uid)))
        lowered = manager._batched_fn(uid, rows).jitted.lower(shape(backend.snapshot_params()), jax.ShapeDtypeStruct((rows, 1, backend.module.hidden_dim), "float32"),
                                                       columns, jax.ShapeDtypeStruct((rows,), "int32"))
        found = scope_of_instructions(lowered.compile().as_text(), SCOPES)
        log(f"{BATCHED_PROGRAM} at {rows} rows: {len(found)} instructions lie in a named scope ({sorted(set(found.values()))})")
        return {BATCHED_PROGRAM: found}
    except Exception as e:  # a program whose text cannot be had: the log's split is left out
        log(f"{BATCHED_PROGRAM}: no program text to read the scopes from ({e!r})")
        return {}


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
    if traffic["passes"] != model["total_ut_steps"]:
        raise ValueError(f"the traffic walks {traffic['passes']} passes, the configuration's total_ut_steps is {model['total_ut_steps']}")
    if traffic["chunk"] < max(traffic["prompt_lengths"]):
        raise ValueError("these blocks take a prompt whole: the traffic's chunk has to hold the longest prompt")
    if max(traffic["prompt_lengths"]) + traffic["answer_cap"] > config["serving"]["decode_max_len"]:
        raise ValueError("the longest prompt and the answers' cap do not fit the configuration's decode_max_len")
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
        log(f"{model['num_hidden_layers']} blocks (the model's {model['first_block']}-{model['first_block'] + model['num_hidden_layers'] - 1}) hidden "
            f"{model['hidden_size']} / {model['num_attention_heads']} heads of {model['head_dim']} / inner {model['intermediate_size']}, run "
            f"{model['total_ut_steps']} times a token, on the device in {time.monotonic() - built:.1f} s")
        # the clients start now and connect while this process compiles
        lead = float(traffic.get("lead_seconds", 0.0))
        loadgen = LoadGenerators(traffic["generator"], plan, config, maddrs, lead_seconds=lead, drain_seconds=120.0)
        warm = time.monotonic()
        slots_total = traffic["processes"] * traffic["slots_per_process"]
        warm_decode(server, config, traffic, [min(traffic["prompt_lengths"]) // 4], log)  # the check's fillers' prompt beside the traffic's
        log(f"warm-up took {time.monotonic() - warm:.1f} s; {watch.count()} compilations so far")
        checked = time.monotonic()
        faults = check_against_reference(server, client_dht, config, traffic, seed, rehearse, log, every_wrong_reference=bool(trace) or rehearse)
        server.handler.decode_sessions.clear_sessions()
        check_seconds = time.monotonic() - checked
        log(f"the reference check took {check_seconds:.1f} s")
        runtime.memory_peak_bytes(devices, log)  # for the log: whether the check or the served traffic sets the run's peak
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (every prompt, uncounted) is set-up
        # the check is the benchmark's own work, and longer in a traced run: its seconds are no part of what a
        # deployment waits for before it serves
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
        scopes = scope_seconds(runtime.TRACE_DIR, batched_program_scopes(server, max(slots_total // 2, 2), log)) if traced else {}
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
    taken = sorted(samples.pop("taken", []))
    if taken and taken[-1] >= traffic["answer_cap"]:
        faults.append(f"a session reached the answers' cap of {traffic['answer_cap']} tokens: its slot idled for the rest of the window")
    prefills = sorted(samples.get("prefill_s", []))
    log(f"window {seconds:.1f} s: {attempted} attempted, {completed} completed, {failed} failed, {tokens} tokens; {len(serving)} "
        f"requests served, {len(shed)} ended in an error on the server; set-up {setup_s:.1f} s; the lead-in's {len(prefills)} prompts "
        f"({model['total_ut_steps']} passes each) took {prefills[0] if prefills else 0:.1f} to {prefills[-1] if prefills else 0:.1f} s each, of "
        f"{lead:.0f} s of lead-in; a session took {taken[0] if taken else 0} to {taken[-1] if taken else 0} tokens of a cap of {traffic['answer_cap']}")
    from perf.readers.counter_ratio import delta

    moved = {"counters": {"before": counters_before, "after": counters_after}}
    programs_run, rows_run = (delta(moved, {"metric": f"hivemind_moe_decode_{name}_total", "series": "path=batched"})
                              for name in ("calls", "steps"))
    cohorts, cohort_passes = (delta(moved, {"metric": f"hivemind_moe_decode_{name}_total"}) for name in ("cohorts", "cohort_passes"))
    attended = delta(moved, {"metric": "hivemind_moe_looped_positions_attended_total", "series": "path=batched"})
    log(f"window: {cohorts:.0f} cohorts of {cohort_passes / max(cohorts, 1):.2f} distinct passes, {programs_run:.0f} batched programs of "
        f"{rows_run / max(programs_run, 1):.2f} rows at {attended / max(rows_run, 1):.0f} positions a row; gap ms p50 / p90 / p95 / p99 "
        f"{_percentiles(samples.get('token_gap_ms', []))}, largest {max(samples.get('token_gap_ms') or [0.0]):.0f}; the client's ms between two "
        f"passes p50 / p90 / p95 / p99 {_percentiles(samples.get('between_ms', []))}; server ms a decode request p50 / p90 / p95 / p99 "
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
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
