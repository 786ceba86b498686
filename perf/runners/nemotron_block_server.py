"""A span of NemotronH-family blocks (NVIDIA-Nemotron-3-Super-120B-A12B) behind the block
server: ONE residual a block, a Mamba-2 state-space mixer (`M`), a grouped-query attention
without position embedding (`*`) or a LatentMoE layer that keeps no cache and holds a SHARE
of its layer's non-gated experts (`E`), by the character of `hybrid_override_pattern` at the
block's place (`share`: the router's outputs, the first held expert; `n_routed_experts`
counts the held); prompts that arrive in chunks. The whole is held to
`perf/reference/nemotron_h_block.py`, given the same share.

Nothing here is copied that could be imported: the load generators are `block_server.py`'s;
the programs' device time by name, the counters at the trace's edges, the share of a wrong
reference's departure and the log's percentiles `hybrid_moe_block_server.py`'s; the rms and
the routing mismatch `moe_block_server.py`'s; the warm-up of chunked prompts, the check's
prompts and widths and the scope of an instruction `sala_block_server.py`'s; the taps on the
served programs' routers `latent_moe_block_server.py`'s. Its own, because theirs do not fit
and a file the benchmark has may not be edited: `build_server` (theirs call their own
module's `block_kwargs`); `scope_seconds` (the span runs programs of THREE names, each at
several buckets whose instruction names collide: a traced program is told by its name and
then by the instructions it ran); the check, which also holds the recurrent STATE a served
session ends with against the reference's.

`correct` is decided by what the served path produced (`check_against_reference`): at the
published widths, against the float32 reference at the highest matmul precision, stream by
stream: the span's output hidden states (largest and rms difference), the recurrent states
of the mixers after the last position, the experts the served programs' routers chose
against the reference's own and against the reference's router on the served programs' own
router inputs, and for each WRONG reference how much of its departure the served outputs
hold.

The lead-in of this runner's cell holds every prefill, so the runner reads the program's
counters when the lead-in starts (`counters_lead`); it reads them again at the trace's edges
(`counters_traced`), and after a traced window it sums the device time of the batched
programs' operations by named scope (`scopes`: `ssm_conv`, `ssm_step`, `moe_experts`, and
`ssm_staging`: the compiler's own copies of a row's state into on-chip memory).

The block class is resolved before a DHT or a client process starts: a program that lacks
it (a parent commit) fails at once."""

from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, List, Optional

from perf import runtime
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators
from perf.runners.hybrid_moe_block_server import MODULE_LINE, _departure_share, _percentiles, _TraceEdges, program_seconds
from perf.runners.latent_moe_block_server import _ANY_INSTRUCTION, _ASYNC_COPY, _ITEMSIZE, _frozen, _RouterTaps
from perf.runners.moe_block_server import _mismatch_share, _rms_err
from perf.runners.sala_block_server import check_prompts, check_widths, filler_prompt, scope_of_instructions, warm_decode

SCOPES = ("ssm_conv", "ssm_step", "ssm_scan", "moe_experts")
STAGING = "ssm_staging"  # no scope of the program's: the compiler's own copies of a row's state, told by their size
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def kinds(config: Dict[str, Any]) -> List[str]:
    """The kind of each block of the span, by its character of the (cut) pattern."""
    model = config["model"]
    pattern = model["hybrid_override_pattern"]
    assert len(pattern) == model["num_hidden_layers"] and set(pattern) <= set(KINDS), pattern
    return [KINDS[character] for character in pattern]


def block_kwargs(config: Dict[str, Any], index: int) -> Dict[str, Any]:
    """Block ``index``'s own sizes: its kind by the pattern, every size from the published keys."""
    model, share = config["model"], config["share"]
    assert model["expand"] * model["hidden_size"] == model["mamba_num_heads"] * model["mamba_head_dim"], "expand x hidden is not heads x head_dim"
    return dict(
        kind=kinds(config)[index], rms_eps=model["norm_eps"],
        mamba_heads=model["mamba_num_heads"], mamba_head_dim=model["mamba_head_dim"], ssm_groups=model["n_groups"],
        ssm_state=model["ssm_state_size"], conv_kernel=model["conv_kernel"], chunk_size=model["chunk_size"],
        time_step_min=model["time_step_min"], time_step_max=model["time_step_max"], time_step_floor=model["time_step_floor"],
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        num_experts=share["router_outputs"], experts_per_token=model["num_experts_per_tok"], latent_dim=model["moe_latent_size"],
        expert_inner=model["moe_intermediate_size"], shared_inner=model["moe_shared_expert_intermediate_size"],
        held_lo=share["held_lo"], held=model["n_routed_experts"], routed_scale=float(model["routed_scaling_factor"]),
    )


def reference_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keyword arguments of the reference's `block`, as the configuration has them."""
    model = config["model"]
    return dict(
        rms_eps=model["norm_eps"], mamba_heads=model["mamba_num_heads"], mamba_head_dim=model["mamba_head_dim"],
        ssm_groups=model["n_groups"], ssm_state=model["ssm_state_size"], num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"], experts_per_token=model["num_experts_per_tok"],
        routed_scale=float(model["routed_scaling_factor"]), held_lo=config["share"]["held_lo"],
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


# ---- the reference, one jitted program a variant and a kind --------------------------


@functools.lru_cache(maxsize=None)
def _jitted_block(frozen_sizes):
    """The reference's block under jit with its sizes fixed (``frozen_sizes``: the hashable form
    of `block`'s keyword arguments), in float32 at the highest matmul precision."""
    import jax

    from perf.reference import nemotron_h_block as reference

    sizes = dict(frozen_sizes)

    def run(params, x):
        with jax.default_matmul_precision("highest"):
            cast = lambda leaf: leaf.astype("float32")
            return reference.block(jax.tree_util.tree_map(cast, params), cast(x), return_routing=True, **sizes)

    return jax.jit(run)


def reference_span(all_params, x, sizes, **variant):
    """`nemotron_h_block.span_with_routing` block by block, each under `_jitted_block`, on ``x``
    ``[streams, T, hidden]``: the output and each block's ``(u, top_e, state)``. ``variant``: the
    keyword arguments of the reference's `block` that make a wrong reference."""
    routing = []
    for params in all_params:
        x, routed = _jitted_block(_frozen({**sizes, **variant}))(params, x)
        routing.append(routed)
    return x, routing


# the wrong references that are read in EVERY run, plain ones too: the state in bf16 (for the record: what no limit can
# tell, beside the dtype the check reads), and of those that a plain limit does not tell (they move the output by less
# than the served rounding does) the one the served outputs held most of on the chip
EVERY_RUN = ("the state kept in bf16", "a window that holds a padded row")
PADDING = "padding"  # a wrong reference of a chunk's right-padding: held against the served row that was padded, and no other


def wrong_references(every: bool = True) -> Dict[str, Any]:
    """What the check must refuse: name -> (keyword arguments of `reference_span`, how it is told:
    ``"departure"`` (the served outputs must hold little of its departure), ``"precision"`` (it differs
    from the served arithmetic in a precision alone: it has to fall outside a limit), `PADDING`
    (a departure, read on the one served row whose chunk was padded: `check_against_reference`
    fills in where and by how many rows), or ``"dtype"`` (the state in bf16 moves the output by LESS
    than the served activations' own rounding does, on every measure and in the first mixer's own
    state too: thirteen chip readings, `tolerances.why`; no limit can tell it, so the check reads what
    the served sessions HOLD, `state_dtype_faults`; its readings are logged for the record). Each
    departs from the model as assumed in ONE thing."""
    references = {
        "the state kept in bf16": (dict(state_dtype="bfloat16"), "dtype"),
        "the skip term D x left out": (dict(skip=False), "departure"),
        "the norm before the gate": (dict(norm_before_gate=True), "departure"),
        "the convolution without its bias": (dict(conv_bias=False), "departure"),
        "a window that holds a padded row": (dict(padding="window"), PADDING),
        "padding that decays and feeds the state": (dict(padding="state"), PADDING),
        "B and C of the wrong group": (dict(groups_reversed=True), "departure"),
        "relu for relu^2 in the experts": (dict(activation="relu"), "departure"),
        "the router's weights not scaled by 5": (dict(routed_scale=1.0), "departure"),
        "the router's matmul in one bf16 pass": (dict(rounded_router=True), "precision"),
        "the shared expert fed the latent": (dict(shared_on_latent=True), "departure"),
        "a rotary embedding on the attention block": (dict(rope=True), "departure"),
    }
    return references if every else {name: references[name] for name in EVERY_RUN}


def _router_mismatch_share(all_params, routing, experts_per_token: int) -> float:
    """The router alone, teacher-forced: per expert block, the reference's float32 router is
    handed the router input that the side under test computed, and its picks are held against
    that side's."""
    import jax

    from perf.reference import nemotron_h_block as reference

    choose = jax.jit(reference.chosen_experts, static_argnums=(2,))
    routed = [(params, u, top_e) for params, (u, top_e, *_state) in zip(all_params, routing) if top_e is not None]
    want = [choose({"router": params["router"], "router_bias": params["router_bias"]}, u, experts_per_token) for params, u, _ in routed]
    return _mismatch_share([top_e for _, _, top_e in routed], want)


def _choices(routing) -> List:
    return [entry[1] for entry in routing if entry[1] is not None]


def _states(routing) -> List:
    return [entry[2] for entry in routing if entry[2] is not None]


def check_shape(rehearse: bool):
    """(prompt, steps, streams) of the reference check: two whole chunks over the wire; the
    batched rows start from prompts a little shorter, whose last chunk comes padded."""
    return (160, 40, 2) if rehearse else (4096, 192, 8)


def _by_stream(run, streams):
    """``run`` on each stream ``[1, T, hidden]`` in turn; the outputs joined along the stream
    axis, and so each block's router input, choices and last state."""
    import numpy as np

    outs, routings = [], []
    for row in range(len(streams)):
        out, routing = run(streams[row:row + 1])
        outs.append(np.asarray(out, np.float32))
        # a router's input is kept where there is a router (4,288 x 4,096 float32 a block a stream)
        routings.append([tuple(None if part is None or (at == 0 and entry[1] is None) else np.asarray(part) for at, part in enumerate(entry))
                         for entry in routing])
    join = lambda parts: None if parts[0] is None else np.concatenate(parts)
    joined = [tuple(join([routing[block][part] for routing in routings]) for part in range(3)) for block in range(len(routings[0]))]
    return np.concatenate(outs), joined


def _state_err(got: List, want: List) -> Dict[str, float]:
    """The rms difference of a mixer's recurrent state over the reference's rms: the largest over the mixers
    (``state_rms_rel``), and the FIRST mixer's (``first_state_rms_rel``). Where the first mixer is the span's first
    block its input is the stream itself, the same on both sides to the bit: what its state departs by is that one
    block's own arithmetic, the state's dtype above all, with no earlier block's rounding and no flipped expert in it."""
    errors = [_rms_err(ours, theirs) for ours, theirs in zip(got, want)]
    return {"state_rms_rel": max(errors, default=0.0), "first_state_rms_rel": errors[0] if errors else 0.0}


def state_dtype_faults(states: List) -> List[str]:
    """The configuration's `assumed.ssm_state_dtype`, read off what the served sessions hold: a state that
    accumulates over thousands of steps is kept in float32."""
    kept = sorted({str(state.dtype) for state in states})
    return [] if kept == ["float32"] else [f"the mixers keep their recurrent state in {kept}, not in float32"]


def judge(readings: Dict[str, float], tolerances: Dict[str, Any]) -> List[str]:
    """The faults of one side's readings on the plain limits (a limit the configuration lacks is not held)."""
    said = {"decode_rel": "of the largest value", "decode_rms_rel": "rms", "state_rms_rel": "rms of a mixer's last state",
            "first_state_rms_rel": "rms of the first mixer's last state",
            "routing_mismatch_share": "of pairs routed otherwise", "router_mismatch_share": "of pairs on its own router inputs"}
    return [f"{readings[name]:.3e} {what}, over {tolerances[name]}" for name, what in said.items()
            if name in readings and name in tolerances and not readings[name] <= tolerances[name]]


def check_against_reference(server, client_dht, config, seed, rehearse, log, slots: int, every_wrong_reference=True) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full
    forward with the same held share, of what the served path produced: streams of
    ``prompt + steps`` positions; (1) stream 0's prompt in chunks and single-token steps
    through the span over the wire, and the recurrent states its session ends with; (2) all
    streams as sessions at different positions (prompts of different lengths, the last chunk
    padded) that step in the same batched programs, at every bucket the window runs
    (`check_widths`: short filler sessions pad the larger ones); (3) the experts that the
    routers of those served programs chose (every chunk and every step of (1) and (2)),
    against the reference's own and against the reference's router on the served programs'
    own router inputs. Then the wrong references of `wrong_references` (all of them, or with
    ``every_wrong_reference`` off those of `EVERY_RUN`): each one's own readings, and how
    much of its departure the served outputs hold (`_departure_share`)."""
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential

    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    prompt, steps, rows = check_shape(rehearse)
    chunk = serving["prompt_chunk"]
    hidden, blocks = model["hidden_size"], model["num_hidden_layers"]
    uids = [f"{serving['uid_prefix']}{i}" for i in range(blocks)]
    all_params = [server.backends[uid].snapshot_params() for uid in uids]
    sizes, per_token = reference_sizes(config), model["num_experts_per_tok"]
    manager = server.handler.decode_sessions
    rng = np.random.default_rng(seed)
    streams = runtime.float16_exact(rng.standard_normal((rows, prompt + steps, hidden), dtype=np.float32))
    faults = []

    reference = lambda count, **variant: _by_stream(lambda x: reference_span(all_params, jnp.asarray(x), **{"sizes": sizes, **variant}),
                                                    streams[:count])
    want, want_routing = reference(rows)
    span_kinds = kinds(config)
    routed = [index for index, kind in enumerate(span_kinds) if kind == "experts"]
    mixers = [uid for uid, kind in zip(uids, span_kinds) if kind == "mamba"]
    last_states = lambda name: [np.asarray(manager._sessions[(uid, name)].leaves[1]) for uid in mixers]
    crossings: Dict[Any, List] = {"wire": []}

    with _RouterTaps() as taps:
        # (1) over the wire, one session, the prompt in chunks
        pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
        pieces = []
        for start in list(range(0, prompt, chunk)) + list(range(prompt, prompt + steps)):
            stop = min(start + chunk, prompt) if start < prompt else start + 1
            pieces.append(pipe.decode_step(streams[:1, start:stop], "reference-check", reset=start == 0))
            crossings["wire"].append(taps.drain(len(routed)))
        wire_states = last_states("reference-check")
        faults += state_dtype_faults(wire_states)
        pipe.close_decode_session("reference-check")
        single = np.concatenate(pieces, axis=1)
        ours = {"decode_rel": runtime.rel_err(single, want[:1]), "decode_rms_rel": _rms_err(single, want[:1]),
                **_state_err(wire_states, [state[:1] for state in _states(want_routing)])}
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
                crossings.setdefault(row, []).append(taps.drain(len(routed)))
        widths = check_widths(rows, slots)
        names = [f"reference-row{row}" for row in range(rows)] + [f"reference-filler{at}" for at in range(widths[-1] - rows)]
        for name in names[rows:]:
            manager._decode_direct(tuple(uids), name, np.zeros((1, filler_prompt(prompt, chunk), hidden), np.float32), reset=True)
            taps.drain(len(routed))  # a filler's: nothing to hold them against
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
            taken = taps.drain(len(routed))  # every live row's pairs, an expert block each: the fillers' are left
            for row in range(rows):
                crossings[row].append([(u[row:row + 1], top_e[row:row + 1]) for u, top_e in taken])
        row0_states = last_states("reference-row0")  # row 0 ends where its stream ends: the reference's last state is its own
    manager.clear_sessions()  # the check's caches leave the device before the wrong references are computed, and the window
    scale = np.abs(want).max()
    served = [(np.concatenate(got[row], axis=1), slice(0, length + steps)) for row, length in enumerate(prompts)]
    batched = {"decode_rel": max(float(np.abs(out - want[row, span]).max() / scale) for row, (out, span) in enumerate(served)),
               "decode_rms_rel": max(_rms_err(out, want[row:row + 1, span]) for row, (out, span) in enumerate(served)),
               **_state_err(row0_states, [state[:1] for state in _states(want_routing)])}
    log(f"reference check: {rows} sessions at positions {prompts} stepping {steps} times in the same batched programs of "
        f"{widths} rows, {batched['decode_rel']:.2e} of the largest value, {batched['decode_rms_rel']:.2e} rms (worst row of each); "
        f"row 0's last states {batched['state_rms_rel']:.2e} rms, the first mixer's {batched['first_state_rms_rel']:.2e}")
    faults += [f"{rows} sessions in one batched program, against the reference's full forward: {fault}" for fault in judge(batched, tolerances)]

    # (3) routing, of the served programs themselves: every chunk and every step of (1) and (2), stream after stream
    held = [("wire", 0, prompt + steps)] + [(row, row, length + steps) for row, length in enumerate(prompts)]
    routing, want_chose = [(None, None)] * blocks, []
    for at, index in enumerate(routed):
        routing[index] = tuple(np.concatenate([taken[at][part] for key, _stream, _upto in held for taken in crossings[key]], axis=1)
                               for part in (0, 1))
        want_chose.append(np.concatenate([want_routing[index][1][stream:stream + 1, :upto] for _key, stream, upto in held], axis=1))
    pairs = sum(top_e.size for top_e in _choices(routing))
    chose = {"routing_mismatch_share": _mismatch_share(_choices(routing), want_chose),
             "router_mismatch_share": _router_mismatch_share(all_params, routing, per_token)}
    log(f"reference check: {chose['routing_mismatch_share']:.4%} of {pairs} (token, slot) pairs of the served programs chose an expert outside "
        f"the reference's set; on the served programs' own router inputs {chose['router_mismatch_share']:.4%} chose one that the reference's "
        f"float32 router does not")
    faults += [f"the served programs' routing: {fault}" for fault in judge(chose, tolerances)]

    # every wrong reference, on the first two streams: it must fail a limit, or the served outputs must hold little of it.
    # A rehearsal's streams are too short to tell a precision: not faulted there
    few = min(rows, 2)
    right_few = [tuple(None if part is None else part[:few] for part in entry) for entry in want_routing]
    padded_by = (1 << ((prompts[1] - 1) % chunk).bit_length()) - ((prompts[1] - 1) % chunk + 1)  # the padding of row 1's last chunk
    for name, (variant, told) in wrong_references(every_wrong_reference).items():
        if told == PADDING:
            variant = dict(padding=(variant["padding"], prompts[1], max(padded_by, 1)))
        out, wrong_routing = reference(few, **variant)
        theirs = {"decode_rel": runtime.rel_err(out, want[:few]), "decode_rms_rel": _rms_err(out, want[:few]),
                  **_state_err(_states(wrong_routing), _states(right_few)),
                  "routing_mismatch_share": _mismatch_share(_choices(wrong_routing), _choices(right_few)),
                  "router_mismatch_share": _router_mismatch_share(all_params, wrong_routing, per_token)}
        pieces = [(o, want[row:row + 1, span], out[row:row + 1, span]) for row, (o, span) in list(enumerate(served))[:few]]
        if told == PADDING:  # row 1 alone was padded there, and only what follows its prompt can hold the departure
            after = slice(prompts[1], prompts[1] + steps)
            holds = abs(_departure_share([(served[1][0][:, after], want[1:2, after], out[1:2, after])]))
        else:
            holds = max(abs(_departure_share([(single, want[:1], out[:1])])), abs(_departure_share(pieces)))
        outside = judge(theirs, tolerances)
        log(f"for the record, the reference with {name}: {theirs['decode_rel']:.2e} of the largest value, {theirs['decode_rms_rel']:.2e} rms, "
            f"last states {theirs['state_rms_rel']:.2e} rms (the first mixer's {theirs['first_state_rms_rel']:.2e}), "
            f"{theirs['routing_mismatch_share']:.4%} of pairs routed otherwise, "
            f"{theirs['router_mismatch_share']:.4%} on its own router inputs: {'outside' if outside else 'inside'} those limits; the served "
            f"outputs hold {holds:.3f} of its departure")
        if told == "precision":  # the served arithmetic shares its rounding: only the limits can tell it
            if not outside and not rehearse:
                faults.append(f"the limits let a reference with {name} pass")
        elif not holds <= tolerances["departure_share"]:  # a program that computes it reads 1 here, whatever the noise
            faults.append(f"the served outputs hold {holds:.3f} of the departure of a reference with {name}, over "
                          f"{tolerances['departure_share']}: the program computes that, not the model")
    return faults


# ---- device time by named scope, in programs of several names and buckets ------------


def instruction_scopes(hlo_text: str, state_bytes=()) -> Dict[str, Optional[str]]:
    """EVERY instruction of an optimized program's text -> the scope of `SCOPES` its `op_name`
    lies in, or None: the scoped ones for the sums, all of them to tell the program by. With
    ``state_bytes`` (the sizes of a row's cache arrays), the asynchronous copies of arrays of
    exactly such a size (`copy-start` / `copy-done`, which carry no `op_name`) are `STAGING`: on
    the v5e the compiler brings a row's state into on-chip memory before the step rewrites it
    there, so the state's read from HBM is THEIR time and not the `ssm_step` operations'."""
    found: Dict[str, Optional[str]] = {match.group(1): None for match in map(_ANY_INSTRUCTION.match, hlo_text.splitlines()) if match}
    found.update(scope_of_instructions(hlo_text, SCOPES))
    for name, dtype, dims in (match.groups() for match in map(_ASYNC_COPY.match, hlo_text.splitlines()) if match):
        if _ITEMSIZE.get(dtype, 0) * math.prod(int(dim) for dim in dims.split(",") if dim) in state_bytes:
            found[name] = STAGING
    return found


def batched_programs(server, buckets: List[int], log) -> Dict[str, List[Dict[str, Optional[str]]]]:
    """A batched decode program's name -> `instruction_scopes` of that kind's program at every
    bucket of ``buckets`` (blocks of one kind run ONE text a bucket), read off the compiled
    programs' own texts (after the window: the compilations are reads of the cache the warm-up
    filled, and no part of a measurement)."""
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
                lowered = manager._batched_fn(uid, rows).jitted.lower(shape(backend.snapshot_params()), xs, columns,
                                                               jax.ShapeDtypeStruct((rows,), "int32"))
                found[name].append(instruction_scopes(lowered.compile().as_text(), {leaf.nbytes for leaf in manager._dummy_rows(uid)}))
            except Exception as e:  # a program whose text cannot be had: the scopes' metrics are left out
                log(f"{name} ({uid}, {rows} rows): no program text to read the scopes from ({e!r})")
                return {}
        scoped = [scope for scope in found[name][-1].values() if scope]
        log(f"{name} at {buckets} rows: at the last, {len(scoped)} of {len(found[name][-1])} instructions lie in a named scope ({sorted(set(scoped))})")
    return found


def scope_seconds(trace_dir, candidates: Dict[str, List[Dict[str, Optional[str]]]], log=None) -> Dict[str, Dict[str, float]]:
    """Device seconds and events of the operations of each named scope in the runs of the
    programs that ``candidates`` names, in the newest trace under ``trace_dir``. A traced
    program (the `XLA Modules` line's name WITH its id) is matched, among its name's
    candidates (one a bucket), to the one that holds most of the instruction names its runs
    executed, and its operations take that candidate's scopes. An operation belongs to the
    run that holds its start. Empty where there is no trace; averaged over the device planes."""
    from perf.trace_reduce import DEVICE_PLANE, OP_LINES, find_xplane, load_planes, op_stem

    path = find_xplane(str(trace_dir))
    if path is None or not candidates:
        return {}
    planes = {name: lines for name, lines in load_planes(path).items() if DEVICE_PLANE.match(name)}
    totals: Dict[str, Dict[str, float]] = {}
    for lines in planes.values():
        runs = sorted((start, start + duration, name) for name, start, duration in lines.get(MODULE_LINE, [])
                      if name.split("(", 1)[0] in candidates)
        executed: Dict[str, List] = {}  # a traced program -> [(instruction, its stem, seconds), ...]
        at = 0
        for name, start, duration in sorted((event for line in OP_LINES for event in lines.get(line, [])), key=lambda e: e[1]):
            while at < len(runs) and runs[at][1] <= start:
                at += 1
            if at == len(runs):
                break
            if runs[at][0] <= start:
                executed.setdefault(runs[at][2], []).append((name.split(" = ", 1)[0].lstrip("%"), op_stem(name), duration / 1e9))
        for program, operations in executed.items():
            names = {instruction for instruction, _stem, _seconds in operations}
            scopes = max(candidates[program.split("(", 1)[0]], key=lambda candidate: len(names & candidate.keys()))
            if log is not None:
                known = sum(seconds for instruction, _stem, seconds in operations if instruction in scopes)
                log(f"traced {program}: {len(operations)} operations of {len(names)} names, {len(names & scopes.keys())} of them in the text matched, "
                    f"which accounts for {known / max(sum(seconds for *_names, seconds in operations), 1e-12):.1%} of their device time")
            for instruction, stem, seconds in operations:
                scope = scopes[instruction] if instruction in scopes else scopes.get(stem)
                if scope:
                    entry = totals.setdefault(scope, {"seconds": 0.0, "count": 0.0, "runs": 0.0})
                    entry["seconds"] += seconds / len(planes)
                    entry["count"] += 1.0 / len(planes)
            for scope in {scope for scope in scopes.values() if scope}:
                totals.setdefault(scope, {"seconds": 0.0, "count": 0.0, "runs": 0.0})["runs"] += sum(
                    1.0 for run in runs if run[2] == program) / len(planes)
    return totals


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
        log(f"{model['num_hidden_layers']} blocks ({model['hybrid_override_pattern']}: the model's {model['first_block']}-"
            f"{model['first_block'] + model['num_hidden_layers'] - 1}) hidden {model['hidden_size']}; mixers of {model['mamba_num_heads']} heads of "
            f"{model['mamba_head_dim']} with a state of {model['ssm_state_size']} in {model['n_groups']} groups; attention {model['num_attention_heads']} / "
            f"{model['num_key_value_heads']} heads of {model['head_dim']}; experts {model['n_routed_experts']} held of {config['share']['router_outputs']}, "
            f"{model['num_experts_per_tok']} a token, in a latent of {model['moe_latent_size']}, on the device in {time.monotonic() - built:.1f} s")
        # the clients start now and connect while this process compiles
        lead = float(traffic.get("lead_seconds", 0.0))
        loadgen = LoadGenerators(traffic["generator"], plan, config, maddrs, lead_seconds=lead, drain_seconds=120.0)
        warm = time.monotonic()
        prompt, _steps, rows = check_shape(rehearse)
        slots_total = traffic["processes"] * traffic["slots_per_process"]
        warm_decode(server, config, traffic, check_prompts(prompt, rows) + [filler_prompt(prompt, traffic["chunk"])], log)
        log(f"warm-up took {time.monotonic() - warm:.1f} s; {watch.count()} compilations so far")
        checked = time.monotonic()
        faults = check_against_reference(server, client_dht, config, seed, rehearse, log, slots_total,
                                         every_wrong_reference=bool(trace) or rehearse)
        server.handler.decode_sessions.clear_sessions()
        check_seconds = time.monotonic() - checked
        log(f"the reference check took {check_seconds:.1f} s")
        runtime.memory_peak_bytes(devices, log)  # for the log: whether the check or the served traffic sets the run's peak
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (every prompt, uncounted) is set-up
        # the check is the benchmark's own work, and a dozen wrong references longer in a traced run: its seconds
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
        buckets = [2**k for k in range(1, (1 << (slots_total - 1).bit_length()).bit_length())]  # those `warm_decode` compiled
        scopes = scope_seconds(runtime.TRACE_DIR, batched_programs(server, buckets, log), log) if traced else {}
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
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
