"""A span of DeepSeek-V3-family blocks (GigaChat3.1-702B-A36B) behind the block server:
multi-head latent attention in every block over one compressed array a position (a step
absorbs, a chunk expands), a leading dense block and sparse blocks with a group-limited
sigmoid router that hold a SHARE of each layer's experts (`share`: the router's outputs,
the first held expert; `n_routed_experts` counts the held), prompts that arrive in chunks.
Each block is built with its own kwargs (dense or sparse by its PUBLISHED number,
`first_block` + its place in the span), and the whole is held to
`perf/reference/gigachat_block.py`, given the same share.

Nothing here is copied that could be imported: the load generators are
`block_server.py`'s; the programs' device time by name, the counters at the trace's
edges, the share of a wrong reference's departure and the log's percentiles
`hybrid_moe_block_server.py`'s; the rms and the routing mismatch `moe_block_server.py`'s;
the warm-up of chunked prompts, the check's prompts and widths and the scope of an
instruction `sala_block_server.py`'s. Two things are this runner's own because theirs do
not fit: `build_server` (the third of its kind: the other two call their own module's
`block_kwargs`, and a file the benchmark has may not be edited to take one as an argument);
`scope_seconds` (theirs keys a program's instructions by the program's name, and here the
dense block and the sparse blocks run programs of ONE name, `jit_batched_step_latent`,
whose instruction names collide: this one tells a traced program by the instructions it
ran). The routing is read off the SERVED programs (`_RouterTaps`: what the routers of the
chunks and the steps of the check's own sessions saw and chose), not off a pass of the
check's own over the blocks, as the two older runners' `program_routing` is.

`correct` is decided by what the served path produced (`check_against_reference`): at
the published widths, against the float32 reference at the highest matmul precision,
stream by stream: the span's output hidden states (largest and rms difference), the
experts the program's blocks chose against the reference's own and against the
reference's router on the program's own router inputs, and for each WRONG reference how
much of its departure the served outputs hold.

The lead-in of this runner's cell holds every prefill, so the runner reads the program's
counters when the lead-in starts (`counters_lead`); it reads them again at the trace's
edges (`counters_traced`: `latent_attend_roofline` takes the positions attended and the
kernel time from the same seconds), and after a traced window it sums the device time
of the batched programs' operations by named scope (`scopes`: `latent_absorb`,
`latent_attend`, `moe_experts`).

The block class is resolved before a DHT or a client process starts: a program that
lacks it (a parent commit) fails at once."""

from __future__ import annotations

import functools
import math
import re
import time
from typing import Any, Dict, List, Optional

from perf import runtime
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators
from perf.runners.hybrid_moe_block_server import MODULE_LINE, _choices, _departure_share, _percentiles, _TraceEdges, program_seconds
from perf.runners.moe_block_server import _mismatch_share, _rms_err
from perf.runners.sala_block_server import check_prompts, check_widths, filler_prompt, scope_of_instructions, warm_decode

SCOPES = ("latent_absorb", "latent_attend", "latent_expand", "moe_experts")


def is_dense(config: Dict[str, Any], index: int) -> bool:
    """Whether the span's block ``index`` is one of the model's leading dense blocks."""
    model = config["model"]
    return model["first_block"] + index < model["first_k_dense_replace"]


def block_kwargs(config: Dict[str, Any], index: int) -> Dict[str, Any]:
    """Block ``index``'s own sizes: dense or sparse by its published number."""
    model, share, rope = config["model"], config["share"], config["model"]["rope_scaling"]
    return dict(
        mlp="dense" if is_dense(config, index) else "sparse",
        num_heads=model["num_attention_heads"], q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        rope_theta=float(model["rope_theta"]), rope_factor=float(rope["factor"]), rope_original=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]), rope_beta_slow=float(rope["beta_slow"]), rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]), rms_eps=model["rms_norm_eps"], ffn_inner=model["intermediate_size"],
        num_experts=share["router_outputs"], experts_per_token=model["num_experts_per_tok"], n_group=model["n_group"],
        topk_group=model["topk_group"], expert_inner=model["moe_intermediate_size"], held_lo=share["held_lo"],
        held=model["n_routed_experts"], routed_scale=model["routed_scaling_factor"],
    )


def reference_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keyword arguments of the reference's `block`, as the configuration has them."""
    model, rope = config["model"], config["model"]["rope_scaling"]
    return dict(
        num_heads=model["num_attention_heads"], qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], rms_eps=model["rms_norm_eps"],
        rope=dict(theta=float(model["rope_theta"]), factor=float(rope["factor"]), original=rope["original_max_position_embeddings"],
                  beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]), mscale=float(rope["mscale"]),
                  mscale_all_dim=float(rope["mscale_all_dim"])),
        experts_per_token=model["num_experts_per_tok"], routed_scale=model["routed_scaling_factor"], n_group=model["n_group"],
        topk_group=model["topk_group"], held_lo=config["share"]["held_lo"],
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


# ---- the reference, one jitted program a variant --------------------------------------


def _frozen(sizes):
    return tuple((key, tuple(value.items()) if isinstance(value, dict) else value) for key, value in sizes.items())


@functools.lru_cache(maxsize=None)
def _jitted_block(dtype: str, frozen_sizes):
    """The reference's block under jit with its sizes fixed (``frozen_sizes``: the hashable
    form of `block`'s keyword arguments), every parameter and the input rounded to ``dtype``
    and the arithmetic done in it (float32: as they are, at the highest matmul precision)."""
    import jax

    from perf.reference import gigachat_block as reference

    sizes = {key: dict(value) if key == "rope" else value for key, value in frozen_sizes}

    def run(params, x):
        with jax.default_matmul_precision("highest"):
            cast = lambda leaf: leaf.astype(dtype)
            return reference.block(jax.tree_util.tree_map(cast, params), cast(x), return_routing=True, **sizes)

    return jax.jit(run)


def reference_span(all_params, x, sizes, dtype: str = "float32", **variant):
    """`gigachat_block.span_with_routing` block by block, each under `_jitted_block`, on
    ``x`` ``[streams, T, hidden]``: the output and each block's ``(m, top_e)``. ``variant``: the
    keyword arguments of the reference's `block` that make a wrong reference."""
    routing = []
    for params in all_params:
        x, routed = _jitted_block(dtype, _frozen({**sizes, **variant}))(params, x)
        routing.append(routed)
    return x, routing


# the wrong references that move the output by less than the served rounding does: the plain
# limits cannot tell a program that computes one of them, only `_departure_share` can, so
# they decide `correct` in every run (the others fall outside a plain limit: the limits' own
# evidence, computed in a traced run and a rehearsal)
NEAR_THE_ROUNDING = ("the selection bias used as a weight", "the query latent's norm left out")


def wrong_references(sizes, every: bool = True) -> Dict[str, Any]:
    """What the limits must refuse: name -> (keyword arguments of `reference_span`, whether
    it differs from the served arithmetic in a precision alone). Each departs from the model
    as assumed in ONE thing."""
    knobs = lambda **route: dict(route_knobs=tuple(route.items()))
    references = {
        "plain rope at theta, no YaRN": (dict(yarn=False), False),
        "m^2 left out of the softmax scale": (dict(softmax_mscale=False), False),
        f"the scale {sizes['qk_nope_head_dim']}^(-1/2)": (dict(scale_dim=sizes["qk_nope_head_dim"]), False),
        "the latent kept and attended without its norm": (dict(latent_norm=False), False),
        "the query latent's norm left out": (dict(query_norm=False), False),
        "rotate-half pairs in place of interleaved ones": (dict(pairs="halves"), False),
        "group limiting left out": (dict(n_group=1, topk_group=1), False),
        "a group's score its one best": (knobs(group_best=1), False),
        "the selection bias used as a weight": (knobs(bias_weighs=True), False),
        "the scale 2.5 left out": (dict(routed_scale=1.0), False),
        "the shared expert left out": (dict(shared=False), False),
        "pairs routed elsewhere not left out (every chosen expert computed, by the held expert at its number mod held)":
            (dict(absent_left_out=False), False),
        "the router's matmul in one bf16 pass": (knobs(rounded=True), True),
        "all bf16, router too": (dict(dtype="bfloat16"), True),
    }
    return references if every else {name: references[name] for name in NEAR_THE_ROUNDING}


def _router_mismatch_share(all_params, routing, sizes) -> float:
    """The router alone, teacher-forced: per sparse block, the reference's float32 router is
    handed the router input that the side under test computed, and its picks are held
    against that side's. The rounding of the input is then shared, and what is left is the
    router's own arithmetic."""
    import jax

    from perf.reference import gigachat_block as reference

    choose = jax.jit(reference.chosen_experts, static_argnums=(2, 3, 4))
    sparse = [(params, m, top_e) for params, (m, top_e) in zip(all_params, routing) if top_e is not None]
    want = [choose({"router": params["router"], "router_bias": params["router_bias"]}, m, sizes["experts_per_token"],
                   sizes["n_group"], sizes["topk_group"]) for params, m, _ in sparse]
    return _mismatch_share([top_e for _, _, top_e in sparse], want)


class _RouterTaps:
    """What the routers of the SERVED programs saw and chose while this is open
    (`routing_stats.ROUTER_TAPS`): one ``(m, top_e)`` a call of a sparse block on a decode
    path, the live rows and positions alone, in the order the calls settled."""

    def __enter__(self):
        from hivemind_tpu.moe.server.routing_stats import ROUTER_TAPS

        self.taken: List = []
        self._tap = lambda seen, chose: self.taken.append((seen, chose))
        ROUTER_TAPS.append(self._tap)
        return self

    def __exit__(self, *_exc):
        from hivemind_tpu.moe.server.routing_stats import ROUTER_TAPS

        ROUTER_TAPS.remove(self._tap)

    def drain(self, sparse: int) -> List:
        """What ONE crossing of the span handed over since the last drain: a pair a sparse
        block, in the span's order."""
        taken, self.taken[:] = list(self.taken), []
        assert len(taken) == sparse, (len(taken), sparse)
        return taken


def check_shape(rehearse: bool):
    """(prompt, steps, streams) of the reference check: the prompt is no power of two,
    past `original_max_position_embeddings`, and three chunks long with the last padded."""
    return (160, 40, 2) if rehearse else (4500, 192, 8)


def _by_stream(run, streams):
    """``run`` on each stream ``[1, T, hidden]`` in turn (a stream of 4,692 positions is one
    program a block); the outputs joined along the stream axis, the routings stream by stream."""
    import numpy as np

    outs, routings = [], []
    for row in range(len(streams)):
        out, routing = run(streams[row:row + 1])
        outs.append(None if out is None else np.asarray(out, np.float32))
        routings.append([(np.asarray(m), None if top_e is None else np.asarray(top_e)) for m, top_e in routing])
    joined = [(np.concatenate([routing[block][0] for routing in routings]),
               None if routings[0][block][1] is None else np.concatenate([routing[block][1] for routing in routings]))
              for block in range(len(routings[0]))]
    return (None if outs[0] is None else np.concatenate(outs)), joined


def check_against_reference(server, client_dht, config, seed, rehearse, log, slots: int, every_wrong_reference=True) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full
    forward with the same held share, of what the served path produced: streams of
    ``prompt + steps`` positions; (1) stream 0's prompt in chunks (the last padded) and
    single-token steps through the span over the wire; (2) all streams as sessions at
    different positions (prompts of different lengths, chunked the same way) that step in
    the same batched programs, at every bucket the window runs (`check_widths`: short filler
    sessions pad the larger ones); (3) the experts that the routers of those served programs
    chose (every chunk and every step of (1) and (2), `_RouterTaps`), against the reference's
    own and against the reference's router on the served programs' own router inputs.
    Then the wrong references of `wrong_references` (all of them, or with
    ``every_wrong_reference`` off those near the rounding): each one's own readings on these
    measures, and how much of its departure the served outputs hold (`_departure_share`).
    One that differs from the served arithmetic in a precision alone has to fall outside a
    limit; of every other the served outputs must hold little."""
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
    want, want_routing = reference(rows)

    # what the routers of the served programs saw and chose: per stream of the check (the one over the wire, then
    # each batched row) its crossings of the span in order, each a pair ``(m, top_e)`` a sparse block
    sparse = [index for index in range(blocks) if not is_dense(config, index)]
    crossings: Dict[Any, List] = {"wire": []}

    with _RouterTaps() as taps:
        # (1) over the wire, one session, the prompt in chunks
        pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
        pieces = []
        for start in list(range(0, prompt, chunk)) + list(range(prompt, prompt + steps)):
            stop = min(start + chunk, prompt) if start < prompt else start + 1
            pieces.append(pipe.decode_step(streams[:1, start:stop], "reference-check", reset=start == 0))
            crossings["wire"].append(taps.drain(len(sparse)))
        pipe.close_decode_session("reference-check")
        single = np.concatenate(pieces, axis=1)
        single_err, single_rms = runtime.rel_err(single, want[:1]), _rms_err(single, want[:1])
        log(f"reference check: a prompt of {prompt} in chunks of {chunk} + {steps} steps through the caches, {single_err:.2e} of the "
            f"largest value, {single_rms:.2e} rms")
        if not (single_err <= tolerances["decode_rel"] and single_rms <= tolerances["decode_rms_rel"]):
            faults.append(f"a prompt of {prompt} in chunks + {steps} steps through the caches is {single_err:.2e} of the largest value and "
                          f"{single_rms:.2e} rms from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

        # (2) the batched programs: row 0 is that stream, the others start from shorter prompts
        prompts = check_prompts(prompt, rows)
        got = [[] for _ in prompts]
        for row, length in enumerate(prompts):
            for start in range(0, length, chunk):
                got[row].append(manager._decode_direct(tuple(uids), f"reference-row{row}", streams[row:row + 1, start:min(start + chunk, length)],
                                                       reset=start == 0))
                crossings.setdefault(row, []).append(taps.drain(len(sparse)))
        widths = check_widths(rows, slots)
        names = [f"reference-row{row}" for row in range(rows)] + [f"reference-filler{at}" for at in range(widths[-1] - rows)]
        for name in names[rows:]:
            manager._decode_direct(tuple(uids), name, np.zeros((1, filler_prompt(prompt, chunk), hidden), np.float32), reset=True)
            taps.drain(len(sparse))  # a filler's: nothing to hold them against
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
            taken = taps.drain(len(sparse))  # every live row's pairs, a sparse block each: the fillers' are left
            for row in range(rows):
                crossings[row].append([(m[row:row + 1], top_e[row:row + 1]) for m, top_e in taken])
    manager.clear_sessions()  # the check's caches leave the device before the wrong references are computed, and the window
    scale = np.abs(want).max()
    served = [(np.concatenate(got[row], axis=1), slice(0, length + steps)) for row, length in enumerate(prompts)]
    batched_err = max(float(np.abs(out - want[row, span]).max() / scale) for row, (out, span) in enumerate(served))
    batched_rms = max(_rms_err(out, want[row:row + 1, span]) for row, (out, span) in enumerate(served))
    log(f"reference check: {rows} sessions at positions {prompts} stepping {steps} times in the same batched programs of "
        f"{widths} rows, {batched_err:.2e} of the largest value, {batched_rms:.2e} rms (worst row of each)")
    if not (batched_err <= tolerances["decode_rel"] and batched_rms <= tolerances["decode_rms_rel"]):
        faults.append(f"{rows} sessions in one batched program are {batched_err:.2e} of the largest value and {batched_rms:.2e} rms "
                      f"from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

    # (3) routing, of the served programs themselves: every chunk and every step of (1) and (2), stream after stream,
    # against the reference's own choices at the same positions
    held = [("wire", 0, prompt + steps)] + [(row, row, length + steps) for row, length in enumerate(prompts)]
    routing, want_chose = [(None, None)] * blocks, []
    for at, index in enumerate(sparse):
        routing[index] = tuple(np.concatenate([taken[at][part] for key, _stream, _upto in held for taken in crossings[key]], axis=1)
                               for part in (0, 1))
        want_chose.append(np.concatenate([want_routing[index][1][stream:stream + 1, :upto] for _key, stream, upto in held], axis=1))
    mismatch = _mismatch_share(_choices(routing), want_chose)
    pairs = sum(top_e.size for top_e in _choices(routing))
    log(f"reference check: {mismatch:.4%} of {pairs} (token, slot) pairs of the served programs chose an expert outside the reference's set")
    if not mismatch <= tolerances["routing_mismatch_share"]:
        faults.append(f"{mismatch:.4%} of (token, slot) pairs differ from the reference's routing, over "
                      f"{tolerances['routing_mismatch_share']:.2%}")
    router = _router_mismatch_share(all_params, routing, sizes)
    log(f"reference check: on the served programs' own router inputs, {router:.4%} of {pairs} pairs chose an expert that the "
        f"reference's float32 router does not")
    if not router <= tolerances["router_mismatch_share"]:
        faults.append(f"{router:.4%} of pairs differ from the float32 router on the same inputs, over "
                      f"{tolerances['router_mismatch_share']:.3%}: the program's router is not computed in float32")

    # every wrong reference, on the first two streams: it must fail a limit, or the served outputs must hold little of it.
    # A rehearsal's few hundred pairs hold too few near-ties to tell a router's precision: not faulted there
    few = min(rows, 2)
    right_few = [(m[:few], None if top_e is None else top_e[:few]) for m, top_e in want_routing]
    for name, (variant, precision_alone) in wrong_references(sizes, every_wrong_reference).items():
        out, wrong_routing = reference(few, **variant)
        largest, rms = runtime.rel_err(out, want[:few]), _rms_err(out, want[:few])
        routed = _mismatch_share(_choices(wrong_routing), _choices(right_few))
        own_router = _router_mismatch_share(all_params, wrong_routing, sizes)
        holds = max(abs(_departure_share([(single, want[:1], out[:1])])),  # over the wire; in the batched programs
                    abs(_departure_share([(o, want[row:row + 1, span], out[row:row + 1, span])
                                          for row, (o, span) in list(enumerate(served))[:few]])))
        outside = (largest > tolerances["decode_rel"] or rms > tolerances["decode_rms_rel"]
                   or routed > tolerances["routing_mismatch_share"] or own_router > tolerances["router_mismatch_share"])
        log(f"for the record, the reference with {name}: {largest:.2e} of the largest value, {rms:.2e} rms, {routed:.4%} of "
            f"pairs routed otherwise, {own_router:.4%} on its own router inputs: {'outside' if outside else 'inside'} "
            f"those limits; the served outputs hold {holds:.3f} of its departure")
        if precision_alone:  # the served arithmetic shares its rounding: only the limits can tell it
            if not outside and not rehearse:
                faults.append(f"the limits let a reference with {name} pass")
        elif not holds <= tolerances["departure_share"]:  # a program that computes it reads 1 here, whatever the noise
            faults.append(f"the served outputs hold {holds:.3f} of the departure of a reference with {name}, over "
                          f"{tolerances['departure_share']}: the program computes that, not the model")
    return faults


_ANY_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_ASYNC_COPY = re.compile(r"^\s*%?(copy-(?:start|done)[\w.\-]*)\s*=\s*\(?(\w+)\[([\d,]*)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1}
BATCHED_PROGRAM = "jit_batched_step_latent"
STAGING = "latent_staging"  # no scope of the program's: the compiler's own copies of a row's array, told by their size


def instruction_scopes(hlo_text: str, row_bytes=()) -> Dict[str, Optional[str]]:
    """EVERY instruction of an optimized program's text -> the scope of `SCOPES` its `op_name`
    lies in, or None: the scoped ones for the sums, all of them to tell the program by. With
    ``row_bytes`` (the sizes of a row's cache arrays), the asynchronous copies of arrays of exactly such a size
    (`copy-start` / `copy-done`, which carry no `op_name`) are `STAGING`: on the v5e the compiler
    brings each row's array into on-chip memory before the step writes and attends it there, so
    the one read of the latents from HBM is THEIR time and not the `latent_attend` operations'."""
    found: Dict[str, Optional[str]] = {match.group(1): None for match in map(_ANY_INSTRUCTION.match, hlo_text.splitlines()) if match}
    found.update(scope_of_instructions(hlo_text, SCOPES))
    for name, dtype, dims in (match.groups() for match in map(_ASYNC_COPY.match, hlo_text.splitlines()) if match):
        if _ITEMSIZE.get(dtype, 0) * math.prod(int(dim) for dim in dims.split(",") if dim) in row_bytes:
            found[name] = STAGING
    return found


def batched_programs(server, buckets: List[int], log) -> List[Dict[str, Optional[str]]]:
    """`instruction_scopes` of every block's batched decode program at every bucket of
    ``buckets`` (a window's cohorts run several: a program of 16 rows numbers its instructions
    otherwise than one of 32), read off the compiled programs' own texts (after the window: the
    compilations are reads of the cache the warm-up filled, and no part of a measurement)."""
    import jax

    manager = server.handler.decode_sessions
    found = []
    for uid, backend in server.backends.items():
        for rows in buckets:
            try:
                shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
                columns = tuple((leaf,) * rows for leaf in shape(manager._dummy_rows(uid)))
                xs = jax.ShapeDtypeStruct((rows, 1, backend.module.hidden_dim), "float32")
                lowered = manager._batched_fn(uid, rows).jitted.lower(shape(backend.snapshot_params()), xs, columns,
                                                               jax.ShapeDtypeStruct((rows,), "int32"))
                found.append(instruction_scopes(lowered.compile().as_text(), row_bytes={leaf.nbytes for leaf in manager._dummy_rows(uid)}))
            except Exception as e:  # a program whose text cannot be had: the scopes' metrics are left out
                log(f"{BATCHED_PROGRAM} ({uid}, {rows} rows): no program text to read the scopes from ({e!r})")
                return []
        scoped = [scope for scope in found[-1].values() if scope]
        log(f"{BATCHED_PROGRAM} ({uid}) at {buckets} rows: at the last, {len(scoped)} of {len(found[-1])} instructions lie in a named scope "
            f"({sorted(set(scoped))})")
    return found


def scope_seconds(trace_dir, candidates: List[Dict[str, Optional[str]]], log=None) -> Dict[str, Dict[str, float]]:
    """Device seconds and events of the operations of each named scope in the runs of
    `BATCHED_PROGRAM` in the newest trace under ``trace_dir``. The span's blocks run programs of
    one name and different texts (a dense block's, a sparse block's), whose instruction names
    collide: each traced program (the `XLA Modules` line's name WITH its id) is matched to the
    candidate (`instruction_scopes` of one block's program at one bucket) that holds most of the
    instruction names its runs executed, and its operations take that candidate's scopes; how
    much of each traced program's device time the chosen text accounts for is logged (a text of
    another bucket would account for little of it). An operation belongs to the run that holds
    its start. Empty where there is no trace; averaged over the
    device planes."""
    from perf.trace_reduce import DEVICE_PLANE, OP_LINES, find_xplane, load_planes, op_stem

    path = find_xplane(str(trace_dir))
    if path is None or not candidates:
        return {}
    planes = {name: lines for name, lines in load_planes(path).items() if DEVICE_PLANE.match(name)}
    totals: Dict[str, Dict[str, float]] = {}
    for lines in planes.values():
        runs = sorted((start, start + duration, name) for name, start, duration in lines.get(MODULE_LINE, [])
                      if name.split("(", 1)[0] == BATCHED_PROGRAM)
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
            scopes = max(candidates, key=lambda candidate: len(names & candidate.keys()))
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
        kinds = ["dense" if is_dense(config, index) else "sparse" for index in range(model["num_hidden_layers"])]
        log(f"{len(kinds)} blocks ({', '.join(kinds)}: the model's {model['first_block']}-{model['first_block'] + len(kinds) - 1}) hidden "
            f"{model['hidden_size']} / {model['num_attention_heads']} heads of {model['qk_nope_head_dim']} + {model['qk_rope_head_dim']} over a "
            f"latent of {model['kv_lora_rank']} / values {model['v_head_dim']} / experts {model['n_routed_experts']} held of "
            f"{config['share']['router_outputs']} in {model['n_group']} groups, {model['num_experts_per_tok']} a token of {model['topk_group']} "
            f"groups, on the device in {time.monotonic() - built:.1f} s")
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
    attended = delta(moved, {"metric": "hivemind_moe_latent_positions_attended_total", "series": "path=batched"})
    log(f"window: {cohorts:.0f} cohorts, {programs_run:.0f} batched programs of {rows_run / max(programs_run, 1):.2f} rows at "
        f"{attended / max(rows_run, 1):.0f} positions a row; gap ms p50 / p90 / p95 / p99 {_percentiles(samples.get('token_gap_ms', []))}, "
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
