"""A span of MiniCPM-SALA blocks behind the block server: block-sparse attention blocks
and lightning linear-attention blocks in one span (`mixer_types`), each built with its
own kwargs, a session's cache a tree (keys, values and compressed keys beside a
recurrent state), prompts that arrive in chunks. The server, the load generators and
the window are `block_server.py`'s; the programs' device time by name, the share of a
wrong reference's departure and the log's percentiles are `hybrid_moe_block_server.py`'s,
imported as they are.

`correct` is decided by what the served path produced (`check_against_reference`): at
the published widths, against `perf/reference/minicpm_sala_block.py` in float32 at the
highest matmul precision, block by block and stream by stream (a stream of 9,192
positions is one program a block). Beside the largest and the rms difference stands
the selection: the blocks that the SERVED programs' queries chose (the chunks' and the
steps' own, one session's and the batched ones at every bucket the window runs, handed
out through `routing_stats.SELECTION_TAPS`) and the reference's did not, or the other way
round (`selection_mismatch_share`), and for each WRONG reference how much of its
departure the served outputs hold (`hybrid_moe_block_server._departure_share`).

The lead-in of this runner's cells holds every prefill, so the runner also reads the
program's counters when the lead-in starts (`counters_lead`: until the window opens),
for `prefill_ms_per_1k_positions.chunked`; and after a traced window it sums the device
time of the decode programs' operations by the named scope each came from
(`scopes`: `lightning_step`, `sparse_select`, `sparse_attend`), which the operation
names in a trace do not carry, through the compiled programs' own text.

The block class is resolved before a DHT or a client process starts: a program that
lacks it (a parent commit) fails at once."""

from __future__ import annotations

import functools
import math
import re
import time
from typing import Any, Dict, List

from perf import runtime
from perf.manifest import plugin
from perf.runners.block_server import LoadGenerators
from perf.runners.hybrid_moe_block_server import _departure_share, _percentiles, program_seconds
from perf.runners.moe_block_server import _rms_err

SCOPES = ("lightning_step", "sparse_select", "sparse_attend")


def mixers(config: Dict[str, Any]) -> List[str]:
    """The span's share of the published `mixer_types`."""
    model = config["model"]
    return model["mixer_types"][model["first_block"]:model["first_block"] + model["num_hidden_layers"]]


def block_kwargs(config: Dict[str, Any], index: int) -> Dict[str, Any]:
    """Block ``index``'s own sizes: its mixer from the configuration's per-layer list."""
    model = config["model"]
    mixer = mixers(config)[index]
    lightning = mixer == "lightning-attn"
    return dict(
        mixer=mixer,
        num_heads=model["lightning_nh"] if lightning else model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model["lightning_head_dim"] if lightning else model["head_dim"],
        ffn_inner=model["intermediate_size"], rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        residual_scale=model["scale_depth"] / math.sqrt(config["published"]["num_hidden_layers"]),
        **model["sparse_config"],
    )


def reference_sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keyword arguments of the reference's `block`, as the configuration has them."""
    model = config["model"]
    return dict(
        alpha=model["scale_depth"] / math.sqrt(config["published"]["num_hidden_layers"]), rms_eps=model["rms_norm_eps"],
        lightning=dict(heads=model["lightning_nh"], head_dim=model["lightning_head_dim"], rope_theta=float(model["rope_theta"])),
        sparse=dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
                    **model["sparse_config"]),
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


def padded_chunks(lengths, chunk: int) -> List[int]:
    """The padded lengths of every chunk that prompts of ``lengths`` arrive in."""
    rests = {min(chunk, length - start) for length in lengths for start in range(0, length, chunk)}
    return sorted({1 << (rest - 1).bit_length() for rest in rests if rest > 1})


def warm_decode(server, config, traffic, check_lengths, log) -> None:
    """Every decode program the traffic and the reference check can reach, per block: a
    chunk of each padded length, the single-session step, and the batched step at every
    power-of-two bucket up to the slots."""
    import numpy as np

    manager = server.handler.decode_sessions
    hidden = config["model"]["hidden_size"]
    slots = traffic["processes"] * traffic["slots_per_process"]
    top = 1 << (slots - 1).bit_length()
    buckets = [2**k for k in range(1, top.bit_length())]
    token = np.zeros((1, 1, hidden), np.float32)
    lengths = padded_chunks(list(traffic["prompt_lengths"]) + list(check_lengths), traffic["chunk"])
    for uid in server.backends:
        for length in lengths:
            manager.decode(uid, f"warm-len{length}", np.zeros((1, length, hidden), np.float32), reset=True)
        manager.decode(uid, f"warm-len{lengths[0]}", token, reset=False)
        names = [f"warm-row{i}" for i in range(max(buckets))]
        for name in names:
            manager.decode(uid, name, np.zeros((1, lengths[0], hidden), np.float32), reset=True)
        for rows in buckets + [max(buckets) - 1]:  # every full bucket, and one short of one (it pads with the dummy rows)
            entries = [(None, manager._sessions[(uid, name)], token) for name in names[:rows]]
            raised = [o for o in manager._decode_batch(uid, entries) if isinstance(o, Exception)]
            if raised:
                raise raised[0]
        manager.clear_sessions()  # the warm-up's caches must not sit on the device through the window
    log(f"decode warm-up: chunks {lengths}, session buckets {buckets}, {len(server.backends)} blocks")


# ---- the reference, one jitted program a kind of block ------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_block(dtype: str, frozen_sizes):
    """The reference's block under jit with its sizes fixed (``frozen_sizes``: the
    hashable form of `block`'s keyword arguments), every parameter and the input rounded
    to ``dtype`` and the arithmetic done in it (float32: as they are, at the highest
    matmul precision)."""
    import jax

    from perf.reference import minicpm_sala_block as reference

    sizes = {key: dict(value) if isinstance(value, tuple) else value for key, value in frozen_sizes}

    def run(params, x, lam):
        with jax.default_matmul_precision("highest"):
            cast = lambda leaf: leaf.astype(dtype)
            lightning = dict(sizes["lightning"], lam=lam.astype(dtype))
            return reference.block(jax.tree_util.tree_map(cast, params), cast(x), return_selection=True,
                                   **{**sizes, "lightning": lightning})

    return jax.jit(run)


def _frozen(sizes):
    return tuple((key, tuple(value.items()) if isinstance(value, dict) else value) for key, value in sizes.items())


def reference_span(all_params, x, sizes, first_block: int, dtype: str = "float32", decay: str = "model",
                   published_layers: int = 32):
    """`minicpm_sala_block.span_with_selection` block by block, each under `_jitted_block`,
    on ONE stream ``[1, T, hidden]``: the output and each block's selection. ``decay``:
    ``model`` (the assumed one), ``none`` (lambda = 1) or ``per_layer`` (MiniMax-01's factor
    by the block's published number): the last two make wrong references."""
    import jax.numpy as jnp

    from perf.reference import minicpm_sala_block as reference

    heads = sizes["lightning"]["heads"]
    selections = []
    for index, params in enumerate(all_params):
        lam = {"model": lambda: reference.decay(heads), "none": lambda: jnp.ones(heads, jnp.float32),
               "per_layer": lambda: reference.decay(heads, first_block + index, published_layers)}[decay]()
        x, picked = _jitted_block(dtype, _frozen(sizes))(params, x, lam)
        selections.append(picked)
    return x, selections


def wrong_references(sizes, published_layers: int, span_layers: int) -> Dict[str, Dict[str, Any]]:
    """What the limits must refuse: name -> keyword arguments of `reference_span`. Each
    departs from the model as assumed in ONE thing."""
    sparse, lightning = sizes["sparse"], sizes["lightning"]
    with_sparse = lambda **change: dict(sizes=dict(sizes, sparse=dict(sparse, **change)))
    with_lightning = lambda **change: dict(sizes=dict(sizes, lightning=dict(lightning, **change)))
    return {
        "all bf16 (weights, activations, state)": dict(dtype="bfloat16"),
        "no decay (lambda = 1)": dict(decay="none"),
        "the decay with MiniMax-01's per-layer factor": dict(decay="per_layer"),
        f"topk {sparse['topk'] // 2} of {sparse['topk']}": with_sparse(topk=sparse["topk"] // 2),
        "no forced window blocks": with_sparse(force_window=False),
        "dense attention at every length": with_sparse(dense_len=1 << 30),
        "rotary embedding in the sparse blocks": with_sparse(rope_theta=lightning["rope_theta"]),
        f"alpha = scale_depth / sqrt({span_layers}), the span's depth": dict(
            sizes=dict(sizes, alpha=sizes["alpha"] * math.sqrt(published_layers / span_layers))),
        "no output gate": dict(sizes=dict(sizes, sparse=dict(sparse, output_gate=False), lightning=dict(lightning, output_gate=False))),
        "no output norm": with_lightning(output_norm=False),
    }


# All ten fall outside a limit at the published widths (my chip runs, PR 41: the three that only move
# the selection by the selection's), so a plain run computes none of them: they are the limits' own
# evidence and are computed in a traced run and a rehearsal


def _as_picked(chosen, blocks: int):
    """Block numbers ``[.., k]`` (-1 = none) as booleans ``[.., blocks]``."""
    import numpy as np

    chosen = np.asarray(chosen)
    picked = np.zeros(chosen.shape[:-1] + (blocks + 1,), bool)
    np.put_along_axis(picked, np.where(chosen >= 0, chosen, blocks), True, axis=-1)  # "none" lands on a slot of its own
    return picked[..., :blocks]


def selection_mismatch_share(ours: List, theirs: List) -> float:
    """Of the blocks that either side's sparse-mode queries selected, the share that
    only ONE side selected: per block of the span ``[.., T, kv_heads, blocks]`` booleans
    (a query in the dense mode selects nothing on either side); None entries
    (lightning blocks) are skipped. 0: the same selection; 1: disjoint, or one side empty."""
    import numpy as np

    differing = total = 0
    for a, b in zip(ours, theirs):
        if a is None:
            continue
        a, b = np.asarray(a), np.asarray(b)
        differing += int((a ^ b).sum())
        total += int(a.sum()) + int(b.sum())
    return differing / max(total, 1)


class _Selections:
    """What the served programs' sparse blocks selected while this is open, in the order
    their calls settled (`routing_stats.SELECTION_TAPS`): one array a call of a sparse block."""

    def __enter__(self):
        from hivemind_tpu.moe.server.routing_stats import SELECTION_TAPS

        self.taken: List = []
        self._tap = self.taken.append
        SELECTION_TAPS.append(self._tap)
        return self

    def __exit__(self, *_exc):
        from hivemind_tpu.moe.server.routing_stats import SELECTION_TAPS

        SELECTION_TAPS.remove(self._tap)

    def drain(self, sparse: List[int]) -> Dict[int, List]:
        """The selections taken since the last drain, by sparse block: the calls crossed
        the span one after another, each through the ``sparse`` blocks in order."""
        taken, self.taken[:] = list(self.taken), []
        assert len(taken) % len(sparse) == 0, (len(taken), sparse)
        return {block: taken[at::len(sparse)] for at, block in enumerate(sparse)}


def check_widths(rows: int, slots: int) -> List[int]:
    """The rows of the batched programs the check steps its sessions in, a share of the
    steps each: its own sessions alone, and with fillers beside them up to each bucket
    the window's cohorts run (half the slots and all of them)."""
    return sorted({rows, max(slots // 2, rows), max(slots, rows)})


def check_against_reference(server, client_dht, config, seed, rehearse, log, slots: int, every_wrong_reference=True) -> List[str]:
    """Outside the window, at the published widths, against the plain reference's full
    forward, of what the served path produced: streams of ``prompt + steps`` positions,
    the prompt no power of two, over `dense_len`, in three chunks; (1) stream 0's chunked
    prefill and single-token steps through the span over the wire; (2) all streams as
    sessions at different positions (prompts of different lengths, chunked the same way)
    that step in the same batched programs, at every bucket the window runs (`check_widths`:
    short filler sessions, which step in the dense mode, pad the larger ones); (3) the
    blocks that THOSE programs' queries selected, chunk by chunk and step by step, against
    the reference's own at the same positions: over all of them, and over the single steps
    alone (a step reads compressed keys written one kernel at a time and gathers from a
    row's own arrays: a chunk's 9,000 positions would drown its 192). Then the wrong
    references: each one's own readings on these measures, and how much of its departure
    the served outputs hold."""
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential

    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    prompt, steps, rows = check_shape(rehearse)
    chunk = serving["prompt_chunk"]
    hidden, blocks = model["hidden_size"], model["num_hidden_layers"]
    uids = [f"{serving['uid_prefix']}{i}" for i in range(blocks)]
    all_params = [server.backends[uid].snapshot_params() for uid in uids]
    sizes, published = reference_sizes(config), config["published"]["num_hidden_layers"]
    manager = server.handler.decode_sessions
    rng = np.random.default_rng(seed)
    streams = runtime.float16_exact(rng.standard_normal((rows, prompt + steps, hidden), dtype=np.float32))
    faults = []

    def reference(row_count, **variant):
        outs, picks = [], []
        for row in range(row_count):
            out, picked = reference_span(all_params, jnp.asarray(streams[row:row + 1]), first_block=model["first_block"],
                                         published_layers=published, **{"sizes": sizes, **variant})
            outs.append(np.asarray(out, np.float32))
            picks.append([None if p is None else np.asarray(p) for p in picked])
        return np.concatenate(outs), [None if picks[0][b] is None else np.concatenate([p[b] for p in picks]) for b in range(blocks)]

    want, want_picked = reference(rows)
    sparse = [b for b in range(blocks) if want_picked[b] is not None]
    prompts = check_prompts(prompt, rows)
    # the served streams whose selections are compared: (stream, its prompt); the first went over the wire, the others
    # are the batched rows. chosen[block][at]: the arrays [positions, kv_heads, topk] that stream's calls handed out
    compared = [(0, prompt)] + list(enumerate(prompts))
    chosen = {block: [[] for _ in compared] for block in sparse}

    with _Selections() as selections:
        # (1) over the wire, one session, the prompt in chunks
        pipe = RemoteSequential(client_dht, serving["uid_prefix"], blocks)
        pieces = [pipe.decode_step(streams[:1, start:min(start + chunk, prompt)], "reference-check", reset=start == 0)
                  for start in range(0, prompt, chunk)]
        for position in range(prompt, prompt + steps):
            pieces.append(pipe.decode_step(streams[:1, position:position + 1], "reference-check"))
        pipe.close_decode_session("reference-check")
        for block, calls in selections.drain(sparse).items():
            chosen[block][0] += [call[0] for call in calls]
        single = np.concatenate(pieces, axis=1)
        single_err, single_rms = runtime.rel_err(single, want[:1]), _rms_err(single, want[:1])
        log(f"reference check: a prompt of {prompt} in chunks of {chunk} + {steps} steps through the caches, {single_err:.2e} of the "
            f"largest value, {single_rms:.2e} rms")
        if not (single_err <= tolerances["decode_rel"] and single_rms <= tolerances["decode_rms_rel"]):
            faults.append(f"a prompt of {prompt} in chunks + {steps} steps through the caches is {single_err:.2e} of the largest value and "
                          f"{single_rms:.2e} rms from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

        # (2) the batched programs: row 0 is that stream, the others start from shorter prompts
        got = [[] for _ in prompts]
        for row, length in enumerate(prompts):
            for start in range(0, length, chunk):
                got[row].append(manager._decode_direct(tuple(uids), f"reference-row{row}", streams[row:row + 1, start:min(start + chunk, length)],
                                                       reset=start == 0))
            for block, calls in selections.drain(sparse).items():
                chosen[block][1 + row] += [call[0] for call in calls]
        widths = check_widths(rows, slots)
        names = [f"reference-row{row}" for row in range(rows)] + [f"reference-filler{at}" for at in range(widths[-1] - rows)]
        for name in names[rows:]:  # a short prompt: these rows step in the dense mode beside the others
            manager._decode_direct(tuple(uids), name, np.zeros((1, filler_prompt(prompt, chunk), hidden), np.float32), reset=True)
        selections.drain(sparse)
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
            for block, calls in selections.drain(sparse).items():
                [call] = calls  # one batched program a block: its live rows, the check's own first
                for row in range(rows):
                    chosen[block][1 + row].append(call[row])
    scale = np.abs(want).max()
    served = [(np.concatenate(got[row], axis=1), slice(0, length + steps)) for row, length in enumerate(prompts)]
    batched_err = max(float(np.abs(out - want[row, span]).max() / scale) for row, (out, span) in enumerate(served))
    batched_rms = max(_rms_err(out, want[row:row + 1, span]) for row, (out, span) in enumerate(served))
    log(f"reference check: {rows} sessions at positions {prompts} stepping {steps} times in the same batched programs of "
        f"{widths} rows, {batched_err:.2e} of the largest value, {batched_rms:.2e} rms (worst row of each)")
    if not (batched_err <= tolerances["decode_rel"] and batched_rms <= tolerances["decode_rms_rel"]):
        faults.append(f"{rows} sessions in one batched program are {batched_err:.2e} of the largest value and {batched_rms:.2e} rms "
                      f"from the reference's full forward, over {tolerances['decode_rel']} / {tolerances['decode_rms_rel']}")

    # (3) the selection of those served programs, stream by stream, against the reference's on the same stream at
    # the same positions: over every position, and over the single steps alone
    ours, right = [None] * blocks, [None] * blocks
    for block in sparse:
        ours[block] = np.concatenate([_as_picked(call, want_picked[block].shape[-1]) for calls in chosen[block] for call in calls])
        right[block] = np.concatenate([want_picked[block][stream, :length + steps] for stream, length in compared])
    stepped = np.concatenate([np.arange(length + steps) >= length for _stream, length in compared])
    assert all(len(ours[block]) == len(stepped) for block in sparse), ([len(ours[block]) for block in sparse], len(stepped))
    only = lambda picked, where: [None if p is None else p[where] for p in picked]
    mismatch, mismatch_steps = selection_mismatch_share(ours, right), selection_mismatch_share(only(ours, stepped), only(right, stepped))
    made = sum(int(picked.sum()) for picked in ours if picked is not None)
    log(f"reference check: of the {made} (query, key-value head, block) selections the served chunks and steps made and the "
        f"reference's, {mismatch:.4%} are on one side only; of the {int(stepped.sum())} single steps' alone, {mismatch_steps:.4%}")
    for name, share in (("served chunks and steps", mismatch), ("served single steps", mismatch_steps)):
        if not share <= tolerances["selection_mismatch_share"]:
            faults.append(f"{share:.4%} of the blocks the {name} selected differ from the reference's selection, over "
                          f"{tolerances['selection_mismatch_share']:.2%}")

    # (4) the compressed keys that those steps WROTE: a step's selection forces the window's blocks, so a kernel
    # written at a step is first read `window_size` positions later, past these streams' end
    stale = stale_kernels(manager, [(uids[block], f"reference-row{row}", length) for block in sparse for row, length in enumerate(prompts)],
                          steps, model["sparse_config"])
    log(f"reference check: of the kernels that the batched steps completed, {stale[0]} of {stale[1]} are not the mean of the keys "
        f"their session holds")
    if stale[0] or not stale[1]:
        faults.append(f"{stale[0]} of the {stale[1]} compressed keys that the steps completed are not the mean of their session's keys")

    # every wrong reference, on the first two streams: it must fall outside a limit, or the served outputs must hold little of it
    wrong = wrong_references(sizes, published, blocks)
    few = min(rows, 2)
    right_few = [None if p is None else p[:few] for p in want_picked]
    for name in (wrong if every_wrong_reference else ()):
        out, wrong_picked = reference(few, **wrong[name])
        largest, rms = runtime.rel_err(out, want[:few]), _rms_err(out, want[:few])
        selected = selection_mismatch_share(wrong_picked, right_few)  # its own selection, held as the programs' is
        # ... and over the streams' last positions alone, which were served as single steps: what a STEP path that
        # selects as this reference does would read there, whatever its chunks selected
        last = lambda picked: [None if p is None else p[:, -steps:] for p in picked]
        selected_steps = selection_mismatch_share(last(wrong_picked), last(right_few))
        holds = max(abs(_departure_share([(single, want[:1], out[:1])])),
                    abs(_departure_share([(o, want[row:row + 1, span], out[row:row + 1, span])
                                          for row, (o, span) in list(enumerate(served))[:few]])))
        outside = (largest > tolerances["decode_rel"] or rms > tolerances["decode_rms_rel"]
                   or selected > tolerances["selection_mismatch_share"])
        log(f"for the record, the reference with {name}: {largest:.2e} of the largest value, {rms:.2e} rms, {selected:.4%} of its "
            f"selections outside the right one's ({selected_steps:.4%} over the last {steps} positions alone, the single steps'): "
            f"{'outside' if outside else 'inside'} those limits; the served outputs hold {holds:.3f} of its departure")
        # the precision below shares the served arithmetic's rounding: only the limits can tell it. A program
        # that computes one of the others reads 1 here, whatever the noise
        if not name.startswith("all bf16") and not holds <= tolerances["departure_share"]:
            faults.append(f"the served outputs hold {holds:.3f} of the departure of a reference with {name}, over "
                          f"{tolerances['departure_share']}: the program computes that, not the model")
        elif not outside and not rehearse:
            faults.append(f"the limits let a reference with {name} pass")
    manager.clear_sessions()  # the check's caches leave the device before the window
    return faults


def stale_kernels(manager, sessions, steps: int, sparse_config: Dict[str, int]):
    """(wrong, all) of the kernels that the last ``steps`` single steps of ``sessions`` [(uid,
    session id, its prompt's length)] completed: a session's compressed keys (the third leaf of
    a sparse block's cache) against the float32 mean, rounded as they are kept, of the keys
    the same session holds (the first leaf), to two units of bf16's last place."""
    import numpy as np

    size, stride = sparse_config["kernel_size"], sparse_config["kernel_stride"]
    wrong = total = 0
    for uid, name, prompt in sessions:
        keys, _values, compressed = manager._sessions[(uid, name)].leaves
        first, last = -(-(prompt + 1 - size) // stride), (prompt + steps - size) // stride  # those that END inside the steps
        held = np.asarray(keys[0, :, first * stride:last * stride + size], np.float32)
        means = np.stack([held[:, (m - first) * stride:(m - first) * stride + size].mean(1) for m in range(first, last + 1)], axis=1)
        kept = np.asarray(compressed[0, :, first:last + 1], np.float32)
        off = np.abs(kept - means).max(-1) > 2.0**-7 * np.abs(means).max(-1)
        wrong, total = wrong + int(off.sum()), total + off.size
    return wrong, total


def check_shape(rehearse: bool):
    """(prompt, steps, streams) of the reference check: the prompt is no power of two,
    over `dense_len`, and three chunks long."""
    return (200 - 40, 40, 2) if rehearse else (9000, 192, 8)


def filler_prompt(prompt: int, chunk: int) -> int:
    """The prompt of a session that only fills a batched program's rows in the check."""
    return min(chunk, prompt) // 4


def check_prompts(prompt: int, rows: int) -> List[int]:
    """The prompts of the batched rows: row 0 the whole one, the others shorter by a little each."""
    return [prompt - max(prompt // 360, 1) * row for row in range(rows)]


# ---- device time by named scope -----------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def scope_of_instructions(hlo_text: str, scopes=SCOPES) -> Dict[str, str]:
    """instruction name -> the scope (of ``scopes``) its `op_name` lies in, for the
    instructions of an optimized program's text whose metadata names one. A fusion
    carries the `op_name` of the operation it was built around."""
    found = {}
    for line in hlo_text.splitlines():
        match = _INSTRUCTION.match(line)
        if match:
            for scope in scopes:
                if f"/{scope}/" in match.group(2) or match.group(2).endswith("/" + scope):
                    found[match.group(1)] = scope
    return found


def scope_seconds(trace_dir, program_scopes: Dict[str, Dict[str, str]]) -> Dict[str, Dict[str, float]]:
    """Device seconds and events of the operations of each named scope in the newest trace
    under ``trace_dir``: ``program_scopes`` maps a program's name (`jit_batched_step_sparse`)
    to its instructions' scopes (`scope_of_instructions`); an operation belongs to the
    program whose run (the `XLA Modules` line) holds its start. Empty where there is no
    trace; averaged over the device planes."""
    from perf.runners.hybrid_moe_block_server import MODULE_LINE
    from perf.trace_reduce import DEVICE_PLANE, OP_LINES, find_xplane, load_planes, op_stem

    path = find_xplane(str(trace_dir))
    if path is None or not program_scopes:
        return {}
    planes = {name: lines for name, lines in load_planes(path).items() if DEVICE_PLANE.match(name)}
    totals: Dict[str, Dict[str, float]] = {}
    for lines in planes.values():
        runs = sorted((start, start + duration, name.split("(", 1)[0]) for name, start, duration in lines.get(MODULE_LINE, []))
        runs = [run for run in runs if run[2] in program_scopes]
        at = 0
        for name, start, duration in sorted((event for line in OP_LINES for event in lines.get(line, [])), key=lambda e: e[1]):
            while at < len(runs) and runs[at][1] <= start:
                at += 1
            if at == len(runs):
                break
            if runs[at][0] <= start:
                instruction = name.split(" = ", 1)[0].lstrip("%")
                scope = program_scopes[runs[at][2]].get(instruction) or program_scopes[runs[at][2]].get(op_stem(name))
                if scope:
                    entry = totals.setdefault(scope, {"seconds": 0.0, "count": 0.0, "runs": 0.0})
                    entry["seconds"] += duration / 1e9 / len(planes)
                    entry["count"] += 1.0 / len(planes)
        for _start, _end, name in runs:
            for scope in set(program_scopes[name].values()):
                totals.setdefault(scope, {"seconds": 0.0, "count": 0.0, "runs": 0.0})["runs"] += 1.0 / len(planes)
    return totals


def batched_program_scopes(server, rows: int, log) -> Dict[str, Dict[str, str]]:
    """The scopes of the instructions of each kind's batched decode program at the bucket
    of ``rows``, read off the compiled program's own text (after the window: the
    compilation is a read of the cache the warm-up filled, and no part of a measurement)."""
    import jax

    manager = server.handler.decode_sessions
    found: Dict[str, Dict[str, str]] = {}
    for uid, backend in server.backends.items():
        kind = getattr(backend.module, "decode_cache_kind", None)
        name = f"jit_batched_step_{kind}"
        if not kind or name in found:
            continue
        try:
            shape = lambda tree: jax.tree_util.tree_map(lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)
            columns = tuple((leaf,) * rows for leaf in shape(manager._dummy_rows(uid)))
            xs = jax.ShapeDtypeStruct((rows, 1, backend.module.hidden_dim), "float32")
            lowered = manager._batched_fn(uid, rows).jitted.lower(shape(backend.snapshot_params()), xs, columns,
                                                           jax.ShapeDtypeStruct((rows,), "int32"))
            found[name] = scope_of_instructions(lowered.compile().as_text())
            log(f"{name}: {len(found[name])} instructions lie in a named scope ({sorted(set(found[name].values()))})")
        except Exception as e:  # a program whose text cannot be had: the scopes' metrics are left out
            log(f"{name}: no program text to read the scopes from ({e!r})")
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
        log(f"{model['num_hidden_layers']} blocks ({', '.join(mixers(config))}) hidden {model['hidden_size']} / "
            f"{model['num_attention_heads']} x {model['head_dim']} heads / {model['num_key_value_heads']} kv / inner "
            f"{model['intermediate_size']} / sparse {model['sparse_config']}, on the device in {time.monotonic() - built:.1f} s")
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
        check_seconds = time.monotonic() - checked
        log(f"the reference check took {check_seconds:.1f} s")
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (every prompt, uncounted) is set-up
        # the check is the benchmark's own work, and ten wrong references longer in a traced run: its seconds are
        # no part of what a deployment waits for before it serves
        setup_s = begin - started - check_seconds
        counters_lead = runtime.counters()
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
        programs = program_seconds(runtime.TRACE_DIR) if traced else {}
        memory_peak = runtime.memory_peak_bytes(devices, log)
        scopes = scope_seconds(runtime.TRACE_DIR, batched_program_scopes(server, 1 << (slots_total - 1).bit_length(), log)) if traced else {}
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
    log(f"window: {cohorts:.0f} cohorts, {programs_run:.0f} batched programs of {rows_run / max(programs_run, 1):.2f} rows; "
        f"gap ms p50 / p90 / p95 / p99 {_percentiles(samples.get('token_gap_ms', []))}, largest "
        f"{max(samples.get('token_gap_ms') or [0.0]):.0f}; server ms a decode request p50 / p90 / p95 / p99 "
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
        "serving": serving,
        "programs": programs,
        "scopes": scopes,
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
