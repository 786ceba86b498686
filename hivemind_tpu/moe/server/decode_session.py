"""Server-side KV-cache decode sessions for pipelined autoregressive inference.

Petals serves Llama blocks with per-client attention caches so each generated token
costs O(context) instead of the O(context²) right-padded recompute that
`RemoteSequential.__call__` implies. This is the session layer for the same
capability on the TPU stack: a client opens a session per block uid (a msgpack
`{"session_id", "reset"}` rides `ExpertRequest.metadata` — no proto change), the
first call prefills the prompt (or its first chunk) into fresh caches, and every
later call advances one token — or, on a chain whose blocks all take chunks, brings
the prompt's next chunk. A session's cache lives on-device as whatever TREE of arrays
the block's `init_decode_cache` returned (a `(cache_k, cache_v)` pair, a recurrent
state, keys with values and compressed keys, a convolution window beside a state, or
NOTHING: a block that keeps no cache has a tree of no leaf and still a session at every block
of its chain): the manager joins, splits, donates, places and counts it leaf by leaf and never
looks inside. EVERY step donates the leaves it
is handed, a session's own call and a batched program alike: the new leaves take the old
ones' buffers, nothing is copied or allocated for them, and a block must not keep a
reference to a cache argument. The step function is jitted once per
(kind of block, batch, chunk-length) signature (`DecodeSessionManager._kind`: equal
blocks share their programs), and sessions expire by TTL / LRU cap so an
abandoned client cannot pin device memory.

**A pass axis** (ISSUE 56). A LOOPED model runs its stack of blocks several times a token with
the same weights, pass u on the keys and values that pass u wrote. A block class says how
many passes its sessions hold (``decode_passes``; 1 where it says nothing: every block but
such a model's), a request names its pass (``loop_pass`` in the metadata, 0 where it names
none), and the manager reads both: a session is still ONE entry of the table a block — one
to the LRU cap, the TTL, `clear_sessions`, a failed step's drop and the eviction counters —
holding a cache tree and a position A PASS (`_Session.trees`, `.positions`), made together
at the reset of pass 0 and dropped together; the byte gauges count every tree. A step reads,
donates and advances the tree of its own pass only, the program is the same at every pass
(the block never learns which it is), and a cohort takes the waiting rows whatever their
pass. The loop's order is held here as "a session is full" is: pass u may not run ahead of
pass u-1. The norm between two passes is the client's.

**Continuous batching, per span chain** (`decode_span_async`; `decode_async` is the
chain of one): a request names the chain of this server's blocks it crosses, and the
chain, not the block, is the unit of batching. Single-token steps of different
clients' sessions that wait on the same chain form a **cohort**, which walks the
chain's blocks in ONE executor call: at each block ONE device call for all the
rows — the block is applied once to ``[rows, 1, hidden]`` with a VECTOR of write
positions, and only its cache update and the attention over the cache run per row
(`layers.common._grouped_cache_step`, one call a row on that row's own cache, kept
``[1, kv_heads, slots, head_dim]``: the queries of a KV head against that head's slots as they lie): the block's
matmuls, and a sparse expert layer above all, see the cohort's rows together — and each row's
output is the next block's input, left on the device: the next block's program is
dispatched while this one runs (at most one program ahead), and only the chain's
last output comes to the host. No event-loop turn, no future, no flush window and
no transfer lie between two blocks; the futures resolve at the chain's end.

A prefill (or any step that cannot be batched) walks the chain the same way for its
one session (`_decode_direct`): the padded prompt is uploaded once, each block's own
program runs on the output of the block before, and the last output is fetched.

Steps that arrive while a cohort is launched wait for the next one, which starts
when this one's last block is dispatched, with all of them up to a full bucket
(`_cohort_rows`: a program costs by the power of two its rows are padded to; with
more than 16 rows under way on the chain, up to the bucket that holds half; and with
that many under way, a cohort SHORT of that bucket waits for the cohort still on the
device to be answered before it is launched: a walk that outruns its clients would
else run a bucket for a few rows). This
cohort's output is awaited and answered beside the next one's launch, so the device
finds the next cohort's first program queued behind this one's last. The cohort
under way is the window; `FLUSH_WINDOW_S` is what a chain that was idle waits.
Chains that differ but share blocks (``[b0…b7]`` beside ``[b4…b7]``) each batch
among their own sessions and meet only at the session locks, taken per block in
address order: correct, merely unmerged.

What a block class owes this path (``index`` as a scalar in a session's own call, as
a vector in a batched step) is written down in `moe/server/layers/__init__.py`. The
session count is bucketed to powers of two so the jit cache stays small. Sessions
keep their caches one tree each, as the tuple of its leaves; a block's program takes
them as they are and hands the new leaves back one array a session, so a block's batch is
ONE dispatch whatever its rows. What the program does with them in between follows from what
the block says of its own step (`decode_rows_apart`; counted by
`hivemind_moe_decode_batched_rows_total{caches}`), by one rule. A cache of ``max_len``
slots, of which a step writes one and reads the rest (`causal_transformer`, `llama_block`,
`olmoe_block`, `exaone_moe_block` with ``window`` = 0, `minicpm_sala_block`'s sparse mixer:
tens of MB a session): APART, the block updates and reads each row's own arrays where they
lie, in the donated buffers themselves; so does a state-space state of 4 MB a session that a
step rewrites whole (`nemotron_h_block`'s mixer: measured both ways, ISSUE 51). A ring of ``window``
slots or a recurrent state of 2 MB (`exaone_moe_block` with a window, the lightning mixer): JOINED leaf
by leaf along the batch axis, stepped as one array and split again, which costs less than
an operation a row (the split's outputs may take the donated inputs' buffers). A bucket's
padding positions each get a throwaway cache of their own (`_padding`: a buffer is donated
once a call) and keep the new one. The price of donation is what a FAILED step leaves: the
sessions whose leaves it took are dropped (`_drop_failed`, by either path), and the
client's next continuation gets the unknown-session ``KeyError`` and re-prefills.
Around the program the host only collects handles before it
(``assemble``: the activations — the last block's output as it is, or one
`np.concatenate` of host rows through the upload program — and one array of write
positions) and assigns them after it (``scatter``). That is what keeps a serving
chip busy when many clients decode one token at a time.

No reference equivalent (the reference serves stateless experts; Petals is its
downstream project — README.md:35-40). Fault note: decode sessions are sticky to
the serving peer, and since r4 a dead peer fails over TRANSPARENTLY — the client
retains the session's input history and re-prefills a replacement
(`RemoteSequential.decode_step`; past the retention cap it degrades to raising,
and the caller restarts with ``reset=True``)."""

from __future__ import annotations

import asyncio
import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hivemind_tpu.compression.floating import to_half
from hivemind_tpu.moe.server.routing_stats import (
    ATTENDED_COLLECTION,
    ROUTING_COLLECTION,
    held_range,
    record_attended,
    record_routing,
)
from hivemind_tpu.telemetry import REGISTRY as _TELEMETRY
from hivemind_tpu.telemetry.device import record_transfer
from hivemind_tpu.telemetry.serving import accrue_span_phase
from hivemind_tpu.telemetry.tracing import trace_sync as _trace_sync
from hivemind_tpu.telemetry.wire import count_work
from hivemind_tpu.utils.logging import get_logger
from hivemind_tpu.utils.asyncio_utils import spawn
from hivemind_tpu.utils.profiling import tracked_jit

logger = get_logger(__name__)

Chain = Tuple[str, ...]  # the uids of this server's blocks that one request crosses, in order

# What the drainer of a chain that was idle waits for other clients' steps to pile
# up: it exists only to merge concurrent streams, so a lone stream skips it.
FLUSH_WINDOW_S = 0.002
# Another session is a merge candidate only if it stepped within this window: an
# actively decoding stream touches its session every token (tens of ms on one
# serving hop), while an abandoned session would otherwise tax every single-stream
# token with the flush window until TTL eviction. Tradeoff: in a DEEP pipeline each
# server sees a session once per pipeline round, so with few concurrent streams and
# a round time past this window, steps route direct and never merge (a rising
# `path="direct"` share of hivemind_moe_decode_steps_total under concurrent load
# is the telltale).
MERGE_RECENCY_S = 0.25

# KV-cache session saturation (ISSUE 9, docs/observability.md "Serving"): the
# session table is the serving peer's scarcest resource (each session pins
# device cache memory) and previously had zero visibility
_SESSIONS = _TELEMETRY.gauge(
    "hivemind_moe_decode_sessions", "live KV-cache decode sessions on this server"
)
_SESSION_OCCUPANCY = _TELEMETRY.gauge(
    "hivemind_moe_decode_session_occupancy",
    "live decode sessions / max_sessions (1.0 = the LRU cap is about to evict)",
)
_EVICTIONS = _TELEMETRY.counter(
    "hivemind_moe_decode_session_evictions_total",
    "decode sessions evicted, by reason (ttl = idle expiry, cap = LRU over max_sessions, failed_step = a step that "
    "had taken the session's caches failed: a step donates them)",
    ("reason",),
)
# every step donates the cache leaves it is handed (ISSUE 50): the new leaves take their buffers, so these are
# the bytes a step neither copies nor allocates anew; counted from the shapes, on the host
_DONATED_BYTES = _TELEMETRY.counter(
    "hivemind_moe_decode_cache_bytes_donated_total",
    "bytes of cache leaves handed to a donating decode program, by path (batched = every position of a batched "
    "program's bucket, its padding positions' throwaway caches included; direct = a session's own step or prefill)",
    ("path",),
)
_DONATED_BATCHED, _DONATED_DIRECT = _DONATED_BYTES.labels("batched"), _DONATED_BYTES.labels("direct")
_PADDING_BYTES = _TELEMETRY.gauge(
    "hivemind_moe_decode_padding_cache_bytes",
    "bytes of the throwaway caches that pad batched decode programs to their bucket: per block as many rows as "
    "the largest padding a call has needed, each a cache of its own (a buffer is donated once a call)",
)
# whether the table is walked for a step (ISSUE 44): every step and every add asks whether an eviction pass
# could have an effect (`_evict_due_locked`), and a pass runs only then; in steady traffic `skipped` follows
# the steps and `ran` the sessions added to a table at its cap
_EVICT_PASSES = _TELEMETRY.counter(
    "hivemind_moe_decode_evict_passes_total",
    "times a decode step or a new session asked for an eviction pass over the session table, by outcome (skipped = "
    "the table was within its cap and no session could have passed its TTL yet: no walk; ran = the table was walked)",
    ("outcome",),
)
_PASS_SKIPPED, _PASS_RAN = _EVICT_PASSES.labels("skipped"), _EVICT_PASSES.labels("ran")
_RESETS = _TELEMETRY.counter(
    "hivemind_moe_decode_session_resets_total",
    "decode sessions created or re-prefilled via reset=True",
)
_STEPS = _TELEMETRY.counter(
    "hivemind_moe_decode_steps_total",
    "decode session steps served, by path (direct = per-session call, "
    "batched = merged into a vmapped continuous batch)",
    ("path",),
)
# where a batched step's host time goes (ISSUE 24). Plain counters beside the
# `decode.*` spans: a registry snapshot carries a histogram only as count and sum
_PHASE_SECONDS = _TELEMETRY.counter(
    "hivemind_moe_decode_phase_seconds_total",
    "host seconds of vmapped decode batches, by phase (assemble = collecting the rows' cache "
    "handles, one host array of activations and one of write positions; step = the one jitted "
    "call, which stacks the caches, steps and unstacks them, until its output is on the host "
    "(a cohort's block before its last: until it is dispatched); "
    "scatter = assigning each session its new caches)",
    ("phase",),
)
_CALLS = _TELEMETRY.counter(
    "hivemind_moe_decode_calls_total",
    "device calls of the decode path (batched = one vmapped step over several sessions; "
    "direct = one per-session step or prefill)",
    ("path",),
)
_CALLS_BATCHED, _CALLS_DIRECT = _CALLS.labels("batched"), _CALLS.labels("direct")
# how often a batched step leaves the rows' caches where they lie (ISSUE 42): a block says
# `decode_rows_apart` of its own step, the manager reads it, and this counts what came of it
_BATCHED_ROWS = _TELEMETRY.counter(
    "hivemind_moe_decode_batched_rows_total",
    "live rows of batched decode programs, by what the program did with their caches (apart = each row's own "
    "arrays updated and read where they lie, a block that says decode_rows_apart; joined = the rows' caches "
    "joined along the batch axis before the step and split after it; none = a block that keeps no cache)",
    ("caches",),
)
# what the session table pins on the device, by the kind of cache a block keeps
# (`decode_cache_kind` on the block class: a sliding-window block's ring is "window";
# a block that does not say keeps every position, "full"); kept by addition as
# sessions enter and leave (`_count_cache_locked`): a walk of a 512-entry table at
# every prefill cost the Mistral cell an eighth of its rate (PERF.md section 6, PR 34)
_CACHE_BYTES = _TELEMETRY.gauge(
    "hivemind_moe_decode_cache_bytes",
    "bytes of decode caches that the session table holds, by kind of cache as the block names it (window = a ring "
    "of a sliding-window block's last positions, full = every position of the session, sparse = keys, values and "
    "compressed keys of a block-sparse attention block, lightning = a linear-attention block's recurrent state, latent = the "
    "normed latents and the shared rotated key of a latent-attention block, ssm = a state-space block's recurrent state and "
    "its convolution window; a block that keeps nothing has no series)",
    ("kind",),
)
_CACHE_ENTRIES = _TELEMETRY.gauge(
    "hivemind_moe_decode_cache_entries",
    "entries (one session at one block) of the session table, by kind of cache",
    ("kind",),
)
# what a prompt costs the device at one block (ISSUE 34): a prefill holds the device while
# every other session's step waits, and a device trace of a few seconds often holds none
_PREFILL_SECONDS = _TELEMETRY.counter(
    "hivemind_moe_decode_prefill_seconds_total",
    "host seconds from the dispatch of a prefill's program at one block (a chunk of more than one "
    "position) until it has finished, its wait behind a program already on the device included",
)
_PREFILL_POSITIONS = _TELEMETRY.counter(
    "hivemind_moe_decode_prefill_positions_total",
    "positions, padded as run, of the prefill programs, one count a block a prompt crosses",
)
_PREFILL_CHUNKS = _TELEMETRY.counter(
    "hivemind_moe_decode_prefill_chunks_total",
    "chunks of more than one position that CONTINUED a session (a prompt that arrives in chunks: every "
    "chunk after its first), one count a chunk whatever the blocks it crosses",
)
# a latent cache is read where it lies, all of it up to the write position: the work of a step grows with
# the context, which the steps' count alone does not say (ISSUE 43); counted here, from the rows' indices
_LATENT_POSITIONS = _TELEMETRY.counter(
    "hivemind_moe_latent_positions_attended_total",
    "positions that the steps of blocks with a latent cache (decode_cache_kind latent: multi-head latent attention) "
    "attended: a live row a step a block, its write position + 1, padding rows excluded; by the step's path (batched "
    "= a row of a cohort's program, direct = a session's own step); prompt chunks are not counted",
    ("path",),
)
# a state-space block's step rewrites its whole state, whatever the context: the bytes it must move are the
# program's own count (ISSUE 51), from the shapes and the live rows, on the host
_SSM_STATE_BYTES = _TELEMETRY.counter(
    "hivemind_moe_ssm_state_bytes_total",
    "bytes of recurrent state and convolution window that the steps of blocks with a state-space cache "
    "(decode_cache_kind ssm) rewrote: a live row a step a block, the row's cache as the session holds it, padding rows "
    "excluded; by the step's path (batched = a row of a cohort's program, direct = a session's own step); prompt "
    "chunks are not counted",
    ("path",),
)
_COHORTS = _TELEMETRY.counter(
    "hivemind_moe_decode_cohorts_total",
    "cohorts of decode steps run: the steps that waited on one span chain, walked through "
    "the chain's blocks in one executor call (one batched device call a block)",
)
# a looped model's blocks run several times a token, each pass on a cache of its own (ISSUE 56): a request names
# its pass, and these say which passes the steps were and how many of them met in one cohort's programs
_PASS_STEPS = _TELEMETRY.counter(
    "hivemind_moe_decode_pass_steps_total",
    "single-position decode steps served (a live row a block, batched and direct alike), by the pass of a looped "
    "model's loop that the request named (0 for a block of one pass, and for a request that names none)",
    ("pass",),
)
_PASS_STEPS_FIRST = _PASS_STEPS.labels("0")
_COHORT_PASSES = _TELEMETRY.counter(
    "hivemind_moe_decode_cohort_passes_total",
    "the number of DISTINCT passes among a cohort's rows, summed over the cohorts: over "
    "hivemind_moe_decode_cohorts_total it reads 1 where the passes never meet in a program, up to the blocks' decode_passes",
)
# a looped block attends, at pass u, what pass u cached: the work of a step grows with the context, as a latent
# cache's does, and is counted the same way, from the rows' positions
_LOOPED_POSITIONS = _TELEMETRY.counter(
    "hivemind_moe_looped_positions_attended_total",
    "positions that the steps of blocks of several passes (decode_cache_kind looped) attended in the cache of the step's "
    "own pass: a live row a step a block, its write position + 1, padding rows excluded; by the step's path (batched = "
    "a row of a cohort's program, direct = a session's own step); prompt chunks are not counted",
    ("path",),
)
# a decode program is a function of what its block IS (ISSUE 61): the blocks of one kind (`DecodeSessionManager._kind`)
# share the one jitted object, so a span of eight equal blocks makes a shape ready once, not eight times
_PROGRAMS = _TELEMETRY.counter(
    "hivemind_moe_decode_programs_total",
    "decode programs (a step, a prefill of one length or a batched bucket) that a block was handed, by origin (built = no "
    "block of its kind had this shape yet, or the block's module cannot be hashed and keeps programs of its own: a new "
    "jitted program, traced and compiled at its first call; shared = the program a block of the same kind built)",
    ("origin",),
)
_PROGRAMS_BUILT, _PROGRAMS_SHARED = _PROGRAMS.labels("built"), _PROGRAMS.labels("shared")


@contextlib.contextmanager
def _batch_phase(phase: str):
    """One phase of a vmapped batch: the `decode.<phase>` span (on the device
    trace's timeline too) and its seconds on the phase counter."""
    started = time.perf_counter()
    try:
        with _trace_sync("decode." + phase):
            yield
    finally:
        _PHASE_SECONDS.inc(time.perf_counter() - started, phase=phase)


def _row_bytes(leaves) -> int:
    return sum(leaf.nbytes for leaf in leaves)


def _next_pow2(n: int) -> int:
    power = 1
    while power < n:
        power *= 2
    return power


# with more rows than this under way on a chain, a cohort leaves half of them to the next one
HALVED_ABOVE = 16


def _cohort_rows(waiting: int, active: int = 0) -> int:
    """How many of ``waiting`` rows the next cohort takes. A batched program costs
    by its bucket, the power of two its rows are padded to, almost as if every row
    were live (each padding row's caches are copied, written and attended over
    like a live one's): 17 rows cost what 32 do, near twice what 16 do. So a cohort
    that would pad more than a quarter of its bucket takes the full bucket below
    instead; the rows left over are the first of the next cohort.

    ``active``: the rows under way on the chain, these and those of the cohorts
    launched and not yet answered. With more than `HALVED_ABOVE` of them a cohort takes
    at most the bucket that holds HALF: closed-loop sessions travel as two cohorts that
    alternate, one's clients turning around while the other is on the device, and that
    is steady only while neither takes the other's rows. Where a program's time is its
    weights' and hardly its rows', a cohort of 24 to 31 of 32 rows beside one of 8 to 1
    is as steady (the small one lasts long enough for every row of the large one to
    come back) and a quarter slower: ISSUE 43 read 31 + 1 for seconds at a time in half
    of its runs, 641 to 673 tokens/s where the others read 695 to 708. Below that many
    rows a program's time is its weights' whatever the block, and halving a cohort
    would double it."""
    bucket = _next_pow2(waiting)
    take = waiting if 4 * waiting >= 3 * bucket else bucket // 2
    return min(take, _half_bucket(active)) if active > HALVED_ABOVE else take


def _half_bucket(active: int) -> int:
    """The bucket that holds half of ``active`` rows: what each of two alternating cohorts carries."""
    return _next_pow2(-(-active // 2))


class _Session:
    """One client's session at one block: ONE entry of the table, to the cap, the TTL, the pins and the locks,
    whatever the passes of the block (`decode_passes`): it holds a cache tree and a position A PASS, made
    together and dropped together."""

    __slots__ = ("trees", "positions", "tree", "nbytes", "row_bytes", "batch", "last_used", "lock", "batch_started")

    def __init__(self, caches, batch: int):
        # ``caches``: what the block's `init_decode_cache` returned, once a pass: a tree of arrays, batch axis
        # first (a `(cache_k, cache_v)` pair is a tree of two leaves; a block that keeps
        # nothing between calls returns a tree of NONE, and its session is a position and a
        # batch); nothing here looks inside. Each is kept FLAT, as the tuple of its leaves:
        # that is what a block is handed and hands back, so no step and no batch walks a
        # tree on the host. A step is handed the leaves of ITS pass, and never learns which it was
        flat = [jax.tree_util.tree_flatten(cache) for cache in caches]
        self.tree = flat[0][1]
        self.trees = [tuple(leaves) for leaves, _tree in flat]
        self.positions = [0] * len(flat)  # a pass's write position: what that pass has cached
        self.row_bytes = _row_bytes(self.trees[0])  # one pass's tree: what a row of a program holds; a step hands back leaves of the same shapes
        self.nbytes = self.row_bytes * len(flat)  # what the entry pins on the device
        self.batch = batch
        self.last_used = time.monotonic()
        # perf_counter at which the batch carrying this session's pending step
        # began to run: the end of that step's queue wait (decode_span_async)
        self.batch_started = 0.0
        self.lock = threading.Lock()

    @property
    def leaves(self):
        """The first pass's leaves: all there is of a block of one pass."""
        return self.trees[0]

    @property
    def index(self) -> int:
        """The first pass's write position."""
        return self.positions[0]

    @index.setter
    def index(self, position: int) -> None:
        self.positions[0] = position

    @property
    def cache(self):
        """The tree as the block's `init_decode_cache` shaped it, of the first pass's leaves as they are now."""
        return jax.tree_util.tree_unflatten(self.tree, self.trees[0])

    @property
    def cache_k(self):
        """A `(cache_k, cache_v)` pair's first leaf (a tree of other shape has none)."""
        cache_k, _cache_v = self.trees[0]
        return cache_k

    @property
    def cache_v(self):
        _cache_k, cache_v = self.trees[0]
        return cache_v


def _out_of_order(loop_pass: int, upto: int, before: int) -> str:
    return (f"pass {loop_pass} of the session would reach position {upto}, past the {before} that pass {loop_pass - 1} "
            f"holds: a pass takes the pass before it as its input")


def _count_pass_steps(loop_pass: int, rows: int = 1) -> None:
    (_PASS_STEPS.labels(str(loop_pass)) if loop_pass else _PASS_STEPS_FIRST).inc(rows)


def _entry_pass(entry) -> int:
    """The pass of a pending step ``(future, sessions, x, loop_pass)``; one handed over without (a caller that
    knows no passes) is of the first."""
    return entry[3] if len(entry) > 3 else 0


class _Output:
    """What one batched program handed back: its output ``y`` ``[bucket, 1, hidden]``
    still on the device (the leading ``rows`` rows are live, in the order of the
    batch's live entries), the routing it sowed, and once somebody needed it on the
    host, that copy; and of a chain's last output, once a row was asked for in the wire's
    half precision, the live rows in it."""

    __slots__ = ("y", "routing", "attended", "rows", "held", "on_host", "in_half", "settled")

    def __init__(self, y, routing, attended, rows: int, held=None):
        self.y, self.routing, self.attended, self.rows, self.held = y, routing, attended, rows, held
        self.on_host: Optional[np.ndarray] = None
        self.in_half: Optional[np.ndarray] = None
        self.settled = False

    def host(self) -> np.ndarray:
        if self.on_host is None:
            self.on_host = np.asarray(self.y)
            record_transfer(self.on_host.nbytes, "device_to_host")
        return self.on_host

    def wire(self) -> np.ndarray:
        """The live rows ``[rows, 1, hidden]`` in float16 as the fp16 codec makes them (`to_half`: its clip and
        its cast, bit for bit what `Float16Compression.compress` makes of a row alone): ONE pass for all the
        rows of a cohort, on the thread that fetched them, where the handlers made one a row on the loop
        thread. A second array: the float32 copy stays as it was fetched, for whoever reads a row of it
        (`_Row.host`). The seconds are the wire's encode work; the bytes are counted where a row is framed."""
        if self.in_half is None:
            started = time.perf_counter()
            live = self.host()[:self.rows]
            self.in_half = to_half(live, False).reshape(live.shape)
            count_work("encode", time.perf_counter() - started, 0)
        return self.in_half

    def settle(self, span=None) -> None:
        """Wait for the program and count its routing (once): the routing's values
        are on the device until then."""
        if not self.settled:
            self.settled = True
            record_routing(self.routing, "batched", span, rows=self.rows, held=self.held)
            record_attended(self.attended, rows=self.rows)
            self.y.block_until_ready()


class _Row:
    """One live row of a batched program's output, left on the device: what a
    cohort hands from a block to the next in place of a host array."""

    __slots__ = ("output", "row")

    def __init__(self, output: _Output, row: int):
        self.output, self.row = output, row

    def host(self) -> np.ndarray:
        return self.output.host()[self.row:self.row + 1]

    def wire(self) -> np.ndarray:
        return self.output.wire()[self.row:self.row + 1]


class DecodeSessionManager:
    """Per-(uid, session_id) KV caches + jitted decode steps for one server.

    :param max_len: cache capacity per session (prompt + generated tokens)
    :param session_ttl: seconds of inactivity before a session is evicted
    :param max_sessions: LRU cap across all uids

    **A program is made once for each KIND of block, not once for each uid** (ISSUE 61). A step, a prefill of one
    length and a batched bucket are functions of what a block IS and of the shapes (the weights come in as an
    argument), so each is looked up by the block's kind — the flax module, how the backend makes dense parameters of
    its stored ones, where its caches are placed: `_kind` — and built only when no block of that kind has built it
    (`_of_kind`). Equal blocks share the ONE jitted object: the second to the last of them meet jax's in-memory cache
    at their first call, and trace, lower, read and load nothing. `_step_fns` / `_batched_fns` are each uid's view
    onto that table, which is all a step looks at; `hivemind_moe_decode_programs_total{origin}` counts both.

    **When eviction runs.** Lazily, at a call, and only at one that can evict: a step or a new
    session asks `_evict_due_locked`, which walks the table (`_evict_locked`) when it is over its
    cap — which only an ADD can bring about (`_enter` with ``reset``), so the pass follows the add
    and the cap holds when the add returns — or when the earliest possible expiry has come
    (`_oldest_use`: no session was last used before it, as of the last pass). Any other call skips
    in O(1). So a session idle past ``session_ttl`` goes at the next step or add after its
    deadline, whichever path it takes (`_enter` for the direct path, `_submit_step` for a cohort's
    row), and a pinned one (a step enqueued or under way) never. What a step does under the
    manager's lock does not grow with the table: whether a block has concurrent streams is kept as
    its two latest stamps (`_recent`: a step is stamped under the lock when it is submitted and when
    it has ended; in between, a cohort's programs note their block's use without a lock,
    `_batched_at`), and the gauges are set where the table changes."""

    def __init__(self, backends, max_len: int = 256, session_ttl: float = 600.0,
                 max_sessions: int = 64):
        self.backends = backends
        self.max_len, self.session_ttl, self.max_sessions = max_len, session_ttl, max_sessions
        self._sessions: Dict[Tuple[str, str], _Session] = {}
        # (kind, shape) -> the ONE program of the blocks of that kind (`_of_kind`), and each uid's view onto it: what
        # a step looks up, so that nothing hashes a module on the way to a program it already has
        self._programs: Dict[tuple, callable] = {}
        self._step_fns: Dict[Tuple[str, int, int], callable] = {}
        self._batched_fns: Dict[Tuple[str, int], callable] = {}
        # uid -> the throwaway caches (each a cache's leaves) that pad a batch to its pow2 bucket, those not in a
        # program right now (`_padding`); their lock is the dispatching threads' alone, never the loop's
        self._padding_rows: Dict[str, List[tuple]] = {}
        self._padding_bytes = 0
        self._padding_lock = threading.Lock()
        self._lock = threading.Lock()
        # both keyed by the span chain (the tuple of uids a request crosses)
        self._pending: Dict[Chain, List] = {}  # chain -> [(future, [the session of each uid], x), ...]
        self._in_flight: Dict[int, int] = {}  # id(session) -> refcount, during a cohort
        self._drainers: Dict[Chain, asyncio.Task] = {}
        # host activations -> the device, one program a bucket (`_device_rows`)
        self._upload = tracked_jit(lambda xs: xs, site="decode_session.upload")
        self._cache_tally: Dict[str, List[int]] = {}  # kind of cache -> [bytes, entries] of the table (`_count_cache_locked`)
        self._passes_of: Dict[Chain, int] = {}  # a chain that was made -> the passes its blocks' sessions hold (`_chain_passes`)
        # no session of the table was last used before this, as of the last eviction pass: sessions are only
        # used later, so until ``session_ttl`` past it no pass can find one expired (`_evict_due_locked`)
        self._oldest_use = float("inf")
        # uid -> [id, last_used, id, last_used] of its two most recently used sessions, the latest first
        # (`_stamp_locked`): what `_concurrent_sessions` needs, kept so that no step walks the table for it
        self._recent: Dict[str, List] = {}
        # uid -> when its last batched program scattered: two or more of the block's sessions were used then.
        # A cohort under way keeps its sessions recent block by block with this ONE store and no lock (the
        # dispatching thread would wait for a lock the loop holds, and then for the interpreter); their
        # stamps proper follow when it resolves
        self._batched_at: Dict[str, float] = {}

    def supports(self, uid: str) -> bool:
        backend = self.backends.get(uid)
        return backend is not None and hasattr(backend.module, "init_decode_cache")

    def _evict_due_locked(self, keep: Optional[_Session] = None) -> None:
        """The eviction a step or an add owes, when it can have an effect: the table is over its cap, or the
        earliest possible expiry has come. Else nothing, in O(1). ``keep``: the session just added."""
        if len(self._sessions) > self.max_sessions or time.monotonic() - self._oldest_use > self.session_ttl:
            _PASS_RAN.inc()
            self._evict_locked(keep)
        else:
            _PASS_SKIPPED.inc()

    def _evict_locked(self, keep: Optional[_Session] = None) -> None:
        """One pass over the table: the sessions idle past ``session_ttl`` go, then the oldest of those over
        ``max_sessions``; never a pinned one, nor ``keep``."""
        now, sessions = time.monotonic(), self._sessions
        expired = [k for k, s in sessions.items() if now - s.last_used > self.session_ttl]
        if expired or len(sessions) > self.max_sessions:
            # sessions with an enqueued-but-unresolved batched step are pinned: evicting
            # one mid-flight would orphan its cache object — the step would "succeed"
            # against the orphan and the client's next continuation would KeyError.
            # _in_flight covers the window after _drain pops entries out of _pending but
            # before their cohort finishes (the device calls themselves).
            pinned = {
                id(session)
                for entries in self._pending.values()
                for entry in entries
                for session in entry[1]
            } | set(self._in_flight) | {id(keep)}
            expired = [k for k in expired if id(sessions[k]) not in pinned]
            if expired:
                self._drop_locked(expired)
                _EVICTIONS.inc(len(expired), reason="ttl")
            over = len(sessions) - self.max_sessions
            if over > 0:  # oldest first (the sort is stable: of two used at once, the one that entered first)
                oldest = sorted((k for k, s in sessions.items() if id(s) not in pinned), key=lambda k: sessions[k].last_used)[:over]
                self._drop_locked(oldest)
                _EVICTIONS.inc(len(oldest), reason="cap")
        self._oldest_use = min((s.last_used for s in sessions.values()), default=float("inf"))

    def _sample_gauges_locked(self) -> None:
        _SESSIONS.set(len(self._sessions))
        _SESSION_OCCUPANCY.set(round(len(self._sessions) / max(self.max_sessions, 1), 4))

    def _stamp_locked(self, uid: str, sessions, now: float) -> None:
        """``sessions`` of ``uid`` (distinct ones) are used at ``now``, a `time.monotonic()` no older than any
        stamp made before (read under the lock, or a session's own `last_used`): their `last_used`, and the
        block's two latest sessions, which are the last two of these."""
        for session in sessions:
            session.last_used = now
        recent = self._recent.get(uid)
        if recent is None:
            recent = self._recent[uid] = [0, float("-inf"), 0, float("-inf")]
        for session in sessions[-2:]:
            if recent[0] != id(session):
                recent[2], recent[3], recent[0] = recent[0], recent[1], id(session)
            recent[1] = now

    def _cache_kind(self, uid: str) -> str:
        return getattr(self.backends[uid].module, "decode_cache_kind", "full")

    def _rows_caches(self, uid: str) -> str:
        """What a batched program of this block does with its rows' caches: ``apart`` (the
        block says `decode_rows_apart`: it is handed each leaf as the tuple of the rows' own
        arrays) or ``joined`` (`_batched_fn`)."""
        return "apart" if getattr(self.backends[uid].module, "decode_rows_apart", False) else "joined"

    def block_passes(self, uid: str) -> int:
        """What the block says of its sessions' passes (`decode_passes` on the module; 1 where it says nothing)."""
        return int(getattr(self.backends[uid].module, "decode_passes", 1))

    def _chain_passes(self, chain: Chain) -> int:
        """How many passes a session of ``chain`` holds: what its blocks say (`decode_passes`, an int on the
        module; 1 where a block says nothing). A looped model's blocks run that many times a token, pass u on
        the cache that pass u wrote, so a session holds that many trees and positions a block. The blocks of a
        chain walk together, so they have to agree: a chain that does not is refused here, when it is made
        (``ValueError``), and at every later step of it."""
        passes = self._passes_of.get(chain)
        if passes is None:
            for uid in chain:
                if not self.supports(uid):
                    raise KeyError(f"expert {uid!r} does not support decode sessions")
            said = [self.block_passes(uid) for uid in chain]
            if len(set(said)) != 1 or said[0] < 1:
                raise ValueError(f"the blocks of the chain {chain!r} disagree on decode_passes ({said}): they cannot walk together")
            passes = self._passes_of[chain] = said[0]
        return passes

    def _check_pass(self, chain: Chain, loop_pass: int) -> None:
        """A request's pass against its chain's, before anything is looked up or donated."""
        passes = self._chain_passes(chain)
        if not 0 <= loop_pass < passes:
            raise ValueError(f"pass {loop_pass} of a chain whose sessions hold {passes} pass(es) ({chain[0]!r} ..)")

    def _count_cache_locked(self, uid: str, session: _Session, entries: int) -> None:
        """A session enters (+1) or leaves (-1) the table at ``uid``: its bytes and
        its entry onto the gauges of that block's kind of cache. One addition a
        change of the table, and never a walk of it at a step or a prefill."""
        if not session.trees[0]:  # a block that keeps nothing pins nothing: its kind has no series
            return
        kind = self._cache_kind(uid)
        tally = self._cache_tally.setdefault(kind, [0, 0])
        tally[0] += entries * session.nbytes
        tally[1] += entries
        _CACHE_BYTES.set(tally[0], kind=kind)
        _CACHE_ENTRIES.set(tally[1], kind=kind)

    def clear_sessions(self) -> None:
        """Empty the session table and its gauges, and release the throwaway caches that padded
        the batches (a warm-up's or a check's sessions leave the device before the traffic
        comes). Steps under way are not waited for."""
        with self._padding_lock:  # a throwaway cache inside a program right now comes back later, and stays counted
            self._padding_bytes -= sum(_row_bytes(row) for rows in self._padding_rows.values() for row in rows)
            self._padding_rows.clear()
            _PADDING_BYTES.set(self._padding_bytes)
        with self._lock:
            self._sessions.clear()
            for kind, tally in self._cache_tally.items():
                tally[:] = [0, 0]
                _CACHE_BYTES.set(0, kind=kind)
                _CACHE_ENTRIES.set(0, kind=kind)
            self._recent.clear()
            self._batched_at.clear()
            self._sample_gauges_locked()

    def _drop_locked(self, keys) -> None:
        """The sessions under ``keys`` leave the table: the tallies, the gauges, and what is kept of the
        latest use of a block that lost one of its two latest sessions or a row of its last batched program,
        found again in ONE walk (a drop is rare; a step never walks)."""
        stale = set()
        for key in keys:
            uid, session = key[0], self._sessions.pop(key)
            self._count_cache_locked(uid, session, -1)
            if id(session) in self._recent.get(uid, ())[::2] or session.last_used >= self._batched_at.get(uid, float("inf")):
                stale.add(uid)
        if stale:  # stamped again in the order they were used, each block's last two stay
            for uid in stale:
                self._recent.pop(uid, None)
                self._batched_at.pop(uid, None)
            for (uid, _name), session in sorted(self._sessions.items(), key=lambda item: item[1].last_used):
                if uid in stale:
                    self._stamp_locked(uid, (session,), session.last_used)
        if keys:
            self._sample_gauges_locked()

    def _drop_failed(self, sessions) -> None:
        """A step that had taken ``sessions``' caches failed: every step DONATES the leaves it is handed, so
        what these sessions point at is deleted buffers, or outputs of a program that failed. They leave the
        table, and their clients' next continuations get the unknown-session ``KeyError`` and re-prefill."""
        doomed = {id(session) for session in sessions}
        if not doomed:
            return
        with self._lock:
            keys = [key for key, session in self._sessions.items() if id(session) in doomed]
            self._drop_locked(keys)
        if keys:
            _EVICTIONS.inc(len(keys), reason="failed_step")

    def _raw_step(self, uid: str):
        """The un-jitted block step; shared by the direct and batched paths so a
        signature change cannot silently diverge them. ``index`` is one write
        position (a session's prefill or step) or a vector of them, one a row (a
        batch of sessions). ``length`` (at most one: the chunk's real positions,
        the rest is padding) goes to a block that asks for it (`_takes_length`).
        ``leaves`` are the leaves of the session's cache tree; the block is handed them
        and hands new ones back in the same order (in a batched step of a block that says
        `decode_rows_apart`, each leaf is the tuple of the rows' own arrays, `_batched_fn`).
        Returns (y, leaves, routing, attended): what the block sowed into
        `ROUTING_COLLECTION` (empty for a block without experts) and into
        `ATTENDED_COLLECTION` (empty for a block whose steps attend all they cached).
        It closes over the parts of the block's kind and over NO backend: the blocks of a kind share
        it, and a jitted closure pins what it captures for the life of the process (`module_backend.py`)."""
        module, dense_params = self.backends[uid].module, self.backends[uid].dense_params

        def step(params, x, leaves, index, *length):
            # int8 weight-only backends: materialize dense weights inside the jit
            # (identity for plain fp32 trees)
            (y, *leaves), sown = module.apply(
                {"params": dense_params(params)}, x, *leaves, index, *length,
                mutable=[ROUTING_COLLECTION, ATTENDED_COLLECTION],
            )
            sown = dict(sown)
            attended = sown.pop(ATTENDED_COLLECTION, {})
            return y, tuple(leaves), sown, attended

        return step

    def _named_by_kind(self, uid: str, program, name: str):
        """A block that names its kind of cache has it in its decode programs' names
        (`jit_batched_step_window`, `jit_step_full`, `jit_prefill_full_2048`: ``name``
        and the kind), so that a device trace tells the kinds of one span, and a
        prefill from a step, apart; any other block's programs keep their names."""
        kind = getattr(self.backends[uid].module, "decode_cache_kind", None)
        if kind:
            program.__name__ = program.__qualname__ = name.format(kind=kind)
        return program

    def _takes_chunks(self, chain: Chain) -> bool:
        """Whether a chunk of more than one position may CONTINUE a session on this
        chain: only if every block of it says so (``decode_takes_chunks``: a prompt
        that arrives in chunks). Any other block's first chunk is its whole prompt."""
        return all(getattr(self.backends[uid].module, "decode_takes_chunks", False) for uid in chain)

    def _takes_length(self, uid: str) -> bool:
        """Whether the block is told how many positions of a right-padded chunk are
        real (a ring cache must keep the padding out); a block that keeps every
        position needs no telling: its padded tail lies past ``index``."""
        return getattr(self.backends[uid].module, "decode_takes_length", False)

    def _step_fn(self, uid: str, batch: int, new_len: int):
        key = (uid, batch, new_len)
        fn = self._step_fns.get(key)
        if fn is None:
            # tracked_jit (ISSUE 19): every compile lands on the compile tracker
            # under one site — a client cycling prompt lengths past the pow2
            # buckets shows up as a recompile storm, not silent latency
            name = "step_{kind}" if new_len == 1 else f"prefill_{{kind}}_{new_len}"
            fn = self._step_fns[key] = self._of_kind(uid, ("step", batch, new_len), lambda: tracked_jit(
                self._named_by_kind(uid, self._raw_step(uid), name), site="decode_session.step", donate_argnums=(2,),
                out_shardings=(None, self._cache_shardings(uid), None, None),
            ))
        return fn

    def _kind(self, uid: str) -> Optional[tuple]:
        """What the block IS to a decode program: everything the program's text depends on and nothing else.
        The flax module (a frozen dataclass: equal, and hashing equal, when its class and every field are, the
        flags this manager reads off it among them), how the backend makes dense parameters of the stored ones
        (the quantization, and `dense_params` as its function and the codec's placement: a `functools.partial`
        compares by identity), and where the block's caches are placed (`_cache_shardings`). ``max_len``, the
        bucket and the parameters' shapes reach a program as the shapes of its arguments. None for a module
        that cannot be hashed: its block keeps programs of its own."""
        backend = self.backends[uid]
        dense = backend.dense_params
        kind = (backend.module, getattr(backend, "weight_quantization", None), getattr(dense, "func", dense),
                tuple(sorted(getattr(dense, "keywords", {}).items())), self._cache_shardings(uid))
        try:
            hash(kind)
        except TypeError:
            return None
        return kind

    def _of_kind(self, uid: str, shape: tuple, build):
        """The program of ``shape`` of the block's kind: what a block of that kind built before, else
        ``build()``, kept for the next block of the kind. No lock: two threads that miss together both build,
        each keeps its own and the later entry stays, which is a program more and never a wrong one (the views' rule)."""
        kind = self._kind(uid)
        key = kind and (kind, *shape)
        fn = self._programs.get(key)
        if fn is None:
            fn = build()
            _PROGRAMS_BUILT.inc()
            if key:
                self._programs[key] = fn
        else:
            _PROGRAMS_SHARED.inc()
        return fn

    def _cache_shardings(self, uid: str):
        """Where `shard_decode_cache` places this block's cache, leaf by leaf, for a
        step's `out_shardings`: left to the compiler, a mesh program's new caches
        come back under shardings of its choosing, and every mixture of those over
        a batch's rows is another program. None, i.e. nothing pinned, for caches
        that no backend placed."""
        if not hasattr(self.backends[uid], "shard_decode_cache"):
            return None
        return tuple(leaf.sharding for leaf in self._dummy_rows(uid))

    def _fresh_caches(self, backend, batch: int):
        """An empty cache tree for ``batch`` rows, placed as the backend serves it."""
        cache = backend.module.init_decode_cache(batch, self.max_len)
        if hasattr(backend, "shard_decode_cache"):
            # mesh-sharded serving: the session's KV lives distributed
            # over the backend's mesh (MeshModuleBackend), so a cache
            # that exceeds one chip's HBM still fits the slice
            cache = backend.shard_decode_cache(*cache)
        return cache

    def _advance(self, uid: str, session: _Session, backend, x, chunk_len: int, new_len: int, loop_pass: int = 0):
        """Run the per-session jitted step on ``x`` (``new_len`` positions, already
        padded to ``chunk_len``; on the host or, mid-chain, where the block before
        left it) under ``session.lock`` on the tree and at the position of ``loop_pass``
        (the ONE program of every pass), store the new caches and return the output
        ON THE DEVICE, its program finished. The step DONATES the caches: if it
        fails (at dispatch or when its result is awaited), what the session still
        points at may be deleted buffers, so the session is dropped and the client's
        next continuation gets the unknown-session KeyError (it re-prefills) instead
        of a read of donated memory."""
        step = self._step_fn(uid, x.shape[0], chunk_len)
        length = (jnp.int32(new_len),) if self._takes_length(uid) else ()
        _CALLS_DIRECT.inc()
        _DONATED_DIRECT.inc(session.row_bytes)
        started = time.perf_counter()
        try:
            with _trace_sync("decode.direct", uid=uid, chunk_len=chunk_len, **{"pass": loop_pass}) as span:
                index = session.positions[loop_pass]
                y, session.trees[loop_pass], routing, attended = step(
                    backend.snapshot_params(), jnp.asarray(x), session.trees[loop_pass], jnp.int32(index), *length,
                )
                record_routing(routing, "direct", span, positions=new_len, held=held_range(backend.module))
                record_attended(attended, positions=new_len)
                kind = self._cache_kind(uid) if chunk_len == 1 else None  # a step's work that the model's sizes alone do not give
                if kind == "latent":
                    _LATENT_POSITIONS.inc(index + 1, path="direct")
                elif kind == "ssm":
                    _SSM_STATE_BYTES.inc(session.row_bytes, path="direct")
                elif kind == "looped":
                    _LOOPED_POSITIONS.inc(index + 1, path="direct")
                # the next block is dispatched when this one has finished: a cohort's
                # program that arrives meanwhile waits for one block of a prefill, not
                # for the chain
                y.block_until_ready()
                if chunk_len > 1:
                    _PREFILL_SECONDS.inc(time.perf_counter() - started)
                    _PREFILL_POSITIONS.inc(x.shape[0] * chunk_len)
                return y
        except Exception:
            self._drop_failed([session])
            raise

    def decode(self, uid: str, session_id: str, x: np.ndarray, reset: bool, loop_pass: int = 0) -> np.ndarray:
        """One session step at one block: the span chain of one (`_decode_direct`)."""
        return self._decode_direct((uid,), session_id, x, reset, loop_pass)

    def _decode_direct(self, chain: Chain, session_id: str, x: np.ndarray, reset: bool, loop_pass: int = 0) -> np.ndarray:
        """One session's step through the span chain, ONE chain on the device: prefill
        (``reset=True``, chunk = the prompt or its first chunk), a further chunk of the
        prompt (a chain whose blocks all take chunks, `_takes_chunks`), or advance one
        token in an existing session, at the pass ``loop_pass`` of a chain whose blocks run several times a
        token (`_chain_passes`; ``reset`` at the first pass makes the session with every pass's cache, at a later
        one it starts that pass's position over). The chunk is padded and uploaded once, each block's own program runs
        on the output of the block before where it lies, and only the chain's last
        output comes to the host (a prompt of 4,096 positions at hidden 6,144 is 100 MB,
        which crossed the host twice a block). Returns the last block's output for the
        chunk. Raises ``KeyError`` for a continuation on an unknown/evicted session."""
        for uid in chain:
            if not self.supports(uid):
                raise KeyError(f"expert {uid!r} does not support decode sessions")
        self._check_pass(chain, loop_pass)
        x = np.asarray(x, np.float32)
        assert x.ndim == 3, f"decode input must be [batch, chunk, hid], got {x.shape}"
        batch, new_len = x.shape[0], x.shape[1]
        if new_len > self.max_len:
            raise ValueError(f"chunk of {new_len} exceeds session max_len={self.max_len}")
        # bucket prefill lengths to powers of two so the jit cache stays at
        # O(log max_len) entries per (uid, batch) instead of one compile per
        # distinct prompt length. Padded tail slots of the cache are invisible
        # (the continuation mask stops at `index`) and are overwritten in place
        # by subsequent single-token steps; padded prefill OUTPUTS are sliced
        # off, and causal attention keeps real prefill positions exact (past the
        # first block the tail holds what the block before made of the padding:
        # finite, and as invisible).
        padded_len = new_len if new_len == 1 else min(_next_pow2(new_len), self.max_len)
        continues = not reset and new_len > 1  # a further chunk of a prompt: it lands past what the session holds
        if continues:
            if not self._takes_chunks(chain):
                raise ValueError(
                    f"only 1-token steps may follow the prefill of session {session_id!r} (got chunk {new_len}): "
                    f"a block of this chain does not take a prompt in chunks"
                )
            with self._lock:
                held = self._sessions.get((chain[0], session_id))
            if held is not None:  # the padded tail has to fit the cache too: a write past its end would be shifted
                padded_len = max(min(padded_len, self.max_len - held.positions[loop_pass]), new_len)
        if padded_len != new_len:
            x = np.pad(x, ((0, 0), (0, padded_len - new_len), (0, 0)))
        record_transfer(x.nbytes, "host_to_device")
        y, entered = x, []
        for uid in chain:
            session = self._enter(uid, session_id, batch, reset, loop_pass)
            with session.lock:
                if reset and loop_pass:
                    self._start_pass_over(uid, session, loop_pass)
                self._check_step(session, session_id, batch, new_len, continues, loop_pass)
                y = self._advance(uid, session, self.backends[uid], y, padded_len, new_len, loop_pass)
                session.positions[loop_pass] += new_len
                _STEPS.inc(path="direct")
                if new_len == 1:
                    _count_pass_steps(loop_pass)
            entered.append(session)
        # re-stamp AFTER the device steps: a step that hits a jit compile can
        # outlast MERGE_RECENCY_S, and a session stamped only at entry would
        # look stale to _concurrent_sessions the instant its own prefill
        # returns — so two freshly-prefilled streams never engage batching.
        # ONE hold for the chain: a lock that another thread holds costs this one the interpreter
        with self._lock:
            now = time.monotonic()
            for uid, session in zip(chain, entered):
                if self._sessions.get((uid, session_id)) is session:  # a direct step pins nothing: it may have been evicted
                    self._stamp_locked(uid, (session,), now)
        if continues:
            _PREFILL_CHUNKS.inc()  # one that a block refused (an unknown session, a full cache) is not counted
        out = np.asarray(y)[:, :new_len]
        record_transfer(out.nbytes, "device_to_host")
        return out

    def _enter(self, uid: str, session_id: str, batch: int, reset: bool, loop_pass: int = 0) -> _Session:
        """The session of ``session_id`` at ``uid``: a fresh one for ``reset`` at the first pass, with a cache
        tree for every pass the block says (`decode_passes`). ``reset`` at a LATER pass leaves the entry in
        place: it is looked up as a continuation's is, and the caller starts that pass over (`_start_pass_over`)."""
        key = (uid, session_id)
        with self._lock:
            if reset and not loop_pass:
                if key in self._sessions:
                    self._drop_locked([key])
                caches = [self._fresh_caches(self.backends[uid], batch) for _ in range(self.block_passes(uid))]
                session = self._sessions[key] = _Session(caches, batch)
                self._count_cache_locked(uid, session, +1)
                _RESETS.inc()
                self._stamp_locked(uid, (session,), time.monotonic())
                self._oldest_use = min(self._oldest_use, session.last_used)
                # an add is the one moment the table can pass its cap: the pass follows it, the
                # new session out of its reach, and the cap holds when the add returns
                self._evict_due_locked(keep=session)
                self._sample_gauges_locked()
                return session
            session = self._known_locked((uid,), session_id)[0]
            self._stamp_locked(uid, (session,), time.monotonic())
        return session

    def _start_pass_over(self, uid: str, session: _Session, loop_pass: int) -> None:
        """``reset`` at a pass after the first (under ``session.lock``): that pass's position starts over, on a
        fresh tree where the pass held anything (a state that a step updates has to start empty; a session's
        first prompt finds the tree its entry was made with)."""
        if session.positions[loop_pass]:
            session.trees[loop_pass] = tuple(jax.tree_util.tree_leaves(self._fresh_caches(self.backends[uid], session.batch)))
            session.positions[loop_pass] = 0
        _RESETS.inc()

    def _stamp_chain_locked(self, chain: Chain, rows: List) -> None:
        """``rows`` (each the sessions of one client at the blocks of ``chain``) are used now: a step of
        each is submitted, or has ended."""
        now = time.monotonic()
        for uid, sessions in zip(chain, zip(*rows)):
            self._stamp_locked(uid, sessions, now)

    def _known_locked(self, chain: Chain, session_id: str) -> List[_Session]:
        """A continuation's sessions at the blocks of ``chain``, after the eviction the call owes (the ONE
        rule of both paths, `_enter` for the direct one and `_submit_step` for a cohort's row: a session
        idle past its TTL is gone before it is looked up). Raises ``KeyError`` for one that is not there."""
        self._evict_due_locked()
        sessions = [self._sessions.get((uid, session_id)) for uid in chain]
        if None in sessions:
            # NEVER silently prefill a continuation: an evicted/expired/unknown
            # session would return semantically-garbage activations. The client
            # must restart generation with reset=True.
            raise KeyError(
                f"unknown or expired decode session {session_id!r} for "
                f"{chain[sessions.index(None)]!r}; restart generation with reset=True"
            )
        return sessions

    def _check_step(self, session: _Session, session_id: str, batch: int, new_len: int, continues: bool = False,
                    loop_pass: int = 0) -> None:
        """What a step must meet at a block (under ``session.lock``). ``continues``: the
        chunk is a further chunk of a prompt, on a chain that takes those. The loop's ORDER is held here, as
        "a session is full" is: pass u of a position takes pass u-1 of it as its input, so a call that would
        carry a later pass's position past the pass before it is refused, and nothing is donated."""
        index = session.positions[loop_pass]
        if index and new_len != 1 and not continues:  # a prefill takes any chunk length (causal within the chunk)
            raise ValueError(
                f"session {session_id!r} already holds {index} positions; "
                f"only 1-token steps may follow the prefill (got chunk {new_len})"
            )
        if index + new_len > self.max_len:
            raise ValueError(f"session {session_id!r} is full ({index}/{self.max_len})")
        if session.batch != batch:
            raise ValueError(f"session {session_id!r} batch is {session.batch}, got {batch}")
        if loop_pass and index + new_len > session.positions[loop_pass - 1]:
            raise ValueError(_out_of_order(loop_pass, index + new_len, session.positions[loop_pass - 1]))

    # ---- continuous batching of single-token steps across sessions ------------

    async def decode_async(self, uid: str, session_id: str, x: np.ndarray, reset: bool, loop_pass: int = 0):
        """One block's step: the span chain of one (`decode_span_async`)."""
        return await self.decode_span_async((uid,), session_id, x, reset, loop_pass)

    async def decode_span_async(self, uids, session_id: str, x: np.ndarray, reset: bool, loop_pass: int = 0):
        """Asyncio entrypoint: one session's step through the span chain ``uids``
        (this server's blocks that the request crosses, in order), at the pass ``loop_pass`` of the
        sessions' passes (`_chain_passes`: a looped model's blocks run several times a token). Batchable steps
        (continuation, chunk 1, session batch 1) join the chain's next cohort, which
        takes every block of the chain as one batched device call over the steps
        that waited together; everything else takes the direct per-session path,
        one chain on the device too (`_decode_direct`).

        Stamps the step's phases onto the caller's ``serving.request`` span, as
        ``TaskPool.submit_task`` does for the pools: ``queue_wait_s`` from the
        enqueue until the cohort that carries the step starts to run (the flush
        window or the cohort before it; a direct step has none), ``compute_s`` the
        rest."""
        started = time.perf_counter()
        out, queue_wait = await self._submit_step(tuple(uids), session_id, x, reset, loop_pass)
        if queue_wait:
            accrue_span_phase("queue_wait_s", queue_wait)
        accrue_span_phase("compute_s", time.perf_counter() - started - queue_wait)
        return out

    async def _submit_step(self, chain: Chain, session_id: str, x: np.ndarray, reset: bool, loop_pass: int = 0):
        """`decode_span_async` without the attribution: (output, seconds queued). A cohort takes the steps that
        wait on the chain WHATEVER their pass: the program is the same at every pass, each row on its own pass's
        arrays."""
        loop = asyncio.get_running_loop()
        # a row in the wire's half precision (the handler's view of an fp16 request's buffer) stays as it came:
        # a cohort widens its rows together, off this thread (`_device_rows`), the direct path its own
        x = x if getattr(x, "dtype", None) == np.float16 else np.asarray(x, np.float32)
        batchable = not reset and x.ndim == 3 and x.shape[0] == 1 and x.shape[1] == 1
        enqueued = time.perf_counter()
        if batchable:
            # ONE hold of the lock a step, and nothing under it that grows with the table. Lookup +
            # enqueue under one hold: releasing in between would let an eviction pass delete a
            # session while this step is pending, so the step would update an orphaned cache and
            # the next continuation KeyErrors
            with self._lock:
                # a chain's sessions are opened together: its first block speaks for it
                batchable = self._concurrent_sessions(chain[0])
                if batchable:
                    self._check_pass(chain, loop_pass)
                    sessions = self._known_locked(chain, session_id)
                    self._stamp_chain_locked(chain, [sessions])
                    future = loop.create_future()
                    self._pending.setdefault(chain, []).append((future, sessions, x, loop_pass))
                    if chain not in self._drainers or self._drainers[chain].done():
                        self._drainers[chain] = spawn(self._drain(chain), name="decode_session.drain")
        if not batchable:
            # prefill, reset, session batch != 1, batching off — or a single
            # actively-decoding stream: the drainer/future/flush-window machinery
            # has nothing to merge and costs ~ms per token, so it takes the direct
            # per-session path (same jitted step; same-session ordering is still
            # serialized by the session lock). ISSUE 10.
            return await loop.run_in_executor(None, self._decode_direct, chain, session_id, x, reset, loop_pass), 0.0
        out = await future
        return out, max(sessions[0].batch_started - enqueued, 0.0)

    def _concurrent_sessions(self, uid: str) -> bool:
        """True when MORE THAN ONE recently-active session exists on this uid
        (so waiting the flush window could actually merge steps). Called under
        self._lock; the caller's own session is always recent. No walk: the block's
        second latest stamp says it (`_stamp_locked`: every step is stamped through it when
        it is submitted and when it has ended, and `_drop_locked` finds the two again when
        one of them leaves), or its last batched program, which used two sessions at least."""
        recent = self._recent.get(uid)
        second_latest = max(recent[3] if recent else float("-inf"), self._batched_at.get(uid, float("-inf")))
        return time.monotonic() - second_latest < MERGE_RECENCY_S

    def _pin_locked(self, entries: List, step: int) -> None:
        """Move the eviction pins of ``entries``' sessions by ``step`` (+1 as they
        leave `_pending` for a cohort, -1 when it has resolved). Under self._lock."""
        for entry in entries:
            for session in entry[1]:
                count = self._in_flight.get(id(session), 0) + step
                if count > 0:
                    self._in_flight[id(session)] = count
                else:
                    self._in_flight.pop(id(session), None)

    async def _drain(self, chain: Chain) -> None:
        """One chain's drainer: it waits the flush window once, for a chain that
        was idle, then launches cohort after cohort until nothing is pending. Steps
        that arrive while a cohort is launched see a live drainer and only enqueue;
        the next cohort takes all of them the moment this one's last block is
        dispatched — its output is awaited and its futures resolved beside that
        (`_resolve`), so the device finds the next cohort's first program queued
        behind this one's last. The one cohort that is held back: with more than
        `HALVED_ABOVE` rows under way, one short of the bucket that holds half of
        them waits until a cohort still unanswered has been answered."""
        loop = asyncio.get_running_loop()
        held: List = []  # entries out of _pending whose sessions this drainer pins
        resolving = {}  # the `_resolve` tasks of cohorts launched and not yet answered -> their rows
        try:
            # the flush window exists to merge OTHER clients' concurrent steps;
            # with a single actively-decoding session it is pure per-token
            # latency (2 ms/step measured): then one loop tick, in which
            # same-tick submitters still merge (ISSUE 10)
            with self._lock:
                window = FLUSH_WINDOW_S if self._concurrent_sessions(chain[0]) else 0.0
            await asyncio.sleep(window)  # let concurrent streams pile up
            while True:
                with self._lock:
                    # the entries leave _pending now, but their caches are updated
                    # until the cohort resolves: keep them pinned against eviction
                    arrived = self._pending.pop(chain, [])
                    self._pin_locked(arrived, +1)
                    held += arrived
                if not held:
                    if not resolving:
                        return  # no await since the pop: a later submitter finds this task done
                    await asyncio.wait(resolving, return_when=asyncio.FIRST_COMPLETED)
                    continue
                # one session must not appear twice in a cohort (its cache would
                # fork): later duplicates roll over to the next one, and so do the
                # rows past a full bucket (`_cohort_rows`)
                seen, cohort, rollover = set(), [], []
                for entry in held:
                    ids = {id(session) for session in entry[1]}
                    if ids & seen:
                        rollover.append(entry)
                    else:
                        seen |= ids
                        cohort.append(entry)
                active = len(held) + sum(resolving.values())
                take = _cohort_rows(len(cohort), active)
                if resolving and active > HALVED_ABOVE and take < _half_bucket(active):
                    # short of the bucket that one of two alternating cohorts fills, while a cohort is still on
                    # the device: that one's rows cannot come back before it is answered and the others' are on
                    # their way, so this one waits for it instead of costing the device a bucket for a few rows.
                    # Only a walk that outruns its clients gets here (ISSUE 50: a dispatch is a third of what it
                    # was); one that the host paces finds a full bucket waiting and launches as ever
                    await asyncio.wait(resolving, return_when=asyncio.FIRST_COMPLETED)
                    continue
                cohort, rollover = cohort[:take], cohort[take:] + rollover
                try:
                    finish = await loop.run_in_executor(None, self._launch_cohort, chain, cohort)
                except Exception as e:
                    failed = [e] * len(cohort)
                    finish = lambda: failed  # noqa: E731
                held = rollover  # the cohort's futures and pins are its `_resolve` task's from here
                task = spawn(self._resolve(chain, cohort, finish), name="decode_session.resolve")
                resolving[task] = len(cohort)
                task.add_done_callback(lambda done: resolving.pop(done, None))
        except asyncio.CancelledError:
            # drainer killed in its flush window or mid-cohort (loop shutdown,
            # server stop): nothing will ever resolve these futures — cancel them
            # so callers unblock instead of waiting forever. Steps that arrived
            # WHILE a cohort was launched only enqueued into _pending (they saw a
            # live drainer), so they are swept too or they strand and pin forever.
            with self._lock:
                stranded = self._pending.pop(chain, [])
            for future, *_step in held + stranded:
                if not future.done():
                    future.cancel()
            for task in resolving:
                task.cancel()
            raise
        finally:
            # the eviction pins MUST drop on every exit path: a leaked pin makes the
            # session permanently unevictable
            with self._lock:
                self._pin_locked(held, -1)

    async def _resolve(self, chain: Chain, cohort: List, finish) -> None:
        """Await a launched cohort's results (``finish``, on the executor) and
        answer its futures; its pins drop here, on every exit path, and in the same
        hold of the lock the sessions of the rows that came through are stamped: the end
        of their step (the dispatching thread takes no lock for it, `_batched_at`: a lock
        that the loop holds would cost it the interpreter, 0.19 ms a program when the
        scatter stamped under the lock, PERF.md §6 PR 44)."""
        ended: List = []  # the sessions of the rows that came through: a row that failed may have lost its sessions
        try:
            try:
                results = await asyncio.get_running_loop().run_in_executor(None, finish)
            except Exception as e:
                results = [e] * len(cohort)
            ended = [entry[1] for entry, result in zip(cohort, results) if not isinstance(result, Exception)]
            for (future, *_step), result in zip(cohort, results):
                if future.done():
                    continue
                if isinstance(result, Exception):
                    future.set_exception(result)
                else:
                    future.set_result(result)
        except asyncio.CancelledError:
            for future, *_step in cohort:
                if not future.done():
                    future.cancel()
            raise
        finally:
            with self._lock:
                self._pin_locked(cohort, -1)
                self._stamp_chain_locked(chain, ended)

    def _launch_cohort(self, chain: Chain, entries: List):
        """Walk ``entries`` [(future, [the session of each uid], x, loop_pass)] (the pass may be left out: the
        first) through the chain: at each block the rows still alive are one `_decode_batch`, whatever their passes, and a
        row's output there is its input at the next block — left on the device
        (`_Row`), so that the next block's program is dispatched while this one's
        runs. The walk runs at most one program ahead of the device: before block
        k+1 is dispatched, block k-1 has finished (its program is the only one at
        work beside block k's). A row that fails at a block keeps that
        exception and leaves the cohort; the blocks after it never see it. Returns
        ``finish``: called once, from any thread, it waits for the chain's last
        program and returns one result (ndarray or Exception) per entry, in order:
        a step is answered in the precision it was asked in, float32, or float16 for a
        row that came in the wire's half precision, cut from the ONE pass the last output's
        rows take together (`_Output.wire`)."""
        _COHORTS.inc()
        # a chain of one pass (every chain but a looped model's) holds one pass by definition: nothing is read off its rows
        passes = len({_entry_pass(entry) for entry in entries}) if self._chain_passes(chain) > 1 else 1
        _COHORT_PASSES.inc(passes)
        results: List = [entry[2] for entry in entries]
        alive = list(range(len(entries)))
        launched: List[_Output] = []
        dispatched = 0  # the blocks of the chain whose batch has been dispatched and scattered

        def fail(error: Exception) -> None:
            # a block's program failed: every row still under way fails with it. The
            # batch that raised at its own dispatch has dropped the sessions whose
            # caches it had taken (`_decode_batch_traced`). The sessions of the blocks
            # dispatched before were handed outputs of programs that were still
            # running, and a program that fails when it is awaited is one of those:
            # what they point at may be unreadable now, and a step that ended at some
            # blocks and not at others is no step. They go too (`_drop_failed`: the
            # clients' next continuations get the unknown-session KeyError and
            # re-prefill); the sessions of blocks never reached stay as they were
            self._drop_failed([session for i in alive for session in entries[i][1][:dispatched]])
            for i in alive:
                results[i] = error

        def finish() -> List:
            with _trace_sync("decode.fetch", rows=len(alive)):
                try:
                    for output in launched:
                        output.settle()
                    for i in alive:
                        if isinstance(results[i], _Row):
                            results[i] = results[i].wire() if entries[i][2].dtype == np.float16 else results[i].host()
                except Exception as e:
                    fail(e)
            return results

        with _trace_sync("decode.cohort", rows=len(entries), chain_len=len(chain), passes=passes):
            try:
                for depth, uid in enumerate(chain):
                    batch = [(entries[i][0], entries[i][1][depth], results[i], *entries[i][3:]) for i in alive]
                    outs = self._decode_batch(uid, batch, fetch=False)
                    dispatched = depth + 1
                    for i, out in zip(alive, outs):
                        results[i] = out
                    alive[:] = [i for i in alive if not isinstance(results[i], Exception)]
                    if not alive:
                        break
                    if isinstance(results[alive[0]], _Row):  # one program, one output for all its rows
                        launched.append(results[alive[0]].output)
                        if len(launched) > 1:
                            launched[-2].settle()
            except Exception as e:
                fail(e)
                alive.clear()
        return finish

    def _batched_fn(self, uid: str, stack: int):
        """The one program of a batch of ``stack`` rows: it takes the rows' caches
        as they are kept, leaf by leaf the tuple of the rows' arrays, joins each leaf
        along the batch axis, applies the block ONCE to all rows with the vector of
        their write positions (`_raw_step`: the block runs its cache update and
        attention per row, all else on the rows together) and hands the new caches back
        one array a leaf a session, so that no operation per session runs outside it.
        A block that says `decode_rows_apart` is handed the tuples UNJOINED and hands
        tuples back: one whose step reads a small part of a large cache updates and
        reads each row's own arrays where they lie, and nothing joins, copies or splits
        them. The caches are DONATED, as a session's own step donates them (`_step_fn`):
        every new leaf takes the buffer of the leaf it replaces, so a step allocates
        and copies nothing for them, and a caller hands each position a cache of its
        own (`_padding`) and keeps no leaf across the call. Keyed by the bucket alone;
        padding rows come in as arguments like live ones."""
        key = (uid, stack)
        fn = self._batched_fns.get(key)
        if fn is None:
            def build():
                step = self._raw_step(uid)
                apart = self._rows_caches(uid) == "apart"
                placed = self._cache_shardings(uid)

                def batched_step(params, xs, columns, indices):
                    leaves = columns if apart else tuple(jnp.concatenate(rows) for rows in columns)
                    y, new, routing, attended = step(params, xs, leaves, indices)
                    if not apart:  # back to one array a leaf a session
                        new = tuple(tuple(jnp.split(leaf, stack)) for leaf in new)
                    return y, new, routing, attended

                return tracked_jit(
                    self._named_by_kind(uid, batched_step, "batched_step_{kind}"), site="decode_session.batched_step",
                    donate_argnums=(2,),
                    out_shardings=(None, placed and tuple((leaf,) * stack for leaf in placed), None, None),
                )

            fn = self._batched_fns[key] = self._of_kind(uid, ("batched", stack), build)
        return fn

    def _padding(self, uid: str, count: int) -> List[tuple]:
        """``count`` throwaway caches (each its leaves) for the padding positions of one
        batched program, taken OUT of the block's store: the program donates them, and a
        buffer is donated once a call, so every position has a cache of its own and no
        other call finds these meanwhile. Their outputs and cache writes are discarded;
        the caller puts the new leaves back (`_keep_padding`). The store grows to the
        largest padding a call has needed, made as a session's first prefill makes its
        cache (`_fresh_caches`: placed like a session's, so that a padded call reaches
        the program a full bucket compiled, and nothing new compiles for it)."""
        if not count:  # a full bucket, which is what the cohort rule aims at
            return []
        with self._padding_lock:
            store = self._padding_rows.setdefault(uid, [])
            taken = [store.pop() for _ in range(min(count, len(store)))]
        fresh = [tuple(jax.tree_util.tree_leaves(self._fresh_caches(self.backends[uid], 1))) for _ in range(count - len(taken))]
        if fresh:
            with self._padding_lock:
                self._padding_bytes += sum(map(_row_bytes, fresh))
                _PADDING_BYTES.set(self._padding_bytes)
        return taken + fresh

    def _keep_padding(self, uid: str, rows, lost: int = 0) -> None:
        """Back into the block's store: ``rows``, the throwaway caches a program handed
        back (or did not take); ``lost`` bytes of them went with a program that failed."""
        with self._padding_lock:
            self._padding_rows.setdefault(uid, []).extend(rows)
            if lost:
                self._padding_bytes -= lost
                _PADDING_BYTES.set(self._padding_bytes)

    def _dummy_rows(self, uid: str) -> tuple:
        """ONE throwaway cache's leaves, as the block's padding positions hold them: what
        a session's cache looks like (shapes, bytes, placement) to whoever lowers a program."""
        [row] = self._padding(uid, 1)
        self._keep_padding(uid, [row])
        return row

    def _decode_batch(self, uid: str, entries: List, fetch: bool = True) -> List:
        """Run one batched step over `entries` [(future, session, x, loop_pass)] (the pass may be left out: the
        first), each row on the arrays and at the position of ITS pass: the rows of one program may be at
        different passes, the program is the same; returns one
        result (ndarray or Exception) per entry, in order. An ``x`` is a host array
        ``[1, 1, hidden]`` or a `_Row` (a cohort's row, still on the device); with
        ``fetch=False`` (a cohort, mid-chain) the live rows come back as `_Row`s of
        a program that may still be running."""
        started = time.perf_counter()
        for entry in entries:
            entry[1].batch_started = started  # ends these steps' queue wait (decode_span_async)
        with _trace_sync("decode.batch", uid=uid) as span:
            return self._decode_batch_traced(uid, entries, span, fetch)

    def _decode_batch_traced(self, uid: str, entries: List, span, fetch: bool) -> List:
        backend = self.backends[uid]
        # per-session locks in a fixed order so the direct path cannot deadlock us
        ordered = sorted(range(len(entries)), key=lambda i: id(entries[i][1]))
        for i in ordered:
            entries[i][1].lock.acquire()
        try:
            results: List = [None] * len(entries)
            live = []
            # each row's pass: read off the entries of a block of several passes only
            passes = [_entry_pass(entry) for entry in entries] if self.block_passes(uid) > 1 else [0] * len(entries)
            for i, (entry, loop_pass) in enumerate(zip(entries, passes)):
                session = entry[1]
                index = session.positions[loop_pass]
                if index == 0:
                    results[i] = KeyError(f"decode session for {uid!r} has no prefill yet")
                elif index + 1 > self.max_len:
                    results[i] = ValueError(f"decode session is full ({index}/{self.max_len})")
                elif session.batch != 1:
                    results[i] = ValueError("batched decode requires session batch 1")
                elif loop_pass and index + 1 > session.positions[loop_pass - 1]:
                    results[i] = ValueError(_out_of_order(loop_pass, index + 1, session.positions[loop_pass - 1]))
                else:
                    live.append(i)
            if span is not None:
                span.set("rows", len(live))
                span.set("passes", len({passes[i] for i in live}))
            if not live:
                return results
            if len(live) == 1:
                # single-stream batch (one client decoding): the vmapped path
                # would stack-copy the session's multi-hundred-KB caches and
                # discard dummy-row work per token — use the per-session jitted
                # step directly (shared with decode(), so signatures can't
                # diverge); ISSUE 10 copy-free batching applied to decode
                [i] = live
                session, x, loop_pass = entries[i][1], entries[i][2], passes[i]
                x = x.host() if isinstance(x, _Row) else np.asarray(x, np.float32)  # the program's one input dtype
                record_transfer(int(x.nbytes), "host_to_device")
                y = self._advance(uid, session, backend, x, 1, 1, loop_pass)
                session.positions[loop_pass] += 1
                session.last_used = time.monotonic()  # the stamp proper follows when the cohort resolves
                # counted "direct": nothing was merged/vmapped (the catalog row
                # defines `batched` as merged into a vmapped continuous batch)
                _STEPS.inc(path="direct")
                _count_pass_steps(loop_pass)
                results[i] = np.asarray(y)[:, :1]
                record_transfer(results[i].nbytes, "device_to_host")
                return results
            sessions, passes = [entries[i][1] for i in live], [passes[i] for i in live]
            # a block that keeps nothing has no caches to take apart or join: "none"
            stack, caches = _next_pow2(len(live)), self._rows_caches(uid) if sessions[0].trees[0] else "none"
            kind = self._cache_kind(uid)
            if span is not None:
                span.set("bucket", stack)
                span.set("cache", kind)
                span.set("caches", caches)
                span.set("donated", True)
            _CALLS_BATCHED.inc()
            with _batch_phase("assemble"):
                # handles only: the rows' caches go in as they are, the write
                # positions as one host array, the activations as one array on the
                # device: the last block's output where these are its rows, else one
                # host array through the upload program
                xs = self._device_rows([entries[i][2] for i in live], stack)
                # a padding row writes a valid mid-cache position; its output is discarded
                padding = self._padding(uid, stack - len(live))
                indices = np.array([session.positions[loop_pass] for session, loop_pass in zip(sessions, passes)] + [1] * len(padding), np.int32)
                # leaf by leaf, the tuple of the rows' arrays (a pair: the keys' tuple and the values'), each row's of its own pass
                columns = tuple(zip(*[session.trees[loop_pass] for session, loop_pass in zip(sessions, passes)] + padding))
                step = self._batched_fn(uid, stack)
            row_bytes = sessions[0].row_bytes  # every position's cache is one row of this block's, padding too
            _DONATED_BATCHED.inc(stack * row_bytes)
            try:
                with _batch_phase("step"):
                    y, new, routing, attended = step(backend.snapshot_params(), xs, columns, indices)
                    output = _Output(y, routing, attended, len(live), held_range(backend.module))
                    if fetch:
                        output.host()
                        output.settle(span)
            except Exception:
                # the program took what it was handed (a step that raised before it donated anything, at its
                # tracing, took nothing): those sessions go, and those throwaway caches
                taken = lambda leaves: any(leaf.is_deleted() for leaf in leaves)  # noqa: E731
                self._drop_failed([session for session, loop_pass in zip(sessions, passes) if taken(session.trees[loop_pass])])
                kept = [row for row in padding if not taken(row)]
                self._keep_padding(uid, kept, lost=(len(padding) - len(kept)) * row_bytes)
                raise
            new = list(zip(*new)) if new else [()] * stack  # row by row, its new leaves (of a tree of none: none)
            if padding:
                self._keep_padding(uid, new[len(live):])
            _STEPS.inc(len(live), path="batched")
            _BATCHED_ROWS.inc(len(live), caches=caches)
            if kind == "latent":
                _LATENT_POSITIONS.inc(int(indices[:len(live)].sum()) + len(live), path="batched")
            elif kind == "ssm":
                _SSM_STATE_BYTES.inc(len(live) * row_bytes, path="batched")
            elif kind == "looped":
                _LOOPED_POSITIONS.inc(int(indices[:len(live)].sum()) + len(live), path="batched")
            if any(passes):
                for loop_pass in set(passes):
                    _count_pass_steps(loop_pass, passes.count(loop_pass))
            else:
                _count_pass_steps(0, len(live))
            with _batch_phase("scatter"):
                now = time.monotonic()
                for row, (i, session, loop_pass, leaves) in enumerate(zip(live, sessions, passes, new)):
                    session.trees[loop_pass] = leaves
                    session.positions[loop_pass] += 1
                    session.last_used = now  # bare stores, no lock: `_batched_at`
                    results[i] = output.host()[row:row + 1] if fetch else _Row(output, row)
                self._batched_at[uid] = now
            return results
        finally:
            for i in ordered:
                entries[i][1].lock.release()

    def _device_rows(self, rows: List, stack: int):
        """The activations of a batch, ``[stack, 1, hidden]`` on the device. Where
        ``rows`` are, in order, the live rows of ONE program's output of this
        bucket, that output is taken as it is (its padding rows ride along: what a
        padding row computes is discarded at every block). Otherwise one host array,
        zero rows as padding, through
        the upload program, so that a block's program meets its activations on the
        device whoever calls it: a cohort mid-chain and the first block of a chain
        reach the same compiled entry. The join is also where rows that came in the
        wire's half precision widen, all in the one numpy call and to numpy's bits (what
        the fp16 codec's `from_half` gives a row): the upload is float32 whatever the
        rows were, so no program sees another dtype."""
        first = rows[0]
        if (isinstance(first, _Row) and first.output.rows == len(rows) and first.output.y.shape[0] == stack
                and all(isinstance(x, _Row) and x.output is first.output and x.row == at for at, x in enumerate(rows))):
            return first.output.y
        rows = [x.host() if isinstance(x, _Row) else x for x in rows]
        if stack > len(rows):
            rows.append(np.zeros((stack - len(rows), *rows[0].shape[1:]), np.float32))
        xs = np.concatenate(rows, dtype=np.float32)
        record_transfer(int(xs.nbytes), "host_to_device")  # the caches are already resident
        return self._upload(xs)
