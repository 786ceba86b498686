"""Expert layer registry (capability parity: reference hivemind/moe/server/layers/).

``@register_expert_class(name, sample_input_fn)`` registers a flax module factory; the
sample input (batch-size-agnostic) defines the expert's I/O schema.

**Decode sessions: the contract of a block that keeps a cache.** A registered class
is served through `DecodeSessionManager` if it has ``init_decode_cache(batch, max_len)``
and its ``__call__(x, *cache, index)`` returns ``(y, *cache)``. The cache is a TREE of
arrays, batch axis first, as the block chooses: ``(cache_k, cache_v)`` (the four blocks
of `common.py`, all of them ``[batch, kv_heads, slots, head_dim]`` bf16, a head's slots
together: ``max_len`` slots, or a ring of ``window``), one recurrent state that a step UPDATES, or keys, values and compressed
keys that a step appends to (`minicpm_sala_block`, both), or ONE array of compressed
latents that every head's key and value are expanded from (`deepseek_v3_block`; a tree of
one leaf is still handed over as ``*cache``: the block's ``__call__(x, cache, index)`` gets
the array, or in a batched step of a block that says ``decode_rows_apart`` the tuple of the
rows' arrays, and returns ``(y, cache)`` in the form it came in), or a window of the last few
inputs of a short convolution (a state with a TIME AXIS of the kernel's width, which a step
rolls by one) beside a recurrent state (`nemotron_h_block`'s Mamba-2 mixer, and `granite_h_block`'s, which is
that mixer's body under a block that is a mixer AND a gated MLP), or NOTHING: a
block that keeps nothing between calls (`nemotron_h_block`'s expert layer: one residual a
block, so a feed-forward part is a block of its own) returns a tree of ZERO leaves, ``()``,
is called ``(x, index)`` and returns ``(y,)``. Such a block still sits in a decode chain: it
has a session at every block like any other (a position and a batch; no byte, no gauge
series, nothing to join, donate, pad or drop but the entry itself), because a chain is
served only if every block of it is. The manager keeps a session's
tree as the tuple of its leaves and never looks inside one: it joins the leaves of a
batch's rows along the batch axis and splits the new leaves back one a row (or hands a
block that says ``decode_rows_apart`` the rows' arrays as they are), DONATES the
leaves to every step, a per-session call's and a batched program's alike (the new leaves
take the old ones' buffers: a block must not keep a reference to a cache argument, and
what it hands back for a leaf has that leaf's shape and dtype), places and counts them
leaf by leaf (`shard_decode_cache` of a mesh backend takes a pair). The block is handed the leaves in the tree's order and
hands new ones back in the same order and shapes. ``index`` is the write position, in
one of two ranks:

- a scalar: ONE session's prefill, prompt chunk or step, ``x`` ``[batch, new_len,
  hidden]``, every row at the same position;
- a vector ``[rows]``: a batched step of ``rows`` different sessions, ``x``
  ``[rows, 1, hidden]``, the caches joined along the batch axis (or, for a block that
  says ``decode_rows_apart``, each leaf the tuple of the rows' arrays), each row at
  its OWN position. The block is applied once to all the rows (it is not vmapped from
  outside since PR 27, so that a sparse expert layer sees the rows together), and
  whatever it does with ``index`` must hold for a vector: `common.apply_rope` takes
  both ranks; a block that keeps its caches joined ``jax.vmap``s its own per-row cache
  code when ``jnp.ndim(index) == 1`` (`common._grouped_cache_step`'s ring); one that
  takes them apart steps row by row (`common._grouped_cache_step` given tuples, the one
  cache step of every block of `common.py`: one inner ``jax.jit`` of the one-row step,
  called once a row). A ``dynamic_update_slice`` or a position lookup written for a
  scalar fails or broadcasts wrongly there, and only in batched steps
  (`tests/test_moe.py::test_custom_cached_block_steps_batched` is the pattern).

The blocks of one chain need not agree on the tree (`exaone_moe_block`: a ring of
``window`` slots for a sliding-window block beside ``max_len`` slots for a
full-attention one; `minicpm_sala_block`: three arrays
beside one); that a session is full stays the manager's to say (``max_len``). A step
that fails must leave no half-updated state: both paths donate the tree, so the manager
drops the sessions whose caches a failed step had taken, a session's own call's and every
row's of a batched program's (and, in a cohort, those of the blocks dispatched before it);
their clients' next continuations get the unknown-session ``KeyError`` and re-prefill
(`hivemind_moe_decode_session_evictions_total{reason="failed_step"}`). A step that raises
before anything was donated (a bad shape at tracing) leaves its sessions as they were.
Optional class attributes:

- ``decode_takes_length = True``: the manager passes one more argument to a per-session
  call, the number of REAL positions of the chunk (a chunk of more than one position
  comes right-padded to a power of two; a cache that keeps every position needs no
  telling, its padded tail lies past ``index``; a ring or a recurrent state must keep
  the padding out). A block that does NOT ask still gets the padded chunk: it writes the
  padded tail into its cache past the real positions, where the causal mask hides it from
  every real query and the next chunk or step overwrites it (`deepseek_v3_block`; what it
  computes for the padded queries is sliced off by the manager);
- ``decode_takes_chunks = True``: a chunk of MORE THAN ONE position may CONTINUE a
  session (a long prompt arrives in chunks, each call continuing where the last one
  ended; the chunk is padded as a prefill is, but never past the cache's end). A chain
  takes such a chunk only if every block of it says so; any other block's first chunk
  is its whole prompt, and a later one raises the ``ValueError`` it always raised;
- ``decode_rows_apart = True`` (an attribute or a property of the module): in a batched
  step each leaf comes as the TUPLE of the rows' own arrays (``[1, ...]`` each, ``rows``
  of them) where it would come joined, and goes back as such a tuple. The block states a
  fact about its own step, the manager reads it, and nothing else chooses. The rule: a
  cache of ``max_len`` slots, of which a step writes a few KB and reads the rest where it
  lies, goes APART: `causal_transformer`, `llama_block`, `olmoe_block`, `exaone_moe_block`
  with ``window`` = 0 (ISSUE 42: the join, the copy and the split of 16 x 33.5 MB around
  every step were a quarter of OLMoE's device time) and `minicpm_sala_block`'s sparse
  mixer (ISSUE 41: a third of its program's time and 2.3 GB of its temporaries at 32 rows
  of 32,768 slots) and `deepseek_v3_block` (18.9 MB of latents a session at 16,384 slots).
  A state that a step REWRITES WHOLE goes by its size, re-read on a trace in ISSUE 51 and not
  assumed: `nemotron_h_block`'s state-space state of 4.19 MB a session goes APART (joined, 16 rows
  are 67 MB copied in, stepped and copied out: the mixer's batched program took 0.99 ms, and 0.81
  with each row's state stepped where it lies by one jitted call a row, a fifth of `copy-done`'s
  time left; its 61 KB window is joined for one convolution and split again inside the block). A
  ring of ``window`` slots (0.5 MB a session) or the lightning state (2.1 MB: not measured apart)
  stays JOINED: the join costs less than an operation a row. A block that keeps nothing has
  nothing to take apart (`hivemind_moe_decode_batched_rows_total{caches="none"}`). Apart, nothing is
  left of the caches' traffic but the step's own write and read: the arrays are donated
  (ISSUE 50), so a ``dynamic_update_slice`` on a row's array writes into that array, and a
  block that copied it first would pay the copy itself. A session's own call is handed arrays;
- ``decode_cache_kind`` (a short string) names the block's decode programs
  (`jit_batched_step_<kind>`, `jit_prefill_<kind>_<positions>`) and its caches in the
  telemetry (`hivemind_moe_decode_cache_bytes{kind}`);
- ``decode_passes`` (an int, an attribute or a property of the module; 1 where a block
  says nothing, which is every block but a LOOPED model's): the block runs that many times
  a token with the same weights, pass u taking what the client made of pass u-1's output,
  and pass u of a position attends what pass u cached, never another pass's
  (`ouro_block`: its ``total_ut_steps``). What it promises is that its step is the SAME at
  every pass: the manager makes a session with ``decode_passes`` trees from
  ``init_decode_cache`` (each call returns ONE pass's tree) and as many positions, under
  ONE entry of the table (one to the cap, the TTL, `clear_sessions`, a failed step's drop
  and the eviction counters; the byte gauges count every tree), and hands a step the
  leaves of the pass its request NAMED (``loop_pass`` in the request's metadata, 0 where it
  names none). The block never sees the pass: it is handed that pass's leaves and hands
  them back, so every pass runs the one program of its bucket and nothing compiles for a
  pass. The loop's order is the manager's to hold (a call that would carry pass u's position
  past pass u-1's is a ``ValueError`` before anything is donated, as is a pass outside
  ``[0, decode_passes)``); ``reset`` at pass 0 makes the session, at a later pass it starts
  that pass over inside it. A cohort takes the waiting rows WHATEVER their pass: a block's
  ``[rows, 1, hidden]`` input may hold rows of different passes of different positions, each
  beside the arrays of its own pass (`decode_rows_apart` makes that free; a block that keeps
  its caches joined gets them joined row by row all the same), and a block may rely on
  nothing about which passes share its step. Padding rows keep one tree. The blocks of one
  chain have to agree on ``decode_passes`` (they walk together): a chain that does not is
  refused when it is made. The norm between two passes, and whether a token runs every
  pass, are the client's (`RemoteSequential.decode_step(.., loop_pass=u)`), not a block's.

A block whose expert layer holds a share of the experts says which in ``held_experts``
(``(lo, hi)``), and the routing counters tell the pairs it computed from the pairs it
chose (`moe/server/routing_stats.py`). A block whose steps attend a SELECTION of what
they cached sows the positions attended and seen into `common.ATTENDED_COLLECTION` as
``attended``, and the same module counts the live rows' (`hivemind_moe_sparse_positions_*_total`);
what it sows there as ``chosen`` (the blocks each query selected) stays on the device
unless a check against a reference taps it (`routing_stats.SELECTION_TAPS`); what a
block's router saw and chose (``router_input``, ``router_choice``) is sown there the same
way (`routing_stats.ROUTER_TAPS`), so that a check reads the router of the served programs
and not of a pass of its own. A block whose steps attend ALL they cached but whose work
grows with it (a latent cache read where it lies: ``decode_cache_kind`` ``latent``) sows
nothing for that: the manager knows each live row's write position and counts the positions
attended itself (`hivemind_moe_latent_positions_attended_total`). A block that
takes a step and a chunk by different forms of the same attention (`deepseek_v3_block`: a
step absorbs, a chunk expands) tells them apart by the call alone: one position with a
cache is a step, anything else a chunk; nothing is configured.

Which rows meet in a batched step is decided per SPAN CHAIN, not per block: the steps
of the sessions that wait on the same chain of this server's blocks walk it together
(`DecodeSessionManager.decode_span_async`), so a block's ``[rows, 1, hidden]`` input
is the previous block's output for those same rows, in the same order, and a block
may rely on nothing about which sessions share its step."""

from hivemind_tpu.moe.server.layers.common import (
    CausalTransformerExpert,
    ExaoneMoeBlockExpert,
    FeedforwardExpert,
    NopExpert,
    TransformerExpert,
    name_to_block,
    name_to_input,
    register_expert_class,
)
from hivemind_tpu.moe.server.layers.dropout import (
    DeterministicDropout,
    DeterministicDropoutExpert,
)
from hivemind_tpu.moe.server.layers.optim import (
    clipped,
    lamb_with_warmup,
    linear_warmup_schedule,
)
