"""Expert layer registry (capability parity: reference hivemind/moe/server/layers/).

``@register_expert_class(name, sample_input_fn)`` registers a flax module factory; the
sample input (batch-size-agnostic) defines the expert's I/O schema.

**Decode sessions: the contract of a block that keeps a cache.** A registered class
is served through `DecodeSessionManager` if it has ``init_decode_cache(batch, max_len)
-> (cache_k, cache_v)`` (two arrays, batch axis first) and its ``__call__(x, cache_k,
cache_v, index)`` returns ``(y, cache_k, cache_v)``. ``index`` is the write position,
in one of two ranks:

- a scalar: ONE session's prefill or step, ``x`` ``[batch, new_len, hidden]``, every
  row at the same position;
- a vector ``[rows]``: a batched step of ``rows`` different sessions, ``x``
  ``[rows, 1, hidden]``, the caches joined along the batch axis, each row at its OWN
  position. The block is applied once to all the rows (it is not vmapped from
  outside since PR 27, so that a sparse expert layer sees the rows together), and
  whatever it does with ``index`` must hold for a vector: hand it to
  `common._decode_attention` / `common.apply_rope`, which take both ranks, or
  ``jax.vmap`` the block's own per-row cache code when ``jnp.ndim(index) == 1``.
  A ``dynamic_update_slice`` or a position lookup written for a scalar fails or
  broadcasts wrongly there, and only in batched steps
  (`tests/test_moe.py::test_custom_cached_block_steps_batched` is the pattern).

The two caches may have any shape with the batch axis first, and the blocks of one
chain need not agree on it (`exaone_moe_block`: ``[batch, kv_heads, slots, head_dim]``,
a ring of ``window`` slots for a sliding-window block beside ``max_len`` slots for a
full-attention one): the manager joins, splits, donates and places each block's caches
as that block's ``init_decode_cache`` made them; that a session is full stays the
manager's to say (``max_len``). Two optional class attributes: ``decode_takes_length =
True`` makes the manager pass a fifth argument to a per-session call, the number of
REAL positions of the chunk (a prefill comes right-padded to a power of two; a cache
that keeps every position needs no telling, its padded tail lies past ``index``; a
ring must keep the padding out); ``decode_cache_kind`` (a short string) names the
block's decode programs (`jit_batched_step_<kind>`, `jit_prefill_<kind>_<positions>`)
and its caches in the telemetry (`hivemind_moe_decode_cache_bytes{kind}`). A block
whose expert layer holds a share of the experts says which in ``held_experts``
(``(lo, hi)``), and the routing counters tell the pairs it computed from the pairs
it chose (`moe/server/routing_stats.py`).

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
