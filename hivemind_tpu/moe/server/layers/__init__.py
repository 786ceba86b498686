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

Which rows meet in a batched step is decided per SPAN CHAIN, not per block: the steps
of the sessions that wait on the same chain of this server's blocks walk it together
(`DecodeSessionManager.decode_span_async`), so a block's ``[rows, 1, hidden]`` input
is the previous block's output for those same rows, in the same order, and a block
may rely on nothing about which sessions share its step."""

from hivemind_tpu.moe.server.layers.common import (
    CausalTransformerExpert,
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
