"""A sparse expert layer whose work follows the tokens routed: float32 router,
top-k, and experts (SwiGLU, or non-gated ``down(relu(up x)^2)``: `ACTIVATIONS`) computed as
grouped matmuls over the (token, slot) pairs sorted by expert (`jax.lax.ragged_dot`).

The layer sees all tokens of a call together, ``[tokens, hidden]``: the pairs are
sorted once, every expert that was chosen is one contiguous group of rows, and its
weights are read once for the whole group, however many tokens chose it. An
expert nobody chose is never read. The FLOPs are those of the routed pairs
(tokens x k) up to the grouped matmul's tiling: XLA's TPU kernel walks the sorted
rows in tiles of m rows and visits a tile once for every group that has rows in
it, at most ``pairs / m + experts - 1`` visits of ``m x hidden x width`` each
(v5e compiler, jax 0.9.0: m = 256 for up to 256 pairs, 512 at 16,384); rows of a
visit that belong to another group are masked, not skipped. No group is padded by
this code.

On a TPU `ragged_dot` is the compiler's own grouped-matmul kernel
(`ragged-dot-none`, with `ragged-dot-metadata` before it, in a device trace); it is
differentiable (its transposes are ragged dots again), so `jax.vjp` goes through.

A layer may hold a SHARE of the experts (`routed_mlp_held`, `routed_swiglu_held`: expert parallelism's
layer without its exchange): it routes over all of them, computes the pairs whose
expert it holds and leaves the others out."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def route_top_k(tokens: jax.Array, router: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Router probabilities in float32 over all experts and the k largest of them
    per token, as they are (not renormalised): ``(top_p [tokens, k], top_e [tokens, k])``.
    The matmul runs at the highest precision: on a TPU a float32 dot is otherwise
    one bf16 pass, and near-ties between experts flip."""
    logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


# what an expert does between its input projections and its output projection: name -> (how many
# input projections it has, the function of their outputs)
ACTIVATIONS = {
    "swiglu": (2, lambda gate, up: jax.nn.silu(gate) * up),  # down(silu(gate x) * up x)
    "relu2": (1, lambda up: jnp.square(jax.nn.relu(up))),  # down(relu(up x)^2): a non-gated expert
}


def _grouped_mlp(tokens: jax.Array, weights: jax.Array, flat: jax.Array, w_in: Tuple[jax.Array, ...],
                 w_down: jax.Array, elsewhere: bool, activation: str) -> jax.Array:
    """The body of every entry point, gated experts and non-gated alike. ``flat``: each
    (token, slot) pair's group, the index of its expert among ``w_down``'s; with
    ``elsewhere``, the index one past the last for a pair whose expert is not among them.
    ``w_in``: the experts' input projections, as many as ``activation`` takes
    (`ACTIVATIONS`). The pairs are sorted by group once, every group is one contiguous run
    of rows for the grouped matmuls (one an input projection, one for the output), and the
    rows past the last group (``elsewhere``) add zero."""
    count, k = weights.shape
    groups = w_down.shape[0]
    projections, inner = ACTIVATIONS[activation]
    assert len(w_in) == projections, (activation, len(w_in))
    order = jnp.argsort(flat, stable=True)  # pairs sorted by expert: one group each
    sizes = jnp.bincount(flat, length=groups + elsewhere)[:groups].astype(jnp.int32)
    rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    rows = rounded(tokens)[order // k]
    projected = [jax.lax.ragged_dot(rows, w, sizes) for w in w_in]
    down = jax.lax.ragged_dot(rounded(inner(*projected)), w_down, sizes)
    if elsewhere:  # rows past the held groups belong to no group: whatever the kernel left there is not read
        down = jnp.where((jnp.arange(count * k) < sizes.sum())[:, None], down, 0.0)
    per_pair = down[jnp.argsort(order)].reshape(count, k, -1)  # back to [token, slot]
    return jnp.einsum("tkh,tk->th", per_pair, weights.astype(jnp.float32))


def routed_swiglu(tokens: jax.Array, top_p: jax.Array, top_e: jax.Array,
                  w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``sum_j top_p[t, j] * down_e(silu(gate_e(x_t)) * up_e(x_t))``, ``e = top_e[t, j]``.

    :param tokens: [tokens, hidden]
    :param w_gate, w_up: [experts, hidden, width]; w_down: [experts, width, hidden]
    :returns: [tokens, hidden] float32

    The matmuls take activations rounded to bf16 and the float32 weights as they are
    kept, and accumulate in float32: a TPU's default precision multiplies float32
    operands in one bf16 pass, so this is the block's other matmuls' arithmetic
    without a bf16 copy of every expert's weights written to memory at each call
    (the CPU multiplies the same operands in float32)."""
    return _grouped_mlp(tokens, top_p, top_e.reshape(-1), (w_gate, w_up), w_down, elsewhere=False, activation="swiglu")


def route_sigmoid_top_k(tokens: jax.Array, router: jax.Array, bias: jax.Array, k: int,
                        scale: float, n_group: int = 1, topk_group: int = 1) -> Tuple[jax.Array, jax.Array]:
    """The DeepSeek-V3 family's router: sigmoid scores over all experts in float32,
    the k experts with the largest ``score + bias`` (the bias picks and does not
    weigh), each weighed by its own score over the sum of the k picked scores, times
    ``scale``: ``(weights [tokens, k], top_e [tokens, k])``. The matmul runs at the
    highest precision, as in `route_top_k`. With ``n_group`` > 1 the choice is
    GROUP-LIMITED: the experts lie in ``n_group`` groups of consecutive numbers, a
    group's score is the sum of its two largest ``score + bias``, the ``topk_group``
    best groups are kept, and the k experts are chosen among theirs (a token's experts
    then lie on at most ``topk_group`` of the hosts that hold a group each). At
    ``n_group`` 1 nothing of it is traced: the program is the ungrouped one."""
    logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = choice.reshape(choice.shape[0], n_group, -1)
        _, kept = jax.lax.top_k(jax.lax.top_k(grouped, 2)[0].sum(-1), topk_group)
        in_kept = (kept[:, :, None] == jnp.arange(n_group)).any(1)  # [tokens, n_group]
        choice = jnp.where(in_kept[:, :, None], grouped, -jnp.inf).reshape(choice.shape)
    _, top_e = jax.lax.top_k(choice, k)
    picked = jnp.take_along_axis(scores, top_e, axis=-1)
    return scale * picked / picked.sum(-1, keepdims=True), top_e


def routed_mlp_held(tokens: jax.Array, weights: jax.Array, top_e: jax.Array, w_up, w_down: jax.Array, lo: int,
                    activation: str) -> jax.Array:
    """The routed experts of a layer that holds the experts ``[lo, lo + held)`` of those the
    router chooses among, whatever an expert is (`ACTIVATIONS`): ``w_up`` ``[held, input,
    width]`` for a non-gated expert (``relu2``), the pair ``(w_gate, w_up)`` of such arrays for
    a gated one (``swiglu``); ``w_down`` ``[held, width, output]``; ``top_e`` counts over ALL
    experts. The experts' input width is ``tokens``' own: the hidden size, or a latent
    narrower than it (LatentMoE: the caller projects down before and up after). The pairs are
    sorted once, held experts first and in order, the pairs routed elsewhere last; the grouped
    matmuls run over the held groups only, and a pair routed elsewhere adds zero (what its
    expert would add is another chip's to compute: nothing stands in for it)."""
    held = w_down.shape[0]
    local = top_e.reshape(-1) - lo
    flat = jnp.where((local >= 0) & (local < held), local, held)  # routed elsewhere: past the last held group
    w_in = tuple(w_up) if isinstance(w_up, (tuple, list)) else (w_up,)
    return _grouped_mlp(tokens, weights, flat, w_in, w_down, elsewhere=True, activation=activation)


def routed_swiglu_held(tokens: jax.Array, weights: jax.Array, top_e: jax.Array, w_gate: jax.Array,
                       w_up: jax.Array, w_down: jax.Array, lo: int) -> jax.Array:
    """`routed_swiglu` for a layer that holds the experts ``[lo, lo + held)`` of those
    the router chooses among: ``w_gate, w_up`` are ``[held, hidden, width]``, ``w_down``
    ``[held, width, hidden]``, ``top_e`` counts over ALL experts (`routed_mlp_held` with
    gated experts)."""
    return routed_mlp_held(tokens, weights, top_e, (w_gate, w_up), w_down, lo, "swiglu")
