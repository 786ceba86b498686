"""Plain float32 reference of a span of Ouro-2.6B's decoder blocks and of the LOOP they run in.

Straightforward `jax.numpy` after the family's published description (ByteDance, "Scaling Latent
Reasoning via Looped Language Models", 2025; `model_type: ouro`): a looped language model runs
its stack of blocks `total_ut_steps` times a token with the same weights. One block, with `N1..N4`
RMS norms of a learned scale and no bias on any projection:

    a = W_o . Attn(rope(W_q N1(x)), rope(W_k N1(x)), W_v N1(x))     h = x + N2(a)
    m = W_down (silu(W_gate N3(h)) * W_up N3(h))                     y = h + N4(m)

(causal softmax at head_dim^-1/2, rotary embedding over the whole head in the rotate-half layout at the
absolute position), and the loop, with `F` the model's final RMS norm (one set of weights):

    x^0 = the embedded tokens;   x^{u+1} = F(Block_last(.. Block_first(x^u) ..)),  u = 0 .. total_ut_steps - 1

every block at pass u attending the keys and values that pass u itself computed: a cached
implementation keeps `total_ut_steps` caches a block. `span` returns EVERY pass's output
(`x^1 .. x^{total_ut_steps}`): the last is what the head and the exit gate read.

No kernels, no cache, no batching tricks, independent of `hivemind_tpu`: it takes the weights as arrays
(a block's tree is read by its leaves' names alone). Departures from the published model, each
written into the configuration's file too:

- the span is a CUT of the stack (the first server's six blocks of 48): the 42 absent blocks are left
  out, so `F` follows this span's output, in the program's client and here alike;
- *assumed* from the family's description, because the catalog's row does not say: the sandwich norms
  (`N2`, `N4`), no biases, `F` after every pass with its output as the next pass's input, a cache for
  every (pass, layer);
- the exit gate (a 2,048 -> 1 linear and a sigmoid on every `x^{u+1}`) is read by nothing here:
  `early_exit_threshold` 1 is the published value and means that every token runs every pass;
- the weights are random, drawn from the seed.

The WRONG programs that a comparison has to tell from the model are here too, each departing in ONE
thing: `span(.., sandwich=False)` (plain pre-norm), `span(.., norm_between=False)` (`F` left out
between the passes), `span(.., passes=3)` handed to a caller that expects four, and
`span_through_one_cache` (the passes of a decoded token sharing ONE cache a block)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta, positions):
    """``x`` ``[batch, seq, heads, dim]`` rotated at ``positions`` ``[seq]`` (rotate-half layout)."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(jnp.concatenate([angles, angles], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([angles, angles], -1))[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _float32(tree):
    return jax.tree_util.tree_map(lambda leaf: jnp.asarray(leaf, jnp.float32), tree)


def _keys_values(params, x, positions, num_kv_heads, rope_theta, rms_eps):
    batch, seq, _hidden = x.shape
    normed = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    k = (normed @ params["key"]["kernel"]).reshape(batch, seq, num_kv_heads, -1)
    v = (normed @ params["value"]["kernel"]).reshape(batch, seq, num_kv_heads, -1)
    return _rope(k, rope_theta, positions), v


def _attend(params, x, k, v, visible, positions, num_heads, rope_theta, rms_eps):
    """The attention sublayer's output `a` for the queries ``x`` ``[batch, seq, hidden]`` at ``positions``
    over keys and values ``[batch, slots, kv_heads, dim]``; ``visible`` ``[seq, slots]`` says which a query sees."""
    batch, seq, _hidden = x.shape
    kv_heads, dim = k.shape[2], k.shape[3]
    normed = _rms_norm(x, params["attention_norm"]["scale"], rms_eps)
    q = _rope((normed @ params["query"]["kernel"]).reshape(batch, seq, num_heads, dim), rope_theta, positions)
    q = q.reshape(batch, seq, kv_heads, num_heads // kv_heads, dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(float(dim))
    scores = jnp.where(visible[None, None, None], scores, -jnp.inf)
    context = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, axis=-1), v)
    return context.reshape(batch, seq, num_heads * dim) @ params["attention_out"]["kernel"]


def _rest(params, x, a, rms_eps, sandwich):
    """From the attention sublayer's output to the block's: the two residuals, the MLP, the output norms."""
    h = x + (_rms_norm(a, params["attention_out_norm"]["scale"], rms_eps) if sandwich else a)
    normed = _rms_norm(h, params["ffn_norm"]["scale"], rms_eps)
    m = (jax.nn.silu(normed @ params["ffn_gate"]["kernel"]) * (normed @ params["ffn_up"]["kernel"])) @ params["ffn_down"]["kernel"]
    return h + (_rms_norm(m, params["ffn_out_norm"]["scale"], rms_eps) if sandwich else m)


def block(params, x, num_heads: int, num_kv_heads: int, rope_theta: float, rms_eps: float, sandwich: bool = True):
    """One block, one pass, the whole sequence ``x`` ``[batch, seq, hidden]``. ``sandwich=False`` is the
    WRONG block of a plain pre-norm model: the norms on the sublayers' outputs left out."""
    seq = x.shape[1]
    positions = jnp.arange(seq)
    k, v = _keys_values(params, x, positions, num_kv_heads, rope_theta, rms_eps)
    a = _attend(params, x, k, v, jnp.tril(jnp.ones((seq, seq), bool)), positions, num_heads, rope_theta, rms_eps)
    return _rest(params, x, a, rms_eps, sandwich)


def span(all_params, final_norm, x, passes: int = 4, norm_between: bool = True, sandwich: bool = True, apply_block=block, **sizes):
    """The loop over the blocks of ``all_params`` (a list of parameter trees), ``final_norm`` the scale of
    `F`: every pass's output, ``[passes, batch, seq, hidden]``. ``norm_between=False`` is the WRONG loop
    that hands a pass's output to the next as it left the last block (`F` only where the model ends).
    ``apply_block``: `block`, or a caller's compiled form of it (one program a block in place of one an operation)."""
    with jax.default_matmul_precision("highest"):
        all_params, final_norm, x = _float32(all_params), jnp.asarray(final_norm, jnp.float32), jnp.asarray(x, jnp.float32)
        outputs = []
        for u in range(passes):
            for params in all_params:
                x = apply_block(params, x, sandwich=sandwich, **sizes)
            normed = _rms_norm(x, final_norm, sizes["rms_eps"])
            outputs.append(normed)
            x = normed if norm_between else x
        return jnp.stack(outputs)


def span_through_one_cache(all_params, final_norm, x, prompt: int, passes: int = 4, apply_block=block, **sizes):
    """The WRONG program whose passes share ONE cache a block, as a served session would run it: the prompt's
    ``prompt`` positions pass by pass (a chunk attends within itself, so each pass is exact, and the cache is left
    holding the LAST pass's keys and values), then one position at a time, pass u of position t writing slot t over
    what pass u-1 wrote there and attending the last pass's keys and values of every position before t. Every pass's
    output, ``[passes, batch, seq, hidden]``; the positions of the prompt are the model's own."""
    with jax.default_matmul_precision("highest"):
        all_params, final_norm, x = _float32(all_params), jnp.asarray(final_norm, jnp.float32), jnp.asarray(x, jnp.float32)
        num_heads, num_kv_heads, rope_theta, rms_eps = (sizes[name] for name in ("num_heads", "num_kv_heads", "rope_theta", "rms_eps"))
        seq = x.shape[1]
        # the prompt, and what the one cache of each block holds after it: the last pass's keys and values
        outputs, current = [], x[:, :prompt]
        for u in range(passes):
            keys, values = [], []
            for params in all_params:
                k, v = _keys_values(params, current, jnp.arange(prompt), num_kv_heads, rope_theta, rms_eps)
                keys.append(jnp.pad(k, ((0, 0), (0, seq - prompt), (0, 0), (0, 0))))
                values.append(jnp.pad(v, ((0, 0), (0, seq - prompt), (0, 0), (0, 0))))
                current = apply_block(params, current, **sizes)
            current = _rms_norm(current, final_norm, rms_eps)
            outputs.append(current)

        def one_position(all_params, final_norm, keys, values, x_t, t):
            at, visible = t[None], (jnp.arange(seq) <= t)[None]
            outs = []
            for u in range(passes):
                for index, params in enumerate(all_params):
                    k, v = _keys_values(params, x_t, at, num_kv_heads, rope_theta, rms_eps)
                    keys[index] = jax.lax.dynamic_update_slice(keys[index], k, (0, t, 0, 0))
                    values[index] = jax.lax.dynamic_update_slice(values[index], v, (0, t, 0, 0))
                    a = _attend(params, x_t, keys[index], values[index], visible, at, num_heads, rope_theta, rms_eps)
                    x_t = _rest(params, x_t, a, rms_eps, True)
                x_t = _rms_norm(x_t, final_norm, rms_eps)
                outs.append(x_t)
            return keys, values, jnp.stack(outs)

        one_position = jax.jit(one_position)
        steps = []
        for t in range(prompt, seq):
            keys, values, outs = one_position(all_params, final_norm, keys, values, x[:, t:t + 1], jnp.int32(t))
            steps.append(outs)
        if not steps:
            return jnp.stack(outputs)
        return jnp.concatenate([jnp.stack(outputs), jnp.concatenate(steps, axis=2)], axis=2)
