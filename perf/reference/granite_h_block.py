"""Plain float32 reference of ibm-granite/granite-4.0-h-micro blocks and of a span of them.

Straightforward `jax.numpy` after the model's published `config.json` (`model_type`
`granitemoehybrid`, `num_local_experts` 0: no expert anywhere) and, where that does not spell
a convention out, the GraniteMoeHybrid family's modeling code, which `model_type` names. ``x``
is ``[batch, T, hidden]``, a WHOLE stream: no cache, no chunks, no batching of sessions, no
kernels. EVERY block is a mixer and a gated MLP under two scaled residuals, r =
`residual_multiplier` 0.22, RMS norms with eps 1e-5 (`rms_norm_eps`), no bias but the
convolution's; the mixer's kind follows from the parameter tree (`layer_types[l]` built it):

    h = x + r * Mixer(RMSNorm1(x))          y = h + r * MLP(RMSNorm2(h))
    MLP(u) = (silu(g) * v) W_out,  [g | v] = u W_in        2,048 -> 2 x 8,192 (`shared_intermediate_size`) -> 2,048

**`mamba`, a Mamba-2 mixer** (a tree with ``in_proj``). H = 64 heads (`mamba_n_heads`) of
P = 64 (`mamba_d_head`), ONE group (`mamba_n_groups`), N = 128 (`mamba_d_state`), K = 4
(`mamba_d_conv`):

    [z | xBC | dt] = u W_in                   widths 4,096 | 4,352 | 64
    xBC'_t = silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})        depthwise, causal, zeros before position 0, WITH bias
    [x | B | C] = xBC'                         x [H, P];  B, C [N], read by every head
    D_t = softplus(dt_t + dt_bias) [H] (no clamp);  A = -exp(A_log) [H]
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t   per head, S [H, P, N] float32;   y_t = S_t C_t + D * x_t
    out = RMSNorm_4096(y * silu(z)) W_o         the gate BEFORE the norm, the norm over all 4,096 values

The mixer is the Mamba-2 mixer that `perf/reference/nemotron_h_block.py` already writes out
(its `mamba`: the recurrence a `lax.scan` POSITION BY POSITION with the state in float32,
nothing of the chunked form), called here with this model's sizes: one plain text of one
mixer, and the knobs that make its wrong references with it.

**`attention`** (a tree with ``query``). 32 query heads and 8 key-value heads of 64 (hidden /
heads), causal softmax in float32 at `attention_multiplier` = 0.015625 (NOT 64^(-1/2) = 0.125),
NO position embedding (`position_embedding_type` `nope`); the masked square, the queries a
block at a time so that the scores of 4,288 positions fit a device.

Independent of the program's `GraniteHBlockExpert`: it reads only that block's parameter tree.

Departures from the published model and assumptions, all of them (the configuration file
`perf/configs/granite-4.0-h-micro-span20.json` lists the same under `assumed`, and the
program's block takes the same):

- the weights are random, drawn from the seed: ``A`` uniform in [1, 16], the step size
  log-uniform in [0.001, 0.1] floored at 1e-4 and inverted through softplus, ``D`` = 1, the
  convolution in +-1/2: the Mamba-2 family's initial ranges (config.json gives none);
- the head width 64 = `hidden_size` / `num_attention_heads` (config.json has no `head_dim`);
- `embedding_multiplier` 12, the final norm, `logits_scaling` 8 and the tied embedding act
  outside the blocks, on the client's side; `rope_theta` is recorded and read by nothing.

The keyword arguments below that default to the model make deliberately WRONG references,
each in ONE thing: what a check must refuse."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference.nemotron_h_block import _float32, _rms_norm, _rotate_half_rope, mamba

MAMBA_SIZES = ("mamba_heads", "mamba_head_dim", "ssm_groups", "ssm_state")
MAMBA_KNOBS = ("state_dtype", "skip", "norm_before_gate", "conv_bias", "padding")
ATTENTION_SIZES = ("num_heads", "num_kv_heads", "head_dim", "attention_multiplier")
ATTENTION_KNOBS = ("rope", "root_scale")


def kind_of(params) -> str:
    return "mamba" if "in_proj" in params else "attention"


def attention(params, u, *, num_heads: int, num_kv_heads: int, head_dim: int, attention_multiplier: float,
              rope: bool = False, root_scale: bool = False, query_block: int = 512):
    batch, seq, _hid = u.shape
    q = (u @ params["query"]["kernel"]).reshape(batch, seq, num_heads, head_dim)
    k = (u @ params["key"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    v = (u @ params["value"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    if rope:  # a wrong reference: `position_embedding_type` is `nope`
        q, k = _rotate_half_rope(q), _rotate_half_rope(k)
    scale = head_dim**-0.5 if root_scale else attention_multiplier  # a wrong reference: the usual 1/8 where the model says 1/64
    k, v = (jnp.repeat(t, num_heads // num_kv_heads, axis=2) for t in (k, v))
    blocks = []
    for start in range(0, seq, query_block):  # the masked square, a block of queries at a time
        scores = jnp.einsum("bqhd,bshd->bhqs", q[:, start:start + query_block], k) * scale
        at_q = start + jnp.arange(scores.shape[2])
        scores = jnp.where(jnp.arange(seq)[None, :] <= at_q[:, None], scores, -jnp.inf)
        blocks.append(jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(blocks, axis=1).reshape(batch, seq, num_heads * head_dim) @ params["attention_out"]["kernel"]


def mlp(params, u, *, halves_swapped: bool = False):
    gate, up = jnp.split(u @ params["mlp_in"]["kernel"], 2, axis=-1)  # [g | v]
    if halves_swapped:  # a wrong reference: silu on the second half
        gate, up = up, gate
    return (jax.nn.silu(gate) * up) @ params["mlp_out"]["kernel"]


def block(params, x, *, rms_eps: float, residual_multiplier: float, return_state: bool = False, halves_swapped: bool = False, **sizes):
    """One block, of the kind its parameters are. ``sizes``: both kinds' sizes together (each kind
    reads its own) and any of the knobs that make a wrong reference. ``return_state``: also return
    the mixer's recurrent state after the last position (None for an attention block)."""
    take = lambda names: {name: sizes[name] for name in names if name in sizes}
    u, state = _rms_norm(x, params["norm"]["scale"], rms_eps), None
    if kind_of(params) == "mamba":
        f, state = mamba(params, u, rms_eps=rms_eps, **take(MAMBA_SIZES + MAMBA_KNOBS))
    else:
        f = attention(params, u, **take(ATTENTION_SIZES + ATTENTION_KNOBS))
    h = x + residual_multiplier * f
    y = h + residual_multiplier * mlp(params, _rms_norm(h, params["mlp_norm"]["scale"], rms_eps), halves_swapped=halves_swapped)
    return (y, state) if return_state else y


def span_with_states(all_params, x, **sizes):
    """The blocks of ``all_params`` (a list of parameter trees) applied in order. Returns the
    output and each block's last recurrent state (None for an attention block)."""
    with jax.default_matmul_precision("highest"):
        x, states = x.astype(jnp.float32), []
        for params in all_params:
            x, state = block(_float32(params), x, return_state=True, **sizes)
            states.append(state)
        return x, states


def span(all_params, x, **sizes):
    return span_with_states(all_params, x, **sizes)[0]
