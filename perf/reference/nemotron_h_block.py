"""Plain float32 reference of NVIDIA-Nemotron-3-Super-120B-A12B blocks and of a span of them.

Straightforward `jax.numpy` after the model's published `config.json` (`model_type`
`nemotron_h`, nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16) and, where that does not spell
a convention out, the NemotronH family's modeling code, which `model_type` names. ``x`` is
``[batch, T, hidden]``, a WHOLE stream: no cache, no chunks, no batching of sessions, no
kernels. A block is ONE residual, ``y = x + f(u)``, ``u = RMSNorm(x)`` with eps 1e-5
(`norm_eps`), no biases but the convolution's; what ``f`` is follows from the parameter
tree (the character of `hybrid_override_pattern` at the block's position built it):

**`M`, a Mamba-2 mixer** (a tree with ``in_proj``). H = 128 heads (`mamba_num_heads`) of
P = 64 (`mamba_head_dim`), G = 8 groups (`n_groups`), N = 128 (`ssm_state_size`), K = 4
(`conv_kernel`):

    [z | xBC | dt] = u W_in                   widths 8,192 | 10,240 | 128
    xBC'_t = silu(b + sum_{j<K} w_j * xBC_{t-K+1+j})        depthwise, causal, zeros before position 0: K shifted adds
    [x | B | C] = xBC'                         x [H, P];  B, C [G, N];  head h reads group floor(h / (H / G))
    D_t = softplus(dt_t + dt_bias) [H];  A = -exp(A_log) [H]
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t   per head, S [H, P, N];   y_t = S_t C_t + D * x_t
    out = GroupRMSNorm(y * silu(z)) W_out       the norm over groups of 8,192 / G values, gate BEFORE norm

The recurrence is a `lax.scan` POSITION BY POSITION with the state in float32.

**`*`, attention** (a tree with ``query``). 32 query heads and 2 key-value heads of 128, causal
softmax at 128^(-1/2) in float32, NO position embedding; the masked square, the queries a
block at a time so that the scores of 4,288 positions fit a device.

**`E`, LatentMoE** (a tree with ``router``). s = sigmoid(u W_r) over 512 experts in float32;
the 22 largest s + b picked (b: a per-expert bias that picks and does not weigh; `n_group`
1: no group limit); w_e = 5 * s_e / sum of the picked s (`norm_topk_prob`,
`routed_scaling_factor`); l = u W_dn (4,096 -> 1,024 = `moe_latent_size`);

    f = (sum_e w_e W2_e relu(l W1_e)^2) W_up + W2_s relu(u W1_s)^2

(`mlp_hidden_act` `relu2`: no gate projection). Every expert the parameters hold is computed
densely for every token in a loop and masked by its weight.

Independent of the program's `NemotronHBlockExpert`: it reads only that block's parameter tree.

Departures from the published model and assumptions, all of them (the configuration file
`perf/configs/nemotron-3-super-120b-span11.json` lists the same under `assumed`, and the
program's block takes the same):

- the weights are random, drawn from the seed: ``A`` uniform in [1, 16], the step size
  log-uniform in [`time_step_min`, `time_step_max`] floored at `time_step_floor` and inverted
  through softplus, ``D`` = 1, as the family initialises them, so that the decays are a real
  model's; the selection bias at a standard deviation of 0.1, which changes picks;
- ASSUMED from the family's code, config.json does not say: pre-norm; no clamp on the step
  size (`time_step_limit` (0, inf)); the gate before the group norm (`norm_before_gate`
  False); no position embedding in an attention block (`rope_theta` and
  `partial_rotary_factor` are recorded and read by nothing); the router and the shared expert
  on the FULL hidden, the latent projections around the routed experts alone, with no norm,
  bias or activation of their own;
- THE HELD SHARE: the parameters may hold only the experts ``[held_lo, held_lo + held)``
  (``experts_up`` is ``[held, latent, width]``) of those the router chooses among. The router
  keeps all its outputs; a pair whose expert is not held adds nothing, here as in the
  program, and ``W_up`` is applied to the held experts' partial sum (linear: the shares of all
  chips add up to the layer, the shared expert counted once). With every expert held
  (``held_lo`` 0) this is the uncut layer;
- `num_nextn_predict_layers` (the multi-token-prediction module), `vocab_size`,
  `tie_word_embeddings` act on the embedding and the head, which live on the client;
  `intermediate_size` is recorded and read by nothing (no `-` occurs in the pattern).

The keyword arguments below that default to the model make deliberately WRONG references,
each in ONE thing: what a check must refuse."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def kind_of(params) -> str:
    return "mamba" if "in_proj" in params else "attention" if "query" in params else "experts"


def causal_conv(xbc, weight, bias):
    """``xbc`` ``[batch, T, channels]``, ``weight`` ``[K, channels]``: K shifted adds, zeros before position 0."""
    taps, seq = weight.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(padded[:, tap:tap + seq] * weight[tap] for tap in range(taps)))


def recurrence(x, b, c, dt, a, state_dtype=jnp.float32, live=None):
    """The state-space recurrence position by position: ``x`` ``[batch, T, H, P]``, ``b``, ``c``
    ``[batch, T, G, N]`` (a position's are copied to its group's ``H / G`` heads as it is taken), ``dt``
    ``[batch, T, H]``, ``a`` ``[H]``. ``state_dtype`` below float32 rounds the state after every position (a wrong
    reference); ``live`` ``[T]`` bool: a position that is not neither decays nor feeds the
    state. Returns (``S_t C_t`` ``[batch, T, H, P]``, the state after the last position)."""
    batch, _seq, heads, dim = x.shape
    live = jnp.ones(x.shape[1], bool) if live is None else live

    def one_position(state, inputs):
        x_t, b_t, c_t, dt_t, live_t = inputs
        b_t, c_t = (jnp.repeat(t, heads // t.shape[1], axis=1) for t in (b_t, c_t))  # head h reads group floor(h / (H / G))
        new = jnp.exp(dt_t * a)[..., None, None] * state.astype(jnp.float32) + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        new = jnp.where(live_t, new, state.astype(jnp.float32)).astype(state_dtype)
        return new, (new.astype(jnp.float32) * c_t[..., None, :]).sum(-1)

    state = jnp.zeros((batch, heads, dim, b.shape[-1]), state_dtype)
    first = lambda t: jnp.moveaxis(t, 1, 0)
    state, y = jax.lax.scan(one_position, state, (first(x), first(b), first(c), first(dt), live))
    return jnp.moveaxis(y, 0, 1), state.astype(jnp.float32)


def mamba(params, u, *, mamba_heads: int, mamba_head_dim: int, ssm_groups: int, ssm_state: int, rms_eps: float,
          state_dtype: str = "float32", skip: bool = True, norm_before_gate: bool = False, conv_bias: bool = True,
          groups_reversed: bool = False, padding=None):
    """``padding`` = ``(what, after, count)`` makes the wrong references of a chunk's right-padding: ``count``
    rows of zeros (what a padded row is at the span's first block) stand after position ``after`` - 1;
    ``what`` ``"window"``: they enter the convolution of the positions that follow; ``"state"``: they decay
    and feed the state. Their own outputs are dropped either way. Returns (the mixer's output, the state
    after the last position ``[batch, H, P, N]``)."""
    heads, dim, groups, width = mamba_heads, mamba_head_dim, ssm_groups, ssm_state
    inner = heads * dim
    channels = inner + 2 * groups * width
    batch, seq, _hid = u.shape
    projected = u @ params["in_proj"]["kernel"]
    z, xbc, dt = projected[..., :inner], projected[..., inner:inner + channels], projected[..., inner + channels:]
    bias = params["conv_bias"] if conv_bias else jnp.zeros_like(params["conv_bias"])
    live = None
    if padding is not None:
        what, after, count = padding
        insert = lambda t: jnp.concatenate([t[:, :after], jnp.zeros((batch, count) + t.shape[2:], t.dtype), t[:, after:]], axis=1)
        keep = jnp.concatenate([jnp.arange(after), jnp.arange(after + count, seq + count)])
        with_rows = causal_conv(insert(xbc), params["conv_weight"], bias)
        if what == "window":  # the rows enter the window, and neither decay nor feed the state
            mixed, dt, live = with_rows, insert(dt), jnp.zeros(seq + count, bool).at[keep].set(True)
        else:  # the window is cut from the real rows, and the padded rows go through the recurrence
            mixed = insert(causal_conv(xbc, params["conv_weight"], bias)).at[:, after:after + count].set(with_rows[:, after:after + count])
            dt = insert(dt)
    else:
        mixed = causal_conv(xbc, params["conv_weight"], bias)
    x = mixed[..., :inner].reshape(batch, -1, heads, dim)
    by_group = lambda t: t.reshape(batch, -1, groups, width)[:, :, ::-1 if groups_reversed else 1]  # reversed: a wrong reference
    b, c = by_group(mixed[..., inner:inner + groups * width]), by_group(mixed[..., inner + groups * width:])
    dt = jax.nn.softplus(dt + params["dt_bias"])  # no clamp: the family's time_step_limit is (0, inf)
    y, state = recurrence(x, b, c, dt, -jnp.exp(params["A_log"]), jnp.dtype(state_dtype), live)
    if skip:
        y = y + params["D"][:, None] * x
    if padding is not None:
        y = y[:, keep]
    y = y.reshape(batch, seq, groups, inner // groups)
    gate = jax.nn.silu(z).reshape(y.shape)
    normed = lambda t: t * jax.lax.rsqrt(jnp.mean(jnp.square(t), axis=-1, keepdims=True) + rms_eps)
    scale = params["gate_norm"].reshape(groups, inner // groups)
    gated = normed(y) * scale * gate if norm_before_gate else normed(y * gate) * scale  # the gate BEFORE the norm is the model's
    return gated.reshape(batch, seq, inner) @ params["out_proj"]["kernel"], state


def _rotate_half_rope(x, theta: float = 10000.0):
    dim, seq = x.shape[-1], x.shape[1]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angles) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(angles)


def attention(params, u, *, num_heads: int, num_kv_heads: int, head_dim: int, rope: bool = False, query_block: int = 512):
    batch, seq, _hid = u.shape
    q = (u @ params["query"]["kernel"]).reshape(batch, seq, num_heads, head_dim)
    k = (u @ params["key"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    v = (u @ params["value"]["kernel"]).reshape(batch, seq, num_kv_heads, head_dim)
    if rope:  # a wrong reference: the family's attention applies none
        q, k = _rotate_half_rope(q), _rotate_half_rope(k)
    k, v = (jnp.repeat(t, num_heads // num_kv_heads, axis=2) for t in (k, v))
    blocks = []
    for start in range(0, seq, query_block):  # the masked square, a block of queries at a time
        scores = jnp.einsum("bqhd,bshd->bhqs", q[:, start:start + query_block], k) * head_dim**-0.5
        at_q = start + jnp.arange(scores.shape[2])
        scores = jnp.where(jnp.arange(seq)[None, :] <= at_q[:, None], scores, -jnp.inf)
        blocks.append(jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(blocks, axis=1).reshape(batch, seq, num_heads * head_dim) @ params["attention_out"]["kernel"]


def route(params, u, experts_per_token: int, scale: float, *, rounded: bool = False):
    """``(weights [.., experts], top_e [.., k])``: the weight of every expert (zero where not picked) and the picks.
    ``rounded``: the router's matmul in one bf16 pass (a wrong reference)."""
    if rounded:
        logits = jnp.dot(u.astype(jnp.bfloat16), params["router"].astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    else:
        logits = u @ params["router"]
    scores = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(scores + params["router_bias"], experts_per_token)
    picked = jnp.take_along_axis(scores, top_e, axis=-1)
    weights = scale * picked / picked.sum(-1, keepdims=True)
    return (jax.nn.one_hot(top_e, scores.shape[-1], dtype=weights.dtype) * weights[..., None]).sum(-2), top_e


def chosen_experts(params, u, experts_per_token: int):
    """The experts the published router chooses for the router inputs ``u`` (any dtype), in
    float32 at the highest matmul precision: a program's routing is held against it on the
    program's OWN router inputs (teacher-forced)."""
    with jax.default_matmul_precision("highest"):
        return route(_float32(params), u.astype(jnp.float32), experts_per_token, 1.0)[1]


def experts(params, u, *, experts_per_token: int, routed_scale: float, held_lo: int, activation: str = "relu2",
            rounded_router: bool = False, shared_on_latent: bool = False, shared: bool = True, routed: bool = True):
    """Returns ``(f, top_e)``. ``shared`` / ``routed`` = False leave that part out (the share test adds the
    routed parts of all shares to the shared expert counted once)."""
    inner = {"relu2": lambda t: jnp.square(jax.nn.relu(t)), "relu": jax.nn.relu}[activation]
    weights, top_e = route(params, u, experts_per_token, routed_scale, rounded=rounded_router)
    held = params["experts_up"].shape[0]
    weights = weights[..., held_lo:held_lo + held]  # a pair routed elsewhere adds nothing here
    latent = u @ params["latent_down"]["kernel"]

    def one_expert(total, expert):  # every held expert on every token, masked by its weight
        w_up, w_down, weight = expert
        return total + weight[..., None] * (inner(latent @ w_up) @ w_down), None

    f = jnp.zeros_like(u)
    if routed:
        summed = jax.lax.scan(one_expert, jnp.zeros_like(latent), (params["experts_up"], params["experts_down"], jnp.moveaxis(weights, -1, 0)))[0]
        f = summed @ params["latent_up"]["kernel"]
    if shared:
        fed = (latent @ params["latent_up"]["kernel"]) if shared_on_latent else u  # wrong: the latent, brought back to the hidden width
        f = f + inner(fed @ params["shared_up"]["kernel"]) @ params["shared_down"]["kernel"]
    return f, top_e


MAMBA_SIZES = ("mamba_heads", "mamba_head_dim", "ssm_groups", "ssm_state")
ATTENTION_SIZES = ("num_heads", "num_kv_heads", "head_dim")
EXPERT_SIZES = ("experts_per_token", "routed_scale", "held_lo")
MAMBA_KNOBS = ("state_dtype", "skip", "norm_before_gate", "conv_bias", "groups_reversed", "padding")
ATTENTION_KNOBS = ("rope",)
EXPERT_KNOBS = ("activation", "rounded_router", "shared_on_latent", "shared", "routed")


def block(params, x, *, rms_eps: float, return_routing: bool = False, **sizes):
    """One block, of the kind its parameters are. ``sizes``: the three kinds' sizes together (each kind
    reads its own) and any of the knobs that make a wrong reference. ``return_routing``: also return
    ``(u, top_e, state)``, the router's input, the experts chosen (None for a block without experts) and
    the recurrent state after the last position (None for a block without one)."""
    u = _rms_norm(x, params["norm"]["scale"], rms_eps)
    kind, top_e, state = kind_of(params), None, None
    take = lambda names: {name: sizes[name] for name in names if name in sizes}
    if kind == "mamba":
        f, state = mamba(params, u, rms_eps=rms_eps, **take(MAMBA_SIZES + MAMBA_KNOBS))
    elif kind == "attention":
        f = attention(params, u, **take(ATTENTION_SIZES + ATTENTION_KNOBS))
    else:
        f, top_e = experts(params, u, **take(EXPERT_SIZES + EXPERT_KNOBS))
    y = x + f
    return (y, (u, top_e, state)) if return_routing else y


def _float32(params):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), params)


def span_with_routing(all_params, x, **sizes):
    """The blocks of ``all_params`` (a list of parameter trees) applied in order. Returns
    the output and each block's ``(u, top_e, state)`` (`block`'s ``return_routing``)."""
    with jax.default_matmul_precision("highest"):
        x, routing = x.astype(jnp.float32), []
        for params in all_params:
            x, routed = block(_float32(params), x, return_routing=True, **sizes)
            routing.append(routed)
        return x, routing


def span(all_params, x, **sizes):
    return span_with_routing(all_params, x, **sizes)[0]
