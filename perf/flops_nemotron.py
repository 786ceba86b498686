"""Operations, bytes and parameters of NVIDIA-Nemotron-3-Super-120B-A12B's blocks: the
yardstick's arithmetic for `ssm_step_roofline` and `moe_experts_roofline.latent`, and the
parameter counts that the configuration's cut is reckoned from (`model`: the
configuration's `model` section, the published keys).

Counted for a state-space step, one row (one session's one position) at one block: the
recurrent state read once and written once, the convolution window read and written, and
the decay, the rank-one update and the read of the state. Counted for the routed experts of
a LatentMoE layer: what the pairs that were routed HERE and the held experts that were hit
require, at the experts' own input width (the latent) and TWO matmuls an expert (non-gated).
Not counted: the projections, norms and gate around the mixer (the block's weights:
`decode_program_ms.ssm` holds them), the joining and splitting of the sessions' states
around a batched program, the latent projections, the shared expert and the router, rows a
grouped matmul's tile holds for another group, the sort and the gathers around the matmuls."""

from __future__ import annotations

from typing import Any, Dict


def mamba_inner(model: Dict[str, Any]) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def conv_channels(model: Dict[str, Any]) -> int:
    return mamba_inner(model) + 2 * model["n_groups"] * model["ssm_state_size"]


def ssm_row_state_bytes(model: Dict[str, Any], state_itemsize: int = 4, window_itemsize: int = 2) -> int:
    """What one session pins at one state-space block: the state ``[H, P, N]`` and the last ``K - 1`` rows of xBC."""
    state = model["mamba_num_heads"] * model["mamba_head_dim"] * model["ssm_state_size"] * state_itemsize
    return state + (model["conv_kernel"] - 1) * conv_channels(model) * window_itemsize


def ssm_step_flops(model: Dict[str, Any]) -> float:
    """Per row: decay and rank-one update of the state (2 a state element) and its product with C (2 a state element)."""
    return 4.0 * model["mamba_num_heads"] * model["mamba_head_dim"] * model["ssm_state_size"]


def ssm_step_bytes(rewritten: float) -> float:
    """``rewritten``: the bytes of state and window the steps rewrote (the program's own count,
    `hivemind_moe_ssm_state_bytes_total`): each is read once and written once."""
    return 2.0 * rewritten


def latent_expert_params(model: Dict[str, Any]) -> int:
    """One routed expert: up and down projections between the latent and its width, no gate, no bias."""
    return 2 * model["moe_latent_size"] * model["moe_intermediate_size"]


def latent_expert_layer_flops(pairs: float, model: Dict[str, Any]) -> float:
    """FLOPs of the two grouped matmuls for ``pairs`` routed (token, expert) pairs, 2 a multiply-accumulate."""
    return 2.0 * pairs * latent_expert_params(model)


def latent_expert_layer_bytes(experts_hit: float, pairs: float, model: Dict[str, Any], weight_itemsize: int = 4,
                              activation_itemsize: int = 4) -> float:
    """Bytes that must move once: the weights of every held expert that was hit, plus each pair's
    latent row in, its inner row (written, then read) and its latent row out."""
    weights = experts_hit * latent_expert_params(model) * weight_itemsize
    return weights + pairs * (2 * model["moe_latent_size"] + 2 * model["moe_intermediate_size"]) * activation_itemsize


def mamba_block_params(model: Dict[str, Any]) -> int:
    """W_in to [z | xBC | dt], the convolution's weights and bias, A_log, dt_bias, D, the gate norm's scale, W_out, the block's norm."""
    hidden, inner, channels, heads = model["hidden_size"], mamba_inner(model), conv_channels(model), model["mamba_num_heads"]
    return hidden * (inner + channels + heads) + (model["conv_kernel"] + 1) * channels + 3 * heads + inner + inner * hidden + hidden


def attention_block_params(model: Dict[str, Any]) -> int:
    hidden, width = model["hidden_size"], model["num_attention_heads"] * model["head_dim"]
    return hidden * (width + 2 * model["num_key_value_heads"] * model["head_dim"]) + width * hidden + hidden


def experts_block_params(model: Dict[str, Any], held: int, router_outputs: int) -> int:
    """Router and its bias, the two latent projections, the shared expert, the block's norm, and ``held`` routed experts."""
    hidden = model["hidden_size"]
    outside = (hidden * router_outputs + router_outputs + 2 * hidden * model["moe_latent_size"]
               + 2 * hidden * model["moe_shared_expert_intermediate_size"] + hidden)
    return outside + held * latent_expert_params(model)


def span_params(model: Dict[str, Any], pattern: str, held: int, router_outputs: int) -> int:
    """The blocks that ``pattern`` names (`M`, `*`, `E`), each `E` holding ``held`` routed experts."""
    each = {"M": mamba_block_params(model), "*": attention_block_params(model), "E": experts_block_params(model, held, router_outputs)}
    return sum(each[kind] for kind in pattern)


def model_params(model: Dict[str, Any], pattern: str, experts: int, per_token: bool = False) -> int:
    """The uncut model: every block of ``pattern``, embedding and head; ``per_token``: with the experts a token touches alone."""
    held = model["num_experts_per_tok"] if per_token else experts
    return span_params(model, pattern, held, experts) + 2 * model["vocab_size"] * model["hidden_size"]
