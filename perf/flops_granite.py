"""Parameters, state bytes and the least work of a batched decode step of granite-4.0-h-micro's
blocks: the yardstick's arithmetic for `ssm_step_roofline.granite` and `granite_block_roofline`,
and the counts that the configuration's cut is reckoned from (`model`: the configuration's
`model` section, the published keys of a `granitemoehybrid` config).

Every block is a mixer AND a gated MLP: a state-space block is 76,182,976 parameters (25.8 M of
mixer, 50.3 M of MLP), an attention block 60,821,504, and 36 + 4 of them with the tied
embedding and the final norm are the model's 3,191,396,096.

Counted for a state-space step, one row (one session's one position) at one block: the
recurrent state read once and written once, the convolution window read and written, and the
decay, the rank-one update and the read of the state (4 FLOPs a state element). Counted for a
state-space block's whole batched program (`jit_batched_step_ssm`): the block's parameters
read once AS THE ARRAYS LIE (the runner hands over their bytes by their dtypes: nothing here
assumes a width), the live rows' states and windows read once and written once, the rows'
hidden states in and out; 2 FLOPs a matmul weight a row beside the steps'. It is a LOWER bound
of what the program moves: padding rows' states and every intermediate are left out, so a
share over 100 % would be a fault of the count."""

from __future__ import annotations

from typing import Any, Dict, Sequence

MAMBA, ATTENTION = "mamba", "attention"


def head_dim(model: Dict[str, Any]) -> int:
    """config.json has no `head_dim`: hidden / heads, 64."""
    return model["hidden_size"] // model["num_attention_heads"]


def mamba_inner(model: Dict[str, Any]) -> int:
    return model["mamba_n_heads"] * model["mamba_d_head"]


def conv_channels(model: Dict[str, Any]) -> int:
    return mamba_inner(model) + 2 * model["mamba_n_groups"] * model["mamba_d_state"]


def mlp_params(model: Dict[str, Any]) -> int:
    """W_in to [g | v] and W_out, no bias: 50,331,648."""
    return 3 * model["hidden_size"] * model["shared_intermediate_size"]


def mamba_mixer_params(model: Dict[str, Any]) -> int:
    """W_in to [z | xBC | dt], the convolution's weights and bias, A_log, dt_bias, D, the gated norm's scale, W_o: 25,847,232."""
    hidden, inner, channels, heads = model["hidden_size"], mamba_inner(model), conv_channels(model), model["mamba_n_heads"]
    return hidden * (inner + channels + heads) + (model["mamba_d_conv"] + 1) * channels + 3 * heads + inner + inner * hidden


def attention_mixer_params(model: Dict[str, Any]) -> int:
    """q and o at heads x head_dim, k and v at kv_heads x head_dim, no bias: 10,485,760."""
    hidden, width = model["hidden_size"], model["num_attention_heads"] * head_dim(model)
    return 2 * hidden * width + 2 * hidden * model["num_key_value_heads"] * head_dim(model)


def block_params(model: Dict[str, Any], kind: str) -> int:
    """A mixer of ``kind``, the MLP and the block's two norm scales: 76,182,976 (`mamba`), 60,821,504 (`attention`)."""
    mixer = {MAMBA: mamba_mixer_params, ATTENTION: attention_mixer_params}[kind](model)
    return mixer + mlp_params(model) + 2 * model["hidden_size"]


def span_params(model: Dict[str, Any], layer_types: Sequence[str]) -> int:
    return sum(block_params(model, kind) for kind in layer_types)


def model_params(model: Dict[str, Any], layer_types: Sequence[str]) -> int:
    """Every block of ``layer_types``, the embedding (tied to the head: counted once) and the final norm."""
    assert model["tie_word_embeddings"], "an untied head is one more vocabulary x hidden"
    return span_params(model, layer_types) + model["vocab_size"] * model["hidden_size"] + model["hidden_size"]


def ssm_row_state_bytes(model: Dict[str, Any], state_itemsize: int = 4, window_itemsize: int = 2) -> int:
    """What one session pins at one state-space block: the state ``[H, P, N]`` and the last ``K - 1`` rows of xBC: 2,123,264."""
    state = mamba_inner(model) * model["mamba_d_state"] * state_itemsize
    return state + (model["mamba_d_conv"] - 1) * conv_channels(model) * window_itemsize


def kv_position_bytes(model: Dict[str, Any], cache_itemsize: int = 2) -> int:
    """A cached position at one attention block: a key and a value for every KV head, bf16: 2,048 B."""
    return 2 * model["num_key_value_heads"] * head_dim(model) * cache_itemsize


def session_bytes(model: Dict[str, Any], layer_types: Sequence[str], max_len: int) -> int:
    """What one session pins across the span: a state and a window a state-space block, ``max_len`` slots an attention block."""
    each = {MAMBA: ssm_row_state_bytes(model), ATTENTION: max_len * kv_position_bytes(model)}
    return sum(each[kind] for kind in layer_types)


def ssm_step_flops(model: Dict[str, Any]) -> float:
    """Per row: decay and rank-one update of the state (2 a state element) and its product with C (2 a state element)."""
    return 4.0 * mamba_inner(model) * model["mamba_d_state"]


def ssm_step_bytes(rewritten: float) -> float:
    """``rewritten``: the bytes of state and window the steps rewrote (the program's own count,
    `hivemind_moe_ssm_state_bytes_total`): each is read once and written once."""
    return 2.0 * rewritten


def ssm_program_bytes(programs: float, rows: float, rewritten: float, param_bytes: float, model: Dict[str, Any],
                      activation_itemsize: int = 4) -> float:
    """Least bytes of ``programs`` batched state-space programs that held ``rows`` live rows in all and rewrote
    ``rewritten`` bytes of state and window: ``param_bytes`` (one block's parameters as its arrays lie) once a
    program, the states and windows in and out, a row's hidden state in and out."""
    return programs * param_bytes + ssm_step_bytes(rewritten) + rows * 2 * model["hidden_size"] * activation_itemsize


def ssm_program_flops(rows: float, model: Dict[str, Any]) -> float:
    """2 a matmul weight a row (the mixer's two projections and the MLP's two), and the state's step."""
    hidden, inner = model["hidden_size"], mamba_inner(model)
    matmuls = hidden * (inner + conv_channels(model) + model["mamba_n_heads"]) + inner * hidden + mlp_params(model)
    return rows * (2.0 * matmuls + ssm_step_flops(model))
