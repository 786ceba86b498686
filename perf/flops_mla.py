"""Operations and bytes of multi-head latent attention's absorbed decode step — the
yardstick's arithmetic for `latent_attend_roofline`.

The work of this kernel grows with the context, so it is counted from what the steps
attended and not from the model's sizes alone: ``positions`` is the sum, over the rows of
the batched programs, of the positions each row's query attended (the program's counter
`hivemind_moe_latent_positions_attended_total`), ``rows`` the number of those rows.

Counted: what the algorithm requires. A position's latent and shared key (``kv_lora_rank +
qk_rope_head_dim`` values) are read ONCE for all heads; every head's absorbed query scores
it (2 x width FLOPs) and every head's mix takes its latent (2 x rank FLOPs). Not counted:
the absorption of `W_kvb` into the query and onto the output and every projection around
it (the block's weights: `decode_program_ms.latent` holds them), the write of the new
position, the copy of a row's array that a batched program makes of an argument it may
not donate, a slot past the session's end that a program reads and masks."""

from __future__ import annotations

from typing import Any, Dict


def latent_width(model: Dict[str, Any]) -> int:
    """Values a position keeps: the latent beside the shared rotated key (576)."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def latent_attend_flops(positions: float, model: Dict[str, Any]) -> float:
    """Scores over the whole width and the mix over the latent, every head: 64 x (576 + 512) x 2 a position."""
    return 2.0 * positions * model["num_attention_heads"] * (latent_width(model) + model["kv_lora_rank"])


def latent_attend_bytes(positions: float, rows: float, model: Dict[str, Any], cache_itemsize: int = 2,
                        activation_itemsize: int = 2) -> float:
    """Each attended position read once (1,152 B), and per row the absorbed queries in
    (heads x width) and the mixed latents out (heads x rank)."""
    a_row = model["num_attention_heads"] * (latent_width(model) + model["kv_lora_rank"]) * activation_itemsize
    return positions * latent_width(model) * cache_itemsize + rows * a_row
