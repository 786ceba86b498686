"""What is left of the CPU-era round benchmark: ALBERT's FLOPs per token.

The benchmark is `perf/` (`BENCHMARK.json`, `python3 -m perf.run`), and its numbers
live in `PERF_LEDGER.jsonl`. `perf/flops.py` holds its own copy of this function, and
`tests/perf/test_perf_flops.py` holds that copy to this one. PR 29 removed everything
else this file had (the timing of a train step, the CPU child drivers and the peak
table, now `perf/peaks.json`) and could not touch `perf/` or `tests/perf/`: the
`benchmark` issue that may, drops the comparison and this file with it."""


def flops_per_token(config, seq_len: int, head_fraction: float = 1.0) -> float:
    """fwd+bwd FLOPs per token ~= 6 * (matmul params-equivalent per token).

    ``head_fraction``: the MLM head (transform + tied decoder) runs only on this
    fraction of positions when the train step uses the masked-only loss path
    (models/albert.py loss_masked_only) — count what actually executes."""
    h, i, L = config.hidden_size, config.intermediate_size, config.num_layers
    per_layer = 4 * h * h + 2 * h * i  # qkv+out projections + ffn (MACs per token)
    attention_quadratic = 2 * seq_len * h  # QK^T + PV MACs per token (x6 below -> FLOPs)
    head = h * config.embedding_size + config.embedding_size * config.vocab_size
    total_params_equiv = L * (per_layer + attention_quadratic) + head_fraction * head
    return 6.0 * total_params_equiv
