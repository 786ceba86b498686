"""What `granite-4.0-h-micro-span20` brings to the benchmark: its configuration file against the catalog's
row, the parameter counts its cut is reckoned from (76,182,976 / 60,821,504 / 3,191,396,096), the plain
reference against the same equations written another way (per position and head, in numpy), the runner's
block kwargs, each wrong program of the check refused at rehearsal size, the traffic's schedule,
`flops_granite` against hand counts, the new reader on hand-made observations (and on a program that lacks
what it reads), the scopes read off a compiled program's text, and the cell's rehearsal end to end (CPU)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import flops_granite  # noqa: E402
from perf import manifest as mf  # noqa: E402
from perf.reference import granite_h_block as reference  # noqa: E402
from perf.runners import granite_block_server as runner  # noqa: E402
from perf.runners import sala_block_server as sala_runner  # noqa: E402
from perf.traffic import long_sessions  # noqa: E402

NAME = "granite-4.0-h-micro-span20"
CONFIG = mf.load_json(mf.PERF / "configs" / f"{NAME}.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = f"{NAME}.longctx32"
WORKLOAD = mf.load_workload(CELL)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_KEYS = [key for key in CONFIG if key not in ("name", "source", "runner")][: list(CONFIG).index("catalog_keys") - 3]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
REDUCED = {"num_hidden_layers": (20, 40), "layer_types": (PERIOD * 2, PERIOD * 4)}
# the widths the issue names, as published
WIDTHS = {"hidden_size": 2048, "intermediate_size": 8192, "shared_intermediate_size": 8192, "mamba_n_heads": 64, "mamba_d_head": 64,
          "mamba_n_groups": 1, "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_chunk_size": 256, "mamba_expand": 2, "num_attention_heads": 32,
          "num_key_value_heads": 8, "attention_multiplier": 0.015625, "residual_multiplier": 0.22, "embedding_multiplier": 12, "logits_scaling": 8,
          "rms_norm_eps": 1e-05, "num_local_experts": 0, "num_experts_per_tok": 0, "position_embedding_type": "nope", "mamba_conv_bias": True,
          "tie_word_embeddings": True, "vocab_size": 100352, "max_position_embeddings": 131072, "model_type": "granitemoehybrid"}
TOY = dict(rms_eps=1e-5, residual_multiplier=0.22, mamba_heads=4, mamba_head_dim=3, ssm_groups=1, ssm_state=5, num_heads=4, num_kv_heads=2,
           head_dim=4, attention_multiplier=0.1)


def _catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    found = [row for row in rows if row["name"] == "granite-4.0-h-micro"]
    if not found:
        pytest.skip("the catalog on this machine has no granite-4.0-h-micro row")
    return found[0]


@pytest.mark.parametrize("key", PUBLISHED_KEYS)
def test_configuration_holds_every_published_value(key):
    """Every key of the catalog row's config, at the top level of the file and in the `model` section the
    runner reads, unchanged except for the two cuts `reduced` lists."""
    assert CONFIG[key] == CONFIG["model"][key]
    if key in REDUCED:
        assert key in CONFIG["reduced"] and (CONFIG[key], CONFIG["published"][key]) == REDUCED[key] and key in CONFIG["reduced_why"]
        return
    if key in WIDTHS:
        assert CONFIG[key] == WIDTHS[key] and type(CONFIG[key]) is type(WIDTHS[key])
    row = _catalog_row()  # skips, and does not fail, where the catalog or the row is not there
    assert CONFIG[key] == row["config"][key] and type(CONFIG[key]) is type(row["config"][key])


def test_configuration_has_every_key_of_the_catalog_row_and_its_sections():
    assert all(section in CONFIG for section in ("source", "reduced", "reduced_why", "assumed", "published", "deployment", "tolerances",
                                                 "rehearsal", "serving", "model"))
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED) and len(PUBLISHED_KEYS) == 33 and set(WIDTHS) <= set(PUBLISHED_KEYS)
    assert not set(CONFIG["reduced"]) & {key for key in PUBLISHED_KEYS if key.endswith(("_dim", "_rank", "_size", "_head", "_state", "_expand"))}  # no width is cut
    assert all(name in CONFIG["assumed"] for name in ("head_dim", "block", "attention_scale", "no_position_embedding", "mamba_mixer",
                                                      "seeded_ssm_weights", "ssm_state_dtype", "client_side", "recorded_not_read", "param_dtype"))
    assert all(name in CONFIG["tolerances"] for name in ("decode_rel", "decode_rms_rel", "first_state_rms_rel", "departure_share", "why"))
    serving = CONFIG["serving"]
    assert (serving["expert_cls"], serving["decode_max_len"], serving["prompt_chunk"], serving["activation_compression"], serving["max_batch_size"],
            serving["param_dtype"]) == ("granite_h_block", 12288, 2048, "float16", 4, "float32") and serving["decode_max_sessions"] == 80 * 20
    assert "FIRST server" in CONFIG["deployment"] and "two servers" in CONFIG["deployment"]
    row = _catalog_row()
    assert set(row["config"]) == set(PUBLISHED_KEYS) and CONFIG["source"] == row["source_url"] and row["head_dim"] is None


def test_the_span_is_two_whole_periods_of_the_published_model():
    assert CONFIG["model"]["first_block"] == 0 and CONFIG["published"]["layer_types"][:20] == CONFIG["layer_types"] == PERIOD * 2
    assert runner.kinds(CONFIG) == PERIOD * 2 and (runner.kinds(CONFIG).count("mamba"), runner.kinds(CONFIG).count("attention")) == (18, 2)
    published = CONFIG["published"]["layer_types"]
    assert (published.count("mamba"), published.count("attention"), len(published)) == (36, 4, 40)
    mixer = runner.block_kwargs(CONFIG, 0)
    assert (mixer["kind"], mixer["mamba_heads"], mixer["mamba_head_dim"], mixer["ssm_groups"], mixer["ssm_state"], mixer["conv_kernel"],
            mixer["chunk_size"], mixer["rms_eps"], mixer["ffn_inner"], mixer["residual_multiplier"]) == ("mamba", 64, 64, 1, 128, 4, 256, 1e-5, 8192, 0.22)
    attention = runner.block_kwargs(CONFIG, 5)
    assert (attention["kind"], attention["num_heads"], attention["num_kv_heads"], attention["head_dim"], attention["attention_multiplier"]) == (
        "attention", 32, 8, 64, 0.015625) and runner.block_kwargs(CONFIG, 15)["kind"] == "attention"
    assert attention["attention_multiplier"] * attention["head_dim"] ** 0.5 == 0.125  # what the served queries are scaled by: a power of two
    toy = runner.block_kwargs(REHEARSAL, 5)
    assert (toy["num_heads"], toy["num_kv_heads"], toy["head_dim"], toy["attention_multiplier"], toy["ffn_inner"]) == (4, 2, 16, 0.03125, 96)
    sizes = runner.reference_sizes(CONFIG)
    assert (sizes["residual_multiplier"], sizes["attention_multiplier"], sizes["ssm_groups"], sizes["num_kv_heads"], sizes["head_dim"]) == (0.22, 0.015625, 1, 8, 64)
    assert [runner.cohort_rows(slots) for slots in (4, 16, 32, 40, 64)] == [4, 16, 16, 32, 32]  # the largest batched program of a traffic


def test_parameter_counts_are_the_issues_table_and_the_rows_3b():
    """From the shapes: a state-space block 76,182,976 (mixer 25,847,232 + MLP 50,331,648 + two norms), an attention block
    60,821,504, the span 1,492.9 M = 5.97 GB at 4 bytes, a session 88.6 MB, and the uncut model 3,191,396,096: the row's "3B"."""
    model, published = CONFIG["model"], CONFIG["published"]
    assert (flops_granite.mamba_mixer_params(model), flops_granite.mlp_params(model), flops_granite.attention_mixer_params(model)) == (
        25_847_232, 50_331_648, 10_485_760)
    assert flops_granite.block_params(model, "mamba") == 76_182_976 and flops_granite.block_params(model, "attention") == 60_821_504
    span = flops_granite.span_params(model, model["layer_types"])
    assert span == 18 * 76_182_976 + 2 * 60_821_504 and round(span * 4 / 1e9, 2) == 5.97
    assert flops_granite.model_params(model, published["layer_types"]) == 3_191_396_096
    # the served block's own parameter trees, by their shapes, are what the functions count
    from hivemind_tpu.moe.server.layers import name_to_block

    for index, count in ((0, 76_182_976), (5, 60_821_504)):
        module = name_to_block["granite_h_block"](model["hidden_size"], **runner.block_kwargs(CONFIG, index))
        shapes = jax.eval_shape(lambda module=module: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, model["hidden_size"]), jnp.float32))["params"])
        assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)) == count
        cache = jax.eval_shape(lambda module=module: module.init_decode_cache(1, 12288))
        wanted = flops_granite.ssm_row_state_bytes(model) if index == 0 else 12288 * flops_granite.kv_position_bytes(model)
        assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in cache) == wanted
    assert flops_granite.ssm_row_state_bytes(model) == 2_097_152 + 26_112 and flops_granite.kv_position_bytes(model) == 2048
    session = flops_granite.session_bytes(model, model["layer_types"], CONFIG["serving"]["decode_max_len"])
    assert session == 18 * 2_123_264 + 2 * 12288 * 2048 == 88_550_400 and round(64 * session / 1e9, 2) == 5.67


def test_benchmark_lists_the_cell_and_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.by_name(manifest["workloads"], CELL, "cell")
    assert cell == {"name": CELL, "config": NAME, "traffic": "longctx32", "chips": 1, "why": WORKLOAD["why"]} and len(WORKLOAD["why"]) <= 200
    entry = mf.by_name(manifest["configs"], NAME, "configuration")
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"] and entry["file"] == f"perf/configs/{NAME}.json"
    reported = {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "per_layer")}
    new = {"ssm_step_roofline.granite", "granite_block_roofline", "ssm_mixer_share_of_program"}
    appended = {"server_handle_ms.decode", "rpc_overhead_ms.decode", "queue_wait_ms.decode", "decode_batched_share", "transfer_kb_per_token.decode",
                "decode_assemble_ms", "decode_step_ms", "decode_scatter_ms", "decode_rows_per_batch", "idle_host_dispatch_share.serve",
                "idle_unlabelled_share.serve", "device_idle_share.serve", "hbm_peak_gb.serve", "decode_program_ms.full", "decode_program_ms.ssm",
                "decode_cache_mb_per_session.full", "decode_cache_mb_per_session.ssm", "prefill_ms_per_1k_positions.ssm",
                "decode_wire_cohort_share", "serialize_ms.decode", "deserialize_ms.decode"}
    assert reported == new | appended
    for name in reported:
        assert mf.load_layer_metric(name)["name"] == name
    for name in new:  # a later cell may be appended to any of these lists
        entry = mf.by_name(manifest["per_layer"], name, "metric")
        assert CELL in entry["workloads"] and entry["moves"] == "decode_tokens_per_s" and entry["unit"] == "%" and entry["source"] == "device_trace"
        assert mf.load_layer_metric(name)["reader"] == "granite_ssm"
    assert {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s"} == {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "end_to_end")}


# ---- the reference, against the same equations written another way ------------------


def _toy_params(seed: int, kind: str, hidden=12, inner_mlp=7):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2] if len(shape) > 1 else 1.0), jnp.float32)
    scale = lambda n: jnp.asarray(1.0 + 0.1 * rng.standard_normal(n), jnp.float32)
    params = {"norm": {"scale": scale(hidden)}, "mlp_norm": {"scale": scale(hidden)}, "mlp_in": {"kernel": draw(hidden, 2 * inner_mlp)},
              "mlp_out": {"kernel": draw(inner_mlp, hidden)}}
    heads, dim, state = TOY["mamba_heads"], TOY["mamba_head_dim"], TOY["ssm_state"]
    inner, channels = heads * dim, heads * dim + 2 * state
    if kind == "mamba":
        params.update(in_proj={"kernel": draw(hidden, inner + channels + heads)}, conv_weight=draw(4, channels), conv_bias=draw(channels) / 2,
                      A_log=jnp.log(jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)), dt_bias=draw(heads) - 2.0,
                      D=scale(heads), gate_norm=scale(inner), out_proj={"kernel": draw(inner, hidden)})
    else:
        q, kv = TOY["num_heads"] * TOY["head_dim"], TOY["num_kv_heads"] * TOY["head_dim"]
        params.update(query={"kernel": draw(hidden, q) * 3}, key={"kernel": draw(hidden, kv) * 3}, value={"kernel": draw(hidden, kv)},
                      attention_out={"kernel": draw(q, hidden)})
    return params


def _by_hand_tail(p, h):
    """``y = h + r * W_out (silu(g) * v)`` for one position, in numpy."""
    silu = lambda t: t / (1.0 + np.exp(-t))
    u = h / np.sqrt((h ** 2).mean() + 1e-5) * p["mlp_norm"]["scale"]
    both = u @ p["mlp_in"]["kernel"]
    return h + 0.22 * ((silu(both[:7]) * both[7:]) @ p["mlp_out"]["kernel"])


def test_a_state_space_block_equals_a_loop_over_positions_and_heads():
    """`reference.block` on a mixer's tree against numpy loops: per position the convolution WITH its bias from its four inputs,
    one group's B and C read by every head, the state's decay, update and read, the skip term, the gate before ONE norm over all the
    inner values, the residual x 0.22, then the MLP (the gate half first) under the second residual x 0.22."""
    params = _toy_params(1, "mamba")
    x = np.random.default_rng(2).standard_normal((1, 9, 12)).astype(np.float32)
    got, last = reference.block(params, jnp.asarray(x), return_state=True, **TOY)
    p = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    heads, dim, width = 4, 3, 5
    inner = heads * dim
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5) * p["norm"]["scale"]
    projected = u @ p["in_proj"]["kernel"]
    z, xbc, dt = projected[:, :inner], projected[:, inner:inner + inner + 2 * width], projected[:, -heads:]
    silu = lambda t: t / (1.0 + np.exp(-t))
    state, want = np.zeros((heads, dim, width)), np.zeros((9, 12))
    for t in range(9):
        mixed = silu(p["conv_bias"] + sum(p["conv_weight"][j] * (xbc[t - 3 + j] if t - 3 + j >= 0 else 0.0) for j in range(4)))
        step = np.log1p(np.exp(dt[t] + p["dt_bias"]))
        b, c = mixed[inner:inner + width], mixed[inner + width:]
        y = np.zeros((heads, dim))
        for h in range(heads):
            xs = mixed[h * dim:(h + 1) * dim]
            state[h] = np.exp(-step[h] * np.exp(p["A_log"][h])) * state[h] + step[h] * np.outer(xs, b)
            y[h] = state[h] @ c + p["D"][h] * xs
        gated = y.reshape(-1) * silu(z[t])
        gated = gated / np.sqrt((gated ** 2).mean() + 1e-5) * p["gate_norm"]
        want[t] = _by_hand_tail(p, x[0, t] + 0.22 * (gated @ p["out_proj"]["kernel"]))
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(last)[0], state, rtol=2e-4, atol=2e-4)


def test_an_attention_block_equals_a_loop_over_positions_and_heads():
    params = _toy_params(3, "attention")
    x = np.random.default_rng(4).standard_normal((1, 11, 12)).astype(np.float32)
    got, last = reference.block(params, jnp.asarray(x), return_state=True, query_block=4, **TOY)
    p = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5) * p["norm"]["scale"]
    q, k, v = (u @ p[name]["kernel"] for name in ("query", "key", "value"))
    want = np.zeros((11, 12))
    for t in range(11):
        context = np.zeros(16)
        for h in range(4):
            kv = h // 2  # two query heads a key-value head
            scores = np.array([q[t, h * 4:(h + 1) * 4] @ k[s, kv * 4:(kv + 1) * 4] for s in range(t + 1)]) * 0.1  # the multiplier, no position embedding
            weights = np.exp(scores - scores.max())
            context[h * 4:(h + 1) * 4] = (weights / weights.sum()) @ v[:t + 1, kv * 4:(kv + 1) * 4]
        want[t] = _by_hand_tail(p, x[0, t] + 0.22 * (context @ p["attention_out"]["kernel"]))
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4, atol=2e-4)
    assert last is None
    rooted = np.asarray(reference.block(params, jnp.asarray(x), root_scale=True, **TOY))  # 4 ** -0.5 = 0.5 where the model says 0.1
    assert float(np.abs(rooted - np.asarray(got)).max()) > 1e-2


def _toy_span(seed: int = 7):
    return [_toy_params(seed + at, kind) for at, kind in enumerate(("mamba", "mamba", "attention", "mamba"))]


WRONG = runner.wrong_references()


def test_the_wrong_references_are_the_issues_nine():
    assert len(WRONG) == 9 and set(runner.EVERY_RUN) <= set(WRONG) and set(runner.wrong_references(every=False)) == set(runner.EVERY_RUN)
    assert [name for name, (_variant, told) in WRONG.items() if told == "dtype"] == ["the state kept in bf16"]
    assert [name for name, (_variant, told) in WRONG.items() if told == runner.PADDING] == ["padding that decays and feeds the state"]
    knobs = {knob for variant, _told in WRONG.values() for knob in variant}
    assert knobs == {"state_dtype", "rope", "root_scale", "residual_multiplier", "norm_before_gate", "conv_bias", "halves_swapped", "skip", "padding"}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_program_is_refused_at_rehearsal_size(name):
    """A program that computed the wrong reference would hand its output over as the served one: the check's
    measures must then say `correct: false`: a plain limit is passed, or the served output holds the whole of
    the wrong reference's departure (`_departure_share` reads 1 where it reads about 0 for the model). The
    state in bf16 is refused by the dtype of what the served sessions hold."""
    variant, told = WRONG[name]
    params = _toy_span()
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, 48, 12)), jnp.float32)
    if told == runner.PADDING:
        variant = dict(padding=(variant["padding"], 30, 2))
    want, states = runner.reference_span(params, x, TOY)
    out, _wrong_states = runner.reference_span(params, x, TOY, **variant)
    want, out = np.asarray(want), np.asarray(out)
    assert [state is None for state in states] == [False, False, True, False] and float(np.abs(out - want).max()) > 0
    served_right = want + 1e-3 * np.random.default_rng(9).standard_normal(want.shape).astype(np.float32)  # the model, and a rounding's noise
    served_wrong = out + 1e-3 * np.random.default_rng(9).standard_normal(want.shape).astype(np.float32)  # the wrong program
    tolerances = REHEARSAL["tolerances"]
    readings = lambda got: {"decode_rel": float(np.abs(got - want).max() / np.abs(want).max()), "decode_rms_rel": runner._rms_err(got, want)}
    assert not runner.judge(readings(served_right), tolerances) and abs(runner._departure_share([(served_right, want, out)])) <= tolerances["departure_share"]
    if told == "dtype":  # no limit tells it, on the chip either: the check reads the dtype of what the served sessions hold
        assert runner.state_dtype_faults([np.zeros((1, 4, 3, 5), np.float32), jnp.zeros((1, 4, 3, 5), jnp.bfloat16)]) == [
            "the mixers keep their recurrent state in ['bfloat16', 'float32'], not in float32"]
        assert runner.state_dtype_faults([np.zeros((1, 4, 3, 5), np.float32)] * 2) == []
        return
    refused = bool(runner.judge(readings(served_wrong), tolerances)) or abs(runner._departure_share([(served_wrong, want, out)])) > tolerances["departure_share"]
    assert refused, name
    assert abs(runner._departure_share([(served_wrong, want, out)])) > 0.9


def test_the_reference_hands_back_the_mixers_last_states_stream_by_stream():
    params = _toy_span()
    x = np.random.default_rng(8).standard_normal((2, 20, 12)).astype(np.float32)
    out, states = runner._by_stream(lambda rows: runner.reference_span(params, jnp.asarray(rows), TOY), x)
    assert out.shape == (2, 20, 12) and [state is None for state in states] == [False, False, True, False] and states[0].shape == (2, 4, 3, 5)
    assert len(runner._mixer_states(states)) == 3
    np.testing.assert_allclose(out, np.asarray(reference.span(params, jnp.asarray(x), **TOY)), rtol=1e-5, atol=1e-5)


# ---- traffic, arithmetic and readers --------------------------------------------------


def test_long_sessions_deals_the_cells_prompts():
    """Nemotron's traffic letter for letter (the issue's lever pulled: `slots_per_process` 16 -> 8, named in the cell's `why`)."""
    traffic = WORKLOAD["traffic"]
    assert (traffic["generator"], traffic["processes"], traffic["slots_per_process"], traffic["chunk"], traffic["answer_cap"], traffic["trace_seconds"]) == (
        "long_sessions", 4, 8, 2048, 4096, 4.0) and "lever" in WORKLOAD["why"]
    assert traffic["prompt_lengths"] == [2048, 4096, 6144, 8192] and traffic["prompt_weights"] == [0.4, 0.3, 0.2, 0.1]
    assert sorted(long_sessions.sizes(traffic)) == [2048] * 13 + [4096] * 10 + [6144] * 6 + [8192] * 3
    assert sum(long_sessions.sizes(traffic)) == 129024 and sum(long_sessions.sizes(traffic)) * 2048 * 2 == pytest.approx(0.53e9, rel=0.01)
    at_64 = {**traffic, "slots_per_process": 16}  # the issue's first size, measured and reported beside this one (PERF.md section 6)
    assert sorted(long_sessions.sizes(at_64)) == [2048] * 26 + [4096] * 19 + [6144] * 13 + [8192] * 6 and sum(long_sessions.sizes(at_64)) == 260096
    assert max(traffic["prompt_lengths"]) + traffic["answer_cap"] == CONFIG["serving"]["decode_max_len"] == 12288
    assert traffic["chunk"] == CONFIG["serving"]["prompt_chunk"]
    prompt, steps, rows = runner.check_shape(False)
    assert (prompt, steps, rows) == (4096, 192, 8) and runner.check_prompts(prompt, rows)[1] % 2048 not in (0, 1)  # row 1's last chunk comes padded
    assert sala_runner.padded_chunks(traffic["prompt_lengths"] + runner.check_prompts(prompt, rows) + [runner.filler_prompt(prompt, 2048)], 2048) == [512, 2048]
    slots = traffic["processes"] * traffic["slots_per_process"]
    assert sala_runner.check_widths(rows, runner.cohort_rows(slots)) == [8, 16]  # the buckets the window's cohorts can run
    assert sala_runner.check_widths(rows, runner.cohort_rows(64)) == [8, 16, 32]
    same = {key: value for key, value in mf.load_workload("nemotron-3-super-120b-span11.longctx32")["traffic"].items() if key != "lead_seconds"}
    assert {key: value for key, value in traffic.items() if key != "lead_seconds"} == same


def test_granite_arithmetic_by_hand():
    model = CONFIG["model"]
    assert flops_granite.ssm_step_flops(model) == 4 * 64 * 64 * 128  # 2.1 MFLOP a row
    assert flops_granite.ssm_step_bytes(2_123_264.0) == 4_246_528.0  # read once, written once
    assert flops_granite.conv_channels(model) == 4352 and flops_granite.mamba_inner(model) == 4096 and flops_granite.head_dim(model) == 64
    # one program of 32 live rows: 304.7 MB of float32 parameters, 135.9 MB of states in and out, 0.5 MB of hidden states
    least = flops_granite.ssm_program_bytes(1.0, 32.0, 32 * 2_123_264.0, 4 * 76_182_976.0, model)
    assert least == 4 * 76_182_976 + 2 * 32 * 2_123_264 + 32 * 2 * 2048 * 4 and least / 819e9 == pytest.approx(538.6e-6, rel=1e-3)
    assert flops_granite.ssm_program_flops(32.0, model) / 197e12 < 0.1 * least / 819e9  # memory-bound on a v5e by a wide margin
    # the parameters' bytes are an ARGUMENT: in bf16 the same program's least bytes fall, and nothing here assumes a width
    assert flops_granite.ssm_program_bytes(1.0, 32.0, 32 * 2_123_264.0, 2 * 76_182_976.0, model) == least - 2 * 76_182_976


def _observations(**extra):
    series = lambda **values: {"series": values}
    calls, steps, rewritten = "hivemind_moe_decode_calls_total", "hivemind_moe_decode_steps_total", "hivemind_moe_ssm_state_bytes_total"
    before = {calls: series(**{"path=batched": 20.0}), steps: series(**{"path=batched": 640.0}), rewritten: series(**{"path=batched": 1e6})}
    after = {calls: series(**{"path=batched": 20.0 + 2000}), steps: series(**{"path=batched": 640.0 + 64000}),
             rewritten: series(**{"path=batched": 1e6 + 1800 * 32 * 2_123_264.0, "path=direct": 7.0})}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters": {"before": before, "after": after}, **extra}


def test_the_three_new_metrics_on_a_recorded_observation():
    """100 cohorts of 32 rows over 20 blocks between the trace's edges: 1,800 state-space programs counted; 1,728 traced."""
    obs = _observations()
    scopes = {"ssm_step": {"seconds": 0.400, "count": 9000.0, "runs": 1728.0}, "ssm_conv": {"seconds": 0.020, "count": 3000.0, "runs": 1728.0},
              "shared_mlp": {"seconds": 0.9, "count": 9000.0, "runs": 1920.0}}
    programs = {"jit_batched_step_ssm": {"seconds": 1.728, "count": 1728.0}, "jit_batched_step_full": {"seconds": 0.2, "count": 192.0}}
    traced = dict(obs, scopes=scopes, programs=programs, counters_traced=obs["counters"], param_bytes={"ssm": 4 * 76_182_976.0, "full": 4 * 60_821_504.0})
    step, block, share = (mf.load_layer_metric(name) for name in ("ssm_step_roofline.granite", "granite_block_roofline", "ssm_mixer_share_of_program"))
    value = mf.read_metric(step, traced)
    least = 2 * 1800 * 32 * 2_123_264.0 / 819e9  # memory-bound
    assert value == pytest.approx(100.0 * least / 1800 * 1728 / 0.400, rel=1e-6) and 0 < value < 100
    assert any("memory-bound" in note and "32.0 rows a program" in note for note in traced["notes"])
    staged = dict(traced, scopes={**scopes, "ssm_staging": {"seconds": 0.600, "count": 55296.0, "runs": 1728.0}})
    assert mf.read_metric(step, staged) == pytest.approx(value / 2.5, rel=1e-6)
    whole = mf.read_metric(block, traced)
    assert whole == pytest.approx(100.0 * (4 * 76_182_976 + 2 * 32 * 2_123_264 + 32 * 2 * 2048 * 4) / 819e9 / 1e-3, rel=1e-6) and 50 < whole < 60
    halved = mf.read_metric(block, dict(traced, param_bytes={"ssm": 2 * 76_182_976.0}))  # bf16 weights: fewer bytes needed, never over 100 by assumption
    assert halved < whole and any("304.7 MB of parameters as they lie" in note for note in traced["notes"])
    assert mf.read_metric(share, traced) == pytest.approx(100.0 * 0.420 / 1.728) and mf.read_metric(share, staged) == pytest.approx(100.0 * 1.020 / 1.728)
    # what each needs, and nothing where it is not there (a parent commit's program, a runner that hands less over)
    for spec, missing in ((step, "scopes"), (step, "counters_traced"), (block, "programs"), (block, "param_bytes"), (block, "counters_traced"),
                          (share, "scopes"), (share, "programs")):
        assert mf.read_metric(spec, {key: value for key, value in traced.items() if key != missing}) is None, (spec["name"], missing)
    older = json.loads(json.dumps(obs["counters"]))  # a program without the counter: nothing, and no exception
    for side in older.values():
        del side["hivemind_moe_ssm_state_bytes_total"]
    assert mf.read_metric(step, dict(traced, counters_traced=older)) is None and mf.read_metric(block, dict(traced, counters_traced=older)) is None
    with pytest.raises(ValueError, match="unknown measure"):
        mf.read_metric({"reader": "granite_ssm", "args": {"measure": "else"}}, traced)


def test_the_accepted_readers_read_this_cell():
    obs = _observations()
    obs["counters"]["after"].update({"hivemind_moe_decode_cache_bytes": {"series": {"kind=ssm": 64 * 18 * 2_123_264.0, "kind=full": 64 * 2 * 12288 * 2048.0}},
                                     "hivemind_moe_decode_cache_entries": {"series": {"kind=ssm": 64 * 18.0, "kind=full": 64 * 2.0}}})
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.ssm"), obs) == pytest.approx(2.123264)
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.full"), obs) == pytest.approx(25.165824)  # the physical bytes: unpadded
    programs = {"jit_batched_step_ssm": {"seconds": 0.9, "count": 1800.0}, "jit_batched_step_full": {"seconds": 0.3, "count": 200.0}}
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.ssm"), {"programs": programs}) == pytest.approx(0.5)
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.full"), {"programs": programs}) == pytest.approx(1.5)


def test_scopes_are_read_off_the_batched_programs():
    """The toy blocks' own batched programs at a bucket of two: a mixer's operations lie in `ssm_conv`, `ssm_step` and
    `shared_mlp`, an attention block's in `nope_attend` and `shared_mlp`, and a mixer's chunk program holds `ssm_scan`."""
    from hivemind_tpu.moe.server.layers import name_to_block
    from perf.runners.nemotron_block_server import instruction_scopes

    hidden = REHEARSAL["model"]["hidden_size"]
    found = {}
    for index in (0, 5):
        module = name_to_block["granite_h_block"](hidden, **runner.block_kwargs(REHEARSAL, index))
        params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden)))["params"]
        cache = module.init_decode_cache(2, 128)
        step = jax.jit(lambda p, x, cache, *rest, module=module: module.apply({"params": p}, x, *cache, *rest))
        text = step.lower(params, jnp.zeros((2, 1, hidden)), cache, jnp.array([70, 90])).compile().as_text()
        found[module.kind] = {**instruction_scopes(text), **sala_runner.scope_of_instructions(text, runner.SCOPES)}
        if module.kind == "mamba":
            one = module.init_decode_cache(1, 128)
            chunk = step.lower(params, jnp.zeros((1, 32, hidden)), one, jnp.int32(64), jnp.int32(20)).compile().as_text()
            assert {"ssm_scan", "shared_mlp"} <= set(instruction_scopes(chunk).values()) | set(sala_runner.scope_of_instructions(chunk, runner.SCOPES).values())
    assert {scope for scope in found["mamba"].values() if scope} == {"ssm_conv", "ssm_step", "shared_mlp"} and None in found["mamba"].values()
    assert {scope for scope in found["attention"].values() if scope} == {"nope_attend", "shared_mlp"}


def test_the_cell_rehearses_end_to_end():
    """`python3 -m perf.run --rehearse-cpu --trace 1` of the cell: exit code 3 (passed, and no measurement), no
    compilation inside the window, the chunked reference check and the nine wrong references in the log,
    the accepted metrics that read this cell among those that would be reported."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    run = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--trace", "1", "--workload", CELL, "--seed", "2147483659"],
                         capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    log = run.stderr
    assert run.returncode == 3, log[-4000:]
    assert "inside it 0" in log and "in chunks of 64" in log and log.count("for the record, the reference with") == 9
    assert "rehearsal passed=True" in log and "failed=0" in log and "mmmmmammmmmmmmmammmm: the model's 0-19" in log
    assert "'nope_attend', 'shared_mlp'" in log and "'shared_mlp', 'ssm_conv', 'ssm_step'" in log
    listed = log[log.index("metrics that would be reported"):]
    for name in ("decode_cache_mb_per_session.ssm", "decode_cache_mb_per_session.full", "prefill_ms_per_1k_positions.ssm", "decode_rows_per_batch",
                 "decode_wire_cohort_share"):
        assert name in listed, name
