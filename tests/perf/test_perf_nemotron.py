"""What `nemotron-3-super-120b-span11` brings to the benchmark: its configuration file against the
catalog's row, the parameter counts its cut is reckoned from, the plain reference against the same
equations written another way (per position and head, in numpy), the runner's block kwargs, each
wrong program of the check refused at rehearsal size, the traffic's schedule, `flops_nemotron` against
hand counts, the new readers on hand-made observations (and on a program that lacks what they read),
the scopes read off a compiled program's text, and the cell's rehearsal end to end (CPU)."""

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

from perf import flops_nemotron  # noqa: E402
from perf import manifest as mf  # noqa: E402
from perf.reference import nemotron_h_block as reference  # noqa: E402
from perf.runners import nemotron_block_server as runner  # noqa: E402
from perf.runners import sala_block_server as sala_runner  # noqa: E402
from perf.traffic import long_sessions  # noqa: E402

NAME = "nemotron-3-super-120b-span11"
CONFIG = mf.load_json(mf.PERF / "configs" / f"{NAME}.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = f"{NAME}.longctx32"
WORKLOAD = mf.load_workload(CELL)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_KEYS = [key for key in CONFIG if key not in ("name", "source", "runner")][: list(CONFIG).index("catalog_keys") - 3]
PATTERN = "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
REDUCED = {"num_hidden_layers": (11, 88), "hybrid_override_pattern": (PATTERN[:11], PATTERN), "n_routed_experts": (64, 512),
           "num_nextn_predict_layers": (0, 1)}
# the widths the issue names, as published
WIDTHS = {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
          "chunk_size": 128, "expand": 2, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128, "num_experts_per_tok": 22,
          "moe_latent_size": 1024, "moe_intermediate_size": 2688, "moe_shared_expert_intermediate_size": 5376, "intermediate_size": 2688,
          "routed_scaling_factor": 5, "norm_eps": 1e-05, "n_group": 1, "topk_group": 1, "n_shared_experts": 1, "mlp_hidden_act": "relu2",
          "max_position_embeddings": 262144, "model_type": "nemotron_h", "vocab_size": 131072, "use_conv_bias": True, "norm_topk_prob": True}
TOY = dict(rms_eps=1e-5, mamba_heads=4, mamba_head_dim=3, ssm_groups=2, ssm_state=5, num_heads=4, num_kv_heads=2, head_dim=4,
           experts_per_token=3, routed_scale=5.0, held_lo=0)


def _catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    found = [row for row in rows if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    if not found:
        pytest.skip("the catalog on this machine has no NVIDIA-Nemotron-3-Super-120B-A12B-BF16 row")
    return found[0]


@pytest.mark.parametrize("key", PUBLISHED_KEYS)
def test_configuration_holds_every_published_value(key):
    """Every key of the catalog row's config, at the top level of the file and in the `model` section the
    runner reads, unchanged except for the four cuts `reduced` lists."""
    assert CONFIG[key] == CONFIG["model"][key]
    if key in REDUCED:
        assert key in CONFIG["reduced"] and (CONFIG[key], CONFIG["published"][key]) == REDUCED[key] and key in CONFIG["reduced_why"]
        return
    if key in WIDTHS:
        assert CONFIG[key] == WIDTHS[key] and type(CONFIG[key]) is type(WIDTHS[key])
    row = _catalog_row()  # skips, and does not fail, where the catalog or the row is not there
    assert CONFIG[key] == row["config"][key] and type(CONFIG[key]) is type(row["config"][key])


def test_configuration_has_every_key_of_the_catalog_row_and_its_sections():
    assert all(section in CONFIG for section in ("source", "reduced", "reduced_why", "assumed", "published", "share", "deployment",
                                                 "tolerances", "rehearsal", "serving", "model"))
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED) and len(PUBLISHED_KEYS) == 50 and set(WIDTHS) <= set(PUBLISHED_KEYS)
    assert not set(CONFIG["reduced"]) & {key for key in PUBLISHED_KEYS if key.endswith(("_dim", "_rank", "_size")) or key == "expand"}  # no width is cut
    share = CONFIG["share"]
    assert (share["router_outputs"], share["held_lo"], share["chips_sharing_a_layer"]) == (512, 0, 8)
    assert all(name in CONFIG["assumed"] for name in ("pre_norm", "time_step_limit", "norm_before_gate", "ssm_state_dtype", "seeded_ssm_weights",
                                                      "no_rotary", "latent_placement", "selection_bias", "recorded_not_read", "param_dtype"))
    assert all(name in CONFIG["tolerances"] for name in ("decode_rel", "decode_rms_rel", "first_state_rms_rel", "routing_mismatch_share",
                                                         "router_mismatch_share", "departure_share", "why"))
    serving = CONFIG["serving"]
    assert (serving["expert_cls"], serving["decode_max_len"], serving["prompt_chunk"], serving["activation_compression"]) == (
        "nemotron_h_block", 12288, 2048, "float16") and serving["decode_max_sessions"] == 48 * 11
    row = _catalog_row()
    assert set(row["config"]) == set(PUBLISHED_KEYS) and CONFIG["source"] == row["source_url"]


def test_the_span_is_blocks_0_to_10_of_the_published_model():
    assert CONFIG["model"]["first_block"] == 0 and CONFIG["published"]["hybrid_override_pattern"][:11] == CONFIG["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert runner.kinds(CONFIG) == ["mamba", "experts"] * 3 + ["mamba", "attention", "experts", "mamba", "experts"]
    published = CONFIG["published"]["hybrid_override_pattern"]
    assert (published.count("M"), published.count("*"), published.count("E"), len(published)) == (40, 8, 40, 88) and "-" not in published
    experts = runner.block_kwargs(CONFIG, 1)
    assert (experts["kind"], experts["num_experts"], experts["held"], experts["held_lo"], experts["experts_per_token"], experts["latent_dim"],
            experts["expert_inner"], experts["shared_inner"], experts["routed_scale"]) == ("experts", 512, 64, 0, 22, 1024, 2688, 5376, 5.0)
    mixer = runner.block_kwargs(CONFIG, 0)
    assert (mixer["kind"], mixer["mamba_heads"], mixer["mamba_head_dim"], mixer["ssm_groups"], mixer["ssm_state"], mixer["conv_kernel"],
            mixer["chunk_size"], mixer["rms_eps"]) == ("mamba", 128, 64, 8, 128, 4, 128, 1e-5)
    attention = runner.block_kwargs(CONFIG, 7)
    assert (attention["kind"], attention["num_heads"], attention["num_kv_heads"], attention["head_dim"]) == ("attention", 32, 2, 128)
    toy = runner.block_kwargs(REHEARSAL, 1)
    assert (toy["num_experts"], toy["held_lo"], toy["held"], toy["latent_dim"]) == (16, 4, 4, 32)
    sizes = runner.reference_sizes(CONFIG)
    assert sizes["held_lo"] == 0 and sizes["routed_scale"] == 5.0 and sizes["ssm_groups"] == 8 and sizes["num_kv_heads"] == 2


def test_parameter_counts_are_the_issues_table_and_the_rows_120b_a12b():
    """From the shapes: the table of the cut (109.64 M, 35.66 M, 54.53 M + 5.505 M an expert, 406.85 M, 2,618.1 M = 10.47 GB at
    4 bytes) and the uncut model, 120.67 B parameters of which a token touches 12.77 B: the row's "120B-A12B"."""
    model, share, published = CONFIG["model"], CONFIG["share"], CONFIG["published"]
    assert flops_nemotron.mamba_block_params(model) == 109_640_064 and flops_nemotron.attention_block_params(model) == 35_655_680
    assert flops_nemotron.latent_expert_params(model) == 5_505_024
    assert flops_nemotron.experts_block_params(model, 0, 512) == 54_530_560 and flops_nemotron.experts_block_params(model, 64, 512) == 406_852_096
    span = flops_nemotron.span_params(model, model["hybrid_override_pattern"], model["n_routed_experts"], share["router_outputs"])
    assert span == 5 * 109_640_064 + 35_655_680 + 5 * 406_852_096 and round(span * 4 / 1e9, 2) == 10.47
    whole = flops_nemotron.model_params(model, published["hybrid_override_pattern"], published["n_routed_experts"])
    touched = flops_nemotron.model_params(model, published["hybrid_override_pattern"], published["n_routed_experts"], per_token=True)
    assert round(whole / 1e9, 2) == 120.67 and round(touched / 1e9, 2) == 12.77
    # the served block's own parameter trees, by their shapes, are what the functions count
    from hivemind_tpu.moe.server.layers import name_to_block

    for index, count in ((0, 109_640_064), (7, 35_655_680), (1, 406_852_096)):
        module = name_to_block["nemotron_h_block"](model["hidden_size"], **runner.block_kwargs(CONFIG, index))
        shapes = jax.eval_shape(lambda module=module: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, model["hidden_size"]), jnp.float32))["params"])
        assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)) == count
    state, window = 128 * 64 * 128 * 4, 3 * 10240 * 2
    assert flops_nemotron.ssm_row_state_bytes(model) == state + window == 4_255_744  # 4.194 MB + 61 KB a session a mixer
    assert 5 * (state + window) + 12288 * 2 * 2 * 128 * 2 == pytest.approx(33.9e6, rel=0.01)  # a session of the span


def test_benchmark_lists_the_cell_and_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.by_name(manifest["workloads"], CELL, "cell")
    assert cell == {"name": CELL, "config": NAME, "traffic": "longctx32", "chips": 1, "why": WORKLOAD["why"]} and len(WORKLOAD["why"]) <= 200
    entry = mf.by_name(manifest["configs"], NAME, "configuration")
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"] and entry["file"] == f"perf/configs/{NAME}.json"
    reported = {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "per_layer")}
    new = {"decode_program_ms.ssm", "decode_program_ms.stateless", "decode_cache_mb_per_session.ssm", "ssm_step_roofline",
           "moe_experts_roofline.latent", "moe_experts_ms_per_step.latent", "prefill_ms_per_1k_positions.ssm"}
    appended = {"server_handle_ms.decode", "rpc_overhead_ms.decode", "queue_wait_ms.decode", "decode_batched_share", "transfer_kb_per_token.decode",
                "decode_assemble_ms", "decode_step_ms", "decode_scatter_ms", "decode_rows_per_batch", "idle_host_dispatch_share.serve",
                "idle_unlabelled_share.serve", "device_idle_share.serve", "hbm_peak_gb.serve", "moe_experts_hit_per_step", "moe_held_pairs_per_step",
                "decode_program_ms.full", "decode_cache_mb_per_session.full"}
    assert new <= reported and appended <= reported
    assert not reported & {"ttft_median_ms", "wire_frames_per_token.decode", "prefill_ms_per_1k_positions.chunked", "moe_load_max_over_mean.held",
                           "moe_experts_roofline.kexaone", "moe_experts_ms_per_step"}
    for name in reported:
        assert mf.load_layer_metric(name)["name"] == name
    for name in new:  # a later cell may be appended to any of these lists, and a later cell or configuration to the manifest's
        entry = mf.by_name(manifest["per_layer"], name, "metric")
        assert CELL in entry["workloads"] and entry["moves"] == "decode_tokens_per_s"
    assert {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s"} <= {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "end_to_end")}
    for name in ("ssm_step_roofline", "moe_experts_roofline.latent"):
        assert mf.by_name(manifest["per_layer"], name, "metric")["unit"] == "%"


# ---- the reference, against the same equations written another way ------------------


def _toy_params(seed: int, kind: str, hidden=12, experts=8, held=8, latent=6, width=7, shared=9):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2] if len(shape) > 1 else 1.0), jnp.float32)
    params = {"norm": {"scale": jnp.asarray(1.0 + 0.1 * rng.standard_normal(hidden), jnp.float32)}}
    heads, dim, groups, state = TOY["mamba_heads"], TOY["mamba_head_dim"], TOY["ssm_groups"], TOY["ssm_state"]
    inner, channels = heads * dim, heads * dim + 2 * groups * state
    if kind == "mamba":
        params.update(in_proj={"kernel": draw(hidden, inner + channels + heads)}, conv_weight=draw(4, channels), conv_bias=draw(channels) / 2,
                      A_log=jnp.log(jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)), dt_bias=draw(heads) - 2.0,
                      D=jnp.asarray(1.0 + 0.1 * rng.standard_normal(heads), jnp.float32),
                      gate_norm=jnp.asarray(1.0 + 0.1 * rng.standard_normal(inner), jnp.float32), out_proj={"kernel": draw(inner, hidden)})
    elif kind == "attention":
        q, kv = TOY["num_heads"] * TOY["head_dim"], TOY["num_kv_heads"] * TOY["head_dim"]
        params.update(query={"kernel": draw(hidden, q)}, key={"kernel": draw(hidden, kv)}, value={"kernel": draw(hidden, kv)},
                      attention_out={"kernel": draw(q, hidden)})
    else:
        params.update(router=draw(hidden, experts) * 3.0, router_bias=jnp.asarray(0.1 * rng.standard_normal(experts), jnp.float32),
                      experts_up=draw(held, latent, width), experts_down=draw(held, width, latent), latent_down={"kernel": draw(hidden, latent)},
                      latent_up={"kernel": draw(latent, hidden)}, shared_up={"kernel": draw(hidden, shared)}, shared_down={"kernel": draw(shared, hidden)})
    return params


def test_mixer_equals_a_loop_over_positions_and_heads():
    """`reference.mamba` against numpy loops: per position the convolution from its four inputs, per head its group's B and C,
    the state's decay, update and read, the skip term, the gate before the group norm."""
    params = _toy_params(1, "mamba")
    x = np.random.default_rng(2).standard_normal((1, 9, 12)).astype(np.float32)
    got = np.asarray(reference.block(params, jnp.asarray(x), **TOY))
    p = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    heads, dim, groups, width = 4, 3, 2, 5
    inner = heads * dim
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5) * p["norm"]["scale"]
    projected = u @ p["in_proj"]["kernel"]
    z, xbc, dt = projected[:, :inner], projected[:, inner:inner + inner + 2 * groups * width], projected[:, -heads:]
    silu = lambda t: t / (1.0 + np.exp(-t))
    state, want = np.zeros((heads, dim, width)), np.zeros((9, 12))
    for t in range(9):
        mixed = silu(p["conv_bias"] + sum(p["conv_weight"][j] * (xbc[t - 3 + j] if t - 3 + j >= 0 else 0.0) for j in range(4)))
        step = np.log1p(np.exp(dt[t] + p["dt_bias"]))
        y = np.zeros((heads, dim))
        for h in range(heads):
            group = h // (heads // groups)
            b = mixed[inner + group * width:inner + (group + 1) * width]
            c = mixed[inner + groups * width + group * width:inner + groups * width + (group + 1) * width]
            xs = mixed[h * dim:(h + 1) * dim]
            state[h] = np.exp(-step[h] * np.exp(p["A_log"][h])) * state[h] + step[h] * np.outer(xs, b)
            y[h] = state[h] @ c + p["D"][h] * xs
        gated = (y.reshape(-1) * silu(z[t])).reshape(groups, -1)
        gated = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
        want[t] = x[0, t] + (gated.reshape(-1) * p["gate_norm"]) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


def test_attention_equals_a_loop_over_positions_and_heads():
    params = _toy_params(3, "attention")
    x = np.random.default_rng(4).standard_normal((1, 11, 12)).astype(np.float32)
    got = np.asarray(reference.block(params, jnp.asarray(x), query_block=4, **{k: v for k, v in TOY.items()}))
    p = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + 1e-5) * p["norm"]["scale"]
    q, k, v = (u @ p[name]["kernel"] for name in ("query", "key", "value"))
    want = np.zeros((11, 12))
    for t in range(11):
        context = np.zeros(16)
        for h in range(4):
            kv = h // 2  # two query heads a key-value head
            scores = np.array([q[t, h * 4:(h + 1) * 4] @ k[s, kv * 4:(kv + 1) * 4] for s in range(t + 1)]) * 4 ** -0.5  # no position embedding
            weights = np.exp(scores - scores.max())
            context[h * 4:(h + 1) * 4] = (weights / weights.sum()) @ v[:t + 1, kv * 4:(kv + 1) * 4]
        want[t] = x[0, t] + context @ p["attention_out"]["kernel"]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("held_lo,held", [(0, 8), (2, 3)])
def test_latent_experts_equal_a_loop_over_tokens(held_lo, held):
    params = _toy_params(5, "experts", held=held)
    x = np.random.default_rng(6).standard_normal((1, 10, 12)).astype(np.float32)
    got, (_u, top_e, _state) = reference.block(params, jnp.asarray(x), return_routing=True, **{**TOY, "held_lo": held_lo})
    p = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    relu2 = lambda t: np.maximum(t, 0.0) ** 2
    want = np.zeros((10, 12))
    for t in range(10):
        u = x[0, t] / np.sqrt((x[0, t] ** 2).mean() + 1e-5) * p["norm"]["scale"]
        scores = 1.0 / (1.0 + np.exp(-(u @ p["router"])))
        picked = np.argsort(-(scores + p["router_bias"]), kind="stable")[:3]
        assert sorted(picked) == sorted(np.asarray(top_e)[0, t])
        latent, routed = u @ p["latent_down"]["kernel"], np.zeros(6)
        for expert in picked:
            if held_lo <= expert < held_lo + held:  # a pair routed elsewhere adds nothing here
                weight = 5.0 * scores[expert] / scores[picked].sum()  # the bias picks and does not weigh
                routed += weight * (relu2(latent @ p["experts_up"][expert - held_lo]) @ p["experts_down"][expert - held_lo])
        want[t] = x[0, t] + routed @ p["latent_up"]["kernel"] + relu2(u @ p["shared_up"]["kernel"]) @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4, atol=2e-4)


def _toy_span(seed: int = 7):
    return [_toy_params(seed + at, kind, held=4) for at, kind in enumerate(("mamba", "experts", "attention", "mamba", "experts"))]


WRONG = runner.wrong_references()


def test_the_wrong_references_are_the_issues_twelve():
    assert len(WRONG) == 12 and set(runner.EVERY_RUN) <= set(WRONG) and set(runner.wrong_references(every=False)) == set(runner.EVERY_RUN)
    assert sorted(name for name, (_variant, told) in WRONG.items() if told in ("precision", "dtype")) == [
        "the router's matmul in one bf16 pass", "the state kept in bf16"] and WRONG["the state kept in bf16"][1] == "dtype"
    assert sorted(name for name, (_variant, told) in WRONG.items() if told == runner.PADDING) == [
        "a window that holds a padded row", "padding that decays and feeds the state"]


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_program_is_refused_at_rehearsal_size(name):
    """A program that computed the wrong reference would hand its output over as the served one: the check's
    measures must then say `correct: false`: a plain limit is passed, or the served output holds the whole of
    the wrong reference's departure (`_departure_share` reads 1 where it reads about 0 for the model). The two
    that differ in a precision alone move a rehearsal's short streams by less than its limits: the router's
    must at least depart, and the chip's limit (`tolerances.why`) is what refuses it; the state's is refused by
    the dtype of what the served sessions hold."""
    variant, told = WRONG[name]
    params, sizes = _toy_span(), {**TOY, "held_lo": 2}
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, 48, 12)), jnp.float32)
    if told == runner.PADDING:
        variant = dict(padding=(variant["padding"], 30, 2))
    want, routing = runner.reference_span(params, x, sizes)
    out, wrong_routing = runner.reference_span(params, x, sizes, **variant)
    want, out = np.asarray(want), np.asarray(out)
    served_right = want + 1e-3 * np.random.default_rng(9).standard_normal(want.shape).astype(np.float32)  # the model, and a rounding's noise
    served_wrong = out + 1e-3 * np.random.default_rng(9).standard_normal(want.shape).astype(np.float32)  # the wrong program
    tolerances = REHEARSAL["tolerances"]
    readings = lambda got: {"decode_rel": float(np.abs(got - want).max() / np.abs(want).max()), "decode_rms_rel": runner._rms_err(got, want)}
    assert not runner.judge(readings(served_right), tolerances) and abs(runner._departure_share([(served_right, want, out)])) <= tolerances["departure_share"]
    if told == "dtype":  # no limit tells it, on the chip either: the check reads the dtype of what the served sessions hold
        assert runner.state_dtype_faults([np.zeros((1, 4, 3, 5), np.float32), jnp.zeros((1, 4, 3, 5), jnp.bfloat16)]) == [
            "the mixers keep their recurrent state in ['bfloat16', 'float32'], not in float32"]
        assert runner.state_dtype_faults([np.zeros((1, 4, 3, 5), np.float32)] * 2) == [] and float(np.abs(out - want).max()) > 0
        return
    if told == "precision":
        moved = float(np.abs(out - want).max()) > 0 or runner._mismatch_share(runner._choices(wrong_routing), runner._choices(routing)) > 0
        assert moved, name
        return
    refused = bool(runner.judge(readings(served_wrong), tolerances)) or abs(runner._departure_share([(served_wrong, want, out)])) > tolerances["departure_share"]
    assert refused, name
    assert abs(runner._departure_share([(served_wrong, want, out)])) > 0.9


def test_the_reference_hands_back_routing_and_last_states():
    params, sizes = _toy_span(), {**TOY, "held_lo": 2}
    x = jnp.asarray(np.random.default_rng(8).standard_normal((2, 20, 12)), jnp.float32)
    out, routing = runner._by_stream(lambda rows: runner.reference_span(params, jnp.asarray(rows), sizes), np.asarray(x))
    assert out.shape == (2, 20, 12) and [entry[1] is not None for entry in routing] == [False, True, False, False, True]
    assert [entry[2] is not None for entry in routing] == [True, False, False, True, False] and routing[0][2].shape == (2, 4, 3, 5)
    assert routing[0][0] is None and routing[1][0].shape == (2, 20, 12)  # a router's input is kept where there is a router
    assert runner._router_mismatch_share(params, routing, 3) == 0.0 and len(runner._states(routing)) == 2 and len(runner._choices(routing)) == 2
    halved = [(u, top_e, None if state is None else state * 0.5) for u, top_e, state in routing]
    assert runner._state_err(runner._states(halved), runner._states(routing)) == {"state_rms_rel": pytest.approx(0.5), "first_state_rms_rel": pytest.approx(0.5)}
    first_right = [runner._states(routing)[0], runner._states(halved)[1]]
    assert runner._state_err(first_right, runner._states(routing)) == {"state_rms_rel": pytest.approx(0.5), "first_state_rms_rel": 0.0}
    assert runner.judge({"state_rms_rel": 0.5, "decode_rel": 0.01, "first_state_rms_rel": 0.2}, {"state_rms_rel": 0.1, "decode_rel": 0.3}) == [
        "5.000e-01 rms of a mixer's last state, over 0.1"]  # a limit the configuration lacks is not held


# ---- traffic, arithmetic and readers --------------------------------------------------


def test_long_sessions_deals_the_cells_prompts():
    traffic = WORKLOAD["traffic"]
    assert (traffic["generator"], traffic["processes"], traffic["slots_per_process"], traffic["chunk"], traffic["answer_cap"]) == (
        "long_sessions", 4, 8, 2048, 4096)
    assert traffic["prompt_lengths"] == [2048, 4096, 6144, 8192] and traffic["prompt_weights"] == [0.4, 0.3, 0.2, 0.1]
    assert sorted(long_sessions.sizes(traffic)) == [2048] * 13 + [4096] * 10 + [6144] * 6 + [8192] * 3
    assert sum(long_sessions.sizes(traffic)) == 129024 and sum(long_sessions.sizes(traffic)) * 4096 * 2 == pytest.approx(1.06e9, rel=0.01)
    assert max(traffic["prompt_lengths"]) + traffic["answer_cap"] == CONFIG["serving"]["decode_max_len"] == 12288
    assert traffic["chunk"] == CONFIG["serving"]["prompt_chunk"]
    prompt, steps, rows = runner.check_shape(False)
    assert (prompt, steps, rows) == (4096, 192, 8) and runner.check_prompts(prompt, rows)[1] % 2048 not in (0, 1)  # row 1's last chunk comes padded
    assert sala_runner.padded_chunks(traffic["prompt_lengths"] + runner.check_prompts(prompt, rows) + [runner.filler_prompt(prompt, 2048)], 2048) == [512, 2048]
    same = {key: value for key, value in mf.load_workload("gigachat-702b-a36b-span5.longctx32")["traffic"].items() if key != "lead_seconds"}
    assert {key: value for key, value in traffic.items() if key != "lead_seconds"} == same  # GigaChat's traffic letter for letter


def test_nemotron_arithmetic_by_hand():
    model = CONFIG["model"]
    assert flops_nemotron.ssm_step_flops(model) == 4 * 128 * 64 * 128  # 4.19 MFLOP a row
    assert flops_nemotron.ssm_step_bytes(4_255_744.0) == 8_511_488.0  # read once, written once
    assert flops_nemotron.ssm_step_bytes(16 * 4_255_744.0) / 819e9 > 16 * flops_nemotron.ssm_step_flops(model) / 197e12  # memory-bound on a v5e
    assert flops_nemotron.latent_expert_layer_flops(44.0, model) == 2 * 44 * 2 * 1024 * 2688
    assert flops_nemotron.latent_expert_layer_bytes(32.0, 44.0, model) == 32 * 5_505_024 * 4 + 44 * (2 * 1024 + 2 * 2688) * 4
    # 32 held experts hit by 44 pairs: 705 MB of weights, 0.86 ms at 819 GB/s, against 2.5 us of matmul: weight-bound
    assert flops_nemotron.latent_expert_layer_bytes(32.0, 44.0, model) / 819e9 == pytest.approx(0.86e-3, rel=0.02)
    assert flops_nemotron.conv_channels(model) == 10240 and flops_nemotron.mamba_inner(model) == 8192


def _observations(**extra):
    series = lambda **values: {"series": values}
    calls, steps, rewritten = "hivemind_moe_decode_calls_total", "hivemind_moe_decode_steps_total", "hivemind_moe_ssm_state_bytes_total"
    before = {calls: series(**{"path=batched": 11.0}), steps: series(**{"path=batched": 176.0}), rewritten: series(**{"path=batched": 1e6})}
    after = {calls: series(**{"path=batched": 11.0 + 1100}), steps: series(**{"path=batched": 176.0 + 17600}),
             rewritten: series(**{"path=batched": 1e6 + 500 * 16 * 4_255_744.0, "path=direct": 7.0})}
    for name, (low, high) in {"expert_layer_calls": (5.0, 505.0), "held_pairs": (100.0, 100.0 + 500 * 44), "experts_hit": (50.0, 50.0 + 500 * 32)}.items():
        before[f"hivemind_moe_{name}_total"], after[f"hivemind_moe_{name}_total"] = series(**{"path=batched": low}), series(**{"path=batched": high})
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters": {"before": before, "after": after}, **extra}


def test_ssm_roofline_reads_the_rewritten_bytes_over_the_scopes_time():
    spec = mf.load_layer_metric("ssm_step_roofline")
    obs = _observations()
    scopes = {"ssm_step": {"seconds": 0.100, "count": 2000.0, "runs": 480.0}}
    traced = dict(obs, scopes=scopes, counters_traced=obs["counters"])
    value = mf.read_metric(spec, traced)
    least = 2 * 500 * 16 * 4_255_744.0 / 819e9  # memory-bound; of 1,100 x 5 / 11 = 500 state-space programs counted
    assert value == pytest.approx(100.0 * least / 500 * 480 / 0.100, rel=1e-6) and 0 < value < 100
    assert any("memory-bound" in note and "16.0 rows a program" in note for note in traced["notes"])
    # where the compiler stages the rows' states, the copies' time is the states' read: it counts with the scope's
    staged = dict(traced, scopes={**scopes, "ssm_staging": {"seconds": 0.300, "count": 7680.0, "runs": 480.0}})
    assert mf.read_metric(spec, staged) == pytest.approx(value / 4, rel=1e-6) and any("in the copies that stage" in note for note in staged["notes"])
    assert mf.read_metric(spec, dict(obs, scopes=scopes)) is None  # a runner that does not read the counters at the trace's edges
    assert mf.read_metric(spec, dict(obs, counters_traced=obs["counters"])) is None  # a runner without scopes
    assert mf.read_metric(spec, dict(obs, scopes={"moe_experts": scopes["ssm_step"]}, counters_traced=obs["counters"])) is None
    older = json.loads(json.dumps(obs["counters"]))  # a program without the counter (a parent commit): nothing, and no exception
    for side in older.values():
        del side["hivemind_moe_ssm_state_bytes_total"]
    assert mf.read_metric(spec, dict(obs, scopes=scopes, counters_traced=older)) is None


def test_latent_experts_roofline_and_the_other_new_readers():
    obs = _observations()
    ops = {"ragged-dot-none": {"seconds": 0.5, "count": 1000}, "ragged-dot-metadata": {"seconds": 0.02, "count": 1000},
           "fusion": {"seconds": 1.0, "count": 10}}
    trace = {"devices": 1, "ops": ops}
    spec = mf.load_layer_metric("moe_experts_roofline.latent")
    value = mf.read_metric(spec, dict(obs, trace=trace, counters_traced=obs["counters"]))
    least = flops_nemotron.latent_expert_layer_bytes(500 * 32.0, 500 * 44.0, CONFIG["model"]) / 819e9
    assert value == pytest.approx(100.0 * least / 500 * 500 / 0.52, rel=1e-6) and 0 < value < 100
    assert mf.read_metric(spec, dict(obs, trace=trace)) is None  # no counters at the trace's edges
    other = dict(obs, config={"model": {"hidden_size": 8}}, trace=trace, counters_traced=obs["counters"])
    assert mf.read_metric(spec, other) is None  # a configuration without a latent
    assert mf.read_metric(mf.load_layer_metric("moe_experts_ms_per_step.latent"), dict(obs, trace=trace)) == pytest.approx(1000 * 0.52 / 500)


def test_cache_gauge_program_and_lead_in_readers():
    obs = _observations()
    obs["counters"]["after"].update({"hivemind_moe_decode_cache_bytes": {"series": {"kind=ssm": 160 * 4_255_744.0, "kind=full": 32 * 12288 * 1024.0}},
                                     "hivemind_moe_decode_cache_entries": {"series": {"kind=ssm": 160.0, "kind=full": 32.0}}})
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.ssm"), obs) == pytest.approx(4.255744)
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.full"), obs) == pytest.approx(12.582912)
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.ssm"), _observations()) is None  # a program without the series
    lead = {side: {"hivemind_moe_decode_prefill_seconds_total": {"series": {"": seconds}},
                   "hivemind_moe_decode_prefill_positions_total": {"series": {"": positions}}}
            for side, seconds, positions in (("before", 1.0, 1000.0), ("after", 31.0, 1000.0 + 11 * 129024))}
    spec = mf.load_layer_metric("prefill_ms_per_1k_positions.ssm")
    assert mf.read_metric(spec, {**obs, "counters_lead": lead}) == pytest.approx(30.0 / (11 * 129024) * 1e6)
    assert mf.read_metric(spec, obs) is None  # a runner that does not read the lead-in
    programs = {"jit_batched_step_ssm": {"seconds": 0.6, "count": 500.0}, "jit_batched_step_stateless": {"seconds": 0.8, "count": 500.0},
                "jit_batched_step_full": {"seconds": 0.03, "count": 100.0}, "jit_prefill_ssm_2048": {"seconds": 9.0, "count": 3.0}}
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.ssm"), {"programs": programs}) == pytest.approx(1.2)
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.stateless"), {"programs": programs}) == pytest.approx(1.6)
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.full"), {"programs": programs}) == pytest.approx(0.3)
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.ssm"), {"programs": {}}) is None


def test_scopes_are_read_off_the_batched_programs():
    """The toy blocks' own batched programs at a bucket of two: a mixer's operations lie in `ssm_conv` and
    `ssm_step`, an expert layer's in `moe_experts`, and a mixer's chunk program holds `ssm_scan`."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden = REHEARSAL["model"]["hidden_size"]
    found = {}
    for index in (0, 1):
        module = name_to_block["nemotron_h_block"](hidden, **runner.block_kwargs(REHEARSAL, index))
        params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden)))["params"]
        cache = module.init_decode_cache(2, 128)
        step = jax.jit(lambda p, x, cache, *rest, module=module: module.apply({"params": p}, x, *cache, *rest, mutable=["routing", "attended"]))
        text = step.lower(params, jnp.zeros((2, 1, hidden)), cache, jnp.array([70, 90])).compile().as_text()
        found[module.kind] = runner.instruction_scopes(text)
        if module.kind == "mamba":
            one = module.init_decode_cache(1, 128)
            chunk = step.lower(params, jnp.zeros((1, 32, hidden)), one, jnp.int32(64), jnp.int32(20)).compile().as_text()
            assert "ssm_scan" in set(runner.instruction_scopes(chunk).values())
    assert {"ssm_conv", "ssm_step"} <= {scope for scope in found["mamba"].values() if scope} and None in found["mamba"].values()
    assert {scope for scope in found["experts"].values() if scope} == {"moe_experts"}


def test_copies_of_a_rows_state_are_the_staging():
    """The compiler's asynchronous copies carry no `op_name`: those of exactly a row's state are `ssm_staging`,
    a smaller one is not, and without the size none is."""
    text = """
  %copy-start.63 = (f32[1,128,64,128]{3,2,1,0:T(8,128)S(1)}, f32[1,128,64,128]{3,2,1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%p.3)
  %copy-done.63 = f32[1,128,64,128]{3,2,1,0:T(8,128)S(1)} copy-done(%copy-start.63)
  %copy-start.7 = (f32[16,1,4096]{2,1,0}, f32[16,1,4096]{2,1,0}, u32[]) copy-start(%p.1)
  %fusion.5 = f32[1,128,64,128]{3,2,1,0} fusion(%copy-done.63), kind=kLoop, metadata={op_name="jit(batched_step_ssm)/ssm_step/mul"}
"""
    staged = runner.instruction_scopes(text, {128 * 64 * 128 * 4, 3 * 10240 * 2})
    assert staged["copy-start.63"] == staged["copy-done.63"] == runner.STAGING and staged["copy-start.7"] is None and staged["fusion.5"] == "ssm_step"
    assert set(runner.instruction_scopes(text).values()) == {None, "ssm_step"}


def test_scope_seconds_tells_a_program_by_its_name_and_then_by_what_it_ran(monkeypatch):
    from perf import trace_reduce

    small, large = {"fusion.1": "ssm_step", "fusion.2": None}, {"fusion.1": None, "fusion.9": "ssm_step", "fusion.2": None}
    experts = {"fusion.1": "moe_experts"}
    candidates = {"jit_batched_step_ssm": [small, large], "jit_batched_step_stateless": [experts]}
    lines = {
        runner.MODULE_LINE: [("jit_batched_step_ssm(11)", 0, 100), ("jit_batched_step_stateless(12)", 100, 100), ("jit_batched_step_ssm(13)", 200, 100),
                             ("jit_step_full(14)", 300, 100)],
        trace_reduce.OP_LINES[0]: [("%fusion.1 = f32[] fusion()", 10, 30_000_000), ("%fusion.2 = f32[] fusion()", 50, 10_000_000),
                                   ("%fusion.1 = f32[] fusion()", 110, 20_000_000),
                                   ("%fusion.9 = f32[] fusion()", 210, 40_000_000), ("%fusion.2 = f32[] fusion()", 260, 10_000_000),
                                   ("%fusion.1 = f32[] fusion()", 310, 50_000_000)],
    }
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: "somewhere")
    monkeypatch.setattr(trace_reduce, "load_planes", lambda _path: {"/device:TPU:0": lines})
    totals = runner.scope_seconds("anywhere", candidates)
    assert totals["ssm_step"] == {"seconds": pytest.approx(0.07), "count": 2.0, "runs": 2.0}  # fusion.1 of the small text, fusion.9 of the large
    assert totals["moe_experts"] == {"seconds": pytest.approx(0.02), "count": 1.0, "runs": 1.0}  # the step of another name is no candidate's
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: None)
    assert runner.scope_seconds("anywhere", candidates) == {}


def test_the_cell_rehearses_end_to_end():
    """`python3 -m perf.run --rehearse-cpu --trace 1` of the cell: exit code 3 (passed, and no measurement), no
    compilation inside the window, the chunked reference check and the twelve wrong references in the log,
    the new metrics among those that would be reported."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    run = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--trace", "1", "--workload", CELL, "--seed", "2147483659"],
                         capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    log = run.stderr
    assert run.returncode == 3, log[-4000:]
    assert "inside it 0" in log and "in chunks of 64" in log and log.count("for the record, the reference with") == 12
    assert "rehearsal passed=True" in log and "failed=0" in log and "MEMEMEM*EME: the model's 0-10" in log
    listed = log[log.index("metrics that would be reported"):]
    for name in ("decode_cache_mb_per_session.ssm", "decode_cache_mb_per_session.full", "prefill_ms_per_1k_positions.ssm",
                 "moe_held_pairs_per_step", "moe_experts_hit_per_step", "decode_rows_per_batch"):
        assert name in listed, name
