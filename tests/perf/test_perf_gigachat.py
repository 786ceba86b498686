"""What `gigachat-702b-a36b-span5` brings to the benchmark: its configuration file against the
catalog's row, the plain reference against the same equations written another way (per
position, per head, in numpy), the runner's block kwargs and wrong references, the traffic's
schedule, `flops_mla` against hand counts, the new reader on hand-made observations (and on a
program that lacks what it reads), the scopes read off a compiled program's text, and the
cell's rehearsal end to end (CPU)."""

import json
import math
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

from perf import flops_mla  # noqa: E402
from perf import manifest as mf  # noqa: E402
from perf.reference import gigachat_block as reference  # noqa: E402
from perf.runners import latent_moe_block_server as runner  # noqa: E402
from perf.runners import sala_block_server as sala_runner  # noqa: E402
from perf.traffic import long_sessions  # noqa: E402

NAME = "gigachat-702b-a36b-span5"
CONFIG = mf.load_json(mf.PERF / "configs" / f"{NAME}.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = f"{NAME}.longctx32"
WORKLOAD = mf.load_workload(CELL)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_KEYS = [key for key in CONFIG if key not in ("name", "source", "runner")][: list(CONFIG).index("catalog_keys") - 3]
REDUCED = {"num_hidden_layers": (5, 64), "n_routed_experts": (8, 256), "num_nextn_predict_layers": (0, 1)}
# the widths the issue names, as published
WIDTHS = {"hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_attention_heads": 64, "q_lora_rank": 1536,
          "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 192, "num_experts_per_tok": 8, "n_group": 8,
          "topk_group": 4, "n_shared_experts": 1, "routed_scaling_factor": 2.5, "first_k_dense_replace": 3, "rope_theta": 100000,
          "rms_norm_eps": 1e-06, "max_position_embeddings": 262144, "model_type": "deepseek_v3", "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True}
ROPE = dict(theta=100.0, factor=8.0, original=16, beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
TOY = dict(num_heads=2, qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=6, rms_eps=1e-6, rope=ROPE, experts_per_token=3, routed_scale=2.5,
           n_group=4, topk_group=2, held_lo=0, query_block=8)


def _catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    found = [row for row in rows if row["name"] == "GigaChat3.1-702B-A36B"]
    if not found:
        pytest.skip("the catalog on this machine has no GigaChat3.1-702B-A36B row")
    return found[0]


@pytest.mark.parametrize("key", PUBLISHED_KEYS)
def test_configuration_holds_every_published_value(key):
    """Every key of the catalog row's config, at the top level of the file and in the `model`
    section the runner reads, unchanged except for the three cuts `reduced` lists."""
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
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED) and len(PUBLISHED_KEYS) == 33
    assert CONFIG["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                      "original_max_position_embeddings": 4096, "rope_type": "yarn"}
    assert CONFIG["share"]["router_outputs"] == 256 and CONFIG["share"]["held_lo"] == 0 and CONFIG["share"]["chips_sharing_a_layer"] == 32
    assert all(name in CONFIG["assumed"] for name in ("rotary_pairs", "softmax_scale", "yarn_ramp", "latent_norm", "group_limited_choice",
                                                      "selection_bias", "param_dtype", "decode_max_len", "embedding_and_head"))
    assert all(name in CONFIG["tolerances"] for name in ("decode_rel", "decode_rms_rel", "routing_mismatch_share",
                                                         "router_mismatch_share", "departure_share", "why"))
    row = _catalog_row()
    assert set(row["config"]) == set(PUBLISHED_KEYS) and CONFIG["source"] == row["source_url"]


def test_the_span_is_blocks_2_to_6_of_the_published_model():
    assert CONFIG["model"]["first_block"] == 2 and CONFIG["model"]["first_k_dense_replace"] == 3
    kinds = [runner.block_kwargs(CONFIG, index)["mlp"] for index in range(CONFIG["model"]["num_hidden_layers"])]
    assert kinds == ["dense", "sparse", "sparse", "sparse", "sparse"]
    sparse = runner.block_kwargs(CONFIG, 1)
    assert (sparse["num_experts"], sparse["held"], sparse["held_lo"], sparse["n_group"], sparse["topk_group"], sparse["experts_per_token"]) == (
        256, 8, 0, 8, 4, 8)
    assert (sparse["num_heads"], sparse["q_lora_rank"], sparse["kv_lora_rank"], sparse["v_head_dim"], sparse["ffn_inner"], sparse["expert_inner"]) == (
        64, 1536, 512, 192, 18432, 2048)
    assert (sparse["rope_theta"], sparse["rope_factor"], sparse["rope_original"]) == (100000.0, 64.0, 4096)
    toy = runner.block_kwargs(REHEARSAL, 4)
    assert toy["mlp"] == "sparse" and (toy["num_experts"], toy["held_lo"], toy["held"], toy["n_group"]) == (16, 4, 4, 4)
    assert toy["held_lo"] // 4 == (toy["held_lo"] + toy["held"] - 1) // 4  # the held experts lie inside ONE group of four
    assert toy["rope_original"] == 64 < sum(runner.check_shape(True)[:2])  # the rehearsal's streams leave the original context
    sizes = runner.reference_sizes(CONFIG)
    assert sizes["rope"]["factor"] == 64.0 and sizes["held_lo"] == 0 and sizes["n_group"] == 8 and sizes["v_head_dim"] == 192


def test_benchmark_lists_the_cell_and_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.by_name(manifest["workloads"], CELL, "cell")
    assert cell == {"name": CELL, "config": NAME, "traffic": "longctx32", "chips": 1, "why": WORKLOAD["why"]}
    assert mf.by_name(manifest["configs"], NAME, "configuration")["reduced"] == CONFIG["reduced"]
    reported = {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "per_layer")}
    new = {"decode_program_ms.latent", "decode_cache_mb_per_session.latent", "latent_positions_per_row", "latent_attend_roofline",
           "prefill_ms_per_1k_positions.latent"}
    held_share = {"moe_experts_hit_per_step", "moe_experts_ms_per_step", "moe_held_pairs_per_step", "moe_load_max_over_mean.held",
                  "moe_experts_roofline.kexaone"}
    assert new <= reported and held_share <= reported and {"hbm_peak_gb.serve", "device_idle_share.serve", "decode_rows_per_batch"} <= reported
    assert "ttft_median_ms" not in reported  # no session opens inside the window: there is no first token to time
    for name in reported:
        assert mf.load_layer_metric(name)["name"] == name
    for name in new:  # a later cell may be appended to any of these lists, and a later cell or configuration to the manifest's
        entry = mf.by_name(manifest["per_layer"], name, "metric")
        assert CELL in entry["workloads"] and entry["moves"] == "decode_tokens_per_s"
    assert {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s"} <= {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "end_to_end")}


# ---- the reference, against the same equations written another way ------------------


def _toy_params(seed: int, sparse: bool, hidden=12, q_rank=6, rank=5, experts=16, held=16, width=7):
    rng = np.random.default_rng(seed)
    heads, nope, roped, v_dim = TOY["num_heads"], TOY["qk_nope_head_dim"], TOY["qk_rope_head_dim"], TOY["v_head_dim"]
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) / math.sqrt(shape[-2]), jnp.float32)
    scale = lambda n: {"scale": jnp.asarray(1.0 + 0.1 * rng.standard_normal(n), jnp.float32)}
    params = {"attention_norm": scale(hidden), "query_down": {"kernel": draw(hidden, q_rank)}, "query_latent_norm": scale(q_rank),
              "query_up": {"kernel": draw(q_rank, heads * (nope + roped))}, "kv_down": {"kernel": draw(hidden, rank + roped)},
              "kv_latent_norm": scale(rank), "kv_up": draw(rank, heads * (nope + v_dim)), "attention_out": {"kernel": draw(heads * v_dim, hidden)},
              "ffn_norm": scale(hidden)}
    if sparse:
        params.update(router=draw(hidden, experts) * 3.0, router_bias=jnp.asarray(0.1 * rng.standard_normal(experts), jnp.float32),
                      experts_gate=draw(held, hidden, width), experts_up=draw(held, hidden, width), experts_down=draw(held, width, hidden),
                      **{f"shared_{name}": {"kernel": draw(*shape)} for name, shape in (("gate", (hidden, width)), ("up", (hidden, width)), ("down", (width, hidden)))})
    else:
        params.update({f"ffn_{name}": {"kernel": draw(*shape)} for name, shape in (("gate", (hidden, 20)), ("up", (hidden, 20)), ("down", (20, hidden)))})
    return params


def test_attention_equals_a_loop_over_positions_and_heads():
    """`reference.attention` (queries in blocks of 8 over 21 positions) against numpy loops:
    per position and head, the key from the normed latent and the shared rotated key, the
    pairs (2i, 2i + 1) rotated by hand at YaRN's frequencies, softmax over the positions seen."""
    params = jax.tree_util.tree_map(np.asarray, _toy_params(0, False))
    h = np.random.default_rng(1).standard_normal((1, 21, 12)).astype(np.float32)
    sizes = {key: TOY[key] for key in ("num_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_eps", "rope", "query_block")}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.attention(jax.tree_util.tree_map(jnp.asarray, params), jnp.asarray(h), **sizes))
    heads, nope, roped, v_dim, rank = 2, 4, 4, 6, 5
    norm = lambda x, scale: x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale
    plain = ROPE["theta"] ** (-np.arange(0, roped, 2) / roped)
    turns = lambda beta: roped * math.log(ROPE["original"] / (beta * 2 * math.pi)) / (2 * math.log(ROPE["theta"]))
    lo, hi = max(math.floor(turns(32.0)), 0), min(math.ceil(turns(1.0)), roped - 1)
    keep = 1 - np.clip((np.arange(roped // 2) - lo) / (hi - lo), 0, 1)
    inv_freq = (1 - keep) * plain / ROPE["factor"] + keep * plain

    def turned(x, t):
        out = np.empty_like(x)
        for i in range(roped // 2):
            a, b, angle = x[..., 2 * i], x[..., 2 * i + 1], t * inv_freq[i]
            out[..., 2 * i], out[..., 2 * i + 1] = a * math.cos(angle) - b * math.sin(angle), a * math.sin(angle) + b * math.cos(angle)
        return out

    q = (norm(h[0] @ params["query_down"]["kernel"], params["query_latent_norm"]["scale"]) @ params["query_up"]["kernel"]).reshape(21, heads, nope + roped)
    down = h[0] @ params["kv_down"]["kernel"]
    c, k_pe = norm(down[:, :rank], params["kv_latent_norm"]["scale"]), down[:, rank:]
    expanded = (c @ params["kv_up"]).reshape(21, heads, nope + v_dim)
    m = 0.1 * math.log(ROPE["factor"]) + 1.0
    want = np.zeros((21, heads * v_dim), np.float64)
    for t in range(21):
        for head in range(heads):
            scores = np.array([q[t, head, :nope] @ expanded[s, head, :nope] + turned(q[t, head, nope:], t) @ turned(k_pe[s], s) for s in range(t + 1)])
            weights = np.exp((scores - scores.max()) * (nope + roped) ** -0.5 * m * m)
            want[t, head * v_dim:(head + 1) * v_dim] = (weights / weights.sum()) @ expanded[:t + 1, head, nope:]
    want = want @ params["attention_out"]["kernel"]
    assert float(np.abs(got[0] - want).max() / np.abs(want).max()) <= 2e-5
    assert (lo, hi) == (0, 1) and inv_freq[1] == pytest.approx(plain[1] / 8.0)  # the toy ramp: pair 0 plain, pair 1 slowed


def test_sparse_layer_equals_a_loop_over_tokens():
    """`reference.block`'s sparse layer against the rule applied token by token: the group-limited
    choice, the chosen scores renormalised and scaled, the shared expert, and with a held share
    the pairs routed elsewhere left out."""
    params = _toy_params(2, True)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 9, 12)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full, (m, top_e) = reference.block(params, x, return_routing=True, **TOY)
        share = {**params, **{name: params[name][4:8] for name in ("experts_gate", "experts_up", "experts_down")}}
        held = reference.block(share, x, **{**TOY, "held_lo": 4})
        attended = x + reference.attention(params, reference._rms_norm(x, params["attention_norm"]["scale"], 1e-6),
                                           **{key: TOY[key] for key in ("num_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_eps", "rope")})
    m, attended = np.asarray(m[0], np.float64), np.asarray(attended[0], np.float64)
    P = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    silu = lambda z: z / (1 + np.exp(-z))
    swiglu = lambda v, gate, up, down: (silu(v @ gate) * (v @ up)) @ down
    for t in range(9):
        scores = 1 / (1 + np.exp(-(m[t] @ P["router"])))
        biased = scores + P["router_bias"]
        groups = [sum(sorted(biased[g * 4:(g + 1) * 4], reverse=True)[:2]) for g in range(4)]
        kept = sorted(range(4), key=lambda g: -groups[g])[:2]
        allowed = [e for e in range(16) if e // 4 in kept]
        chosen = sorted(allowed, key=lambda e: -biased[e])[:3]
        assert sorted(np.asarray(top_e[0, t])) == sorted(chosen), t
        weights = {e: 2.5 * scores[e] / sum(scores[c] for c in chosen) for e in chosen}
        shared = swiglu(m[t], *(P[f"shared_{name}"]["kernel"] for name in ("gate", "up", "down")))
        routed = lambda experts: sum(weights[e] * swiglu(m[t], P["experts_gate"][e], P["experts_up"][e], P["experts_down"][e]) for e in experts)
        assert np.allclose(np.asarray(full[0, t]), attended[t] + shared + routed(chosen), atol=2e-4), t
        assert np.allclose(np.asarray(held[0, t]), attended[t] + shared + routed([e for e in chosen if 4 <= e < 8]), atol=2e-4), t


def test_every_wrong_reference_departs_from_the_right_one():
    """The fourteen wrong references of the check, at toy sizes on one stream through a dense
    and a sparse block with a held share: each moves the output or the routing."""
    sparse = _toy_params(5, True, held=4)
    params = [_toy_params(4, False), sparse]
    sizes = {**TOY, "held_lo": 4}
    x = jnp.asarray(np.random.default_rng(7).standard_normal((1, 40, 12)), jnp.float32)
    want, routing = runner.reference_span(params, x, sizes)
    wrong = runner.wrong_references(sizes)
    assert len(wrong) == 14 and set(runner.NEAR_THE_ROUNDING) <= set(wrong) == set(runner.wrong_references(sizes, every=True))
    assert set(runner.wrong_references(sizes, every=False)) == set(runner.NEAR_THE_ROUNDING)
    for name, (variant, precision_alone) in wrong.items():
        out, wrong_routing = runner.reference_span(params, x, **{"sizes": sizes, **variant})
        moved = float(jnp.abs(out.astype(jnp.float32) - want).max() / jnp.abs(want).max())
        rerouted = runner._mismatch_share(runner._choices(wrong_routing), runner._choices(routing))
        assert moved > (1e-5 if precision_alone else 1e-3) or rerouted > 0.01, name  # forty toy tokens hold few near-ties of a router
        assert precision_alone == (name in ("the router's matmul in one bf16 pass", "all bf16, router too"))
    assert runner._router_mismatch_share(params, routing, sizes) == 0.0


# ---- traffic, arithmetic and readers --------------------------------------------------


def test_long_sessions_deals_the_cells_prompts():
    traffic = WORKLOAD["traffic"]
    assert (traffic["generator"], traffic["processes"], traffic["slots_per_process"], traffic["chunk"], traffic["answer_cap"]) == (
        "long_sessions", 4, 8, 2048, 4096)
    assert traffic["prompt_lengths"] == [2048, 4096, 6144, 8192] and traffic["prompt_weights"] == [0.4, 0.3, 0.2, 0.1]
    assert sorted(long_sessions.sizes(traffic)) == [2048] * 13 + [4096] * 10 + [6144] * 6 + [8192] * 3  # the issue's second traffic: its lever
    assert sum(long_sessions.sizes(traffic)) == 129024 and sum(long_sessions.sizes(traffic)) * 7168 * 2 == pytest.approx(1.85e9, rel=0.01)
    assert max(traffic["prompt_lengths"]) + traffic["answer_cap"] == CONFIG["serving"]["decode_max_len"] == 12288
    assert traffic["chunk"] == CONFIG["serving"]["prompt_chunk"] and CONFIG["serving"]["decode_max_sessions"] >= 33 * 5
    prompt, _steps, rows = runner.check_shape(False)
    assert prompt > CONFIG["rope_scaling"]["original_max_position_embeddings"] and prompt & (prompt - 1) and -(-prompt // 2048) == 3
    assert sala_runner.padded_chunks(traffic["prompt_lengths"] + runner.check_prompts(prompt, rows) + [runner.filler_prompt(prompt, 2048)], 2048) == [512, 2048]
    assert WORKLOAD["why"] == mf.by_name(mf.load_manifest()["workloads"], CELL, "cell")["why"] and len(WORKLOAD["why"]) <= 200


def test_latent_arithmetic_by_hand():
    model = CONFIG["model"]
    assert flops_mla.latent_width(model) == 576
    assert flops_mla.latent_attend_flops(1.0, model) == 64 * (576 + 512) * 2  # 139,264 a position
    assert flops_mla.latent_attend_bytes(1.0, 0.0, model) == 1152  # one position's latent and shared key, bf16
    assert flops_mla.latent_attend_bytes(0.0, 1.0, model) == 64 * (576 + 512) * 2  # a row's absorbed queries in, mixed latents out
    # 16 rows at 8,192 positions: 151 MB read a block a step, memory-bound on a v5e (184 us against 93 us of matmul)
    positions = 16 * 8192.0
    assert flops_mla.latent_attend_bytes(positions, 16.0, model) == pytest.approx(151.0e6 + 2.2e6, rel=0.01)
    assert flops_mla.latent_attend_bytes(positions, 16.0, model) / 819e9 > flops_mla.latent_attend_flops(positions, model) / 197e12


def _observations(**extra):
    series = lambda **values: {"series": values}
    names = ("hivemind_moe_decode_calls_total", "hivemind_moe_decode_steps_total", "hivemind_moe_latent_positions_attended_total")
    before = {name: series(**{"path=batched": 10.0}) for name in names}
    after = {names[0]: series(**{"path=batched": 110.0}), names[1]: series(**{"path=batched": 1610.0}),
             names[2]: series(**{"path=batched": 10.0 + 1600 * 8000.0, "path=direct": 5.0})}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters": {"before": before, "after": after}, **extra}


def test_latent_roofline_reads_the_traced_positions_over_the_scopes_time():
    spec = mf.load_layer_metric("latent_attend_roofline")
    obs = _observations()
    scopes = {"latent_attend": {"seconds": 0.060, "count": 4800.0, "runs": 95.0}}
    traced = dict(obs, scopes=scopes, counters_traced=obs["counters"])
    value = mf.read_metric(spec, traced)
    least = flops_mla.latent_attend_bytes(1600 * 8000.0, 1600.0, CONFIG["model"]) / 819e9  # memory-bound; of 100 programs counted
    assert value == pytest.approx(100.0 * least / 100 * 95 / 0.060, rel=1e-6) and 0 < value < 100
    # where the compiler stages the rows' arrays, the copies' time is the latents' read: it counts with the scope's
    staged = dict(traced, scopes={**scopes, "latent_staging": {"seconds": 0.180, "count": 3040.0, "runs": 95.0}})
    assert mf.read_metric(spec, staged) == pytest.approx(value / 4, rel=1e-6)
    assert any("in the copies that stage" in note for note in staged["notes"])
    assert any("memory-bound" in note and "8000 positions a row" in note for note in traced["notes"])
    assert mf.read_metric(spec, dict(obs, scopes=scopes)) is None  # a runner that does not read the counters at the trace's edges
    assert mf.read_metric(spec, dict(obs, counters_traced=obs["counters"])) is None  # a runner without scopes
    assert mf.read_metric(spec, dict(obs, scopes={"moe_experts": scopes["latent_attend"]}, counters_traced=obs["counters"])) is None
    older = json.loads(json.dumps(obs["counters"]))  # a program without the counter (a parent commit): nothing, and no exception
    for side in older.values():
        del side["hivemind_moe_latent_positions_attended_total"]
    assert mf.read_metric(spec, dict(obs, scopes=scopes, counters_traced=older)) is None
    assert mf.read_metric(mf.load_layer_metric("latent_positions_per_row"), {**obs, "counters": older}) is None
    assert mf.read_metric(mf.load_layer_metric("latent_positions_per_row"), obs) == pytest.approx(8000.0)  # the batched rows alone


def test_cache_gauge_and_lead_in_prefill_readers():
    obs = _observations()
    obs["counters"]["after"].update({"hivemind_moe_decode_cache_bytes": {"series": {"kind=latent": 160 * 12288 * 576 * 2.0}},
                                     "hivemind_moe_decode_cache_entries": {"series": {"kind=latent": 160.0}}})
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.latent"), obs) == pytest.approx(14.155776)
    assert mf.read_metric(mf.load_layer_metric("decode_cache_mb_per_session.latent"), _observations()) is None
    lead = {side: {"hivemind_moe_decode_prefill_seconds_total": {"series": {"": seconds}},
                   "hivemind_moe_decode_prefill_positions_total": {"series": {"": positions}}}
            for side, seconds, positions in (("before", 1.0, 1000.0), ("after", 21.0, 1000.0 + 5 * 129024))}
    spec = mf.load_layer_metric("prefill_ms_per_1k_positions.latent")
    assert mf.read_metric(spec, {**obs, "counters_lead": lead}) == pytest.approx(20.0 / (5 * 129024) * 1e6)
    assert mf.read_metric(spec, obs) is None  # a runner that does not read the lead-in
    programs = {"jit_batched_step_latent": {"seconds": 0.5, "count": 100.0}, "jit_prefill_latent_2048": {"seconds": 9.0, "count": 3.0}}
    assert mf.read_metric(mf.load_layer_metric("decode_program_ms.latent"), {"programs": programs}) == pytest.approx(5.0)


def test_scopes_are_read_off_the_batched_program():
    """The toy block's own batched program at a bucket of two: operations lie in `latent_absorb`,
    `latent_attend` and `moe_experts`; its chunk program holds `latent_expand`."""
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden = REHEARSAL["model"]["hidden_size"]
    module = name_to_block["deepseek_v3_block"](hidden, **runner.block_kwargs(REHEARSAL, 1))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, hidden)))["params"]
    [cache] = module.init_decode_cache(1, 128)
    step = jax.jit(lambda p, x, cache, index: module.apply({"params": p}, x, cache, index, mutable=["routing", "attended"]))
    text = step.lower(params, jnp.zeros((2, 1, hidden)), (cache, cache), jnp.array([70, 90])).compile().as_text()
    assert {"latent_absorb", "latent_attend", "moe_experts"} <= set(runner.scope_of_instructions(text, runner.SCOPES).values())
    every = runner.instruction_scopes(text)
    assert None in every.values() and {scope for scope in every.values() if scope} >= {"latent_attend", "moe_experts"}
    assert {name for name, scope in every.items() if scope} == set(runner.scope_of_instructions(text, runner.SCOPES))
    chunk = step.lower(params, jnp.zeros((1, 32, hidden)), cache, jnp.int32(64)).compile().as_text()
    assert {"latent_expand", "latent_attend"} <= set(runner.scope_of_instructions(chunk, runner.SCOPES).values())


def test_copies_of_a_rows_array_are_the_staging():
    """The compiler's asynchronous copies carry no `op_name`: those of exactly a row's array are
    `latent_staging`, a smaller one (the activations) is not, and without the size none is."""
    text = """
  %copy-start.63 = (bf16[1,12288,576]{1,2,0:T(8,128)(2,1)S(1)}, bf16[1,12288,576]{1,2,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%p.3)
  %copy-done.63 = bf16[1,12288,576]{1,2,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.63)
  %copy-done.65 = f32[32,1,7168]{2,1,0:T(1,128)S(1)} copy-done(%copy-start.65)
  %fusion.681 = f32[64,12288]{1,0} fusion(%copy-done.63), kind=kOutput, metadata={op_name="jit(step)/jit(_step_rows)/latent_attend/dot_general"}
"""
    row = {12288 * 576 * 2}
    assert runner.instruction_scopes(text, row) == {"copy-start.63": "latent_staging", "copy-done.63": "latent_staging", "copy-done.65": None,
                                                    "fusion.681": "latent_attend"}
    assert set(runner.instruction_scopes(text).values()) == {None, "latent_attend"}


def test_scope_seconds_tells_programs_of_one_name_by_what_they_ran(monkeypatch):
    """The dense block's and the sparse blocks' batched programs share a NAME and their
    instruction names collide (`fusion.7` is an attention dot in one text and an MLP fusion in
    the other): a traced program, known by its id, takes the scopes of the candidate that holds
    most of the instruction names its runs executed."""
    from perf import trace_reduce

    dense = {"fusion.7": "latent_attend", "fusion.8": None, "fusion.9": None, "copy.1": None}
    sparse = {"fusion.7": None, "fusion.8": "latent_attend", "fusion.9": "moe_experts", "ragged-dot.1": "moe_experts", "sort.3": None}
    ms = 1e6  # nanoseconds
    modules = [("jit_batched_step_latent(11)", 0.0, 10 * ms), ("jit_batched_step_latent(22)", 20 * ms, 10 * ms),
               ("jit_batched_step_latent(22)", 40 * ms, 10 * ms), ("jit_prefill_latent_2048(33)", 60 * ms, 10 * ms)]
    ops = [("%fusion.7 = f32[8] fusion(...)", 1 * ms, 2 * ms), ("%fusion.8 = f32[8] fusion(...)", 4 * ms, 1 * ms), ("%copy.1 = copy(...)", 6 * ms, 1 * ms)]
    for start in (20 * ms, 40 * ms):
        ops += [("%fusion.7 = f32[8] fusion(...)", start + 1 * ms, 3 * ms), ("%fusion.8 = f32[8] fusion(...)", start + 4 * ms, 1 * ms),
                ("%ragged-dot.1 = ragged-dot(...)", start + 6 * ms, 2 * ms), ("%sort.3 = sort(...)", start + 8 * ms, 1 * ms)]
    ops.append(("%fusion.8 = f32[8] fusion(...)", 61 * ms, 5 * ms))  # another program's: not counted
    planes = {"/device:TPU:0": {runner.MODULE_LINE: modules, "XLA Ops": ops}, "/host:CPU": {"python": [("x", 0.0, 1.0)]}}
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: "a-trace")
    monkeypatch.setattr(trace_reduce, "load_planes", lambda _path: planes)
    got = runner.scope_seconds("anywhere", [dense, sparse])
    assert got["latent_attend"] == {"seconds": pytest.approx(0.002 + 2 * 0.001), "count": 3.0, "runs": 3.0}
    assert got["moe_experts"] == {"seconds": pytest.approx(2 * 0.002), "count": 2.0, "runs": 2.0}  # the sparse program's two runs alone
    assert runner.scope_seconds("anywhere", []) == {}
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _dir: None)
    assert runner.scope_seconds("anywhere", [dense, sparse]) == {}


def test_the_cell_rehearses_end_to_end():
    """`python3 -m perf.run --rehearse-cpu --trace 1` of the cell: exit code 3 (passed, and no
    measurement), no compilation inside the window, the chunked reference check and the
    fourteen wrong references in the log, the new metrics among those that would be reported."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    run = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--trace", "1", "--workload", CELL, "--seed", "2147483659"],
                         capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    log = run.stderr
    assert run.returncode == 3, log[-4000:]
    assert "inside it 0" in log and "in chunks of 64" in log and log.count("for the record, the reference with") == 14
    assert "rehearsal passed=True" in log and "failed=0" in log and "dense, sparse, sparse, sparse, sparse: the model's 2-6" in log
    listed = log[log.index("metrics that would be reported"):]
    for name in ("decode_cache_mb_per_session.latent", "latent_positions_per_row", "prefill_ms_per_1k_positions.latent",
                 "moe_held_pairs_per_step", "moe_load_max_over_mean.held", "decode_rows_per_batch"):
        assert name in listed, name
