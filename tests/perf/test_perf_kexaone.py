"""What `k-exaone-236b-span5` brings to the benchmark: its configuration file against
the catalog's row, the plain reference against a per-token loop, the runner's block
kwargs and reference layers, the new readers on hand-made observations (and on a
program that lacks what they read), and the cell's rehearsal end to end (CPU)."""

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

from perf import manifest as mf  # noqa: E402
from perf.reference import k_exaone_block as reference  # noqa: E402
from perf.runners import hybrid_moe_block_server as runner  # noqa: E402

CONFIG = mf.load_json(mf.PERF / "configs" / "k-exaone-236b-span5.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = "k-exaone-236b-span5.longgen32"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_KEYS = [key for key in CONFIG if key not in ("name", "source", "runner")][: list(CONFIG).index("catalog_keys") - 3]
CUT = {"num_hidden_layers": (48, 5), "num_experts": (128, 8), "num_nextn_predict_layers": (1, 0)}
# the widths the issue names, as published
WIDTHS = {"hidden_size": 6144, "num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128, "sliding_window": 128,
          "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_experts_per_tok": 8, "num_shared_experts": 1,
          "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "norm_topk_prob": True, "rms_norm_eps": 1e-05,
          "first_k_dense_replace": 1, "sliding_window_pattern": "LLLG", "vocab_size": 153600}


def _catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    found = [row for row in rows if row["name"] == "K-EXAONE-236B-A23B"]
    if not found:
        pytest.skip("the catalog on this machine has no K-EXAONE-236B-A23B row")
    return found[0]


@pytest.mark.parametrize("key", PUBLISHED_KEYS)
def test_configuration_holds_every_published_value(key):
    """Every key of the catalog row's config, at the top level of the file and in the
    `model` section the runner reads, unchanged except for the three cuts `reduced` lists."""
    assert CONFIG[key] == CONFIG["model"][key]
    if key in CUT:
        assert key in CONFIG["reduced"] and CONFIG[key] == CUT[key][1] and CONFIG["published"][key] == CUT[key][0]
    else:
        assert key not in CONFIG["reduced"]
        if key in WIDTHS:
            assert CONFIG[key] == WIDTHS[key] and type(CONFIG[key]) is type(WIDTHS[key])
        row = _catalog_row()  # skips, and does not fail, where the catalog or the row is not there
        assert CONFIG[key] == row["config"][key] and type(CONFIG[key]) is type(row["config"][key])


def test_published_keys_are_the_catalogs():
    row = _catalog_row()
    assert sorted(PUBLISHED_KEYS) == sorted(row["config"]) and row["source_url"] == CONFIG["source"]
    assert {key: row["config"][key] for key in CUT} == {key: cut[0] for key, cut in CUT.items()}
    assert sorted(CONFIG["reduced"]) == sorted(CUT) and set(CONFIG["reduced_why"]) == set(CUT)


def test_block_kinds_and_the_share():
    """Blocks 0-4 are L L L G L with block 0 dense: a whole LLLG period, window to full
    3 : 1 among the sparse blocks; 8 of 128 experts held, the router at 128 outputs."""
    kwargs = [runner.block_kwargs(CONFIG, index) for index in range(CONFIG["model"]["num_hidden_layers"])]
    assert [kw["window"] for kw in kwargs] == [128, 128, 128, 0, 128]
    assert [kw["ffn_inner"] for kw in kwargs] == [18432, 0, 0, 0, 0]
    assert all(kw["num_experts"] == 128 and kw["held"] == 8 and kw["held_lo"] == 0 and kw["experts_per_token"] == 8
               and kw["num_heads"] * kw["head_dim"] == 8192 != CONFIG["model"]["hidden_size"] for kw in kwargs)
    assert runner.reference_layers(CONFIG) == [{"window": w, "rope": w > 0} for w in (128, 128, 128, 0, 128)]
    assert CONFIG["share"]["chips_sharing_a_layer"] * CONFIG["model"]["num_experts"] == CONFIG["share"]["router_outputs"] == 128
    toy = [runner.block_kwargs(REHEARSAL, index) for index in range(5)]
    assert [kw["window"] for kw in toy] == [8, 8, 8, 0, 8] and toy[1]["held"] == 4 and toy[1]["num_experts"] == 16


def _toy_block(kind_index: int, seed: int = 0):
    from hivemind_tpu.moe.server.layers import name_to_block

    hidden = REHEARSAL["model"]["hidden_size"]
    module = name_to_block["exaone_moe_block"](hidden, **runner.block_kwargs(REHEARSAL, kind_index))
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((2, 21, hidden)), jnp.float32)
    return module, module.init(jax.random.PRNGKey(20 + kind_index), x[:1, :4])["params"], x


@pytest.mark.parametrize("index", [1, 3])  # sparse/window, sparse/full
def test_reference_against_a_per_token_loop(index):
    """The reference's block, position by position in float64 numpy: the window as a
    loop bound, rope by hand on the sliding block and none on the full one, the router's
    bias in the choice only, the chosen scores renormalised and scaled, the held
    experts' part and the shared expert."""
    _module, params, x = _toy_block(index)
    layer, sizes = runner.reference_layers(REHEARSAL)[index], runner.reference_sizes(REHEARSAL)
    got = np.asarray(reference.span([params], x, [layer], **sizes), np.float64)
    p = jax.tree_util.tree_map(lambda leaf: np.asarray(leaf, np.float64), params)
    heads, kv, dim, eps = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"], sizes["rms_eps"]
    norm = lambda v, scale: v / np.sqrt((v**2).mean(-1, keepdims=True) + eps) * scale
    silu = lambda a: a / (1 + np.exp(-a))
    swiglu = lambda m, gate, up, down: (silu(m @ gate) * (m @ up)) @ down

    def rope(v, position):
        inv = sizes["rope_theta"] ** (-np.arange(0, dim, 2) / dim)
        angle = np.concatenate([position * inv, position * inv])
        return v * np.cos(angle) + np.concatenate([-v[dim // 2:], v[:dim // 2]]) * np.sin(angle)

    want = np.zeros_like(got)
    for b in range(x.shape[0]):
        xb = np.asarray(x[b], np.float64)
        n = norm(xb, p["attention_norm"]["scale"])
        q = norm((n @ p["query"]["kernel"]).reshape(-1, heads, dim), p["query_norm"]["scale"])
        k = norm((n @ p["key"]["kernel"]).reshape(-1, kv, dim), p["key_norm"]["scale"])
        v = (n @ p["value"]["kernel"]).reshape(-1, kv, dim)
        for t in range(xb.shape[0]):
            first = max(0, t - layer["window"] + 1) if layer["window"] else 0
            context = np.zeros((heads, dim))
            for h in range(heads):
                g = h // (heads // kv)
                qt = rope(q[t, h], t) if layer["rope"] else q[t, h]
                keys = np.stack([rope(k[s, g], s) if layer["rope"] else k[s, g] for s in range(first, t + 1)])
                scores = keys @ qt / np.sqrt(dim)
                weights = np.exp(scores - scores.max())
                context[h] = (weights / weights.sum()) @ v[first:t + 1, g]
            hidden = xb[t] + context.reshape(-1) @ p["attention_out"]["kernel"]
            m = norm(hidden, p["ffn_norm"]["scale"])
            s = 1 / (1 + np.exp(-(m @ p["router"])))
            chosen = np.argsort(-(s + p["router_bias"]))[:sizes["experts_per_token"]]
            y = hidden + swiglu(m, p["shared_gate"]["kernel"], p["shared_up"]["kernel"], p["shared_down"]["kernel"])
            for e in chosen:
                local = e - sizes["held_lo"]
                if 0 <= local < p["experts_gate"].shape[0]:  # held here; the others are another chip's
                    y += sizes["routed_scale"] * s[e] / s[chosen].sum() * swiglu(
                        m, p["experts_gate"][local], p["experts_up"][local], p["experts_down"][local])
            want[b, t] = y
    assert np.abs(got - want).max() / np.abs(want).max() <= 1e-5


def _observation(held_pairs=0.0, hit=0.0, calls=0.0, kernel_s=0.0, events=0, programs=None, gauges=None, traced=None):
    series = lambda value: {"series": {"path=batched": value}}
    after = {"hivemind_moe_held_pairs_total": series(held_pairs), "hivemind_moe_routed_pairs_total": series(16 * held_pairs),
             "hivemind_moe_experts_hit_total": series(hit), "hivemind_moe_expert_layer_calls_total": series(calls)}
    for (metric, kind), value in (gauges or {}).items():
        after.setdefault(metric, {"series": {}})["series"][f"kind={kind}"] = value
    edges = {}
    if traced:  # (held pairs, held experts hit, calls) the program counted between the trace's two edges, on top of 5 before it
        before = {name: series(5.0) for name in ("hivemind_moe_held_pairs_total", "hivemind_moe_experts_hit_total",
                                                 "hivemind_moe_expert_layer_calls_total")}
        edges = {"counters_traced": {"before": before, "after": {name: series(5.0 + value) for name, value in zip(before, traced)}}}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters": {"before": {}, "after": after}, "programs": programs or {}, **edges,
            "trace": {"devices": 1, "ops": {"ragged-dot-none": {"seconds": kernel_s * 0.9, "count": events},
                                            "ragged-dot-metadata": {"seconds": kernel_s * 0.1, "count": events},
                                            "fusion": {"seconds": 1.0, "count": 5}}}}


def test_held_roofline_reader_by_hand():
    """The work and the kernel time come from the same seconds: between the trace's two
    edges the program counted 96 calls of 16 held pairs on 7 held experts each (the
    window's mean call, 4 pairs on 2 experts, is NOT what is read), at the EXPERTS' width
    (2048, not the dense 18432): memory-bound, seven experts' float32 weights a call."""
    from perf.readers import moe_roofline_held

    spec = mf.load_layer_metric("moe_experts_roofline.kexaone")["args"]
    expert = 3 * 6144 * 2048
    least_per_call = (7 * expert * 4 + 16 * (2 * 6144 + 4 * 2048) * 4) / 819e9
    obs = _observation(held_pairs=4_000.0, hit=2_000.0, calls=1000.0, kernel_s=0.3, events=300,  # 100 calls traced, 3 ms each
                       traced=(96 * 16.0, 96 * 7.0, 96.0))  # a counter lags its program: 96 of the 100 were counted inside
    assert moe_roofline_held.read(obs, **spec) == pytest.approx(100.0 * least_per_call * 100 / 0.3, rel=1e-9)
    assert 40.0 < moe_roofline_held.read(obs, **spec) < 45.0
    assert any("memory-bound" in note for note in obs["notes"])
    assert mf.read_metric(mf.load_layer_metric("moe_held_pairs_per_step"), obs) == pytest.approx(4.0)
    del obs["counters_traced"]  # a runner that does not read the counters at the trace's edges
    assert moe_roofline_held.read(obs, **spec) is None


def test_held_load_reader_by_hand():
    """The fullest held expert's pairs over the mean held expert's: 8 held experts."""
    series = lambda value: {"series": {"path=batched": value}}
    obs = {"counters": {"before": {}, "after": {"hivemind_moe_expert_max_pairs_total": series(3_000.0),
                                                "hivemind_moe_held_pairs_total": series(8_000.0)}}}
    assert mf.read_metric(mf.load_layer_metric("moe_load_max_over_mean.held"), obs) == pytest.approx(3.0)


def test_program_and_gauge_readers_by_hand():
    programs = {"jit_batched_step_window": {"seconds": 0.9, "count": 300.0}, "jit_batched_step_full": {"seconds": 0.8, "count": 100.0},
                "jit_prefill_window_512": {"seconds": 0.06, "count": 4.0}, "jit_prefill_full_4096": {"seconds": 0.09, "count": 1.0},
                "jit_step_window": {"seconds": 5.0, "count": 3.0}, "jit_batched_step": {"seconds": 7.0, "count": 9.0}}
    gauges = {("hivemind_moe_decode_cache_bytes", "window"): 128 * 524288.0, ("hivemind_moe_decode_cache_entries", "window"): 128.0,
              ("hivemind_moe_decode_cache_bytes", "full"): 32 * 33554432.0, ("hivemind_moe_decode_cache_entries", "full"): 32.0}
    obs = _observation(programs=programs, gauges=gauges)
    read = lambda name: mf.read_metric(mf.load_layer_metric(name), obs)
    assert read("decode_program_ms.window") == pytest.approx(3.0) and read("decode_program_ms.full") == pytest.approx(8.0)
    # ten prompts' programs held the device 0.3 s for 40,960 padded positions a block, by the program's own counters
    obs["counters"]["after"].update({"hivemind_moe_decode_prefill_seconds_total": {"series": {"_": 0.3}},
                                     "hivemind_moe_decode_prefill_positions_total": {"series": {"_": 40960.0}}})
    assert read("prefill_ms_per_1k_positions") == pytest.approx(1000 * 0.3 / 40.96)
    assert read("decode_cache_mb_per_session.window") == pytest.approx(0.524288)
    assert read("decode_cache_mb_per_session.full") == pytest.approx(33.554432)


@pytest.mark.parametrize("metric", ["moe_held_pairs_per_step", "moe_experts_roofline.kexaone", "decode_cache_mb_per_session.window",
                                    "decode_cache_mb_per_session.full", "decode_program_ms.window", "decode_program_ms.full",
                                    "prefill_ms_per_1k_positions", "moe_load_max_over_mean.held"])
def test_new_readers_return_nothing_on_a_program_without_what_they_read(metric):
    """A parent commit has no held-pairs counter, no cache gauges and no programs named
    by kind (and a runner of its time sums no programs): each reader gives nothing and
    does not raise, with and without a trace."""
    parent = {"config": CONFIG, "device": {"kind": "TPU v5 lite"},
              "counters": {"before": {}, "after": {"hivemind_moe_expert_layer_calls_total": {"series": {"path=batched": 100.0}}}},
              "trace": {"devices": 1, "ops": {"ragged-dot-none": {"seconds": 0.1, "count": 30}}}}
    if metric != "moe_held_pairs_per_step":  # `counter_ratio` reads a counter that is not there as 0; the parent cannot run this cell
        assert mf.read_metric(mf.load_layer_metric(metric), parent) is None
    assert mf.read_metric(mf.load_layer_metric(metric), {"config": CONFIG, "device": {"kind": "TPU v5 lite"}}) is None
    named_otherwise = {**parent, "programs": {"jit_batched_step": {"seconds": 1.0, "count": 10.0}}}
    if metric.startswith("decode_program_ms"):
        assert mf.read_metric(mf.load_layer_metric(metric), named_otherwise) is None


def test_program_seconds_of_a_directory_without_a_trace(tmp_path):
    assert runner.program_seconds(tmp_path) == {}


def test_the_cell_is_in_the_manifest_with_the_traffic_the_issue_names():
    manifest = mf.load_manifest()
    cell = mf.by_name(manifest["workloads"], CELL, "cell")
    traffic = mf.load_workload(CELL)["traffic"]
    assert cell["chips"] == 1 and cell["config"] == "k-exaone-236b-span5" and cell["traffic"] == "longgen32"
    assert {key: traffic[key] for key in ("generator", "processes", "slots_per_process", "prompt_lengths", "prompt_weights",
                                          "sessions_per_slot", "lead_seconds", "trace_seconds")} == {
        "generator": "decode_sessions", "processes": 4, "slots_per_process": 8, "prompt_lengths": [512, 1024, 2048, 4096],
        "prompt_weights": [0.4, 0.3, 0.2, 0.1], "sessions_per_slot": 4, "lead_seconds": 12.0, "trace_seconds": 4.0}
    assert (traffic["answer_min"], traffic["answer_max"]) in ((1024, 2048), (2048, 4096))
    reported = {m["name"] for kind in ("end_to_end", "per_layer") for m in mf.cell_metrics(manifest, CELL, kind)}
    assert {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s", "moe_held_pairs_per_step", "moe_experts_roofline.kexaone",
            "moe_load_max_over_mean.held", "ttft_median_ms", "server_handle_ms.decode", "rpc_overhead_ms.decode", "queue_wait_ms.decode",
            "moe_experts_ms_per_step", "decode_cache_mb_per_session.window", "decode_cache_mb_per_session.full",
            "decode_program_ms.window", "decode_program_ms.full", "prefill_ms_per_1k_positions", "device_idle_share.serve",
            "hbm_peak_gb.serve"} <= reported
    assert "moe_experts_roofline" not in reported and "moe_load_max_over_mean" not in reported  # OLMoE's width and expert count
    assert list(mf.load_workload(CELL)["end_to_end"]) == ["decode_tokens_per_s", "token_gap_p95_ms", "setup_s"]  # as ISSUE 34 defines the cell


def test_the_cell_rehearses_end_to_end():
    """The cell's whole path at toy sizes: server, warm-up, the reference checks with
    every wrong reference, client processes, window, readers. A rehearsal that passed
    exits with code 3."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--workload", CELL, "--trace", "1",
                           "--seconds", "4"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 3, done.stderr[-3000:]
    assert done.stdout.strip() == ""
    for metric in ("moe_held_pairs_per_step", "decode_cache_mb_per_session.full", "decode_cache_mb_per_session.window",
                   "decode_batched_share", "decode_rows_per_batch"):
        assert metric in done.stderr
    assert "dense/window, sparse/window, sparse/window, sparse/full, sparse/window" in done.stderr
    assert "sessions at positions [20, 19, 18, 17]" in done.stderr
    assert "on the program's own router inputs, 0.0000% of" in done.stderr
    layers = runner.reference_layers(CONFIG)
    assert done.stderr.count("for the record, the reference with") == len(runner.wrong_references(layers)) == 11
    # a plain run on the chip computes the three that only `departure_share` tells from the served rounding
    assert list(runner.wrong_references(layers, every=False)) == list(runner.NEAR_THE_ROUNDING)
    assert "gap ms p50 / p90 / p95 / p99" in done.stderr
