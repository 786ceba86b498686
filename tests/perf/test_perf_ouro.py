"""What `ouro-2.6b-span6` brings to the benchmark: its configuration file against the published values
WRITTEN HERE (and against the catalog's row where this machine has one), the parameter and byte counts
its cut is reckoned from, the reference's loop against the same loop written another way, the runner's
block kwargs, the traffic's schedule and the client's norm, the new readers on hand-made observations
(and on a program that lacks what they read), and the cell's rehearsal end to end (CPU)."""

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

from perf import flops_ouro  # noqa: E402
from perf import manifest as mf  # noqa: E402
from perf.reference import ouro_block as reference  # noqa: E402
from perf.runners import looped_block_server as runner  # noqa: E402
from perf.traffic import looped_sessions  # noqa: E402

NAME = "ouro-2.6b-span6"
CONFIG = mf.load_json(mf.PERF / "configs" / f"{NAME}.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = f"{NAME}.loopgen32"
WORKLOAD = mf.load_workload(CELL)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# the published values, as ISSUE 56 section A lists them (the catalog's row, copied here so that the test holds
# without the catalog); num_hidden_layers is the one key the configuration changes
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128, "intermediate_size": 5632,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "layer_types": ["full_attention"] * 48, "num_hidden_layers": 48, "total_ut_steps": 4,
    "early_exit_threshold": 1, "max_position_embeddings": 65536, "vocab_size": 49152, "tie_word_embeddings": False,
    "max_window_layers": 48, "model_type": "ouro",
}
NEW_METRICS = {"decode_program_ms.looped": "decode_tokens_per_s", "looped_step_roofline": "decode_tokens_per_s",
               "decode_cache_mb_per_session.looped": "decode_tokens_per_s", "decode_passes_per_cohort": "decode_tokens_per_s",
               "decode_pass_steps_per_token": "decode_tokens_per_s", "rpc_overhead_ms.looped": "token_gap_p95_ms",
               "loop_between_ms": "token_gap_p95_ms", "prefill_ms_per_1k_positions.looped": "decode_tokens_per_s"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_every_published_value(key):
    """Both copies (the top level, which the driver compares with the catalog, and `model`, which the runner reads)."""
    want = 6 if key == "num_hidden_layers" else PUBLISHED[key]
    assert CONFIG[key] == want and CONFIG["model"][key] == want, (key, CONFIG[key], CONFIG["model"][key])
    assert type(CONFIG[key]) is type(want)


def test_configuration_sections_and_the_cut():
    assert CONFIG["reduced"] == ["num_hidden_layers"] and CONFIG["published"] == {"num_hidden_layers": 48}
    assert CONFIG["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json" and CONFIG["runner"] == "looped_block_server"
    assert CONFIG["model"]["first_block"] == 0 and {key: value for key, value in CONFIG["model"].items() if key != "first_block"} == {
        key: CONFIG[key] for key in PUBLISHED}
    assert {"sandwich_norms", "no_biases", "norm_between_passes", "cache_per_pass", "exit_gate"} <= set(CONFIG["assumed"])
    assert {"reduced_why", "deployment", "tolerances", "rehearsal", "serving"} <= set(CONFIG)
    serving = CONFIG["serving"]
    assert serving["expert_cls"] == "ouro_block" and serving["param_dtype"] == "float32" and serving["activation_compression"] == "float16"
    traffic = WORKLOAD["traffic"]
    assert serving["decode_max_len"] == max(traffic["prompt_lengths"]) + traffic["answer_cap"] and traffic["answer_cap"] % 128 == 0
    assert serving["decode_max_sessions"] == 48 * CONFIG["num_hidden_layers"]  # a session counts ONCE a block, whatever its passes
    assert traffic["passes"] == CONFIG["total_ut_steps"] and looped_sessions.RMS_EPS == CONFIG["rms_norm_eps"]
    assert set(CONFIG["tolerances"]) >= {"decode_rel", "decode_rms_rel", "departure_share", "why"}


def test_configuration_is_the_catalogs_row_but_for_the_cut():
    """Where this machine has the catalog and the row: every key of the row's `config`, value for value, but the cut."""
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    found = [row for row in rows if row["name"] == "Ouro-2.6B"]
    if not found:
        pytest.skip("the catalog on this machine has no Ouro-2.6B row")
    [row] = found
    assert CONFIG["source"] == row["source_url"]
    differing = [key for key, value in row["config"].items() if CONFIG.get(key, "absent") != value]
    assert differing == CONFIG["reduced"], differing
    assert row["config"] == PUBLISHED, "the values written in this test are not the catalog's"


def test_parameter_and_byte_counts_by_hand():
    model = {**CONFIG["model"], "num_hidden_layers": 48}
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert flops_ouro.block_params(model) == block == 51_388_416
    whole = 48 * block + 2 * 49152 * 2048 + 2048 + 2049
    assert flops_ouro.model_params(model, 48) == whole == 2_667_974_657  # the row's "2.6B"
    assert flops_ouro.model_params(model, 48) - 48 * block == 201_330_689  # embedding, head, final norm, gate
    assert flops_ouro.position_bytes(model) == 2 * 16 * 128 * 2 == 8192
    assert flops_ouro.position_bytes_all_passes(model) == 32768  # a position a block: once a pass
    assert flops_ouro.session_cache_bytes(model, 1536) == 4 * 1536 * 8192 == 50_331_648
    assert 6 * block * 4 == pytest.approx(1.233e9, rel=1e-3)  # the span's float32 weights
    # one batched program of 16 rows at 1,100 positions each: the weights once, the rows' keys and values, their hidden states
    rows, positions = 16.0, 16 * 1100.0
    by_hand = block * 4 + positions * 8192 + rows * 2 * 2048 * 4
    assert flops_ouro.step_bytes(1.0, rows, positions, model) == by_hand == pytest.approx(349.99e6, rel=1e-3)
    assert flops_ouro.step_flops(rows, positions, model) == 2 * rows * (block - 4 * 2048) + 4 * positions * 16 * 128
    assert flops_ouro.step_bytes(3.0, 3 * rows, 3 * positions, model) == 3 * by_hand


def test_benchmark_lists_the_cell_and_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.by_name(manifest["workloads"], CELL, "cell")
    assert cell == {"name": CELL, "config": NAME, "traffic": "loopgen32", "chips": 1, "why": WORKLOAD["why"]} and len(WORKLOAD["why"]) <= 200
    entry = mf.by_name(manifest["configs"], NAME, "configuration")
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"] and entry["file"] == f"perf/configs/{NAME}.json"
    reported = {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "per_layer")}
    appended = {"server_handle_ms.decode", "queue_wait_ms.decode", "decode_batched_share", "decode_rows_per_batch", "decode_assemble_ms",
                "decode_step_ms", "decode_scatter_ms", "transfer_kb_per_token.decode", "device_idle_share.serve", "hbm_peak_gb.serve",
                "idle_host_dispatch_share.serve", "idle_unlabelled_share.serve"}
    assert set(NEW_METRICS) <= reported and appended <= reported
    # its subtraction assumes one request a token; no session opens in the window
    assert not reported & {"rpc_overhead_ms.decode", "ttft_median_ms", "wire_frames_per_token.decode"}
    for name in reported:
        assert mf.load_layer_metric(name)["name"] == name
    for name, moves in NEW_METRICS.items():  # a later cell may be appended to any of these lists
        entry = mf.by_name(manifest["per_layer"], name, "metric")
        assert CELL in entry["workloads"] and entry["moves"] == moves
    assert mf.by_name(manifest["per_layer"], "looped_step_roofline", "metric")["unit"] == "%"
    assert {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s"} <= {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "end_to_end")}
    assert {name: spec["reader"] for name, spec in WORKLOAD["end_to_end"].items()} == {
        "decode_tokens_per_s": "rate", "token_gap_p95_ms": "percentile", "setup_s": "observed"}


# ---- the reference, and what the runner makes of the configuration ----------------------------------


def _toy_params(seed: int, hidden=16, heads=2, inner=24):
    rng = np.random.default_rng(seed)
    draw = lambda rows, cols: {"kernel": jnp.asarray(rng.standard_normal((rows, cols)) / np.sqrt(rows), jnp.float32)}
    scale = lambda: {"scale": jnp.asarray(1.0 + 0.1 * rng.standard_normal(hidden), jnp.float32)}
    return {"attention_norm": scale(), "attention_out_norm": scale(), "ffn_norm": scale(), "ffn_out_norm": scale(),
            "query": draw(hidden, hidden), "key": draw(hidden, hidden), "value": draw(hidden, hidden), "attention_out": draw(hidden, hidden),
            "ffn_gate": draw(hidden, inner), "ffn_up": draw(hidden, inner), "ffn_down": draw(inner, hidden)}


TOY = dict(num_heads=2, num_kv_heads=2, rope_theta=1e6, rms_eps=1e-6)


def test_the_loop_is_four_walks_with_the_norm_between_and_returns_every_pass():
    """`span` against the same loop written out by hand from `block`, and a block against a loop over positions and heads."""
    params, x = [_toy_params(1), _toy_params(2)], jnp.asarray(np.random.default_rng(3).standard_normal((1, 7, 16)), jnp.float32)
    final = jnp.asarray(1.0 + 0.1 * np.random.default_rng(4).standard_normal(16), jnp.float32)
    outs = np.asarray(reference.span(params, final, x, passes=4, **TOY))
    assert outs.shape == (4, 1, 7, 16)
    norm = lambda t: t / jnp.sqrt((t * t).mean(-1, keepdims=True) + 1e-6) * final
    with jax.default_matmul_precision("highest"):
        current = x
        for u in range(4):
            current = norm(reference.block(params[1], reference.block(params[0], current, **TOY), **TOY))
            np.testing.assert_allclose(outs[u], np.asarray(current), rtol=1e-5, atol=1e-5)
        # one block, position by position and head by head, in numpy
        p = jax.tree_util.tree_map(np.asarray, params[0])
        rms = lambda t, name: t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6) * p[name]["scale"]
        xs = np.asarray(x)[0]
        normed = rms(xs, "attention_norm")
        q, k, v = (normed @ p[name]["kernel"] for name in ("query", "key", "value"))

        def rope(t, position):  # [heads, dim] at one position, rotate-half layout
            dim = t.shape[-1]
            angles = position * 1e6 ** (-np.arange(0, dim, 2) / dim)
            cos, sin = np.cos(np.concatenate([angles, angles])), np.sin(np.concatenate([angles, angles]))
            return t * cos + np.concatenate([-t[..., dim // 2:], t[..., :dim // 2]], -1) * sin

        context = np.zeros_like(xs)
        for t in range(7):
            for head in range(2):
                cut = slice(8 * head, 8 * head + 8)
                query = rope(q[t, cut], t)
                scores = np.array([query @ rope(k[s, cut], s) for s in range(t + 1)]) / np.sqrt(8.0)
                weights = np.exp(scores - scores.max())
                context[t, cut] = (weights / weights.sum()) @ v[:t + 1, cut]
        h = xs + rms(context @ p["attention_out"]["kernel"], "attention_out_norm")
        gate, up = rms(h, "ffn_norm") @ p["ffn_gate"]["kernel"], rms(h, "ffn_norm") @ p["ffn_up"]["kernel"]
        y = h + rms((gate / (1 + np.exp(-gate)) * up) @ p["ffn_down"]["kernel"], "ffn_out_norm")
        np.testing.assert_allclose(np.asarray(reference.block(params[0], x, **TOY))[0], y, rtol=2e-4, atol=2e-4)
    # the wrong loops differ, and three passes are the first three of four
    assert np.abs(np.asarray(reference.span(params, final, x, passes=4, norm_between=False, **TOY)) - outs)[1:].max() > 0.1
    assert np.abs(np.asarray(reference.span(params, final, x, passes=4, sandwich=False, **TOY)) - outs).max() > 0.1
    np.testing.assert_array_equal(np.asarray(reference.span(params, final, x, passes=3, **TOY)), outs[:3])
    shared = np.asarray(reference.span_through_one_cache(params, final, x, prompt=4, passes=4, **TOY))
    np.testing.assert_allclose(shared[:, :, :4], outs[:, :, :4], rtol=1e-5, atol=1e-5)  # the prompt is the model's own
    assert np.abs(shared[:, :, 4:] - outs[:, :, 4:]).max() > 0.05


def test_the_first_decoded_pass_of_a_shared_cache_differs_where_the_passes_do():
    """What `span_through_one_cache` computes at the first decoded position: pass 0 attends the prompt's keys of the
    LAST pass (the cache held them), so it differs from the model exactly when the passes' keys differ."""
    params, x = [_toy_params(5)], jnp.asarray(np.random.default_rng(6).standard_normal((1, 6, 16)), jnp.float32)
    final = jnp.ones(16, jnp.float32)
    right = np.asarray(reference.span(params, final, x, passes=2, **TOY))
    shared = np.asarray(reference.span_through_one_cache(params, final, x, prompt=5, passes=2, **TOY))
    assert np.abs(shared[0, :, 5] - right[0, :, 5]).max() > 1e-3 and np.allclose(shared[:, :, :5], right[:, :, :5], atol=1e-5)


def test_block_kwargs_are_the_configurations_sizes():
    kwargs = runner.block_kwargs(CONFIG["model"])
    assert kwargs == dict(num_heads=16, num_kv_heads=16, head_dim=128, ffn_inner=5632, rope_theta=1e6, rms_eps=1e-6, total_ut_steps=4)
    assert runner.reference_sizes(CONFIG["model"]) == dict(num_heads=16, num_kv_heads=16, rope_theta=1e6, rms_eps=1e-6)
    from hivemind_tpu.moe.server.layers import name_to_block

    module = name_to_block["ouro_block"](2048, **kwargs)
    assert module.decode_passes == 4 and module.decode_cache_kind == "looped"
    shapes = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048), jnp.float32))["params"])
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes)) == flops_ouro.block_params(CONFIG["model"])
    cache = jax.eval_shape(lambda: module.init_decode_cache(1, 1536))
    assert sum(leaf.size * leaf.dtype.itemsize for leaf in cache) * 4 == flops_ouro.session_cache_bytes(CONFIG["model"], 1536)
    assert set(runner.wrong_references(CONFIG["model"])) >= {"one cache shared by the passes", "F left out between the passes",
                                                               "the output norms left out (plain pre-norm)", "three passes for four",
                                                               "float8 weights and block inputs"}


# ---- the traffic ---------------------------------------------------------------------------------------


def test_looped_sessions_deals_the_same_prompts_for_every_seed():
    traffic = WORKLOAD["traffic"]
    assert (traffic["processes"], traffic["slots_per_process"], traffic["chunk"], traffic["passes"]) == (4, 8, 1024, 4)
    dealt = {}
    for seed in (0, 7, 3_900_000_011, 2**31 + 5):
        plan = looped_sessions.schedule(traffic, seed)
        slots = [slot for process in plan["processes"] for slot in process]
        assert [len(process) for process in plan["processes"]] == [8] * 4 and all(len(slot) == 1 for slot in slots)
        dealt[seed] = sorted(slot[0][0] for slot in slots)
        assert all(slot[0][1:3] == [1024, traffic["answer_cap"]] and slot[0][4:] == [4, looped_sessions.norm_seed(seed)] for slot in slots)
        assert len({slot[0][3] for slot in slots}) == 32  # a stream of its own a slot
    assert all(lengths == [256] * 13 + [512] * 10 + [768] * 6 + [1024] * 3 for lengths in dealt.values())
    assert sum(dealt[0]) == 16_128
    assert looped_sessions.schedule(traffic, 7) == looped_sessions.schedule(traffic, 7) != looped_sessions.schedule(traffic, 8)
    assert looped_sessions.norm_seed(7) != looped_sessions.norm_seed(8) and 0 <= looped_sessions.norm_seed(2**31 + 5) < 2**31 - 1


def test_the_clients_norm_is_the_references():
    scale = looped_sessions.final_norm(5, 64)
    assert scale.dtype == np.float32 and scale.shape == (64,) and 0.5 < scale.min() and scale.max() < 1.5 and scale.std() > 0.05
    np.testing.assert_array_equal(scale, looped_sessions.final_norm(5, 64))
    x = np.random.default_rng(1).standard_normal((1, 3, 64)).astype(np.float32) * 7
    got = looped_sessions.apply_final_norm(x, scale)
    want = np.asarray(reference._rms_norm(jnp.asarray(x), jnp.asarray(scale), 1e-6))
    assert got.dtype == np.float32 and np.array_equal(got, got.astype(np.float16).astype(np.float32))  # what the float16 wire carries
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_a_slot_walks_every_token_through_the_passes_and_samples_its_own_time():
    """`drive_slot` against a pipe that records its calls: the prompt and every token go through `passes` calls in
    pass order, a token is counted when its last pass returns, and `between_ms` holds passes - 1 samples a token."""
    import time

    calls = []

    class Pipe:
        def decode_step(self, x, session, reset=False, loop_pass=0):
            calls.append((x.shape[1], reset, loop_pass))
            return np.asarray(x, np.float32) + 1.0

        def close_decode_session(self, session):
            calls.append("closed")

    out = looped_sessions.new_result()
    now = time.monotonic()
    looped_sessions.drive_slot(Pipe(), [[24, 32, 6, 11, 4, 9]], dict(begin=now + 0.05, end=now + 30.0, hidden=8, tag="t", slot=0, slots=1), out)
    assert calls[:4] == [(24, True, 0), (24, True, 1), (24, True, 2), (24, True, 3)] and calls[-1] == "closed"
    steps = calls[4:-1]
    assert steps == [(1, False, u) for _token in range(5) for u in range(4)]  # the cap of 6 counts the prompt's last position
    assert out["taken"] == [6] and out["attempted"] == 1 and out["completed"] == 1 and out["failed"] == 0 and not out["errors"]
    assert out["tokens"] == len(out["token_gap_ms"]) and len(out["between_ms"]) == 3 * out["tokens"] and len(out["prefill_s"]) == 1


# ---- the new readers, on hand-made observations -----------------------------------------------------------


def _snapshot(**metrics):
    return {name: {"type": "counter", "series": dict(series)} for name, series in metrics.items()}


def test_looped_roofline_reads_the_programs_bytes_over_their_device_time():
    calls, steps, attended = "hivemind_moe_decode_calls_total", "hivemind_moe_decode_steps_total", "hivemind_moe_looped_positions_attended_total"
    before = _snapshot(**{calls: {"path=batched": 100.0}, steps: {"path=batched": 1600.0}, attended: {"path=batched": 1_000_000.0}})
    after = _snapshot(**{calls: {"path=batched": 160.0}, steps: {"path=batched": 2560.0}, attended: {"path=batched": 2_056_000.0}})
    obs = {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters_traced": {"before": before, "after": after},
           "programs": {"jit_batched_step_looped": {"seconds": 60 * 0.0007, "count": 60.0}, "jit_upload": {"seconds": 1.0, "count": 9.0}}}
    spec = mf.load_layer_metric("looped_step_roofline")
    # 60 programs of 16 rows at 1,100 positions a row: 349.99 MB a program at 819 GB/s is 427.3 us; traced at 700 us
    assert mf.read_metric(spec, obs) == pytest.approx(100 * (349.99e6 / 819e9) / 0.0007, rel=1e-3)
    assert any("memory-bound" in note and "16.0 rows" in note and "1100 positions" in note for note in obs["notes"])
    for broken in ({**obs, "programs": {}}, {key: value for key, value in obs.items() if key != "counters_traced"},
                   {**obs, "counters_traced": {"before": {k: v for k, v in before.items() if k != attended}, "after": {k: v for k, v in after.items() if k != attended}}}):
        assert mf.read_metric(spec, broken) is None  # a parent's program, a plain run, a runner that sums no programs: nothing, and no raise


def test_the_other_new_metrics_read_what_they_say():
    passes, cohorts, pass_steps = "hivemind_moe_decode_cohort_passes_total", "hivemind_moe_decode_cohorts_total", "hivemind_moe_decode_pass_steps_total"
    bytes_, entries = "hivemind_moe_decode_cache_bytes", "hivemind_moe_decode_cache_entries"
    seconds, positions = "hivemind_moe_decode_prefill_seconds_total", "hivemind_moe_decode_prefill_positions_total"
    before = _snapshot(**{passes: {"_": 10.0}, cohorts: {"_": 5.0}, pass_steps: {f"pass={u}": 100.0 for u in range(4)}})
    after = _snapshot(**{passes: {"_": 310.0}, cohorts: {"_": 105.0}, pass_steps: {f"pass={u}": 100.0 + 960.0 for u in range(4)},
                         bytes_: {"kind=looped": 192 * 58_720_256.0}, entries: {"kind=looped": 192.0}})
    lead = {"before": _snapshot(**{seconds: {"_": 1.0}, positions: {"_": 1000.0}}), "after": _snapshot(**{seconds: {"_": 13.0}, positions: {"_": 401_000.0}})}
    obs = {"config": CONFIG, "counters": {"before": before, "after": after}, "counters_lead": lead,
           "samples": {"token_gap_ms": [100.0, 120.0, 140.0], "between_ms": [0.2, 0.3, 0.4, 9.0]},
           "serving": [{"kind": "decode", "total_s": 0.020, "loop_pass": u % 4} for u in range(8)] + [{"kind": "forward", "total_s": 5.0}],
           "programs": {"jit_batched_step_looped": {"seconds": 0.05, "count": 100.0}, "jit_prefill_looped_1024": {"seconds": 9.0, "count": 3.0}}}
    read = lambda name: mf.read_metric(mf.load_layer_metric(name), obs)
    assert read("decode_passes_per_cohort") == pytest.approx(3.0)
    assert read("decode_pass_steps_per_token") == pytest.approx(4.0)
    assert read("decode_cache_mb_per_session.looped") == pytest.approx(58.720256)  # 4 x 1,792 x 8 KB
    assert read("rpc_overhead_ms.looped") == pytest.approx(120.0 - 4 * 20.0)
    assert read("loop_between_ms") == pytest.approx(0.3)
    assert read("prefill_ms_per_1k_positions.looped") == pytest.approx(1000 * 12.0 / 400.0)
    assert read("decode_program_ms.looped") == pytest.approx(0.5)
    # a pass skipped shows: the last pass's series is the tokens
    after[pass_steps]["series"]["pass=2"] -= 96.0
    assert read("decode_pass_steps_per_token") == pytest.approx(3.9)
    # a program that lacks the counters (a parent commit): nothing, and no raise
    bare = {"config": CONFIG, "counters": {"before": {}, "after": {}}, "counters_lead": {"before": {}, "after": {}}, "samples": {}, "serving": []}
    for name in NEW_METRICS:
        assert mf.read_metric(mf.load_layer_metric(name), bare) is None, name


def test_the_check_judges_the_worst_pass():
    want = np.ones((4, 1, 5, 8), np.float32)
    got = want.copy()
    got[2, 0, 3, 1] += 0.2  # one value of the third pass
    readings = runner._readings(got, want)
    assert readings["decode_rel"] == pytest.approx(0.2) and readings["decode_rms_rel"] == pytest.approx(np.sqrt(0.04 / 40))
    assert runner.judge(readings, {"decode_rel": 0.3, "decode_rms_rel": 0.1}) == []
    assert len(runner.judge(readings, {"decode_rel": 0.1, "decode_rms_rel": 0.01})) == 2
    assert runner.check_shape(False) == (128, 8)


# ---- end to end -------------------------------------------------------------------------------------------------


def test_the_cell_rehearses_end_to_end():
    """`python3 -m perf.run --rehearse-cpu --trace 1` of the cell (2 x 2 slots, toy widths, 4 passes): exit code 3 (passed,
    and no measurement), no compilation inside the window, the loop over the wire and in mixed-pass batched programs
    against the reference, the six wrong references in the log, the new metrics among those that would be reported."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    run = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--trace", "1", "--workload", CELL, "--seed", "2147483659"],
                         capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    log = run.stderr
    assert run.returncode == 3, log[-4000:]
    assert "inside it 0" in log and "run 4 times a token" in log and log.count("for the record, the reference with") == 6
    assert "one entry a block holding 4 trees" in log and "programs held rows of different passes" in log
    assert "rehearsal passed=True" in log and "failed=0" in log and "FAULT" not in log
    listed = log[log.index("metrics that would be reported"):]
    for name in ("decode_cache_mb_per_session.looped", "decode_passes_per_cohort", "decode_pass_steps_per_token", "loop_between_ms",
                 "rpc_overhead_ms.looped", "prefill_ms_per_1k_positions.looped", "decode_rows_per_batch"):
        assert name in listed, name
