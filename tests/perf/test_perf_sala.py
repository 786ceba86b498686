"""What `minicpm-sala-span8` brings to the benchmark: its configuration file against the
catalog's row, the plain reference against per-position loops written another way, the
runner's block kwargs and wrong references, the traffic generator's schedule, the new
readers on hand-made observations (and on a program that lacks what they read), the
scopes read off a compiled program's text, and the cell's rehearsal end to end (CPU)."""

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

from perf import flops_sala  # noqa: E402
from perf import manifest as mf  # noqa: E402
from perf.reference import minicpm_sala_block as reference  # noqa: E402
from perf.runners import sala_block_server as runner  # noqa: E402
from perf.traffic import long_sessions  # noqa: E402

CONFIG = mf.load_json(mf.PERF / "configs" / "minicpm-sala-span8.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = "minicpm-sala-span8.longctx32"
WORKLOAD = mf.load_workload(CELL)
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_KEYS = [key for key in CONFIG if key not in ("name", "source", "runner")][: list(CONFIG).index("catalog_keys") - 3]
# the widths the issue names, as published
WIDTHS = {"hidden_size": 4096, "intermediate_size": 16384, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
          "lightning_nh": 32, "lightning_nkv": 32, "lightning_head_dim": 128, "rms_norm_eps": 1e-06, "rope_theta": 10000,
          "scale_depth": 1.4, "max_position_embeddings": 524288, "vocab_size": 73448, "qk_norm": True, "attn_use_rope": False,
          "lightning_use_rope": True, "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True}
SPARSE_CONFIG = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64, "init_blocks": 1, "window_size": 2048,
                 "dense_len": 8192}
TOY = dict(alpha=1.4 / math.sqrt(32), rms_eps=1e-6, lightning=dict(heads=2, head_dim=8, rope_theta=10000.0),
           sparse=dict(heads=4, kv_heads=2, head_dim=8, kernel_size=4, kernel_stride=2, block_size=4, topk=3, init_blocks=1,
                       window_size=4, dense_len=12))


def _catalog_row():
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    found = [row for row in rows if row["name"] == "MiniCPM-SALA"]
    if not found:
        pytest.skip("the catalog on this machine has no MiniCPM-SALA row")
    return found[0]


@pytest.mark.parametrize("key", PUBLISHED_KEYS)
def test_configuration_holds_every_published_value(key):
    """Every key of the catalog row's config, at the top level of the file and in the
    `model` section the runner reads, unchanged except for the one cut `reduced` lists."""
    assert CONFIG[key] == CONFIG["model"][key]
    if key == "num_hidden_layers":
        assert CONFIG["reduced"] == [key] and CONFIG[key] == 8 and CONFIG["published"][key] == 32
        return
    if key in WIDTHS:
        assert CONFIG[key] == WIDTHS[key] and type(CONFIG[key]) is type(WIDTHS[key])
    row = _catalog_row()  # skips, and does not fail, where the catalog or the row is not there
    assert CONFIG[key] == row["config"][key] and type(CONFIG[key]) is type(row["config"][key])


def test_configuration_has_every_key_of_the_catalog_row_and_its_sections():
    assert all(section in CONFIG for section in ("source", "reduced", "reduced_why", "assumed", "published", "deployment",
                                                 "tolerances", "rehearsal", "serving", "model"))
    assert CONFIG["model"]["sparse_config"] == SPARSE_CONFIG and CONFIG["model"]["first_block"] == 9
    assert all(name in CONFIG["assumed"] for name in ("sparse_config", "lightning_decay", "qk_norm", "output_norm", "output_gate",
                                                      "topk_includes_forced", "mup_denominator"))
    row = _catalog_row()
    assert set(row["config"]) == set(PUBLISHED_KEYS) and CONFIG["source"] == row["source_url"]


def test_the_span_is_blocks_9_to_16_of_the_published_mixers():
    assert len(CONFIG["mixer_types"]) == 32
    assert runner.mixers(CONFIG) == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    sparse, lightning = runner.block_kwargs(CONFIG, 0), runner.block_kwargs(CONFIG, 3)
    assert sparse["mixer"] == "minicpm4" and lightning["mixer"] == "lightning-attn" and sparse["dense_len"] == 8192
    assert sparse["residual_scale"] == lightning["residual_scale"] == pytest.approx(1.4 / math.sqrt(32))  # the published depth
    assert (sparse["num_heads"], sparse["num_kv_heads"], sparse["head_dim"], sparse["ffn_inner"]) == (32, 2, 128, 16384)
    toy = runner.block_kwargs(REHEARSAL, 7)
    assert toy["mixer"] == "minicpm4" and toy["dense_len"] == 64 and toy["head_dim"] == 16


def test_benchmark_lists_the_cell_and_its_metrics():
    manifest = mf.load_manifest()
    cell = mf.by_name(manifest["workloads"], CELL, "cell")
    assert cell == {"name": CELL, "config": "minicpm-sala-span8", "traffic": "longctx32", "chips": 1, "why": WORKLOAD["why"]}
    assert mf.by_name(manifest["configs"], "minicpm-sala-span8", "configuration")["reduced"] == ["num_hidden_layers"]
    reported = {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "per_layer")}
    new = {"decode_program_ms.sparse", "decode_program_ms.lightning", "decode_cache_mb_per_session.sparse",
           "decode_cache_mb_per_session.lightning", "sparse_attended_share", "prefill_ms_per_1k_positions.chunked",
           "lightning_step_roofline", "sparse_attend_roofline"}
    assert new <= reported and "ttft_median_ms" not in reported and "hbm_peak_gb.serve" in reported
    for name in reported:
        assert mf.load_layer_metric(name)["name"] == name
    for name in new:
        assert mf.by_name(manifest["per_layer"], name, "metric")["workloads"] == [CELL]
    assert {entry["name"] for entry in mf.cell_metrics(manifest, CELL, "end_to_end")} == {"decode_tokens_per_s", "token_gap_p95_ms", "setup_s"}


# ---- the reference, against the same equations written another way ------------------


def _toy_params(seed: int, lightning: bool):
    rng = np.random.default_rng(seed)
    heads, dim = (2, 8) if lightning else (4, 8)
    kv = heads if lightning else 2
    hidden, width = 16, heads * dim
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) / math.sqrt(shape[0]), jnp.float32)
    scale = lambda n: {"scale": jnp.asarray(1.0 + 0.1 * rng.standard_normal(n), jnp.float32)}
    params = {"attention_norm": scale(hidden), "query": {"kernel": draw(hidden, width)}, "key": {"kernel": draw(hidden, kv * dim)},
              "value": {"kernel": draw(hidden, kv * dim)}, "query_norm": scale(dim), "key_norm": scale(dim),
              "gate": {"kernel": draw(hidden, width)}, "attention_out": {"kernel": draw(width, hidden)}, "ffn_norm": scale(hidden),
              "ffn_gate": {"kernel": draw(hidden, 24)}, "ffn_up": {"kernel": draw(hidden, 24)}, "ffn_down": {"kernel": draw(24, hidden)}}
    if lightning:
        params["output_norm"] = scale(width)
    return params


def test_lightning_recurrence_equals_the_quadratic_form():
    """o_t = sum_{s <= t} lambda^(t - s) (q_t . k_s) v_s / sqrt(d): the reference's scan
    against the explicit [T, T] form, per head."""
    params, x = _toy_params(0, True), jnp.asarray(np.random.default_rng(1).standard_normal((1, 20, 16)), jnp.float32)
    h = reference._rms_norm(x, params["attention_norm"]["scale"], 1e-6)
    with jax.default_matmul_precision("highest"):
        got = reference.lightning_mixer(params, h, heads=2, head_dim=8, rope_theta=10000.0, rms_eps=1e-6, output_gate=False,
                                        output_norm=False)
        q, k, v = ((h @ params[name]["kernel"]).reshape(1, 20, 2, 8) for name in ("query", "key", "value"))
        q = reference._rope(reference._rms_norm(q, params["query_norm"]["scale"], 1e-6), 10000.0)
        k = reference._rope(reference._rms_norm(k, params["key_norm"]["scale"], 1e-6), 10000.0)
        t, s = jnp.arange(20)[:, None], jnp.arange(20)[None, :]
        lam = reference.decay(2)
        decays = jnp.where(s <= t, lam[:, None, None] ** jnp.maximum(t - s, 0), 0.0)
        want = jnp.einsum("hts,bshd->bthd", jnp.einsum("bthd,bshd->hts", q, k) * decays, v) / math.sqrt(8)
        want = want.reshape(1, 20, 16) @ params["attention_out"]["kernel"]
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= 1e-5
    assert np.allclose(np.asarray(reference.decay(32))[[0, 31]], [math.exp(-2 ** -0.25), math.exp(-2 ** -8)])
    assert float(reference.decay(32, 31, 32)[0]) > 0.99999 and np.allclose(reference.decay(32, 0, 32), reference.decay(32), atol=1e-4)


def test_sparse_selection_equals_a_loop_over_queries():
    """`selected_blocks` against the rule applied query by query in Python: complete
    kernels, softmax over them, summed over the group, a block's score the max over the
    kernels that overlap it, block 0 and the window forced, the best `topk` taken."""
    rng = np.random.default_rng(2)
    sizes = TOY["sparse"]
    seq, kv, group, dim = 30, 2, 2, 8
    q, k = jnp.asarray(rng.standard_normal((seq, kv, group, dim)), jnp.float32), jnp.asarray(rng.standard_normal((seq, kv, dim)), jnp.float32)
    keys = ("kernel_size", "kernel_stride", "block_size", "topk", "init_blocks", "window_size")
    got = np.asarray(reference.selected_blocks(q, k, jnp.arange(seq), **{key: sizes[key] for key in keys}))
    ks, stride, bs, topk, window = (sizes[key] for key in ("kernel_size", "kernel_stride", "block_size", "topk", "window_size"))
    for t in range(seq):
        n, own = t + 1, t // bs
        kernels = [m for m in range(seq) if m * stride + ks <= n]
        for head in range(kv):
            means = np.stack([np.asarray(k[m * stride:m * stride + ks, head]).mean(0) for m in kernels]) if kernels else np.zeros((0, dim))
            logits = np.asarray(q[t, head]) @ means.T / math.sqrt(dim)
            probs = (np.exp(logits - logits.max(-1, keepdims=True)) / np.exp(logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)).sum(0) if kernels else []
            scores = {}
            for block in range(own + 1):
                over = [probs[i] for i, m in enumerate(kernels) if m * stride + ks > block * bs and m * stride < (block + 1) * bs]
                scores[block] = math.inf if block == 0 or block > own - window // bs else (max(over) if over else -math.inf)
            want = sorted(sorted(scores, key=lambda block: (-scores[block], block))[:topk])
            want = [block for block in want if scores[block] > -math.inf]
            assert sorted(np.flatnonzero(got[t, head])) == want, (t, head)


def test_sparse_mixer_in_dense_mode_is_plain_causal_attention():
    params, x = _toy_params(3, False), jnp.asarray(np.random.default_rng(4).standard_normal((1, 11, 16)), jnp.float32)
    h = reference._rms_norm(x, params["attention_norm"]["scale"], 1e-6)
    with jax.default_matmul_precision("highest"):
        got, picked = reference.sparse_mixer(params, h, rms_eps=1e-6, output_gate=False, return_selection=True, **TOY["sparse"])
        q = reference._rms_norm((h @ params["query"]["kernel"]).reshape(1, 11, 4, 8), params["query_norm"]["scale"], 1e-6)
        k = reference._rms_norm((h @ params["key"]["kernel"]).reshape(1, 11, 2, 8), params["key_norm"]["scale"], 1e-6)
        v = (h @ params["value"]["kernel"]).reshape(1, 11, 2, 8)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, 2, axis=2)) / math.sqrt(8)
        scores = jnp.where(jnp.tril(jnp.ones((11, 11), bool)), scores, -jnp.inf)
        want = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), jnp.repeat(v, 2, axis=2)).reshape(1, 11, 32) @ params["attention_out"]["kernel"]
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= 1e-5 and not bool(picked.any())  # 11 < dense_len 12


def test_every_wrong_reference_departs_from_the_right_one():
    """The ten wrong references of the check, at toy sizes on one stream through a sparse
    and a lightning block: each moves the output or the selection."""
    params = [_toy_params(5, False), _toy_params(6, True)]
    x = jnp.asarray(np.random.default_rng(7).standard_normal((1, 40, 16)), jnp.float32)
    want, picked = runner.reference_span(params, x, TOY, first_block=9)
    wrong = runner.wrong_references(TOY, 32, 8)
    assert len(wrong) == 10
    for name, variant in wrong.items():
        out, wrong_picked = runner.reference_span(params, x, first_block=9, **{"sizes": TOY, **variant})
        moved = float(jnp.abs(out.astype(jnp.float32) - want).max() / jnp.abs(want).max())
        reselected = runner.selection_mismatch_share(wrong_picked, picked)
        assert moved > 1e-3 or reselected > 0.05, name
    assert runner.selection_mismatch_share(picked, picked) == 0.0


def test_selection_mismatch_share_counts_both_sides():
    picked = np.zeros((1, 2, 1, 8), bool)
    picked[0, 0, 0, [0, 3, 5]] = True
    chosen = np.array([[[[0, 3, 6]], [[-1, -1, -1]]]])
    ours = runner._as_picked(chosen, 8)
    assert ours.shape == picked.shape and ours[0, 0, 0].tolist() == [True, False, False, True, False, False, True, False]
    assert not ours[0, 1].any()
    assert runner.selection_mismatch_share([None, ours], [None, picked]) == pytest.approx(2 / 6)  # 5 and 6, of 3 + 3
    assert runner.selection_mismatch_share([np.zeros_like(picked)], [picked]) == 1.0


# ---- traffic, arithmetic and readers --------------------------------------------------


def test_long_sessions_deals_one_fixed_multiset_of_prompts():
    traffic = WORKLOAD["traffic"]
    assert (traffic["processes"], traffic["slots_per_process"], traffic["chunk"], traffic["answer_cap"]) == (4, 8, 4096, 8192)
    assert sorted(long_sessions.sizes(traffic)) == [8192] * 13 + [12288] * 10 + [16384] * 6 + [24576] * 3
    assert sum(long_sessions.sizes(traffic)) * 4096 * 2 == pytest.approx(3.3e9, rel=0.02)  # bytes of fp16 prompts
    plans = [long_sessions.schedule(traffic, seed) for seed in (1, 2**31 + 5)]
    flat = [[slot[0][0] for process in plan["processes"] for slot in process] for plan in plans]
    assert sorted(flat[0]) == sorted(flat[1]) and flat[0] != flat[1] and all(len(process) == 8 for process in plans[0]["processes"])
    assert min(traffic["prompt_lengths"]) >= CONFIG["model"]["sparse_config"]["dense_len"]  # every step of the window selects
    assert max(traffic["prompt_lengths"]) + traffic["answer_cap"] <= CONFIG["serving"]["decode_max_len"]
    assert long_sessions.SERVER_PATH == "decode" and traffic["chunk"] == CONFIG["serving"]["prompt_chunk"]
    assert runner.padded_chunks(traffic["prompt_lengths"] + runner.check_prompts(9000, 8), 4096) == [1024, 4096]


def test_mixer_arithmetic_by_hand():
    model = CONFIG["model"]
    assert flops_sala.lightning_step_bytes(model) == 2 * 32 * 128 * 128 * 4 + 4 * 32 * 128 * 2  # 4.2 MB: the state, in and out
    assert flops_sala.lightning_step_flops(model) == 4 * 32 * 128 * 128
    assert flops_sala.sparse_attend_bytes(model) == 2 * 4096 * 2 * 128 * 2 + 2 * 32 * 128 * 2  # 4.2 MB: 64 blocks of keys and values
    assert flops_sala.sparse_attend_flops(model) == 4 * 4096 * 32 * 128


def _observations(**extra):
    series = lambda **values: {"series": values}
    before = {"hivemind_moe_decode_calls_total": series(**{"path=batched": 10.0}), "hivemind_moe_decode_steps_total": series(**{"path=batched": 300.0}),
              "hivemind_moe_decode_prefill_seconds_total": series(**{"": 1.0}), "hivemind_moe_decode_prefill_positions_total": series(**{"": 1000.0})}
    after = {"hivemind_moe_decode_calls_total": series(**{"path=batched": 110.0}), "hivemind_moe_decode_steps_total": series(**{"path=batched": 3500.0}),
             "hivemind_moe_decode_prefill_seconds_total": series(**{"": 1.0}), "hivemind_moe_decode_prefill_positions_total": series(**{"": 1000.0})}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters": {"before": before, "after": after}, **extra}


def test_scope_roofline_reads_work_over_time():
    scopes = {"lightning_step": {"seconds": 0.012, "count": 60.0, "runs": 60.0}}
    obs = _observations(scopes=scopes)
    value = mf.read_metric(mf.load_layer_metric("lightning_step_roofline"), obs)
    least = flops_sala.lightning_step_bytes(CONFIG["model"]) / 819e9  # memory-bound
    assert value == pytest.approx(100.0 * least * 32 * 60 / 0.012, rel=1e-6) and 0 < value < 100
    assert any("memory-bound" in note for note in obs["notes"])
    assert mf.read_metric(mf.load_layer_metric("sparse_attend_roofline"), obs) is None  # no such scope in the trace
    assert mf.read_metric(mf.load_layer_metric("lightning_step_roofline"), _observations()) is None  # a runner without scopes
    assert mf.read_metric(mf.load_layer_metric("lightning_step_roofline"), {"scopes": scopes, "config": CONFIG}) is None


def test_lead_in_counters_carry_the_chunked_prefill():
    obs = _observations()
    lead = json.loads(json.dumps(obs["counters"]))
    lead["after"]["hivemind_moe_decode_prefill_seconds_total"]["series"][""] = 31.0
    lead["after"]["hivemind_moe_decode_prefill_positions_total"]["series"][""] = 1000.0 + 8 * 401408
    spec = mf.load_layer_metric("prefill_ms_per_1k_positions.chunked")
    assert mf.read_metric(spec, {**obs, "counters_lead": lead}) == pytest.approx(30.0 / (8 * 401408) * 1e6)
    assert mf.read_metric(spec, obs) is None  # a runner that does not read the lead-in
    assert mf.read_metric(mf.load_layer_metric("sparse_attended_share"), obs) is None  # a program without the counters
    obs["counters"]["before"].update({"hivemind_moe_sparse_positions_attended_total": {"series": {"": 0.0}},
                                      "hivemind_moe_sparse_positions_cached_total": {"series": {"": 0.0}}})
    obs["counters"]["after"].update({"hivemind_moe_sparse_positions_attended_total": {"series": {"": 4064.0 * 50}},
                                     "hivemind_moe_sparse_positions_cached_total": {"series": {"": 16000.0 * 50}}})
    assert mf.read_metric(mf.load_layer_metric("sparse_attended_share"), obs) == pytest.approx(25.4)


def test_scopes_are_read_off_a_compiled_program():
    """`scope_of_instructions` on a hand-made text, and on the toy sparse block's own
    compiled step: operations lie in `sparse_select` and in `sparse_attend`."""
    text = '''
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(batched_step_lightning)/Block/lightning_step/mul" source_file="x.py"}
  ROOT %add.1 = f32[4]{0} add(%a, %b), metadata={op_name="jit(batched_step_lightning)/Block/ffn_up/dot_general"}
  %gather.7 = bf16[2]{0} gather(%a, %b), metadata={op_name="jit(step)/Block/sparse_attend/vmap()/gather"}
  %copy.2 = f32[4]{0} copy(%a)
'''
    assert runner.scope_of_instructions(text) == {"fusion.3": "lightning_step", "gather.7": "sparse_attend"}
    from hivemind_tpu.moe.server.layers import name_to_block

    module = name_to_block["minicpm_sala_block"](REHEARSAL["model"]["hidden_size"], **runner.block_kwargs(REHEARSAL, 0))
    x = jnp.zeros((2, 1, REHEARSAL["model"]["hidden_size"]))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, x.shape[-1])))["params"]
    cache = module.init_decode_cache(2, 128)
    step = jax.jit(lambda p, x, cache, index: module.apply({"params": p}, x, *cache, index))
    found = runner.scope_of_instructions(step.lower(params, x, cache, jnp.array([70, 90])).compile().as_text())
    assert {"sparse_select", "sparse_attend"} <= set(found.values())


def test_the_cell_rehearses_end_to_end():
    """`python3 -m perf.run --rehearse-cpu --trace 1` of the cell: exit code 3 (passed, and
    no measurement), no compilation inside the window, the chunked reference check and the
    ten wrong references in the log, the new metrics among those that would be reported."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    run = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--trace", "1", "--workload", CELL, "--seed", "2147483659"],
                         capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    log = run.stderr
    assert run.returncode == 3, log[-4000:]
    assert "inside it 0" in log and "in chunks of 64" in log and log.count("for the record, the reference with") == 10
    assert "rehearsal passed=True" in log and "failed=0" in log
    listed = log[log.index("metrics that would be reported"):]
    for name in ("decode_cache_mb_per_session.sparse", "decode_cache_mb_per_session.lightning", "sparse_attended_share",
                 "prefill_ms_per_1k_positions.chunked", "decode_rows_per_batch"):
        assert name in listed, name


@pytest.fixture(scope="module")
def rehearsal_swarm():
    """A DHT pair as `run` makes it, for servers at the rehearsal sizes."""
    from hivemind_tpu.dht import DHT

    server_dht = DHT(start=True)
    client_dht = DHT(initial_peers=[str(m) for m in server_dht.get_visible_maddrs()], start=True)
    yield server_dht, client_dht
    client_dht.shutdown()
    server_dht.shutdown()


def _checked(rehearsal_swarm, seed: int):
    """The faults and the log of `check_against_reference` on a server built as `run` builds it."""
    from hivemind_tpu.moe.server.layers import name_to_block

    server_dht, client_dht = rehearsal_swarm
    server = runner.build_server(REHEARSAL, seed, server_dht, name_to_block["minicpm_sala_block"])
    lines = []
    try:
        faults = runner.check_against_reference(server, client_dht, REHEARSAL, seed, True, lines.append, slots=4, every_wrong_reference=False)
    finally:
        server.shutdown()
    return faults, "\n".join(lines)


@pytest.mark.parametrize("wrong", [None, "topk", "window", "dense", "stale_kernels"])
def test_a_wrong_selection_in_the_step_path_is_not_correct(monkeypatch, rehearsal_swarm, wrong):
    """`correct` holds the blocks that the SERVED steps select (the batched programs at
    every bucket and the session's own step, through `SELECTION_TAPS`) to the reference's:
    a step path that takes half of `topk`, drops the forced window blocks, attends
    densely at every length departs in the selection of the single steps, whatever its
    chunks (the prefill path is left right) and whatever the outputs' limits say; one
    that never writes a compressed key is told by the kernels its sessions hold; the
    right program passes."""
    from hivemind_tpu.ops import block_sparse_attention as sparse_ops

    select_rows, write_kernel = sparse_ops.select_rows, sparse_ops.write_kernel

    def wrong_select(q, compressed, positions, config, shared=False):
        if not shared:  # the rows of a step: a chunk's queries (one session's keys, `shared`) select as they should
            config = {"topk": config._replace(topk=config.topk // 2), "window": config._replace(window_size=0)}.get(wrong, config)
        return select_rows(q, compressed, positions, config, shared)

    monkeypatch.setattr(sparse_ops, "select_rows", wrong_select)
    if wrong == "stale_kernels":
        monkeypatch.setattr(sparse_ops, "write_kernel", lambda compressed, cache_k, kernel, due, config: write_kernel(
            compressed, cache_k, kernel, False, config))
    if wrong == "dense":
        from hivemind_tpu.moe.server.layers.minicpm_sala import MiniCPMSalaBlockExpert

        step = MiniCPMSalaBlockExpert._sparse_step
        monkeypatch.setattr(MiniCPMSalaBlockExpert, "_sparse_step", lambda self, ops, config, *rest: step(
            self, ops, config._replace(dense_len=1 << 30), *rest))
    faults, log = _checked(rehearsal_swarm, seed=11 if wrong is None else 12)
    assert "single steps' alone" in log and "batched programs of [2, 4] rows" in log
    if wrong is None:
        assert faults == [], faults
    elif wrong == "stale_kernels":  # read `window_size` positions later: told by the kernels themselves
        assert any("compressed keys that the steps completed" in fault for fault in faults), (faults, log)
    else:
        assert any("served single steps selected differ" in fault for fault in faults), (faults, log)
