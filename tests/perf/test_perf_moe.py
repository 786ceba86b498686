"""What `olmoe-1b-7b-span4` brings to the benchmark: its configuration file against
the catalog's row, `perf/flops_moe.py` against hand counts, the plain reference
against a per-token loop over the chosen experts, the program's block (built with the
runner's kwargs) against the reference at the rehearsal sizes, the roofline reader on a
hand-made observation, and the cell's rehearsal end to end (CPU)."""

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

from perf import flops_moe, manifest as mf  # noqa: E402
from perf.reference import olmoe_block as reference  # noqa: E402

CONFIG = mf.load_json(mf.PERF / "configs" / "olmoe-1b-7b-span4.json")
REHEARSAL = mf.rehearsal_config(CONFIG)
CELL = "olmoe-1b-7b-span4.decode32"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# the catalog row's config (model-configs guide, OLMoE-1B-7B-0125-Instruct), as published
PUBLISHED = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
             "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe", "norm_topk_prob": False,
             "num_attention_heads": 16, "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
             "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
             "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_every_published_value(key):
    """Every key of the source's config.json, at the top level of the file and in the
    `model` section the runner reads, unchanged except for the cut that `reduced` lists."""
    assert CONFIG[key] == CONFIG["model"][key]
    if key in CONFIG["reduced"]:
        assert key == "num_hidden_layers" and CONFIG[key] == 4
    else:
        assert CONFIG[key] == PUBLISHED[key] and type(CONFIG[key]) is type(PUBLISHED[key])


def test_published_values_are_the_catalogs():
    if not CATALOG.exists():
        pytest.skip("the catalog beside the model-configs guide is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    [row] = [row for row in rows if row["name"] == "OLMoE-1B-7B-0125-Instruct"]
    assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]


def test_parameters_by_hand():
    model = CONFIG["model"]
    attention, experts, router, norms = 4 * 2048**2, 64 * 3 * 2048 * 1024, 2048 * 64, 4 * 2048
    assert flops_moe.expert_params(2048, 1024) == 6_291_456
    assert flops_moe.moe_block_params(model) == attention + experts + router + norms == 419_569_664
    assert experts / flops_moe.moe_block_params(model) > 0.95
    assert flops_moe.moe_block_params_per_token(model) == attention + 8 * 6_291_456 + router + norms == 67_248_128


@pytest.mark.parametrize("pairs, hit, want_flops, want_bytes", [
    (8, 8, 2 * 8 * 6_291_456, 8 * 6_291_456 * 4 + 8 * (2 * 2048 + 4 * 1024) * 4),  # one token
    (48, 35, 2 * 48 * 6_291_456, 35 * 6_291_456 * 4 + 48 * 8192 * 4),  # six rows of a batched step
    (16384, 64, 2 * 16384 * 6_291_456, 64 * 6_291_456 * 4 + 16384 * 8192 * 4),  # a prefill of 2,048
])
def test_expert_layer_flops_and_bytes_by_hand(pairs, hit, want_flops, want_bytes):
    assert flops_moe.expert_layer_flops(pairs, 2048, 1024) == want_flops
    assert flops_moe.expert_layer_bytes(hit, pairs, 2048, 1024, weight_itemsize=4, activation_itemsize=4) == want_bytes


def _toy(blocks=2, batch=2, seq=12):
    from hivemind_tpu.moe.server.layers import name_to_block
    from perf.runners.moe_block_server import _block_kwargs, _reference_sizes

    sizes = REHEARSAL["model"]
    module = name_to_block["olmoe_block"](sizes["hidden_size"], **_block_kwargs(sizes))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((batch, seq, sizes["hidden_size"])), jnp.float32)
    params = [module.init(jax.random.PRNGKey(20 + i), x[:1, :4])["params"] for i in range(blocks)]
    return module, params, x, _reference_sizes(sizes)


def test_reference_against_a_per_token_loop_over_the_chosen_experts():
    """The dense-and-masked expert layer equals, token by token, the sum over that
    token's k chosen experts of p_e times the expert's SwiGLU; p_e is not renormalised."""
    _module, [params], x, sizes = _toy(blocks=1)
    k, eps = sizes["experts_per_token"], sizes["rms_eps"]
    with jax.default_matmul_precision("highest"):
        # h = the block's input to its expert half: take it from a block whose experts give nothing
        silent = {**params, "experts_down": jnp.zeros_like(params["experts_down"])}
        h = np.asarray(reference.block(silent, x, **sizes), np.float64)
        got = np.asarray(reference.block(params, x, **sizes), np.float64)
    gate, up, down, router = (np.asarray(params[name], np.float64) for name in ("experts_gate", "experts_up", "experts_down", "router"))
    scale = np.asarray(params["ffn_norm"]["scale"], np.float64)
    want = h.copy()
    for b in range(h.shape[0]):
        for t in range(h.shape[1]):
            m = h[b, t] / np.sqrt((h[b, t] ** 2).mean() + eps) * scale
            logits = m @ router
            p = np.exp(logits - logits.max())
            p /= p.sum()
            chosen = np.argsort(-p)[:k]
            assert p[chosen].sum() < 1.0  # used as they are
            for e in chosen:
                a = m @ gate[e]
                want[b, t] += p[e] * (((a / (1 + np.exp(-a))) * (m @ up[e])) @ down[e])
    assert np.abs(got - want).max() / np.abs(want).max() <= 1e-5


def test_block_span_against_reference_at_rehearsal_sizes():
    module, params, x, sizes = _toy()
    got = x
    for block_params in params:
        got = module.apply({"params": block_params}, got)
    want = reference.span(params, x, **sizes)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= CONFIG["tolerances"]["decode_rel"]


def test_block_span_input_gradient_against_reference():
    module, params, x, sizes = _toy()
    grad = jnp.asarray(np.random.default_rng(1).standard_normal(x.shape), jnp.float32)

    def program(xx):
        for block_params in params:
            xx = module.apply({"params": block_params}, xx)
        return xx

    _, vjp = jax.vjp(program, x)
    _, want = reference.span_input_grad(params, x, grad, **sizes)
    assert float(jnp.abs(vjp(grad)[0] - want).max() / jnp.abs(want).max()) <= 2 * CONFIG["tolerances"]["decode_rel"]


@pytest.mark.parametrize("router", ["float32 (route_top_k)", "one bf16 pass", "bf16 logits"])
def test_router_limit_tells_a_bf16_router_from_the_float32_one(router):
    """`router_mismatch_share` at the published widths, the router alone: 4,096
    bf16-valued router inputs of unit rms (what the block's ffn norm hands its
    router), a router drawn as the block draws it. The program's `route_top_k` chooses
    the reference's experts exactly; a router whose matmul is one bf16 pass, or whose
    logits are bf16, differs on a share of the pairs that is over the limit."""
    from hivemind_tpu.ops.sparse_experts import route_top_k
    from perf.runners.moe_block_server import _router_mismatch_share

    model, limit = CONFIG["model"], CONFIG["tolerances"]["router_mismatch_share"]
    hidden, experts, k = model["hidden_size"], model["num_experts"], model["num_experts_per_tok"]
    rng = np.random.default_rng(5)
    m = jnp.asarray(rng.standard_normal((1, 4096, hidden)), jnp.bfloat16)
    params = {"router": jnp.asarray(rng.standard_normal((hidden, experts)) / np.sqrt(hidden), jnp.float32)}
    rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    if router == "float32 (route_top_k)":
        top_e = route_top_k(m.reshape(-1, hidden), params["router"], k)[1].reshape(1, -1, k)
    elif router == "one bf16 pass":
        top_e = reference.chosen_experts({"router": rounded(params["router"])}, m, k)
    else:
        with jax.default_matmul_precision("highest"):
            logits = rounded(m.astype(jnp.float32) @ params["router"])
        top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)[1]
    share = _router_mismatch_share(reference, [params], [(m, top_e)], k)
    if router == "float32 (route_top_k)":
        assert share == 0.0
    else:
        assert share > 2 * limit, f"{share:.4%} against a limit of {limit:.4%}"


def _observation(pairs, hit, calls, kernel_s, events):
    series = lambda value: {"series": {"path=batched": value}}
    after = {"hivemind_moe_routed_pairs_total": series(pairs), "hivemind_moe_experts_hit_total": series(hit),
             "hivemind_moe_expert_layer_calls_total": series(calls), "hivemind_moe_expert_max_pairs_total": series(0.0)}
    return {"config": CONFIG, "device": {"kind": "TPU v5 lite"}, "counters": {"before": {}, "after": after},
            "trace": {"devices": 1, "ops": {"ragged-dot-none": {"seconds": kernel_s * 0.9, "count": events},
                                            "ragged-dot-metadata": {"seconds": kernel_s * 0.1, "count": events},
                                            "fusion": {"seconds": 1.0, "count": 5}}}}


def test_roofline_reader_by_hand():
    from perf.readers import moe_roofline

    spec = mf.load_layer_metric("moe_experts_roofline")["args"]
    # 1,000 calls in the window, 35 experts hit and 48 pairs each: memory-bound, 35 experts' float32 weights a call
    least_per_call = (35 * 6_291_456 * 4 + 48 * 8192 * 4) / 819e9
    obs = _observation(pairs=48_000.0, hit=35_000.0, calls=1000.0, kernel_s=0.2, events=300)  # 100 calls traced, 2 ms each
    assert moe_roofline.read(obs, **spec) == pytest.approx(100.0 * least_per_call * 100 / 0.2, rel=1e-9)
    assert 50.0 < moe_roofline.read(obs, **spec) < 60.0
    assert any("memory-bound" in note for note in obs["notes"])
    ms = mf.read_metric(mf.load_layer_metric("moe_experts_ms_per_step"), obs)
    assert ms == pytest.approx(2.0)


@pytest.mark.parametrize("broken", ["no trace", "no counters", "no such operation", "a program without the counters"])
def test_roofline_reader_returns_nothing_where_there_is_nothing_to_read(broken):
    from perf.readers import moe_roofline

    spec = mf.load_layer_metric("moe_experts_roofline")["args"]
    obs = _observation(48_000.0, 35_000.0, 1000.0, 0.2, 300)
    if broken == "no trace":
        obs.pop("trace")
    elif broken == "no counters":
        obs.pop("counters")
    elif broken == "no such operation":
        obs["trace"]["ops"] = {"fusion": {"seconds": 1.0, "count": 5}}
    else:
        obs["counters"]["after"] = {}
    assert moe_roofline.read(obs, **spec) is None


@pytest.mark.parametrize("name, want", [("moe_experts_hit_per_step", 35.0), ("moe_load_max_over_mean", 4.0 * 64 / 48)])
def test_counter_metrics_by_hand(name, want):
    obs = _observation(48_000.0, 35_000.0, 1000.0, 0.2, 300)
    obs["counters"]["after"]["hivemind_moe_expert_max_pairs_total"] = {"series": {"path=batched": 4000.0, "path=direct": 9e9}}
    assert mf.read_metric(mf.load_layer_metric(name), obs) == pytest.approx(want)


def test_the_cell_rehearses_end_to_end():
    """The cell's whole path at toy sizes: server, warm-up, the reference checks,
    client processes, window, readers. A rehearsal that passed exits with code 3."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run([sys.executable, "-m", "perf.run", "--rehearse-cpu", "--workload", CELL, "--trace", "1",
                           "--seconds", "4"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 3, done.stderr[-3000:]
    assert done.stdout.strip() == ""
    for metric in ("moe_experts_hit_per_step", "moe_load_max_over_mean", "decode_step_ms", "decode_batched_share"):
        assert metric in done.stderr
    assert "sessions at positions" in done.stderr and "chose an expert outside the reference's set" in done.stderr
    assert "on the program's own router inputs, 0.0000% of" in done.stderr
