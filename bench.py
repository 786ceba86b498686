"""Round benchmark: ALBERT-base MLM training throughput on one TPU chip, with the
host-side drivers (averaging, serving wire path, swarm simulator) beside it.

Prints ONE JSON line: tokens/sec/chip for the flagship collaborative-pretraining
model (fwd+bwd+optax update, bf16 compute), plus achieved MFU relative to the 35%
north-star target (BASELINE.json: ALBERT-base tokens/sec/chip at >=35% MFU). Every
number names the platform it was taken on. The device measurement needs a TPU: with
none, with a kernel that fails its check, or with a host driver that fails, the run
exits non-zero and prints no result.

One process owns the chip: this one. The host drivers run as children pinned to
the CPU (``JAX_PLATFORMS=cpu``) and never ask for it."""

import json
import time


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


# per-chip peak bf16 FLOP/s by device_kind substring (Google Cloud TPU documentation,
# system architecture pages of each generation)
_PEAK_BF16_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6": 918e12,
}


def peak_flops(device) -> float:
    """The chip's published bf16 peak. A device that is not in the table is an
    error: a utilization against somebody else's peak is not a measurement."""
    kind = device.device_kind.lower()
    for key, value in _PEAK_BF16_FLOPS.items():
        if key in kind:
            return value
    raise ValueError(
        f"no published peak for device_kind {device.device_kind!r}; add it to "
        f"_PEAK_BF16_FLOPS with its source"
    )


_HOST_PLATFORM = "cpu"  # what every host driver below is pinned to


def _run_host_driver(script_name: str, argv: list, timeout: float) -> dict:
    """Run one host-side benchmarks/ driver as a child pinned to the CPU (this
    process owns the chip) and return its JSON line. A child that fails, hangs or
    prints no result fails the whole run."""
    import os
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", script_name)
    print(f"# {script_name}: child with JAX_PLATFORMS={_HOST_PLATFORM}", file=sys.stderr, flush=True)
    run = subprocess.run(
        [sys.executable, script, *argv], timeout=timeout, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": _HOST_PLATFORM},
    )
    if run.returncode != 0:
        raise RuntimeError(f"{script_name} exited with code {run.returncode}: {run.stderr[-2000:]}")
    for line in run.stdout.splitlines():
        if line.strip().startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"{script_name} printed no result: {run.stdout[-2000:]}")


def _averaging_gbps(timeout: float = 420.0, compression: str = "FLOAT16") -> dict:
    """Second driver metric: butterfly all-reduce GB/s/peer over loopback (host and
    network work; the payload is numpy)."""
    return _run_host_driver(
        "benchmark_averaging.py",
        ["--num_peers", "4", "--target_group_size", "4", "--num_rounds", "3",
         "--num_params", "4000000", "--min_matchmaking_time", "1.0",
         "--compression", compression],
        timeout,
    )


def _averaging_gbps_q8(timeout: float = 420.0) -> dict:
    """The quantized tier of the same A/B (ISSUE 11): identical swarm/payload
    with the uniform8 wire codec (per-link error feedback on), so BENCH
    artifacts track the 8-bit GB/s/peer (fp32-equivalent) next to fp16."""
    return _averaging_gbps(timeout=timeout, compression="uniform8")


def _llama_serving(timeout: float = 420.0) -> dict:
    """Third driver metric: checkpoint-served KV-cache decode tok/s of a 2-layer,
    hidden-256 block stack ON THE CPU — a number about the RPC and session path,
    not about the chip — carrying the serving-attribution summary (ISSUE 9)."""
    return _run_host_driver(
        "benchmark_llama_serving.py",
        ["--platform", _HOST_PLATFORM, "--hidden_dim", "256", "--inner", "704",
         "--layers", "2", "--generate", "32"],
        timeout,
    )


def _swarm_sim(timeout: float = 420.0) -> dict:
    """Fourth driver metric (ISSUE 12): the in-process swarm simulator's scale
    numbers — peers simulated, sim-seconds per wall-second, beam-search routing
    recall@beam vs the oracle, and same-seed determinism. Pure host work on a
    virtual clock; the bench config is a mid-size soak (the full
    1k-peer/10k-expert acceptance run lives in the slow chaos suite)."""
    return _run_host_driver(
        "benchmark_swarm_sim.py",
        ["--scenario", "soak", "--peers", "300", "--grid", "8", "8", "40",
         "--beam_size", "8", "--trials", "4"],
        timeout,
    )


def measure_main() -> dict:
    """The device measurement: returns the result dict, or raises when jax finds
    no TPU or a kernel fails its on-device check."""
    import jax

    from hivemind_tpu.utils.platform import configure_compilation_cache, describe_devices

    configure_compilation_cache()
    import optax

    from hivemind_tpu.models import AlbertConfig, make_synthetic_mlm_batch, make_train_step
    from hivemind_tpu.ops.device_check import validate_kernels

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and jax found platform {device.platform!r}: no result"
        )
    peak = peak_flops(device)
    seq_len = 512
    masked_fraction = 0.25  # loss_masked_only budget (see flops_per_token)

    config = AlbertConfig.base(max_position=seq_len)
    optimizer = optax.adamw(1e-4)

    _steps = {}  # remat -> (model, train_step); built lazily, jit-cached

    def get_step(remat: bool):
        if remat not in _steps:
            cfg = AlbertConfig.base(max_position=seq_len, remat=remat)
            _steps[remat] = make_train_step(cfg, optimizer, masked_loss_fraction=masked_fraction)
        return _steps[remat]

    def _is_oom(error: Exception) -> bool:
        text = str(error)
        return "RESOURCE_EXHAUSTED" in text or "out of memory" in text.lower()

    def measure(batch_size: int, num_steps: int, remat: bool = False, flash: bool = True):
        """Throughput of one config; fresh state each time (buffers are donated)."""
        import os

        model, train_step = get_step(remat)
        batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, batch_size, seq_len)
        params = model.init(jax.random.PRNGKey(1), batch["input_ids"][:1, :8])["params"]
        opt_state = optimizer.init(params)
        step = jax.jit(train_step, donate_argnums=(0, 1))
        # attention_auto reads the env var when the step is TRACED — i.e. at this
        # first call — so pin it here, per variant
        os.environ["HIVEMIND_TPU_FLASH_ATTENTION"] = "1" if flash else "0"
        loss, params, opt_state = step(params, opt_state, batch)  # compile
        jax.block_until_ready(loss)
        loss, params, opt_state = step(params, opt_state, batch)  # settle caches
        jax.block_until_ready(loss)
        start = time.perf_counter()
        for _ in range(num_steps):
            loss, params, opt_state = step(params, opt_state, batch)
        jax.block_until_ready(loss)
        elapsed = time.perf_counter() - start
        return batch_size * seq_len * num_steps / elapsed, float(loss)

    # the Mosaic-compiled kernels against their float32 references, on this chip;
    # a kernel that fails raises, and the run has no result
    validation = validate_kernels(interpret=False)

    # auto-tune (batch size, remat) on the actual chip: the MXU/HBM sweet spot
    # varies by generation. Plain candidates ascend until OOM; remat trades
    # recompute FLOPs for activation memory, so it unlocks the larger batches —
    # probe it from the last plain size upward and keep whichever wins. Only
    # running out of memory ends a sweep; any other failure is the run's.
    best = None
    plain_limit = None
    for candidate in (32, 64, 128, 256):
        try:
            tps, _ = measure(candidate, num_steps=5, remat=False)
        except Exception as e:
            if not _is_oom(e):
                raise
            plain_limit = candidate
            break  # larger plain candidates will also fail
        if best is None or tps > best[1]:
            best = (candidate, tps, False)
    remat_start = plain_limit if plain_limit is not None else 256
    for candidate in (c for c in (128, 256, 512) if c >= remat_start):
        try:
            tps, _ = measure(candidate, num_steps=5, remat=True)
        except Exception as e:
            if not _is_oom(e):
                raise
            break
        if best is None or tps > best[1]:
            best = (candidate, tps, True)
    if best is None:
        raise RuntimeError("no batch size fit the chip, not even the smallest candidate")
    batch_size, _, use_remat = best

    # flash-vs-einsum A/B at the tuned config: the headline number uses the
    # WINNER, and the artifact records both sides
    ab = {
        name: measure(batch_size, num_steps=10, remat=use_remat, flash=flash)[0]
        for name, flash in (("flash", True), ("plain", False))
    }
    use_flash = ab["flash"] >= ab["plain"]
    tokens_per_sec, final_loss = measure(batch_size, 20, remat=use_remat, flash=use_flash)
    mfu = tokens_per_sec * flops_per_token(config, seq_len, head_fraction=masked_fraction) / peak
    return {
        "metric": "albert_base_mlm_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.35, 4),
        "device": describe_devices(),
        "extra": {
            "mfu": round(mfu, 4),
            "peak_bf16_flops": peak,
            "batch_size": batch_size,
            "remat": use_remat,
            "seq_len": seq_len,
            "masked_loss_fraction": masked_fraction,
            "final_loss": round(float(final_loss), 4),
            "attention": "flash" if use_flash else "plain",
            "attention_tokens_per_sec": {k: round(v, 1) for k, v in ab.items()},
            "device_validation": validation,
        },
    }


def telemetry_section(averaging=None, serving=None) -> dict:
    """The telemetry snapshot embedded in every BENCH artifact (ISSUE 2): the
    bench process's own registry plus the averaging swarm's snapshot (shipped
    through the subprocess's JSON extra), so round artifacts carry a per-phase
    breakdown.

    ISSUE 8: the averaging swarm's ledger + watchdog summary ride along
    (``attribution`` key) — rounds run, mean/p95 per-phase durations, straggler
    scores, stall count and max loop lag — so a perf regression's artifact says
    WHERE the regression lives (matchmaking? one slow peer? a blocked loop?),
    not just the headline number."""
    try:
        from hivemind_tpu.telemetry import build_peer_snapshot

        section: dict = {"bench_process": build_peer_snapshot()}
    except Exception as e:  # the artifact must survive a broken local install
        section = {"error": repr(e)[:200]}
    averaging_extra = (averaging or {}).get("extra") or {}
    swarm = averaging_extra.get("telemetry")
    if swarm:
        section["averaging_swarm"] = swarm
    attribution = averaging_extra.get("attribution")
    if attribution:
        section["attribution"] = attribution
    # ISSUE 9: the serving swarm's per-request attribution summary (per-expert
    # p50/p95, phase decomposition, batch occupancy, shed count) rides under
    # "serving" — a serving regression's artifact names the phase that moved
    serving_extra = (serving or {}).get("extra") or {}
    if serving_extra.get("serving"):
        section["serving"] = serving_extra["serving"]
    # ISSUE 19: the device-side story — this process's compile/memory/transfer
    # snapshot, plus the serving subprocess's steady-state compile guard (a
    # recompile storm in the decode loop is a silent tok/s regression)
    device: dict = {}
    try:
        from hivemind_tpu.telemetry.device import device_snapshot

        local = device_snapshot()
        if local:
            device["bench_process"] = local
    except Exception as e:
        device["error"] = repr(e)[:200]
    if serving_extra.get("device") is not None:
        device["serving"] = serving_extra["device"]
    if serving_extra.get("steady_state_compiles") is not None:
        device["serving_steady_state_compiles"] = serving_extra["steady_state_compiles"]
    if device:
        section["device"] = device
    return section


def lint_section() -> dict:
    """ISSUE 16: the hivemind-lint summary embedded in every BENCH artifact —
    per-rule violation/suppressed/allowlisted counts (no finding bodies), so
    each round records the static health of the exact tree it measured.
    Defensive: lint trouble must never take the benchmark down."""
    import os
    import sys

    try:
        tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
        if tools_dir not in sys.path:
            sys.path.insert(0, tools_dir)
        from lint.engine import run_suite

        summary = run_suite().to_json(include_findings=False)
        summary["total_stale_allowlist"] = sum(
            rule.get("stale_allowlist", 0) for rule in summary.get("rules", {}).values()
        )
        return summary
    except Exception as e:
        return {"error": repr(e)[:200]}


def main() -> None:
    result = measure_main()  # first: with no TPU there is nothing to report
    averaging = _averaging_gbps()
    averaging_q8 = _averaging_gbps_q8()
    serving = _llama_serving()
    swarm_sim = _swarm_sim()

    extra = result["extra"]
    # every host number says where it ran: none of them is a statement about the chip
    extra["host_platform"] = _HOST_PLATFORM
    extra["averaging_gbps_per_peer"] = averaging["value"]
    # the quantized tier's fp32-equivalent rate + its success rate (the lossy
    # tier must not buy throughput with failed rounds)
    extra["averaging_gbps_q8_per_peer"] = averaging_q8["value"]
    extra["averaging_q8_success_rate"] = averaging_q8["extra"].get("success_rate")
    extra["llama_serving_tok_s"] = {
        "value": serving["value"], "platform": _HOST_PLATFORM, "hidden_dim": 256, "layers": 2,
    }
    # ISSUE 12: the swarm simulator's scale numbers — peers simulated,
    # sim-seconds/wall-second, routing recall@beam, same-seed determinism
    swarm_extra = swarm_sim["extra"]
    extra["swarm_sim"] = {
        "peers": swarm_sim["value"],
        "sim_seconds_per_wall_second": swarm_extra.get("sim_seconds_per_wall_second"),
        "recall_at_beam": swarm_extra.get("recall_at_beam"),
        "deterministic": swarm_extra.get("deterministic"),
        "get_success_rate": swarm_extra.get("get_success_rate"),
        # virtual-time round-ledger summary (ISSUE 17): round totals and
        # straggler attribution aggregated from the sim's synthesized
        # allreduce spans — part of the determinism digest above
        "ledger": swarm_extra.get("ledger"),
        "failures": swarm_extra.get("failures"),
    }
    # the swarm telemetry + attribution snapshots land ONCE, in
    # result["telemetry"] below — strip them from the copied extra so the
    # artifact does not carry them twice
    extra["averaging_extra"] = {
        k: v for k, v in averaging["extra"].items() if k not in ("telemetry", "attribution")
    }
    result["telemetry"] = telemetry_section(averaging, serving)
    result["lint"] = lint_section()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
