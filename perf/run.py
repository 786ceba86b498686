"""Run ONE cell of the benchmark once and print its result as the last line.

    python3 -m perf.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m perf.run --rehearse-cpu --workload <cell>    # toy sizes on the CPU: never a result

From the root of a checkout. The cell's configuration names its runner
(`perf/runners/`), the cell its traffic generator (`perf/traffic/`), each metric its
reader (`perf/readers/`): this file names none of them. With `--trace 0` the result
carries the cell's end-to-end metrics (profiler off); with `--trace 1` a few seconds
of the window are traced and the result carries the per-layer metrics and the
breakdown. The measurement path needs a TPU whose `device_kind` is in
`perf/peaks.json` and as many chips as the cell asks for; anything else exits
non-zero and prints no result."""

from __future__ import annotations

import time

_PROCESS_STARTED = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

EXIT_NO_DEVICE = 2
EXIT_REHEARSAL = 3  # a rehearsal never exits 0: it is not a measurement


def log(text: str) -> None:
    print(f"[{time.monotonic() - _PROCESS_STARTED:7.1f} s] {text}", file=sys.stderr, flush=True)


def place_caches() -> str:
    """The persistent compile cache: where JAX_COMPILATION_CACHE_DIR says, else the
    program's own fixed place inside the checkout (`<checkout>/.jax_cache`). Every
    program is cached, however quick it was to compile, so that only the first run
    of a cell in a checkout compiles."""
    import jax

    from hivemind_tpu.utils.platform import configure_compilation_cache

    cache_dir = configure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="the cell's whole path at its rehearsal sizes on the CPU; prints no "
                             "device metric and exits with code 3 when it passed")
    args = parser.parse_args(argv)

    from perf import manifest as mf

    manifest = mf.load_manifest()
    if args.rehearse_cpu and args.workload not in [c["name"] for c in manifest["workloads"]]:
        # a cell that exists as data only (not yet proven on the chip) can be rehearsed, never measured
        workload = mf.load_workload(args.workload)
        cell = {"name": workload["name"], "config": workload["config"], "chips": workload["chips"]}
        config = mf.load_json(mf.PERF / "configs" / f"{cell['config']}.json")
    else:
        cell = mf.by_name(manifest["workloads"], args.workload, "cell")
        workload = mf.load_workload(cell["name"])
        config = mf.load_config(manifest, cell["config"])
    seconds = args.seconds if args.seconds is not None else float(manifest["run_seconds"])
    chips = int(cell["chips"])

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={max(chips, 1)}".strip()
        config = mf.rehearsal_config(config)
        seconds = args.seconds if args.seconds is not None else 6.0
        log("REHEARSAL on the CPU at toy sizes: this is not a measurement and prints no device metric.")

    # this process holds the chip; load generators are children pinned to the CPU
    from hivemind_tpu.utils.platform import describe_devices

    device = describe_devices()
    log(f"device: platform={device['platform']} kind={device['kind']!r} count={device['count']}")
    if not args.rehearse_cpu:
        from perf.peaks import peak_for

        if device["platform"] != "tpu":
            log(f"jax found platform {device['platform']!r}, not 'tpu': nothing to measure")
            return EXIT_NO_DEVICE
        if device["count"] < chips:
            log(f"the cell asks for {chips} chips, jax found {device['count']}")
            return EXIT_NO_DEVICE
        try:
            peak_for(device["kind"])
        except LookupError as e:
            log(str(e))
            return EXIT_NO_DEVICE
    if not args.rehearse_cpu:  # a rehearsal leaves nothing behind that a measurement could find
        log(f"compile cache: {place_caches()}")

    runner = mf.plugin("runners", config["runner"])
    observations = runner.run(
        config=config, workload=workload, chips=chips, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), rehearse=args.rehearse_cpu, started=_PROCESS_STARTED, log=log,
    )
    observations.update(config=config, workload=workload, chips=chips)
    observations["device"] = {**device, **observations.get("device", {})}

    if args.trace:
        specs = {entry["name"]: mf.load_layer_metric(entry["name"])
                 for entry in mf.cell_metrics(manifest, cell["name"], "per_layer")}
        units = {entry["name"]: entry["unit"] for entry in manifest["per_layer"]}
    else:
        specs = {entry["name"]: workload["end_to_end"][entry["name"]]
                 for entry in mf.cell_metrics(manifest, cell["name"], "end_to_end") if entry["name"] in workload["end_to_end"]}
        units = {entry["name"]: entry["unit"] for entry in manifest["end_to_end"]}
    metrics = {}
    for name, spec in specs.items():
        try:
            value = mf.read_metric(spec, observations)
        except LookupError as e:  # no published peak for this device
            if not args.rehearse_cpu:
                raise
            log(f"{name}: needs a listed TPU ({e})")
            continue
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    for note in observations.get("notes", []):
        log(f"note: {note}")
    for name, samples in observations.get("samples", {}).items():
        log(f"samples: {name} n={len(samples)}")

    if args.rehearse_cpu:
        log(f"rehearsal passed={observations['correct']} attempted={observations['attempted']} "
            f"failed={observations['failed']}; metrics that would be reported: {sorted(metrics)}")
        return EXIT_REHEARSAL if observations["correct"] else 1

    result = {
        "correct": bool(observations["correct"]),
        "attempted": int(observations["attempted"]),
        "failed": int(observations["failed"]),
        "metrics": metrics,
        "device": observations["device"],
    }
    if args.trace and observations.get("trace"):
        from perf.trace_reduce import breakdown

        result["breakdown"] = breakdown(observations["trace"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the program's daemon threads (loop runner, watchdog) must not hold the exit
