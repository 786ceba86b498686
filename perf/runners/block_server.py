"""The block server under test: a span of consecutive decoder blocks behind
`Server` -> `TaskPool` -> `ModuleBackend` / `DecodeSessionManager`, driven over the
p2p wire by `RemoteSequential` clients. This process builds the server (as
`Server.create` does, one `ModuleBackend` per block, each seeded from `--seed`) and
owns the chip; the load is other processes pinned to the CPU (`perf/loadgen.py`),
each with a DHT identity of its own.

Set-up: weights on the device from the seed; every program the cell's traffic can
reach is run once (its prompt lengths and every power-of-two session bucket, or its
forward and backward row buckets); the correctness checks against the plain
reference. Then the window, timed by the clients on their own clock."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List

from perf import runtime
from perf.manifest import ROOT, plugin

WORK = ROOT / ".perf_run"  # run-time files; listed in .gitignore


def _block_kwargs(model: Dict[str, Any]) -> Dict[str, Any]:
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
                ffn_inner=model["intermediate_size"], rope_theta=model["rope_theta"], rms_eps=model["rms_norm_eps"])


def _reference_sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    return dict(num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
                rope_theta=model["rope_theta"], rms_eps=model["rms_norm_eps"])


def build_server(config: Dict[str, Any], seed: int, dht):
    """What `Server.create` does for `expert_cls`, with each block's weights drawn
    on the device from its own seed and a frozen (`sgd(0.0)`) optimizer."""
    import optax

    from hivemind_tpu.moe import Server
    from hivemind_tpu.moe.server.layers import name_to_block, name_to_input
    from hivemind_tpu.moe.server.module_backend import ModuleBackend

    model, serving = config["model"], config["serving"]
    backends = {}
    for index in range(model["num_hidden_layers"]):
        uid = f"{serving['uid_prefix']}{index}"
        module = name_to_block[serving["expert_cls"]](model["hidden_size"], **_block_kwargs(model))
        backends[uid] = ModuleBackend(
            uid, module, optimizer=optax.sgd(0.0), sample_input=name_to_input[serving["expert_cls"]](4, model["hidden_size"]),
            max_batch_size=serving["max_batch_size"], rng_seed=(int(seed) * 64 + index) % (2**31 - 1),
        )
    server = Server(dht, backends, decode_max_len=serving["decode_max_len"],
                    decode_max_sessions=serving["decode_max_sessions"],
                    activation_compression=serving["activation_compression"])
    server.run_in_background(await_ready=True)
    return server


def warm_decode(server, config, traffic, log) -> None:
    """Every decode program the traffic can reach, per block: a prefill of each
    prompt length, the single-session step, and the vmapped step at every
    power-of-two bucket up to the slots."""
    import numpy as np

    manager = server.handler.decode_sessions
    hidden = config["model"]["hidden_size"]
    slots = traffic["processes"] * traffic["slots_per_process"]
    top = 1 << (slots - 1).bit_length()
    buckets = [2**k for k in range(1, top.bit_length())]
    token = np.zeros((1, 1, hidden), np.float32)
    shortest = min(traffic["prompt_lengths"])
    for uid in server.backends:
        for length in traffic["prompt_lengths"]:
            manager.decode(uid, f"warm-len{length}", np.zeros((1, length, hidden), np.float32), reset=True)
        manager.decode(uid, f"warm-len{shortest}", token, reset=False)
        names = [f"warm-row{i}" for i in range(max(buckets))]
        for name in names:
            manager.decode(uid, name, np.zeros((1, shortest, hidden), np.float32), reset=True)
        # every full bucket (each row's slice of the stacked caches is a program of
        # its own), and one short of a bucket, which pads with the dummy rows
        for rows in buckets + [max(buckets) - 1]:
            entries = [(None, manager._sessions[(uid, name)], token) for name in names[:rows]]
            raised = [o for o in manager._decode_batch(uid, entries) if isinstance(o, Exception)]
            if raised:
                raise raised[0]
        with manager._lock:  # the warm-up's caches must not sit on the device through the window
            manager._sessions.clear()
    log(f"decode warm-up: prompts {traffic['prompt_lengths']}, session buckets {buckets}, {len(server.backends)} blocks")


def warm_finetune(server, config, traffic, log) -> None:
    """Forward and backward of every row bucket a device batch can have."""
    import numpy as np

    hidden, cap = config["model"]["hidden_size"], config["serving"]["max_batch_size"]
    rows, buckets = traffic["sequences"], []
    total = rows
    while total <= max(cap, rows):
        bucket = 1 << (total - 1).bit_length()
        if bucket not in buckets:
            buckets.append(bucket)
        total += rows
    for backend in server.backends.values():
        for bucket in buckets:
            x = np.zeros((bucket, traffic["positions"], hidden), np.float32)
            backend.forward(x)
            backend.backward(x, x)
    log(f"fine-tune warm-up: row buckets {buckets} x {traffic['positions']} positions, forward and backward, "
        f"{len(server.backends)} blocks")


def check_against_reference(server, client_dht, config, seed, rehearse, path, log) -> List[str]:
    """The cell's own path against the plain reference, outside the window, at the
    published widths: for decode traffic one session's prefill and single-token steps
    through the span against the reference's full forward; for fine-tuning one forward
    and backward against its output and input gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.moe import RemoteSequential
    from perf.reference import mistral_block as reference

    model, serving, tolerances = config["model"], config["serving"], config["tolerances"]
    prefill, steps, rows, length = (16, 4, 2, 16) if rehearse else (128, 16, 2, 128)
    rng = np.random.default_rng(seed)
    pipe = RemoteSequential(client_dht, serving["uid_prefix"], model["num_hidden_layers"])
    all_params = [server.backends[f"{serving['uid_prefix']}{i}"].snapshot_params() for i in range(model["num_hidden_layers"])]
    sizes = _reference_sizes(model)
    faults = []

    if path == "decode":
        stream = runtime.float16_exact(rng.standard_normal((1, prefill + steps, model["hidden_size"]), dtype=np.float32))
        chunks = [pipe.decode_step(stream[:, :prefill], "reference-check", reset=True)]
        for position in range(prefill, prefill + steps):
            chunks.append(pipe.decode_step(stream[:, position:position + 1], "reference-check"))
        pipe.close_decode_session("reference-check")
        got = np.concatenate(chunks, axis=1)
        want = np.asarray(jax.jit(lambda p, x: reference.span(p, x, **sizes))(all_params, jnp.asarray(stream)))
        decode_err = runtime.rel_err(got, want)
        log(f"reference check: prefill {prefill} + {steps} steps through the cache, {decode_err:.2e} of the largest value")
        if not decode_err <= tolerances["decode_rel"]:
            faults.append(f"prefill {prefill} + {steps} steps through the cache is {decode_err:.2e} from the reference's "
                          f"full forward, over {tolerances['decode_rel']}")
        with server.handler.decode_sessions._lock:  # its caches leave the device before the window
            server.handler.decode_sessions._sessions.clear()
        return faults

    x = runtime.float16_exact(rng.standard_normal((rows, length, model["hidden_size"]), dtype=np.float32))
    grad = runtime.float16_exact(rng.standard_normal((rows, length, model["hidden_size"]), dtype=np.float32))
    # the numpy-level calls behind `RemoteSequential.__call__`: under jax, that call is a
    # host callback inside a device program, and in THIS process the device it would
    # hold is the one the server needs to answer it (it deadlocks on a TPU)
    blocks = model["num_hidden_layers"]
    y = pipe._span_forward(0, blocks, x)
    grad_x = pipe._span_backward(0, blocks, x, grad)
    want_y, want_grad = jax.jit(lambda p, a, g: reference.span_input_grad(p, a, g, **sizes))(
        all_params, jnp.asarray(x), jnp.asarray(grad))
    forward_err, backward_err = runtime.rel_err(y, want_y), runtime.rel_err(grad_x, want_grad)
    log(f"reference check: forward of {rows} x {length} {forward_err:.2e}, input gradient {backward_err:.2e} of the largest value")
    if not forward_err <= tolerances["forward_rel"]:
        faults.append(f"forward of {rows} x {length} is {forward_err:.2e} from the reference, over {tolerances['forward_rel']}")
    if not backward_err <= tolerances["backward_rel"]:
        faults.append(f"input gradient of {rows} x {length} is {backward_err:.2e} from the reference, over {tolerances['backward_rel']}")
    return faults


class LoadGenerators:
    """The CPU-pinned client processes of one window."""

    def __init__(self, generator: str, plan, config, maddrs, lead_seconds: float, drain_seconds: float):
        self.children, self.results = [], []
        shutil.rmtree(WORK / "loadgen", ignore_errors=True)
        (WORK / "loadgen").mkdir(parents=True)
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join(
            [str(ROOT)] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p])}
        env.pop("XLA_FLAGS", None)
        total = sum(len(slots) for slots in plan["processes"])
        for index, slots in enumerate(plan["processes"]):
            spec = dict(generator=generator, lead_seconds=lead_seconds, slots_total=total,
                        first_slot=sum(len(s) for s in plan["processes"][:index]), initial_peers=maddrs, uid_prefix=config["serving"]["uid_prefix"],
                        num_blocks=config["model"]["num_hidden_layers"], hidden=config["model"]["hidden_size"],
                        slots=slots, tag=f"p{index}", drain_seconds=drain_seconds,
                        result=str(WORK / "loadgen" / f"result{index}.json"))
            spec_path = WORK / "loadgen" / f"spec{index}.json"
            spec_path.write_text(json.dumps(spec))
            child = subprocess.Popen(
                [sys.executable, "-m", "perf.loadgen", "--spec", str(spec_path)], cwd=ROOT, env=env,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=open(WORK / "loadgen" / f"stderr{index}.log", "w"),
                text=True, start_new_session=True,
            )
            self.children.append((child, spec))

    def wait_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        for child, _spec in self.children:
            line = [None]
            reader = threading.Thread(target=lambda: line.__setitem__(0, child.stdout.readline()), daemon=True)
            reader.start()
            reader.join(max(deadline - time.monotonic(), 1.0))
            if line[0] is None or line[0].strip() != "READY":
                raise RuntimeError(f"a load generator did not become ready (said {line[0]!r}, code {child.poll()})")

    def go(self, begin: float, end: float) -> None:
        for child, _spec in self.children:
            child.stdin.write(f"GO {begin!r} {end!r}\n")
            child.stdin.flush()

    def collect(self, timeout: float) -> List[Dict[str, Any]]:
        deadline = time.monotonic() + timeout
        for child, spec in self.children:
            try:
                code = child.wait(max(deadline - time.monotonic(), 1.0))
            except subprocess.TimeoutExpired:
                raise RuntimeError("a load generator did not finish") from None
            if code != 0:
                raise RuntimeError(f"a load generator exited with code {code}")
            with open(spec["result"]) as handle:
                self.results.append(json.load(handle))
        return self.results

    def stop(self) -> None:
        for child, _spec in self.children:
            if child.poll() is None:
                child.kill()
            child.wait(timeout=30.0)
            for stream in (child.stdin, child.stdout):
                if stream is not None:
                    stream.close()


def run(*, config, workload, chips, seed, seconds, trace, rehearse, started, log) -> Dict[str, Any]:
    import jax

    from hivemind_tpu.dht import DHT

    traffic = workload["traffic"]
    if rehearse:  # the toy block's cache is short: the cell's rehearsal lengths fit it
        traffic = {**traffic, **workload.get("rehearsal_traffic", {})}
    generator = plugin("traffic", traffic["generator"])
    plan = generator.schedule(traffic, seed)
    watch, tap = runtime.CompileWatch(), runtime.LedgerTap()
    devices = jax.devices()[:chips]
    model = config["model"]

    server_dht = DHT(start=True)
    maddrs = [str(m) for m in server_dht.get_visible_maddrs()]
    client_dht = DHT(initial_peers=maddrs, start=True)
    loadgen = None
    server = None
    try:
        built = time.monotonic()
        server = build_server(config, seed, server_dht)
        log(f"{model['num_hidden_layers']} blocks hidden {model['hidden_size']} / {model['num_attention_heads']} heads / "
            f"{model['num_key_value_heads']} kv heads / inner {model['intermediate_size']} on the device in "
            f"{time.monotonic() - built:.1f} s")
        # the clients start now and connect while this process compiles
        lead = float(traffic.get("lead_seconds", 0.0))
        loadgen = LoadGenerators(traffic["generator"], plan, config, maddrs, lead_seconds=lead, drain_seconds=120.0)
        warm = time.monotonic()
        {"decode": warm_decode, "forward_backward": warm_finetune}[generator.SERVER_PATH](server, config, traffic, log)
        log(f"warm-up took {time.monotonic() - warm:.1f} s; {watch.count()} compilations so far")
        faults = check_against_reference(server, client_dht, config, seed, rehearse, generator.SERVER_PATH, log)
        loadgen.wait_ready(timeout=180.0)

        tracer = runtime.Tracer(min(traffic.get("trace_seconds", 4.0), seconds / 2), after=seconds / 4 + 0.5 + lead, log=log) if trace else None
        begin = time.monotonic() + 0.5 + lead  # the lead-in (slots already at work, uncounted) is set-up
        setup_s = begin - started
        loadgen.go(begin, begin + seconds)
        if tracer is not None:
            tracer.start()
        time.sleep(max(begin - time.monotonic(), 0.0))  # the lead-in's records and counts are not the window's
        tap.drain()
        compiles_before, counters_before = watch.count(), runtime.counters()
        results = loadgen.collect(timeout=lead + seconds + 240.0)
        compiles_after, counters_after = watch.count(), runtime.counters()
        records = tap.drain()
        traced = tracer.finish() if tracer is not None else {}
        memory_peak = runtime.memory_peak_bytes(devices, log)
    finally:
        if loadgen is not None:
            loadgen.stop()
        tap.close()
        if server is not None:
            server.shutdown()
        client_dht.shutdown()
        server_dht.shutdown()

    slots = [slot for result in results for slot in result["slots"]]
    samples: Dict[str, List[float]] = {}
    for slot in slots:
        for key, value in slot.items():
            if isinstance(value, list) and key != "errors":
                samples.setdefault(key, []).extend(value)
    attempted, failed = sum(s["attempted"] for s in slots), sum(s["failed"] for s in slots)
    tokens, completed = sum(s["tokens"] for s in slots), sum(s["completed"] for s in slots)
    stuck = sum(result["stuck_slots"] for result in results)
    for error in sorted({e for s in slots for e in s["errors"]})[:5]:
        log(f"client error: {error}")
    serving = [r for r in records["serving"] if "error" not in r]
    shed = [r for r in records["serving"] if "error" in r]
    if compiles_after != compiles_before:
        faults.append(f"{compiles_after - compiles_before} compilation(s) inside the window: the warm-up missed a shape")
    if stuck:
        faults.append(f"{stuck} client slot(s) never returned")
    if not tokens:
        faults.append("no work completed inside the window")
    log(f"window {seconds:.1f} s: {attempted} attempted, {completed} completed, {failed} failed, {tokens} tokens; "
        f"{len(serving)} requests served, {len(shed)} ended in an error on the server; set-up {setup_s:.1f} s")
    for fault in faults:
        log(f"FAULT: {fault}")
    return {
        "correct": not faults,
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "window_s": seconds,
        "counts": {"tokens": tokens, "sessions": completed, "requests": completed},
        "samples": samples,
        "counters": {"before": counters_before, "after": counters_after},
        "serving": serving,
        "device": {"memory_peak_bytes": memory_peak, **(
            {"busy_s": traced["trace"]["busy_s"], "window_s": traced["trace"]["window_s"]} if traced.get("trace") else {})},
        "notes": [f"compilations before the window {compiles_before}, inside it {compiles_after - compiles_before}"],
        **traced,
    }
