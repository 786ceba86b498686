#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # the chip check; fails unless jax finds a TPU
    python chip_smoke.py --rehearse-cpu   # same code, toy sizes, on the CPU: proves
                                          # nothing about the chip and never exits 0

Drives the system's two halves once each through the entry points a user calls, at
the full width of the models the repo supports (depth cut, weights from seeds):

  K  every Pallas kernel, compiled by Mosaic, against float32 references
  T  the trainer: ALBERT-base MLM through `hivemind_tpu.optim.Optimizer`, two peers,
     epochs closed by a successful butterfly all-reduce of group size 2
  S  the server: `Server.create` -> TaskPool -> ModuleBackend / DecodeSessionManager
     driven by RemoteExpert and RemoteSequential (ffn hid 1024; llama_block 4096)
  C  the same serving path through `python -m hivemind_tpu.hivemind_cli.run_server`
     on a synthesized checkpoint, with a CPU-pinned client
  M  (only where jax finds >= 4 devices) T on a dp x tp mesh through SliceOptimizer,
     a tp x sp flash-ring train step, and C with --mesh_devices 4, float32 and int8

One process owns the chip(s) at any moment. This parent never imports jax: it starts
the chip-holding children one after another with its own environment, and every
other process (the serving client) with JAX_PLATFORMS=cpu. Each child writes its
findings to a report file; the first failed check ends the run with a non-zero exit
code. The last line of a passing run is one JSON object naming the device."""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".chip_smoke"  # everything built at run time; listed in .gitignore
TIME_LIMIT_S = 1150.0  # the contract allows 1200 s, compilation included
REHEARSAL_EXIT_CODE = 3

# bf16 compute against a float32 (or other-backend bf16) reference, relative to the
# reference's largest value
SERVING_TOLERANCE = 3e-2
# the same jitted math reached through the server; bf16 outputs, so two bf16 steps
# (2^-8 each) of the largest value cover a differently fused program
WIRE_TOLERANCE = 1e-2
# two peers after gradient + state averaging over an fp16 wire, absolute
PEER_PARAM_TOLERANCE = 2e-3


def sizes(rehearse: bool) -> dict:
    """What each phase runs. The toy column exists only for --rehearse-cpu."""
    if rehearse:
        return dict(
            label="toy sizes (CPU rehearsal)", interpret=True,
            attention=[("toy-bidirectional", 1, 128, 2, 64, False), ("toy-causal", 1, 64, 2, 128, True)],
            quant=(64, 4096),
            albert="tiny", seq_len=128, batch=4, target_batch=32, epochs=2,
            ffn_hidden=64, ffn_batch=4,
            llama=dict(hidden=256, heads=2, inner=512), block_batch=2,
            prefill=16, decode=4, decode_max_len=32,
        )
    return dict(
        label="full width", interpret=False,
        attention=None, quant=None,  # ops.device_check.MAIN_PATH_*
        # ALBERT-base as published; 32 sequences of 512 tokens per peer per step is
        # what one 16 GB chip holds without rematerialization, and a run closes an
        # epoch every 256 sequences so that the smoke sees two of them
        albert="base", seq_len=512, batch=32, target_batch=256, epochs=2,
        ffn_hidden=1024, ffn_batch=16,  # BASELINE config 4
        llama=dict(hidden=4096, heads=32, inner=11008), block_batch=2,  # BASELINE config 5
        prefill=128, decode=32, decode_max_len=256,
    )


class SmokeFailure(Exception):
    pass


def say(text: str = "") -> None:
    print(text, flush=True)


def check(condition: bool, text: str) -> None:
    if not condition:
        raise SmokeFailure(text)
    say(f"    ok  {text}")


def metric(name: str, snapshot: dict = None) -> dict:
    """One metric's values keyed by label value, read from this process's registry
    or from a scraped ``/metrics.json``."""
    if snapshot is None:
        from hivemind_tpu.telemetry import REGISTRY

        snapshot = REGISTRY.snapshot()
    series = snapshot.get(name, {}).get("series", {})
    return {key.split("=", 1)[-1]: value for key, value in series.items()}


def synthesize_checkpoint(path: Path, hidden: int, heads: int, kv_heads: int,
                          inner: int, layers: int) -> None:
    """A sharded HF-layout Llama checkpoint of random weights from seed 0, one shard a
    layer (numpy and safetensors only: the parent calls it). The tests of the loader
    write their checkpoints with it too."""
    import numpy as np
    from safetensors.numpy import save_file

    rng = np.random.RandomState(0)
    (path / "config.json").write_text(json.dumps({
        "hidden_size": hidden, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "intermediate_size": inner,
        "num_hidden_layers": layers, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5,  # Llama-2's value, not the loader's default: it must reach the blocks
    }))
    head_dim = hidden // heads
    weight_map = {}
    scale = 1.0 / np.sqrt(hidden)
    for layer in range(layers):
        prefix = f"model.layers.{layer}."
        tensors = {
            prefix + "self_attn.q_proj.weight": rng.randn(heads * head_dim, hidden) * scale,
            prefix + "self_attn.k_proj.weight": rng.randn(kv_heads * head_dim, hidden) * scale,
            prefix + "self_attn.v_proj.weight": rng.randn(kv_heads * head_dim, hidden) * scale,
            prefix + "self_attn.o_proj.weight": rng.randn(hidden, hidden) * scale,
            prefix + "mlp.gate_proj.weight": rng.randn(inner, hidden) * scale,
            prefix + "mlp.up_proj.weight": rng.randn(inner, hidden) * scale,
            prefix + "mlp.down_proj.weight": rng.randn(hidden, inner) * scale,
            prefix + "input_layernorm.weight": np.ones(hidden),
            prefix + "post_attention_layernorm.weight": np.ones(hidden),
        }
        shard = f"model-{layer:05d}-of-{layers:05d}.safetensors"
        save_file({k: v.astype(np.float32) for k, v in tensors.items()}, path / shard)
        weight_map.update({name: shard for name in tensors})
    (path / "model.safetensors.index.json").write_text(json.dumps({"weight_map": weight_map}))


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-9))


# =========================================================================== parent


class Child:
    """One process this script started; its output is echoed line by line."""

    def __init__(self, name: str, argv: list, env: dict):
        self.name, self.lines = name, []
        say(f"[parent] starting {name}: {' '.join(argv)}")
        self.process = subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, start_new_session=True,
        )
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.process.stdout:
            self.lines.append(line.rstrip("\n"))
            say(f"[{self.name}] {line.rstrip()}")

    def wait(self, deadline: float) -> int:
        try:
            code = self.process.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name} did not finish inside the time limit") from None
        self._reader.join(timeout=5.0)
        return code

    def wait_for_line(self, marker: str, deadline: float) -> str:
        seen = 0
        while time.monotonic() < deadline:
            upto = len(self.lines)
            for line in self.lines[seen:upto]:
                if marker in line:
                    return line
            seen = upto
            if self.process.poll() is not None:
                raise SmokeFailure(f"{self.name} exited with code {self.process.returncode} before {marker!r}")
            time.sleep(0.2)
        raise SmokeFailure(f"{self.name} did not print {marker!r} inside the time limit")

    def stop(self) -> None:
        if self.process.poll() is None:
            os.killpg(self.process.pid, signal.SIGINT)  # run_server shuts down on it
            try:
                self.process.wait(timeout=20.0)
            except subprocess.TimeoutExpired:
                os.killpg(self.process.pid, signal.SIGKILL)
                self.process.wait(timeout=10.0)


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def run_parent(args) -> int:
    started = time.monotonic()
    deadline = started + TIME_LIMIT_S
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    phases = args.phases.upper().split(",")
    chip_env = dict(os.environ)  # the chip-holding children see what we were given
    client_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if args.rehearse_cpu:
        say("REHEARSAL on the CPU at toy sizes: this proves nothing about the chip.")
        flags = os.environ.get("XLA_FLAGS", "")
        chip_env = {**client_env, "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count=4".strip()}
    common = ["--rehearse-cpu"] if args.rehearse_cpu else []
    children: list = []

    def run_child(name: str, role: str, env: dict, *extra: str) -> dict:
        report_path = WORK / f"{name}.json"
        child = Child(
            name, [sys.executable, str(REPO / "chip_smoke.py"), "--child", role,
                   "--report", str(report_path), *common, *extra], env,
        )
        children.append(child)
        code = child.wait(deadline)
        if code != 0:
            raise SmokeFailure(f"{name} exited with code {code}")
        return json.loads(report_path.read_text())

    def serve(name: str, *server_args: str) -> tuple:
        """run_server as the chip-holding child; returns (child, maddr, metrics url)."""
        port = free_port()
        server = Child(
            name, [sys.executable, "-m", "hivemind_tpu.hivemind_cli.run_server",
                   "--llama_checkpoint", str(WORK / "checkpoint"), "--llama_uid_prefix", "ckpt.",
                   "--decode_max_len", str(sz["decode_max_len"]), "--metrics-port", str(port),
                   *server_args], chip_env,
        )
        children.append(server)
        devices = json.loads(server.wait_for_line("devices: ", deadline).split("devices: ", 1)[1])
        if not args.rehearse_cpu and devices["platform"] != "tpu":
            raise SmokeFailure(f"{name} runs on {devices['platform']!r}, not on the tpu")
        maddr = server.wait_for_line("listening: ", deadline).split("listening: ", 1)[1].strip()
        server.wait_for_line("serving 2 experts", deadline)
        return server, maddr, f"http://127.0.0.1:{port}"

    sz = sizes(args.rehearse_cpu)
    try:
        report = run_child("device", "device", chip_env, "--phases", ",".join(p for p in phases if p in "KTS"))
        device = report["device"]
        mesh_phase = "M" in phases and device["count"] >= 4
        if "C" in phases or mesh_phase:
            (WORK / "checkpoint").mkdir()
            llama = sz["llama"]
            synthesize_checkpoint(WORK / "checkpoint", llama["hidden"], llama["heads"], llama["heads"],
                                  llama["inner"], layers=2)
        if "C" in phases:
            say("== phase C: run_server (the CLI) on a synthesized checkpoint, CPU-pinned client")
            server, maddr, metrics = serve("server")
            run_child("client", "client", client_env, "--maddr", maddr, "--metrics", metrics,
                      "--save", str(WORK / "decode_one_chip.npy"))
            server.stop()
        if mesh_phase:
            run_child("mesh", "mesh", chip_env)
            if "C" in phases:
                say("== phase M(c): run_server --mesh_devices 4, outputs against the one-chip server's")
                server, maddr, metrics = serve("mesh-server", "--mesh_devices", "4")
                run_child("mesh-client", "client", client_env, "--maddr", maddr, "--metrics", metrics,
                          "--compare", str(WORK / "decode_one_chip.npy"), "--mesh_devices", "4")
                server.stop()
            say("== phase M(d): run_server --mesh_devices 4 --weight_quantization int8 (the codec per shard)")
            server, maddr, metrics = serve("mesh-int8-server", "--mesh_devices", "4", "--weight_quantization", "int8")
            run_child("mesh-int8-client", "client", client_env, "--maddr", maddr, "--metrics", metrics,
                      "--mesh_devices", "4", "--int8")
            server.stop()
        elif "M" in phases:
            say(f"== phase M: NOT RUN — jax found {device['count']} device(s), the mesh phase needs 4")
    except SmokeFailure as failure:
        say(f"FAIL: {failure}")
        return 1
    finally:
        for child in children:
            child.stop()

    cache_dir = Path(report["compilation_cache_dir"])
    entries = sum(1 for _ in cache_dir.iterdir()) if cache_dir.is_dir() else 0
    say(f"compilation cache: {cache_dir} holds {entries} entries")
    say(f"all phases passed in {time.monotonic() - started:.0f} s")
    if args.rehearse_cpu:
        say(f"REHEARSAL passed on {device}: not a chip pass, exit code {REHEARSAL_EXIT_CODE}.")
        return REHEARSAL_EXIT_CODE
    if entries == 0:
        say("FAIL: the run left no entries in the compilation cache")
        return 1
    say(json.dumps({"ok": True, "device": device}))
    return 0


# ===================================================================== chip children


def start_child(args) -> dict:
    """Common start of every child: place the compile cache, name the device and the
    installation, refuse anything but a TPU unless this is the labelled rehearsal."""
    import jax

    from hivemind_tpu.utils.platform import configure_compilation_cache, describe_devices

    cache_dir = configure_compilation_cache()
    device = describe_devices()
    say(f"platform={device['platform']} device_kind={device['kind']!r} device_count={device['count']}")
    if args.child == "client":
        say(f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')}: this process is pinned to the CPU, "
            f"the server owns the chip")
        if device["platform"] != "cpu":
            raise SmokeFailure("the serving client must not hold an accelerator")
    elif device["platform"] != "tpu" and not args.rehearse_cpu:
        raise SmokeFailure(
            f"jax.devices()[0].platform is {device['platform']!r}, not 'tpu' — nothing to check "
            f"(--rehearse-cpu runs the toy rehearsal)"
        )
    import flax
    import jaxlib
    import numpy
    import optax

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    say(f"python {sys.version.split()[0]}, jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu_version}, flax {flax.__version__}, optax {optax.__version__}, "
        f"numpy {numpy.__version__}")
    say(f"compilation cache: {cache_dir}")
    return {"device": device, "compilation_cache_dir": cache_dir}


class Phases:
    """Prints a header per phase and, when it ends, its wall and compile seconds."""

    def __init__(self):
        import jax

        self.compile_seconds, self.compiles = 0.0, 0
        self.summary = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kwargs) -> None:
        if event.startswith("/jax/core/compile/"):  # trace + lowering + backend compile
            self.compile_seconds += duration
            self.compiles += event.endswith("backend_compile_duration")

    def run(self, key: str, title: str, fn, *fn_args) -> None:
        say(f"== phase {key}: {title}")
        started, compile_before, compiles_before = time.monotonic(), self.compile_seconds, self.compiles
        fn(*fn_args)
        self.summary[key] = {
            "seconds": round(time.monotonic() - started, 1),
            "compile_seconds": round(self.compile_seconds - compile_before, 1),
            "compiles": self.compiles - compiles_before,
        }
        say(f"== phase {key} passed: {self.summary[key]['seconds']} s, of which "
            f"{self.summary[key]['compile_seconds']} s tracing and compiling "
            f"{self.summary[key]['compiles']} programs")


def count_kernels(jitted, *example_args) -> int:
    """Distinct Mosaic custom calls in the lowered program of a jitted function (a
    kernel called at one shape from several layers lowers to one function)."""
    return getattr(jitted, "jitted", jitted).lower(*example_args).as_text().count("tpu_custom_call")


def phase_kernels(sz: dict) -> None:
    from hivemind_tpu.ops.device_check import (
        ATTENTION_TOLERANCE,
        MAIN_PATH_ATTENTION,
        MAIN_PATH_QUANT_SHAPE,
        AttentionShape,
        validate_kernels,
    )

    shapes = [AttentionShape(*s) for s in sz["attention"]] if sz["attention"] else MAIN_PATH_ATTENTION
    quant = tuple(sz["quant"] or MAIN_PATH_QUANT_SHAPE)
    say(f"  pallas_call interpret={sz['interpret']}; bf16 operands; references in float32")
    for shape in shapes:
        say(f"  flash forward and fused backward at {shape}")
    say(f"  blockwise int8 quantize/dequantize at {quant[0]}x{quant[1]} float32")
    report = validate_kernels(sz["interpret"], shapes, quant)  # raises on the first failure
    for name, errors in report.items():
        if isinstance(errors, dict):
            say(f"    ok  {name}: " + ", ".join(f"{k}={v:.3g}" for k, v in errors.items()))
    say(f"    (attention tolerance {ATTENTION_TOLERANCE} of the reference's largest value)")


def albert_config(sz: dict, **overrides):
    from hivemind_tpu.models import AlbertConfig

    if sz["albert"] == "base":
        return AlbertConfig.base(max_position=sz["seq_len"], **overrides)
    return AlbertConfig.tiny(max_position=sz["seq_len"], num_heads=4, **overrides)


def albert_recipe():
    """The optimizer of examples/albert/run_trainer.py."""
    from hivemind_tpu.moe.server.layers import lamb_with_warmup

    return lamb_with_warmup(1e-3, 100, 10_000)


def averaging_outcomes() -> dict:
    return metric("hivemind_optim_averaging_rounds_total")


def check_swarm_rounds(rounds_before: int, outcomes_before: dict, peers: int, epochs: int) -> None:
    """The epochs must have ended in SUCCESSFUL group-of-two all-reduces: the
    optimizers log a warning and carry on with local gradients when a round fails,
    which is right for a swarm and wrong for a smoke."""
    from hivemind_tpu.telemetry.ledger import LEDGER

    outcomes = averaging_outcomes()
    ok = outcomes.get("ok", 0) - outcomes_before.get("ok", 0)
    degraded = outcomes.get("degraded_to_local", 0) - outcomes_before.get("degraded_to_local", 0)
    check(degraded == 0, f"no epoch fell back to local gradients ({degraded:.0f} did)")
    check(ok >= peers * epochs, f"{ok:.0f} gradient rounds succeeded across {peers} peers x {epochs} epochs")
    rounds = LEDGER.records()[rounds_before:]
    pairs = [r for r in rounds if r.get("group_size") == 2]
    check(len(pairs) >= peers * epochs,
          f"round ledger: {len(pairs)} of {len(rounds)} all-reduce rounds ran with group size 2")


def run_peers(loops: list, timeout: float) -> None:
    """Run one training loop per peer on its own thread; re-raise the first error."""
    errors: list = []

    def guarded(loop):
        try:
            loop()
        except BaseException as e:  # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=guarded, args=(loop,), daemon=True) for loop in loops]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout)
    if errors:
        raise errors[0]
    if any(thread.is_alive() for thread in threads):
        raise SmokeFailure(f"a peer did not reach its epoch within {timeout:.0f} s")


def phase_trainer(sz: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.models import AlbertForMaskedLM, make_mlm_loss_fn, make_synthetic_mlm_batch
    from hivemind_tpu.optim import Optimizer
    from hivemind_tpu.telemetry.ledger import LEDGER

    config = albert_config(sz)
    model = AlbertForMaskedLM(config)
    loss_and_grad = jax.jit(jax.value_and_grad(make_mlm_loss_fn(model, masked_loss_fraction=0.25)))
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), config, sz["batch"], sz["seq_len"])
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"][:1, :8])["params"]
    count = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    say(f"  ALBERT-{sz['albert']}: hidden {config.hidden_size}, {config.num_layers} shared layers, "
        f"{config.num_heads} heads, vocab {config.vocab_size}, {count / 1e6:.1f}M parameters, "
        f"{jnp.dtype(config.dtype).name} compute")
    say(f"  per peer per step {sz['batch']} x {sz['seq_len']} tokens, masked-only loss (0.25), LAMB + "
        f"warmup + clipping, epoch = {sz['target_batch']} sequences, 2 peers, {sz['epochs']} epochs")
    kernels = count_kernels(loss_and_grad, params, batch)
    if jax.default_backend() == "tpu":
        check(kernels >= 2, f"the train step's program calls {kernels} distinct Mosaic kernels (flash forward, fused backward)")
    started = time.monotonic()
    first_loss = float(loss_and_grad(params, batch)[0])
    say(f"  first step, cold (trace + compile + run): {time.monotonic() - started:.1f} s")
    check(abs(first_loss - math.log(config.vocab_size)) < 1.0,
          f"loss at initialization {first_loss:.3f} is ln(vocab) = {math.log(config.vocab_size):.3f} +- 1")

    boot = DHT(start=True)
    dhts = [boot, DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)]
    rounds_before, outcomes_before = len(LEDGER.records()), averaging_outcomes()
    optimizers = [
        Optimizer(
            dht=dht, run_id="chip_smoke_albert", target_batch_size=sz["target_batch"],
            params=jax.tree_util.tree_map(jnp.copy, params), optimizer=albert_recipe(),
            batch_size_per_step=sz["batch"], matchmaking_time=3.0, averaging_timeout=60.0,
            target_group_size=2, verbose=True,
        )
        for dht in dhts
    ]
    losses: list = [[], []]

    def peer_loop(index: int):
        def loop():
            rng = jax.random.PRNGKey(100 + index)
            while optimizers[index].local_epoch < sz["epochs"]:
                rng, key = jax.random.split(rng)
                peer_batch = make_synthetic_mlm_batch(key, config, sz["batch"], sz["seq_len"])
                loss, grads = loss_and_grad(optimizers[index].params, peer_batch)
                losses[index].append(float(loss))
                optimizers[index].step(grads)

        return loop

    try:
        run_peers([peer_loop(0), peer_loop(1)], timeout=420.0)
        for index, peer_losses in enumerate(losses):
            check(bool(np.isfinite(peer_losses).all()),
                  f"peer {index}: {len(peer_losses)} steps, every loss finite (last {peer_losses[-1]:.3f})")
        check_swarm_rounds(rounds_before, outcomes_before, peers=2, epochs=sz["epochs"])
        epochs = [opt.local_epoch for opt in optimizers]
        check(epochs[0] == epochs[1] == sz["epochs"], f"both peers are at epoch {epochs}")
        leaves = [jax.tree_util.tree_leaves(opt.params) for opt in optimizers]
        apart = max(float(jnp.abs(a - b).max()) for a, b in zip(*leaves))
        check(apart < PEER_PARAM_TOLERANCE,
              f"the peers' parameters differ by at most {apart:.2e} (tolerance {PEER_PARAM_TOLERANCE})")
        moved = max(float(jnp.abs(a - b).max()) for a, b in zip(leaves[0], jax.tree_util.tree_leaves(params)))
        check(moved > 0.0, f"the update was applied: parameters moved by up to {moved:.2e} from initialization")
    finally:
        for opt in optimizers:
            opt.shutdown()
        for dht in dhts:
            dht.shutdown()


def dense_reference_params(params):
    """A (possibly int8-stored) parameter tree as dense float32, decoded with numpy
    so that the reference does not depend on the kernel it checks."""
    import jax
    import numpy as np

    from hivemind_tpu.ops.quantized_params import QuantizedTensor

    def decode(leaf):
        if not isinstance(leaf, QuantizedTensor):
            return leaf
        flat = np.asarray(leaf.codes, np.float32) * (np.asarray(leaf.absmax)[:, None] / 127.0)
        return flat.reshape(-1)[: leaf.size].reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree_util.tree_map(decode, params, is_leaf=lambda leaf: isinstance(leaf, QuantizedTensor))


def wire_exact(array):
    """Round to what the float16 wire carries, so inputs reach the server unchanged."""
    import numpy as np

    return np.asarray(array, np.float16).astype(np.float32)


def decode_sessions(pipe, inputs, names: list, prefill: int) -> list:
    """Prefill each named session with ``inputs[i][:, :prefill]``, then step all of
    them one token at a time — concurrently, behind a barrier, when there are
    several. Returns per session the outputs of every position."""
    import numpy as np

    outputs = [[pipe.decode_step(x[:, :prefill], name, reset=True)] for x, name in zip(inputs, names)]
    barrier = threading.Barrier(len(names))

    def stepper(index: int):
        def loop():
            for pos in range(prefill, inputs[index].shape[1]):
                barrier.wait(timeout=120.0)
                outputs[index].append(pipe.decode_step(inputs[index][:, pos : pos + 1], names[index]))

        return loop

    run_peers([stepper(i) for i in range(len(names))], timeout=300.0)
    for name in names:
        pipe.close_decode_session(name)
    return [np.concatenate(chunks, axis=1) for chunks in outputs]


def decode_step_counts() -> dict:
    return metric("hivemind_moe_decode_steps_total")


def check_decode_chain(name: str, outputs, reference, against: str) -> None:
    import numpy as np

    check(outputs.shape == reference.shape and bool(np.isfinite(outputs).all()),
          f"{name}: {outputs.shape[1]} positions, finite")
    last, everywhere = rel_err(outputs[:, -1], reference[:, -1]), rel_err(outputs, reference)
    check(everywhere < SERVING_TOLERANCE,
          f"{name}: decode chain against {against}: last position {last:.2e}, all positions "
          f"{everywhere:.2e} (tolerance {SERVING_TOLERANCE})")


def serving_counters(served: dict, metrics: dict = None) -> dict:
    """What the checked window must and must not move, from the serving ledger's
    summary and the metric registry — the server's own, or scraped from it."""
    return {
        "requests": served["requests"], "failed": served["errors"] + served["sheds"],
        "compiles": sum(metric("hivemind_device_compiles_total", metrics).values()),
        "moved": metric("hivemind_device_transfer_bytes_total", metrics),
    }


def check_serving_counters(before: dict, after: dict) -> None:
    requests, failed = after["requests"] - before["requests"], after["failed"] - before["failed"]
    check(requests > 0 and failed == 0, f"{requests} requests served after warm-up, {failed} failed or shed")
    compiles = after["compiles"] - before["compiles"]
    check(compiles == 0, f"{compiles:.0f} compile events after warm-up, tracked sites and jax's own "
                         f"({before['compiles']:.0f} before)")
    moved = {key: after["moved"][key] - before["moved"].get(key, 0) for key in after["moved"]}
    check(all(moved.get(key, 0) > 0 for key in ("host_to_device", "device_to_host")),
          f"hivemind_device_transfer_bytes_total moved: {moved}")


def phase_server(sz: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteExpert, RemoteSequential, Server
    from hivemind_tpu.moe.server.dht_handler import get_experts
    from hivemind_tpu.telemetry.serving import SERVING_LEDGER

    llama, on_tpu = sz["llama"], jax.default_backend() == "tpu"
    block_kwargs = {"num_heads": llama["heads"], "ffn_inner": llama["inner"]}
    total = sz["prefill"] + sz["decode"]
    say(f"  Server.create x3 (what run_server calls): 1 ffn expert hid {sz['ffn_hidden']}; "
        f"2 llama_block hidden {llama['hidden']} / {llama['heads']} heads / inner {llama['inner']}, Adam; "
        f"1 llama_block of the same width served int8 weight-only")
    say(f"  requests: forward + backward on each class (ffn batch {sz['ffn_batch']}; block batch "
        f"{sz['block_batch']} x schema length 64), prefill {sz['prefill']} + {sz['decode']} decode steps "
        f"through both blocks, two sessions stepping concurrently, int8 forward and decode")

    boot = DHT(start=True)
    maddrs = [str(m) for m in boot.get_visible_maddrs()]
    dhts = [boot] + [DHT(initial_peers=maddrs, start=True) for _ in range(3)]
    client_dht = dhts[3]
    servers = [
        Server.create(expert_uids=["ffn.0"], expert_cls="ffn", hidden_dim=sz["ffn_hidden"],
                      dht=dhts[0], start=True),
        Server.create(expert_uids=["blk.0", "blk.1"], expert_cls="llama_block", hidden_dim=llama["hidden"],
                      expert_kwargs=block_kwargs, decode_max_len=sz["decode_max_len"], dht=dhts[1], start=True),
        Server.create(expert_uids=["q8.0"], expert_cls="llama_block", hidden_dim=llama["hidden"],
                      expert_kwargs=block_kwargs, decode_max_len=sz["decode_max_len"], dht=dhts[2],
                      weight_quantization="int8", start=True),
    ]
    backends = {uid: backend for server in servers for uid, backend in server.backends.items()}
    try:
        uids = ["ffn.0", "blk.0", "blk.1", "q8.0"]
        give_up = time.monotonic() + 30.0
        while None in (infos := get_experts(client_dht, uids)) and time.monotonic() < give_up:
            time.sleep(0.5)
        check(None not in infos, f"the client resolves {uids} from the DHT")
        experts = {uid: RemoteExpert(info, client_dht.node.p2p) for uid, info in zip(uids, infos)}
        pipe = RemoteSequential(client_dht, "blk.", 2)
        pipe_q8 = RemoteSequential(client_dht, "q8.", 1)

        rng = np.random.RandomState(7)
        x_ffn = wire_exact(rng.randn(sz["ffn_batch"], sz["ffn_hidden"]))
        x_blk = wire_exact(rng.randn(sz["block_batch"], 64, llama["hidden"]))
        g_ffn, g_blk = wire_exact(rng.randn(*x_ffn.shape)), wire_exact(rng.randn(*x_blk.shape))
        streams = [wire_exact(rng.randn(1, total, llama["hidden"])) for _ in range(2)]

        def requests(tag: str, decode_rounds: int = 1) -> dict:
            """Every kind of request once; backward LAST, because it trains the expert."""
            out = {
                "ffn_fwd": experts["ffn.0"].forward_np(x_ffn)[0],
                "blk_fwd": experts["blk.0"].forward_np(x_blk)[0],
                "q8_fwd": experts["q8.0"].forward_np(x_blk)[0],
                "single": decode_sessions(pipe, streams[:1], [f"{tag}-single"], sz["prefill"])[0],
            }
            for attempt in range(decode_rounds):
                out["pair"] = decode_sessions(pipe, streams, [f"{tag}-a{attempt}", f"{tag}-b{attempt}"], sz["prefill"])
                if decode_step_counts().get("batched", 0) > 0:
                    break  # the vmapped program exists now
            out["q8_decode"] = decode_sessions(pipe_q8, streams[:1], [f"{tag}-q8"], sz["prefill"])[0]
            out["ffn_bwd"] = experts["ffn.0"].backward_np(x_ffn, g_ffn)[0]
            out["blk_bwd"] = experts["blk.0"].backward_np(x_blk, g_blk)[0]
            return out

        started = time.monotonic()
        requests("warm", decode_rounds=8)
        say(f"  warm-up pass (every program compiles here): {time.monotonic() - started:.1f} s")
        check(decode_step_counts().get("batched", 0) > 0,
              f"concurrent sessions merged into the vmapped step: {decode_step_counts()}")
        if on_tpu:
            for uid, example, least in (("blk.0", x_blk, 1), ("q8.0", x_blk, 2)):  # flash (+ the int8 decoder)
                kernels = count_kernels(backends[uid]._jit_forward, backends[uid].snapshot_params(), jnp.asarray(example))
                check(kernels >= least, f"{uid}: the serving forward program calls {kernels} distinct Mosaic kernels")

        # float32 inputs and parameters into module.apply, locally, with the
        # parameters the servers hold now (the warm-up's backward trained them)
        def local(uid, x):
            params = dense_reference_params(backends[uid].snapshot_params())
            return jax.jit(backends[uid].module.apply)({"params": params}, jnp.asarray(x))

        def local_grad(uid, x, g):
            apply = lambda xx: backends[uid].module.apply({"params": backends[uid].snapshot_params()}, xx)
            return jax.jit(lambda xx, gg: jax.vjp(apply, xx)[1](gg)[0])(jnp.asarray(x), jnp.asarray(g))

        both = jnp.concatenate([jnp.asarray(s) for s in streams])
        want = {
            "ffn_fwd": local("ffn.0", x_ffn), "blk_fwd": local("blk.0", x_blk), "q8_fwd": local("q8.0", x_blk),
            "pair": local("blk.1", local("blk.0", both)), "q8_decode": local("q8.0", streams[0]),
            "ffn_bwd": local_grad("ffn.0", x_ffn, g_ffn), "blk_bwd": local_grad("blk.0", x_blk, g_blk),
        }
        want = {key: np.asarray(value) for key, value in want.items()}
        updates_before = {uid: backends[uid].update_count for uid in ("ffn.0", "blk.0")}

        before, steps_before = serving_counters(SERVING_LEDGER.summary()), decode_step_counts()
        got = requests("checked")
        after, steps = serving_counters(SERVING_LEDGER.summary()), decode_step_counts()

        for key, tolerance in (("ffn_fwd", WIRE_TOLERANCE), ("blk_fwd", WIRE_TOLERANCE), ("q8_fwd", WIRE_TOLERANCE),
                               ("ffn_bwd", SERVING_TOLERANCE), ("blk_bwd", SERVING_TOLERANCE)):
            err = rel_err(got[key], want[key])
            check(got[key].shape == want[key].shape and err < tolerance,
                  f"{key} {got[key].shape} against local module.apply: {err:.2e} (tolerance {tolerance})")
        for key, outputs, reference in (
            ("single session", got["single"], want["pair"][:1]),
            ("concurrent session a", got["pair"][0], want["pair"][:1]),
            ("concurrent session b", got["pair"][1], want["pair"][1:]),
            ("int8 session", got["q8_decode"], want["q8_decode"]),
        ):
            check_decode_chain(key, outputs, reference, "the full-sequence forward")
        for uid, updates in updates_before.items():
            check(backends[uid].update_count == updates + 1, f"{uid}: the backward request applied one optimizer update")
        check_serving_counters(before, after)
        say(f"    decode steps in the checked pass: "
            f"{ {key: steps[key] - steps_before.get(key, 0) for key in steps} }")
    finally:
        for server in servers:
            server.shutdown()
        for dht in reversed(dhts):
            dht.shutdown()


# ============================================================================= mesh


def device_memory_table() -> list:
    """Per device: live bytes as DeviceMemoryMonitor attributes them (each device
    counts the shard it holds), what an even split of every array's bytes would
    say, and the backend's own bytes_in_use."""
    import jax

    from hivemind_tpu.telemetry.device import DeviceMemoryMonitor

    sample = DeviceMemoryMonitor().sample()
    even: dict = {}
    for array in jax.live_arrays():
        devices = list(array.devices())
        for device in devices:
            even[str(device)] = even.get(str(device), 0) + array.nbytes // len(devices)
    rows = []
    for device in jax.local_devices():
        entry = sample["devices"].get(str(device), {})
        stats = device.memory_stats() or {}
        rows.append({"device": str(device), "by_shard": entry.get("bytes", 0), "even_split": even.get(str(device), 0),
                     "bytes_in_use": stats.get("bytes_in_use")})
        say(f"    {rows[-1]}")
    return rows


def check_placement(tree, mesh, what: str, ways: int) -> None:
    """The tree lives on every device of the mesh, and each leaf its rule shards
    costs a device 1/``ways`` of the leaf's bytes."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    devices = set().union(*(leaf.devices() for leaf in leaves))
    check(devices == set(mesh.devices.flat), f"{what} live on {len(devices)} distinct devices")
    shard_bytes = lambda leaf: math.prod(leaf.sharding.shard_shape(leaf.shape)) * leaf.dtype.itemsize
    sharded = [leaf for leaf in leaves if not leaf.sharding.is_fully_replicated]
    whole, each = sum(leaf.nbytes for leaf in sharded), sum(shard_bytes(leaf) for leaf in sharded)
    replicated = sum(leaf.nbytes for leaf in leaves) - whole
    check(sharded and each * ways == whole,
          f"{what}: {len(sharded)} sharded leaves hold {whole / 1e6:.1f} MB, {each / 1e6:.1f} MB (1/{ways}) on each "
          f"device; the other {replicated / 1e6:.1f} MB replicate")


def phase_mesh(sz: dict) -> None:
    """ALBERT on four devices: (a) through SliceOptimizer on dp=2 x tp=2 beside a host
    Optimizer peer, until an epoch closes in a successful swarm round; (b) one train
    step on tp=2 x sp=2 — the flash ring — against the same step on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.models import AlbertForMaskedLM, make_mlm_loss_fn, make_synthetic_mlm_batch
    from hivemind_tpu.optim import Optimizer, SliceOptimizer
    from hivemind_tpu.parallel import make_mesh, params_shardings
    from hivemind_tpu.telemetry.ledger import LEDGER

    on_tpu = jax.default_backend() == "tpu"
    host_config = albert_config(sz)
    host_model = AlbertForMaskedLM(host_config)
    host_loss_and_grad = jax.jit(jax.value_and_grad(make_mlm_loss_fn(host_model, 0.25)))
    batch = make_synthetic_mlm_batch(jax.random.PRNGKey(0), host_config, sz["batch"], sz["seq_len"])
    params = host_model.init(jax.random.PRNGKey(0), batch["input_ids"][:1, :8])["params"]

    def on_mesh(**axes):
        mesh = make_mesh(**axes)
        model = AlbertForMaskedLM(albert_config(sz, mesh=mesh))
        with mesh:
            loss_and_grad = jax.jit(jax.value_and_grad(make_mlm_loss_fn(model, 0.25)))
        return mesh, loss_and_grad, jax.device_put(params, params_shardings(params, mesh))

    say(f"  (b) one train step, {sz['batch']} x {sz['seq_len']} tokens, on make_mesh(tp=2, sp=2): "
        f"pallas_call inside lax.scan inside shard_map")
    mesh, ring_loss_and_grad, ring_params = on_mesh(tp=2, sp=2)
    ring_batch = jax.device_put(batch, NamedSharding(mesh, P("dp", "sp")))
    check_placement(ring_params, mesh, "tp x sp parameters", ways=2)
    if on_tpu:
        with mesh:
            kernels = count_kernels(ring_loss_and_grad, ring_params, ring_batch)
        check(kernels >= 1, f"the flash-ring step's program calls {kernels} distinct Mosaic kernels")
    with mesh:
        ring_loss, ring_grads = ring_loss_and_grad(ring_params, ring_batch)
    one_loss, one_grads = host_loss_and_grad(params, batch)
    check(abs(float(ring_loss) - float(one_loss)) < 2e-2,
          f"flash-ring loss {float(ring_loss):.4f} against one device's {float(one_loss):.4f} (tolerance 2e-2)")
    # over the whole tree: leaves whose gradient is zero in exact arithmetic (the key
    # bias, under softmax) hold nothing but rounding
    pairs = list(zip(*map(jax.tree_util.tree_leaves, (ring_grads, one_grads))))
    grad_err = math.sqrt(sum(float(jnp.sum((a - b) ** 2)) for a, b in pairs) / sum(float(jnp.sum(b**2)) for _, b in pairs))
    check(grad_err < 5e-2, f"flash-ring gradient against one device's, relative L2 distance: {grad_err:.2e} (tolerance 5e-2)")
    del ring_params, ring_grads, one_grads

    say(f"  (a) SliceOptimizer on make_mesh(dp=2, tp=2) beside a host Optimizer peer, "
        f"epoch = {sz['target_batch']} sequences, until epoch 1 closes")
    mesh, slice_loss_and_grad, slice_params = on_mesh(dp=2, tp=2)
    slice_batch = jax.device_put(batch, NamedSharding(mesh, P("dp", "sp")))
    with mesh:
        if on_tpu:
            kernels = count_kernels(slice_loss_and_grad, slice_params, slice_batch)
            check(kernels >= 2, f"the dp x tp step's program calls {kernels} distinct Mosaic kernels, per shard")
        # compile before the swarm starts, as phase T does: a peer that spends its first
        # half minute compiling while the other waits in matchmaking is a test of the
        # swarm's patience, not of the chip
        started = time.monotonic()
        float(slice_loss_and_grad(slice_params, slice_batch)[0])
        say(f"  first dp x tp step, cold (trace + compile + run): {time.monotonic() - started:.1f} s")
    boot = DHT(start=True)
    host_dht = DHT(initial_peers=[str(m) for m in boot.get_visible_maddrs()], start=True)
    rounds_before, outcomes_before = len(LEDGER.records()), averaging_outcomes()
    common = dict(run_id="chip_smoke_slice", optimizer=optax.adamw(1e-3), target_batch_size=sz["target_batch"],
                  batch_size_per_step=sz["batch"], target_group_size=2, matchmaking_time=3.0,
                  averaging_timeout=60.0, verbose=True)
    slice_opt = SliceOptimizer(mesh=mesh, params=slice_params, dht_factory=lambda: boot, **common)
    host_opt = Optimizer(dht=host_dht, params=jax.tree_util.tree_map(jnp.copy, params), **common)
    losses: list = [[], []]

    def slice_loop():
        rng = jax.random.PRNGKey(200)
        while slice_opt.local_epoch < 1:
            rng, key = jax.random.split(rng)
            peer_batch = make_synthetic_mlm_batch(key, host_config, sz["batch"], sz["seq_len"])
            peer_batch = jax.device_put(peer_batch, NamedSharding(mesh, P("dp", "sp")))
            with mesh:
                loss, grads = slice_loss_and_grad(slice_opt.params, peer_batch)
            losses[0].append(float(loss))
            slice_opt.step(grads, batch_size=sz["batch"])

    def host_loop():
        rng = jax.random.PRNGKey(201)
        while host_opt.local_epoch < 1:
            rng, key = jax.random.split(rng)
            loss, grads = host_loss_and_grad(host_opt.params, make_synthetic_mlm_batch(key, host_config, sz["batch"], sz["seq_len"]))
            losses[1].append(float(loss))
            host_opt.step(grads, batch_size=sz["batch"])

    try:
        run_peers([slice_loop, host_loop], timeout=420.0)
        for name, peer_losses in zip(("slice", "host"), losses):
            check(bool(np.isfinite(peer_losses).all()), f"{name} peer: {len(peer_losses)} steps, every loss finite")
        check_swarm_rounds(rounds_before, outcomes_before, peers=2, epochs=1)
        check(slice_opt.local_epoch == host_opt.local_epoch == 1, "slice and host peers are both at epoch 1")
        check_placement(slice_opt.params, mesh, "dp x tp parameters after the update", ways=2)
        apart = max(rel_err(a, b) for a, b in zip(*map(jax.tree_util.tree_leaves, (slice_opt.params, host_opt.params))))
        check(apart < PEER_PARAM_TOLERANCE, f"slice and host parameters differ by {apart:.2e} of the largest value")
        say("  per-device memory (satellite: even split under-counts replicated arrays):")
        rows = device_memory_table()
        if on_tpu:
            check(all(row["bytes_in_use"] for row in rows), "memory_stats()['bytes_in_use'] is non-zero on every chip")
    finally:
        slice_opt.shutdown()
        host_opt.shutdown()
        host_dht.shutdown()


# =========================================================================== client


def scrape(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(url, timeout=30.0) as response:
        return json.loads(response.read())


def scraped_counters(url: str) -> dict:
    return serving_counters(scrape(url + "/serving")["summary"], scrape(url + "/metrics.json"))


def child_client(args, sz: dict) -> None:
    """The CPU-pinned client of the run_server child: a decode chain through both
    checkpoint blocks against the same blocks applied to the whole sequence here."""
    import jax.numpy as jnp
    import numpy as np

    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteSequential
    from hivemind_tpu.moe.server.layers import name_to_block
    from hivemind_tpu.moe.server.llama_loader import (
        LlamaCheckpointConfig,
        ShardedSafetensorsReader,
        _block_params_from_hf,
        predict_block_param_bytes,
    )
    from hivemind_tpu.ops.quantized_params import quantize_params

    checkpoint = WORK / "checkpoint"
    config, reader = LlamaCheckpointConfig.load(checkpoint), ShardedSafetensorsReader(checkpoint)
    total = sz["prefill"] + sz["decode"]
    say(f"  2 checkpoint blocks (hidden {config.hidden_size}, {config.num_attention_heads} heads, inner "
        f"{config.intermediate_size}), prefill {sz['prefill']} + {sz['decode']} decode steps, reference on the CPU")
    stream = wire_exact(np.random.RandomState(11).randn(1, total, config.hidden_size))
    dht = DHT(initial_peers=[args.maddr], start=True)
    try:
        pipe = RemoteSequential(dht, "ckpt.", 2)
        decode_sessions(pipe, [stream], ["warm"], sz["prefill"])
        before = scraped_counters(args.metrics)
        [outputs] = decode_sessions(pipe, [stream], ["checked"], sz["prefill"])
        after = scraped_counters(args.metrics)
        gauges = scrape(args.metrics + "/metrics.json")
    finally:
        dht.shutdown()

    module = name_to_block["llama_block"](
        config.hidden_size, num_heads=config.num_attention_heads, num_kv_heads=config.num_key_value_heads,
        rope_theta=config.rope_theta, ffn_inner=config.intermediate_size, rms_eps=config.rms_norm_eps,
    )
    reference = jnp.asarray(stream)
    for layer in range(2):
        params = _block_params_from_hf(reader, layer)
        if args.int8:  # what an int8 server holds: the codec's jnp form here, decoded with numpy
            params = dense_reference_params(quantize_params(params))
        reference = module.apply({"params": params}, reference)
    reference = np.asarray(reference)
    check_decode_chain("checked session", outputs, reference, "the full-sequence forward on the CPU")
    check_serving_counters(before, after)

    live = metric("hivemind_device_memory_bytes", gauges)
    peak = metric("hivemind_device_memory_peak_bytes", gauges)
    say(f"    server memory per device, live: {live}")
    say(f"    server memory per device, peak: {peak}")
    share = 2 * predict_block_param_bytes(config, "int8" if args.int8 else None) / max(args.mesh_devices, 1)
    check(0.95 * share < max(live.values()) < 1.15 * share,
          f"each device holds its share of the 2 served blocks, {share / 1e6:.0f} MB, and nothing of the block the "
          f"HBM plan probed with: {max(live.values()) / 1e6:.0f} MB live")
    if args.mesh_devices:
        check(len(live) == args.mesh_devices and min(live.values()) > 0,
              f"the served blocks' shards live on {len(live)} distinct devices")
        check(max(live.values()) < 0.5 * sum(live.values()),
              f"no device holds more than {max(live.values()) / sum(live.values()):.2f} of the live bytes")
        check(len(peak) == args.mesh_devices and min(peak.values()) > 0, "peak memory is non-zero on every device")
    if args.save:
        np.save(args.save, outputs)
    if args.compare:
        one_chip = np.load(args.compare)
        err = rel_err(outputs, one_chip)
        check(err < SERVING_TOLERANCE, f"mesh-served outputs against the one-chip server's: {err:.2e} "
                                       f"(tolerance {SERVING_TOLERANCE})")


# ============================================================================= main


def run_child(args) -> int:
    sz = sizes(args.rehearse_cpu)
    try:
        report = start_child(args)
        if args.child == "client":
            child_client(args, sz)
        else:
            from hivemind_tpu.telemetry.device import arm_device_telemetry

            arm_device_telemetry()  # as run_server does: the watchdog samples device memory throughout
            phases = Phases()
            say(f"sizes: {sz['label']}")
            if args.child == "mesh":
                phases.run("M", "ALBERT on four devices: flash ring (tp x sp), SliceOptimizer swarm round (dp x tp)",
                           phase_mesh, sz)
            else:
                wanted = args.phases.upper().split(",")
                for key, title, fn in (
                    ("K", "every Pallas kernel against its float32 reference", phase_kernels),
                    ("T", "the trainer takes steps (Optimizer, two peers, butterfly all-reduce)", phase_trainer),
                    ("S", "the server answers requests (Server.create, RemoteExpert, RemoteSequential)", phase_server),
                ):
                    if key in wanted:
                        phases.run(key, title, fn, sz)
            report["phases"] = phases.summary
        Path(args.report).write_text(json.dumps(report))
        return 0
    except SmokeFailure as failure:
        say(f"FAIL: {failure}")
        return 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="toy sizes on the CPU, Pallas in interpret mode: proves nothing about the "
                             "chip, prints no result and exits with code 3 when every phase passed")
    parser.add_argument("--phases", default="K,T,S,C,M", help="subset of phases to run (debugging)")
    parser.add_argument("--child", choices=["device", "mesh", "client"], help=argparse.SUPPRESS)
    parser.add_argument("--report", help=argparse.SUPPRESS)
    parser.add_argument("--maddr", help=argparse.SUPPRESS)
    parser.add_argument("--metrics", help=argparse.SUPPRESS)
    parser.add_argument("--save", help=argparse.SUPPRESS)
    parser.add_argument("--compare", help=argparse.SUPPRESS)
    parser.add_argument("--mesh_devices", type=int, default=0, help=argparse.SUPPRESS)
    parser.add_argument("--int8", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args()
    return run_child(args) if args.child else run_parent(args)


if __name__ == "__main__":
    sys.exit(main())
