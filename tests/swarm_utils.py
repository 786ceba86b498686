"""Shared swarm builders for the test suite (layout parity: reference
tests/test_utils/dht_swarms.py). All tests launch REAL localhost swarms — there is
no fake network backend, so test and production code paths are identical."""

import codecs
import contextlib
import os
import re
import selectors
import socket
import subprocess
import sys
import time
from pathlib import Path
from typing import NamedTuple

import jax

from hivemind_tpu.dht import DHT
from hivemind_tpu.moe.server.decode_session import DecodeSessionManager
from hivemind_tpu.moe.server.module_backend import ModuleBackend
from hivemind_tpu.p2p.native_transport import build_daemon_binary, read_daemon_banner


def launch_dht_swarm(n: int):
    """n DHT peers on real localhost sockets; the first is everyone's bootstrap."""
    first = DHT(start=True)
    maddrs = [str(m) for m in first.get_visible_maddrs()]
    return [first] + [DHT(initial_peers=maddrs, start=True) for _ in range(n - 1)]


def shutdown_all(components, dhts):
    """Tear down averagers/optimizers first, then their DHTs."""
    for component in components:
        component.shutdown()
    for dht in dhts:
        dht.shutdown()


class OneProgramBackend(ModuleBackend):
    """A `ModuleBackend` whose state is drawn by ONE program (through `_init_state`, the
    hook `MeshModuleBackend` has for the same). Eager, a block's init compiles a program an
    operation and shape, 80 to 190 a block: a quarter of the block files' seconds (ISSUE 53).
    The same keys draw the same values."""

    def _init_state(self, samples, rng_seed: int):
        def make():
            params = self.module.init(jax.random.PRNGKey(rng_seed), *samples)["params"]
            return params, (self.optimizer.init(params) if self.weight_quantization is None else None)

        return jax.jit(make)()


class ManagerSharingPrograms(DecodeSessionManager):
    """A fresh manager (sessions, pools, padding, counters and per-uid views of its own) that takes a
    jitted program from the managers before it. The library shares a program among the blocks of one
    KIND within a manager (`DecodeSessionManager._of_kind`: ``max_len`` reaches a program as the shape of
    an argument); all this adds is that the managers of a process keep ONE such table, so the file's
    tests compile each program once a process and not once a test. A test that counts compilations
    builds a `DecodeSessionManager`."""

    _shared: dict = {}

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._programs = self._shared


def decode_compiles() -> int:
    """Compilations of the decode path's jit sites so far in this process (`COMPILE_TRACKER`)."""
    from hivemind_tpu.telemetry.device import COMPILE_TRACKER

    counts = COMPILE_TRACKER.counts()
    return sum(counts.get("decode_session." + site, 0) for site in ("batched_step", "step", "upload"))


def wait_until(condition, timeout: float):
    """Poll ``condition`` until it holds or ``timeout`` passes; what it returned last."""
    deadline = time.monotonic() + timeout
    while not (held := condition()) and time.monotonic() < deadline:
        time.sleep(0.05)
    return held


def wait_for_experts(dht, uids, served_by=None, timeout: float = 10.0):
    """Until ``dht`` resolves every one of ``uids``, with the peer ``served_by`` among its servers
    if given (a replacement's record has then joined the dead server's): a server declares
    its experts in the background, after `Server.create(start=True)` has returned."""
    from hivemind_tpu.moe.server.dht_handler import get_experts

    def declared():
        infos = get_experts(dht, list(uids))
        servers = lambda info: [replica.peer_id for replica in info.replica_set]
        return all(info is not None and (served_by is None or served_by in servers(info)) for info in infos)

    assert wait_until(declared, timeout), f"{list(uids)} were not declared within {timeout:g} s"


# -------------------------------------------- child processes, started one bounded way

REPO_ROOT = Path(__file__).resolve().parent.parent


def cpu_child_env() -> dict:
    """Every process of a several-on-one-host run is pinned to the CPU and finds the repo."""
    paths = [str(REPO_ROOT)] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(paths))


def stop_process(process: subprocess.Popen) -> None:
    """Kill a child, if it still runs, and reap it; 10 s without its exit is an error."""
    process.kill()
    process.wait(10)


def read_child_until(proc, marker: str, timeout: float = 60.0, stream: str = "stdout") -> str:
    """Accumulate a child's stdout (or stderr) until the regex ``marker`` matches, EOF,
    or the deadline.

    Reads the RAW non-blocking fd in chunks: selecting on the fd and then calling
    ``readline()`` silently strands any second line inside the TextIO buffer (the
    fd shows no data, the selector never fires again) — a hang this helper exists
    to avoid."""
    fd = getattr(proc, stream).fileno()
    os.set_blocking(fd, False)
    decoder = codecs.getincrementaldecoder("utf-8")("replace")
    deadline = time.monotonic() + timeout
    seen = ""
    with selectors.DefaultSelector() as sel:
        sel.register(fd, selectors.EVENT_READ)
        while time.monotonic() < deadline and not re.search(marker, seen):
            if not sel.select(timeout=1.0):
                if proc.poll() is not None:
                    break
                continue
            chunk = os.read(fd, 65536)
            if not chunk:
                break  # EOF
            seen += decoder.decode(chunk)
    return seen


def wait_for_children(processes, timeout: float) -> str:
    """Wait against ONE deadline until every child has exited, or the first has exited
    non-zero (its partners then wait for nobody). Returns what cut the wait short, or ""."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        codes = [process.poll() for process in processes]
        failed = [(i, code) for i, code in enumerate(codes) if code]
        if failed:
            return "child %d exited %d" % failed[0]
        if None not in codes:
            return ""
        time.sleep(0.1)
    return f"the deadline of {timeout:g} s passed"


class RelayDaemon(NamedTuple):
    process: subprocess.Popen
    port: int
    pubkey_hex: str  # "" from a daemon built without libcrypto


def start_relay_daemon(*args: str, banner_timeout: float = 30) -> RelayDaemon:
    """A relay daemon on a free port, built first if it has to be: the library's own
    build (serialized by its flock, so workers that all start without the binary are
    safe) and its bounded banner read. A failed build or a missing banner is an error
    with its text. ``args`` follow the port: identity file, unix socket path."""
    binary, error = build_daemon_binary()
    assert binary is not None, f"relay daemon: {error}"
    process = subprocess.Popen([str(binary), "0", *args], stdout=subprocess.PIPE)
    banner = read_daemon_banner(process, banner_timeout)
    if banner is None:
        stop_process(process)
        raise AssertionError(f"relay daemon {binary} printed no banner within {banner_timeout:g} s")
    listening, identity = banner
    pubkey_hex = identity.rsplit(" ", 1)[-1] if identity.startswith("relay identity ") else ""
    return RelayDaemon(process, int(listening.rsplit(" ", 1)[-1]), pubkey_hex)


def run_jax_workers(script_text: str, tmp_path: Path, args=(), n: int = 2, timeout: float = 120):
    """Run ``script_text`` as ``n`` children, each given its index, the ``jax.distributed``
    coordinator's port and ``args``, against ONE deadline. The first child to exit
    non-zero, or the deadline, kills the rest: a partner blocked in a collective never
    outlives the worker it waits for. Returns ``[(returncode, output)]``.

    The probe of the port stays bound (never listening) until the children are gone: the
    coordinator's bind sets the same two options and is accepted beside it, while no
    other process of the machine is handed that port."""
    script = tmp_path / "worker.py"
    script.write_text(script_text)
    with contextlib.ExitStack() as stack:
        probe = stack.enter_context(socket.socket())
        probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        probe.bind(("127.0.0.1", 0))
        port = str(probe.getsockname()[1])
        # output goes to files, not pipes: nothing has to be read while the children run
        logs = [stack.enter_context(open(tmp_path / f"worker{i}.log", "w+")) for i in range(n)]
        workers = []
        stack.callback(lambda: [stop_process(worker) for worker in workers])
        for log in logs:
            command = [sys.executable, str(script), str(len(workers)), port, *args]
            workers.append(subprocess.Popen(command, stdout=log, stderr=subprocess.STDOUT, env=cpu_child_env()))
        verdict = wait_for_children(workers, timeout)
        results = []
        for worker, log in zip(workers, logs):
            if worker.poll() is None:
                stop_process(worker)
                log.write(f"\n[killed: {verdict}]")  # after the child's last byte: one offset
            log.seek(0)
            results.append((worker.returncode, log.read()))
        return results
