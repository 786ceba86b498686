"""The harness bounds what it runs (ISSUE 48): a test that hangs fails by name with
every thread's stack instead of costing the run its clock, and a child process is
started one bounded way. Driven with a 1 s bound passed to the function under test."""

import asyncio
import os
import re
import shutil
import signal
import subprocess
import sys
import time

import pytest
import conftest
from conftest import TEST_LIMIT_S, _run_async_test, time_limit
from swarm_utils import REPO_ROOT, cpu_child_env, read_child_until, run_jax_workers, start_relay_daemon, stop_process


# the line a test at its bound writes past the capture starts so (an xdist worker says which it is)
WORKER = os.environ.get("PYTEST_XDIST_WORKER", "")
NAMED = f"TEST_LIMIT {WORKER and f'[{WORKER}] '}"


def _sleeps_past_it():
    time.sleep(30)


def _awaits_forever():
    async def forever():
        await asyncio.Event().wait()

    _run_async_test(forever, {}, allow_task_leaks=False)


def _blocks_in_set_up():
    # what the relay fixtures did: an unbounded readline() on a child that says nothing
    silent = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(30)"], stdout=subprocess.PIPE)
    try:
        silent.stdout.readline()
    finally:
        stop_process(silent)


@pytest.mark.parametrize("hang", [_sleeps_past_it, _awaits_forever, _blocks_in_set_up])
def test_a_hang_is_failed_inside_the_bound_with_every_threads_stack(hang, capfd, monkeypatch):
    past_capture, terminal = os.pipe()  # stands in for the run's real stderr
    monkeypatch.setattr(conftest, "_real_stderr", terminal)
    started = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="the hanging one was still running after the 1 s"):
        with time_limit(1.0, "the hanging one"):
            hang()
    assert time.monotonic() - started < 1.0 + 5.0
    stacks = capfd.readouterr().err
    assert "Current thread" in stacks and hang.__name__ in stacks, stacks[-2000:]
    os.close(terminal)
    with open(past_capture) as said:
        assert said.read() == f"\n{NAMED}the hanging one was still running after 1 s\n"

    # the next one in the same process runs clean: its own bound is armed and taken off
    # again, no alarm of the failed one is left to fire, and this test's bound is back
    with time_limit(1.0, "the next one"):
        time.sleep(0.1)
    time.sleep(1.2)
    remaining, _ = signal.getitimer(signal.ITIMER_REAL)
    assert TEST_LIMIT_S - 30 < remaining <= TEST_LIMIT_S


@pytest.fixture
def bound_in_set_up_and_tear_down():
    armed = [signal.getitimer(signal.ITIMER_REAL)[0]]
    yield armed
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= TEST_LIMIT_S  # tear-down


def test_every_phase_of_a_test_runs_under_the_bound(bound_in_set_up_and_tear_down):
    armed = bound_in_set_up_and_tear_down + [signal.getitimer(signal.ITIMER_REAL)[0]]
    assert all(0 < remaining <= TEST_LIMIT_S for remaining in armed), armed


_ONE_DIES_ONE_BLOCKS = r"""
import socket, sys
if sys.argv[1] == "1":
    sys.exit(3)
listener = socket.socket()
listener.bind(("127.0.0.1", 0))
listener.listen()
print("BLOCKED", flush=True)
listener.accept()  # forever: nobody dials
"""


def test_a_worker_that_dies_takes_its_blocked_partner_with_it(tmp_path):
    started = time.monotonic()
    (code0, out0), (code1, _out1) = run_jax_workers(_ONE_DIES_ONE_BLOCKS, tmp_path, timeout=120)
    assert time.monotonic() - started < 10
    assert code1 == 3
    assert code0 == -signal.SIGKILL, out0
    assert "[killed: child 1 exited 3]" in out0


_BUILD_AND_DIAL = r"""
import socket, sys
from pathlib import Path
sys.path.insert(0, sys.argv[4])
from hivemind_tpu.p2p import native_transport
native_transport.NATIVE_DIR = Path(sys.argv[3])
from swarm_utils import start_relay_daemon, stop_process

daemon = start_relay_daemon()
try:
    socket.create_connection(("127.0.0.1", daemon.port), timeout=5).close()
    print(f"ANSWERS_{sys.argv[1]} identity={daemon.pubkey_hex}", flush=True)
finally:
    stop_process(daemon.process)
"""


def test_two_builds_at_once_both_end_with_a_daemon_that_answers(tmp_path):
    """What three xdist workers do on a tree that starts without the binary."""
    native = tmp_path / "native"
    native.mkdir()
    for name in ("Makefile", "relay_daemon.cpp"):
        shutil.copy(REPO_ROOT / "hivemind_tpu" / "native" / name, native / name)
    results = run_jax_workers(
        _BUILD_AND_DIAL, tmp_path, [str(native), str(REPO_ROOT / "tests")], timeout=180
    )
    for i, (code, out) in enumerate(results):
        assert code == 0 and f"ANSWERS_{i}" in out, f"builder {i} exited {code}:\n{out[-3000:]}"
    assert (native / "relay_daemon").exists()


def test_a_daemon_that_prints_no_banner_is_an_error_not_a_hang(tmp_path, monkeypatch):
    mute = tmp_path / "relay_daemon"
    mute.write_text("#!/bin/sh\nexec sleep 30\n")
    mute.chmod(0o755)
    # a directory without the source: nothing to build, the binary is taken as it is
    monkeypatch.setattr("hivemind_tpu.p2p.native_transport.NATIVE_DIR", tmp_path)
    with pytest.raises(AssertionError, match="printed no banner within 1 s"):
        start_relay_daemon(banner_timeout=1)


# ------------------------------------------------- one budget a test, a hang named at once

_CHILD_TESTS = r"""
import time

import conftest
import pytest

conftest.TEST_LIMIT_S = 3.0  # read when a test's set-up starts: these tests' one budget
conftest.SPENT_BUDGET_GRACE_S = 1.0


@pytest.fixture
def hangs_in_tear_down():
    yield
    time.sleep(60)


def test_hangs_in_call_and_tear_down(hangs_in_tear_down):
    time.sleep(60)


@pytest.fixture
def tear_down_reads_what_is_left():
    yield
    print("LEFT_IN_TEAR_DOWN=%.2f" % conftest.signal.getitimer(conftest.signal.ITIMER_REAL)[0])
    assert False, "shown with what it printed"


def test_call_uses_most_of_the_budget(tear_down_reads_what_is_left):
    time.sleep(1.5)


def test_ends_in_time():
    print("a test that ends in time says this to the captured stdout only")
"""


@pytest.fixture(scope="module")
def child_run(tmp_path_factory):
    """One child pytest run under this harness with capture on, a budget of 3 s a test and
    1 s for a phase that starts with it spent: (its stderr up to the hung tear-down's line,
    whether it still ran then, the seconds until then, all of its stderr, its stdout)."""
    directory = tmp_path_factory.mktemp("child_run")
    (directory / "test_child.py").write_text(_CHILD_TESTS)
    env = cpu_child_env()
    env["PYTHONPATH"] = os.pathsep.join([str(REPO_ROOT / "tests"), env["PYTHONPATH"]])
    command = [sys.executable, "-m", "pytest", "test_child.py", "-p", "conftest", "-p", "no:cacheprovider"]
    command += ["-p", "no:xdist", "-p", "no:randomly", "-q", "--rootdir", str(directory)]
    started = time.monotonic()
    child = subprocess.Popen(command, cwd=directory, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        named_at_once = read_child_until(child, r"\(tear-down\) was still running.*\n", 90, stream="stderr")
        still_running = child.poll() is None
        hang_failed_after = time.monotonic() - started
        out, rest = child.communicate(timeout=90)
    finally:
        stop_process(child)
    return named_at_once, still_running, hang_failed_after, named_at_once + rest.decode(), out.decode()


def test_a_test_hung_in_call_and_tear_down_costs_one_budget_and_is_named_before_the_run_ends(child_run):
    named_at_once, still_running, hang_failed_after, _stderr, out = child_run
    node = "test_child.py::test_hangs_in_call_and_tear_down"
    assert f"{NAMED}{node} (call) was still running after 3 s\n" in named_at_once
    assert f"{NAMED}{node} (tear-down) was still running after 1 s\n" in named_at_once
    assert still_running, "the lines were read from a run that had two tests to go"
    # one budget and the tear-down's second, plus the child's start (jax, 8 devices)
    assert hang_failed_after < 3.0 + 1.0 + 30.0
    assert f"FAILED {node}" in out and f"ERROR {node}" in out, out[-3000:]
    assert re.search(r"1 failed, 2 passed,( \d+ warnings?,)? 2 errors", out), out[-3000:]  # a warning: a raise that a callback swallowed


def test_a_tear_down_has_what_the_call_left_of_the_budget(child_run):
    out = child_run[-1]
    (left,) = re.findall(r"LEFT_IN_TEAR_DOWN=([0-9.]+)", out)
    assert 1.0 < float(left) <= 3.0 - 1.5, out[-3000:]  # more than a spent budget's second


def test_only_a_test_at_its_bound_reaches_the_real_stderr_and_its_stacks_stay_captured(child_run):
    _, _, _, stderr, out = child_run
    lines = [line for line in stderr.splitlines() if line]
    assert lines and all(line.startswith(f"{NAMED}test_child.py::test_hangs_in_call") for line in lines), stderr
    assert "Current thread" not in stderr and "Current thread" in out  # with the test, in the summary
    assert "a test that ends in time" not in stderr
