"""End-to-end multi-host slice TRAINING (examples/slice_training.py): two real
jax.distributed processes form one mesh, take local optax steps, and average
with a plain host-resident swarm peer through SliceAverager rounds. Completes
the two-tier story: the slice both TRAINS over ICI and AVERAGES over the swarm."""

import os
import re
import socket
import subprocess
import sys

from swarm_utils import cpu_child_env, read_child_until, stop_process, wait_for_children

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLE = os.path.join(_REPO, "examples", "slice_training.py")

_COMPANION = r"""
import sys, time
import numpy as np
maddr = sys.argv[1]
import jax
jax.config.update("jax_platforms", "cpu")
from hivemind_tpu.averaging import DecentralizedAverager
from hivemind_tpu.dht import DHT

dht = DHT(initial_peers=[maddr], start=True)
dim = 16
avg = DecentralizedAverager(
    [np.zeros(dim, np.float32), np.zeros((dim, dim), np.float32)],  # b, w (sorted keys)
    dht, prefix="slice_train_test_params", start=True,
    target_group_size=2, min_matchmaking_time=1.0,
)
joined = 0
deadline = time.monotonic() + 90  # must stay under the parent's deadline
while joined < 2 and time.monotonic() < deadline:
    try:
        if avg.step(timeout=45) is not None:
            joined += 1
            print(f"COMPANION_ROUND_{joined}", flush=True)
    except Exception as e:
        print(f"companion round failed: {e!r}", flush=True)
assert joined >= 1, "companion never joined a slice round"
avg.shutdown(); dht.shutdown()
print("COMPANION_DONE", flush=True)
"""


def test_two_process_slice_trains_and_averages_with_swarm(tmp_path):
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{probe.getsockname()[1]}"
    env = cpu_child_env()
    common = [
        sys.executable, _EXAMPLE, "--platform", "cpu", "--devices_per_proc", "2",
        "--num_processes", "2", "--coordinator", coord,
        "--run_id", "slice_train_test", "--dim", "16", "--batch_size", "8",
        "--steps", "40", "--steps_per_round", "20",
    ]
    procs = [
        subprocess.Popen(
            common + ["--process_id", str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )
        for i in range(2)
    ]
    companion = None
    try:
        # process 0 prints its DHT address once its dht_factory runs
        head = read_child_until(procs[0], r"--initial_peers \S+\s", timeout=180)
        assert "--initial_peers" in head, head[-3000:]
        maddr = re.search(r"--initial_peers (\S+)", head).group(1)

        script = tmp_path / "companion.py"
        script.write_text(_COMPANION)
        companion = subprocess.Popen(
            [sys.executable, str(script), maddr],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )

        children = procs + [companion]
        wait_for_children(children, timeout=240)  # > the companion's own 90 s deadline
        for child in children:
            stop_process(child)
        out0, out1, comp_out = (child.communicate(timeout=10)[0] for child in children)
        outs = [head + out0, out1]

        for i, out in enumerate(outs):
            assert procs[i].returncode == 0, f"slice proc {i} failed:\n{out[-3000:]}"
        assert companion.returncode == 0, f"companion failed:\n{comp_out[-3000:]}"

        # at least one swarm round succeeded on the slice side...
        assert "swarm_round_ok=True" in outs[0], outs[0][-2000:]
        # ...the companion reduced with it...
        assert "COMPANION_ROUND_1" in comp_out, comp_out[-2000:]
        # ...and training converged (toy identity regression: loss well below init)
        finals = [
            float(re.search(r"FINAL_LOSS \d ([\d.eE+-]+)", out).group(1)) for out in outs
        ]
        assert all(f < 0.5 for f in finals), finals
        assert abs(finals[0] - finals[1]) < 1e-4, finals  # SPMD: same global loss
    finally:
        for proc in procs + ([companion] if companion else []):
            stop_process(proc)
