"""Multi-host slice semantics (VERDICT r2 next-round #4): TWO REAL
`jax.distributed`-initialized CPU processes form ONE mesh, and the slice joins a
swarm as ONE peer — only process 0 owns any networking; process 1 participates in
collective staging/adoption and provably never constructs a DHT.

The worker script below is executed in two subprocesses (4 virtual devices each →
one 8-device dp mesh). Process 0 also hosts a plain host-resident peer so the
swarm has two members; after the round BOTH processes must hold the exact
cross-peer average in their device shards.
"""

from swarm_utils import run_jax_workers

_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1])
port = sys.argv[2]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=proc_id
)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hivemind_tpu.averaging import DecentralizedAverager, SliceAverager
from hivemind_tpu.dht import DHT

devices = np.array(jax.devices()).reshape(8)
mesh = Mesh(devices, ("dp",))

rng = np.random.RandomState(7)
w_host = rng.randn(8, 16).astype(np.float32)
b_host = rng.randn(32).astype(np.float32)
tree = {
    "w": jax.device_put(w_host, NamedSharding(mesh, P("dp"))),
    "b": jax.device_put(b_host, NamedSharding(mesh, P())),
}
peer_w = rng.randn(8, 16).astype(np.float32)  # same RNG on both procs: same values
peer_b = rng.randn(32).astype(np.float32)

common = dict(
    prefix="slice_round", start=True, target_group_size=2,
    min_matchmaking_time=1.0, request_timeout=1.0,
    sender_timeout=5.0, reducer_timeout=10.0,
)

plain_dht = plain_peer = None
if proc_id == 0:
    boot = DHT(start=True)
    maddrs = [str(m) for m in boot.get_visible_maddrs()]
    plain_dht = DHT(initial_peers=maddrs, start=True)
    # flatten order is sorted dict keys: b, w
    plain_peer = DecentralizedAverager([peer_b, peer_w], plain_dht, **common)
    dht_factory = lambda: boot
else:
    dht_factory = lambda: (_ for _ in ()).throw(
        AssertionError("dht_factory called on a non-network process")
    )

slice_avg = SliceAverager(tree, mesh, dht_factory, **(common if proc_id == 0 else {}))

# the structural claim: non-zero processes own NO networking objects at all
if proc_id != 0:
    assert slice_avg.dht is None and slice_avg.averager is None
    assert not slice_avg.is_network_process

if proc_id == 0:
    control = plain_peer.step(wait=False, timeout=40)
    ok = slice_avg.step(timeout=40)
    assert control.result(timeout=60) is not None
else:
    ok = slice_avg.step(timeout=40)

assert ok, f"[{proc_id}] slice round failed"
expected_w = (w_host + peer_w) / 2.0
expected_b = (b_host + peer_b) / 2.0
averaged = slice_avg.device_tree


def check_shards(arr, expected):
    # a multi-process global array cannot be materialized whole; every process
    # verifies the shards IT holds — together the two processes cover the array
    assert arr.addressable_shards, "process holds no shards"
    for shard in arr.addressable_shards:
        np.testing.assert_allclose(
            np.asarray(shard.data), expected[shard.index], rtol=1e-6, atol=1e-7
        )


check_shards(averaged["w"], expected_w)
check_shards(averaged["b"], expected_b)
assert averaged["w"].sharding.spec == P("dp")
if proc_id == 0:
    with plain_peer.get_tensors() as tensors:
        np.testing.assert_allclose(tensors[0], expected_b, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(tensors[1], expected_w, rtol=1e-6, atol=1e-7)
    plain_peer.shutdown(); plain_dht.shutdown()

# ---- failure path: the other swarm peer is gone, so the network round cannot
# form a group; EVERY process must observe ok=False and device state unchanged
ok_fail = slice_avg.step(timeout=6)
assert not ok_fail, f"[{proc_id}] round unexpectedly succeeded with no peers"
check_shards(slice_avg.device_tree["w"], expected_w)
check_shards(slice_avg.device_tree["b"], expected_b)

slice_avg.shutdown()
print(f"SLICE_OK_{proc_id}", flush=True)
"""


def test_two_process_slice_is_one_swarm_peer(tmp_path):
    for i, (code, out) in enumerate(run_jax_workers(_WORKER, tmp_path)):
        assert code == 0, f"worker {i} exited {code}:\n{out[-3000:]}"
        assert f"SLICE_OK_{i}" in out, out[-3000:]
