"""hivemind_tpu: a TPU-native framework for decentralized deep learning.

Capabilities mirror learning-at-home/hivemind (see SURVEY.md): a Kademlia-style DHT
for masterless peer discovery, fault-tolerant butterfly all-reduce with gradient
compression, a collaborative optimizer equivalent to large-batch synchronous training
over an elastic swarm, and a decentralized Mixture-of-Experts serving stack — designed
TPU-first on jax/XLA/pjit: device math is jax, a TPU slice acts as one logical swarm
peer (intra-slice reductions ride the ICI mesh via jax collectives), and networking is
a single-process asyncio runtime instead of the reference's fork-per-service topology
(reference: hivemind/__init__.py:1-14).
"""

from hivemind_tpu.utils.loop import EventLoopShutdownError, LoopRunner, get_loop_runner
from hivemind_tpu.utils.serializer import MSGPackSerializer
from hivemind_tpu.utils.timed_storage import (
    DHTExpiration,
    TimedStorage,
    ValueWithExpiration,
    get_dht_time,
)

__version__ = "0.1.0"


def __getattr__(name):  # lazy top-level API so `import hivemind_tpu` stays light
    import importlib

    top_level = {
        "DHT": "hivemind_tpu.dht",
        "DHTNode": "hivemind_tpu.dht.node",
        "P2P": "hivemind_tpu.p2p",
        "PeerID": "hivemind_tpu.p2p",
        "DecentralizedAverager": "hivemind_tpu.averaging",
        "MeshAverager": "hivemind_tpu.averaging",
        "NATTraversal": "hivemind_tpu.p2p",
        "Optimizer": "hivemind_tpu.optim",
        "GradientAverager": "hivemind_tpu.optim",
        "TrainingStateAverager": "hivemind_tpu.optim",
        "PowerSGDGradientAverager": "hivemind_tpu.optim",
        "GradScaler": "hivemind_tpu.optim",
        "ProgressTracker": "hivemind_tpu.optim",
        "Server": "hivemind_tpu.moe",
        "ModuleBackend": "hivemind_tpu.moe",
        "RemoteExpert": "hivemind_tpu.moe",
        "RemoteMixtureOfExperts": "hivemind_tpu.moe",
        "RemoteSequential": "hivemind_tpu.moe",
        "RemoteSwitchMixtureOfExperts": "hivemind_tpu.moe",
        "register_expert_class": "hivemind_tpu.moe",
        "RetryPolicy": "hivemind_tpu.resilience",
        "Deadline": "hivemind_tpu.resilience",
        "BreakerBoard": "hivemind_tpu.resilience",
        "CHAOS": "hivemind_tpu.resilience",
        "SimNetwork": "hivemind_tpu.sim",
        "SimPeer": "hivemind_tpu.sim",
        "LinkMatrix": "hivemind_tpu.sim",
        "run_scenario": "hivemind_tpu.sim",
    }
    if name in top_level:
        module = importlib.import_module(top_level[name])
        return getattr(module, name)
    raise AttributeError(f"module 'hivemind_tpu' has no attribute {name!r}")
