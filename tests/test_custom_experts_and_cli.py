"""Custom expert registration end-to-end + CLI smoke tests
(scope: reference tests/test_custom_experts.py, test_cli_scripts.py, test_start_server.py)."""

import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from swarm_utils import read_child_until, stop_process, wait_for_experts


def test_register_custom_expert_end_to_end():
    from hivemind_tpu.dht import DHT
    from hivemind_tpu.moe import RemoteExpert, Server, get_experts, register_expert_class

    class GatedExpert(nn.Module):
        hidden_dim: int

        @nn.compact
        def __call__(self, x):
            gate = nn.sigmoid(nn.Dense(self.hidden_dim)(x))
            return x * gate

    register_expert_class("gated_test", lambda batch, hid: np.zeros((batch, hid), np.float32))(GatedExpert)

    server = Server.create(
        expert_uids=["gated_test_grid.0"], expert_cls="gated_test", hidden_dim=16,
        start=True, optim_factory=lambda: optax.sgd(1e-3),
    )
    try:
        wait_for_experts(server.dht, ["gated_test_grid.0"])
        info = get_experts(server.dht, ["gated_test_grid.0"])[0]
        assert info is not None
        client_dht = DHT(initial_peers=[str(m) for m in server.dht.get_visible_maddrs()], start=True)
        expert = RemoteExpert(info, client_dht.node.p2p)
        x = jnp.asarray(np.random.RandomState(0).randn(3, 16), jnp.float32)
        out = expert(x)
        backend = server.backends["gated_test_grid.0"]
        expected = backend.module.apply({"params": backend.params}, x)
        # fp16 wire tolerance: the server's default activation compression is
        # negotiated via the DHT record this test resolved (exact-wire behavior
        # is covered by test_serving_compression.py)
        assert np.allclose(np.asarray(out), np.asarray(expected), atol=2e-2)
        client_dht.shutdown()
    finally:
        server.shutdown()
        server.dht.shutdown()


@pytest.mark.parametrize(
    "module,extra",
    [
        ("hivemind_tpu.hivemind_cli.run_dht", ["--refresh_period", "1"]),
        (
            "hivemind_tpu.hivemind_cli.run_server",
            ["--expert_uids", "cli_test.0", "--hidden_dim", "16", "--expert_cls", "ffn"],
        ),
    ],
    ids=["run_dht", "run_server"],
)
def test_cli_starts_and_listens(module, extra):
    """The real CLI entrypoints come up and announce a dialable address."""
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "."}
    proc = subprocess.Popen(
        [sys.executable, "-m", module, *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )
    try:
        buffer = read_child_until(proc, "listening", timeout=60)
        assert "listening" in buffer, (
            f"{module} never announced a listening address; output: {buffer[-500:]}"
        )
    finally:
        stop_process(proc)


def test_run_server_custom_module_path(tmp_path):
    """--custom_module_path imports a user file whose @register_expert_class
    decorators run before the server builds experts (reference custom_experts.py)."""
    custom = tmp_path / "my_experts.py"
    custom.write_text(
        "import flax.linen as nn\n"
        "import numpy as np\n"
        "from hivemind_tpu.moe import register_expert_class\n\n"
        "@register_expert_class('scaled_cli', lambda b, h: np.zeros((b, h), np.float32))\n"
        "class Scaled(nn.Module):\n"
        "    hidden_dim: int\n"
        "    @nn.compact\n"
        "    def __call__(self, x):\n"
        "        return x * self.param('s', nn.initializers.ones, ())\n"
    )
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "."}
    proc = subprocess.Popen(
        [sys.executable, "-m", "hivemind_tpu.hivemind_cli.run_server",
         "--expert_uids", "scaled_cli_grid.0", "--expert_cls", "scaled_cli",
         "--hidden_dim", "8", "--custom_module_path", str(custom), "--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )
    try:
        seen = read_child_until(proc, "serving 1 experts", timeout=60)
        assert "serving 1 experts" in seen, f"server did not start: {seen[-2000:]}"
        assert "loaded custom expert module" in seen
    finally:
        stop_process(proc)
