"""What the CPU can say about the chip check: where the compile cache goes, and that
`chip_smoke.py` refuses to pass without a TPU. The check itself runs on the chip."""

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from hivemind_tpu.utils import platform

_REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_updates(monkeypatch):
    """Every ``jax.config.update`` the helper makes, without changing jax's config."""
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: updates.append((name, value)))
    return updates


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch, cache_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert platform.configure_compilation_cache() == "/somewhere/outside"
    assert cache_updates == []  # jax reads the variable itself; code sets no other directory


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch, cache_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(_REPO / ".jax_cache")
    assert platform.configure_compilation_cache() == expected
    assert platform.configure_compilation_cache() == expected  # the same on every call
    assert cache_updates == [("jax_compilation_cache_dir", expected)] * 2
    # derived from the package's location: no temporary name, pid or timestamp in it
    assert not expected.startswith(("/tmp", "/var/tmp")) and "tmp" not in Path(expected).name
    assert str(os.getpid()) not in expected and not re.search(r"\d{6,}", expected)
    assert ".jax_cache/" in (_REPO / ".gitignore").read_text().splitlines()


def test_apply_platform_places_the_cache_for_every_entry_point(monkeypatch, cache_updates):
    import argparse

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    parser = argparse.ArgumentParser()
    platform.add_platform_arg(parser)
    platform.apply_platform(parser.parse_args(["--platform", "cpu"]))
    assert cache_updates == [
        ("jax_platforms", "cpu"), ("jax_compilation_cache_dir", str(_REPO / ".jax_cache")),
    ]


def test_no_other_cache_directory_is_set_in_code():
    setters = sorted(
        str(path.relative_to(_REPO))
        for path in _REPO.rglob("*.py")
        if not any(part.startswith(".") for part in path.relative_to(_REPO).parts)
        and "jax_compilation_cache_dir" in path.read_text()
    )
    assert setters == ["hivemind_tpu/utils/platform.py", "tests/test_chip_smoke.py"]


def test_chip_smoke_fails_without_a_tpu_and_names_what_it_found():
    run = subprocess.run(
        [sys.executable, str(_REPO / "chip_smoke.py")], cwd=_REPO, timeout=240,
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode != 0
    assert "platform=cpu" in run.stdout
    assert "jax.devices()[0].platform is 'cpu', not 'tpu'" in run.stdout
    assert '"ok"' not in run.stdout  # no result line


def test_describe_devices_is_what_jax_reports():
    device = jax.devices()[0]
    assert platform.describe_devices() == {
        "platform": device.platform, "kind": device.device_kind, "count": len(jax.devices()),
    }
