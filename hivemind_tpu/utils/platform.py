"""Device plumbing shared by every entry point that compiles: the ``--platform``
argument, the persistent compilation cache, and the one-line device description
that every result carries. Call :func:`apply_platform` (which also places the
cache) before the first device use."""

from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Any, Dict

# <checkout>/.jax_cache: derived from the package's location only. The directory
# is part of the cache key, so it must be the same in every process and every run
# of one checkout — never a temporary directory, a pid or a timestamp.
DEFAULT_COMPILATION_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compilation_cache() -> str:
    """Place jax's persistent compilation cache and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
    here (or anywhere else in code) sets another directory. Otherwise the cache
    goes to :data:`DEFAULT_COMPILATION_CACHE_DIR`."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILATION_CACHE_DIR))
    return str(DEFAULT_COMPILATION_CACHE_DIR)


def add_platform_arg(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--platform",
        default=None,
        help="run on this jax platform (e.g. cpu) instead of the default backend",
    )


def apply_platform(args: argparse.Namespace) -> None:
    """Honor ``--platform`` and place the compilation cache; must run before the
    first device use."""
    if getattr(args, "platform", None):
        import jax

        jax.config.update("jax_platforms", args.platform)
    configure_compilation_cache()


def describe_devices() -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` as jax reports them — every benchmark and
    smoke result names the device it ran on with exactly this."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
