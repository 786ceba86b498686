"""The layer map's arrows point one way: `hivemind_tpu/ops/` holds device ops and the
choice among them on one device, and imports nothing from the layers built on it."""

import ast
from pathlib import Path

import pytest

OPS = Path(__file__).resolve().parents[1] / "hivemind_tpu" / "ops"
ABOVE = ("hivemind_tpu.parallel", "hivemind_tpu.moe", "hivemind_tpu.optim", "hivemind_tpu.averaging")


def _imported(path: Path):
    """Every module an `import` or a `from ... import` names, at module level or
    inside a function."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            # a relative import is relative to `hivemind_tpu.ops`
            package = ["hivemind_tpu", "ops"][: 3 - node.level] if node.level else []
            module = ".".join(package + ([node.module] if node.module else []))
            yield module
            yield from (f"{module}.{alias.name}" for alias in node.names)


@pytest.mark.parametrize("module", sorted(path.name for path in OPS.glob("*.py")))
def test_ops_import_nothing_from_the_layers_above(module):
    upward = sorted({name for name in _imported(OPS / module) if name.startswith(ABOVE)})
    assert not upward, f"hivemind_tpu/ops/{module} imports {upward}"


def test_parallel_still_exports_the_plain_core():
    from hivemind_tpu.ops.attention import plain_attention as held
    from hivemind_tpu.parallel import plain_attention

    assert plain_attention is held
