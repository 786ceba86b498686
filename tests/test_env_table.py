"""The environment variables the library reads against the one table that documents
them (`docs/quickstart.md`, "Environment variables"): a case a variable, and the
converse in one more."""

import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"HIVEMIND_[A-Z_0-9]+")


def _read_by_the_library() -> list:
    return sorted({name for path in (ROOT / "hivemind_tpu").rglob("*.py") for name in NAME.findall(path.read_text())})


def _rows() -> dict:
    """Variable -> the cells of its row (default, reader, purpose)."""
    section = (ROOT / "docs" / "quickstart.md").read_text().split("## Environment variables", 1)[1].split("\n## ", 1)[0]
    rows = {}
    for line in section.splitlines():
        cells = [cell.strip() for cell in line.strip().strip("|").split("|")]
        if line.startswith("| `HIVEMIND_") and len(cells) == 4:
            rows[cells[0].strip("`")] = cells[1:]
    return rows


@pytest.mark.parametrize("variable", _read_by_the_library())
def test_the_table_names_every_variable_the_library_reads(variable):
    row = _rows().get(variable)
    assert row is not None, f"docs/quickstart.md does not document {variable}"
    default, reader, purpose = row
    assert default and purpose
    module = re.search(r"`([\w/]+\.py)`", reader)
    assert module is not None, f"{variable}: the row names no module"
    assert variable in (ROOT / "hivemind_tpu" / module.group(1)).read_text(), f"{variable}: {module.group(1)} does not read it"


def test_the_table_names_no_variable_the_library_no_longer_reads():
    assert sorted(_rows()) == _read_by_the_library()
