"""The documents against the tree: every repository path that `README.md` and
`docs/*.md` name in backticks exists. The reference's own `hivemind/...` paths, and
what a run leaves behind (listed in `.gitignore`), are not this tree's to hold."""

import itertools
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
DOCUMENTS = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]
# where a document's short form is rooted: `p2p/mux.py` is `hivemind_tpu/p2p/mux.py`
BASES = [ROOT, ROOT / "hivemind_tpu", ROOT / "tests", ROOT / "docs", ROOT / "tools", ROOT / "hivemind_tpu" / "moe"]
SUFFIXES = (".py", ".md", ".json", ".jsonl", ".yml", ".cpp", ".proto", ".conf", ".toml")
IGNORED = {line.strip().rstrip("/") for line in (ROOT / ".gitignore").read_text().splitlines() if line.strip()}


def _expand(text: str) -> list:
    """`optim/{optimizer,state_averager}.py` names two files."""
    match = re.search(r"\{([^{}]*,[^{}]*)\}", text)
    if match is None:
        return [text]
    return list(itertools.chain.from_iterable(
        _expand(text[:match.start()] + part + text[match.end():]) for part in match.group(1).split(",")))


def named_paths(document: Path) -> list:
    """The backticked tokens that read as paths: `dir/file.py`, `dir/module.attribute`,
    `dir/module`, `dir/`, or a bare `file.py` (with `:lines`, `::test` and arguments cut)."""
    found = set()
    for token in re.findall(r"`([^`\n]+)`", document.read_text()):
        token = re.sub(r":[\d,\-–]+$", "", (token.split("::")[0].split() or [""])[0]).rstrip(".,;:)")
        if token.startswith(("hivemind/", "/", "~", "http")):
            continue
        for path in _expand(token):
            *directories, name = path.rstrip("/").split("/")
            if not re.fullmatch(r"[\w.\-]+", name) or not all(re.fullmatch(r"\.?[\w\-]+", part) for part in directories):
                continue
            if path.rstrip("/") in IGNORED or (directories and directories[0] in IGNORED):
                continue
            if directories or (name.endswith(SUFFIXES) and not name.startswith(".")):
                found.add(path.rstrip("/"))
    return sorted(found)


def _exists(path: str) -> bool:
    if "/" not in path:  # a bare file name: anywhere in the tree
        return any(ROOT.rglob(path))
    # `proto/regen.sh` as it stands; `parallel/ring_attention.mesh_attention_core` and
    # `hivemind_cli/run_dht` name a module: the part before the first dot, as a file or a package
    directory, name = path.rsplit("/", 1)
    module = f"{directory}/{name.split('.')[0]}"
    return any((base / candidate).exists() for base in BASES for candidate in (path, module + ".py", module))


@pytest.mark.parametrize("document", DOCUMENTS, ids=lambda path: path.name)
def test_every_path_a_document_names_exists(document):
    missing = [path for path in named_paths(document) if not _exists(path)]
    assert not missing, f"{document.name} names paths that are not in the tree: {missing}"
