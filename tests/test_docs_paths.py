"""The documents' commands name files that exist.

Every ``python`` / ``python3`` / ``bash`` command inside a fenced block of
``README.md`` or a ``docs/*.md`` page whose script lives in this repo (a
``*.py`` / ``*.sh`` at the root, or under ``bin/``, ``benchmarks/``,
``examples/``, ``tests/``) must name a file in the tree: a page that tells
its reader to run a harness that is gone is worse than no page."""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCUMENTS = ["README.md"] + sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md"))
_COMMAND = re.compile(r"\b(?:python3?|bash)\s+(?:-u\s+)?([\w./-]+\.(?:py|sh))\b")
_OURS = ("bin/", "benchmarks/", "examples/", "tests/")
# The launch examples' stand-in for the reader's own training script
# (``tpurun -np 4 python train.py``, as ``mpirun ... python train.py``).
_READERS_OWN = {"train.py"}


def _fenced_lines(text):
    inside = False
    for number, line in enumerate(text.splitlines(), 1):
        if line.lstrip().startswith("```"):
            inside = not inside
        elif inside:
            yield number, line


def _scripts(text):
    for number, line in _fenced_lines(text):
        for path in _COMMAND.findall(line):
            path = path.removeprefix("./")
            if path in _READERS_OWN:
                continue
            if path.startswith(_OURS) or "/" not in path:
                yield number, path


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_files_that_exist(document):
    text = (ROOT / document).read_text()
    missing = [f"{document}:{number}: {path}"
               for number, path in _scripts(text)
               if not (ROOT / path).is_file()]
    assert not missing, "commands name files that are gone:\n" + "\n".join(
        missing)


def test_the_scan_finds_commands():
    """The scan is not vacuous: it reads the entry points the README gives."""
    found = {path for _, path in _scripts((ROOT / "README.md").read_text())}
    assert "chip_smoke.py" in found
    assert any(path.startswith("examples/") for path in found)
