"""The records agree with the tree (ISSUE 31).

Three guards on the program's own shape — imports point one way, the
count of ``CHIASWARM_*`` knobs only shrinks, the README names no knob
that nothing reads — and three on the documents every session starts
from: a back-ticked file name in ``README.md``, ``PERF.md`` or the verify
skill has to be a file of this checkout. Pure text and ``ast``; nothing
here imports the package.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PACKAGE = ROOT / "chiaswarm_tpu"

#: the layers below the worker: none of them may import the layers above
LOWER = ("core", "ops", "models", "schedulers", "parallel", "pipelines",
         "serving", "obs")
UPPER = ("chiaswarm_tpu.node", "chiaswarm_tpu.workloads")

#: distinct whole ``CHIASWARM_*`` names under ``chiaswarm_tpu/``.
#: Shrink-only, like ``.swarmlint-baseline.json``: a PR that removes a
#: knob lowers it, and no PR raises it (ROADMAP D4).
KNOB_CEILING = 46
_KNOB_RE = re.compile(r"CHIASWARM_[A-Z0-9_]*[A-Z0-9](?![A-Z0-9_])")

#: where a back-ticked path may live
BASES = ("", "chiaswarm_tpu", "perfbench", "tools", "tests", "tests/bench",
         ".github/workflows")
_PATH_RE = re.compile(r"`([^`\s]+\.(?:py|json|md|yml|txt))`")
#: what a run writes, and what the driver keeps outside the checkout
RUN_ARTIFACTS = frozenset({
    "kbench.json", "result.json", "mosaic_calls.txt", "settings.json",
    "residency.json", "TESTS_LAST_RUN.json", "REVIEW.md",
    ".swarmflow-cache.json",
})


def _imports(path: Path) -> set[str]:
    """Absolute dotted names of every module ``path`` imports, at any
    depth (a function-level import is an arrow too)."""
    package = path.relative_to(ROOT).parts[:-1]
    found: set[str] = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: resolve against this package
                parent = package[:len(package) - node.level + 1]
                base = ".".join([*parent, *([base] if base else [])])
            found.add(base)
            found.update(f"{base}.{alias.name}" for alias in node.names)
    return found


def _knobs(paths) -> set[str]:
    return {name for path in paths
            for name in _KNOB_RE.findall(path.read_text())}


def _reaches_up(name: str) -> bool:
    return any(name == up or name.startswith(up + ".") for up in UPPER)


def test_lower_layers_import_nothing_above_them():
    arrows = sorted(
        f"{path.relative_to(ROOT)} -> {name}"
        for layer in LOWER
        for path in (PACKAGE / layer).rglob("*.py")
        for name in _imports(path) if _reaches_up(name))
    assert arrows == []


def test_knob_count_only_shrinks():
    names = _knobs(PACKAGE.rglob("*.py"))
    assert len(names) <= KNOB_CEILING, sorted(names)


def test_readme_names_only_knobs_something_reads():
    # tests/ too: the nightly soaks' seed is the suite's own knob
    read = _knobs([*PACKAGE.rglob("*.py"), *(ROOT / "tools").rglob("*.py"),
                   *(ROOT / "tests").glob("test_*.py"),
                   ROOT / "chip_smoke.py"])
    assert sorted(_knobs([ROOT / "README.md"]) - read) == []


@pytest.mark.parametrize(
    "document", ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"])
def test_every_file_a_document_names_exists(document):
    text = (ROOT / document).read_text()
    missing = sorted({
        name for name in _PATH_RE.findall(text)
        if "<" not in name and "*" not in name
        and name.rsplit("/", 1)[-1] not in RUN_ARTIFACTS
        and not any((ROOT / base / name).is_file() for base in BASES)})
    assert missing == []
