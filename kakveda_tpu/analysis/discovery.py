"""Source-tree discovery shared by every static check.

ONE definition of "the code tree" and "the docs corpus" — previously
``scripts/check_knobs.py`` had its own walker and any new checker would
have grown another, and the two would drift (each with its own idea of
what counts as code). Both the
invariant linter (:mod:`kakveda_tpu.analysis.framework`) and the knob
checker (:mod:`kakveda_tpu.analysis.knobs`) walk through here.

Scope decisions, inherited from check_knobs and now load-bearing for the
lint rules too:

* ``tests/`` is NOT code: test fixtures deliberately contain rule
  violations and ``KAKVEDA_TEST_*`` levers that are not operator surface.
* ``kakveda/`` (the retrieved reference tree) and ``__pycache__`` are
  never scanned.
"""

from __future__ import annotations

from pathlib import Path
from typing import Iterator

# Code that can introduce operator-facing knobs or violate design
# invariants. Tests are deliberately excluded (see module docstring).
CODE_PATHS = ("kakveda_tpu", "scripts", "bench.py", "chip_smoke.py", "__graft_entry__.py")

# The docs corpus a knob/fault-site must be discoverable from.
DOC_PATHS = ("CLAUDE.md", "README.md", "TROUBLESHOOTING.md", "BASELINE.md", "docs")

# Never descend into these directory names anywhere in the tree.
SKIP_DIRS = frozenset({"__pycache__", "kakveda", ".git", ".pytest_cache"})


def _skipped(root: Path, p: Path) -> bool:
    return any(part in SKIP_DIRS for part in p.relative_to(root).parts)


def code_files(root: Path) -> Iterator[Path]:
    """Every Python source file in the scanned code tree, sorted."""
    root = Path(root)
    for rel in CODE_PATHS:
        p = root / rel
        if p.is_file():
            yield p
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not _skipped(root, f):
                    yield f


def md_files(root: Path) -> Iterator[Path]:
    """Every markdown file in the docs corpus, sorted."""
    root = Path(root)
    for rel in DOC_PATHS:
        p = root / rel
        if p.is_file():
            yield p
        elif p.is_dir():
            for f in sorted(p.rglob("*.md")):
                if not _skipped(root, f):
                    yield f
