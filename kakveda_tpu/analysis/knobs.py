"""Knob-documentation and fault-site-catalog parity — the checks
``scripts/check_knobs.py`` pioneered, now shared with the invariant
linter's ``knob-docs`` and ``fault-site-catalog`` rules so both entry
points enforce ONE contract over ONE tree walk
(:mod:`kakveda_tpu.analysis.discovery`).

Contract (unchanged from the original script): every ``KAKVEDA_*`` env
knob the code reads must be documented in the docs corpus, every
documented knob must still be read by code (dead-knob drift), and every
``faults.site("…")`` registered in code must appear in
docs/robustness.md's catalog — the only surface an operator can discover
``KAKVEDA_FAULTS`` arms from.
"""

from __future__ import annotations

import re
from pathlib import Path

from kakveda_tpu.analysis.discovery import code_files, md_files

KNOB_RE = re.compile(r"KAKVEDA_[A-Z0-9_]+")
# A fault-site registration in code: faults.site("engine.dispatch") /
# _faults.site("gfkb.append"). Dotted lowercase names only — the call in
# core/faults.py's own site() definition has no literal and never matches.
SITE_RE = re.compile(r"""\bsite\(\s*["']([a-z0-9_]+(?:\.[a-z0-9_]+)+)["']\s*\)""")

# Internal/cross-process plumbing set by our own launchers, not operators.
ALLOWLIST = frozenset({
    "KAKVEDA_PROCESS_ID",  # set per-process by the multihost launcher
    "KAKVEDA_CRASHSWEEP_CHILD",  # marker set per-child by the crash sweep
})

# Knobs the docs legitimately mention without the scanned code tree reading
# them — test-surface levers (tests/ is excluded from the code walk on
# purpose) and docs-about-the-docs. Anything else documented-but-unread is
# dead-knob drift and fails.
DOC_ONLY_ALLOWLIST = frozenset({
    # tests/test_hf_integration.py: prompt/expectation for the real-weight
    # integration test (tests/ is outside the code scan)
    "KAKVEDA_HF_PROMPT",
    "KAKVEDA_HF_EXPECT",
})


def referenced_knobs(root: Path) -> dict:
    """knob -> sorted list of repo-relative files referencing it."""
    refs: dict = {}
    for f in code_files(Path(root)):
        try:
            text = f.read_text(errors="replace")
        except OSError:
            continue
        for m in set(KNOB_RE.findall(text)):
            if m.rstrip("_") != m or m == "KAKVEDA_":
                continue
            refs.setdefault(m, []).append(str(f.relative_to(root)))
    for files in refs.values():
        files.sort()
    return refs


def documented_knobs(root: Path) -> set:
    docs: set = set()
    for f in md_files(Path(root)):
        try:
            docs.update(KNOB_RE.findall(f.read_text(errors="replace")))
        except OSError:
            continue
    return docs


def undocumented_knobs(root: Path) -> dict:
    """knob -> referencing files, for every knob the docs never mention."""
    refs = referenced_knobs(root)
    docs = documented_knobs(root)
    return {
        k: v for k, v in sorted(refs.items())
        if k not in docs and k not in ALLOWLIST
    }


def registered_fault_sites(root: Path) -> dict:
    """site name -> sorted list of repo-relative files registering it."""
    refs: dict = {}
    for f in code_files(Path(root)):
        try:
            text = f.read_text(errors="replace")
        except OSError:
            continue
        for m in set(SITE_RE.findall(text)):
            refs.setdefault(m, []).append(str(f.relative_to(root)))
    for files in refs.values():
        files.sort()
    return refs


def undocumented_fault_sites(root: Path) -> dict:
    """Registered sites docs/robustness.md never mentions — the catalog is
    the only surface an operator can discover KAKVEDA_FAULTS arms from."""
    doc = Path(root) / "docs" / "robustness.md"
    try:
        text = doc.read_text(errors="replace")
    except OSError:
        text = ""
    return {k: v for k, v in sorted(registered_fault_sites(root).items())
            if k not in text}


def dead_knobs(root: Path) -> list:
    """Documented knobs the code no longer references — dead-knob drift."""
    refs = referenced_knobs(root)
    docs = documented_knobs(root)
    return sorted(
        k for k in docs
        if k not in refs
        and k not in DOC_ONLY_ALLOWLIST
        and k.rstrip("_") == k and k != "KAKVEDA_"
    )
