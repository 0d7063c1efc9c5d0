#!/usr/bin/env python
"""Invariant lint: machine-enforce the CLAUDE.md design contracts.

AST static analysis over the code tree (no imports, no jax — sub-second):
single-writer transition helpers, stats-lock discipline, host-sync hazards in jit
bodies, typed-error discipline on service paths, fault-site resolve-once,
plus the knob-docs / fault-site-catalog parity checks shared with
``scripts/check_knobs.py``. Rule catalog: docs/static-analysis.md.

Usage::

    python scripts/lint_invariants.py [root] [--json] [--rule ID ...]
                                      [--list-rules] [--update-baseline]
                                      [--changed]

``--changed`` scans only the files git reports as modified/staged/
untracked (filtered to the lint's code tree) — a sub-100 ms pre-commit
loop. Whole-tree rules (knob docs, fault-site catalog, lock-order …)
need the full corpus and are skipped in that mode: the full-tree run
stays the tier-1 gate.

Exit codes (stable; tier-1 asserts them via tests/test_lint_invariants.py):
0 = clean (suppressed/baselined findings allowed), 1 = live findings,
2 = usage or internal error.

Suppress a deliberate exception inline with ``# kakveda: allow[rule-id]``
(same line or the line above) and a comment saying why. The committed
baseline (kakveda_tpu/analysis/baseline.json) grandfathers findings
without suppressing new ones — it ships empty; keep it that way.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

# Script-mode bootstrap: `python scripts/lint_invariants.py` puts scripts/
# on sys.path, not the repo root the package imports need.
_REPO = Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from kakveda_tpu.analysis import discovery  # noqa: E402
from kakveda_tpu.analysis.framework import (  # noqa: E402
    BASELINE_REL,
    all_rules,
    run_lint,
)


def _changed_files(root: Path) -> list:
    """Modified + staged + untracked .py files inside the lint's code
    tree, as absolute paths. Empty list = nothing relevant changed."""
    import subprocess

    out = subprocess.run(
        ["git", "-C", str(root), "status", "--porcelain", "--untracked-files=all"],
        capture_output=True, text=True, timeout=10, check=True,
    ).stdout
    rels = set()
    for line in out.splitlines():
        if len(line) < 4:
            continue
        path = line[3:].strip()
        if " -> " in path:  # rename: scan the new side
            path = path.split(" -> ", 1)[1]
        path = path.strip('"')
        if path.endswith(".py"):
            rels.add(path)
    picked = []
    for rel in sorted(rels):
        p = root / rel
        if not p.is_file() or discovery._skipped(root, p):
            continue
        if any(rel == c or rel.startswith(c + "/") for c in discovery.CODE_PATHS):
            picked.append(p)
    return picked


def main(argv: list) -> int:
    ap = argparse.ArgumentParser(
        prog="lint_invariants.py",
        description="AST invariant lint (docs/static-analysis.md)",
    )
    ap.add_argument("root", nargs="?", default=str(_REPO))
    ap.add_argument("--json", action="store_true", help="machine output")
    ap.add_argument("--rule", action="append", help="run only these rule ids")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline to grandfather current findings",
    )
    ap.add_argument(
        "--changed", action="store_true",
        help="scan only git-modified files; per-file rules only (pre-commit)",
    )
    try:
        args = ap.parse_args(argv[1:])
    except SystemExit as e:
        return 2 if e.code else 0

    if args.list_rules:
        for rid, rule in all_rules().items():
            print(f"{rid}: {rule.invariant}")
        return 0

    root = Path(args.root).resolve()
    if not root.is_dir():
        print(f"lint_invariants: not a directory: {root}", file=sys.stderr)
        return 2
    files = None
    if args.changed:
        if args.update_baseline:
            print("lint_invariants: --changed and --update-baseline are "
                  "incompatible (baseline needs the full tree)", file=sys.stderr)
            return 2
        try:
            files = _changed_files(root)
        except Exception as e:  # noqa: BLE001 — not-a-git-checkout etc.
            print(f"lint_invariants: --changed needs git: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2
        if not files:
            print("lint_invariants: ok — no changed code files")
            return 0
    try:
        res = run_lint(root, rule_ids=args.rule, files=files)
    except KeyError as e:
        print(f"lint_invariants: unknown rule {e.args[0]!r} "
              "(see --list-rules)", file=sys.stderr)
        return 2
    except Exception as e:  # noqa: BLE001 — internal error is exit 2, not a traceback-as-failure
        print(f"lint_invariants: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.update_baseline:
        path = root / BASELINE_REL
        keys = sorted(f.baseline_key for f in res.findings + res.baselined)
        path.write_text(json.dumps(keys, indent=2) + "\n")
        print(f"lint_invariants: baseline rewritten with {len(keys)} key(s)")
        return 0

    if args.json:
        print(json.dumps({
            "findings": [f.as_dict() for f in res.findings],
            "suppressed": [f.as_dict() for f in res.suppressed],
            "baselined": [f.as_dict() for f in res.baselined],
            "rules": res.rules_run,
        }))
        return 1 if res.findings else 0

    for f in res.findings:
        print(f.human())
    for f in res.baselined:
        print(f"{f.human()}  [baselined]")
    status = "FAIL" if res.findings else "ok"
    print(
        f"lint_invariants: {status} — {len(res.findings)} finding(s), "
        f"{len(res.suppressed)} suppressed, {len(res.baselined)} baselined "
        f"({len(res.rules_run)} rule(s))"
    )
    return 1 if res.findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
