"""Tier-1 guard: scripts/verify_static.sh — the one-shot pre-commit
static gate (invariant lint + knob parity + ledger smoke) — passes on
the committed tree. CI and the pre-commit habit share one entry point;
this test is what keeps the script from rotting."""

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_verify_static_green():
    env = dict(os.environ)
    # the script runs its own interpreter, which must find the package
    env.setdefault("PYTHONPATH", os.pathsep.join(
        p for p in (env.get("PYTHONPATH", ""), str(ROOT)) if p
    ))
    r = subprocess.run(
        ["bash", str(ROOT / "scripts" / "verify_static.sh")],
        capture_output=True, text=True, timeout=300, cwd=str(ROOT), env=env,
    )
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
    assert "ledger smoke: ok" in r.stdout
    assert "verify_static: all stages green" in r.stdout


def test_verify_static_changed_mode_accepts_flag():
    r = subprocess.run(
        ["bash", str(ROOT / "scripts" / "verify_static.sh"), "--changed"],
        capture_output=True, text=True, timeout=300, cwd=str(ROOT),
    )
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"


def test_script_uses_python_executable_on_path():
    """The script must not hardcode an interpreter path — it runs under
    whatever `python` the caller's env resolves (tier-1, probe loop,
    operator shell)."""
    src = (ROOT / "scripts" / "verify_static.sh").read_text()
    assert "set -euo pipefail" in src
    assert sys.executable not in src
