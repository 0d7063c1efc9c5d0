"""Tier-1 guard: the invariant linter (scripts/lint_invariants.py,
docs/static-analysis.md) runs CLEAN over the tree, and every rule provably
detects its target violation — a known-bad fixture per rule must produce
exactly the expected finding and its known-good twin must pass, guarding
against false negatives AND false positives as the rules evolve.

Deliberately imports no jax: the analysis package is pure stdlib AST, and
this file must stay runnable (and fast — the whole-tree run is budgeted
< 10 s) without a backend.
"""

import json
import subprocess
import sys
import textwrap
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from kakveda_tpu.analysis.framework import run_lint  # noqa: E402


def _tree(tmp_path: Path, files: dict) -> Path:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def _findings(root: Path, rule: str):
    return run_lint(root, rule_ids=[rule]).findings


# ---------------------------------------------------------------------------
# the tree itself
# ---------------------------------------------------------------------------


def test_tree_is_clean_and_fast():
    """The shipped tree passes every rule (exit 0) well inside the tier-1
    budget — and the committed baseline stays empty."""
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint_invariants.py"), str(ROOT)],
        capture_output=True, text=True, timeout=60,
    )
    wall = time.perf_counter() - t0
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
    assert wall < 10.0, f"lint took {wall:.1f}s — budget is 10s"
    baseline = json.loads((ROOT / "kakveda_tpu/analysis/baseline.json").read_text())
    assert baseline == [], "the baseline must stay empty — fix, don't grandfather"


def test_json_output_and_exit_codes():
    r = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint_invariants.py"),
         str(ROOT), "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stdout
    out = json.loads(r.stdout)
    assert out["findings"] == []
    assert len(out["rules"]) >= 6
    r = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint_invariants.py"),
         str(ROOT), "--rule", "no-such-rule"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 2


# ---------------------------------------------------------------------------
# one transformer block
# ---------------------------------------------------------------------------


def test_the_block_is_spelled_out_once():
    """Where ``forward-flag-parity`` compared four copies of the block there
    is one: in ``kakveda_tpu/models/`` a layer's two norms are each read in
    exactly one function, ``llama.transformer_block``, and each of the four
    forward paths reaches it (directly, or through ``llama.run_layers``). That
    a path also runs it RIGHT is behaviour, asserted by the ``decode_parity``
    fixture (tests/conftest.py) in every family's test."""
    import ast

    reads, calls = {"attn_norm": [], "mlp_norm": []}, {}
    for path in sorted((ROOT / "kakveda_tpu" / "models").glob("*.py")):
        for fn in ast.walk(ast.parse(path.read_text())):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for n in ast.walk(fn):
                if isinstance(n, ast.Subscript) and isinstance(n.ctx, ast.Load):
                    key = n.slice.value if isinstance(n.slice, ast.Constant) else None
                    if key in reads:
                        reads[key].append(fn.name)
                elif isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                    calls.setdefault(fn.name, set()).add(n.func.id)
    assert reads == {"attn_norm": ["transformer_block"], "mlp_norm": ["transformer_block"]}
    assert "transformer_block" in calls["run_layers"]
    for path_fn in ("forward", "decode_step", "_forward_wide", "_stage_apply"):
        assert calls[path_fn] & {"transformer_block", "run_layers"}, path_fn


# ---------------------------------------------------------------------------
# single-writer
# ---------------------------------------------------------------------------

_SW_GOOD = """
    class BrownoutController:
        def __init__(self):
            self._step = 0
        def _set_brownout_state(self, new_step, pressure):
            self._step = new_step
        def note_pressure(self, pressure):
            if pressure > 0.9:
                self._set_brownout_state(self._step + 1, pressure)
"""


def test_single_writer_bad(tmp_path):
    bad = textwrap.dedent(_SW_GOOD) + (
        "    def force(self):\n"
        "        self._step = 3\n"
    )
    root = _tree(tmp_path, {"kakveda_tpu/core/admission.py": bad})
    fs = _findings(root, "single-writer")
    assert len(fs) == 1, [f.human() for f in fs]
    assert "_step" in fs[0].message and "force" in fs[0].message


def test_single_writer_good(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/core/admission.py": _SW_GOOD})
    assert _findings(root, "single-writer") == []


# ---------------------------------------------------------------------------
# stats-lock
# ---------------------------------------------------------------------------

_SL_BAD = """
    import threading

    class ContinuousBatcher:
        def __init__(self):
            self.stats_lock = threading.RLock()
            self.spec_stats = {"chunks": 0}
        def process_chunk(self):
            self.spec_stats["chunks"] += 1
"""

_SL_GOOD = """
    import threading

    class ContinuousBatcher:
        def __init__(self):
            self.stats_lock = threading.RLock()
            self.spec_stats = {"chunks": 0}
        def process_chunk(self):
            with self.stats_lock:
                s = self.spec_stats
                s["chunks"] += 1
"""


def test_stats_lock_bad(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": _SL_BAD})
    fs = _findings(root, "stats-lock")
    assert len(fs) == 1, [f.human() for f in fs]
    assert "process_chunk" in fs[0].message


def test_stats_lock_good_including_alias(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": _SL_GOOD})
    assert _findings(root, "stats-lock") == []


def test_stats_lock_alias_mutation_outside_lock(tmp_path):
    """An alias taken under the lock but mutated outside it is still a
    violation — the lexical block is the contract."""
    src = _SL_GOOD.replace(
        "            with self.stats_lock:\n"
        "                s = self.spec_stats\n"
        "                s[\"chunks\"] += 1",
        "            with self.stats_lock:\n"
        "                s = self.spec_stats\n"
        "            s[\"chunks\"] += 1",
    )
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": src})
    fs = _findings(root, "stats-lock")
    assert len(fs) == 1, [f.human() for f in fs]


def test_stats_lock_external_read(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/models/serving.py": _SL_GOOD,
        "kakveda_tpu/service/panel.py": """
            def panel(engine):
                return engine.cb.spec_stats
        """,
    })
    fs = _findings(root, "stats-lock")
    assert len(fs) == 1 and fs[0].file == "kakveda_tpu/service/panel.py"


def test_stats_lock_real_tree_guard_deletion(tmp_path):
    """Acceptance criterion: deleting a `with stats_lock` guard from the
    REAL serving module makes the lint fail."""
    dst = tmp_path / "kakveda_tpu/models/serving.py"
    dst.parent.mkdir(parents=True, exist_ok=True)
    src = (ROOT / "kakveda_tpu/models/serving.py").read_text()
    dst.write_text(src)
    assert _findings(tmp_path, "stats-lock") == []

    guarded = (
        'with self.stats_lock:\n            self.prefix_stats["registered"] += 1'
    )
    assert guarded in src
    dst.write_text(src.replace(
        guarded, 'self.prefix_stats["registered"] += 1', 1
    ))
    fs = _findings(tmp_path, "stats-lock")
    assert len(fs) >= 1, "deleting a stats_lock guard must fail the lint"


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


def test_host_sync_bad(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/models/m.py": """
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x)
        """,
    })
    fs = _findings(root, "host-sync")
    assert len(fs) == 1 and "np.asarray" in fs[0].message


def test_host_sync_good(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/models/m.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np

            @jax.jit
            def step(x):
                return jnp.asarray(x) + 1

            def host_side(x):
                return np.asarray(x)  # fine: not a traced body
        """,
    })
    assert _findings(root, "host-sync") == []


def test_host_sync_scan_body_and_item(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/ops/o.py": """
            import jax

            def outer(xs):
                def body(carry, x):
                    return carry + x.item(), None
                return jax.lax.scan(body, 0, xs)
        """,
    })
    fs = _findings(root, "host-sync")
    assert len(fs) == 1 and ".item()" in fs[0].message


def test_host_sync_mirror_copy(tmp_path):
    bad = """
        import jax.numpy as jnp

        class CB:
            def step(self):
                return jnp.asarray(self._kv_np)
    """
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": bad})
    fs = _findings(root, "host-sync")
    assert len(fs) == 1 and ".copy()" in fs[0].message
    root2 = _tree(tmp_path / "g", {
        "kakveda_tpu/models/serving.py": bad.replace("self._kv_np", "self._kv_np.copy()"),
    })
    assert _findings(root2, "host-sync") == []


# ---------------------------------------------------------------------------
# typed-errors
# ---------------------------------------------------------------------------

_TE_BAD = """
    def handler(eng):
        try:
            return eng.submit([1, 2, 3])
        except Exception:
            return None
"""


def test_typed_errors_bad(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/service/h.py": _TE_BAD})
    fs = _findings(root, "typed-errors")
    assert len(fs) == 1, [f.human() for f in fs]


def test_typed_errors_good_variants(tmp_path):
    root = _tree(tmp_path, {
        # Typed errors handled first: the broad tail is fine.
        "kakveda_tpu/service/a.py": """
            def handler(eng):
                try:
                    return eng.submit([1])
                except OverloadError:
                    raise
                except Exception:
                    return None
        """,
        # Propagating the original exception keeps it typed.
        "kakveda_tpu/service/b.py": """
            def handler(eng, fut):
                try:
                    return eng.submit([1])
                except Exception as e:
                    fut.set_exception(e)
        """,
        # No typed-error source in the try: broad catch is fine.
        "kakveda_tpu/service/c.py": """
            async def handler(request):
                try:
                    return await request.json()
                except Exception:
                    return {}
        """,
    })
    assert _findings(root, "typed-errors") == []


# ---------------------------------------------------------------------------
# fault-site-once
# ---------------------------------------------------------------------------


def test_fault_site_once_bad(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/x.py": """
            from kakveda_tpu.core import faults as _faults

            def hot_path():
                _faults.site("engine.hotloop").fire()
        """,
    })
    fs = _findings(root, "fault-site-once")
    assert len(fs) == 1 and "hot_path" in fs[0].message


def test_fault_site_once_good(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/x.py": """
            from kakveda_tpu.core import faults as _faults

            _MODULE_SITE = _faults.site("engine.import_time")

            class C:
                def __init__(self):
                    self._site = _faults.site("engine.ctor")
                def hot(self):
                    self._site.fire()
        """,
    })
    assert _findings(root, "fault-site-once") == []


# ---------------------------------------------------------------------------
# fault-site-catalog + knob-docs (check_knobs, as rules)
# ---------------------------------------------------------------------------


def test_fault_site_catalog_rule(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/x.py": """
            from kakveda_tpu.core import faults as _faults
            _A = _faults.site("engine.newsite")
            _B = _faults.site("gfkb.cataloged")
        """,
        "docs/robustness.md": "| `gfkb.cataloged` | somewhere | documented |\n",
    })
    fs = _findings(root, "fault-site-catalog")
    assert len(fs) == 1 and "engine.newsite" in fs[0].message


def test_knob_docs_rule(tmp_path):
    root = _tree(tmp_path, {
        "kakveda_tpu/x.py": """
            import os
            os.environ.get("KAKVEDA_TOTALLY_NEW_KNOB")
            os.environ.get("KAKVEDA_DOCUMENTED_KNOB")
        """,
        "docs/a.md": "`KAKVEDA_DOCUMENTED_KNOB` does x; `KAKVEDA_GONE_KNOB` is dead\n",
    })
    fs = _findings(root, "knob-docs")
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 2, [f.human() for f in fs]
    assert "KAKVEDA_TOTALLY_NEW_KNOB" in msgs and "KAKVEDA_GONE_KNOB" in msgs


# ---------------------------------------------------------------------------
# framework: pragmas, baseline, syntax errors
# ---------------------------------------------------------------------------


def test_suppression_pragma(tmp_path):
    src = _SL_BAD.replace(
        'self.spec_stats["chunks"] += 1',
        'self.spec_stats["chunks"] += 1  # kakveda: allow[stats-lock]',
    )
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": src})
    res = run_lint(root, rule_ids=["stats-lock"])
    assert res.findings == [] and len(res.suppressed) == 1


def test_pragma_on_preceding_line(tmp_path):
    src = _SL_BAD.replace(
        '            self.spec_stats["chunks"] += 1',
        '            # kakveda: allow[stats-lock]\n'
        '            self.spec_stats["chunks"] += 1',
    )
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": src})
    res = run_lint(root, rule_ids=["stats-lock"])
    assert res.findings == [] and len(res.suppressed) == 1


def test_baseline_grandfathers_but_does_not_hide_new(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": _SL_BAD})
    res = run_lint(root, rule_ids=["stats-lock"])
    assert len(res.findings) == 1
    bl = root / "kakveda_tpu/analysis/baseline.json"
    bl.parent.mkdir(parents=True, exist_ok=True)
    bl.write_text(json.dumps([res.findings[0].baseline_key]))
    res = run_lint(root, rule_ids=["stats-lock"])
    assert res.findings == [] and len(res.baselined) == 1


def test_unparseable_file_is_a_finding(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/broken.py": "def f(:\n"})
    res = run_lint(root, rule_ids=["stats-lock"])
    assert len(res.findings) == 1 and res.findings[0].rule == "syntax"


def test_cli_exit_1_on_findings(tmp_path):
    root = _tree(tmp_path, {"kakveda_tpu/models/serving.py": _SL_BAD})
    r = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "lint_invariants.py"), str(root)],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 1
    assert "stats-lock" in r.stdout
