#!/usr/bin/env python
"""Render a bench result (one-line JSON from bench.py, or a driver
BENCH_r{N}.json) as a readable table, with the BASELINE.md north stars
called out.

    python scripts/bench_report.py result.json
    python bench.py | python scripts/bench_report.py -

No deps beyond stdlib; safe to run anywhere — it never initializes an
accelerator backend."""

from __future__ import annotations

import json
import sys

NORTH_STARS = {
    # metric-name prefix -> (target, comparator, unit)
    "preflight_warn_p50_ms": (10.0, "<", "ms"),
    "ingest_throughput_traces_per_sec": (10_000.0, ">=", "traces/s"),
}


def _flatten(doc: dict) -> list:
    """A bench line is {headline..., extra_metrics: [...]}; a driver
    BENCH_r{N}.json wraps it ({"rc": ..., "tail": "...stderr+stdout..."},
    the JSON line being the last {-prefixed line of the tail)."""
    for key in ("result", "stdout", "tail"):
        v = doc.get(key)
        if isinstance(v, str):
            lines = [ln for ln in v.splitlines() if ln.lstrip().startswith("{")]
            if lines:
                try:
                    doc = json.loads(lines[-1])
                    break
                except ValueError:
                    continue
        elif isinstance(v, dict):
            doc = v
            break
    if "metric" not in doc:
        rc = doc.get("rc")
        raise SystemExit(
            f"no metric JSON found (rc={rc}); keys: {sorted(doc)[:8]}"
        )
    return [doc] + list(doc.get("extra_metrics", []))


def _star(name: str, value: float) -> str:
    for prefix, (target, op, unit) in NORTH_STARS.items():
        if name.startswith(prefix):
            ok = value < target if op == "<" else value >= target
            verdict = "MET" if ok else "MISSED"
            return f"  <- north star {op} {target:g} {unit}: {verdict}"
    return ""


def main() -> int:
    path = sys.argv[1] if len(sys.argv) > 1 else "-"
    raw = sys.stdin.read() if path == "-" else open(path).read()
    try:
        doc = json.loads(raw)  # whole file (driver files are pretty-printed)
    except ValueError:
        # bench stdout piped with stderr noise: find the JSON line
        line = next(
            (ln for ln in raw.splitlines() if ln.lstrip().startswith("{")), raw
        )
        doc = json.loads(line)
    metrics = _flatten(doc)
    width = max(len(m["metric"]) for m in metrics)
    for m in metrics:
        extras = {
            k: v
            for k, v in m.items()
            if k not in ("metric", "value", "unit", "vs_baseline", "extra_metrics")
        }
        extra_s = " ".join(f"{k}={v}" for k, v in sorted(extras.items()))
        print(
            f"{m['metric']:<{width}}  {m['value']:>12,.3f} {m.get('unit', ''):<11}"
            f"(vs_baseline {m.get('vs_baseline', '—')})"
            f"{_star(m['metric'], float(m['value']))}"
        )
        if extra_s:
            print(f"{'':<{width}}  {extra_s}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
