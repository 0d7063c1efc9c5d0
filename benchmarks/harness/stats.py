"""The percentile every latency metric uses (nearest rank; copied in idea from
the repo's ``traffic/slo.py``, without importing it)."""

from __future__ import annotations


def percentile(xs, q: float):
    """Nearest-rank percentile; None on empty input."""
    xs = sorted(xs)
    if not xs:
        return None
    i = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return float(xs[i])
