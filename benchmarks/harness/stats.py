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


def segment_median(xs, dues, q: float, segments: int, t_start: float, seconds: float):
    """The median, over ``segments`` equal parts of the window, of each part's
    ``q``-th percentile: ``xs[i]`` counts in the part its due time ``dues[i]``
    falls in, and in no other. One stall of the machine spoils the part it
    falls in (two on a border) and the backlog it leaves; the median of five
    parts stands while two or fewer are spoilt, where the window's own
    percentile is drawn from its worst part. With one segment it is the
    window's percentile. None where a part holds no request."""
    if segments <= 1:
        return percentile(xs, q)
    parts = [[] for _ in range(segments)]
    for x, due in zip(xs, dues):
        parts[min(segments - 1, max(0, int((due - t_start) / seconds * segments)))].append(x)
    if not all(parts):
        return None
    per_part = sorted(percentile(p, q) for p in parts)
    mid = len(per_part) // 2
    return per_part[mid] if len(per_part) % 2 else (per_part[mid - 1] + per_part[mid]) / 2.0
