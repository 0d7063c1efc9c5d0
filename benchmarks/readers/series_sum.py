"""Sum of the window's deltas of several /metrics series, times ``scale``,
optionally over the delta of another series, plus ``offset``.

``series`` and ``over`` name a family, its labels and a ``field``: ``sum`` (a
histogram's ``_sum``, the default), ``count`` (its ``_count``) or ``value`` (a
counter's own sample). So the time of several phases per cycle is
``series`` = their sums over the cycle's count; the share of a cycle under no
phase is ``offset`` 100, ``scale`` -100, the children's sums over the cycle's
sum. A program that has none of the series (a scrape without a sample of any
of them) reads nothing; a series that is there and did not move reads 0, and so
does one that is missing beside others that are there (a phase the window
never entered)."""

from harness import prom

_SUFFIX = {"sum": "_sum", "count": "_count", "value": ""}


def _delta(ctx, spec):
    """The window's delta of one series, or None where the scrape after the
    window holds no sample of it."""
    name = spec["family"] + _SUFFIX[spec.get("field", "sum")]
    want = [f'{k}="{v}"' for k, v in spec.get("labels", {}).items()]
    if not any(n == name and all(w in ls for w in want) for n, ls in ctx["prom_after"]):
        return None
    return prom.delta(ctx["prom_before"], ctx["prom_after"], name, **spec.get("labels", {}))


def read(ctx, params):
    parts = [_delta(ctx, sp) for sp in params["series"]]
    if all(p is None for p in parts):
        return None
    v = sum(p for p in parts if p is not None)
    if "over" in params:
        base = _delta(ctx, params["over"])
        if base is None or base <= 0:
            return None
        v /= base
    return params.get("offset", 0.0) + v * params.get("scale", 1.0)
