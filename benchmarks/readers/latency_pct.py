"""A percentile of an open-loop stream's latency in ms, over EVERY request of
the window, each timed from when it was due. ``field``: ``done`` (last byte)
or ``first`` (first streamed delta). A failed request has no latency and
counts as slower than any: if the percentile falls on one, there is no value."""

from harness.stats import percentile


def read(ctx, params):
    recs = ctx["streams"].get(params["endpoint"])
    if not recs:
        return None
    xs = []
    for r in recs:
        t = r.get(params["field"])
        failed = t is None or r.get("error") or r.get("status", 200) != 200
        xs.append(float("inf") if failed else (t - r["due"]) * 1e3)
    v = percentile(xs, params["q"])
    return None if v == float("inf") else v
