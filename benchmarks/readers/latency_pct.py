"""A percentile of an open-loop stream's latency in ms, over EVERY request of
the window, each timed from when it was due. ``field``: ``done`` (last byte)
or ``first`` (first streamed delta). A failed request has no latency and
counts as slower than any: if the percentile falls on one, there is no value.

``segments`` (default 1: the window's own percentile): the median over that
many equal parts of the window, by due time, of each part's percentile
(``harness/stats.segment_median``). Every request counts in exactly one part,
a failed one still as slower than any in its part; fewer requests than parts
give no value."""

from harness.stats import segment_median


def read(ctx, params):
    recs = ctx["streams"].get(params["endpoint"])
    if not recs:
        return None
    xs = []
    for r in recs:
        t = r.get(params["field"])
        failed = t is None or r.get("error") or r.get("status", 200) != 200
        xs.append(float("inf") if failed else (t - r["due"]) * 1e3)
    v = segment_median(xs, [r["due"] for r in recs], params["q"], int(params.get("segments", 1)),
                       ctx["t_start"], ctx["seconds"])
    return None if v == float("inf") else v
