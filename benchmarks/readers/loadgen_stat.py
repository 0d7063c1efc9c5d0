"""A statistic of the load generator's own records: a latency percentile
(``field`` done/first, over the requests that were answered) or how late its
sends began. ``records``: ``streams`` (the window's, the default) or ``probe``
(a traced run's few seconds at the stream's ``probe`` rate)."""

from harness.stats import percentile


def read(ctx, params):
    recs = ctx.get(params.get("records", "streams"), {}).get(params["endpoint"])
    if not recs:
        return None
    if params["stat"] == "late":
        return percentile([max(0.0, r["late_s"]) * 1e3 for r in recs], params["q"])
    xs = [(r[params["field"]] - r["due"]) * 1e3 for r in recs
          if r.get(params["field"]) is not None and not r.get("error") and r.get("status", 200) == 200]
    return percentile(xs, params["q"])
