"""Time per output token in ms, per request, then a percentile over all
requests: (last delta - first delta) / (tokens that came after the first
delta). The server streams a delta per decode chunk, not per token, so the
first delta already carries several tokens: under the byte tokenizer that the
configuration assumes, as many as it has characters. Tokens are the ones the
engine really produced for that prompt, taken from the launcher's record. A
failed request has no time and counts as slower than any. ``segments`` as in
``readers/latency_pct.py`` (default 1: the window's own percentile)."""

from harness.stats import segment_median


def read(ctx, params):
    recs = ctx["streams"].get("chat")
    tokens = ctx.get("chat_tokens") or {}
    if not recs:
        return None
    xs, dues = [], []
    for r in recs:
        n = tokens.get(r["prompt"])
        if r.get("first") is None or r.get("error") or not r.get("done") or not n:
            xs.append(float("inf"))  # a failed request is slower than any
            dues.append(r["due"])
            continue
        later = n - r["first_chars"]
        if later >= 1:
            xs.append((r["last"] - r["first"]) * 1e3 / later)
            dues.append(r["due"])
    v = segment_median(xs, dues, params["q"], int(params.get("segments", 1)), ctx["t_start"], ctx["seconds"])
    return None if v == float("inf") else v
