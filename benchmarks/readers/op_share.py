"""A kernel's own share of its roofline, by the kernel's name among the
device operations of the trace. Reported only where the trace has that kernel.
``work``: flash_prefill — causal attention among one admitted prompt's tokens,
per layer: 4 x (p^2 / 2) x heads x head_dim operations; bytes: q, k, v, out."""

import re

from harness import peaks


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr:
        return None
    rx = re.compile(params["op"])
    ops = [(n, s) for n, s in tr.get("ops", {}).items() if rx.search(n)]
    secs = sum(s[0] for _, s in ops)
    runs = sum(s[1] for _, s in ops)
    if runs == 0 or secs <= 0:
        return None
    cfg = ctx["cell"].config
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    p = params["rows"]  # the admit bucket that takes the kernel
    flops = runs * 4 * (p * p / 2) * h * hd
    nbytes = runs * 2 * (2 * p * h * hd + 2 * p * kv * hd)
    least, _ = peaks.roofline_seconds(flops, nbytes, ctx["device"]["kind"])
    return peaks.share_pct(least, secs, params["op"])
