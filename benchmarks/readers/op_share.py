"""A kernel's own share of its roofline, by the kernel's name among the
device operations of the trace. Reported only where the trace has that kernel.
``work`` names what one run of the kernel does, and the configuration's family
counts it (``families/<family>.py:work``; ``flash_prefill``: causal attention
among the ``rows`` tokens of the admit bucket that takes the kernel)."""

import re

from harness import manifest, peaks


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
    shape = {k: v for k, v in params.items() if k not in ("op", "work")}
    need = manifest.load_family(cfg).work(cfg, params["work"], **shape)
    flops, nbytes = runs * need["flops"], runs * need["bytes"]
    least, _ = peaks.roofline_seconds(flops, nbytes, ctx["device"]["kind"])
    return peaks.share_pct(least, secs, params["op"])
