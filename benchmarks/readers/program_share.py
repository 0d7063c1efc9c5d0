"""A jitted program's share of its roofline, or of the chip's compute peak,
from the device trace: the work the algorithm needs from the cell's shapes
(the index scan's from ``harness/peaks.py``, a model's forward pass from its
family's ``work``, ``families/<family>.py``), over the device time of the WHOLE program
that does it, found by the program's name — so the share reads the same work
whether a Pallas kernel, XLA or something later implements it. ``reads``
narrows a name that says little (the match program is ``jit__lambda``) to the
programs in which an operation takes an operand of that type and shape, the
index itself: another jitted lambda is then not summed into the match
program's time.

``work``: knn (one exact scan per run of the match program), prefill / decode
(the model's forward pass).
``of``: roofline (the larger of bytes/bandwidth and operations/peak) or mfu
(operations over the compute peak alone).
"""

import re

from harness import manifest, peaks, prom


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr:
        return None
    kind, sizes, work = ctx["device"]["kind"], ctx["sizes"], params["work"]
    rx = re.compile(params["program"])
    names = [k for k in tr["programs"] if rx.search(k)]
    if "reads" in params:
        operand = params["reads"].format(row_type={2: "bf16", 4: "f32"}[int(sizes["row_bytes"])], **sizes)
        reading = {v[2] for op, v in tr["ops"].items() if operand in op}
        names = [k for k in names if k in reading]
    secs = sum(tr["programs"][k][0] for k in names)
    runs = sum(tr["programs"][k][1] for k in names)
    if runs == 0 or secs <= 0:
        return None  # the program did not run in the traced window: nothing to read
    before, after = ctx["prom_before"], ctx["prom_after"]
    if work == "knn":
        batch = prom.mean_delta(before, after, "kakveda_microbatch_batch_size") or 1.0
        flops = runs * peaks.knn_scan_flops(sizes["index_capacity"], sizes["dim"], batch)
        nbytes = runs * peaks.knn_scan_bytes(sizes["index_capacity"], sizes["dim"], sizes["row_bytes"], batch, sizes["top_k"])
    elif work in ("prefill", "decode"):
        cfg = ctx["cell"].config
        recs = [r for r in (ctx.get("chat_records") or {}).values() if r["out"]]
        if not recs:
            return None
        p_mean = sum(len(r["ids"]) for r in recs) / len(recs)
        family = manifest.load_family(cfg)
        if work == "prefill":
            # one run admits one prompt: its tokens through the layers, causal
            # attention among them, one row of logits
            need = family.work(cfg, "prefill", tokens=p_mean, attended=p_mean * p_mean / 2, head_rows=1)
        else:
            chunks = prom.delta(before, after, "kakveda_serving_chunk_seconds_count")
            tokens = sum(len(r["out"]) for r in recs)
            if chunks <= 0:
                return None
            per_run = tokens / chunks  # tokens decoded per chunk program, over the window
            ctx_len = p_mean + sum(len(r["out"]) for r in recs) / len(recs) / 2
            need = family.work(cfg, "decode", tokens=per_run, attended=per_run * ctx_len, head_rows=per_run,
                               steps=sizes["serve_chunk"])
        flops, nbytes = runs * need["flops"], runs * need["bytes"]
    else:
        raise KeyError(f"unknown work {work!r}")
    pk = peaks.device_peaks(kind)
    if params["of"] == "mfu":
        least = flops / pk["flops_bf16"]
    else:
        least, _ = peaks.roofline_seconds(flops, nbytes, kind)
    return peaks.share_pct(least, secs, params.get("program", work))
