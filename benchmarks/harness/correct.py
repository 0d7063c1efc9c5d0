"""What decides ``correct``: every number an endpoint's ``check`` compared
(``endpoints/<name>.py``: the answers of the timed window against the plain
references), beside the cell's limit for it (``limits/<cell>.json``, set from
measured readings: PERF.md section 2). A run is correct when every number that
has a limit is there and within it.

``served_gaps`` / ``argmax_gaps`` turn a served model's reference logits into
the numbers compared. They read logits, not a model: every family's reference
hands them the same [B, S, vocab_live] array.
"""

from __future__ import annotations


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, compared): every number that has a limit, beside it."""
    compared, ok = {}, True
    for name, limit in limits.items():
        if name not in numbers:
            compared[name] = {"value": None, "limit": limit}
            ok = False
            continue
        v = numbers[name]
        compared[name] = {"value": v, "limit": limit}
        if not (v <= limit):
            ok = False
    return ok, compared


def served_gaps(ref_logits, prompts_len, served) -> list:
    """For each served token, how far its reference logit lies below the
    reference's best at that position. ``served[r]`` are the tokens request r
    was given after a prompt of ``prompts_len[r]`` tokens."""
    import numpy as np

    lg = np.asarray(ref_logits)
    gaps = []
    for r, (p, toks) in enumerate(zip(prompts_len, served)):
        for k, t in enumerate(toks):
            row = lg[r, p - 1 + k]
            gaps.append(float(row.max() - row[t]))
    return gaps


def argmax_gaps(ref_logits, other_logits, prompts_len, served) -> list:
    """The control's reading: at each position of the same prompts and tokens,
    the reference-logit gap of the token the OTHER pass puts first."""
    import numpy as np

    lg, ot = np.asarray(ref_logits), np.asarray(other_logits)
    gaps = []
    for r, (p, toks) in enumerate(zip(prompts_len, served)):
        for k in range(len(toks)):
            row = lg[r, p - 1 + k]
            gaps.append(float(row.max() - row[int(ot[r, p - 1 + k].argmax())]))
    return gaps
