"""What decides ``correct``: every number an endpoint's ``check`` compared
(``endpoints/<name>.py``: the answers of the timed window against the plain
references), beside the cell's limit for it (``limits/<cell>.json``, set from
measured readings: PERF.md section 2). A run is correct when every number that
has a limit is there and within it.
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
