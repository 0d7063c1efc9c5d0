"""Poisson arrivals: due times in [0, seconds) at ``rate`` a second.

As many exponential gaps as the mean count, drawn from the stream's
``gaps_seed`` (a number in the traffic file) and scaled to fill the window
exactly. Copied in idea from the repo's ``traffic/scenarios.py`` (seeded
exponential gaps), without its chaos timeline and without importing it.

The run's ``--seed`` has no part in it. A p95 over some hundreds of requests
moves with WHERE the short gaps cluster: with the order drawn from the seed,
chat-short's ttft_p95_ms read 516-671 ms over six seeds and within 2 % on one
seed twice (PERF.md section 2). So every seed offers the same arrivals; the
seed draws the words and the weights.
"""

from __future__ import annotations

import random


def schedule(spec: dict, rate: float, seconds: float) -> list:
    n = max(1, int(round(rate * seconds)))
    rng = random.Random(f"gaps:{spec.get('gaps_seed', 0)}:{n}")
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps) * (n / (n + 1.0))
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out
