"""Prometheus text: sums of a family's samples, and deltas over a window."""

from __future__ import annotations


def parse(text: str) -> dict:
    """{(name, labels-string): value} for every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, rest = line.partition(" ")
        value = rest.split(" ", 1)[0]
        name, brace, labels = head.partition("{")
        try:
            out[(name, labels.rstrip("}") if brace else "")] = float(value)
        except ValueError:
            continue
    return out


def total(samples: dict, name: str, **labels) -> float:
    """Sum of ``name``'s samples whose labels include every given pair."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(v for (n, ls), v in samples.items() if n == name and all(w in ls for w in want))


def delta(before: dict, after: dict, name: str, **labels) -> float:
    return total(after, name, **labels) - total(before, name, **labels)


def mean_delta(before: dict, after: dict, family: str, **labels):
    """Mean of a histogram's observations inside the window (sum and count
    deltas), or None when it observed nothing there."""
    n = delta(before, after, family + "_count", **labels)
    if n <= 0:
        return None
    return delta(before, after, family + "_sum", **labels) / n
