"""Published peaks of the chips, the work an exact index scan needs from its
shapes, and the arithmetic of a share. (The work of a model's forward pass is
its family's: ``families/<family>.py:work``.)

The peaks are the table of the repo's ``bench.py`` (``DEVICE_PEAKS``), copied
here so that a later PR cannot move the yardstick. Source: Google Cloud TPU
documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB per chip).
An unknown ``device_kind`` is an error, never a default.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def device_peaks(kind: str) -> dict:
    if kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device_kind {kind!r}; add them with their source")
    return DEVICE_PEAKS[kind]


def knn_scan_bytes(capacity: int, dim: int, row_bytes: int, batch: int, top_k: int) -> int:
    """Bytes one exact top-k scan must move: every row of the index once,
    the dense queries, and the (value, index) results."""
    return capacity * dim * row_bytes + batch * dim * 4 + batch * top_k * 8


def knn_scan_flops(capacity: int, dim: int, batch: int) -> int:
    return 2 * capacity * dim * batch


def roofline_seconds(flops: float, nbytes: float, kind: str) -> tuple:
    """Least time the chip could take, and which bound sets it."""
    pk = device_peaks(kind)
    tf, tb = flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")


def share_pct(least_s: float, measured_s: float, what: str) -> float:
    """A share of a roofline or a peak, in percent. Over 105 % the count of
    operations or bytes is too high, or the time leaves out part of the work:
    that is a fault, not a result."""
    if measured_s <= 0:
        raise ValueError(f"{what}: no measured time")
    pct = 100.0 * least_s / measured_s
    if pct > 105.0:
        raise ValueError(f"{what}: {pct:.1f} % of the roofline — operations/bytes over-counted or time under-counted")
    return pct
