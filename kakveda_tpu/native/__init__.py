"""ctypes bindings for the C++ native host tier (src/native.cc).

The native library accelerates the host-side hot loops around the TPU
core: signature-text featurization (the per-trace CPU cost of the
10k traces/sec ingest path), the GFKB's append-only persistence
(group-commit writer vs the reference's open+write+close per record,
reference: services/gfkb/app.py:49-51), and host-tier scoring
(:func:`score_block` / :func:`score_candidates` / :func:`score_gather` —
the sparse-dot cosine under every degraded-window warn and routed
overflow match, index/tiers.py; the gather form scores candidate row ids
in place from warm arrays or cold memmap shards, no materialization). ctypes releases the GIL for the duration of each
foreign call, so a long scoring scan never blocks the event loop.

Everything here is optional: ``load()`` returns None when the library is
absent and cannot be built, and every consumer falls back to the pure
Python implementation. Set ``KAKVEDA_NATIVE=0`` to force the fallback,
``KAKVEDA_NATIVE=require`` to fail loudly instead of falling back.
Scoring knobs (docs/observability.md registry): ``KAKVEDA_NATIVE_THREADS``
(0 = one per CPU, capped at 16) and ``KAKVEDA_NATIVE_MIN_ROWS`` (row floor
below which the numpy path wins — thread/ctypes overhead dominates tiny
scans).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional

import numpy as np

log = logging.getLogger("kakveda.native")

_DIR = Path(__file__).parent
_LIB_PATH = _DIR / "build" / "libkakveda_native.so"

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _build() -> bool:
    """Compile the library in-tree (g++ is part of the supported toolchain)."""
    try:
        subprocess.run(
            ["make", "-s"],
            cwd=_DIR,
            check=True,
            capture_output=True,
            timeout=120,
        )
        return _LIB_PATH.exists()
    except (subprocess.SubprocessError, OSError) as e:  # noqa: PERF203
        stderr = getattr(e, "stderr", b"") or b""
        log.warning(
            "native build failed (%s); stderr tail: %s",
            e, stderr.decode(errors="replace")[-2000:],
        )
        return False


def load() -> Optional[ctypes.CDLL]:
    """The shared library, building it on first use; None if unavailable."""
    global _lib, _load_attempted
    if _lib is not None:
        return _lib
    env = os.environ.get("KAKVEDA_NATIVE", "auto").lower()
    if _load_attempted:
        if env == "require":  # every caller hears it, not just the first
            raise RuntimeError("KAKVEDA_NATIVE=require but the native library did not load")
        return None
    _load_attempted = True
    if env in ("0", "false", "off"):
        return None
    # Rebuild when the source is newer than the .so (a stale library would
    # be missing newly added symbols); a source-less artifact deployment
    # (built .so, no src/) is simply never stale.
    src = _DIR / "src" / "native.cc"
    stale = not _LIB_PATH.exists() or (
        src.exists() and src.stat().st_mtime > _LIB_PATH.stat().st_mtime
    )
    if stale and not _build() and not _LIB_PATH.exists():
        if env == "require":
            raise RuntimeError("KAKVEDA_NATIVE=require but the native library cannot be built")
        log.warning("native library unavailable; using the pure-Python host tier")
        return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
    except OSError as e:
        if env == "require":
            raise
        log.warning("native load failed (%s); using the pure-Python host tier", e)
        return None

    try:
        _bind(lib)
    except AttributeError as e:
        # A stale prebuilt .so (rebuild unavailable) lacking newly added
        # symbols must degrade to the Python fallback, not crash load().
        if env == "require":
            raise
        log.warning("native library is stale and cannot be rebuilt (%s); using Python fallback", e)
        return None
    _lib = lib
    return _lib


def _bind(lib: ctypes.CDLL) -> None:
    lib.kkv_crc32.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.kkv_crc32.restype = ctypes.c_uint32
    lib.kkv_encode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_char_p,
    ]
    lib.kkv_encode_batch.restype = ctypes.c_int
    lib.kkv_encode_sparse_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_char_p,
    ]
    lib.kkv_encode_sparse_batch.restype = ctypes.c_int
    lib.kkv_log_open.argtypes = [ctypes.c_char_p, ctypes.c_long]
    lib.kkv_log_open.restype = ctypes.c_void_p
    lib.kkv_log_append.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long]
    lib.kkv_log_append.restype = ctypes.c_int
    lib.kkv_log_flush.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.kkv_log_flush.restype = ctypes.c_int
    lib.kkv_log_close.argtypes = [ctypes.c_void_p]
    lib.kkv_log_close.restype = None
    lib.kkv_score_block.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_long,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_long,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
    ]
    lib.kkv_score_block.restype = ctypes.c_int
    lib.kkv_score_candidates.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_long,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
    ]
    lib.kkv_score_candidates.restype = ctypes.c_int
    lib.kkv_score_gather.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int,
    ]
    lib.kkv_score_gather.restype = ctypes.c_int


def available() -> bool:
    return load() is not None


def status() -> dict:
    """Load/build status for /readyz: did the library load, from where,
    and under which policy. Never triggers a build by itself beyond the
    normal first-use ``load()``."""
    try:
        lib = load()
    except RuntimeError:  # KAKVEDA_NATIVE=require and unbuildable
        lib = None
    return {
        "available": lib is not None,
        "mode": os.environ.get("KAKVEDA_NATIVE", "auto").lower(),
        "lib": str(_LIB_PATH) if _LIB_PATH.exists() else None,
        "threads": score_threads(),
    }


# ---------------------------------------------------------------------------
# host-tier scoring
# ---------------------------------------------------------------------------


def score_threads() -> int:
    """KAKVEDA_NATIVE_THREADS, resolved: 0/unset = one per CPU, capped at
    16 (scoring is memory-bound well before that)."""
    try:
        t = int(os.environ.get("KAKVEDA_NATIVE_THREADS", "0"))
    except ValueError:
        t = 0
    if t <= 0:
        t = os.cpu_count() or 1
    return max(1, min(t, 16))


def score_min_rows() -> int:
    """KAKVEDA_NATIVE_MIN_ROWS: total-row floor below which callers keep
    the numpy path (ctypes marshalling beats the win on tiny scans)."""
    try:
        return max(0, int(os.environ.get("KAKVEDA_NATIVE_MIN_ROWS", "256")))
    except ValueError:
        return 256


def _f32c(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, np.float32)


_PF = ctypes.POINTER(ctypes.c_float)
_PI32 = ctypes.POINTER(ctypes.c_int32)
_PI64 = ctypes.POINTER(ctypes.c_int64)


def score_block(
    qdense: np.ndarray,
    idx: np.ndarray,
    val: np.ndarray,
    dim: int,
    *,
    threads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """Scores ``[B, n]`` for B dense queries (``[B, dim+1]``, pad column
    zero) over the same n fixed-width sparse rows, or None when the native
    library is unavailable or the call fails (caller falls back to numpy).
    """
    lib = load()
    if lib is None:
        return None
    q = _f32c(qdense if qdense.ndim == 2 else qdense[None, :])
    b, n = q.shape[0], idx.shape[0]
    if q.shape[1] != dim + 1:
        return None
    idx_c = np.ascontiguousarray(idx, np.int32)
    val_c = _f32c(val)
    out = np.empty((b, n), np.float32)
    rc = lib.kkv_score_block(
        q.ctypes.data_as(_PF), b, dim,
        idx_c.ctypes.data_as(_PI32), val_c.ctypes.data_as(_PF),
        n, idx_c.shape[1] if idx_c.ndim == 2 else 0,
        out.ctypes.data_as(_PF),
        score_threads() if threads is None else threads,
    )
    if rc != 0:
        log.warning("kkv_score_block failed (rc=%d); numpy fallback", rc)
        return None
    return out[0] if qdense.ndim == 1 else out


def score_candidates(
    qdense: np.ndarray,
    idx: np.ndarray,
    val: np.ndarray,
    offsets: np.ndarray,
    dim: int,
    *,
    threads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """Flat scores ``[offsets[-1]]`` where query q covers candidate rows
    ``[offsets[q], offsets[q+1])`` — the one thread-pooled entry point
    behind degraded warn, overflow routed matching and the mining attach
    path. None on unavailability/failure (caller falls back to numpy)."""
    lib = load()
    if lib is None:
        return None
    q = _f32c(qdense)
    if q.ndim != 2 or q.shape[1] != dim + 1:
        return None
    off = np.ascontiguousarray(offsets, np.int64)
    total = int(off[-1])
    idx_c = np.ascontiguousarray(idx, np.int32)
    val_c = _f32c(val)
    out = np.empty(total, np.float32)
    rc = lib.kkv_score_candidates(
        q.ctypes.data_as(_PF), q.shape[0], dim,
        idx_c.ctypes.data_as(_PI32), val_c.ctypes.data_as(_PF),
        off.ctypes.data_as(_PI64),
        idx_c.shape[1] if idx_c.ndim == 2 else 0,
        out.ctypes.data_as(_PF),
        score_threads() if threads is None else threads,
    )
    if rc != 0:
        log.warning("kkv_score_candidates failed (rc=%d); numpy fallback", rc)
        return None
    return out


def score_gather(
    qdense: np.ndarray,
    idx: np.ndarray,
    val: np.ndarray,
    rows: np.ndarray,
    dim: int,
    *,
    threads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """Scores ``[len(rows)]`` for one dense query over row ids gathered
    straight from a base array — the warm tier's resident ``[cap, K]``
    arrays or a cold shard's memmap (pages fault in inside the C call,
    GIL released). STRICTLY zero-copy on idx/val: a dtype or layout
    mismatch returns None rather than silently copying a multi-GB shard.
    Row ids must be in range — the kernel does not bounds-check them."""
    lib = load()
    if lib is None:
        return None
    q = _f32c(qdense)
    if q.ndim != 1 or q.shape[0] != dim + 1:
        return None
    if (
        idx.ndim != 2 or val.ndim != 2
        or idx.dtype != np.int32 or val.dtype != np.float32
        or not idx.flags["C_CONTIGUOUS"] or not val.flags["C_CONTIGUOUS"]
    ):
        return None
    r = np.ascontiguousarray(rows, np.int64)
    if len(r) and (int(r.min()) < 0 or int(r.max()) >= idx.shape[0]):
        return None
    out = np.empty(len(r), np.float32)
    rc = lib.kkv_score_gather(
        q.ctypes.data_as(_PF), dim,
        idx.ctypes.data_as(_PI32), val.ctypes.data_as(_PF),
        idx.shape[1], r.ctypes.data_as(_PI64), len(r),
        out.ctypes.data_as(_PF),
        score_threads() if threads is None else threads,
    )
    if rc != 0:
        log.warning("kkv_score_gather failed (rc=%d); numpy fallback", rc)
        return None
    return out


class AppendLog:
    """Buffered append-only log with explicit group-commit flush.

    Pure-Python fallback when the native library is absent — same API, one
    ``open`` file object with Python-side buffering.
    """

    def __init__(self, path: str | os.PathLike, flush_bytes: int = 1 << 20):
        self._path = str(path)
        self._lib = load()
        self._h = None
        self._f = None
        if self._lib is not None:
            self._h = self._lib.kkv_log_open(self._path.encode(), flush_bytes)
        if self._h is None:
            self._lib = None
            self._f = open(self._path, "ab", buffering=flush_bytes)

    @property
    def native(self) -> bool:
        return self._h is not None

    def append(self, record: bytes) -> None:
        """Append one record (caller includes the trailing newline)."""
        if self._h is not None:
            if self._lib.kkv_log_append(self._h, record, len(record)) != 0:
                raise OSError(f"native append failed: {self._path}")
        else:
            self._f.write(record)

    def flush(self, fsync: bool = False) -> None:
        if self._h is not None:
            if self._lib.kkv_log_flush(self._h, 1 if fsync else 0) != 0:
                raise OSError(f"native flush failed: {self._path}")
        else:
            self._f.flush()
            if fsync:
                os.fsync(self._f.fileno())

    def close(self) -> None:
        if self._h is not None:
            self._lib.kkv_log_close(self._h)
            self._h = None
        elif self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
