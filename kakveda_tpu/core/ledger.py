"""Runtime compile-and-transfer ledger (``KAKVEDA_LEDGER=1``).

The static half of the device-plane pass (:mod:`kakveda_tpu.analysis.
device`) reasons about retrace hazards and donation misuse from the AST;
this module is the dynamic half, the same cross-check shape as the
concurrency sanitizer (static lock-order graph ↔ runtime lock
instrumentation). The static rules say "this call site CANNOT retrace";
the ledger proves at runtime that it DIDN'T: every XLA backend compile is
counted against the jit entry point that triggered it, and every
host↔device transfer seam reports its bytes against the request phase it
served.

Off by default the module is inert: :func:`note_transfer` is one module
attribute check, nothing patches jax, nothing registers listeners. With
``KAKVEDA_LEDGER=1`` and :func:`maybe_install`:

* ``jax.jit`` is wrapped so every jitted callable created AFTER install
  carries its function name; calling it pushes that label onto a
  thread-local stack. A ``jax.monitoring`` duration listener on the
  backend-compile event attributes each actual XLA compile to the label
  on top of the stack (``unattributed`` when the compile came from a jit
  created before install — wrap those regions in :func:`entry`).
* Transfer seams (``ShardedKnn._replicate`` h2d, ``topk_result`` d2h,
  the serving engine's mirror upload / token fetch) call
  :func:`note_transfer`; bytes accumulate per (direction, phase), the
  phase being whatever :func:`phase` context is active on that thread.
* :func:`mark_warm` draws the warmup line: compiles after it are the
  bug the static retrace-hazard rule exists to prevent, so each one is
  recorded as a ``post_warmup_compile`` flight-recorder event (served at
  ``GET /flightrecorder``) and counted in :func:`ledger_report` —
  bench.py's serve/warn rows assert that count is ZERO, and the
  tiered/mine rows assert the per-entry compile counts stay inside the
  O(log N) pow2-bucket envelope.

Metrics: ``kakveda_compile_total{fn}``,
``kakveda_compile_cache_hits_total`` and
``kakveda_transfer_bytes{direction,phase}`` (``core/metrics.py``
registry; catalog in docs/observability.md).

Dependency-free at import (stdlib only; jax, the metrics registry and
the flight recorder are imported lazily at install/use) so the analysis
pass and its tests can import this module without a backend.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from typing import Dict, List, Optional

_TRUTHY = frozenset({"1", "true", "yes", "on"})

#: jax.monitoring event suffix that fires exactly once per program handed
#: to the backend (NOT per trace, NOT per in-memory jit-cache hit). A
#: program found in the persistent compilation cache still fires it — and
#: also fires the hit event below, so real compiles = compiles − hits.
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enabled() -> bool:
    """Is the ledger armed? Read at :func:`maybe_install` time, not per
    call — benches set ``KAKVEDA_LEDGER=1`` before building the objects
    under test."""
    return os.environ.get("KAKVEDA_LEDGER", "").strip().lower() in _TRUTHY


# ---------------------------------------------------------------------------
# process-global ledger state
# ---------------------------------------------------------------------------

# Guards the tables below. A raw lock ON PURPOSE (mirrors sanitize.py):
# the ledger must never show up in its own instrumentation.
_STATE_LOCK = threading.Lock()
# entry label -> number of XLA backend compiles attributed to it.
_COMPILES: Dict[str, int] = {}
# Compiles observed after mark_warm(): [{"fn", "t", "duration_ms"}].
_POST_WARMUP: List[dict] = []
# direction ("h2d"|"d2h") -> phase -> bytes.
_TRANSFERS: Dict[str, Dict[str, int]] = {}
# Programs served from the persistent compilation cache (ops/device.py
# places it) instead of being compiled.
_CACHE_HITS = 0
_WARM = False

_INSTALLED = False
_ORIG_JIT = None  # jax.jit before the labeling wrapper replaced it
# jax.monitoring has no unregister: the listener is registered ONCE per
# process and deafened via _INSTALLED; install/uninstall cycles (tests)
# must not stack duplicate registrations.
_LISTENER_REGISTERED = False

_TLS = threading.local()

_RECORDER = None  # lazy FlightRecorder("ledger")


def _recorder():
    global _RECORDER
    if _RECORDER is None:
        from kakveda_tpu.core import metrics as _metrics

        _RECORDER = _metrics.FlightRecorder("ledger")
    return _RECORDER


def _metric(name: str, help: str, labels):
    """Label-family get-or-create, lazy and failure-proof: the ledger
    records into its own tables regardless; the Prometheus mirror is
    best-effort (shapes are pre-declared in metrics._CORE_FAMILIES)."""
    try:
        from kakveda_tpu.core import metrics as _metrics

        return _metrics.get_registry().counter(name, help, labels)
    except Exception:
        return None


def _entry_stack() -> List[str]:
    st = getattr(_TLS, "entries", None)
    if st is None:
        st = _TLS.entries = []
    return st


def _phase_stack() -> List[str]:
    st = getattr(_TLS, "phases", None)
    if st is None:
        st = _TLS.phases = []
    return st


@contextlib.contextmanager
def entry(name: str):
    """Attribute any compile triggered inside the block to ``name``.
    Needed only for jits created BEFORE install (module-level jits in
    already-imported modules); jits created after install self-label."""
    st = _entry_stack()
    st.append(name)
    try:
        yield
    finally:
        st.pop()


@contextlib.contextmanager
def phase(name: str):
    """Attribute transfer bytes inside the block to request phase
    ``name`` (``warn``/``ingest``/``admit``/``decode``/…)."""
    st = _phase_stack()
    st.append(name)
    try:
        yield
    finally:
        st.pop()


# ---------------------------------------------------------------------------
# compile attribution
# ---------------------------------------------------------------------------


class _LabeledJit:
    """A jitted callable that pushes its label while running, so the
    monitoring listener can attribute the backend compile the first call
    (per shape signature) triggers. Pure delegation otherwise — lower/
    eval_shape/clear_cache etc. pass through untouched."""

    __slots__ = ("_jitted", "_label")

    def __init__(self, jitted, label: str):
        self._jitted = jitted
        self._label = label

    def __call__(self, *args, **kwargs):
        st = _entry_stack()
        st.append(self._label)
        try:
            return self._jitted(*args, **kwargs)
        finally:
            st.pop()

    def __get__(self, obj, objtype=None):  # decorated methods keep binding
        if obj is None:
            return self
        return functools.partial(self.__call__, obj)

    def __getattr__(self, item):
        return getattr(self._jitted, item)

    def __repr__(self):
        return f"<ledger-labeled jit {self._label!r}>"


def _patched_jit(fun=None, **kwargs):
    """Drop-in ``jax.jit``: same semantics, but the returned callable is
    wrapped with its function name for compile attribution. Handles both
    ``jax.jit(f, ...)`` and the kwargs-only decorator-factory form."""
    if fun is None:
        return functools.partial(_patched_jit, **kwargs)
    jitted = _ORIG_JIT(fun, **kwargs)
    label = getattr(fun, "__name__", None)
    if not label or label == "<lambda>":
        # A lambda has no useful name; leave it unwrapped so its compiles
        # attribute to the enclosing entry() (or the self-labeled caller).
        return jitted
    return _LabeledJit(jitted, label)


def _on_duration_event(event: str, duration: float, **kw) -> None:
    """jax.monitoring listener: count backend compiles by current entry."""
    if not _INSTALLED or not event.endswith(_COMPILE_EVENT_SUFFIX):
        return
    st = _entry_stack()
    label = st[-1] if st else "unattributed"
    with _STATE_LOCK:
        _COMPILES[label] = _COMPILES.get(label, 0) + 1
        warm = _WARM
        if warm:
            evt = {
                "fn": label,
                "t": round(time.time(), 6),
                "duration_ms": round(duration * 1000.0, 3),
            }
            _POST_WARMUP.append(evt)
    fam = _metric(
        "kakveda_compile_total",
        "XLA backend compiles attributed per jit entry point "
        "(KAKVEDA_LEDGER=1)", ("fn",),
    )
    if fam is not None:
        fam.labels(fn=label).inc()
    if warm:
        _recorder().record(
            "post_warmup_compile", fn=label,
            duration_ms=round(duration * 1000.0, 3),
        )


def _on_event(event: str, **kw) -> None:
    """jax.monitoring listener: count persistent-compile-cache hits."""
    global _CACHE_HITS
    if not _INSTALLED or event != _CACHE_HIT_EVENT:
        return
    with _STATE_LOCK:
        _CACHE_HITS += 1
    fam = _metric(
        "kakveda_compile_cache_hits_total",
        "Programs loaded from the persistent compilation cache instead of "
        "compiled (KAKVEDA_LEDGER=1)", (),
    )
    if fam is not None:
        fam.inc()


def maybe_install() -> bool:
    """Install the ledger if ``KAKVEDA_LEDGER=1`` and not yet installed.
    Idempotent; returns whether the ledger is installed after the call.
    Importing jax happens here, never at module import."""
    global _INSTALLED, _ORIG_JIT, _LISTENER_REGISTERED
    if _INSTALLED:
        return True
    if not enabled():
        return False
    import jax
    from jax import monitoring as _monitoring

    with _STATE_LOCK:
        if _INSTALLED:
            return True
        if jax.jit is not _patched_jit:
            if _ORIG_JIT is None:
                _ORIG_JIT = jax.jit
            jax.jit = _patched_jit
        if not _LISTENER_REGISTERED:
            _monitoring.register_event_duration_secs_listener(_on_duration_event)
            _monitoring.register_event_listener(_on_event)
            _LISTENER_REGISTERED = True
        _INSTALLED = True
    return True


def uninstall() -> None:
    """Restore ``jax.jit`` and deafen the listener (it stays registered —
    jax.monitoring has no unregister — but no-ops while not installed).
    Jitted callables created while installed keep working; they just
    stop attributing. Test hygiene, not a production path."""
    global _INSTALLED
    with _STATE_LOCK:
        if _ORIG_JIT is not None:
            import jax

            jax.jit = _ORIG_JIT
            # _ORIG_JIT itself is kept: a partial(jax.jit, …) captured
            # while installed still routes through _patched_jit and must
            # keep resolving the real jit.
        _INSTALLED = False


def installed() -> bool:
    return _INSTALLED


# ---------------------------------------------------------------------------
# transfer accounting
# ---------------------------------------------------------------------------


def note_transfer(direction: str, nbytes: int) -> None:
    """Record ``nbytes`` moving ``h2d`` or ``d2h`` under the current
    phase. Callers invoke this unconditionally at the module seams; when
    the ledger is not installed it is one attribute check."""
    if not _INSTALLED or nbytes <= 0:
        return
    st = _phase_stack()
    ph = st[-1] if st else "unphased"
    with _STATE_LOCK:
        by_phase = _TRANSFERS.setdefault(direction, {})
        by_phase[ph] = by_phase.get(ph, 0) + int(nbytes)
    fam = _metric(
        "kakveda_transfer_bytes",
        "Host<->device transfer bytes by direction and request phase "
        "(KAKVEDA_LEDGER=1)", ("direction", "phase"),
    )
    if fam is not None:
        fam.labels(direction=direction, phase=ph).inc(int(nbytes))


def mark_warm() -> None:
    """Draw the warmup line: every compile from here on is recorded as a
    ``post_warmup_compile`` flight-recorder event and counted in the
    report (bench rows assert on that count)."""
    global _WARM
    with _STATE_LOCK:
        _WARM = True


def ledger_report() -> dict:
    """Snapshot of everything the ledger has seen (deep-copied)."""
    with _STATE_LOCK:
        compiles = dict(_COMPILES)
        post = [dict(e) for e in _POST_WARMUP]
        transfers = {d: dict(p) for d, p in _TRANSFERS.items()}
        warm = _WARM
        cache_hits = _CACHE_HITS
    return {
        "enabled": enabled(),
        "installed": _INSTALLED,
        "warm": warm,
        "compiles": compiles,
        "compile_total": sum(compiles.values()),
        "cache_hits": cache_hits,
        "post_warmup_compiles": len(post),
        "post_warmup": post,
        "transfer_bytes": {
            d: sum(p.values()) for d, p in transfers.items()
        },
        "transfer_by_phase": transfers,
    }


def reset() -> None:
    """Zero the tables and the warm flag (install state is kept)."""
    global _WARM, _CACHE_HITS
    with _STATE_LOCK:
        _COMPILES.clear()
        _POST_WARMUP.clear()
        _TRANSFERS.clear()
        _CACHE_HITS = 0
        _WARM = False
    global _RECORDER
    _RECORDER = None
