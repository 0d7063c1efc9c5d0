"""Host phases on two clocks: the profiler's timeline and ``/metrics``.

One primitive names what the host is doing in the two served loops (the
``/warn`` batch cycle, the serving engine's iteration):

- ``annotate(name)`` — a context manager around SYNC work. The block is a
  ``jax.profiler.TraceAnnotation`` (so it sits on the profiler's clock next
  to the device's operations, and ``benchmarks/harness/xplane.idle_gaps``
  names the device's idle gaps by it) AND one observation of
  ``kakveda_host_phase_seconds{phase=<name>}``. Never hold one across an
  ``await``: other coroutines run on the thread in between.
- ``observe_phase(name, seconds)`` — the histogram alone, for intervals
  that contain an ``await``, cross threads, or happen once per request.

A phase of a loop that lasts over ``STALL_S`` also adds its seconds to
``kakveda_host_stall_seconds_total{loop=warn|serve}`` and leaves one
``stall {phase, ms}`` event in the ``host/phases`` flight recorder. Stalls
spread over the phases in proportion to their time are the machine's
(a frozen host freezes whatever runs); stalls in one phase are the
program's. Exempt (``_NO_STALL``): phases that wait by design — for
arrivals, or for the device's decode chunk (chunk_steps x the model's
step: over the limit for a 7B model, whatever the host does) — the two
cycle phases that contain them (and every other phase, so counting them
would count a stall twice), and the per-request ``warn.http`` and
``warn.batcher.wake`` (concurrent requests would each count the same
freeze).
Names outside the two loops (``gfkb.insert``, ``llama.generate``, …) are
histogram + annotation only.

Phase vocabulary: docs/observability.md § Phases.

``profile(logdir)`` captures a trace of the enclosed block;
``KAKVEDA_PROFILE_DIR`` makes the server capture one warm ``/warn`` at
start-up (``service/main.py``).
"""

from __future__ import annotations

import contextlib
import logging
import os
from time import perf_counter
from typing import Iterator, Optional

from kakveda_tpu.core import metrics as _metrics

log = logging.getLogger("kakveda.profiling")

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # noqa: BLE001 — no profiler: phases still reach /metrics

    class _TraceAnnotation:  # type: ignore[no-redef]
        __slots__ = ()

        def __init__(self, name: str):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None


# PERF.md's own "over 100 ms": no phase of either loop takes a tenth of
# that when the host runs.
STALL_S = 0.1
_NO_STALL = frozenset({
    "warn.cycle", "warn.batcher.collect", "warn.http", "warn.batcher.wake",
    "serve.cycle", "serve.wait", "serve.chunk.fetch",
})

_REG = _metrics.get_registry()
_PHASE_HIST = _REG.histogram(
    "kakveda_host_phase_seconds",
    "Host wall of one named phase of the /warn batch cycle or the serving "
    "engine loop (profiling.annotate / observe_phase; the phase label is "
    "the TraceAnnotation's name)", ("phase",),
)
_STALL_TOTAL = _REG.counter(
    "kakveda_host_stall_seconds_total",
    "Seconds spent in loop phases that each lasted over 0.1 s (phases that "
    "wait by design, for arrivals or for the device's chunk, are exempt)",
    ("loop",),
)
# Both children exist from import, so a loop that never stalled reads 0 on
# a scrape rather than nothing.
_STALL_BY_LOOP = {
    "warn": _STALL_TOTAL.labels(loop="warn"),
    "serve": _STALL_TOTAL.labels(loop="serve"),
}
_RECORDER = _metrics.FlightRecorder("host/phases")


class _Phase:
    """What a name resolves to, once: its histogram child and, for a phase
    that can stall a loop, that loop's counter child."""

    __slots__ = ("name", "hist", "stall")

    def __init__(self, name: str):
        self.name = name
        self.hist = _PHASE_HIST.labels(phase=name)
        loop = None
        if name not in _NO_STALL:
            if name.startswith("serve."):
                loop = "serve"
            elif name.startswith(("warn.", "gfkb.match.")):
                loop = "warn"
        self.stall = _STALL_BY_LOOP.get(loop)

    def observe(self, seconds: float) -> None:
        self.hist.observe(seconds)
        if seconds > STALL_S and self.stall is not None:
            self.stall.inc(seconds)
            _RECORDER.record("stall", phase=self.name, ms=round(seconds * 1e3, 3))


class _Phases(dict):
    """name -> _Phase, resolved on first use and kept."""

    def __missing__(self, name: str) -> _Phase:
        # Two threads racing on a new name build equal objects over the
        # same registry children; the last one kept is as good as the first.
        ph = self[name] = _Phase(name)
        return ph


_PHASES = _Phases()


def observe_phase(name: str, seconds: float) -> None:
    """One observation of phase ``name``, timed by the caller."""
    _PHASES[name].observe(seconds)


class annotate:
    """``with annotate(name):`` — the block on the profiler's timeline and
    in ``kakveda_host_phase_seconds{phase=name}``. The block's own
    exception propagates unchanged (``__exit__`` returns False and nothing
    here catches it)."""

    __slots__ = ("_ph", "_ann", "_t0")

    def __init__(self, name: str):
        self._ph = _PHASES[name]

    def __enter__(self) -> None:
        ann = _TraceAnnotation(self._ph.name)
        ann.__enter__()
        self._ann = ann
        self._t0 = perf_counter()

    def __exit__(self, exc_type, exc, tb) -> bool:
        seconds = perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        self._ph.observe(seconds)
        return False


@contextlib.contextmanager
def profile(logdir: str | os.PathLike) -> Iterator[None]:
    """Capture a device trace of the enclosed block into ``logdir``."""
    try:
        import jax.profiler

        jax.profiler.start_trace(str(logdir))
        started = True
    except Exception as e:  # noqa: BLE001
        log.warning("profiler unavailable: %s", e)
        started = False
    try:
        yield
    finally:
        if started:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
                log.info("device trace written to %s", logdir)
            except Exception as e:  # noqa: BLE001
                log.warning("profiler stop failed: %s", e)


def startup_profile_dir() -> Optional[str]:
    return os.environ.get("KAKVEDA_PROFILE_DIR") or None
