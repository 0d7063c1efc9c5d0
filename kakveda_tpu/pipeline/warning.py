"""Pre-flight warning policy — 'has something like this failed before?'

Parity with the reference's warning service
(reference: services/warning_policy/app.py:19-72): build the signature text,
match against the GFKB, compare the best score to the config threshold
(default 0.8), attach a pattern id when a known pattern covers the matched
failure type, and answer block|warn|silent with a confidence score.

Unlike the reference — which pays an HTTP hop to GFKB plus a full TF-IDF
refit per request — this policy calls the device index in-process; the match
is a warm compiled matmul+top-k, and ``warn_batch`` amortizes many
concurrent pre-flight checks into one device call (the <10 ms p50 path).
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Sequence

from kakveda_tpu.core import metrics as _metrics
from kakveda_tpu.core import profiling
from kakveda_tpu.core.config import ConfigStore
from kakveda_tpu.core.fingerprint import signature_text
from kakveda_tpu.core.schemas import WarningRequest, WarningResponse
from kakveda_tpu.index.gfkb import GFKB
from kakveda_tpu.pipeline.classifier import HALLUCINATION_CITATION

# The demo pattern the reference's policy knows how to attach
# (reference: services/warning_policy/app.py:40-48).
_CITATION_PATTERN_NAME = "Citation hallucination without sources"

log = logging.getLogger("kakveda.warning")


class WarningPolicy:
    def __init__(self, gfkb: GFKB, config: Optional[ConfigStore] = None):
        self.gfkb = gfkb
        self.config = config or ConfigStore()
        reg = _metrics.get_registry()
        self._m_batch = reg.histogram(
            "kakveda_warn_batch_seconds",
            "Match wall per warn batch: signatures, featurize, dispatch, "
            "fetch and match assembly (not the device scan alone)",
        )
        self._m_verdicts = reg.counter(
            "kakveda_warn_requests_total",
            "Pre-flight warn verdicts by action", ("action",),
        )

    def warn(self, req: WarningRequest) -> WarningResponse:
        return self.warn_batch([req])[0]

    def warn_batch(self, reqs: Sequence[WarningRequest]) -> List[WarningResponse]:
        t0 = time.perf_counter()
        with profiling.annotate("warn.signature"):
            sigs = [signature_text(r.prompt, r.tools, r.env) for r in reqs]
        # Device-loss degraded mode (core/admission.py): while the backend
        # is latched DEGRADED we never even dispatch (a wedged chip hangs,
        # it doesn't error) — the GFKB's host-warm/disk-cold tiers answer
        # instead (index/tiers.py, `match_batch_fallback`), flagged
        # `degraded=true`. A fresh LOSS of the device here latches the
        # mode and takes the same fallback, so the request that DISCOVERS
        # the outage still gets a verdict. Any other device-side failure
        # (a kernel Mosaic refuses, an out-of-memory) is raised: the chip
        # is there, and a host answer would hide that the program on it
        # is broken.
        from kakveda_tpu.core import admission as _admission

        health = _admission.get_device_health()
        degraded = False
        if health.degraded:
            all_matches, tier_info = self.gfkb.match_batch_fallback(sigs)
            degraded = True
        else:
            try:
                all_matches, tier_info = self.gfkb.match_batch_info(sigs)
            except Exception as e:  # noqa: BLE001 — classify, maybe degrade
                if not health.note_failure(e, where="gfkb.match"):
                    log.error(
                        "gfkb.match failed on the device and is NOT served "
                        "from the host tiers (%s: %s)", type(e).__name__, e,
                    )
                    raise
                all_matches, tier_info = self.gfkb.match_batch_fallback(sigs)
                degraded = True
        self._m_batch.observe(time.perf_counter() - t0)
        with profiling.annotate("warn.patterns"):
            # The one pattern id a verdict can carry, read from the live
            # state every batch: a pattern upserted since the last batch is
            # seen by this one.
            citation_pattern_id = self.gfkb.pattern_id(_CITATION_PATTERN_NAME)
        with profiling.annotate("warn.policy"):
            # One read of the config: its stat for hot reload is a syscall,
            # so it belongs under the phase that uses what it returns.
            threshold, default_action = self.config.verdict_inputs()
            out: List[WarningResponse] = []
            for matches in all_matches:
                best = matches[0] if matches else None
                score = best.score if best else 0.0

                pattern_id = (
                    citation_pattern_id
                    if best and best.failure_type == HALLUCINATION_CITATION
                    else None
                )

                if best and score >= threshold:
                    out.append(
                        WarningResponse(
                            action=default_action,
                            confidence=score,
                            pattern_id=pattern_id,
                            references=[best],
                            message=(
                                f"This execution matches past failure type {best.failure_type} "
                                f"(failure_id={best.failure_id}, similarity={score:.2f}). "
                                f"Suggested mitigation: {best.suggested_mitigation or 'n/a'}"
                            ),
                            degraded=degraded,
                            tier=tier_info.get("tier"),
                            nprobe=tier_info.get("nprobe"),
                        )
                    )
                else:
                    out.append(
                        WarningResponse(
                            action="silent" if default_action == "silent" else "warn",
                            confidence=score,
                            pattern_id=pattern_id,
                            references=[],
                            message="No high-similarity match found in GFKB.",
                            degraded=degraded,
                            tier=tier_info.get("tier"),
                            nprobe=tier_info.get("nprobe"),
                        )
                    )
            for r in out:
                self._m_verdicts.labels(action=r.action).inc()
        return out
