"""Global Failure Knowledge Base — the framework's center of gravity.

Capability parity with the reference GFKB service
(reference: services/gfkb/app.py:23-198): append-only JSONL persistence with
versioning-by-append, ``F-%04d``/``FP-%04d`` id minting, top-k similarity
match, and pattern upsert with identity-by-name. Re-designed TPU-first:

  * every canonical failure's ``signature_text`` is embedded once at upsert
    time (hashed n-grams, kakveda_tpu.ops.featurizer) and lives in an
    HBM-resident [capacity, dim] matrix sharded over the mesh's ``data``
    axis — instead of the reference's read-the-whole-file + TF-IDF-refit per
    match request (reference: services/gfkb/app.py:54-56,81-89);
  * a match is one compiled matmul + sharded top-k (kakveda_tpu.ops.knn),
    batched across concurrent queries;
  * the index is fully replayable from ``failures.jsonl`` (checkpoint =
    the append log, mirroring the reference's durability-by-append design).

Deliberate deviations from the reference, both documented here:
  * id minting counts *canonical* failures, not JSONL rows — the reference
    mints ``F-{len(rows)+1}`` so version appends create id gaps
    (reference: services/gfkb/app.py:117); here ids are dense.
  * the reference applies the ``failure_type`` filter *after* truncating to
    top-5 so a type-filtered query can return fewer (or zero) matches even
    when matching failures exist (reference: services/gfkb/app.py:89-91).
    ``type_filter="post"`` (default) preserves that observable behavior;
    ``type_filter="pre"`` fixes it with a device-side pre-selection mask
    (per-slot type ids AND-ed into the valid mask before top-k).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import OrderedDict, deque
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh

from kakveda_tpu import native
from kakveda_tpu.core import faults as _faults
from kakveda_tpu.core import ledger as _ledger
from kakveda_tpu.core import metrics as _metrics
from kakveda_tpu.core import profiling

log = logging.getLogger("kakveda.gfkb")
from kakveda_tpu.core.schemas import (
    CanonicalFailureRecord,
    FailureMatch,
    PatternEntity,
    Severity,
    utcnow,
)
from kakveda_tpu.index.tiers import TierConfig, TieredIndex
from kakveda_tpu.ops.featurizer import HashedNGramFeaturizer, dense_rows_to_sparse
from kakveda_tpu.ops.knn import ShardedKnn, batch_bucket
from kakveda_tpu.parallel.mesh import create_mesh
from kakveda_tpu.core import sanitize


class SnapshotError(RuntimeError):
    """Snapshot unavailable or aborted (persist=False, concurrent reload) —
    a caller-side condition, distinct from device/runtime failures."""


class HostFallbackDisabled(RuntimeError):
    """Degraded-mode matching requested but the host tiers are disabled
    (KAKVEDA_HOST_FALLBACK=0) — a configuration condition, typed so the
    warn path never confuses it with a device failure."""


def _iso(ts: str):
    """Parse our own model_dump_json timestamps. Pydantic writes tz-aware
    UTC as '…Z', which datetime.fromisoformat only learned in Python 3.11
    — on 3.10 the bare call raised and the blanket corruption-fallback in
    _restore_snapshot silently degraded EVERY restore to a full log
    replay (the snapshot fast path never actually ran)."""
    from datetime import datetime

    if ts.endswith("Z"):
        ts = ts[:-1] + "+00:00"
    return datetime.fromisoformat(ts)


def _record_from_snapshot(obj: dict) -> dict:
    """Snapshot rows are our own model_dump_json output: re-hydrate the two
    non-JSON-native field types for model_construct (which skips the
    validators that would otherwise do this)."""
    obj["created_at"] = _iso(obj["created_at"])
    obj["updated_at"] = _iso(obj["updated_at"])
    obj["impact_severity"] = Severity(obj["impact_severity"])
    return obj


class GFKB:
    """Failure + pattern store with a device-resident similarity index."""

    def __init__(
        self,
        data_dir: str | Path = "data",
        mesh: Optional[Mesh] = None,
        capacity: int = 1 << 14,
        dim: int = 2048,
        top_k: int = 5,
        featurizer: Optional[HashedNGramFeaturizer] = None,
        persist: bool = True,
        tier_config: Optional[TierConfig] = None,
    ):
        self.data_dir = Path(data_dir)
        self.persist = persist
        if persist:
            self.data_dir.mkdir(parents=True, exist_ok=True)
        self.failures_path = self.data_dir / "failures.jsonl"
        self.patterns_path = self.data_dir / "patterns.jsonl"
        # Replication idempotency (fleet ingest fan-in, docs/scale-out.md):
        # bus events applied through upsert_failures_batch(event_id=…) are
        # dedup'd on this set, persisted as their own append log so DLQ
        # replay and at-least-once redelivery stay dedup-safe ACROSS
        # restarts. Bounded (KAKVEDA_GFKB_APPLIED_MAX, FIFO eviction) —
        # far larger than any plausible redelivery window.
        self.applied_path = self.data_dir / "applied_events.jsonl"
        self._applied_events: "OrderedDict[str, bool]" = OrderedDict()
        self._applied_max = int(os.environ.get("KAKVEDA_GFKB_APPLIED_MAX", "65536"))
        # Lifecycle side-log (docs/robustness.md § failure-memory
        # lifecycle): row aging and duplicate collapse append
        # {"op": "tomb"|"live", "id", "reason", "ts"} lines here instead
        # of touching the record schema — a KAKVEDA_GFKB_COMPACT=0 store
        # stays byte-identical to the pre-lifecycle format. Tombstoned
        # slots keep their records, ids and (type, signature) keys: slot
        # stability is load-bearing for dense id minting, replay
        # latest-wins and replication cursors. They are filtered out of
        # every match assembly host-side, zeroed on device (so they never
        # consume top-k candidates), fence replicated re-inserts (2xx
        # drop), and resurrect in place on an ORGANIC upsert.
        self.tombstones_path = self.data_dir / "tombstones.jsonl"
        self._tombstoned: Dict[int, str] = {}  # slot -> reason
        # Compaction posture: generation/ts live in the snapshot manifest's
        # "compact" section; the age auto-trigger counts from process start
        # when the store has never compacted.
        self._opened_ts = time.time()
        self._last_compact_ts = 0.0
        self._compact_generation = 0
        self._compact_inflight = False
        self._compact_bytes = int(os.environ.get("KAKVEDA_GFKB_COMPACT_BYTES", "0"))
        self._compact_age_s = float(os.environ.get("KAKVEDA_GFKB_COMPACT_AGE_S", "0"))

        self.mesh = mesh if mesh is not None else create_mesh("data:-1")
        self.featurizer = featurizer or HashedNGramFeaturizer(dim=dim)
        self.top_k = top_k
        self._knn = ShardedKnn(self.mesh, capacity, dim, k=top_k)
        self._emb, self._valid = self._knn.alloc()
        # Per-slot failure-type ids (device int32 side-table) for the
        # device-side type pre-filter; host mapping type name -> id.
        self._types = self._knn.alloc_i32()
        self._type_ids: Dict[str, int] = {}

        # Host-side metadata: one entry per canonical failure, slot-aligned.
        self._records: List[CanonicalFailureRecord] = []
        self._slot_by_key: Dict[Tuple[str, str], int] = {}
        self._slot_by_id: Dict[str, int] = {}
        # Pattern store: set-backed mutable state per name. The log is
        # DELTA-append — each line carries only the failure_ids/apps new in
        # that upsert, and replay unions lines — because re-appending the
        # full membership per upsert (the reference's model,
        # services/gfkb/app.py:168-198) makes both the log and the per-batch
        # serialize cost O(N²) over a failure stream. Full-record lines from
        # older logs replay identically (union of growing prefixes).
        self._pattern_state: Dict[str, dict] = {}  # name -> mutable state
        # Reentrant: compact() snapshots and swaps the log under ONE
        # critical section (a snapshot racing in between would pin a log
        # offset the swap is about to invalidate).
        self._snapshot_write_lock = sanitize.named_lock(
            "GFKB._snapshot_write_lock", kind="rlock"
        )
        # Bumped by reload(); snapshot() aborts if it changed mid-write so a
        # purge (external log rewrite + reload) can't race a snapshot into
        # resurrecting pre-purge records.
        self._generation = 0
        # Per-type aggregates maintained incrementally at upsert so pattern
        # detection reads them O(1) instead of rescanning every record per
        # batch (O(N²) over a failure stream).
        self._ids_by_type: Dict[str, List[str]] = {}
        self._apps_by_type: Dict[str, set] = {}
        self._lock = sanitize.named_lock("GFKB._lock")
        # Upserts append records under the lock but embed AFTER releasing it
        # (_embed_new_slots). Consumers of (records, embeddings) pairs —
        # snapshot(), records_and_embeddings() — must not observe appended
        # records whose rows are still zero: they drain this in-flight
        # counter first (snapshots would otherwise persist zero vectors
        # permanently, since restore never re-embeds).
        self._pending_embeds = 0
        self._embeds_cv = threading.Condition(self._lock)
        # Group-commit append logs (C++ writer when available): records are
        # buffered and flushed after each upsert batch instead of paying an
        # open+write+close per record (the reference's pattern,
        # services/gfkb/app.py:49-51).
        self._logs: Dict[Path, "native.AppendLog"] = {}
        # Crash-safe replay: a torn FINAL line (a crash mid-append) is
        # tolerated at startup — replay warns, remembers the offset here,
        # and the next append truncates the file back to it before
        # writing, so the torn bytes never corrupt a later record.
        # Mid-file corruption still raises (that is data loss, not a torn
        # tail, and must not be silently truncated away).
        self._truncate_pending: Dict[Path, int] = {}
        # Chaos-harness sites (core/faults.py), resolved once.
        self._fault_append = _faults.site("gfkb.append")
        self._fault_snapshot = _faults.site("gfkb.snapshot")
        self._fault_mine = _faults.site("gfkb.mine_state")
        # Durable-write seams of the compaction fence + the tombstone
        # append — the crash-point sweep (index/crashsweep.py) arms these
        # one at a time and certifies recovery at every kill offset.
        self._fault_compact_delta = _faults.site("gfkb.compact_delta")
        self._fault_compact_fence = _faults.site("gfkb.compact_fence")
        self._fault_compact_swap = _faults.site("gfkb.compact_swap")
        self._fault_tombstone = _faults.site("gfkb.tombstone")
        # Device-loss drill site, SHARED with the device-health probe
        # (core/admission.py): armed, every match dispatch fails exactly
        # like a wedged backend — and the probe keeps failing until it is
        # disarmed, which is what un-latches degraded mode.
        self._fault_device = _faults.site("device.unavailable")

        # Tiered storage hierarchy (index/tiers.py): the host-warm tier
        # mirrors every row's sparse (idx, val) embedding slot-aligned —
        # degraded-mode matching, overflow past the device hot-row budget
        # and snapshot restore ALL serve through it (one abstraction, not
        # the PR-5 parallel mirror) — and the disk-cold tier pages rows
        # past the warm budget in from memmap shards on demand. Routing
        # is IVF-style over coarse centroids maintained per ingest batch.
        # KAKVEDA_HOST_FALLBACK=0 opts out of the host tiers entirely (no
        # mirror, no fallback, no hot cap — degraded warn then errors).
        self._host_fallback = os.environ.get("KAKVEDA_HOST_FALLBACK", "1") != "0"
        self._tiers: Optional[TieredIndex] = None
        if self._host_fallback:
            self._tiers = TieredIndex(
                self.featurizer.dim,
                tier_config or TierConfig(),
                self.data_dir if persist else None,
            )
        self._m_warn_fallback = _metrics.get_registry().counter(
            "kakveda_warn_fallback_total",
            "Warn verdicts served by the host-side fallback index while "
            "the backend is degraded",
        )
        _rep = _metrics.get_registry().counter(
            "kakveda_gfkb_replicate_apply_total",
            "Bus-replicated ingest events applied to this GFKB by outcome "
            "(applied|dedup; fenced counts individual tombstoned ROWS "
            "dropped by the lifecycle fence)", ("outcome",),
        )
        self._m_rep_applied = _rep.labels(outcome="applied")
        self._m_rep_dedup = _rep.labels(outcome="dedup")
        self._m_rep_fenced = _rep.labels(outcome="fenced")
        # Lifecycle metrics — children resolved here, BEFORE _replay():
        # the startup applied-log compaction already counts into the
        # shared kakveda_gfkb_compact_total family.
        _reg0 = _metrics.get_registry()
        _cmp = _reg0.counter(
            "kakveda_gfkb_compact_total",
            "Durable-log compactions by store and outcome (ok|skipped|"
            "error|stale_tmp; stale_tmp = leftover temp file from a "
            "crashed rewrite, removed before the next attempt)",
            ("store", "outcome"),
        )
        self._m_compact = {
            (st, oc): _cmp.labels(store=st, outcome=oc)
            for st in ("failures", "applied", "tombstones")
            for oc in ("ok", "skipped", "error", "stale_tmp")
        }
        _tmb = _reg0.counter(
            "kakveda_gfkb_tombstone_total",
            "Row lifecycle transitions by reason (aged = TTL demotion, "
            "collapsed = near-duplicate fold, resurrected = organic "
            "re-upsert of a tombstoned signature)",
            ("reason",),
        )
        self._m_tombstone = {
            r: _tmb.labels(reason=r) for r in ("aged", "collapsed", "resurrected")
        }
        self._g_tombstoned = _reg0.gauge(
            "kakveda_gfkb_tombstoned_rows",
            "Currently tombstoned (resident but never matched) GFKB rows",
        )

        # Incremental mining state (KAKVEDA_MINE_INCREMENTAL=0 restores
        # the full-sweep-only behavior bit-for-bit: no state, no cache, no
        # extra device dispatches). The union-find + aggregates live on
        # host; each ingest batch gets ONE delta top-k dispatch against
        # the resident index (ops/incremental.py) whose packed result is
        # drained lazily — or zero dispatches when a recent warn match for
        # the same signature already fetched the neighbors.
        self._mine_enabled = os.environ.get("KAKVEDA_MINE_INCREMENTAL", "1") != "0"
        self._mine = None
        # pending delta results: (knn, slots np.int32, packed, generation)
        self._mine_pending: deque = deque()
        self._mine_pending_max = int(os.environ.get("KAKVEDA_MINE_PENDING_MAX", "256"))
        # signature_text -> (scores, slots, generation): the warn path's
        # already-fetched neighbors, reused for free attachment at ingest.
        self._match_cache: "OrderedDict[str, tuple]" = OrderedDict()
        self._match_cache_max = int(os.environ.get("KAKVEDA_MINE_MATCH_CACHE", "4096"))
        self.mine_delta_dispatches = 0  # observability + reuse tests
        self._mine_merges_seen = 0
        if self._mine_enabled:
            from kakveda_tpu.ops.incremental import ClusterState

            self._mine = ClusterState(
                threshold=float(os.environ.get("KAKVEDA_MINE_THRESHOLD", "0.6")),
                k=int(os.environ.get("KAKVEDA_MINE_K", "32")),
            )
        reg = _metrics.get_registry()
        self._m_mine_update = reg.histogram(
            "kakveda_mine_update_seconds",
            "Incremental cluster-state update wall per drained delta batch",
        )
        self._m_mine_clusters = reg.gauge(
            "kakveda_mine_clusters",
            "Live clusters in the incremental mining state",
        )
        _attach = reg.counter(
            "kakveda_mine_attach_total",
            "Rows attached to the incremental cluster state by neighbor source",
            ("source",),
        )
        self._m_mine_attach = {
            s: _attach.labels(source=s) for s in ("delta", "reused", "tier")
        }
        self._m_mine_merges = reg.counter(
            "kakveda_mine_merges_total",
            "Cluster merges performed by incremental attachment",
        )
        # Published immutable view for lock-free matching: a tuple swap is
        # atomic under the GIL, so match_batch never takes the data lock —
        # see match_batch for the consistency argument.
        self._view = (self._knn, self._emb, self._valid, self._types, self._records)

        if persist:
            self._replay()
        self._mine_after_replay()
        self._publish()

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------

    def _append_jsonl(self, path: Path, obj: dict) -> None:
        """Buffer one record; callers group-commit with :meth:`_flush_logs`
        at the end of each public mutation (read-your-writes for external
        readers of the JSONL files, one syscall per batch instead of an
        open+write+close per record)."""
        self._append_line(path, json.dumps(obj, ensure_ascii=False))

    def _append_line(self, path: Path, line: str) -> None:
        """Raw pre-serialized variant: the streaming path serializes with
        pydantic's C serializer (model_dump_json) and skips the Python json
        encoder entirely."""
        if not self.persist:
            return
        self._fault_append.fire()
        pend = self._truncate_pending.pop(path, None)
        if pend is not None:
            # First append since a torn tail was tolerated at replay:
            # truncate the file back to the last complete record before
            # anything new lands after the torn bytes.
            lg = self._logs.pop(path, None)
            if lg is not None:
                lg.close()
            try:
                os.truncate(path, pend)
                log.warning("truncated torn tail of %s to %d bytes", path, pend)
            except OSError as e:
                log.error("could not truncate torn tail of %s: %s", path, e)
                self._truncate_pending[path] = pend
                raise
        alog = self._logs.get(path)
        if alog is None:
            alog = self._logs[path] = native.AppendLog(path)
        alog.append((line + "\n").encode("utf-8"))

    def _flush_logs(self) -> None:
        for log in self._logs.values():
            log.flush()

    def close(self) -> None:
        """Flush and close the append logs (safe to call repeatedly)."""
        with self._lock:
            self._close_locked()

    def _close_locked(self) -> None:
        """Caller holds ``_lock`` (reload() closes mid-rebuild while
        already inside its locked section)."""
        for log in self._logs.values():
            log.close()
        self._logs.clear()

    def _iter_log_lines(self, path: Path, offset: int, parse):
        """Yield ``parse(line)`` for each JSONL line of ``path`` from byte
        ``offset``, tolerating exactly one torn FINAL line: a record that
        fails to decode/parse with nothing but whitespace after it is a
        crash mid-append — warn, schedule truncate-on-next-append
        (``_truncate_pending``) and stop. A bad record with more data
        after it is mid-file corruption and raises."""
        with path.open("rb") as f:
            if offset:
                f.seek(offset)
            pos = f.tell()
            for raw in f:
                line_start = pos
                pos += len(raw)
                try:
                    text = raw.decode("utf-8").strip()
                    if not text:
                        continue
                    parsed = parse(text)
                except Exception as e:  # noqa: BLE001 — decode OR parse failure
                    rest = f.read()
                    if rest.strip():
                        raise ValueError(
                            f"corrupt record mid-file in {path} at byte "
                            f"{line_start} ({type(e).__name__}: {e}); refusing "
                            "to replay past it"
                        ) from e
                    log.warning(
                        "tolerating torn final line of %s at byte %d (%s); "
                        "will truncate on next append",
                        path, line_start, type(e).__name__,
                    )
                    self._truncate_pending[path] = line_start
                    return
                yield parsed

    def _replay(self) -> None:
        """Rebuild host metadata + device index from the append logs,
        fast-forwarding through a snapshot when one is valid (startup at
        1M rows is dominated by re-embedding + re-parsing otherwise).
        Both logs tolerate one torn final line (see _iter_log_lines)."""
        if self.failures_path.exists():
            tail_offset = self._restore_snapshot()
            latest: Dict[Tuple[str, str], CanonicalFailureRecord] = {}
            order: List[Tuple[str, str]] = []
            for rec in self._iter_log_lines(
                self.failures_path, tail_offset,
                lambda t: CanonicalFailureRecord.model_validate(json.loads(t)),
            ):
                key = (rec.failure_type, rec.signature_text)
                if key in self._slot_by_key:  # snapshot row updated in tail
                    self._records[self._slot_by_key[key]] = rec
                    self._apps_by_type.setdefault(rec.failure_type, set()).update(
                        rec.affected_apps
                    )
                    if self._mine is not None:
                        # Membership is unchanged by a version update, but
                        # the cluster's app span may have widened.
                        self._mine.note_apps(
                            self._slot_by_key[key], rec.affected_apps
                        )
                    continue
                if key not in latest:
                    order.append(key)
                latest[key] = rec
            if order:
                base = len(self._records)
                self._records.extend(latest[k] for k in order)
                for i, k in enumerate(order):
                    self._slot_by_key[k] = base + i
                    self._slot_by_id[latest[k].failure_id] = base + i
                for k in order:
                    rec = latest[k]
                    self._ids_by_type.setdefault(rec.failure_type, []).append(rec.failure_id)
                    self._apps_by_type.setdefault(rec.failure_type, set()).update(
                        rec.affected_apps
                    )
                self._ensure_capacity(len(self._records))
                tids = np.asarray(
                    [self._type_id(latest[k].failure_type) for k in order], np.int32
                )
                self._insert_texts_chunked(
                    [latest[k].signature_text for k in order],
                    np.arange(base, base + len(order), dtype=np.int32),
                    tids,
                )

        if self.patterns_path.exists():
            for p in self._iter_log_lines(
                self.patterns_path, 0,
                lambda t: PatternEntity.model_validate(json.loads(t)),
            ):
                self._merge_pattern_line(p)

        if self.applied_path.exists():
            # Replication dedup set: replayed whole (the log is ids only,
            # ~40 bytes/event) with the same torn-tail tolerance. A torn
            # final id means that event's rows may replay once more — an
            # occurrence bump, never a duplicate record (upserts key on
            # (failure_type, signature_text)).
            n_lines = 0
            for rec in self._iter_log_lines(self.applied_path, 0, json.loads):
                n_lines += 1
                eid = rec.get("id") if isinstance(rec, dict) else None
                if isinstance(eid, str):
                    self._applied_note_locked(eid)
            self._compact_applied_log(n_lines)

        if self.tombstones_path.exists():
            # Lifecycle side-log: net tombstone state replays from byte 0
            # (tiny — one op line per transition; compact() rewrites it to
            # net state). Unknown ids skip-with-warning — the failures log
            # can be independently rewritten (purge) or truncated.
            for rec in self._iter_log_lines(self.tombstones_path, 0, json.loads):
                if not isinstance(rec, dict):
                    log.warning("non-object tombstone line skipped")
                    continue
                fid = rec.get("id")
                slot = self._slot_by_id.get(fid) if isinstance(fid, str) else None
                if slot is None:
                    log.warning("tombstone line for unknown id %r skipped", fid)
                    continue
                if rec.get("op") == "tomb":
                    self._tombstoned[slot] = str(rec.get("reason", "aged"))
                else:
                    self._tombstoned.pop(slot, None)
            if self._tombstoned:
                # The replay above re-embedded every row; re-zero the
                # tombstoned ones so they never consume top-k candidates.
                self._zero_device_rows_locked(sorted(self._tombstoned))
            self._g_tombstoned.set(len(self._tombstoned))

    def _compact_applied_log(self, n_lines: int) -> None:
        """Rewrite ``applied_events.jsonl`` to the retained dedup tail.

        The in-memory set is bounded (KAKVEDA_GFKB_APPLIED_MAX, FIFO) but
        the on-disk log only ever appended — a long-lived replica replayed
        an unbounded file every restart just to discard most of it here.
        Startup is the one safe moment to rewrite (single-threaded, no
        append handle open yet); the swap is write-tmp + atomic replace so
        a crash mid-compaction leaves the old log intact. Ids evicted from
        the bounded set were unreplayable as dedup evidence anyway — their
        events re-apply as occurrence bumps, the documented FIFO contract.
        ``KAKVEDA_GFKB_APPLIED_COMPACT=0`` opts out (docs/scale-out.md)."""
        if not self.persist:
            return
        tmp = self.applied_path.with_suffix(".tmp")
        if tmp.exists():
            # A crash between the tmp write and os.replace strands the
            # temp file — it is never valid input (the real log is still
            # live), so remove it before any early return can leak it.
            try:
                tmp.unlink()
                self._m_compact[("applied", "stale_tmp")].inc()
            except OSError as e:
                log.warning("stale %s could not be removed: %s", tmp, e)
        if n_lines <= len(self._applied_events):
            return
        if os.environ.get("KAKVEDA_GFKB_APPLIED_COMPACT", "1") == "0":
            self._m_compact[("applied", "skipped")].inc()
            return
        # A pending torn-tail truncation is handled by the rewrite itself
        # (only fully parsed ids survive), so drop the schedule.
        self._truncate_pending.pop(self.applied_path, None)
        try:
            with tmp.open("w", encoding="utf-8") as f:
                for eid in self._applied_events:
                    f.write(json.dumps({"id": eid}) + "\n")
            os.replace(tmp, self.applied_path)
            self._m_compact[("applied", "ok")].inc()
            log.info(
                "compacted %s: %d -> %d ids",
                self.applied_path, n_lines, len(self._applied_events),
            )
        except OSError as e:  # disk trouble: keep the uncompacted log
            log.warning("applied-log compaction skipped: %s", e)
            self._m_compact[("applied", "error")].inc()
            tmp.unlink(missing_ok=True)

    # --- snapshot / restore --------------------------------------------

    # v2: embeddings persist as sparse (idx, val) pairs (~16× smaller,
    # no re-sparsify on restore). v3 adds a content checksum over the
    # snapshot payload to the manifest, so a corrupted snapshot (bad disk,
    # partial copy) degrades to full replay instead of restoring garbage
    # vectors. v4 adds the incremental-mining cluster labels
    # (clusters.npy) with their OWN manifest checksum: a bad cluster file
    # degrades to one full re-mine (state marked stale), never to full
    # log replay and never to restoring unverified labels. v5 adds the
    # tiered-index state — centroids.npy + tier_assign.npy (the IVF
    # router) with their own checksum, and a "tiers" manifest section
    # recording the hot boundary; overflow rows persist through the same
    # sparse payload (sourced from the host tiers instead of the device).
    # A bad/missing tier file degrades to one router rebuild from the
    # restored rows, never to full log replay. Older snapshots fall back
    # to full replay — acceptable one-time cost, no migration path needed.
    _SNAPSHOT_VERSION = 5
    _TAIL_HASH_BYTES = 4096
    _SNAPSHOT_PAYLOAD = ("sparse_idx.npy", "sparse_val.npy", "records.jsonl")

    @classmethod
    def _snapshot_checksum(cls, sd: Path) -> str:
        """sha256 over the snapshot payload files, in manifest order — THE
        content checksum both snapshot() and _restore_snapshot() compute."""
        import hashlib

        h = hashlib.sha256()
        for name in cls._SNAPSHOT_PAYLOAD:
            with (sd / name).open("rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    h.update(chunk)
            h.update(b"\x00")
        return h.hexdigest()

    def _snapshot_dir(self) -> Path:
        return self.data_dir / "snapshot"

    def _log_prefix_hash(self, offset: int) -> str:
        """sha256 of the last ≤4KB of failures.jsonl before ``offset`` —
        cheap integrity check that the log the snapshot covered is still
        the same log (purge-demo rewrites it, for instance)."""
        import hashlib

        start = max(0, offset - self._TAIL_HASH_BYTES)
        with self.failures_path.open("rb") as f:
            f.seek(start)
            return hashlib.sha256(f.read(offset - start)).hexdigest()

    def snapshot(self) -> Path:
        """Write an atomic point-in-time snapshot: slot-ordered embedding
        rows (no re-embed on restore) + pre-serialized records (no pydantic
        re-validate) + a manifest pinning the covered failures.jsonl byte
        range. Restore replays only the log tail written after it."""
        import shutil
        import tempfile

        # Capture a consistent view under the data lock: records list copy
        # (records are replaced, never mutated) + a device-side HBM copy of
        # the embedding buffer (fast). The slow parts — the multi-GB host
        # transfer and the disk write — run WITHOUT the data lock so a live
        # service's warn/ingest path doesn't stall. A separate snapshot lock
        # serializes concurrent snapshot() calls (endpoint + shutdown).
        if not self.persist:
            raise SnapshotError("snapshot requires a persistent GFKB (persist=True)")
        # Multi-host discipline: under multi-controller JAX, snapshot() is a
        # COLLECTIVE — the slot gather over the globally-sharded buffer
        # needs every process to run the same program, so every process
        # must call snapshot(), and every process writes to ITS OWN
        # data_dir. Symmetric writes are load-bearing, not redundancy: a
        # host that restored from a snapshot runs different insert programs
        # at startup than a host that full-replayed, which desynchronizes
        # the SPMD lockstep (observed as gloo size-mismatch aborts). The
        # deployment contract is per-host data dirs — a shared data_dir
        # across processes is already invalid (every host would
        # double-append the same log lines).
        with self._snapshot_write_lock:
            with self._lock:
                self._drain_pending_embeds()
                # Fold every pending delta attach into the union-find so
                # the persisted labels cover exactly the persisted rows —
                # a pending-at-snapshot edge would otherwise be lost on
                # restore (desynced labels, the thing v4 must never do).
                self._mine_drain_locked()
                self._flush_logs()
                records = list(self._records)
                n = len(records)
                mine_labels = None
                mine_threshold = None
                if (
                    self._mine is not None
                    and not self._mine.stale
                    and self._mine.n_rows == n
                ):
                    mine_labels = self._mine.labels()
                    mine_threshold = self._mine.threshold
                offset = self.failures_path.stat().st_size if self.failures_path.exists() else 0
                # Capture the knn alongside the buffer: a concurrent growth
                # re-shard swaps self._knn and would decode emb_copy's
                # layout with the wrong rows_per_shard.
                knn = self._knn
                emb_copy = knn.device_copy(self._emb)
                log_hash = self._log_prefix_hash(offset) if offset else ""
                generation = self._generation
                hot_n = min(n, self._hot_cap())
                router_state = (
                    self._tiers.export_router_state()
                    if self._tiers is not None else None
                )

            vecs = knn.gather_slots(emb_copy, np.arange(hot_n, dtype=np.int32))
            del emb_copy
            # Persist SPARSE (idx, val) pairs, not the dense matrix:
            # hashed-ngram rows are ~98% zeros, so the snapshot shrinks
            # ~16× (0.5 GB vs 8 GB at 1M×2048) — at 1M rows the dense
            # write/read dominated restore AND its writeback stalled the
            # first post-snapshot restore on slow disks (measured r5:
            # 253 s restore right after a dense snapshot vs 120 s
            # isolated). Restore feeds these pairs straight to the device
            # scatter with no re-sparsify pass.
            sp_idx, sp_val = dense_rows_to_sparse(vecs, knn.dim)
            del vecs
            if n > hot_n:
                # Overflow rows never touched the device: their sparse
                # pairs come straight from the host tiers (warm RAM or
                # cold shards), padded to a common row width.
                o_idx, o_val = self._tiers._rows_block(
                    np.arange(hot_n, n, dtype=np.int64)
                )
                kk = max(sp_idx.shape[1], o_idx.shape[1])

                def _pad(a, fill, dtype):
                    out = np.full((a.shape[0], kk), fill, dtype)
                    out[:, : a.shape[1]] = a
                    return out

                sp_idx = np.concatenate(
                    [_pad(sp_idx, knn.dim, np.int32), _pad(o_idx, knn.dim, np.int32)]
                )
                sp_val = np.concatenate(
                    [_pad(sp_val, 0.0, np.float32), _pad(o_val, 0.0, np.float32)]
                )
            sd = self._snapshot_dir()
            tmp = Path(tempfile.mkdtemp(dir=self.data_dir, prefix=".snapshot-"))
            old = self.data_dir / f".snapshot-old-{os.getpid()}-{id(tmp)}"
            try:
                np.save(tmp / "sparse_idx.npy", sp_idx)
                np.save(tmp / "sparse_val.npy", sp_val)
                with (tmp / "records.jsonl").open("w", encoding="utf-8") as f:
                    f.writelines(r.model_dump_json() + "\n" for r in records)
                # Chaos site: a snapshot-write failure here exercises the
                # except path below — tmp is removed and the previous
                # snapshot (if any) stays installed.
                self._fault_snapshot.fire()
                manifest = {
                    "version": self._SNAPSHOT_VERSION,
                    "n": n,
                    "dim": knn.dim,
                    "log_offset": offset,
                    "log_hash": log_hash,
                    # Content checksum: restore verifies it and
                    # degrades to full replay on any mismatch.
                    "checksum": self._snapshot_checksum(tmp),
                    # Compaction posture survives snapshot rewrites — the
                    # generation fence (compact()) bumps it via its own
                    # manifest rewrite.
                    "compact": {
                        "generation": self._compact_generation,
                        "ts": self._last_compact_ts,
                    },
                }
                if mine_labels is not None:
                    import hashlib

                    np.save(tmp / "clusters.npy", mine_labels.astype(np.int32))
                    manifest["mine"] = {
                        "n": n,
                        "threshold": mine_threshold,
                        # Own checksum (not part of the main payload
                        # tuple): a rotted cluster file costs one full
                        # re-mine, not a full log replay.
                        "checksum": hashlib.sha256(
                            (tmp / "clusters.npy").read_bytes()
                        ).hexdigest(),
                    }
                if router_state is not None:
                    import hashlib

                    cent, assign = router_state
                    np.save(tmp / "centroids.npy", cent.astype(np.float32))
                    np.save(tmp / "tier_assign.npy", assign.astype(np.int32))
                    h = hashlib.sha256((tmp / "centroids.npy").read_bytes())
                    h.update((tmp / "tier_assign.npy").read_bytes())
                    manifest["tiers"] = {
                        "n": n,
                        "hot": hot_n,
                        # Own checksum: a rotted router file costs one
                        # router rebuild from the restored rows, not a
                        # full log replay (routing is derived state).
                        "checksum": h.hexdigest(),
                    }
                (tmp / "manifest.json").write_text(json.dumps(manifest))
                # Swap via renames under the data lock: serialized with
                # reload(), and a crash mid-swap leaves at worst no snapshot
                # (full replay fallback), never a half-written one.
                with self._lock:
                    if self._generation != generation:
                        raise SnapshotError(
                            "GFKB was reloaded during snapshot; snapshot aborted — retry"
                        )
                    if sd.exists():
                        sd.rename(old)
                    tmp.rename(sd)
                shutil.rmtree(old, ignore_errors=True)
            except BaseException:
                shutil.rmtree(tmp, ignore_errors=True)
                if old.exists() and not sd.exists():
                    old.rename(sd)  # restore the previous snapshot
                raise
            return sd

    def _restore_snapshot(self) -> int:
        """Load a valid snapshot; returns the failures.jsonl byte offset to
        replay from (0 = no usable snapshot, full replay)."""
        sd = self._snapshot_dir()
        manifest_path = sd / "manifest.json"
        if not manifest_path.exists():
            return 0
        try:
            manifest = json.loads(manifest_path.read_text())
            if manifest.get("version") != self._SNAPSHOT_VERSION:
                return 0
            if manifest.get("dim") != self._knn.dim:
                return 0
            offset = int(manifest.get("log_offset", 0))
            size = self.failures_path.stat().st_size if self.failures_path.exists() else 0
            if size < offset:
                return 0  # log truncated/rewritten since the snapshot
            if offset and self._log_prefix_hash(offset) != manifest.get("log_hash"):
                return 0  # log rewritten in place (e.g. purge) — full replay
            if self._snapshot_checksum(sd) != manifest.get("checksum"):
                # Payload doesn't match what snapshot() wrote (bit rot,
                # partial copy, hand edits): restoring would install
                # garbage vectors the warn path then trusts — degrade to
                # full replay from the append log instead.
                log.warning(
                    "snapshot at %s fails its content checksum; ignoring it "
                    "and replaying the full log", sd,
                )
                return 0
            cm = manifest.get("compact") or {}
            self._compact_generation = int(cm.get("generation", 0))
            self._last_compact_ts = float(cm.get("ts", 0.0) or 0.0)
            n = int(manifest["n"])
            records = []
            with (sd / "records.jsonl").open("r", encoding="utf-8") as f:
                for line in f:
                    if line.strip():
                        # our own snapshot — construct without re-validation
                        records.append(
                            CanonicalFailureRecord.model_construct(
                                **_record_from_snapshot(json.loads(line))
                            )
                        )
            if len(records) != n:
                return 0
            sp_idx = np.load(sd / "sparse_idx.npy")
            sp_val = np.load(sd / "sparse_val.npy")
            if (
                sp_idx.shape != sp_val.shape
                or sp_idx.shape[0] != n
                or sp_idx.dtype != np.int32
                or sp_val.dtype != np.float32
            ):
                return 0
        except Exception:  # noqa: BLE001 — any corruption ⇒ full replay
            return 0
        # Grow the index BEFORE installing the records: _ensure_capacity
        # re-embeds from self._records on growth, which would re-do exactly
        # the work the snapshot vectors exist to skip.
        self._ensure_capacity(n)
        self._records = records
        self._slot_by_key = {
            (r.failure_type, r.signature_text): i for i, r in enumerate(records)
        }
        self._slot_by_id = {r.failure_id: i for i, r in enumerate(records)}
        for r in records:
            self._ids_by_type.setdefault(r.failure_type, []).append(r.failure_id)
            self._apps_by_type.setdefault(r.failure_type, set()).update(r.affected_apps)
        if n:
            tids = np.asarray([self._type_id(r.failure_type) for r in records], np.int32)
            # route=False: the router's persisted partition (or a rebuild)
            # installs after the rows, instead of re-assigning online.
            self._bulk_insert_chunked(
                lambda i, j: (sp_idx[i:j], sp_val[i:j]),
                np.arange(n, dtype=np.int32),
                tids,
                route=False,
            )
        self._mine_restore(sd, manifest)
        self._restore_tiers(sd, manifest)
        return offset

    def _restore_tiers(self, sd: Path, manifest: dict) -> None:
        """Install the snapshot's IVF router state. NEVER trusts an
        unverified partition: a missing section, checksum mismatch or
        shape error degrades to ONE router rebuild from the restored rows
        — routing is derived state; it must not force a full log replay
        and must not silently misroute."""
        t = self._tiers
        if t is None or t.router is None:
            return
        try:
            mf = manifest.get("tiers")
            if not mf:
                raise ValueError("snapshot carries no tier state")
            import hashlib

            h = hashlib.sha256((sd / "centroids.npy").read_bytes())
            h.update((sd / "tier_assign.npy").read_bytes())
            if h.hexdigest() != mf.get("checksum"):
                raise ValueError("tier-state checksum mismatch")
            cent = np.load(sd / "centroids.npy")
            assign = np.load(sd / "tier_assign.npy")
            if len(assign) != len(self._records) or int(mf.get("n", -1)) != len(assign):
                raise ValueError("tier-state shape mismatch")
            t.restore_router_state(cent, assign)
        except Exception as e:  # noqa: BLE001 — degrade, never desync
            log.warning(
                "tier-router restore failed (%s: %s); rebuilding the "
                "coarse partition from the restored rows",
                type(e).__name__, e,
            )
            t.rebuild_router()

    def _mine_restore(self, sd: Path, manifest: dict) -> None:
        """Seed the incremental cluster state from a snapshot's labels.
        NEVER installs unverified labels: any missing/mismatched field,
        checksum failure or injected fault leaves the state stale, which
        costs exactly one full re-mine on the next mine_patterns call."""
        m = self._mine
        if m is None:
            return
        try:
            self._fault_mine.fire()
            mf = manifest.get("mine")
            if not mf:
                m.mark_stale("snapshot carries no cluster state")
                return
            import hashlib

            raw = (sd / "clusters.npy").read_bytes()
            if hashlib.sha256(raw).hexdigest() != mf.get("checksum"):
                raise ValueError("cluster-state checksum mismatch")
            import io

            labels = np.load(io.BytesIO(raw))
            if (
                labels.shape != (len(self._records),)
                or labels.dtype != np.int32
                or int(mf.get("n", -1)) != len(self._records)
            ):
                raise ValueError("cluster-state shape mismatch")
            if float(mf.get("threshold", -1.0)) != m.threshold:
                # Config changed since the snapshot: labels were built for
                # a different graph — full re-mine, don't reinterpret.
                m.mark_stale("snapshot threshold differs from configured")
                return
            m.seed(
                labels,
                [(r.failure_type, r.failure_id, r.affected_apps) for r in self._records],
            )
        except Exception as e:  # noqa: BLE001 — degrade, never desync
            log.warning(
                "cluster-state restore failed (%s: %s); first mine will run "
                "a full sweep", type(e).__name__, e,
            )
            m.mark_stale(f"restore failed: {type(e).__name__}")

    def _bulk_insert_chunked(
        self, sparsify, slots: np.ndarray, tids: np.ndarray, route: bool = True
    ) -> None:
        """Bulk insert in bounded 64k chunks: insert inputs are replicated
        on every device, so a million-row restore in one call would put the
        whole matrix on each chip. ``sparsify(i, j)`` yields the (idx, val)
        pair for rows [i, j) — rows always ship sparse (hashed-ngram
        embeddings are ~98% zeros; at 1M rows that is ~250 MB over the wire
        instead of 8 GB). Slots past the hot cap land in the host tiers
        only — the device never grows past its row budget."""
        chunk = 1 << 16
        hot = self._hot_cap()
        for i in range(0, len(slots), chunk):
            j = min(i + chunk, len(slots))
            sp_i, sp_v = sparsify(i, j)
            self._store_tier_rows(slots[i:j], sp_i, sp_v, route=route)
            dev = slots[i:j] < hot
            if dev.any():
                self._emb, self._valid, self._types = self._knn.insert_sparse(
                    self._emb, self._valid, self._types,
                    sp_i[dev], sp_v[dev], slots[i:j][dev], tids[i:j][dev],
                )

    def _insert_texts_chunked(self, texts: List[str], slots: np.ndarray, tids: np.ndarray) -> None:
        """Signature texts (replay/rebuild): encode sparse per chunk — no
        dense host matrix ever materializes."""
        self._bulk_insert_chunked(
            lambda i, j: self.featurizer.encode_batch_sparse(texts[i:j]), slots, tids
        )

    def reload(self) -> None:
        """Drop all in-memory/device state and replay the append logs.

        Required after any external rewrite of the JSONL files (e.g. the
        dashboard's purge-demo flow) so the device index, id minting and
        host metadata stay consistent with the log. Any existing snapshot
        describes the pre-rewrite state and is deleted; an in-flight
        snapshot sees the generation bump at its swap step and aborts
        (reload deliberately does NOT take the snapshot-write lock — a
        purge must not stall behind a multi-GB snapshot disk write).
        """
        import shutil

        with self._lock:
            self._generation += 1
            shutil.rmtree(self._snapshot_dir(), ignore_errors=True)
            # Reopen the append logs: an external rewrite may have replaced
            # the files (new inode), and a held fd would append to the old
            # one. _lock is already held here — close() would deadlock.
            self._close_locked()
            self._emb, self._valid = self._knn.alloc()
            self._types = self._knn.alloc_i32()
            self._type_ids = {}
            self._records = []
            self._slot_by_key = {}
            self._slot_by_id = {}
            self._pattern_state = {}
            self._ids_by_type = {}
            self._apps_by_type = {}
            self._tombstoned = {}
            # The rewrite replaced the files; any torn-tail truncation
            # scheduled against the OLD files must not fire on the new ones.
            self._truncate_pending = {}
            # Host tiers describe pre-rewrite slots (including any cold
            # shards on disk) — drop them with everything else.
            if self._tiers is not None:
                self._tiers.reset()
            if self._mine is not None:
                from kakveda_tpu.ops.incremental import ClusterState

                self._mine = ClusterState(
                    threshold=self._mine.threshold, k=self._mine.k
                )
            self._mine_pending.clear()
            self._match_cache.clear()
            self._mine_merges_seen = 0
            if self.persist:
                self._replay()
            self._mine_after_replay()
            self._publish()

    def _mine_after_replay(self) -> None:
        """Post-replay invariant: the cluster state must cover exactly the
        replayed rows or be stale. A snapshot restore seeds it; a full log
        replay (or a log tail with rows the snapshot never saw) leaves a
        gap that only a full re-mine can close."""
        m = self._mine
        if m is None:
            return
        if len(self._records) and m.n_rows != len(self._records):
            m.mark_stale("replayed rows not covered by restored cluster state")
        nc = m.n_clusters_cached()
        if nc is not None:
            self._m_mine_clusters.set(nc)

    # ------------------------------------------------------------------
    # failures
    # ------------------------------------------------------------------

    @property
    def count(self) -> int:
        return len(self._records)

    def list_failures(self) -> List[CanonicalFailureRecord]:
        with self._lock:
            return list(self._records)

    def list_failures_page(
        self, offset: int = 0, limit: int = 50, newest_first: bool = True
    ) -> List[CanonicalFailureRecord]:
        """A page of records without copying the whole list — dashboard
        views at 1M records must not pay O(N) per page render."""
        with self._lock:
            n = len(self._records)
            if newest_first:
                hi = max(0, n - offset)
                lo = max(0, hi - limit)
                return self._records[lo:hi][::-1]
            return self._records[offset : offset + limit]

    def get_failure(self, failure_id: str) -> Optional[CanonicalFailureRecord]:
        """O(1) id lookup via the maintained id→slot map."""
        with self._lock:
            slot = self._slot_by_id.get(failure_id)
            return self._records[slot] if slot is not None else None

    def all_apps(self) -> List[str]:
        """Sorted union of affected apps — maintained incrementally so the
        dashboard's app dropdowns never scan the record list."""
        with self._lock:
            out: set = set()
            for apps in self._apps_by_type.values():
                out |= apps
            return sorted(out)

    def records_and_embeddings(self) -> Tuple[List[CanonicalFailureRecord], np.ndarray]:
        """Consistent (records, slot-aligned embedding rows) pair — captured
        atomically so a concurrent reload() (purge) can't misalign row i
        with records[i]. The slow host transfer happens after the lock via a
        device-side buffer copy."""
        with self._lock:
            self._drain_pending_embeds()
            records = list(self._records)
            knn = self._knn  # growth re-shard swaps the knn; pair it with the buffer
            emb_copy = knn.device_copy(self._emb)
            hot_n = min(len(records), self._hot_cap())
        vecs = knn.gather_slots(emb_copy, np.arange(hot_n, dtype=np.int32))
        if len(records) > hot_n:
            # Overflow rows densify from the host tiers (the device never
            # held them). Callers of this API (full-sweep mining, audits)
            # already accept O(N·dim) host memory.
            o_idx, o_val = self._tiers._rows_block(
                np.arange(hot_n, len(records), dtype=np.int64)
            )
            dense = np.zeros((len(records) - hot_n, knn.dim + 1), np.float32)
            rows = np.broadcast_to(
                np.arange(dense.shape[0])[:, None], o_idx.shape
            )
            np.add.at(dense, (rows, np.minimum(o_idx, knn.dim)), o_val)
            vecs = np.concatenate([vecs, dense[:, : knn.dim]])
        return records, vecs

    def type_aggregate(self, failure_type: str) -> Tuple[List[str], List[str]]:
        """(failure_ids in insertion order, sorted affected apps) for a type
        — maintained incrementally so per-batch pattern detection never
        rescans the record list."""
        with self._lock:
            return (
                list(self._ids_by_type.get(failure_type, [])),
                sorted(self._apps_by_type.get(failure_type, set())),
            )

    def _publish(self) -> None:
        """Swap the lock-free read view (call with the data lock held, or
        single-threaded during init)."""
        self._view = (self._knn, self._emb, self._valid, self._types, self._records)

    def _type_id(self, failure_type: str) -> int:
        """Dense id for a failure type (assigns on first sight; callers hold
        the data lock when creating records)."""
        tid = self._type_ids.get(failure_type)
        if tid is None:
            tid = self._type_ids[failure_type] = len(self._type_ids)
        return tid

    def _build_index(self, new_cap: int, records: Sequence[CanonicalFailureRecord]):
        """Allocate a capacity-``new_cap`` index populated with ``records``
        (re-embed + type scatter). Pure construction — no shared state."""
        knn = ShardedKnn(self.mesh, new_cap, self._knn.dim, k=self.top_k)
        emb, valid = knn.alloc()
        types = knn.alloc_i32()
        if records:
            chunk = 1 << 16
            # _type_id MINTS unseen ids — replay reaches here before any
            # upsert has registered the types (raw dict access crashed a
            # reopen whose log had outgrown the configured capacity).
            tids = np.asarray([self._type_id(r.failure_type) for r in records], np.int32)
            for i in range(0, len(records), chunk):
                batch = records[i : i + chunk]
                sp_i, sp_v = self.featurizer.encode_batch_sparse(
                    [r.signature_text for r in batch]
                )
                slots = np.arange(i, i + len(batch), dtype=np.int32)
                emb, valid, types = knn.insert_sparse(
                    emb, valid, types, sp_i, sp_v, slots, tids[i : i + chunk]
                )
        return knn, emb, valid, types

    def _ensure_capacity(self, needed: int) -> None:
        """Init-time growth (replay/restore run single-threaded). The
        device only ever grows to the hot cap; overflow is the tiers'."""
        needed = min(needed, self._hot_cap())
        if needed <= self._knn.capacity:
            return
        new_cap = self._knn.capacity
        while new_cap < needed:
            new_cap *= 2
        self._knn, self._emb, self._valid, self._types = self._build_index(
            new_cap, self._records[:needed]
        )
        self._publish()

    def _grow_and_reembed(self) -> None:
        """Runtime growth: an explicit re-shard event. The expensive work —
        re-embedding every record and building the doubled index — runs
        WITHOUT the data lock so concurrent matches and ingests aren't
        stalled behind it; the swap re-checks under the lock and retries if
        a reload or competing growth won the race. Rows appended while the
        rebuild ran are delta-scattered at swap time. Growth stops at the
        hot cap — rows past it are host-tier only, by design."""
        while True:
            with self._lock:
                hot = self._hot_cap()
                needed = min(len(self._records), hot)
                if needed <= self._knn.capacity:
                    return
                records = list(self._records[:hot])
                old_knn = self._knn
                gen = self._generation
            new_cap = old_knn.capacity
            while new_cap < len(records):
                new_cap *= 2
            knn, emb, valid, types = self._build_index(new_cap, records)
            with self._lock:
                if self._generation != gen or self._knn is not old_knn:
                    continue  # reload or another growth swapped first; re-check
                hot_now = min(len(self._records), hot)
                if hot_now > new_cap:
                    continue  # appends outran the doubling; rebuild bigger
                if hot_now > len(records):
                    delta = self._records[len(records) : hot_now]
                    d_i, d_v = self.featurizer.encode_batch_sparse(
                        [r.signature_text for r in delta]
                    )
                    dslots = np.arange(len(records), hot_now, dtype=np.int32)
                    dtids = np.asarray(
                        [self._type_id(r.failure_type) for r in delta], np.int32
                    )
                    emb, valid, types = knn.insert_sparse(
                        emb, valid, types, d_i, d_v, dslots, dtids
                    )
                self._knn, self._emb, self._valid, self._types = knn, emb, valid, types
                self._publish()
                return

    def upsert_failure(
        self,
        *,
        failure_type: str,
        signature_text: str,
        app_id: str,
        impact_severity: Severity,
        context_signature: Optional[dict] = None,
        root_cause: Optional[str] = None,
        resolution: Optional[str] = None,
    ) -> Tuple[CanonicalFailureRecord, bool]:
        """Versioned upsert; returns (record, created).

        Identity is (failure_type, signature_text) — same as the reference's
        reverse scan (reference: services/gfkb/app.py:108-113). Updates bump
        version/occurrences, merge affected apps, and let root cause /
        resolution evolve; every write re-appends to the JSONL log.
        """
        with self._lock:
            key = (failure_type, signature_text)
            slot = self._slot_by_key.get(key)
            now = utcnow()
            gen = self._generation
            revived = False
            if slot is None:
                created = True
                rec = CanonicalFailureRecord(
                    failure_id=f"F-{len(self._records) + 1:04d}",
                    version=1,
                    created_at=now,
                    updated_at=now,
                    failure_type=failure_type,
                    root_cause=root_cause,
                    context_signature=context_signature or {},
                    impact_severity=impact_severity,
                    resolution=resolution,
                    occurrences=1,
                    affected_apps=[app_id],
                    signature_text=signature_text,
                )
                slot = len(self._records)
                tid = self._type_id(failure_type)
                self._records.append(rec)
                self._slot_by_key[key] = slot
                self._slot_by_id[rec.failure_id] = slot
                self._ids_by_type.setdefault(failure_type, []).append(rec.failure_id)
                self._apps_by_type.setdefault(failure_type, set()).add(app_id)
                if self._mine is not None and not self._mine.stale:
                    self._mine.add_row(slot, failure_type, rec.failure_id, [app_id])
            else:
                created = False
                old = self._records[slot]
                rec = old.model_copy(deep=True)
                rec.version += 1
                rec.updated_at = now
                rec.occurrences += 1
                if app_id not in rec.affected_apps:
                    rec.affected_apps.append(app_id)
                self._apps_by_type.setdefault(failure_type, set()).add(app_id)
                if self._mine is not None:
                    self._mine.note_apps(slot, [app_id])
                rec.root_cause = root_cause or rec.root_cause
                rec.resolution = resolution or rec.resolution
                rec.context_signature = context_signature or rec.context_signature
                self._records[slot] = rec
                if slot in self._tombstoned:
                    # Organic resurrection: the signature is live traffic
                    # again. Durable "live" line, then re-embed below —
                    # the device row was zeroed at tombstone time.
                    self._resurrect_locked(slot, rec)
                    tid = self._type_id(failure_type)
                    revived = True
                # Same signature text => identical embedding; an un-tombstoned
                # update needs no device write.
            need_embed = created or revived
            self._append_jsonl(self.failures_path, rec.model_dump(mode="json"))
            self._flush_logs()
            if need_embed:
                self._pending_embeds += 1
        if need_embed:
            self._embed_new_slots([slot], [signature_text], [tid], gen)
        return rec, created

    def _applied_note_locked(self, event_id: str) -> None:
        """Record an applied replication event id in the bounded dedup set
        (caller holds ``_lock``, or is single-threaded construction)."""
        self._applied_events[event_id] = True
        self._applied_events.move_to_end(event_id)
        while len(self._applied_events) > self._applied_max:
            self._applied_events.popitem(last=False)

    def apply_replication(self, rows: Sequence[dict], event_id: str) -> int:
        """Apply one bus-replicated ingest event (fleet fan-in) through the
        normal tiered insert path, idempotently by event id: at-least-once
        redelivery and DLQ replay of an already-applied event are no-ops.
        Returns the number of rows applied (0 on dedup)."""
        out = self.upsert_failures_batch(rows, event_id=event_id)
        if out:
            self._m_rep_applied.inc()
        else:
            self._m_rep_dedup.inc()
        return len(out)

    @staticmethod
    def shard_key_of(rec: CanonicalFailureRecord) -> str:
        """The ownership shard key of one record — the app that created it
        (``affected_apps[0]``, insertion-ordered), signature as fallback.
        Must agree with fleet.ownership.shard_key_of_row (placement and
        residency accounting read the same key)."""
        return rec.affected_apps[0] if rec.affected_apps else rec.signature_text

    def shard_key_counts(self) -> Dict[str, int]:
        """Resident rows per shard key — the per-range row counts behind
        /readyz's ownership section and `cli status`. O(N) on demand; at
        readiness-probe cadence that is noise next to a device match."""
        out: Dict[str, int] = {}
        with self._lock:
            for slot, rec in enumerate(self._records):
                if slot in self._tombstoned:
                    continue  # retired rows are not placement-relevant residency
                k = self.shard_key_of(rec)
                out[k] = out.get(k, 0) + 1
        return out

    def export_rows(self, since: int = 0) -> Tuple[List[dict], int]:
        """Snapshot the record range ``[since, count)`` as replication-shaped
        row dicts, plus the count watermark at export time.

        This is the range-migration export surface (fleet/ownership.py):
        the first call ships the snapshot, a second call with the returned
        watermark drains the delta appended during the ship. Rows carry the
        full ``affected_apps`` so the receiving upsert reconstructs the
        record's app span, and re-encode their signature on apply — the
        hashed-ngram featurizer is deterministic, so the receiver's vectors
        are identical to the source's. Slots only ever append (updates stay
        in place), so a slot range IS a consistent delta cursor.
        Tombstoned rows are excluded — a migration must not re-materialize
        a row the lifecycle retired (the receiver would serve it)."""
        with self._lock:
            recs = [
                r
                for i, r in enumerate(self._records[since:], start=since)
                if i not in self._tombstoned
            ]
            count = len(self._records)
        rows = [
            {
                "failure_type": rec.failure_type,
                "root_cause": rec.root_cause,
                "context_signature": dict(rec.context_signature or {}),
                "impact_severity": rec.impact_severity.value
                if hasattr(rec.impact_severity, "value") else rec.impact_severity,
                "resolution": rec.resolution,
                "signature_text": rec.signature_text,
                "app_id": self.shard_key_of(rec),
                "affected_apps": list(rec.affected_apps),
            }
            for rec in recs
        ]
        return rows, count

    def upsert_failures_batch(
        self, items: Sequence[dict], event_id: Optional[str] = None
    ) -> List[Tuple[CanonicalFailureRecord, bool]]:
        """Batched upsert for the streaming-ingest path.

        New signatures are embedded in one ``encode_batch`` and written to the
        device in one scatter — the 10k traces/sec path.

        ``event_id`` (replication apply): when set and already applied, the
        whole batch is a dedup no-op; otherwise the id is appended to its
        own log AFTER the row lines, so a crash between the two replays the
        rows on redelivery (an occurrence bump) rather than losing them.
        """
        # Ledger attribution: embed/scatter compiles and uploads land on
        # the ingest entry/phase.
        with _ledger.entry("ingest"), _ledger.phase("ingest"):
            out = self._upsert_failures_batch(items, event_id)
        # Size/age compaction trigger rides the ingest cadence (background
        # thread — the batch never waits on a checkpoint write).
        self._maybe_auto_compact()
        return out

    def _upsert_failures_batch(
        self, items: Sequence[dict], event_id: Optional[str] = None
    ) -> List[Tuple[CanonicalFailureRecord, bool]]:
        out: List[Tuple[CanonicalFailureRecord, bool]] = []
        new_slots: List[int] = []
        new_texts: List[str] = []
        new_tids: List[int] = []
        with self._lock:
            if event_id is not None and event_id in self._applied_events:
                return []
            gen = self._generation
            now = utcnow()
            for item in items:
                key = (item["failure_type"], item["signature_text"])
                slot = self._slot_by_key.get(key)
                if slot is None:
                    # model_construct: inputs are classifier-built and typed;
                    # skipping validation keeps batch inserts off the pydantic
                    # hot loop (single-record upsert_failure keeps validating).
                    rec = CanonicalFailureRecord.model_construct(
                        failure_id=f"F-{len(self._records) + 1:04d}",
                        version=1,
                        created_at=now,
                        updated_at=now,
                        failure_type=item["failure_type"],
                        root_cause=item.get("root_cause"),
                        context_signature=item.get("context_signature") or {},
                        impact_severity=Severity(item["impact_severity"]),
                        resolution=item.get("resolution"),
                        occurrences=1,
                        # Migration-shipped rows carry the source record's
                        # full app list; ingest rows just their own app.
                        affected_apps=list(item.get("affected_apps") or [item["app_id"]]),
                        signature_text=item["signature_text"],
                    )
                    slot = len(self._records)
                    self._records.append(rec)
                    self._slot_by_key[key] = slot
                    self._slot_by_id[rec.failure_id] = slot
                    self._ids_by_type.setdefault(rec.failure_type, []).append(rec.failure_id)
                    self._apps_by_type.setdefault(rec.failure_type, set()).add(item["app_id"])
                    if self._mine is not None and not self._mine.stale:
                        self._mine.add_row(
                            slot, rec.failure_type, rec.failure_id,
                            list(rec.affected_apps),
                        )
                    new_slots.append(slot)
                    new_texts.append(rec.signature_text)
                    new_tids.append(self._type_id(rec.failure_type))
                    out.append((rec, True))
                else:
                    if event_id is not None and slot in self._tombstoned:
                        # Lifecycle fence: a replicated event (at-least-once
                        # redelivery, DLQ replay) re-carrying a tombstoned
                        # row drops it cleanly — same 2xx-drop shape as the
                        # stale-epoch ownership fence (docs/scale-out.md).
                        # Only an ORGANIC upsert resurrects.
                        self._m_rep_fenced.inc()
                        continue
                    old = self._records[slot]
                    rec = old.model_copy(deep=True)
                    rec.version += 1
                    rec.updated_at = now
                    rec.occurrences += 1
                    for app in item.get("affected_apps") or [item["app_id"]]:
                        if app not in rec.affected_apps:
                            rec.affected_apps.append(app)
                        self._apps_by_type.setdefault(rec.failure_type, set()).add(app)
                    if self._mine is not None:
                        self._mine.note_apps(
                            slot, item.get("affected_apps") or [item["app_id"]]
                        )
                    rec.root_cause = item.get("root_cause") or rec.root_cause
                    rec.resolution = item.get("resolution") or rec.resolution
                    rec.context_signature = item.get("context_signature") or rec.context_signature
                    self._records[slot] = rec
                    if slot in self._tombstoned:
                        # Organic resurrection: re-embed via the new-slot
                        # scatter below (the device row was zeroed).
                        self._resurrect_locked(slot, rec)
                        new_slots.append(slot)
                        new_texts.append(rec.signature_text)
                        new_tids.append(self._type_id(rec.failure_type))
                    out.append((rec, False))
                self._append_line(self.failures_path, rec.model_dump_json())
            if event_id is not None:
                self._applied_note_locked(event_id)
                self._append_line(self.applied_path, json.dumps({"id": event_id}))
            self._flush_logs()
            if new_slots:
                self._pending_embeds += 1
        if new_slots:
            self._embed_new_slots(new_slots, new_texts, new_tids, gen)
        return out

    def _embed_new_slots(
        self, slots: List[int], texts: List[str], tids: List[int], gen: int
    ) -> None:
        """Embed freshly appended records and scatter them into the index.

        Runs AFTER the metadata lock is released: the (expensive) host-side
        embedding never blocks matches or other ingests. Correctness under
        concurrency: slots are disjoint per caller, scatters are idempotent,
        and a growth that raced us re-embeds every record it captured plus a
        delta — so whichever order the swaps land, every slot ends up
        written. A reload (generation bump) makes the slots meaningless;
        replay already re-embedded everything from the log, so we skip.
        Callers incremented _pending_embeds under the append lock; the
        finally block releases snapshot()/records_and_embeddings() waiters."""
        try:
            # Sparse path: hashed-ngram rows are ~98% zeros; shipping (idx,
            # val) pairs instead of dense [B, dim] keeps streaming ingest off
            # the host→device wire bottleneck (the dense transfer dominated
            # the whole pipeline at 10k traces/sec rates).
            sp_idx, sp_val = self.featurizer.encode_batch_sparse(texts)
            arr_slots = np.asarray(slots, dtype=np.int32)
            arr_tids = np.asarray(tids, dtype=np.int32)
            with self._lock:
                if self._generation != gen:
                    return  # reloaded since append; replay covered these rows
                # Host tiers first: a device scatter that dies on a wedged
                # backend must still leave degraded-mode matching complete —
                # and slots past the hot cap live ONLY here.
                self._store_tier_rows(arr_slots, sp_idx, sp_val)
                hot = self._hot_cap()
                dev = arr_slots < hot
                need_growth = min(len(self._records), hot) > self._knn.capacity
                if not need_growth and dev.any():
                    with profiling.annotate("gfkb.insert"):
                        self._emb, self._valid, self._types = self._knn.insert_sparse(
                            self._emb, self._valid, self._types,
                            sp_idx[dev], sp_val[dev], arr_slots[dev], arr_tids[dev],
                        )
                    self._publish()
            if need_growth:
                # The rebuild re-embeds every hot record, these included.
                self._grow_and_reembed()
            self._mine_attach_new(slots, texts, sp_idx, sp_val, gen)
        finally:
            with self._lock:
                self._pending_embeds -= 1
                self._embeds_cv.notify_all()

    # ------------------------------------------------------------------
    # incremental mining state
    # ------------------------------------------------------------------

    def _mine_attach_new(self, slots, texts, sp_idx, sp_val, gen) -> None:
        """Queue attach-neighbors for freshly inserted rows.

        Rows whose signature a recent warn match already scored reuse
        those neighbors outright (zero device work); the rest share ONE
        delta top-k dispatch against the resident index — O(ΔN·N) per
        batch. The packed result's host copy starts immediately but is
        consumed lazily (mine_patterns drains it), so the ingest path
        never pays a device→host fetch RTT here. Any failure degrades the
        state to stale (one full re-mine) — mining is derived state and
        must never fail an ingest."""
        m = self._mine
        if m is None or m.stale:
            return
        try:
            self._fault_mine.fire()
            reused = []  # (slot, neigh_slots, sims)
            tier_attach = []  # overflow rows: neighbors from the host tiers
            delta_rows: List[int] = []
            hot = self._hot_cap()
            with self._lock:
                if self._generation != gen:
                    return
                for i, (s, t) in enumerate(zip(slots, texts)):
                    hit = self._match_cache.get(t)
                    if hit is not None and hit[2] == gen:
                        reused.append((s, hit[1], hit[0]))
                    else:
                        delta_rows.append(i)
                if delta_rows:
                    if sp_idx is None:
                        sub_texts = [texts[i] for i in delta_rows]
                        d_idx, d_val = self.featurizer.encode_batch_sparse(sub_texts)
                    else:
                        d_idx = sp_idx[delta_rows]
                        d_val = sp_val[delta_rows]
                    # Overflow rows aren't in the device index: their
                    # neighbors come from the host tiers' (routed) top-k
                    # instead of a device dispatch — same attach contract.
                    ovf = [
                        j for j, i in enumerate(delta_rows) if int(slots[i]) >= hot
                    ]
                    if ovf and self._tiers is not None:
                        # One batched host match for every overflow row —
                        # the candidate gather and (native) scoring run
                        # once per ingest batch, not once per row.
                        batch = self._tiers.match_host_batch(
                            d_idx[ovf], d_val[ovf], m.k + 1
                        )
                        for j, (nscores, nslots, _mode) in zip(ovf, batch):
                            tier_attach.append(
                                (int(slots[delta_rows[j]]), nslots, nscores)
                            )
                        keep = [j for j in range(len(delta_rows)) if j not in set(ovf)]
                        delta_rows = [delta_rows[j] for j in keep]
                        d_idx, d_val = d_idx[keep], d_val[keep]
                if delta_rows:
                    from kakveda_tpu.ops.incremental import delta_topk_sparse

                    # Dispatch under the data lock (PJRT buffer-hold rule,
                    # same as match_batch); +1 neighbor: each row's top-1
                    # against the post-insert index is itself.
                    with profiling.annotate("gfkb.mine.delta"):
                        packed = delta_topk_sparse(
                            self._emb, self._valid, d_idx, d_val, m.k + 1
                        )
                    self.mine_delta_dispatches += 1
                    self._mine_pending.append(
                        (
                            self._knn,
                            np.asarray([slots[i] for i in delta_rows], np.int32),
                            packed,
                            gen,
                        )
                    )
            for s, nslots, nsims in reused:
                m.attach(int(s), nslots, nsims)
                self._m_mine_attach["reused"].inc()
            for s, nslots, nsims in tier_attach:
                keep = np.isfinite(nsims) & (nsims >= m.threshold)
                m.attach(int(s), nslots[keep], nsims[keep])
                self._m_mine_attach["tier"].inc()
            if len(self._mine_pending) > self._mine_pending_max:
                with self._lock:
                    self._mine_drain_locked()
        except Exception as e:  # noqa: BLE001 — degrade, never fail ingest
            log.warning(
                "incremental mining attach failed (%s: %s); state marked "
                "stale — next mine_patterns runs a full sweep",
                type(e).__name__, e,
            )
            m.mark_stale(f"attach failed: {type(e).__name__}")
            with self._lock:
                self._mine_pending.clear()

    def _mine_drain_locked(self) -> int:
        """Fold every pending delta top-k result into the union-find
        (call with the data lock held). Packed buffers started their host
        copy at dispatch, so the fetch here is normally a no-wait read."""
        m = self._mine
        if m is None:
            return 0
        drained = 0
        while self._mine_pending:
            knn, d_slots, packed, gen = self._mine_pending.popleft()
            if gen != self._generation or m.stale:
                continue
            t0 = time.perf_counter()
            try:
                self._fault_mine.fire()
                from kakveda_tpu.ops.incremental import unpack_topk
                from kakveda_tpu.ops.knn import physical_to_slot

                sims, phys = unpack_topk(packed, len(d_slots))
                for row in range(len(d_slots)):
                    keep = np.isfinite(sims[row]) & (sims[row] >= m.threshold)
                    keep &= phys[row] < knn.capacity
                    p = phys[row][keep]
                    sl = (
                        p
                        if knn.single_device
                        else physical_to_slot(p, knn.n_shards, knn.rows_per_shard)
                    )
                    m.attach(int(d_slots[row]), sl, sims[row][keep])
                    self._m_mine_attach["delta"].inc()
                drained += len(d_slots)
            except Exception as e:  # noqa: BLE001 — degrade, never desync
                log.warning(
                    "incremental mining drain failed (%s: %s); state marked "
                    "stale — next mine_patterns runs a full sweep",
                    type(e).__name__, e,
                )
                m.mark_stale(f"drain failed: {type(e).__name__}")
                self._mine_pending.clear()
                break
            self._m_mine_update.observe(time.perf_counter() - t0)
        nc = m.n_clusters_cached()
        if nc is not None:
            self._m_mine_clusters.set(nc)
        return drained

    def mine_drain(self) -> int:
        """Public drain: apply pending incremental deltas, return the
        number of rows attached."""
        with self._lock:
            return self._mine_drain_locked()

    def mine_state_info(self) -> dict:
        """Freshness view of the incremental state (service/mine endpoint
        + tests): enabled flag, row/cluster/dirty counts, staleness and
        the pending (not yet drained) delta batches."""
        with self._lock:
            if self._mine is None:
                return {"enabled": False}
            info = self._mine.info()
            info.update(
                enabled=True,
                pending=len(self._mine_pending),
                covers_all_rows=self._mine.n_rows == len(self._records),
                delta_dispatches=self.mine_delta_dispatches,
            )
            return info

    def mine_pop_dirty(self) -> List[dict]:
        """Aggregate snapshots of clusters touched since the last call
        (drains pending deltas first so 'dirty' is current)."""
        with self._lock:
            self._mine_drain_locked()
            m = self._mine
            if m is None or m.stale:
                return []
            out = m.pop_dirty()
            self._m_mine_merges.inc(m.merges - self._mine_merges_seen)
            self._mine_merges_seen = m.merges
            nc = m.n_clusters_cached()
            if nc is not None:
                self._m_mine_clusters.set(nc)
            return out

    def mine_usable(self, threshold: float) -> bool:
        """Can mine_patterns serve this call incrementally? Requires the
        state to be enabled, non-stale, covering every record, and built
        for the same threshold (a different threshold is a different
        graph — full sweep)."""
        with self._lock:
            m = self._mine
            return (
                m is not None
                and not m.stale
                and m.n_rows == len(self._records)
                and m.threshold == float(threshold)
            )

    def mine_reseed(self, labels: np.ndarray, threshold: float, n_records: int) -> bool:
        """Install a full-sweep result as the new incremental baseline.
        ``n_records`` is the record count the sweep covered; rows appended
        during the sweep leave the state stale (the next sweep catches
        them) rather than silently uncovered."""
        with self._lock:
            m = self._mine
            if m is None:
                return False
            self._mine_pending.clear()
            if n_records != len(self._records) or len(labels) != n_records:
                m.mark_stale("records changed during the full sweep")
                return False
            m.seed(
                labels,
                [(r.failure_type, r.failure_id, r.affected_apps) for r in self._records],
                threshold=threshold,
            )
            nc = m.n_clusters_cached()
            if nc is not None:
                self._m_mine_clusters.set(nc)
            if self._tiers is not None:
                # A fresh full-sweep partition is the best coarse structure
                # available — re-seed the IVF router's centroids from it
                # (ops/incremental.py centroid export; failure keeps the
                # online partition, routing is derived state).
                self._tiers.reseed_router(labels)
            return True

    def _drain_pending_embeds(self) -> None:
        """Wait (holding the lock via the condition) until no appended
        record is still awaiting its embedding scatter. Call with the data
        lock held; may release and re-acquire it."""
        while self._pending_embeds > 0:
            self._embeds_cv.wait(timeout=30.0)

    # ------------------------------------------------------------------
    # lifecycle: row aging, duplicate collapse, log compaction
    # ------------------------------------------------------------------

    @staticmethod
    def _fsync_dir(path: Path) -> None:
        """fsync a directory so a just-completed rename is durable, not
        merely ordered — best-effort (not every platform supports it)."""
        try:
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        except OSError:
            pass

    def _zero_device_rows_locked(self, slots) -> None:
        """Overwrite device rows with zeros (pad-only sparse rows — the
        scatter's SET semantics make that a clean row wipe) and un-type
        them (tid -1 matches no real type id), so a tombstoned row can
        neither score nor pass the type pre-filter. Warm/cold tier rows
        stay in place: the warm inverted index keeps postings for
        overwritten slots by design, so every host-path assembly filters
        tombstoned slots explicitly instead. Caller holds ``_lock`` (or
        is single-threaded init replay)."""
        arr = np.asarray(sorted(int(s) for s in slots), np.int32)
        arr = arr[arr < min(self._hot_cap(), self._knn.capacity)]
        if not len(arr):
            return
        sp_idx = np.full((len(arr), 1), self._knn.dim, np.int32)
        sp_val = np.zeros((len(arr), 1), np.float32)
        self._emb, self._valid, self._types = self._knn.insert_sparse(
            self._emb, self._valid, self._types,
            sp_idx, sp_val, arr, np.full(len(arr), -1, np.int32),
        )
        self._publish()

    def _tombstone_rows_locked(
        self, slots, reason: str, now: Optional[float] = None
    ) -> List[int]:
        """Durable "tomb" op line first, then the state flip, per slot —
        a crash between rows leaves every completed transition replayable
        and the rest simply not taken. Returns the slots actually
        tombstoned (already-tombstoned slots are skipped). Caller holds
        ``_lock`` and zeroes the device rows afterwards."""
        wrote: List[int] = []
        ts = now if now is not None else time.time()
        for slot in slots:
            slot = int(slot)
            if slot in self._tombstoned or not 0 <= slot < len(self._records):
                continue
            try:
                self._fault_tombstone.fire()
                self._append_jsonl(
                    self.tombstones_path,
                    {
                        "op": "tomb",
                        "id": self._records[slot].failure_id,
                        "reason": reason,
                        "ts": ts,
                    },
                )
            except (OSError, _faults.FaultInjected) as e:
                # Durable-before-visible: a transition that never hit disk
                # never happened — the row STAYS LIVE and the pass stops
                # (IO trouble is file-wide, not per-row). Aging/collapse
                # report fewer rows; nothing is half-tombstoned.
                log.warning(
                    "tombstone write failed after %d rows (%s: %s)",
                    len(wrote), type(e).__name__, e,
                )
                break
            self._tombstoned[slot] = reason
            self._m_tombstone[reason].inc()
            wrote.append(slot)
        if wrote:
            self._flush_logs()
            self._g_tombstoned.set(len(self._tombstoned))
        return wrote

    def _resurrect_locked(self, slot: int, rec: CanonicalFailureRecord) -> None:
        """Organic upsert over a tombstoned slot brings it back: durable
        "live" op line, state flip, metrics. Caller holds ``_lock`` and
        re-embeds the slot (its device row was zeroed at tombstone
        time)."""
        self._append_jsonl(
            self.tombstones_path,
            {"op": "live", "id": rec.failure_id, "ts": time.time()},
        )
        self._tombstoned.pop(slot, None)
        self._m_tombstone["resurrected"].inc()
        self._g_tombstoned.set(len(self._tombstoned))

    def age_rows(
        self, ttl_s: Optional[float] = None, now: Optional[float] = None
    ) -> dict:
        """TTL demotion — the terminal hop of hot→warm→cold→tombstone:
        retire every row whose last version write predates ``now - ttl_s``,
        EXCEPT slots in the cold tier's promote-LRU (recently paged in by
        live queries — touch evidence the record timestamps don't carry,
        index/tiers.py ``recently_promoted_slots``). Tombstoning is
        terminal-but-resident: slots, ids and keys stay stable (dense id
        minting, replay latest-wins and replication cursors depend on
        that); reclaiming LOG bytes is :meth:`compact`'s job. ``now`` is
        injectable so the month-compressed aging scenario and the recovery
        bench run without waiting out a real TTL."""
        if ttl_s is None:
            ttl_s = float(os.environ.get("KAKVEDA_GFKB_AGE_TTL_S", "0"))
        if ttl_s <= 0:
            return {"tombstoned": 0, "ttl_s": ttl_s}
        ts = now if now is not None else time.time()
        with self._lock:
            exempt = (
                self._tiers.recently_promoted_slots()
                if self._tiers is not None
                else set()
            )
            victims = [
                slot
                for slot, rec in enumerate(self._records)
                if slot not in self._tombstoned
                and slot not in exempt
                and ts - rec.updated_at.timestamp() > ttl_s
            ]
            wrote = self._tombstone_rows_locked(victims, "aged", now=ts)
            if wrote:
                self._zero_device_rows_locked(wrote)
        return {"tombstoned": len(wrote), "ttl_s": ttl_s, "exempt": len(exempt)}

    def collapse_duplicates(self, min_cluster: Optional[int] = None) -> dict:
        """Near-duplicate collapse over the incremental mining clusters:
        every cluster with ≥ ``min_cluster`` live members keeps ONE
        exemplar (the min live slot — the labels' own min-member
        convention), folds the victims' occurrence counts and app spans
        into it via a normal version-bump log line (replayable, no new
        record shape), and tombstones the victims. Mining is derived
        state: a stale or behind state means NO collapse this round —
        never collapse on unverified labels."""
        if min_cluster is None:
            min_cluster = int(os.environ.get("KAKVEDA_GFKB_DUP_COLLAPSE", "0"))
        out = {"collapsed": 0, "clusters": 0, "min_cluster": min_cluster}
        if min_cluster <= 1:
            return out
        from kakveda_tpu.ops.incremental import collapse_groups

        with self._lock:
            m = self._mine
            if m is None:
                out["reason"] = "incremental mining disabled"
                return out
            self._mine_drain_locked()
            if m.stale or m.n_rows != len(self._records):
                out["reason"] = "mine state stale or behind"
                return out
            now = utcnow()
            for exemplar, victims in collapse_groups(
                m.labels(), min_cluster, exclude=self._tombstoned
            ):
                ex = self._records[exemplar].model_copy(deep=True)
                ex.version += 1
                ex.updated_at = now
                for v in victims:
                    vr = self._records[v]
                    ex.occurrences += vr.occurrences
                    for app in vr.affected_apps:
                        if app not in ex.affected_apps:
                            ex.affected_apps.append(app)
                self._apps_by_type.setdefault(ex.failure_type, set()).update(
                    ex.affected_apps
                )
                m.note_apps(exemplar, list(ex.affected_apps))
                self._records[exemplar] = ex
                self._append_line(self.failures_path, ex.model_dump_json())
                wrote = self._tombstone_rows_locked(victims, "collapsed")
                if wrote:
                    self._zero_device_rows_locked(wrote)
                out["collapsed"] += len(wrote)
                out["clusters"] += 1
            self._flush_logs()
        return out

    def compact(self) -> dict:
        """Checkpoint+delta rewrite of the failures log.

        Takes a fresh snapshot (the checkpoint), rewrites failures.jsonl
        down to ONLY the bytes appended after it, and rewrites the
        tombstone side-log to net state — restart replay then parses the
        delta instead of the full version-append history. The swap is
        FENCED by the snapshot manifest: the manifest (log_offset=0,
        generation bump) swaps via temp+fsync+rename BEFORE the log does,
        so a crash at ANY byte leaves a (manifest, log) pair that replays
        to the pre- or post-compaction state, never a hybrid:

          * before the manifest swap — the old manifest still covers the
            old log at its recorded offset (pre-state);
          * between the two swaps — offset 0 replays the FULL old log
            over the snapshot; versioned upserts replay latest-wins in
            place, converging to the same records (post-state);
          * after the log swap — offset 0 replays exactly the delta
            (post-state).

        The patterns log is untouched (delta-append is already compact —
        lines carry only new members). ``KAKVEDA_GFKB_COMPACT=0`` refuses
        outright — the bit-for-bit append-only opt-out. A concurrent
        reload aborts via the snapshot generation check. Auto-trigger:
        ``KAKVEDA_GFKB_COMPACT_BYTES`` / ``KAKVEDA_GFKB_COMPACT_AGE_S``
        (checked post-ingest-batch, default off)."""
        if not self.persist:
            raise SnapshotError("compaction requires a persistent GFKB (persist=True)")
        if os.environ.get("KAKVEDA_GFKB_COMPACT", "1") == "0":
            self._m_compact[("failures", "skipped")].inc()
            return {"compacted": False, "reason": "KAKVEDA_GFKB_COMPACT=0"}
        stale = self.failures_path.with_suffix(".compact-tmp")
        if stale.exists():
            # A crash between the delta write and the log swap strands the
            # temp file; it is never valid input (whichever log is live at
            # failures.jsonl wins) — remove it before this attempt.
            try:
                stale.unlink()
                self._m_compact[("failures", "stale_tmp")].inc()
            except OSError as e:
                log.warning("stale %s could not be removed: %s", stale, e)
        with self._snapshot_write_lock:
            try:
                self.snapshot()
                out = self._compact_swap_locked()
            except SnapshotError:
                self._m_compact[("failures", "skipped")].inc()
                raise
            except (OSError, _faults.FaultInjected) as e:
                self._m_compact[("failures", "error")].inc()
                log.error("failures-log compaction failed: %s", e)
                raise
        self._m_compact[("failures", "ok")].inc()
        log.info(
            "compacted %s: %d -> %d bytes (generation %d)",
            self.failures_path, out["bytes_before"], out["bytes_after"],
            out["generation"],
        )
        return out

    def _compact_swap_locked(self) -> dict:
        """The fenced swap — caller holds the snapshot-write lock with the
        just-written snapshot installed; takes ``_lock`` for the swap so
        no append lands between the tail read and the log replace. Every
        file move is temp+fsync+rename inside data_dir."""
        with self._lock:
            sd = self._snapshot_dir()
            manifest_path = sd / "manifest.json"
            manifest = json.loads(manifest_path.read_text())
            offset = int(manifest.get("log_offset", 0))
            size = (
                self.failures_path.stat().st_size
                if self.failures_path.exists()
                else 0
            )
            with self.failures_path.open("rb") as f:
                f.seek(offset)
                tail = f.read()
            # A torn final line the last replay tolerated must not survive
            # into the new log (truncation is the contract, never leniency).
            pend = self._truncate_pending.get(self.failures_path)
            if pend is not None and pend >= offset:
                tail = tail[: pend - offset]
            tmp = self.failures_path.with_suffix(".compact-tmp")
            with tmp.open("wb") as f:
                f.write(tail)
                f.flush()
                os.fsync(f.fileno())
            self._fault_compact_delta.fire()
            gen = self._compact_generation + 1
            manifest["log_offset"] = 0
            manifest["log_hash"] = ""
            manifest["compact"] = {"generation": gen, "ts": time.time()}
            mtmp = sd / "manifest.json.tmp"
            with mtmp.open("w", encoding="utf-8") as f:
                f.write(json.dumps(manifest))
                f.flush()
                os.fsync(f.fileno())
            os.replace(mtmp, manifest_path)
            self._fsync_dir(sd)
            # THE FENCE: from here, replay starts at byte 0 of whichever
            # file is live at failures.jsonl — the full old log (latest-
            # wins convergence) or the delta below; both reach post-state.
            self._fault_compact_fence.fire()
            os.replace(tmp, self.failures_path)
            self._fsync_dir(self.data_dir)
            self._fault_compact_swap.fire()
            # The append handle points at the replaced inode — reopen; and
            # a torn-tail truncation scheduled against the old file must
            # not fire on the new one (the rewrite dropped the torn bytes).
            self._close_locked()
            self._truncate_pending.pop(self.failures_path, None)
            self._compact_generation = gen
            self._last_compact_ts = time.time()
            n_tomb = self._compact_tombstones_locked()
        return {
            "compacted": True,
            "generation": gen,
            "bytes_before": size,
            "bytes_after": len(tail),
            "checkpoint_rows": int(manifest.get("n", 0)),
            "tombstone_lines": n_tomb,
        }

    def _compact_tombstones_locked(self) -> int:
        """Rewrite the tombstone side-log to net state (one "tomb" line
        per currently tombstoned slot) through the same temp+fsync+rename
        seam. A crash mid-rewrite keeps the old log, which replays to the
        same net state. Returns the lines written."""
        if not self._tombstoned and not self.tombstones_path.exists():
            return 0
        lg = self._logs.pop(self.tombstones_path, None)
        if lg is not None:
            lg.close()
        tmp = self.tombstones_path.with_suffix(".compact-tmp")
        try:
            with tmp.open("w", encoding="utf-8") as f:
                for slot in sorted(self._tombstoned):
                    f.write(
                        json.dumps(
                            {
                                "op": "tomb",
                                "id": self._records[slot].failure_id,
                                "reason": self._tombstoned[slot],
                                "ts": self._last_compact_ts,
                            }
                        )
                        + "\n"
                    )
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.tombstones_path)
            self._truncate_pending.pop(self.tombstones_path, None)
            self._m_compact[("tombstones", "ok")].inc()
        except OSError as e:
            log.warning("tombstone-log compaction skipped: %s", e)
            tmp.unlink(missing_ok=True)
            self._m_compact[("tombstones", "error")].inc()
        return len(self._tombstoned)

    def _maybe_auto_compact(self) -> None:
        """Size/age compaction trigger (KAKVEDA_GFKB_COMPACT_BYTES /
        _AGE_S, 0 = off), checked after each ingest batch. The compaction
        runs on a daemon thread — ingest never waits on a checkpoint
        write; one inflight flag keeps it single-flight."""
        if not self.persist or self._compact_inflight:
            return
        if self._compact_bytes <= 0 and self._compact_age_s <= 0:
            return
        if os.environ.get("KAKVEDA_GFKB_COMPACT", "1") == "0":
            return
        try:
            size = self.failures_path.stat().st_size
        except OSError:
            return
        due = self._compact_bytes > 0 and size >= self._compact_bytes
        if not due and self._compact_age_s > 0 and size > 0:
            last = self._last_compact_ts or self._opened_ts
            due = (time.time() - last) >= self._compact_age_s
        if not due:
            return
        with self._lock:
            if self._compact_inflight:
                return
            self._compact_inflight = True

        def _run() -> None:
            try:
                self.compact()
            except Exception as e:  # noqa: BLE001 — never fail/abort ingest
                log.warning("auto-compaction failed (%s: %s)", type(e).__name__, e)
            finally:
                self._compact_inflight = False

        threading.Thread(
            target=_run, name="kakveda-gfkb-compact", daemon=True
        ).start()

    def lifecycle_info(self) -> dict:
        """Durability/lifecycle posture (cli status, tests): tombstone
        counts by reason, compaction generation/timestamp, current
        failures-log byte size."""
        with self._lock:
            by_reason: Dict[str, int] = {}
            for r in self._tombstoned.values():
                by_reason[r] = by_reason.get(r, 0) + 1
            size = 0
            if self.persist:
                try:
                    size = self.failures_path.stat().st_size
                except OSError:
                    size = 0
            return {
                "tombstoned": len(self._tombstoned),
                "by_reason": by_reason,
                "compact_generation": self._compact_generation,
                "last_compact_ts": self._last_compact_ts,
                "failures_log_bytes": size,
            }

    # ------------------------------------------------------------------
    # host tiers (degraded mode, overflow, restore — one hierarchy)
    # ------------------------------------------------------------------

    def _hot_cap(self) -> int:
        """Logical slots the device-hot tier may hold. Unbounded without
        the host tiers (KAKVEDA_HOST_FALLBACK=0 — nothing could absorb an
        overflow) or with tiering off (pre-tiered growth semantics)."""
        if self._tiers is None:
            return 1 << 62
        return self._tiers.cfg.hot_rows

    def _store_tier_rows(
        self, slots, sp_idx: np.ndarray, sp_val: np.ndarray, route: bool = True
    ) -> None:
        """Land freshly embedded rows in the host tiers (warm RAM, or the
        cold memmap past the warm budget) and feed the router's per-batch
        delta update. Rows land BEFORE the device scatter, so a scatter
        that dies on a wedged backend still leaves degraded-mode matching
        complete. ``route=False`` skips the router assignment (snapshot
        restore installs the persisted router state instead)."""
        if self._tiers is None:
            return
        self._tiers.insert(np.asarray(slots, np.int64), sp_idx, sp_val, route=route)

    def index_info(self) -> dict:
        """The device index as it stands, for /readyz: match path, store
        dtype, and where each block of rows actually sits (read off the
        array's own shards, not the mesh that was asked for)."""
        knn, emb = self._view[0], self._view[1]
        info = knn.info()
        info["placement"] = [
            {
                "device": s.device.id,
                "rows": [s.index[0].start or 0, s.index[0].stop or emb.shape[0]],
            }
            for s in emb.addressable_shards
        ]
        return info

    def tiers_info(self) -> dict:
        """Tier residency/routing view (readyz + tests)."""
        if self._tiers is None:
            return {"enabled": False}
        info = self._tiers.info()
        info["enabled"] = True
        return info

    def match_batch_fallback(
        self,
        signature_texts: Sequence[str],
        failure_type: Optional[str] = None,
    ) -> Tuple[List[List[FailureMatch]], dict]:
        """Device-free top-k from the host tiers — the degraded-mode path
        (and the code overflow matching shares). Small corpora take the
        exact inverted-index walk (bit-for-bit the PR-5 fallback scores);
        past the routing floor the IVF router narrows each query to
        ``nprobe`` candidate lists with exact scoring over candidates. A
        routing fault degrades that query to the exact scan — slower,
        never wrong-but-confident. Returns ``(matches, info)`` where
        ``info`` carries the serving ``tier``/``nprobe`` for verdicts.
        ``failure_type`` keeps :meth:`match_batch`'s default
        post-truncation filter semantics."""
        if self._tiers is None:
            raise HostFallbackDisabled(
                "host fallback disabled (KAKVEDA_HOST_FALLBACK=0)"
            )
        q_idx, q_val = self.featurizer.encode_batch_sparse(list(signature_texts))
        with self._lock:
            records = list(self._records)
            tomb = set(self._tombstoned)
        n = len(records)
        if n == 0:
            return [[] for _ in signature_texts], {"tier": "warm", "nprobe": None}
        out: List[List[FailureMatch]] = []
        k = self.top_k
        routed = False
        # One batched host match: candidate dedup + the cold tier's
        # coalesced read plan + (native) scoring run once per warn batch.
        batch = self._tiers.match_host_batch(q_idx, q_val, max(k, 1))
        for scores, slots, mode in batch:
            routed = routed or mode == "routed"
            row: List[FailureMatch] = []
            for s, slot in zip(scores.tolist(), slots.tolist()):
                if s <= 0.0 or slot >= n or slot in tomb:
                    continue  # padding / tombstoned rows never surface
                rec = records[slot]
                if failure_type and rec.failure_type != failure_type:
                    continue
                row.append(
                    FailureMatch(
                        failure_id=rec.failure_id,
                        version=rec.version,
                        score=min(1.0, max(-1.0, float(s))),
                        failure_type=rec.failure_type,
                        suggested_mitigation=rec.resolution,
                    )
                )
            out.append(row)
        self._m_warn_fallback.inc(len(signature_texts))
        info = {
            "tier": "warm_routed" if routed else "warm",
            "nprobe": self._tiers.cfg.nprobe if routed else None,
        }
        return out, info

    # ------------------------------------------------------------------
    # match
    # ------------------------------------------------------------------

    def match(
        self,
        signature_text: str,
        failure_type: Optional[str] = None,
        type_filter: str = "post",
    ) -> List[FailureMatch]:
        return self.match_batch([signature_text], failure_type, type_filter)[0]

    def match_batch(
        self,
        signature_texts: Sequence[str],
        failure_type: Optional[str] = None,
        type_filter: str = "post",
    ) -> List[List[FailureMatch]]:
        return self.match_batch_info(signature_texts, failure_type, type_filter)[0]

    def match_batch_info(
        self,
        signature_texts: Sequence[str],
        failure_type: Optional[str] = None,
        type_filter: str = "post",
    ) -> Tuple[List[List[FailureMatch]], dict]:
        """Top-k similarity matches for a batch of queries (one device call),
        plus serving provenance (``tier``/``nprobe``) for verdicts.

        Slots within the hot cap are answered by the exact device scan;
        when the corpus has overflowed onto the host tiers, each query
        additionally gathers a routed (or exact-degraded) host top-k over
        the overflow slots and the two are merged by score — the device
        stays exact over what it holds, the tiers make the rest
        representable.

        ``type_filter``:
          * ``"post"`` (default) — reference-compatible: the type filter
            applies AFTER top-k truncation, so a filtered query can return
            < k matches even when more of that type exist (the reference's
            observable behavior, services/gfkb/app.py:89-91).
          * ``"pre"`` — device-side pre-selection: the per-slot type id is
            AND-ed into the valid mask BEFORE top-k, so the query returns k
            hits whenever ≥ k failures of that type exist.

        Concurrency design: the query embedding (host work) runs before the
        lock and the result fetch runs after it; the lock covers only the
        async DISPATCH of the top-k (microseconds). Dispatches must be serialized
        with mutators because inserts donate the index buffers and PJRT's
        buffer-hold bookkeeping is not safe against a concurrent reader
        dispatch; once dispatched, execution ordering protects the read.
        Warn latency therefore no longer serializes behind ingest's
        embedding work, capacity-growth re-embeds (both off-lock now), or
        other matches' result fetches.
        """
        # Ledger attribution: any compile or transfer below lands on the
        # warn entry/phase (jits made before the ledger's install, and
        # lambda jits, inherit the ambient entry).
        with _ledger.entry("warn"), _ledger.phase("warn"):
            return self._match_batch_info(signature_texts, failure_type, type_filter)

    def _match_batch_info(
        self,
        signature_texts: Sequence[str],
        failure_type: Optional[str] = None,
        type_filter: str = "post",
    ) -> Tuple[List[List[FailureMatch]], dict]:
        # Sparse query form: (idx, val) pairs ship ~60× fewer bytes per
        # pre-flight check than dense rows; the device densifies before the
        # same top-k (identical scores). topk_async_sparse buckets ragged
        # batches internally.
        with profiling.annotate("gfkb.match.featurize"):
            q_idx, q_val = self.featurizer.encode_batch_sparse(list(signature_texts))
        b = q_idx.shape[0]

        with self._lock:
            knn, emb, valid, types, records = self._view
            # Tombstone filter set: device rows are zeroed (score 0, never
            # outrank a real match) but can still occupy candidate
            # positions — the assembly drop below is what guarantees a
            # retired row never surfaces in a verdict.
            tomb = set(self._tombstoned) if self._tombstoned else ()
            n = len(records)
            if n == 0:
                return [[] for _ in signature_texts], {"tier": "hot", "nprobe": None}
            tid = None
            if type_filter == "pre" and failure_type is not None:
                tid = self._type_ids.get(failure_type)
                if tid is None:
                    return [[] for _ in signature_texts], {"tier": "hot", "nprobe": None}
            with profiling.annotate("gfkb.match.dispatch"):
                # Device-loss drill point: armed, the dispatch dies the way
                # a wedged backend does, and the warn path's degraded-mode
                # fallback (WarningPolicy → match_batch_fallback) takes over.
                self._fault_device.fire()
                if tid is not None:
                    valid = knn.mask_valid(valid, types, tid)
                packed = knn.topk_async_sparse(emb, valid, q_idx, q_val)
        with profiling.annotate("gfkb.match.fetch"):
            scores, slots = knn.topk_result(packed)
        with profiling.annotate("gfkb.match.assemble"):
            info = {"tier": "hot", "nprobe": None}
            hot = self._hot_cap()
            if n > hot and self._tiers is not None:
                # Overflow: merge the device's exact hot top-k with the host
                # tiers' (routed) top-k over slots the device doesn't hold.
                modes: set = set()
                m_scores, m_slots = [], []
                k = scores.shape[1]
                overflow = self._tiers.match_host_batch(q_idx, q_val, k, min_slot=hot)
                for i in range(b):
                    o_s, o_sl, mode = overflow[i]
                    modes.add(mode)
                    if tid is not None and len(o_sl):
                        keep = np.asarray(
                            [records[int(s)].failure_type == failure_type for s in o_sl]
                        )
                        o_s, o_sl = o_s[keep], o_sl[keep]
                    cs = np.concatenate([scores[i], o_s])
                    csl = np.concatenate([slots[i], o_sl])
                    order = np.argsort(-cs)[:k]
                    m_scores.append(cs[order])
                    m_slots.append(csl[order])
                scores = np.stack(m_scores)
                slots = np.stack(m_slots)
                if "fault_exact" in modes:
                    info = {"tier": "tiered_fault", "nprobe": None}
                elif modes == {"routed"}:
                    info = {"tier": "tiered", "nprobe": self._tiers.cfg.nprobe}
                else:
                    info = {"tier": "tiered_exact", "nprobe": None}

            if self._mine is not None and self._match_cache_max > 0 and failure_type is None:
                # Remember the fetched neighbors per signature: a pre-flight
                # warn is usually followed by the SAME signature being
                # ingested when the trace fails, and these rows make its
                # cluster attachment free (no extra device dispatch).
                with self._lock:
                    gen_now = self._generation
                    for i in range(b):
                        self._match_cache[signature_texts[i]] = (
                            scores[i], slots[i], gen_now
                        )
                        self._match_cache.move_to_end(signature_texts[i])
                    while len(self._match_cache) > self._match_cache_max:
                        self._match_cache.popitem(last=False)

            out: List[List[FailureMatch]] = []
            for i in range(b):
                row: List[FailureMatch] = []
                for s, slot in zip(scores[i], slots[i]):
                    if s <= -1.0 or slot >= n or int(slot) in tomb:
                        continue  # padding / invalid / tombstoned rows
                    rec = records[int(slot)]
                    if failure_type and rec.failure_type != failure_type:
                        continue
                    row.append(
                        FailureMatch(
                            failure_id=rec.failure_id,
                            version=rec.version,
                            # f32 accumulation can nudge an exact self-match a hair
                            # past 1.0; cosine is bounded, so clamp.
                            score=min(1.0, max(-1.0, float(s))),
                            failure_type=rec.failure_type,
                            suggested_mitigation=rec.resolution,
                        )
                    )
                out.append(row)
        return out, info

    # ------------------------------------------------------------------
    # patterns
    # ------------------------------------------------------------------

    def _merge_pattern_line(self, p: PatternEntity) -> None:
        """Union one log line into the in-memory state (replay path). Works
        for both delta lines and legacy full-membership lines."""
        st = self._pattern_state.get(p.name)
        if st is None:
            self._pattern_state[p.name] = {
                "pattern_id": p.pattern_id,
                "name": p.name,
                "created_at": p.created_at,
                "fid_list": list(dict.fromkeys(p.failure_ids)),
                "fid_set": set(p.failure_ids),
                "app_list": list(dict.fromkeys(p.affected_apps)),
                "app_set": set(p.affected_apps),
                "description": p.description,
            }
            return
        for f in p.failure_ids:
            if f not in st["fid_set"]:
                st["fid_set"].add(f)
                st["fid_list"].append(f)
        for a in p.affected_apps:
            if a not in st["app_set"]:
                st["app_set"].add(a)
                st["app_list"].append(a)
        if p.description:
            st["description"] = p.description

    def _pattern_view(self, st: dict) -> PatternEntity:
        """Materialized read view. Lists are copied so callers can't mutate
        live state; membership order is insertion order (first-seen), not
        lexicographic — sorting N ids per upsert is exactly the O(N log N)
        per-batch cost the delta design removes."""
        return PatternEntity.model_construct(
            pattern_id=st["pattern_id"],
            name=st["name"],
            created_at=st["created_at"],
            failure_ids=list(st["fid_list"]),
            affected_apps=list(st["app_list"]),
            description=st["description"],
        )

    def list_patterns(self) -> List[PatternEntity]:
        """Latest state per pattern (dedup-for-presentation, like the
        reference's GET /patterns, services/gfkb/app.py:150-157)."""
        with self._lock:
            return [self._pattern_view(st) for st in self._pattern_state.values()]

    def pattern_id(self, name: str) -> Optional[str]:
        """Id of the pattern named ``name``, or None: one dict read under the
        lock, where ``list_patterns()`` copies every pattern's failure ids."""
        with self._lock:
            st = self._pattern_state.get(name)
            return st["pattern_id"] if st is not None else None

    def upsert_pattern(
        self,
        *,
        name: str,
        failure_ids: Sequence[str],
        affected_apps: Sequence[str],
        description: Optional[str] = None,
    ) -> Tuple[PatternEntity, bool]:
        """Identity-by-name pattern upsert with set-union merge
        (reference: services/gfkb/app.py:168-198).

        Streaming-safe: the in-memory union is set-backed (O(delta) per
        call), only the *new* members are appended to the log, and a no-op
        upsert (nothing new) skips the append entirely."""
        with self._lock:
            st = self._pattern_state.get(name)
            created = st is None
            if created:
                st = {
                    "pattern_id": f"FP-{len(self._pattern_state) + 1:04d}",
                    "name": name,
                    "created_at": utcnow(),
                    "fid_list": [],
                    "fid_set": set(),
                    "app_list": [],
                    "app_set": set(),
                    "description": description,
                }
                self._pattern_state[name] = st
            new_f = [f for f in dict.fromkeys(failure_ids) if f not in st["fid_set"]]
            new_a = [a for a in dict.fromkeys(affected_apps) if a not in st["app_set"]]
            desc_changed = bool(description) and description != st["description"]
            if not created and not new_f and not new_a and not desc_changed:
                return self._pattern_view(st), False
            st["fid_list"].extend(new_f)
            st["fid_set"].update(new_f)
            st["app_list"].extend(new_a)
            st["app_set"].update(new_a)
            if description:
                st["description"] = description
            delta = PatternEntity.model_construct(
                pattern_id=st["pattern_id"],
                name=name,
                created_at=st["created_at"],
                failure_ids=new_f,
                affected_apps=new_a,
                description=st["description"],
            )
            self._append_line(self.patterns_path, delta.model_dump_json())
            self._flush_logs()
            return self._pattern_view(st), created
