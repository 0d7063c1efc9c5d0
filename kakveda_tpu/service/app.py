"""The platform's HTTP surface — reference REST contracts on one port.

Route map (reference originals in parentheses):

  POST /ingest                  (ingestion:8102, services/ingestion/app.py:15)
  POST /warn                    (warning-policy:8105, services/warning_policy/app.py:19)
  GET  /failures                (gfkb:8101, services/gfkb/app.py:74)
  POST /failures/match          (gfkb, services/gfkb/app.py:79)
  POST /failures/upsert         (gfkb, services/gfkb/app.py:105)
  GET  /patterns                (gfkb, services/gfkb/app.py:150)
  POST /patterns/upsert         (gfkb, services/gfkb/app.py:168)
  GET  /health/{app_id}         (health-scoring:8106, services/health_scoring/app.py:116)
  POST /subscribe /publish, GET /topics
                                (event-bus:8100, services/event_bus/app.py:28-59)
  GET  /healthz /readyz         (liveness/readiness)
  GET  /metrics /flightrecorder (metrics plane — Prometheus exposition +
                                 serving flight-recorder dump; also mounted
                                 on the dashboard. docs/observability.md)

The warn route drains through a MicroBatcher so concurrent pre-flight
checks share one device call. External subscribers registered via
/subscribe get HTTP callbacks exactly like the reference bus delivered.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

from aiohttp import web
from pydantic import ValidationError

from kakveda_tpu.core import admission as _admission
from kakveda_tpu.core import faults as _faults
from kakveda_tpu.core.admission import DeviceUnavailableError, OverloadError
from kakveda_tpu.core.profiling import observe_phase
from kakveda_tpu.core import sanitize
from kakveda_tpu.core import trace as _trace
from kakveda_tpu.core.runtime import ensure_request_id, get_runtime_config
from kakveda_tpu.core.schemas import (
    FailureMatchRequest,
    IngestBatchRequest,
    IngestRequest,
    Severity,
    WarningRequest,
)
from kakveda_tpu.platform import Platform
from kakveda_tpu.service.batcher import MicroBatcher

log = logging.getLogger("kakveda.service")


def _native_status() -> dict:
    """Native library load/build status for /readyz (ISSUE 11): operators
    see at a glance whether the host-tier scoring engine is live or the
    process is running on the numpy fallbacks."""
    from kakveda_tpu import native as _native

    return _native.status()

PLATFORM_KEY: web.AppKey[Platform] = web.AppKey("platform", Platform)
WARN_BATCHER_KEY: web.AppKey[MicroBatcher] = web.AppKey("warn_batcher", MicroBatcher)
_GOSSIP_TASK_KEY: web.AppKey[object] = web.AppKey("fleet_gossip_task", object)
_STALL_WATCHDOG_KEY: web.AppKey[object] = web.AppKey("sanitize_stall_watchdog", object)

# Chaos site for the HTTP tier, resolved once at import: an armed
# service.handler fault turns a request into a clean 500 before its
# handler runs — proving callers survive the platform's own API failing.
_FAULT_HANDLER = _faults.site("service.handler")
# Fleet replication apply (docs/robustness.md): armed, a peer's
# /replicate apply dies with a clean 500 — the publishing bus retries,
# breaks, dead-letters, and `dlq replay` converges the gap later. Never
# a lost row, never a failed ingest at the origin.
_FAULT_REPLICATE = _faults.site("fleet.replicate_apply")


def _json_error(status: int, message: str) -> web.Response:
    return web.json_response({"ok": False, "error": message}, status=status)


def overload_response(e: OverloadError) -> web.Response:
    """THE 429 shape — admission sheds, brownout rejections and the
    per-client token bucket all answer identically: a ``Retry-After``
    header plus the hint repeated in the JSON body for clients that
    only read bodies."""
    return web.json_response(
        {
            "ok": False,
            "error": str(e),
            "retry_after": round(e.retry_after, 2),
            "reason": e.reason or "overload",
        },
        status=429,
        headers={"Retry-After": str(max(1, int(round(e.retry_after))))},
    )


def degraded_response(e: DeviceUnavailableError) -> web.Response:
    """503 for device-loss degraded mode: retryable by contract — the
    background probe un-latches when the chip answers again."""
    return web.json_response(
        {
            "ok": False,
            "error": str(e),
            "retry_after": round(e.retry_after, 2),
            "degraded": True,
        },
        status=503,
        headers={"Retry-After": str(max(1, int(round(e.retry_after))))},
    )


def metrics_routes() -> list:
    """The metrics-plane routes, shared by the service app AND the
    dashboard (one registry per process — scraping either port sees the
    whole picture):

      GET /metrics         Prometheus text exposition of the process-global
                           registry (serving lifecycle, spec gate, pipeline,
                           bus — see docs/observability.md for the catalog).
      GET /flightrecorder  JSON dump of every live flight recorder's ring
                           (recent request timelines + gate/k transitions
                           per serving engine).
    """
    from kakveda_tpu.core import metrics as _metrics

    async def metrics_ep(request):
        return web.Response(
            body=_metrics.get_registry().render().encode("utf-8"),
            headers={"Content-Type": _metrics.PROMETHEUS_CONTENT_TYPE},
        )

    async def flightrecorder_ep(request):
        return web.json_response({"recorders": _metrics.dump_recorders()})

    async def trace_ring_ep(request):
        tr = _trace.get_tracer()
        try:
            limit = int(request.query["n"]) if "n" in request.query else None
        except ValueError:
            limit = None
        return web.json_response(
            {"plane": tr.plane(), "spans": tr.dump(limit=limit)}
        )

    async def trace_one_ep(request):
        tid = request.match_info["trace_id"]
        return web.json_response(
            {"trace_id": tid, "spans": _trace.get_tracer().dump(tid)}
        )

    return [
        web.get("/metrics", metrics_ep),
        web.get("/flightrecorder", flightrecorder_ep),
        web.get("/trace", trace_ring_ep),
        web.get("/trace/{trace_id}", trace_one_ep),
    ]


@web.middleware
async def request_context_middleware(request: web.Request, handler):
    """Request id + duration logging (reference: dashboard app.py:590-611).

    When the otel middleware runs outside this one it already resolved the
    request id (and put it on the span); reuse it so logs, the echoed
    header and the trace all carry ONE id."""
    cfg = get_runtime_config(service_name="kakveda-tpu")
    rid = request.get("request_id") or ensure_request_id(
        request.headers.get(cfg.request_id_header)
    )
    # Causal trace (core/trace.py): extract the incoming W3C context or
    # start a new root that FOLDS the request id (ensure_request_id mints
    # 32 lowercase hex — a valid trace id), so logs, the echoed header and
    # the cross-process span tree all join on one key. Handlers reach the
    # span via request["trace_span"] to attach provenance.
    span = _trace.get_tracer().start_span(
        "service.request",
        traceparent=request.headers.get(_trace.TRACEPARENT_HEADER),
        trace_id=rid,
        path=request.path,
        method=request.method,
        rid=rid,
    )
    request["trace_span"] = span
    span.activate()
    started = time.perf_counter()
    try:
        _FAULT_HANDLER.fire()
        response = await handler(request)
    except _faults.FaultInjected as e:
        response = _json_error(500, str(e))
    except OverloadError as e:
        # Shed by admission control / brownout / rate limit anywhere under
        # the handler: ONE conversion point to 429 + Retry-After.
        response = overload_response(e)
    except DeviceUnavailableError as e:
        response = degraded_response(e)
    except web.HTTPException as e:
        e.headers[cfg.request_id_header] = rid
        span.deactivate()
        span.end(
            "error" if e.status >= 500
            else "shed" if e.status == 429
            else span.outcome,
            status=e.status,
        )
        raise
    except BaseException:
        span.deactivate()
        span.end("error")
        raise
    duration_ms = int((time.perf_counter() - started) * 1000)
    response.headers[cfg.request_id_header] = rid
    span.deactivate()
    span.end(
        "shed" if response.status == 429
        else "degraded" if response.status == 503
        else "error" if response.status >= 500
        else span.outcome,  # a 200 degraded-warn handler may have marked it
        status=response.status,
    )
    log.info(
        "request",
        extra={
            "request_id": rid,
            "path": request.path,
            "method": request.method,
            "status_code": response.status,
            "duration_ms": duration_ms,
        },
    )
    return response


def make_app(
    platform: Optional[Platform] = None,
    admission: Optional[_admission.AdmissionController] = None,
    **platform_kw,
) -> web.Application:
    plat = platform or Platform(**platform_kw)
    from kakveda_tpu.core import otel

    # Overload protection (core/admission.py): bounded per-class admission
    # ahead of every queue, with 429 + Retry-After shedding (converted by
    # the middleware above). Process-global by default so the serving
    # engine and this app see ONE pressure picture; tests inject private
    # controllers.
    adm = admission if admission is not None else _admission.get_admission()
    health = _admission.get_device_health()

    # Traffic capture (kakveda_tpu/traffic/capture.py): every warn/ingest
    # arrival lands in this bounded ring so `traffic record` can pull GET
    # /flightrecorder and convert the timeline into a replayable traffic
    # log. One deque append per request when enabled; KAKVEDA_TRAFFIC_
    # CAPTURE=0 makes record() a no-op (capacity 0).
    from kakveda_tpu.core.metrics import FlightRecorder

    _cap_on = os.environ.get("KAKVEDA_TRAFFIC_CAPTURE", "1") != "0"
    traffic_rec = FlightRecorder(
        "traffic",
        capacity=int(os.environ.get("KAKVEDA_TRAFFIC_CAPTURE_N", "2048"))
        if _cap_on else 0,
    )

    # Optional per-client token bucket (KAKVEDA_RATELIMIT_RPS) on the
    # unauthenticated write path — same 429 shape as admission sheds.
    rl_rps = float(os.environ.get("KAKVEDA_RATELIMIT_RPS", "0") or 0)
    bucket = None
    if rl_rps > 0:
        from kakveda_tpu.core.ratelimit import TokenBucket

        burst = os.environ.get("KAKVEDA_RATELIMIT_BURST")
        bucket = TokenBucket(rl_rps, float(burst) if burst else None)

    def _ratelimit(request) -> None:
        if bucket is None:
            return
        ok, ra = bucket.allow(request.remote or "anon")
        if not ok:
            adm.note_shed("ingest", "ratelimit", retry_after=ra)
            raise OverloadError(
                f"per-client rate limit exceeded ({rl_rps:g} rps)",
                retry_after=ra, klass="ingest", reason="ratelimit",
            )

    middlewares = [request_context_middleware]
    if otel.setup_otel("platform"):
        middlewares.insert(0, otel.otel_middleware())
    app = web.Application(middlewares=middlewares)
    app[PLATFORM_KEY] = plat

    # Trace provenance resolved ONCE at construction (hot paths must not
    # re-derive it per request): recorded spans carry the replica id, and
    # warn spans note whether the native scorer could have served them.
    _trace.get_tracer().service = plat.replica_id or ""
    _native_avail = bool(_native_status().get("available"))
    from kakveda_tpu.core import metrics as _metrics_reg

    _h_warn = _metrics_reg.get_registry().histogram(
        "kakveda_warn_request_seconds",
        "End-to-end /warn wall inside the service handler "
        "(exemplar-linked to its trace id)",
    )

    # Micro-batcher shape is operator surface now that fleets tune it per
    # replica (docs/scale-out.md): KAKVEDA_WARN_MAX_BATCH coalesced
    # requests per device call, KAKVEDA_WARN_DEADLINE_MS straggler wait.
    warn_max_batch = int(os.environ.get("KAKVEDA_WARN_MAX_BATCH", "64") or 64)
    warn_deadline_s = float(os.environ.get("KAKVEDA_WARN_DEADLINE_MS", "2") or 2) / 1e3
    warn_batcher: MicroBatcher = MicroBatcher(
        plat.warn_batch, max_batch=warn_max_batch, deadline_s=warn_deadline_s,
        max_queue=adm.limits["warn"], admission=adm,
        # Tenant identity for weighted-fair batch composition + the
        # tenant-aware queue bound (docs/robustness.md § multi-tenancy).
        # The warn body is parsed BEFORE submit, so — unlike the ingest
        # slots, which shed pre-parse by contract and stay tenant-blind —
        # the app key is free here.
        tenant_key=lambda r: r.app_id,
    )
    app[WARN_BATCHER_KEY] = warn_batcher

    # Fleet wiring (docs/scale-out.md): a replica spawned by
    # `cli up --replicas N` carries its identity in env. Peers are
    # subscribed on the local bus so accepted ingest replicates out
    # (gfkb.replicate, at-least-once) and control state gossips out
    # (fleet.control, ephemeral); stale fleet subscriptions from a
    # previous topology are pruned so dead URLs don't burn the breaker.
    from kakveda_tpu.events.bus import TOPIC_FLEET_CONTROL, TOPIC_GFKB_REPLICATE
    from kakveda_tpu.fleet.gossip import FleetView, GossipPublisher

    replica_id = os.environ.get("KAKVEDA_REPLICA_ID", "")
    fleet_peers = [
        u.strip().rstrip("/")
        for u in (os.environ.get("KAKVEDA_FLEET_PEERS", "") or "").split(",")
        if u.strip()
    ]
    gossip_ttl = float(os.environ.get("KAKVEDA_FLEET_GOSSIP_TTL_S", "5") or 5)
    fleet_view = FleetView(ttl_s=gossip_ttl)

    # Sharded ownership (KAKVEDA_FLEET_OWNERSHIP=1, fleet/ownership.py):
    # this replica holds only its owned + standby key ranges; replication
    # is range-scoped on per-peer topics and /replicate fences stale-epoch
    # events. The acknowledged view persists (data_dir/ownership.json) so
    # a restart mid-topology-change resumes at the epoch it had — the
    # spawn env only seeds epoch 1. Off (default): legacy full
    # replication, bit-for-bit.
    own_state = None
    own_path = plat.data_dir / "ownership.json"
    if os.environ.get("KAKVEDA_FLEET_OWNERSHIP", "0") == "1":
        from kakveda_tpu.fleet.ownership import (
            OwnershipState,
            OwnershipView,
            parse_members,
        )

        members = parse_members(os.environ.get("KAKVEDA_FLEET_MEMBERS", ""))
        if not members:  # solo dev run: self owns everything
            members = {replica_id or "r?": ""}
        env_view = OwnershipView(
            members,
            replication=int(os.environ.get("KAKVEDA_FLEET_REPLICATION", "2") or 2),
            vnodes=int(os.environ.get("KAKVEDA_FLEET_VNODES", "64") or 64),
        )
        persisted = OwnershipView.load(own_path)
        own_state = OwnershipState(
            persisted
            if persisted is not None and persisted.epoch > env_view.epoch
            else env_view,
            replica_id or "r?",
        )
        plat.ownership = own_state

    def _sync_fleet_subscriptions() -> None:
        """Ownership-mode bus wiring, re-run on every acknowledged view
        swap: gossip goes to every current member, replication rides ONE
        per-destination topic per peer (own retry/breaker/DLQ lane each),
        and topics of departed members — plus any legacy broadcast
        subscription — are pruned so dead URLs don't burn breakers."""
        from kakveda_tpu.events.bus import (
            TOPIC_GFKB_REPLICATE_PREFIX,
            replicate_topic,
        )

        view = own_state.view
        self_id = own_state.self_id
        want = {
            TOPIC_FLEET_CONTROL: {
                url + "/fleet/gossip"
                for rid, url in view.members.items()
                if rid != self_id and url
            },
            TOPIC_GFKB_REPLICATE: set(),  # never broadcast under ownership
        }
        for rid, url in view.members.items():
            if rid != self_id and url:
                want[replicate_topic(rid)] = {url + "/replicate"}
        for topic in list(plat.bus.topics()):
            if topic.startswith(TOPIC_GFKB_REPLICATE_PREFIX) and topic not in want:
                want[topic] = set()  # departed member
        for topic, urls in want.items():
            for url in plat.bus.url_subscribers(topic):
                if url not in urls:
                    plat.bus.unsubscribe(topic, url)
            for url in sorted(urls):
                plat.bus.subscribe(topic, url)

    gossip: Optional[GossipPublisher] = None
    if own_state is not None and (fleet_peers or len(own_state.view.members) > 1):
        plat.bus.mark_ephemeral(TOPIC_FLEET_CONTROL)
        _sync_fleet_subscriptions()
        gossip = GossipPublisher(
            plat.bus, adm, health, replica_id or "r?", fleet_view,
            interval_s=float(os.environ.get("KAKVEDA_FLEET_GOSSIP_S", "1") or 1),
            ownership=own_state,
        )
    elif fleet_peers:
        plat.bus.mark_ephemeral(TOPIC_FLEET_CONTROL)
        for topic, suffix in (
            (TOPIC_FLEET_CONTROL, "/fleet/gossip"),
            (TOPIC_GFKB_REPLICATE, "/replicate"),
        ):
            want = {p + suffix for p in fleet_peers}
            for url in plat.bus.url_subscribers(topic):
                if url not in want:
                    plat.bus.unsubscribe(topic, url)
            for url in sorted(want):
                plat.bus.subscribe(topic, url)
        gossip = GossipPublisher(
            plat.bus, adm, health, replica_id or "r?", fleet_view,
            interval_s=float(os.environ.get("KAKVEDA_FLEET_GOSSIP_S", "1") or 1),
        )

    async def _on_startup(app):
        warn_batcher.start()
        if gossip is not None:
            import asyncio as _asyncio

            app[_GOSSIP_TASK_KEY] = _asyncio.get_running_loop().create_task(
                gossip.run()
            )
        if sanitize.enabled():
            # Loop-stall watchdog: the runtime half of the static
            # event-loop-blocking rule. Stalls past
            # KAKVEDA_SANITIZE_STALL_MS dump the loop thread's stack to
            # the sanitizer flight recorder (docs/robustness.md).
            wd = sanitize.LoopStallWatchdog()
            await wd.start()
            app[_STALL_WATCHDOG_KEY] = wd

    async def _on_cleanup(app):
        wd = app.get(_STALL_WATCHDOG_KEY)
        if wd is not None:
            await wd.stop()
        t = app.get(_GOSSIP_TASK_KEY)
        if t is not None:
            import asyncio as _asyncio

            t.cancel()
            try:
                await t
            except _asyncio.CancelledError:
                pass
        await warn_batcher.stop()
        plat.bus.close()  # cancel a pending DLQ auto-replay timer

    app.on_startup.append(_on_startup)
    app.on_cleanup.append(_on_cleanup)

    # --- liveness -------------------------------------------------------

    async def healthz(request):
        return web.json_response({"ok": True})

    async def readyz(request):
        """Readiness WITH mode report: degraded (device loss) and the
        brownout ladder are operating states a balancer/operator must see
        — a degraded platform still answers warns (host fallback), so
        ok stays true; routing decisions read the mode fields."""
        from kakveda_tpu.models.attention import traced_paths
        from kakveda_tpu.ops.device import device_report

        body = {
            "ok": True,
            "gfkb_count": plat.gfkb.count,
            # What this process runs on, beside whether it still does:
            # platform/kind/count as JAX reports them, the index's match
            # path and row placement, and the attention path each compiled
            # engine program took.
            "device": {
                **health.info(),
                **device_report(),
                "index": plat.gfkb.index_info(),
                "attention_paths": traced_paths(),
            },
            "admission": adm.info(),
            "tiers": plat.gfkb.tiers_info(),
            "native": _native_status(),
        }
        body["fleet"] = {
            "replica_id": replica_id,
            "peers": len(fleet_peers),
            "view": fleet_view.peers(),
            "degraded_any": fleet_view.any_degraded(),
            "worst_brownout": fleet_view.worst_brownout(),
        }
        if own_state is not None:
            view = own_state.view
            owned_arcs, standby_arcs = view.arc_counts(own_state.self_id)
            rows = {"owned": 0, "standby": 0, "foreign": 0}
            # O(distinct shard keys) — app counts, not row scans, per probe.
            for key, n in plat.gfkb.shard_key_counts().items():
                role = view.role(own_state.self_id, key)
                bucket = role if role in ("owner", "standby") else "foreign"
                rows["owned" if bucket == "owner" else bucket] += n
            body["ownership"] = {
                "enabled": True,
                "epoch": view.epoch,
                "replication": view.replication,
                "members": list(view.members),
                "owned_arcs": owned_arcs,
                "standby_arcs": standby_arcs,
                "rows": rows,
            }
        return web.json_response(body)

    # --- ingest ---------------------------------------------------------

    async def ingest(request):
        # Admission runs BEFORE the body is parsed: a shed must cost
        # microseconds, and pydantic-validating a payload we are about to
        # 429 would burn the event-loop time the shed exists to protect.
        _ratelimit(request)
        with adm.slot("ingest"):
            try:
                req = IngestRequest.model_validate(await request.json())
            except (ValidationError, ValueError) as e:
                return _json_error(422, str(e))
            traffic_rec.record("ingest", app_id=req.trace.app_id, n=1)
            with _trace.get_tracer().start_span(
                "gfkb.ingest", app_id=req.trace.app_id, n=1
            ):
                await plat.ingest(req.trace)
        return web.json_response({"ok": True, "trace_id": req.trace.trace_id})

    async def ingest_batch(request):
        """Batched ingest — one validate + one device scatter per batch
        (kakveda_tpu.platform.Platform.ingest_batch), the rate the
        streaming pipeline actually sustains. Returns per-batch failure
        count so callers can track detection rates without a second call.
        Admission gates BEFORE the body parse (shed-while-cheap): under a
        flood, a 429 costs no JSON decode and no pydantic pass — measured
        in the overload bench, validating shed batches was most of the
        event-loop damage."""
        _ratelimit(request)
        with adm.slot("ingest"):
            try:
                req = IngestBatchRequest.model_validate(await request.json())
            except (ValidationError, ValueError) as e:
                return _json_error(422, str(e))
            if not req.traces:
                return web.json_response({"ok": True, "n": 0, "failures": 0})
            traffic_rec.record(
                "ingest", app_id=req.traces[0].app_id, n=len(req.traces)
            )
            with _trace.get_tracer().start_span(
                "gfkb.ingest", app_id=req.traces[0].app_id, n=len(req.traces)
            ):
                signals = await plat.ingest_batch(req.traces)
        return web.json_response(
            {"ok": True, "n": len(req.traces), "failures": len(signals)}
        )

    # --- fleet (replication fan-in + control gossip) --------------------

    _m_fence = None
    _m_stale_view = None
    if own_state is not None:
        from kakveda_tpu.core import metrics as _metrics_mod

        _own_reg = _metrics_mod.get_registry()
        _m_fence = _own_reg.counter(
            "kakveda_fleet_fenced_rows_total",
            "Replicated rows dropped by the ownership-epoch fence (stale "
            "events for ranges this replica no longer holds)",
        )
        _m_stale_view = _own_reg.counter(
            "kakveda_fleet_stale_view_total",
            "Gossip samples revealing a peer at a newer ownership epoch "
            "than the locally acknowledged view",
        )

    async def replicate(request):
        """Apply one bus-replicated ingest event from a peer replica —
        idempotent by event id (GFKB dedup set), through the tiered
        insert path. A failure here (chaos: fleet.replicate_apply) is a
        clean 500 back to the peer's bus, whose retry/breaker/DLQ policy
        owns redelivery; a 429 shed behaves the same way. Either way the
        event converges later — it is never silently dropped here.

        Ownership-epoch fence: a scoped event stamped with an OLDER epoch
        than the acknowledged view (a DLQ replay or straggler retry from
        before a migration) keeps only the rows this replica still holds;
        an event left with none is acknowledged as a clean drop — 2xx, so
        the origin's at-least-once machinery retires it instead of
        retrying a range that migrated away. Rows this replica DOES still
        hold apply idempotently as ever — never a double insert, never an
        un-migrate."""
        try:
            body = await request.json()
        except ValueError as e:
            return _json_error(422, str(e))
        event_id, rows = body.get("id"), body.get("rows")
        if not isinstance(event_id, str) or not isinstance(rows, list):
            return _json_error(422, "id (str) and rows (list) required")
        # Continue the ORIGIN's trace (envelope "trace" stamp, set by
        # Platform.replicate_rows) — replication, DLQ dead-letter and
        # `dlq replay` redelivery all correlate back to the ingest that
        # produced the rows. No stamp → parent under the local request.
        with _trace.get_tracer().start_span(
            "gfkb.replicate_apply",
            traceparent=body.get("trace") or None,
            origin=body.get("origin"), event_id=event_id, n=len(rows),
        ) as rspan:
            dropped = 0
            epoch = body.get("epoch")
            if isinstance(epoch, int):
                rspan.set(epoch=epoch)
            if (
                own_state is not None
                and isinstance(epoch, int)
                and epoch < own_state.view.epoch
            ):
                from kakveda_tpu.fleet.ownership import shard_key_of_row

                view = own_state.view
                kept = [
                    r for r in rows
                    if isinstance(r, dict)
                    and view.is_holder(own_state.self_id, shard_key_of_row(r))
                ]
                dropped = len(rows) - len(kept)
                if dropped:
                    _m_fence.inc(dropped)
                if not kept:
                    rspan.set(dropped=dropped, reason="stale_epoch")
                    return web.json_response(
                        {"ok": True, "applied": 0, "deduped": False,
                         "dropped": dropped, "reason": "stale_epoch"}
                    )
                rows = kept
            _FAULT_REPLICATE.fire()
            import asyncio as _asyncio

            loop = _asyncio.get_running_loop()
            with adm.slot("ingest"):
                try:
                    applied = await loop.run_in_executor(
                        None, plat.gfkb.apply_replication, rows, event_id
                    )
                except (KeyError, ValueError) as e:  # malformed row payload
                    rspan.set(error=type(e).__name__)
                    rspan.end("error")
                    return _json_error(422, f"bad replication rows: {e}")
            rspan.set(applied=applied, deduped=applied == 0)
            out = {"ok": True, "applied": applied, "deduped": applied == 0}
            if dropped:
                out["dropped"] = dropped
            return web.json_response(out)

    async def fleet_ownership_get(request):
        if own_state is None:
            return web.json_response({"enabled": False})
        return web.json_response({"enabled": True, **own_state.view.to_dict()})

    async def fleet_ownership_post(request):
        """Acknowledge a new epoch'd ownership view (the router's
        promotion push, or the rebalance flip). Monotonic: an epoch at or
        below the acknowledged one is a no-op ``stale`` ack — pushes may
        arrive out of order and replays must not regress the view. A real
        swap persists atomically and rewires the per-peer replication
        topics before returning."""
        if own_state is None:
            return _json_error(409, "ownership disabled on this replica")
        from kakveda_tpu.fleet.ownership import OwnershipView

        try:
            new_view = OwnershipView.from_dict(await request.json())
        except (ValueError, KeyError, TypeError) as e:
            return _json_error(422, f"bad ownership view: {e}")
        cur = own_state.view
        if new_view.epoch <= cur.epoch:
            return web.json_response(
                {"ok": True, "stale": True, "epoch": cur.epoch}
            )
        own_state.view = new_view  # one reference write — readers swap whole
        try:
            new_view.save(own_path)
        except OSError as e:
            log.warning("ownership view persist failed: %s", e)
        _sync_fleet_subscriptions()
        log.info(
            "ownership epoch %d -> %d (%d members)",
            cur.epoch, new_view.epoch, len(new_view.members),
        )
        return web.json_response(
            {"ok": True, "stale": False, "epoch": new_view.epoch}
        )

    # Migration export is CONTROL PLANE, not tenant background work: the
    # flood that trips the autoscaler is the same flood a background
    # admission slot would shed this ship behind, and a fleet that cannot
    # migrate while saturated can never scale OUT of saturation
    # (metastable). Bounded by its own tiny in-flight counter instead —
    # shed-never-hang still holds: past the bound it 429s immediately and
    # the router's next rebalance attempt retries.
    export_inflight = 0

    async def fleet_export(request):
        """Migration export (fleet/ownership.py run_rebalance): the rows
        past ``since`` that THIS replica is the responsible source for,
        grouped by gaining target. Pure read — rows ship as replication
        dicts and re-embed deterministically at the target (hashed n-gram
        featurizer), so no vector payloads cross the wire. Runs off the
        event loop under its own control-plane bound (never the
        background class — tenant floods must not starve a migration)."""
        if own_state is None:
            return _json_error(409, "ownership disabled on this replica")
        from kakveda_tpu.fleet.ownership import (
            OwnershipView,
            plan_targets,
            responsible_source,
            shard_key_of_row,
        )

        try:
            body = await request.json()
            old_v = OwnershipView.from_dict(body["old"])
            new_v = OwnershipView.from_dict(body["new"])
            sources = [str(s) for s in body.get("sources") or []]
            since = int(body.get("since", 0))
        except (ValueError, KeyError, TypeError) as e:
            return _json_error(422, f"bad export request: {e}")
        import asyncio as _asyncio

        nonlocal export_inflight
        if export_inflight >= 2:
            return _json_error(429, "export concurrency bound")
        loop = _asyncio.get_running_loop()
        export_inflight += 1
        try:
            rows, count = await loop.run_in_executor(
                None, plat.gfkb.export_rows, since
            )
        finally:
            export_inflight -= 1
        grouped: dict = {}
        for row in rows:
            key = shard_key_of_row(row)
            if responsible_source(key, old_v, sources) != own_state.self_id:
                continue
            for tgt in plan_targets(key, old_v, new_v):
                grouped.setdefault(tgt, []).append(row)
        return web.json_response({"rows": grouped, "count": count})

    async def fleet_gossip(request):
        """Fold one peer control sample into the fleet view and re-feed
        the folded pressure into the local admission controller (an input
        — gate state only ever moves through the controller's own
        single-writer helpers)."""
        try:
            body = await request.json()
        except ValueError as e:
            return _json_error(422, str(e))
        fresh = fleet_view.fold(body) if isinstance(body, dict) else False
        if fresh:
            adm.note_fleet_pressure(
                fleet_view.fleet_pressure(), ttl_s=fleet_view.ttl_s
            )
            if own_state is not None:
                # Stale-ring-view detection: a peer gossiping a newer
                # epoch means this replica missed an ownership push (the
                # router retries it next probe tick; doctor surfaces the
                # disagreement meanwhile).
                peer_epoch = body.get("ownership_epoch")
                if (
                    isinstance(peer_epoch, int)
                    and peer_epoch > own_state.view.epoch
                ):
                    _m_stale_view.inc()
                    log.warning(
                        "stale ownership view: peer %s at epoch %d, local %d",
                        body.get("replica"), peer_epoch, own_state.view.epoch,
                    )
        return web.json_response({"ok": True, "fresh": fresh})

    # --- warn (micro-batched) -------------------------------------------

    async def warn(request):
        t_in = time.perf_counter()
        try:
            req = WarningRequest.model_validate(await request.json())
        except (ValidationError, ValueError) as e:
            return _json_error(422, str(e))
        traffic_rec.record("warn", app_id=req.app_id, prompt=req.prompt)
        # The batcher's bounded queue is the warn class's shed point (its
        # limit IS the admission bound); a degraded backend still answers
        # here through the GFKB host fallback — warn is the last class to
        # go dark, by design.
        t0 = time.perf_counter()
        with _trace.get_tracer().start_span(
            "gfkb.warn", app_id=req.app_id
        ) as gspan:
            t_sub = time.perf_counter()
            res = await warn_batcher.submit(req)
            t_out = time.perf_counter()
            gspan.set(
                tier=res.tier, nprobe=res.nprobe, degraded=res.degraded,
                native=_native_avail, action=res.action,
            )
            if res.degraded:
                gspan.outcome = "degraded"
                parent = request.get("trace_span")
                if parent is not None:
                    parent.outcome = "degraded"
        _h_warn.observe(
            time.perf_counter() - t0, exemplar=gspan.trace_id or None
        )
        resp = web.json_response(res.model_dump())
        # The handler's own work on either side of the batcher: read +
        # validate, then verdict span + dump + JSON (the wait between the
        # two is the batcher's, under its own series).
        observe_phase("warn.http", (t_sub - t_in) + (time.perf_counter() - t_out))
        return resp

    # --- GFKB -----------------------------------------------------------

    async def list_failures(request):
        return web.json_response(
            {"failures": [f.model_dump(mode="json") for f in plat.failures()]}
        )

    async def match(request):
        try:
            req = FailureMatchRequest.model_validate(await request.json())
        except (ValidationError, ValueError) as e:
            return _json_error(422, str(e))
        matches = plat.gfkb.match(req.signature_text, failure_type=req.failure_type)
        return web.json_response({"matches": [m.model_dump() for m in matches]})

    async def upsert_failure(request):
        try:
            body = await request.json()
            rec, created = plat.gfkb.upsert_failure(
                failure_type=body["failure_type"],
                signature_text=body["signature_text"],
                app_id=body["app_id"],
                impact_severity=Severity(body["impact_severity"]),
                context_signature=body.get("context_signature"),
                root_cause=body.get("root_cause"),
                resolution=body.get("resolution"),
            )
        except (KeyError, ValueError, ValidationError) as e:
            return _json_error(422, str(e))
        # Manual upserts replicate like ingest-classified rows do — an
        # operator correction must not diverge the fleet's shards. One
        # publish path (Platform.replicate_rows) covers both the legacy
        # broadcast and range-scoped ownership fan-out.
        await plat.replicate_rows(
            [
                {
                    "failure_type": body["failure_type"],
                    "signature_text": body["signature_text"],
                    "app_id": body["app_id"],
                    "impact_severity": body["impact_severity"],
                    "context_signature": body.get("context_signature"),
                    "root_cause": body.get("root_cause"),
                    "resolution": body.get("resolution"),
                }
            ]
        )
        return web.json_response(
            {"ok": True, "created": created, "failure": rec.model_dump(mode="json")}
        )

    async def list_patterns(request):
        return web.json_response(
            {"patterns": [p.model_dump(mode="json") for p in plat.patterns_list()]}
        )

    async def upsert_pattern(request):
        try:
            body = await request.json()
            p, created = plat.gfkb.upsert_pattern(
                name=body["name"],
                failure_ids=body.get("failure_ids", []),
                affected_apps=body.get("affected_apps", []),
                description=body.get("description"),
            )
        except (KeyError, ValueError, ValidationError) as e:
            return _json_error(422, str(e))
        return web.json_response(
            {"ok": True, "created": created, "pattern": p.model_dump(mode="json")}
        )

    # --- health timeline ------------------------------------------------

    async def app_health(request):
        app_id = request.match_info["app_id"]
        limit = min(max(int(request.query.get("limit", 50)), 1), 500)
        return web.json_response({"app_id": app_id, "points": plat.health_history(app_id, limit)})

    # --- event bus (external pub/sub contract) --------------------------

    async def subscribe(request):
        body = await request.json()
        topic, cb = body.get("topic"), body.get("callback_url")
        if not topic or not cb:
            return _json_error(422, "topic and callback_url required")
        n = plat.bus.subscribe(topic, cb)
        return web.json_response({"ok": True, "topic": topic, "subscribers": n})

    async def snapshot(request):
        """Point-in-time GFKB snapshot: restart restores it and replays only
        the log tail (startup at 1M rows drops from minutes to seconds)."""
        import asyncio as _asyncio

        loop = _asyncio.get_running_loop()
        from kakveda_tpu.index.gfkb import SnapshotError

        try:
            with adm.slot("background"):
                path = await loop.run_in_executor(None, plat.gfkb.snapshot)
        except SnapshotError as e:  # persist=False, or aborted by a reload
            return _json_error(409, str(e))
        return web.json_response({"ok": True, "path": str(path), "entries": plat.gfkb.count})

    async def mine_patterns(request):
        """Pattern mining over the GFKB. Body (all optional):
        {"threshold": 0.6, "mode": "auto"|"full"|"incremental"}.
        ``auto`` serves from the streaming cluster state when possible
        (drain deltas, re-emit dirty clusters — milliseconds); ``full``
        forces the whole-corpus device sweep (compaction/audit). The
        response carries freshness fields: the mode actually used, rows
        drained, dirty/total cluster counts, staleness and wall time."""
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 — empty body is fine
            body = {}
        try:
            threshold = float(body.get("threshold", 0.6))
        except (TypeError, ValueError, AttributeError):
            return _json_error(422, "threshold must be a number")
        mode = body.get("mode", "auto") if isinstance(body, dict) else "auto"
        if mode not in ("auto", "full", "incremental"):
            return _json_error(422, "mode must be auto|full|incremental")
        import asyncio as _asyncio

        loop = _asyncio.get_running_loop()
        with adm.slot("background"):
            found, info = await loop.run_in_executor(None, plat.mine, threshold, mode)
        return web.json_response(
            {
                "ok": True,
                "patterns": [p.model_dump(mode="json") for p in found],
                "mining": info,
            }
        )

    async def unsubscribe(request):
        body = await request.json()
        topic, cb = body.get("topic"), body.get("callback_url")
        if not topic or not cb:
            return _json_error(422, "topic and callback_url required")
        plat.bus.unsubscribe(topic, cb)
        return web.json_response({"ok": True, "topic": topic})

    async def publish(request):
        body = await request.json()
        topic, event = body.get("topic"), body.get("event")
        if not topic or event is None:
            return _json_error(422, "topic and event required")
        delivered = await plat.bus.publish(topic, event)
        return web.json_response({"ok": True, "delivered": delivered})

    async def topics(request):
        return web.json_response({"topics": plat.bus.topics()})

    app.add_routes(
        [
            web.get("/healthz", healthz),
            web.get("/readyz", readyz),
            web.post("/ingest", ingest),
            web.post("/ingest/batch", ingest_batch),
            web.post("/warn", warn),
            web.get("/failures", list_failures),
            web.post("/failures/match", match),
            web.post("/failures/upsert", upsert_failure),
            web.get("/patterns", list_patterns),
            web.post("/patterns/upsert", upsert_pattern),
            web.post("/patterns/mine", mine_patterns),
            web.post("/snapshot", snapshot),
            web.get("/health/{app_id}", app_health),
            web.post("/subscribe", subscribe),
            web.post("/unsubscribe", unsubscribe),
            web.post("/publish", publish),
            web.get("/topics", topics),
            web.post("/replicate", replicate),
            web.post("/fleet/gossip", fleet_gossip),
            web.get("/fleet/ownership", fleet_ownership_get),
            web.post("/fleet/ownership", fleet_ownership_post),
            web.post("/fleet/export", fleet_export),
        ]
    )
    app.add_routes(metrics_routes())
    return app


def make_agent_echo_app(agent_name: str = "agent-echo") -> web.Application:
    """Reference external-agent contract (reference: services/agent_echo/app.py):
    /health, /capabilities, /invoke echoing events back."""
    app = web.Application()

    async def health(request):
        return web.json_response({"ok": True, "service": agent_name, "status": "healthy"})

    async def capabilities(request):
        return web.json_response(
            {
                "name": agent_name,
                "capabilities": ["echo"],
                "events_in": ["*"],
                "events_out": ["echo"],
            }
        )

    async def invoke(request):
        body = await request.json()
        out = {
            "event_type": "echo",
            "payload": {
                "received_event_type": str(body.get("event_type") or "unknown"),
                "received_payload": body.get("payload"),
                "agent": agent_name,
            },
        }
        return web.json_response({"status": "ok", "events": [out]})

    app.add_routes(
        [
            web.get("/health", health),
            web.get("/capabilities", capabilities),
            web.post("/invoke", invoke),
        ]
    )
    return app
