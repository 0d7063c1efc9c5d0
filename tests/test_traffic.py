"""Record-replay traffic harness (kakveda_tpu/traffic/, docs/robustness.md
§ traffic harness): seeded scenario determinism, traffic-log round-trips,
the flight-recorder capture seam, open-loop replay accounting (every
dispatch terminates in exactly one bucket), chaos-timeline application,
SLO gate evaluation, and the satellite mechanisms the harness gates —
Retry-After jitter, gossip pressure-floor decay, note_wait ladder
re-evaluation, DLQ auto-replay on breaker re-close, and router probe
phase stagger. Fault-arming tests and the in-process storm smoke carry
the chaos marker.

Global-state discipline (same as test_overload.py): the admission /
brownout / device-health controllers and the fault registry are
process-global, so every test resets them before AND after."""

import asyncio
import json
import time

import pytest

from kakveda_tpu.core import admission as adm_mod
from kakveda_tpu.core import faults
from kakveda_tpu.core.admission import AdmissionController, BrownoutController
from kakveda_tpu.traffic import (
    ReplayResult,
    SLO,
    SCENARIOS,
    evaluate,
    from_flightrecorder,
    make_scenario,
    read_log,
    replay,
    run_chaos,
    run_scenario,
    write_log,
)


@pytest.fixture(autouse=True)
def _clean_globals():
    faults.disarm()
    adm_mod.reset_for_tests()
    yield
    faults.disarm()
    adm_mod.reset_for_tests()


# ---------------------------------------------------------------------------
# scenarios: pure in (seed, knobs)
# ---------------------------------------------------------------------------


def test_scenario_determinism_every_generator():
    """Same seed → identical arrival schedule, app-key sequence, bodies,
    and chaos timeline — for EVERY registered generator. This is what
    makes a scenario name + seed a reproducible bug report."""
    for name in SCENARIOS:
        a = make_scenario(name, seed=7)
        b = make_scenario(name, seed=7)
        assert a.arrival_schedule() == b.arrival_schedule(), name
        assert a.app_key_sequence() == b.app_key_sequence(), name
        assert a.events == b.events, name
        assert a.chaos == b.chaos, name


def test_scenario_seed_changes_schedule():
    a = make_scenario("hot_key", seed=1)
    b = make_scenario("hot_key", seed=2)
    assert a.arrival_schedule() != b.arrival_schedule()


def test_unknown_scenario_is_typed_error():
    with pytest.raises(ValueError):
        make_scenario("nope")


def test_hot_key_skew_concentrates_on_one_app():
    sc = make_scenario("hot_key", seed=3)
    keys = sc.app_key_sequence()
    hot = keys.count("app-0") / len(keys)
    assert 0.8 < hot < 1.0  # declared 90%, Bernoulli noise allowed


def test_storm_scenario_shape():
    """The composed drill: three phases, a device-loss window that CLOSES
    (disarm is part of the timeline), gossip pressure ticks through
    recovery, and an SLO that never sheds warn/ingest."""
    sc = make_scenario("storm", seed=4, duration_s=12.0, gossip_ttl_s=5.0)
    phases = {e["phase"] for e in sc.events}
    assert {"baseline", "storm", "recovery"} <= phases
    b, s = sc.notes["storm_start_s"], sc.notes["storm_end_s"]
    assert 0.0 < b < s < 12.0
    arms = [a for a in sc.chaos if a["action"] == "faults" and a.get("spec")]
    disarms = [a for a in sc.chaos if a["action"] == "faults" and not a.get("spec")]
    assert arms and disarms, "device-loss window must open AND close"
    assert all("device.unavailable" in a["spec"] for a in arms)
    assert max(a["t"] for a in disarms) > max(a["t"] for a in arms)
    ticks = [a for a in sc.chaos if a["action"] == "fleet_pressure"]
    assert any(a["pressure"] == 0.0 and a["t"] >= s for a in ticks), (
        "recovery needs live zero-pressure gossip ticks (live samples "
        "REPLACE the floor; TTL only covers dead peers)")
    assert "warn" not in sc.slo.shed_only and "ingest" not in sc.slo.shed_only
    assert sc.slo.zero_hung and "warn" in sc.slo.zero_lost
    assert sc.slo.recovery_s == 5.0


# ---------------------------------------------------------------------------
# traffic logs
# ---------------------------------------------------------------------------


def test_log_round_trip_preserves_schedule(tmp_path):
    sc = make_scenario("diurnal", seed=9)
    p = tmp_path / "t.jsonl"
    n = write_log(p, sc.events, meta={"scenario": "diurnal", "seed": 9})
    assert n == len(sc.events)
    meta, events = read_log(p)
    assert meta["scenario"] == "diurnal" and meta["version"] == 1
    assert [e["t"] for e in events] == sc.arrival_schedule()
    assert [e.get("app_id", "") for e in events] == sc.app_key_sequence()


def test_log_read_skips_malformed_lines(tmp_path):
    """Skip-with-warning per line (the bus subscription-replay contract):
    a torn or hand-edited log replays what it can."""
    p = tmp_path / "t.jsonl"
    good = {"t": 0.5, "method": "POST", "path": "/warn", "klass": "warn"}
    p.write_text(
        json.dumps({"kakveda_traffic_log": 1, "meta": {}}) + "\n"
        + "{\"t\": 0.1, \"path\"\n"          # torn mid-object
        + "5\n"                               # valid JSON, not a dict
        + json.dumps({"path": "/warn"}) + "\n"  # no offset
        + json.dumps(good) + "\n"
    )
    _, events = read_log(p)
    assert events == [good]


def test_from_flightrecorder_is_deterministic():
    payload = {"recorders": [{"name": "traffic", "events": [
        {"t": 100.0, "kind": "warn", "app_id": "a", "prompt": "Cite it."},
        {"t": 100.4, "kind": "ingest", "app_id": "b", "n": 3},
        {"t": 101.0, "kind": "warn", "app_id": "a", "prompt": "Again."},
    ]}]}
    ev1 = from_flightrecorder(payload, seed=3)
    ev2 = from_flightrecorder(payload, seed=3)
    assert ev1 == ev2
    assert [e["t"] for e in ev1] == [0.0, 0.4, 1.0]  # rebased to first event
    assert ev1[0]["body"] == {"app_id": "a", "prompt": "Cite it."}  # byte-faithful
    assert len(ev1[1]["body"]["traces"]) == 3  # shape-faithful
    assert from_flightrecorder({"recorders": []}) == []


# ---------------------------------------------------------------------------
# open-loop replay: terminal accounting
# ---------------------------------------------------------------------------


def _ev(t, path, klass, method="POST", phase="baseline"):
    return {"t": t, "method": method, "path": path, "klass": klass,
            "app_id": "app-0", "phase": phase, "body": {}}


def test_replay_buckets_every_outcome():
    """2xx/429/503/other map to ok/shed/degraded/error; a LOCAL event
    without a dispatcher is skipped (and NOT counted as lost); a LOCAL
    event with one records its TTFT. The accounting must balance."""
    statuses = {"/ok": 200, "/shed": 429, "/deg": 503, "/boom": 500}

    async def post(path, body):
        return statuses[path]

    async def gen(event):
        return 0.01  # ttft seconds

    events = [
        _ev(0.0, "/ok", "warn"), _ev(0.0, "/shed", "warn"),
        _ev(0.0, "/deg", "warn"), _ev(0.0, "/boom", "background"),
        _ev(0.0, "/generate", "interactive", method="LOCAL"),
        _ev(0.0, "/nope", "interactive", method="LOCAL"),
    ]
    res = asyncio.run(replay(
        events, post=post, speed=100.0, timeout_s=5.0,
        extra_dispatch={"/generate": gen}))
    counts = res.class_counts()
    assert counts["warn"] == {"ok": 1, "shed": 1, "degraded": 1}
    assert counts["background"] == {"error": 1}
    assert counts["interactive"] == {"ok": 1, "skipped": 1}
    assert res.generated("warn") == 3
    assert res.generated("interactive") == 1  # skipped never entered the system
    assert res.ttft_ms() == [10.0]
    # The gates read this accounting: a warn shed fails shed_only outright,
    # and zero_lost balances because every dispatch landed in a bucket.
    rep = evaluate(SLO(), res)
    by = {g.gate: g for g in rep.gates}
    assert not by["shed_only"].ok and by["shed_only"].observed == {"warn": 1}
    assert by["zero_hung"].ok and by["zero_lost[warn]"].ok


def test_replay_timeout_is_hung_not_lost():
    async def post(path, body):
        await asyncio.sleep(0.5)
        return 200

    res = asyncio.run(replay(
        [_ev(0.0, "/warn", "warn")], post=post, timeout_s=0.05))
    assert res.class_counts()["warn"] == {"hung": 1}
    rep = evaluate(SLO(), res)
    by = {g.gate: g for g in rep.gates}
    assert not by["zero_hung"].ok        # SHED-NEVER-HANG, end to end
    assert by["zero_lost[warn]"].ok      # hung is terminal accounting, not loss


def test_replay_is_open_loop():
    """A slow response must not delay later arrivals (closed-loop clients
    self-throttle and hide the very overload the harness measures)."""
    sends = []

    async def post(path, body):
        sends.append(asyncio.get_running_loop().time())
        await asyncio.sleep(0.3)
        return 200

    events = [_ev(0.0, "/warn", "warn"), _ev(0.05, "/warn", "warn")]
    res = asyncio.run(replay(events, post=post, timeout_s=5.0))
    assert len(sends) == 2
    assert sends[1] - sends[0] < 0.25  # second fired on schedule, not after 0.3s
    assert res.late_p95_ms() < 200.0


@pytest.mark.chaos
def test_replay_dispatch_fault_drops_to_error():
    """The harness's own failure mode (traffic.dispatch, docs/robustness.md
    catalog): an armed dispatch fault loses the request into the error
    bucket — counted, never raised out of the replay."""
    async def post(path, body):
        return 200

    faults.arm("traffic.dispatch:1:-1")
    res = asyncio.run(replay(
        [_ev(0.0, "/warn", "warn"), _ev(0.0, "/warn", "warn")],
        post=post, timeout_s=5.0))
    assert res.class_counts()["warn"] == {"error": 2}
    assert faults.site("traffic.dispatch").fired == 2


# ---------------------------------------------------------------------------
# chaos timelines
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_run_chaos_applies_and_skips():
    """faults entries re-arm the registry (empty spec disarms — disarm IS
    how an outage ends); fleet_pressure feeds the admission controller
    exactly like a gossip sample; actions missing their handle skip with
    a warning instead of failing the run."""
    adm = AdmissionController(enabled=True,
                              brownout=BrownoutController(enabled=False))
    seen = {}

    async def go():
        timeline = [
            {"t": 0.0, "action": "faults", "spec": "device.unavailable:1.0:-1"},
            {"t": 0.01, "action": "fleet_pressure", "pressure": 0.9, "ttl_s": 5.0},
            {"t": 0.02, "action": "kill_replica", "replica": 1},
            {"t": 0.03, "action": "bogus"},
            {"t": 0.3, "action": "faults", "spec": ""},
        ]

        async def probe():
            await asyncio.sleep(0.15)  # well inside the [0.0, 0.3) window
            seen["armed_mid_window"] = faults.site("device.unavailable").armed
        applied, _ = await asyncio.gather(
            run_chaos(timeline, admission=adm), probe())
        return applied

    applied = asyncio.run(go())
    assert seen["armed_mid_window"]
    assert not faults.site("device.unavailable").armed  # window closed
    assert adm.fleet_pressure() == pytest.approx(0.9)
    by_action = {a["action"]: a for a in applied}
    assert by_action["faults"]["applied"]
    assert by_action["fleet_pressure"]["applied"]
    assert not by_action["kill_replica"]["applied"]  # no supervisor handle
    assert not by_action["bogus"]["applied"]


# ---------------------------------------------------------------------------
# SLO gates
# ---------------------------------------------------------------------------


def _fake_result(base_ms, storm_ms, recovery_s=None):
    res = ReplayResult(ladder_recovery_s=recovery_s)
    for ms in base_ms:
        res.records.append({"klass": "warn", "phase": "baseline",
                            "status": "ok", "latency_ms": ms, "late_ms": 0.0})
    for ms in storm_ms:
        res.records.append({"klass": "warn", "phase": "storm",
                            "status": "ok", "latency_ms": ms, "late_ms": 0.0})
    res.generated_counts["warn"] = len(res.records)
    return res


def test_slo_baseline_ratio_gate():
    res = _fake_result([10.0] * 20, [100.0] * 20)
    ok = evaluate(SLO(warn_p95_x_baseline=12.0), res)
    bad = evaluate(SLO(warn_p95_x_baseline=5.0), res)
    g_ok = {g.gate: g for g in ok.gates}["warn_p95_x_baseline"]
    g_bad = {g.gate: g for g in bad.gates}["warn_p95_x_baseline"]
    assert g_ok.ok and g_ok.observed == pytest.approx(10.0)
    assert not g_bad.ok


def test_slo_ratio_gate_vacuous_without_phases():
    """Capture replays have a single phase — the self-normalizing ratio
    gate passes vacuously rather than failing a log that never declared
    a storm."""
    res = ReplayResult()
    res.records.append({"klass": "warn", "phase": "capture", "status": "ok",
                        "latency_ms": 5.0, "late_ms": 0.0})
    res.generated_counts["warn"] = 1
    rep = evaluate(SLO(warn_p95_x_baseline=2.0), res)
    assert {g.gate: g for g in rep.gates}["warn_p95_x_baseline"].ok


def test_slo_recovery_gate():
    never = evaluate(SLO(recovery_s=3.0), _fake_result([1.0], [1.0]))
    slow = evaluate(SLO(recovery_s=3.0), _fake_result([1.0], [1.0], recovery_s=9.0))
    fast = evaluate(SLO(recovery_s=3.0), _fake_result([1.0], [1.0], recovery_s=1.2))
    assert {g.gate: g for g in never.gates}["recovery_s"].observed == "never recovered"
    assert not {g.gate: g for g in slow.gates}["recovery_s"].ok
    assert {g.gate: g for g in fast.gates}["recovery_s"].ok


def test_slo_shed_rate_ceiling():
    res = ReplayResult()
    for status in ("ok", "shed"):
        res.records.append({"klass": "background", "phase": "storm",
                            "status": status, "latency_ms": 1.0, "late_ms": 0.0})
    res.generated_counts["background"] = 2
    at = evaluate(SLO(max_shed_rate={"background": 0.5}, zero_lost=()), res)
    under = evaluate(SLO(max_shed_rate={"background": 0.4}, zero_lost=()), res)
    assert {g.gate: g for g in at.gates}["max_shed_rate[background]"].ok
    assert not {g.gate: g for g in under.gates}["max_shed_rate[background]"].ok


# ---------------------------------------------------------------------------
# satellites: the mechanisms the harness gates
# ---------------------------------------------------------------------------


def test_retry_after_jitter_bounded(monkeypatch):
    """±25% multiplicative spread de-phases the retry wave; jitter=0 keeps
    the honest drain estimate exactly. The typed-429 floor (0.1 s) holds
    either way."""
    monkeypatch.setenv("KAKVEDA_ADMIT_RA_JITTER", "0.25")
    adm = AdmissionController(enabled=True,
                              brownout=BrownoutController(enabled=False))
    samples = [adm.retry_after("warn") for _ in range(200)]
    # No drain rate measured yet → base is the honest 1 s default.
    assert all(0.75 <= s <= 1.25 for s in samples)
    assert max(samples) - min(samples) > 0.05  # actually spread, not constant

    monkeypatch.setenv("KAKVEDA_ADMIT_RA_JITTER", "0")
    adm0 = AdmissionController(enabled=True,
                               brownout=BrownoutController(enabled=False))
    assert {adm0.retry_after("warn") for _ in range(20)} == {1.0}


def test_gossip_pressure_floor_decays_and_ladder_recovers():
    """Satellite drill for the storm's recovery phase, without HTTP: peer
    gossip steps the ladder down; zero-pressure ticks (live samples
    REPLACE the floor) bring it back to `normal` — and an expired TTL
    stops a silent peer from pinning the ladder. Every transition runs
    through _set_brownout_state (the single writer is what the gauge
    vector + transition counter ride on)."""
    adm = AdmissionController(
        enabled=True,
        brownout=BrownoutController(enabled=True, enter=0.85, exit=0.5,
                                    dwell_s=0.0))
    for _ in range(4):
        adm.note_fleet_pressure(0.95, ttl_s=5.0)
    assert adm.brownout.state == "shed_interactive"
    assert adm.brownout.class_shed("interactive")
    assert not adm.brownout.class_shed("warn")  # ladder never sheds warn

    t0 = time.monotonic()
    for _ in range(8):
        adm.note_fleet_pressure(0.0, ttl_s=5.0)
        if adm.brownout.state == "normal":
            break
    assert adm.brownout.state == "normal"
    assert time.monotonic() - t0 < 5.0  # inside the gossip TTL, by a mile
    assert adm.fleet_pressure() == 0.0

    # TTL path: a peer that goes SILENT (no zero tick) expires off the floor.
    adm.note_fleet_pressure(0.95, ttl_s=0.1)
    assert adm.fleet_pressure() == pytest.approx(0.95)
    time.sleep(0.15)
    assert adm.fleet_pressure() == 0.0


def test_note_wait_reevaluates_ladder():
    """warn traffic flows through the micro-batcher's bounded queue, never
    try_admit/release — note_wait (one call per batch drain) must feed the
    ladder, or a warn-only recovery tail produces ZERO pressure samples
    and the ladder freezes at its storm step."""
    adm = AdmissionController(
        enabled=True,
        brownout=BrownoutController(enabled=True, enter=0.85, exit=0.5,
                                    dwell_s=0.0))
    adm.note_fleet_pressure(0.95, ttl_s=0.1)
    adm.note_fleet_pressure(0.95, ttl_s=0.1)
    assert adm.brownout.state != "normal"
    time.sleep(0.15)  # floor expired; only warn drains tick from here on
    for _ in range(8):
        adm.note_wait("warn", 0.001)
        if adm.brownout.state == "normal":
            break
    assert adm.brownout.state == "normal"


def test_router_probe_phase_stagger():
    """Per-replica probe phases: deterministic (blake2b of the replica id —
    the ring's derivation discipline), inside [0, interval), and actually
    spread so N replicas don't see N simultaneous probes per interval."""
    from kakveda_tpu.fleet.router import Router

    backends = {f"replica-{i}": f"http://127.0.0.1:{9000 + i}" for i in range(6)}
    r1 = Router(backends, probe_interval_s=2.0)
    r2 = Router(backends, probe_interval_s=2.0)
    phases = {rid: r1.probe_phase(rid) for rid in backends}
    assert phases == {rid: r2.probe_phase(rid) for rid in backends}
    assert all(0.0 <= p < 2.0 for p in phases.values())
    assert len(set(phases.values())) > 1


@pytest.mark.chaos
def test_dlq_auto_replay_on_breaker_reclose(tmp_path, monkeypatch):
    """Full arc: delivery fails → DLQ + breaker open → endpoint heals →
    half-open probe succeeds → breaker RE-closes → the bus schedules one
    auto-replay (KAKVEDA_DLQ_AUTO_S) that drains the dead-letter queue
    without an operator. Safe because replay is idempotent for
    subscribers by contract."""
    monkeypatch.setenv("KAKVEDA_BUS_RETRIES", "1")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_COOLDOWN", "0")
    monkeypatch.setenv("KAKVEDA_DLQ_AUTO_S", "0.05")
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    from kakveda_tpu.events.bus import EventBus

    received = []

    async def hook(request):
        received.append((await request.json()).get("n"))
        return web.json_response({"ok": True})

    async def go():
        app = web.Application()
        app.router.add_post("/hook", hook)
        server = TestServer(app)
        await server.start_server()
        try:
            url = str(server.make_url("/hook"))
            dlq = tmp_path / "dlq.jsonl"
            bus = EventBus(dlq_path=dlq)
            bus.subscribe("t", url)

            faults.arm("bus.deliver:1:-1")
            assert await bus.publish("t", {"n": 1}) == 0
            assert bus.breaker_states()[url] == "open"
            assert len(dlq.read_text().splitlines()) == 1

            # Endpoint heals: cooldown 0 → this delivery is the half-open
            # probe; success re-closes the breaker and arms the timer.
            faults.disarm()
            assert await bus.publish("t", {"n": 2}) == 1
            assert bus.breaker_states()[url] == "closed"

            # The timer thread replays via sync HTTP while this loop is
            # parked in sleep — poll for the drain.
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if dlq.read_text() == "" and 1 in received:
                    break
                await asyncio.sleep(0.05)
            assert dlq.read_text() == ""
            assert received == [2, 1]  # live event first, then the replay
            assert bus._m_dlq_auto.labels(result="scheduled").value >= 1
            assert bus._m_dlq_auto.labels(result="replayed").value >= 1
        finally:
            await server.close()

    asyncio.run(go())


def test_bus_close_cancels_pending_dlq_auto_timer(tmp_path, monkeypatch):
    """The DLQ auto-replay timer's close path: a bus shut down while a
    replay is pending cancels the timer (no delivery fires against a
    torn-down platform), close() is idempotent, and a closed bus never
    arms another timer."""
    monkeypatch.setenv("KAKVEDA_BUS_RETRIES", "1")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_COOLDOWN", "0")
    monkeypatch.setenv("KAKVEDA_DLQ_AUTO_S", "0.2")
    from aiohttp import web
    from aiohttp.test_utils import TestServer

    from kakveda_tpu.events.bus import EventBus

    received = []

    async def hook(request):
        received.append((await request.json()).get("n"))
        return web.json_response({"ok": True})

    async def go():
        app = web.Application()
        app.router.add_post("/hook", hook)
        server = TestServer(app)
        await server.start_server()
        try:
            url = str(server.make_url("/hook"))
            dlq = tmp_path / "dlq.jsonl"
            bus = EventBus(dlq_path=dlq)
            bus.subscribe("t", url)

            faults.arm("bus.deliver:1:-1")
            assert await bus.publish("t", {"n": 1}) == 0
            faults.disarm()
            assert await bus.publish("t", {"n": 2}) == 1  # re-close arms timer
            assert bus._dlq_auto_timer is not None

            bus.close()
            assert bus._dlq_auto_timer is None
            bus.close()  # idempotent

            await asyncio.sleep(0.4)  # past the would-have-fired deadline
            assert len(dlq.read_text().splitlines()) == 1  # never replayed
            assert received == [2]

            # A closed bus never arms another timer.
            faults.arm("bus.deliver:1:-1")
            await bus.publish("t", {"n": 3})
            faults.disarm()
            await bus.publish("t", {"n": 4})
            assert bus._dlq_auto_timer is None
        finally:
            await server.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# capture seam + storm smoke (through the real HTTP stack)
# ---------------------------------------------------------------------------


def _mk_service(tmp_path, adm, dim=256):
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app

    plat = Platform(data_dir=tmp_path / "data", capacity=1 << 10, dim=dim)
    return make_app(platform=plat, admission=adm)


def test_capture_roundtrip_over_http(tmp_path):
    """The whole record path: real warn/ingest arrivals land in the
    traffic flight-recorder ring → GET /flightrecorder converts to a log
    → the log replays against the same service with nothing lost. Same
    dump + same seed → identical log (capture→replay determinism)."""
    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.traffic.scenarios import synth_traces

    adm = AdmissionController(enabled=True,
                              brownout=BrownoutController(enabled=False))
    app = _mk_service(tmp_path, adm)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for i in range(3):
                r = await client.post("/warn", json={
                    "app_id": f"app-{i % 2}",
                    "prompt": f"Cite sources for claim {i}.",
                })
                assert r.status == 200
            r = await client.post("/ingest/batch", json={
                "traces": synth_traces(0, "app-9", 2)})
            assert r.status in (200, 202)

            r = await client.get("/flightrecorder")
            payload = await r.json()
            ev1 = from_flightrecorder(payload, seed=3)
            assert from_flightrecorder(payload, seed=3) == ev1
            assert [e["klass"] for e in ev1] == ["warn"] * 3 + ["ingest"]
            assert ev1[0]["t"] == 0.0

            p = tmp_path / "cap.jsonl"
            write_log(p, ev1, meta={"source": "test"})
            _, events = read_log(p)
            assert [e["t"] for e in events] == [e["t"] for e in ev1]
            assert ([e.get("app_id") for e in events]
                    == [e.get("app_id") for e in ev1])

            async def post(path, body):
                resp = await client.post(path, json=body)
                await resp.read()
                return resp.status

            res = await replay(events, post=post, speed=100.0, timeout_s=30.0)
            counts = res.class_counts()
            assert counts["warn"] == {"ok": 3}
            assert counts["ingest"] == {"ok": 1}
        finally:
            await client.close()

    asyncio.run(go())


@pytest.mark.chaos
def test_storm_smoke_slo_gated(tmp_path):
    """The acceptance drill, sized for tier-1: seeded storm (hot-key warn
    + background mine flood + device-loss window + gossiped pressure)
    through the real HTTP stack, every SLO gate asserted — zero hung,
    zero lost warns, sheds confined to sheddable classes, bounded warn
    degradation, ladder back at `normal` within the gossip TTL."""
    from aiohttp.test_utils import TestClient, TestServer

    sc = make_scenario("storm", seed=5, duration_s=8.0, gossip_ttl_s=3.0)
    brown = BrownoutController(enabled=True, enter=0.85, exit=0.5, dwell_s=0.25)
    # warn sized for DEGRADED throughput: the device-loss window serves
    # warn from the host tiers, and the ladder never sheds warn — the
    # class limit must clear the storm's arrival rate at warm-tier speed.
    adm = AdmissionController(
        limits={"warn": 64, "ingest": 2, "interactive": 8, "background": 1},
        enabled=True, brownout=brown)
    app = _mk_service(tmp_path, adm)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async def post(path, body):
                resp = await client.post(path, json=body)
                await resp.read()
                return resp.status

            # One request of each kind before the clock starts. A fresh
            # process compiles the mining programs on its first
            # /patterns/mine (~0.8 s here), and the storm's first mine is
            # its first: the warns queued behind that one compile were the
            # storm's whole p95 (700 ms against a 5 ms baseline), which the
            # drill then read as unbounded degradation. A served process has
            # mined before a storm reaches it.
            for klass in ("warn", "background"):
                ev = next(e for e in sc.events if e["klass"] == klass)
                assert await post(ev["path"], ev["body"]) == 200

            return await run_scenario(
                sc, post=post, speed=1.5, timeout_s=15.0, admission=adm,
                recovery_horizon_s=20.0)
        finally:
            await client.close()

    res = asyncio.run(go())
    report = evaluate(sc.slo, res)
    assert report.ok, report.summary()
    assert res.generated("warn") > 50  # the drill actually drove traffic
    counts = res.class_counts()
    assert counts.get("warn", {}).get("shed", 0) == 0
    assert counts.get("warn", {}).get("hung", 0) == 0
    assert res.ladder_recovery_s is not None and res.ladder_recovery_s <= 3.0
