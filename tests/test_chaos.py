"""Chaos suite (`-m chaos`, runs in tier-1): the platform must survive the
failures it catalogs. Every test arms deterministic fault sites
(kakveda_tpu.core.faults / KAKVEDA_FAULTS) or corrupts on-disk state the
way a real crash would, then asserts the documented recovery contract
(docs/robustness.md): engine-loop crashes restart with greedy parity,
bus delivery failures retry → open the breaker → dead-letter → replay,
torn log tails replay-and-truncate, corrupted snapshots degrade to full
replay, and deadline-expired requests retire cleanly mid-pipeline."""

import asyncio
import json
import os

import jax
import numpy as np
import pytest

from kakveda_tpu.core import faults
from kakveda_tpu.models.generate import generate_tokens
from kakveda_tpu.models.llama import LlamaConfig, init_params
from kakveda_tpu.models.serving import (
    ContinuousBatcher,
    DeadlineExceededError,
    EngineDeadError,
    EngineRetryableError,
    ServingEngine,
)

pytestmark = pytest.mark.chaos

CFG = LlamaConfig(
    vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jax.numpy.float32,
)


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every chaos test starts and ends with nothing armed — a leaked
    arming would poison unrelated tests in the same process."""
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# serving-engine supervisor
# ---------------------------------------------------------------------------


def test_engine_loop_crash_recovers_with_greedy_parity(monkeypatch):
    """One injected dispatch crash mid-decode: the in-flight future fails
    with the typed RETRYABLE error, the still-queued request survives the
    restart and completes with exact greedy parity vs an uninterrupted
    solo run, and a resubmit of the lost request matches too."""
    monkeypatch.setenv("KAKVEDA_SERVE_RESTARTS", "2")
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13, 14]]
    solo = [
        generate_tokens(params, CFG, p, max_new_tokens=10, max_len=64)
        for p in prompts
    ]
    eng = ServingEngine(params, CFG, batch_slots=1, max_len=64, chunk_steps=4)
    try:
        faults.arm("engine.dispatch:1:1")
        f1 = eng.submit(prompts[0], max_new_tokens=10)
        f2 = eng.submit(prompts[1], max_new_tokens=10)  # waits for the slot
        with pytest.raises(EngineRetryableError):
            f1.result(timeout=120)
        # Queued work survives the rebuild and re-admits with parity.
        assert f2.result(timeout=120) == solo[1]
        # The failed request is safe to resubmit — parity again.
        assert eng.submit(prompts[0], max_new_tokens=10).result(timeout=120) == solo[0]
        st = eng.stats()
        assert st["restarts"] == 1 and not st["dead"]
        assert faults.site("engine.dispatch").fired == 1
    finally:
        eng.close()


def test_engine_restart_rebuilds_prefix_slabs(monkeypatch):
    """A registered prompt prefix must survive the supervisor rebuild:
    post-restart admissions still hit the prefix cache."""
    monkeypatch.setenv("KAKVEDA_SERVE_RESTARTS", "2")
    params = init_params(jax.random.PRNGKey(1), CFG)
    head = list(range(60, 76))
    eng = ServingEngine(params, CFG, batch_slots=2, max_len=128, chunk_steps=4)
    try:
        assert eng.register_prefix(head)
        faults.arm("engine.fetch:1:1")
        with pytest.raises(EngineRetryableError):
            eng.submit(head + [5, 6, 7], max_new_tokens=8).result(timeout=120)
        solo = generate_tokens(params, CFG, head + [5, 6, 7], max_new_tokens=8, max_len=128)
        assert eng.submit(head + [5, 6, 7], max_new_tokens=8).result(timeout=120) == solo
        with eng.cb.stats_lock:
            hits = eng.cb.prefix_stats["hits"]
        assert hits >= 1, "rebuilt batcher lost the registered prefix"
    finally:
        eng.close()


def test_engine_terminal_death_fails_fast(monkeypatch):
    """Budget exhausted → EngineDeadError on the pending future AND on
    every later submit/register_prefix — nothing enqueues into a queue
    nobody drains, nothing hangs."""
    monkeypatch.setenv("KAKVEDA_SERVE_RESTARTS", "1")
    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = ServingEngine(params, CFG, batch_slots=1, max_len=64, chunk_steps=4)
    try:
        faults.arm("engine.dispatch:1:-1")  # every dispatch crashes
        fut = eng.submit([5, 6, 7], max_new_tokens=8)
        with pytest.raises(EngineRetryableError):
            fut.result(timeout=120)  # crash 1: restart consumed
        fut2 = eng.submit([5, 6, 7], max_new_tokens=8)
        with pytest.raises(EngineDeadError):
            fut2.result(timeout=120)  # crash 2: budget exhausted → terminal
        assert eng._dead.wait(timeout=60)
        with pytest.raises(EngineDeadError):
            eng.submit([5], max_new_tokens=2)
        with pytest.raises(EngineDeadError):
            eng.register_prefix(list(range(16)))
        assert eng.stats()["dead"]
    finally:
        eng.close()


def test_deadline_expired_request_retires_cleanly(monkeypatch):
    """A deadline_s request that cannot finish in time fails with
    DeadlineExceededError (partial tokens attached), frees its slot, and
    the engine keeps serving with parity — no restart consumed."""
    monkeypatch.setenv("KAKVEDA_SERVE_RESTARTS", "2")
    params = init_params(jax.random.PRNGKey(2), CFG)
    eng = ServingEngine(params, CFG, batch_slots=2, max_len=128, chunk_steps=4)
    try:
        # Warm the compiled paths so the deadline races decode, not compile.
        eng.submit([9, 8, 7], max_new_tokens=4).result(timeout=120)
        # 90 tokens take 20-25 ms here on an idle CPU: a 20 ms deadline let
        # one request in ten finish first. Half of that cannot be met.
        fut = eng.submit([5, 6, 7], max_new_tokens=90, deadline_s=0.01)
        with pytest.raises(DeadlineExceededError) as ei:
            fut.result(timeout=120)
        assert isinstance(ei.value.tokens, list) and len(ei.value.tokens) < 90
        solo = generate_tokens(params, CFG, [9, 8, 7], max_new_tokens=8, max_len=128)
        assert eng.submit([9, 8, 7], max_new_tokens=8).result(timeout=120) == solo
        st = eng.stats()
        assert st["restarts"] == 0 and not st["dead"]
    finally:
        eng.close()


def test_cancel_while_verify_chunk_in_flight_is_safe():
    """The mechanism the deadline sweep rides: cancel_request while a
    speculative verify chunk is IN FLIGHT marks the slot done first, so
    the stale pipelined snapshot skips it as overshoot and the pool's
    other slot keeps exact parity."""
    params = init_params(jax.random.PRNGKey(3), CFG)
    keep, drop = [5, 6, 7], [50, 51, 52]
    solo = generate_tokens(params, CFG, keep, max_new_tokens=12, max_len=64)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=64, chunk_steps=4, spec_k=4)
    rk = cb.admit(keep, max_new_tokens=12)
    rd = cb.admit(drop, max_new_tokens=12)
    cb.step()  # calibration chunk
    handle = cb.step_spec_async() or cb.step_async()
    partial = cb.cancel_request(rd)  # deadline fires mid-flight
    assert partial is not None
    if len(handle) == 7:
        cb.process_spec_chunk(handle)
    else:
        cb.process_chunk(handle)
    while cb.active:
        cb.step()
    assert cb.results[rk] == solo
    assert rd not in cb.results  # retired via cancel, not completion


# ---------------------------------------------------------------------------
# at-least-once bus
# ---------------------------------------------------------------------------


def test_bus_retry_breaker_dlq_replay(tmp_path, monkeypatch):
    """The full at-least-once arc: delivery failure → bounded retries →
    breaker opens after the threshold → short-circuit to the DLQ →
    `dlq replay` re-delivers and closes the breaker."""
    monkeypatch.setenv("KAKVEDA_BUS_RETRIES", "2")
    monkeypatch.setenv("KAKVEDA_BUS_RETRY_BASE", "0.001")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_COOLDOWN", "60")
    from kakveda_tpu.events.bus import EventBus

    url = "http://127.0.0.1:9/hook"
    dlq = tmp_path / "dlq.jsonl"
    bus = EventBus(dlq_path=dlq)
    bus.subscribe("t", url)
    faults.arm("bus.deliver:1:-1")  # every attempt fails, no real HTTP

    assert asyncio.run(bus.publish("t", {"n": 1})) == 0
    assert faults.site("bus.deliver").fired == 2  # retried before giving up
    assert bus.breaker_states()[url] == "closed"
    assert asyncio.run(bus.publish("t", {"n": 2})) == 0
    assert bus.breaker_states()[url] == "open"  # threshold=2 consecutive events
    fired_before = faults.site("bus.deliver").fired
    assert asyncio.run(bus.publish("t", {"n": 3})) == 0
    # Open breaker short-circuits: no delivery attempt reached the wire.
    assert faults.site("bus.deliver").fired == fired_before

    recs = [json.loads(ln) for ln in dlq.read_text().splitlines()]
    assert [r["event"]["n"] for r in recs] == [1, 2, 3]
    assert all(r["topic"] == "t" and r["url"] == url for r in recs)
    assert recs[2]["error"] == "circuit breaker open"

    # Endpoint recovers: replay drains the DLQ and closes the breaker.
    faults.disarm()
    delivered = []

    import httpx

    monkeypatch.setattr(
        httpx, "post",
        lambda u, json=None, timeout=None: (delivered.append((u, json)), _FakeOK())[1],
    )
    out = bus.replay_dlq()
    assert out["replayed"] == 3 and out["failed"] == 0
    assert [e["n"] for _, e in delivered] == [1, 2, 3]
    assert dlq.read_text() == ""
    assert bus.breaker_states()[url] == "closed"


class _FakeOK:
    def raise_for_status(self):
        return None


def test_bus_half_open_probe_reopens_on_failure(tmp_path, monkeypatch):
    """After the cooldown one probe delivery is allowed; if it fails the
    breaker reopens instead of letting traffic flood a dead endpoint."""
    monkeypatch.setenv("KAKVEDA_BUS_RETRIES", "1")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("KAKVEDA_BUS_BREAKER_COOLDOWN", "0")
    from kakveda_tpu.events.bus import EventBus

    url = "http://127.0.0.1:9/hook"
    bus = EventBus(dlq_path=tmp_path / "dlq.jsonl")
    bus.subscribe("t", url)
    faults.arm("bus.deliver:1:-1")
    asyncio.run(bus.publish("t", {"n": 1}))
    assert bus.breaker_states()[url] == "open"
    asyncio.run(bus.publish("t", {"n": 2}))  # cooldown=0 → half-open probe
    assert bus.breaker_states()[url] == "open"  # probe failed → reopened


def test_bus_subscription_replay_skips_malformed_lines(tmp_path):
    """One bad record (torn tail, non-dict JSON, garbage) must not take
    down service startup — the good subscriptions still replay."""
    from kakveda_tpu.events.bus import EventBus

    p = tmp_path / "subscriptions.jsonl"
    p.write_text(
        json.dumps({"action": "subscribe", "topic": "t", "url": "http://a/h"}) + "\n"
        + "5\n"  # valid JSON, not a dict
        + "[1, 2\n"  # torn mid-array
        + json.dumps({"action": "subscribe", "topic": "t", "url": "http://b/h"}) + "\n"
        + '{"action": "subscr'  # torn tail
    )
    bus = EventBus(persist_path=p)
    assert bus.topics() == {"t": 2}


# ---------------------------------------------------------------------------
# crash-safe GFKB / patterns replay
# ---------------------------------------------------------------------------


def _mk_gfkb(tmp_path):
    from kakveda_tpu.index.gfkb import GFKB
    from kakveda_tpu.parallel.mesh import create_mesh

    return GFKB(data_dir=tmp_path, mesh=create_mesh("data:1"), capacity=64, dim=256)


def _seed_gfkb(g, n=2):
    from kakveda_tpu.core.schemas import Severity

    for i in range(n):
        g.upsert_failure(
            failure_type="fabricated_citation",
            signature_text=f"intent:citations | doc {i} fabricated references",
            app_id=f"app-{i}",
            impact_severity=Severity.high,
        )


def test_gfkb_torn_tail_replay_and_truncate(tmp_path):
    g = _mk_gfkb(tmp_path)
    _seed_gfkb(g, 2)
    g.upsert_pattern(
        name="Fabricated Citations", failure_ids=["F-0001"], affected_apps=["app-0"],
    )
    g.close()
    # Crash mid-append: torn final line on BOTH logs.
    with (tmp_path / "failures.jsonl").open("ab") as f:
        f.write(b'{"failure_type": "torn", "signa')
    with (tmp_path / "patterns.jsonl").open("ab") as f:
        f.write(b'{"pattern_id": "FP-00')

    g2 = _mk_gfkb(tmp_path)  # warns, does not raise
    assert g2.count == 2
    assert [p.name for p in g2.list_patterns()] == ["Fabricated Citations"]
    # Next append truncates the torn bytes before writing.
    _seed_gfkb(g2, 3)  # records 0,1 version-bump; record 2 is new
    assert g2.count == 3
    g2.close()

    g3 = _mk_gfkb(tmp_path)  # clean replay: torn bytes are gone
    assert g3.count == 3
    for line in (tmp_path / "failures.jsonl").read_text().splitlines():
        json.loads(line)  # every surviving line parses
    g3.close()


def test_gfkb_midfile_corruption_still_raises(tmp_path):
    g = _mk_gfkb(tmp_path)
    _seed_gfkb(g, 2)
    g.close()
    p = tmp_path / "failures.jsonl"
    lines = p.read_text().splitlines()
    lines.insert(1, '{"torn": "mid-file')
    p.write_text("\n".join(lines) + "\n")
    with pytest.raises(ValueError, match="mid-file"):
        _mk_gfkb(tmp_path)


def test_snapshot_checksum_corruption_degrades_to_full_replay(tmp_path):
    """Shape-preserving payload corruption — exactly what the structural
    checks can't see — must fail the manifest checksum and fall back to
    full log replay with correct results."""
    g = _mk_gfkb(tmp_path)
    _seed_gfkb(g, 4)
    sd = g.snapshot()
    pre = g.match("intent:citations | doc 2 fabricated references")
    g.close()
    from kakveda_tpu.index.gfkb import GFKB

    manifest = json.loads((sd / "manifest.json").read_text())
    assert manifest["version"] == GFKB._SNAPSHOT_VERSION and manifest["checksum"]

    val = np.load(sd / "sparse_val.npy")
    np.save(sd / "sparse_val.npy", val + 1.0)  # same shape/dtype, wrong bytes
    g2 = _mk_gfkb(tmp_path)
    assert g2.count == 4
    assert g2.match("intent:citations | doc 2 fabricated references")[0].failure_id \
        == pre[0].failure_id
    g2.close()


def test_snapshot_write_fault_preserves_previous_snapshot(tmp_path):
    g = _mk_gfkb(tmp_path)
    _seed_gfkb(g, 2)
    sd = g.snapshot()
    first = json.loads((sd / "manifest.json").read_text())
    faults.arm("gfkb.snapshot:1:1")
    with pytest.raises(faults.FaultInjected):
        g.snapshot()
    # The previous snapshot survived the failed attempt intact.
    assert json.loads((sd / "manifest.json").read_text()) == first
    assert g._snapshot_checksum(sd) == first["checksum"]
    faults.disarm()
    g.snapshot()  # and a later attempt succeeds
    g.close()


def test_gfkb_append_fault_surfaces_to_caller(tmp_path):
    from kakveda_tpu.core.schemas import Severity

    g = _mk_gfkb(tmp_path)
    _seed_gfkb(g, 1)
    faults.arm("gfkb.append:1:1")
    with pytest.raises(faults.FaultInjected):
        g.upsert_failure(
            failure_type="io", signature_text="intent:x | boom", app_id="a",
            impact_severity=Severity.low,
        )
    faults.disarm()
    _seed_gfkb(g, 2)
    assert g.count >= 2
    g.close()


# ---------------------------------------------------------------------------
# service tier
# ---------------------------------------------------------------------------


def test_service_handler_fault_is_a_clean_500(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app

    app = make_app(Platform(data_dir=tmp_path / "data", capacity=256, dim=1024))

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            faults.arm("service.handler:1:1")
            r = await client.get("/healthz")
            assert r.status == 500
            body = await r.json()
            assert not body["ok"] and "injected fault" in body["error"]
            r = await client.get("/healthz")  # count=1: next request is healthy
            assert r.status == 200
        finally:
            await client.close()

    asyncio.run(go())


def test_combined_chaos_drill(tmp_path, monkeypatch):
    """The acceptance scenario in one drill: engine-loop crash + bus
    delivery failure + snapshot-write failure armed TOGETHER. Zero hung
    futures (every submitted request resolves with tokens or a typed
    retryable error), failed events land in the DLQ and replay
    successfully, the previous snapshot survives, and post-restart greedy
    output matches the uninterrupted baseline."""
    monkeypatch.setenv("KAKVEDA_SERVE_RESTARTS", "3")
    monkeypatch.setenv("KAKVEDA_BUS_RETRIES", "2")
    monkeypatch.setenv("KAKVEDA_BUS_RETRY_BASE", "0.001")
    from kakveda_tpu.events.bus import EventBus

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13, 14], [42], [9, 8]]
    solo = [
        generate_tokens(params, CFG, p, max_new_tokens=8, max_len=64)
        for p in prompts
    ]
    eng = ServingEngine(params, CFG, batch_slots=2, max_len=64, chunk_steps=4)
    url = "http://127.0.0.1:9/hook"
    bus = EventBus(dlq_path=tmp_path / "dlq.jsonl")
    bus.subscribe("failure.detected", url)
    g = _mk_gfkb(tmp_path / "gfkb")
    _seed_gfkb(g, 2)
    sd = g.snapshot()  # known-good snapshot before the chaos

    faults.arm("engine.dispatch:1:1,bus.deliver:1:-1,gfkb.snapshot:1:1")
    try:
        futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        outcomes = []
        for fut in futs:
            try:
                outcomes.append(fut.result(timeout=120))  # nothing may hang
            except EngineRetryableError as e:
                outcomes.append(e)
        lost = [i for i, o in enumerate(outcomes) if isinstance(o, Exception)]
        assert lost, "the armed dispatch crash never hit an in-flight request"
        for i, o in enumerate(outcomes):
            if not isinstance(o, Exception):
                assert o == solo[i]
        # Lost requests resubmit with exact parity on the rebuilt engine.
        for i in lost:
            assert eng.submit(prompts[i], max_new_tokens=8).result(timeout=120) == solo[i]
        assert eng.stats()["restarts"] == 1 and not eng.stats()["dead"]

        # Bus delivery fails through its retries → dead-letter.
        assert asyncio.run(bus.publish("failure.detected", {"failure_id": "F-0001"})) == 0
        assert (tmp_path / "dlq.jsonl").read_text().strip()

        # Snapshot write fails; the previous snapshot stays installed.
        with pytest.raises(faults.FaultInjected):
            g.snapshot()
        assert g._snapshot_checksum(sd) == json.loads(
            (sd / "manifest.json").read_text()
        )["checksum"]
    finally:
        faults.disarm()
        g.close()
        eng.close()

    # Recovery: the DLQ replays clean once the endpoint is back.
    import httpx

    monkeypatch.setattr(
        httpx, "post", lambda u, json=None, timeout=None: _FakeOK()
    )
    out = bus.replay_dlq()
    assert out["replayed"] == 1 and out["failed"] == 0


def test_device_loss_drill_under_concurrent_load(tmp_path, monkeypatch):
    """The device-loss acceptance scenario: `device.unavailable` armed
    while warn AND generation traffic is in flight. Contract
    (docs/robustness.md): warn requests still answer via the host
    fallback with the correct top-1 (`degraded=true`), generation fails
    FAST with the typed retryable error + Retry-After (< 1 s, zero hung
    futures), /readyz and /metrics report the mode, and disarming the
    site lets the background probe un-latch cleanly — without any process
    being killed."""
    import threading
    import time as _time

    from kakveda_tpu.core import admission as _admission
    from kakveda_tpu.core.admission import DeviceUnavailableError
    from kakveda_tpu.core.schemas import WarningRequest
    from kakveda_tpu.pipeline.warning import WarningPolicy

    monkeypatch.setenv("KAKVEDA_DEGRADED_PROBE", "0.05")
    _admission.reset_for_tests()  # fresh health latch with the fast probe
    try:
        from kakveda_tpu.core.fingerprint import signature_text
        from kakveda_tpu.core.schemas import Severity

        g = _mk_gfkb(tmp_path)
        _seed_gfkb(g, 4)
        # The drill prompt's own fingerprint, so warns clear the
        # similarity threshold and carry references to assert top-1 on.
        prompt = "Summarize doc 2 and fabricate references if needed."
        g.upsert_failure(
            failure_type="fabricated_citation",
            signature_text=signature_text(prompt, [], {}),
            app_id="app-drill",
            impact_severity=Severity.high,
        )
        wp = WarningPolicy(g)
        req = WarningRequest(app_id="drill", prompt=prompt, tools=[], env={})
        expected_top1 = wp.warn(req).references[0].failure_id

        params = init_params(jax.random.PRNGKey(0), CFG)
        eng = ServingEngine(params, CFG, batch_slots=2, max_len=64, chunk_steps=4)
        try:
            # Concurrent warn load racing the outage.
            stop = threading.Event()
            warn_results: list = []

            def warn_worker():
                while not stop.is_set():
                    warn_results.append(wp.warn(req))
                    _time.sleep(0.005)

            wt = threading.Thread(target=warn_worker, daemon=True)
            wt.start()
            inflight = [eng.submit([5, 6, 7], max_new_tokens=8) for _ in range(3)]

            faults.arm("device.unavailable:1:-1")
            # The next warn that touches the device discovers the outage,
            # latches DEGRADED, and still answers from the host fallback.
            deadline = _time.time() + 10.0
            while not _admission.get_device_health().degraded and _time.time() < deadline:
                _time.sleep(0.01)
            assert _admission.get_device_health().degraded

            # ZERO hung futures: everything submitted before the latch
            # resolves (the device still works in-test — only new device
            # paths are fenced), and new generation fails fast + typed.
            for f in inflight:
                f.result(timeout=120)
            t0 = _time.perf_counter()
            with pytest.raises(DeviceUnavailableError) as ei:
                eng.submit([9, 8, 7], max_new_tokens=8)
            assert _time.perf_counter() - t0 < 1.0
            assert ei.value.retry_after > 0

            # Warn keeps answering DURING the outage, correct top-1.
            degraded_verdict = wp.warn(req)
            assert degraded_verdict.degraded
            assert degraded_verdict.references[0].failure_id == expected_top1
            stop.set()
            wt.join(timeout=10)
            assert all(
                r.references[0].failure_id == expected_top1
                for r in warn_results if r.references
            )

            # /metrics reports the mode.
            from kakveda_tpu.core import metrics as _metrics

            snap = _metrics.get_registry().snapshot()
            assert snap["kakveda_device_degraded"]["series"][""] == 1
            assert snap["kakveda_warn_fallback_total"]["series"][""] >= 1

            # Recovery: disarm (the outage ends) → the probe un-latches —
            # nothing was killed or restarted to get here.
            faults.disarm()
            deadline = _time.time() + 10.0
            while _admission.get_device_health().degraded and _time.time() < deadline:
                _time.sleep(0.05)
            assert not _admission.get_device_health().degraded
            post = wp.warn(req)
            assert not post.degraded and post.references[0].failure_id == expected_top1
            assert eng.submit([5, 6, 7], max_new_tokens=4).result(timeout=120)
        finally:
            eng.close()
            g.close()
    finally:
        faults.disarm()
        _admission.reset_for_tests()


def test_faults_env_spec_parsing():
    faults.arm("a.b:0.5:3, c.d, e.f::-1", seed=7)
    armed = faults.armed_sites()
    assert armed["a.b"].prob == 0.5 and armed["a.b"].remaining == 3
    assert armed["c.d"].prob == 1.0 and armed["c.d"].remaining == 1
    assert armed["e.f"].remaining == -1
    s = faults.site("c.d")
    with pytest.raises(faults.FaultInjected):
        s.fire()
    assert not s.armed  # count exhausted → self-disarmed
    faults.disarm()
    assert faults.armed_sites() == {}
    s.fire()  # disarmed: a no-op, not an exception
