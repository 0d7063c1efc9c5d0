"""Elastic autoscaler tests (fleet/autoscaler.py, docs/scale-out.md §
Elastic fleet): the pure policy (dwell/cooldown hysteresis, min/max
clamps, victim selection, replacement budget + expo backoff, fault-
outcome retry semantics), the executor's chaos-site contracts
(fleet.scale_spawn never flips the epoch early; fleet.scale_drain aborts
with the replica still serving), flap accounting + the scale_log decision
ledger, and the flash-crowd chaos drill over real subprocess replicas
(scale-up within dwell bounds, lossless drain with zero lost warns,
SIGKILLed owner replaced with its rows healed)."""

import asyncio
import json
import time
import uuid
from datetime import datetime, timezone

import pytest
from aiohttp.test_utils import TestClient, TestServer

from kakveda_tpu.core import faults
from kakveda_tpu.fleet.autoscaler import (
    Autoscaler,
    PolicyState,
    ScaleKnobs,
    commit,
    decide,
    policy_selftest,
)
from kakveda_tpu.fleet.ownership import MigrationError, OwnershipView


def run(coro):
    return asyncio.run(coro)


def snap(occs, dead=None):
    """Policy snapshot from {rid: occupancy} (+ {rid: dead_for_s})."""
    dead = dead or {}
    reps = {
        r: {"live": r not in dead, "occupancy": o,
            "dead_for_s": dead.get(r, 0.0)}
        for r, o in occs.items()
    }
    live = [o for r, o in occs.items() if r not in dead]
    return {"replicas": reps, "pressure": max(live, default=0.0)}


K = ScaleKnobs(up_occ=0.8, down_occ=0.3, dwell_s=5.0, cooldown_s=15.0,
               min_replicas=1, max_replicas=4, replace_s=10.0,
               replace_backoff_s=5.0, replace_max=3)


# ---------------------------------------------------------------------------
# pure policy: decide/commit on synthetic FleetView snapshots
# ---------------------------------------------------------------------------


def test_policy_selftest_passes():
    """The canned table verify_static.sh stage 4 runs is green."""
    assert policy_selftest() >= 20


def test_dwell_blocks_until_sustained():
    st = PolicyState()
    hot = snap({"r0": 0.9, "r1": 0.85})
    assert decide(hot, st, K, 0.0).action == "none"
    assert decide(hot, st, K, 4.9).action == "none"
    d = decide(hot, st, K, 5.0)
    assert d.action == "scale_up" and d.n == 2


def test_dip_resets_dwell_clock():
    st = PolicyState()
    hot, mid = snap({"r0": 0.9}), snap({"r0": 0.5})
    decide(hot, st, K, 0.0)
    decide(mid, st, K, 4.0)  # mid-band: both clocks reset
    assert st.high_since is None and st.low_since is None
    decide(hot, st, K, 4.5)
    assert decide(hot, st, K, 9.0).action == "none"  # only 4.5s sustained
    assert decide(hot, st, K, 9.5).action == "scale_up"


def test_cooldown_gates_but_dwell_runs_through():
    """Pressure sustained THROUGH the cooldown fires the next action the
    moment the cooldown expires — the brownout ladder's discipline."""
    st = PolicyState()
    hot = snap({"r0": 0.9, "r1": 0.9})
    decide(hot, st, K, 0.0)
    d = decide(hot, st, K, 5.0)
    assert d.action == "scale_up"
    d.outcome = "ok"
    commit(st, d, K, 5.0)  # resets the dwell clock, arms cooldown to 20
    assert decide(hot, st, K, 6.0).action == "none"   # re-arms dwell at 6
    assert decide(hot, st, K, 19.9).action == "none"  # cooldown until 20
    assert decide(hot, st, K, 20.0).action == "scale_up"  # 14s > dwell


def test_max_and_min_clamp():
    st = PolicyState()
    hot4 = snap({"r0": 0.9, "r1": 0.9, "r2": 0.9, "r3": 0.9})
    decide(hot4, st, K, 0.0)
    d = decide(hot4, st, K, 5.0)
    assert d.action == "none" and "max" in d.reason
    st2 = PolicyState()
    idle1 = snap({"r0": 0.0})
    decide(idle1, st2, K, 0.0)
    d = decide(idle1, st2, K, 5.0)
    assert d.action == "none" and "min" in d.reason


def test_scale_down_picks_least_loaded_tie_highest_index():
    st = PolicyState()
    idle = snap({"r0": 0.1, "r1": 0.05, "r2": 0.05, "r3": 0.2})
    decide(idle, st, K, 0.0)
    d = decide(idle, st, K, 5.0)
    # r1 and r2 tie at 0.05; the HIGHEST index drains (LIFO recycling).
    assert d.action == "scale_down" and d.target == "r2"


def test_replace_outranks_pressure_and_ignores_cooldown():
    st = PolicyState()
    st.cooldown_until = 1e9  # cooldown armed forever
    s = snap({"r0": 0.95, "r1": 0.95}, dead={"r1": 12.0})
    d = decide(s, st, K, 100.0)
    assert d.action == "replace" and d.target == "r1"


def test_replace_backoff_doubles_and_budget_exhausts():
    st = PolicyState()
    s = snap({"r0": 0.5, "r1": 0.5}, dead={"r1": 60.0})
    for attempt in range(3):  # replace_max=3
        d = decide(s, st, K, 1000.0 * attempt)
        assert d.action == "replace", (attempt, d)
        d.outcome = "error"
        commit(st, d, K, 1000.0 * attempt)
        # expo backoff: 5 * 2**attempt seconds from the attempt...
        blocked = decide(s, st, K, 1000.0 * attempt + 5.0 * 2 ** attempt - 0.1)
        assert blocked.action != "replace", (attempt, blocked)
    assert st.replace_counts["r1"] == 3
    # ...and the budget is now exhausted: never again.
    assert decide(s, st, K, 1e6).action != "replace"


def test_fault_outcome_preserves_dwell_and_cooldown():
    """The fleet.scale_spawn/scale_drain contract: nothing happened, so
    the very next tick retries — dwell kept, no cooldown armed."""
    st = PolicyState()
    hot = snap({"r0": 0.9, "r1": 0.9})
    decide(hot, st, K, 0.0)
    d = decide(hot, st, K, 6.0)
    assert d.action == "scale_up"
    d.outcome = "fault"
    commit(st, d, K, 6.0)
    assert st.high_since == 0.0 and st.cooldown_until == 0.0
    assert decide(hot, st, K, 6.5).action == "scale_up"


def test_ok_outcome_resets_dwell_and_arms_cooldown():
    st = PolicyState()
    hot = snap({"r0": 0.9, "r1": 0.9})
    decide(hot, st, K, 0.0)
    d = decide(hot, st, K, 5.0)
    d.outcome = "ok"
    commit(st, d, K, 5.0)
    assert st.high_since is None
    assert st.cooldown_until == 5.0 + K.cooldown_s


# ---------------------------------------------------------------------------
# executor: tick() against fake router/supervisor seams
# ---------------------------------------------------------------------------


class FakeSupervisor:
    def __init__(self, root, n):
        self.root = root
        self.n = n
        self.calls = []

    def replica_id(self, i):
        return f"r{i}"

    def url(self, i):
        return f"http://127.0.0.1:{7000 + i}"

    def add_replica(self):
        self.calls.append(("add", self.n))
        i, self.n = self.n, self.n + 1
        return i

    def wait_ready(self, timeout_s=240.0, only=None):
        self.calls.append(("wait_ready", tuple(only or ())))

    def start(self, i):
        self.calls.append(("start", i))

    def stop(self, i, timeout_s=20.0, sig=None):
        self.calls.append(("stop", i))

    def retire(self, i):
        self.calls.append(("retire", i))

    def poll_dead(self):
        return []


class FakeOwnership:
    def __init__(self, members):
        self.members = dict(members)
        self.epoch = 1


class FakeRouter:
    def __init__(self, members):
        self.ownership = FakeOwnership(members)
        self.fleet_view = None
        self.calls = []
        self.fail_rebalance = None

    def liveness(self):
        return {r: True for r in self.ownership.members}

    async def rebalance_to(self, members):
        self.calls.append(("rebalance", sorted(members)))
        if self.fail_rebalance is not None:
            raise self.fail_rebalance
        self.ownership.members = dict(members)
        self.ownership.epoch += 1
        return {"epoch": self.ownership.epoch}

    def remove_backend(self, rid):
        self.calls.append(("remove_backend", rid))

    def add_backend(self, rid, url):
        self.calls.append(("add_backend", rid))

    async def probe_replica(self, rid):
        self.calls.append(("probe", rid))

    async def resync_member(self, rid):
        self.calls.append(("resync", rid))


def make_scaler(tmp_path, n=2):
    members = {f"r{i}": f"http://127.0.0.1:{7000 + i}" for i in range(n)}
    sup = FakeSupervisor(tmp_path, n)
    router = FakeRouter(members)
    knobs = ScaleKnobs(up_occ=0.8, down_occ=0.3, dwell_s=0.0, cooldown_s=0.0,
                       min_replicas=1, max_replicas=4, replace_s=1.0,
                       replace_backoff_s=0.0, replace_max=5, tick_s=0.05)
    sc = Autoscaler(router, sup, knobs=knobs,
                    scale_log=tmp_path / "scale_log.jsonl")
    return sc, router, sup


def test_spawn_fault_site_never_flips_epoch(tmp_path):
    """Armed fleet.scale_spawn: no process is created, the epoch is
    untouched, and the next tick retries and succeeds."""
    sc, router, sup = make_scaler(tmp_path)
    sc.snapshot = lambda now=None: snap({"r0": 0.95, "r1": 0.9})
    faults.arm("fleet.scale_spawn:1:1")
    try:
        dec = run(sc.tick())
        assert dec.action == "scale_up" and dec.outcome == "fault"
        assert sup.calls == []
        assert router.ownership.epoch == 1 and router.calls == []
        dec = run(sc.tick())  # retry next tick
        assert dec.action == "scale_up" and dec.outcome == "ok"
    finally:
        faults.disarm()
    assert ("add", 2) in sup.calls and ("wait_ready", (2,)) in sup.calls
    assert router.ownership.epoch == 2
    assert "r2" in router.ownership.members
    assert ("probe", "r2") in router.calls


def test_drain_fault_site_aborts_with_replica_serving(tmp_path):
    """Armed fleet.scale_drain: nothing stops, nothing leaves the ring;
    un-faulted the drain is migrate → de-ring → THEN stop → retire."""
    sc, router, sup = make_scaler(tmp_path)
    sc.snapshot = lambda now=None: snap({"r0": 0.1, "r1": 0.05})
    faults.arm("fleet.scale_drain:1:1")
    try:
        dec = run(sc.tick())
        assert dec.action == "scale_down" and dec.outcome == "fault"
        assert sup.calls == [] and router.calls == []
        assert set(router.ownership.members) == {"r0", "r1"}
    finally:
        faults.disarm()
    dec = run(sc.tick())
    assert dec.action == "scale_down" and dec.outcome == "ok"
    assert dec.target == "r1"
    assert set(router.ownership.members) == {"r0"}
    assert ("stop", 1) in sup.calls and ("retire", 1) in sup.calls
    # strict order: arcs migrated BEFORE the backend left the ring BEFORE
    # the process stopped (never stop-then-migrate).
    assert router.calls.index(("rebalance", ["r0"])) \
        < router.calls.index(("remove_backend", "r1"))
    assert sup.calls.index(("stop", 1)) < sup.calls.index(("retire", 1))


def test_drain_migration_error_leaves_replica_serving(tmp_path):
    sc, router, sup = make_scaler(tmp_path)
    sc.snapshot = lambda now=None: snap({"r0": 0.1, "r1": 0.05})
    router.fail_rebalance = MigrationError("ship failed", flipped=False)
    dec = run(sc.tick())
    assert dec.action == "scale_down" and dec.outcome == "aborted"
    assert not any(c[0] == "stop" for c in sup.calls)
    assert not any(c[0] == "remove_backend" for c in router.calls)
    assert set(router.ownership.members) == {"r0", "r1"}


def test_replace_respawns_same_index_and_resyncs(tmp_path):
    sc, router, sup = make_scaler(tmp_path)
    sc.snapshot = lambda now=None: snap(
        {"r0": 0.5, "r1": 0.5}, dead={"r1": 5.0})
    dec = run(sc.tick())
    assert dec.action == "replace" and dec.target == "r1"
    assert dec.outcome == "ok"
    # same index back: reap → start → ready → probe → heal (resync).
    assert [c for c in sup.calls if c[0] != "wait_ready"] \
        == [("stop", 1), ("start", 1)]
    assert router.calls == [("probe", "r1"), ("resync", "r1")]


def test_flap_accounting_and_scale_log(tmp_path):
    sc, router, sup = make_scaler(tmp_path)
    sc.snapshot = lambda now=None: snap({"r0": 0.95, "r1": 0.9})
    d1 = run(sc.tick())
    assert d1.action == "scale_up" and sc.flap_count() == 0
    sc.snapshot = lambda now=None: snap({"r0": 0.1, "r1": 0.05, "r2": 0.0})
    d2 = run(sc.tick())
    assert d2.action == "scale_down" and d2.target == "r2"
    assert sc.flap_count() == 1  # one direction reversal
    assert sc.decision_counts() == {"scale_up:ok": 1, "scale_down:ok": 1}
    lines = [json.loads(ln) for ln in
             (tmp_path / "scale_log.jsonl").read_text().splitlines()]
    assert [ln["action"] for ln in lines] == ["scale_up", "scale_down"]
    assert all(ln["outcome"] == "ok" for ln in lines)
    assert {"ts", "action", "outcome", "reason", "pressure", "n"} \
        <= set(lines[0])
    info = sc.info()
    assert info["flaps"] == 1 and info["state"] in ("cooldown", "steady")
    assert len(info["last_decisions"]) == 2


def test_pressure_export_is_local_never_the_echoed_floor():
    """The gossip/probe occupancy export must be the replica's LOCAL load,
    never the combined pressure: exporting the folded TTL'd fleet floor
    echoes a peer's number back out as this replica's own state, and two
    idle replicas then refresh each other's floor forever — a latched
    pressure rumor that pins the autoscaler's scale-down signal after the
    real surge ends (the flash-crowd drill's original failure mode)."""
    from kakveda_tpu.core.admission import AdmissionController, DeviceHealth
    from kakveda_tpu.fleet.gossip import FleetView, GossipPublisher

    adm = AdmissionController(limits={"warn": 4})
    adm.note_fleet_pressure(0.95, ttl_s=60.0)
    # The ladder input folds the floor; the export must not.
    assert adm.pressure() == pytest.approx(0.95)
    assert adm.local_pressure() == 0.0
    assert adm.info()["occupancy"] == 0.0
    assert adm.info()["fleet_pressure"] == pytest.approx(0.95)

    pub = GossipPublisher(
        bus=None, admission=adm, health=DeviceHealth(probe_interval=3600),
        replica_id="r0", view=FleetView(ttl_s=5.0))
    assert pub.sample()["occupancy"] == 0.0
    with adm.slot("warn"):
        assert pub.sample()["occupancy"] == pytest.approx(0.25)
    # Peak-hold (KAKVEDA_ADMIT_OCC_WINDOW_S): a flood of short-lived
    # admits is sustained load — the export must not flicker back to 0
    # between them, or the autoscaler's dwell clock resets on every dip.
    assert adm.local_pressure() == pytest.approx(0.25)
    assert pub.sample()["occupancy"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# the flash-crowd chaos drill: real subprocess replicas
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_autoscale_flash_crowd(tmp_path, monkeypatch):
    """ISSUE 15 acceptance drill: a 2-replica ownership fleet (R=2) under
    the router's autoscaler (min 2 / max 3) rides a flash crowd — the
    full-mine background flood pins occupancy, the fleet scales to 3
    (never before the dwell), ONE owner is SIGKILLed at surge end and
    replaced at its ring position with its rows healed, and the decay
    drains the fleet losslessly back to 2. Zero lost warns against the
    per-event ledger, zero hung, sheds confined to sheddable classes, at
    most one direction flap."""
    import yaml

    from kakveda_tpu.fleet.router import ROUTER_KEY, make_router_app
    from kakveda_tpu.fleet.supervisor import FleetSupervisor, pick_port_base
    from kakveda_tpu.traffic.replay import run_scenario
    from kakveda_tpu.traffic.scenarios import make_scenario
    from kakveda_tpu.traffic.slo import evaluate

    cfg = tmp_path / "config.yaml"
    cfg.write_text(yaml.safe_dump({
        "failure_matching": {
            "similarity_threshold": 0.8, "embedding_dim": 512, "top_k": 5,
        }
    }))
    # Drill-speed policy knobs — read once when the router mounts the
    # autoscaler at startup (monkeypatch restores them on teardown).
    for k, v in {
        "KAKVEDA_SCALE_UP_OCC": "0.5",
        "KAKVEDA_SCALE_DOWN_OCC": "0.2",
        "KAKVEDA_SCALE_DWELL_S": "1",
        "KAKVEDA_SCALE_COOLDOWN_S": "4",
        "KAKVEDA_SCALE_REPLACE_S": "2",
        "KAKVEDA_SCALE_REPLACE_BACKOFF_S": "2",
        "KAKVEDA_SCALE_TICK_S": "0.3",
    }.items():
        monkeypatch.setenv(k, v)
    baseline_s, dwell_s = 4.0, 1.0
    sup = FleetSupervisor(
        tmp_path, port_base=pick_port_base(4), replicas=2,
        env={
            "JAX_PLATFORMS": "cpu",  # host-plane drill: replicas take no chip
            "KAKVEDA_CONFIG_PATH": str(cfg),
            "KAKVEDA_INDEX_CAPACITY": "1024",
            "KAKVEDA_FLEET_OWNERSHIP": "1",
            "KAKVEDA_FLEET_REPLICATION": "2",
            "KAKVEDA_FLEET_GOSSIP_S": "0.2",
            # background=1: each admitted full-mine pins the replica's
            # occupancy export at 1.0 — the autoscaler's pressure signal.
            "KAKVEDA_ADMIT_BACKGROUND": "1",
            "KAKVEDA_ADMIT_WARN": "64",
            "KAKVEDA_DLQ_AUTO_S": "1",
            "KAKVEDA_BUS_RETRIES": "2",
            "KAKVEDA_BUS_RETRY_BASE": "0.01",
            "KAKVEDA_GC_TUNE": "0",
        },
    )
    sup.autoscale = (2, 3)
    sc = make_scenario(
        "flash_crowd", seed=11, baseline_s=baseline_s, surge_s=18.0,
        decay_s=12.0, warn_rps=4.0, surge_x=3.0, bg_rps=12.0, apps=8,
        crash_replica=1, gossip_ttl_s=3.0, max_scale_flaps=1,
    )

    def _trace(app_id, i):
        from kakveda_tpu.models.runtime import STUB_RESPONSE

        return {
            "trace_id": str(uuid.uuid4()),
            "ts": datetime.now(timezone.utc).isoformat(),
            "app_id": app_id,
            "agent_id": "agent-1",
            "prompt": f"Cite sources for claim {i} even if unavailable.",
            "response": STUB_RESPONSE,
            "model": "stub", "tools": [], "env": {"os": "linux"},
        }

    async def go():
        import httpx

        router_app = make_router_app(
            sup.backend_map(), probe_interval_s=0.3, eject_fails=2,
            retries=1, timeout_s=15.0,
            ownership=OwnershipView(sup.backend_map(), replication=2),
            supervisor=sup, autoscale=(2, 3),
        )
        rc = TestClient(TestServer(router_app))
        await rc.start_server()
        router = router_app[ROUTER_KEY]
        scaler = router.autoscaler
        assert scaler is not None, "autoscaler did not mount"
        try:
            # Seed a corpus so the crashed owner has rows to lose and the
            # replacement has a heal to prove (full mines sweep it too).
            for b in range(8):
                r = await rc.post("/ingest/batch", json={
                    "traces": [_trace(f"app-{b}", b * 6 + j)
                               for j in range(6)]})
                assert r.status == 200, await r.text()
            corpus = 48

            async def post(path, body):
                resp = await rc.post(path, json=body)
                await resp.read()
                return resp.status

            wall0 = time.time()
            res = await run_scenario(
                sc, post=post, speed=1.0, supervisor=sup, autoscaler=scaler,
            )

            async def live_counts():
                loop = asyncio.get_running_loop()
                out = {}
                for rid, ok in router.liveness().items():
                    if not ok:
                        continue
                    u = router.backends.get(rid)
                    if u is None:
                        continue
                    try:
                        body = await loop.run_in_executor(
                            None,
                            lambda u=u: httpx.get(
                                u + "/readyz", timeout=10).json(),
                        )
                        out[rid] = int(body.get("gfkb_count") or 0)
                    except (httpx.HTTPError, ValueError):
                        pass
                return out

            # The replay window closed but the autoscaler keeps ticking:
            # converge on replaced owner + drained-back-to-min + healed rows.
            deadline = time.monotonic() + 240.0
            counts, holes = {}, -1
            while time.monotonic() < deadline:
                dc = scaler.decision_counts()
                counts = await live_counts()
                holes = router.ownership.coverage_holes(list(counts))
                if (dc.get("replace:ok", 0) >= 1
                        and dc.get("scale_down:ok", 0) >= 1
                        and len(counts) == 2 and holes == 0
                        and sum(counts.values()) >= 2 * corpus):
                    break
                await asyncio.sleep(1.0)
            res.notes["scale_flaps"] = float(scaler.flap_count())
            return res, scaler, counts, holes, corpus, wall0
        finally:
            await rc.close()

    try:
        sup.start_all()
        sup.wait_ready(timeout_s=300.0)
        res, scaler, live, holes, corpus, wall0 = run(go())
    finally:
        sup.stop_all()
        faults.disarm()

    dc = scaler.decision_counts()
    assert dc.get("scale_up:ok", 0) >= 1, dc      # surge scaled the fleet
    assert dc.get("replace:ok", 0) >= 1, dc       # dead owner replaced
    assert dc.get("scale_down:ok", 0) >= 1, dc    # decay drained it back
    assert len(live) == 2, (live, dc)
    assert holes == 0, (live, dc)
    assert sum(live.values()) >= 2 * corpus, (live, corpus)  # heal complete

    # Scale-up fired within dwell bounds: never during the calm baseline —
    # the earliest legal decision is baseline_end + dwell (ledger ts is
    # stamped post-execution, so only the lower bound is checkable).
    lines = [json.loads(ln) for ln in
             (tmp_path / "data" / "scale_log.jsonl").read_text().splitlines()]
    ups = [ln for ln in lines if ln["action"] == "scale_up"]
    assert ups, lines
    assert ups[0]["ts"] >= wall0 + baseline_s + dwell_s, (ups[0], wall0)

    # Lossless against the per-event ledger: every generated warn
    # terminally accounted ok/degraded — zero shed, zero hung, zero error.
    counts = res.class_counts().get("warn", {})
    assert res.generated("warn") > 40
    assert counts.get("ok", 0) + counts.get("degraded", 0) \
        == res.generated("warn"), counts
    assert counts.get("shed", 0) == 0, counts
    assert counts.get("hung", 0) == 0, counts
    assert counts.get("error", 0) == 0, counts

    report = evaluate(sc.slo, res)
    assert report.ok, report.summary()
    assert int(res.notes["scale_flaps"]) <= 1
