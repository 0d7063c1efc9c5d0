"""Two /warn batches in flight (service/batcher.py): the micro-batcher
closes and hands off batch N+1 while batch N is still on its executor thread,
never a third; only the timing of a batch changes, not what it holds, who it
answers, or what the submit-side bounds count.

``run_batch`` here blocks on one event per batch, so a test decides which
batch returns when. The metrics registry is process-global: every batcher
takes a name of its own and its series start at zero."""

import asyncio
import sys
import threading
import time
import uuid
from datetime import datetime, timezone

import pytest

from kakveda_tpu.core import metrics
from kakveda_tpu.core.admission import DeviceHealth, OverloadError
from kakveda_tpu.core.faults import FaultInjected
from kakveda_tpu.service.batcher import MicroBatcher

DEADLINE_S = 0.005


class Gate:
    """A ``run_batch`` that records each batch at its hand-off and holds it
    until the test opens that batch's event (by hand-off order)."""

    def __init__(self, fail=(), hold=True):
        self.started = []       # the requests of each batch, in hand-off order
        self.t_started = []
        self.events = [threading.Event() for _ in range(16)]
        self.fail = set(fail)
        self._lock = threading.Lock()
        if not hold:
            self.open_all()

    def __call__(self, reqs):
        with self._lock:
            idx = len(self.started)
            self.started.append(list(reqs))
            self.t_started.append(time.monotonic())
        if not self.events[idx].wait(10):
            raise TimeoutError(f"batch {idx} was never released")
        if idx in self.fail:
            raise RuntimeError(f"batch {idx} failed")
        return [f"{r}@{idx}" for r in reqs]

    def open_all(self):
        for e in self.events:
            e.set()


async def _until(cond, timeout=5.0):
    end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < end, "condition never held"
        await asyncio.sleep(0.002)


async def _submit(mb, *reqs):
    """One task per request, enqueued in the order given."""
    tasks = []
    for r in reqs:
        tasks.append(asyncio.create_task(mb.submit(r)))
        await asyncio.sleep(0)
    return tasks


def _series(family, name):
    return metrics.get_registry().snapshot()[family]["series"].get(f"batcher={name}")


def _overlapped(name):
    return _series("kakveda_microbatch_overlapped_total", name)


def _batches(name):
    return _series("kakveda_microbatch_batch_size", name)["count"]


def _phase(name):
    s = metrics.get_registry().snapshot()["kakveda_host_phase_seconds"]["series"]
    return s.get(f"phase={name}", {"count": 0, "sum": 0.0})


def _run(coro_fn, gate, **kw):
    """Drive ``coro_fn(mb)`` against a started batcher; whatever happens, no
    executor thread is left blocked and the drain loop is stopped."""
    kw.setdefault("max_batch", 4)
    kw.setdefault("deadline_s", DEADLINE_S)

    async def go():
        mb = MicroBatcher(gate, **kw)
        mb.start()
        try:
            return await coro_fn(mb)
        finally:
            gate.open_all()
            await mb.stop()

    return asyncio.run(go())


def test_second_batch_is_handed_off_before_the_first_returns_and_the_third_is_not():
    gate = Gate()

    async def go(mb):
        (a,) = await _submit(mb, "a")
        await _until(lambda: len(gate.started) == 1)
        (b,) = await _submit(mb, "b")
        await _until(lambda: len(gate.started) == 2)  # a has not returned
        assert not a.done()
        (c,) = await _submit(mb, "c")
        await asyncio.sleep(10 * DEADLINE_S)  # far past c's deadline
        assert gate.started == [["a"], ["b"]] and not c.done()
        gate.events[0].set()  # a place frees: c closes at once
        await _until(lambda: len(gate.started) == 3)
        assert await a == "a@0" and not b.done()
        gate.open_all()
        assert await asyncio.gather(b, c) == ["b@1", "c@2"]

    _run(go, gate, name="pipe-order")
    # a found nothing in flight; b found a, c found b
    assert _overlapped("pipe-order") == 2 and _batches("pipe-order") == 3


def test_with_both_places_taken_the_open_batch_fills_to_max_batch_and_max_queue_sheds():
    gate = Gate()

    async def go(mb):
        first = await _submit(mb, "a")
        await _until(lambda: len(gate.started) == 1)
        first += await _submit(mb, "b")
        await _until(lambda: len(gate.started) == 2)
        held = await _submit(mb, "c")          # opens the third batch
        await asyncio.sleep(4 * DEADLINE_S)    # its deadline passes; no place
        held += await _submit(mb, *"defghi")   # six more: queued, and counted
        assert mb._depth() == 6
        with pytest.raises(OverloadError) as shed:
            await mb.submit("j")
        assert shed.value.reason == "queue_full"
        assert len(gate.started) == 2
        gate.events[0].set()
        await _until(lambda: len(gate.started) == 3)
        assert gate.started[2] == list("cdef")  # max_batch, in arrival order
        gate.open_all()
        got = await asyncio.gather(*first, *held)
        assert got[:6] == ["a@0", "b@1", "c@2", "d@2", "e@2", "f@2"]
        assert sorted(g.split("@")[0] for g in got[6:]) == list("ghi")

    _run(go, gate, max_batch=4, max_queue=6, name="pipe-fill")


def test_batches_that_return_out_of_order_resolve_their_own_waiters():
    gate = Gate()

    async def go(mb):
        a1, a2 = await _submit(mb, "a1", "a2")
        await _until(lambda: len(gate.started) == 1)
        (b,) = await _submit(mb, "b")
        await _until(lambda: len(gate.started) == 2)
        gate.events[1].set()  # the second batch returns first
        assert await b == "b@1"
        assert not a1.done() and not a2.done()
        gate.events[0].set()
        assert await asyncio.gather(a1, a2) == ["a1@0", "a2@0"]

    _run(go, gate, name="pipe-ooo")


def test_an_exception_fails_the_waiters_of_its_own_batch_only():
    gate = Gate(fail={0})

    async def go(mb):
        a1, a2 = await _submit(mb, "a1", "a2")
        await _until(lambda: len(gate.started) == 1)
        (b,) = await _submit(mb, "b")
        await _until(lambda: len(gate.started) == 2)
        gate.open_all()
        got = await asyncio.gather(a1, a2, b, return_exceptions=True)
        assert [type(g) for g in got[:2]] == [RuntimeError, RuntimeError]
        assert got[0] is got[1] and "batch 0" in str(got[0])
        assert got[2] == "b@1"
        # the failed batch gave its place back: two more can fly
        assert await asyncio.gather(*await _submit(mb, "c")) == ["c@2"]

    _run(go, gate, name="pipe-fail")


@pytest.mark.parametrize("in_flight", [0, 1])
def test_a_lone_request_is_handed_off_after_the_deadline(in_flight):
    gate = Gate()
    deadline_s = 0.05

    async def go(mb):
        if in_flight:
            await _submit(mb, "ahead")
            await _until(lambda: len(gate.started) == 1)
        t0 = time.monotonic()
        (lone,) = await _submit(mb, "lone")
        await _until(lambda: len(gate.started) == in_flight + 1)
        waited = gate.t_started[in_flight] - t0
        assert 0.9 * deadline_s <= waited < 1.0, waited
        gate.open_all()
        assert await lone == f"lone@{in_flight}"

    _run(go, gate, deadline_s=deadline_s, name=f"pipe-lone-{in_flight}")


def test_stop_cancels_the_waiters_in_flight_and_of_the_open_batch():
    gate = Gate()

    async def go():
        mb = MicroBatcher(gate, max_batch=4, deadline_s=DEADLINE_S, name="pipe-stop")
        mb.start()
        (a,) = await _submit(mb, "a")
        await _until(lambda: len(gate.started) == 1)
        (b,) = await _submit(mb, "b")
        await _until(lambda: len(gate.started) == 2)
        (c,) = await _submit(mb, "c")  # the open batch, waiting for a place
        await asyncio.sleep(4 * DEADLINE_S)
        await mb.stop()
        for t in (a, b, c):
            with pytest.raises(asyncio.CancelledError):
                await t
        assert mb._flights == {} and mb._carry == []
        # the two executor calls return to nobody and disturb nothing
        gate.open_all()
        await asyncio.sleep(0.05)
        assert mb._flights == {} and not mb._places.locked()
        # and a restarted batcher has both its places
        mb.start()
        d, e, f = await _submit(mb, "d", "e", "f")
        assert await asyncio.gather(d, e, f) == ["d@2", "e@2", "f@2"]
        await mb.stop()

    try:
        asyncio.run(go())
    finally:
        gate.open_all()


def test_the_overlap_counter_counts_only_hand_offs_beside_a_batch_in_flight():
    gate = Gate(hold=False)

    async def go(mb):
        assert _overlapped("pipe-count") == 0  # there from the start, at zero
        for r in "abc":  # one at a time: each finds nothing in flight
            assert await mb.submit(r) == f"{r}@{len(gate.started) - 1}"
        assert _overlapped("pipe-count") == 0 and _batches("pipe-count") == 3
        for e in gate.events:
            e.clear()
        tasks = await _submit(mb, "d")
        await _until(lambda: len(gate.started) == 4)
        tasks += await _submit(mb, "e")
        await _until(lambda: len(gate.started) == 5)
        gate.open_all()
        await asyncio.gather(*tasks)
        assert _overlapped("pipe-count") == 1 and _batches("pipe-count") == 5

    _run(go, gate, name="pipe-count")


def test_fair_composition_happens_once_at_the_close_and_the_carry_waits_for_a_place():
    gate = Gate()

    async def go(mb):
        tasks = await _submit(mb, "f-0")
        await _until(lambda: len(gate.started) == 1)
        tasks += await _submit(mb, "f-1")
        await _until(lambda: len(gate.started) == 2)
        tasks += await _submit(mb, "f-2", "f-3", "v-0", "f-4", "f-5")
        await asyncio.sleep(4 * DEADLINE_S)
        assert len(gate.started) == 2 and mb._carry == []  # nothing composed yet
        gate.events[0].set()
        await _until(lambda: len(gate.started) == 3)
        # 2 x max_batch candidates, one seat a tenant while both have work
        assert gate.started[2] == ["f-2", "v-0"]
        await asyncio.sleep(4 * DEADLINE_S)
        # both places taken again: the carry stays where stop() and the
        # submit-side bounds see it
        assert [it[0] for it in mb._carry] == ["f-3", "f-4"] and mb._depth() == 3
        gate.events[1].set()
        await _until(lambda: len(gate.started) == 4)
        assert gate.started[3] == ["f-3", "f-4"]  # per-tenant FIFO survives
        gate.open_all()
        got = await asyncio.gather(*tasks)
        assert sorted(g.split("@")[0] for g in got) == [
            "f-0", "f-1", "f-2", "f-3", "f-4", "f-5", "v-0"]

    _run(go, gate, max_batch=2, tenant_key=lambda r: r.split("-")[0], name="pipe-fair")


def test_each_batch_leaves_one_cycle_and_its_parts_sum_to_at_most_it():
    name = "pipe-phases"

    def run_batch(reqs):
        time.sleep(0.02)
        return list(reqs)

    async def go():
        mb = MicroBatcher(run_batch, max_batch=2, deadline_s=0.001, name=name)
        mb.start()
        try:
            return await asyncio.gather(*await _submit(mb, *[f"r{i}" for i in range(12)]))
        finally:
            await mb.stop()

    assert asyncio.run(go()) == [f"r{i}" for i in range(12)]
    batches = _batches(name)
    parts = [_phase(f"{name}.batcher.{p}") for p in ("collect", "handoff", "resolve")]
    cycle = _phase(f"{name}.cycle")
    assert batches >= 6 and cycle["count"] == batches
    assert [p["count"] for p in parts] == [batches] * 3
    # a batch's life holds its collect, its 20 ms on the executor and its
    # resolve; lives overlap, so they sum to more than the wall they took
    assert cycle["sum"] >= sum(p["sum"] for p in parts) + 0.02 * batches - 1e-3
    assert _overlapped(name) >= batches - 2
    assert _phase(f"{name}.batcher.wake")["count"] == 12


def test_each_batch_leaves_one_patterns_and_one_policy_phase(tmp_path):
    """The benchmark reads both names (`warn_patterns_ms`, `warn_policy_ms`,
    `warn_cycle_unspanned_pct`): the verdict tail of every batch observes
    each exactly once, however little is left inside."""
    from kakveda_tpu.core.schemas import WarningRequest
    from kakveda_tpu.platform import Platform

    name = "pipe-tail"
    plat = Platform(data_dir=tmp_path / "data", capacity=64, dim=1024)
    reqs = [WarningRequest(app_id="app-A", prompt=f"Summarize report {i} with citations.",
                           tools=[], env={"os": "linux"}) for i in range(10)]
    tail = ("warn.patterns", "warn.policy")
    before = [_phase(p)["count"] for p in tail]

    async def go():
        mb = MicroBatcher(plat.warn_batch, max_batch=2, deadline_s=0.001, name=name)
        mb.start()
        try:
            return await asyncio.gather(*await _submit(mb, *reqs))
        finally:
            await mb.stop()

    assert len(asyncio.run(asyncio.wait_for(go(), 120))) == len(reqs)
    batches = _batches(name)
    assert batches >= 5
    assert [_phase(p)["count"] - b for p, b in zip(tail, before)] == [batches, batches]


def test_latching_a_lost_device_twice_is_one_transition():
    """Two batches in flight may both discover the loss."""
    h = DeviceHealth(probe_interval=3600, probe_fn=lambda: None)

    def transitions():
        return metrics.get_registry().snapshot()[
            "kakveda_device_degraded_transitions_total"]["series"].get("to=degraded", 0)

    before = transitions()
    lost = FaultInjected("device.unavailable")
    try:
        got = []
        threads = [threading.Thread(target=lambda: got.append(h.note_failure(lost, where="gfkb.match")))
                   for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(5)
        assert got == [True, True] and h.degraded
        assert transitions() - before == 1
        assert len([e for e in h.recorder.dump() if e["kind"] == "degraded"]) == 1
    finally:
        h.unlatch("test over")


def test_overlapped_batches_give_every_request_its_own_exact_answer(tmp_path):
    """The real warn path under two executor threads with a short switch
    interval: each of many concurrent requests gets the answer a lone call
    gives it, and batches did overlap."""
    from kakveda_tpu.core.schemas import TracePayload, WarningRequest
    from kakveda_tpu.models.runtime import STUB_RESPONSE
    from kakveda_tpu.platform import Platform

    name = "pipe-real"
    plat = Platform(data_dir=tmp_path / "data", capacity=256, dim=1024)
    topics = ["billing ledger", "kernel scheduler", "protein folding", "tax treaty",
              "violin repair", "glacier retreat"]
    prompts = [f"Summarize the {t} report and include citations for every claim about {t}."
               for t in topics]
    asyncio.run(plat.ingest_batch([
        TracePayload(trace_id=str(uuid.uuid4()), ts=datetime.now(timezone.utc), app_id=f"app-{i}",
                     agent_id="agent-1", prompt=p, response=STUB_RESPONSE, model="stub",
                     temperature=0.2, tools=[], env={"os": "linux"})
        for i, p in enumerate(prompts)]))
    reqs = [WarningRequest(app_id=f"app-{i % 3}", prompt=prompts[i % len(prompts)], tools=[],
                           env={"os": "linux"}) for i in range(48)]
    alone = [plat.warn_batch([r])[0] for r in reqs[:len(prompts)]]
    assert len({a.references[0].failure_id for a in alone}) == len(prompts)

    async def go():
        mb = MicroBatcher(plat.warn_batch, max_batch=4, deadline_s=0.001, name=name)
        mb.start()
        try:
            return await asyncio.gather(*[mb.submit(r) for r in reqs])
        finally:
            await mb.stop()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got = asyncio.run(asyncio.wait_for(go(), 120))
    finally:
        sys.setswitchinterval(interval)
    for i, res in enumerate(got):
        want = alone[i % len(prompts)]
        assert res.references[0].failure_id == want.references[0].failure_id
        assert res.confidence == pytest.approx(want.confidence, abs=1e-6)
        assert (res.action, res.pattern_id, res.degraded, res.tier) == (
            want.action, want.pattern_id, want.degraded, want.tier)
    assert _batches(name) >= 12 and _overlapped(name) > 0
