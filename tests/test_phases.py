"""The phase primitive (core/profiling.py) and where the two served loops use
it: every phase of a /warn batch cycle and of a serving-engine iteration
lands in ``kakveda_host_phase_seconds{phase}``, the per-request series add up
to what the caller waits for, and a long phase counts as a stall.

The registry is process-global: every assertion compares two snapshots."""

import asyncio
import importlib
import sys
import uuid
from datetime import datetime, timezone

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from kakveda_tpu.core import metrics, profiling
from kakveda_tpu.models.llama import LlamaConfig, init_params
from kakveda_tpu.models.runtime import STUB_RESPONSE

PHASES = "kakveda_host_phase_seconds"
STALLS = "kakveda_host_stall_seconds_total"

WARN_CHILDREN = (
    "warn.batcher.collect", "warn.batcher.handoff", "warn.signature",
    "gfkb.match.featurize", "gfkb.match.dispatch", "gfkb.match.fetch",
    "gfkb.match.assemble", "warn.patterns", "warn.policy", "warn.batcher.resolve",
)
SERVE_PHASES = (
    "serve.cycle", "serve.wait", "serve.pump", "serve.admit", "serve.expire",
    "serve.chunk.dispatch", "serve.chunk.fetch", "serve.chunk.process",
)


def _trace(app_id: str, prompt: str) -> dict:
    return {
        "trace_id": str(uuid.uuid4()), "ts": datetime.now(timezone.utc).isoformat(),
        "app_id": app_id, "agent_id": "agent-1", "prompt": prompt, "response": STUB_RESPONSE,
        "model": "stub", "temperature": 0.2, "tools": [], "env": {"os": "linux"},
    }


def _hist(family: str, label: str) -> dict:
    s = metrics.get_registry().snapshot().get(family, {}).get("series", {})
    return s.get(label, {"count": 0, "sum": 0.0})


def _phase(name: str) -> dict:
    return _hist(PHASES, f"phase={name}")


def _stall(loop: str) -> float:
    return metrics.get_registry().snapshot()[STALLS]["series"].get(f"loop={loop}", 0.0)


def _stall_events(phase: str) -> list:
    return [e for e in profiling._RECORDER.dump() if e["kind"] == "stall" and e["phase"] == phase]


def test_annotate_observes_the_phase_once_per_block():
    before = _phase("unit.once")
    for _ in range(3):
        with profiling.annotate("unit.once"):
            pass
    after = _phase("unit.once")
    assert after["count"] - before["count"] == 3
    assert after["sum"] >= before["sum"]


def test_annotate_reraises_the_blocks_own_exception_unchanged():
    boom = KeyError("the block's own")
    before = _phase("unit.raises")["count"]
    with pytest.raises(KeyError) as got:
        with profiling.annotate("unit.raises"):
            raise boom
    assert got.value is boom
    assert _phase("unit.raises")["count"] - before == 1  # observed all the same


def test_a_name_resolves_to_one_child():
    first = profiling.annotate("unit.child")._ph
    assert profiling.annotate("unit.child")._ph is first
    assert first.hist is metrics.get_registry().histogram(
        PHASES, "", ("phase",)).labels(phase="unit.child")
    profiling.observe_phase("unit.child", 0.001)
    assert profiling._PHASES["unit.child"] is first


def test_observe_phase_adds_the_callers_seconds():
    before = _phase("unit.observed")
    profiling.observe_phase("unit.observed", 0.004)
    profiling.observe_phase("unit.observed", 0.006)
    after = _phase("unit.observed")
    assert after["count"] - before["count"] == 2
    assert after["sum"] - before["sum"] == pytest.approx(0.010, abs=1e-5)


def test_annotate_works_with_the_profiler_patched_away(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # the import raises
    try:
        mod = importlib.reload(profiling)
        assert mod._TraceAnnotation.__module__ == mod.__name__  # the stand-in
        before = _phase("unit.noprofiler")["count"]
        with mod.annotate("unit.noprofiler"):
            pass
        assert _phase("unit.noprofiler")["count"] - before == 1
    finally:
        monkeypatch.undo()
        importlib.reload(profiling)
    assert profiling._TraceAnnotation is jax.profiler.TraceAnnotation


@pytest.mark.parametrize(
    "phase,seconds,loop,counts",
    [
        ("warn.policy", 0.25, "warn", True),
        ("gfkb.match.fetch", 0.15, "warn", True),
        ("serve.chunk.process", 0.30, "serve", True),
        ("warn.policy", 0.05, "warn", False),            # under the limit
        ("serve.admit", 0.099, "serve", False),
        ("warn.batcher.collect", 5.0, "warn", False),    # waits for arrivals by design
        ("serve.wait", 0.25, "serve", False),
        ("serve.chunk.fetch", 0.125, "serve", False),    # waits for the device's chunk by design
        ("warn.cycle", 5.0, "warn", False),              # contains the wait, and every child
        ("serve.cycle", 0.25, "serve", False),
        ("warn.http", 0.25, "warn", False),              # per request: concurrent, not loop time
        ("warn.batcher.wake", 0.25, "warn", False),
        ("gfkb.insert", 0.40, "warn", False),            # not a phase of either loop
        ("llama.generate", 3.0, "serve", False),
    ],
)
def test_a_long_loop_phase_is_a_stall(phase, seconds, loop, counts):
    stall0, events0 = _stall(loop), len(_stall_events(phase))
    profiling.observe_phase(phase, seconds)
    moved = _stall(loop) - stall0
    events = _stall_events(phase)[events0:]
    if counts:
        assert moved == pytest.approx(seconds, abs=1e-5)
        assert len(events) == 1 and events[0]["ms"] == pytest.approx(seconds * 1e3)
    else:
        assert moved == 0 and events == []


def test_a_bare_scrape_names_the_new_families_and_not_the_old_one():
    text = metrics.MetricsRegistry().render()
    assert "kakveda_device_block_seconds" not in text
    for family in (PHASES, STALLS, "kakveda_microbatch_wait_seconds",
                   "kakveda_serving_first_chunk_seconds"):
        assert f"# TYPE {family} " in text
    # an engine or batcher that never stalled reads 0, not nothing
    live = metrics.get_registry().render()
    assert f'{STALLS}{{loop="warn"}}' in live and f'{STALLS}{{loop="serve"}}' in live
    assert not hasattr(metrics, "device_block")


def test_every_phase_of_a_warn_cycle_is_observed(tmp_path):
    """N concurrent /warn through the service app: each phase of the cycle
    moves, every request leaves one queue wait and one handler observation,
    and the phases cover the cycle (disjoint parts of it: never more than the
    whole, and on an unloaded loop nearly all of it)."""
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app

    n = 24
    plat = Platform(data_dir=tmp_path / "data", capacity=256, dim=1024)
    app = make_app(plat)

    def body(i):
        return {"app_id": f"app-{i % 3}", "prompt": f"Summarize document {i} and include citations.",
                "tools": [], "env": {"os": "linux"}}

    async def round_of(client, count):
        rs = await asyncio.gather(*[client.post("/warn", json=body(i)) for i in range(count)])
        assert [r.status for r in rs] == [200] * count

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            for i in range(3):  # a small index: an empty one answers before any dispatch
                r = await client.post("/ingest", json={"trace": _trace(f"app-{i}", body(i)["prompt"])})
                assert r.status == 200
            await round_of(client, 4)  # the match program compiles here
            names = WARN_CHILDREN + ("warn.cycle", "warn.http", "warn.batcher.wake")
            before = {p: _phase(p) for p in names}
            wait0 = _hist("kakveda_microbatch_wait_seconds", "batcher=warn")["count"]
            await round_of(client, n)
            after = {p: _phase(p) for p in names}
            wait1 = _hist("kakveda_microbatch_wait_seconds", "batcher=warn")["count"]
            return before, after, wait1 - wait0
        finally:
            await client.close()

    before, after, waits = asyncio.run(go())
    moved = {p: after[p]["count"] - before[p]["count"] for p in after}
    assert all(v > 0 for v in moved.values()), moved
    assert waits == n and moved["warn.http"] == n and moved["warn.batcher.wake"] == n
    cycles = moved["warn.cycle"]
    assert all(moved[p] == cycles for p in WARN_CHILDREN), moved
    spent = {p: after[p]["sum"] - before[p]["sum"] for p in after}
    children = sum(spent[p] for p in WARN_CHILDREN)
    assert children <= spent["warn.cycle"] + 1e-4
    assert children >= 0.8 * spent["warn.cycle"], spent


def test_the_engine_loop_is_spanned_and_ttft_adds_up():
    """A ServingEngine at a tiny size: every phase of an iteration is seen,
    each request leaves one first-chunk observation, and queue wait + prefill +
    first chunk is the recorded time to the first token."""
    from kakveda_tpu.models.serving import ServingEngine

    cfg = LlamaConfig(
        vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jax.numpy.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7], [10, 11, 12, 13, 14], [42], [9, 8], [100, 101, 102, 103]]
    label = "engine=phases-test"
    before = {p: _phase(p)["count"] for p in SERVE_PHASES}
    first0 = _hist("kakveda_serving_first_chunk_seconds", label)["count"]
    # 2 slots for 5 requests: some wait in the queue, some are admitted at once
    eng = ServingEngine(params, cfg, batch_slots=2, max_len=64, chunk_steps=4, name="phases-test")
    try:
        futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
    finally:
        eng.close()
    assert all(len(o) == 9 for o in outs)
    for f in futs:
        tl = f.timeline
        assert tl["first_chunk_ms"] >= 0
        assert tl["queue_wait_ms"] + tl["prefill_ms"] + tl["first_chunk_ms"] == pytest.approx(
            tl["ttft_ms"], abs=1.0)
    assert _hist("kakveda_serving_first_chunk_seconds", label)["count"] - first0 == len(prompts)
    moved = {p: _phase(p)["count"] - before[p] for p in SERVE_PHASES}
    assert all(v > 0 for v in moved.values()), moved
    assert moved["serve.admit"] == len(prompts)
    events = [e for e in eng.recorder.dump() if e["kind"] == "request"]
    assert len(events) == len(prompts) and all("first_chunk_ms" in e for e in events)
