"""Streaming generation: engine on_tokens callbacks, runtime text-delta
generator, and the playground SSE endpoint.

Beyond-reference capability: the reference's playground blocks on one full
Ollama reply per request (services/dashboard/app.py:3127-3299); here text
deltas reach the client per decode chunk, token-identical to the blocking
path.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from kakveda_tpu.models.generate import LlamaRuntime, generate_tokens
from kakveda_tpu.models.llama import LlamaConfig, init_params
from kakveda_tpu.models.serving import ContinuousBatcher, ServingEngine

CFG = LlamaConfig(
    vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)


def test_batcher_on_tokens_streams_exact_results():
    """Chunk callbacks deliver exactly the tokens the blocking result
    carries, in order, with done=True on the final chunk."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13]]
    streamed = {0: [], 1: []}
    flags = {0: [], 1: []}

    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=64, chunk_steps=4)
    rids = [
        cb.admit(
            p, max_new_tokens=10,
            on_tokens=(lambda i: lambda new, done: (streamed[i].extend(new), flags[i].append(done)))(i),
        )
        for i, p in enumerate(prompts)
    ]
    while cb.active:
        cb.step()
    for i, rid in enumerate(rids):
        assert streamed[i] == cb.results[rid]
        assert flags[i][-1] is True
        assert all(f is False for f in flags[i][:-1])


def test_engine_stream_callback_runs_on_loop():
    params = init_params(jax.random.PRNGKey(0), CFG)
    got = []
    eng = ServingEngine(params, CFG, batch_slots=2, max_len=64, chunk_steps=4)
    try:
        fut = eng.submit([5, 6, 7], 8, on_tokens=lambda new, done: got.extend(new))
        result = fut.result(timeout=120)
        assert got == result
    finally:
        eng.close()


def test_engine_cancel_frees_slot_midflight():
    """cancel() on a mid-decode request resolves its Future with the
    partial tokens and frees the slot for new traffic; a later request
    still gets exact solo parity (the cancelled slot's rows are masked
    and overwritten like any retired slot's)."""
    import time as _time

    from kakveda_tpu.models.generate import generate_tokens

    params = init_params(jax.random.PRNGKey(0), CFG)
    solo = generate_tokens(params, CFG, [9, 8, 7], max_new_tokens=10, max_len=64)
    eng = ServingEngine(params, CFG, batch_slots=1, max_len=64, chunk_steps=2)
    try:
        fut = eng.submit([5, 6, 7], 40)
        for _ in range(200):  # wait until it is actually decoding
            if eng.cb.active:
                break
            _time.sleep(0.05)
        eng.cancel(fut)
        partial = fut.result(timeout=60)
        assert len(partial) < 40  # stopped early, partial tokens returned
        # The freed slot serves the next request with exact parity.
        assert eng.generate_ids([9, 8, 7], 10) == solo
    finally:
        eng.close()


def test_engine_cancel_queued_request():
    """Cancelling a request still waiting for a slot cancels its Future
    outright and it is never admitted."""
    from concurrent.futures import CancelledError

    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = ServingEngine(params, CFG, batch_slots=1, max_len=64, chunk_steps=2)
    try:
        first = eng.submit([5, 6, 7], 30)  # occupies the only slot
        waiting = eng.submit([1, 2, 3], 30)
        eng.cancel(waiting)
        with pytest.raises(CancelledError):
            waiting.result(timeout=60)
        assert len(first.result(timeout=120)) > 0  # the running one completes
        assert eng.stats()["completed"] == 1
    finally:
        eng.close()


def test_generate_stream_cancel_before_first_token(monkeypatch):
    """A streaming request still WAITING for a slot (pool full, zero
    deltas delivered) cancels promptly when the consumer sets the cancel
    event — it must not sit until its first token arrives."""
    import threading
    import time as _time

    from kakveda_tpu.models.generate import LlamaRuntime

    monkeypatch.setenv("KAKVEDA_SERVE_CONTINUOUS", "1")
    monkeypatch.setenv("KAKVEDA_SERVE_SLOTS", "1")
    rt = LlamaRuntime(cfg=CFG, seed=0)
    try:
        eng = rt.engine()
        # The blocker occupies the only slot until the test lets it go: its
        # first chunk's callback holds the loop (a bounded wait). Its length
        # alone does not hold it — with the programs already compiled (this
        # file's earlier tests, one worker) 40 tokens take less than the
        # second slept below, and the queued stream was admitted after all.
        release = threading.Event()
        blocker = eng.submit([5, 6, 7], 40, on_tokens=lambda new, done: release.wait(60))
        cancel_ev = threading.Event()
        got: list = []

        def consume():
            for d in rt.generate_stream("queued then abandoned", max_tokens=10, cancel=cancel_ev):
                got.append(d)

        t = threading.Thread(target=consume)
        t.start()
        _time.sleep(1.0)  # let it enqueue behind the blocker
        cancel_ev.set()
        t.join(timeout=30)
        assert not t.is_alive(), "stream consumer still blocked after cancel"
        assert got == []  # never produced a token
        release.set()
        assert len(blocker.result(timeout=120)) > 0  # slot owner unaffected
    finally:
        release.set()
        rt.retire()


@pytest.mark.parametrize("seed,spec_k", [(0, 0), (1, 0), (2, 4), (3, 4)])
def test_engine_randomized_submit_cancel_stress(seed, spec_k):
    """Randomized interleaving of submits and cancels against the live
    engine: every Future must resolve (result or CancelledError), the
    slot pool must fully drain (free == B), and accounting must balance.
    The slot-reuse/cancel/pipelining interactions this shakes out are
    exactly the ones a deterministic test can't enumerate."""
    import random
    from concurrent.futures import CancelledError

    rng = random.Random(seed)
    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = ServingEngine(params, CFG, batch_slots=2, max_len=64, chunk_steps=2, spec_k=spec_k)
    futs = []
    try:
        for _ in range(24):
            op = rng.random()
            if op < 0.7 or not futs:
                prompt = [rng.randrange(5, 250) for _ in range(rng.randrange(1, 9))]
                futs.append(eng.submit(prompt, rng.randrange(4, 24)))
            else:
                eng.cancel(rng.choice(futs))
            if rng.random() < 0.3:
                import time as _time

                _time.sleep(0.05)
        results = 0
        cancelled = 0
        for f in futs:
            try:
                toks = f.result(timeout=300)
                assert isinstance(toks, list)
                results += 1
            except CancelledError:
                cancelled += 1
        assert results + cancelled == len(futs)
        # Pool fully drained: every slot back on the free list.
        for _ in range(100):
            if len(eng.cb.free) == eng.cb.B and not eng.cb.slots:
                break
            import time as _time

            _time.sleep(0.1)
        assert len(eng.cb.free) == eng.cb.B and not eng.cb.slots
    finally:
        eng.close()


@pytest.mark.parametrize("continuous", ["1", "0"])
def test_runtime_generate_stream_matches_generate(monkeypatch, continuous):
    """Joined deltas equal the blocking generate() text on BOTH paths —
    engine streaming and the chunked solo fallback."""
    monkeypatch.setenv("KAKVEDA_SERVE_CONTINUOUS", continuous)
    rt = LlamaRuntime(cfg=CFG, seed=0)
    try:
        prompt = "stream parity check"
        blocking = rt.generate(prompt, max_tokens=12).text
        parts = list(rt.generate_stream(prompt, max_tokens=12))
        assert len(parts) >= 1
        assert "".join(parts) == blocking
    finally:
        rt.retire()


def test_playground_stream_sse(tmp_path, monkeypatch):
    """The SSE endpoint emits delta events then a done event, records the
    run, and the concatenated deltas equal the blocking response text."""
    from kakveda_tpu.dashboard.app import make_dashboard_app
    from kakveda_tpu.platform import Platform

    monkeypatch.setenv("KAKVEDA_SERVE_CONTINUOUS", "1")
    from kakveda_tpu.dashboard.core import RATE_LIMITER

    RATE_LIMITER._hits.clear()
    rt = LlamaRuntime(cfg=CFG, seed=0)
    plat = Platform(data_dir=tmp_path / "data", capacity=256, dim=1024)
    app = make_dashboard_app(platform=plat, db_path=tmp_path / "dash.db", model=rt)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/login",
                data={"email": "admin@local", "password": "admin123", "next": "/"},
                allow_redirects=False,
            )
            assert r.status == 302
            blocking = rt.generate("hello stream").text  # endpoint default max_tokens
            r = await client.post(
                "/playground/stream", data={"prompt": "hello stream", "target": "model"}
            )
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            body = await r.text()
            events = [
                json.loads(line[len("data: "):])
                for line in body.splitlines()
                if line.startswith("data: ")
            ]
            assert events, body
            assert events[-1].get("done") is True
            text = "".join(e.get("delta", "") for e in events)
            assert text == blocking
            # The run landed in trace_runs like /playground/run does.
            r = await client.get("/runs?q=provider:tpu")
            assert r.status == 200
        finally:
            await client.close()

    asyncio.run(go())
    rt.retire()


def test_playground_stream_stub_and_fallback(tmp_path):
    """The stub runtime streams word-by-word (hermetic SSE demo), and a
    runtime WITHOUT generate_stream still streams via the one-delta
    fallback."""
    from kakveda_tpu.dashboard.app import make_dashboard_app
    from kakveda_tpu.dashboard.core import RATE_LIMITER
    from kakveda_tpu.models.runtime import StubRuntime
    from kakveda_tpu.platform import Platform

    class NoStream(StubRuntime):
        generate_stream = None  # simulate a runtime without streaming

    RATE_LIMITER._hits.clear()
    plat = Platform(data_dir=tmp_path / "data", capacity=256, dim=1024)
    app = make_dashboard_app(
        platform=plat, db_path=tmp_path / "dash.db", model=StubRuntime()
    )
    app2 = make_dashboard_app(
        platform=plat, db_path=tmp_path / "dash2.db", model=NoStream()
    )

    from kakveda_tpu.models.runtime import STUB_RESPONSE

    async def run_one(a):
        client = TestClient(TestServer(a))
        await client.start_server()
        try:
            r = await client.post(
                "/login",
                data={"email": "admin@local", "password": "admin123", "next": "/"},
                allow_redirects=False,
            )
            assert r.status == 302
            r = await client.post(
                "/playground/stream", data={"prompt": "please cite sources"}
            )
            assert r.status == 200
            events = [
                json.loads(line[len("data: "):])
                for line in (await r.text()).splitlines()
                if line.startswith("data: ")
            ]
            deltas = [e["delta"] for e in events if "delta" in e]
            assert events[-1].get("done") is True
            return deltas
        finally:
            await client.close()

    async def go():
        word_deltas = await run_one(app)
        assert len(word_deltas) > 1 and "".join(word_deltas) == STUB_RESPONSE
        fallback_deltas = await run_one(app2)
        assert len(fallback_deltas) == 1 and fallback_deltas[0] == STUB_RESPONSE

    asyncio.run(go())
