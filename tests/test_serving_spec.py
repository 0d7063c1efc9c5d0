"""Speculative decoding inside the continuous-batching engine: each chunk
verifies k host-drafted tokens in ONE forward, advancing greedy slots
1..k+1 tokens per weight stream — with outputs TOKEN-IDENTICAL to the
plain chunked path (accepted drafts equal their own greedy verdicts by
construction; corrections are greedy).

Decode is weight-bandwidth-bound, so the k+1-wide verify rides the same
weight stream as a 1-wide step; on repetitive traffic (judge templates,
citation lists) acceptance multiplies tokens/stream. KAKVEDA_SERVE_SPEC=k
enables it on the engine; sampled slots fall back to plain chunks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kakveda_tpu.models.generate import generate_tokens
from kakveda_tpu.models.llama import LlamaConfig, init_params
from kakveda_tpu.models.serving import ContinuousBatcher, ServingEngine

CFG = LlamaConfig(
    vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)

PROMPTS = [[5, 6, 7], [10, 11, 12, 13, 14], [42], [9, 8]]


def _solo(params, cfg, n=12):
    return [
        generate_tokens(params, cfg, p, max_new_tokens=n, max_len=128) for p in PROMPTS
    ]


LOOP_PROMPT = [7, 8, 9, 7, 8, 9, 7, 8, 9]


@functools.lru_cache(maxsize=None)
def _period1_model(n=40, run=16):
    """(params, solo output) of the first seeded tiny model whose greedy
    continuation of LOOP_PROMPT ends in a constant run of at least ``run``
    tokens: the period-1 regime speculation is for. Searched, not assumed
    of one seed: which random model settles into a loop changes with the
    JAX version's RNG and the CPU's summation order (about one seed in
    eight does)."""
    for seed in range(64):
        params = init_params(jax.random.PRNGKey(seed), CFG)
        solo = generate_tokens(params, CFG, LOOP_PROMPT, max_new_tokens=n, max_len=128)
        if solo[-run:] == [solo[-1]] * run:
            return params, solo
    raise AssertionError("no seed in range(64) gives a model that settles into a constant run")


def test_spec_chunk_parity_multi_slot():
    """run_all's step() dispatches to verify chunks for a greedy pool."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    solo = _solo(params, CFG)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    assert cb.run_all(PROMPTS, max_new_tokens=12) == solo
    assert cb.spec_stats["chunks"] > 0
    # Every chunk emits at least one token per active slot.
    assert cb.spec_stats["emitted"] >= cb.spec_stats["slot_chunks"]


def test_draft_lookup_semantics():
    """The host draft heuristic itself (acceptance-neutral to parity, so
    only a direct test catches a shift bug that would silently collapse
    the speculative win): longest recent suffix match, copy SHIFTED by
    one (the copy's first token is the t0 analog, not a draft)."""
    d = ContinuousBatcher._draft
    # History "A B C x ... A B C" — 3-token suffix matches at j=2; the
    # t0 analog is hist[3] (=9), drafts start at hist[4].
    hist = [7, 8, 3, 9, 4, 5, 7, 8, 3]
    assert d(hist, 4) == [4, 5, 7, 8]
    # Single-token match only: last token 3 occurred at j=1; t0 analog is
    # hist[2], drafts from hist[3].
    hist2 = [1, 3, 6, 2, 5, 3]
    assert d(hist2, 3) == [2, 5, 3]
    # Longest match preferred over a more recent shorter one: suffix
    # [8, 3] matches ending at j=2 even though a later lone 3 sits at
    # j=4; the t0 analog is hist[3] (=1), drafts start at hist[4].
    hist3 = [9, 8, 3, 1, 3, 2, 8, 3]
    assert d(hist3, 2) == [3, 2]
    # No earlier occurrence / degenerate history: PAD drafts.
    assert d([1, 2, 3], 3) == [0, 0, 0]
    assert d([5], 2) == [0, 0]
    assert d([], 2) == [0, 0]
    # Copy region running off the end extrapolates PERIODICALLY (period =
    # anchor distance): [4, 6] tiles forward instead of padding.
    hist4 = [4, 6, 4]
    assert d(hist4, 4) == [4, 6, 4, 6]


def test_draft_period1_not_degenerate():
    """A trailing same-token run used to anchor at j=n-2 with an empty
    copy region — all-PAD drafts, zero acceptance on exactly the most
    repetitive traffic speculation targets. Periodic extrapolation tiles
    the run (period 1) instead."""
    d = ContinuousBatcher._draft
    assert d([5, 5, 5, 5, 5], 4) == [5, 5, 5, 5]
    assert d([9, 3, 7, 7, 7], 3) == [7, 7, 7]
    # Period-2 loop drafts its own continuation.
    assert d([1, 2, 1, 2, 1, 2], 3) == [2, 1, 2]


def test_spec_acceptance_on_repetitive_traffic():
    """A model that settles into an argmax loop must accept drafts:
    emitted tokens per slot-chunk > 1 on average (the spec win exists).
    The output ends in a period-1 constant run — the exact case the old
    suffix lookup degenerated to all-PAD drafts on (anchoring at j=n-2 left
    an empty copy region; periodic extrapolation tiles the run instead),
    which left this assertion failing at rate == 1.0."""
    params, solo = _period1_model()
    p = LOOP_PROMPT
    cb = ContinuousBatcher(params, CFG, batch_slots=1, max_len=128, chunk_steps=4, spec_k=4)
    rid = cb.admit(p, max_new_tokens=40)
    while cb.slots:
        cb.step_spec()
    assert cb.results[rid] == solo
    rate = cb.spec_stats["emitted"] / cb.spec_stats["slot_chunks"]
    assert rate > 1.3, cb.spec_stats
    assert cb.spec_stats["accepted"] > 0
    # Adaptive k recovered to the ceiling inside the constant run.
    assert max(cb.spec_stats["k_trace"]) == 4


def test_spec_parity_int8_kv():
    cfg = LlamaConfig(
        vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32, kv_quant="int8",
    )
    params = init_params(jax.random.PRNGKey(2), cfg)
    solo = _solo(params, cfg, n=8)
    cb = ContinuousBatcher(params, cfg, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    assert cb.run_all(PROMPTS, max_new_tokens=8) == solo


def test_spec_parity_sliding_window():
    cfg = LlamaConfig(
        vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32, sliding_window=12,
    )
    params = init_params(jax.random.PRNGKey(3), cfg)
    solo = _solo(params, cfg, n=10)
    cb = ContinuousBatcher(params, cfg, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    assert cb.run_all(PROMPTS, max_new_tokens=10) == solo


def test_engine_spec_greedy_and_sampled_fallback(monkeypatch):
    """Engine with KAKVEDA_SERVE_SPEC: greedy traffic goes through verify
    chunks (spec stats move) with exact solo parity; a sampled request
    flips the pool to plain chunks and still completes."""
    from concurrent.futures import ThreadPoolExecutor

    monkeypatch.setenv("KAKVEDA_SERVE_SPEC", "4")
    params = init_params(jax.random.PRNGKey(0), CFG)
    solo = _solo(params, CFG)
    eng = ServingEngine(params, CFG, batch_slots=2, max_len=128, chunk_steps=4)
    try:
        assert eng.cb.spec_k == 4
        with ThreadPoolExecutor(max_workers=len(PROMPTS)) as ex:
            outs = list(ex.map(lambda p: eng.generate_ids(p, 12), PROMPTS))
        assert outs == solo
        assert eng.cb.spec_stats["chunks"] > 0
        sampled = eng.generate_ids([5, 6, 7], 8, temperature=0.9)
        assert len(sampled) >= 1
    finally:
        eng.close()


def test_all_levers_compose():
    """Spec verify chunks + prefix caching + int8 KV + streaming callbacks
    in ONE engine, exact parity with solo decode — the composite a real
    deployment would run (judge traffic: shared template head, greedy,
    quantized cache, streamed to the UI)."""
    cfg = LlamaConfig(
        vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32, kv_quant="int8",
    )
    params = init_params(jax.random.PRNGKey(5), cfg)
    head = list(range(40, 56))
    prompts = [head + [5, 6], head + list(range(80, 95)), head]
    solo = [
        generate_tokens(params, cfg, p, max_new_tokens=10, max_len=128) for p in prompts
    ]
    streamed = {i: [] for i in range(len(prompts))}
    cb = ContinuousBatcher(params, cfg, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    assert cb.register_prefix(head)
    rids = {}
    pending = list(enumerate(prompts))
    while pending or cb.slots:
        while pending and cb.free:
            i, p = pending.pop(0)
            rids[cb.admit(
                p, max_new_tokens=10,
                on_tokens=(lambda i: lambda new, done: streamed[i].extend(new))(i),
            )] = i
        cb.step()  # dispatches spec (greedy pool)
    outs = [None] * len(prompts)
    for rid, i in rids.items():
        outs[i] = cb.results[rid]
        assert streamed[i] == cb.results[rid]
    assert outs == solo
    assert cb.spec_stats["chunks"] > 0
    assert cb.prefix_stats["hits"] == len(prompts)


def test_spec_streaming_callbacks():
    """on_tokens fires per verify chunk with the accepted tokens."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    got, flags = [], []
    cb = ContinuousBatcher(params, CFG, batch_slots=1, max_len=128, chunk_steps=4, spec_k=4)
    rid = cb.admit(
        [5, 6, 7], max_new_tokens=10,
        on_tokens=lambda new, done: (got.extend(new), flags.append(done)),
    )
    while cb.slots:
        cb.step_spec()
    assert got == cb.results[rid]
    assert flags[-1] is True


# ---------------------------------------------------------------------------
# Acceptance auto-gate, per-slot adaptive k, and pipelined verify chunks.
# ---------------------------------------------------------------------------


def _drain_pipelined_spec(cb, prompts, max_new=12):
    """The ServingEngine's pipelined ordering, inline: dispatch verify
    chunk i+1 before fetching chunk i's acceptance; drain before any
    admission; fall back to pipelined plain chunks when spec_ready()
    says so (sampled slot or gate off)."""
    pending = list(enumerate(prompts))
    order, handle, spec_handle = {}, None, None
    while pending or cb.slots or handle is not None or spec_handle is not None:
        if pending and cb.free and spec_handle is not None:
            cb.process_spec_chunk(spec_handle)
            spec_handle = None
        while pending and cb.free:
            i, p = pending.pop(0)
            order[cb.admit(p, max_new_tokens=max_new)] = i
        if cb.spec_ready():
            cb.process_chunk(handle)
            handle = None
            if spec_handle is not None and cb.spec_pipeline_ready():
                nxt = cb.step_spec_async()
                cb.process_spec_chunk(spec_handle)
                spec_handle = nxt
            else:
                cb.process_spec_chunk(spec_handle)
                spec_handle = None
                if cb.slots and cb.spec_ready():
                    spec_handle = cb.step_spec_async()
        elif cb.slots:
            cb.process_spec_chunk(spec_handle)
            spec_handle = None
            nxt = cb.step_async()
            cb.process_chunk(handle)
            handle = nxt
        else:
            cb.process_chunk(handle)
            cb.process_spec_chunk(spec_handle)
            handle = spec_handle = None
    outs = [None] * len(prompts)
    for rid, i in order.items():
        outs[i] = cb.results.pop(rid)
    return outs


def test_pipelined_spec_parity(monkeypatch):
    """Verify chunk i+1 dispatched before chunk i's acceptance reaches
    the host (device-threaded slot_pos, cursor drafts) stays token-
    identical to solo decode — including across retire/admit boundaries
    where the pipeline must drain and resync from host mirrors."""
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_CALIB", "0")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_BREAKEVEN", "0")  # gate stays open
    params = init_params(jax.random.PRNGKey(0), CFG)
    solo = _solo(params, CFG)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    assert _drain_pipelined_spec(cb, PROMPTS) == solo
    assert cb.spec_stats["chunks"] > 0
    assert cb._spec_pending == 0  # pipeline fully drained


def test_pipelined_spec_cursor_continues_accepted_run(monkeypatch):
    """On a period-1 pool the pipelined path must KEEP accepting: the
    cursor extends the in-flight chunk's predicted emission, so full-
    accept chunks chain without the host ever seeing the previous chunk
    first (the acceptance-preserving half of the pipeline win)."""
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_CALIB", "0")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_BREAKEVEN", "0")
    params, solo = _period1_model()
    cb = ContinuousBatcher(params, CFG, batch_slots=1, max_len=128, chunk_steps=4, spec_k=4)
    assert _drain_pipelined_spec(cb, [LOOP_PROMPT], max_new=40) == [solo]
    s = cb.spec_stats
    assert s["emitted"] / s["slot_chunks"] > 1.3, s
    assert s["accepted"] > 0


def test_gate_disables_spec_on_low_acceptance(monkeypatch):
    """A pool whose acceptance can't clear break-even must turn itself
    OFF after warmup and decode plain — parity intact, later chunks are
    plain chunks (no more configured slowdowns)."""
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_CALIB", "0")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_WARMUP", "2")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_BREAKEVEN", "1000")  # unreachable
    params = init_params(jax.random.PRNGKey(0), CFG)
    solo = _solo(params, CFG)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    assert cb.run_all(PROMPTS, max_new_tokens=12) == solo
    assert cb.spec_stats["gate_state"] == "off"
    assert cb.spec_stats["chunks"] >= 2  # warmup spec chunks ran
    spec_chunks_at_off = cb.spec_stats["chunks"]
    assert len(cb._plain_walls) > 0  # post-gate decoding went plain
    # A second drain on the gated-off pool runs NO spec chunks at all.
    assert cb.run_all(PROMPTS, max_new_tokens=12) == solo
    assert cb.spec_stats["chunks"] == spec_chunks_at_off


def test_gate_keeps_spec_on_high_acceptance(monkeypatch):
    """The opposite verdict: acceptance above break-even keeps the gate
    ON through warmup (speculation stays enabled for the pool)."""
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_CALIB", "0")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_WARMUP", "2")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_BREAKEVEN", "0")
    params = init_params(jax.random.PRNGKey(2), CFG)
    p = [7, 8, 9, 7, 8, 9, 7, 8, 9]
    cb = ContinuousBatcher(params, CFG, batch_slots=1, max_len=128, chunk_steps=4, spec_k=4)
    cb.run_all([p], max_new_tokens=40)
    assert cb.spec_stats["gate_state"] == "on"
    assert cb.spec_stats["tokens_per_verify"] > 1.0


def test_gate_reprobe_reenters_warmup(monkeypatch):
    """An OFF gate re-probes after KAKVEDA_SERVE_SPEC_REPROBE plain
    chunks: traffic may have turned repetitive, and warmup (with a
    hysteresis margin) re-measures instead of staying off forever."""
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_CALIB", "0")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_WARMUP", "1")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_BREAKEVEN", "1000")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_REPROBE", "2")
    params = init_params(jax.random.PRNGKey(0), CFG)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    cb.run_all(PROMPTS, max_new_tokens=12)
    spec_chunks = cb.spec_stats["chunks"]
    assert spec_chunks >= 1
    # Another drain: the re-probe window re-opens the gate to warmup and
    # spec chunks run again (then the unreachable break-even closes it).
    cb.run_all(PROMPTS, max_new_tokens=12)
    assert cb.spec_stats["chunks"] > spec_chunks


def test_adaptive_k_shrinks_on_rejection(monkeypatch):
    """A slot whose drafts keep missing halves its draft width toward 1
    (the k trace ends narrow), so dead speculation stops paying host
    drafting and verify width."""
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_CALIB", "0")
    monkeypatch.setenv("KAKVEDA_SERVE_SPEC_BREAKEVEN", "0")
    params = init_params(jax.random.PRNGKey(1), CFG)
    p = [7, 8, 9, 7, 8, 9, 7, 8, 9]  # this seed's output does NOT loop
    cb = ContinuousBatcher(params, CFG, batch_slots=1, max_len=128, chunk_steps=4, spec_k=4)
    cb.run_all([p], max_new_tokens=24)
    kt = cb.spec_stats["k_trace"]
    assert kt[0] == 4 and 1 in kt, kt


def test_cancel_during_inflight_verify_chunk():
    """cancel_request between step_spec_async and process_spec_chunk: the
    stale snapshot must skip the cancelled slot (done-flag first), the
    survivor keeps exact solo parity, and the freed slot re-admits
    cleanly after the pipeline drains."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    p_keep, p_cancel = [10, 11, 12, 13, 14], [5, 6, 7]
    solo_keep = generate_tokens(params, CFG, p_keep, max_new_tokens=12, max_len=128)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    rid_c = cb.admit(p_cancel, max_new_tokens=12)
    rid_k = cb.admit(p_keep, max_new_tokens=12)
    h = cb.step_spec_async()
    got = cb.cancel_request(rid_c)
    assert got == []  # nothing emitted yet
    finished = cb.process_spec_chunk(h)
    assert rid_c not in finished
    while cb.slots:
        cb.step_spec()
    assert cb.results[rid_k] == solo_keep
    # Freed slot is reusable and the re-admitted request is exact too.
    rid2 = cb.admit(p_cancel, max_new_tokens=8)
    while cb.slots:
        cb.step_spec()
    assert cb.results[rid2] == generate_tokens(
        params, CFG, p_cancel, max_new_tokens=8, max_len=128
    )


def test_admit_refused_while_verify_chunk_in_flight():
    """Admission with an un-processed verify chunk would race the
    device-threaded slot_pos — it must refuse loudly, and succeed after
    the handle is processed."""
    import pytest

    params = init_params(jax.random.PRNGKey(0), CFG)
    cb = ContinuousBatcher(params, CFG, batch_slots=2, max_len=128, chunk_steps=4, spec_k=4)
    cb.admit([5, 6, 7], max_new_tokens=8)
    h = cb.step_spec_async()
    with pytest.raises(RuntimeError, match="in flight"):
        cb.admit([1, 2, 3], max_new_tokens=8)
    cb.process_spec_chunk(h)
    cb.admit([1, 2, 3], max_new_tokens=8)
    while cb.slots:
        cb.step_spec()


def test_prefix_slab_drafting():
    """A slot whose own history has NO anchor defers to a registered
    prefix's n-gram index: template spans draft from the slab corpus
    (the cross-corpus fallback) with literal, non-cyclic copies — so
    template traffic drafts continuations its short history has never
    emitted."""
    params = init_params(jax.random.PRNGKey(5), CFG)
    head = list(range(40, 56))
    cb = ContinuousBatcher(params, CFG, batch_slots=1, max_len=128, chunk_steps=4, spec_k=4)
    assert cb.register_prefix(head)
    # No token repeats inside this prompt → no self-anchor; the (43, 44)
    # bigram exists only in the registered head.
    cb.admit([7, 43, 44], max_new_tokens=8)
    st = list(cb.slots.values())[0]
    drafts, cursor, pred = cb._draft_slot(st, 4)
    assert st.index.anchor == (-1, 0)  # no self-anchor: prefix corpus answered
    # The head continues (43, 44) with 45, 46, ... — pred[0] is the t0
    # analog, drafts follow it.
    assert pred == [45, 46, 47, 48, 49]
    assert drafts == [46, 47, 48, 49]
    assert cursor is not None
    while cb.slots:
        cb.step_spec()
