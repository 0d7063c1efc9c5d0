"""Benchmarks: warn p50 @1M GFKB, ingest throughput, serving + mining.

One `python bench.py` run measures warn, ingest, decode MFU (+curve,
+int8), speculative decode, continuous batching, warn-under-ingest,
warn-under-decode and pattern mining, and prints ONE JSON line —
headline = the warn north star, with the rest under ``extra_metrics`` so
the driver's BENCH_r{N}.json carries every number.
``KAKVEDA_BENCH_METRIC=warn|pallas|ingest|decode|spec|continuous|mixed|
mixed-decode|mine|serve|overload|tiered|recovery|ownership|storm|tenants|
elastic`` runs a single metric instead (``overload`` floods the HTTP tier past its
admission bounds and proves shedding keeps warn p95 bounded; ``tiered``
A/Bs the IVF-routed tiered GFKB against the exact oracle at 1M rows plus
a 10M host/disk arm — docs/robustness.md, docs/performance.md § tiered;
``recovery`` certifies the GFKB durability lifecycle — ≥5× restart
replay after compaction, recall@1 parity, aging resident-bytes bound,
crash-point sweep with zero corrupt recoveries — docs/robustness.md
§ failure-memory lifecycle;
``storm`` replays the seeded hot-key-skew + failure-storm scenario with
its chaos timeline through the traffic harness and self-certifies the
SLO gates — kakveda_tpu/traffic/, docs/robustness.md § traffic harness;
``elastic`` runs the flash-crowd autoscaling drill — scale 2→4→2 with a
SIGKILLed owner replaced, zero lost warns, ≤1 flap — and self-certifies
the elastic contract, docs/scale-out.md § elastic fleet).

== warn: pre-flight warning p50 latency at a 1M-entry GFKB.

The north-star metric (BASELINE.md): the reference answers a pre-flight
match by reading the whole failures.jsonl, pydantic-validating every row,
re-fitting a TF-IDF vectorizer on (query + corpus) and scoring with sklearn
— O(N) work per request (reference: services/gfkb/app.py:79-102,
services/shared/similarity.py:14-20). Here the same request is: hash-embed
the query (host), one warm compiled matmul + sharded top-k on device, map
slots to records (host).

``vs_baseline`` is the measured speedup over the reference's algorithm on
this same host: sklearn TF-IDF refit+score timed at a small corpus size and
scaled linearly to the benchmark index size (its cost is O(N) in corpus
rows; linear extrapolation is *generous* to the reference since refit
memory effects get worse, and waiting for real 1M-row refits would take
minutes per query).

Measured as the per-request cost of the μ-batched serving pipeline (batch
i's device match overlaps batch i-1's result fetch) — the configuration the
warn service actually runs; single-request wall latency is printed to
stderr.

The chip metrics need a TPU and fail without one; the host-plane drills
(``_HOST_DRILLS`` below) time Python, C++ and subprocess orchestration,
run wherever they are started, pin every replica child to the CPU and say
``platform: cpu`` in their row.

Prints exactly one JSON line:
  {"metric": "preflight_warn_p50_ms_at_<N>_gfkb", "value": <p50 ms/request>,
   "unit": "ms", "vs_baseline": <reference_p50_ms / our_p50_ms>}

Env knobs: KAKVEDA_BENCH_N (index entries; default 1M on TPU, 100k
elsewhere), KAKVEDA_BENCH_DIM (default 2048), KAKVEDA_BENCH_QUERIES,
KAKVEDA_BENCH_BATCH (warn μ-batch, default 64), KAKVEDA_BENCH_TRACES /
KAKVEDA_BENCH_INGEST_BATCH (ingest), KAKVEDA_BENCH_DECODE_PRESET (1b|tiny)
/ KAKVEDA_BENCH_DECODE_BATCH / KAKVEDA_BENCH_DECODE_STEPS (decode MFU).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _measure_ours(n: int, dim: int, n_queries: int) -> float:
    import jax

    from kakveda_tpu.core.fingerprint import signature_text
    from kakveda_tpu.ops.featurizer import HashedNGramFeaturizer
    from kakveda_tpu.ops.knn import ShardedKnn
    from kakveda_tpu.parallel.mesh import create_mesh

    import jax.numpy as jnp

    mesh = create_mesh("data:-1")
    knn = ShardedKnn(mesh, capacity=n, dim=dim, k=5)
    emb, valid = knn.alloc()

    if os.environ.get("KAKVEDA_BENCH_REAL_EMB", "0") == "1":
        # Honest variant: embed n GENERATED signature texts with the
        # production featurizer (chunked, off-clock) instead of random unit
        # vectors — hashed n-gram rows are sparse and clustered, so this
        # rules out surprises from tie-handling on near-duplicate scores.
        # Setup costs minutes at 1M (host featurize + sparse upload).
        t0 = time.time()
        feat_fill = HashedNGramFeaturizer(dim=dim)
        verbs = ["Summarize", "Explain", "Describe", "Review", "Audit", "Outline"]
        tails = [
            "and include citations even if not provided",
            "adding references for every claim",
            "with sources listed",
            "without making up sources",
        ]
        chunk = 1 << 14
        types = None
        for start in range(0, n, chunk):
            m = min(chunk, n - start)
            sigs_fill = [
                signature_text(
                    f"{verbs[(start + i) % len(verbs)]} document {start + i} "
                    f"{tails[(start + i) % len(tails)]}",
                    [],
                    {"os": "linux"},
                )
                for i in range(m)
            ]
            sp_i, sp_v = feat_fill.encode_batch_sparse(sigs_fill)
            if types is None:
                types = knn.alloc_i32()
            emb, valid, types = knn.insert_sparse(
                emb, valid, types, sp_i, sp_v,
                np.arange(start, start + m, dtype=np.int32),
                np.zeros(m, np.int32),
            )
        jax.block_until_ready(emb)
        print(f"bench: real-embedding fill of {n:,} rows took {time.time() - t0:.0f}s", file=sys.stderr)
    else:
        # Default: random unit vectors generated *on device* (embedding 1M
        # signature texts on one host — or shipping 8 GB of vectors over
        # the wire — would dominate setup; the device-side match cost, the
        # thing being measured, is identical — verified by the
        # KAKVEDA_BENCH_REAL_EMB=1 variant, docs/performance.md).
        chunk = 1 << 16

        @jax.jit
        def _fill(emb_buf, valid_buf, key, start):
            v = jax.random.normal(key, (chunk, dim), jnp.float32)
            v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
            emb_buf = jax.lax.dynamic_update_slice(emb_buf, v.astype(emb_buf.dtype), (start, 0))
            valid_buf = jax.lax.dynamic_update_slice(
                valid_buf, jnp.ones((chunk,), jnp.bool_), (start,)
            )
            return emb_buf, valid_buf

        key = jax.random.PRNGKey(0)
        for start in range(0, n - chunk + 1, chunk):
            key, sub = jax.random.split(key)
            emb, valid = _fill(emb, valid, sub, start)
        jax.block_until_ready(emb)
    # Lightweight metadata side-table (what GFKB.match consults after top-k).
    meta = [{"failure_id": f"F-{i:07d}", "failure_type": "HALLUCINATION_CITATION"} for i in range(n)]

    feat = HashedNGramFeaturizer(dim=dim)
    B = int(os.environ.get("KAKVEDA_BENCH_BATCH", 64))  # μ-batch of concurrent pre-flights
    depth = int(os.environ.get("KAKVEDA_BENCH_PIPELINE", 4))
    # Need enough batches to fill the pipeline and still record ≥8 periods.
    n_batches = max(depth + 8, n_queries // B)
    sig_batches = [
        [
            signature_text(
                f"Summarize document {b}-{i} and include citations even if not provided.",
                [],
                {"os": "linux"},
            )
            for i in range(B)
        ]
        for b in range(n_batches)
    ]

    def finish(packed):
        # Sparse dispatch buckets ragged batches; rows past B are pad rows
        # (all-zero queries — they score 0.0 against real rows, so slice,
        # don't threshold).
        scores, slots = knn.topk_result(packed)
        return [
            [{**meta[int(s)], "score": float(v)} for v, s in zip(sr, tr) if v > -1.0 and int(s) < n]
            for sr, tr in zip(scores[:B], slots[:B])
        ]

    # Warm both stages. From here the measured loop reuses the one bucketed
    # batch shape — the ledger window (when armed) must see ZERO compiles
    # past this line, the runtime twin of the static retrace-hazard rule.
    warm = knn.topk_async_sparse(emb, valid, *feat.encode_batch_sparse(sig_batches[0]))
    finish(warm)
    _ledger_mark_warm()

    # Pipelined serving loop with a depth-D in-flight window: batch i's
    # device match + host copy overlap the fetches of batches i-1..i-D, the
    # way the warn service drains its μ-batch queue. Per-request cost is the
    # steady-state pipeline period / B.
    from collections import deque

    periods = []
    inflight: deque = deque()
    t_prev = time.perf_counter()
    for sigs in sig_batches:
        q_idx, q_val = feat.encode_batch_sparse(sigs)
        inflight.append(knn.topk_async_sparse(emb, valid, q_idx, q_val))
        if len(inflight) > depth:
            res = finish(inflight.popleft())
            assert len(res) == B
            now = time.perf_counter()
            periods.append((now - t_prev) * 1000.0)
            t_prev = now
    while inflight:
        finish(inflight.popleft())

    # Single-request wall latency (same compiled batch shape, padded),
    # dispatch through result fetch.
    t0 = time.perf_counter()
    finish(knn.topk_async_sparse(emb, valid, *feat.encode_batch_sparse(sig_batches[0])))
    single_ms = (time.perf_counter() - t0) * 1000.0
    print(f"bench: single-batch wall latency {single_ms:.1f} ms", file=sys.stderr)

    return float(np.percentile(periods, 50)) / B


def _measure_ingest(n_traces: int, batch: int) -> tuple[float, float, float]:
    """Streaming-ingest throughput: traces/sec through the full pipeline
    (fingerprint + rule classify + hash-embed + batched device insert +
    failure.detected fan-out to pattern/health reactors).

    Returns (ours_tps, sequential_tps) where sequential is the same
    pipeline driven one trace at a time with per-append flush — the
    reference's processing model (per-trace HTTP event → classify → JSONL
    append, services/failure_classifier/app.py:30-91) minus its 5
    container-boundary HTTP hops, so the comparison is generous to it.
    """
    import asyncio
    import tempfile
    from datetime import datetime, timezone
    from pathlib import Path

    from kakveda_tpu.core.schemas import TracePayload
    from kakveda_tpu.platform import Platform

    def mk_traces(m: int, tag: str):
        ts = datetime.now(timezone.utc)
        return [
            TracePayload(
                trace_id=f"t-{tag}-{i}",
                ts=ts,
                app_id=f"app-{i % 7}",
                agent_id="bench",
                prompt=f"Summarize report {i} with citations for every claim.",
                response=f"Done [{i}] (Smith 2021) as requested.",
                model="stub",
                tools=[],
                env={"os": "linux"},
            )
            for i in range(m)
        ]

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-"))
    plat = Platform(data_dir=tmp / "batched", capacity=1 << 20, dim=2048)

    async def run_batched() -> float:
        warm = mk_traces(batch, "warm")
        await plat.ingest_batch(warm)  # compile embed+insert for this shape
        traces = mk_traces(n_traces, "b")
        t0 = time.perf_counter()
        for i in range(0, n_traces - batch + 1, batch):
            await plat.ingest_batch(traces[i : i + batch])
        dt = time.perf_counter() - t0
        return (n_traces // batch) * batch / dt

    ours_tps = asyncio.run(run_batched())

    seq_n = min(n_traces, 512)  # sequential is slow; sample and report its rate
    plat_seq = Platform(data_dir=tmp / "seq", capacity=1 << 14, dim=2048)

    async def run_seq() -> float:
        await plat_seq.ingest_batch(mk_traces(1, "warmseq"))
        traces = mk_traces(seq_n, "s")
        t0 = time.perf_counter()
        for t in traces:
            await plat_seq.ingest(t)  # per-trace bus fan-out, like the reference
        dt = time.perf_counter() - t0
        return seq_n / dt

    seq_tps = asyncio.run(run_seq())

    # HTTP e2e variant: the same batched pipeline driven through the REAL
    # aiohttp server (POST /ingest/batch) by concurrent clients — shows
    # what request framing/validation costs against the in-process rate
    # (VERDICT r4 #4; the reference's surface is per-trace HTTP,
    # services/ingestion/app.py:15-21).
    async def run_http() -> float:
        from aiohttp.test_utils import TestClient, TestServer

        from kakveda_tpu.service.app import make_app

        plat_http = Platform(data_dir=tmp / "http", capacity=1 << 20, dim=2048)
        app = make_app(platform=plat_http)
        server = TestServer(app)
        await server.start_server()
        n_clients = int(os.environ.get("KAKVEDA_BENCH_INGEST_CLIENTS", 4))
        clients = [TestClient(server) for _ in range(n_clients)]
        for c in clients:
            await c.start_server()
        try:
            # Payloads serialized off-clock; warm the compiled embed+insert.
            warm = [t.model_dump(mode="json") for t in mk_traces(batch, "hw")]
            await clients[0].post("/ingest/batch", json={"traces": warm})
            payloads = [t.model_dump(mode="json") for t in mk_traces(n_traces, "h")]
            chunks = [
                payloads[i : i + batch] for i in range(0, n_traces - batch + 1, batch)
            ]

            async def worker(client, mine):
                for ch in mine:
                    r = await client.post("/ingest/batch", json={"traces": ch})
                    assert r.status == 200, await r.text()

            t0 = time.perf_counter()
            await asyncio.gather(
                *(worker(c, chunks[i::n_clients]) for i, c in enumerate(clients))
            )
            dt = time.perf_counter() - t0
            return len(chunks) * batch / dt
        finally:
            for c in clients:
                await c.close()

    http_tps = asyncio.run(run_http())
    return ours_tps, seq_tps, http_tps


def _preset_cfg(preset: str):
    """Model shapes for the serving benches: '1b' = TinyLlama-1.1B (the
    small-open-checkpoint serving class), else the tiny CPU smoke shape."""
    from kakveda_tpu.models.llama import LlamaConfig

    return LlamaConfig.tinyllama_1b() if preset == "1b" else LlamaConfig()


# Published per-chip peaks, keyed by jax.devices()[0].device_kind exactly
# as JAX reports it: (bf16 FLOP/s, HBM bytes/s). Source: Google Cloud TPU
# documentation, system architecture pages for each generation. A device
# that is not listed is an error — utilisation against somebody else's
# peak is not a number.
DEVICE_PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),  # v5e
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),  # v6e / Trillium
}


def device_peaks(device_kind: str) -> tuple:
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one chip of this kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"bench.DEVICE_PEAKS with its source (known: {sorted(DEVICE_PEAKS)})"
        ) from None


def _measure_decode(preset: str, bsz: int, steps: int) -> dict:
    """Serving bench: prefill + steady-state decode tokens/sec and MFU on
    the current chip, via the fused whole-generation-on-device decode
    (models/generate.py:generate_tokens_fused — one compiled program per
    generation).

    Weight VALUES don't affect speed, so the model is random-init at real
    shapes (no pretrained weights ship in this image); `vs_baseline` is the
    batched-vs-unbatched throughput ratio measured in the same run.
    """
    import jax
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import _generate_fused_jit
    from kakveda_tpu.models.llama import init_cache, init_params

    cfg = _preset_cfg(preset)

    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), init_params(jax.random.PRNGKey(0), cfg)
    )
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    if os.environ.get("KAKVEDA_BENCH_QUANT") == "int8":
        from kakveda_tpu.models.quant import quantize_params_int8

        params = quantize_params_int8(params)
        print("bench[decode]: int8 weight-only quantization enabled", file=sys.stderr)
    # Matmul FLOPs/token: 2·(params excl. embedding gather) + attention
    # (QK^T and PV: 4·L·ctx·d_model at the mean decode context).
    n_mat = n_params - int(np.prod(params["embed"].shape))
    plen = 128
    mean_ctx = plen + steps / 2
    flops_per_tok = 2 * n_mat + 4 * cfg.n_layers * mean_ctx * cfg.d_model

    # HBM bandwidth is the decode roofline: MFU alone makes decode look
    # bad (it is bandwidth-bound); % of peak HBM says how close to the
    # real ceiling the run is.
    kind = jax.devices()[0].device_kind
    peak_flops, peak_hbm = device_peaks(kind)

    rng = np.random.default_rng(0)

    def timed(prm, b: int, p: int, n_steps: int, reps: int = 3, cfg_=None) -> float:
        """Best-of-reps wall time of one fused generation (prefill p tokens
        + n_steps decode) at batch b. Every timing carries the same fixed
        dispatch + fetch cost — all derived numbers below are *slopes*
        between two timings, which cancels it."""
        c = cfg_ or cfg
        toks = jnp.asarray(rng.integers(3, c.vocab_size, size=(b, p)), jnp.int32)
        valid = jnp.ones((b, 512), bool)
        offs = jnp.zeros((b,), jnp.int32)
        key = jax.random.PRNGKey(0)
        temp = jnp.asarray(1e-6, jnp.float32)

        def gen():
            cache = init_cache(c, batch=b, max_len=512)
            out = _generate_fused_jit(
                prm, c, toks, cache, valid, offs, key, temp, n_steps, True
            )
            return np.asarray(out)

        gen()  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            gen()
            best = min(best, time.perf_counter() - t0)
        return best

    s_lo = max(1, steps // 4)

    def decode_rate(prm, b: int, cfg_=None) -> float:
        dt = timed(prm, b, plen, steps, cfg_=cfg_) - timed(prm, b, plen, s_lo, cfg_=cfg_)
        return b * (steps - s_lo) / max(dt, 1e-9)

    decode_tps = decode_rate(params, bsz)
    solo_tps = decode_rate(params, 1)
    # Batch-scaling curve: defaults to 4×/8× the configured batch so an
    # operator who shrank KAKVEDA_BENCH_DECODE_BATCH for a small device
    # never gets surprise-large allocations; KAKVEDA_BENCH_DECODE_CURVE
    # overrides (empty string disables).
    curve = {}
    curve_env = os.environ.get("KAKVEDA_BENCH_DECODE_CURVE", f"{bsz * 4},{bsz * 8}")
    for b in (int(x) for x in curve_env.split(",") if x):
        if b != bsz:
            curve[b] = decode_rate(params, b)
    curve[bsz] = decode_tps

    # int8 weight-only decode at the same batch: decode streams every dense
    # weight from HBM per step, so halving the weight bytes is the headline
    # serving lever (models/quant.py). Skipped when the main run is already
    # int8 (KAKVEDA_BENCH_QUANT) or KAKVEDA_BENCH_INT8=0.
    int8_tps = None
    int8_curve: dict = {}
    if (
        os.environ.get("KAKVEDA_BENCH_QUANT") != "int8"
        and os.environ.get("KAKVEDA_BENCH_INT8", "1") != "0"
    ):
        from kakveda_tpu.models.quant import quantize_params_int8

        qparams = quantize_params_int8(params)
        int8_tps = decode_rate(qparams, bsz)
        # int8 row of the SAME batch curve: halving the weight stream
        # matters most where weights dominate traffic (small batch) and
        # fades as the KV cache takes over (large batch) — the crossover
        # is visible only with both rows measured.
        int8_curve = {bsz: int8_tps}
        for b in curve:
            if b != bsz:
                int8_curve[b] = decode_rate(qparams, b)
        del qparams

    # int8 KV cache at the largest curve batch: past the crossover the
    # cache is the binding HBM stream, so this is where cache quant pays.
    kv8_tps = None
    if os.environ.get("KAKVEDA_BENCH_KV8", "1") != "0":
        import dataclasses as _dc

        cfg8 = _dc.replace(cfg, kv_quant="int8")
        b_big = max(curve)
        kv8_tps = {b_big: decode_rate(params, b_big, cfg8)}
        if bsz != b_big:
            kv8_tps[bsz] = decode_rate(params, bsz, cfg8)

    # Prefill slope between two prompt lengths at one decode step.
    p_hi = 384
    dt_p = timed(params, bsz, p_hi, 1) - timed(params, bsz, plen, 1)
    prefill_tps = bsz * (p_hi - plen) / max(dt_p, 1e-9)

    mfu = decode_tps * flops_per_tok / peak_flops
    prefill_mfu = prefill_tps * (2 * n_mat) / peak_flops

    # Decode roofline: achieved HBM traffic as a fraction of peak
    # bandwidth. Per step the chip streams every dense weight once
    # (2 bytes/param bf16) plus each sequence's K/V prefix
    # (2·L·KV·hd·mean_ctx·2 bytes); "good" decode = hbm_util near 1,
    # NOT mfu near 1 (decode is bandwidth-bound by construction).
    def hbm_util(tps: float, b: int, w_bytes_per_param: float, cache_itemsize: float) -> float:
        w_bytes = w_bytes_per_param * n_mat
        kv_bytes = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * mean_ctx * cache_itemsize
        return (tps / b) * (w_bytes + b * kv_bytes) / peak_hbm

    cache_b = 2 if cfg.dtype == jnp.bfloat16 else 4
    utils = {"bf16": hbm_util(decode_tps, bsz, 2.0, cache_b)}
    if int8_tps:
        utils["int8"] = hbm_util(int8_tps, bsz, 1.0, cache_b)
    if kv8_tps:
        b_big = max(kv8_tps)
        # int8 rows + one f32 scale per head_dim elements
        utils["kv8"] = hbm_util(kv8_tps[b_big], b_big, 2.0, 1.0 + 4.0 / cfg.head_dim)
    return {
        "decode_tps": decode_tps,
        "prefill_tps": prefill_tps,
        "solo_tps": solo_tps,
        "int8_tps": int8_tps,
        "int8_curve": int8_curve,
        "kv8_tps": kv8_tps,
        "hbm_util": utils,
        "mfu": mfu,
        "prefill_mfu": prefill_mfu,
        "curve": curve,
        "n_params": n_params,
        "batch": bsz,
        "device_kind": kind,
        "peak_tflops": peak_flops / 1e12,
        "peak_hbm_gbps": peak_hbm / 1e9,
    }


def _measure_spec(preset: str, steps: int, k: int) -> dict:
    """Draft-free speculative decoding vs plain fused decode, single
    sequence (the playground / LLM-judge path). Both are ONE compiled
    program per generation; timings are slopes between two generation
    lengths (cancels the remote-TPU dispatch RTT). tokens/round is the
    measured acceptance — each round costs one weight stream, so the
    speedup ceiling is tokens_per_round (weight-bandwidth-bound decode).
    Weight values DO affect this metric (acceptance depends on how
    repetitive the model's output is); random-init is the conservative
    case — real checkpoints on judge-style prompts repeat far more."""
    import jax
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import generate_tokens_fused
    from kakveda_tpu.models.llama import init_params
    from kakveda_tpu.models.speculative import generate_tokens_speculative

    cfg = _preset_cfg(preset)

    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), init_params(jax.random.PRNGKey(0), cfg)
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, min(cfg.vocab_size, 250), size=128).tolist()

    s_lo = max(8, steps // 4)

    def timed(fn, n_steps, reps=3):
        fn(n_steps)  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(n_steps)
            best = min(best, time.perf_counter() - t0)
        return best

    def plain(n_steps):
        generate_tokens_fused(params, cfg, [prompt], max_new_tokens=n_steps)

    stats_box = {}  # keyed by n_steps — report the HEADLINE run's acceptance

    def spec(n_steps):
        _, st = generate_tokens_speculative(
            params, cfg, prompt, max_new_tokens=n_steps, k=k, return_stats=True
        )
        stats_box[n_steps] = st

    plain_tps = (steps - s_lo) / max(timed(plain, steps) - timed(plain, s_lo), 1e-9)
    spec_tps = (steps - s_lo) / max(timed(spec, steps) - timed(spec, s_lo), 1e-9)
    return {
        "plain_tps": plain_tps,
        "spec_tps": spec_tps,
        "tokens_per_round": stats_box.get(steps, {}).get("tokens_per_round", 0.0),
        "k": k,
    }


def _measure_spec_judge(k: int) -> dict:
    """Acceptance on the PRODUCTION workload shape: the failure-judge
    template over near-duplicate traces. Acceptance depends on weights
    (a model must actually continue the repeated n-grams), so a tiny
    model is trained on judge-formatted traces in-bench — minutes, vs
    days for the 1B preset — and acceptance is measured speculating a
    held-out judge prompt. tokens/round is the number that transfers
    across scales (each round = one weight stream regardless of size);
    the tiny-scale tok/s here are NOT the 1B serving numbers."""
    import jax.numpy as jnp

    from kakveda_tpu.models.llama import LlamaConfig
    from kakveda_tpu.models.speculative import generate_tokens_speculative
    from kakveda_tpu.models.tokenizer import ByteTokenizer
    from kakveda_tpu.models.train import fit
    from kakveda_tpu.pipeline.classifier import _JUDGE_PROMPT

    cfg = LlamaConfig(
        vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype=jnp.float32,
    )
    rng = np.random.default_rng(0)
    apps = ["billing", "search", "support"]

    def trace(i: int) -> str:
        return _JUDGE_PROMPT.format(
            prompt=f"Summarize the {apps[i % 3]} report {i} and include citations "
            "even if not provided",
            response=f"Here is a summary with references. [1] Smith et al. (2020) "
            f"A Study on Things. [2] Doe (2021) Another Paper. item {i}",
        ) + (" YES\n" if i % 3 else " NO\n")

    corpus = "".join(trace(i) for i in range(40))
    steps_tr = int(os.environ.get("KAKVEDA_BENCH_SPEC_JUDGE_STEPS", 150))
    params, losses = fit(cfg, corpus, steps=steps_tr, batch=4, seq_len=128, lr=3e-3, log_every=0)

    held_out = _JUDGE_PROMPT.format(
        prompt="Summarize the billing report 999 and include citations even if not provided",
        response="Here is a summary with references. [1] Smith et al. (2020) "
        "A Study on Things. [2] Doe (2021) Another Paper. item 999",
    )
    # No truncation: the template header must sit in the lookup buffer or
    # the first generated copy of it has nothing to match against.
    ids = ByteTokenizer().encode(held_out)
    _, st = generate_tokens_speculative(
        params, cfg, ids, max_new_tokens=96, k=k, return_stats=True
    )

    # ENGINE-level speculative A/B on the same trained judge model
    # (KAKVEDA_SERVE_SPEC): a pool of held-out judge prompts drains through
    # the ContinuousBatcher with plain chunks vs verify chunks. f32 weights
    # → outputs must be token-identical; acceptance here is the measured
    # judge-workload number that transfers to serving scale.
    from kakveda_tpu.models.serving import ContinuousBatcher

    # Prompts truncated so the admission bucket (pow2 255→256) leaves real
    # decode room in the 512 window — a 384-token prompt buckets to 511
    # and the pool would emit ONE token per request (a degenerate A/B).
    pool_prompts = [
        ByteTokenizer().encode(
            _JUDGE_PROMPT.format(
                prompt=f"Summarize the {apps[i % 3]} report {900 + i} and include "
                "citations even if not provided",
                response="Here is a summary with references. [1] Smith et al. (2020) "
                f"A Study on Things. [2] Doe (2021) Another Paper. item {900 + i}",
            )
        )[-255:]
        for i in range(6)
    ]

    def drain_pipelined(cb):
        """ONE engine-shaped pipelined drain for BOTH arms (dispatch
        chunk i+1 before fetching chunk i; verify chunks thread their
        post-acceptance slot_pos on device and draft from copy cursors —
        the same ordering the ServingEngine loop runs). The arms differ
        only in what spec_ready() dispatches, so an auto-gated-off spec
        pool times the SAME code path as the plain arm by construction —
        the gate's "within 5% of plain" contract is structural, not
        luck. Reusable: a warm pass doubles as gate calibration."""
        pending = list(enumerate(pool_prompts))
        order, handle, spec_handle = {}, None, None
        t0 = time.perf_counter()
        while pending or cb.slots or handle is not None or spec_handle is not None:
            if pending and cb.free and spec_handle is not None:
                # Admission needs host-authoritative slot state.
                cb.process_spec_chunk(spec_handle)
                spec_handle = None
            while pending and cb.free:
                i, p = pending.pop(0)
                order[cb.admit(p, max_new_tokens=96)] = i
            if cb.spec_ready():
                cb.process_chunk(handle)
                handle = None
                if spec_handle is not None and cb.spec_pipeline_ready():
                    # Full-accept regime: overlap draft/accept with the
                    # next verify chunk's device time (cursor drafts).
                    nxt = cb.step_spec_async()
                    cb.process_spec_chunk(spec_handle)
                    spec_handle = nxt
                else:
                    # Acceptance-preserving sync order.
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
        wall = time.perf_counter() - t0
        outs = [None] * len(pool_prompts)
        for rid, i in order.items():
            outs[i] = cb.results.pop(rid)
        return wall, outs

    # ONE batcher per arm, reused warm→measured: the spec batcher's warm
    # pass doubles as the auto-gate's calibration+warmup, so the measured
    # pass reports the gate's SETTLED verdict (spec chunks if they pay,
    # plain fallback if they don't) — a fresh batcher would re-pay warmup
    # spec chunks inside the timed window.
    cb_plain = ContinuousBatcher(params, cfg, batch_slots=3, max_len=512, chunk_steps=8)
    cb_spec = ContinuousBatcher(
        params, cfg, batch_slots=3, max_len=512, chunk_steps=8, spec_k=k
    )
    drain_pipelined(cb_plain)  # warm compiled paths off-clock
    drain_pipelined(cb_spec)  # warm + gate calibration
    # Best-of-3 per arm: the tiny-preset drains are ~100 ms, where one
    # scheduler hiccup would swamp the within-5% gate contract.
    wall_plain, outs_plain = drain_pipelined(cb_plain)
    wall_spec, outs_spec = drain_pipelined(cb_spec)
    for _ in range(2):
        wall_plain = min(wall_plain, drain_pipelined(cb_plain)[0])
        wall_spec = min(wall_spec, drain_pipelined(cb_spec)[0])
    # Parity is exact in math (tests/test_serving_spec.py, f32); tolerate
    # at most one request flipping on a bitwise logit tie (argmax order
    # differs across program shapes — the CLAUDE.md greedy-parity gotcha)
    # and fail loudly past that.
    n_mismatch = sum(a != b for a, b in zip(outs_plain, outs_spec))
    if n_mismatch > 1:
        raise RuntimeError(
            f"engine verify chunks diverged on {n_mismatch}/{len(outs_plain)} "
            "judge requests — beyond tie noise, a real parity bug"
        )

    # THE read API (CLAUDE.md): the lock-guarded deep-copy snapshot, never
    # the live dicts — single-threaded here, but the discipline is uniform.
    s = cb_spec.stats_snapshot()["spec"]
    engine_rate = s["emitted"] / s["slot_chunks"] if s["slot_chunks"] else 0.0
    return {
        "tokens_per_round": st["tokens_per_round"],
        "rounds": st["rounds"],
        "train_loss": float(losses[-1]),
        "train_steps": steps_tr,
        "engine_wall_plain_s": wall_plain,
        "engine_wall_spec_s": wall_spec,
        "engine_tokens_per_verify": engine_rate,
        "engine_parity_mismatches": n_mismatch,
        "engine_gate_state": s["gate_state"],
        "engine_break_even": s["break_even"],
        "engine_tokens_per_verify_recent": s["tokens_per_verify"],
        "engine_accept_rate": s["accepted"] / s["drafted"] if s["drafted"] else 0.0,
        "engine_k_trace": list(s["k_trace"])[-16:],
    }


def _bench_spec(backend: str) -> dict:
    preset = os.environ.get("KAKVEDA_BENCH_DECODE_PRESET", "1b" if _on_tpu(backend) else "tiny")
    steps = int(os.environ.get("KAKVEDA_BENCH_SPEC_STEPS", 256))
    k = int(os.environ.get("KAKVEDA_BENCH_SPEC_K", 8))
    print(f"bench[spec]: backend={backend} preset={preset} steps={steps} k={k}", file=sys.stderr)
    r = _measure_spec(preset, steps, k)
    print(
        f"bench[spec]: speculative {r['spec_tps']:,.0f} tok/s vs plain {r['plain_tps']:,.0f} "
        f"tok/s @batch 1 ({r['tokens_per_round']:.2f} tokens/round, k={k}, random-init "
        f"= conservative acceptance floor)",
        file=sys.stderr,
    )
    out = {
        "metric": f"speculative_decode_tokens_per_sec_{preset}_b1",
        "value": round(r["spec_tps"], 1),
        "unit": "tokens/sec",
        "vs_baseline": round(r["spec_tps"] / r["plain_tps"], 2) if r["plain_tps"] > 0 else 0.0,
        "plain_tps": round(r["plain_tps"], 1),
        "tokens_per_round": round(r["tokens_per_round"], 2),
    }
    if os.environ.get("KAKVEDA_BENCH_SPEC_JUDGE", "1") != "0":
        j = _measure_spec_judge(k)
        # Projection to the main preset: rounds are weight-stream-bound,
        # so tok/s scales with acceptance at ~the floor run's per-round
        # overhead. Clearly a projection, not a measurement.
        overhead = (
            r["plain_tps"] * r["tokens_per_round"] / r["spec_tps"]
            if r["spec_tps"] > 0 else 1.0
        )
        projected = r["plain_tps"] * j["tokens_per_round"] / max(overhead, 1e-9)
        print(
            f"bench[spec]: judge-workload acceptance {j['tokens_per_round']:.2f} "
            f"tokens/round (tiny model trained {j['train_steps']} steps on the "
            f"judge template, loss {j['train_loss']:.3f}) — projected "
            f"{projected:,.0f} tok/s at {preset} scale at that acceptance",
            file=sys.stderr,
        )
        out["judge_tokens_per_round"] = round(j["tokens_per_round"], 2)
        out["judge_projected_tps"] = round(projected, 1)
        print(
            f"bench[spec]: ENGINE verify chunks on the judge pool — "
            f"{j['engine_wall_plain_s']:.2f}s pipelined-plain vs {j['engine_wall_spec_s']:.2f}s spec "
            f"({j['engine_wall_plain_s'] / max(j['engine_wall_spec_s'], 1e-9):.2f}x, "
            f"{j['engine_tokens_per_verify']:.2f} tokens/verify, "
            f"accept {j['engine_accept_rate']:.2f}, gate {j['engine_gate_state']} "
            f"@break-even {j['engine_break_even']:.2f}, "
            f"k trace {j['engine_k_trace']}, "
            f"{j['engine_parity_mismatches']} tie-flips)",
            file=sys.stderr,
        )
        out["engine_spec_speedup"] = round(
            j["engine_wall_plain_s"] / max(j["engine_wall_spec_s"], 1e-9), 2
        )
        out["engine_tokens_per_verify"] = round(j["engine_tokens_per_verify"], 2)
        # The auto-gate's verdict: when verify chunks can't clear the
        # measured break-even the pool decodes plain — the spec arm then
        # matches the plain arm instead of shipping a configured slowdown.
        out["engine_gate_state"] = j["engine_gate_state"]
        out["engine_break_even"] = round(j["engine_break_even"], 2)
        out["engine_accept_rate"] = round(j["engine_accept_rate"], 3)
        out["engine_adaptive_k_trace"] = j["engine_k_trace"]
    return out


def _measure_mixed(n: int, dim: int) -> dict:
    """Warn latency under concurrent streaming ingest — the decoupling
    claim: match dispatches serialize only on microsecond-scale lock holds,
    never on ingest's host-side embedding or growth re-embeds. Reports
    warn p50 idle vs p50 with a background ingest_batch storm."""
    import asyncio
    import tempfile
    import threading
    from datetime import datetime, timezone
    from pathlib import Path

    from kakveda_tpu.core.schemas import TracePayload, WarningRequest
    from kakveda_tpu.platform import Platform

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-mixed-"))
    plat = Platform(data_dir=tmp, capacity=max(n, 1 << 15), dim=dim)

    def mk_traces(m: int, tag: str):
        ts = datetime.now(timezone.utc)
        return [
            TracePayload(
                trace_id=f"t-{tag}-{i}", ts=ts, app_id=f"app-{i % 7}", agent_id="bench",
                prompt=f"Summarize report {tag}-{i} with citations for every claim.",
                response=f"Done [{i}] (Smith 2021).", tools=[], env={"os": "linux"},
            )
            for i in range(m)
        ]

    reqs = [
        WarningRequest(app_id="app-0", agent_id="bench",
                       prompt=f"Explain document {i} and include citations", tools=[], env={})
        for i in range(64)
    ]
    # Seed + warm both compiled paths.
    asyncio.run(plat.ingest_batch(mk_traces(512, "seed")))
    plat.warn_batch(reqs)

    def warn_p50(rounds: int) -> float:
        lat = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            plat.warn_batch(reqs)
            lat.append((time.perf_counter() - t0) * 1000.0 / len(reqs))
        return float(np.percentile(lat, 50))

    idle_p50 = warn_p50(30)

    stop = threading.Event()

    def ingest_storm():
        i = 0
        while not stop.is_set():
            asyncio.run(plat.ingest_batch(mk_traces(512, f"s{i}")))
            i += 1

    t = threading.Thread(target=ingest_storm)
    t.start()
    try:
        loaded_p50 = warn_p50(30)
    finally:
        stop.set()
        t.join()
    return {"idle_p50_ms": idle_p50, "loaded_p50_ms": loaded_p50}


def _measure_mixed_decode(n: int, dim: int, preset: str, chunk_steps: int) -> dict:
    """Warn latency while a continuous Llama generation storm shares the
    chip — SURVEY §7's 'interleaving generate steps with match batches'.

    The storm runs through DecodeSession (chunked dispatch): each chunk is a
    bounded device program, so a warn batch waits at most ~chunk_steps
    decode steps in the device queue instead of a whole generation (a
    single fused 128-step program at 1B scale blocks the chip for hundreds
    of ms). Reports warn p50/request idle vs loaded, plus the decode tok/s
    the storm sustains while sharing.

    HBM budget at the default TPU config (v5e 16 GB): 1M×2048 bf16 index
    4.0 GB + 1.1B bf16 params 2.2 GB + [16, KV4, 512, 64] caches 0.4 GB +
    transient scratch — comfortably co-resident.
    """
    import threading

    import jax
    import jax.numpy as jnp

    from kakveda_tpu.core.fingerprint import signature_text
    from kakveda_tpu.models.generate import DecodeSession
    from kakveda_tpu.models.llama import LlamaConfig, init_params
    from kakveda_tpu.ops.featurizer import HashedNGramFeaturizer
    from kakveda_tpu.ops.knn import ShardedKnn
    from kakveda_tpu.parallel.mesh import create_mesh

    # --- index (same synthetic fill as the warn bench) -------------------
    mesh = create_mesh("data:-1")
    knn = ShardedKnn(mesh, capacity=n, dim=dim, k=5)
    emb, valid = knn.alloc()
    chunk = 1 << 16

    @jax.jit
    def _fill(emb_buf, valid_buf, key, start):
        v = jax.random.normal(key, (chunk, dim), jnp.float32)
        v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
        emb_buf = jax.lax.dynamic_update_slice(emb_buf, v.astype(emb_buf.dtype), (start, 0))
        valid_buf = jax.lax.dynamic_update_slice(valid_buf, jnp.ones((chunk,), jnp.bool_), (start,))
        return emb_buf, valid_buf

    key = jax.random.PRNGKey(0)
    for start in range(0, n - chunk + 1, chunk):
        key, sub = jax.random.split(key)
        emb, valid = _fill(emb, valid, sub, start)
    jax.block_until_ready(emb)

    feat = HashedNGramFeaturizer(dim=dim)
    B = 64
    sigs = [
        signature_text(f"Summarize document {i} and include citations.", [], {"os": "linux"})
        for i in range(B)
    ]
    q_idx, q_val = feat.encode_batch_sparse(sigs)
    knn.topk_result(knn.topk_async_sparse(emb, valid, q_idx, q_val))  # warm

    def warn_p50(rounds: int) -> float:
        lat = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            knn.topk_result(knn.topk_async_sparse(emb, valid, q_idx, q_val))
            lat.append((time.perf_counter() - t0) * 1000.0 / B)
        return float(np.percentile(lat, 50))

    idle_p50 = warn_p50(30)

    # --- generation storm ------------------------------------------------
    cfg = _preset_cfg(preset)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), init_params(jax.random.PRNGKey(0), cfg)
    )
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(3, cfg.vocab_size, size=128)) for _ in range(16)]

    stop = threading.Event()
    tok_count = [0]

    def storm():
        while not stop.is_set():
            sess = DecodeSession(params, cfg, prompts, chunk_steps=chunk_steps, max_len=512)
            while not stop.is_set():
                c = sess.step_chunk()
                if c is None:
                    break
                tok_count[0] += c.size

    t = threading.Thread(target=storm)
    t.start()
    try:
        # Let the storm warm its compiled chunk program before measuring.
        deadline = time.time() + 60
        while tok_count[0] < 16 * chunk_steps * 2 and time.time() < deadline:
            time.sleep(0.5)
        c0, t0 = tok_count[0], time.perf_counter()
        loaded_p50 = warn_p50(30)
        storm_tps = (tok_count[0] - c0) / (time.perf_counter() - t0)
    finally:
        stop.set()
        t.join()
    return {
        "idle_p50_ms": idle_p50,
        "loaded_p50_ms": loaded_p50,
        "storm_decode_tps": storm_tps,
        "chunk_steps": chunk_steps,
    }


def _measure_mine(n: int, dim: int, n_templates: int) -> dict:
    """Batch pattern mining over ``n`` REAL hashed-ngram embeddings — the
    BASELINE 'batch clustering over full GFKB embeddings' config.

    Corpus: ``n_templates`` distinct failure shapes (prompt templates with
    per-row wording variation), embedded with the production featurizer.
    Sanity = cluster purity against the generating template: rows whose
    label's majority-template matches their own. The reference has no
    comparable capability (its pattern detector is a group-by on
    failure_type, services/pattern_detector/app.py:40-47); vs_baseline is
    purity, not a speedup."""
    import jax
    import jax.numpy as jnp

    from kakveda_tpu.core.fingerprint import signature_text
    from kakveda_tpu.ops.clustering import cluster_embeddings
    from kakveda_tpu.ops.featurizer import HashedNGramFeaturizer

    rng = np.random.default_rng(0)
    verbs = ["Summarize", "Explain", "Describe", "Review", "Outline"]
    objs = ["report", "paper", "contract", "dataset", "incident", "ticket"]
    tails = [
        "and include citations even if not provided",
        "and add references for every claim",
        "listing all sources used",
        "with a short bibliography",
    ]
    template_ids = rng.integers(0, n_templates, size=n)
    feat = HashedNGramFeaturizer(dim=dim)
    texts = []
    for i in range(n):
        t = int(template_ids[i])
        # Template fixes the stable wording; per-row noise varies the rest.
        text = (
            f"{verbs[t % len(verbs)]} the {objs[(t // len(verbs)) % len(objs)]} "
            f"variant {t} {tails[t % len(tails)]} item {rng.integers(0, 9)}"
        )
        texts.append(signature_text(text, [], {"os": "linux"}))
    # Embed + ship sparse (idx, val) pairs and densify ON DEVICE — the
    # dense [N, dim] form is ~98% zeros; the sparse pairs are ~60× smaller.
    # Untimed vs mining: production embeddings already live in HBM.
    from functools import partial as _partial

    @_partial(jax.jit, donate_argnums=(0,))
    def _scatter_chunk(buf, idx, val, row0):
        b, k = idx.shape
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k))
        chunk = jnp.zeros((b, dim + 1), jnp.float32).at[rows, idx].add(val)[:, :dim]
        return jax.lax.dynamic_update_slice(buf, chunk, (row0, 0))

    t0 = time.perf_counter()
    enc_chunk = 1 << 14
    n_pad = -(-n // enc_chunk) * enc_chunk  # buffer padded so the tail
    v_dev = jnp.zeros((n_pad, dim), jnp.float32)  # chunk never clamps
    t_embed = 0.0
    for s in range(0, n, enc_chunk):
        te = time.perf_counter()
        idx, val = feat.encode_batch_sparse(texts[s : s + enc_chunk])
        t_embed += time.perf_counter() - te
        if idx.shape[0] < enc_chunk:  # pad tail to the compiled shape
            pad = enc_chunk - idx.shape[0]
            idx = np.concatenate([idx, np.full((pad, idx.shape[1]), dim, np.int32)])
            val = np.concatenate([val, np.zeros((pad, val.shape[1]), np.float32)])
        v_dev = _scatter_chunk(v_dev, idx, val, jnp.asarray(s, jnp.int32))
    if n_pad != n:
        v_dev = v_dev[:n]
    jax.block_until_ready(v_dev)
    t_ship = time.perf_counter() - t0 - t_embed
    print(f"bench[mine]: embedded {n:,} texts in {t_embed:.1f}s", file=sys.stderr, flush=True)
    print(f"bench[mine]: sparse device upload took {t_ship:.1f}s", file=sys.stderr, flush=True)

    t0 = time.perf_counter()
    labels = cluster_embeddings(v_dev, threshold=0.6)
    t_mine = time.perf_counter() - t0

    def _purity(lab: np.ndarray, tmpl: np.ndarray) -> float:
        """Majority-template share per cluster label."""
        order = np.argsort(lab, kind="stable")
        sl, st = lab[order], tmpl[order]
        bounds = np.flatnonzero(np.r_[True, sl[1:] != sl[:-1], True])
        correct = 0
        for a, b in zip(bounds[:-1], bounds[1:]):
            _, counts = np.unique(st[a:b], return_counts=True)
            correct += int(counts.max())
        return correct / len(lab)

    purity = _purity(labels, template_ids)

    # --- incremental streaming arm --------------------------------------
    # The same corpus streamed through ingest-time attachment
    # (ops/incremental.py): per batch, ONE delta top-k against the rows
    # inserted so far + host union-find updates — O(ΔN·N) per batch,
    # amortized over the stream — then "refresh" = materialize labels
    # from the live state, which is what mine_patterns pays per call
    # instead of the full O(N²) sweep. Parity vs the full-mine oracle is
    # asserted EXACTLY (same packed-label convention), purity against the
    # generating templates like the full arm.
    from kakveda_tpu.ops.clustering import _KNN_K, _corpus_pad
    from kakveda_tpu.ops.incremental import ClusterState, delta_topk_dense, unpack_topk

    n_inc = min(n, int(os.environ.get("KAKVEDA_BENCH_MINE_INC_N", 20_000)))
    inc_bs = 1 << max(4, int(os.environ.get("KAKVEDA_BENCH_MINE_INC_BATCH", 512)).bit_length() - 1)
    thr = 0.6
    if n_inc == n:
        labels_sub, full_wall_sub = labels, t_mine
    else:
        t0 = time.perf_counter()
        labels_sub = cluster_embeddings(v_dev[:n_inc], threshold=thr)
        full_wall_sub = time.perf_counter() - t0
    P = _corpus_pad(n_inc)
    v_pad = (
        jnp.concatenate([v_dev[:n_inc], jnp.zeros((P - n_inc, dim), jnp.float32)])
        if P != n_inc
        else v_dev[:n_inc]
    )
    state = ClusterState(threshold=thr, k=_KNN_K)
    # warm the single compiled delta program off-clock
    jax.block_until_ready(delta_topk_dense(v_pad[:inc_bs], v_pad, inc_bs, _KNN_K + 1))
    t_stream = 0.0
    for s in range(0, n_inc, inc_bs):
        e = min(s + inc_bs, n_inc)
        t0 = time.perf_counter()
        packed = delta_topk_dense(v_pad[s : s + inc_bs], v_pad, e, _KNN_K + 1)
        sims, idx = unpack_topk(packed, e - s)
        for r in range(e - s):
            state.add_row(s + r)
        for r in range(e - s):
            state.attach(s + r, idx[r], sims[r])
        t_stream += time.perf_counter() - t0
    t0 = time.perf_counter()
    inc_labels = state.labels()
    t_refresh = time.perf_counter() - t0
    inc = {
        "n": n_inc,
        "stream_wall_s": t_stream,
        "amortized_ms_per_row": t_stream * 1000.0 / n_inc,
        "refresh_wall_s": t_refresh,
        "full_wall_s": full_wall_sub,
        "refresh_speedup": full_wall_sub / max(t_refresh, 1e-9),
        "parity": bool(np.array_equal(inc_labels, labels_sub)),
        "purity": _purity(inc_labels, template_ids[:n_inc]),
        "clusters": state.n_clusters,
        "batch": inc_bs,
    }

    return {
        "n": n,
        "wall_s": t_mine,
        "embed_s": t_embed,
        "clusters": int(len(np.unique(labels))),
        "purity": purity,
        "incremental": inc,
    }


def _measure_reference(dim_corpus: int, n_queries: int, target_n: int) -> float:
    """Reference algorithm (TF-IDF refit per query) on this host, timed at
    ``dim_corpus`` rows and linearly extrapolated to ``target_n`` rows."""
    try:
        from sklearn.feature_extraction.text import TfidfVectorizer
        from sklearn.metrics.pairwise import cosine_similarity
    except ImportError:
        return float("nan")

    from kakveda_tpu.core.fingerprint import signature_text

    corpus = [
        signature_text(f"Summarize report {i} and include citations please", [], {"os": "linux"})
        for i in range(dim_corpus)
    ]
    queries = [
        signature_text(f"Explain paper {i} and add references", [], {"os": "linux"})
        for i in range(n_queries)
    ]

    lat = []
    for q in queries:
        t0 = time.perf_counter()
        vec = TfidfVectorizer(ngram_range=(1, 2), min_df=1)
        X = vec.fit_transform([q] + corpus)
        sims = cosine_similarity(X[0:1], X[1:]).flatten()
        top = np.argsort(-sims)[:5]
        assert top.shape == (5,)
        lat.append((time.perf_counter() - t0) * 1000.0)
    p50_small = float(np.percentile(lat, 50))
    return p50_small * (target_n / dim_corpus)


def _on_tpu(backend: str) -> bool:
    return backend == "tpu"


def _bench_pallas(backend: str) -> dict:
    """Pallas-vs-XLA A/B on the SAME inputs: compiles (not interpret mode,
    on TPU) the fused kNN kernel (ops/pallas_knn.py) and the int8-streaming
    flash attention (models/attention.py:flash_gqa_cache), times each
    against its XLA fallback with the slope method (two run lengths, so the
    fixed per-dispatch cost cancels), and checks result parity.

    This is the hardware proof VERDICT r4 asked for: interpret-mode CPU
    tests verify kernel semantics, but only this run proves Mosaic
    compilation, VMEM fit at production tiles, and the actual speedup.
    ``compiled: true`` in the output means the kernels ran through Mosaic.
    """
    import jax
    import jax.numpy as jnp

    from kakveda_tpu.models.attention import _gqa_xla, _pick_block, flash_gqa_cache
    from kakveda_tpu.models.llama import _kv_dequant, _kv_quant_rows
    from kakveda_tpu.ops.knn import ShardedKnn
    from kakveda_tpu.parallel.mesh import create_mesh

    on_tpu = _on_tpu(backend)
    interpret = not on_tpu  # CPU smoke exercises kernel logic via interpreter

    def slope_ms(f, args, iters=(4, 12) if on_tpu else (1, 2)):
        """Steady-state ms/call: (t[iters1] - t[iters0]) / (i1 - i0)."""
        out = f(*args)
        jax.block_until_ready(out)  # compile + warm
        times = []
        for it in iters:
            t0 = time.perf_counter()
            for _ in range(it):
                out = f(*args)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        return (times[1] - times[0]) / (iters[1] - iters[0]) * 1000.0

    # --- fused top-k kNN vs matmul + lax.top_k --------------------------
    n = int(os.environ.get("KAKVEDA_BENCH_PALLAS_N", 1_000_000 if on_tpu else 16_384))
    dim = int(os.environ.get("KAKVEDA_BENCH_PALLAS_DIM", 2048 if on_tpu else 256))
    B = int(os.environ.get("KAKVEDA_BENCH_BATCH", 64))
    mesh = create_mesh("data:-1")
    knn = ShardedKnn(mesh, capacity=n, dim=dim, k=5, use_pallas=True)
    knn._pallas_interpret = interpret
    emb, valid = knn.alloc()
    chunk = min(1 << 16, knn.capacity)

    @jax.jit
    def _fill(emb_buf, valid_buf, key, start):
        v = jax.random.normal(key, (chunk, dim), jnp.float32)
        v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
        emb_buf = jax.lax.dynamic_update_slice(emb_buf, v.astype(emb_buf.dtype), (start, 0))
        valid_buf = jax.lax.dynamic_update_slice(valid_buf, jnp.ones((chunk,), jnp.bool_), (start,))
        return emb_buf, valid_buf

    key = jax.random.PRNGKey(0)
    for start in range(0, knn.capacity - chunk + 1, chunk):
        key, sub = jax.random.split(key)
        emb, valid = _fill(emb, valid, sub, start)
    q = np.random.default_rng(0).standard_normal((B, dim), np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    qd = jnp.asarray(q)

    impl = knn._topk_single_impl if knn.single_device else knn._topk_impl
    knn.use_pallas = True
    f_pallas = jax.jit(impl)
    r_pallas = np.asarray(f_pallas(emb, valid, qd))
    knn.use_pallas = False
    f_xla = jax.jit(impl)
    r_xla = np.asarray(f_xla(emb, valid, qd))
    knn.use_pallas = True
    k = knn.k
    knn_parity = bool(
        np.array_equal(r_pallas[:, k:], r_xla[:, k:])  # same row ids
        and np.allclose(r_pallas[:, :k], r_xla[:, :k], atol=2e-2)
    )
    knn_pallas_ms = slope_ms(f_pallas, (emb, valid, qd))
    knn_xla_ms = slope_ms(f_xla, (emb, valid, qd))
    del emb, valid
    print(
        f"bench[pallas]: knn {knn.capacity}x{dim} B={B} — pallas {knn_pallas_ms:.2f} ms "
        f"vs XLA {knn_xla_ms:.2f} ms (parity={knn_parity}, compiled={not interpret})",
        file=sys.stderr,
    )

    # --- int8-KV flash attention vs XLA dequant-up-front ----------------
    if on_tpu:
        fb, fs, fh, fkv, fd, fl = 16, 1, 32, 4, 64, 2048
    else:
        fb, fs, fh, fkv, fd, fl = 2, 1, 8, 2, 64, 128
    key = jax.random.PRNGKey(1)
    kq, kk, kv_ = jax.random.split(key, 3)
    qa = jax.random.normal(kq, (fb, fs, fh, fd), jnp.bfloat16)
    k_f = jax.random.normal(kk, (fb, fkv, fl, fd), jnp.float32)
    v_f = jax.random.normal(kv_, (fb, fkv, fl, fd), jnp.float32)
    k_i8, k_sc = _kv_quant_rows(k_f)
    v_i8, v_sc = _kv_quant_rows(v_f)
    pos0 = jnp.asarray(fl - fs, jnp.int32)
    kv_valid = jnp.ones((fb, fl), jnp.bool_)
    sr = -(-(fs * (fh // fkv)) // 8) * 8
    q_blk = _pick_block(sr, 512, 8)
    l_blk = _pick_block(fl, 512, 128)

    @jax.jit
    def f_flash(qa, k_i8, k_sc, v_i8, v_sc):
        return flash_gqa_cache(
            qa, k_i8, v_i8, pos0, kv_valid,
            k_scale=k_sc, v_scale=v_sc, q_blk=q_blk, l_blk=l_blk,
            interpret=interpret,
        )

    @jax.jit
    def f_xla_attn(qa, k_i8, k_sc, v_i8, v_sc):
        kd = _kv_dequant(k_i8, k_sc, qa.dtype)
        vd = _kv_dequant(v_i8, v_sc, qa.dtype)
        return _gqa_xla(qa, kd, vd, pos0, kv_valid)

    args = (qa, k_i8, k_sc, v_i8, v_sc)
    o_flash = np.asarray(f_flash(*args), np.float32)
    o_xla = np.asarray(f_xla_attn(*args), np.float32)
    flash_diff = float(np.max(np.abs(o_flash - o_xla)))
    flash_ms = slope_ms(f_flash, args)
    xla_attn_ms = slope_ms(f_xla_attn, args)
    print(
        f"bench[pallas]: int8 flash [{fb},{fkv},{fl},{fd}] — flash {flash_ms:.3f} ms "
        f"vs XLA {xla_attn_ms:.3f} ms (max|Δ|={flash_diff:.1e})",
        file=sys.stderr,
    )

    knn_speedup = knn_xla_ms / knn_pallas_ms if knn_pallas_ms > 0 else 0.0
    return {
        "metric": "pallas_knn_speedup_vs_xla",
        "value": round(knn_speedup, 2),
        "unit": "x",
        "vs_baseline": round(knn_speedup, 2),
        "compiled": not interpret,
        "knn": {
            "rows": knn.capacity, "dim": dim, "batch": B,
            "pallas_ms": round(knn_pallas_ms, 3), "xla_ms": round(knn_xla_ms, 3),
            "parity": knn_parity,
        },
        "flash_attn_int8": {
            "shape_bkld": [fb, fkv, fl, fd],
            "flash_ms": round(flash_ms, 4), "xla_ms": round(xla_attn_ms, 4),
            "speedup": round(xla_attn_ms / flash_ms, 2) if flash_ms > 0 else 0.0,
            "max_abs_diff": flash_diff,
        },
    }


def _bench_warn(backend: str) -> dict:
    default_n = 1_000_000 if _on_tpu(backend) else 100_000
    n = int(os.environ.get("KAKVEDA_BENCH_N", default_n))
    dim = int(os.environ.get("KAKVEDA_BENCH_DIM", 2048))
    n_queries = int(os.environ.get("KAKVEDA_BENCH_QUERIES", 64))

    print(f"bench[warn]: backend={backend} n={n} dim={dim} queries={n_queries}", file=sys.stderr)
    _ledger_reset()
    t0 = time.time()
    ours_p50 = _measure_ours(n, dim, n_queries)
    print(f"bench[warn]: ours p50={ours_p50:.3f} ms (setup+run {time.time() - t0:.0f}s)", file=sys.stderr)
    # Self-certifying (KAKVEDA_LEDGER=1): the measured loop ran entirely on
    # warm compiled programs — a post-warmup compile fails the metric.
    ledger_plane = _ledger_certify("bench[warn]")

    ref_p50 = _measure_reference(2000, min(10, n_queries), n)
    print(f"bench[warn]: reference (extrapolated) p50={ref_p50:.1f} ms", file=sys.stderr)

    vs = ref_p50 / ours_p50 if ours_p50 > 0 and np.isfinite(ref_p50) else 0.0
    out = {
        "metric": f"preflight_warn_p50_ms_at_{n}_gfkb",
        "value": round(ours_p50, 3),
        "unit": "ms",
        "vs_baseline": round(vs, 1),
    }
    if ledger_plane:
        out["ledger"] = ledger_plane
    return out


def _bench_ingest(backend: str) -> dict:
    n_traces = int(os.environ.get("KAKVEDA_BENCH_TRACES", 20_000))
    batch = int(os.environ.get("KAKVEDA_BENCH_INGEST_BATCH", 512))
    print(f"bench[ingest]: backend={backend} traces={n_traces} batch={batch}", file=sys.stderr)
    ours_tps, seq_tps, http_tps = _measure_ingest(n_traces, batch)
    print(
        f"bench[ingest]: batched {ours_tps:,.0f} traces/s | over HTTP "
        f"(POST /ingest/batch, real server) {http_tps:,.0f} traces/s | per-trace "
        f"(reference model, no HTTP hops) {seq_tps:,.0f} traces/s",
        file=sys.stderr,
    )
    return {
        "metric": "ingest_throughput_traces_per_sec",
        "value": round(ours_tps, 1),
        "unit": "traces/sec",
        "vs_baseline": round(ours_tps / seq_tps, 1) if seq_tps > 0 else 0.0,
        "http_tps": round(http_tps, 1),
    }


def _bench_decode(backend: str) -> dict:
    preset = os.environ.get("KAKVEDA_BENCH_DECODE_PRESET", "1b" if _on_tpu(backend) else "tiny")
    bsz = int(os.environ.get("KAKVEDA_BENCH_DECODE_BATCH", 16))
    steps = int(os.environ.get("KAKVEDA_BENCH_DECODE_STEPS", 128))
    print(f"bench[decode]: backend={backend} preset={preset} batch={bsz} steps={steps}", file=sys.stderr)
    r = _measure_decode(preset, bsz, steps)
    curve_s = " ".join(f"b{b}={v:,.0f}" for b, v in sorted(r["curve"].items()))
    int8_s = (
        " | int8 " + " ".join(f"b{b}={v:,.0f}" for b, v in sorted(r["int8_curve"].items()))
        if r["int8_curve"] else ""
    )
    kv8_s = (
        " | kv8 " + " ".join(f"b{b}={v:,.0f}" for b, v in sorted(r["kv8_tps"].items()))
        if r["kv8_tps"] else ""
    )
    util_s = " ".join(f"{k}={v*100:.0f}%" for k, v in r["hbm_util"].items())
    print(
        f"bench[decode]: {r['n_params']/1e9:.2f}B params on {r['device_kind']} "
        f"(peak {r['peak_tflops']:.0f} bf16 TFLOP/s, {r['peak_hbm_gbps']:.0f} GB/s HBM assumed) — "
        f"decode {r['decode_tps']:,.0f} tok/s @batch {r['batch']} (MFU {r['mfu']*100:.1f}%), "
        f"prefill {r['prefill_tps']:,.0f} tok/s (MFU {r['prefill_mfu']*100:.1f}%), "
        f"unbatched {r['solo_tps']:,.0f} tok/s, curve {curve_s}{int8_s}{kv8_s} "
        f"| HBM roofline {util_s}",
        file=sys.stderr,
    )
    out = {
        "metric": f"decode_tokens_per_sec_{preset}_b{bsz}",
        "value": round(r["decode_tps"], 1),
        "unit": "tokens/sec",
        "vs_baseline": round(r["decode_tps"] / r["solo_tps"], 1) if r["solo_tps"] > 0 else 0.0,
        "mfu": round(r["mfu"], 4),
        "hbm_util": {k: round(v, 3) for k, v in r["hbm_util"].items()},
        "prefill_tokens_per_sec": round(r["prefill_tps"], 1),
        "prefill_mfu": round(r["prefill_mfu"], 4),
        "decode_tps_curve": {str(b): round(v, 1) for b, v in sorted(r["curve"].items())},
    }
    if r["int8_curve"]:
        out["int8_decode_tps"] = round(r["int8_tps"], 1)
        out["int8_decode_tps_curve"] = {str(b): round(v, 1) for b, v in sorted(r["int8_curve"].items())}
    if r["kv8_tps"]:
        out["kv8_decode_tps_curve"] = {str(b): round(v, 1) for b, v in sorted(r["kv8_tps"].items())}
    return out


def _bench_mixed(backend: str) -> dict:
    n = int(os.environ.get("KAKVEDA_BENCH_MIXED_N", 1 << 15))
    dim = int(os.environ.get("KAKVEDA_BENCH_DIM", 2048))
    print(f"bench[mixed]: backend={backend} n={n} dim={dim}", file=sys.stderr)
    r = _measure_mixed(n, dim)
    print(
        f"bench[mixed]: warn p50 idle {r['idle_p50_ms']:.3f} ms vs under-ingest "
        f"{r['loaded_p50_ms']:.3f} ms",
        file=sys.stderr,
    )
    return {
        "metric": "warn_p50_ms_under_concurrent_ingest",
        "value": round(r["loaded_p50_ms"], 3),
        "unit": "ms",
        "vs_baseline": round(r["idle_p50_ms"] / r["loaded_p50_ms"], 2)
        if r["loaded_p50_ms"] > 0
        else 0.0,
        "idle_p50_ms": round(r["idle_p50_ms"], 3),
    }


def _bench_mixed_decode(backend: str) -> dict:
    n = int(os.environ.get("KAKVEDA_BENCH_MIXED_N", 1 << 20 if _on_tpu(backend) else 1 << 14))
    dim = int(os.environ.get("KAKVEDA_BENCH_DIM", 2048))
    preset = os.environ.get("KAKVEDA_BENCH_DECODE_PRESET", "1b" if _on_tpu(backend) else "tiny")
    chunk_steps = int(os.environ.get("KAKVEDA_BENCH_CHUNK_STEPS", 8))
    print(
        f"bench[mixed-decode]: backend={backend} n={n} dim={dim} preset={preset} chunk={chunk_steps}",
        file=sys.stderr,
    )
    r = _measure_mixed_decode(n, dim, preset, chunk_steps)
    print(
        f"bench[mixed-decode]: warn p50 idle {r['idle_p50_ms']:.3f} ms vs under-decode "
        f"{r['loaded_p50_ms']:.3f} ms (storm {r['storm_decode_tps']:,.0f} tok/s, "
        f"chunks of {r['chunk_steps']} steps)",
        file=sys.stderr,
    )
    return {
        "metric": f"warn_p50_ms_under_decode_at_{n}_gfkb",
        "value": round(r["loaded_p50_ms"], 3),
        "unit": "ms",
        "vs_baseline": round(r["idle_p50_ms"] / r["loaded_p50_ms"], 2)
        if r["loaded_p50_ms"] > 0
        else 0.0,
        "idle_p50_ms": round(r["idle_p50_ms"], 3),
        "storm_decode_tps": round(r["storm_decode_tps"], 1),
    }


def _bus_dlq_count() -> int:
    """Process-cumulative dead-lettered event count off the metrics plane
    (kakveda_bus_dlq_total) — folded into the serve metric so a chaos'd
    bench line carries its own DLQ evidence."""
    from kakveda_tpu.core import metrics as _metrics

    fam = _metrics.get_registry().snapshot().get("kakveda_bus_dlq_total", {})
    return int(sum(v for v in fam.get("series", {}).values() if isinstance(v, (int, float))))


def _bench_serve(backend: str) -> dict:
    """Concurrent-HTTP serving SLOs: N separate logged-in clients drive
    playground generation through a REAL aiohttp dashboard server (all
    decodes share one ServingEngine, continuous batching) while a warn
    stream hits the service API — the mixed workload a deployment actually
    sees. Reports request p50/p95, aggregate decode tok/s, and warn p95
    under load. The reference can't exercise this: its playground and eval
    loops are strictly sequential HTTP calls to Ollama
    (services/dashboard/app.py:3127-3299, 2315-2393).

    vs_baseline = concurrency speedup: sum of request latencies (what a
    sequential server would take) / measured wall."""
    import asyncio
    import tempfile
    from pathlib import Path

    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.dashboard.app import make_dashboard_app
    from kakveda_tpu.models.generate import LlamaRuntime
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app as make_service_app

    preset = os.environ.get("KAKVEDA_BENCH_DECODE_PRESET", "1b" if _on_tpu(backend) else "tiny")
    n_clients = int(os.environ.get("KAKVEDA_BENCH_SERVE_CLIENTS", 16))
    reqs_per = int(os.environ.get("KAKVEDA_BENCH_SERVE_REQS", 2))
    import jax
    import jax.numpy as jnp

    from kakveda_tpu.models.llama import init_params

    cfg = _preset_cfg(preset)
    # bf16 weights, like the decode bench: serving streams weights every
    # step, and f32 random-init params would double that stream.
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), init_params(jax.random.PRNGKey(0), cfg)
    )

    rng = np.random.default_rng(0)
    prompts = [
        "Review failure report %d: %s" % (i, " ".join(
            str(w) for w in rng.integers(0, 999, size=12)
        ))
        for i in range(n_clients)
    ]

    def run_workload(pipeline: str) -> dict:
        """One full concurrent-HTTP round at the given pipelining setting
        (fresh runtime + apps, so the engine thread reads the env)."""
        os.environ["KAKVEDA_SERVE_PIPELINE"] = pipeline
        # The login limiter is process-global and keyed by peer IP: inside
        # the full sweep, this metric's 2×n_clients logins (all 127.0.0.1)
        # cross the 20/60s window and every later login bounces — which
        # zeroed the metric with a bare AssertionError. Fresh window per
        # workload, exactly like tests/test_dashboard.py's fixture.
        from kakveda_tpu.dashboard.core import RATE_LIMITER

        RATE_LIMITER._hits.clear()
        ledger_live = _ledger_reset()
        rt = LlamaRuntime(cfg=cfg, params=params, seed=0)
        tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-serve-"))
        plat = Platform(data_dir=tmp / "data", capacity=1 << 14, dim=2048)
        dash = make_dashboard_app(platform=plat, db_path=tmp / "dash.db", model=rt)
        svc = make_service_app(platform=plat)
        lat_play: list = []
        lat_warn: list = []
        lat_ttft: list = []
        stop = asyncio.Event()

        async def go():
            server = TestServer(dash)
            await server.start_server()
            svc_server = TestServer(svc)
            await svc_server.start_server()
            clients = [TestClient(server) for _ in range(n_clients)]
            svc_client = TestClient(svc_server)
            t_wall = 0.0
            try:
                for c in clients:
                    await c.start_server()
                    r = await c.post(
                        "/login",
                        data={"email": "admin@local", "password": "admin123", "next": "/"},
                        allow_redirects=False,
                    )
                    assert r.status == 302
                await svc_client.start_server()
                # Warm both compiled paths off-clock (engine decode + warn match).
                await clients[0].post(
                    "/playground/run", data={"prompt": "warm up", "target": "model"}
                )
                await svc_client.post("/warn", json={"app_id": "warm", "prompt": "warm"})
                if ledger_live:
                    # Ledger window: run every benchmark prompt once
                    # off-clock so ALL admit buckets / prefill widths are
                    # compiled, then draw the warm line — the measured
                    # workload below must compile NOTHING (certified after
                    # the run; a violation fails the metric).
                    for c, p in zip(clients, prompts):
                        await c.post(
                            "/playground/run", data={"prompt": p, "target": "model"}
                        )
                    _ledger_mark_warm()

                async def play_worker(client, prompt):
                    for _ in range(reqs_per):
                        t0 = time.perf_counter()
                        r = await client.post(
                            "/playground/run", data={"prompt": prompt, "target": "model"}
                        )
                        await r.text()
                        lat_play.append(time.perf_counter() - t0)
                        assert r.status == 200

                async def warn_worker():
                    i = 0
                    while not stop.is_set():
                        t0 = time.perf_counter()
                        r = await svc_client.post(
                            "/warn",
                            json={"app_id": "bench", "prompt": f"Cite sources for claim {i}."},
                        )
                        await r.json()
                        lat_warn.append(time.perf_counter() - t0)
                        assert r.status == 200
                        i += 1
                        await asyncio.sleep(0.02)

                wt = asyncio.create_task(warn_worker())
                t0 = time.perf_counter()
                await asyncio.gather(*(play_worker(c, p) for c, p in zip(clients, prompts)))
                t_wall = time.perf_counter() - t0
                stop.set()
                await wt
                # TTFT via the SSE endpoint: time from POST to the first
                # delta event (streaming makes this a real SLO — the
                # blocking path's first byte IS the last byte).
                for p in prompts[:4]:
                    ts = time.perf_counter()
                    r = await clients[0].post(
                        "/playground/stream", data={"prompt": p, "target": "model"}
                    )
                    async for _chunk in r.content.iter_any():
                        lat_ttft.append(time.perf_counter() - ts)
                        break
                    await r.release()
            finally:
                for c in clients:
                    await c.close()
                await svc_client.close()
            return t_wall

        wall = asyncio.run(go())
        # Self-certifying (KAKVEDA_LEDGER=1): the measured workload ran on
        # warm compiled programs only — zero post-warmup compiles.
        ledger_plane = _ledger_certify(f"bench[serve] pipeline={pipeline}")
        completed = restarts = 0
        if rt._engine is not None:
            est = rt._engine.stats()
            completed = est["completed"]
            restarts = est.get("restarts", 0)
            rt._engine.close()
        p50, p95 = (float(x) for x in np.percentile(lat_play, [50, 95]))
        return {
            "wall": wall,
            "p50": p50,
            "p95": p95,
            "p95_warn": float(np.percentile(lat_warn, 95)) if lat_warn else 0.0,
            "n_warns": len(lat_warn),
            "n_reqs": len(lat_play),
            "seq_est": float(np.sum(lat_play)),
            "completed": completed,
            "restarts": restarts,
            "ttft_p50": float(np.percentile(lat_ttft, 50)) if lat_ttft else 0.0,
            "ledger": ledger_plane,
        }

    prev_env = os.environ.get("KAKVEDA_SERVE_PIPELINE")
    prev_spec = os.environ.get("KAKVEDA_SERVE_SPEC")
    spec_arm = None
    try:
        # A/B the chunk-pipelining lever (dispatch chunk i+1 before fetching
        # chunk i — hides the per-chunk fetch RTT, the dominant per-chunk
        # cost on remote-attached chips). Unpipelined first so the
        # pipelined run (the headline) runs on the warmer process.
        base = run_workload("0")
        piped = run_workload("1")
        if _on_tpu(backend):
            # Third arm, hardware only: speculative verify chunks over the
            # same HTTP workload. Decode is weight-bound on TPU, so the
            # k+1-wide verify is where acceptance becomes throughput; on
            # CPU the arm would just burn sweep minutes re-measuring
            # compute-bound behavior the spec metric already reports.
            os.environ["KAKVEDA_SERVE_SPEC"] = "8"
            spec_arm = run_workload("1")
    finally:
        if prev_env is None:
            os.environ.pop("KAKVEDA_SERVE_PIPELINE", None)
        else:
            os.environ["KAKVEDA_SERVE_PIPELINE"] = prev_env
        if prev_spec is None:
            os.environ.pop("KAKVEDA_SERVE_SPEC", None)
        else:
            os.environ["KAKVEDA_SERVE_SPEC"] = prev_spec

    r = piped
    tok_s = r["n_reqs"] * 64 / r["wall"] if r["wall"] > 0 else 0.0  # generate() default max_tokens
    print(
        f"bench[serve]: {n_clients} clients × {reqs_per} reqs ({preset}) — "
        f"p50 {r['p50']*1000:.0f} ms, p95 {r['p95']*1000:.0f} ms, {tok_s:,.0f} tok/s agg, "
        f"warn p95 under load {r['p95_warn']*1000:.1f} ms ({r['n_warns']} warns), "
        f"concurrency speedup {r['seq_est']/r['wall']:.1f}x | unpipelined p95 "
        f"{base['p95']*1000:.0f} ms (pipeline gain {base['p95']/max(r['p95'],1e-9):.2f}x)",
        file=sys.stderr,
    )
    return {
        "metric": "serve_http_p95_ms_concurrent",
        "value": round(r["p95"] * 1000, 1),
        "unit": "ms",
        "vs_baseline": round(r["seq_est"] / r["wall"], 2) if r["wall"] > 0 else 0.0,
        "clients": n_clients,
        "requests": r["n_reqs"],
        "p50_ms": round(r["p50"] * 1000, 1),
        "agg_tokens_per_sec": round(tok_s, 1),
        "warn_p95_ms_under_load": round(r["p95_warn"] * 1000, 2),
        "engine_completed": r["completed"],
        # Robustness plane: zero in a healthy run — nonzero restarts or
        # dead-lettered events mean the workload survived real failures
        # (or a KAKVEDA_FAULTS chaos arm was active for this sweep).
        "engine_restarts": base["restarts"] + r["restarts"],
        "dlq_events": _bus_dlq_count(),
        # Overload plane (process-cumulative, like dlq_events): zero in a
        # healthy un-flooded run; nonzero means admission shed requests /
        # the brownout ladder moved during this process.
        "shed_total": _admission_shed_count(),
        "brownout_transitions": _brownout_transition_count(),
        "preset": preset,
        "unpipelined_p95_ms": round(base["p95"] * 1000, 1),
        "pipeline_p95_gain": round(base["p95"] / max(r["p95"], 1e-9), 2),
        "stream_ttft_p50_ms": round(r["ttft_p50"] * 1000, 1),
        # Certified by _ledger_certify inside run_workload: the headline
        # (pipelined) workload saw zero post-warmup XLA compiles.
        **({"ledger": r["ledger"]} if r.get("ledger") else {}),
        **(
            {
                "spec_p95_ms": round(spec_arm["p95"] * 1000, 1),
                "spec_p95_gain": round(r["p95"] / max(spec_arm["p95"], 1e-9), 2),
            }
            if spec_arm is not None
            else {}
        ),
    }


def _admission_shed_count() -> int:
    """Process-cumulative shed/429 count off the metrics plane
    (kakveda_admission_shed_total) — folded into the serve row so a bench
    line carries its own overload evidence, like dlq_events."""
    from kakveda_tpu.core import metrics as _metrics

    fam = _metrics.get_registry().snapshot().get("kakveda_admission_shed_total", {})
    return int(sum(v for v in fam.get("series", {}).values() if isinstance(v, (int, float))))


def _brownout_transition_count() -> int:
    from kakveda_tpu.core import metrics as _metrics

    fam = _metrics.get_registry().snapshot().get(
        "kakveda_brownout_transitions_total", {}
    )
    return int(sum(v for v in fam.get("series", {}).values() if isinstance(v, (int, float))))


def _bench_overload(backend: str) -> dict:
    """Overload-protection SLO: drive the service HTTP tier PAST capacity
    and prove that shedding — not queueing — absorbs the excess. Two
    phases against one live aiohttp server with deliberately small
    admission bounds: (1) unloaded warn p95 baseline; (2) saturation —
    ingest floods pinned past their class bound plus a warn storm wider
    than the warn bound — measuring admitted-warn p95 WHILE saturated,
    the shed/429 counts per class, and the brownout ladder's time-in-state
    occupancy. The acceptance bar: saturated warn p95 ≤ 2× unloaded (the
    queue never grows past what drains) with shed counters > 0 (the
    excess went to cheap 429s, not to timeouts). The reference has no
    admission control anywhere — overload just times out every caller."""
    import asyncio
    import tempfile
    from pathlib import Path

    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.core import admission as _adm
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app as make_service_app

    n_warn_clients = int(os.environ.get("KAKVEDA_BENCH_OVERLOAD_CLIENTS", 8))
    n_ingest_clients = 4
    duration = float(os.environ.get("KAKVEDA_BENCH_OVERLOAD_DUR", 8.0))

    # Private controller (the global one must stay clean for the serve
    # metric): small bounds so a laptop-sized flood genuinely saturates,
    # fast brownout dwell so the ladder is observable within the window.
    # ingest=1: the admitted ingest stream still burns real embed+insert
    # compute (sharing the GIL and the GFKB data lock with warn matches),
    # so the bound is what keeps warn's latency bounded — everything past
    # it is the excess that must shed.
    brown = _adm.BrownoutController(
        enabled=True, enter=0.85, exit=0.5, dwell_s=0.25,
    )
    adm = _adm.AdmissionController(
        limits={"warn": 16, "ingest": 1, "interactive": 8, "background": 1},
        enabled=True, brownout=brown,
    )

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-overload-"))
    plat = Platform(data_dir=tmp / "data", capacity=1 << 12, dim=1024)
    svc = make_service_app(platform=plat, admission=adm)

    def _trace(i: int) -> dict:
        return {
            "trace_id": f"ov-{i}",
            "ts": time.time(),
            "app_id": f"app-{i % 4}",
            "prompt": "Cite sources for claim %d even if unavailable." % i,
            "response": "According to [Smith 2020] (fabricated).",
            "tools": [],
            "env": {"os": "linux"},
        }

    # Pre-serialized flood payloads: the load generator shares ONE event
    # loop (and GIL) with the server under test, so per-attempt payload
    # construction would pollute the latency being measured. 64 distinct
    # batches cycle so ingest still sees fresh signatures.
    _hdr = {"Content-Type": "application/json"}
    ingest_bodies = [
        json.dumps(
            {"traces": [_trace(b * 10_000 + k) for k in range(32)]}
        ).encode()
        for b in range(64)
    ]
    warn_bodies = [
        json.dumps(
            {"app_id": f"w{i % 8}", "prompt": f"Cite sources for claim {i}."}
        ).encode()
        for i in range(256)
    ]

    lat_solo: list = []
    lat_unloaded: list = []
    lat_saturated: list = []
    status_counts = {"warn_200": 0, "warn_429": 0, "ingest_200": 0, "ingest_429": 0}

    async def go():
        server = TestServer(svc)
        await server.start_server()
        client = TestClient(server)
        await client.start_server()
        try:
            # Warm the compiled match path off-clock.
            for i in range(4):
                await client.post("/warn", json={"app_id": "warm", "prompt": f"warm {i}"})
            # Solo reference: one sequential client, no concurrency at all
            # (context for the report; the ratio uses the like-for-like
            # storm baseline below).
            for i in range(50):
                t0 = time.perf_counter()
                r = await client.post(
                    "/warn", json={"app_id": "base", "prompt": f"Cite sources for claim {i}."}
                )
                await r.json()
                assert r.status == 200
                lat_solo.append(time.perf_counter() - t0)

            stop = asyncio.Event()

            async def ingest_flooder(wid: int):
                i = wid
                while not stop.is_set():
                    r = await client.post(
                        "/ingest/batch",
                        data=ingest_bodies[i % len(ingest_bodies)], headers=_hdr,
                    )
                    await r.read()
                    status_counts["ingest_200" if r.status == 200 else "ingest_429"] += 1
                    if r.status == 429:
                        # Back off a token 50 ms on a shed — far below the
                        # Retry-After hint (so the class stays saturated
                        # the whole window) but not a zero-delay hammer:
                        # the load generator shares this host's core(s)
                        # with the server, and a spin-flood would measure
                        # raw HTTP parse cost, not admission control.
                        await asyncio.sleep(0.05)
                    i += 1

            async def warn_flooder(wid: int, sink: list):
                i = wid
                while not stop.is_set():
                    t0 = time.perf_counter()
                    r = await client.post(
                        "/warn",
                        data=warn_bodies[i % len(warn_bodies)], headers=_hdr,
                    )
                    await r.read()
                    if r.status == 200:
                        status_counts["warn_200"] += 1
                        sink.append(time.perf_counter() - t0)
                    else:
                        status_counts["warn_429"] += 1
                        await asyncio.sleep(0.001)
                    i += 1

            async def ingest_steady():
                # ONE polite client — exactly the admitted ingest
                # concurrency. Present in BOTH phases: the admitted
                # stream is the platform's steady state, not overload.
                i = 0
                while not stop.is_set():
                    r = await client.post(
                        "/ingest/batch",
                        data=ingest_bodies[i % len(ingest_bodies)], headers=_hdr,
                    )
                    await r.read()
                    status_counts["ingest_200" if r.status == 200 else "ingest_429"] += 1
                    i += 1

            # Phase 1 — the AT-CAPACITY workload: the full warn storm plus
            # the one admitted ingest stream, nothing shed. Its p95 is the
            # like-for-like baseline the overloaded phase is held to
            # (≤ 2×): what the flood may NOT do is degrade the work the
            # platform already admitted.
            tasks = [
                asyncio.create_task(warn_flooder(w, lat_unloaded))
                for w in range(n_warn_clients)
            ] + [asyncio.create_task(ingest_steady())]
            await asyncio.sleep(duration / 2)
            stop.set()
            await asyncio.gather(*tasks)

            # Phase 2 — same storm PLUS ingest floods driven past the
            # ingest class bound: the excess must shed as 429s while the
            # admitted warn stream stays within 2× of phase 1.
            stop.clear()
            tasks = [
                asyncio.create_task(ingest_flooder(w)) for w in range(n_ingest_clients)
            ] + [
                asyncio.create_task(warn_flooder(w, lat_saturated))
                for w in range(n_warn_clients)
            ]
            await asyncio.sleep(duration)
            stop.set()
            await asyncio.gather(*tasks)
        finally:
            await client.close()
    asyncio.run(go())

    p95_solo = float(np.percentile(lat_solo, 95))
    p95_base = float(np.percentile(lat_unloaded, 95)) if lat_unloaded else 0.0
    p95_sat = float(np.percentile(lat_saturated, 95)) if lat_saturated else 0.0
    ratio = p95_sat / p95_base if p95_base > 0 else 0.0
    sheds = adm.shed_counts()
    shed_total = int(sum(sheds.values()))
    occ = brown.occupancy()
    occ_pct = {
        s: round(100.0 * v / max(1e-9, sum(occ.values())), 1) for s, v in occ.items()
    }
    print(
        f"bench[overload]: warn p95 {p95_base*1000:.1f} ms at-capacity -> "
        f"{p95_sat*1000:.1f} ms saturated ({ratio:.2f}x; solo ref "
        f"{p95_solo*1000:.1f} ms) over {duration:.0f}s; "
        f"{status_counts['warn_200']} warns served, "
        f"{shed_total} shed ({status_counts['warn_429']} warn 429s, "
        f"{status_counts['ingest_429']} ingest 429s); brownout occupancy "
        f"{ {k: v for k, v in occ_pct.items() if v > 0} }",
        file=sys.stderr,
    )
    # Self-certifying, like the mine metric: bounded-latency-while-shedding
    # IS the result. A saturated p95 that blew past 2× unloaded means the
    # queue absorbed the excess (the failure mode this layer removes), and
    # zero sheds means the server was never actually saturated.
    max_ratio = float(os.environ.get("KAKVEDA_BENCH_OVERLOAD_MAX_RATIO", 2.0))
    if shed_total == 0:
        raise AssertionError(
            "overload bench never shed a request — the flood did not "
            "saturate the admission bounds; latency bound not demonstrated"
        )
    if ratio > max_ratio:
        raise AssertionError(
            f"warn p95 under overload is {ratio:.2f}x its unloaded value "
            f"(bound {max_ratio}x) — queueing, not shedding, absorbed the excess"
        )
    return {
        "metric": "overload_warn_p95_ms_saturated",
        "value": round(p95_sat * 1000, 2),
        "unit": "ms",
        # Ratio vs unloaded: the acceptance bound is <= 2.0 (bounded
        # latency while saturated), enforced above.
        "vs_baseline": round(ratio, 2),
        "warn_p95_ms_unloaded": round(p95_base * 1000, 2),
        "warn_p95_ms_solo": round(p95_solo * 1000, 2),
        "warns_served_saturated": status_counts["warn_200"],
        "warn_429": status_counts["warn_429"],
        "ingest_429": status_counts["ingest_429"],
        "shed_total": shed_total,
        "shed_by_class": {k: int(v) for k, v in sheds.items()},
        "brownout_occupancy_pct": occ_pct,
        "brownout_transitions": _brownout_transition_count(),
        "duration_s": duration,
    }


def _bench_ownership(backend: str) -> dict:
    """Sharded-ownership bench (fleet/ownership.py, docs/scale-out.md):
    capacity ratio, write amplification, scatter-gather warn parity
    against a single-node oracle, and a live scale-out migration with
    zero lost warns — all self-certifying (any gate failing raises).

    The fleet runs KAKVEDA_FLEET_OWNERSHIP=1 at R-way range replication:
    each replica holds only its owned + standby ranges, ingest replicates
    range-scoped (write amplification R, not N), and warn scatter-gathers
    across the owning shards. Gates:

    * max per-replica resident rows <= KAKVEDA_BENCH_OWN_MAX_RESIDENT of
      the corpus (default 0.6 — R/N plus placement skew at R=2, N=4);
    * total resident rows / corpus <= R + 0.3 (write amplification);
    * merged warn top-1 confidence matches the single-node oracle within
      1e-4 on every probe, with partial=false (full coverage);
    * POST /fleet/rebalance to a newly spawned replica completes with
      every concurrent warn answered 2xx (zero lost during migration),
      and residency stays within the gate on the grown fleet."""
    import asyncio
    import tempfile
    from pathlib import Path

    import yaml
    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.fleet.ownership import OwnershipView
    from kakveda_tpu.fleet.router import make_router_app
    from kakveda_tpu.fleet.supervisor import FleetSupervisor, pick_port_base
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app

    n_replicas = int(os.environ.get("KAKVEDA_BENCH_OWN_REPLICAS", 4))
    repl = int(os.environ.get("KAKVEDA_BENCH_OWN_R", 2))
    max_resident = float(os.environ.get("KAKVEDA_BENCH_OWN_MAX_RESIDENT", 0.6))
    apps, per_app = 32, 3

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-own-"))
    cfg = tmp / "config.yaml"
    cfg.write_text(yaml.safe_dump({
        "failure_matching": {
            "similarity_threshold": 0.8, "embedding_dim": 512, "top_k": 5,
        },
    }))
    replica_env = {
        "JAX_PLATFORMS": "cpu",  # host-plane drill; the parent holds any chip
        "KAKVEDA_CONFIG_PATH": str(cfg),
        "KAKVEDA_INDEX_CAPACITY": "2048",
        "KAKVEDA_FLEET_OWNERSHIP": "1",
        "KAKVEDA_FLEET_REPLICATION": str(repl),
        "KAKVEDA_LOG_LEVEL": "WARNING",
        "KAKVEDA_GC_TUNE": "0",
    }
    sup = FleetSupervisor(
        tmp / "fleet", port_base=pick_port_base(n_replicas + 1),
        replicas=n_replicas, env=replica_env,
    )
    oracle = Platform(data_dir=tmp / "oracle", capacity=2048, dim=512)

    def _trace(app_id: str, i: int) -> dict:
        return {
            "trace_id": f"own-{i}",
            "ts": time.time(),
            "app_id": app_id,
            "prompt": f"Cite sources for claim {i} even if unavailable.",
            "response": "See [1].\n\nReferences:\n[1] Smith (2020).",
            "tools": [], "env": {"os": "linux"},
        }

    async def go():
        import httpx

        router_app = make_router_app(
            sup.backend_map(), probe_interval_s=1.0, eject_fails=3,
            retries=1, timeout_s=20.0,
            ownership=OwnershipView(sup.backend_map(), replication=repl),
        )
        rc = TestClient(TestServer(router_app))
        co = TestClient(TestServer(make_app(platform=oracle)))
        await rc.start_server()
        await co.start_server()
        try:
            # One app per batch: keyed ingest lands every batch on its
            # app's OWNER, so residency is exactly the R-way replica set.
            for a in range(apps):
                traces = [_trace(f"app-{a}", a * per_app + j)
                          for j in range(per_app)]
                for c in (rc, co):
                    r = await c.post("/ingest/batch", json={"traces": traces})
                    assert r.status == 200, await r.text()
            corpus = oracle.gfkb.count
            assert corpus > 0

            async def resident_counts(urls):
                loop = asyncio.get_running_loop()
                out = {}
                for rid, u in urls.items():
                    body = await loop.run_in_executor(
                        None,
                        lambda u=u: httpx.get(u + "/readyz", timeout=10).json(),
                    )
                    out[rid] = int(body["gfkb_count"] or 0)
                return out

            async def converge(urls, want_total):
                deadline = time.monotonic() + 120.0
                counts = await resident_counts(urls)
                while time.monotonic() < deadline:
                    if sum(counts.values()) >= want_total:
                        return counts
                    await asyncio.sleep(0.5)
                    counts = await resident_counts(urls)
                return counts

            counts = await converge(sup.backend_map(), repl * corpus)
            total = sum(counts.values())
            capacity_ratio = max(counts.values()) / corpus
            write_amp = total / corpus

            # Scatter parity: near-dup probes (one per app) must merge to
            # the single-node oracle's top-1 confidence with full coverage.
            mismatches = []
            for a in range(apps):
                q = {"app_id": f"app-{a}",
                     "prompt": f"Cite sources for claim {a * per_app} "
                               "even when sources are unavailable."}
                rf = await (await rc.post("/warn", json=q)).json()
                ro = await (await co.post("/warn", json=q)).json()
                if rf.get("partial") is not False:
                    mismatches.append((q["app_id"], "partial", rf.get("partial")))
                elif abs(float(rf["confidence"]) - float(ro["confidence"])) > 1e-4:
                    mismatches.append(
                        (q["app_id"], float(rf["confidence"]), float(ro["confidence"]))
                    )

            # Live scale-out: spawn replica N, run the migration protocol
            # through the router while warn traffic keeps flowing.
            loop = asyncio.get_running_loop()
            idx = await loop.run_in_executor(None, sup.add_replica)
            await loop.run_in_executor(None, sup.wait_ready, 300.0)
            stop = asyncio.Event()
            mig_counts = {"ok": 0, "lost": 0}

            async def warn_loop():
                i = 0
                while not stop.is_set():
                    r = await rc.post("/warn", json={
                        "app_id": f"app-{i % apps}",
                        "prompt": f"Cite sources for claim {i} even if unavailable.",
                    })
                    await r.read()
                    mig_counts["ok" if r.status == 200 else "lost"] += 1
                    i += 1

            wtask = asyncio.create_task(warn_loop())
            t0 = time.perf_counter()
            r = await rc.post("/fleet/rebalance", json={
                "add": {"id": sup.replica_id(idx), "url": sup.url(idx)}})
            mig = await r.json()
            migration_wall = time.perf_counter() - t0
            stop.set()
            await wtask
            assert r.status == 200 and mig.get("ok"), mig

            grown = await converge(sup.backend_map(), repl * corpus)
            return {
                "corpus": corpus, "counts": counts,
                "capacity_ratio": capacity_ratio, "write_amp": write_amp,
                "mismatches": mismatches, "migration": mig,
                "migration_wall_s": migration_wall,
                "migration_warns": dict(mig_counts),
                "grown_capacity_ratio": max(grown.values()) / corpus,
            }
        finally:
            await rc.close()
            await co.close()

    try:
        sup.start_all()
        sup.wait_ready(timeout_s=300.0)
        out = asyncio.run(go())
    finally:
        sup.stop_all()
        oracle.gfkb.close()

    print(
        f"bench[ownership]: corpus {out['corpus']} rows @ {n_replicas} "
        f"replicas R={repl}: max resident {out['capacity_ratio']:.3f}x "
        f"(bound {max_resident}), write amp {out['write_amp']:.2f} "
        f"(bound {repl + 0.3}); parity mismatches {len(out['mismatches'])}; "
        f"migration {out['migration']['rows_moved']} rows in "
        f"{out['migration_wall_s']:.2f} s with "
        f"{out['migration_warns']['ok']} concurrent warns ok / "
        f"{out['migration_warns']['lost']} lost; grown resident "
        f"{out['grown_capacity_ratio']:.3f}x",
        file=sys.stderr,
    )
    if out["capacity_ratio"] > max_resident:
        raise AssertionError(
            f"per-replica residency {out['capacity_ratio']:.3f}x corpus "
            f"exceeds {max_resident} — ownership is not range-scoping storage"
        )
    if out["write_amp"] > repl + 0.3:
        raise AssertionError(
            f"write amplification {out['write_amp']:.2f} exceeds R+0.3="
            f"{repl + 0.3} — replication is not range-scoped"
        )
    if out["mismatches"]:
        raise AssertionError(
            f"scatter warn diverged from the single-node oracle on "
            f"{len(out['mismatches'])} probes: {out['mismatches'][:5]}"
        )
    if out["migration_warns"]["lost"]:
        raise AssertionError(
            f"{out['migration_warns']['lost']} warns lost during the "
            "range migration — the zero-lost contract broke"
        )
    if out["grown_capacity_ratio"] > max_resident:
        raise AssertionError(
            f"post-migration residency {out['grown_capacity_ratio']:.3f}x "
            f"exceeds {max_resident}"
        )
    return {
        "metric": f"ownership_sharded_gfkb_{n_replicas}r{repl}",
        "platform": "cpu",
        "value": round(out["capacity_ratio"], 3),
        "unit": "max_resident_x_corpus",
        "vs_baseline": 1.0,  # full replication resides 1.0x everywhere
        "corpus_rows": out["corpus"],
        "resident_rows": out["counts"],
        "write_amplification": round(out["write_amp"], 2),
        "parity_probes": apps,
        "parity_mismatches": len(out["mismatches"]),
        "migration_rows_moved": out["migration"]["rows_moved"],
        "migration_wall_s": round(out["migration_wall_s"], 3),
        "migration_epoch": out["migration"]["epoch"],
        "migration_warns_ok": out["migration_warns"]["ok"],
        "migration_warns_lost": out["migration_warns"]["lost"],
        "grown_capacity_ratio": round(out["grown_capacity_ratio"], 3),
        "replication": repl,
        "replicas": n_replicas,
    }


def _bench_storm(backend: str) -> dict:
    """SLO-gated storm drill (kakveda_tpu/traffic/, docs/robustness.md §
    traffic harness): replay the composed hot-key-skew + failure-storm
    scenario open-loop through the real HTTP tier and self-certify the
    graceful-degradation contract IN-RUN.

    Arm A (single process): seeded `storm` scenario — 90% hot-key warn
    at capacity, a background mine flood past its class bound, and the
    chaos timeline (a device-loss window armed via core/faults.py plus
    gossiped fleet-pressure ticks). The SLO gates assert: zero hung
    requests, zero lost warns, sheds confined to sheddable classes (warn
    and ingest NEVER shed), storm-phase warn p95 within the declared
    multiple of the same run's baseline p95, and the brownout ladder back
    at `normal` within the gossip TTL of the storm window closing.

    Arm B (fleet): the same scenario against a replica fleet behind the
    front router with one replica KILLED mid-storm (SIGTERM via the
    supervisor — the chaos timeline's kill_replica action). Gates: zero
    hung, zero lost warns (the router retries idempotent reads onto the
    survivor), warn keeps flowing after the kill.

    Any gate failing raises — a storm row whose degradation was not
    graceful is not a result."""
    import asyncio
    import tempfile
    from pathlib import Path

    import yaml
    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.core import admission as _adm
    from kakveda_tpu.core import faults as _faults
    from kakveda_tpu import traffic as _traffic
    from kakveda_tpu.traffic.slo import percentile as _pct

    seed = int(os.environ.get("KAKVEDA_BENCH_STORM_SEED", 5))
    duration = float(os.environ.get("KAKVEDA_BENCH_STORM_DUR", 8.0))
    speed = float(os.environ.get("KAKVEDA_BENCH_STORM_SPEED", 1.0))
    gossip_ttl = float(os.environ.get("KAKVEDA_BENCH_STORM_TTL", 3.0))
    # Degraded-window warn p95 gate: with the native scorer the warm-tier
    # sweep under device loss must hold ≤8× baseline (ISSUE 11); the
    # pre-native bound stays for numpy-only hosts. Env override wins.
    from kakveda_tpu import native as _native

    _p95x_env = os.environ.get("KAKVEDA_BENCH_STORM_P95X")
    if _p95x_env is not None:
        p95x = float(_p95x_env)
    else:
        p95x = 8.0 if _native.available() else 50.0
    fleet_on = os.environ.get("KAKVEDA_BENCH_STORM_FLEET", "1") != "0"

    # Arm the runtime concurrency sanitizer for the drill (unless the
    # operator decided): every lock the solo arm constructs below records
    # acquisition-order edges, and the row self-certifies the observed
    # graph is acyclic — the dynamic complement of the static lock-order
    # rule, under real storm traffic.
    from kakveda_tpu.core import sanitize as _sanitize

    _sanitize_armed = os.environ.get("KAKVEDA_BENCH_STORM_SANITIZE", "1") != "0"
    if _sanitize_armed:
        os.environ.setdefault("KAKVEDA_SANITIZE", "1")
        _sanitize.reset()

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-storm-"))

    # ---- arm A: single process, full SLO certification ----------------
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app as make_service_app

    sc = _traffic.make_scenario(
        "storm", seed=seed, duration_s=duration,
        gossip_ttl_s=gossip_ttl, warn_p95_x=p95x,
    )
    brown = _adm.BrownoutController(
        enabled=True, enter=0.85, exit=0.5, dwell_s=0.25,
    )
    # warn sized for DEGRADED throughput (during the device-loss window
    # the queue absorbs the warm-tier drain rate — warn must never shed);
    # background at 1 makes the mine flood the sheddable excess.
    adm = _adm.AdmissionController(
        limits={"warn": 64, "ingest": 2, "interactive": 8, "background": 1},
        enabled=True, brownout=brown,
    )
    plat = Platform(data_dir=tmp / "data", capacity=1 << 10, dim=1024)
    svc = make_service_app(platform=plat, admission=adm)

    async def solo():
        client = TestClient(TestServer(svc))
        await client.start_server()
        try:
            async def post(path, body):
                resp = await client.post(path, json=body)
                await resp.read()
                return resp.status

            return await _traffic.run_scenario(
                sc, post=post, speed=speed, admission=adm,
            )
        finally:
            await client.close()

    try:
        res = asyncio.run(solo())
    finally:
        _faults.disarm()  # never leak a chaos window into later metrics
    report = _traffic.evaluate(sc.slo, res)
    base_p95 = _pct(res.latencies_ms("warn", phase="baseline"), 95)
    storm_p95 = _pct(res.latencies_ms("warn", phase="storm"), 95)
    print(
        f"bench[storm]: solo — {len(res.records)} dispatched, "
        f"warn p95 baseline {base_p95:.1f} ms / storm {storm_p95:.1f} ms, "
        f"ladder recovery {res.ladder_recovery_s and round(res.ladder_recovery_s, 2)}s "
        f"(ttl {gossip_ttl}s); {report.summary()}",
        file=sys.stderr,
    )
    if not report.ok:
        raise AssertionError(f"storm drill failed its SLO — {report.summary()}")

    # ---- arm B: fleet with one replica killed mid-storm ----------------
    fleet_out: dict = {"skipped": True}
    if fleet_on:
        from kakveda_tpu.fleet.router import make_router_app
        from kakveda_tpu.fleet.supervisor import FleetSupervisor, pick_port_base

        n_replicas = int(os.environ.get("KAKVEDA_BENCH_STORM_REPLICAS", 2))
        cfg = tmp / "config.yaml"
        cfg.write_text(yaml.safe_dump({
            "failure_matching": {
                "similarity_threshold": 0.8, "embedding_dim": 512, "top_k": 5,
            },
        }))
        replica_env = {
            "JAX_PLATFORMS": "cpu",  # host-plane drill; the parent holds any chip
            "KAKVEDA_CONFIG_PATH": str(cfg),
            "KAKVEDA_INDEX_CAPACITY": "2048",
            "KAKVEDA_LOG_LEVEL": "WARNING",
            "KAKVEDA_GC_TUNE": "0",
        }
        fsc = _traffic.make_scenario(
            "storm", seed=seed + 1, duration_s=duration,
            gossip_ttl_s=gossip_ttl, warn_p95_x=p95x,
            device_loss=False, fleet_pressure=False,
            kill_replica=n_replicas - 1,
        )
        sup = FleetSupervisor(
            tmp / "fleet", port_base=pick_port_base(n_replicas),
            replicas=n_replicas, env=replica_env,
        )
        sup.start_all()

        async def fleet():
            router_app = make_router_app(
                sup.backend_map(), probe_interval_s=0.5, eject_fails=2,
                retries=1, timeout_s=20.0,
            )
            rc = TestClient(TestServer(router_app))
            await rc.start_server()
            try:
                async def post(path, body):
                    resp = await rc.post(path, json=body)
                    await resp.read()
                    return resp.status

                return await _traffic.run_scenario(
                    fsc, post=post, speed=speed, supervisor=sup,
                )
            finally:
                await rc.close()

        try:
            sup.wait_ready(timeout_s=300.0)
            fres = asyncio.run(fleet())
        finally:
            sup.stop_all()
        kill_t = next(
            c["t"] for c in fsc.chaos if c["action"] == "kill_replica"
        )
        after_kill_ok = sum(
            1 for r in fres.records
            if r["klass"] == "warn" and r["status"] == "ok"
            and r["phase"] in ("storm", "recovery")
        )
        counts = fres.class_counts()
        warn_c = counts.get("warn", {})
        lost = fres.generated("warn") - sum(warn_c.values())
        hung = sum(c.get("hung", 0) for c in counts.values())
        bad_shed = {k: c.get("shed", 0) for k, c in counts.items()
                    if c.get("shed", 0) and k in ("warn", "ingest")}
        errors = warn_c.get("error", 0)
        print(
            f"bench[storm]: fleet — {n_replicas} replicas, replica "
            f"{n_replicas - 1} killed at t={kill_t}s; warn counts {warn_c}, "
            f"{after_kill_ok} warns ok during/after the kill window",
            file=sys.stderr,
        )
        if hung or lost > 0 or errors or bad_shed or not after_kill_ok:
            raise AssertionError(
                f"fleet storm arm broke the degradation contract: hung={hung} "
                f"lost={lost} warn_errors={errors} bad_sheds={bad_shed} "
                f"after_kill_ok={after_kill_ok}"
            )
        fleet_out = {
            "replicas": n_replicas,
            "killed_replica_at_s": kill_t,
            "warn_counts": warn_c,
            "warn_ok_after_kill": after_kill_ok,
            "late_p95_ms": fres.late_p95_ms(),
        }

    sanitizer_out: dict = {"armed": False}
    if _sanitize_armed:
        _rep = _sanitize.sanitizer_report()
        # Self-certifying like the SLO gates: an observed lock-order cycle
        # under storm traffic is a latent deadlock, not a result.
        if _rep["cycles"]:
            raise AssertionError(
                f"storm drill observed lock-order cycle(s): {_rep['cycles']}"
            )
        sanitizer_out = {
            "armed": True,
            "lock_order_edges": len(_rep["edges"]),
            "lock_order_cycles": 0,
            "stalls": len(_rep["stalls"]),
        }

    # Trace-plane certification, self-certifying like the SLO gates:
    # every dispatch span ends in the same finally that buckets its
    # record, so a storm run with tracing armed must leave ZERO orphan
    # spans — started minus ended is the span analogue of a lost warn.
    from kakveda_tpu.core import trace as _trace_mod

    tplane = _trace_mod.get_tracer().plane()
    if tplane.get("orphaned"):
        raise AssertionError(
            f"storm drill leaked {tplane['orphaned']} orphan span(s) "
            f"(started {tplane['started']}, ended {tplane['ended']})"
        )

    ratio = round(storm_p95 / max(base_p95, 1e-9), 2)
    return {
        "metric": "storm_warn_p95_degradation",
        "platform": "cpu",
        "value": ratio,
        "unit": "x_baseline",
        "vs_baseline": ratio,
        "slo_ok": report.ok,
        "slo": report.to_dict(),
        "scenario": {"name": "storm", "seed": seed, "duration_s": duration,
                     "speed": speed, "gossip_ttl_s": gossip_ttl},
        "native": _native.available(),
        "warn_p95_gate_x": p95x,
        "warn_p95_baseline_ms": round(base_p95, 2),
        "warn_p95_storm_ms": round(storm_p95, 2),
        "ladder_recovery_s": res.ladder_recovery_s
        and round(res.ladder_recovery_s, 3),
        "dispatched": len(res.records),
        "class_counts": res.class_counts(),
        "shed_counts": adm.shed_counts(),
        "brownout_occupancy": {
            k: round(v, 2) for k, v in adm.brownout.occupancy().items()
        },
        "late_p95_ms": res.late_p95_ms(),
        "fleet": fleet_out,
        "sanitizer": sanitizer_out,
        "trace": tplane,
    }


def _bench_tenants(backend: str) -> dict:
    """Noisy-neighbor tenant-isolation drill (docs/robustness.md §
    multi-tenancy): replay the seeded `noisy_neighbor` scenario — victim
    apps warm up alone, then ONE flooder opens up at ~10x the warn drain
    rate — open-loop through the real HTTP tier, and self-certify the
    isolation contract IN-RUN via the tenant SLO gates:

    * ``min_flood_shed_share`` — ≥90% of all sheds land on the flooder
      (the tenant-aware queue bound aims the pain at whoever owns the
      backlog);
    * ``max_victim_shed_rate`` — victims keep ≥95% admission;
    * ``victim_p95_x_baseline`` — victim ok-p95 during the flood within
      the declared multiple of the same victims' baseline-phase p95
      (deficit round-robin batch composition, not luck);
    * ``max_tenant_starvation_s`` — no victim goes a bounded span of
      scheduled time without one success (the promotion bound, observed).

    Any gate failing raises — an isolation row where victims absorbed the
    flood is not a result. The gates bind only where the flood outruns the
    warn drain rate; on a backend fast enough to absorb it nobody sheds and
    the shed gates pass vacuously (the row's ``tenant_counts`` show which)."""
    import asyncio
    import tempfile
    from pathlib import Path

    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.core import admission as _adm
    from kakveda_tpu.core import faults as _faults
    from kakveda_tpu import traffic as _traffic
    from kakveda_tpu.traffic.slo import percentile as _pct

    seed = int(os.environ.get("KAKVEDA_BENCH_TENANTS_SEED", 7))
    duration = float(os.environ.get("KAKVEDA_BENCH_TENANTS_DUR", 8.0))
    speed = float(os.environ.get("KAKVEDA_BENCH_TENANTS_SPEED", 1.0))
    flood_rps = float(os.environ.get("KAKVEDA_BENCH_TENANTS_FLOOD_RPS", 150.0))
    max_batch = os.environ.get("KAKVEDA_BENCH_TENANTS_MAX_BATCH", "4")

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-tenants-"))

    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app as make_service_app

    sc = _traffic.make_scenario(
        "noisy_neighbor", seed=seed, duration_s=duration,
        flood_rps=flood_rps,
    )
    brown = _adm.BrownoutController(
        enabled=True, enter=0.85, exit=0.5, dwell_s=0.25,
    )
    # warn sized SMALL on purpose: the whole drill is what happens when
    # the warn queue saturates — the tenant-aware bound (not the ladder,
    # which never sheds warn) must decide who eats the 429s.
    adm = _adm.AdmissionController(
        limits={"warn": 16, "ingest": 2, "interactive": 8, "background": 1},
        enabled=True, brownout=brown,
    )

    # Env knobs are read at make_app time, so set-and-restore around
    # construction only.
    _saved = {k: os.environ.get(k) for k in ("KAKVEDA_WARN_MAX_BATCH",)}
    os.environ["KAKVEDA_WARN_MAX_BATCH"] = max_batch
    try:
        plat = Platform(data_dir=tmp / "data", capacity=1 << 10, dim=1024)
        svc = make_service_app(platform=plat, admission=adm)
    finally:
        for k, v in _saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    async def run():
        client = TestClient(TestServer(svc))
        await client.start_server()
        try:
            async def post(path, body):
                resp = await client.post(path, json=body)
                await resp.read()
                return resp.status

            return await _traffic.run_scenario(
                sc, post=post, speed=speed, admission=adm,
            )
        finally:
            await client.close()

    try:
        res = asyncio.run(run())
    finally:
        _faults.disarm()
    report = _traffic.evaluate(sc.slo, res)

    flood_app = sc.slo.flood_app
    tenant_counts = res.tenant_counts("warn")
    flood_c = tenant_counts.get(flood_app, {})
    victim_c: dict = {}
    for app, c in tenant_counts.items():
        if app and app != flood_app:
            for k, v in c.items():
                victim_c[k] = victim_c.get(k, 0) + v
    total_sheds = sum(c.get("shed", 0) for c in tenant_counts.values())
    flood_share = (flood_c.get("shed", 0) / total_sheds) if total_sheds else 1.0
    victim_total = sum(victim_c.values())
    victim_shed_rate = (victim_c.get("shed", 0) / victim_total
                        if victim_total else 0.0)
    vic_apps = [a for a in tenant_counts if a and a != flood_app]
    base_p95 = _pct([x for a in vic_apps
                     for x in res.tenant_latencies_ms(a, phase="baseline")], 95)
    flood_p95 = _pct([x for a in vic_apps
                      for x in res.tenant_latencies_ms(a, phase="flood")], 95)
    ratio = round(flood_p95 / max(base_p95, 1e-9), 2)
    print(
        f"bench[tenants]: {len(res.records)} dispatched, flooder "
        f"{flood_c}, victims {victim_c}; victim p95 baseline "
        f"{base_p95:.1f} ms / flood {flood_p95:.1f} ms ({ratio}x), "
        f"flood shed share {flood_share:.3f}; {report.summary()}",
        file=sys.stderr,
    )
    if not report.ok:
        raise AssertionError(
            f"tenant isolation drill failed its SLO — {report.summary()}"
        )

    return {
        "metric": "tenants_victim_p95_degradation",
        "value": ratio,
        "unit": "x_baseline",
        "vs_baseline": ratio,
        "slo_ok": report.ok,
        "slo": report.to_dict(),
        "scenario": {"name": "noisy_neighbor", "seed": seed,
                     "duration_s": duration, "speed": speed,
                     "flood_rps": flood_rps,
                     "warn_max_batch": int(max_batch)},
        "victim_p95_baseline_ms": round(base_p95, 2),
        "victim_p95_flood_ms": round(flood_p95, 2),
        "victim_shed_rate": round(victim_shed_rate, 4),
        "flood_shed_share": round(flood_share, 4),
        "tenant_counts": tenant_counts,
        "dispatched": len(res.records),
        "class_counts": res.class_counts(),
        "shed_counts": adm.shed_counts(),
        "admission_tenants": adm.tenants_info(),
        "late_p95_ms": res.late_p95_ms(),
    }


def _bench_elastic(backend: str) -> dict:
    """Elastic self-healing fleet drill (fleet/autoscaler.py,
    docs/scale-out.md § elastic fleet) — self-certifying, any gate
    failing raises.

    A 2-replica sharded-ownership fleet (R=2) runs under the router's
    autoscaler (min 2 / max 4) with drill-speed policy knobs. The seeded
    `flash_crowd` scenario replays open-loop: baseline warn, then a 5×
    warn ramp + a full-mine background flood that pins replica occupancy,
    then ONE OWNER SIGKILLed at surge end (the crash_replica chaos
    action), then decay. Gates:

    * the sustained surge scales the fleet 2→4 (>= 2 scale_up:ok);
    * the SIGKILLed owner is replaced (>= 1 replace:ok) and the ring
      re-converges: zero coverage holes, resident rows back to R×corpus;
    * the decay drains the fleet back to 2 via the lossless
      migrate-then-stop protocol (live == 2 at the end);
    * the scenario SLO holds: zero lost warns, zero hung, sheds confined
      to interactive/background, and at most max_scale_flaps=1 direction
      reversal (2→4→2 is exactly one flap).

    Replicas are ALWAYS pinned to CPU here — a host-plane drill whose
    parent has touched JAX cannot start a chip-claiming child."""
    import asyncio
    import tempfile
    from pathlib import Path

    import yaml
    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.core import faults as _faults
    from kakveda_tpu import traffic as _traffic
    from kakveda_tpu.fleet.ownership import OwnershipView
    from kakveda_tpu.fleet.router import ROUTER_KEY, make_router_app
    from kakveda_tpu.fleet.supervisor import FleetSupervisor, pick_port_base

    seed = int(os.environ.get("KAKVEDA_BENCH_ELASTIC_SEED", 7))
    surge_s = float(os.environ.get("KAKVEDA_BENCH_ELASTIC_SURGE_S", 50.0))
    decay_s = float(os.environ.get("KAKVEDA_BENCH_ELASTIC_DECAY_S", 45.0))
    n_start, n_max, repl = 2, 4, 2
    apps, per_app = 24, 3

    tmp = Path(tempfile.mkdtemp(prefix="kakveda-bench-elastic-"))
    cfg = tmp / "config.yaml"
    cfg.write_text(yaml.safe_dump({
        "failure_matching": {
            "similarity_threshold": 0.8, "embedding_dim": 512, "top_k": 5,
        },
    }))
    replica_env = {
        "JAX_PLATFORMS": "cpu",  # host-plane drill; the parent holds any chip
        "KAKVEDA_CONFIG_PATH": str(cfg),
        "KAKVEDA_INDEX_CAPACITY": "2048",
        "KAKVEDA_FLEET_OWNERSHIP": "1",
        "KAKVEDA_FLEET_REPLICATION": str(repl),
        # background=1 makes each admitted full-mine pin the replica's
        # occupancy export at 1.0 — the autoscaler's pressure signal.
        "KAKVEDA_ADMIT_BACKGROUND": "1",
        "KAKVEDA_ADMIT_WARN": "64",
        # Heal seam: replication events dead-lettered at the origins
        # while the crashed owner is down auto-replay on breaker re-close.
        "KAKVEDA_DLQ_AUTO_S": "2",
        "KAKVEDA_LOG_LEVEL": "WARNING",
        "KAKVEDA_GC_TUNE": "0",
    }
    # Drill-speed policy knobs (read once at autoscaler mount). Saved and
    # restored so a full sweep's later rows see the operator's env.
    drill_knobs = {
        "KAKVEDA_SCALE_UP_OCC": "0.6",
        "KAKVEDA_SCALE_DOWN_OCC": "0.2",
        "KAKVEDA_SCALE_DWELL_S": "2",
        "KAKVEDA_SCALE_COOLDOWN_S": "5",
        "KAKVEDA_SCALE_REPLACE_S": "3",
        "KAKVEDA_SCALE_REPLACE_BACKOFF_S": "3",
        "KAKVEDA_SCALE_TICK_S": "0.5",
    }
    saved_env = {k: os.environ.get(k) for k in drill_knobs}
    os.environ.update(drill_knobs)

    sc = _traffic.make_scenario(
        "flash_crowd", seed=seed, baseline_s=4.0, surge_s=surge_s,
        decay_s=decay_s, warn_rps=4.0, surge_x=5.0, bg_rps=12.0,
        apps=apps, crash_replica=1, gossip_ttl_s=3.0, max_scale_flaps=1,
    )
    sup = FleetSupervisor(
        tmp / "fleet", port_base=pick_port_base(n_max + 1),
        replicas=n_start, env=replica_env,
    )
    sup.autoscale = (n_start, n_max)

    def _trace(app_id: str, i: int) -> dict:
        return {
            "trace_id": f"el-{i}",
            "ts": time.time(),
            "app_id": app_id,
            "prompt": f"Cite sources for claim {i} even if unavailable.",
            "response": "See [1].\n\nReferences:\n[1] Smith (2020).",
            "tools": [], "env": {"os": "linux"},
        }

    async def go():
        import httpx

        router_app = make_router_app(
            sup.backend_map(), probe_interval_s=0.5, eject_fails=2,
            retries=1, timeout_s=20.0,
            ownership=OwnershipView(sup.backend_map(), replication=repl),
            supervisor=sup, autoscale=(n_start, n_max),
        )
        rc = TestClient(TestServer(router_app))
        await rc.start_server()
        router = router_app[ROUTER_KEY]
        scaler = router.autoscaler
        assert scaler is not None, "autoscaler did not mount"
        try:
            # Seed a corpus so the crashed owner has rows to lose — and
            # the replacement has a heal to prove.
            for a in range(apps):
                traces = [_trace(f"app-{a}", a * per_app + j)
                          for j in range(per_app)]
                r = await rc.post("/ingest/batch", json={"traces": traces})
                assert r.status == 200, await r.text()
            corpus = apps * per_app

            async def post(path, body):
                resp = await rc.post(path, json=body)
                await resp.read()
                return resp.status

            res = await _traffic.run_scenario(
                sc, post=post, speed=1.0, supervisor=sup,
                autoscaler=scaler,
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

            # The replay window closed; the autoscaler keeps ticking.
            # Converge: replacement done, fleet drained back to n_start,
            # zero coverage holes, resident rows back to R×corpus.
            deadline = time.monotonic() + 180.0
            counts, holes = {}, ["unpolled"]
            while time.monotonic() < deadline:
                dc = scaler.decision_counts()
                counts = await live_counts()
                holes = router.ownership.coverage_holes(list(counts))
                if (dc.get("replace:ok", 0) >= 1
                        and len(counts) == n_start
                        and not holes
                        and sum(counts.values()) >= repl * corpus):
                    break
                await asyncio.sleep(1.0)
            res.notes["scale_flaps"] = float(scaler.flap_count())
            return res, scaler.decision_counts(), counts, holes, corpus
        finally:
            await rc.close()

    try:
        sup.start_all()
        sup.wait_ready(timeout_s=300.0)
        res, dcounts, live, holes, corpus = asyncio.run(go())
    finally:
        sup.stop_all()
        _faults.disarm()  # never leak a chaos window
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    ups = dcounts.get("scale_up:ok", 0)
    downs = dcounts.get("scale_down:ok", 0)
    replaces = dcounts.get("replace:ok", 0)
    peak = n_start + ups
    report = _traffic.evaluate(sc.slo, res)
    print(
        f"bench[elastic]: {n_start}→{peak}→{len(live)} replicas "
        f"(ups={ups} downs={downs} replaces={replaces}, "
        f"flaps={int(res.notes.get('scale_flaps', -1))}); "
        f"resident {sum(live.values())} rows vs R×corpus {repl * corpus}, "
        f"coverage holes {holes or 0}; decisions {dcounts}; "
        f"{report.summary()}",
        file=sys.stderr,
    )
    if ups < 2:
        raise AssertionError(
            f"flash crowd never scaled 2→4: scale_up:ok={ups} "
            f"(decisions {dcounts})"
        )
    if replaces < 1:
        raise AssertionError(
            f"SIGKILLed owner was never replaced (decisions {dcounts})"
        )
    if len(live) != n_start:
        raise AssertionError(
            f"fleet did not drain back to {n_start}: live={sorted(live)} "
            f"(decisions {dcounts})"
        )
    if holes:
        raise AssertionError(
            f"coverage holes after replacement: {holes}"
        )
    if sum(live.values()) < repl * corpus:
        raise AssertionError(
            f"heal incomplete: {sum(live.values())} resident rows < "
            f"R×corpus {repl * corpus} ({live})"
        )
    if not report.ok:
        raise AssertionError(
            f"elastic drill failed its SLO — {report.summary()}"
        )
    return {
        "metric": "elastic_fleet_flash_crowd",
        "platform": "cpu",
        "value": peak,
        "unit": "peak_replicas",
        "vs_baseline": n_start,
        "slo_ok": report.ok,
        "slo": report.to_dict(),
        "scenario": {"name": "flash_crowd", "seed": seed,
                     "surge_s": surge_s, "decay_s": decay_s},
        "scale_decisions": dcounts,
        "scale_ups_ok": ups,
        "scale_downs_ok": downs,
        "replaces_ok": replaces,
        "scale_flaps": int(res.notes.get("scale_flaps", -1)),
        "final_replicas": len(live),
        "resident_rows": live,
        "corpus_rows": corpus,
        "replication": repl,
        "coverage_holes": 0,
        "dispatched": len(res.records),
        "class_counts": res.class_counts(),
        "late_p95_ms": res.late_p95_ms(),
    }


def _bench_mine(backend: str) -> dict:
    n = int(os.environ.get("KAKVEDA_BENCH_MINE_N", 500_000 if _on_tpu(backend) else 20_000))
    dim = int(os.environ.get("KAKVEDA_BENCH_DIM", 2048))
    n_templates = int(os.environ.get("KAKVEDA_BENCH_MINE_TEMPLATES", 120))
    print(f"bench[mine]: backend={backend} n={n} dim={dim} templates={n_templates}", file=sys.stderr)
    _ledger_reset()
    r = _measure_mine(n, dim, n_templates)
    print(
        f"bench[mine]: clustered {r['n']:,} embeddings in {r['wall_s']:.1f}s "
        f"({r['clusters']} clusters, purity {r['purity']:.3f}; host embed {r['embed_s']:.1f}s)",
        file=sys.stderr,
    )
    inc = r["incremental"]
    print(
        f"bench[mine]: incremental — streamed {inc['n']:,} rows at "
        f"{inc['amortized_ms_per_row']:.3f} ms/row amortized "
        f"(batch {inc['batch']}); cluster refresh {inc['refresh_wall_s']*1000:.1f} ms "
        f"vs full sweep {inc['full_wall_s']:.2f}s "
        f"({inc['refresh_speedup']:.0f}x), parity={inc['parity']}, "
        f"purity {inc['purity']:.3f}",
        file=sys.stderr,
    )
    # Self-certifying: a wall time whose clustering is wrong is not a
    # result. Purity is computed on THIS run's labels (not a calibration
    # run at another scale); below the floor the metric FAILS rather than
    # reporting a meaningless speed. The incremental arm must ALSO match
    # the full-mine oracle's partition exactly and clear the same purity
    # floor — a fast refresh with different clusters is not a result.
    min_purity = float(os.environ.get("KAKVEDA_BENCH_MINE_MIN_PURITY", 0.99))
    if r["purity"] < min_purity:
        raise AssertionError(
            f"mine purity {r['purity']:.4f} below the {min_purity} floor at "
            f"{r['n']:,} rows ({r['clusters']} clusters) — wall time not reportable"
        )
    if not inc["parity"]:
        raise AssertionError(
            f"incremental mine diverged from the full-mine partition at "
            f"{inc['n']:,} rows — refresh speed not reportable"
        )
    if inc["purity"] < min_purity:
        raise AssertionError(
            f"incremental mine purity {inc['purity']:.4f} below the "
            f"{min_purity} floor at {inc['n']:,} rows"
        )
    # Self-certifying (KAKVEDA_LEDGER=1): pow2 corpus padding bounds any
    # single entry point (build_knn_edges' _block_topk, the delta top-k)
    # to O(log N) distinct lowerings as the GFKB grows — per-fn compile
    # counts past 2·log2(N)+8 mean the bucketing regressed.
    envelope = 2 * max(1, int(np.ceil(np.log2(max(n, 2))))) + 8
    ledger_plane = _ledger_certify("bench[mine]", max_per_fn=envelope)
    return {
        **({"ledger": ledger_plane, "ledger_envelope": envelope}
           if ledger_plane else {}),
        "metric": f"mine_wall_s_at_{n}_gfkb",
        "value": round(r["wall_s"], 2),
        "unit": "s",
        "vs_baseline": round(r["purity"], 4),
        "clusters": r["clusters"],
        "purity": round(r["purity"], 4),
        "min_purity": min_purity,
        "incremental": {
            "n": inc["n"],
            "amortized_ms_per_row": round(inc["amortized_ms_per_row"], 4),
            "stream_wall_s": round(inc["stream_wall_s"], 3),
            "refresh_wall_s": round(inc["refresh_wall_s"], 4),
            "full_wall_s": round(inc["full_wall_s"], 3),
            "refresh_speedup": round(inc["refresh_speedup"], 1),
            "parity": inc["parity"],
            "purity": round(inc["purity"], 4),
            "clusters": inc["clusters"],
        },
    }


def _bench_continuous(backend: str) -> dict:
    """Continuous vs static batching under mixed-length traffic (opt-in:
    not part of the default sweep). N requests whose EOS-free decode
    lengths vary widely; static batching decodes every cohort to its
    longest member, continuous batching refills retired slots."""
    import jax
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import generate_tokens_fused
    from kakveda_tpu.models.llama import LlamaConfig, init_params
    from kakveda_tpu.models.serving import ContinuousBatcher

    preset = os.environ.get("KAKVEDA_BENCH_DECODE_PRESET", "1b" if _on_tpu(backend) else "tiny")
    cfg = _preset_cfg(preset)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), init_params(jax.random.PRNGKey(0), cfg)
    )
    rng = np.random.default_rng(0)
    n_req, slots = 32, 8
    prompts = [list(rng.integers(3, cfg.vocab_size, size=int(rng.integers(16, 64)))) for _ in range(n_req)]
    lengths = [int(x) for x in rng.integers(8, 128, size=n_req)]  # decode lengths

    # Static: cohorts of `slots`, each decoded to its max length.
    def run_static() -> float:
        t0 = time.perf_counter()
        total = 0
        for s in range(0, n_req, slots):
            batch = prompts[s : s + slots]
            steps = max(lengths[s : s + slots])
            out = generate_tokens_fused(params, cfg, batch, max_new_tokens=steps)
            total += sum(min(len(o), L) for o, L in zip(out, lengths[s : s + slots]))
        return total / (time.perf_counter() - t0)

    def run_continuous() -> float:
        cb = ContinuousBatcher(params, cfg, batch_slots=slots, max_len=256, chunk_steps=8)
        t0 = time.perf_counter()
        pending = list(zip(prompts, lengths))
        done_tokens = 0
        while pending or cb.active:
            while pending and cb.has_capacity:
                p, L = pending.pop(0)
                cb.admit(p, max_new_tokens=L)
            for rid in cb.step():
                done_tokens += len(cb.results[rid])
        return done_tokens / (time.perf_counter() - t0)

    # Per-request decode: what online traffic cost BEFORE the shared
    # engine — each request runs its own decode stream to completion
    # (the pre-round-4 playground/eval/judge path, and the reference's
    # sequential per-request Ollama hop). Subset of requests, scaled:
    # a full pass at batch-1 would dominate the metric's wall time.
    def run_per_request(n_sub: int = 8) -> float:
        t0 = time.perf_counter()
        total = 0
        for p, L in list(zip(prompts, lengths))[:n_sub]:
            out = generate_tokens_fused(params, cfg, [p], max_new_tokens=L)
            total += len(out[0])
        return total / (time.perf_counter() - t0)

    # Prefix-cache A/B: the judge/system-preamble traffic shape — a long
    # shared prompt head + short per-request tails, short decodes (so
    # admission prefill dominates). Registered prefixes scatter a
    # precomputed K/V slab instead of re-running the head's FLOPs.
    def run_prefix(register: bool) -> float:
        pre_len = 256 if _on_tpu(backend) else 64
        rng2 = np.random.default_rng(7)  # own stream: A and B see identical prompts
        pre = [int(x) for x in rng2.integers(3, cfg.vocab_size, size=pre_len)]
        pfx_prompts = [
            pre + [int(x) for x in rng2.integers(3, cfg.vocab_size, size=int(rng2.integers(4, 24)))]
            for _ in range(16)
        ]
        cb = ContinuousBatcher(params, cfg, batch_slots=slots, max_len=512, chunk_steps=8)
        if register:
            cb.register_prefix(pre)
        # Warm every admission shape off-clock: suffix lengths 4/12/20 hit
        # the three power-of-two suffix-chunk widths (8/16/32) the measured
        # set draws from — otherwise their compiles land in the timed pass.
        warm = [pre + [5] * s for s in (4, 12, 20)]
        cb.run_all(warm, max_new_tokens=8)
        t0 = time.perf_counter()
        cb.run_all(pfx_prompts, max_new_tokens=8)
        return time.perf_counter() - t0

    run_static()  # compile/warm all paths
    static_tps = run_static()
    # Warm ALL measured requests: each distinct decode length L is its own
    # static scan length → its own compile; warming a subset would leave
    # cold compiles inside the timed pass and deflate per_request_tps.
    run_per_request()
    per_req_tps = run_per_request()
    run_continuous()
    cont_tps = run_continuous()
    wall_nopfx = run_prefix(False)
    wall_pfx = run_prefix(True)
    print(
        f"bench[continuous]: prefix-cache A/B — shared-head workload "
        f"{wall_nopfx:.2f}s uncached vs {wall_pfx:.2f}s cached "
        f"({wall_nopfx / max(wall_pfx, 1e-9):.2f}x)",
        file=sys.stderr,
    )
    return {
        "metric": "continuous_batching_tokens_per_sec",
        "value": round(cont_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(cont_tps / static_tps, 2) if static_tps > 0 else 0.0,
        "static_tps": round(static_tps, 1),
        "per_request_tps": round(per_req_tps, 1),
        "vs_per_request": round(cont_tps / per_req_tps, 2) if per_req_tps > 0 else 0.0,
        "prefix_wall_s_uncached": round(wall_nopfx, 3),
        "prefix_wall_s_cached": round(wall_pfx, 3),
        "prefix_speedup": round(wall_nopfx / max(wall_pfx, 1e-9), 2),
    }


def _bench_tiered(backend: str) -> dict:
    """Tiered-GFKB routing A/B, self-certifying vs the exact oracle (the
    ``mine`` metric's style): build a clustered sparse corpus through the
    REAL tier insert path (warm RAM + IVF router; the big arm spills most
    rows to cold memmap shards), then answer the same queries twice —
    routed (nprobe candidate lists, exact top-k over candidates) and the
    exact full scan — and report recall@1 plus both latency distributions.
    The acceptance bar (ISSUE 7): routed p50 ≤ 0.25× exact p50 at 1M rows
    with recall@1 ≥ 0.99, and a ≥10M-row corpus running end-to-end via the
    host/disk tiers. Host-only by design: the tiers exist precisely for
    rows the device cannot hold, so this metric survives a chip outage.

    Native arm (ISSUE 11): when the C++ scorer is available the same
    queries run twice more with it force-disabled, reporting the
    numpy-vs-native A/B, and the big arm's routed p50 must clear
    ``KAKVEDA_BENCH_TIERED_NATIVE_MS`` (default 120 ms) — a self-certified
    bound on host-side match latency at 10M rows.
    """
    from kakveda_tpu.index.tiers import TierConfig, TieredIndex

    n = int(os.environ.get("KAKVEDA_BENCH_TIERED_N", 1 << 20))
    dim = int(os.environ.get("KAKVEDA_BENCH_TIERED_DIM", 2048))
    n_queries = int(os.environ.get("KAKVEDA_BENCH_TIERED_QUERIES", 128))
    big_n = int(os.environ.get("KAKVEDA_BENCH_TIERED_BIG_N", 10_000_000))
    print(
        f"bench[tiered]: n={n} dim={dim} queries={n_queries} big_n={big_n}",
        file=sys.stderr,
    )
    _ledger_reset()

    rng = np.random.default_rng(7)
    K = 16  # nnz per synthetic row (hashed-ngram rows are similarly sparse)

    def make_rows(n_rows: int, n_templates: int, batch: int):
        """Yield (slots, idx, val, template_ids) batches: each template
        owns K stable feature buckets; rows jitter the weights and swap
        in 2 noise features — clustered like real failure signatures."""
        tmpl_feats = rng.integers(0, dim, size=(n_templates, K), dtype=np.int64)
        for s in range(0, n_rows, batch):
            e = min(n_rows, s + batch)
            t = rng.integers(0, n_templates, size=e - s)
            idx = tmpl_feats[t].astype(np.int32)
            val = (1.0 + 0.1 * rng.standard_normal((e - s, K))).astype(np.float32)
            noise = rng.integers(0, dim, size=(e - s, 2))
            idx[:, K - 2 :] = noise
            val /= np.maximum(np.linalg.norm(val, axis=1, keepdims=True), 1e-9)
            yield np.arange(s, e, dtype=np.int64), idx, val, t

    def build(n_rows: int, n_templates: int, cfg: TierConfig, data_dir=None):
        tiers = TieredIndex(dim, cfg, data_dir)
        templates = np.empty(n_rows, np.int64)
        t0 = time.perf_counter()
        for slots, idx, val, t in make_rows(n_rows, n_templates, 8192):
            tiers.insert(slots, idx, val)
            templates[slots[0] : slots[-1] + 1] = t
        return tiers, templates, time.perf_counter() - t0

    def make_queries(tiers, n_rows: int, m: int):
        """Noisy copies of random stored rows — built ONCE so the routed
        and exact arms answer the identical query set."""
        out = []
        for s in rng.integers(0, n_rows, size=m).tolist():
            row = tiers.row(int(s))
            q_idx = row[0].astype(np.int32)
            q_val = row[1] + 0.05 * rng.standard_normal(len(row[1])).astype(np.float32)
            q_val /= max(float(np.linalg.norm(q_val)), 1e-9)
            out.append((q_idx, q_val))
        return out

    def run_queries(tiers, queries, exact: bool):
        lat, top1, scores1 = [], [], []
        for q_idx, q_val in queries:
            t0 = time.perf_counter()
            sc, sl, _mode = tiers.match_host(q_idx, q_val, 5, exact=exact)
            lat.append((time.perf_counter() - t0) * 1000.0)
            top1.append(int(sl[0]) if len(sl) else -1)
            scores1.append(float(sc[0]) if len(sc) else -np.inf)
        return np.asarray(lat), np.asarray(top1), np.asarray(scores1)

    # --- 1M arm: warm-resident, routed vs exact on the same corpus -----
    cfg = TierConfig(
        tiered=True, hot_rows=0, warm_rows=1 << 62, nprobe=8,
        max_list=1 << 62, promote_cache=4096,
    )
    tiers, templates, build_s = build(n, 1024, cfg)
    print(
        f"bench[tiered]: built {n:,} rows in {build_s:.1f}s "
        f"({tiers.info()['centroids']} centroids)", file=sys.stderr,
    )
    queries = make_queries(tiers, n, n_queries)
    lat_r, top_r, sc_r = run_queries(tiers, queries, exact=False)
    lat_e, top_e, sc_e = run_queries(tiers, queries, exact=True)
    # native A/B: same corpus, same queries, scorer force-disabled — the
    # numpy arm is exactly the KAKVEDA_NATIVE=0 code path.
    native_avail = bool(tiers.scorer.enabled)
    native_ab = {"available": native_avail}
    if native_avail:
        tiers.scorer.enabled = False
        lat_r_np, _, _ = run_queries(tiers, queries, exact=False)
        lat_e_np, _, _ = run_queries(tiers, queries, exact=True)
        tiers.scorer.enabled = True
        native_ab["routed_p50_numpy_ms"] = round(float(np.percentile(lat_r_np, 50)), 3)
        native_ab["exact_p50_numpy_ms"] = round(float(np.percentile(lat_e_np, 50)), 3)
        print(
            f"bench[tiered]: numpy arm routed p50="
            f"{native_ab['routed_p50_numpy_ms']:.3f}ms exact p50="
            f"{native_ab['exact_p50_numpy_ms']:.3f}ms", file=sys.stderr,
        )
    # recall@1: routed top-1 matches the oracle slot, or ties its score
    # (duplicate templates make exact ties common).
    recall = float(np.mean((top_r == top_e) | (sc_r >= sc_e - 1e-5)))
    p50_r, p95_r = float(np.percentile(lat_r, 50)), float(np.percentile(lat_r, 95))
    p50_e, p95_e = float(np.percentile(lat_e, 50)), float(np.percentile(lat_e, 95))
    ratio = p50_r / p50_e if p50_e > 0 else float("inf")
    print(
        f"bench[tiered]: routed p50={p50_r:.3f}ms p95={p95_r:.3f}ms | exact "
        f"p50={p50_e:.3f}ms p95={p95_e:.3f}ms | ratio={ratio:.3f} "
        f"recall@1={recall:.4f}", file=sys.stderr,
    )

    # --- big arm: ≥10M rows end-to-end through warm + cold (disk) ------
    big = {}
    if big_n > 0:
        import tempfile
        from pathlib import Path

        with tempfile.TemporaryDirectory(prefix="kakveda-tiered-") as td:
            cfg_big = TierConfig(
                tiered=True, hot_rows=0, warm_rows=1 << 20, nprobe=4,
                max_list=1 << 62, promote_cache=8192,
                cold_dir=Path(td) / "cold",
            )
            tiers_b, _tmpl, build_big_s = build(big_n, 256, cfg_big)
            info = tiers_b.info()
            print(
                f"bench[tiered]: big arm {big_n:,} rows in {build_big_s:.1f}s "
                f"(warm={info['warm']:,} cold={info['cold']:,})",
                file=sys.stderr,
            )
            queries_b = make_queries(tiers_b, big_n, 32)
            lat_b, top_b, sc_b = run_queries(tiers_b, queries_b, exact=False)
            # sampled oracle: the exact scan is O(N) at 10M — certify
            # recall on a subset of the same queries
            m_oracle = 8
            lat_be, top_be, sc_be = run_queries(tiers_b, queries_b[:m_oracle], exact=True)
            big_native = {}
            if tiers_b.scorer.enabled:
                tiers_b.scorer.enabled = False
                lat_b_np, _, _ = run_queries(tiers_b, queries_b, exact=False)
                tiers_b.scorer.enabled = True
                native_ms = float(
                    os.environ.get("KAKVEDA_BENCH_TIERED_NATIVE_MS", 120.0)
                )
                p50_native = float(np.percentile(lat_b, 50))
                big_native = {
                    "routed_p50_numpy_ms": round(float(np.percentile(lat_b_np, 50)), 3),
                    "native_p50_budget_ms": native_ms,
                    # ISSUE 11 self-certification: 10M-row routed match p50
                    # must clear the native budget when the scorer loaded.
                    "native_p50_ok": bool(p50_native <= native_ms),
                }
            big = {
                "n": big_n,
                "build_s": round(build_big_s, 1),
                "warm_rows": int(info["warm"]),
                "cold_rows": int(info["cold"]),
                "routed_p50_ms": round(float(np.percentile(lat_b, 50)), 3),
                "routed_p95_ms": round(float(np.percentile(lat_b, 95)), 3),
                "exact_p50_ms": round(float(np.percentile(lat_be, 50)), 3),
                "recall_at1_sampled": round(
                    float(np.mean((top_b[:m_oracle] == top_be) | (sc_b[:m_oracle] >= sc_be - 1e-5))), 4
                ),
                **big_native,
            }

    # Self-certifying (KAKVEDA_LEDGER=1): the tiers are host-resident by
    # design — any jit entry that compiled during this metric must still
    # sit inside the O(log N) pow2-bucket envelope (today the window is
    # expected to be compile-free; a violation means device code crept
    # into the host tiers without bucketing).
    envelope = 2 * max(1, int(np.ceil(np.log2(max(big_n, n, 2))))) + 8
    ledger_plane = _ledger_certify("bench[tiered]", max_per_fn=envelope)
    return {
        **({"ledger": ledger_plane, "ledger_envelope": envelope}
           if ledger_plane else {}),
        "metric": f"tiered_warn_routed_p50_ms_at_{n}",
        "value": round(p50_r, 3),
        "unit": "ms",
        # headline self-certification: exact-scan p50 over routed p50 —
        # ≥4 means the ≤0.25× sublinear bar holds.
        "vs_baseline": round(p50_e / p50_r, 1) if p50_r > 0 else 0.0,
        "recall_at1": round(recall, 4),
        "exact_p50_ms": round(p50_e, 3),
        "exact_p95_ms": round(p95_e, 3),
        "routed_p95_ms": round(p95_r, 3),
        "sublinear_ratio": round(ratio, 4),
        "sublinear_ok": bool(ratio <= 0.25),
        "recall_ok": bool(recall >= 0.99),
        "build_s": round(build_s, 1),
        "centroids": int(tiers.info()["centroids"]),
        "native": native_ab,
        "big": big,
    }


_RECOVERY_CHILD = r'''
import json, sys, time
from pathlib import Path
from kakveda_tpu.index.gfkb import GFKB

mode, data = sys.argv[1], Path(sys.argv[2])
cap, dim, n, versions = (int(a) for a in sys.argv[3:7])
sig = lambda i: (
    f"recovery bench failure signature {i} stack frame worker pool shard {i % 17}"
)
if mode == "seed":
    kb = GFKB(data_dir=data, capacity=cap, dim=dim)
    B = 1024
    t0 = time.perf_counter()
    for v in range(versions):
        for s in range(0, n, B):
            kb.upsert_failures_batch([
                {"failure_type": "oom" if i % 2 else "timeout",
                 "signature_text": sig(i), "app_id": f"app-{i % 7}",
                 "impact_severity": "high"}
                for i in range(s, min(n, s + B))
            ])
    kb.close()
    print(json.dumps({
        "seed_s": round(time.perf_counter() - t0, 2),
        "log_bytes": (data / "failures.jsonl").stat().st_size,
        "log_lines": n * versions,
    }))
elif mode == "open":
    queries = json.loads(sys.stdin.read())
    # Warm the process on a throwaway store of the SAME row count, then
    # compact+reopen it: jit compilation is code-and-shape-shaped, not
    # state-shaped — a production restart with a persistent compile
    # cache would not re-pay the replay-path OR bulk-restore-path
    # compiles per stored row. Both arms (uncompacted and compacted)
    # get the identical treatment, so the timed delta is purely
    # replay-vs-checkpoint.
    import tempfile
    _wd = Path(tempfile.mkdtemp())
    _wk = GFKB(data_dir=_wd, capacity=cap, dim=dim)
    for _s in range(0, n, 1024):
        _wk.upsert_failures_batch([
            {"failure_type": "oom", "signature_text": f"warmup row {_i}",
             "app_id": "warm", "impact_severity": "high"}
            for _i in range(_s, min(n, _s + 1024))
        ])
    _wk.compact()
    _wk.close()
    GFKB(data_dir=_wd, capacity=cap, dim=dim).close()
    t0 = time.perf_counter()
    kb = GFKB(data_dir=data, capacity=cap, dim=dim)
    open_s = time.perf_counter() - t0
    top1 = [
        [str(m[0].failure_id), float(m[0].score)] if m else None
        for m in kb.match_batch(queries)
    ]
    info = kb.lifecycle_info()
    kb.close()
    print(json.dumps({"open_s": round(open_s, 3), "top1": top1,
                      "rows": len(kb._records), "lifecycle": info}))
elif mode == "compact":
    kb = GFKB(data_dir=data, capacity=cap, dim=dim)
    out = kb.compact()
    kb.close()
    print(json.dumps(out))
elif mode == "aging":
    # Month-compressed aging: replay the aging scenario's ingest events
    # into a fresh store stamping each cohort at its VIRTUAL time, then
    # run the TTL pass with an injected clock and compact. Certifies the
    # resident-bytes bound without waiting out real weeks.
    import datetime
    from kakveda_tpu.traffic.scenarios import make_scenario
    sc = make_scenario("aging", seed=11, duration_s=8.0)
    kb = GFKB(data_dir=data, capacity=cap, dim=dim)
    comp = sc.notes["compression"]
    now0 = time.time()
    for e in sc.events:
        if e["klass"] != "ingest":
            continue
        res = kb.upsert_failures_batch([
            {"failure_type": "hallucinated_citation",
             "signature_text": t["prompt"],
             "app_id": e["app_id"], "impact_severity": "high"}
            for t in e["body"]["traces"]
        ])
        # Stamp the touched records at the event's VIRTUAL timestamp —
        # upsert returns the stored objects, so age_rows sees cohort k as
        # k virtual weeks old even though the whole replay took seconds.
        vts = datetime.datetime.fromtimestamp(
            now0 + e["t"] * comp, tz=datetime.timezone.utc
        )
        with kb._lock:
            for rec, _created in res:
                rec.updated_at = vts
    bytes_before = (data / "failures.jsonl").stat().st_size
    rows_before = len(kb._records)
    now_virtual = now0 + sc.duration_s * comp
    aged = kb.age_rows(ttl_s=sc.notes["age_ttl_virtual_s"], now=now_virtual)
    out = kb.compact()
    kb.close()
    print(json.dumps({
        "rows": rows_before,
        "aged": aged["tombstoned"],
        "bytes_before": bytes_before,
        "bytes_after": (data / "failures.jsonl").stat().st_size
        + (data / "tombstones.jsonl").stat().st_size,
        "compact": out,
    }))
else:
    raise SystemExit(f"unknown mode {mode}")
'''


def _bench_recovery(backend: str) -> dict:
    """GFKB durability-lifecycle certification, self-certifying end to end.

    Four sub-certifications, each of which RAISES on failure (ISSUE 18):
    (1) restart-replay wall at ``KAKVEDA_BENCH_RECOVERY_N × _VERSIONS``
    log lines (default 10k signatures × 30 occurrence bumps = 300k —
    the months-of-recurrences shape the lifecycle exists for: a
    signature recurring daily for a month appends 30 update lines the
    checkpoint folds into one) must improve ≥
    ``KAKVEDA_BENCH_RECOVERY_IMPROVE``× (default 5×) after checkpoint+
    delta compaction; (2) recall@1 parity on a held-out warn set vs the
    uncompacted oracle (top-1 id equal, or score tie within 1e-5); (3)
    the month-compressed aging scenario tombstones its expired cohorts
    and ends with failures-log+tombstone bytes strictly below the
    uncompacted log (resident-bytes bound); (4) the crash-point sweep
    over every lifecycle kill offset reports ``corrupt_recoveries == 0``.

    Host-durability by design: every store open/seed/compact runs in a
    CPU-pinned child process (``JAX_PLATFORMS=cpu`` — the bench parent
    holds any chip, and this is host work).
    """
    import shutil
    import subprocess
    import tempfile
    from pathlib import Path

    n = int(os.environ.get("KAKVEDA_BENCH_RECOVERY_N", 10_000))
    versions = int(os.environ.get("KAKVEDA_BENCH_RECOVERY_VERSIONS", 30))
    n_queries = int(os.environ.get("KAKVEDA_BENCH_RECOVERY_QUERIES", 64))
    improve_min = float(os.environ.get("KAKVEDA_BENCH_RECOVERY_IMPROVE", 5.0))
    cap = int(os.environ.get("KAKVEDA_BENCH_RECOVERY_CAP", 2048))
    dim = 256
    print(
        f"bench[recovery]: n={n} versions={versions} queries={n_queries} "
        f"improve_min={improve_min}x",
        file=sys.stderr,
    )

    env = {k: v for k, v in os.environ.items() if not k.startswith("KAKVEDA_")}
    env["JAX_PLATFORMS"] = "cpu"
    # Tiered serving shape: rows past the hot cap live in the host warm
    # tier, which is the realistic ≥100k-row production profile AND what
    # the restore path is optimized for (device scatter for hot rows
    # only, numpy install for warm).
    env["KAKVEDA_GFKB_HOT_ROWS"] = str(cap)

    def child(mode: str, data: Path, stdin: str = "") -> dict:
        proc = subprocess.run(
            [sys.executable, "-c", _RECOVERY_CHILD, mode, str(data),
             str(cap), str(dim), str(n), str(versions)],
            input=stdin, capture_output=True, text=True, env=env,
            timeout=3600,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"bench[recovery] {mode} child failed rc={proc.returncode}:\n"
                f"{proc.stderr[-2000:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    root = Path(tempfile.mkdtemp(prefix="kakveda-recovery-"))
    try:
        store = root / "store"
        store.mkdir()
        seeded = child("seed", store)
        print(
            f"bench[recovery]: seeded {seeded['log_lines']:,} log lines "
            f"({seeded['log_bytes']:,}B) in {seeded['seed_s']}s",
            file=sys.stderr,
        )
        rng = np.random.default_rng(23)
        queries = [
            f"recovery bench failure signature {i} stack frame worker pool "
            f"shard {i % 17}"
            for i in rng.integers(0, n, size=n_queries).tolist()
        ]
        qjson = json.dumps(queries)

        # Uncompacted oracle: replay the full version-append history.
        pre = child("open", store, stdin=qjson)
        # Compact, then reopen: checkpoint + (empty) delta.
        child("compact", store)
        post = child("open", store, stdin=qjson)
        improve = pre["open_s"] / max(post["open_s"], 1e-9)
        parity = [
            a is None and b is None
            or (a is not None and b is not None
                and (a[0] == b[0] or b[1] >= a[1] - 1e-5))
            for a, b in zip(pre["top1"], post["top1"])
        ]
        recall = float(np.mean(parity))
        print(
            f"bench[recovery]: replay {pre['open_s']}s -> {post['open_s']}s "
            f"({improve:.1f}x) recall@1={recall:.4f}",
            file=sys.stderr,
        )
        if improve < improve_min:
            raise RuntimeError(
                f"bench[recovery]: compaction replay speedup {improve:.2f}x "
                f"< required {improve_min}x"
            )
        if recall < 1.0:
            raise RuntimeError(
                f"bench[recovery]: recall@1 parity {recall:.4f} < 1.0 vs "
                f"uncompacted oracle"
            )

        # Month-compressed aging scenario: resident-bytes bound.
        aging_dir = root / "aging"
        aging_dir.mkdir()
        aging = child("aging", aging_dir)
        print(
            f"bench[recovery]: aging scenario rows={aging['rows']} "
            f"aged={aging['aged']} bytes {aging['bytes_before']:,} -> "
            f"{aging['bytes_after']:,}",
            file=sys.stderr,
        )
        if aging["aged"] <= 0:
            raise RuntimeError(
                "bench[recovery]: aging scenario tombstoned no rows"
            )
        if aging["bytes_after"] >= aging["bytes_before"]:
            raise RuntimeError(
                f"bench[recovery]: resident bytes not bound after aging "
                f"({aging['bytes_before']} -> {aging['bytes_after']})"
            )

        # Crash-point sweep: every lifecycle kill offset must recover.
        from kakveda_tpu.index.crashsweep import run_sweep

        sweep = run_sweep(rows=8, aged=4)
        print(
            f"bench[recovery]: crash sweep kill_points="
            f"{sweep['kill_points']} corrupt={sweep['corrupt_recoveries']}",
            file=sys.stderr,
        )
        if sweep["corrupt_recoveries"] != 0:
            raise RuntimeError(
                f"bench[recovery]: crash sweep found "
                f"{sweep['corrupt_recoveries']} corrupt recoveries: "
                f"{sweep['failures'][:3]}"
            )

        return {
            "metric": f"recovery_replay_speedup_at_{n * versions}_lines",
            "value": round(improve, 2),
            "unit": "x",
            "vs_baseline": round(improve, 1),
            "replay_uncompacted_s": pre["open_s"],
            "replay_compacted_s": post["open_s"],
            "log_bytes": seeded["log_bytes"],
            "log_lines": seeded["log_lines"],
            "recall_at1": round(recall, 4),
            "recall_ok": bool(recall >= 1.0),
            "speedup_ok": bool(improve >= improve_min),
            "aging": {
                "rows": aging["rows"],
                "aged": aging["aged"],
                "bytes_before": aging["bytes_before"],
                "bytes_after": aging["bytes_after"],
                "bytes_bound_ok": True,
            },
            "crash_sweep": {
                "kill_points": sweep["kill_points"],
                "corrupt_recoveries": sweep["corrupt_recoveries"],
                "sites": sweep["sites"],
            },
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _metrics_plane() -> dict:
    """Compact snapshot of the process-global metrics registry, folded
    into every emitted bench JSON line: BENCH_*.json then carries the
    acceptance/gate/prefix-hit trajectories the metrics the run generated
    — not just the headline walls. Zero-valued series are dropped."""
    try:
        from kakveda_tpu.core.metrics import get_registry

        return get_registry().snapshot(compact=True)
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        return {}


def _trace_plane() -> dict:
    """Counters of the process-global causal tracer (core/trace.py),
    folded into every bench JSON line next to metrics_plane: spans
    started/ended/recorded/dropped plus the orphan count (started minus
    ended — a nonzero value means some span never terminated, the trace
    analogue of a lost warn)."""
    try:
        from kakveda_tpu.core.trace import get_tracer

        return get_tracer().plane()
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        return {}


def _lint_findings() -> int:
    """Invariant-lint finding count over this tree (the AST rules of
    scripts/lint_invariants.py, docs/static-analysis.md), folded into the
    bench JSON line so every BENCH_r{N}.json records whether the design
    contracts held at measurement time. 0 = clean; -1 = the linter itself
    failed (never sink a bench line over telemetry)."""
    try:
        from pathlib import Path

        from kakveda_tpu.analysis.framework import run_lint

        return len(run_lint(Path(__file__).resolve().parent).findings)
    except Exception:  # noqa: BLE001 — lint telemetry must never sink a bench line
        return -1


_CONCURRENCY_RULES = ("lockset-race", "lock-order", "event-loop-blocking",
                      "unjoined-thread")


def _concurrency_findings() -> int:
    """Finding count of the static concurrency pass alone (lockset races,
    lock-order cycles, event-loop blockers, unjoined threads) — split out
    from lint_findings so a regression in thread discipline is visible as
    its own number. 0 = clean; -1 = linter failure."""
    try:
        from pathlib import Path

        from kakveda_tpu.analysis.framework import run_lint

        res = run_lint(Path(__file__).resolve().parent,
                       rule_ids=_CONCURRENCY_RULES)
        return len(res.findings)
    except Exception:  # noqa: BLE001 — lint telemetry must never sink a bench line
        return -1


_DEVICE_RULES = ("constant-capture", "donation-after-use",
                 "dynamic-slice-by-trace", "host-sync", "retrace-hazard")


def _device_findings() -> int:
    """Finding count of the static device-plane pass alone (retrace
    hazards, donation-after-use, constant capture, traced-size slices,
    host syncs) — split out from lint_findings so a regression in
    device-plane hygiene is visible as its own number. 0 = clean;
    -1 = linter failure."""
    try:
        from pathlib import Path

        from kakveda_tpu.analysis.framework import run_lint

        res = run_lint(Path(__file__).resolve().parent,
                       rule_ids=_DEVICE_RULES)
        return len(res.findings)
    except Exception:  # noqa: BLE001 — lint telemetry must never sink a bench line
        return -1


def _ledger_plane() -> dict:
    """Compile-and-transfer ledger evidence for the bench line, when armed
    (KAKVEDA_LEDGER=1): total XLA backend compiles attributed so far,
    compiles seen after the bench marked itself warm (the runtime twin of
    the static retrace-hazard rule — nonzero means something retraced on
    the measured path), and host<->device bytes by direction. Empty dict
    when the ledger is not installed."""
    try:
        from kakveda_tpu.core import ledger

        if not ledger.installed():
            return {}
        rep = ledger.ledger_report()
        return {
            "compile_total": rep["compile_total"],
            "post_warmup_compiles": rep["post_warmup_compiles"],
            "transfer_bytes": rep["transfer_bytes"],
        }
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        return {}


def _ledger_reset() -> bool:
    """Arm a per-metric ledger window: reset the tables (the warm flag
    included) and report whether the ledger is live. Each self-certifying
    bench calls this up front so its assertions see only its own window."""
    try:
        from kakveda_tpu.core import ledger

        if not ledger.installed():
            return False
        ledger.reset()
        return True
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        return False


def _ledger_mark_warm() -> None:
    try:
        from kakveda_tpu.core import ledger

        if ledger.installed():
            ledger.mark_warm()
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        pass


def _ledger_certify(metric: str, max_per_fn: "int | None" = None) -> dict:
    """Close a per-metric ledger window: return the plane for the bench
    row and RAISE (self-certifying, like the mine purity floor) when the
    window saw post-warmup compiles, or — with ``max_per_fn`` — when any
    single entry point compiled more than the O(log N) pow2-bucket
    envelope allows. No-op ({}) when the ledger is not installed."""
    try:
        from kakveda_tpu.core import ledger

        if not ledger.installed():
            return {}
        rep = ledger.ledger_report()
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        return {}
    if rep["warm"] and rep["post_warmup_compiles"]:
        raise AssertionError(
            f"{metric}: {rep['post_warmup_compiles']} post-warmup XLA "
            f"compile(s) on the measured path — something retraced: "
            f"{rep['post_warmup']}"
        )
    if max_per_fn is not None and rep["compiles"]:
        worst = max(rep["compiles"], key=rep["compiles"].get)
        if rep["compiles"][worst] > max_per_fn:
            raise AssertionError(
                f"{metric}: entry {worst!r} compiled {rep['compiles'][worst]} "
                f"times, past the O(log N) envelope of {max_per_fn} — "
                f"shapes are not bucketing: {rep['compiles']}"
            )
    return {
        "compile_total": rep["compile_total"],
        "compiles": rep["compiles"],
        "post_warmup_compiles": rep["post_warmup_compiles"],
        "transfer_bytes": rep["transfer_bytes"],
    }


def _sanitizer_plane() -> dict:
    """Runtime-sanitizer evidence for the bench line, when armed
    (KAKVEDA_SANITIZE=1): loop stalls seen, distinct lock-order edges
    observed, and any cycles among them. Empty dict when disarmed."""
    try:
        from kakveda_tpu.core import sanitize

        rep = sanitize.sanitizer_report()
        if not rep["enabled"] and not rep["edges"] and not rep["stalls"]:
            return {}
        return {
            "sanitizer_stalls": len(rep["stalls"]),
            "lock_order_edges": len(rep["edges"]),
            "lock_order_cycles": len(rep["cycles"]),
        }
    except Exception:  # noqa: BLE001 — telemetry must never sink a bench line
        return {}


# Metric name -> function, in sweep order.
def _metric_fns() -> dict:
    return {
        "warn": _bench_warn,
        "pallas": _bench_pallas,
        "ingest": _bench_ingest,
        "decode": _bench_decode,
        "spec": _bench_spec,
        "continuous": _bench_continuous,
        "serve": _bench_serve,
        "overload": _bench_overload,
        "mixed": _bench_mixed,
        "mixed-decode": _bench_mixed_decode,
        "mine": _bench_mine,
        "tiered": _bench_tiered,
        "recovery": _bench_recovery,
        "ownership": _bench_ownership,
        "storm": _bench_storm,
        "tenants": _bench_tenants,
        "elastic": _bench_elastic,
    }


# Host-plane drills: they time Python, C++ and subprocess orchestration and
# certify a contract by raising; no device number comes out of them, so
# they may be run one at a time on a machine without a chip.
_HOST_DRILLS = frozenset({
    "overload", "tiered", "recovery", "ownership", "storm", "tenants", "elastic",
})


def _planes() -> dict:
    out = {
        "metrics_plane": _metrics_plane(),
        "trace_plane": _trace_plane(),
        "lint_findings": _lint_findings(),
        "concurrency_findings": _concurrency_findings(),
        "device_findings": _device_findings(),
    }
    out.update(_sanitizer_plane())
    out.update(_ledger_plane())
    return out


def main() -> int:
    from kakveda_tpu.core import ledger
    from kakveda_tpu.ops.device import device_report, setup_compile_cache

    setup_compile_cache()
    # Arm the compile-and-transfer ledger (no-op unless KAKVEDA_LEDGER=1)
    # BEFORE any kakveda model/ops module imports: jits created after
    # install self-label with their function names, so compile counts
    # attribute to real entry points instead of "unattributed".
    ledger.maybe_install()

    fns = _metric_fns()
    which = os.environ.get("KAKVEDA_BENCH_METRIC", "all")
    if which != "all" and which not in fns:
        print(f"bench: unknown KAKVEDA_BENCH_METRIC={which!r} "
              f"(all|{'|'.join(fns)})", file=sys.stderr)
        return 2

    # One look at the device, before anything is measured. A backend that
    # cannot initialise raises here. Without a TPU only a single host-plane
    # drill may run; nothing else falls back to the CPU, because a CPU
    # timing under a device metric's name is worse than no number.
    device = device_report()
    backend = device["platform"]
    if backend != "tpu" and which not in _HOST_DRILLS:
        print(
            f"bench: no TPU (JAX reports {device}); the chip metrics do not "
            f"run on {backend!r}. Host-plane drills run one at a time with "
            f"KAKVEDA_BENCH_METRIC={'|'.join(sorted(_HOST_DRILLS))}.",
            file=sys.stderr,
        )
        return 2

    if which != "all":
        out = fns[which](backend)
        out["device"] = device
        out.update(_planes())
        print(json.dumps(out))
        return 0

    # Default: every metric in one run, one JSON line. A metric that
    # raises does not stop the others, but the sweep then exits non-zero.
    results, failed = [], []
    for name, fn in fns.items():
        t_metric = time.perf_counter()
        try:
            row = fn(backend)
        except Exception as e:  # noqa: BLE001 — run the rest, then fail the sweep
            import traceback

            traceback.print_exc()
            print(f"bench: {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
            failed.append(name)
            continue
        row["device"] = device
        results.append(row)
        print(f"bench: {name} done in {time.perf_counter() - t_metric:.1f}s",
              file=sys.stderr)
    if results:
        headline = results[0]
        headline["extra_metrics"] = results[1:]
        headline["failed_metrics"] = failed
        headline.update(_planes())
        print(json.dumps(headline))
    if failed:
        print(f"bench: {len(failed)} metric(s) failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
