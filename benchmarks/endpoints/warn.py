"""``POST /warn``: the pre-flight question in front of every LLM call.

A stream of this endpoint takes from its traffic file: ``apps`` and
``hot_share`` (the repo's hot-key skew), ``kinds`` (shares of near-duplicates
of stored failures, citation prompts no one stored, unrelated prompts),
``prompt_chars``, ``check_sample`` and a ``warmup`` recipe (``bursts``: the
concurrent sizes that fill the batcher's buckets, ``repeat``, ``preroll_s``).
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np

from harness import reference_gfkb as ref
from harness import textgen
from harness.stats import percentile
from harness.streams import post_json

VERDICT_MARGIN = 0.001  # twice conf_gap's limit: nearer the threshold than that, either verdict is sound


def body(st, j: int, n_total: int) -> dict:
    sp = st.spec
    rng = textgen.rng_for(st.seed, "warn", 1_000_000 + j)
    x, kind = rng.random(), None
    for k, share in sp["kinds"].items():  # {"near": .5, "intent": .25, "other": .25}
        kind = k
        if x < share:
            break
        x -= share
    length = st.lengths(n_total, "warn")[j % n_total]
    app = textgen.pick_app(rng, sp["apps"], sp["hot_share"])
    return textgen.warn_request(st.corpus, st.seed, j, kind, max(1, st.stored), length, app)


def prepare(st, n: int, first: int) -> None:
    st.bodies = [body(st, first + j, n) for j in range(n)]
    st.wire = [json.dumps(b).encode() for b in st.bodies]


GIVE_UP_S = 60.0  # after its due time a caller stops asking: the request has then failed


async def call(st, target, j: int, due: float) -> dict:
    """One caller's question. A 429 is the server's "not now": it names a
    ``retry_after`` (header, and body "for clients that only read bodies",
    ``service/app.py``), and the caller waits that long and asks again. The
    request stays timed from when it was first due, so a shed request is a
    late one, by all it waited; ``tries`` counts its sends."""
    tries = 0
    while True:
        tries += 1
        status, res, done = await post_json(target, "/warn", st.wire[j])
        if status != 429 or done - due > GIVE_UP_S:
            break
        try:
            wait = float(json.loads(res["error"])["retry_after"])
        except (ValueError, KeyError, TypeError):
            wait = 1.0
        await asyncio.sleep(min(max(wait, 0.05), 5.0))
    return {"j": j, "due": due, "done": done, "status": status, "tries": tries, "body": st.bodies[j], "res": res}


def failed(rec: dict) -> bool:
    """A request that never got its 200: a 429 still standing ``GIVE_UP_S``
    after the due time, or any other status."""
    return rec["status"] != 200


async def warm_up(st, target, passes: int) -> None:
    wu = st.spec.get("warmup", {})
    for size in wu.get("bursts", []):
        for _ in range(wu.get("repeat", 1)):
            res = await asyncio.gather(*[
                post_json(target, "/warn", body(st, 9_000_000 + size * 100 + k, 64)) for k in range(size)])
            bad = [r for r in res if r[0] != 200]
            if bad:
                raise RuntimeError(f"warm-up /warn -> {bad[0][:2]}")


async def check(st, run) -> dict:
    """After the window: a seeded sample of its answers against the plain
    reference (a worker thread: numpy and worker processes, no event loop)."""
    sizes = run.sizes
    t0 = time.perf_counter()
    out = await run.loop.run_in_executor(None, lambda: compare(
        st.records, st.seed, st.stored, int(sizes["dim"]), float(sizes["similarity_threshold"]),
        int(st.spec.get("check_sample", 256)), control=bool(run.args.control), row_bytes=int(sizes["row_bytes"])))
    run.say(f"reference (/warn): {st.stored} stored rows, {out['answers']} answers, {time.perf_counter() - t0:.2f}s")
    # Beside the numbers compared, the window as its callers saw it (reported
    # in every run, traced or not; nothing here has a limit).
    lat = sorted((r["done"] - r["due"]) * 1e3 for r in st.records if r["status"] == 200)
    out.update(shed_then_answered=sum(1 for r in st.records if r["tries"] > 1 and r["status"] == 200),
               over_100ms=sum(1 for x in lat if x > 100.0), max_ms=lat[-1] if lat else None,
               **{f"p{q}_ms": percentile(lat, q) for q in (50, 90, 95, 99)})
    return out


def compare(records: list, seed: int, stored: int, dim: int, threshold: float, sample_n: int,
            control: bool = False, stored_rows: np.ndarray | None = None, row_bytes: int = 2) -> dict:
    """A seeded sample of the window's /warn answers against the exact best
    match over ALL stored failures, in the arithmetic the configuration
    states: rows and queries rounded to bf16, products and sums in float32.
    (The served answer carries the best match alone, ``references[0]``, of the
    k = 5 the scan keeps: the best is what the guarantee is about.)

    conf_gap   widest |served confidence - reference best score|
    top1_gap   widest shortfall of the served match's reference score below the best
    verdict    answers whose action / reference-or-none differs from the reference's
               (those whose reference score is within VERDICT_MARGIN of the threshold aside)
    not_hot    answers flagged degraded or not served by the device tier
    """
    ok = [r for r in records if r["status"] == 200]
    rng = textgen.rng_for(seed, "sample", 1)
    picked = [ok[i] for i in sorted(rng.sample(range(len(ok)), min(sample_n, len(ok))))]
    out = {"answers": len(picked)}
    if not picked:
        return out
    held = ref.stated(stored_rows, row_bytes) if stored_rows is not None else ref.embed_stored(seed, stored, dim, row_bytes)
    q = ref.stated(ref.embed(
        [(r["body"]["prompt"], r["body"]["tools"], sorted(r["body"]["env"])) for r in picked], dim), row_bytes)
    sc = ref.scores(q, held)
    best = sc.max(axis=1)
    conf = np.array([r["res"]["confidence"] for r in picked], np.float32)
    out["conf_gap"] = float(np.abs(conf - best).max())
    top1 = [0.0]
    verdict = 0
    for k, r in enumerate(picked):
        refs = r["res"].get("references") or []
        if refs:
            slot = int(refs[0]["failure_id"].split("-")[1]) - 1
            top1.append(float(best[k] - sc[k, slot]) if 0 <= slot < held.shape[0] else 9.0)
        want_match = best[k] >= threshold
        if abs(best[k] - threshold) > VERDICT_MARGIN and (bool(refs) != bool(want_match) or r["res"]["action"] != "warn"):
            verdict += 1
    out["top1_gap"] = max(top1)
    out["verdict"] = verdict
    out["not_hot"] = sum(1 for r in ok if r["res"].get("degraded") is not False or r["res"].get("tier") != "hot")
    if control:  # calibration only: the rows held in int8 (one scale a row), from the float32 rows
        rows = stored_rows if stored_rows is not None else ref.embed_stored(seed, stored, dim, 4)
        sc8 = ref.scores(q, ref.quantize_rows_int8(rows))
        out["control_conf_gap"] = float(np.abs(sc8.max(axis=1) - best).max())
        out["control_top1_gap"] = float((best - sc[np.arange(len(picked)), sc8.argmax(axis=1)]).max())
    return out


def answers_from_scores(served: np.ndarray, threshold: float) -> list:
    """The /warn answers a scan that scored ``served`` [q, stored] would give:
    how the control, the reference computed in a lower precision, is put in
    the program's place."""
    out = []
    for row in served:
        slot = int(row.argmax())
        refs = [{"failure_id": f"F-{slot + 1:04d}"}] if row[slot] >= threshold else []
        out.append({"action": "warn", "confidence": float(row[slot]), "references": refs,
                    "degraded": False, "tier": "hot"})
    return out


def sweep_row(st, t_end: float) -> dict:
    recs = st.records
    lat = [(r["done"] - r["due"]) * 1e3 for r in recs if r["status"] == 200]
    return dict(n=len(recs), failed=sum(1 for r in recs if failed(r)), shed=sum(r["tries"] - 1 for r in recs),
                p50_ms=percentile(lat, 50), p95_ms=percentile(lat, 95), p99_ms=percentile(lat, 99),
                late_p95_ms=percentile([r["late_s"] * 1e3 for r in recs], 95),
                late_max_ms=max(r["late_s"] for r in recs) * 1e3, max_ms=max(lat or [0.0]),
                drain_s=max(r["done"] for r in recs) - t_end)
