"""Dashboard ``POST /playground/stream`` (server-sent events): what
playground users and the LLM judge send.

A stream of this endpoint takes from its traffic file: ``prompt_chars``
(bytes; under the byte tokenizer a prompt's tokens are its bytes + 1),
``check_sample`` and a ``warmup`` recipe (``prompt_bytes``: one length inside
each admit bucket the traffic uses, ``concurrent``: as many at once as there
are slots).
"""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

from harness import manifest, textgen
from harness.stats import percentile

NEEDS_LOGIN = True
SEGMENTS = 5  # parts of the window in the candidate reading "median of the parts' p95s" (PERF.md section 2: measured, not taken)


async def stream_call(target, prompt: str) -> dict:
    """One request read to its end: when the first delta and the last
    arrived, and whether the stream ended with ``done``."""
    rec = {"first": None, "first_chars": 0, "last": None, "deltas": 0, "done": False, "error": None}
    try:
        async with target.session.post(target.dash + "/playground/stream",
                                       data={"prompt": prompt, "target": "model"}) as r:
            if r.status != 200:
                rec["error"] = f"status {r.status}"
                return rec
            event = ""
            async for raw in r.content:
                line = raw.decode(errors="replace").rstrip("\n")
                if line.startswith("event:"):
                    event = line[6:].strip()
                elif line.startswith("data: "):
                    now = time.perf_counter()
                    payload = json.loads(line[6:])
                    if event == "error" or "error" in payload:
                        rec["error"] = str(payload)[:300]
                    elif "delta" in payload:
                        if rec["first"] is None:
                            rec["first"], rec["first_chars"] = now, len(payload["delta"])
                        rec["last"] = now
                        rec["deltas"] += 1
                    elif payload.get("done"):
                        rec["done"] = True
    except (aiohttp.ClientError, asyncio.TimeoutError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


def prepare(st, n: int, first: int) -> None:
    lengths = st.lengths(n, "chat")
    st.bodies = [textgen.chat_prompt(st.corpus, st.seed, first + j, lengths[(first + j) % n]) for j in range(n)]


async def call(st, target, j: int, due: float) -> dict:
    rec = await stream_call(target, st.bodies[j])
    rec.update(j=j, due=due, prompt=st.bodies[j])
    return rec


def failed(rec: dict) -> bool:
    return bool(rec["error"]) or not rec["done"]


async def warm_up(st, target, passes: int) -> None:
    wu = st.spec.get("warmup", {})
    for nbytes in wu.get("prompt_bytes", []):
        prompts = [textgen.chat_prompt(st.corpus, st.seed, 9_000_000 + passes * 1000 + nbytes * 10 + k, nbytes)
                   for k in range(wu.get("concurrent", 1))]
        res = await asyncio.gather(*[stream_call(target, p) for p in prompts])
        bad = [r for r in res if failed(r)]
        if bad:
            raise RuntimeError(f"warm-up /playground/stream -> {bad[0]}")


async def check(st, run) -> dict:
    return await run.loop.run_in_executor(None, compare, st, run)


def compare(st, run) -> dict:
    """Once the window has closed and the device's memory peak is read: a
    seeded sample of the finished requests, the longest among them, each
    prompt with its served tokens through the plain reference on the chip.

    logit_gap_mean  mean gap by which a served (greedy) token's reference logit lies below the
                    reference's best at its position, over all sampled tokens (compared)
    logit_gap       the widest such gap (reported, not compared: it swings threefold from seed to
                    seed and comes within 2.4 times of its control's, PERF.md section 2)
    unserved        requests the engine never answered, or answered with an error

    Reported beside them, from the same records: every candidate reading of the first-token and
    per-token tails (the window's own percentile, the median of its SEGMENTS parts' percentiles,
    the window's median), so that a run under ``--freeze`` shows which of them one freeze moves.
    """
    ctx = run.ctx
    recs = run.srv.ctl("/chat/records")["records"]
    by_prompt = {}
    vocab_live = int(run.sizes["vocab_live"])
    for r in recs:
        text = bytes(i - 3 for i in r["ids"] if i >= 3).decode(errors="replace")
        by_prompt[text] = r
    ctx["chat_tokens"] = {p: len(r["out"]) for p, r in by_prompt.items() if r["out"] is not None}
    ctx["chat_records"] = by_prompt
    sent = [r["prompt"] for r in st.records]
    unserved = sum(1 for p in sent if p not in by_prompt or by_prompt[p]["out"] is None)
    done = [by_prompt[p] for p in sent if p in by_prompt and by_prompt[p]["out"]]
    out = {"unserved": unserved, "requests": 0}
    lat, tpot = manifest.load_module("readers", "latency_pct"), manifest.load_module("readers", "chat_tpot")
    first = {"endpoint": st.endpoint, "field": "first"}
    out.update(ttft_window_p95_ms=lat.read(ctx, {**first, "q": 95}),
               ttft_segments_p95_ms=lat.read(ctx, {**first, "q": 95, "segments": SEGMENTS}),
               ttft_window_p50_ms=lat.read(ctx, {**first, "q": 50}),
               tpot_window_p95_ms=tpot.read(ctx, {"q": 95}),
               tpot_segments_p95_ms=tpot.read(ctx, {"q": 95, "segments": SEGMENTS}))
    if not done:
        return out
    k = int(st.spec.get("check_sample", 8))
    order = sorted(range(len(done)), key=lambda i: -(len(done[i]["ids"]) + len(done[i]["out"])))
    picked = {order[0]}
    rng = textgen.rng_for(st.seed, "sample", 3)
    picked.update(rng.sample(range(len(done)), min(len(done), k - 1)))
    sample = [{"ids": done[i]["ids"], "out": done[i]["out"]} for i in sorted(picked)]
    res = run.srv.ctl("/chat/reference", {"sample": sample, "vocab_live": vocab_live,
                                          "control": bool(run.args.control)}, 900.0)
    run.say(f"reference (chat): {len(sample)} requests, {len(res['gaps'])} served tokens, {res['reference_s']:.2f}s")
    gaps = res["gaps"]
    out.update(requests=len(sample), served_tokens=len(gaps), logit_gap_mean=sum(gaps) / len(gaps), logit_gap=max(gaps))
    if "control_gaps" in res:
        ctl = res["control_gaps"]
        out.update(control_logit_gap_mean=sum(ctl) / len(ctl), control_logit_gap=max(ctl))
    return out


def sweep_row(st, t_end: float) -> dict:
    recs = st.records
    okr = [r for r in recs if r["first"] is not None and not r["error"]]
    return dict(n=len(recs), failed=sum(1 for r in recs if failed(r)),
                ttft_p50_ms=percentile([(r["first"] - r["due"]) * 1e3 for r in okr], 50),
                ttft_p95_ms=percentile([(r["first"] - r["due"]) * 1e3 for r in okr], 95),
                wall_p95_ms=percentile([(r["last"] - r["due"]) * 1e3 for r in okr], 95),
                late_p95_ms=percentile([r["late_s"] * 1e3 for r in recs], 95),
                drain_s=max([r["last"] for r in okr] or [t_end]) - t_end)
