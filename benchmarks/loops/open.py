"""The open loop: each request is sent at its due time whether or not earlier
ones have returned (independent callers), and timed from when it was DUE.
Copied in idea from the repo's ``traffic/replay.py``; lateness is reported.

``prepare`` is set-up's part: the schedule (``arrivals/<kind>.py``) and every
request's body (the endpoint's ``prepare``), so that the window's own process
only sends and reads.
"""

from __future__ import annotations

import asyncio
import time

from harness import manifest


def prepare(st, rate: float, seconds: float, first: int) -> None:
    st.sched = manifest.load_module("arrivals", st.spec.get("arrivals")).schedule(st.spec, rate, seconds)
    st.kind.prepare(st, len(st.sched), first)


async def run(st, target, t_start: float, concurrency_cap: int = 4096) -> list:
    """Each call's record; ``late_s`` is how long after its due time the send
    really began."""
    tasks = []
    sem = asyncio.Semaphore(concurrency_cap)

    async def one(j, due_abs):
        async with sem:
            began = time.perf_counter()
            rec = await st.kind.call(st, target, j, due_abs)
            rec["late_s"] = began - due_abs
            return rec

    for j, due in enumerate(st.sched):
        due_abs = t_start + due
        delay = due_abs - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(j, due_abs)))
    return list(await asyncio.gather(*tasks))
