"""The one general traffic generator: a traffic file lists streams, and each
stream names an endpoint (``endpoints/<name>.py``: what one request is, how it
is warmed, failed and checked), a loop (``loops/<name>.py``: when requests
are sent) and, for an open loop, its arrivals (``arrivals/<name>.py``). A mix
of several streams is a file with several entries; nothing here knows a cell's
or an endpoint's name.
"""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

from . import manifest, textgen


class Target:
    def __init__(self, api: str, dash: str):
        self.api, self.dash = api, dash
        self.session: aiohttp.ClientSession | None = None

    async def open(self):
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0), cookie_jar=aiohttp.CookieJar(unsafe=True),
            timeout=aiohttp.ClientTimeout(total=180))

    async def close(self):
        if self.session is not None:
            await self.session.close()

    async def login(self):
        async with self.session.post(self.dash + "/login", data={
                "email": "admin@local", "password": "admin123", "next": "/"}) as r:
            await r.read()
            if r.status != 200 or not len(self.session.cookie_jar):
                raise RuntimeError(f"dashboard login failed ({r.status})")


async def post_json(target: Target, path: str, body) -> tuple:
    data = body if isinstance(body, bytes) else json.dumps(body).encode()
    try:
        async with target.session.post(target.api + path, data=data,
                                       headers={"Content-Type": "application/json"}) as r:
            raw = await r.read()
            done = time.perf_counter()
            if r.status != 200:
                return r.status, {"error": raw[:300].decode(errors="replace")}, done
            return 200, json.loads(raw), done
    except (aiohttp.ClientError, asyncio.TimeoutError) as e:
        return 0, {"error": f"{type(e).__name__}: {e}"}, time.perf_counter()


class Stream:
    """One entry of a traffic file's ``streams``."""

    def __init__(self, spec: dict, seed: int, corpus: textgen.Corpus, stored: int, seconds: float):
        self.spec, self.seed, self.corpus, self.stored, self.seconds = spec, seed, corpus, stored, seconds
        self.endpoint = spec["endpoint"]
        self.kind = manifest.load_module("endpoints", spec["endpoint"])
        self.loop = manifest.load_module("loops", spec["loop"])
        self.records: list = []

    def lengths(self, n: int, salt: str) -> list:
        """``n`` prompt lengths over the stream's ``prompt_chars``, in the
        order the traffic file's ``gaps_seed`` draws."""
        key = (n, salt)
        if getattr(self, "_len_key", None) != key:
            lo, hi = self.spec["prompt_chars"]
            self._len_cache, self._len_key = textgen.lengths_for(self.spec.get("gaps_seed", 0), n, lo, hi, salt), key
        return self._len_cache

    def prepare(self, rate: float | None = None, seconds: float | None = None, first: int = 0) -> None:
        """Set-up's part of the window. ``seconds`` and ``first`` (the first
        request's number) are the pre-roll's: the same stream, shorter, with
        requests of its own; ``rate`` is the sweep's."""
        self.loop.prepare(self, rate or self.spec.get("rate_rps"), seconds or self.seconds, first)

    async def run(self, target: Target, t_start: float) -> list:
        self.records = await self.loop.run(self, target, t_start)
        return self.records
