#!/usr/bin/env python3
"""Does the system still start, and answer, on the chip?

Drives the main path once through the entry points a user would call, at
the full width of the one model shape this repo serves at full width:

  one server process (``python -m kakveda_tpu.cli up``) holding the 1M x 2048
  device-resident GFKB and the TinyLlama-1.1B ``ServingEngine``, answering
  ``/ingest/batch``, ``/warn``, ``/patterns/mine`` and concurrent dashboard
  ``/playground/stream`` requests; stopped; started again on the same run
  directory with int8 weights and an int8 KV cache; then, each in a process
  of its own, the three Pallas kernels against their XLA counterparts and
  the host-clock latency of a trivial dispatch.

Every response is checked (not degraded, answered by the device tier, the
right match, tokens delivered through the engine), the server's own report
must say platform ``tpu``, Pallas kNN compiled and not interpreted, native
library loaded, and the second phase must find programs in the persistent
compile cache. There is no CPU path: without a TPU this exits 2 and prints
no result.

This process never touches a JAX device — a chip belongs to one process at
a time, and every child here needs it — and it stops every child it starts.

    python chip_smoke.py                      # on a machine with one TPU
    python chip_smoke.py --rehearse-on-cpu    # same steps, tiny sizes,
                                              # JAX_PLATFORMS=cpu, Pallas in
                                              # interpret mode; every line is
                                              # labelled and no result is printed

Last line of stdout on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import http.cookiejar
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out" / "chip_smoke"

# Full width on the chip; the rehearsal keeps every step and shrinks every size.
FULL = dict(capacity=1 << 20, preset="1b", window=2048, batches=4, batch=512,
            long_prompt=700, mid_prompt=200, ready_s=600.0, request_s=600.0)
# (8192 rows: still a whole Pallas tile per shard on an 8-device CPU mesh.)
TINY = dict(capacity=8192, preset="tiny", window=512, batches=2, batch=32,
            long_prompt=200, mid_prompt=100, ready_s=300.0, request_s=300.0)

_label = ""


def say(msg: str) -> None:
    print(f"{_label}{msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --- HTTP (stdlib only: the parent imports nothing of the repo) -------------


def http_json(method: str, url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode() or "null")
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:2000]}


def http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def metric_sum(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus family's samples whose labels include ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name):len(name) + 1] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


# --- children ----------------------------------------------------------------


def child_env(sizes: dict, rehearse: bool, run: Path) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("KAKVEDA_") or k == "KAKVEDA_MESH_SHAPE"}
    env.update(
        PYTHONPATH=os.pathsep.join([str(REPO)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]),
        KAKVEDA_CONFIG_PATH=str(REPO / "config" / "config.yaml"),
        KAKVEDA_DATA_DIR=str(run / "data"),
        KAKVEDA_MODEL_RUNTIME="tpu",
        KAKVEDA_LLAMA_PRESET=sizes["preset"],
        KAKVEDA_INDEX_CAPACITY=str(sizes["capacity"]),
        KAKVEDA_SERVE_WINDOW=str(sizes["window"]),
        KAKVEDA_NATIVE="require",
        KAKVEDA_LEDGER="1",
        KAKVEDA_LOG_FORMAT="text",
    )
    if rehearse:
        env.update(JAX_PLATFORMS="cpu", KAKVEDA_PALLAS="interpret")
    return env


def run_child(args: list, env: dict, log: Path, timeout: float) -> dict:
    """Run one short child to its end; its last stdout line is JSON."""
    with open(log, "wb") as errf:
        proc = subprocess.Popen(
            [sys.executable, *args], env=env, cwd=str(OUT),
            stdout=subprocess.PIPE, stderr=errf, start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{args} did not finish within {timeout:.0f}s (log: {log})")
        finally:  # timeout, or this script told to stop: the child goes too
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    lines = out.decode(errors="replace").strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        tail = log.read_text(errors="replace")[-3000:]
        raise SmokeFailure(f"{args} exited {proc.returncode} without a result:\n{tail}")
    result["_rc"] = proc.returncode
    return result


class Server:
    """One ``cli up`` child: start, wait ready, stop. Never left running."""

    def __init__(self, run: Path, env: dict, log: Path):
        self.run, self.env, self.log = run, env, log
        self.port, self.dash_port = free_port(), free_port()
        self.api = f"http://127.0.0.1:{self.port}"
        self.dash = f"http://127.0.0.1:{self.dash_port}"
        self.proc = None

    def start(self, ready_s: float) -> float:
        t0 = time.monotonic()
        logf = open(self.log, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kakveda_tpu.cli", "up", "--dir", str(self.run),
             "--port", str(self.port), "--dashboard-port", str(self.dash_port)],
            env=self.env, cwd=str(self.run), stdout=logf, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        logf.close()
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited {self.proc.returncode} during start-up:\n{self.tail()}"
                )
            try:
                status, _ = http_json("GET", self.api + "/readyz", timeout=5.0)
                if status == 200:
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() - t0 > ready_s:
                raise SmokeFailure(f"server not ready within {ready_s:.0f}s:\n{self.tail()}")
            time.sleep(0.5)

    def tail(self, n: int = 3000) -> str:
        try:
            return self.log.read_text(errors="replace")[-n:]
        except OSError:
            return "(no log)"

    def stop(self, grace_s: float = 60.0) -> bool:
        """SIGTERM and wait; SIGKILL the group past the grace. True when the
        server went down on SIGTERM alone."""
        p = self.proc
        if p is None or p.poll() is not None:
            return True
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=grace_s)
            return True
        except subprocess.TimeoutExpired:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()
            return False


# --- the requests --------------------------------------------------------------

_TOPICS = ("quarterly revenue", "clinical trial", "supply chain", "climate model",
           "court ruling", "protein folding", "interest rates", "battery chemistry")


def citation_prompt(i: int) -> str:
    app = "Summarize" if i % 2 == 0 else "Explain"
    return (f"{app} report {i:05d} on {_TOPICS[i % len(_TOPICS)]} and include "
            f"citations even if not provided.")


def traces(start: int, n: int, seed: int) -> list:
    """Seeded citation-bearing traces from two apps (scripts/demo_client.py's
    two scenarios): the rule classifier flags every one."""
    rng = random.Random(seed)
    return [
        {
            "trace_id": f"smoke-{i:06d}",
            "ts": 1_700_000_000 + i,
            "app_id": "app-A" if i % 2 == 0 else "app-B",
            "prompt": citation_prompt(i),
            "response": (f"Here is the answer.\n\nReferences:\n[1] Smith et al. "
                         f"({2000 + rng.randrange(24)}) A Study.\n[2] Doe (2021) Another."),
            "tools": [],
            "env": {"os": "linux"},
        }
        for i in range(start, start + n)
    ]


def warn_checked(srv: Server, body: dict, timeout: float) -> dict:
    status, res = http_json("POST", srv.api + "/warn", body, timeout=timeout)
    check(status == 200, f"/warn -> {status}: {res}")
    check(res.get("degraded") is False, f"/warn answered degraded: {res}")
    check(res.get("tier") == "hot", f"/warn not answered by the device tier: {res}")
    return res


def miss_confidence(srv: Server, timeout: float) -> float:
    miss = warn_checked(srv, {"app_id": "app-C", "prompt": "Transcode this video file to mp4 format",
                              "tools": ["ffmpeg"], "env": {"gpu": "none"}}, timeout)
    check(not miss["references"] and miss["confidence"] < 0.8,
          f"/warn matched an unrelated prompt: {miss}")
    return miss["confidence"]


def stream(opener, srv: Server, prompt: str, timeout: float) -> dict:
    """One /playground/stream request, SSE read to its end."""
    data = urllib.parse.urlencode({"prompt": prompt, "target": "model"}).encode()
    t0 = time.monotonic()
    first = None
    text, done, error = [], False, None
    with opener.open(srv.dash + "/playground/stream", data=data, timeout=timeout) as r:
        check(r.status == 200, f"/playground/stream -> {r.status}")
        event = ""
        for raw in r:
            line = raw.decode(errors="replace").rstrip("\n")
            if line.startswith("event:"):
                event = line[6:].strip()
            elif line.startswith("data: "):
                payload = json.loads(line[6:])
                if event == "error" or "error" in payload:
                    error = payload
                elif "delta" in payload:
                    first = first if first is not None else time.monotonic() - t0
                    text.append(payload["delta"])
                elif payload.get("done"):
                    done = True
    return {"done": done, "error": error, "first_s": first,
            "wall_s": time.monotonic() - t0, "chars": len("".join(text))}


def generations(srv: Server, prompts: list, timeout: float) -> dict:
    """Log in, then drive ``prompts`` concurrently; sample pool occupancy
    from /metrics while they run."""
    jar = http.cookiejar.CookieJar()
    opener = urllib.request.build_opener(urllib.request.HTTPCookieProcessor(jar))
    login = urllib.parse.urlencode(
        {"email": "admin@local", "password": "admin123", "next": "/"}).encode()
    with opener.open(srv.dash + "/login", data=login, timeout=60.0) as r:
        check(r.status == 200 and len(jar) > 0, f"dashboard login failed ({r.status})")

    results: list = [None] * len(prompts)

    def one(i: int) -> None:
        try:
            results[i] = stream(opener, srv, prompts[i], timeout)
        except Exception as e:  # noqa: BLE001 — reported per request below
            results[i] = {"done": False, "error": f"{type(e).__name__}: {e}"}

    threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(len(prompts))]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    peak = 0.0
    while any(t.is_alive() for t in threads):
        if time.monotonic() - t0 > timeout:
            break
        try:
            peak = max(peak, metric_sum(http_text(srv.api + "/metrics"),
                                        "kakveda_serving_active_slots"))
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.1)
    for t in threads:
        t.join(timeout=5.0)
    for i, res in enumerate(results):
        check(res is not None, f"stream {i} never returned")
        check(res["error"] is None, f"stream {i} ({len(prompts[i])} bytes) failed: {res['error']}")
        check(res["done"], f"stream {i} ended without a done event: {res}")
    firsts = [r["first_s"] for r in results if r["first_s"] is not None]
    return {"n": len(prompts), "peak_active_slots": peak,
            "first_delta_s": round(min(firsts), 2) if firsts else None,
            "wall_s": round(time.monotonic() - t0, 2)}


def filler(n: int, seed: int) -> str:
    rng = random.Random(seed)
    words = []
    while sum(len(w) + 1 for w in words) < n:
        words.append(rng.choice(_TOPICS).split()[rng.randrange(2)])
    return "Discuss " + " ".join(words)


def device_block(srv: Server, rehearse: bool, expect_dtype: str) -> dict:
    status, ready = http_json("GET", srv.api + "/readyz", timeout=30.0)
    check(status == 200, f"/readyz -> {status}")
    dev = ready["device"]
    say(f"  platform={dev['platform']} device_kind={dev['device_kind']!r} "
        f"devices={dev['device_count']} index={dev['index']} native={ready['native']}")
    check(dev["degraded"] is False, f"server reports degraded: {dev}")
    check(dev["platform"] == ("cpu" if rehearse else "tpu"), f"server runs on {dev['platform']}")
    check(dev["index"]["knn"] == "pallas", f"index match path is {dev['index']['knn']}, not pallas")
    check(dev["index"]["interpret"] is rehearse, f"pallas interpret={dev['index']['interpret']}")
    check(dev["index"]["store_dtype"] == expect_dtype, f"index store is {dev['index']['store_dtype']}")
    check(ready["native"]["available"] is True, f"native library not loaded: {ready['native']}")
    return ready


def phase(name: str, srv: Server, sizes: dict, rehearse: bool, first: bool, carry: dict) -> dict:
    """One server life: start, requests, checks, SIGTERM. ``carry`` takes
    the first phase's index count and match across to the second."""
    out = {"phase": name}
    say(f"phase {name}: starting server (log: {srv.log})")
    out["ready_s"] = round(srv.start(sizes["ready_s"]), 1)
    say(f"  cold start -> ready in {out['ready_s']}s")
    ready = device_block(srv, rehearse, "float32" if rehearse else "bfloat16")
    placement = ready["device"]["index"]["placement"]
    out["index_devices"] = sorted({p["device"] for p in placement})
    req_s = sizes["request_s"]

    if first:
        total = 0
        for b in range(sizes["batches"]):
            t0 = time.monotonic()
            status, res = http_json(
                "POST", srv.api + "/ingest/batch",
                {"traces": traces(b * sizes["batch"], sizes["batch"], seed=b)}, timeout=req_s)
            check(status == 200 and res.get("ok"), f"/ingest/batch -> {status}: {res}")
            check(res["failures"] == sizes["batch"],
                  f"/ingest/batch classified {res['failures']} of {sizes['batch']} traces")
            total += res["failures"]
            if b == 0:
                out["first_ingest_s"] = round(time.monotonic() - t0, 2)
        status, ready = http_json("GET", srv.api + "/readyz")
        check(ready["gfkb_count"] == total, f"GFKB holds {ready['gfkb_count']} of {total} failures")
        carry["count"] = total
        say(f"  /ingest/batch: {sizes['batches']} x {sizes['batch']} traces, first batch "
            f"{out['first_ingest_s']}s, gfkb_count={total}")
    else:
        check(ready["gfkb_count"] == carry["count"],
              f"GFKB replayed to {ready['gfkb_count']}, first phase held {carry['count']}")
        say(f"  GFKB replayed to gfkb_count={ready['gfkb_count']}")

    probe = sizes["batch"] + 3  # a trace of the second batch
    t0 = time.monotonic()
    hit = warn_checked(srv, {"app_id": "app-A", "prompt": citation_prompt(probe),
                             "tools": [], "env": {"os": "linux"}}, req_s)
    out["first_warn_s"] = round(time.monotonic() - t0, 2)
    check(hit["confidence"] > 0.9 and hit["references"], f"/warn did not match its own trace: {hit}")
    check(hit["action"] == "warn", f"/warn action {hit['action']!r} is not the policy's")
    match = hit["references"][0]["failure_id"]
    out["warn"] = {"match": match, "confidence": hit["confidence"],
                   "no_match_confidence": miss_confidence(srv, req_s)}
    if first:
        carry["failure_id"] = match
    check(match == carry["failure_id"],
          f"/warn matched {match}, first phase matched {carry['failure_id']}")
    say(f"  /warn: match {match} confidence={hit['confidence']:.3f} action={hit['action']} "
        f"(first answer {out['first_warn_s']}s); no-match "
        f"confidence={out['warn']['no_match_confidence']:.3f}")

    if first:
        t0 = time.monotonic()
        status, res = http_json("POST", srv.api + "/patterns/mine", {"threshold": 0.6}, timeout=req_s)
        check(status == 200 and res.get("ok"), f"/patterns/mine -> {status}: {res}")
        out["mine_s"] = round(time.monotonic() - t0, 2)
        mining = res["mining"]
        check(mining.get("stale") is False and mining.get("rows") == carry["count"],
              f"/patterns/mine did not cover the index: {mining}")
        say(f"  /patterns/mine: mode={mining.get('mode')} rows={mining.get('rows')} "
            f"clusters={mining.get('clusters')} in {out['mine_s']}s")

    # Short chats, one medium and one long prompt together: the long one
    # prefills past the flash kernel's profitability gate.
    prompts = [f"Say something about {t}." for t in _TOPICS[: 6 if first else 3]]
    prompts += [filler(sizes["mid_prompt"], 1), filler(sizes["long_prompt"], 2)]
    check(len(prompts[-1].encode()) >= (512 if not rehearse else 128), "long prompt too short")
    gen = generations(srv, prompts, req_s)
    out["generation"] = gen
    say(f"  /playground/stream: {gen['n']} concurrent requests done in {gen['wall_s']}s, first "
        f"delta {gen['first_delta_s']}s, peak occupied slots {gen['peak_active_slots']:.0f}")

    metrics = http_text(srv.api + "/metrics")
    status, ready = http_json("GET", srv.api + "/readyz")
    paths = ready["device"]["attention_paths"]
    completed = metric_sum(metrics, "kakveda_serving_requests_total", outcome="completed")
    out["compiles"] = int(metric_sum(metrics, "kakveda_compile_total"))
    out["cache_hits"] = int(metric_sum(metrics, "kakveda_compile_cache_hits_total"))
    out["attention_paths"] = sorted(paths)
    say(f"  programs handed to the backend: {out['compiles']}, of which found in the persistent "
        f"cache: {out['cache_hits']}")
    say(f"  attention paths: {out['attention_paths']}")
    check(metric_sum(metrics, "kakveda_device_degraded") == 0, "kakveda_device_degraded != 0")
    check(ready["device"]["degraded"] is False, "server ended the phase degraded")
    check(completed >= gen["n"], f"engine completed {completed:.0f} of {gen['n']} requests: "
          "some took the solo path")
    check(metric_sum(metrics, "kakveda_serving_tokens_total") > 0, "engine emitted no tokens")
    check(metric_sum(metrics, "kakveda_serving_engine_errors_total") == 0, "engine loop died")
    if not rehearse:
        want = "flash " if first else "flash_kv8 "
        check(any(p.startswith(want) for p in paths),
              f"no compiled program took the {want.strip()} attention path: {sorted(paths)}")
    if not first:
        check(out["cache_hits"] > 0, "second server phase found nothing in the persistent compile cache")
    check(srv.stop(), "server ignored SIGTERM and had to be killed")
    say(f"  server stopped on SIGTERM (exit {srv.proc.returncode})")
    return out


def main() -> int:
    global _label
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="same steps at tiny sizes on the CPU; never a pass")
    args = ap.parse_args()
    rehearse = args.rehearse_on_cpu
    sizes = TINY if rehearse else FULL
    if rehearse:
        _label = "[REHEARSAL on cpu - not a chip result] "

    if not (REPO / "kakveda_tpu" / "cli").is_dir():
        print("chip_smoke: no kakveda_tpu package beside this script; run it from "
              "the root of a checkout", file=sys.stderr)
        return 2

    # Told to stop (the caller's time limit): unwind through the finally
    # blocks, which stop every child.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    shutil.rmtree(OUT, ignore_errors=True)
    run = OUT / "run"
    run.mkdir(parents=True)
    env = child_env(sizes, rehearse, run)

    # What does JAX find? Asked in a child: the answer costs the chip.
    probe = run_child(
        ["-c", "import jax, json; d = jax.devices(); print(json.dumps({'platform': "
               "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"],
        env, OUT / "probe.log", 300.0)
    device = {k: probe[k] for k in ("platform", "kind", "count")}
    if device["platform"] != "tpu" and not rehearse:
        print(f"chip_smoke: JAX finds no TPU here (it reports {device}); this check "
              f"has no CPU path.", file=sys.stderr)
        return 2
    say(f"device: platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']}")

    # Built from what git would commit: the native library is rebuilt from
    # source by the server (KAKVEDA_NATIVE=require fails it otherwise).
    shutil.rmtree(REPO / "kakveda_tpu" / "native" / "build", ignore_errors=True)

    servers: list = []
    carry: dict = {}
    try:
        phases = []
        for name, extra in (("1 bf16", {}),
                            ("2 int8 weights + int8 KV",
                             {"KAKVEDA_QUANT": "int8", "KAKVEDA_KV_QUANT": "int8"})):
            srv = Server(run, {**env, **extra}, OUT / f"server-{name[0]}.log")
            servers.append(srv)
            phases.append(phase(name, srv, sizes, rehearse, name[0] == "1", carry))

        say("kernels vs XLA, in a process of their own")
        kern = run_child(["-m", "kakveda_tpu.ops.chipcheck", "kernels"], env,
                         OUT / "kernels.log", sizes["ready_s"])
        for case in kern["knn"]["cases"] + kern["flash"]["cases"]:
            say(f"  {case}")
        say(f"  kNN path {kern['knn']['path']}; compiled by Mosaic: {kern['knn']['compiled']}; "
            f"programs {kern['compiles']}")
        check(kern["ok"] and kern["_rc"] == 0, "a Pallas kernel disagrees with its XLA counterpart")
        check(kern["knn"]["compiled"] is not rehearse, "kernels ran interpreted")
        check(kern["device"]["platform"] == device["platform"], "kernel check ran elsewhere")

        say("trivial dispatch, in a process of its own")
        disp = run_child(["-m", "kakveda_tpu.ops.chipcheck", "dispatch"], env,
                         OUT / "dispatch.log", 300.0)
        say(f"  on {disp['device']}: dispatch -> block_until_ready "
            f"{disp['dispatch_ready_ms']} ms over {disp['calls']} warm calls; with a 32-byte "
            f"fetch p50 {disp['dispatch_fetch_ms']['p50']} ms (reported, not claimed)")
        check(disp["device"]["platform"] == device["platform"], "dispatch check ran elsewhere")

        (OUT / "summary.json").write_text(json.dumps(
            {"device": device, "rehearsal": rehearse, "phases": phases,
             "kernels": kern, "dispatch": disp}, indent=1))
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        for srv in servers:
            srv.stop(grace_s=10.0)

    if rehearse:
        say("every step ran; this says the script works, nothing about the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
