#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the served path.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never touches a JAX device. It starts one child — the launcher
``benchmarks/harness/server_main.py``, which builds the configuration and
calls the program's own ``run_server`` — after writing, from the seed, the
append log of the failures the deployment already holds, which the server
replays at its start. It waits on ``/readyz``, requires the platform to be
``tpu`` with as many chips as the cell asks for, warms exactly the shapes the
cell's traffic uses, drives the traffic from here for ``--seconds``, reads
``/metrics`` before and after, decides ``correct`` against the plain
references, stops the child, and prints one JSON line. Everything a cell is
made of is found by name: ``BENCHMARK.json`` names files under
``benchmarks/{configs,traffic,limits,metrics}``, and those name modules under
``benchmarks/{endpoints,loops,arrivals,readers,families}`` (a configuration
that has a model names its family, ``families/<family>.py``); nothing here
knows a cell's, an endpoint's, a metric's or an architecture's name.

Beside the server runs a canary (``harness/machine.py``), a child that only
sleeps and writes down every gap between two wake-ups of over 50 ms: what it
saw inside the window is in every result (``numbers.machine_freeze_ms``) and
decides nothing. ``--freeze AT:SECONDS`` holds server, load generator and
canary for SECONDS, AT seconds into the window, as a freeze of the machine
would; like ``--rates`` it is calibration and prints no result.

Without a chip it exits 2 and prints no result. ``--rehearse-on-cpu`` drives
the same steps at the configuration's ``rehearsal`` sizes on the CPU, labels
every line and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from types import SimpleNamespace  # noqa: E402

from harness import correct, machine, manifest, prom, store, streams, textgen  # noqa: E402

_label = ""


def say(msg: str) -> None:
    print(f"{_label}{msg}", file=sys.stderr, flush=True)


class RunFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body=None, timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method="GET" if body is None else "POST",
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode() or "null")
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:3000]}


def http_text(url: str, timeout: float = 60.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


class Server:
    """The launcher child: start, wait ready, control, stop. Never left running."""

    def __init__(self, cell: manifest.Cell, seed: int, run_dir: Path, rehearse: bool, fault: str = ""):
        self.cell, self.seed, self.run_dir, self.rehearse, self.fault = cell, seed, run_dir, rehearse, fault
        self.port, self.dash_port, self.ctl_port = free_port(), free_port(), free_port()
        self.api = f"http://127.0.0.1:{self.port}"
        self.dash = f"http://127.0.0.1:{self.dash_port}"
        self.ctl_url = f"http://127.0.0.1:{self.ctl_port}"
        self.log = run_dir / "server.log"
        self.proc = None

    def env(self, config: dict) -> dict:
        env = {k: v for k, v in os.environ.items() if not k.startswith("KAKVEDA_")}
        env.update({k: str(v) for k, v in config.get("env", {}).items()})
        env.update(
            PYTHONPATH=os.pathsep.join([str(ROOT)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]),
            KAKVEDA_CONFIG_PATH=str(ROOT / "config" / "config.yaml"),
            KAKVEDA_DATA_DIR=str(self.run_dir / "data"),
            KAKVEDA_NATIVE="require",
            KAKVEDA_LOG_FORMAT="text",
            KAKVEDA_LOG_LEVEL="WARNING",
        )
        # One fixed cache directory inside the checkout (the path is part of
        # the cache's key), unless the machine comes with one set.
        env.setdefault("JAX_COMPILATION_CACHE_DIR", str(BENCH / ".jax_cache"))
        if self.rehearse:
            env.update(JAX_PLATFORMS="cpu", KAKVEDA_PALLAS="interpret")
        return env

    def start(self, config: dict, config_file: Path, ready_s: float) -> None:
        (self.run_dir / "data").mkdir(parents=True, exist_ok=True)
        with open(self.log, "ab") as logf:
            self.proc = subprocess.Popen(
                [sys.executable, str(BENCH / "harness" / "server_main.py"),
                 "--config-file", str(config_file), "--seed", str(self.seed),
                 "--port", str(self.port), "--dash-port", str(self.dash_port),
                 "--ctl-port", str(self.ctl_port), "--data-dir", str(self.run_dir / "data"),
                 "--fault", self.fault],
                env=self.env(config), cwd=str(self.run_dir), stdout=logf, stderr=subprocess.STDOUT,
                start_new_session=True)
        t0 = time.perf_counter()
        while True:
            if self.proc.poll() is not None:
                raise RunFailure(f"server exited {self.proc.returncode} during start-up:\n{self.tail()}")
            try:
                status, _ = http_json(self.api + "/readyz", timeout=5.0)
                if status == 200:
                    return
            except (urllib.error.URLError, OSError):
                pass
            if time.perf_counter() - t0 > ready_s:
                raise RunFailure(f"server not ready within {ready_s:.0f}s:\n{self.tail()}")
            time.sleep(0.25)

    def ctl(self, path: str, body: dict | None = None, timeout: float = 300.0) -> dict:
        status, out = http_json(self.ctl_url + path, body or {}, timeout=timeout)
        if status != 200:
            raise RunFailure(f"control {path} -> {status}: {out}")
        return out

    def tail(self, n: int = 3000) -> str:
        try:
            return self.log.read_text(errors="replace")[-n:]
        except OSError:
            return "(no log)"

    def stop(self, grace_s: float = 30.0) -> None:
        p = self.proc
        if p is None or p.poll() is not None:
            return
        p.send_signal(signal.SIGTERM)
        try:
            p.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()


# --- set-up: warm-up and pre-roll ---------------------------------------------------


async def warm_up(target: streams.Target, srv: Server, cell_streams: list, loop) -> int:
    """Drive each stream's ``warmup`` recipe (its endpoint knows how) until a
    whole pass compiles nothing new. Returns the passes made."""
    for passes in range(1, 5):
        before = (await loop.run_in_executor(None, srv.ctl, "/info"))["compiles"]
        for st in cell_streams:
            try:
                await st.kind.warm_up(st, target, passes)
            except RuntimeError as e:
                raise RunFailure(str(e)) from e
        after = (await loop.run_in_executor(None, srv.ctl, "/info"))["compiles"]
        if after == before and passes > 1:
            return passes
    return passes


async def preroll(target: streams.Target, cell_streams: list) -> None:
    """A stream's own traffic for its ``warmup.preroll_s`` seconds, unmeasured,
    before the window: what the server still settles after its start and the
    first requests (warn-steady's first seconds read 5 % slower than its later
    ones, PERF.md section 6) is then set-up and not spread."""
    for st in cell_streams:
        secs = st.spec.get("warmup", {}).get("preroll_s", 0)
        if not secs:
            continue
        st.prepare(seconds=float(secs), first=9_500_000)
        recs = await st.run(target, time.perf_counter() + 0.05)
        bad = [r for r in recs if st.kind.failed(r)]
        if bad:
            raise RunFailure(f"pre-roll: {len(bad)} of {len(recs)} requests failed: {bad[0].get('res') or bad[0].get('error')}")
        say(f"pre-roll: {len(recs)} requests over {secs} s")


async def probes(target: streams.Target, cell_streams: list) -> dict:
    """In a traced run, once the window has closed and its counters are read:
    a stream's ``probe``, a few seconds of the same stream at another rate
    (warn-steady's: 4/5 of what the server sustains, where no bound would
    hold), for a per-layer metric to report. The window's records stay."""
    out = {}
    for st in cell_streams:
        probe = st.spec.get("probe")
        if not probe:
            continue
        window = st.records
        st.prepare(rate=float(probe["rate_rps"]), seconds=float(probe["seconds"]), first=9_700_000)
        out[st.endpoint] = await st.run(target, time.perf_counter() + 0.05)
        st.records = window
        say(f"probe: {len(out[st.endpoint])} requests at {probe['rate_rps']} a second over {probe['seconds']} s")
    return out


# --- the run ----------------------------------------------------------------------


async def run_cell(args, cell: manifest.Cell, srv: Server, sizes: dict, canary) -> dict:
    loop = asyncio.get_running_loop()
    corpus = textgen.Corpus(args.seed)
    traffic = cell.traffic
    if args.rehearse_on_cpu:  # a stream's ``rehearsal`` keys: what the CPU can carry
        traffic = {**traffic, "streams": [{**sp, **sp.get("rehearsal", {})} for sp in traffic["streams"]]}
    stored = int(sizes.get("gfkb_fill", 0))
    cell_streams = [streams.Stream(sp, args.seed, corpus, stored, float(args.seconds)) for sp in traffic["streams"]]
    target = streams.Target(srv.api, srv.dash)
    await target.open()
    out: dict = {}
    try:
        if any(getattr(st.kind, "NEEDS_LOGIN", False) for st in cell_streams):
            await target.login()
        # The server has replayed the log that set-up wrote (harness/store.py):
        # it holds what the configuration says, in the type it says.
        ready = json.loads(await loop.run_in_executor(None, http_text, srv.api + "/readyz"))
        if int(ready["gfkb_count"]) != stored:
            raise RunFailure(f"the index holds {ready['gfkb_count']} failures where the configuration states {stored}")
        held = ready["device"]["index"].get("store_dtype")
        want = {2: "bfloat16", 4: "float32"}[int(sizes["row_bytes"])]
        if held != want:
            raise RunFailure(f"the index holds {held} rows where the configuration states {want}")
        passes = await warm_up(target, srv, cell_streams, loop)
        say(f"warm-up: {passes} passes, the last compiled nothing")
        await preroll(target, cell_streams)
        await loop.run_in_executor(None, srv.ctl, "/chat/mark")
        info0 = await loop.run_in_executor(None, srv.ctl, "/info")
        prom0 = prom.parse(await loop.run_in_executor(None, http_text, srv.api + "/metrics"))

        rates = [float(x) for x in args.rates.split(",")] if args.rates else [None]
        sweep = []
        for rate in rates:
            for st in cell_streams:
                st.prepare(rate)
            t_start = time.perf_counter() + 0.05
            setup_s = t_start - T_PROCESS_START
            t_start_mono = t_start + (time.monotonic() - time.perf_counter())  # the canary's clock
            freezer = None
            if args.freeze:
                at, secs = args.freeze
                freezer = machine.start("freeze", t_start_mono + at, secs, srv.proc.pid, f"{os.getpid()},{canary.pid}")
            trace_task = None
            if args.trace:
                trace_task = asyncio.ensure_future(traced_window(srv, loop, t_start, float(args.seconds), traffic))
            # The window allocates a record per request; a full collection of
            # this process's heap (tens of thousands of prepared bodies) would
            # stop the sends for a tenth of a second or more and read as latency.
            gc.collect()
            gc.freeze()
            gc.disable()
            try:
                await asyncio.gather(*[st.run(target, t_start) for st in cell_streams])
            finally:
                gc.enable()
                machine.stop(freezer)
            t_end = t_start + float(args.seconds)
            if args.rates:
                sweep.append({"rate": rate, **{k: v for st in cell_streams for k, v in st.kind.sweep_row(st, t_end).items()}})
                say("sweep " + json.dumps(sweep[-1]))
            trace = await trace_task if trace_task else None
        if args.rates:
            return {"sweep": sweep}

        prom1 = prom.parse(await loop.run_in_executor(None, http_text, srv.api + "/metrics"))
        info1 = await loop.run_in_executor(None, srv.ctl, "/info")
        compiles_in_window = info1["compiles"] - info0["compiles"]
        say(f"compiles inside the window: {compiles_in_window}")

        ctx = {
            "cell": cell, "sizes": sizes, "seconds": float(args.seconds), "t_start": t_start, "t_end": t_end,
            "setup_s": setup_s, "device": info1, "prom_before": prom0, "prom_after": prom1,
            "streams": {st.endpoint: st.records for st in cell_streams}, "trace": trace, "stored": stored,
            "probe": await probes(target, cell_streams) if args.trace else {},
            "machine": machine.seen(srv.run_dir, t_start_mono, t_start_mono + float(args.seconds)),
        }
        say("machine: " + json.dumps(ctx["machine"]))
        run = SimpleNamespace(args=args, srv=srv, ctx=ctx, sizes=sizes, target=target, loop=loop, say=say)
        numbers = {}
        for st in cell_streams:
            numbers.update(await st.kind.check(st, run))
        numbers["compiles_in_window"] = compiles_in_window
        numbers.update(ctx["machine"])
        ok, compared = correct.judge(numbers, {**cell.limits, "compiles_in_window": 0})
        out.update(ctx=ctx, numbers=numbers, correct=ok, compared=compared,
                   attempted=sum(len(st.records) for st in cell_streams),
                   failed=sum(1 for st in cell_streams for r in st.records if st.kind.failed(r)))
        return out
    finally:
        await target.close()


async def traced_window(srv: Server, loop, t_start: float, seconds: float, traffic: dict) -> dict:
    """Trace ``trace_seconds`` from the middle of the window."""
    span = min(float(traffic.get("trace_seconds", 4.0)), seconds * 0.8)
    begin = t_start + (seconds - span) / 2
    await asyncio.sleep(max(0.0, begin - time.perf_counter()))
    trace_dir = srv.run_dir / "trace"
    await loop.run_in_executor(None, srv.ctl, "/trace/start", {"dir": str(trace_dir)})
    await asyncio.sleep(span)
    return await loop.run_in_executor(None, lambda: srv.ctl("/trace/stop", {}, 600.0))


def main() -> int:
    global _label
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--control", type=int, default=0, help="also read the lower-precision control (calibration only)")
    ap.add_argument("--rates", default="", help="comma list: one window per rate, no result (the sweep)")
    ap.add_argument("--freeze", default="", help="AT:SECONDS: hold server, load generator and canary, no result")
    ap.add_argument("--fault", default="", help="benchmarks/tests only, with --rehearse-on-cpu: harness/faults.py")
    args = ap.parse_args()
    if args.fault and not args.rehearse_on_cpu:
        ap.error("--fault breaks the timed path for the tests; it runs only with --rehearse-on-cpu")
    if args.rehearse_on_cpu:
        _label = "[rehearsal on CPU — not a result] "
    elif args.freeze:
        _label = "[--freeze: calibration — not a result] "
    if args.freeze:
        args.freeze = tuple(float(x) for x in args.freeze.split(":"))
        if len(args.freeze) != 2 or not 0 <= args.freeze[0] < sum(args.freeze) < args.seconds:
            ap.error("--freeze AT:SECONDS has to end inside the window")
    try:
        cell = manifest.load_cell(args.workload)
    except manifest.ManifestError as e:
        say(f"refused: {e}")
        return 2
    if not (ROOT / "kakveda_tpu").is_dir():
        say("refused: the program (kakveda_tpu/) is not in this directory")
        return 2
    config = cell.config
    sizes = dict(config["sizes"])
    if args.rehearse_on_cpu:
        sizes.update(config.get("rehearsal", {}).get("sizes", {}))
        config = {**config, "env": {**config.get("env", {}), **config.get("rehearsal", {}).get("env", {})}}
        config.update(config.get("rehearsal", {}).get("model", {}))
    run_dir = BENCH / ".runs" / f"{cell.name}-{args.seed}-{os.getpid()}"
    if run_dir.exists():
        shutil.rmtree(run_dir)
    run_dir.mkdir(parents=True)
    config_file = run_dir / "config.json"
    config_file.write_text(json.dumps(config))
    srv = Server(cell, args.seed, run_dir, args.rehearse_on_cpu, args.fault)
    canary = machine.start("canary", run_dir / machine.GAPS_FILE)
    rc, result = 1, None
    try:
        stored = int(sizes.get("gfkb_fill", 0))
        if stored:  # the failures the deployment already holds: the server replays them at its start
            say(f"store: {stored} failures written to the append log in "
                f"{store.write_failure_log(run_dir / 'data', args.seed, stored):.2f}s")
        srv.start(config, config_file, ready_s=1100.0)
        info = srv.ctl("/info")
        if not args.rehearse_on_cpu:
            if info["platform"] != "tpu":
                say(f"refused: JAX found platform {info['platform']!r}, not a TPU")
                return 2
            if info["count"] < cell.chips:
                say(f"refused: the cell asks for {cell.chips} chip(s), JAX found {info['count']}")
                return 2
        say(f"server ready {time.perf_counter() - T_PROCESS_START:.1f}s after start on {info['platform']} "
            f"{info['kind']} x{info['count']}")
        out = asyncio.run(run_cell(args, cell, srv, sizes, canary))
        if "sweep" in out:  # exploration: rows on standard error, no result
            return 0
        ctx = out["ctx"]
        metrics = {}
        entries = [(m, json.loads((BENCH / "metrics" / f"{m['name']}.json").read_text())) for m in cell.end_to_end] \
            if not args.trace else cell.per_layer
        for entry, desc in entries:
            try:
                v = manifest.load_module("readers", desc["reader"]).read(ctx, desc.get("params", {}))
            except ValueError as e:  # a share over 100 %: a fault in the count, shown and not hidden
                raise RunFailure(f"metric {entry['name']}: {e}") from e
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        missing = [m["name"] for m, _ in entries if m["name"] not in metrics and not args.trace]
        if missing:
            raise RunFailure(f"end-to-end metric(s) without a value: {missing} "
                             f"(attempted {out['attempted']}, failed {out['failed']})")
        dev = ctx["device"]
        device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
                  "memory_peak_bytes": dev["memory_peak_bytes"]}
        result = {"correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
                  "metrics": metrics, "device": device}
        if args.trace and ctx["trace"]:
            device.update(busy_s=ctx["trace"]["busy_s"], window_s=ctx["trace"]["window_s"])
            result["breakdown"] = ctx["trace"]["breakdown"]
        result["numbers"] = out["numbers"]
        result["compared"] = out["compared"]
        rc = 0
    except RunFailure as e:
        say(f"FAILED: {e}")
        rc = 1
    finally:
        srv.stop()
        machine.stop(canary)
        shutil.rmtree(run_dir, ignore_errors=True)
    if result is None or args.rehearse_on_cpu or args.freeze:
        if result is not None:
            say("would have printed: " + json.dumps(result))
        return rc if result is None else 0
    for name, c in result["compared"].items():
        say(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
