"""The launcher: the one child that holds the chip.

It builds what the configuration file says (its family's seeded weights,
``families/<family>.py``, put into the program's runtime table), calls the program's own
``run_server`` — the same server, ``ServingEngine`` and routes as ``cli up`` —
and, beside it, answers a small control port for what only the process that
holds the chip can do: report the device and its memory, start and stop the
profiler, hand out what the engine was asked and answered, and run the plain
reference on the chip once the model's state is freed.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


class State:
    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.lock = threading.Lock()
        self.records = []          # (prompt ids, future) for every engine submit
        self.runtime = None
        self.model_cfg = None
        self.family = None         # the module families/<family>.py of the configuration
        self.seed = 0
        self.trace_dir = None
        self.trace_t0 = None


STATE = State()


def _count_compiles():
    import jax.monitoring

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with STATE.lock:
                STATE.compiles += 1
                STATE.compile_s += float(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _build_model(config: dict, seed: int):
    """The configuration's family (``families/<family>.py``) builds the
    program's runtime object round seeded weights; it goes under "tpu" in the
    program's runtime table, and ``run_server`` then serves it as it would a
    preset. What follows is the same for every family."""
    from harness import manifest
    from kakveda_tpu.models import runtime as rt_mod

    family = manifest.load_family(config)
    rt = family.build(config, seed)
    eng = rt.engine()  # the KV pool is part of set-up, not of the first request
    if eng is None:
        raise RuntimeError("the configuration asks for the ServingEngine and the runtime built none")
    submit = eng.submit

    def recording_submit(prompt_ids, *a, **kw):
        fut = submit(prompt_ids, *a, **kw)
        with STATE.lock:
            STATE.records.append((list(prompt_ids), fut))
        return fut

    eng.submit = recording_submit
    rt_mod._RUNTIMES["tpu"] = rt
    STATE.runtime, STATE.model_cfg, STATE.family = rt, config, family


def _device_info() -> dict:
    import jax

    devs = jax.devices()
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    with STATE.lock:
        compiles, compile_s = STATE.compiles, STATE.compile_s
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(peaks), "memory_peak_by_device": peaks,
            "compiles": compiles, "compile_s": compile_s}


def _trace_start(body: dict) -> dict:
    import jax

    STATE.trace_dir = body["dir"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(STATE.trace_dir, profiler_options=opts)
    STATE.trace_t0 = time.perf_counter()
    return {"ok": True}


def _trace_stop(body: dict) -> dict:
    import jax

    from harness import xplane

    wall = time.perf_counter() - STATE.trace_t0
    jax.profiler.stop_trace()
    t0 = time.perf_counter()
    path = xplane.newest_xplane(STATE.trace_dir)
    planes = xplane.read_planes(path)
    out = xplane.summarize(planes)
    out.update(traced_wall_s=wall, reduce_s=time.perf_counter() - t0, xplane_bytes=path.stat().st_size)
    shutil.rmtree(STATE.trace_dir, ignore_errors=True)
    return out


def _chat_records(body: dict) -> dict:
    """What the engine was asked and what it answered, since ``mark``."""
    with STATE.lock:
        recs = list(STATE.records)
    out = []
    for ids, fut in recs:
        toks, err = None, None
        if fut.done():
            try:
                toks = [int(t) for t in fut.result()]
            except Exception as e:  # noqa: BLE001 — reported per request
                err = f"{type(e).__name__}: {e}"
        out.append({"ids": ids, "out": toks, "error": err})
    return {"records": out}


def _chat_mark(body: dict) -> dict:
    with STATE.lock:
        STATE.records.clear()
    return {"ok": True}


def _free_model() -> None:
    from kakveda_tpu.models import runtime as rt_mod

    rt = STATE.runtime
    if rt is not None:
        rt.retire()
        rt.params = None
        rt_mod._RUNTIMES.pop("tpu", None)
        STATE.runtime = None
    gc.collect()


def _chat_reference(body: dict) -> dict:
    """Free the model, then run the plain reference over the sampled requests:
    each prompt with the tokens it was served. Returns every served token's
    gap; with ``control`` the lower-precision control's reading as well."""
    import numpy as np

    from harness import correct

    _free_model()
    sample = body["sample"]  # [{"ids": [...], "out": [...]}]
    live = int(body["vocab_live"])
    width = max(len(s["ids"]) + len(s["out"]) for s in sample)
    width = -(-width // 64) * 64
    toks = np.zeros((len(sample), width), np.int32)
    for r, s in enumerate(sample):
        seq = s["ids"] + s["out"]
        toks[r, :len(seq)] = seq
    plen, served = [len(s["ids"]) for s in sample], [s["out"] for s in sample]
    t0 = time.perf_counter()
    family = STATE.family
    lg = family.reference_logits(STATE.seed, STATE.model_cfg, toks, live)
    out = {"gaps": correct.served_gaps(lg, plen, served), "reference_s": time.perf_counter() - t0}
    if body.get("control"):
        ctl = family.reference_logits(STATE.seed, STATE.model_cfg, toks, live, control=True)
        out["control_gaps"] = correct.argmax_gaps(lg, ctl, plen, served)
    return out


ROUTES = {
    "/info": lambda body: _device_info(),
    "/trace/start": _trace_start,
    "/trace/stop": _trace_stop,
    "/chat/records": _chat_records,
    "/chat/mark": _chat_mark,
    "/chat/reference": _chat_reference,
}


class Control(BaseHTTPRequestHandler):
    def log_message(self, *a):  # quiet
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        try:
            body = json.loads(self.rfile.read(n) or b"{}")
            fn = ROUTES[self.path]
            code, out = 200, fn(body)
        except Exception as e:  # noqa: BLE001 — the parent decides what a failed control call means
            import traceback

            code, out = 500, {"error": f"{type(e).__name__}: {e}", "trace": traceback.format_exc()[-2000:]}
        data = json.dumps(out).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--dash-port", type=int, required=True)
    ap.add_argument("--ctl-port", type=int, required=True)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--fault", default="", help="benchmarks/tests only: break the timed path (harness/faults.py)")
    args = ap.parse_args()
    config = json.loads(Path(args.config_file).read_text())
    STATE.seed = args.seed

    _count_compiles()
    from kakveda_tpu.ops.device import setup_compile_cache

    setup_compile_cache()
    if args.fault:
        from harness import faults

        faults.plant(args.fault)
    if config.get("family"):
        _build_model(config, args.seed)

    ctl = ThreadingHTTPServer(("127.0.0.1", args.ctl_port), Control)
    threading.Thread(target=ctl.serve_forever, daemon=True).start()

    from kakveda_tpu.service.main import run_server

    return run_server(host="127.0.0.1", port=args.port, data_dir=args.data_dir,
                      dashboard_port=args.dash_port)


if __name__ == "__main__":
    sys.exit(main())
