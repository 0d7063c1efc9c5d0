"""Server entry point: `kakveda-tpu up` / `python -m kakveda_tpu.service`.

Runs the platform API (reference port 8100-8106 contracts) and the
dashboard (reference port 8110) from one process over one shared
intelligence core — two listeners, zero HTTP hops between pipeline stages.
"""

from __future__ import annotations

import asyncio
import logging
import os

from aiohttp import web

from kakveda_tpu.core.runtime import get_runtime_config, setup_logging
from kakveda_tpu.platform import Platform
from kakveda_tpu.service.app import make_app

log = logging.getLogger("kakveda.service")


async def _serve(
    plat: Platform, host: str, port: int, dashboard_port: int | None
) -> None:
    api_app = make_app(plat)
    api_runner = web.AppRunner(api_app)
    await api_runner.setup()
    await web.TCPSite(api_runner, host, port).start()
    log.info("platform API on http://%s:%d (gfkb entries: %d)", host, port, plat.gfkb.count)

    if dashboard_port:
        from kakveda_tpu.dashboard.app import make_dashboard_app

        dash_app = make_dashboard_app(platform=plat)
        dash_runner = web.AppRunner(dash_app)
        await dash_runner.setup()
        await web.TCPSite(dash_runner, host, dashboard_port).start()
        log.info("dashboard on http://%s:%d", host, dashboard_port)

    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await api_runner.cleanup()
        # Graceful shutdown snapshot: the next start restores it and replays
        # only the log tail instead of re-embedding the whole GFKB.
        try:
            plat.gfkb.snapshot()
            log.info("gfkb snapshot written (%d entries)", plat.gfkb.count)
        except Exception as e:  # noqa: BLE001 — shutdown must not fail on this
            log.warning("shutdown snapshot failed: %s", e)


def run_server(
    host: str = "127.0.0.1",
    port: int = 8100,
    data_dir: str | None = None,
    dashboard_port: int | None = 8110,
) -> int:
    setup_logging(service_name="kakveda-tpu")
    cfg = get_runtime_config(service_name="kakveda-tpu")

    from kakveda_tpu.ops.device import device_report, setup_compile_cache

    cache_dir = setup_compile_cache()

    # Join the multi-host world (if configured) BEFORE the Platform builds
    # its mesh — jax.devices() must already span the pod.
    from kakveda_tpu.parallel.distributed import initialize_multihost

    initialize_multihost()

    # Compile-and-transfer ledger (KAKVEDA_LEDGER=1) installs BEFORE the
    # Platform so its jit wrapping covers the match/ingest programs built
    # at construction; /metrics then carries kakveda_compile_total and
    # kakveda_transfer_bytes (docs/observability.md).
    from kakveda_tpu.core import ledger

    if ledger.maybe_install():
        log.info("compile-and-transfer ledger installed (KAKVEDA_LEDGER=1)")
    from kakveda_tpu.parallel.mesh import create_mesh

    plat = Platform(
        data_dir=data_dir or cfg.data_dir,
        capacity=cfg.index_capacity,
        mesh=create_mesh(cfg.mesh_shape),
    )
    log.info(
        "running on %s; index %s; compile cache %s",
        device_report(), plat.gfkb.index_info(), cache_dir,
    )

    # Generational-GC tuning for the streaming path: ingest allocates ~2k
    # short-lived objects per 512-batch (pydantic records + dicts), which
    # trips gen-2 collections every ~13 batches — observed as periodic
    # ~100 ms pauses in an otherwise ~30 ms/batch stream. Freezing the
    # startup object graph takes the permanent majority of the heap out of
    # every collection; raised thresholds amortize the rest.
    # KAKVEDA_GC_TUNE=0 restores CPython defaults.
    if os.environ.get("KAKVEDA_GC_TUNE", "1") != "0":
        import gc

        gc.collect()
        gc.freeze()
        gc.set_threshold(50_000, 20, 20)

    # Zero-code operator profiling: KAKVEDA_PROFILE_DIR=/path captures an
    # XPlane trace of one warm pre-flight match at startup.
    from kakveda_tpu.core import profiling
    from kakveda_tpu.core.schemas import WarningRequest

    logdir = profiling.startup_profile_dir()
    if logdir:
        probe = WarningRequest(app_id="_profile", prompt="startup profile probe", tools=[], env={})
        plat.warn(probe)  # warm/compile outside the trace
        with profiling.profile(logdir):
            plat.warn(probe)
    try:
        asyncio.run(_serve(plat, host, port, dashboard_port))
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    run_server()
