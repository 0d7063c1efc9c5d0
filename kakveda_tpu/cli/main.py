"""`kakveda-tpu` CLI: init | up | down | status | reset | logs | dlq | traffic | compact | doctor | version.

Verb parity with the reference CLI (reference: kakveda_cli/cli.py:46-409),
re-targeted at the single-process TPU platform: where the reference
orchestrates a 9-container docker-compose stack, `up` here starts the
in-process service layer (all reference REST contracts on one port) and
`doctor` checks the JAX/TPU environment instead of the Docker daemon.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from pathlib import Path


def _cmd_version(args: argparse.Namespace) -> int:
    from kakveda_tpu import __version__

    print(f"kakveda-tpu {__version__}")
    return 0


def _cmd_init(args: argparse.Namespace) -> int:
    from kakveda_tpu.core.config import write_default_config

    root = Path(args.dir)
    cfg = root / "config" / "config.yaml"
    if cfg.exists() and not args.force:
        print(f"config already exists at {cfg} (use --force to overwrite)")
    else:
        write_default_config(cfg)
        print(f"wrote {cfg}")
    (root / "data").mkdir(parents=True, exist_ok=True)
    print(f"data dir ready at {root / 'data'}")
    if args.wizard or args.yes:
        from kakveda_tpu.cli.wizard import run_wizard

        run_wizard(root, assume_yes=args.yes)
    return 0


def _cmd_doctor(args: argparse.Namespace) -> int:
    checks = []

    def check(name, fn):
        try:
            detail = fn()
            checks.append((name, True, detail))
        except Exception as e:  # noqa: BLE001 — doctor reports, never crashes
            checks.append((name, False, f"{type(e).__name__}: {e}"))

    def _jax():
        # An accelerator plug-in that is present but cannot initialise
        # raises here and fails the check: that machine is broken for
        # serving, and a quiet CPU fallback would say it is fine. Note that
        # doctor takes the chip while it runs — not beside a live server.
        import jax

        from kakveda_tpu.ops.device import device_report, setup_compile_cache

        cache_dir = setup_compile_cache()
        rep = device_report()
        return (
            f"{jax.__version__} platform={rep['platform']} "
            f"device_kind={rep['device_kind']!r} devices={rep['device_count']} "
            f"compile_cache={cache_dir}"
        )

    def _mesh():
        from kakveda_tpu.parallel.mesh import create_mesh

        mesh = create_mesh(os.environ.get("KAKVEDA_MESH_SHAPE", "data:-1"))
        return f"axes={dict(mesh.shape)}"

    def _device_compute():
        import jax
        import jax.numpy as jnp

        x = jnp.ones((128, 128), jnp.float32)
        y = jax.jit(lambda a: (a @ a).sum())(x)
        return f"matmul ok (sum={float(y):.0f})"

    def _native():
        from kakveda_tpu import native

        return "C++ fast path loaded" if native.available() else "pure-python fallback (run make in kakveda_tpu/native)"

    def _config_parse():
        from kakveda_tpu.core.config import ConfigStore

        cs = ConfigStore()
        return f"threshold={cs.similarity_threshold()} top_k={cs.match_top_k()}"

    def _jwt_secret():
        from kakveda_tpu.core.runtime import get_runtime_config

        rc = get_runtime_config(service_name="doctor")
        if rc.env == "production" and rc.dashboard_jwt_secret == "dev-secret-change-me":
            raise RuntimeError("production with default JWT secret — set DASHBOARD_JWT_SECRET")
        return "set" if rc.dashboard_jwt_secret != "dev-secret-change-me" else "dev default (fine outside production)"

    def _serving_levers():
        """The env-tunable serving configuration in one line — what the
        engine will actually run with (models/serving.py knobs)."""
        e = os.environ.get
        parts = [
            f"continuous={'on' if e('KAKVEDA_SERVE_CONTINUOUS', '1') != '0' else 'OFF'}",
            f"slots={e('KAKVEDA_SERVE_SLOTS', '8')}",
            f"window={e('KAKVEDA_SERVE_WINDOW', 'auto')}",
            f"chunk={e('KAKVEDA_SERVE_CHUNK', '8')}",
            f"pipeline={'on' if e('KAKVEDA_SERVE_PIPELINE', '1') != '0' else 'OFF'}",
            f"prefix={'on' if e('KAKVEDA_SERVE_PREFIX', '1') != '0' else 'OFF'}",
            f"spec_k={e('KAKVEDA_SERVE_SPEC', '0')}",
            f"spec_gate=warmup{e('KAKVEDA_SERVE_SPEC_WARMUP', '8')}"
            f"/calib{e('KAKVEDA_SERVE_SPEC_CALIB', '2')}"
            f"/reprobe{e('KAKVEDA_SERVE_SPEC_REPROBE', '256')}",
            f"quant={e('KAKVEDA_QUANT', 'none')}",
            f"kv_quant={e('KAKVEDA_KV_QUANT', 'none')}",
        ]
        if e("KAKVEDA_HBM_BUDGET"):
            parts.append(f"hbm_budget={e('KAKVEDA_HBM_BUDGET')}")
        return " ".join(parts)

    def _redis():
        url = os.environ.get("KAKVEDA_REDIS_URL")
        if not url:
            return "not configured (in-memory revocation/rate-limit)"
        import redis  # type: ignore[import-not-found]

        redis.Redis.from_url(url, socket_timeout=1).ping()
        # Redact userinfo — the URL may carry a password, and doctor output
        # lands in terminals and CI logs.
        safe = url.split("@", 1)[-1] if "@" in url else url.split("//", 1)[-1]
        return f"reachable at {safe}"

    def _fleet():
        """Per-replica health + fleet admission mode, from the manifest
        `up --replicas` writes (fleet.json) — mirrors the router's
        /readyz report for operators without curl."""
        from kakveda_tpu.fleet.supervisor import read_manifest

        manifest = read_manifest(args.dir)
        if not manifest:
            return "single-process (no fleet.json)"
        import httpx

        parts = []
        worst = "normal"
        live_ids = []
        epochs = {}
        for rep in manifest.get("replicas", []):
            rid = rep.get("id", "?")
            pidp = Path(rep.get("pid_file", ""))
            alive = False
            try:
                alive = _pid_alive(int(pidp.read_text().strip()))
            except (OSError, ValueError):
                pass
            mode = "down"
            if alive:
                try:
                    r = httpx.get(rep["url"] + "/readyz", timeout=2.0)
                    r.raise_for_status()
                    body = r.json()
                    adm = body.get("admission", {})
                    mode = adm.get("brownout", "?")
                    steps = ("normal", "no_spec", "clamped",
                             "shed_background", "shed_interactive")
                    if mode in steps and steps.index(mode) > steps.index(worst):
                        worst = mode
                    live_ids.append(rid)
                    own = body.get("ownership") or {}
                    if own.get("enabled"):
                        epochs[rid] = int(own.get("epoch", 0))
                except (httpx.HTTPError, ValueError):
                    mode = "unreachable"
            parts.append(f"{rid}={'up' if alive else 'DOWN'}/{mode}")
        if any("DOWN" in p or "unreachable" in p for p in parts):
            raise RuntimeError(" ".join(parts))
        own_note = ""
        if epochs:
            # Sharded ownership (fleet/ownership.py): every reachable
            # replica must agree on the epoch, and every key range needs
            # at least one live holder — either failing is a doctor
            # ERROR, not a warning (stale views mis-fence replication;
            # a coverage hole silently un-answers a key range).
            if len(set(epochs.values())) > 1:
                raise RuntimeError(
                    f"{' '.join(parts)} ownership epochs DISAGREE: {epochs}"
                )
            from kakveda_tpu.fleet.ownership import OwnershipView

            top = max(epochs, key=epochs.get)
            url = next(r["url"] for r in manifest["replicas"]
                       if r.get("id") == top)
            try:
                view = OwnershipView.from_dict(
                    httpx.get(url + "/fleet/ownership", timeout=2.0).json()
                )
            except (httpx.HTTPError, ValueError, KeyError) as e:
                raise RuntimeError(f"ownership view unreadable: {e}") from e
            holes = view.coverage_holes(live_ids)
            if holes:
                raise RuntimeError(
                    f"{' '.join(parts)} COVERAGE HOLES: {holes} ranges "
                    f"have zero live holders (epoch {epochs[top]})"
                )
            own_note = (f" ownership=epoch:{epochs[top]}"
                        f"/R:{view.replication}/holes:0")
        scale_note = ""
        auto = manifest.get("autoscale")
        if auto:
            # Elastic fleet (fleet/autoscaler.py): surface the policy
            # bounds and the last ledgered decision so an operator sees a
            # crash-looping replacement or a stuck drain without curl.
            scale_note = f" autoscale={auto.get('min')}..{auto.get('max')}"
            last = None
            try:
                lines = Path(auto.get("scale_log", "")).read_text().splitlines()
                if lines:
                    last = json.loads(lines[-1])
            except (OSError, ValueError):
                pass
            if last:
                scale_note += (f" last={last.get('action')}:"
                               f"{last.get('outcome')}→{last.get('target')}")
        return f"{' '.join(parts)} fleet_mode={worst}{own_note}{scale_note}"

    def _tenant_plane():
        """Tenant fairness posture (docs/robustness.md § multi-tenancy):
        reads the live server's /readyz admission.tenants block. A tenant
        pinned at 100% shed — many sheds, ZERO admits — is a doctor
        ERROR: either a flooder that should be talked to, or (if it's a
        victim) an isolation bug. No live server is fine (the plane only
        exists in-process)."""
        pid = _read_pid(Path(args.dir))
        if not (pid and _pid_alive(pid)):
            return "no live server (probes /readyz when one is up)"
        import httpx

        body = httpx.get(args.url + "/readyz", timeout=2.0).json()
        tenants = (body.get("admission") or {}).get("tenants")
        if not tenants:
            return "admission reports no tenant block (older server?)"
        if not tenants.get("fair", False):
            return "KAKVEDA_TENANT_FAIR=0 — global FIFO, no isolation"
        pinned = [
            row for row in tenants.get("top_shed", [])
            if row.get("sheds", 0) >= 20 and row.get("admits", 0) == 0
        ]
        note = (
            f"fair=on table={tenants.get('table_size')}/"
            f"{tenants.get('table_max')} share_cap={tenants.get('max_share')} "
            f"promotions={tenants.get('promotions') or {}}"
        )
        top = tenants.get("top_shed", [])
        if top:
            worst = top[0]
            note += (f" top_shed={worst.get('tenant')}:"
                     f"{worst.get('sheds')}")
        if pinned:
            raise RuntimeError(
                f"{note} — tenant(s) pinned at 100% shed: "
                + ", ".join(f"{r['tenant']} ({r['sheds']} sheds, 0 admits)"
                            for r in pinned)
            )
        return note

    def _replay_budget():
        """Durability posture vs the operator's recovery-time budget:
        KAKVEDA_GFKB_REPLAY_BUDGET_S > 0 turns the replay estimate into a
        hard doctor check — a restart that would replay longer than the
        budget is an error to fix with `kakveda-tpu compact`, not a
        surprise during the next incident."""
        data = Path(args.dir) / "data"
        if not data.exists():
            return "no data dir yet"
        post = _durability_posture(data)
        budget = float(os.environ.get("KAKVEDA_GFKB_REPLAY_BUDGET_S", "0"))
        est = post["replay_estimate_s"]
        note = (
            f"replay≈{est}s ({post['replayable_bytes']}B replayable, "
            f"generation {post['compact_generation']}, "
            f"{post['tombstoned_rows']} tombstoned)"
        )
        if budget > 0 and est > budget:
            raise RuntimeError(
                f"{note} exceeds KAKVEDA_GFKB_REPLAY_BUDGET_S={budget} — "
                f"run `kakveda-tpu compact`"
            )
        return note

    check("python", lambda: sys.version.split()[0])
    check("replay budget", _replay_budget)
    check("tenant plane", _tenant_plane)
    check("fleet", _fleet)
    check("jax", _jax)
    check("device mesh", _mesh)
    check("device compute", _device_compute)
    check("native extension", _native)
    check("config", lambda: str(Path(os.environ.get("KAKVEDA_CONFIG_PATH", "config/config.yaml")).resolve()))
    check("serving levers", _serving_levers)
    check("config parse", _config_parse)
    check("jwt secret", _jwt_secret)
    check("redis", _redis)
    check("data dir writable", lambda: _writable(os.environ.get("KAKVEDA_DATA_DIR", "data")))

    ok = all(c[1] for c in checks)
    for name, good, detail in checks:
        print(f"[{'ok' if good else 'FAIL'}] {name}: {detail}")
    return 0 if ok else 1


def _writable(d: str) -> str:
    p = Path(d)
    p.mkdir(parents=True, exist_ok=True)
    probe = p / ".probe"
    probe.write_text("ok")
    probe.unlink()
    return str(p.resolve())


def _cmd_reset(args: argparse.Namespace) -> int:
    root = Path(args.dir)
    data = root / "data"
    if not data.exists():
        print(f"nothing to reset at {data}")
        return 0
    if not args.yes:
        print(f"would delete {data} — re-run with --yes to confirm")
        return 1
    shutil.rmtree(data)
    print(f"deleted {data}")
    return 0


def _pid_path(root: Path) -> Path:
    return root / "server.pid"


def _log_path(root: Path) -> Path:
    return root / "server.log"


def _read_pid(root: Path) -> int | None:
    p = _pid_path(root)
    try:
        return int(p.read_text().strip())
    except (OSError, ValueError):
        return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def _durability_posture(data: Path) -> dict:
    """Per-store durability posture from the files alone — no jax, no
    GFKB construction, safe against a live server holding the store.

    Replay time is estimated as replayable-bytes / KAKVEDA_GFKB_REPLAY_RATE
    (bytes/s, default 4 MiB/s — conservative for the pydantic JSONL parse
    path); replayable bytes start at the snapshot manifest's log_offset,
    so a compaction directly shrinks the estimate the operator sees."""
    rate = float(os.environ.get("KAKVEDA_GFKB_REPLAY_RATE", str(4 << 20)))
    stores = {}
    replayable = 0
    for name in ("failures", "patterns", "applied_events", "tombstones"):
        f = data / f"{name}.jsonl"
        try:
            size = f.stat().st_size
        except OSError:
            size = 0
        stores[name] = {"bytes": size}
        replayable += size
    manifest = {}
    try:
        manifest = json.loads((data / "snapshot" / "manifest.json").read_text())
    except (OSError, ValueError):
        pass
    offset = int(manifest.get("log_offset", 0) or 0)
    # The snapshot replaces log replay up to log_offset.
    stores["failures"]["replayable_bytes"] = max(
        0, stores["failures"]["bytes"] - offset
    )
    replayable -= min(offset, stores["failures"]["bytes"])
    tomb = 0
    f = data / "tombstones.jsonl"
    if f.exists():
        net = {}
        try:
            for ln in f.read_text().splitlines():
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue  # torn tail — the store's replay handles it
                if rec.get("op") == "tomb":
                    net[rec.get("id")] = rec.get("reason")
                else:
                    net.pop(rec.get("id"), None)
            tomb = len(net)
        except OSError:
            pass
    compact = manifest.get("compact") or {}
    return {
        "stores": stores,
        "snapshot_rows": int(manifest.get("n", 0) or 0),
        "compact_generation": int(compact.get("generation", 0) or 0),
        "last_compact_ts": float(compact.get("ts", 0.0) or 0.0),
        "tombstoned_rows": tomb,
        "replayable_bytes": max(0, replayable),
        "replay_estimate_s": round(max(0, replayable) / rate, 3),
    }


def _cmd_status(args: argparse.Namespace) -> int:
    root = Path(args.dir)
    data = root / "data"
    status = {"data_dir": str(data), "exists": data.exists()}
    for name in ("failures", "patterns", "health"):
        f = data / f"{name}.jsonl"
        status[name] = sum(1 for ln in f.read_text().splitlines() if ln.strip()) if f.exists() else 0
    if data.exists():
        status["durability"] = _durability_posture(data)
    pid = _read_pid(root)
    status["server"] = (
        {"pid": pid, "running": _pid_alive(pid)} if pid else {"pid": None, "running": False}
    )
    if status["server"]["running"]:
        # Tenant plane (docs/robustness.md § multi-tenancy): quota table
        # occupancy + top shed tenants + promotion counts, straight from
        # the live server's /readyz admission block. Best effort — an
        # unreachable server just omits the block.
        try:
            import httpx

            body = httpx.get(args.url + "/readyz", timeout=2.0).json()
            tenants = (body.get("admission") or {}).get("tenants")
            if tenants:
                status["tenants"] = tenants
        except Exception:  # noqa: BLE001 — status reports, never crashes
            pass
    replicas = {}
    for pidp in sorted(root.glob("replica-*.pid")):
        try:
            rpid = int(pidp.read_text().strip())
        except (OSError, ValueError):
            continue
        replicas[pidp.stem] = {"pid": rpid, "running": _pid_alive(rpid)}
    if replicas:
        status["replicas"] = replicas
        # Sharded ownership: per-replica owned/standby ranges + resident
        # row split and the acknowledged epoch, straight from /readyz.
        from kakveda_tpu.fleet.supervisor import read_manifest

        manifest = read_manifest(root) or {}
        if (manifest.get("ownership") or {}).get("enabled"):
            import httpx

            ownership = {}
            for rep in manifest.get("replicas", []):
                rid = rep.get("id", "?")
                try:
                    body = httpx.get(rep["url"] + "/readyz", timeout=2.0).json()
                    own = body.get("ownership") or {}
                    ownership[rid] = {
                        "epoch": own.get("epoch"),
                        "owned_arcs": own.get("owned_arcs"),
                        "standby_arcs": own.get("standby_arcs"),
                        "rows": own.get("rows"),
                        "gfkb_count": body.get("gfkb_count"),
                    }
                except (httpx.HTTPError, ValueError):
                    ownership[rid] = {"unreachable": True}
            status["ownership"] = ownership
        auto = (manifest or {}).get("autoscale")
        if auto:
            # Elastic fleet: policy bounds + the tail of the decision
            # ledger (data/scale_log.jsonl — one typed record per
            # autoscaler decision, docs/scale-out.md § elastic fleet).
            block = {"min": auto.get("min"), "max": auto.get("max")}
            try:
                lines = Path(auto.get("scale_log", "")).read_text().splitlines()
                block["decisions"] = len(lines)
                block["last_decisions"] = [
                    json.loads(ln) for ln in lines[-5:] if ln.strip()
                ]
            except (OSError, ValueError):
                block["decisions"] = 0
            status["autoscale"] = block
    print(json.dumps(status, indent=2))
    return 0


def _cmd_compact(args: argparse.Namespace) -> int:
    """Offline failures-log compaction: open the GFKB against the data
    dir, checkpoint + rewrite, print the posture delta. Refuses while a
    recorded server owns the store — the GFKB is single-writer, and a
    live process would keep appending into the pre-swap inode."""
    root = Path(args.dir)
    data = root / "data"
    pid = _read_pid(root)
    if pid and _pid_alive(pid) and not args.force:
        print(
            f"server pid {pid} is running against {data} — stop it first "
            f"(or --force if you know the pid file is stale)",
            file=sys.stderr,
        )
        return 1
    if not (data / "failures.jsonl").exists():
        print(f"nothing to compact: no failures log under {data}")
        return 0
    before = _durability_posture(data)
    # A chip belongs to one process at a time: a maintenance rewrite that
    # initialised the TPU would fail beside a live server, or take the
    # chip a server is about to need. Host work only — pin JAX to the CPU
    # before it is first imported.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from kakveda_tpu.core.config import ConfigStore
    from kakveda_tpu.index.gfkb import GFKB
    from kakveda_tpu.ops.device import setup_compile_cache

    setup_compile_cache()

    dim = args.dim or ConfigStore().embedding_dim()
    kb = GFKB(data_dir=data, capacity=args.capacity, dim=dim)
    try:
        if args.age_ttl > 0:
            aged = kb.age_rows(ttl_s=args.age_ttl)
            print(f"aged out {aged['tombstoned']} rows (ttl {args.age_ttl}s)")
        if args.collapse > 1:
            col = kb.collapse_duplicates(min_cluster=args.collapse)
            print(
                f"collapsed {col['collapsed']} rows across "
                f"{col['clusters']} clusters"
            )
        out = kb.compact()
    finally:
        kb.close()
    after = _durability_posture(data)
    print(
        json.dumps(
            {
                "compact": out,
                "replay_estimate_s": {
                    "before": before["replay_estimate_s"],
                    "after": after["replay_estimate_s"],
                },
                "durability": after,
            },
            indent=2,
        )
    )
    return 0


def _cmd_up(args: argparse.Namespace) -> int:
    root = Path(args.dir)

    if getattr(args, "replica_index", None) is not None:
        # We ARE a fleet replica (spawned by the supervisor): a plain
        # single-process server with its own data dir and pid file beside
        # server.pid (replica-<i>.pid / data/replica-<i>/). Fleet identity
        # (KAKVEDA_REPLICA_ID / _FLEET_SELF / _FLEET_PEERS) arrived in env.
        i = int(args.replica_index)
        try:
            from kakveda_tpu.service.main import run_server
        except ImportError:
            print("the HTTP service layer is not available in this build", file=sys.stderr)
            return 1
        pidp = root / f"replica-{i}.pid"
        root.mkdir(parents=True, exist_ok=True)
        pidp.write_text(str(os.getpid()))
        try:
            return run_server(
                host=args.host,
                port=args.port,
                data_dir=str(root / "data" / f"replica-{i}"),
                dashboard_port=args.dashboard_port or None,
            )
        finally:
            try:
                if int(pidp.read_text().strip()) == os.getpid():
                    pidp.unlink()
            except (OSError, ValueError):
                pass

    pid = _read_pid(root)
    # pid == os.getpid(): we ARE the detached child (the parent recorded
    # our pid before exec'ing us) — not a conflict.
    if pid and pid != os.getpid() and _pid_alive(pid):
        print(f"server already running (pid {pid}); `kakveda-tpu down` first", file=sys.stderr)
        return 1

    if getattr(args, "detach", False):
        # Background mode, the reference's `up` semantics
        # (reference: kakveda_cli/cli.py:104-123 detaches via compose):
        # re-exec the foreground verb with stdout/err into server.log and
        # record the child pid for down/logs.
        import subprocess

        cmd = [
            sys.executable, "-m", "kakveda_tpu.cli", "up",
            "--dir", str(root), "--host", args.host, "--port", str(args.port),
            "--dashboard-port", str(args.dashboard_port),
        ]
        if getattr(args, "replicas", 0):
            cmd += ["--replicas", str(args.replicas),
                    "--port-base", str(args.port_base or 0)]
            if getattr(args, "autoscale", None):
                cmd += ["--autoscale", args.autoscale]
        root.mkdir(parents=True, exist_ok=True)  # fresh --dir: log lives inside
        logf = open(_log_path(root), "ab")
        proc = subprocess.Popen(
            cmd, stdout=logf, stderr=subprocess.STDOUT, start_new_session=True
        )
        _pid_path(root).write_text(str(proc.pid))
        print(f"server starting (pid {proc.pid}); logs: {_log_path(root)}")
        return 0

    if getattr(args, "replicas", 0):
        return _run_fleet(args, root)

    try:
        from kakveda_tpu.service.main import run_server
    except ImportError:
        print("the HTTP service layer is not available in this build", file=sys.stderr)
        return 1
    _pid_path(root).write_text(str(os.getpid()))
    try:
        return run_server(
            host=args.host,
            port=args.port,
            data_dir=str(root / "data"),
            dashboard_port=args.dashboard_port or None,
        )
    finally:
        try:
            if _read_pid(root) == os.getpid():
                _pid_path(root).unlink()
        except OSError:
            pass


def _run_fleet(args: argparse.Namespace, root: Path) -> int:
    """`up --replicas N [--port-base P] [--autoscale MIN:MAX]`: spawn N
    replica servers on P..P+N-1 (per-replica pid/log files, private data
    dirs), wait for readiness, then serve the front router
    (fleet/router.py) on --port. The router supervises: health probes +
    ejection always; process restarts within KAKVEDA_FLEET_RESTARTS — or,
    with --autoscale, the elastic policy loop (fleet/autoscaler.py):
    scale-up on sustained pressure, lossless drain on idle, dead-replica
    replacement (which subsumes the restart duty). Teardown (SIGTERM/exit
    or `kakveda-tpu down`) stops every replica."""
    from aiohttp import web

    from kakveda_tpu.fleet.router import make_router_app
    from kakveda_tpu.fleet.supervisor import FleetSupervisor

    autoscale = None
    if getattr(args, "autoscale", None):
        try:
            mn_s, mx_s = str(args.autoscale).split(":", 1)
            autoscale = (int(mn_s), int(mx_s))
        except ValueError:
            print(f"bad --autoscale {args.autoscale!r} (want MIN:MAX)",
                  file=sys.stderr)
            return 2
        if not (1 <= autoscale[0] <= autoscale[1]):
            print(f"bad --autoscale bounds {autoscale} (want 1 <= min <= max)",
                  file=sys.stderr)
            return 2

    port_base = args.port_base or (args.port + 1)
    sup = FleetSupervisor(
        root, host=args.host, port_base=port_base,
        replicas=args.replicas, router_port=args.port,
    )
    if autoscale is not None:
        sup.autoscale = autoscale  # manifest block for status/doctor
    try:
        sup.start_all()
    except RuntimeError as e:  # more replicas than chips: refused before any spawn
        print(f"fleet: {e}", file=sys.stderr)
        return 1
    _pid_path(root).write_text(str(os.getpid()))
    print(
        f"fleet: {args.replicas} replicas starting on ports "
        f"{port_base}..{port_base + args.replicas - 1} "
        f"(replica-<i>.pid / replica-<i>.log under {root})"
        + (f" autoscale={autoscale[0]}..{autoscale[1]}" if autoscale else "")
    )
    try:
        sup.wait_ready(timeout_s=float(os.environ.get("KAKVEDA_FLEET_READY_S", "240")))
        app = make_router_app(sup.backend_map(), supervisor=sup,
                              autoscale=autoscale)
        print(f"fleet router on http://{args.host}:{args.port}")
        web.run_app(app, host=args.host, port=args.port, print=None)
        return 0
    finally:
        sup.stop_all()
        try:
            if _read_pid(root) == os.getpid():
                _pid_path(root).unlink()
        except OSError:
            pass


def _cmd_down(args: argparse.Namespace) -> int:
    """Stop the server recorded in server.pid (SIGTERM, bounded wait) —
    real process management, matching the operational intent of the
    reference's compose-backed `down` (reference: kakveda_cli/cli.py:124-136)."""
    import signal
    import time

    root = Path(args.dir)
    rc = 0
    pid = _read_pid(root)
    if pid is None:
        print("no server.pid — nothing to stop")
    elif not _pid_alive(pid):
        print(f"stale server.pid (pid {pid} not running); cleaning up")
        _pid_path(root).unlink(missing_ok=True)
    else:
        os.kill(pid, signal.SIGTERM)
        deadline = time.time() + args.timeout
        while _pid_alive(pid) and time.time() < deadline:
            time.sleep(0.2)
        if _pid_alive(pid):
            print(f"pid {pid} did not exit within {args.timeout}s (still running)",
                  file=sys.stderr)
            rc = 1
        else:
            _pid_path(root).unlink(missing_ok=True)
            print(f"stopped (pid {pid})")

    # Fleet sweep: a foreground fleet parent tears its replicas down on
    # exit, but a crashed parent (or a SIGKILL'd router) leaves
    # replica-<i>.pid files behind — stop whatever still runs.
    for pidp in sorted(root.glob("replica-*.pid")):
        try:
            rpid = int(pidp.read_text().strip())
        except (OSError, ValueError):
            pidp.unlink(missing_ok=True)
            continue
        if _pid_alive(rpid):
            os.kill(rpid, signal.SIGTERM)
            deadline = time.time() + args.timeout
            while _pid_alive(rpid) and time.time() < deadline:
                time.sleep(0.2)
            if _pid_alive(rpid):
                print(f"replica pid {rpid} did not exit within {args.timeout}s",
                      file=sys.stderr)
                rc = 1
                continue
            print(f"stopped replica (pid {rpid})")
        pidp.unlink(missing_ok=True)
    (root / "fleet.json").unlink(missing_ok=True)
    return rc


def _cmd_dlq(args: argparse.Namespace) -> int:
    """Inspect / replay the event bus dead-letter queue (data/dlq.jsonl —
    events whose HTTP delivery exhausted its retries or short-circuited on
    an open breaker; docs/robustness.md). ``list`` prints a summary,
    ``replay`` re-POSTs every event and rewrites the file with what still
    fails."""
    dlq = Path(args.dir) / "data" / "dlq.jsonl"
    if args.action == "replay":
        from kakveda_tpu.events.bus import replay_dlq_file

        out = replay_dlq_file(dlq, timeout=args.timeout)
        print(json.dumps(out, indent=2))
        return 0 if out["failed"] == 0 else 1
    # list: per-(topic, url) counts plus the newest error, no event bodies
    # (they can be large and may carry payload data).
    if not dlq.exists():
        print(json.dumps({"path": str(dlq), "events": 0, "entries": []}, indent=2))
        return 0
    groups: dict = {}
    total = 0
    for line in dlq.read_text(encoding="utf-8").splitlines():
        if not line.strip():
            continue
        total += 1
        try:
            rec = json.loads(line)
            key = (rec.get("topic"), rec.get("url"))
            g = groups.setdefault(key, {"count": 0, "last_error": None, "last_ts": 0})
            g["count"] += 1
            if rec.get("ts", 0) >= g["last_ts"]:
                g["last_ts"] = rec.get("ts", 0)
                g["last_error"] = rec.get("error")
        except ValueError:
            groups.setdefault(("<malformed>", None), {"count": 0})["count"] += 1
    print(json.dumps({
        "path": str(dlq),
        "events": total,
        "entries": [
            {"topic": t, "url": u, **g} for (t, u), g in sorted(groups.items(), key=str)
        ],
    }, indent=2))
    return 0


def _cmd_traffic(args: argparse.Namespace) -> int:
    """Record-replay traffic harness (kakveda_tpu/traffic/,
    docs/robustness.md § traffic harness):

    * ``record`` — pull GET /flightrecorder from a live server and convert
      its ``traffic`` ring into a replayable JSONL traffic log.
    * ``replay`` — drive a traffic log (or a named ``--scenario``)
      open-loop against a live server at ``--speed``; prints the replay
      accounting and the SLO report; rc 1 on SLO failure.
    * ``storm`` — hermetic in-process storm drill: private platform +
      admission controller, the composed hot-key-skew + failure-storm
      scenario WITH its chaos timeline (device-loss window, gossiped
      fleet pressure), SLO-gated. The same harness the `storm` bench row
      runs; this verb is the operator-sized version.

    Chaos ``faults`` actions arm `core/faults.py` IN THIS PROCESS — they
    reach a remote server only via its own ``KAKVEDA_FAULTS_TIMELINE``
    env; ``replay --url`` therefore replays traffic faithfully but leaves
    remote fault windows to the server's timeline.
    """
    import asyncio

    from kakveda_tpu import traffic as T

    if args.action == "record":
        import urllib.request

        with urllib.request.urlopen(args.url.rstrip("/") + "/flightrecorder",
                                    timeout=args.timeout) as r:
            payload = json.loads(r.read().decode("utf-8"))
        events = T.from_flightrecorder(payload, seed=args.seed)
        n = T.write_log(args.out, events,
                        meta={"source": args.url, "seed": args.seed})
        print(json.dumps({"out": str(args.out), "events": n}))
        return 0 if n else 1

    async def _replay_against_url(events, chaos=None, notes=None):
        import aiohttp

        base = args.url.rstrip("/")
        async with aiohttp.ClientSession() as sess:
            async def post(path, body):
                async with sess.post(base + path, json=body) as resp:
                    await resp.read()
                    return resp.status

            sc = T.Scenario(name="cli", seed=args.seed, duration_s=0.0,
                            events=events, chaos=chaos or [],
                            notes=notes or {})
            return await T.run_scenario(
                sc, post=post, speed=args.speed,
                max_concurrency=args.max_concurrency,
                timeout_s=args.timeout)

    if args.action == "replay":
        if args.scenario:
            sc = T.make_scenario(args.scenario, seed=args.seed,
                                 duration_s=args.duration)
            events, chaos, notes, slo = sc.events, sc.chaos, sc.notes, sc.slo
        else:
            if not args.log:
                print("replay needs --log or --scenario", file=sys.stderr)
                return 2
            meta, events = T.read_log(args.log)
            chaos, notes, slo = [], {}, T.SLO()
        res = asyncio.run(_replay_against_url(events, chaos, notes))
        import dataclasses

        rep = T.evaluate(dataclasses.replace(slo, recovery_s=None), res)
        print(json.dumps({"replay": res.to_dict(), "slo": rep.to_dict()},
                         indent=2))
        print(rep.summary(), file=sys.stderr)
        return 0 if rep.ok else 1

    # storm: hermetic in-process drill (TestServer — no port, no detach).
    import tempfile

    from aiohttp.test_utils import TestClient, TestServer

    from kakveda_tpu.core import admission as _adm
    from kakveda_tpu.ops.device import setup_compile_cache
    from kakveda_tpu.platform import Platform
    from kakveda_tpu.service.app import make_app

    setup_compile_cache()
    sc = T.make_scenario("storm", seed=args.seed, duration_s=args.duration,
                         gossip_ttl_s=args.gossip_ttl)
    brown = _adm.BrownoutController(enabled=True, enter=0.85, exit=0.5,
                                    dwell_s=0.25)
    # warn sized for DEGRADED throughput: during the device-loss window
    # the queue must absorb the warm-tier drain rate without shedding
    # (warn never sheds is a gate); background at 1 so the mine flood is
    # the sheddable excess.
    adm = _adm.AdmissionController(
        limits={"warn": 64, "ingest": 2, "interactive": 8, "background": 1},
        enabled=True, brownout=brown)
    tmp = Path(tempfile.mkdtemp(prefix="kakveda-traffic-storm-"))

    async def _storm():
        plat = Platform(data_dir=tmp / "data", capacity=1 << 10, dim=256)
        client = TestClient(TestServer(make_app(platform=plat, admission=adm)))
        await client.start_server()
        try:
            async def post(path, body):
                resp = await client.post(path, json=body)
                await resp.read()
                return resp.status

            return await T.run_scenario(
                sc, post=post, speed=args.speed,
                max_concurrency=args.max_concurrency,
                timeout_s=args.timeout, admission=adm)
        finally:
            await client.close()

    res = asyncio.run(_storm())
    rep = T.evaluate(sc.slo, res)
    print(json.dumps({"replay": res.to_dict(), "slo": rep.to_dict()},
                     indent=2))
    print(rep.summary(), file=sys.stderr)
    return 0 if rep.ok else 1


def _cmd_trace(args: argparse.Namespace) -> int:
    """Fetch one trace from a running server / router and render its tree.

    Against a router the id scatter-assembles across the fleet (GET
    /trace/{id} merges every replica's ring); against a single replica it
    is that process's ring only. Prints the ASCII tree plus per-source
    span counts when present."""
    import urllib.error
    import urllib.request

    url = args.url.rstrip("/") + "/trace/" + args.trace_id
    try:
        with urllib.request.urlopen(url, timeout=args.timeout) as r:
            body = json.loads(r.read().decode("utf-8"))
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"trace fetch failed: {url}: {e}", file=sys.stderr)
        return 1
    spans = body.get("spans") or []
    if not spans:
        print(f"no spans for trace {args.trace_id} at {args.url}")
        return 1
    tree = body.get("tree")
    if not tree:
        from kakveda_tpu.core.trace import render_trace

        tree = render_trace(spans)
    print(tree)
    if body.get("sources"):
        print(json.dumps({"sources": body["sources"]}))
    return 0


def _cmd_logs(args: argparse.Namespace) -> int:
    """Tail server.log (written by `up --detach`), optionally following —
    the reference's `logs` verb over a file instead of docker-compose
    (reference: kakveda_cli/cli.py:167-181)."""
    import time

    root = Path(args.dir)
    logp = _log_path(root)
    if not logp.exists():
        print(f"no log file at {logp} (start with `kakveda-tpu up --detach`)", file=sys.stderr)
        return 1
    lines = logp.read_text(encoding="utf-8", errors="replace").splitlines()
    for ln in (lines[-args.tail :] if args.tail > 0 else []):
        print(ln)
    if not args.follow:
        return 0
    with logp.open("r", encoding="utf-8", errors="replace") as f:
        f.seek(0, os.SEEK_END)
        try:
            while True:
                ln = f.readline()
                if ln:
                    print(ln, end="")
                else:
                    time.sleep(0.5)
        except KeyboardInterrupt:
            pass
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kakveda-tpu", description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("init", help="write default config + create data dir")
    sp.add_argument("--dir", default=".", help="project root")
    sp.add_argument("--force", action="store_true")
    sp.add_argument("--wizard", action="store_true", help="interactive .env setup")
    sp.add_argument("--yes", action="store_true", help="write .env with all defaults, no questions")
    sp.set_defaults(fn=_cmd_init)

    sp = sub.add_parser("up", help="start the platform server")
    sp.add_argument("--dir", default=".")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=8100)
    sp.add_argument("--dashboard-port", type=int, default=8110, help="0 disables the dashboard")
    sp.add_argument("-d", "--detach", action="store_true", help="run in the background (server.pid/server.log)")
    sp.add_argument("--replicas", type=int, default=0,
                    help="spawn N service replicas behind a front router on --port (docs/scale-out.md)")
    sp.add_argument("--port-base", type=int, default=0,
                    help="first replica port (default --port + 1)")
    sp.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                    help="elastic fleet bounds: the router's autoscaler "
                         "scales replicas between MIN and MAX "
                         "(--replicas is the starting count; "
                         "docs/scale-out.md § elastic fleet)")
    # Internal: set by the fleet supervisor on the children it spawns.
    sp.add_argument("--replica-index", type=int, default=None, help=argparse.SUPPRESS)
    sp.set_defaults(fn=_cmd_up)

    sp = sub.add_parser("down", help="stop the server recorded in server.pid")
    sp.add_argument("--dir", default=".")
    sp.add_argument("--timeout", type=float, default=30.0)
    sp.set_defaults(fn=_cmd_down)

    sp = sub.add_parser("status", help="show data-store row counts")
    sp.add_argument("--dir", default=".")
    sp.add_argument("--url", default="http://127.0.0.1:8100",
                    help="live server base URL for the tenant-plane probe")
    sp.set_defaults(fn=_cmd_status)

    sp = sub.add_parser("reset", help="delete local data stores")
    sp.add_argument("--dir", default=".")
    sp.add_argument("--yes", action="store_true")
    sp.set_defaults(fn=_cmd_reset)

    sp = sub.add_parser("logs", help="tail server.log")
    sp.add_argument("--dir", default=".")
    sp.add_argument("-n", "--tail", type=int, default=50)
    sp.add_argument("-f", "--follow", action="store_true")
    sp.set_defaults(fn=_cmd_logs)

    sp = sub.add_parser("dlq", help="inspect / replay the bus dead-letter queue")
    sp.add_argument("action", nargs="?", choices=("list", "replay"), default="list")
    sp.add_argument("--dir", default=".")
    sp.add_argument("--timeout", type=float, default=5.0, help="per-POST replay timeout")
    sp.set_defaults(fn=_cmd_dlq)

    sp = sub.add_parser(
        "traffic",
        help="record / replay traffic logs, run SLO-gated storm drills",
    )
    sp.add_argument("action", choices=("record", "replay", "storm"))
    sp.add_argument("--url", default="http://127.0.0.1:8000",
                    help="server base URL (record/replay)")
    sp.add_argument("--out", default="traffic.jsonl",
                    help="record: output traffic log path")
    sp.add_argument("--log", default=None,
                    help="replay: traffic log to drive")
    sp.add_argument("--scenario", default=None,
                    help="replay: named scenario instead of a log "
                         "(diurnal|hot_key|failure_storm|near_dup|mixed|"
                         "storm|aging)")
    sp.add_argument("--seed", type=int, default=0)
    sp.add_argument("--duration", type=float, default=12.0,
                    help="scenario duration in seconds")
    sp.add_argument("--speed", type=float, default=1.0,
                    help="replay speed factor (2 = twice real time)")
    sp.add_argument("--max-concurrency", type=int, default=None,
                    help="bounded client concurrency "
                         "(default KAKVEDA_TRAFFIC_MAX_CONC)")
    sp.add_argument("--timeout", type=float, default=15.0,
                    help="per-request timeout seconds (hung past this)")
    sp.add_argument("--gossip-ttl", type=float, default=5.0,
                    help="storm: gossip TTL / ladder recovery bound")
    sp.set_defaults(fn=_cmd_traffic)

    sp = sub.add_parser(
        "trace", help="fetch + render one causal trace (router assembles fleet-wide)"
    )
    sp.add_argument("trace_id", help="32-hex trace id (x-request-id of the request)")
    sp.add_argument("--url", default="http://localhost:8000")
    sp.add_argument("--timeout", type=float, default=5.0)
    sp.set_defaults(fn=_cmd_trace)

    sp = sub.add_parser(
        "compact",
        help="offline GFKB lifecycle maintenance: optional aging/collapse, "
             "then checkpoint+delta log compaction (server must be down)",
    )
    sp.add_argument("--dir", default=".")
    sp.add_argument("--capacity", type=int, default=1 << 14,
                    help="GFKB device capacity (match the server's)")
    sp.add_argument("--dim", type=int, default=0,
                    help="embedding dim (0 = from config)")
    sp.add_argument("--age-ttl", type=float, default=0.0,
                    help="tombstone rows idle longer than this many seconds "
                         "before compacting (0 = skip aging)")
    sp.add_argument("--collapse", type=int, default=0,
                    help="collapse mining clusters with ≥ N near-duplicate "
                         "members to one exemplar (0 = skip)")
    sp.add_argument("--force", action="store_true",
                    help="compact even though server.pid looks alive")
    sp.set_defaults(fn=_cmd_compact)

    sp = sub.add_parser("doctor", help="check the runtime environment")
    sp.add_argument("--dir", default=".", help="project root (for .env)")
    sp.add_argument("--url", default="http://127.0.0.1:8100",
                    help="live server base URL for the tenant-plane probe")
    sp.set_defaults(fn=_cmd_doctor)

    sp = sub.add_parser("version", help="print version")
    sp.set_defaults(fn=_cmd_version)

    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    # Apply the wizard-written .env (real environment wins) so the config
    # consumers see what docker compose would; `init` must not load it —
    # it may be about to (re)write the file.
    if args.cmd in ("up", "doctor", "status"):
        from kakveda_tpu.cli.wizard import load_dotenv

        load_dotenv(Path(getattr(args, "dir", ".")) / ".env")
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
