"""Replica process lifecycle — spawn, watch, tear down.

``cli up --replicas N --port-base P`` (and the fleet bench / chaos drill)
drive fleets through this one class so the file conventions stay uniform
with the single-process server (cli/main.py):

    <root>/replica-<i>.pid     child pid (written by the child itself,
                               like server.pid)
    <root>/replica-<i>.log     child stdout/stderr
    <root>/data/replica-<i>/   the child's private data_dir (per-host
                               GFKB data-dir invariant — replicas must
                               never share an append log)
    <root>/fleet.json          manifest {router_port, replicas:[{id,url,…}]}
                               read by `cli doctor` / `cli status`

Each child is a plain single-process server (``cli up --replica-index i``)
with its fleet identity in env: ``KAKVEDA_REPLICA_ID``,
``KAKVEDA_FLEET_SELF``, ``KAKVEDA_FLEET_PEERS`` — the service app wires
gossip + replication from those (service/app.py).

One process per chip: a TPU chip belongs to one process at a time, so
unless the children are pinned off the accelerator (``JAX_PLATFORMS`` set
and TPU-free — the bench/test fleets) replica ``i`` is given chip ``i`` and
nothing else, and a fleet larger than the host's chips is refused at
launch. The supervisor itself never touches a JAX device: it asks a
throwaway child how many chips there are (:func:`probe_local_chips`).

Teardown is SIGTERM + bounded wait, THEN a bounded SIGKILL escalation
(``KAKVEDA_FLEET_STOP_KILL_S``); a killed process frees its chip.
"""

from __future__ import annotations

import functools
import json
import logging
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional

log = logging.getLogger("kakveda.fleet")


def pick_port_base(n: int, host: str = "127.0.0.1") -> int:
    """Find a base port with ``n`` consecutive free ports — bench/tests
    allocate fleets on ephemeral ranges without clashing."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind((host, 0))
            base = s.getsockname()[1]
        if base + n >= 65535:
            continue
        ok = True
        for p in range(base, base + n):
            with socket.socket() as s:
                try:
                    s.bind((host, p))
                except OSError:
                    ok = False
                    break
        if ok:
            return base
    raise RuntimeError("could not find a free consecutive port range")


def probe_local_chips() -> tuple:
    """(platform, local device count) as JAX reports them — asked in a
    short-lived child, because a parent that initialised JAX would hold
    every chip its replicas need. The child exits before any replica
    starts, which frees them."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.local_devices(); print(d[0].platform, len(d))"],
        capture_output=True, text=True, timeout=300,
    )
    if out.returncode != 0:
        raise RuntimeError(
            "could not count local accelerator chips; JAX said:\n"
            + out.stderr[-2000:]
        )
    platform, count = out.stdout.split()[-2:]
    return platform, int(count)


def _chip_env(i: int) -> Dict[str, str]:
    """libtpu's own variables for 'this process sees chip ``i`` only'."""
    return {
        "TPU_VISIBLE_CHIPS": str(i),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # Each single-chip process runs its own mesh controller; on one
        # host they need distinct ports.
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{8476 + i}",
        "TPU_MESH_CONTROLLER_PORT": str(8476 + i),
    }


class FleetSupervisor:
    """Spawn/supervise/tear down N replica processes under one root."""

    def __init__(
        self,
        root: str | Path,
        *,
        host: str = "127.0.0.1",
        port_base: int,
        replicas: int,
        env: Optional[Dict[str, str]] = None,
        router_port: Optional[int] = None,
    ):
        self.root = Path(root)
        self.host = host
        self.port_base = int(port_base)
        self.n = int(replicas)
        self.extra_env = dict(env or {})
        self.router_port = router_port
        self.procs: Dict[int, subprocess.Popen] = {}
        # Indices drained away by the autoscaler: excluded from the
        # active fleet (backend_map/poll/manifest) and recycled first by
        # add_replica so ports and ring positions stay bounded.
        self.retired: set = set()
        # (min, max) when the fleet runs under an autoscaler — stamped
        # into the manifest so status/doctor know to report scale state.
        self.autoscale: Optional[tuple] = None
        self.root.mkdir(parents=True, exist_ok=True)

    # -- identity --------------------------------------------------------

    def replica_id(self, i: int) -> str:
        return f"r{i}"

    def url(self, i: int) -> str:
        return f"http://{self.host}:{self.port_base + i}"

    def active_indices(self) -> List[int]:
        """Spawned-slot indices minus the retired ones — the fleet."""
        return [i for i in range(self.n) if i not in self.retired]

    def urls(self) -> List[str]:
        return [self.url(i) for i in self.active_indices()]

    def backend_map(self) -> Dict[str, str]:
        """{replica_id: url} — what make_router_app consumes."""
        return {self.replica_id(i): self.url(i) for i in self.active_indices()}

    def pid_file(self, i: int) -> Path:
        return self.root / f"replica-{i}.pid"

    def log_file(self, i: int) -> Path:
        return self.root / f"replica-{i}.log"

    def data_dir(self, i: int) -> Path:
        return self.root / "data" / f"replica-{i}"

    # -- spawn -----------------------------------------------------------

    def cpu_pinned(self) -> bool:
        """Do the children's env pin JAX off the accelerator?"""
        plats = {**os.environ, **self.extra_env}.get("JAX_PLATFORMS", "")
        plats = [p.strip().lower() for p in plats.split(",") if p.strip()]
        return bool(plats) and "tpu" not in plats

    @functools.cached_property
    def chips(self) -> Optional[int]:
        """Local TPU chips to hand out, one per replica; None = children
        do not take a chip (CPU-pinned, or no TPU here). Asked once, on
        the first spawn."""
        if self.cpu_pinned():
            return None
        platform, count = probe_local_chips()
        return count if platform == "tpu" else None

    def _require_chips(self, replicas: int) -> None:
        """Refuse — at once, not after a readiness timeout — more
        chip-taking replicas than this host has chips."""
        if self.chips is not None and replicas > self.chips:
            raise RuntimeError(
                f"{replicas} replicas need {replicas} TPU chips and this host "
                f"has {self.chips}: one process per chip. Start at most "
                f"{self.chips} replicas here, or pin the fleet off the "
                f"accelerator with JAX_PLATFORMS=cpu."
            )

    def _child_env(self, i: int) -> Dict[str, str]:
        env = dict(os.environ)
        # Append the repo root this package was imported from to
        # PYTHONPATH, keeping whatever else is there.
        import kakveda_tpu

        repo = str(Path(kakveda_tpu.__file__).resolve().parents[1])
        parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        if repo not in parts:
            parts.append(repo)
        env["PYTHONPATH"] = os.pathsep.join(parts)
        active = self.active_indices()
        peers = [self.url(j) for j in active if j != i]
        env.update(
            KAKVEDA_REPLICA_ID=self.replica_id(i),
            KAKVEDA_FLEET_SELF=self.url(i),
            KAKVEDA_FLEET_PEERS=",".join(peers),
            # Seed membership for sharded ownership (fleet/ownership.py);
            # inert unless the child also gets KAKVEDA_FLEET_OWNERSHIP=1
            # (usually via extra_env below). Children spawned later by
            # add_replica see the grown membership; earlier children learn
            # it from the epoch'd /fleet/ownership push instead.
            KAKVEDA_FLEET_MEMBERS=",".join(
                f"{self.replica_id(j)}={self.url(j)}" for j in active
            ),
        )
        if self.chips is not None:
            env.update(_chip_env(i))
        env.update(self.extra_env)
        return env

    def start(self, i: int) -> subprocess.Popen:
        """Spawn replica ``i`` detached-ish (own session so a router
        SIGINT doesn't tear the fleet down un-supervised)."""
        self._require_chips(i + 1)
        cmd = [
            sys.executable, "-m", "kakveda_tpu.cli", "up",
            "--dir", str(self.root),
            "--host", self.host,
            "--port", str(self.port_base + i),
            "--dashboard-port", "0",
            "--replica-index", str(i),
        ]
        self.data_dir(i).mkdir(parents=True, exist_ok=True)
        logf = open(self.log_file(i), "ab")
        proc = subprocess.Popen(
            cmd, stdout=logf, stderr=subprocess.STDOUT,
            env=self._child_env(i), start_new_session=True,
        )
        logf.close()
        self.procs[i] = proc
        return proc

    def start_all(self) -> None:
        self._require_chips(self.n)  # before any spawn
        for i in self.active_indices():
            self.start(i)
        self.write_manifest()

    def add_replica(self) -> int:
        """Scale out by one: recycle the lowest retired slot (its port
        and ring position come back) or spawn replica ``n`` on the next
        port, then refresh the manifest. The caller (router
        /fleet/rebalance, autoscaler, bench, drill) still owns the range
        migration — this only creates the process. Returns the index."""
        if self.retired:
            i = min(self.retired)
            self.retired.discard(i)
        else:
            i = self.n
            self.n = i + 1
        self.start(i)
        self.write_manifest()
        return i

    def retire(self, i: int) -> None:
        """Drop a (stopped) replica from the active fleet — the
        autoscaler's scale-down epilogue. The slot recycles via
        add_replica; the data dir stays (its rows were migrated away,
        logs keep their forensic value)."""
        self.retired.add(i)
        self.procs.pop(i, None)
        self.pid_file(i).unlink(missing_ok=True)
        self.write_manifest()

    # -- watch -----------------------------------------------------------

    def alive(self, i: int) -> bool:
        p = self.procs.get(i)
        return p is not None and p.poll() is None

    def poll_dead(self) -> List[int]:
        return [
            i for i in self.active_indices()
            if i in self.procs and not self.alive(i)
        ]

    def wait_ready(self, timeout_s: float = 180.0,
                   only: Optional[Iterable[int]] = None) -> None:
        """Block until every replica's /readyz answers — replica startup
        (jax import + platform build) dominates fleet bring-up. ``only``
        narrows the wait to those indices: the autoscaler waits on JUST
        the replica it spawned, so an unrelated peer dying mid-spawn (the
        flash-crowd crash drill) cannot fail the scale-up."""
        import httpx

        deadline = time.monotonic() + timeout_s
        pending = set(self.active_indices() if only is None else only)
        while pending:
            for i in sorted(pending):
                if not self.alive(i):
                    tail = ""
                    try:
                        tail = self.log_file(i).read_text(errors="replace")[-2000:]
                    except OSError:
                        pass
                    raise RuntimeError(
                        f"replica {i} exited during startup; log tail:\n{tail}"
                    )
                try:
                    r = httpx.get(self.url(i) + "/readyz", timeout=2.0)
                    if r.status_code == 200:
                        pending.discard(i)
                except httpx.HTTPError:
                    pass
            if pending:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"replicas {sorted(pending)} not ready within {timeout_s}s"
                    )
                time.sleep(0.25)

    # -- teardown --------------------------------------------------------

    def stop(self, i: int, timeout_s: float = 20.0, sig: int = signal.SIGTERM) -> None:
        """Signal + bounded wait, then a bounded SIGKILL escalation so a
        wedged replica cannot hang `down`/scale-down forever."""
        p = self.procs.get(i)
        if p is None or p.poll() is not None:
            return
        try:
            p.send_signal(sig)
        except ProcessLookupError:
            return
        try:
            p.wait(timeout=timeout_s)
            return
        except subprocess.TimeoutExpired:
            pass
        grace = 5.0
        try:
            grace = float(os.environ.get("KAKVEDA_FLEET_STOP_KILL_S", "") or 5.0)
        except ValueError:
            pass
        log.warning("replica %d did not exit within %.0fs; escalating to "
                    "SIGKILL (reap grace %.0fs)", i, timeout_s, grace)
        try:
            p.kill()
            p.wait(timeout=max(0.1, grace))
        except ProcessLookupError:
            return
        except subprocess.TimeoutExpired:
            log.warning("replica %d still not reaped %.0fs after SIGKILL",
                        i, grace)

    def stop_all(self, timeout_s: float = 20.0) -> None:
        for i in list(self.procs):
            self.stop(i, timeout_s=timeout_s)
        for i in list(self.procs):
            self.pid_file(i).unlink(missing_ok=True)
        (self.root / "fleet.json").unlink(missing_ok=True)

    # -- manifest --------------------------------------------------------

    def write_manifest(self) -> None:
        manifest = {
            "router_port": self.router_port,
            "host": self.host,
            "port_base": self.port_base,
            "ownership": {
                "enabled": self.extra_env.get("KAKVEDA_FLEET_OWNERSHIP")
                == "1"
                or os.environ.get("KAKVEDA_FLEET_OWNERSHIP") == "1",
                "replication": int(
                    self.extra_env.get("KAKVEDA_FLEET_REPLICATION")
                    or os.environ.get("KAKVEDA_FLEET_REPLICATION", "2")
                    or 2
                ),
            },
            "replicas": [
                {
                    "id": self.replica_id(i),
                    "url": self.url(i),
                    "pid_file": str(self.pid_file(i)),
                    "log_file": str(self.log_file(i)),
                    "data_dir": str(self.data_dir(i)),
                }
                for i in self.active_indices()
            ],
        }
        if self.autoscale is not None:
            manifest["autoscale"] = {
                "min": int(self.autoscale[0]),
                "max": int(self.autoscale[1]),
                "scale_log": str(self.root / "data" / "scale_log.jsonl"),
            }
        (self.root / "fleet.json").write_text(json.dumps(manifest, indent=2))


def read_manifest(root: str | Path) -> Optional[dict]:
    """The fleet manifest written at spawn, or None (single-process)."""
    p = Path(root) / "fleet.json"
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError):
        return None
