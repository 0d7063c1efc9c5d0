"""Bring-up contract: the system runs on the chip or says loudly that it does not.

Nothing here needs a chip. The tests pin what a machine WITHOUT one must
see: `chip_smoke.py` fails fast and prints why; its CPU rehearsal walks every
step but prints no result; a device-side failure that is not a loss of the
device is never answered from the host; the compile cache lands where it can
be found again; the peaks table refuses a device it does not know; a replica
fleet larger than the host's chips is refused at launch.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _smoke(args, cwd, script=SMOKE, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # this machine has no TPU; say so quickly
    return subprocess.run(
        [sys.executable, str(script), *args],
        capture_output=True, text=True, cwd=str(cwd), env=env, timeout=timeout,
    )


def test_chip_smoke_without_a_tpu_fails_fast_and_says_why(tmp_path):
    t0 = time.monotonic()
    proc = _smoke([], cwd=tmp_path)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, lone)
    proc = _smoke([], cwd=tmp_path, script=lone)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "checkout" in proc.stderr


def test_chip_smoke_rehearsal_walks_every_step_and_prints_no_result():
    proc = _smoke(["--rehearse-on-cpu"], cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines and all(line.startswith("[REHEARSAL on cpu") for line in lines)
    assert '"ok": true' not in proc.stdout
    for step in ("/ingest/batch", "/warn: match", "/patterns/mine", "/playground/stream",
                 "GFKB replayed", "found in the persistent cache", "kernels vs XLA",
                 "trivial dispatch"):
        assert any(step in line for line in lines), step
    summary = json.loads((ROOT / "chiprun_out" / "chip_smoke" / "summary.json").read_text())
    assert summary["rehearsal"] is True and summary["device"]["platform"] == "cpu"
    assert summary["phases"][1]["cache_hits"] > 0


# --- no fallback that hides the device --------------------------------------


def test_only_loss_of_device_latches_degraded():
    from kakveda_tpu.core import faults
    from kakveda_tpu.core.admission import DeviceHealth

    is_loss = DeviceHealth.is_backend_error
    assert is_loss(RuntimeError("UNAVAILABLE: socket closed"))
    assert is_loss(RuntimeError("DEADLINE_EXCEEDED: device did not answer"))
    assert is_loss(faults.FaultInjected("device.unavailable"))

    class XlaRuntimeError(RuntimeError):  # the type alone must not latch
        pass

    assert not is_loss(XlaRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported shape cast"))
    assert not is_loss(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"))
    assert not is_loss(RuntimeError("the tpu pjrt client said hello"))
    assert not is_loss(faults.FaultInjected("engine.dispatch"))


def test_compile_error_on_warn_is_raised_not_served_from_the_host(tmp_path):
    """A device program that fails for a reason other than losing the device
    reaches the caller; the warn is NOT answered from the host tiers and the
    platform does not latch degraded."""
    from kakveda_tpu.core import admission
    from kakveda_tpu.core.schemas import WarningRequest
    from kakveda_tpu.platform import Platform

    admission.reset_for_tests()
    plat = Platform(data_dir=tmp_path / "data", capacity=256, dim=1024)
    try:
        def refuse(*a, **kw):
            raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

        plat.gfkb.match_batch_info = refuse
        with pytest.raises(RuntimeError, match="Mosaic"):
            plat.warn(WarningRequest(app_id="a", prompt="cite sources", tools=[], env={}))
        assert not admission.get_device_health().degraded
    finally:
        admission.reset_for_tests()


def test_engine_fallback_only_for_allocation_failure(monkeypatch):
    from kakveda_tpu.models import serving
    from kakveda_tpu.models.generate import LlamaRuntime

    def boom(msg):
        def ctor(*a, **kw):
            raise RuntimeError(msg)
        return ctor

    rt = LlamaRuntime()
    monkeypatch.setattr(serving, "ServingEngine", boom("INTERNAL: Mosaic failed to compile"))
    with pytest.raises(RuntimeError, match="Mosaic"):
        rt.engine()
    rt = LlamaRuntime()
    monkeypatch.setattr(serving, "ServingEngine", boom("RESOURCE_EXHAUSTED: out of HBM"))
    assert rt.engine() is None and rt._retired


def test_is_tpu_backend_lets_a_backend_init_failure_raise(monkeypatch):
    import jax

    from kakveda_tpu.ops import device

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        device.is_tpu_backend()


# --- a compile cache that can be placed ---------------------------------------

_CACHE_CHILD = (
    "import os, jax\n"
    "from kakveda_tpu.ops.device import setup_compile_cache\n"
    "print(setup_compile_cache()); print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_dirs(cwd, env_dir=None):
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_CHILD], capture_output=True,
                         text=True, cwd=str(cwd), env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-2:]


def test_compile_cache_same_in_checkout_path_from_any_cwd(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    a = _cache_dirs(tmp_path / "a")
    b = _cache_dirs(tmp_path / "b")
    assert a == b == [str(ROOT / ".jax_cache")] * 2


def test_compile_cache_leaves_the_environment_variable_alone(tmp_path):
    placed = str(tmp_path / "placed")
    returned, configured = _cache_dirs(tmp_path, env_dir=placed)
    assert returned == placed
    assert configured == placed  # JAX read the variable itself; no code set another


# --- peaks, fleets ---------------------------------------------------------------


def test_peaks_table_refuses_an_unknown_device_kind():
    sys.path.insert(0, str(ROOT))
    try:
        import bench
    finally:
        sys.path.pop(0)
    assert bench.device_peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("TPU v9 hypothetical")
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("cpu")


def test_fleet_larger_than_the_hosts_chips_is_refused_at_launch(tmp_path, monkeypatch):
    from kakveda_tpu.fleet import supervisor as sup_mod

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sup_mod, "probe_local_chips", lambda: ("tpu", 1))
    sup = sup_mod.FleetSupervisor(tmp_path / "f", port_base=45000, replicas=2)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="one process per chip"):
        sup.start_all()
    assert time.monotonic() - t0 < 5 and not sup.procs


def test_each_replica_is_given_exactly_one_chip(tmp_path, monkeypatch):
    from kakveda_tpu.fleet import supervisor as sup_mod

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sup_mod, "probe_local_chips", lambda: ("tpu", 4))
    sup = sup_mod.FleetSupervisor(tmp_path / "f", port_base=45000, replicas=4)
    chips = [sup._child_env(i)["TPU_VISIBLE_CHIPS"] for i in range(4)]
    assert chips == ["0", "1", "2", "3"]
    assert sup._child_env(2)["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_cpu_pinned_fleet_takes_no_chip_and_asks_nobody(tmp_path, monkeypatch):
    from kakveda_tpu.fleet import supervisor as sup_mod

    def never():
        raise AssertionError("a CPU-pinned fleet must not probe for chips")

    monkeypatch.setattr(sup_mod, "probe_local_chips", never)
    sup = sup_mod.FleetSupervisor(tmp_path / "f", port_base=45000, replicas=8,
                                  env={"JAX_PLATFORMS": "cpu"})
    assert sup.chips is None
    assert "TPU_VISIBLE_CHIPS" not in sup._child_env(7)
