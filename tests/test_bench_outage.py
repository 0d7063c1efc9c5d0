"""bench.py without a chip: non-zero exit, a reason on stderr, no result.

The chip metrics are device numbers; a run that finds no TPU (or cannot
initialise a backend at all) must fail, not print a CPU timing or an
"outage envelope" with exit 0. A single host-plane drill may still be
started on a CPU machine — that is the one exception, and it names itself.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env):
    env = {k: v for k, v in os.environ.items() if not k.startswith("KAKVEDA_BENCH")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, timeout=180,
    )


def test_no_tpu_is_a_failure_with_no_result():
    proc = _run_bench({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout[-500:]
    assert "no TPU" in proc.stderr


def test_chip_metric_on_cpu_is_refused():
    proc = _run_bench({"JAX_PLATFORMS": "cpu", "KAKVEDA_BENCH_METRIC": "warn"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_backend_that_cannot_initialise_is_a_failure_with_no_envelope():
    proc = _run_bench({"JAX_PLATFORMS": "nonexistent"})
    assert proc.returncode != 0
    assert "chip_unavailable" not in proc.stdout
    assert proc.stdout.strip() == ""
    assert "nonexistent" in proc.stderr


def test_unknown_metric_is_refused():
    proc = _run_bench({"JAX_PLATFORMS": "cpu", "KAKVEDA_BENCH_METRIC": "nope"})
    assert proc.returncode == 2
    assert "unknown KAKVEDA_BENCH_METRIC" in proc.stderr
