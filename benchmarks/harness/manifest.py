"""BENCHMARK.json and the data files a cell names: one place that finds them.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
per-layer metrics name themselves. Each is a file found by that name:

    benchmarks/configs/<config>.json     (the path BENCHMARK.json gives as ``file``)
    benchmarks/traffic/<traffic>.json    (streams; each names an endpoint, a loop and its arrivals)
    benchmarks/limits/<cell>.json        (what ``correct`` holds this cell to)
    benchmarks/metrics/<metric>.json     (names a reader under benchmarks/readers/)

and what a stream or a metric needs in code is a module found by name as well:

    benchmarks/endpoints/<endpoint>.py   (bodies, one call, warm-up, failed, check)
    benchmarks/loops/<loop>.py           (open: sent at due times whatever has returned)
    benchmarks/arrivals/<arrivals>.py    (the due times)
    benchmarks/readers/<reader>.py       (a metric from records, /metrics or the trace)

A configuration that has a model names its family (``"family": "<name>"``):

    benchmarks/families/<family>.py      (check, build, reference_logits, work: the only file
                                          that knows an architecture's keys or the program's
                                          constructors for it)

so a later PR adds a cell, a configuration, a model family, a mix, an endpoint,
an arrival kind or a metric by adding files and one entry, and edits nothing
that is here.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class ManifestError(Exception):
    """BENCHMARK.json, or a file it names, is missing or inconsistent."""


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise ManifestError(f"{what}: no file {path}")
    try:
        obj = json.loads(path.read_text())
    except ValueError as e:
        raise ManifestError(f"{what}: {path} is not JSON ({e})") from e
    if not isinstance(obj, dict):
        raise ManifestError(f"{what}: {path} must hold a JSON object")
    return obj


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """The module ``benchmarks/<kind>/<name>.py`` (endpoints, loops, arrivals, readers, families)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"{kind}: no file {path}")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(config: dict, bench_dir: Path = BENCH_DIR):
    """The family module of a configuration that has a model (``"family"``)."""
    return load_module("families", config["family"], bench_dir)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file as run
    traffic: dict         # the traffic mix
    limits: dict          # {number: limit}: what ``correct`` holds the cell to
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list       # (BENCHMARK.json entry, metrics/<name>.json descriptor)


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json", "benchmark")


def _applies(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_cell(name: str, root: Path = ROOT, bench_dir: Path | None = None) -> Cell:
    """Resolve one cell and every file it needs; refuse a cell whose files are
    missing, before any process is started."""
    bench_dir = bench_dir or (root / "benchmarks")
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm.get("workloads", [])}
    if name not in cells:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm.get("configs", [])}
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names unknown config {w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"], f"config {w['config']!r}")
    if config.get("family"):  # a configuration that has a model: its family refuses what it cannot build
        try:
            load_family(config, bench_dir).check(config)
        except ValueError as e:
            raise ManifestError(f"config {w['config']!r}: {e}") from e
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json", f"traffic {w['traffic']!r}")
    if not traffic.get("streams"):
        raise ManifestError(f"traffic {w['traffic']!r} has no streams")
    for sp in traffic["streams"]:
        for kind, key in (("endpoints", "endpoint"), ("loops", "loop"), ("arrivals", "arrivals")):
            if key == "arrivals" and key not in sp:
                continue  # a loop that sends on return, not at due times, names none
            if not (bench_dir / kind / f"{sp.get(key)}.py").is_file():
                raise ManifestError(f"traffic {w['traffic']!r}: a stream's {key} {sp.get(key)!r} has no file under {kind}/")
    # A cell with nothing to hold it to would report correct with nothing
    # compared: refused here, before any process is started.
    limits = _load_json(bench_dir / "limits" / f"{name}.json", f"limits of cell {name!r}").get("limits")
    if not limits:
        raise ManifestError(f"limits of cell {name!r}: the file names no number to compare")
    e2e = [m for m in bm.get("end_to_end", []) if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = []
    for m in bm.get("per_layer", []):
        if not _applies(m, name):
            continue
        if m["moves"] not in e2e_names:
            raise ManifestError(
                f"per-layer metric {m['name']!r} moves {m['moves']!r}, which cell {name!r} does not report")
        desc = _load_json(bench_dir / "metrics" / f"{m['name']}.json", f"metric {m['name']!r}")
        reader = bench_dir / "readers" / f"{desc.get('reader', '')}.py"
        if not reader.is_file():
            raise ManifestError(f"metric {m['name']!r}: no reader {reader}")
        per_layer.append((m, desc))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"], config=config,
        traffic=traffic, limits=limits, end_to_end=e2e, per_layer=per_layer,
    )
