"""``readers/series_sum`` on two scrapes recorded from the program's own
``/metrics`` (a CPU run of the service app: 4 ``/warn`` before the first scrape,
48 more in 3 concurrent rounds before the second; buckets left out)."""

import json
import re
from pathlib import Path

import pytest

from harness import manifest, prom

BENCH = Path(__file__).resolve().parents[1]
RECORDED = Path(__file__).resolve().parent / "recorded"
PHASES = "kakveda_host_phase_seconds"


@pytest.fixture(scope="module")
def ctx():
    return {"prom_before": prom.parse((RECORDED / "scrape_before.txt").read_text()),
            "prom_after": prom.parse((RECORDED / "scrape_after.txt").read_text())}


@pytest.fixture(scope="module")
def reader():
    return manifest.load_module("readers", "series_sum")


def by_hand(sample: str) -> float:
    """A sample's delta between the two files, found with nothing of the harness."""
    def value(name):
        m = re.search("^" + re.escape(sample) + r" (\S+)$", (RECORDED / name).read_text(), re.M)
        return float(m.group(1))
    return value("scrape_after.txt") - value("scrape_before.txt")


def phase(name, field=None):
    return {"family": PHASES, "labels": {"phase": name}, **({"field": field} if field else {})}


def test_sums_over_another_series_count(ctx, reader):
    got = reader.read(ctx, {"series": [phase("warn.batcher.handoff"), phase("warn.batcher.resolve")],
                            "over": phase("warn.cycle", "count"), "scale": 1000.0})
    cycles = by_hand(PHASES + '_count{phase="warn.cycle"}')
    want = 1000.0 * (by_hand(PHASES + '_sum{phase="warn.batcher.handoff"}')
                     + by_hand(PHASES + '_sum{phase="warn.batcher.resolve"}')) / cycles
    assert 3 <= cycles <= 6  # 48 requests in 3 rounds of 16, a round now and then in two batches
    assert got == pytest.approx(want, rel=1e-9) and 0 < got < 5


def test_the_share_of_a_cycle_under_no_phase(ctx, reader):
    desc = json.loads((BENCH / "metrics" / "warn_cycle_unspanned_pct.json").read_text())
    children = [sp["labels"]["phase"] for sp in desc["params"]["series"]]
    assert len(children) == 10 and "warn.cycle" not in children and "warn.http" not in children
    got = reader.read(ctx, desc["params"])
    covered = sum(by_hand(f'{PHASES}_sum{{phase="{p}"}}') for p in children)
    want = 100.0 * (1 - covered / by_hand(PHASES + '_sum{phase="warn.cycle"}'))
    assert got == pytest.approx(want, abs=1e-9) and 0 <= got < 5


def test_a_counter_that_did_not_move_reads_zero_and_one_that_did_its_delta(ctx, reader):
    stall = {"family": "kakveda_host_stall_seconds_total", "field": "value"}
    assert reader.read(ctx, {"series": [{**stall, "labels": {"loop": "serve"}}], "scale": 1000.0}) == 0.0
    moved = reader.read(ctx, {"series": [{**stall, "labels": {"loop": "warn"}}], "scale": 1000.0})
    assert moved == pytest.approx(1000.0 * by_hand('kakveda_host_stall_seconds_total{loop="warn"}'))


def test_a_program_without_the_series_reads_nothing(ctx, reader):
    gone = {"family": "kakveda_no_such_seconds", "labels": {"phase": "x"}}
    assert reader.read(ctx, {"series": [gone]}) is None
    assert reader.read(ctx, {"series": [phase("warn.policy")], "over": gone}) is None
    # the serving engine did not run here: its phases are in neither scrape
    assert reader.read(ctx, {"series": [phase("serve.chunk.process")]}) is None
    # a phase the window never entered, beside ones it did, counts nothing
    both = reader.read(ctx, {"series": [phase("warn.policy"), phase("serve.wait")]})
    assert both == pytest.approx(by_hand(PHASES + '_sum{phase="warn.policy"}'))
    old = {"prom_before": {}, "prom_after": {("kakveda_warn_batch_seconds_sum", ""): 1.0}}
    assert reader.read(old, {"series": [phase("warn.cycle")], "over": phase("warn.cycle", "count")}) is None


NEW = ["batcher_wait_ms.warn", "warn_cycle_ms", "warn_collect_ms", "warn_handoff_ms", "warn_featurize_ms",
       "warn_fetch_ms", "warn_assemble_ms", "warn_patterns_ms", "warn_policy_ms", "warn_http_ms",
       "warn_cycle_unspanned_pct", "host_stall_ms.warn", "serve_first_chunk_ms", "serve_dispatch_ms",
       "serve_process_ms", "serve_loop_unspanned_pct", "host_stall_ms.chat", "serve_fetch_ms", "warn_wake_ms"]


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_reads_the_recorded_window_or_nothing(ctx, name):
    """Every metric of the phases has its files, is listed in BENCHMARK.json for
    one cell, and on a /warn-only window reads a number where it is a /warn
    metric and nothing (or a stall counter's 0) where it is the engine's."""
    desc = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
    entry = next(m for m in manifest.load_benchmark()["per_layer"] if m["name"] == name)
    assert entry["workloads"] in (["warn-steady"], ["chat-short"])
    assert (entry["unit"], entry["moves"], entry["layer"]) == (desc["unit"], desc["moves"], desc["layer"])
    v = manifest.load_module("readers", desc["reader"]).read(ctx, desc["params"])
    if entry["workloads"] == ["warn-steady"]:
        assert v is not None and v >= 0
    else:
        assert v in (None, 0.0)
