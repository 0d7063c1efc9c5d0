"""A first-token tail one freeze of the machine cannot move
(``readers/latency_pct`` with ``segments``), and the canary that says whether
the machine froze (``harness/machine.py``)."""

import os
import signal
import time

import pytest

from harness import machine, manifest, stats

latency = manifest.load_module("readers", "latency_pct")
tpot = manifest.load_module("readers", "chat_tpot")
freeze_reader = manifest.load_module("readers", "machine_freeze")

FIRST = {"endpoint": "chat", "field": "first", "q": 95}


def _window(stall_at=None, stall_s=1.5, rate=12.0, seconds=40.0, service_s=0.3, drain=4.0):
    """480 requests due evenly over 40 s, each answered 0.3-0.4 s after it was
    due. A stall of the machine at ``stall_at`` holds every request due inside
    it until it ends, and the backlog it leaves drains over ``drain`` times its
    length: a request due t seconds into the drain is late by what is left of
    the backlog."""
    recs = []
    for j in range(int(rate * seconds)):
        due = 100.0 + j / rate
        ttft = service_s + 0.1 * ((j * 37) % 100) / 100.0
        if stall_at is not None:
            t = due - 100.0 - stall_at
            if 0 <= t < stall_s:
                ttft += stall_s - t
            elif stall_s <= t < stall_s * (1 + drain):
                ttft += stall_s * (1 - (t - stall_s) / (stall_s * drain))
        recs.append({"due": due, "first": due + ttft, "last": due + ttft + 0.9, "done": True, "error": None,
                     "first_chars": 8, "prompt": f"p{j}"})
    return {"streams": {"chat": recs}, "t_start": 100.0, "t_end": 100.0 + seconds, "seconds": seconds,
            "chat_tokens": {r["prompt"]: 64 for r in recs}}


def test_one_segment_is_the_windows_own_percentile():
    ctx = _window()
    xs = [(r["first"] - r["due"]) * 1e3 for r in ctx["streams"]["chat"]]
    assert latency.read(ctx, FIRST) == stats.percentile(xs, 95)
    assert latency.read(ctx, {**FIRST, "segments": 1}) == stats.percentile(xs, 95)
    assert tpot.read(ctx, {"q": 95}) == tpot.read(ctx, {"q": 95, "segments": 1}) == pytest.approx(900 / 56)


@pytest.mark.parametrize("stall_at", [20.0, 3.0, 14.5, 30.0])  # inside a part, at the start, across a border, late
def test_a_stall_moves_the_windows_p95_and_not_the_segment_median(stall_at):
    clean, hit = _window(), _window(stall_at=stall_at)
    seg = {**FIRST, "segments": 5}
    assert latency.read(hit, FIRST) > latency.read(clean, FIRST) + 500  # the 1.5 s stall and its 6 s drain: 90 requests late
    assert latency.read(hit, seg) == pytest.approx(latency.read(clean, seg), rel=0.01)
    assert latency.read(clean, seg) == pytest.approx(latency.read(clean, FIRST), rel=0.01)  # a steady stream: the same tail


def test_every_request_counts_in_exactly_one_part_and_a_failed_one_poisons_its_part():
    ctx = _window()
    recs = ctx["streams"]["chat"]
    seg = {**FIRST, "segments": 5}
    clean = latency.read(ctx, seg)
    for r in recs[100:106]:  # six failed requests among the 96 due in the second part: its p95 falls on one
        r["first"] = None
    assert latency.read(ctx, FIRST) == pytest.approx(latency.read(_window(), FIRST), rel=0.05)  # 6 of 480: under the window's 5 %
    assert latency.read(ctx, seg) == pytest.approx(clean, rel=0.01)  # one part of five reads "no value": the median stands
    for r in recs[200:206] + recs[300:306]:
        r["first"] = None
    assert latency.read(ctx, seg) is None  # three parts of five have no value: neither has the metric
    parts = [0] * 5
    for r in recs:
        parts[min(4, int((r["due"] - 100.0) / 40.0 * 5))] += 1
    assert parts == [96] * 5


def test_fewer_requests_than_parts_give_no_value():
    ctx = _window()
    ctx["streams"]["chat"] = ctx["streams"]["chat"][:4]  # all due in the first part
    assert latency.read(ctx, {**FIRST, "segments": 5}) is None
    assert latency.read(ctx, FIRST) is not None
    assert stats.segment_median([1.0, 2.0, 3.0, 4.0], [0.5, 1.5, 2.5, 3.5], 50, 4, 0.0, 4.0) == 2.5  # an even count: the mean of the middle two
    assert stats.segment_median([], [], 95, 5, 0.0, 40.0) is None


# --- the canary -----------------------------------------------------------------------


def test_the_canarys_gaps_reduce_to_what_lay_inside_the_window(tmp_path):
    f = tmp_path / "canary.txt"
    f.write_text("90.000000 0.200000\n"      # before the window: not counted
                 "99.900000 0.300000\n"      # across its start: 0.2 s inside
                 "120.000000 1.510000\n"     # the freeze
                 "139.950000 0.100000\n"     # across its end: 0.05 s inside
                 "150.000000 0.1")           # a line cut short by the canary's end: left out
    gaps = machine.read_gaps(f)
    assert len(gaps) == 4
    out = machine.reduce(gaps, 100.0, 140.0)
    assert out["machine_freeze_ms"] == pytest.approx(200 + 1510 + 50)
    assert out["machine_freeze_max_ms"] == pytest.approx(1510) and out["machine_freezes"] == 3
    assert machine.reduce([], 100.0, 140.0) == {"machine_freeze_ms": 0, "machine_freeze_max_ms": 0.0, "machine_freezes": 0}
    assert machine.read_gaps(tmp_path / "none.txt") == []
    assert freeze_reader.read({"machine": out}, {"stat": "sum"}) == pytest.approx(1760)
    assert freeze_reader.read({"machine": out}, {"stat": "max"}) == pytest.approx(1510)
    assert freeze_reader.read({}, {"stat": "sum"}) is None  # no canary ran: nothing to read


def test_a_held_canary_writes_the_gap_down_and_the_freezer_lets_everything_go_on(tmp_path):
    """The two children for real: the freezer stops the canary for 0.4 s, as
    ``run.py --freeze`` has it stop server, load generator and canary."""
    f = tmp_path / "canary.txt"
    canary = machine.start("canary", f)
    try:
        time.sleep(0.3)
        t0 = time.monotonic()
        freezer = machine.start("freeze", t0 + 0.1, 0.4, "", canary.pid)
        assert freezer.wait(timeout=10) == 0
        time.sleep(0.3)
        assert canary.poll() is None  # let go on, not killed
        inside = machine.reduce(machine.read_gaps(f), t0, time.monotonic())
        assert 350 <= inside["machine_freeze_max_ms"] <= 1500
        # ended early (SIGTERM), the freezer still lets them go on
        freezer = machine.start("freeze", time.monotonic(), 30.0, "", canary.pid)
        time.sleep(0.5)
        machine.stop(freezer)
        n = len(machine.read_gaps(f))
        time.sleep(0.3)
        os.kill(canary.pid, signal.SIGSTOP)
        time.sleep(0.2)
        os.kill(canary.pid, signal.SIGCONT)
        time.sleep(0.3)
        assert len(machine.read_gaps(f)) > n  # it ran on after the early end, and saw this gap
    finally:
        machine.stop(canary)
    assert canary.poll() is not None
