"""The yardstick's own arithmetic, against cases worked by hand. Run on the
CPU by hand (``python -m pytest benchmarks/tests -q``); outside ``tests/``, so
tier-1's count is untouched."""

import json
import shutil
from pathlib import Path

import pytest

from harness import manifest, peaks, prom, stats, store, textgen, xplane
from harness import reference_gfkb as ref

BENCH = Path(__file__).resolve().parents[1]


# --- arrivals and percentiles -----------------------------------------------------


def test_percentile_nearest_rank():
    xs = list(range(1, 101))  # 1..100
    assert stats.percentile(xs, 50) == 51  # index round(0.5 * 99) = 50 -> value 51
    assert stats.percentile(xs, 95) == 95  # index round(94.05) = 94 -> value 95
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None


def test_poisson_schedule_fills_the_window_and_repeats():
    poisson = manifest.load_module("arrivals", "poisson")
    a = poisson.schedule({}, rate=50, seconds=10)
    assert len(a) == 500 and a == sorted(a) and 0 < a[0] and a[-1] < 10
    assert a == poisson.schedule({"gaps_seed": 0}, rate=50, seconds=10)
    assert a != poisson.schedule({"gaps_seed": 1}, rate=50, seconds=10)
    gaps = [y - x for x, y in zip([0.0] + a, a)]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert 0.8 < var / mean ** 2 < 1.25  # exponential gaps: squared coefficient of variation 1


def test_lengths_spread_evenly_in_an_order_the_traffic_file_draws():
    a = textgen.lengths_for(0, 100, 64, 255, "chat")
    b = textgen.lengths_for(1, 100, 64, 255, "chat")
    assert sorted(a) == sorted(b) and a != b and min(a) == 64 and max(a) == 255
    assert a == textgen.lengths_for(0, 100, 64, 255, "chat")


def test_chat_prompt_has_the_asked_bytes_and_is_ascii():
    c = textgen.Corpus(5)
    for n in (64, 100, 255):
        p = textgen.chat_prompt(c, 5, 3, n)
        assert len(p.encode()) == n and p.isascii()


# --- prometheus text --------------------------------------------------------------


def test_prom_mean_delta():
    before = prom.parse('a_sum{k="x"} 1.0\na_count{k="x"} 2\na_sum{k="y"} 5\na_count{k="y"} 1\n# HELP a\n')
    after = prom.parse('a_sum{k="x"} 4.0\na_count{k="x"} 8\na_sum{k="y"} 5\na_count{k="y"} 1\n')
    assert prom.mean_delta(before, after, "a", k="x") == pytest.approx(0.5)
    assert prom.mean_delta(before, after, "a", k="y") is None  # observed nothing in the window
    assert prom.mean_delta(before, after, "a") == pytest.approx(0.5)


# --- operations and bytes ---------------------------------------------------------


MISTRAL = json.loads((BENCH / "configs" / "judge-mistral-7b.json").read_text())
mistral = manifest.load_module("families", MISTRAL["family"])


def test_mistral_parameter_counts_by_hand():
    pc = mistral.param_counts(MISTRAL)
    # attention: 4096*4096 (q) + 2 * 4096*1024 (k, v) + 4096*4096 (o) = 41,943,040
    # mlp: 3 * 4096 * 14336 = 176,160,768  -> 218,103,808 a layer
    assert pc["per_layer"] == 218_103_808
    assert pc["embed"] == pc["lm_head"] == 131_072_000
    assert pc["total"] == 12 * 218_103_808 + 262_144_000


def test_knn_scan_bytes_by_hand():
    # 1M x 2048 bf16 rows = 4 GiB; 64 dense f32 queries = 512 KiB; 64 x 5 (value, index) pairs
    assert peaks.knn_scan_bytes(1 << 20, 2048, 2, 64, 5) == (1 << 32) + 64 * 2048 * 4 + 64 * 5 * 8
    assert peaks.knn_scan_flops(1 << 20, 2048, 64) == 2 * (1 << 20) * 2048 * 64


def test_lm_forward_flops_by_hand():
    # one decode token attending 100 cached positions
    f = 2 * 12 * 218_103_808 + 4 * 100 * 32 * 128 * 12 + 2 * 131_072_000
    assert mistral.work(MISTRAL, "prefill", tokens=1, attended=100, head_rows=1) == {
        "flops": f, "bytes": 2 * (12 * 218_103_808 + 131_072_000)}
    # a chunk program of 8 steps reads the weights once a step
    assert mistral.work(MISTRAL, "decode", tokens=1, attended=100, head_rows=1, steps=8) == {
        "flops": f, "bytes": 8 * 2 * (12 * 218_103_808 + 131_072_000)}


def test_roofline_and_share():
    least, bound = peaks.roofline_seconds(0, 819e9, "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(1.0)
    least, bound = peaks.roofline_seconds(197e12, 1, "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(1.0)
    assert peaks.share_pct(0.5, 1.0, "x") == pytest.approx(50.0)
    with pytest.raises(ValueError):
        peaks.share_pct(1.2, 1.0, "x")  # over 100 %: a fault in the count
    with pytest.raises(KeyError):
        peaks.device_peaks("TPU v9")


# --- trace reduction --------------------------------------------------------------


def _planes():
    us = 1000
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_match(123)", 0, 100 * us), ("jit_match(123)", 200 * us, 100 * us),
                            ("jit_insert(9)", 400 * us, 50 * us)],
            "XLA Ops": [("fusion.1", 0, 60 * us), ("knn_kernel", 50 * us, 50 * us),  # overlap 10 us
                        ("fusion.1", 200 * us, 100 * us), ("scatter.2", 400 * us, 50 * us)],
        },
        "/host:CPU": {"thread-1": [("gfkb.match.fetch", 90 * us, 120 * us), ("outer", 0, 1000 * us)]},
    }


def test_busy_union_idle_and_program_time_by_hand():
    p = _planes()
    assert xplane.union_ns([(0, 60), (50, 50), (200, 100)]) == 200
    s = xplane.summarize(p)
    assert s["busy_s"] == pytest.approx(250e-6)          # 100 + 100 + 50 us
    assert s["window_s"] == pytest.approx(1000e-6)       # the host span reaches 1 ms
    assert s["programs"]["jit_match(123)"] == [pytest.approx(200e-6), 2]
    assert s["ops"]["knn_kernel"] == [pytest.approx(50e-6), 1, "jit_match(123)"]  # the program it ran in
    assert s["programs"]["jit_insert(9)"] == [pytest.approx(50e-6), 1]
    gaps = dict(xplane.idle_gaps(p))
    # the gap 100-200 us has its middle under gfkb.match.fetch; the others only under "outer"
    assert gaps["gfkb.match.fetch"] == pytest.approx(100e-6)
    assert gaps["outer"] == pytest.approx((100 + 550) * 1e-6)
    assert s["breakdown"]["device_ops"][0][0] == "program:jit_match"


def test_breakdown_names_are_short_and_uncovered_gaps_say_what_ended_before():
    hlo = ("%fused_topk.1 = (f32[1024,8,8]{2,1,0:T(8,128)S(1)}, s32[1024,8,8]{2,1,0}) custom-call(f32[8,2048]{1,0} %pad.2, "
           "bf16[1048576,2048]{1,0} %p), custom_call_target=\"tpu_custom_call\"")
    assert xplane.short_op(hlo) == "%fused_topk.1 custom-call"
    assert xplane.short_op("%while.7 = (s32[], bf16[16,8,2048,128]{3,1,2,0}) while(%tuple), body=%b") == "%while.7 while"
    assert xplane.short_op("plain name") == "plain name"
    us = 1000
    p = {"/device:TPU:0": {"XLA Ops": [(hlo, 0, 100 * us), (hlo, 500 * us, 100 * us)]},
         "/host:CPU": {"python3": [("gfkb.match.fetch", 90 * us, 20 * us)]}}
    assert dict(xplane.idle_gaps(p)) == {"after gfkb.match.fetch": pytest.approx(400e-6)}
    assert xplane.top_device_ops(p)[0] == ["%fused_topk.1 custom-call", pytest.approx(200e-6)]


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        xplane.busy_seconds({"/host:CPU": {"t": [("x", 0, 10)]}})


def test_recorded_trace_from_the_chip():
    """A small trace recorded on a v5e (three runs of ``jit_tiny_step`` under
    a ``bench.small_step`` annotation)."""
    path = BENCH / "tests" / "recorded" / "small.xplane.pb"
    planes = xplane.read_planes(path)
    assert xplane.device_planes(planes) == ["/device:TPU:0"]
    s = xplane.summarize(planes)
    (secs, runs), = [v for k, v in s["programs"].items() if xplane.program_name(k) == "jit_tiny_step"]
    assert runs == 3 and 0 < secs < 0.01
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] >= secs * 0.5
    assert any("bench.small_step" in ev for lines in planes.values() for evs in lines.values() for ev, _, _ in evs)


# --- the manifest -----------------------------------------------------------------


def test_every_cell_of_the_benchmark_resolves():
    bm = manifest.load_benchmark()
    for w in bm["workloads"]:
        cell = manifest.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_a_cell_whose_files_are_missing_is_refused(tmp_path):
    root = tmp_path
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(".*", "__pycache__"))
    assert manifest.load_cell("warn-steady", root=root).name == "warn-steady"
    (root / "benchmarks" / "traffic" / "warn-steady.json").unlink()
    with pytest.raises(manifest.ManifestError, match="traffic"):
        manifest.load_cell("warn-steady", root=root)
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell("nope", root=root)
    (root / "benchmarks" / "metrics" / "knn_roofline.json").unlink()  # a per-layer metric of that cell
    shutil.copy(BENCH / "traffic" / "warn-steady.json", root / "benchmarks" / "traffic" / "warn-steady.json")
    with pytest.raises(manifest.ManifestError, match="knn_roofline"):
        manifest.load_cell("warn-steady", root=root)


def test_a_cell_with_nothing_to_hold_it_to_is_refused(tmp_path):
    """No limits file, or one that names no number: such a cell would report
    ``correct`` with nothing compared. A stream whose endpoint, loop or
    arrivals has no module is refused as well."""
    root = tmp_path
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(".*", "__pycache__"))
    limits = root / "benchmarks" / "limits" / "warn-steady.json"
    limits.write_text(json.dumps({"cell": "warn-steady", "limits": {}}))
    with pytest.raises(manifest.ManifestError, match="names no number"):
        manifest.load_cell("warn-steady", root=root)
    limits.unlink()
    with pytest.raises(manifest.ManifestError, match="limits"):
        manifest.load_cell("warn-steady", root=root)
    shutil.copy(BENCH / "limits" / "warn-steady.json", limits)
    (root / "benchmarks" / "arrivals" / "poisson.py").unlink()
    with pytest.raises(manifest.ManifestError, match="arrivals"):
        manifest.load_cell("warn-steady", root=root)


# --- the stored failures ----------------------------------------------------------


def test_the_stored_log_holds_what_the_reference_embeds(tmp_path):
    """One record a stored failure, ids F-0001.., the signature the reference
    embeds for that failure; the same seed writes the same log, with worker
    processes or without."""
    store.write_failure_log(tmp_path / "a", 5, 40)
    lines = (tmp_path / "a" / "failures.jsonl").read_text().splitlines()
    assert len(lines) == 40
    corpus = textgen.Corpus(5)
    for i in (0, 17, 39):
        rec = json.loads(lines[i])
        assert rec["failure_id"] == f"F-{i + 1:04d}" and rec["occurrences"] == 1
        assert rec["signature_text"] == ref.signature_text(*corpus.stored_item(i))
        assert ref.is_failure(corpus.trace(i, corpus.stored_length(i))["prompt"], "References:\n[1] x")
    assert len({json.loads(ln)["signature_text"] for ln in lines}) == 40
    pat = json.loads((tmp_path / "a" / "patterns.jsonl").read_text())
    assert pat["failure_ids"] == [json.loads(ln)["failure_id"] for ln in lines] and pat["affected_apps"] == ["app-0", "app-1"]
    store.write_failure_log(tmp_path / "b", 5, 40)
    assert (tmp_path / "b" / "failures.jsonl").read_text() == "\n".join(lines) + "\n"


def test_stored_rows_rounded_sparse_equal_the_dense_rows_rounded():
    rows = ref.embed([textgen.Corpus(9).stored_item(i) for i in range(64)])
    assert (ref.embed_stored(9, 64, 2048, row_bytes=2) == ref.round_bf16(rows)).all()
    assert (ref.embed_stored(9, 64, 2048, row_bytes=4) == rows).all()


# --- a program found by what it reads ---------------------------------------------


def test_the_match_program_is_the_lambda_that_reads_the_index():
    """``jit__lambda`` says little: with ``reads``, only the program in which an
    operation takes the index as an operand is the match program."""
    share = manifest.load_module("readers", "program_share")
    us = 1000
    scan = "%fused_topk.1 = (f32[1024,8,8]) custom-call(f32[8,2048]{1,0} %pad.2, bf16[1048576,2048]{1,0:T(8,128)(2,1)} %e.1)"
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit__lambda(1)", 0, 6000 * us), ("jit__lambda(2)", 7000 * us, 3000 * us)],
        "XLA Ops": [(scan, 10 * us, 5900 * us), ("%add.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)", 7000 * us, 3000 * us)]}}
    tr = xplane.summarize(planes)
    # both are named jit__lambda: by name alone they are one program of 9 ms
    assert {xplane.program_name(k) for k in tr["programs"]} == {"jit__lambda"}
    assert tr["ops"][scan][2] == "jit__lambda(1)"
    sizes = {"index_capacity": 1 << 20, "dim": 2048, "row_bytes": 2, "top_k": 5}
    ctx = {"trace": tr, "device": {"kind": "TPU v5 lite"}, "sizes": sizes, "prom_before": {}, "prom_after": {}}
    params = {"program": "^jit__lambda\\(", "work": "knn", "of": "roofline", "reads": "{row_type}[{index_capacity},{dim}]"}
    # 4 GiB + a query and its results over 819 GB/s = 5.24 ms of the 6 ms the reading program took
    assert share.read(ctx, params) == pytest.approx(100 * 5.244e-3 / 6e-3, rel=1e-3)
    other = {**sizes, "index_capacity": 1 << 21}  # no operation reads an index of that shape: nothing to read
    assert share.read({**ctx, "sizes": other}, params) is None


def test_a_probe_metric_reads_the_probe_and_not_the_window():
    stat = manifest.load_module("readers", "loadgen_stat")
    window = [{"due": 0.0, "done": 0.010 + 0.001 * k, "status": 200, "late_s": 0.0} for k in range(100)]
    probe = [{"due": 0.0, "done": 0.050, "status": 200, "late_s": 0.0}, {"due": 0.0, "done": 9.0, "status": 429, "late_s": 0.0}]
    ctx = {"streams": {"warn": window}, "probe": {"warn": probe}}
    params = {"endpoint": "warn", "stat": "latency", "field": "done", "q": 95}
    assert stat.read(ctx, params) == pytest.approx(104.0)                         # the window's own
    assert stat.read(ctx, {**params, "records": "probe"}) == pytest.approx(50.0)  # answered requests only
    assert stat.read({"streams": {"warn": window}, "probe": {}}, {**params, "records": "probe"}) is None


def test_a_shed_warn_is_asked_again_and_timed_from_its_due_time(monkeypatch):
    import asyncio
    import time
    from types import SimpleNamespace

    warn = manifest.load_module("endpoints", "warn")
    answers = [(429, {"error": json.dumps({"ok": False, "retry_after": 0.05, "reason": "overload"})}),
               (429, {"error": "not json"}),  # a 429 whose body says nothing: asked again after a second
               (200, {"action": "warn"})]
    sent = []

    async def post_json(target, path, body):
        sent.append(time.perf_counter())
        status, res = answers[len(sent) - 1]
        return status, res, time.perf_counter()

    monkeypatch.setattr(warn, "post_json", post_json)
    st = SimpleNamespace(wire=[b"{}"], bodies=[{}])
    due = time.perf_counter()
    rec = asyncio.run(warn.call(st, None, 0, due))
    assert rec["status"] == 200 and rec["tries"] == 3 and not warn.failed(rec)
    assert sent[1] - sent[0] >= 0.05 and sent[2] - sent[1] >= 1.0
    assert rec["done"] - rec["due"] >= 1.05  # late by all it waited, not failed
    # a caller gives up GIVE_UP_S after the due time: that request has failed
    sent.clear()
    answers[:] = [(429, {"error": "{}"})]
    rec = asyncio.run(warn.call(st, None, 0, time.perf_counter() - warn.GIVE_UP_S - 1))
    assert rec["status"] == 429 and rec["tries"] == 1 and warn.failed(rec)
