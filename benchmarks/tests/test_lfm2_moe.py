"""The family ``lfm2_moe`` as the benchmark holds it: its work by hand, its
counters on a recorded window (and on a program that lacks them), its seeded
weights, its int8 control judged not correct by the cell's limits, and the
cell rehearsed on the CPU: ``correct`` true, and false with a token altered."""

import json
from pathlib import Path

import numpy as np
import pytest

from harness import correct, manifest, prom
from test_families import _copy, _rehearse

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "judge-lfm2-24b-a2b.json").read_text())
TINY = {**CONFIG, **CONFIG["rehearsal"]["model"]}


def test_work_by_hand():
    fam = manifest.load_module("families", "lfm2_moe")
    d, ff, fe, v = 2048, 11776, 1536, 65536
    conv = d * 3 * d + d * d + 3 * d            # in-projection, out-projection, three taps
    attn = 2 * d * 32 * 64 + 2 * d * 8 * 64     # q and o, k and v
    shared = 6 * conv + 2 * attn + 2 * 3 * d * ff + 6 * d * 64
    pc = fam.param_counts(CONFIG)
    assert (pc["shared"], pc["expert"], pc["head"]) == (shared, 3 * d * fe, d * v)
    # one token, one (query, key) pair, one row of logits: 4 experts a token in each of 6 expert layers
    one = fam.work(CONFIG, "prefill", tokens=1, attended=1, head_rows=1)
    assert one["flops"] == 2 * (shared + 6 * 4 * 3 * d * fe) + 6 * 2 * 3 * d + 4 * 2048 * 2 + 2 * d * v
    assert one["bytes"] == 2 * (shared + d * v + 6 * 64 * 3 * d * fe)  # a prefill reads every expert
    # a chunk of 8 steps that touched 36 experts a layer a step: 0.80 GB outside the experts, 18.87 MB an expert
    step = fam.work(CONFIG, "decode", tokens=96, attended=96 * 200, head_rows=96, steps=8, touched=36)
    assert step["bytes"] == 8 * 2 * (shared + d * v + 6 * 36 * 3 * d * fe)
    assert 0.80e9 < 2 * (shared + d * v) < 0.81e9 and 3 * d * fe * 2 == 18874368
    assert fam.work(CONFIG, "flash_prefill", rows=256)["flops"] == 4 * (256 * 256 / 2) * 32 * 64
    with pytest.raises(KeyError):
        fam.work(CONFIG, "scan")


def test_a_configuration_the_family_cannot_build_is_refused():
    fam = manifest.load_module("families", "lfm2_moe")
    fam.check(CONFIG)
    fam.check(TINY)
    for change, match in (({"layer_types": ["conv"]}, "layer_types"), ({"layer_types": ["conv"] * 7 + ["scan"]}, "unknown"),
                          ({"num_experts_per_tok": 65}, "more experts")):
        with pytest.raises(ValueError, match=match):
            fam.check({**CONFIG, **change})
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        fam.check({k: v for k, v in CONFIG.items() if k != "moe_intermediate_size"})


def _ctx(after: str):
    return {"trace": None, "prom_before": prom.parse('kakveda_serving_chunk_seconds_count{engine="e"} 1000\n'),
            "prom_after": prom.parse('kakveda_serving_chunk_seconds_count{engine="e"} 1533\n' + after)}


def test_the_expert_counters_read_the_window_and_nothing_from_a_program_without_them():
    counter = ('kakveda_moe_experts_touched_count{engine="e"} 25584\n'
               'kakveda_moe_experts_touched_sum{engine="e"} 818688\n')  # mean 32 experts a layer a step
    for name in ("moe_experts_touched", "moe_load_max_over_mean"):
        desc = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        read = manifest.load_module("readers", desc["reader"]).read
        # the parent's program has no such counter: the reader reads nothing and does not raise
        assert read(_ctx(""), desc["params"]) is None
    desc = json.loads((BENCH / "metrics" / "moe_experts_touched.json").read_text())
    assert manifest.load_module("readers", desc["reader"]).read(_ctx(counter), desc["params"]) == 32.0


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 3_000_000_123])
def test_chat_control_int8_matmuls_is_not_correct(seed):
    """The plain reference with both operands of every matmul but the router's
    in int8, put in the program's place and judged by the cell's own limits
    file, as ``test_correct.py`` does for the Mistral family. At the cell's
    depth, layer kinds, 64 experts and top-4, hidden 512 (a size a test run
    holds): the control reads ``logit_gap_mean`` 0.075-0.106 on these seeds on
    the CPU, 0.076-0.103 at the published widths on the chip (PERF.md
    section 2), against the limit 0.040."""
    fam = manifest.load_module("families", "lfm2_moe")
    cfg = {**CONFIG, "hidden_size": 512, "intermediate_size": 2048, "moe_intermediate_size": 256,
           "num_attention_heads": 8, "num_key_value_heads": 2, "vocab_size": 320}
    live = 259
    toks = np.random.default_rng(seed % (1 << 32)).integers(3, live, (4, 128))
    plen = [64] * 4
    lg = np.asarray(fam.reference_logits(seed, cfg, toks, live))
    # greedy tokens of the reference itself stand for a sound server: gap 0
    served = [[int(lg[r, plen[r] - 1].argmax())] for r in range(4)]
    limits = json.loads((BENCH / "limits" / "chat-short-lfm2.json").read_text())["limits"]
    ok, compared = correct.judge({"logit_gap_mean": max(correct.served_gaps(lg, plen, served)), "unserved": 0}, limits)
    assert ok, compared
    ctl = fam.reference_logits(seed, cfg, toks, live, control=True)
    every = [list(map(int, toks[r, plen[r]:])) for r in range(4)]
    gaps = correct.argmax_gaps(lg, ctl, plen, every)
    ok, compared = correct.judge({"logit_gap_mean": sum(gaps) / len(gaps), "unserved": 0}, limits)
    assert not ok, compared


def test_the_cell_and_its_entries():
    cell = manifest.load_cell("chat-short-lfm2")
    assert cell.config["family"] == "lfm2_moe" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    names = {m["name"] for m, _ in cell.per_layer}
    assert {"moe_experts_touched", "moe_load_max_over_mean", "decode_mfu", "prefill_mfu", "device_idle_pct.chat",
            "serve_queue_wait_ms", "serve_chunk_ms"} <= names
    # not given the new cell, each for its reason (PERF.md sections 3 and 7): a fixed count of experts; every
    # Mosaic op; a test of the benchmark's own that holds a phase metric to one cell
    assert not {"decode_roofline", "flash_roofline", "serve_loop_unspanned_pct", "serve_fetch_ms"} & names
    old = manifest.load_cell("chat-short")
    assert not {m["name"] for m, _ in old.per_layer} & {"moe_experts_touched", "moe_load_max_over_mean"}


def test_seeded_weights_and_the_calibrated_bias():
    model = manifest.load_module("families", "lfm2_moe_model")
    seed = 2_500_000_011
    params = model.make_params(seed, TINY)
    again = model.layer_weights(seed, TINY, 2)
    assert np.array_equal(np.asarray(again["we_down"]), np.asarray(params["layers"][2]["we_down"]))
    assert np.array_equal(np.asarray(again["expert_bias"]), np.asarray(params["layers"][2]["expert_bias"]))
    assert "expert_bias" not in params["layers"][0] and "conv_in" in params["layers"][0] and "wq" in params["layers"][1]
    head = model.head_weights(seed, TINY)
    assert np.array_equal(np.asarray(head["lm_head"][:, 40]), np.asarray(head["embed"][40]))  # tied
    assert not np.asarray(head["lm_head"][:, :35]).any() and not np.asarray(head["lm_head"][:, 130:]).any()
    for layer in (1, 2):  # the bias evens out the sample's load: max over mean, before and after
        before, after = model.LOADS[(seed, layer)]
        assert after < 1.05 < before
    other = model.make_params(7, TINY)
    assert not np.array_equal(np.asarray(other["layers"][2]["expert_bias"]), np.asarray(params["layers"][2]["expert_bias"]))


# --- the cell, rehearsed on the CPU -------------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    """A copy whose limit is the rehearsal's own: at the tiny widths (8 experts,
    top-2, d = 128) a route chosen differently in bf16 moves a logit by up to
    1.2, so sound runs read ``logit_gap_mean`` 0.0029-0.0057 on three seeds and
    ``--fault chat_token`` 0.134 (CPU readings; the cell's limit is set from
    chip readings at the published widths, PERF.md section 2)."""
    import os

    root = _copy(tmp_path_factory.mktemp("lfm2"))
    for name in ("kakveda_tpu", "config"):
        os.symlink(BENCH.parent / name, root / name)
    (root / "benchmarks" / "limits" / "chat-short-lfm2.json").write_text(json.dumps(
        {"cell": "chat-short-lfm2", "limits": {"logit_gap_mean": 0.02, "unserved": 0}}))
    return root


def test_the_cell_is_correct_on_the_sound_path(rehearsal_root):
    out = _rehearse(rehearsal_root, "chat-short-lfm2", 2_500_000_211, "")
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0 and out["numbers"]["served_tokens"] > 0


def test_the_cell_with_a_token_altered_is_not_correct(rehearsal_root):
    out = _rehearse(rehearsal_root, "chat-short-lfm2", 2_500_000_211, "chat_token")
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["logit_gap_mean"]["value"] > 0.02
