"""What decides ``correct``, shown to fail.

The controls: the plain reference put in the program's place and computed in
the nearest precision below the one the configuration states (int8 rows for
the bf16 index, int8 matmuls for the bf16 model), judged by the limits of the
cell's own limits file. On the chip they were read at the cells' own sizes
(PERF.md section 2); here at a size a test run can hold.

The faults: ``run.py --rehearse-on-cpu --fault <name>`` skips only the look
for a chip and drives the rest of a run — server, fill, warm-up, window,
references, ``judge`` — with the timed path broken underneath
(``harness/faults.py``), and ``correct`` has to come out false.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from harness import correct, manifest, reference_gfkb as ref, textgen

BENCH = Path(__file__).resolve().parents[1]
warn = manifest.load_module("endpoints", "warn")


def _limits(cell: str) -> dict:
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())["limits"]


# --- warn: the index in int8 where the configuration states bf16 -------------------


def _warn_case(seed: int, stored: int, n: int):
    corpus = textgen.Corpus(seed)
    rows = ref.embed([corpus.stored_item(i) for i in range(stored)])
    kinds = ("near", "near", "intent", "other")
    bodies = [textgen.warn_request(corpus, seed, j, kinds[j % 4], stored, 200 + 37 * j % 600, "app-1") for j in range(n)]
    q = ref.embed([(b["prompt"], b["tools"], sorted(b["env"])) for b in bodies])
    return rows, bodies, q


def _records(bodies, served_scores, threshold):
    return [{"status": 200, "body": b, "res": res}
            for b, res in zip(bodies, warn.answers_from_scores(served_scores, threshold))]


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 3_000_000_123])
def test_warn_control_int8_rows_is_not_correct(seed):
    stored, n = 4096, 256
    rows, bodies, q = _warn_case(seed, stored, n)
    limits = _limits("warn-steady")
    # the stated precision in the program's place: correct
    stated = ref.scores(ref.round_bf16(q), ref.round_bf16(rows))
    numbers = warn.compare(_records(bodies, stated, 0.8), seed, stored, 2048, 0.8, n, stored_rows=rows)
    ok, compared = correct.judge(numbers, limits)
    assert ok, compared
    # the control in the program's place: not correct
    control = ref.scores(ref.round_bf16(q), ref.quantize_rows_int8(rows))
    numbers = warn.compare(_records(bodies, control, 0.8), seed, stored, 2048, 0.8, n, stored_rows=rows)
    ok, compared = correct.judge(numbers, limits)
    assert not ok, compared


# --- chat: the model's matmuls in int8 where the configuration states bf16 ---------


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 3_000_000_123])
def test_chat_control_int8_matmuls_is_not_correct(seed):
    lm = manifest.load_module("families", "mistral")

    cfg = json.loads((BENCH / "configs" / "judge-mistral-7b.json").read_text())
    cfg = {**cfg, **cfg["rehearsal"]["model"], "hidden_size": 512, "intermediate_size": 1792,
           "num_attention_heads": 4, "num_key_value_heads": 1, "num_hidden_layers": 4}
    live = 259
    rng = np.random.default_rng(seed % (1 << 32))
    toks = rng.integers(3, live, (4, 128))
    plen = [64] * 4
    lg = np.asarray(lm.reference_logits(seed, cfg, toks, live))
    # greedy tokens of the reference itself stand for a sound server: gap 0
    served = [[int(lg[r, plen[r] - 1 + k].argmax()) for k in range(1)] for r in range(4)]
    assert max(correct.served_gaps(lg, plen, served)) == 0.0
    ctl = lm.reference_logits(seed, cfg, toks, live, control=True)
    every = [list(map(int, toks[r, plen[r]:])) for r in range(4)]
    gaps = correct.argmax_gaps(lg, ctl, plen, every)
    limits = _limits("chat-short")
    ok, compared = correct.judge({"logit_gap_mean": sum(gaps) / len(gaps), "unserved": 0}, limits)
    assert not ok, compared


# --- the timed path broken underneath ------------------------------------------------


@pytest.mark.parametrize("fault,cell", [
    ("warn_answer", "warn-steady"), ("chat_token", "chat-short")])
def test_a_broken_timed_path_is_not_correct(fault, cell):
    out = _rehearse(cell, 2_500_000_200, fault)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("cell", ["warn-steady", "chat-short"])
def test_the_sound_path_is_correct(cell):
    out = _rehearse(cell, 2_500_000_201, "")
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0


def _rehearse(cell: str, seed: int, fault: str) -> dict:
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", cell, "--seed", str(seed), "--seconds", "3",
           "--rehearse-on-cpu"] + (["--fault", fault] if fault else [])
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip() == "", "a rehearsal prints no result"
    line = [ln for ln in p.stderr.splitlines() if "would have printed: " in ln][-1]
    return json.loads(line.split("would have printed: ", 1)[1])
