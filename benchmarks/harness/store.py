"""The failures a deployment already holds, as the append log its server
replays at start.

A configuration's ``gfkb_fill`` stored failures are written to
``<data dir>/failures.jsonl`` before the server is started: one record a
failure, field for field what the program's own ingest appends for that trace
(looked at on a log the program wrote, PERF.md section 4), made from
``--seed``; and to ``patterns.jsonl`` the one pattern that ingest keeps for
failures of this type, naming every one of them (``/warn`` looks it up for
each batch it answers). ``run_server`` then does what it does after any
restart: it reads the logs, validates each record, featurizes every signature
with its own featurizer and inserts the rows on the device in bulk. Nothing of the program
is imported here and no embedding is handed over: the rows on the device are
the program's own work.

Through ``/ingest/batch`` the default server stores ~500 failures a second
(the dashboard's run store is on that path), which held the fill to 8,192 rows
of the 1,048,576; the replay takes 262,144 in about the time that took.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import time
from pathlib import Path

from . import reference_gfkb as ref
from . import textgen

_TS = "2023-11-14T22:13:20Z"  # 1_700_000_000, the corpus's epoch: every record the same age
_ROOT_CAUSE = "Model produced citations without provided sources"
_RESOLUTION = "Ask model to explicitly say 'no sources available' when none are provided"


def record(corpus: textgen.Corpus, i: int) -> dict:
    """Stored failure ``i`` as the program's ingest logs the trace ``corpus.trace(i)``."""
    t = corpus.trace(i, corpus.stored_length(i))
    return {
        "failure_id": f"F-{i + 1:04d}", "version": 1, "created_at": _TS, "updated_at": _TS,
        "failure_type": "HALLUCINATION_CITATION", "root_cause": _ROOT_CAUSE,
        "context_signature": {"prompt_shape": t["prompt"][:200], "model": None, "tools": t["tools"], "env": t["env"]},
        "impact_severity": "medium", "resolution": _RESOLUTION, "occurrences": 1, "affected_apps": [t["app_id"]],
        "signature_text": ref.signature_text(t["prompt"], t["tools"], sorted(t["env"])),
    }


def _lines(job) -> str:
    seed, start, stop = job
    corpus = textgen.Corpus(seed)
    return "".join(json.dumps(record(corpus, i), separators=(",", ":")) + "\n" for i in range(start, stop))


def workers() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def pattern(n: int, apps: int = 2) -> dict:
    """The citation pattern as ingest leaves it after failures 0..n-1."""
    return {"pattern_id": "FP-0001", "name": "Citation hallucination without sources", "created_at": _TS,
            "failure_ids": [f"F-{i + 1:04d}" for i in range(n)], "affected_apps": [f"app-{a}" for a in range(min(apps, n))],
            "description": "Same prompt pattern causes hallucinated citations across apps"}


def write_failure_log(data_dir: Path, seed: int, n: int) -> float:
    """Write failures 0..n-1 (ids F-0001..) to ``data_dir/failures.jsonl`` and
    their pattern to ``patterns.jsonl``; worker processes each make their
    share from the seed. Returns the seconds."""
    t0 = time.perf_counter()
    data_dir.mkdir(parents=True, exist_ok=True)
    step = 8192
    jobs = [(seed, s, min(n, s + step)) for s in range(0, n, step)]
    with open(data_dir / "failures.jsonl", "w") as f:
        if len(jobs) == 1:
            f.write(_lines(jobs[0]))
        else:
            with mp.get_context("spawn").Pool(min(workers(), len(jobs))) as pool:
                for part in pool.imap(_lines, jobs):
                    f.write(part)
    (data_dir / "patterns.jsonl").write_text(json.dumps(pattern(n), separators=(",", ":")) + "\n")
    return time.perf_counter() - t0
