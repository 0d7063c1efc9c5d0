"""The verdict tail of a /warn batch (pipeline/warning.py): the citation
pattern's id, the similarity threshold and the default action are read from
the live state once a batch, in O(1): one dict read under the index lock (no
copy of the pattern's failure ids) and one ``stat`` of the config file.
Nothing is kept from one batch to the next, so nothing can go stale."""

import os

import pytest
import yaml

from kakveda_tpu.core import admission, faults
from kakveda_tpu.core.config import ConfigStore
from kakveda_tpu.core.fingerprint import signature_text
from kakveda_tpu.core.schemas import Severity, WarningRequest
from kakveda_tpu.index.gfkb import GFKB
from kakveda_tpu.pipeline.classifier import HALLUCINATION_CITATION
from kakveda_tpu.pipeline.warning import _CITATION_PATTERN_NAME, WarningPolicy

CITATION_PROMPT = "Summarize this document and include citations even if not provided."
TIMEOUT_PROMPT = "Call the billing tool and retry until it answers."
N_IDS = 4000


def _req(prompt):
    return WarningRequest(app_id="app-A", prompt=prompt, tools=[], env={"os": "linux"})


REQS = [_req(CITATION_PROMPT), _req(TIMEOUT_PROMPT), _req("An unrelated question about the weather.")]


def _write(path, threshold, action):
    path.write_text(yaml.safe_dump({
        "failure_matching": {"similarity_threshold": threshold},
        "warning_policy": {"default_action": action},
    }))


@pytest.fixture
def kb(tmp_path):
    g = GFKB(data_dir=tmp_path / "data", capacity=64, dim=1024)
    for ftype, prompt in ((HALLUCINATION_CITATION, CITATION_PROMPT), ("TOOL_TIMEOUT", TIMEOUT_PROMPT)):
        g.upsert_failure(
            failure_type=ftype,
            signature_text=signature_text(prompt, [], {"os": "linux"}),
            app_id="app-A",
            impact_severity=Severity.medium,
            resolution="fix",
        )
    yield g
    g.close()


@pytest.fixture
def policy(kb, tmp_path):
    cfg = tmp_path / "config.yaml"
    _write(cfg, 0.8, "warn")
    return WarningPolicy(kb, ConfigStore(cfg))


def _upsert_patterns(kb, names):
    """Each pattern names a few thousand failures, as an aged index's does."""
    for n, name in enumerate(names):
        kb.upsert_pattern(
            name=name,
            failure_ids=[f"F-{n}-{i:05d}" for i in range(N_IDS)],
            affected_apps=["app-A", f"app-{n}"],
        )


def _scan(kb):
    """What the tail computed before: a scan over list_patterns()' copies."""
    return next((p.pattern_id for p in kb.list_patterns() if p.name == _CITATION_PATTERN_NAME), None)


@pytest.mark.parametrize(
    "names, expected",
    [
        ((), None),
        (("Tool timeouts under retry",), None),
        ((_CITATION_PATTERN_NAME,), "FP-0001"),
        (("Tool timeouts under retry", _CITATION_PATTERN_NAME, "Prompt injection via tool output"), "FP-0002"),
    ],
    ids=["no-pattern", "other-pattern-only", "citation-alone", "citation-among-several"],
)
def test_pattern_id_on_citation_matches_is_what_the_scan_over_list_patterns_gives(kb, policy, names, expected):
    _upsert_patterns(kb, names)
    assert kb.pattern_id(_CITATION_PATTERN_NAME) == _scan(kb) == expected
    citation, timeout, unrelated = policy.warn_batch(REQS)
    assert citation.references and citation.references[0].failure_type == HALLUCINATION_CITATION
    assert citation.pattern_id == expected
    # the id rides on citation matches alone
    assert timeout.references and timeout.pattern_id is None
    assert unrelated.pattern_id is None


def test_pattern_id_survives_a_replay_of_the_log(kb, tmp_path):
    _upsert_patterns(kb, ("Tool timeouts under retry", _CITATION_PATTERN_NAME))
    kb.close()
    again = GFKB(data_dir=tmp_path / "data", capacity=64, dim=1024)
    try:
        assert again.pattern_id(_CITATION_PATTERN_NAME) == "FP-0002"
        assert again.pattern_id("never upserted") is None
    finally:
        again.close()


def test_a_pattern_upserted_between_two_batches_is_carried_by_the_second(kb, policy):
    assert policy.warn_batch(REQS)[0].pattern_id is None
    _upsert_patterns(kb, (_CITATION_PATTERN_NAME,))
    assert policy.warn_batch(REQS)[0].pattern_id == "FP-0001"
    # growing the pattern changes nothing a verdict carries
    kb.upsert_pattern(name=_CITATION_PATTERN_NAME, failure_ids=["F-new"], affected_apps=["app-Z"])
    assert policy.warn_batch(REQS)[0].pattern_id == "FP-0001"


def test_a_batch_copies_no_pattern_and_stats_the_config_once(kb, policy, monkeypatch):
    _upsert_patterns(kb, (_CITATION_PATTERN_NAME,))
    policy.warn_batch(REQS)  # the first read parses the file

    def no_copy():
        raise AssertionError("warn_batch copied every pattern's failure ids")

    monkeypatch.setattr(kb, "list_patterns", no_copy)
    cfg = os.fspath(policy.config.path)
    real_stat, stats = os.stat, []

    def counting_stat(path, *a, **kw):
        if not isinstance(path, int) and os.fspath(path) == cfg:
            stats.append(path)
        return real_stat(path, *a, **kw)

    monkeypatch.setattr(os, "stat", counting_stat)
    for batch in range(1, 4):
        assert policy.warn_batch(REQS)[0].pattern_id == "FP-0001"
        assert len(stats) == batch  # at most one a batch, and hot reload needs that one


def test_a_config_edit_between_two_batches_judges_the_second(kb, policy):
    first = policy.warn_batch(REQS)
    assert [r.action for r in first] == ["warn", "warn", "warn"]
    assert first[0].references and not first[2].references
    cfg = policy.config.path
    _write(cfg, 0.8, "block")
    os.utime(cfg, ns=(1, 1))  # whatever the clock's grain, the mtime differs
    second = policy.warn_batch(REQS)
    assert [r.action for r in second] == ["block", "block", "warn"]
    # a threshold no score reaches: the same matches are now below it
    _write(cfg, 1.5, "block")
    os.utime(cfg, ns=(2, 2))
    third = policy.warn_batch(REQS)
    assert [r.action for r in third] == ["warn", "warn", "warn"]
    assert not third[0].references and third[0].confidence == second[0].confidence
    cfg.unlink()  # a missing file is the defaults, on the next read
    assert [r.action for r in policy.warn_batch(REQS)] == ["warn", "warn", "warn"]
    assert policy.warn_batch(REQS)[0].references


def test_the_degraded_fallback_has_the_same_tail(kb, policy):
    _upsert_patterns(kb, ("Tool timeouts under retry", _CITATION_PATTERN_NAME))
    hot = policy.warn_batch(REQS)
    faults.arm("device.unavailable:1:-1")  # the probe keeps failing too
    try:
        assert admission.get_device_health().note_failure(
            faults.FaultInjected("device.unavailable"), where="test")
        cold = policy.warn_batch(REQS)
    finally:
        faults.disarm()
        admission.reset_for_tests()
    assert all(r.degraded for r in cold) and not any(r.degraded for r in hot)
    assert [r.pattern_id for r in cold] == [r.pattern_id for r in hot] == ["FP-0002", None, None]
    assert [r.action for r in cold] == [r.action for r in hot]
