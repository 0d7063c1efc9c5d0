"""Incremental streaming pattern mining (ops/incremental.py + GFKB wiring).

Covers the contract stack bottom-up: the streaming ClusterState reproduces
the full-sweep partition exactly in the documented graph-equivalence regime
(every row's above-threshold degree ≤ k — property-tested over random
clustered corpora), the GFKB ingest path attaches rows with at most ONE
delta dispatch per batch (ZERO when a warn match already fetched the
neighbors), `KAKVEDA_MINE_INCREMENTAL=0` reproduces the full-sweep-only
behavior bit-for-bit, the cluster state rides the v4 snapshot
checksum-verified (corruption/faults degrade to one full re-mine, NEVER to
desynced labels), and `build_knn_edges` compiles O(log N) times over a
growing corpus thanks to pow2 padding.
"""

import numpy as np
import pytest

from kakveda_tpu.core import faults
from kakveda_tpu.core.schemas import Severity
from kakveda_tpu.index.gfkb import GFKB
from kakveda_tpu.ops.clustering import _KNN_K, _corpus_pad, cluster_embeddings
from kakveda_tpu.ops.incremental import (
    ClusterState,
    delta_topk_dense,
    unpack_topk,
)
from kakveda_tpu.pipeline.patterns import PatternDetector


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# ClusterState vs the full-sweep oracle
# ---------------------------------------------------------------------------


def _clustered_corpus(rng, n_clusters, max_size, dim=64, jitter=0.04):
    """Random well-separated cluster centers, ≤ max_size members each —
    keeps every row's above-threshold degree under the cap so the
    graph-equivalence regime holds by construction (asserted by callers)."""
    rows = []
    for _ in range(n_clusters):
        c = rng.standard_normal(dim)
        c /= np.linalg.norm(c)
        for _ in range(int(rng.integers(1, max_size + 1))):
            w = c + jitter * rng.standard_normal(dim)
            rows.append(w / np.linalg.norm(w))
    order = rng.permutation(len(rows))
    return np.stack(rows).astype(np.float32)[order]


def _stream(vecs, threshold, k, batch=16):
    """The bench streaming arm in miniature: pad the corpus to its pow2
    bucket, stream batches through ONE delta top-k each, fold into a
    ClusterState, and materialize labels."""
    import jax.numpy as jnp

    n, dim = vecs.shape
    P = _corpus_pad(n)
    v_pad = jnp.asarray(
        np.concatenate([vecs, np.zeros((P - n, dim), np.float32)])
        if P != n
        else vecs
    )
    state = ClusterState(threshold=threshold, k=k)
    for s in range(0, n, batch):
        e = min(s + batch, n)
        q = np.zeros((batch, dim), np.float32)
        q[: e - s] = vecs[s:e]
        packed = delta_topk_dense(jnp.asarray(q), v_pad, e, k + 1)
        sims, idx = unpack_topk(packed, e - s)
        for r in range(e - s):
            state.add_row(s + r)
        for r in range(e - s):
            state.attach(s + r, idx[r], sims[r])
    return state


def test_streaming_parity_property_in_degree_cap_regime():
    """Whenever per-row above-threshold degree ≤ k, the incremental
    partition equals the full sweep's EXACTLY — the documented
    graph-equivalence regime, over randomized corpora and insertion
    orders (including rows that bridge earlier-separate groups)."""
    threshold, k = 0.6, 8
    checked = 0
    for seed in range(6):
        rng = np.random.default_rng(seed)
        vecs = _clustered_corpus(rng, n_clusters=7, max_size=6)
        sims = vecs @ vecs.T
        np.fill_diagonal(sims, 0.0)
        degree = (sims >= threshold).sum(axis=1)
        if degree.max() > k:
            continue  # outside the documented regime for this draw
        state = _stream(vecs, threshold, k, batch=int(rng.integers(3, 17)))
        oracle = cluster_embeddings(vecs, threshold=threshold)
        assert np.array_equal(state.labels(), oracle), f"seed {seed}"
        checked += 1
    assert checked >= 4, "property exercised on too few draws"


def test_streaming_merge_of_bridged_groups():
    """A late row similar to two so-far-separate groups merges them —
    unions are lazy (edge set → components at refresh), so the merge
    lands exactly like the full sweep's."""
    rng = np.random.default_rng(3)
    a = rng.standard_normal(64)
    a /= np.linalg.norm(a)
    b = rng.standard_normal(64)
    b /= np.linalg.norm(b)
    mid = (a + b) / np.linalg.norm(a + b)

    def jit(v):
        w = v + 0.03 * rng.standard_normal(64)
        return (w / np.linalg.norm(w)).astype(np.float32)

    vecs = np.stack([jit(a), jit(a), jit(b), jit(b), mid.astype(np.float32)])
    if float(min(mid @ vecs[0], mid @ vecs[2])) < 0.6:
        pytest.skip("bridge row did not clear the threshold for this draw")
    state = _stream(vecs, 0.6, k=8, batch=2)
    labels = state.labels()
    oracle = cluster_embeddings(vecs, threshold=0.6)
    assert np.array_equal(labels, oracle)
    assert len(np.unique(labels)) == 1  # the bridge merged everything


def test_cluster_state_rejects_slot_gaps():
    st = ClusterState(threshold=0.6, k=4)
    st.add_row(0)
    st.add_row(2)  # gap: slot 1 never arrived
    assert st.stale and "non-contiguous" in st.stale_reason


def test_pop_dirty_only_touched_clusters():
    """After a seed (full sweep just emitted everything) only clusters
    touched by later rows are re-emitted."""
    st = ClusterState(threshold=0.9, k=4)
    st.seed(np.zeros(3, np.int32), [("T", f"F-{i}", [f"a{i}"]) for i in range(3)])
    assert st.pop_dirty() == []  # nothing touched since the sweep
    st.add_row(3, "T", "F-3", ["a3"])
    st.attach(3, [0], [0.95])
    dirty = st.pop_dirty()
    assert [d["label"] for d in dirty] == [0]
    assert dirty[0]["n"] == 4 and "F-3" in dirty[0]["fids"]
    assert st.pop_dirty() == []  # drained


# ---------------------------------------------------------------------------
# GFKB wiring: ingest-time attachment, dispatch accounting, parity
# ---------------------------------------------------------------------------


def _mk(tmp_path, **kw):
    kw.setdefault("capacity", 256)
    kw.setdefault("dim", 1024)
    return GFKB(data_dir=tmp_path / "data", **kw)


_CORPUS = [
    # one canonical record shared by two apps (singleton cluster, 2 apps)
    ("HALLUCINATION_CITATION", "intent:citations_required | summarize the quarterly report", "app-A"),
    ("HALLUCINATION_CITATION", "intent:citations_required | summarize the quarterly report", "app-B"),
    # a family of near-identical timeout signatures across apps
    ("TIMEOUT", "timeout while calling payments api attempt 0", "app-A"),
    ("TIMEOUT", "timeout while calling payments api attempt 1", "app-B"),
    ("TIMEOUT", "timeout while calling payments api attempt 2", "app-C"),
    # an unrelated singleton
    ("SCHEMA", "totally different failure shape xyz", "app-D"),
]


def _seed_corpus(g):
    for ftype, sig, app in _CORPUS:
        g.upsert_failure(
            failure_type=ftype, signature_text=sig, app_id=app,
            impact_severity=Severity.medium,
        )


def _label_parity(g, threshold=0.6):
    g.mine_drain()
    _, vecs = g.records_and_embeddings()
    return np.array_equal(g._mine.labels(), cluster_embeddings(vecs, threshold=threshold))


def test_gfkb_ingest_attachment_matches_full_sweep(tmp_path):
    g = _mk(tmp_path)
    _seed_corpus(g)
    assert _label_parity(g)
    info = g.mine_state_info()
    assert info["enabled"] and not info["stale"] and info["covers_all_rows"]
    g.close()


def test_mine_patterns_incremental_equals_full(tmp_path):
    """Same corpus, two GFKBs: patterns emitted by incremental mining are
    byte-identical (name/fids/apps/description) to a forced full sweep."""

    def run(base, mode):
        g = _mk(base)
        det = PatternDetector(g)
        _seed_corpus(g)
        pats, info = det.mine_patterns_ex(0.6, mode)
        g.close()
        return {
            (p.name, tuple(p.failure_ids), tuple(sorted(p.affected_apps)), p.description)
            for p in pats
        }, info

    inc, inc_info = run(tmp_path / "inc", "auto")
    full, full_info = run(tmp_path / "full", "full")
    assert inc_info["mode"] == "incremental" and full_info["mode"] == "full"
    assert inc == full and inc  # identical and non-empty
    assert inc_info["wall_ms"] >= 0 and inc_info["covers_all_rows"]


def test_incremental_mine_reemits_only_dirty_clusters(tmp_path):
    g = _mk(tmp_path)
    det = PatternDetector(g)
    _seed_corpus(g)
    first, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "incremental" and first
    # quiescent corpus → nothing dirty → nothing re-emitted
    again, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "incremental" and again == []
    # one new row dirties exactly its cluster
    g.upsert_failure(
        failure_type="TIMEOUT",
        signature_text="timeout while calling payments api attempt 3",
        app_id="app-E", impact_severity=Severity.medium,
    )
    third, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "incremental"
    assert all("timeout" in p.name.lower() for p in third)
    g.close()


def test_warn_topk_reuse_skips_delta_dispatch(tmp_path):
    """The acceptance criterion: when the warn path already fetched a
    signature's neighbors, ingesting that signature attaches WITHOUT a
    new device dispatch; a cold signature costs exactly one."""
    from kakveda_tpu.parallel.mesh import create_mesh

    g = _mk(tmp_path, mesh=create_mesh("data:1"))
    _seed_corpus(g)
    base = g.mine_delta_dispatches
    sig = "timeout while calling payments api attempt 9"
    g.match(sig)  # pre-flight warn fetches + caches the neighbors
    g.upsert_failure(
        failure_type="TIMEOUT", signature_text=sig, app_id="app-Z",
        impact_severity=Severity.medium,
    )
    assert g.mine_delta_dispatches == base  # reused, zero new dispatches
    assert _label_parity(g)  # and the attachment is still correct
    # cold signature (no warn first): exactly one delta dispatch
    g.upsert_failure(
        failure_type="SCHEMA", signature_text="another unseen failure shape pqr",
        app_id="app-Z", impact_severity=Severity.medium,
    )
    assert g.mine_delta_dispatches == base + 1
    assert _label_parity(g)
    g.close()


def test_incremental_disabled_reproduces_full_behavior(tmp_path, monkeypatch):
    """KAKVEDA_MINE_INCREMENTAL=0: no state, no dispatches, and
    mine_patterns emits exactly what the default path emits."""
    monkeypatch.setenv("KAKVEDA_MINE_INCREMENTAL", "0")
    g = _mk(tmp_path)
    det = PatternDetector(g)
    _seed_corpus(g)
    assert g._mine is None and g.mine_delta_dispatches == 0
    assert g.mine_state_info() == {"enabled": False}
    pats, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "full"
    monkeypatch.delenv("KAKVEDA_MINE_INCREMENTAL")
    g2 = _mk(tmp_path / "on")
    det2 = PatternDetector(g2)
    _seed_corpus(g2)
    pats2, _ = det2.mine_patterns_ex(0.6)
    key = lambda ps: {  # noqa: E731
        (p.name, tuple(p.failure_ids), tuple(sorted(p.affected_apps)), p.description)
        for p in ps
    }
    assert key(pats) == key(pats2)
    g.close()
    g2.close()


def test_threshold_change_full_sweep_then_reseeds(tmp_path):
    g = _mk(tmp_path)
    det = PatternDetector(g)
    _seed_corpus(g)
    assert det.mine_patterns_ex(0.6)[1]["mode"] == "incremental"
    _, info = det.mine_patterns_ex(0.5, "incremental")
    assert info["mode"] == "full" and info["fallback"]  # different graph
    # the sweep re-seeded the baseline at 0.5 → serveable incrementally now
    assert det.mine_patterns_ex(0.5)[1]["mode"] == "incremental"
    g.close()


def test_mine_mode_validation(tmp_path):
    g = _mk(tmp_path)
    with pytest.raises(ValueError):
        PatternDetector(g).mine_patterns(mode="bogus")
    g.close()


# ---------------------------------------------------------------------------
# snapshot v4: cluster labels ride the manifest, checksum-verified
# ---------------------------------------------------------------------------


def test_snapshot_restores_cluster_state(tmp_path):
    g = _mk(tmp_path)
    _seed_corpus(g)
    g.mine_drain()
    labels = g._mine.labels()
    g.snapshot()
    g.close()
    g2 = _mk(tmp_path)
    assert g2.mine_usable(0.6), g2.mine_state_info()
    assert np.array_equal(g2._mine.labels(), labels)
    # and a post-restore ingest keeps attaching incrementally
    g2.upsert_failure(
        failure_type="TIMEOUT", signature_text="timeout while calling payments api attempt 4",
        app_id="app-F", impact_severity=Severity.medium,
    )
    assert _label_parity(g2)
    g2.close()


def test_log_tail_beyond_snapshot_degrades_to_full_remine(tmp_path):
    """Rows appended after the snapshot are unknown to the persisted
    labels: restore must mark the state stale (one full re-mine), never
    serve a partial labeling."""
    g = _mk(tmp_path)
    _seed_corpus(g)
    g.snapshot()
    g.upsert_failure(
        failure_type="SCHEMA", signature_text="tail row after the snapshot",
        app_id="app-T", impact_severity=Severity.medium,
    )
    g.close()
    g2 = _mk(tmp_path)
    assert not g2.mine_usable(0.6)
    det = PatternDetector(g2)
    _, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "full"  # and the sweep re-seeds:
    assert g2.mine_usable(0.6)
    g2.close()


def test_corrupt_cluster_snapshot_degrades_to_full_remine_only(tmp_path):
    """A rotted clusters.npy costs ONE full re-mine — the records/vector
    restore is untouched (no full log replay, no re-embedding)."""
    g = _mk(tmp_path)
    _seed_corpus(g)
    g.mine_drain()
    g.snapshot()
    n = g.count
    g.close()
    cl = tmp_path / "data" / "snapshot" / "clusters.npy"
    cl.write_bytes(cl.read_bytes()[:-7] + b"garbage")
    g2 = _mk(tmp_path)
    assert g2.count == n  # record restore unaffected
    st = g2.mine_state_info()
    assert st["stale"]  # checksum refused the labels (reason may be the
    # restore failure or the post-replay coverage gap — both degrade)
    det = PatternDetector(g2)
    _, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "full"
    assert _label_parity(g2)  # re-seeded, trustworthy again
    g2.close()


# ---------------------------------------------------------------------------
# chaos: the gfkb.mine_state fault site (docs/robustness.md)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_mine_state_fault_on_attach_degrades_not_desyncs(tmp_path):
    """An injected cluster-state failure mid-ingest must (a) not fail the
    ingest, (b) latch the state stale, (c) cost exactly one full re-mine
    — after which incremental service resumes with correct labels."""
    g = _mk(tmp_path)
    det = PatternDetector(g)
    _seed_corpus(g)
    faults.arm("gfkb.mine_state:1:1")
    rec, created = g.upsert_failure(
        failure_type="TIMEOUT", signature_text="timeout while calling payments api attempt 5",
        app_id="app-G", impact_severity=Severity.medium,
    )
    assert created and rec.failure_id  # ingest survived the fault
    st = g.mine_state_info()
    assert st["stale"]
    _, info = det.mine_patterns_ex(0.6, "incremental")
    assert info["mode"] == "full" and info["fallback"]
    assert g.mine_usable(0.6) and _label_parity(g)  # healed via re-seed
    g.close()


@pytest.mark.chaos
def test_mine_state_fault_on_restore_degrades_to_full_remine(tmp_path):
    """Snapshot restore with the fault armed: labels are REFUSED (stale
    state), the vector/record restore is unaffected, and the next mine
    heals with one full sweep — never desynced labels."""
    g = _mk(tmp_path)
    _seed_corpus(g)
    g.mine_drain()
    g.snapshot()
    n = g.count
    g.close()
    faults.arm("gfkb.mine_state:1:1")
    g2 = _mk(tmp_path)
    assert g2.count == n
    st = g2.mine_state_info()
    assert st["stale"] and not g2.mine_usable(0.6)
    det = PatternDetector(g2)
    _, info = det.mine_patterns_ex(0.6)
    assert info["mode"] == "full"
    assert _label_parity(g2)
    g2.close()


# ---------------------------------------------------------------------------
# satellite: pow2 corpus padding keeps build_knn_edges compiles O(log N)
# ---------------------------------------------------------------------------


def test_build_knn_edges_compiles_once_per_pow2_bucket():
    """Growing the corpus across several _BLOCK boundaries inside one
    pow2 bucket must NOT respecialize _block_topk; crossing the bucket
    compiles exactly once more."""
    from kakveda_tpu.ops.clustering import _block_topk, build_knn_edges

    rng = np.random.default_rng(0)

    def corpus(n):
        v = rng.standard_normal((n, 64)).astype(np.float32)
        return v / np.linalg.norm(v, axis=1, keepdims=True)

    _block_topk.clear_cache()
    for n in (1100, 1500, 2047, 2048):  # three 1024-boundaries, one bucket
        build_knn_edges(corpus(n))
    assert _block_topk._cache_size() == 1, _block_topk._cache_size()
    build_knn_edges(corpus(2100))  # crosses into the 4096 bucket
    assert _block_topk._cache_size() == 2
