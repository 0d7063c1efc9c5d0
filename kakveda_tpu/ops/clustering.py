"""Device-side clustering of failure embeddings.

Connected components of the threshold cosine-similarity graph. Two tiers:

- **dense** (N ≤ _DENSE_MAX): one [N, N] adjacency + on-device min-label
  propagation to fixpoint — the small-N oracle.
- **kNN graph** (any N): ONE blocked top-k sweep builds a symmetric-union
  k-nearest-neighbor candidate graph (each row keeps its k best neighbors;
  an edge exists when either endpoint keeps the other), edges below the
  threshold are dropped, and connected components run on that sparse graph
  on host. Total device work is O(N²·d_c) for the single sweep — not per
  fixpoint iteration like a dense propagation — with d_c the candidate
  dim: full dim up to _EXACT_SWEEP_MAX rows, a random projection above it
  (candidates from the projection, every surviving edge re-scored at full
  dim, so edge *weights* are always exact; projection only affects which
  candidates are seen).

Graph-equivalence note: the union-kNN graph preserves the dense partition
whenever every row has ≤ k neighbors above threshold (then it IS the
threshold graph). Rows with more neighbors keep their k nearest, and
mutual-kNN chains keep real clusters connected; pathological merges that
hinge on a single pair ranked > k from both sides can split — the
documented approximation that buys 1M-row mining
(the reference's pattern detector is a group-by on failure_type,
services/pattern_detector/app.py:40-47 — no similarity clustering at all).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_DENSE_MAX = 8192
_BLOCK = 1024
# Query rows per device dispatch: each dispatch costs one device→host
# fetch, so bigger blocks amortize it.
_QBLOCK = 4096
_EXACT_SWEEP_MAX = 1 << 17  # full-dim candidate sweep up to 131k rows
_MINE_DIM = 256  # projection dim for the candidate sweep beyond that
_KNN_K = 32
_BIG = jnp.iinfo(jnp.int32).max


@jax.jit
def _propagate_labels(adj: jax.Array) -> jax.Array:
    n = adj.shape[0]
    init = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        labels, changed, it = state
        return jnp.logical_and(changed, it < n)

    def body(state):
        labels, _, it = state
        # min over neighbors' labels (self-edge keeps own label).
        neigh = jnp.where(adj, labels[None, :], _BIG)
        new = jnp.minimum(labels, jnp.min(neigh, axis=1))
        return new, jnp.any(new != labels), it + 1

    labels, _, _ = jax.lax.while_loop(cond, body, (init, jnp.bool_(True), jnp.int32(0)))
    return labels


@partial(jax.jit, static_argnames=("k",))
def _block_topk(q: jax.Array, v: jax.Array, valid: jax.Array, k: int):
    """Streaming top-k of ``q @ v.T`` without materializing [Q, N]: scan
    over column blocks collecting per-block candidates, then one exact
    merge. The per-block select uses ``approx_max_k`` — the TPU-native
    partial-reduce (an exact top-k on other backends); its <1 recall is
    candidate-level only and every surviving edge is exact-rescored by the
    caller. q [Q, d], v [Np, d] (Np multiple of _BLOCK), valid [Np]."""
    nb = v.shape[0] // _BLOCK
    vb = v.reshape(nb, _BLOCK, v.shape[1])
    validb = valid.reshape(nb, _BLOCK)
    bases = (jnp.arange(nb) * _BLOCK).astype(jnp.int32)
    kb = min(k, _BLOCK)

    def scan_fn(_, block):
        vj, okj, base = block
        sims = jax.lax.dot_general(
            q, vj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Q, B]
        sims = jnp.where(okj[None, :], sims, -jnp.inf)
        vals, idx = jax.lax.approx_max_k(sims, kb, recall_target=0.98)
        return None, (vals, (idx + base).astype(jnp.int32))

    _, (ys_v, ys_i) = jax.lax.scan(scan_fn, None, (vb, validb, bases))
    # [nb, Q, kb] -> [Q, nb*kb], exact merge down to k.
    q_rows = q.shape[0]
    flat_v = jnp.transpose(ys_v, (1, 0, 2)).reshape(q_rows, nb * kb)
    flat_i = jnp.transpose(ys_i, (1, 0, 2)).reshape(q_rows, nb * kb)
    bv, sel = jax.lax.top_k(flat_v, min(k, nb * kb))
    bi = jnp.take_along_axis(flat_i, sel, axis=1)
    # Pack (values, indices) into ONE output buffer => one host fetch per
    # dispatch (indices are exact in f32 up to 2^24 rows).
    return jnp.concatenate([bv, bi.astype(jnp.float32)], axis=1)


@partial(jax.jit, static_argnames=())
def _rescore_pairs(v: jax.Array, rows: jax.Array, cols: jax.Array) -> jax.Array:
    """Exact full-dim cosine for candidate pairs (embeddings are unit-norm)."""
    return jnp.sum(v[rows] * v[cols], axis=1)


def _project(v: jax.Array, out_dim: int) -> jax.Array:
    """Fixed-seed Gaussian random projection, re-normalized — preserves
    cosine ranking well enough for CANDIDATE generation (edges are
    re-scored exactly afterwards)."""
    r = jax.random.normal(jax.random.PRNGKey(7), (v.shape[1], out_dim), jnp.float32)
    p = v @ (r / np.sqrt(out_dim))
    return p / jnp.maximum(jnp.linalg.norm(p, axis=1, keepdims=True), 1e-12)


def _corpus_pad(n: int) -> int:
    """Padded corpus length for the blocked sweep: the next power of two
    (≥ _BLOCK). Padding only to the next _BLOCK multiple re-specializes
    ``_block_topk`` on every 1024-row boundary the GFKB crosses — O(N)
    compiles over a growing corpus; pow2 buckets make it O(log N), and the
    pad rows are valid-masked so results are identical. Thin wrapper over
    the ONE blessed bucket seam (``ops/knn.pow2_bucket``)."""
    from kakveda_tpu.ops.knn import pow2_bucket

    return pow2_bucket(n, floor=_BLOCK)


def build_knn_edges(
    vecs: np.ndarray, *, k: int = _KNN_K, threshold: float = 0.6,
    force_projection: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """(rows, cols) of the symmetric-union kNN graph restricted to exact
    cosine ≥ threshold. One blocked sweep; O(N·k) edges out.

    ``force_projection`` activates the random-projection candidate tier
    below its natural _EXACT_SWEEP_MAX switch-over — the recall tests use
    it to observe projection-tier behavior at CI-tractable sizes."""
    v = jnp.asarray(vecs, jnp.float32)
    n, d = v.shape
    kk = min(k + 1, n)  # +1: each row's own top-1 is itself

    exact = (n <= _EXACT_SWEEP_MAX or d <= _MINE_DIM) and not (
        force_projection and d > _MINE_DIM
    )
    vc = v if exact else _project(v, _MINE_DIM)

    total = _corpus_pad(n)  # bucketed corpus length — never size by raw n
    if total != n:
        vc_p = jnp.zeros((total, vc.shape[1]), vc.dtype).at[:n].set(vc)
    else:
        vc_p = vc
    valid = jnp.arange(total) < n

    # Dispatch every query block up front (async), then drain fetches — the
    # device computes block i+1 while the host pulls block i's packed
    # results, so each fetch overlaps compute.
    pending = []
    for start in range(0, n, _QBLOCK):
        stop = min(start + _QBLOCK, n)
        q = vc[start:stop]
        if q.shape[0] < _QBLOCK:  # pad the last block to keep one compile
            q = jnp.concatenate([q, jnp.zeros((_QBLOCK - q.shape[0], q.shape[1]), q.dtype)])
        packed = _block_topk(q, vc_p, valid, kk)
        packed.copy_to_host_async()
        pending.append((start, stop, packed))

    rows_out, cols_out, sims_out = [], [], []
    for start, stop, dev in pending:
        packed = np.asarray(dev)[: stop - start]
        kk_eff = packed.shape[1] // 2  # ≤ kk when the padded index is tiny
        bv_h = packed[:, :kk_eff]
        bi_h = packed[:, kk_eff:].astype(np.int64)
        qi = np.repeat(np.arange(start, stop), kk_eff)
        ci = bi_h.reshape(-1)
        sv = bv_h.reshape(-1)
        keep = (ci != qi) & np.isfinite(sv)
        rows_out.append(qi[keep])
        cols_out.append(ci[keep])
        sims_out.append(sv[keep])

    rows = np.concatenate(rows_out) if rows_out else np.zeros(0, np.int64)
    cols = np.concatenate(cols_out) if cols_out else np.zeros(0, np.int64)
    sims = np.concatenate(sims_out) if sims_out else np.zeros(0, np.float32)

    if not exact:
        # Candidates came from the projection; re-score exactly, in chunks
        # that bound the gather memory (two [chunk, d] f32 gathers live per
        # dispatch — 128k × 2048 ≈ 1 GB each; 1M-pair chunks OOMed a 16 GB
        # chip).
        chunk = 1 << 17
        exact_sims = np.empty_like(sims)
        for s in range(0, len(rows), chunk):
            e = min(s + chunk, len(rows))
            exact_sims[s:e] = np.asarray(
                _rescore_pairs(v, jnp.asarray(rows[s:e]), jnp.asarray(cols[s:e]))
            )
        sims = exact_sims

    keep = sims >= threshold
    return rows[keep], cols[keep]


def _sparse_components(n: int, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """Connected components over an edge list; labels = min member index
    (the dense path's convention)."""
    try:
        from scipy.sparse import coo_matrix
        from scipy.sparse.csgraph import connected_components

        g = coo_matrix((np.ones(len(rows), np.int8), (rows, cols)), shape=(n, n))
        _, comp = connected_components(g, directed=False)
    except ImportError:  # vectorized host label propagation fallback
        comp = np.arange(n, dtype=np.int64)
        # undirected: propagate both ways each sweep
        r = np.concatenate([rows, cols])
        c = np.concatenate([cols, rows])
        while True:
            new = comp.copy()
            np.minimum.at(new, r, comp[c])
            if np.array_equal(new, comp):
                break
            comp = new
        return comp.astype(np.int32)

    mins = np.full(comp.max() + 1 if len(comp) else 0, np.iinfo(np.int64).max)
    np.minimum.at(mins, comp, np.arange(n))
    return mins[comp].astype(np.int32)


def cluster_embeddings(
    vecs: np.ndarray, threshold: float = 0.6, *, knn_k: int = _KNN_K,
    force_projection: bool = False,
) -> np.ndarray:
    """Connected-component labels for L2-normalized embeddings [N, d].

    Returns int32 labels [N]; rows in the same component share a label
    (the smallest member index).
    """
    v = jnp.asarray(vecs, dtype=jnp.float32)
    n = v.shape[0]
    if n == 0:
        return np.zeros(0, np.int32)
    if n <= _DENSE_MAX and not force_projection:
        sims = v @ v.T
        # SAME graph family as the large-N tier: union-top-k edges above
        # the threshold, not the raw threshold graph. The raw graph
        # transitively chains boilerplate-heavy corpora into one giant
        # component (observed: 120 templates → 2 clusters, purity 0.02 at
        # 5k rows, while the degree-capped tier is pure at every larger
        # scale) — so the degree cap is part of the clustering SEMANTICS,
        # scale-invariant across tiers, not an approximation artifact.
        k = min(knn_k + 1, n)  # +1: top-k includes the self-match
        vals, idx = jax.lax.top_k(sims, k)
        r = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
        adj = jnp.zeros((n, n), bool).at[r, idx].set(vals >= threshold)
        adj = jnp.logical_or(adj, adj.T)  # symmetric union
        # Ensure self-edges so isolated rows keep their own label.
        adj = jnp.logical_or(adj, jnp.eye(n, dtype=bool))
        return np.asarray(_propagate_labels(adj))

    rows, cols = build_knn_edges(
        vecs, k=knn_k, threshold=threshold, force_projection=force_projection
    )
    return _sparse_components(n, rows, cols)
