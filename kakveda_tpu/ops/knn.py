"""Sharded cosine top-k over a device-resident embedding matrix.

This is the kernel that replaces the reference's entire match path —
load-all-JSONL + pydantic validate + TF-IDF refit + sklearn cosine per query
(reference: services/gfkb/app.py:79-102, services/shared/similarity.py:14-20)
— with one compiled device program:

    scores = Q @ E^T          (MXU matmul, f32 accumulation)
    local top-k per shard     (lax.top_k)
    all_gather(k·n candidates) over ICI, merge with a second top-k

The embedding matrix is row-sharded over the mesh's ``data`` axis with
*round-robin* slot placement (slot ``s`` lives on shard ``s % n``), so every
shard does equal matmul work regardless of how full the index is. All shapes
are static: capacity is fixed at allocation, queries are padded to bucketed
batch sizes by the caller, so the hot path never retraces.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kakveda_tpu.core import ledger
from kakveda_tpu.ops import pallas_knn

# Sentinel below any reachable cosine score (valid range [-1, 1]).
_NEG = -2.0


def slot_to_physical(slots: np.ndarray, n_shards: int, rows_per_shard: int) -> np.ndarray:
    """Logical insert slot -> physical row in the [capacity, d] array.

    Round-robin: slot s -> shard s % n, row-in-shard s // n. Keeps shard load
    balanced while the index fills.
    """
    return (slots % n_shards) * rows_per_shard + slots // n_shards


def physical_to_slot(phys: np.ndarray, n_shards: int, rows_per_shard: int) -> np.ndarray:
    shard = phys // rows_per_shard
    row = phys % rows_per_shard
    return row * n_shards + shard


class ShardedKnn:
    """Compiled insert + cosine-top-k over a sharded [capacity, dim] matrix.

    Owns no state: callers (kakveda_tpu.index.gfkb.DeviceIndex) hold the
    (embeddings, valid) device arrays and thread them through ``insert`` /
    ``topk``. ``insert`` donates its buffers, so updates are in-place in HBM.
    """

    def __init__(
        self,
        mesh: Mesh,
        capacity: int,
        dim: int,
        k: int = 5,
        store_dtype: jnp.dtype | None = None,
        shard_axis: str = "data",
        use_pallas: bool | None = None,
    ):
        if shard_axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {shard_axis!r}: {mesh.axis_names}")
        self.mesh = mesh
        self.axis = shard_axis
        self.n_shards = mesh.shape[shard_axis]
        if capacity % self.n_shards != 0:
            capacity += self.n_shards - capacity % self.n_shards
        self.dim = dim
        self.k = k

        # Fused Pallas match kernel (ops/pallas_knn.py): on by default on TPU
        # when the layout qualifies; KAKVEDA_PALLAS=0|1|interpret overrides
        # ("interpret" runs the kernel through the Pallas interpreter so the
        # CPU test suite exercises the exact kernel logic).
        env = os.environ.get("KAKVEDA_PALLAS", "auto").lower()
        self._pallas_interpret = env == "interpret"
        if use_pallas is None:
            if env == "auto":
                from kakveda_tpu.ops.device import is_tpu_backend

                use_pallas = is_tpu_backend()
            else:
                use_pallas = env not in ("0", "false", "off")
        rows = capacity // self.n_shards
        tile = pallas_knn.DEFAULT_ROW_TILE
        if (
            use_pallas
            and dim % 128 == 0
            and capacity >= tile * self.n_shards
            and k <= pallas_knn._KPAD
        ):
            rows = -(-rows // tile) * tile  # per-shard rows to a tile multiple
            capacity = rows * self.n_shards
            self.use_pallas = True
            self._pallas_tile = tile
        else:
            self.use_pallas = False
            self._pallas_tile = tile
        self.capacity = capacity
        self.rows_per_shard = rows
        if store_dtype is None:
            from kakveda_tpu.ops.device import is_tpu_backend

            store_dtype = jnp.bfloat16 if is_tpu_backend() else jnp.float32
        self.store_dtype = store_dtype

        # Single-device meshes take a plain-jit path: identical math, no
        # shard_map / NamedSharding — the natural degenerate case.
        if capacity > (1 << 24):
            raise ValueError(
                f"capacity {capacity} exceeds 2^24: packed f32 row indices "
                "would lose precision (widen _pack before raising this limit)"
            )
        self.single_device = mesh.devices.size == 1

        # The /warn match program. A named function, not a lambda: the
        # profiler's trace calls the program jit_<name>, and the benchmark
        # finds it by "match" in that name.
        def _match_sparse(e, v, i, x):
            impl = self._topk_single_impl if self.single_device else self._topk_impl
            return impl(e, v, self._densify_q(i, x))

        if self.single_device:
            self._device = mesh.devices.flat[0]
            sharding = jax.sharding.SingleDeviceSharding(self._device)
            self._emb_sharding = sharding
            self._valid_sharding = sharding
            self._repl = sharding
            self._topk = jax.jit(self._topk_single_impl)
        else:
            self._emb_sharding = NamedSharding(mesh, P(shard_axis, None))
            self._valid_sharding = NamedSharding(mesh, P(shard_axis))
            self._repl = NamedSharding(mesh, P())
            self._topk = jax.jit(self._topk_impl)
        self._topk_sparse = jax.jit(_match_sparse)
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0, 1))
        self._insert_sparse = jax.jit(self._insert_sparse_impl, donate_argnums=(0, 1, 2))
        # Int32 side-table (per-slot failure-type ids) sharded like `valid`:
        # scattered on insert, AND-ed into the valid mask for device-side
        # type-filtered matches.
        self._scatter_i32_jit = jax.jit(
            lambda a, rows, vals: a.at[rows].set(vals, mode="drop"), donate_argnums=(0,)
        )
        self._mask_jit = jax.jit(lambda valid, types, tid: valid & (types == tid))
        # Allocation happens INSIDE jit with explicit output shardings: under
        # multi-controller JAX (process_count > 1) no single host could
        # device_put a full [capacity, dim] host array onto the global mesh —
        # and even single-host this skips a host→device transfer of zeros.
        cap = self.capacity
        sd = self.store_dtype
        self._alloc_jit = jax.jit(
            lambda: (jnp.zeros((cap, dim), sd), jnp.zeros((cap,), jnp.bool_)),
            out_shardings=(self._emb_sharding, self._valid_sharding),
        )
        self._alloc_i32_jit = jax.jit(
            lambda: jnp.full((cap,), -1, jnp.int32), out_shardings=self._valid_sharding
        )
        # Persistent jit (shape-keyed cache) for the snapshot gather — a
        # fresh wrapper per call would recompile every snapshot. Replicated
        # output so every process can read the gathered rows to host.
        self._gather = jax.jit(lambda e, p: e[p].astype(jnp.float32), out_shardings=self._repl)
        self._copy = jax.jit(jnp.copy)

    def info(self) -> dict:
        """Which match path this index compiled to, for /readyz."""
        return {
            "knn": "pallas" if self.use_pallas else "xla",
            "interpret": bool(self.use_pallas and self._pallas_interpret),
            "store_dtype": jnp.dtype(self.store_dtype).name,
            "capacity": self.capacity,
            "dim": self.dim,
            "shards": self.n_shards,
        }

    def device_copy(self, emb: jax.Array) -> jax.Array:
        """Device-side copy of the embedding buffer (fast HBM copy) so
        callers can release their lock before the slow host transfer."""
        return self._copy(emb)

    # --- allocation ------------------------------------------------------

    def alloc(self) -> Tuple[jax.Array, jax.Array]:
        """Fresh (embeddings, valid) buffers on the mesh, zeroed."""
        return self._alloc_jit()

    def alloc_i32(self) -> jax.Array:
        """Fresh per-slot int32 side-table (-1 = unset), sharded like valid."""
        return self._alloc_i32_jit()

    def _replicate(self, x: np.ndarray) -> jax.Array:
        """Host array → replicated device array. Every process passes the
        same value (the SPMD contract: all hosts see the same log/queries),
        which is exactly what device_put-to-replicated supports under
        multi-controller JAX."""
        ledger.note_transfer("h2d", getattr(x, "nbytes", 0))
        return jax.device_put(x, self._repl)

    def scatter_i32(self, arr: jax.Array, slots: np.ndarray, values: np.ndarray) -> jax.Array:
        """Write int32 values at logical slots (donates ``arr``)."""
        phys = slot_to_physical(np.asarray(slots, dtype=np.int32), self.n_shards, self.rows_per_shard)
        return self._scatter_i32_jit(
            arr, self._replicate(phys), self._replicate(np.asarray(values, np.int32))
        )

    def mask_valid(self, valid: jax.Array, types: jax.Array, type_id: int) -> jax.Array:
        """valid AND (types == type_id) — the device-side pre-selection mask
        for type-filtered matches. ``type_id`` stays a Python scalar so it
        replicates implicitly on any mesh."""
        return self._mask_jit(valid, types, type_id)

    # --- insert ----------------------------------------------------------

    def _insert_impl(self, emb, valid, vecs, phys_rows):
        emb = emb.at[phys_rows].set(vecs.astype(emb.dtype), mode="drop")
        valid = valid.at[phys_rows].set(True, mode="drop")
        return emb, valid

    def insert(
        self,
        emb: jax.Array,
        valid: jax.Array,
        vecs: np.ndarray,
        slots: np.ndarray,
    ) -> Tuple[jax.Array, jax.Array]:
        """Write rows for logical ``slots`` (new inserts or version updates)."""
        phys = slot_to_physical(np.asarray(slots, dtype=np.int32), self.n_shards, self.rows_per_shard)
        vecs_d = self._replicate(np.asarray(vecs, dtype=np.float32))
        return self._insert(emb, valid, vecs_d, self._replicate(phys))

    def _insert_sparse_impl(self, emb, valid, types, idx, val, phys_rows, tids):
        # Pad entries carry idx == dim → dropped by the densify scatter;
        # pad rows carry phys == capacity → dropped by the row scatter.
        rows = self._densify_q(idx, val)
        emb = emb.at[phys_rows].set(rows.astype(emb.dtype), mode="drop")
        valid = valid.at[phys_rows].set(True, mode="drop")
        types = types.at[phys_rows].set(tids, mode="drop")
        return emb, valid, types

    def insert_sparse(
        self,
        emb: jax.Array,
        valid: jax.Array,
        types: jax.Array,
        idx: np.ndarray,  # [B, K] int32 bucket ids (pad = dim)
        val: np.ndarray,  # [B, K] f32 weights (pad = 0)
        slots: np.ndarray,
        tids: np.ndarray,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Sparse-row insert: ships (idx, val) pairs instead of dense [B, dim]
        rows — hashed n-gram embeddings are ~98% zeros, so this cuts the
        host→device transfer of the streaming-ingest path ~60×. Rows are
        densified on device by a scatter-add, and the per-slot type-id
        side-table is scattered in the same program (one dispatch per batch,
        not three). Batch is padded to a power-of-two bucket so the jit
        never retraces on ragged tail batches."""
        b = len(slots)
        bb = batch_bucket(max(b, 1))
        phys = np.full((bb,), self.capacity, dtype=np.int32)  # pad = drop
        phys[:b] = slot_to_physical(
            np.asarray(slots, dtype=np.int32), self.n_shards, self.rows_per_shard
        )
        tids_p = np.full((bb,), -1, dtype=np.int32)
        tids_p[:b] = np.asarray(tids, np.int32)
        if idx.shape[0] != bb:
            pad_i = np.full((bb, idx.shape[1]), self.dim, dtype=np.int32)
            pad_v = np.zeros((bb, idx.shape[1]), dtype=np.float32)
            pad_i[:b] = idx
            pad_v[:b] = val
            idx, val = pad_i, pad_v
        return self._insert_sparse(
            emb,
            valid,
            types,
            self._replicate(np.ascontiguousarray(idx)),
            self._replicate(np.ascontiguousarray(val)),
            self._replicate(phys),
            self._replicate(tids_p),
        )

    def gather_slots(self, emb: jax.Array, slots: np.ndarray) -> np.ndarray:
        """Host copy of the embedding rows for logical ``slots`` (snapshot
        path). Chunked so a 1M-row gather never materializes a second
        full-size host buffer at once."""
        phys = slot_to_physical(np.asarray(slots, dtype=np.int32), self.n_shards, self.rows_per_shard)
        out = np.empty((len(phys), self.dim), dtype=np.float32)
        chunk = 1 << 16
        for i in range(0, len(phys), chunk):
            out[i : i + chunk] = np.asarray(self._gather(emb, self._replicate(phys[i : i + chunk])))
        return out

    # --- match -----------------------------------------------------------

    @staticmethod
    def _pack(vals: jax.Array, phys: jax.Array) -> jax.Array:
        """Fuse (scores, rows) into one [B, 2k] f32 buffer.

        One output buffer means one device→host fetch per match call. Row
        indices are exact in f32 up to 2^24 (capacities beyond 16M rows
        would need a wider packing).
        """
        return jnp.concatenate([vals, phys.astype(jnp.float32)], axis=1)

    def _local_topk(self, emb, valid, q):
        """Per-shard (scores, rows): fused Pallas kernel when enabled, else
        matmul + lax.top_k. Identical results either way (same tie-break)."""
        if self.use_pallas:
            return pallas_knn.fused_topk(
                emb, valid, q, k=self.k,
                row_tile=self._pallas_tile, interpret=self._pallas_interpret,
            )
        scores = jax.lax.dot_general(
            q.astype(emb.dtype),
            emb,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        scores = jnp.where(valid[None, :], scores, _NEG)
        return jax.lax.top_k(scores, min(self.k, emb.shape[0]))

    def _topk_single_impl(self, emb, valid, q):
        """Degenerate one-shard path: one local top-k, plain jit."""
        vals, idx = self._local_topk(emb, valid, q)
        return self._pack(vals, idx)

    def _topk_impl(self, emb, valid, q):
        k = self.k

        def local(emb_l, valid_l, q_l):
            # [B, kk] local candidates from this shard's rows.
            vals, idx = self._local_topk(emb_l, valid_l, q_l)
            kk = vals.shape[1]
            shard = jax.lax.axis_index(self.axis)
            phys = idx + shard * emb_l.shape[0]
            # Gather every shard's candidates, merge with a second top-k.
            all_vals = jax.lax.all_gather(vals, self.axis, axis=0)  # [n, B, kk]
            all_phys = jax.lax.all_gather(phys, self.axis, axis=0)
            n = all_vals.shape[0]
            B = all_vals.shape[1]
            flat_vals = jnp.transpose(all_vals, (1, 0, 2)).reshape(B, n * kk)
            flat_phys = jnp.transpose(all_phys, (1, 0, 2)).reshape(B, n * kk)
            mvals, midx = jax.lax.top_k(flat_vals, min(k, n * kk))
            mphys = jnp.take_along_axis(flat_phys, midx, axis=1)
            return self._pack(mvals, mphys)

        # check_vma=False: after the all_gather every shard computes the
        # identical merged top-k, so the outputs are replicated by
        # construction, but the static analysis can't prove it.
        return jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(self.axis, None), P(self.axis), P()),
            out_specs=P(),
            check_vma=False,
        )(emb, valid, q)

    def topk_async(self, emb: jax.Array, valid: jax.Array, q: np.ndarray) -> jax.Array:
        """Dispatch a match and start the host copy; returns the packed
        [B, 2k] device buffer. Pair with ``topk_result`` — lets a serving
        loop pipeline batch i's compute with batch i-1's fetch."""
        qd = jax.device_put(jnp.asarray(q, dtype=jnp.float32), self._repl)
        packed = self._topk(emb, valid, qd)
        packed.copy_to_host_async()
        return packed

    def _densify_q(self, idx: jax.Array, val: jax.Array) -> jax.Array:
        b = idx.shape[0]
        q = jnp.zeros((b, self.dim), jnp.float32)
        return q.at[jnp.arange(b)[:, None], idx].add(val, mode="drop")

    def topk_async_sparse(
        self, emb: jax.Array, valid: jax.Array, idx: np.ndarray, val: np.ndarray
    ) -> jax.Array:
        """Sparse-query dispatch: ships (idx, val) pairs — ~60× smaller
        than dense hashed-ngram rows — and densifies on device before the
        same top-k (identical results to ``topk_async``). The batch pads
        to a power-of-two bucket internally (pad rows carry idx == dim, the
        densify drop sentinel) so ragged batches never retrace — same
        contract as insert_sparse. Result rows beyond the
        caller's batch belong to pad rows: an all-zero query scores 0.0
        against every valid index row, so callers must SLICE results to
        their batch size (a score threshold cannot identify pad rows)."""
        b = idx.shape[0]
        bb = batch_bucket(max(b, 1))
        if b != bb:
            pad_i = np.full((bb, idx.shape[1]), self.dim, np.int32)
            pad_v = np.zeros((bb, val.shape[1]), np.float32)
            pad_i[:b] = idx
            pad_v[:b] = val
            idx, val = pad_i, pad_v
        packed = self._topk_sparse(
            emb,
            valid,
            self._replicate(np.ascontiguousarray(idx)),
            self._replicate(np.ascontiguousarray(val)),
        )
        packed.copy_to_host_async()
        return packed

    def topk_result(self, packed: jax.Array) -> Tuple[np.ndarray, np.ndarray]:
        """(scores, logical slots) from a ``topk_async`` buffer."""
        host = np.asarray(packed)
        ledger.note_transfer("d2h", host.nbytes)
        kk = host.shape[1] // 2
        vals = host[:, :kk]
        phys = host[:, kk:].astype(np.int64)
        if self.single_device:
            return vals, phys  # physical row == logical slot on one shard
        return vals, physical_to_slot(phys, self.n_shards, self.rows_per_shard)

    def topk(self, emb: jax.Array, valid: jax.Array, q: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k (scores, logical slots) for a [B, dim] query batch."""
        return self.topk_result(self.topk_async(emb, valid, q))


def pow2_bucket(n: int, *, floor: int = 1, cap: int | None = None) -> int:
    """THE blessed pow2-bucket seam: smallest power-of-two ≥ ``n`` starting
    from ``floor`` (itself a power of two), optionally clamped to ``cap``.

    Every data-dependent Python size that becomes a jit argument shape must
    round through here (directly or via the thin wrappers ``batch_bucket``,
    ``generate._bucket_len``, ``ContinuousBatcher.bucket_for``) — bucketed
    shapes bound distinct lowerings to O(log N) while exact-fit shapes
    retrace per distinct size, and one retrace costs far more than the
    kernel it wraps. The static ``retrace-hazard`` rule
    (kakveda_tpu/analysis/device.py) recognizes exactly this seam; the
    runtime ledger (core/ledger.py) cross-checks the compile counts.
    """
    b = floor
    while b < n:
        b <<= 1
    return b if cap is None else min(b, cap)


@functools.lru_cache(maxsize=8)
def batch_bucket(b: int) -> int:
    """Pad query batches to power-of-two buckets so jit never retraces."""
    return pow2_bucket(b)
