"""On-device self-checks, each run by ``chip_smoke.py`` in a process of its own.

``kernels``  — every Pallas kernel the serving path selects (the fused kNN
               match, the bf16 flash kernel, the int8-KV flash kernel) at
               the shapes the server's own gates send it, against its XLA
               counterpart on the same inputs: ids equal, values within
               bf16 tolerance. A kernel Mosaic refuses raises here.
``dispatch`` — host-clock dispatch → ``block_until_ready`` of a trivial
               jitted program: the fixed cost under every device call.

Sizes come from the same environment the server reads
(``KAKVEDA_INDEX_CAPACITY``, ``KAKVEDA_LLAMA_PRESET``,
``KAKVEDA_SERVE_WINDOW``/``_SLOTS``, ``KAKVEDA_PALLAS``), so the check
compiles the programs the server compiled and finds them in the persistent
cache. Prints one JSON object on the last line of stdout.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# bf16 has 8 bits of mantissa: one ulp at the |2..4| magnitudes attention
# outputs reach is 2^-6.
_BF16_MAX_ABS = 0.05
_BF16_MEAN_ABS = 5e-3


def _knn_parity(interpret: bool) -> dict:
    from kakveda_tpu.core.config import ConfigStore
    from kakveda_tpu.core.runtime import get_runtime_config
    from kakveda_tpu.ops.featurizer import HashedNGramFeaturizer
    from kakveda_tpu.ops.knn import ShardedKnn
    from kakveda_tpu.parallel.mesh import create_mesh

    rc = get_runtime_config(service_name="chipcheck")
    dim = ConfigStore().embedding_dim()
    mesh = create_mesh(rc.mesh_shape)
    pallas = ShardedKnn(mesh, rc.index_capacity, dim, k=5)
    if not pallas.use_pallas:
        raise RuntimeError(
            f"the index did not select the Pallas match kernel: {pallas.info()}"
        )
    xla = ShardedKnn(mesh, pallas.capacity, dim, k=5, use_pallas=False)

    feat = HashedNGramFeaturizer(dim=dim)
    n = min(2048, pallas.capacity // 2)
    texts = [
        f"intent_tags:t{i % 7} | prompt_hint:parity row {i} of the kernel check "
        f"shard {i % 13} | tools:t{i % 3} | env_keys:os"
        for i in range(n)
    ]
    emb, valid = pallas.alloc()
    types = pallas.alloc_i32()
    # Every third slot stays empty so the occupancy mask is exercised.
    slots = (np.arange(n, dtype=np.int32) * 3) // 2
    for s in range(0, n, 512):
        idx, val = feat.encode_batch_sparse(texts[s : s + 512])
        emb, valid, types = pallas.insert_sparse(
            emb, valid, types, idx, val, slots[s : s + 512],
            np.zeros(len(idx), np.int32),
        )
    out = {"path": pallas.info(), "rows": n, "cases": []}
    for b in (1, 64):
        q_idx, q_val = feat.encode_batch_sparse(texts[:b])
        ps, pi = pallas.topk_result(pallas.topk_async_sparse(emb, valid, q_idx, q_val))
        xs, xi = xla.topk_result(xla.topk_async_sparse(emb, valid, q_idx, q_val))
        ps, pi, xs, xi = ps[:b], pi[:b], xs[:b], xi[:b]
        # Ranks that tie at score 0 (rows sharing no feature with the
        # query) may order differently; the ranks that mean anything must
        # name the same rows.
        live = xs > 1e-3
        case = {
            "batch": b,
            "ids_equal": bool(np.all(pi[live] == xi[live])),
            "top1_is_self": bool(np.all(pi[:, 0] == slots[:b])),
            "max_abs_diff": float(np.max(np.abs(ps - xs))),
        }
        case["ok"] = bool(
            case["ids_equal"] and case["top1_is_self"] and case["max_abs_diff"] <= 2e-2
        )
        out["cases"].append(case)
    out["compiled"] = not interpret
    out["ok"] = all(c["ok"] for c in out["cases"])
    return out


def _flash_parity(interpret: bool) -> dict:
    from kakveda_tpu.models import attention as A
    from kakveda_tpu.models.generate import LlamaRuntime
    from kakveda_tpu.models.llama import _kv_dequant, _kv_quant_rows

    cfg = LlamaRuntime.preset_config()
    window = min(int(os.environ.get("KAKVEDA_SERVE_WINDOW", 512)), cfg.max_seq_len)
    slots = int(os.environ.get("KAKVEDA_SERVE_SLOTS", "8"))
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # (batch, query rows): the engine's admission prefill at the prompt
    # buckets up to the half-window, and one decode step of the full pool.
    shapes = [(1, p) for p in (64, 256, 1024) if p <= window // 2] + [(slots, 1)]
    out = {"window": window, "heads": [h, kv, d], "cases": []}
    for kv8 in (False, True):
        for b, s in shapes:
            if not interpret and not A._flash_ok(s, h, kv, window, d):
                raise RuntimeError(f"flash layout gate refuses s={s} l={window} d={d}")
            # What the dispatcher sends the kernel: every shape with an
            # int8 cache, a bf16 cache only past the profitability gate.
            if not kv8 and not A._flash_wins(s, h, kv, window) and not interpret:
                continue
            ks = jax.random.split(jax.random.PRNGKey(7 * s + b), 3)
            q = jax.random.normal(ks[0], (b, s, h, d), cfg.dtype)
            k = jax.random.normal(ks[1], (b, kv, window, d), cfg.dtype)
            v = jax.random.normal(ks[2], (b, kv, window, d), cfg.dtype)
            pos0 = jnp.asarray(0 if s > 1 else window, jnp.int32)
            # Left pads differ per row, and the tail is unwritten.
            col = jnp.arange(window)[None, :]
            valid = (col >= jnp.arange(b)[:, None] * 3) & (col < window - 5)
            sr = -(-s * (h // kv) // 8) * 8
            blocks = dict(
                q_blk=A._pick_block(sr, 512, 8),
                l_blk=A._pick_block(window, 512, 128),
                interpret=interpret,
            )
            if kv8:
                k8, ksc = _kv_quant_rows(k)
                v8, vsc = _kv_quant_rows(v)
                got = A.flash_gqa_cache(
                    q, k8, v8, pos0, valid, k_scale=ksc, v_scale=vsc, **blocks
                )
                want = A._gqa_xla(
                    q, _kv_dequant(k8, ksc, q.dtype), _kv_dequant(v8, vsc, q.dtype),
                    pos0, valid,
                )
            else:
                got = A.flash_gqa_cache(q, k, v, pos0, valid, **blocks)
                want = A._gqa_xla(q, k, v, pos0, valid)
            diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
            case = {
                "kernel": "flash_kv8" if kv8 else "flash",
                "q": [b, s, h, d],
                "finite": bool(np.all(np.isfinite(np.asarray(got, np.float32)))),
                "max_abs_diff": float(diff.max()),
                "mean_abs_diff": float(diff.mean()),
            }
            case["ok"] = bool(
                case["finite"]
                and case["max_abs_diff"] <= _BF16_MAX_ABS
                and case["mean_abs_diff"] <= _BF16_MEAN_ABS
            )
            out["cases"].append(case)
    kernels = {c["kernel"] for c in out["cases"]}
    out["compiled"] = not interpret
    out["ok"] = kernels == {"flash", "flash_kv8"} and all(c["ok"] for c in out["cases"])
    return out


def kernels() -> dict:
    interpret = os.environ.get("KAKVEDA_PALLAS", "auto").lower() == "interpret"
    knn = _knn_parity(interpret)
    flash = _flash_parity(interpret)
    return {"ok": knn["ok"] and flash["ok"], "knn": knn, "flash": flash}


def dispatch(n: int = 200) -> dict:
    """Median host-clock cost of dispatching a trivial compiled program and
    waiting for it, and of also fetching its 32-byte result."""
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    f(x).block_until_ready()

    def timed(call) -> list:
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            call()
            ts.append((time.perf_counter() - t0) * 1e3)
        return ts

    ready = timed(lambda: f(x).block_until_ready())
    fetch = timed(lambda: np.asarray(f(x)))
    return {
        "ok": True,
        "calls": n,
        "dispatch_ready_ms": {
            "p50": round(float(np.median(ready)), 4),
            "p90": round(float(np.percentile(ready, 90)), 4),
            "min": round(min(ready), 4),
        },
        "dispatch_fetch_ms": {"p50": round(float(np.median(fetch)), 4)},
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    checks = {"kernels": kernels, "dispatch": dispatch}
    if len(argv) != 1 or argv[0] not in checks:
        print(f"usage: python -m kakveda_tpu.ops.chipcheck {'|'.join(checks)}",
              file=sys.stderr)
        return 2
    from kakveda_tpu.core import ledger
    from kakveda_tpu.ops.device import device_report, setup_compile_cache

    setup_compile_cache()
    ledger.maybe_install()
    out = checks[argv[0]]()
    out["device"] = device_report()
    rep = ledger.ledger_report()
    out["compiles"] = {"total": rep["compile_total"], "cache_hits": rep["cache_hits"]}
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
