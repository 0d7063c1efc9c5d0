"""Model runtime abstraction: stub | tpu (JAX Llama) | ollama.

The reference calls Ollama over HTTP and falls back to a deterministic
citation-bearing stub on any error
(reference: services/dashboard/app.py:1182-1258,
scripts/demo_client.py:23-40). That stub *is* the test backend: it always
emits fake citations, so the full failure pipeline is exercisable with no
LLM.

Here the runtime is a first-class interface:

  * ``StubRuntime`` — byte-for-byte the reference's canned response, zero
    dependencies, the hermetic default.
  * ``LlamaRuntime`` (kakveda_tpu.models.llama) — the in-tree JAX Llama,
    TP-sharded on the same mesh as the GFKB index; replaces the Ollama HTTP
    hop with an on-pod forward pass.
  * ``OllamaRuntime`` — HTTP client kept for drop-in compatibility with
    reference deployments; falls back to the stub like the reference does.

Every result carries provider/model/latency metadata in the reference's
meta shape so dashboards and eval scorecards transfer unchanged.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Protocol
from kakveda_tpu.core import sanitize

# The reference's exact stub text (services/dashboard/app.py:1193-1199) —
# fake citations that trip the rule classifier deterministically.
STUB_RESPONSE = (
    "Here is a summary with references.\n\n"
    "References:\n"
    "[1] Smith et al. (2020) A Study on Things.\n"
    "[2] Doe (2021) Another Paper.\n"
)


class UnknownModelError(ValueError):
    """Requested model label isn't among the runtime's checkpoints.

    A distinct type so UI callers can turn ONLY stale-label rejections
    into a friendly chat reply while real serving errors (no decode room,
    prompt too long, …) still surface as server errors."""


class HBMBudgetError(RuntimeError):
    """Loading a checkpoint would exceed the runtime's HBM weight budget
    and nothing (more) can be evicted. Raised BEFORE the upload — the
    alternative is OOMing the chip that also serves the GFKB index."""


def _parse_bytes(s) -> Optional[int]:
    """'8GiB' | '8G' | '512M' | raw int → bytes (None/'' → None)."""
    if s is None or s == "":
        return None
    if isinstance(s, (int, float)):
        return int(s)
    t = str(s).strip().upper().removesuffix("B").removesuffix("I")
    mult = {"K": 2**10, "M": 2**20, "G": 2**30, "T": 2**40}.get(t[-1:], 1)
    if mult != 1:
        t = t[:-1]
    return int(float(t) * mult)


def _tree_bytes(tree) -> int:
    """Exact on-device bytes of a param tree (int8 pairs count both the
    int8 matrix and its scales)."""
    import jax

    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype")
    )


@dataclass
class GenerateResult:
    text: str
    meta: Dict[str, Any] = field(default_factory=dict)


class ModelRuntime(Protocol):
    name: str

    def generate(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 256) -> GenerateResult: ...


def generate_batch(
    runtime: "ModelRuntime", prompts: list, *, model: Optional[str] = None, max_tokens: int = 256
) -> list:
    """Batched generation through whatever the runtime offers: the TPU
    runtime decodes the whole list in one left-padded stream
    (LlamaRuntime.generate_batch); stub/ollama fall back to a per-prompt
    loop. Callers (eval runner, LLM classifier) stay runtime-agnostic."""
    fn = getattr(runtime, "generate_batch", None)
    if callable(fn):
        return fn(prompts, model=model, max_tokens=max_tokens)
    return [runtime.generate(p, model=model, max_tokens=max_tokens) for p in prompts]


def list_models(runtime: "ModelRuntime") -> list:
    """Model names the runtime can serve, for the playground dropdown
    (reference: services/dashboard/app.py:286-306, Ollama /api/tags).
    Runtimes advertise via a ``list_models`` method; anything else falls
    back to a single entry."""
    fn = getattr(runtime, "list_models", None)
    if callable(fn):
        try:
            return list(fn()) or [getattr(runtime, "name", "model")]
        except Exception:  # noqa: BLE001 — listing is best-effort
            pass
    return [getattr(runtime, "name", "model")]


def _serving_stats_unavailable(name: str) -> Dict[str, Any]:
    return {"runtime": name, "engine": None}


class StubRuntime:
    """Deterministic canned-response backend — the hermetic test model."""

    name = "stub"

    def __init__(self, model_label: str = "stub"):
        self.model_label = model_label

    def list_models(self) -> list:
        return [self.model_label]

    def serving_stats(self) -> Dict[str, Any]:
        return _serving_stats_unavailable("stub")

    def generate(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 256) -> GenerateResult:
        started = time.perf_counter()
        text = STUB_RESPONSE
        return GenerateResult(
            text=text,
            meta={
                "provider": "stub",
                "model": model or self.model_label,
                "latency_ms": int((time.perf_counter() - started) * 1000),
            },
        )

    def generate_stream(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 256, cancel=None):
        """Deterministic chunked stream so the SSE path is exercisable with
        no hardware: the canned response arrives word by word, joining to
        exactly generate().text."""
        words = STUB_RESPONSE.split(" ")
        for i, w in enumerate(words):
            if cancel is not None and cancel.is_set():
                return
            yield w if i == len(words) - 1 else w + " "


class OllamaRuntime:
    """HTTP client for an external Ollama, with stub fallback on any error —
    reference-compatible behavior (services/dashboard/app.py:1182-1199)."""

    name = "ollama"

    def __init__(self, url: Optional[str] = None, model: Optional[str] = None, timeout: float = 8.0):
        self.url = url or os.environ.get("OLLAMA_URL", "http://localhost:11434")
        self.model = model or os.environ.get("OLLAMA_MODEL", "llama3")
        self.timeout = timeout
        self._stub = StubRuntime()

    def list_models(self) -> list:
        """Installed Ollama models via /api/tags (reference:
        services/dashboard/app.py:286-306); configured default on failure."""
        import httpx

        try:
            r = httpx.get(f"{self.url}/api/tags", timeout=3.0)
            r.raise_for_status()
            names = [m.get("name") for m in r.json().get("models", []) if m.get("name")]
            return names or [self.model]
        except Exception:  # noqa: BLE001
            return [self.model]

    def serving_stats(self) -> Dict[str, Any]:
        return _serving_stats_unavailable("ollama")

    def generate(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 256) -> GenerateResult:
        import httpx

        mdl = model or self.model
        started = time.perf_counter()
        try:
            r = httpx.post(
                f"{self.url}/api/generate",
                json={"model": mdl, "prompt": prompt, "stream": False},
                timeout=self.timeout,
            )
            r.raise_for_status()
            latency_ms = int((time.perf_counter() - started) * 1000)
            return GenerateResult(
                text=r.json().get("response") or "",
                meta={"provider": "ollama", "model": mdl, "url": self.url, "latency_ms": latency_ms},
            )
        except Exception as e:  # noqa: BLE001 — any failure falls back to the stub
            latency_ms = int((time.perf_counter() - started) * 1000)
            res = self._stub.generate(prompt, model=mdl)
            res.meta.update(
                {"latency_ms": latency_ms, "url": self.url, "error": f"{type(e).__name__}: {e}"}
            )
            return res


class MultiModelRuntime:
    """Several HF checkpoints behind one runtime, routed by model label —
    the playground's model dropdown with real choices, like the reference's
    Ollama installed-model list (services/dashboard/app.py:286-306) but
    served in-process on the TPU.

    ``KAKVEDA_HF_CKPTS=/ckpts/llama-1b:/ckpts/qwen3-0.6b`` (os.pathsep-
    separated checkpoint directories; any supported family — see
    models/hf_convert.py). Labels are the directory basenames; the first
    entry is the default model. Checkpoints load LAZILY on first use, so
    only models actually requested occupy HBM.

    **HBM budget** (``hbm_budget_bytes`` / ``KAKVEDA_HBM_BUDGET=12GiB``):
    the runtime accounts exact weight bytes per loaded model plus the
    serving engine's KV pool, and when a new load would cross the budget
    it LRU-evicts idle models first and raises :class:`HBMBudgetError`
    (before the upload) if eviction can't make room — never an OOM on the
    chip that co-hosts the GFKB index. Set the budget to chip HBM minus
    the index + workspace reserve (docs/performance.md co-residency
    table). No budget → the pre-round-4 behavior (operator's call)."""

    name = "tpu"

    def __init__(
        self,
        paths: list,
        *,
        quant: Optional[str] = None,
        mesh=None,
        hbm_budget_bytes: Optional[int] = None,
    ):
        import threading

        if not paths:
            raise ValueError("MultiModelRuntime needs at least one checkpoint path")
        if quant not in (None, "none", "int8"):
            # Fail at construction (= server startup), not on the first
            # generate request — parity with LlamaRuntime.from_env.
            raise ValueError(f"unknown quant mode {quant!r} (int8|none)")
        self._paths = {os.path.basename(os.path.normpath(p)): p for p in paths}
        if len(self._paths) != len(paths):
            raise ValueError(f"duplicate checkpoint basenames in {paths}")
        self._default = os.path.basename(os.path.normpath(paths[0]))
        self._quant = quant
        self._mesh = mesh
        self._budget = (
            hbm_budget_bytes
            if hbm_budget_bytes is not None
            else _parse_bytes(os.environ.get("KAKVEDA_HBM_BUDGET"))
        )
        self._loaded: Dict[str, Any] = {}  # label -> LlamaRuntime, LRU order
        self._bytes: Dict[str, int] = {}  # label -> exact weight+KV bytes
        self._load_lock = sanitize.named_lock("MultiModelRuntime._load_lock")  # serializes load/evict/budget
        self._lru_lock = sanitize.named_lock("MultiModelRuntime._lru_lock")  # guards _loaded order mutations only
        # HBM headroom on the metrics plane: budget is static, loaded
        # bytes move on every load/evict — headroom is the difference,
        # computed by the dashboard/alert side.
        from kakveda_tpu.core import metrics as _metrics

        reg = _metrics.get_registry()
        self._m_budget = reg.gauge(
            "kakveda_hbm_budget_bytes",
            "Configured HBM weight+KV budget (0 = unbudgeted)",
        )
        self._m_loaded = reg.gauge(
            "kakveda_hbm_loaded_bytes",
            "Resident weight+KV bytes accounted by the model router",
        )
        self._m_budget.set(self._budget or 0)

    def _estimate_bytes(self, path: str) -> int:
        """Pre-load footprint estimate from config.json alone (no weight
        IO): eval_shape of the param tree (+int8 halving) plus the serving
        engine's KV pool. Replaced by exact accounting after the load."""
        import json as _json

        import jax
        import jax.numpy as jnp

        from kakveda_tpu.models.hf_convert import hf_config_to_llama
        from kakveda_tpu.models.llama import init_params

        with open(os.path.join(path, "config.json")) as f:
            cfg = hf_config_to_llama(_json.load(f), dtype=jnp.bfloat16)
        shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        w = _tree_bytes(shapes)
        if self._quant == "int8":
            # Dense matrices drop to 1 byte/elt + per-row f32 scales; the
            # (unquantized) norms/embeddings are a small fraction. A ~0.55
            # factor over-estimates slightly — safe direction for a budget.
            w = int(w * 0.55)
        return w + self._engine_pool_bytes(cfg)

    @staticmethod
    def _engine_pool_bytes(cfg) -> int:
        """KV bytes the shared ServingEngine will pin once this model
        serves traffic (slots × window × layers × K+V), from the same env
        knobs LlamaRuntime.engine uses."""
        import numpy as np

        slots = int(os.environ.get("KAKVEDA_SERVE_SLOTS", "8"))
        window = min(
            int(os.environ.get("KAKVEDA_SERVE_WINDOW", min(512, cfg.max_seq_len))),
            cfg.max_seq_len,
        )
        if os.environ.get("KAKVEDA_KV_QUANT", "").lower() == "int8":
            # int8 pool: 1 byte/element + one f32 per-row scale per head_dim
            # elements (models/llama.py:_kv_quant_rows). Charging the dense
            # dtype here over-charges ~2× — safe, but it skews the admin
            # panel's resident-bytes figure and triggers eviction early.
            itemsize = 1.0 + 4.0 / cfg.head_dim
        else:
            itemsize = float(np.dtype(cfg.dtype).itemsize)
        # a cache per layer type: K/V for the attention layers, L-1 rows of
        # d_model for each conv layer
        kv = window * len(cfg.layers_of("full_attention")) * 2 * cfg.n_kv_heads * cfg.head_dim * itemsize
        conv = len(cfg.layers_of("conv")) * (cfg.conv_l_cache - 1) * cfg.d_model * np.dtype(cfg.dtype).itemsize
        return int(slots * (kv + conv))

    def _evict_lru(self, keep: str) -> bool:
        """Drop the least-recently-used loaded model (never ``keep``);
        returns False when nothing is evictable. Caller holds _load_lock.

        ``retire()`` closes the engine under ITS lock and bars a rebuild,
        so a thread mid-generate on the evicted runtime can't re-pin a KV
        pool behind the budget's back — it finishes on the solo path and
        the weights free when the last in-flight caller drops them."""
        with self._lru_lock:
            victim = next((lb for lb in self._loaded if lb != keep), None)
            rt = self._loaded.pop(victim) if victim is not None else None
        if rt is None:
            return False
        self._bytes.pop(victim, None)
        rt.retire()
        self._m_loaded.set(self.loaded_bytes())
        return True

    def loaded_bytes(self) -> int:
        # dict() is an atomic C-level copy: the serving panel calls this
        # from a handler thread while loads/evictions mutate _bytes, and
        # iterating the live dict would raise mid-mutation.
        return sum(dict(self._bytes).values())

    def serving_stats(self) -> Dict[str, Any]:
        """Ops snapshot for the admin serving panel: budget accounting
        plus each resident model's engine stats."""
        with self._lru_lock:
            # Snapshot under the order lock: the hot-path LRU touch pops
            # and reinserts entries, and an unguarded items() can see the
            # dict change size mid-iteration.
            resident = list(self._loaded.items())
        return {
            "runtime": "tpu-multi",
            "budget_bytes": self._budget,
            "loaded_bytes": self.loaded_bytes(),
            "models": {
                label: {
                    "bytes": self._bytes.get(label, 0),
                    **rt.serving_stats(),
                }
                for label, rt in resident
            },
            "available": sorted(self._paths),
        }

    def _get(self, model: Optional[str]):
        label = model or self._default
        if label not in self._paths:
            raise UnknownModelError(
                f"unknown model {label!r}; available: {sorted(self._paths)}"
            )
        rt = self._loaded.get(label)
        if rt is not None:
            # Hot path: no load lock (a slow checkpoint load on another
            # label must not stall serving). LRU touch under the cheap
            # order lock; if the label was just evicted, this request
            # still runs on the retired runtime it already holds.
            with self._lru_lock:
                cur = self._loaded.pop(label, None)
                if cur is not None:
                    self._loaded[label] = cur
            return rt
        # Serialize checkpoint loads: concurrent first requests for one
        # label would otherwise each convert + upload the full weight
        # set (double HBM for the same model).
        with self._load_lock:
            rt = self._loaded.get(label)
            if rt is not None:
                return rt
            if self._budget is not None:
                est = self._estimate_bytes(self._paths[label])
                while (
                    self.loaded_bytes() + est > self._budget
                    and self._evict_lru(keep=label)
                ):
                    pass
                if self.loaded_bytes() + est > self._budget:
                    raise HBMBudgetError(
                        f"loading {label!r} needs ~{est / 2**20:.0f} MiB but only "
                        f"{(self._budget - self.loaded_bytes()) / 2**20:.0f} MiB of the "
                        f"{self._budget / 2**20:.0f} MiB HBM budget remains "
                        "(KAKVEDA_HBM_BUDGET) and nothing is left to evict"
                    )
            from kakveda_tpu.models.generate import LlamaRuntime

            rt = LlamaRuntime.from_hf(
                self._paths[label], mesh=self._mesh, quant=self._quant
            )
            self._bytes[label] = _tree_bytes(rt.params) + self._engine_pool_bytes(rt.cfg)
            with self._lru_lock:
                self._loaded[label] = rt
            self._m_loaded.set(self.loaded_bytes())
            return rt

    def list_models(self) -> list:
        return list(self._paths)

    def generate(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 256) -> GenerateResult:
        return self._get(model).generate(prompt, model=model, max_tokens=max_tokens)

    def generate_batch(self, prompts: list, *, model: Optional[str] = None, max_tokens: int = 256) -> list:
        return self._get(model).generate_batch(prompts, model=model, max_tokens=max_tokens)

    def generate_stream(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 256, cancel=None):
        """Stream from the resolved model's runtime (SSE playground path).
        Default budget matches generate()/generate_batch here — a streamed
        answer must not silently truncate shorter than the blocking one."""
        return self._get(model).generate_stream(
            prompt, model=model, max_tokens=max_tokens, cancel=cancel
        )


_RUNTIMES: Dict[str, Any] = {}


def get_runtime(name: Optional[str] = None) -> ModelRuntime:
    """Resolve the configured runtime (KAKVEDA_MODEL_RUNTIME: stub|tpu|ollama).
    With ``KAKVEDA_HF_CKPTS`` set, ``tpu`` serves every listed checkpoint
    behind one multi-model router."""
    name = (name or os.environ.get("KAKVEDA_MODEL_RUNTIME", "stub")).lower()
    if name in _RUNTIMES:
        return _RUNTIMES[name]
    if name == "stub":
        rt: ModelRuntime = StubRuntime()
    elif name == "ollama":
        rt = OllamaRuntime()
    elif name == "tpu":
        multi = os.environ.get("KAKVEDA_HF_CKPTS")
        if multi:
            quant = os.environ.get("KAKVEDA_QUANT") or None
            rt = MultiModelRuntime(
                [p for p in multi.split(os.pathsep) if p], quant=quant
            )
        else:
            from kakveda_tpu.models.generate import LlamaRuntime

            rt = LlamaRuntime.from_env()
    else:
        raise ValueError(f"unknown model runtime: {name!r}")
    _RUNTIMES[name] = rt
    return rt
