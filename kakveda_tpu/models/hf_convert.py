"""HF checkpoint → kakveda param pytree (eight model families; a ninth,
``lfm2_moe``, by its config keys alone).

The reference delegates all real-model inference to an external Ollama
daemon (reference: services/dashboard/app.py:1182-1258) — which is also how
it supports many model families. Here real weights load directly onto the
TPU mesh: point ``KAKVEDA_HF_CKPT`` at any local HF-format checkpoint
directory of a supported family — Llama, Mistral, Qwen2, Qwen3, Gemma,
Gemma-2, Phi-3, Mixtral — and ``runtime=tpu`` serves it in-process
(``KAKVEDA_HF_CKPTS`` serves several at once). Every family delta is a
config flag on one runtime (see :func:`hf_config_to_llama`).

Conversion notes (all verified by the logit-parity tests in
tests/test_hf_convert.py against ``transformers.LlamaForCausalLM``):

  * HF ``nn.Linear`` stores ``[out, in]``; our matmuls are ``x @ W`` with
    ``W [in, out]`` — every projection transposes.
  * HF Llama uses the split-half ("NEOX") RoPE convention, identical to
    ``llama.apply_rope``, so q/k need **no** permutation (unlike raw Meta
    weights, which interleave).
  * ``tie_word_embeddings`` (Llama-3.2-1B, Gemma-style) → lm_head is the
    transposed embedding table.
  * ``rope_scaling.rope_type == "llama3"`` maps onto the flat rope_* fields
    of :class:`LlamaConfig`; other scaling types are rejected loudly rather
    than silently mis-positioned.
  * Vocab not divisible by 8 is padded up so the tp axis can shard the
    embed/lm_head tables; ``cfg.effective_vocab`` records the real size and
    sampling masks the pad logits.

Tensors stream one at a time through host RAM (safetensors ``safe_open`` /
lazy torch load) and are cast to ``param_dtype`` (default bfloat16 — what
the MXU wants) before the next loads, so an 8B model converts within
~2×8 GB host memory, not 4×.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kakveda_tpu.models.llama import LlamaConfig, Params

__all__ = ["hf_config_to_llama", "load_hf_checkpoint", "shard_params"]

_VOCAB_MULTIPLE = 8


_SUPPORTED_FAMILIES = (
    "llama", "mistral", "qwen2", "qwen3", "mixtral", "gemma", "gemma2", "phi3",
    "lfm2_moe",
)
# Families whose config maps but whose checkpoint tensor names are not
# learnt yet (no such files are in the repository): ``load_hf_checkpoint``
# refuses them; seeded weights in the program's layout serve them.
_CONFIG_ONLY_FAMILIES = ("lfm2_moe",)
_GEMMA_FAMILIES = ("gemma", "gemma2")


def hf_config_to_llama(hf: Dict[str, Any], *, dtype=jnp.bfloat16) -> LlamaConfig:
    """Map an HF ``config.json`` dict to :class:`LlamaConfig`.

    Eight HF families share the Llama block structure and load onto the one
    runtime: ``llama`` (the baseline), ``mistral`` (adds a sliding attention
    window and sometimes an explicit head_dim), ``qwen2`` (adds q/k/v
    projection biases), ``qwen3`` (per-head q/k RMSNorm), ``mixtral``
    (replaces the dense MLP with a sparse MoE block — models/moe.py),
    ``gemma`` (GeGLU activation, sqrt(d_model) embedding scale, explicit
    head_dim; its (1+w) RMSNorm convention is absorbed at conversion by
    storing the materialized 1+w weights), ``gemma2`` (gemma plus
    alternating per-layer sliding windows, attention/final logit
    softcapping, an explicit query scale, and sandwich post-norms), and
    ``phi3`` (fused qkv / gate_up projections split at conversion, longrope
    per-dim frequency scaling). ``lfm2_moe`` (:func:`_lfm2_moe_config`) is a
    layer-type list of gated short convolutions and attention, dense layers
    before sigmoid-routed expert layers. Anything else is rejected loudly."""
    family = hf.get("model_type") or "llama"
    if family not in _SUPPORTED_FAMILIES:
        raise ValueError(
            f"unsupported model_type={family!r} (supported: {', '.join(_SUPPORTED_FAMILIES)})"
        )
    if family == "lfm2_moe":
        return _lfm2_moe_config(hf, dtype)
    rope = hf.get("rope_scaling") or {}
    kw: Dict[str, Any] = {}
    if rope:
        rtype = rope.get("rope_type") or rope.get("type")
        if rtype == "llama3":
            kw = dict(
                rope_factor=float(rope["factor"]),
                rope_low_freq_factor=float(rope.get("low_freq_factor", 1.0)),
                rope_high_freq_factor=float(rope.get("high_freq_factor", 4.0)),
                rope_original_max_len=int(rope.get("original_max_position_embeddings", 8192)),
            )
        elif rtype == "longrope" and family == "phi3":
            # Phi-3 longrope: per-dim frequency divisors, selected
            # DYNAMICALLY at runtime (short while the sequence fits the
            # original pretraining context, long beyond it — HF's
            # dynamic_rope_update semantics); the cos/sin attention
            # scaling is static from the config's extension ratio.
            import math as _math

            orig = int(
                hf.get("original_max_position_embeddings")
                or hf.get("max_position_embeddings")
            )
            maxp = int(hf.get("max_position_embeddings", orig))
            scale = maxp / orig
            if rope.get("attention_factor") is not None:
                # HF honors an explicit attention_factor verbatim.
                attn_scale = float(rope["attention_factor"])
            else:
                attn_scale = (
                    _math.sqrt(1.0 + _math.log(scale) / _math.log(orig))
                    if scale > 1.0
                    else 1.0
                )
            hd_half = (
                int(hf.get("head_dim") or int(hf["hidden_size"]) // int(hf["num_attention_heads"]))
                // 2
            )
            short = tuple(float(f) for f in rope["short_factor"])
            long = tuple(float(f) for f in rope["long_factor"])
            if len(short) != hd_half or len(long) != hd_half:
                raise ValueError(
                    f"longrope factor lists must have head_dim//2={hd_half} entries "
                    f"(got {len(short)}/{len(long)})"
                )
            kw = dict(
                rope_dim_factors=short,
                rope_dim_factors_long=long,
                rope_original_max_len=orig,
                rope_attn_scaling=attn_scale,
            )
        else:
            raise ValueError(
                f"unsupported rope_scaling type: {rtype!r} "
                "(llama3; longrope for phi3)"
            )

    # Sliding-window attention: Mistral applies it whenever the config sets
    # one; Qwen2/Qwen3 additionally gate on use_sliding_window and only
    # past max_window_layers — the mixed-layer form has no support here, so
    # it fails loudly rather than serving wrong attention.
    window = int(hf.get("sliding_window") or 0)
    if family in ("qwen2", "qwen3") and window:
        if not hf.get("use_sliding_window", False):
            window = 0
        else:
            # HF semantics: the first max_window_layers layers use FULL
            # attention, the rest slide. Only the uniform cases map here.
            # The missing-key default matches Qwen2Config's (28), so a
            # config without the key resolves the same way HF resolves it.
            mwl = int(hf.get("max_window_layers", 28))
            if mwl >= int(hf["num_hidden_layers"]):
                window = 0  # every layer full attention
            elif mwl != 0:
                raise ValueError(
                    "qwen2 mixed full/sliding layers (0 < max_window_layers < "
                    "num_hidden_layers) is not supported"
                )

    n_heads = int(hf["num_attention_heads"])
    head_dim = int(hf.get("head_dim") or 0)
    if head_dim and head_dim * n_heads == int(hf["hidden_size"]):
        head_dim = 0  # derived value; keep the config canonical

    moe_kw: Dict[str, Any] = {}
    if family == "mixtral":
        moe_kw = dict(
            n_experts=int(hf["num_local_experts"]),
            n_experts_per_tok=int(hf.get("num_experts_per_tok", 2)),
            router_aux_coef=float(hf.get("router_aux_loss_coef", 0.0)),
        )
    if family == "gemma2":
        hd_real = head_dim or int(hf["hidden_size"]) // n_heads
        qpas = float(hf.get("query_pre_attn_scalar") or 0.0)
        qs = qpas**-0.5 if qpas else 0.0
        if qs and abs(qs - hd_real**-0.5) < 1e-12:
            qs = 0.0  # equals the default head_dim scale; keep canonical
        # The runtime assumes gemma2's default alternation (even layers
        # slide, odd full). A config that spells out a DIFFERENT
        # layer_types pattern must fail loudly, not serve wrong masks.
        lt = hf.get("layer_types")
        if lt is not None and window:
            want = [
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(int(hf["num_hidden_layers"]))
            ]
            if list(lt) != want:
                raise ValueError(
                    "gemma2 layer_types deviates from the even-slide/odd-full "
                    "alternation; this pattern is not supported"
                )
        moe_kw.update(
            alt_window=window > 0,
            attn_softcap=float(hf.get("attn_logit_softcapping") or 0.0),
            final_softcap=float(hf.get("final_logit_softcapping") or 0.0),
            query_scale=qs,
            post_norms=True,
        )

    vocab = int(hf["vocab_size"])
    padded = -(-vocab // _VOCAB_MULTIPLE) * _VOCAB_MULTIPLE
    return LlamaConfig(
        **moe_kw,
        vocab_size=padded,
        effective_vocab=vocab if padded != vocab else None,
        d_model=int(hf["hidden_size"]),
        n_layers=int(hf["num_hidden_layers"]),
        n_heads=n_heads,
        n_kv_heads=int(hf.get("num_key_value_heads", n_heads)),
        d_ff=int(hf["intermediate_size"]),
        max_seq_len=int(hf.get("max_position_embeddings", 2048)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        dtype=dtype,
        attn_bias=bool(hf.get("attention_bias", family == "qwen2")),
        qk_norm=family == "qwen3",
        sliding_window=window,
        head_dim_opt=head_dim,
        act_fn="gelu_tanh" if family in _GEMMA_FAMILIES else "silu",
        scale_embed=family in _GEMMA_FAMILIES,
        **kw,
    )


def _lfm2_moe_config(hf: Dict[str, Any], dtype) -> LlamaConfig:
    """LFM2-MoE's published keys: ``layer_types`` (conv | full_attention, one
    per layer), ``conv_L_cache`` taps, ``num_dense_layers`` leading dense
    layers of ``intermediate_size`` before expert layers of
    ``moe_intermediate_size``, the sigmoid router's switches, per-head q/k
    RMSNorm, ``rope_parameters``. No biases anywhere (``conv_bias`` true is
    refused: the operator has none here)."""
    from kakveda_tpu.models.llama import LAYER_KINDS

    n_layers = int(hf["num_hidden_layers"])
    layer_types = tuple(hf["layer_types"])
    if len(layer_types) != n_layers:
        raise ValueError(
            f"lfm2_moe: layer_types names {len(layer_types)} layers, num_hidden_layers is {n_layers}"
        )
    unknown = sorted(set(layer_types) - set(LAYER_KINDS))
    if unknown:
        raise ValueError(f"lfm2_moe: unknown layer type(s) {unknown} (known: {', '.join(LAYER_KINDS)})")
    if hf.get("conv_bias"):
        raise ValueError("lfm2_moe: conv_bias=true is not supported")
    rope = hf.get("rope_parameters") or {}
    if (rope.get("rope_type") or "default") != "default":
        raise ValueError(f"lfm2_moe: unsupported rope_type {rope.get('rope_type')!r}")
    n_heads = int(hf["num_attention_heads"])
    vocab = int(hf["vocab_size"])
    padded = -(-vocab // _VOCAB_MULTIPLE) * _VOCAB_MULTIPLE
    return LlamaConfig(
        vocab_size=padded,
        effective_vocab=vocab if padded != vocab else None,
        d_model=int(hf["hidden_size"]),
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=int(hf.get("num_key_value_heads", n_heads)),
        d_ff=int(hf["moe_intermediate_size"]),
        d_ff_dense=int(hf["intermediate_size"]),
        n_dense_layers=int(hf.get("num_dense_layers", 0)),
        max_seq_len=int(hf.get("max_position_embeddings", 2048)),
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1000000.0))),
        norm_eps=float(hf.get("norm_eps", 1e-5)),
        dtype=dtype,
        qk_norm=True,
        layer_types=layer_types,
        conv_l_cache=int(hf["conv_L_cache"]),
        n_experts=int(hf["num_experts"]),
        n_experts_per_tok=int(hf["num_experts_per_tok"]),
        router_score="sigmoid",
        router_bias=bool(hf.get("use_expert_bias", False)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
    )


# ---------------------------------------------------------------------------
# tensor streaming
# ---------------------------------------------------------------------------


def _iter_weight_files(path: str) -> Iterator[str]:
    """Checkpoint shard files, index-ordered when an index exists."""
    for index_name in ("model.safetensors.index.json", "pytorch_model.bin.index.json"):
        idx = os.path.join(path, index_name)
        if os.path.exists(idx):
            with open(idx) as f:
                files = sorted(set(json.load(f)["weight_map"].values()))
            for fn in files:
                yield os.path.join(path, fn)
            return
    for name in ("model.safetensors", "pytorch_model.bin"):
        p = os.path.join(path, name)
        if os.path.exists(p):
            yield p
            return
    raise FileNotFoundError(f"no model weights (safetensors or bin) under {path}")


def _tensor_reader(path: str) -> Callable[[], Iterator[Tuple[str, np.ndarray]]]:
    """Yield (name, float32 ndarray) one tensor at a time across all shards."""

    def gen() -> Iterator[Tuple[str, np.ndarray]]:
        for fn in _iter_weight_files(path):
            if fn.endswith(".safetensors"):
                from safetensors import safe_open

                # framework="pt": bfloat16 tensors are not representable as
                # numpy dtypes, so route through torch and upcast.
                with safe_open(fn, framework="pt") as f:
                    for name in f.keys():
                        t = f.get_tensor(name)
                        yield name, t.to(dtype=_torch().float32).numpy()
            else:
                sd = _torch().load(fn, map_location="cpu", weights_only=True)
                for name, t in sd.items():
                    yield name, t.to(dtype=_torch().float32).numpy()

    return gen


def _torch():
    import torch

    return torch


# ---------------------------------------------------------------------------
# conversion
# ---------------------------------------------------------------------------


def _empty_tree(cfg: LlamaConfig) -> Params:
    keys = ["attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"]
    if cfg.n_experts:
        keys += ["router", "we_gate", "we_up", "we_down"]
    else:
        keys += ["w_gate", "w_up", "w_down"]
    if cfg.attn_bias:
        keys += ["bq", "bk", "bv"]
    if cfg.post_norms:
        keys += ["post_attn_norm", "post_ffw_norm"]
    if cfg.qk_norm:
        keys += ["q_norm", "k_norm"]
    return {
        "embed": None,
        "layers": [{k: None for k in keys} for _ in range(cfg.n_layers)],
        "final_norm": None,
        "lm_head": None,
    }


def _pad_vocab_rows(arr: np.ndarray, padded: int) -> np.ndarray:
    if arr.shape[0] == padded:
        return arr
    out = np.zeros((padded,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def load_hf_checkpoint(
    path: str,
    *,
    param_dtype=jnp.bfloat16,
    compute_dtype=None,
) -> Tuple[Params, LlamaConfig]:
    """Load + convert an HF Llama checkpoint directory.

    Returns host-resident jnp arrays in ``param_dtype``; use
    :func:`shard_params` to place them on a mesh. ``compute_dtype`` defaults
    to ``param_dtype`` and becomes ``cfg.dtype`` (the activation dtype).
    """
    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    if hf_cfg.get("model_type") in _CONFIG_ONLY_FAMILIES:
        raise ValueError(
            f"model_type={hf_cfg['model_type']!r}: the config maps (hf_config_to_llama) but "
            "this family's checkpoint tensor names are not mapped yet"
        )
    cfg = hf_config_to_llama(hf_cfg, dtype=compute_dtype or param_dtype)
    # Gemma applies RMSNorm gain as (1 + w) with zero-init weights; storing
    # the materialized 1+w keeps every forward path convention-free. The
    # materialized gains stay FLOAT32 (norm_dtype) — cast to bf16 their
    # spacing near 1.0 is 2^-8, which would discard the zero-centered
    # parameterization's precision; rms_norm applies f32 gains in f32
    # (HF GemmaRMSNorm's convention).
    is_gemma = hf_cfg.get("model_type") in _GEMMA_FAMILIES
    norm_off = 1.0 if is_gemma else 0.0
    norm_dtype = jnp.float32 if is_gemma else None

    params = _empty_tree(cfg)
    seen = set()
    # Mixtral expert tensors arrive one (layer, expert, projection) at a
    # time; stage them (already cast to param_dtype) and stack per layer
    # at the end into the [E, ...] arrays the MoE block wants.
    staged: Dict[Tuple[int, str], list] = {}

    def put(
        slot: Dict[str, Any] | Params, key: str, arr: np.ndarray, *, transpose: bool, dtype=None
    ) -> None:
        a = arr.T if transpose else arr
        slot[key] = jnp.asarray(a).astype(dtype or param_dtype)

    def stage_expert(li: int, key: str, ei: int, arr: np.ndarray, *, transpose: bool) -> None:
        lst = staged.setdefault((li, key), [None] * cfg.n_experts)
        if not 0 <= ei < cfg.n_experts:
            raise ValueError(f"expert index {ei} out of range (n_experts={cfg.n_experts})")
        lst[ei] = jnp.asarray(arr.T if transpose else arr).astype(param_dtype)

    for name, arr in _tensor_reader(path)():
        seen.add(name)
        base = name.removeprefix("model.")
        if base == "embed_tokens.weight":
            put(params, "embed", _pad_vocab_rows(arr, cfg.vocab_size), transpose=False)
        elif base == "norm.weight":
            put(params, "final_norm", arr + norm_off, transpose=False, dtype=norm_dtype)
        elif name == "lm_head.weight":
            put(params, "lm_head", _pad_vocab_rows(arr, cfg.vocab_size), transpose=True)
        elif base.startswith("layers."):
            _, idx, rest = base.split(".", 2)
            layer = params["layers"][int(idx)]
            match rest:
                case "input_layernorm.weight":
                    put(layer, "attn_norm", arr + norm_off, transpose=False, dtype=norm_dtype)
                case "post_attention_layernorm.weight":
                    # Gemma-2's post_attention_layernorm is a SANDWICH norm
                    # (applied to the attention output); everywhere else it
                    # is the pre-MLP norm.
                    key = "post_attn_norm" if cfg.post_norms else "mlp_norm"
                    put(layer, key, arr + norm_off, transpose=False, dtype=norm_dtype)
                case "pre_feedforward_layernorm.weight":
                    put(layer, "mlp_norm", arr + norm_off, transpose=False, dtype=norm_dtype)
                case "post_feedforward_layernorm.weight":
                    put(layer, "post_ffw_norm", arr + norm_off, transpose=False, dtype=norm_dtype)
                case "self_attn.q_proj.weight":
                    put(layer, "wq", arr, transpose=True)
                case "self_attn.k_proj.weight":
                    put(layer, "wk", arr, transpose=True)
                case "self_attn.v_proj.weight":
                    put(layer, "wv", arr, transpose=True)
                case "self_attn.q_proj.bias" | "self_attn.k_proj.bias" | "self_attn.v_proj.bias":
                    if not cfg.attn_bias:
                        raise ValueError(
                            f"checkpoint carries {name} but the config resolved attn_bias=False"
                        )
                    put(layer, "b" + rest.split(".")[1][0], arr, transpose=False)
                case "self_attn.o_proj.weight":
                    put(layer, "wo", arr, transpose=True)
                case "mlp.gate_proj.weight":
                    put(layer, "w_gate", arr, transpose=True)
                case "mlp.up_proj.weight":
                    put(layer, "w_up", arr, transpose=True)
                case "mlp.down_proj.weight":
                    put(layer, "w_down", arr, transpose=True)
                case "self_attn.q_norm.weight":
                    put(layer, "q_norm", arr, transpose=False)
                case "self_attn.k_norm.weight":
                    put(layer, "k_norm", arr, transpose=False)
                case "self_attn.qkv_proj.weight":
                    # Phi-3 fuses q/k/v into one [nq+2·nkv, d_model] matrix.
                    nq = cfg.n_heads * cfg.head_dim
                    nkv = cfg.n_kv_heads * cfg.head_dim
                    put(layer, "wq", arr[:nq], transpose=True)
                    put(layer, "wk", arr[nq : nq + nkv], transpose=True)
                    put(layer, "wv", arr[nq + nkv :], transpose=True)
                case "mlp.gate_up_proj.weight":
                    # Phi-3 fuses gate/up into one [2·d_ff, d_model] matrix.
                    put(layer, "w_gate", arr[: cfg.d_ff], transpose=True)
                    put(layer, "w_up", arr[cfg.d_ff :], transpose=True)
                case "self_attn.rotary_emb.inv_freq":
                    pass  # derived, not a parameter
                case "block_sparse_moe.gate.weight":
                    put(layer, "router", arr, transpose=True)
                case _ if rest.startswith("block_sparse_moe.experts."):
                    # experts.{i}.w1|w2|w3.weight — w1=gate, w2=down, w3=up
                    parts = rest.split(".")
                    ei, proj = int(parts[2]), parts[3]
                    key = {"w1": "we_gate", "w2": "we_down", "w3": "we_up"}.get(proj)
                    if key is None or parts[4:] != ["weight"]:
                        raise ValueError(f"unrecognized expert tensor: {name}")
                    stage_expert(int(idx), key, ei, arr, transpose=True)
                case _:
                    raise ValueError(f"unrecognized layer tensor: {name}")
        elif name.endswith("rotary_emb.inv_freq"):
            pass
        else:
            raise ValueError(f"unrecognized tensor: {name}")

    for (li, key), lst in staged.items():
        holes = [i for i, a in enumerate(lst) if a is None]
        if holes:
            raise ValueError(f"layer {li} {key}: missing experts {holes[:8]}")
        params["layers"][li][key] = jnp.stack(lst)

    if params["lm_head"] is None:
        # Gemma ties by class default and omits the key from config.json.
        tie_default = hf_cfg.get("model_type") in _GEMMA_FAMILIES
        if not hf_cfg.get("tie_word_embeddings", tie_default):
            raise ValueError("checkpoint has no lm_head and tie_word_embeddings is false")
        params["lm_head"] = params["embed"].T

    missing = [k for k in ("embed", "final_norm") if params[k] is None] + [
        f"layers.{i}.{k}"
        for i, layer in enumerate(params["layers"])
        for k, v in layer.items()
        if v is None
    ]
    if missing:
        raise ValueError(f"checkpoint missing tensors for: {missing[:8]}{'…' if len(missing) > 8 else ''}")
    return params, cfg


def shard_params(params: Params, cfg: LlamaConfig, mesh) -> Params:
    """Place a host param tree onto ``mesh`` per the Megatron TP layout
    (llama.param_specs_like — also places int8 weight-only trees)."""
    from jax.sharding import NamedSharding

    from kakveda_tpu.models.llama import param_specs_like, specs_for_mesh
    from kakveda_tpu.parallel.distributed import put_global

    specs = specs_for_mesh(param_specs_like(params, cfg), mesh)
    return jax.tree.map(
        lambda x, s: put_global(x, NamedSharding(mesh, s)),
        params,
        specs,
    )
