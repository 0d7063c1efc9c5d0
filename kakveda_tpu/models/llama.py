"""In-tree JAX transformer core — the framework's on-pod model runtime.

Replaces the reference's HTTP hop to an external Ollama daemon
(reference: services/dashboard/app.py:1182-1258) with a transformer that
lives on the same TPU mesh as the GFKB index, so the scenario runner,
playground and LLM failure-classifier share the pod. One forward serves
nine HF families — Llama, Mistral, Qwen2/3, Gemma/Gemma-2, Phi-3,
Mixtral, LFM2-MoE — every family delta a flag on :class:`LlamaConfig`
(models/hf_convert.py maps the configs and, for the first eight, the
checkpoints). A layer is attention or, by ``LlamaConfig.layer_types``, a
gated short convolution (:func:`conv_operator`) whose per-sequence state is
a cache of its own kind beside K/V; its FFN is dense or routed experts
(models/moe.py).

Design is TPU-first, pure functional JAX (no framework classes):

  * params are a plain pytree with a parallel tree of ``PartitionSpec``s —
    tensor parallelism shards attention heads and FFN width over the ``tp``
    mesh axis (Megatron layout: column-parallel qkv/gate/up, row-parallel
    o/down; XLA inserts the all-reduces from the sharding constraints);
  * batch is data-parallel over ``dp``; the sequence axis is context-
    parallel over ``cp`` with **ring attention** (shard_map + ppermute with
    an online-softmax accumulator), so long contexts scale across devices
    while weights stay put — see ``ring_attention``;
  * everything jits with static shapes: fixed seq len per call, KV-cache
    decode for generation.

GQA, RoPE, RMSNorm, SwiGLU — Llama-3 architecture; ``LlamaConfig.llama3_8b``
matches the released 8B shapes, tiny configs drive tests and the hermetic
runtime.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Params = Dict[str, Any]

_NEG_INF = -1e30

LAYER_KINDS = ("full_attention", "conv")


class UnsupportedLayerError(ValueError):
    """A path that cannot run a layer kind the config names (a conv layer
    in the pipeline, in training, under speculation or prefix reuse): it
    refuses the config rather than take a silently wrong path."""


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 264  # ByteTokenizer's 259, padded to a tp-friendly multiple of 8
    d_model: int = 256
    n_layers: int = 2
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1024
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Llama-3.1-style NTK rope scaling (HF `rope_scaling.rope_type=llama3`).
    # factor == 1.0 means off. Kept as flat floats so the config stays
    # hashable (it is a static jit argument).
    rope_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    # When a checkpoint's vocab is padded up to a TP-friendly multiple,
    # `vocab_size` is the padded table size and `effective_vocab` the real
    # tokenizer vocab; sampling masks logits beyond it. None = no padding.
    effective_vocab: Optional[int] = None
    # Model-family knobs (Qwen2 / Mistral share the Llama block structure):
    # q/k/v projection biases (Qwen2), a sliding attention window in tokens
    # (Mistral; 0 = full causal), and an explicit head_dim for checkpoints
    # where it isn't d_model/n_heads (Mistral-NeMo-style). Flat scalars so
    # the config stays hashable (it is a static jit argument).
    attn_bias: bool = False
    sliding_window: int = 0
    head_dim_opt: int = 0  # 0 = derive from d_model // n_heads
    # Gemma-family deltas: tanh-GELU gate activation (GeGLU) and
    # sqrt(d_model) embedding scaling. Gemma's (1+w) RMSNorm convention
    # needs NO flag — conversion stores the materialized 1+w weights.
    act_fn: str = "silu"  # "silu" | "gelu_tanh"
    scale_embed: bool = False
    # Gemma-2 deltas: alternating per-layer sliding window (even layers
    # slide, odd run full causal), tanh softcapping of attention scores
    # and final logits, an explicit query scale (0 = head_dim**-0.5), and
    # sandwich norms (post-attention / post-feedforward RMSNorms inside
    # each residual branch).
    alt_window: bool = False
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    query_scale: float = 0.0
    post_norms: bool = False
    # Qwen3-style per-head q/k RMSNorm (over head_dim, applied pre-RoPE).
    qk_norm: bool = False
    # Phi-3 longrope: per-dimension inverse-frequency divisors (length
    # head_dim/2, tuples so the config stays hashable). HF semantics are
    # DYNAMIC: short factors while the running sequence fits the original
    # pretraining context (rope_original_max_len), long factors once it
    # exceeds it; the attention scaling on cos/sin is static.
    rope_dim_factors: tuple = ()  # short factors
    rope_dim_factors_long: tuple = ()
    rope_attn_scaling: float = 1.0
    # KV-cache quantization ("" | "int8"): int8 rows + per-row f32 scales
    # halve the cache — the dominant HBM resident past moderate
    # batch·context — doubling the servable window per chip. Serving-layer
    # knob (KAKVEDA_KV_QUANT=int8 on the runtime), orthogonal to weight
    # quant; parity bounds in tests/test_quant.py.
    kv_quant: str = ""

    def layer_window(self, li: int) -> int:
        """Effective sliding window for layer ``li`` (0 = full causal)."""
        if not self.sliding_window:
            return 0
        if self.alt_window and li % 2 == 1:
            return 0
        return self.sliding_window
    # Sparse Mixture-of-Experts MLP (Mixtral family; models/moe.py).
    # n_experts == 0 means dense. expert_capacity_factor <= 0 means no-drop
    # dispatch (exact; decode + parity tests); positive caps each expert at
    # ceil(T·k/E·factor) tokens per dispatch (training discipline).
    n_experts: int = 0
    n_experts_per_tok: int = 2
    expert_capacity_factor: float = 0.0
    # Load-balancing aux-loss coefficient for MoE fine-tunes (HF Mixtral's
    # router_aux_loss_coef); 0 disables the aux term in lm_loss.
    router_aux_coef: float = 0.0
    # The router's published switches (models/moe.py:router_topk). Mixtral:
    # softmax over all experts, top-k, renormalised. LFM2: sigmoid scores,
    # selection by score + a per-expert bias (``use_expert_bias``: the layer
    # carries ``expert_bias`` [E], used to SELECT only), weights from the
    # unbiased scores, renormalised when ``norm_topk_prob``, times
    # ``routed_scaling_factor``.
    router_score: str = "softmax"  # "softmax" | "sigmoid"
    router_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # ``n_dense_layers`` leading layers keep a dense SwiGLU of width
    # ``d_ff_dense`` before the expert layers of width ``d_ff`` (LFM2's
    # num_dense_layers / intermediate_size beside moe_intermediate_size).
    n_dense_layers: int = 0
    d_ff_dense: int = 0
    # Layer-type list (LFM2 ``layer_types``): one of LAYER_KINDS per layer;
    # () = every layer attention. A "conv" layer's operator is the gated
    # short convolution (``conv_operator``) with a depthwise causal filter of
    # ``conv_l_cache`` taps; its per-sequence state is the last
    # ``conv_l_cache - 1`` rows of the gated input, not K/V.
    layer_types: tuple = ()
    conv_l_cache: int = 3

    def layer_kind(self, li: int) -> str:
        return self.layer_types[li] if self.layer_types else "full_attention"

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """Indices of the layers of one kind, in order: a layer's place in
        this tuple is its index into that kind's cache list."""
        return tuple(i for i in range(self.n_layers) if self.layer_kind(i) == kind)

    def layer_is_moe(self, li: int) -> bool:
        return bool(self.n_experts) and li >= self.n_dense_layers

    @property
    def has_conv(self) -> bool:
        return "conv" in self.layer_types

    @property
    def head_dim(self) -> int:
        return self.head_dim_opt or self.d_model // self.n_heads

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def tinyllama_1b(cls) -> "LlamaConfig":
        """TinyLlama-1.1B widths — the one full-width shape with a chip
        history in this repo; fits one 16 GB chip beside a 4 GiB index."""
        return cls(
            vocab_size=32000,
            d_model=2048,
            n_layers=22,
            n_heads=32,
            n_kv_heads=4,
            d_ff=5632,
            max_seq_len=2048,
        )

    @classmethod
    def llama3_8b(cls, vocab_size: int = 128256) -> "LlamaConfig":
        return cls(
            vocab_size=vocab_size,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_ff=14336,
            max_seq_len=8192,
            rope_theta=500000.0,
        )


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: LlamaConfig, dtype=jnp.float32) -> Params:
    """He-ish init; compute runs in cfg.dtype. ``dtype`` is the STORAGE
    dtype of the matrices: f32 for training and the parity tests, bf16 for
    a served model — what the HF loader delivers (models/hf_convert.py), so
    a seeded random model occupies what a deployed checkpoint would. Each
    leaf is drawn in f32 and cast on its own, so the f32 transient is one
    leaf, never the tree. Norm gains stay f32 either way."""
    keys = jax.random.split(rng, cfg.n_layers + 2)

    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    hd = cfg.head_dim
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 7)
        layer = {
            "attn_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "mlp_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if cfg.layer_kind(i) == "conv":
            layer["conv_in"] = dense(k[0], cfg.d_model, (cfg.d_model, 3 * cfg.d_model))
            layer["conv_w"] = dense(k[1], cfg.conv_l_cache, (cfg.conv_l_cache, cfg.d_model))
            layer["conv_out"] = dense(k[3], cfg.d_model, (cfg.d_model, cfg.d_model))
        else:
            layer["wq"] = dense(k[0], cfg.d_model, (cfg.d_model, cfg.n_heads * hd))
            layer["wk"] = dense(k[1], cfg.d_model, (cfg.d_model, cfg.n_kv_heads * hd))
            layer["wv"] = dense(k[2], cfg.d_model, (cfg.d_model, cfg.n_kv_heads * hd))
            layer["wo"] = dense(k[3], cfg.n_heads * hd, (cfg.n_heads * hd, cfg.d_model))
        if cfg.layer_is_moe(i):
            ke = jax.random.split(k[4], 3)
            layer["router"] = dense(k[5], cfg.d_model, (cfg.d_model, cfg.n_experts))
            layer["we_gate"] = dense(ke[0], cfg.d_model, (cfg.n_experts, cfg.d_model, cfg.d_ff))
            layer["we_up"] = dense(ke[1], cfg.d_model, (cfg.n_experts, cfg.d_model, cfg.d_ff))
            layer["we_down"] = dense(ke[2], cfg.d_ff, (cfg.n_experts, cfg.d_ff, cfg.d_model))
            if cfg.router_bias:
                layer["expert_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        else:
            ff = cfg.d_ff_dense if cfg.n_experts and cfg.d_ff_dense else cfg.d_ff
            layer["w_gate"] = dense(k[4], cfg.d_model, (cfg.d_model, ff))
            layer["w_up"] = dense(k[5], cfg.d_model, (cfg.d_model, ff))
            layer["w_down"] = dense(k[6], ff, (ff, cfg.d_model))
        if cfg.attn_bias and "wq" in layer:
            layer["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
            layer["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
            layer["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        if cfg.post_norms:
            layer["post_attn_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
            layer["post_ffw_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        if cfg.qk_norm and "wq" in layer:
            layer["q_norm"] = jnp.ones((hd,), jnp.float32)
            layer["k_norm"] = jnp.ones((hd,), jnp.float32)
        layers.append(layer)
    return {
        "embed": dense(keys[-2], cfg.d_model, (cfg.vocab_size, cfg.d_model)),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": dense(keys[-1], cfg.d_model, (cfg.d_model, cfg.vocab_size)),
    }


def param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpec tree: Megatron TP layout over the ``tp`` axis."""

    def layer_specs(li: int) -> Params:
        layer = {"attn_norm": P(), "mlp_norm": P()}
        if cfg.layer_kind(li) == "conv":
            # Replicated: the depthwise filter and the gates act per channel,
            # and a column-split in-projection would cut across B | C | x.
            layer.update({"conv_in": P(), "conv_w": P(), "conv_out": P()})
        else:
            layer.update({"wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"), "wo": P("tp", None)})
            if cfg.attn_bias:
                # Column-parallel biases follow their projection's out axis.
                layer.update({"bq": P("tp"), "bk": P("tp"), "bv": P("tp")})
            if cfg.qk_norm:
                layer.update({"q_norm": P(), "k_norm": P()})
        if cfg.layer_is_moe(li):
            # Expert parallelism over ``ep`` on the stacked-expert axis,
            # composing with TP over the ffn width; the router is tiny and
            # replicated.
            layer.update(
                {
                    "router": P(),
                    "we_gate": P("ep", None, "tp"),
                    "we_up": P("ep", None, "tp"),
                    "we_down": P("ep", "tp", None),
                }
            )
            if cfg.router_bias:
                layer["expert_bias"] = P()
        else:
            layer.update({"w_gate": P(None, "tp"), "w_up": P(None, "tp"), "w_down": P("tp", None)})
        if cfg.post_norms:
            layer.update({"post_attn_norm": P(), "post_ffw_norm": P()})
        return layer

    return {
        "embed": P("tp", None),  # vocab-sharded table
        "layers": [layer_specs(li) for li in range(cfg.n_layers)],
        "final_norm": P(),
        "lm_head": P(None, "tp"),
    }


def specs_for_mesh(specs, mesh: Mesh):
    """Drop spec axes the mesh doesn't have (→ replicated on that dim):
    a MoE spec's ``ep`` axis on a dp×tp serving mesh, or ``tp`` on a pure-dp
    mesh, degrades to replication instead of erroring."""
    names = set(mesh.axis_names)

    def fix(s):
        return P(*(a if a in names else None for a in s))

    return jax.tree.map(fix, specs, is_leaf=lambda x: isinstance(x, P))


def _is_quant_leaf(x) -> bool:
    return isinstance(x, dict) and set(x.keys()) == {"q", "s"}


def param_specs_like(params: Params, cfg: LlamaConfig) -> Params:
    """Spec tree matching ``params``' structure — handles int8 weight-only
    leaves (models/quant.py): the int8 matrix shards like the original
    weight and the per-output-channel scale drops the contraction (in) axis
    — sharded for column-parallel projections, replicated for row-parallel,
    and keeping the leading ``ep`` axis for stacked MoE experts."""
    base = param_specs(cfg)

    def expand(w, spec):
        if _is_quant_leaf(w):
            s_spec = P(*spec[:-2], spec[-1]) if len(spec) >= 2 else P(None)
            return {"q": spec, "s": s_spec}
        return spec

    return jax.tree.map(expand, params, base, is_leaf=_is_quant_leaf)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------



def wmat(w, dt) -> jax.Array:
    """Materialize a dense weight at compute dtype. Accepts a raw array or
    an int8 weight-only pair ``{"q", "s"}`` (models/quant.py) — the dequant
    multiply fuses into the consuming matmul, so quantized weights stream
    from HBM at int8 width. Handles 2-D dense and stacked [E, in, out]
    MoE expert weights alike (scale broadcasts over the in axis)."""
    if isinstance(w, dict):
        return w["q"].astype(dt) * w["s"].astype(dt)[..., None, :]
    return w.astype(dt)


QKV_KEYS = ("wq", "wk", "wv")


def _one_device(w) -> bool:
    sharding = getattr(jax.tree.leaves(w)[0], "sharding", None)
    return sharding is None or len(sharding.device_set) == 1


def fuse_qkv(params: Params) -> Params:
    """The tree with each attention layer's ``wq``, ``wk``, ``wv`` held as
    one weight ``wqkv`` [d_model, (H + 2·KV)·hd], q | k | v columns in that
    order, which :func:`qkv_proj` reads with one dot. On the TPU three
    separate weights each change layout at every call of a program that
    reads them, and the dot that reads ``wq`` waits for a copy of its own
    normed input; one weight does neither. Each column is the same
    contraction, so the logits are the same.

    Serving only: ``LlamaRuntime`` fuses where it takes its params, and the
    tree it is given keeps its three leaves (training, conversion, sharding
    and quantization all produce and read those). An int8 weight-only pair
    fuses as a pair, its per-column scales concatenated alike. A layer whose
    weights span more than one device (``shard_params``' column TP) keeps
    its three: a fused weight's columns would have to be interleaved per
    shard. Conv layers have no such keys. Built layer by layer into new
    dicts; the three originals go when the caller lets go of its tree."""
    layers = []
    for layer in params["layers"]:
        if all(k in layer for k in QKV_KEYS) and _one_device(layer["wq"]):
            parts = [layer[k] for k in QKV_KEYS]
            layer = {k: v for k, v in layer.items() if k not in QKV_KEYS}
            layer["wqkv"] = jax.tree.map(lambda *a: jnp.concatenate(a, axis=-1), *parts)
        layers.append(layer)
    return {**params, "layers": layers}


def unfuse_qkv(params: Params, cfg: LlamaConfig) -> Params:
    """:func:`fuse_qkv` undone: ``wq``, ``wk``, ``wv`` cut out of ``wqkv``
    (the tree a checkpoint was written from)."""
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def cut(a):
        return jnp.split(a, [nq, nq + nkv], axis=-1)

    layers = []
    for layer in params["layers"]:
        if "wqkv" in layer:
            layer = dict(layer)
            w = layer.pop("wqkv")
            if isinstance(w, dict):  # int8 pair
                layer.update({k: {"q": q, "s": s} for k, q, s in zip(QKV_KEYS, cut(w["q"]), cut(w["s"]))})
            else:
                layer.update(zip(QKV_KEYS, cut(w)))
        layers.append(layer)
    return {**params, "layers": layers}


def qkv_proj(
    h: jax.Array, layer: Params, cfg: LlamaConfig, dt
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q/k/v projections with optional attention biases (Qwen2-style): one
    dot where the layer holds ``wqkv`` (:func:`fuse_qkv`), three otherwise.
    h: [B, S, d_model] -> q [B,S,H,hd], k/v [B,S,KV,hd]."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    if "wqkv" in layer:
        nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        q, k, v = jnp.split(h @ wmat(layer["wqkv"], dt), [nq, nq + nkv], axis=-1)
    else:
        q = h @ wmat(layer["wq"], dt)
        k = h @ wmat(layer["wk"], dt)
        v = h @ wmat(layer["wv"], dt)
    if "bq" in layer:
        q = q + layer["bq"].astype(dt)
        k = k + layer["bk"].astype(dt)
        v = v + layer["bv"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if "q_norm" in layer:
        # Qwen3 per-head q/k RMSNorm over head_dim, pre-RoPE.
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if cfg.query_scale:
        # The kernels scale scores by head_dim**-0.5; fold an explicit
        # query scale (Gemma-2's query_pre_attn_scalar**-0.5) into q so
        # every kernel stays convention-free. Commutes with RoPE
        # (rotations are linear) — but must apply AFTER the optional
        # q_norm: RMSNorm is scale-invariant, so a pre-norm fold would be
        # silently cancelled for any config combining both flags.
        q = q * jnp.asarray(cfg.query_scale * math.sqrt(hd), dt)
    return q, k, v


def mask_pad_vocab(logits: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """−inf the padded vocab columns (converted checkpoints pad the table
    to a TP-friendly multiple; sampling must never emit a pad id). Works
    on [..., V]; identity when the vocab isn't padded."""
    if cfg.effective_vocab is None:
        return logits
    return logits.at[..., cfg.effective_vocab :].set(-jnp.inf)


def softcap_logits(logits: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 tanh logit softcapping: cap·tanh(x/cap); identity at cap=0.
    The ONE definition shared by every decode path."""
    if not cap:
        return logits
    return cap * jnp.tanh(logits / cap)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if w.dtype == jnp.float32 and x.dtype != jnp.float32:
        # f32 gain weights under a low-precision compute dtype apply in
        # f32 BEFORE the downcast — Gemma's convention (its materialized
        # 1+w gains stay f32 at conversion; bf16 spacing near 1.0 is 2^-8,
        # which would swamp the zero-centered parameterization).
        return ((x32 * scale) * w).astype(x.dtype)
    return (x32 * scale).astype(x.dtype) * w.astype(x.dtype)


def _rope_freqs(
    cfg: LlamaConfig,
    positions: jax.Array,
    seq_len: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables [..., head_dim/2] for given positions.

    With ``rope_factor > 1`` applies Llama-3.1's wavelength-dependent NTK
    scaling (matches HF ``_compute_llama3_parameters``): low-frequency
    components are stretched by ``factor``, high-frequency kept, and the
    band between ``low/high_freq_factor`` wavelength thresholds is blended.

    ``seq_len`` ([B] or scalar) overrides the longrope regime-select
    length. Chunked prefill MUST pass the full prompt length here: an
    early chunk's ``max(positions)+1`` is below ``rope_original_max_len``
    even when the whole prompt is past it, and rotating early-chunk K/V
    with short factors would diverge from single-shot prefill of the same
    prompt (whose positions span the full length).
    """
    half = cfg.head_dim // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if cfg.rope_dim_factors:
        # Phi-3 longrope: per-dim frequency divisors. HF switches short →
        # long factors once the running sequence exceeds the original
        # pretraining context (seq_len = max position + 1). The regime is
        # selected PER ROW — batch-global selection (what a shared HF
        # inv_freq buffer does) would let one long sequence flip its
        # co-batched neighbors' rotations, breaking batched-vs-solo
        # parity in the continuous batcher. A traced select; no retrace.
        inv_short = inv / jnp.asarray(cfg.rope_dim_factors, jnp.float32)
        if cfg.rope_dim_factors_long:
            inv_long = inv / jnp.asarray(cfg.rope_dim_factors_long, jnp.float32)
            if seq_len is None:
                eff_len = jnp.max(positions, axis=-1, keepdims=True) + 1
            else:
                eff_len = jnp.asarray(seq_len, jnp.int32)[..., None]
            long_row = eff_len > cfg.rope_original_max_len  # [..., 1]
            ang = positions[..., None].astype(jnp.float32)
            ang = jnp.where(long_row[..., None], ang * inv_long, ang * inv_short)
            scale = cfg.rope_attn_scaling
            return jnp.cos(ang) * scale, jnp.sin(ang) * scale
        inv = inv_short
    if cfg.rope_factor != 1.0:
        wavelen = 2.0 * math.pi / inv
        low_wl = cfg.rope_original_max_len / cfg.rope_low_freq_factor
        high_wl = cfg.rope_original_max_len / cfg.rope_high_freq_factor
        smooth = (cfg.rope_original_max_len / wavelen - cfg.rope_low_freq_factor) / (
            cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
        )
        blended = (1.0 - smooth) * inv / cfg.rope_factor + smooth * inv
        inv = jnp.where(wavelen > low_wl, inv / cfg.rope_factor, jnp.where(wavelen < high_wl, inv, blended))
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., half]
    if cfg.rope_attn_scaling != 1.0:
        return (
            jnp.cos(ang) * cfg.rope_attn_scaling,
            jnp.sin(ang) * cfg.rope_attn_scaling,
        )
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [B?, S, D/2] broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)  # [B, S, 1, half]
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, KV, D] -> [B, S, KV*n_rep, D] (GQA broadcast).

    Only the reference-oracle `causal_attention` and the ring fallback use
    this — the production paths keep the group axis explicit
    (models/attention.py) so K/V are never materialized ``n_rep``-wide."""
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(b, s, kv * n_rep, d)


def causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, q_off: jax.Array | int = 0, window: int = 0
) -> jax.Array:
    """Plain causal attention — the readable O(S²)-memory reference oracle
    that the fused paths are parity-tested against (tests/test_llama.py).
    q: [B,Sq,H,D], k/v: [B,Sk,H,D] (already GQA-repeated). ``q_off`` is the
    global position of q[0] relative to k[0] (for cached decode); ``window``
    > 0 restricts each query to the last ``window`` positions (sliding-window
    attention, Mistral semantics: keep iff q_pos − k_pos < window). Returns
    [B,Sq,H,D]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    q_pos = jnp.arange(q.shape[1]) + q_off
    k_pos = jnp.arange(k.shape[1])
    mask = q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    n_chunks: int,
    key_block: int = 2048,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Ring attention body — runs *inside* shard_map, sequence sharded over
    ``axis_name``. Each step attends the local queries against the currently
    held K/V chunk with the right global causal mask, folds the result into
    an online-softmax accumulator, then rotates K/V one hop around the ring
    (ppermute over ICI). FLOP-pattern equivalent to blockwise flash
    attention across devices; no device ever holds the full sequence.

    q: [B, S_local, H_local, D]; k/v: [B, S_local, KV_local, D] —
    **un-repeated** GQA heads, so each ring hop moves the raw KV chunk
    (n_rep× less ICI traffic than rotating repeated heads).

    Within each hop the held chunk is processed in ``key_block``-column
    sub-blocks feeding the SAME online-softmax accumulators, so the
    transient score tensor is [B,KV,R,S_l,key_block] f32 — never
    [..., S_l, S_l]. At S_local = 8k that caps the per-hop scratch at
    ~key_block/S_l of the unblocked cost (blockwise/flash structure at
    the second level, after the ring's device level).
    """
    b, s_l, h, d = q.shape
    kv = k.shape[2]
    r = h // kv
    scale = d**-0.5
    me = jax.lax.axis_index(axis_name)

    q5 = q.reshape(b, s_l, kv, r, d)
    q_pos = me * s_l + jnp.arange(s_l)  # global positions of local queries
    m = jnp.full((b, kv, r, s_l), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, kv, r, s_l), jnp.float32)
    acc = jnp.zeros((b, kv, r, s_l, d), jnp.float32)

    kb = min(key_block, s_l)

    perm = [(j, (j + 1) % n_chunks) for j in range(n_chunks)]
    k_cur, v_cur = k, v
    for i in range(n_chunks):  # static unroll: n_chunks is a mesh constant
        src = (me - i) % n_chunks  # whose chunk we hold this step
        for j in range(0, s_l, kb):  # sub-blocks (static ragged tail ok)
            jb = min(kb, s_l - j)
            k_sub = jax.lax.slice_in_dim(k_cur, j, j + jb, axis=1)
            v_sub = jax.lax.slice_in_dim(v_cur, j, j + jb, axis=1)
            k_pos = src * s_l + j + jnp.arange(jb)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", q5, k_sub).astype(jnp.float32) * scale
            if softcap:
                scores = softcap_logits(scores, softcap)
            keep2d = q_pos[:, None] >= k_pos[None, :]
            if window:
                keep2d &= (q_pos[:, None] - k_pos[None, :]) < window
            mask = keep2d[None, None, None]
            scores = jnp.where(mask, scores, _NEG_INF)

            blk_max = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m, blk_max)
            # Re-mask after the exp: if every score in this block is masked
            # the subtraction would give exp(0)=1 on the first such step.
            p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(v_sub.dtype), v_sub
            ).astype(jnp.float32)
            m = m_new

        if i < n_chunks - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l[..., None], 1e-20)
    # [B, KV, R, S, D] -> [B, S, H, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s_l, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _sequence_attention(
    q: jax.Array,
    kv: Params,
    entry: Optional[Params],
    window: int,
    softcap: float,
    *,
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
    cp_axis: Optional[str] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """What ``forward`` and the pipeline stage hand :func:`transformer_block`:
    no cache, attention over the whole sequence — grouped XLA, or the ring
    over ``cp_axis`` when the mesh splits the sequence."""
    k, v = kv["k"], kv["v"]
    if mesh is not None and cp_axis is not None and mesh.shape[cp_axis] > 1:
        # the ring rotates sequence-major [B, S, KV, D] chunks
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        n_cp = mesh.shape[cp_axis]
        tp = "tp" if "tp" in mesh.axis_names else None
        tp_size = mesh.shape[tp] if tp else 1
        if cfg.n_kv_heads % tp_size:
            # TP shards the head axis; grouped ring needs whole KV groups
            # per shard, so fall back to rotating repeated heads.
            k = _repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
            v = _repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
        spec = P("dp", cp_axis, tp, None)
        attn = jax.shard_map(
            partial(
                ring_attention_local,
                axis_name=cp_axis,
                n_chunks=n_cp,
                window=window,
                softcap=softcap,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    else:
        # Grouped attention over the whole sequence: K/V head-major, no
        # GQA repeat, differentiable XLA path (training runs through here).
        from kakveda_tpu.models.attention import _gqa_xla

        attn = _gqa_xla(q, k, v, 0, None, window=window, softcap=softcap)
    return attn, entry


def _act(x: jax.Array, act_fn: str) -> jax.Array:
    if act_fn == "gelu_tanh":  # Gemma's GeGLU gate
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _mlp_block(x: jax.Array, layer: Params, act_fn: str = "silu") -> jax.Array:
    dt = x.dtype
    gate = _act(x @ wmat(layer["w_gate"], dt), act_fn)
    up = x @ wmat(layer["w_up"], dt)
    return (gate * up) @ wmat(layer["w_down"], dt)


def embed_tokens(params: Params, cfg: LlamaConfig, tokens: jax.Array) -> jax.Array:
    """Token embedding at compute dtype; Gemma scales by sqrt(d_model)."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    return x


def mlp_block(
    x: jax.Array, layer: Params, cfg: LlamaConfig, return_aux: bool = False,
    token_mask: Optional[jax.Array] = None,
):
    """Dense SwiGLU or sparse-MoE MLP, keyed on the layer's params
    (MoE layers carry a ``router``; models/moe.py). With ``return_aux``
    returns ``(out, aux, counts)`` — aux is the layer's load-balancing loss
    (0 for dense layers), counts the pairs each expert got (None for dense
    layers). ``token_mask`` [B, S] keeps tokens that stand for nothing (a
    serving pool's idle slots) out of the experts' dispatch."""
    if "router" in layer:
        from kakveda_tpu.models.moe import moe_mlp

        return moe_mlp(x, layer, cfg, return_aux=return_aux, token_mask=token_mask)
    out = _mlp_block(x, layer, cfg.act_fn)
    return (out, jnp.zeros((), jnp.float32), None) if return_aux else out


def init_conv_state(cfg: LlamaConfig, batch: int) -> jax.Array:
    """A conv layer's per-sequence state: the last ``conv_l_cache - 1`` rows
    of its gated input ``u``; zeros stand for "before the sequence"."""
    return jnp.zeros((batch, cfg.conv_l_cache - 1, cfg.d_model), cfg.dtype)


def conv_operator(
    h: jax.Array, layer: Params, state: Optional[jax.Array] = None, valid: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """LFM2's gated short convolution, THE one body every forward path
    calls with a view of that layer's state:

        [B, C, x~] = split3(h W_in);  u = B * x~
        v_t = sum_j w[j] * u_{t-(L-1)+j}   (depthwise, causal, L taps)
        out = (C * v) W_out

    ``h`` [N, S, D] (already normed); ``state`` [N, L-1, D], the rows of
    ``u`` before ``h``'s first position (None = the sequence starts here);
    ``valid`` [N, S] — False marks pad positions, whose ``u`` is zeroed so a
    left-padded prompt convolves exactly as the unpadded one. Returns
    (out [N, S, D], the new state: the last L-1 rows of ``u``). The taps are
    summed in float32."""
    dt = h.dtype
    s = h.shape[1]
    gate_b, gate_c, xt = jnp.split(h @ wmat(layer["conv_in"], dt), 3, axis=-1)
    u = gate_b * xt
    if valid is not None:
        u = jnp.where(valid[..., None], u, jnp.zeros((), dt))
    w = layer["conv_w"].astype(jnp.float32)  # [L, D], w[L-1] on the current position
    taps = w.shape[0]
    if state is None:
        state = jnp.zeros((h.shape[0], taps - 1, h.shape[2]), dt)
    ext = jnp.concatenate([state.astype(dt), u], axis=1)  # [N, L-1+S, D]
    v = sum(ext[:, j:j + s].astype(jnp.float32) * w[j] for j in range(taps))
    out = (gate_c * v.astype(dt)) @ wmat(layer["conv_out"], dt)
    return out, ext[:, s:]


def transformer_block(
    x: jax.Array,
    layer: Params,
    cfg: LlamaConfig,
    li: int,
    cos: jax.Array,
    sin: jax.Array,
    attend,
    entry: Optional[Params] = None,
    *,
    conv_valid: Optional[jax.Array] = None,
    token_mask: Optional[jax.Array] = None,
    return_aux: bool = False,
):
    """THE transformer block, the one body ``forward``, ``decode_step``, the
    serving chunk (serving._forward_wide) and the pipeline stage all run:
    norm -> conv operator or attention -> optional sandwich norm -> residual
    -> norm -> FFN -> optional sandwich norm -> residual. Every model-family
    flag is read HERE (or in ``qkv_proj`` / ``mlp_block`` below it), so a new
    flag or layer kind is one edit and no path can forget it.

    A path owns only where a layer's state lives. ``entry`` is layer ``li``'s
    own buffers of the path's cache — ``{"k", "v"}`` (+ ``"ks"``, ``"vs"``
    under int8 K/V) or ``{"conv"}`` — None for a path without one. For an
    attention layer the path's ``attend(q, kv, entry, window, softcap)`` gets
    the rotated queries [B, S, H, D] and the new rows ``kv`` head-major
    [B, KV, S, D], already int8 + scales under the same keys when the cache
    is quantized; it writes them where its cache wants them and returns
    (attention [B, S, H, D], the new entry).

    ``conv_valid`` [B, S] keeps pad positions out of a conv state,
    ``token_mask`` [B, S] keeps tokens out of the experts' dispatch. Returns
    (x, the new entry, aux, pairs); the last two are ``mlp_block``'s under
    ``return_aux``, else None."""
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    dt = h.dtype
    if cfg.layer_kind(li) == "conv":
        mix, state = conv_operator(h, layer, None if entry is None else entry["conv"], conv_valid)
        entry = {"conv": state}
    else:
        b, s, _ = h.shape
        q, k, v = qkv_proj(h, layer, cfg, dt)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        # head-major [B, KV, S, D], at the dtype a cache holds
        kv = {"k": k.transpose(0, 2, 1, 3).astype(cfg.dtype), "v": v.transpose(0, 2, 1, 3).astype(cfg.dtype)}
        if entry is not None and cfg.kv_quant == "int8":
            # One per-row quantizer before any path's write: a slot's cache
            # bytes equal its solo decode's, so int8 parity is exact.
            (k8, k_sc), (v8, v_sc) = _kv_quant_rows(kv["k"]), _kv_quant_rows(kv["v"])
            kv = {"k": k8, "v": v8, "ks": k_sc, "vs": v_sc}
        attn, entry = attend(q, kv, entry, cfg.layer_window(li), cfg.attn_softcap)
        mix = attn.reshape(b, s, cfg.n_heads * cfg.head_dim) @ wmat(layer["wo"], dt)
    if "post_attn_norm" in layer:  # Gemma-2 sandwich norm
        mix = rms_norm(mix, layer["post_attn_norm"], cfg.norm_eps)
    x = x + mix

    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    out = mlp_block(h, layer, cfg, return_aux=return_aux, token_mask=token_mask)
    m, aux, pairs = out if return_aux else (out, None, None)
    if "post_ffw_norm" in layer:
        m = rms_norm(m, layer["post_ffw_norm"], cfg.norm_eps)
    return x + m, entry, aux, pairs


def run_layers(
    params: Params, cfg: LlamaConfig, x, cos, sin, attend, cache: Optional[Params] = None, **block_kw
):
    """Every layer's :func:`transformer_block` in turn, and the bookkeeping
    of a cache per layer type, written once: layer ``li``'s entry is at its
    place in ``cfg.layers_of(kind)`` of each of its kind's lists (K/V slabs
    and int8 scales for attention, ``conv`` for conv). ``cache`` is those
    lists (a ``pos`` beside them is ignored), or None for a path that keeps
    no state. Returns (x, the new lists in the same order — None without a
    cache, [(aux, pairs)] per layer)."""
    new = None if cache is None else {key: [] for key in cache if key != "pos"}
    place = {li: i for kind in LAYER_KINDS for i, li in enumerate(cfg.layers_of(kind))}
    stats = []
    for li, layer in enumerate(params["layers"]):
        entry = None
        if cache is not None:
            keys = ("conv",) if cfg.layer_kind(li) == "conv" else tuple(key for key in new if key != "conv")
            entry = {key: cache[key][place[li]] for key in keys}
        x, entry, aux, pairs = transformer_block(x, layer, cfg, li, cos, sin, attend, entry, **block_kw)
        stats.append((aux, pairs))
        if new is not None:
            for key, val in entry.items():
                new[key].append(val)
    return x, new, stats


def lm_logits(params: Params, cfg: LlamaConfig, x: jax.Array) -> jax.Array:
    """The tail every path ends with: final norm, lm head, f32 logits,
    Gemma-2's final softcap."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ wmat(params["lm_head"], cfg.dtype)).astype(jnp.float32)
    return softcap_logits(logits, cfg.final_softcap)


def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,
    *,
    mesh: Optional[Mesh] = None,
    cp_axis: Optional[str] = None,
    positions: Optional[jax.Array] = None,
    with_aux: bool = False,
):
    """Full-sequence forward: tokens [B, S] -> logits [B, S, vocab].

    With ``mesh``+``cp_axis`` the sequence axis is context-parallel and
    attention runs as a ring over that axis; RoPE positions are the *global*
    positions, threaded in by the caller via ``positions`` when the local
    shard doesn't start at 0 (handled automatically under jit because the
    whole [B, S] array is logically global). ``with_aux`` returns
    ``(logits, aux)`` where aux is the summed MoE load-balancing loss
    across layers (0 for dense models).
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    cos, sin = _rope_freqs(cfg, positions)

    x = embed_tokens(params, cfg, tokens)
    attend = partial(_sequence_attention, cfg=cfg, mesh=mesh, cp_axis=cp_axis)
    x, _, stats = run_layers(params, cfg, x, cos, sin, attend, return_aux=True)
    aux = sum((a for a, _ in stats), jnp.zeros((), jnp.float32))
    logits = lm_logits(params, cfg, x)
    return (logits, aux) if with_aux else logits


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------


def init_cache(cfg: LlamaConfig, batch: int, max_len: Optional[int] = None) -> Params:
    """Per-layer K/V buffer lists, **head-major** [B, KV, max_len, hd]: each
    kv-head's rows are contiguous, so the flash kernel DMA-streams
    [l_blk, hd] tiles without striding over the head axis. Each layer's
    buffer is dynamic-update-sliced independently, which XLA turns into
    in-place row writes — one stacked [L, ...] array (whether rebuilt with
    jnp.stack or updated with a leading-dim DUS) either rewrites the whole
    cache per decode step or compiles pathologically at 1B scale.

    With ``cfg.kv_quant == "int8"`` the K/V buffers are int8 with per-row
    (per position, per kv-head) f32 scales ``ks``/``vs`` [B, KV, max_len]:
    the cache — the dominant HBM resident past moderate batch·context —
    halves, doubling the servable context window per chip. Rows quantize
    on write and dequantize on read (`_kv_quant_rows`/`_kv_dequant`)."""
    ml = max_len or cfg.max_seq_len
    hd = cfg.head_dim
    shape = (batch, cfg.n_kv_heads, ml, hd)
    # A cache per layer type: the K/V lists hold the attention layers only
    # (layer li's entry is at its place in ``cfg.layers_of("full_attention")``),
    # and a config with conv layers gets ``conv``, one [B, L-1, D] state per
    # conv layer (int8 KV stays attention's alone).
    n_attn = len(cfg.layers_of("full_attention"))
    cache = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.kv_quant == "int8":
        cache.update(
            k=[jnp.zeros(shape, jnp.int8) for _ in range(n_attn)],
            v=[jnp.zeros(shape, jnp.int8) for _ in range(n_attn)],
            ks=[jnp.zeros(shape[:3], jnp.float32) for _ in range(n_attn)],
            vs=[jnp.zeros(shape[:3], jnp.float32) for _ in range(n_attn)],
        )
    else:
        cache.update(
            k=[jnp.zeros(shape, cfg.dtype) for _ in range(n_attn)],
            v=[jnp.zeros(shape, cfg.dtype) for _ in range(n_attn)],
        )
    if cfg.has_conv:
        cache["conv"] = [init_conv_state(cfg, batch) for _ in cfg.layers_of("conv")]
    return cache


def _kv_quant_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8 quantization of K/V rows [..., hd]:
    returns (int8 values, f32 scales [...]) with x ≈ q · scale. Per-row
    absmax keeps the error relative to each position's own magnitude —
    a shared tensor scale would crush early-layer K norms."""
    x32 = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x32), axis=-1) / 127.0
    safe = jnp.maximum(s, 1e-8)[..., None]
    q = jnp.clip(jnp.round(x32 / safe), -127, 127).astype(jnp.int8)
    return q, s


def _kv_dequant(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`_kv_quant_rows`; unwritten slots carry scale 0 and
    dequantize to exact zeros (masked by kv_valid/causality anyway)."""
    return q.astype(dtype) * s[..., None].astype(dtype)


def decode_step(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] — prompt chunk or single sampled token
    cache: Params,
    kv_valid: Optional[jax.Array] = None,  # [B, max_len] — False masks pad slots
    pos_offset: Optional[jax.Array] = None,  # [B] — logical-position shift (left-pad)
    last_only: bool = False,
    seq_total: Optional[jax.Array] = None,  # [B] — full-sequence length for longrope
) -> Tuple[jax.Array, Params]:
    """Incremental forward with KV cache; returns (logits [B, S, V], cache).

    ``kv_valid``/``pos_offset`` enable exact left-padded batching: sequence
    b's real tokens sit in cache slots [offset_b, …], RoPE positions are
    slot − offset_b (so they match the unpadded sequence), and attention
    never reads a pad slot. Both default to the unpadded single-stream
    behavior.

    ``seq_total`` (per-row full prompt length) overrides the Phi-3
    longrope short/long regime select — REQUIRED for chunked prefill so
    early chunks rotate with the same regime single-shot prefill would
    use (see :func:`_rope_freqs`); decode steps leave it None (the running
    length, HF's dynamic-switch semantics).

    ``last_only=True`` computes final-norm + lm_head for the last position
    only (logits [B, 1, V]) — sampling never reads the others, and at
    serving shapes the full-prefill vocab projection
    (2·B·S·d_model·vocab FLOPs) costs more than the entire rest of the
    prefill.
    """
    from kakveda_tpu.models.attention import gqa_cache_attention

    b, s = tokens.shape
    pos0 = cache["pos"]
    positions = jnp.broadcast_to(jnp.arange(s) + pos0, (b, s))
    if pos_offset is not None:
        positions = positions - pos_offset[:, None]
    cos, sin = _rope_freqs(cfg, positions, seq_total)

    x = embed_tokens(params, cfg, tokens)
    # pad slots (left-padded batching, bucketed admits) stay out of a conv
    # state and out of the experts' dispatch
    tok_valid = None if kv_valid is None else jax.lax.dynamic_slice_in_dim(kv_valid, pos0, s, axis=1)

    def attend(q, kv, entry, window, softcap):
        # One scalar write position for the whole batch: each buffer is
        # dynamic-update-sliced on its own, which XLA turns into in-place
        # row writes (init_cache).
        new = {
            key: jax.lax.dynamic_update_slice(entry[key], rows, (0, 0, pos0) + (0,) * (rows.ndim - 3))
            for key, rows in kv.items()
        }
        # Fused cached attention: Pallas flash on TPU, grouped XLA einsum
        # elsewhere — either way K/V are read once, not n_rep times, and
        # the causal mask (q_pos >= slot) also excludes unwritten slots.
        # int8 caches pass raw tiles + scales: the flash kernel streams
        # int8 from HBM and dequantizes in VMEM (the bandwidth win).
        attn = gqa_cache_attention(
            q, new["k"], new["v"], pos0, kv_valid,
            window=window, softcap=softcap, k_scale=new.get("ks"), v_scale=new.get("vs"),
        )
        return attn, new

    x, lists, _ = run_layers(
        params, cfg, x, cos, sin, attend, cache, conv_valid=tok_valid, token_mask=tok_valid
    )
    if last_only:
        x = x[:, -1:, :]
    return lm_logits(params, cfg, x), {"pos": pos0 + s, **lists}
