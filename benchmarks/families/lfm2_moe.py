"""The model family ``lfm2_moe``: LiquidAI's LFM2-MoE decoder — a layer-type
list of gated short-convolution operators and grouped-query attention, dense
SwiGLU layers before sigmoid-routed expert layers with a selection bias.

The half the parent process loads: ``check``, ``work`` and what defers to the
heavy half (``families/lfm2_moe_model.py``: seeded weights, the bias
calibration, the plain reference). Imports no JAX. See ``families/mistral.py``
for what a family module provides.
"""

from __future__ import annotations

import functools

from harness import manifest

NEEDS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
         "num_hidden_layers", "num_dense_layers", "num_experts", "num_experts_per_tok", "layer_types",
         "conv_L_cache", "vocab_size", "norm_eps", "rope_parameters")
KINDS = ("conv", "full_attention")


def check(config: dict) -> None:
    missing = [k for k in NEEDS if k not in config]
    if missing:
        raise ValueError(f"family lfm2_moe needs the published key(s) {missing}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("family lfm2_moe: layer_types does not name num_hidden_layers layers")
    unknown = sorted(set(config["layer_types"]) - set(KINDS))
    if unknown:
        raise ValueError(f"family lfm2_moe: unknown layer type(s) {unknown}")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("family lfm2_moe: num_attention_heads is not a multiple of num_key_value_heads")
    if config["num_experts_per_tok"] > config["num_experts"]:
        raise ValueError("family lfm2_moe: more experts per token than experts")


@functools.cache
def _model():
    return manifest.load_module("families", "lfm2_moe_model")


def build(config: dict, seed: int):
    """A ``LlamaRuntime`` round seeded weights (the selection biases calibrated
    on them): ``run_server`` then serves it as it would a preset."""
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import LlamaRuntime
    from kakveda_tpu.models.hf_convert import hf_config_to_llama

    # the published config.json keys sit at the top level of the file; a parent
    # tree that does not know the model_type refuses here, at once
    lcfg = hf_config_to_llama(config, dtype=jnp.bfloat16)
    return LlamaRuntime(cfg=lcfg, params=_model().make_params(seed, config), model_label=config["name"])


def reference_logits(seed: int, config: dict, tokens, vocab_live: int, control: bool = False):
    """[B, S, vocab_live] float32 logits of ``tokens``; the control computes
    every matmul but the router's with both operands rounded to int8."""
    return _model().logits(seed, config, tokens, vocab_live, int8=control)


# --- the work a forward pass needs, from the published keys ---------------------------


def param_counts(cfg: dict) -> dict:
    """Parameters by part: what every token passes (operators, dense layers,
    routers, the head's rows) and one expert's."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    n_conv = sum(1 for t in cfg["layer_types"] if t == "conv")
    n_attn = len(cfg["layer_types"]) - n_conv
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    conv = d * 3 * d + d * d + cfg["conv_L_cache"] * d
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    shared = n_conv * conv + n_attn * attn + n_dense * 3 * d * cfg["intermediate_size"] + n_moe * d * cfg["num_experts"]
    return {"shared": shared, "expert": 3 * d * cfg["moe_intermediate_size"], "head": d * cfg["vocab_size"],
            "n_moe": n_moe, "n_attn": n_attn, "n_conv": n_conv, "heads_x_dim": h * hd}


def forward_flops(cfg: dict, tokens: float, attended: float, head_rows: float) -> float:
    """2 x the parameters a token passes (both dense layers, the operators, the
    routers, ``num_experts_per_tok`` experts a token an expert layer), the
    head's rows for ``head_rows`` rows, attention's QK^T and PV over ``attended``
    (query, key) pairs in the attention layers, and the convolution's 2 L D a
    token a conv layer."""
    pc = param_counts(cfg)
    per_token = pc["shared"] + pc["n_moe"] * cfg["num_experts_per_tok"] * pc["expert"]
    conv = 2 * cfg["conv_L_cache"] * cfg["hidden_size"] * pc["n_conv"]
    return (2 * per_token + conv) * tokens + 4 * attended * pc["heads_x_dim"] * pc["n_attn"] + 2 * pc["head"] * head_rows


def weight_bytes(cfg: dict, touched: float, bytes_per_param: int = 2) -> float:
    """What one pass over the weights moves at least: everything outside the
    experts once (no embedding table, only rows), and in each expert layer
    the ``touched`` experts that got a token."""
    pc = param_counts(cfg)
    return (pc["shared"] + pc["head"] + pc["n_moe"] * touched * pc["expert"]) * bytes_per_param


def work(config: dict, what: str, **shape) -> dict:
    """One run of ``what``:

    prefill        one admitted prompt: ``tokens`` through the layers, ``attended`` pairs,
                   ``head_rows`` rows of logits; every expert's weights read once
    decode         one chunk program: the counts over its ``steps`` steps; bytes: a step's
                   non-expert weights once and ``touched`` experts' weights once per expert
                   layer, the least any implementation moves for that routing (default: every
                   expert, an upper count that no share is taken of: PERF.md section 7)
    flash_prefill  the attention kernel alone (``families/mistral.py``)
    """
    if what == "flash_prefill":
        return manifest.load_module("families", "mistral").work(config, what, **shape)
    if what in ("prefill", "decode"):
        return {"flops": forward_flops(config, shape["tokens"], shape["attended"], shape["head_rows"]),
                "bytes": shape.get("steps", 1) * weight_bytes(config, shape.get("touched", config["num_experts"]))}
    raise KeyError(f"family lfm2_moe: unknown work {what!r}")
