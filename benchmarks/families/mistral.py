"""The model family ``mistral``: the Llama/Mistral-shaped dense decoder (GQA
attention + SwiGLU, every layer alike).

A configuration that has a model names its family (``"family": "mistral"``),
and the harness finds ``families/<family>.py`` by that name. A family module
is the only benchmark file that knows an architecture's keys or the program's
constructors for it. It provides:

    check(config)                 refuse a configuration that lacks a key the family needs
    build(config, seed)           seeded weights in the served type and the program's layout,
                                  and the program's runtime object around them
    reference_logits(seed, config, tokens, vocab_live, control=False)
                                  the plain reference; ``control=True`` its lower-precision control
    work(config, what, **shape)   {"flops", "bytes"} the algorithm needs for one run of ``what``

This half imports no JAX (the parent process loads it for ``check`` and
``work``); weights and the reference are in ``families/mistral_model.py``.
A later family is new files beside these; it edits nothing here.
"""

from __future__ import annotations

import functools

from harness import manifest

NEEDS = ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
         "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta")


def check(config: dict) -> None:
    missing = [k for k in NEEDS if k not in config]
    if missing:
        raise ValueError(f"family mistral needs the published key(s) {missing}")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("family mistral: num_attention_heads is not a multiple of num_key_value_heads")


@functools.cache
def _model():
    return manifest.load_module("families", "mistral_model")


def build(config: dict, seed: int):
    """A ``LlamaRuntime`` round seeded weights: ``run_server`` then serves it as
    it would a preset (seam 1 of ISSUE 23)."""
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import LlamaRuntime
    from kakveda_tpu.models.hf_convert import hf_config_to_llama

    # the published config.json keys sit at the top level of the file
    lcfg = hf_config_to_llama(config, dtype=jnp.bfloat16)
    return LlamaRuntime(cfg=lcfg, params=_model().make_params(seed, config), model_label=config["name"])


def reference_logits(seed: int, config: dict, tokens, vocab_live: int, control: bool = False):
    """[B, S, vocab_live] float32 logits of ``tokens``; the control computes
    every matmul with both operands rounded to int8."""
    return _model().logits(seed, config, tokens, vocab_live, int8=control)


# --- the work a forward pass needs, from the published keys ---------------------------


def param_counts(cfg: dict) -> dict:
    """Parameters of a Llama/Mistral-shaped decoder from its published keys."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * ff
    layers = cfg["num_hidden_layers"]
    return {"per_layer": attn + mlp, "layers": layers * (attn + mlp), "embed": v * d, "lm_head": d * v,
            "total": layers * (attn + mlp) + 2 * v * d}


def forward_flops(cfg: dict, tokens: int, attended: int, head_rows: int) -> int:
    """Operations a forward pass needs: 2 per parameter of the layers per token,
    attention's QK^T and PV over ``attended`` (query, key) pairs, and the output
    head for ``head_rows`` rows (prefill reads one row's logits, decode all)."""
    pc = param_counts(cfg)
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    attn = 4 * attended * h * hd * cfg["num_hidden_layers"]
    return 2 * pc["layers"] * tokens + attn + 2 * pc["lm_head"] * head_rows


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    pc = param_counts(cfg)
    return (pc["layers"] + pc["lm_head"]) * bytes_per_param  # a step reads no embedding table, only rows


def work(config: dict, what: str, **shape) -> dict:
    """One run of ``what``:

    prefill        one admitted prompt: ``tokens`` through the layers, ``attended`` (query, key)
                   pairs, ``head_rows`` rows of logits; the weights read once
    decode         one chunk program: the same counts over its ``steps`` steps, the weights read
                   once a step
    flash_prefill  the attention kernel alone, causal among ``rows`` tokens of one prompt, per
                   layer: 4 x (rows^2 / 2) x heads x head_dim operations; bytes: q, k, v, out
    """
    if what in ("prefill", "decode"):
        return {"flops": forward_flops(config, shape["tokens"], shape["attended"], shape["head_rows"]),
                "bytes": (shape["steps"] if what == "decode" else 1) * weight_bytes(config)}
    if what == "flash_prefill":
        h, kv = config["num_attention_heads"], config["num_key_value_heads"]
        hd = config.get("head_dim") or config["hidden_size"] // h
        p = shape["rows"]
        return {"flops": 4 * (p * p / 2) * h * hd, "bytes": 2 * (2 * p * h * hd + 2 * p * kv * hd)}
    raise KeyError(f"family mistral: unknown work {what!r}")
