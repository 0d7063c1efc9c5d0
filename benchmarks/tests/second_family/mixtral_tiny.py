"""A second model family, to prove the seam: a Mixtral-shaped decoder (the
attention half of the Mistral block, then a sparse mixture of SwiGLU experts:
float32 softmax over all experts' router logits, the top
``num_experts_per_tok`` kept and renormalised, as ``transformers``'
MixtralSparseMoeBlock does it). The program already runs that block
(``models/moe.py``).

It lives under ``benchmarks/tests/`` and no cell names it. The test copies it
to ``families/mixtral_tiny.py`` of a copy of the benchmark, beside a
configuration, a cell and its limits, and edits nothing the copy had: the path
a ``model_config`` PR walks with a new architecture. What it shares with
``mistral`` (head, norms, attention, the layer loop) it takes from that
family's module by name.
"""

from __future__ import annotations

import functools

from harness import manifest

NEEDS = ("num_local_experts", "num_experts_per_tok")


@functools.cache
def _dense():
    return manifest.load_module("families", "mistral")


@functools.cache
def _blocks():
    return manifest.load_module("families", "mistral_model")


def check(config: dict) -> None:
    _dense().check(config)
    missing = [k for k in NEEDS if k not in config]
    if missing:
        raise ValueError(f"family mixtral_tiny needs the published key(s) {missing}")
    if config["num_experts_per_tok"] > config["num_local_experts"]:
        raise ValueError("family mixtral_tiny: more experts per token than experts")


# --- weights: its own tree (router, we_gate / we_up / we_down) -------------------------


def _layer_params(key, cfg: dict) -> dict:
    import jax

    b = _blocks()
    m, e = b.dims(cfg), cfg["num_local_experts"]
    d, ff = m["d"], m["ff"]
    dense_layer = b.layer_params(key, m)
    k = jax.random.split(jax.random.fold_in(key, 1 << 16), 4)
    layer = {n: dense_layer[n] for n in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm")}
    layer.update(router=b.dense(k[0], d, (d, e)), we_gate=b.dense(k[1], d, (e, d, ff)),
                 we_up=b.dense(k[2], d, (e, d, ff)), we_down=b.dense(k[3], ff, (e, ff, d)))
    return layer


def _layer_weights(seed: int, cfg: dict, i: int) -> dict:
    import jax

    return jax.jit(lambda root: _layer_params(jax.random.fold_in(root, i), cfg))(_blocks().root_key(seed))


def make_params(seed: int, cfg: dict) -> dict:
    import jax

    b = _blocks()

    @jax.jit
    def build_tree(root):
        head = b.head_params(jax.random.fold_in(root, 1 << 20), b.dims(cfg))
        layers = [_layer_params(jax.random.fold_in(root, i), cfg) for i in range(cfg["num_hidden_layers"])]
        return {"embed": head["embed"], "layers": layers, "final_norm": head["final_norm"], "lm_head": head["lm_head"]}

    return build_tree(b.root_key(seed))


def build(config: dict, seed: int):
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import LlamaRuntime
    from kakveda_tpu.models.hf_convert import hf_config_to_llama

    return LlamaRuntime(cfg=hf_config_to_llama(config, dtype=jnp.bfloat16), params=make_params(seed, config),
                        model_label=config["name"])


# --- its own reference ---------------------------------------------------------------


def _block(x, lw, cfg, int8):
    import jax
    import jax.numpy as jnp

    b = _blocks()
    x = b.attention(x, lw, cfg, int8)
    n2 = b.norm(x, lw["mlp_norm"], cfg["rms_norm_eps"])
    # the router stays float32 in the control too: the program's does (models/moe.py)
    probs = jax.nn.softmax(b.mm(n2, lw["router"], False), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    share = jnp.sum(jax.nn.one_hot(idx, cfg["num_local_experts"]) * top[..., None], axis=-2)  # [B, S, E]
    y = jnp.zeros_like(x)
    for e in range(cfg["num_local_experts"]):  # every expert on every token, weighted by its share (0 if not chosen)
        h = jax.nn.silu(b.mm(n2, lw["we_gate"][e], int8)) * b.mm(n2, lw["we_up"][e], int8)
        y = y + share[..., e:e + 1] * b.mm(h, lw["we_down"][e], int8)
    return x + y


def reference_logits(seed: int, config: dict, tokens, vocab_live: int, control: bool = False):
    return _blocks().run_layers(seed, config, tokens, vocab_live, control, 4, _block, _layer_weights)


# --- its own work: experts per token x expert width, not every expert -----------------


def work(config: dict, what: str, **shape) -> dict:
    if what == "flash_prefill":
        return _dense().work(config, what, **shape)
    d, ff, layers = config["hidden_size"], config["intermediate_size"], config["num_hidden_layers"]
    e, k = config["num_local_experts"], config["num_experts_per_tok"]
    as_dense = _dense().work(config, what, **shape)  # counts one expert a layer: 3 d ff
    tokens = shape["tokens"]
    flops = as_dense["flops"] + 2 * layers * tokens * ((k - 1) * 3 * d * ff + d * e)
    touched = min(e, max(1, round(tokens * k)))  # experts whose weights a run reads
    nbytes = _dense().weight_bytes(config) + 2 * layers * ((touched - 1) * 3 * d * ff + d * e)
    return {"flops": flops, "bytes": nbytes * shape.get("steps", 1)}
