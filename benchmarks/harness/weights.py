"""Seeded weights of a Llama/Mistral-shaped decoder, made on the device.

The benchmark makes the weights, not the program: ``make_params`` builds the
whole tree in one jitted call, in the type it is served in (bf16 matrices,
float32 norm gains), in the layout ``kakveda_tpu.models.llama`` reads. The
plain reference asks ``layer_weights``/``head_weights`` for the same values
one layer at a time, so it never holds a second copy of the model.

Keys are the published ``config.json`` keys (hidden_size, ...), not the
program's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _root_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _dims(cfg: dict) -> dict:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, kv=kv, hd=hd, ff=cfg["intermediate_size"], v=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def _dense(key, fan_in, shape):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(jnp.bfloat16)


def _gain(key, n):
    # Not all ones: a norm whose gain is dropped must show in the logits.
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _layer(key, m: dict) -> dict:
    k = jax.random.split(key, 9)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]
    return {
        "attn_norm": _gain(k[7], d),
        "wq": _dense(k[0], d, (d, h * hd)),
        "wk": _dense(k[1], d, (d, kv * hd)),
        "wv": _dense(k[2], d, (d, kv * hd)),
        "wo": _dense(k[3], h * hd, (h * hd, d)),
        "mlp_norm": _gain(k[8], d),
        "w_gate": _dense(k[4], d, (d, ff)),
        "w_up": _dense(k[5], d, (d, ff)),
        "w_down": _dense(k[6], ff, (ff, d)),
    }


# Byte tokenizer: id = byte + 3 (0-2 are pad/bos/eos). Printable ASCII is 32..126.
PRINTABLE_IDS = (3 + 32, 3 + 127)


def _head(key, m: dict) -> dict:
    """The output head's columns outside the printable ASCII ids are zero, so
    those ids score exactly 0 and the best of the 95 others (about N(0, 1)
    each) is above them: greedy output is text, never EOS and never a broken
    UTF-8 sequence the server would withhold. Every request then yields
    exactly its ``max_tokens``, for every seed (configs: ``assumed``)."""
    k = jax.random.split(key, 3)
    ids = jnp.arange(m["v"])
    printable = ((ids >= PRINTABLE_IDS[0]) & (ids < PRINTABLE_IDS[1])).astype(jnp.bfloat16)
    return {
        "embed": _dense(k[0], m["d"], (m["v"], m["d"])),
        "final_norm": _gain(k[1], m["d"]),
        "lm_head": _dense(k[2], m["d"], (m["d"], m["v"])) * printable[None, :],
    }


def make_params(seed: int, cfg: dict) -> dict:
    """The whole tree, one jitted call."""
    m = _dims(cfg)

    @jax.jit
    def build(root):
        head = _head(jax.random.fold_in(root, 1 << 20), m)
        layers = [_layer(jax.random.fold_in(root, i), m) for i in range(m["L"])]
        return {"embed": head["embed"], "layers": layers, "final_norm": head["final_norm"],
                "lm_head": head["lm_head"]}

    return build(_root_key(seed))


@functools.lru_cache(maxsize=4)
def _layer_maker(dims: tuple):
    m = dict(dims)
    return jax.jit(lambda root, i: _layer(jax.random.fold_in(root, i), m))


def layer_weights(seed: int, cfg: dict, i: int) -> dict:
    """Layer ``i`` alone; one compiled program serves every layer."""
    return _layer_maker(tuple(sorted(_dims(cfg).items())))(_root_key(seed), jnp.int32(i))


def head_weights(seed: int, cfg: dict) -> dict:
    m = _dims(cfg)
    return jax.jit(lambda root: _head(jax.random.fold_in(root, 1 << 20), m))(_root_key(seed))
