"""The Mistral/Llama-shaped decoder as the benchmark holds it: seeded weights
made on the device, and the plain reference of its forward pass. The heavy
half of the family ``mistral`` (``families/mistral.py`` is the half the parent
process loads: it imports no JAX). Imports nothing of the program.

**Weights.** The benchmark makes them, not the program: ``make_params`` builds
the whole tree in one jitted call, in the type it is served in (bf16 matrices,
float32 norm gains), in the layout ``kakveda_tpu.models.llama`` reads. The
reference asks ``layer_weights``/``head_weights`` for the same values one
layer at a time, so it never holds a second copy of the model. Keys are the
published ``config.json`` keys (hidden_size, ...), not the program's.

**Reference.** The forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision, with no kernels, no cache and no batching
tricks. Block, as published (Mistral-7B-v0.1 ``config.json`` / the Llama block):
  h  = x + Wo . attn(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
  y  = h + Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))
  n(x) = x / sqrt(mean(x^2) + eps) * gain
RoPE in the split-half convention of the HF checkpoints, base ``rope_theta``;
grouped-query attention (each KV head serves heads/kv_heads query heads);
causal mask, and a sliding window of ``sliding_window`` positions (a query
sees keys at distance < window). Logits over the first ``vocab_live`` ids
only: the byte tokenizer that is ``assumed`` never produces the others, and
the program masks them.

``int8=True`` is the CONTROL: the same pass with every matrix multiplication's
two operands rounded to int8 (weights one scale per output channel,
activations one scale per row) — the nearest precision below the bf16 the
configuration states.

A family of another architecture that shares pieces (the head, the norm, the
attention half of a block) loads this module by name and calls them; it edits
nothing here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# --- weights -------------------------------------------------------------------------


def root_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def dims(cfg: dict) -> dict:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, kv=kv, hd=hd, ff=cfg["intermediate_size"], v=cfg["vocab_size"], L=cfg["num_hidden_layers"])


def dense(key, fan_in, shape):
    return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(jnp.bfloat16)


def gain(key, n):
    # Not all ones: a norm whose gain is dropped must show in the logits.
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def layer_params(key, m: dict) -> dict:
    k = jax.random.split(key, 9)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]
    return {
        "attn_norm": gain(k[7], d),
        "wq": dense(k[0], d, (d, h * hd)),
        "wk": dense(k[1], d, (d, kv * hd)),
        "wv": dense(k[2], d, (d, kv * hd)),
        "wo": dense(k[3], h * hd, (h * hd, d)),
        "mlp_norm": gain(k[8], d),
        "w_gate": dense(k[4], d, (d, ff)),
        "w_up": dense(k[5], d, (d, ff)),
        "w_down": dense(k[6], ff, (ff, d)),
    }


# Byte tokenizer: id = byte + 3 (0-2 are pad/bos/eos). Printable ASCII is 32..126.
PRINTABLE_IDS = (3 + 32, 3 + 127)


def head_params(key, m: dict) -> dict:
    """The output head's columns outside the printable ASCII ids are zero, so
    those ids score exactly 0 and the best of the 95 others (about N(0, 1)
    each) is above them: greedy output is text, never EOS and never a broken
    UTF-8 sequence the server would withhold. Every request then yields
    exactly its ``max_tokens``, for every seed (configs: ``assumed``)."""
    k = jax.random.split(key, 3)
    ids = jnp.arange(m["v"])
    printable = ((ids >= PRINTABLE_IDS[0]) & (ids < PRINTABLE_IDS[1])).astype(jnp.bfloat16)
    return {
        "embed": dense(k[0], m["d"], (m["v"], m["d"])),
        "final_norm": gain(k[1], m["d"]),
        "lm_head": dense(k[2], m["d"], (m["d"], m["v"])) * printable[None, :],
    }


def make_params(seed: int, cfg: dict) -> dict:
    """The whole tree, one jitted call."""
    m = dims(cfg)

    @jax.jit
    def build(root):
        head = head_params(jax.random.fold_in(root, 1 << 20), m)
        layers = [layer_params(jax.random.fold_in(root, i), m) for i in range(m["L"])]
        return {"embed": head["embed"], "layers": layers, "final_norm": head["final_norm"],
                "lm_head": head["lm_head"]}

    return build(root_key(seed))


@functools.lru_cache(maxsize=4)
def _layer_maker(dim_items: tuple):
    m = dict(dim_items)
    return jax.jit(lambda root, i: layer_params(jax.random.fold_in(root, i), m))


def layer_weights(seed: int, cfg: dict, i: int) -> dict:
    """Layer ``i`` alone; one compiled program serves every layer."""
    return _layer_maker(tuple(sorted(dims(cfg).items())))(root_key(seed), jnp.int32(i))


def head_weights(seed: int, cfg: dict) -> dict:
    m = dims(cfg)
    return jax.jit(lambda root: head_params(jax.random.fold_in(root, 1 << 20), m))(root_key(seed))


# --- the plain reference ---------------------------------------------------------------


def q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def mm(x, w, int8: bool):
    w = w.astype(jnp.float32)
    if int8:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    # x: [B, S, H, D]; split-half rotation.
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(x, lw, cfg, int8):
    """The attention half of a block: x + Wo . attn(...)."""
    b, s, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n1 = norm(x, lw["attn_norm"], eps)
    q = rope(mm(n1, lw["wq"], int8).reshape(b, s, h, hd), theta)
    k = rope(mm(n1, lw["wk"], int8).reshape(b, s, kv, hd), theta)
    v = mm(n1, lw["wv"], int8).reshape(b, s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(jnp.float32(hd))
    qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = qi >= ki
    win = cfg.get("sliding_window")
    if win:
        keep &= (qi - ki) < win
    p = jax.nn.softmax(jnp.where(keep[None, None], sc, -1e30), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=jax.lax.Precision.HIGHEST).reshape(b, s, h * hd)
    return x + mm(a, lw["wo"], int8)


def _layer(x, lw, cfg, int8):
    x = attention(x, lw, cfg, int8)
    n2 = norm(x, lw["mlp_norm"], cfg["rms_norm_eps"])
    return x + mm(jax.nn.silu(mm(n2, lw["w_gate"], int8)) * mm(n2, lw["w_up"], int8), lw["w_down"], int8)


def run_layers(seed: int, cfg: dict, tokens, vocab_live: int, int8: bool, rows: int, block, weights_of):
    """The loop every decoder family shares: embed, ``block(x, lw, cfg, int8)``
    with ``weights_of(seed, cfg, i)`` layer by layer, ``rows`` sequences at a
    time so that it fits beside nothing else on the chip, final norm, head."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head = head_weights(seed, cfg)
    step = jax.jit(lambda x, lw: block(x, lw, cfg, int8))
    xs = [head["embed"][tokens[s:s + rows]].astype(jnp.float32) for s in range(0, tokens.shape[0], rows)]
    for i in range(cfg["num_hidden_layers"]):
        lw = weights_of(seed, cfg, i)
        xs = [step(x, lw) for x in xs]
        del lw
    # the head goes in as an argument: closed over, its values would be constants
    # of the program and every seed would compile its own
    fin = jax.jit(lambda x, gain, w: mm(norm(x, gain, cfg["rms_norm_eps"]), w, int8))
    w_live = head["lm_head"][:, :vocab_live]
    return jnp.concatenate([fin(x, head["final_norm"], w_live) for x in xs], axis=0)


def logits(seed: int, cfg: dict, tokens, vocab_live: int, int8: bool = False, rows: int = 4):
    """[B, S, vocab_live] float32 logits of ``tokens`` [B, S] (right-padded;
    causal attention keeps the padding out of every earlier position)."""
    return run_layers(seed, cfg, tokens, vocab_live, int8, rows, _layer, layer_weights)
