"""The plain reference of a Mistral/Llama-shaped decoder: the forward pass in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision, with
no kernels, no cache and no batching tricks. It imports nothing of the
program and takes its weights from ``weights`` (the benchmark's own), one
layer at a time.

Block, as published (Mistral-7B-v0.1 ``config.json`` / the Llama block):
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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights as W


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, int8: bool):
    w = w.astype(jnp.float32)
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    # x: [B, S, H, D]; split-half rotation.
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(x, lw, cfg, int8):
    b, s, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n1 = _norm(x, lw["attn_norm"], eps)
    q = _rope(_mm(n1, lw["wq"], int8).reshape(b, s, h, hd), theta)
    k = _rope(_mm(n1, lw["wk"], int8).reshape(b, s, kv, hd), theta)
    v = _mm(n1, lw["wv"], int8).reshape(b, s, kv, hd)
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
    x = x + _mm(a, lw["wo"], int8)
    n2 = _norm(x, lw["mlp_norm"], eps)
    return x + _mm(jax.nn.silu(_mm(n2, lw["w_gate"], int8)) * _mm(n2, lw["w_up"], int8), lw["w_down"], int8)


def logits(seed: int, cfg: dict, tokens, vocab_live: int, int8: bool = False, rows: int = 4):
    """[B, S, vocab_live] float32 logits of ``tokens`` [B, S] (right-padded;
    causal attention keeps the padding out of every earlier position).
    Layer by layer, ``rows`` sequences at a time, so that it fits beside
    nothing else on the chip."""
    tokens = jnp.asarray(tokens, jnp.int32)
    head = W.head_weights(seed, cfg)
    step = jax.jit(lambda x, lw: _layer(x, lw, cfg, int8))
    xs = [head["embed"][tokens[s:s + rows]].astype(jnp.float32) for s in range(0, tokens.shape[0], rows)]
    for i in range(cfg["num_hidden_layers"]):
        lw = W.layer_weights(seed, cfg, i)
        xs = [step(x, lw) for x in xs]
        del lw
    # the head goes in as an argument: closed over, its values would be constants
    # of the program and every seed would compile its own
    fin = jax.jit(lambda x, gain, w: _mm(_norm(x, gain, cfg["rms_norm_eps"]), w, int8))
    w_live = head["lm_head"][:, :vocab_live]
    return jnp.concatenate([fin(x, head["final_norm"], w_live) for x in xs], axis=0)


def served_gaps(ref_logits, prompts_len, served) -> list:
    """For each served token, how far its reference logit lies below the
    reference's best at that position. ``served[r]`` are the tokens request r
    was given after a prompt of ``prompts_len[r]`` tokens."""
    import numpy as np

    lg = np.asarray(ref_logits)
    gaps = []
    for r, (p, toks) in enumerate(zip(prompts_len, served)):
        for k, t in enumerate(toks):
            row = lg[r, p - 1 + k]
            gaps.append(float(row.max() - row[t]))
    return gaps


def argmax_gaps(ref_logits, other_logits, prompts_len, served) -> list:
    """The control's reading: at each position of the same prompts and tokens,
    the reference-logit gap of the token the OTHER pass puts first."""
    import numpy as np

    lg, ot = np.asarray(ref_logits), np.asarray(other_logits)
    gaps = []
    for r, (p, toks) in enumerate(zip(prompts_len, served)):
        for k in range(len(toks)):
            row = lg[r, p - 1 + k]
            gaps.append(float(row.max() - row[int(ot[r, p - 1 + k].argmax())]))
    return gaps
