"""LFM2-MoE as the benchmark holds it: seeded weights made on the device, the
router's selection bias calibrated on them, and the plain reference of the
forward pass. The heavy half of the family ``lfm2_moe`` (``families/lfm2_moe.py``
is the half the parent process loads: it imports no JAX). Imports nothing of
the program; the norm, RoPE, the int8 rounding and the seeded initialisers are
``families/mistral_model.py``'s, taken by name.

**The layers' equations** (the published ``lfm2_moe`` config keys and modelling
code; no biases anywhere, RMSNorm ``norm_eps``):

  block, every layer   h <- h + op(n_op(h));  h <- h + ffn(n_ffn(h))
  conv operator        [B, C, x~] = split3(x W_in);  u = B * x~
                       v_t = sum_{j<L} w[j] * u_{t-(L-1)+j}   (depthwise, causal, zeros before the start)
                       out = (C * v) W_out
  attention            q, k, v projections; per-head RMSNorm over head_dim on q and on k (gains
                       q_norm, k_norm) BEFORE RoPE (split-half, base rope_theta); causal softmax
                       at 1/sqrt(head_dim); grouped-query (heads / kv_heads queries per K/V head)
  ffn, i < num_dense   W2(silu(W1 x) * W3 x), width intermediate_size
  ffn, expert layer    s = sigmoid(x W_r);  sel = top-k(s + b);  g = s[sel] / (sum s[sel] + 1e-6),
                       times routed_scaling_factor;  y = sum_{e in sel} g_e W2_e(silu(W1_e x) * W3_e x)
  after the last layer one RMSNorm, then the head: the embedding table, tied (``assumed``).

Departures, each where it is made: the router's matmul is float32 in the
reference AND in its int8 control (the program's is float32 too: a control
that flipped routes by rounding the router would measure the router, and the
control is there to measure the matmuls' precision); logits over the first
``vocab_live`` ids only.

**Weights**, in the program's layout (``kakveda_tpu.models.llama``): bf16
matrices, float32 gains, seeded normal at std 1/sqrt(fan-in). ``expert_bias``
is not random: see :func:`expert_biases`.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from harness import manifest, textgen

HEAD_FOLD = 1 << 20
CALIB_PROMPTS, CALIB_ROUNDS = 48, 64


@functools.cache
def _b():
    return manifest.load_module("families", "mistral_model")


MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
              "moe_intermediate_size", "num_experts", "num_experts_per_tok", "conv_L_cache", "vocab_size",
              "num_hidden_layers", "num_dense_layers", "layer_types", "norm_eps", "rope_parameters",
              "norm_topk_prob", "routed_scaling_factor", "use_expert_bias")


def frozen(cfg: dict) -> str:
    """The model's keys of a configuration as one hashable value: what a
    compiled maker or a calibration is cached under."""
    return json.dumps({k: cfg[k] for k in MODEL_KEYS if k in cfg}, sort_keys=True)


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"], e=cfg["num_experts"],
                k=cfg["num_experts_per_tok"], taps=cfg["conv_L_cache"], v=cfg["vocab_size"],
                L=cfg["num_hidden_layers"], dense=cfg.get("num_dense_layers", 0))


# --- weights -------------------------------------------------------------------------


def layer_params(key, cfg: dict, i: int, bias=None) -> dict:
    b, m = _b(), dims(cfg)
    d = m["d"]
    k = jax.random.split(key, 12)
    lw = {"attn_norm": b.gain(k[0], d), "mlp_norm": b.gain(k[1], d)}
    if cfg["layer_types"][i] == "conv":
        lw.update(conv_in=b.dense(k[2], d, (d, 3 * d)), conv_w=b.dense(k[3], m["taps"], (m["taps"], d)),
                  conv_out=b.dense(k[4], d, (d, d)))
    else:
        h, kv, hd = m["h"], m["kv"], m["hd"]
        lw.update(wq=b.dense(k[2], d, (d, h * hd)), wk=b.dense(k[3], d, (d, kv * hd)),
                  wv=b.dense(k[4], d, (d, kv * hd)), wo=b.dense(k[5], h * hd, (h * hd, d)),
                  q_norm=b.gain(k[6], hd), k_norm=b.gain(k[7], hd))
    if i < m["dense"]:
        ff = m["ff"]
        lw.update(w_gate=b.dense(k[8], d, (d, ff)), w_up=b.dense(k[9], d, (d, ff)), w_down=b.dense(k[10], ff, (ff, d)))
    else:
        e, fe = m["e"], m["fe"]
        ke = jax.random.split(k[11], 4)
        lw.update(router=b.dense(ke[0], d, (d, e)), we_gate=b.dense(ke[1], d, (e, d, fe)),
                  we_up=b.dense(ke[2], d, (e, d, fe)), we_down=b.dense(ke[3], fe, (e, fe, d)),
                  expert_bias=jnp.zeros((e,), jnp.float32) if bias is None else bias)
    return lw


# Byte tokenizer: id = byte + 3; printable ASCII is 32..126 (families/mistral_model.py).
def head_params(key, cfg: dict) -> dict:
    """The embedding table, and the head tied to it (the family's convention;
    the program holds two arrays). The head's columns outside the printable
    ASCII ids are zero, so greedy output is text, never EOS, and every
    request yields exactly its ``max_tokens`` (configs: ``assumed``)."""
    b, m = _b(), dims(cfg)
    k = jax.random.split(key, 2)
    embed = b.dense(k[0], m["d"], (m["v"], m["d"]))
    ids = jnp.arange(m["v"])
    printable = ((ids >= b.PRINTABLE_IDS[0]) & (ids < b.PRINTABLE_IDS[1])).astype(jnp.bfloat16)
    return {"embed": embed, "final_norm": b.gain(k[1], m["d"]), "lm_head": embed.T * printable[None, :]}


@functools.lru_cache(maxsize=64)
def _layer_maker(model: str, i: int):
    cfg = json.loads(model)
    return jax.jit(lambda root, bias: layer_params(jax.random.fold_in(root, i), cfg, i, bias))


def layer_weights(seed: int, cfg: dict, i: int, calibrated: bool = True) -> dict:
    """Layer ``i`` alone; an expert layer with its calibrated selection bias."""
    bias = expert_biases(seed, cfg).get(i) if calibrated else None
    if bias is None:
        bias = jnp.zeros((cfg["num_experts"],), jnp.float32)
    return _layer_maker(frozen(cfg), i)(_b().root_key(seed), bias)


def head_weights(seed: int, cfg: dict) -> dict:
    model = json.loads(frozen(cfg))
    return jax.jit(lambda root: head_params(jax.random.fold_in(root, HEAD_FOLD), model))(_b().root_key(seed))


def make_params(seed: int, cfg: dict) -> dict:
    """The whole tree, one jitted call, the calibrated biases put in."""
    biases = expert_biases(seed, cfg)
    model = json.loads(frozen(cfg))
    zero = jnp.zeros((cfg["num_experts"],), jnp.float32)

    @jax.jit
    def build_tree(root, bias_list):
        head = head_params(jax.random.fold_in(root, HEAD_FOLD), model)
        layers = [layer_params(jax.random.fold_in(root, i), model, i, bias_list[i])
                  for i in range(model["num_hidden_layers"])]
        return {"embed": head["embed"], "layers": layers, "final_norm": head["final_norm"], "lm_head": head["lm_head"]}

    return build_tree(_b().root_key(seed), [biases.get(i, zero) for i in range(model["num_hidden_layers"])])


# --- the selection bias: what it is for in the trained model ------------------------------


def calibration_tokens(seed: int):
    """A seeded sample of what the experts will see: the traffic's own byte
    prompts (``harness/textgen.chat_prompt``, 64-255 bytes), each followed by
    64 seeded printable ids standing for the served continuation. Returns
    (tokens [N, S] right-padded, which of them are real)."""
    corpus = textgen.Corpus(seed)
    rows = []
    for j in range(CALIB_PROMPTS):
        rng = textgen.rng_for(seed, "sample", 7_000 + j)
        text = textgen.chat_prompt(corpus, seed, 8_000_000 + j, rng.randint(64, 255))
        rows.append([1] + [c + 3 for c in text.encode()] + [rng.randrange(*_b().PRINTABLE_IDS) for _ in range(64)])
    width = -(-max(len(r) for r in rows) // 64) * 64
    toks = jnp.asarray([r + [0] * (width - len(r)) for r in rows], jnp.int32)
    real = jnp.asarray([[True] * len(r) + [False] * (width - len(r)) for r in rows])
    return toks, real


@functools.lru_cache(maxsize=4)
def _calibrated(seed: int, model: str) -> dict:
    cfg = json.loads(model)
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    toks, real = calibration_tokens(seed)
    x = head_weights(seed, cfg)["embed"][toks].astype(jnp.float32)

    @jax.jit
    def scores_of(x, lw):  # the router's view of this layer's tokens, as the block computes it
        h = x + operator(x, lw, cfg, False)
        return jax.nn.sigmoid(_b().mm(_b().norm(h, lw["mlp_norm"], cfg["norm_eps"]), lw["router"], False))

    @jax.jit
    def balance(scores, real):
        even = jnp.sum(real) * k / e  # pairs an expert gets under even load

        def load(b):
            _, idx = jax.lax.top_k(scores + b, k)
            return jnp.sum(jax.nn.one_hot(idx, e) * real[..., None, None], axis=(0, 1, 2))

        def round_(r, b):
            # the published update's direction (raise an under-loaded expert's bias, lower an
            # over-loaded one's), its step proportional to the shortfall and shrinking by round
            return b + 0.05 * (0.96 ** r) * jnp.clip((even - load(b)) / even, -1.0, 1.0)

        b = jax.lax.fori_loop(0, CALIB_ROUNDS, round_, jnp.zeros((e,), jnp.float32))
        return b, jnp.max(load(jnp.zeros_like(b))) / even, jnp.max(load(b)) / even

    step = jax.jit(lambda x, lw: block(x, lw, cfg, False))
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(seed, cfg, i, calibrated=False)
        if "router" in lw:
            out[i], before, after = balance(scores_of(x, lw), real)
            LOADS[(seed, i)] = (float(before), float(after))
            lw = dict(lw, expert_bias=out[i])
        x = step(x, lw)
        del lw
    return out


LOADS: dict = {}  # (seed, layer) -> max-over-mean load on the sample, before and after calibration


def expert_biases(seed: int, cfg: dict) -> dict:
    """{expert layer: its selection bias [E]}: the values that even out the
    experts' load on :func:`calibration_tokens` run through the seeded model,
    layer by layer (a layer's tokens have passed the calibrated layers below
    it). That is what the bias is for in the trained model; a seeded random
    router alone sends one expert several times the mean (PERF.md). Program
    and reference get the same arrays: computed once a process, here."""
    if not cfg.get("use_expert_bias", False):
        return {}
    return _calibrated(int(seed), frozen(cfg))


# --- the plain reference -----------------------------------------------------------------


def conv_operator(n1, lw, int8):
    b = _b()
    gate_b, gate_c, xt = jnp.split(b.mm(n1, lw["conv_in"], int8), 3, axis=-1)
    u = gate_b * xt
    w = lw["conv_w"].astype(jnp.float32)  # [L, D]; w[L-1] multiplies the current position
    taps, s = w.shape[0], u.shape[1]
    ext = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))  # zeros before the sequence's start
    v = sum(ext[:, j:j + s] * w[j] for j in range(taps))
    return b.mm(gate_c * v, lw["conv_out"], int8)


def attention(n1, lw, cfg, int8):
    b = _b()
    bsz, s, d = n1.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    q = b.norm(b.mm(n1, lw["wq"], int8).reshape(bsz, s, h, hd), lw["q_norm"], eps)
    k = b.norm(b.mm(n1, lw["wk"], int8).reshape(bsz, s, kv, hd), lw["k_norm"], eps)
    q, k = b.rope(q, theta), b.rope(k, theta)
    v = b.mm(n1, lw["wv"], int8).reshape(bsz, s, kv, hd)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(keep[None, None], sc, -1e30), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=jax.lax.Precision.HIGHEST).reshape(bsz, s, h * hd)
    return b.mm(a, lw["wo"], int8)


def operator(x, lw, cfg, int8):
    n1 = _b().norm(x, lw["attn_norm"], cfg["norm_eps"])
    return conv_operator(n1, lw, int8) if "conv_in" in lw else attention(n1, lw, cfg, int8)


def experts(n2, lw, cfg, int8):
    b = _b()
    e, k = lw["router"].shape[1], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(b.mm(n2, lw["router"], False))  # float32 in the control too (module docstring)
    _, idx = jax.lax.top_k(s + lw["expert_bias"], k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    g = g * cfg.get("routed_scaling_factor", 1.0)
    share = jnp.sum(jax.nn.one_hot(idx, e) * g[..., None], axis=-2)  # [B, S, E]; 0 where not chosen

    def one(y, ew):  # every expert on every token, weighted by its share
        wg, wu, wd, sh = ew
        return y + sh[..., None] * b.mm(jax.nn.silu(b.mm(n2, wg, int8)) * b.mm(n2, wu, int8), wd, int8), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n2), (lw["we_gate"], lw["we_up"], lw["we_down"], jnp.moveaxis(share, -1, 0)))
    return y


def block(x, lw, cfg, int8):
    b = _b()
    x = x + operator(x, lw, cfg, int8)
    n2 = b.norm(x, lw["mlp_norm"], cfg["norm_eps"])
    if "router" in lw:
        return x + experts(n2, lw, cfg, int8)
    return x + b.mm(jax.nn.silu(b.mm(n2, lw["w_gate"], int8)) * b.mm(n2, lw["w_up"], int8), lw["w_down"], int8)


def logits(seed: int, cfg: dict, tokens, vocab_live: int, int8: bool = False, rows: int = 4):
    """[B, S, vocab_live] float32 logits of ``tokens`` [B, S] (right-padded:
    causal attention and a causal convolution keep the padding out of every
    earlier position), ``rows`` sequences at a time, layer by layer."""
    b = _b()
    tokens = jnp.asarray(tokens, jnp.int32)
    head = head_weights(seed, cfg)
    step = jax.jit(lambda x, lw: block(x, lw, cfg, int8))
    xs = [head["embed"][tokens[s:s + rows]].astype(jnp.float32) for s in range(0, tokens.shape[0], rows)]
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(seed, cfg, i)
        xs = [step(x, lw) for x in xs]
        del lw
    fin = jax.jit(lambda x, gain, w: b.mm(b.norm(x, gain, cfg["norm_eps"]), w, int8))
    w_live = head["lm_head"][:, :vocab_live]
    return jnp.concatenate([fin(x, head["final_norm"], w_live) for x in xs], axis=0)
