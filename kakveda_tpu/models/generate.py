"""Sampling loop + the `tpu` model runtime over the in-tree Llama.

The decode loop drives ``decode_step`` (KV-cache incremental forward) with
fixed [B, 1] token shapes, so after the first call everything is a warm
compiled program. Greedy or temperature sampling.

``LlamaRuntime`` is the drop-in ``runtime=tpu`` backend
(kakveda_tpu.models.runtime.get_runtime): same GenerateResult meta shape as
the stub/ollama tiers. Without a checkpoint it runs a deterministic
randomly-initialized model — useful for latency/meta plumbing and tests.
Real weights load two ways: ``KAKVEDA_HF_CKPT=/path/to/hf_dir`` converts a
local HF Llama checkpoint + tokenizer in place (models/hf_convert.py, logit
parity tested), or ``KAKVEDA_LLAMA_CKPT`` restores an orbax checkpoint of
the param pytree (the in-tree training path).
"""

from __future__ import annotations

import logging
import os
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from kakveda_tpu.models.llama import (
    LlamaConfig,
    Params,
    decode_step,
    fuse_qkv,
    init_cache,
    init_params,
    mask_pad_vocab,
    unfuse_qkv,
)
from kakveda_tpu.models.runtime import GenerateResult
from kakveda_tpu.models.tokenizer import ByteTokenizer
from kakveda_tpu.core import sanitize


@partial(jax.jit, static_argnames=("cfg", "last_only"))
def _decode_jit(params, cfg: LlamaConfig, tokens, cache, last_only=False):
    return decode_step(params, cfg, tokens, cache, last_only=last_only)


def _last_logits(logits: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """[B, S, V] -> [B, V] of the final position, with padded-vocab columns
    masked out so sampling can never emit a token the tokenizer lacks
    (converted checkpoints pad vocab to a TP-friendly multiple)."""
    return mask_pad_vocab(logits[:, -1, :], cfg)


@jax.jit
def _sample_top_p(rng, logits, temperature, top_p):
    """Nucleus sampling: keep the smallest prefix of the probability-sorted
    vocab whose mass reaches ``top_p``, renormalize, sample. Runs entirely
    on device with fixed shapes so the decode loop stays retrace-free."""
    scaled = logits / temperature
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A token survives if the mass *before* it is < top_p (the first token
    # always survives even when its own probability exceeds top_p).
    keep_sorted = (cum - probs) < top_p
    cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    masked = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
    return jax.random.categorical(rng, masked, axis=-1)



def _prefill_width(plen: int, chunk: int) -> int:
    """Prompt-window width under chunked prefill: unchanged when the prompt
    fits one chunk (prefill takes the single-shot branch — rounding would
    only widen it), else the next chunk multiple. The ONE place the
    rounding lives: DecodeSession's pack width and the runtime's cache
    sizing must agree on it."""
    if chunk <= 0 or plen <= chunk:
        return plen
    return -(-plen // chunk) * chunk


def _bucket_len(need: int, cap: int) -> int:
    """Power-of-two cache window ≥ need (capped): the window is part of the
    compiled program signature, so exact-fit lengths would recompile for
    every distinct prompt length. Thin wrapper over the ONE blessed bucket
    seam (``ops/knn.pow2_bucket``) with the decode floor/cap semantics."""
    from kakveda_tpu.ops.knn import pow2_bucket

    return pow2_bucket(need, floor=64, cap=cap)


def _pack_prompts(prompts: list[list[int]], ml: int, plen: Optional[int] = None):
    """Left-pad a ragged prompt batch into the shared convention used by
    every batched decode path: (tokens [B, plen] i32, kv_valid [B, ml]
    bool, pos_offset [B] i32, plen). Sequence i's real tokens occupy
    columns [off_i, plen); its cache rows [off_i, …) are valid and its
    RoPE positions are slot − off_i. An explicit ``plen`` (≥ the longest
    prompt) widens the left padding — chunked prefill uses it to round
    the prompt window to a chunk multiple."""
    import numpy as onp

    plen = max(plen or 0, max(len(p) for p in prompts))
    toks = onp.zeros((len(prompts), plen), onp.int32)
    valid = onp.zeros((len(prompts), ml), bool)
    offsets = onp.zeros((len(prompts),), onp.int32)
    for i, p in enumerate(prompts):
        off = plen - len(p)
        toks[i, off:] = p
        offsets[i] = off
        valid[i, off:] = True  # real prompt slots + all future decode slots
    return toks, valid, offsets, plen


def generate_tokens(
    params: Params,
    cfg: LlamaConfig,
    prompt_ids: list[int],
    *,
    max_new_tokens: int = 64,
    temperature: float = 0.0,
    top_p: float = 1.0,
    rng: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
    max_len: Optional[int] = None,
) -> list[int]:
    """Autoregressive decode; returns only the newly generated ids."""
    if max_len is None:
        ml = _bucket_len(len(prompt_ids) + max_new_tokens + 1, cfg.max_seq_len)
    else:
        ml = max_len
    cache = init_cache(cfg, batch=1, max_len=ml)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    prompt = jnp.asarray([prompt_ids], jnp.int32)
    logits, cache = _decode_jit(params, cfg, prompt, cache, last_only=True)
    last = _last_logits(logits, cfg)

    out: list[int] = []
    for _ in range(max_new_tokens):
        if temperature > 0.0:
            rng, sub = jax.random.split(rng)
            if top_p < 1.0:
                nxt = _sample_top_p(sub, last, temperature, top_p)
            else:
                nxt = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        tok = int(nxt[0])
        if eos_id is not None and tok == eos_id:
            break
        out.append(tok)
        if len(prompt_ids) + len(out) >= ml:
            break
        logits, cache = _decode_jit(params, cfg, nxt[:, None].astype(jnp.int32), cache)
        last = _last_logits(logits, cfg)
    return out


@partial(jax.jit, static_argnames=("cfg", "last_only"))
def _decode_batch_jit(params, cfg: LlamaConfig, tokens, cache, kv_valid, pos_offset, last_only=False):
    return decode_step(
        params, cfg, tokens, cache, kv_valid=kv_valid, pos_offset=pos_offset, last_only=last_only
    )


def generate_tokens_batch(
    params: Params,
    cfg: LlamaConfig,
    prompts: list[list[int]],
    *,
    max_new_tokens: int = 64,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
) -> list[list[int]]:
    """Batched autoregressive decode over variable-length prompts.

    Left-pads to the longest prompt; per-sequence position offsets and a
    KV-validity mask make each sequence's logits identical to what
    :func:`generate_tokens` would produce for it alone — batching is a
    throughput optimization, not an approximation. The parity caveat: all
    sequences share one cache window sized for the LONGEST prompt, so when
    ``max(len(prompt)) + max_new_tokens + 1`` exceeds ``cfg.max_seq_len``,
    shorter sequences truncate where their solo call (with its smaller
    window) would have kept generating. Used by the LLM classifier tier to
    judge a whole ingest batch in one decode stream.
    """
    import numpy as onp

    bsz = len(prompts)
    if bsz == 0:
        return []
    plen = max(len(p) for p in prompts)
    if plen + 1 > cfg.max_seq_len:
        raise ValueError(
            f"longest prompt ({plen} tokens) leaves no room in the cache window "
            f"(max_seq_len={cfg.max_seq_len}); truncate prompts before calling"
        )
    ml = _bucket_len(plen + max_new_tokens + 1, cfg.max_seq_len)
    toks, valid, offsets, _ = _pack_prompts(prompts, ml)
    cache = init_cache(cfg, batch=bsz, max_len=ml)
    kv_valid = jnp.asarray(valid)
    pos_offset = jnp.asarray(offsets)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    logits, cache = _decode_batch_jit(
        params, cfg, jnp.asarray(toks), cache, kv_valid, pos_offset, last_only=True
    )
    last = _last_logits(logits, cfg)

    outs: list[list[int]] = [[] for _ in range(bsz)]
    done = [False] * bsz
    for _ in range(max_new_tokens):
        if temperature > 0.0:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        # One device→host transfer for the whole step — int(t) per sequence
        # would sync B times per decoded token.
        step_toks = onp.asarray(nxt).tolist()
        for i, tok in enumerate(step_toks):
            if done[i]:
                continue
            if eos_id is not None and tok == eos_id:
                done[i] = True
                continue
            outs[i].append(tok)
        if all(done) or plen + max(len(o) for o in outs) >= ml:
            break
        logits, cache = _decode_batch_jit(
            params, cfg, nxt[:, None].astype(jnp.int32), cache, kv_valid, pos_offset
        )
        last = _last_logits(logits, cfg)
    return outs


@partial(jax.jit, static_argnames=("cfg", "n_steps", "greedy"))
def _decode_chunk_jit(
    params,
    cfg: LlamaConfig,
    last,  # [B, V] logits of the previous position (vocab-masked)
    cache,
    kv_valid,
    pos_offset,
    rng,
    temperature,
    n_steps: int,
    greedy: bool,
):
    """``n_steps`` sampled decode steps as one compiled scan, resumable:
    returns (tokens [B, n_steps], last, cache, rng) so the caller can chain
    chunks. Chunked dispatch is what lets pre-flight warn batches interleave
    with generation on the same chip — a whole-generation program is a
    multi-hundred-ms device-queue block (SURVEY §7 'interleaving generate
    steps with match batches')."""

    def body(carry, _):
        last, cache, rng = carry
        if greedy:
            nxt = jnp.argmax(last, axis=-1)
        else:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, last / temperature, axis=-1)
        logits, cache = decode_step(
            params, cfg, nxt[:, None].astype(jnp.int32), cache,
            kv_valid=kv_valid, pos_offset=pos_offset,
        )
        nl = mask_pad_vocab(logits[:, -1, :], cfg)
        return (nl, cache, rng), nxt

    (last, cache, rng), toks = jax.lax.scan(body, (last, cache, rng), None, length=n_steps)
    return toks.T, last, cache, rng  # toks: [B, n_steps]


@partial(jax.jit, static_argnames=("cfg",))
def _prefill_jit(params, cfg: LlamaConfig, prompt, cache, kv_valid, pos_offset, seq_total=None):
    logits, cache = decode_step(
        params, cfg, prompt, cache, kv_valid=kv_valid, pos_offset=pos_offset,
        last_only=True, seq_total=seq_total,
    )
    last = mask_pad_vocab(logits[:, -1, :], cfg)
    return last, cache


def prefill(
    params,
    cfg: LlamaConfig,
    prompt: jax.Array,  # [B, P] left-padded
    cache,
    kv_valid,
    pos_offset,
    chunk: int = 0,
):
    """Prefill the cache for a left-padded prompt batch; returns
    (last_logits [B, V] vocab-masked, cache).

    ``chunk`` > 0 processes the prompt in fixed-size pieces, each an
    incremental ``decode_step`` over the shared cache — bounding the
    per-dispatch activation footprint to O(chunk · d_ff) instead of
    O(P · d_ff). That is the long-context prefill path: a 128k-token
    prompt's single-shot [P, d_ff] transients run to gigabytes, while
    chunked prefill compiles ONE chunk-shaped program reused P/chunk
    times. The prompt width must be a chunk multiple — callers widen the
    left padding via ``_pack_prompts(..., plen=rounded)`` so the caller's
    kv_valid/pos_offset mirrors stay authoritative. Exactness: cached
    attention makes chunked and single-shot prefill mathematically
    identical; parity is tested.
    """
    if chunk <= 0 or prompt.shape[1] <= chunk:
        return _prefill_jit(params, cfg, prompt, cache, kv_valid, pos_offset)
    if prompt.shape[1] % chunk:
        raise ValueError(
            f"chunked prefill needs the prompt width ({prompt.shape[1]}) padded "
            f"to a multiple of chunk={chunk} (pack with plen=rounded)"
        )
    # Phi-3 longrope selects short/long factors from the sequence length:
    # each chunk must see the FULL per-row prompt length (width − left pad),
    # not its own max position, or early chunks of a long prompt rotate K/V
    # in the short regime while single-shot prefill uses long throughout.
    seq_total = None
    if cfg.rope_dim_factors_long:
        seq_total = jnp.asarray(prompt.shape[1], jnp.int32) - pos_offset
    last = None
    for s in range(0, prompt.shape[1], chunk):
        last, cache = _prefill_jit(
            params, cfg, prompt[:, s : s + chunk], cache, kv_valid, pos_offset, seq_total
        )
    return last, cache


def _generate_fused_jit(
    params,
    cfg: LlamaConfig,
    prompt: jax.Array,  # [B, P]
    cache,
    kv_valid,
    pos_offset,
    rng,
    temperature,
    max_new_tokens: int,
    greedy: bool,
):
    """Whole generation in two dispatches (prefill + one decode scan).
    Kept as the throughput path; the chunked path (DecodeSession) trades a
    few dispatches for device-queue preemption points."""
    last, cache = _prefill_jit(params, cfg, prompt, cache, kv_valid, pos_offset)
    toks, _, _, _ = _decode_chunk_jit(
        params, cfg, last, cache, kv_valid, pos_offset, rng, temperature,
        max_new_tokens, greedy,
    )
    return toks


def generate_tokens_fused(
    params: Params,
    cfg: LlamaConfig,
    prompts: list[list[int]],
    *,
    max_new_tokens: int = 64,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
) -> list[list[int]]:
    """Whole-generation-on-device decode: prefill + ``max_new_tokens`` decode
    steps run as ONE compiled program (`lax.scan` over decode_step), so a
    generation costs a single host→device dispatch and a single result fetch
    instead of one round-trip per token — it removes the per-step dispatch
    overhead.

    Trade-off vs :func:`generate_tokens_batch`: always runs the full
    ``max_new_tokens`` steps (no early exit when every sequence hit EOS) —
    the host truncates at the first EOS afterwards. Greedy output parity
    with the step-loop is exact; sampled output differs only in RNG
    consumption order.
    """
    import numpy as onp

    bsz = len(prompts)
    if bsz == 0:
        return []
    plen = max(len(p) for p in prompts)
    if plen + 1 > cfg.max_seq_len:
        raise ValueError(
            f"longest prompt ({plen} tokens) leaves no room in the cache window "
            f"(max_seq_len={cfg.max_seq_len}); truncate prompts before calling"
        )
    ml = _bucket_len(plen + max_new_tokens + 1, cfg.max_seq_len)
    steps = min(max_new_tokens, ml - plen - 1)
    toks, valid, offsets, _ = _pack_prompts(prompts, ml)
    cache = init_cache(cfg, batch=bsz, max_len=ml)
    out = _generate_fused_jit(
        params,
        cfg,
        jnp.asarray(toks),
        cache,
        jnp.asarray(valid),
        jnp.asarray(offsets),
        rng if rng is not None else jax.random.PRNGKey(0),
        jnp.asarray(max(temperature, 1e-6), jnp.float32),
        steps,
        temperature <= 0.0,
    )
    rows = onp.asarray(out)
    outs: list[list[int]] = []
    for row in rows:
        ids = row.tolist()
        if eos_id is not None and eos_id in ids:
            ids = ids[: ids.index(eos_id)]
        outs.append(ids)
    return outs


class DecodeSession:
    """Resumable chunked generation over one left-padded prompt batch.

    ``step_chunk()`` dispatches the next ``chunk_steps`` decode steps as one
    compiled program and fetches the sampled tokens. Bounding the per-
    dispatch slice is the serving-side scheduling mechanism for sharing the
    chip: the device queue gets a preemption point every chunk, so a
    pre-flight warn batch waits at most ~chunk_steps·(per-step time) instead
    of a whole generation (SURVEY §7 'interleaving generate steps with match
    batches'). Token parity with :func:`generate_tokens_fused` is exact for
    greedy decoding and RNG-exact for sampling (the rng threads through
    chunks in the same split order).
    """

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        prompts: list[list[int]],
        *,
        chunk_steps: int = 8,
        max_len: Optional[int] = None,
        temperature: float = 0.0,
        rng: Optional[jax.Array] = None,
        prefill_chunk: int = 0,
    ):
        import numpy as onp

        if not prompts:
            raise ValueError("empty prompt batch")
        self.params, self.cfg = params, cfg
        self.chunk_steps = chunk_steps
        self.greedy = temperature <= 0.0
        self.temperature = jnp.asarray(max(temperature, 1e-6), jnp.float32)
        natural_plen = max(len(p) for p in prompts)
        # Chunked prefill widens the prompt window to a chunk multiple
        # (extra left padding) so every piece hits one compiled shape; the
        # padding can consume up to chunk−1 decode slots when the window
        # is capped at max_seq_len — the price of retrace-free prefill.
        plen = _prefill_width(natural_plen, prefill_chunk)
        ml = max_len or cfg.max_seq_len
        if plen + 1 > ml:
            raise ValueError(
                f"longest prompt ({natural_plen}"
                + (f", padded to {plen} for prefill_chunk={prefill_chunk}" if plen != natural_plen else "")
                + f") leaves no room (max_len={ml})"
            )
        bsz = len(prompts)
        toks, valid, offsets, plen = _pack_prompts(prompts, ml, plen=plen)
        self.kv_valid = jnp.asarray(valid)
        self.pos_offset = jnp.asarray(offsets)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        cache = init_cache(cfg, batch=bsz, max_len=ml)
        self._last, self._cache = prefill(
            params, cfg, jnp.asarray(toks), cache, self.kv_valid, self.pos_offset,
            chunk=prefill_chunk,
        )
        self._pos = plen
        self._max_len = ml

    @property
    def steps_left(self) -> int:
        return max(0, self._max_len - 1 - self._pos)

    def step_chunk(self, n: Optional[int] = None):
        """Run the next min(n, steps_left) decode steps; returns the sampled
        token matrix [B, steps] as a numpy array (None when the cache
        window is exhausted)."""
        import numpy as onp

        steps = min(n or self.chunk_steps, self.steps_left)
        if steps <= 0:
            return None
        toks, self._last, self._cache, self.rng = _decode_chunk_jit(
            self.params, self.cfg, self._last, self._cache, self.kv_valid,
            self.pos_offset, self.rng, self.temperature, steps, self.greedy,
        )
        self._pos += steps
        return onp.asarray(toks)


class LlamaRuntime:
    """`runtime=tpu`: on-device Llama generation with the shared meta shape."""

    name = "tpu"

    def __init__(
        self,
        cfg: Optional[LlamaConfig] = None,
        params: Optional[Params] = None,
        seed: int = 0,
        tokenizer=None,
        model_label: Optional[str] = None,
        quant: Optional[str] = None,
    ):
        self.cfg = cfg or LlamaConfig.tiny()
        self.tokenizer = tokenizer if tokenizer is not None else ByteTokenizer()
        if self.cfg.vocab_size < self.tokenizer.vocab_size:
            raise ValueError("model vocab smaller than tokenizer vocab")
        kvq = os.environ.get("KAKVEDA_KV_QUANT", "")
        if kvq and kvq != "none":
            if kvq != "int8":
                raise ValueError(f"unknown KAKVEDA_KV_QUANT={kvq!r} (int8|none)")
            import dataclasses as _dc

            # Serving-layer cache quantization: every decode path this
            # runtime spawns (chunked, engine, speculative) inherits the
            # flag through self.cfg.
            self.cfg = _dc.replace(self.cfg, kv_quant="int8")
        if self.cfg.effective_vocab is None and self.tokenizer.vocab_size < self.cfg.vocab_size:
            # The table is padded past the tokenizer (tp-friendly multiple):
            # without effective_vocab the pad-vocab mask is a no-op and a
            # random-init/underspecified model can argmax an id the
            # tokenizer cannot decode — ByteTokenizer.decode then raises
            # mid-request (observed as stochastic playground 500s). Every
            # decode path masks via mask_pad_vocab(cfg), so clamping here
            # covers chunked, engine, speculative and batch serving alike.
            import dataclasses as _dc

            self.cfg = _dc.replace(self.cfg, effective_vocab=self.tokenizer.vocab_size)
        self.params = params if params is not None else init_params(jax.random.PRNGKey(seed), self.cfg)
        if quant == "int8":
            # Weight-only int8 serving: halves the HBM weight stream that
            # bounds decode throughput (models/quant.py).
            from kakveda_tpu.models.quant import quantize_params_int8

            self.params = quantize_params_int8(self.params)
        elif quant not in (None, "none"):
            raise ValueError(f"unknown quant mode {quant!r} (int8|none)")
        # Every program this runtime serves reads one q|k|v weight a layer.
        self.params = fuse_qkv(self.params)
        self.quant = quant
        self.model_label = model_label or f"llama-{self.cfg.n_layers}L-{self.cfg.d_model}d"
        import threading

        self._engine = None
        self._engine_lock = sanitize.named_lock("LlamaRuntime._engine_lock")
        self._retired = False

    @staticmethod
    def preset_config() -> LlamaConfig:
        """The model shape ``KAKVEDA_LLAMA_PRESET`` names (default tiny)."""
        preset = os.environ.get("KAKVEDA_LLAMA_PRESET", "tiny").lower()
        presets = {
            "tiny": LlamaConfig.tiny,
            "1b": LlamaConfig.tinyllama_1b,
            "tinyllama-1b": LlamaConfig.tinyllama_1b,
            "8b": LlamaConfig.llama3_8b,
            "llama3-8b": LlamaConfig.llama3_8b,
        }
        if preset not in presets:
            raise ValueError(
                f"unknown KAKVEDA_LLAMA_PRESET={preset!r} ({'|'.join(presets)})"
            )
        return presets[preset]()

    @classmethod
    def from_env(cls) -> "LlamaRuntime":
        quant = os.environ.get("KAKVEDA_QUANT") or None
        if quant not in (None, "none", "int8"):
            raise ValueError(f"unknown KAKVEDA_QUANT={quant!r} (int8|none)")
        # KAKVEDA_HF_DIR is the documented operator-facing alias (VERDICT
        # item 8: one env var from proven real-weight parity on any
        # machine with a local HF checkpoint); KAKVEDA_HF_CKPT predates it
        # and wins when both are set.
        hf_ckpt = os.environ.get("KAKVEDA_HF_CKPT") or os.environ.get("KAKVEDA_HF_DIR")
        if hf_ckpt:
            return cls.from_hf(hf_ckpt, quant=quant)
        cfg = cls.preset_config()
        params = None
        if cfg != LlamaConfig.tiny():
            # Full-width presets hold their seeded weights the way a
            # converted checkpoint would (bf16), not as f32 masters.
            params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
        rt = cls(cfg=cfg, params=params)
        ckpt = os.environ.get("KAKVEDA_LLAMA_CKPT")
        if ckpt:
            rt.load_checkpoint(ckpt)
        if quant == "int8":
            from kakveda_tpu.models.quant import quantize_params_int8

            rt.params = quantize_params_int8(rt.params)
            rt.quant = quant
        return rt

    @classmethod
    def from_hf(cls, path: str, *, mesh=None, quant: Optional[str] = None) -> "LlamaRuntime":
        """Real-weight serving: convert a local HF Llama checkpoint directory
        (weights + tokenizer files) and serve it on the TPU runtime. With a
        ``mesh``, params are placed per the Megatron TP layout; ``quant``
        ("int8") applies weight-only quantization before placement.
        Replaces the reference's Ollama daemon hop
        (reference: services/dashboard/app.py:1182-1258)."""
        from kakveda_tpu.models.hf_convert import load_hf_checkpoint, shard_params
        from kakveda_tpu.models.tokenizer import HFTokenizer

        params, cfg = load_hf_checkpoint(path)
        if quant not in (None, "none", "int8"):
            raise ValueError(f"unknown quant mode {quant!r} (int8|none)")
        rt_quant = None
        if quant == "int8":
            from kakveda_tpu.models.quant import quantize_params_int8

            params = quantize_params_int8(params)
            rt_quant = quant
        if mesh is not None:
            params = shard_params(params, cfg, mesh)  # handles int8 leaves
        tok = HFTokenizer(path)
        label = os.path.basename(os.path.normpath(path))
        rt = cls(cfg=cfg, params=params, tokenizer=tok, model_label=label)
        rt.quant = rt_quant
        return rt

    def load_checkpoint(self, path: str) -> None:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        # A checkpoint holds the tree training writes, three q/k/v leaves a
        # layer: restore into that structure, then fuse as __init__ does.
        self.params = fuse_qkv(ckptr.restore(path, unfuse_qkv(self.params, self.cfg)))
        with self._engine_lock:
            if self._engine is not None:
                # The engine captured the old param tree at construction;
                # drop it so the next online request rebuilds on the new
                # weights instead of serving the stale ones.
                self._engine.close()
                self._engine = None

    def list_models(self) -> list:
        return [self.model_label]

    def engine(self):
        """The shared online ServingEngine (continuous batching), or None
        when disabled. KAKVEDA_SERVE_CONTINUOUS=0 opts out (falls back to
        one decode stream per call); KAKVEDA_SERVE_SLOTS / _SERVE_WINDOW /
        _SERVE_CHUNK size the pool. Lazy: offline users (training, bench
        static paths) never pay for the loop thread."""
        if os.environ.get("KAKVEDA_SERVE_CONTINUOUS", "1") == "0" or self._retired:
            return None
        if self._engine is None:
            with self._engine_lock:
                if self._retired:
                    # Evicted by MultiModelRuntime's HBM budget: never
                    # rebuild the KV pool — an in-flight generate falls
                    # back to the solo decode (params stay alive only as
                    # long as its caller holds this runtime).
                    return None
                if self._engine is None:
                    from kakveda_tpu.models.serving import ServingEngine

                    window = int(
                        os.environ.get(
                            "KAKVEDA_SERVE_WINDOW", min(512, self.cfg.max_seq_len)
                        )
                    )
                    try:
                        self._engine = ServingEngine(
                            self.params, self.cfg,
                            batch_slots=int(os.environ.get("KAKVEDA_SERVE_SLOTS", "8")),
                            max_len=min(window, self.cfg.max_seq_len),
                            chunk_steps=int(os.environ.get("KAKVEDA_SERVE_CHUNK", "8")),
                            eos_id=self.tokenizer.EOS,
                            name=self.model_label,
                        )
                    except Exception as e:  # noqa: BLE001 — re-raised unless OOM
                        # Only a KV-pool ALLOCATION failure on a
                        # memory-tight chip (the co-residency case the HBM
                        # budget exists for) degrades to the solo path —
                        # loudly, and without retrying the allocation on
                        # every request. Anything else (a kernel that does
                        # not compile, a shape bug) is a fault to surface,
                        # not to serve around.
                        if "RESOURCE_EXHAUSTED" not in str(e):
                            raise
                        logging.getLogger("kakveda.serving").error(
                            "ServingEngine KV-pool allocation failed; online "
                            "continuous batching DISABLED for %s, requests "
                            "take the solo decode path: %s",
                            self.model_label, e,
                        )
                        self._retired = True
                        return None
                    logging.getLogger("kakveda.serving").info(
                        "serving engine %s: %d slots x %d positions; weights on "
                        "devices %s, KV pool on devices %s",
                        self.model_label, self._engine.cb.B, self._engine.cb.max_len,
                        sorted(d.id for d in jax.tree.leaves(self.params)[0].devices()),
                        sorted(d.id for d in self._engine.cb.cache["k"][0].devices()),
                    )
        return self._engine

    def register_prefix(self, prefix: str) -> bool:
        """Precompute a shared prompt prefix (system preamble, judge
        template) on the serving engine so every later request that starts
        with it prefills only its suffix. No-op (False) when the engine is
        disabled or the prefix is unsuitable (see
        ContinuousBatcher.register_prefix)."""
        eng = self.engine()
        if eng is None:
            return False
        ids = self.tokenizer.encode(prefix)
        try:
            return eng.register_prefix(ids)
        except (RuntimeError, TimeoutError):
            # A failed registration must not break serving: engine
            # closed/dead (RuntimeError family) or a saturated pool timing
            # the registration future out. Deliberately NOT a broad
            # except — OverloadError/DeviceUnavailableError must surface.
            return False

    def serving_stats(self) -> dict:
        """Ops snapshot for the admin serving panel — engine pool state
        (without constructing one: observability must not allocate a KV
        pool on a chip it is checking) plus the serving-lever flags."""
        eng = self._engine  # peek, never build
        stats = None
        if eng is not None:
            # stats() is the lock-guarded deep-copy snapshot (the loop
            # thread mutates spec_stats/k_trace concurrently with this
            # panel) — never read the live dicts here.
            stats = {
                **eng.stats(),
                "active": eng.cb.active,
                "slots": eng.cb.B,
                "window": eng.cb.max_len,
                "closed": eng._closed.is_set(),
            }
            if not eng.cb.spec_k:
                stats["spec"] = None
        return {
            "runtime": "tpu",
            "model": self.model_label,
            "quant": self.quant or "none",
            "kv_quant": self.cfg.kv_quant or "none",
            "retired": self._retired,
            "engine": stats,
        }

    def retire(self) -> None:
        """Tear down the serving engine and bar rebuilding — called by the
        HBM-budget evictor. In-flight generates finish on the solo path;
        device memory frees once the last caller drops this runtime."""
        with self._engine_lock:
            self._retired = True
            if self._engine is not None:
                self._engine.close()
                self._engine = None

    def _generate_ids_chunked(self, ids: list[list[int]], max_tokens: int) -> list[list[int]]:
        """Greedy decode via chunked dispatch (DecodeSession): ~chunk_steps
        tokens per device program instead of one (the per-token host loop
        pays a full dispatch per token), with
        EOS early-exit checked between chunks and the device queue left
        preemptible for concurrent pre-flight matches."""
        import numpy as onp

        plen = max(len(p) for p in ids)
        # Long-context serving: KAKVEDA_PREFILL_CHUNK=512 (etc.) prefills
        # in fixed pieces, bounding activation memory per dispatch.
        pchunk = int(os.environ.get("KAKVEDA_PREFILL_CHUNK", "0"))
        plen = _prefill_width(plen, pchunk)
        ml = _bucket_len(plen + max_tokens + 1, self.cfg.max_seq_len)
        sess = DecodeSession(
            self.params, self.cfg, ids, chunk_steps=16, max_len=ml, prefill_chunk=pchunk
        )
        eos = self.tokenizer.EOS
        outs: list[list[int]] = [[] for _ in ids]
        done = [False] * len(ids)
        budget = min(max_tokens, sess.steps_left)
        while budget > 0 and not all(done):
            chunk = sess.step_chunk(min(16, budget))
            if chunk is None:
                break
            budget -= chunk.shape[1]
            for i, row in enumerate(onp.asarray(chunk)):
                for t in row.tolist():
                    if done[i]:
                        break
                    if t == eos:
                        done[i] = True
                    elif len(outs[i]) < max_tokens:
                        outs[i].append(t)
        return outs

    def generate_batch(
        self, prompts: list, *, model: Optional[str] = None, max_tokens: int = 64
    ) -> list:
        """Batched generation: one decode stream for the whole list, exact
        per-sequence parity with generate()."""
        started = time.perf_counter()
        # Device-loss fail-fast: while the backend is latched DEGRADED,
        # every decode path (engine AND solo) would dispatch into a wedged
        # chip and hang — raise the typed retryable error in microseconds
        # instead (shed-never-hang, docs/robustness.md).
        from kakveda_tpu.core import admission as _admission

        _admission.get_device_health().check()
        ids = [self.tokenizer.encode(p)[-self.cfg.max_seq_len // 2 :] for p in prompts]
        from kakveda_tpu.core import profiling

        eng = self.engine()
        extra = {}
        new_ids = None
        if eng is not None and all(eng.fits(len(i), max_tokens) for i in ids):
            # Online path: the whole list joins the SHARED slot pool, so a
            # judge batch and a concurrent playground chat decode together.
            try:
                if len(ids) >= 2:
                    # Eval datasets and judge batches share a prompt head
                    # (instruction template). Register the batch's common
                    # token prefix once so all-but-the-first admissions
                    # reuse its K/V slab (register_prefix dedupes repeats
                    # and refuses unhelpful/unsafe prefixes itself).
                    common = os.path.commonprefix(ids)
                    if len(common) >= 16:
                        try:
                            eng.register_prefix(list(common))
                        except (RuntimeError, TimeoutError):
                            # Registration is an optimization only: engine
                            # closed mid-flight (RuntimeError) or a
                            # saturated pool timing out the registration
                            # future must not fail the batch itself. Typed
                            # admission errors are NOT RuntimeErrors and
                            # still surface (docs/static-analysis.md,
                            # typed-errors).
                            pass
                with profiling.annotate("llama.generate_batch_online"):
                    futs = [eng.submit(i, max_new_tokens=max_tokens) for i in ids]
                    new_ids = [f.result() for f in futs]
                extra = {"continuous": True}
            except RuntimeError:
                # Engine closed/died between fits() and the results: the
                # solo path below still serves the request.
                new_ids = None
        if new_ids is None:
            with profiling.annotate("llama.generate_batch"):
                new_ids = self._generate_ids_chunked(ids, max_tokens)
        latency_ms = int((time.perf_counter() - started) * 1000)
        label = model or self.model_label
        return [
            GenerateResult(
                text=self.tokenizer.decode(out),
                meta={
                    "provider": "tpu",
                    "model": label,
                    "latency_ms": latency_ms,
                    "tokens_generated": len(out),
                    "batched": len(prompts),
                    **extra,
                },
            )
            for out in new_ids
        ]

    def generate_stream(
        self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 64,
        cancel=None,
    ):
        """Streaming generation: yields text deltas as decode chunks land.

        Engine path: the request joins the shared continuous-batching pool
        and each chunk's accepted tokens surface through the engine's
        ``on_tokens`` callback (token-identical to the blocking path).
        Fallback (engine disabled / request doesn't fit): chunked solo
        decode yielding per device chunk. Deltas join to exactly the text
        ``generate`` would return; incomplete UTF-8 at a chunk boundary is
        withheld until the bytes complete (decode uses errors="replace",
        so an unstable replacement char must never be emitted early).

        Capability beyond the reference: its playground blocks on a full
        Ollama response per request (services/dashboard/app.py:3127-3299);
        here first tokens reach the client after one decode chunk.

        ``cancel`` (optional ``threading.Event``): set by the consumer on
        client disconnect — observed BETWEEN deltas too (a request still
        queued or mid-prefill cancels promptly, not only after its first
        token arrives). Closing the generator has the same effect.
        """
        from kakveda_tpu.core import admission as _admission

        _admission.get_device_health().check()  # degraded: fail fast, never hang
        ids = self.tokenizer.encode(prompt)[-self.cfg.max_seq_len // 2 :]

        def deltas(all_ids: list, done: bool, prev: str) -> tuple:
            text = self.tokenizer.decode(all_ids)
            if not done:
                text = text.rstrip("�")  # partial multi-byte tail
            if text.startswith(prev) and len(text) > len(prev):
                return text[len(prev):], text
            return "", prev

        eng = self.engine()
        if eng is not None and eng.fits(len(ids), max_tokens):
            import queue as _q

            ch: "_q.Queue" = _q.Queue()
            try:
                fut = eng.submit(
                    ids, max_tokens,
                    on_tokens=lambda new, done: ch.put((list(new), done)),
                )
            except RuntimeError:
                fut = None  # engine closed: solo fallback below
            if fut is not None:
                out: list = []
                prev = ""
                try:
                    while True:
                        try:
                            new, done = ch.get(timeout=0.5)
                        except _q.Empty:
                            if cancel is not None and cancel.is_set():
                                break  # finally cancels the engine request
                            if fut.done():  # engine died mid-request
                                fut.result()  # raises the loop's error
                                break
                            continue
                        out.extend(new)
                        d, prev = deltas(out, done, prev)
                        if d:
                            yield d
                        if done:
                            break
                finally:
                    # Abandoned mid-stream (consumer close() → GeneratorExit
                    # lands at the yield): free the engine slot instead of
                    # decoding a result nobody will read.
                    if not fut.done():
                        eng.cancel(fut)
                return

        # Solo fallback: same chunked decode as _generate_ids_chunked, one
        # yield per device chunk.
        import numpy as onp

        plen = len(ids)
        pchunk = int(os.environ.get("KAKVEDA_PREFILL_CHUNK", "0"))
        plen = _prefill_width(plen, pchunk)
        ml = _bucket_len(plen + max_tokens + 1, self.cfg.max_seq_len)
        sess = DecodeSession(
            self.params, self.cfg, [ids], chunk_steps=16, max_len=ml, prefill_chunk=pchunk
        )
        eos = self.tokenizer.EOS
        out = []
        prev = ""
        budget = min(max_tokens, sess.steps_left)
        done = False
        while budget > 0 and not done:
            if cancel is not None and cancel.is_set():
                break  # abandoned: stop dispatching chunks
            chunk = sess.step_chunk(min(16, budget))
            if chunk is None:
                break
            budget -= chunk.shape[1]
            for t in onp.asarray(chunk)[0].tolist():
                if t == eos or len(out) >= max_tokens:
                    done = True
                    break
                out.append(t)
            d, prev = deltas(out, done or budget <= 0, prev)
            if d:
                yield d

    def generate(self, prompt: str, *, model: Optional[str] = None, max_tokens: int = 64) -> GenerateResult:
        started = time.perf_counter()
        from kakveda_tpu.core import admission as _admission

        _admission.get_device_health().check()  # degraded: fail fast, never hang
        ids = self.tokenizer.encode(prompt)[-self.cfg.max_seq_len // 2 :]
        from kakveda_tpu.core import profiling

        meta_extra = {}
        if os.environ.get("KAKVEDA_SPEC", "") == "1":
            # Single-sequence latency mode: draft-free speculative decoding
            # (models/speculative.py) — token-identical to the chunked
            # greedy path, 1..k+1 tokens per weight stream. Trade-off: the
            # whole generation is ONE device program, so concurrent warn
            # batches lose their per-chunk preemption points; leave it off
            # when the chip is shared.
            from kakveda_tpu.models.speculative import generate_tokens_speculative

            with profiling.annotate("llama.generate_spec"):
                new_ids, stats = generate_tokens_speculative(
                    self.params, self.cfg, ids, max_new_tokens=max_tokens,
                    eos_id=self.tokenizer.EOS, return_stats=True,
                )
            meta_extra = {"speculative": True, "tokens_per_round": round(stats["tokens_per_round"], 2)}
        else:
            eng = self.engine()
            new_ids = None
            if eng is not None and eng.fits(len(ids), max_tokens):
                # Online path: join the shared continuous-batching pool —
                # concurrent requests (other chats, eval rows, judge calls)
                # decode in ONE batch. Greedy slot parity keeps the output
                # identical to the solo decode below.
                try:
                    with profiling.annotate("llama.generate_online"):
                        fut = eng.submit(ids, max_tokens)
                        new_ids = fut.result()
                    meta_extra = {"continuous": True}
                    # The engine attaches the request's lifecycle timeline
                    # (queue wait, prefill, TTFT, tokens/s, engine request
                    # id) to the Future — surfaced in meta so HTTP layers
                    # can hang it on the request's OTel span and correlate
                    # traces with /metrics and the flight recorder.
                    tl = getattr(fut, "timeline", None)
                    if tl is not None:
                        meta_extra["serve"] = tl
                except RuntimeError:
                    new_ids = None  # engine closed/died: solo path below
            if new_ids is None:
                with profiling.annotate("llama.generate"):
                    new_ids = self._generate_ids_chunked([ids], max_tokens)[0]
        text = self.tokenizer.decode(new_ids)
        return GenerateResult(
            text=text,
            meta={
                "provider": "tpu",
                "model": model or self.model_label,
                "latency_ms": int((time.perf_counter() - started) * 1000),
                "tokens_generated": len(new_ids),
                **meta_extra,
            },
        )
