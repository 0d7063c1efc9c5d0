"""Continuous batching — THE online serving path.

``ServingEngine`` (bottom of this module) is what LlamaRuntime routes
``generate``/``generate_batch`` through by default
(KAKVEDA_SERVE_CONTINUOUS=0 opts out): one daemon loop thread owns a
shared ContinuousBatcher, concurrent callers block on Futures, and every
online request — playground chat, eval row, LLM-judge call — joins one
decode batch. Offline throughput paths (bench, training eval) keep
calling ``generate_tokens_fused`` directly.

The playground, eval runner and LLM-judge tier all call generate. Static
batching (`generate_tokens_batch`/`_fused`) decodes a fixed cohort to the
longest member: every finished (EOS) sequence leaves its batch slot idle
until the whole cohort drains, and new requests wait for the next cohort.
Under mixed-length traffic that wastes both slots and latency.

**Design.** A `ContinuousBatcher` owns a fixed [B, KV, max_len, D] KV-cache
(static shapes — nothing ever retraces) and treats the batch axis as B
independent *slots*:

  * **admit**: a new prompt prefills into one free slot — a [1, P] prefill
    whose cache rows are scattered into the batch cache at that slot
    (`_admit_jit`). Other slots are untouched; admission interleaves with
    decoding chunks.
  * **step_chunk**: ONE bounded decode program advances every active slot
    by up to `chunk_steps` tokens (same chunked-dispatch scheduling that
    lets pre-flight warn batches share the chip — models/generate.py
    `DecodeSession`). Inactive slots decode garbage into their own slot
    positions that admission later overwrites — masked out by per-slot
    `kv_valid`, never visible to active slots. The program works on the
    first `attend_len` rows of every slab, a power of two that covers the
    pool's live rows (`_grow_valid`): a handful of programs, one per
    length, and a pool of short sequences stops reading its whole window.
  * **retire**: EOS/length-exhausted slots free on the host between
    chunks; their results return to callers and the slot re-enters the
    free list.

Throughput model: with static batching a cohort of B requests whose decode
lengths are L_i costs max(L_i) steps of B-wide compute; continuous
batching costs ~mean(L_i) per request at steady state — the delta grows
with length variance (bench: `KAKVEDA_BENCH_METRIC=continuous python
bench.py`, reported in docs/performance.md).

Capability replaced: the reference serves generations through sequential
per-request Ollama HTTP calls (services/dashboard/app.py:1182-1258) — no
batching at all; eval loops run one example at a time
(app.py:2315-2393).

Use the class directly (``ContinuousBatcher(params, cfg, ...)``); it
accepts the same param trees as every other forward path, including int8
weight-only quantized ones (llama.wmat). Decoding is greedy by default;
``admit(..., temperature=t)`` samples that slot only (a [B] temperature
vector threads through the chunk body; greedy slots stay exact).
"""

from __future__ import annotations

import copy
import logging
import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kakveda_tpu.core import admission as _admission
from kakveda_tpu.core import faults as _faults
from kakveda_tpu.core import metrics as _metrics
from kakveda_tpu.core.admission import DeviceUnavailableError, OverloadError
from kakveda_tpu.core import ledger as _ledger
from kakveda_tpu.core.profiling import annotate, observe_phase
from kakveda_tpu.core import sanitize
from kakveda_tpu.core import trace as _trace
from kakveda_tpu.models.llama import (
    LlamaConfig,
    Params,
    UnsupportedLayerError,
    decode_step,
    init_cache,
    mask_pad_vocab,
)
from kakveda_tpu.models.speculative import NgramIndex, copy_run

log = logging.getLogger("kakveda.serving")

_GATE_STATES = ("disabled", "warmup", "on", "off")


class EngineRetryableError(RuntimeError):
    """An in-flight request was lost to a serving-engine loop death. The
    request's slot state is gone but the supervisor is rebuilding the
    engine — resubmitting is safe (no tokens were delivered to the
    Future). RuntimeError subclass so existing solo-fallback callers
    (LlamaRuntime.generate*) handle it without changes."""


class EngineDeadError(RuntimeError):
    """The serving engine is permanently dead: the supervisor's restart
    budget (KAKVEDA_SERVE_RESTARTS) is exhausted, or the rebuild itself
    failed. submit()/register_prefix() raise this IMMEDIATELY — fail fast
    instead of enqueueing into a queue nobody drains."""


class DeadlineExceededError(RuntimeError):
    """A request's ``deadline_s`` expired before it completed. Carries the
    tokens decoded so far in ``.tokens`` (possibly empty — the request may
    have expired while still queued)."""

    def __init__(self, message: str, tokens: Optional[List[int]] = None):
        super().__init__(message)
        self.tokens: List[int] = list(tokens or [])


def _cache_lists(cache: Params) -> Dict[str, list]:
    """A cache's per-layer lists, everything but the scalar ``pos``: a cache
    per layer type — K/V slabs (+ int8 scales) of the attention layers,
    ``conv`` states of the conv layers. Every entry is batch-leading, so one
    rule scatters a [1, ...] scratch entry into a slot."""
    return {key: val for key, val in cache.items() if key != "pos"}


def _scatter_slot(cache: Params, scratch: Params, slot) -> Params:
    """Write a single-sequence ``scratch`` cache into batch slot ``slot`` of
    ``cache``, entry by entry: the prompt's K/V rows, and its conv states —
    whatever the slot held before is gone."""
    out = {"pos": cache["pos"]}
    for key, entries in _cache_lists(cache).items():
        out[key] = [
            jax.lax.dynamic_update_slice(ce, se, (slot,) + (0,) * (ce.ndim - 1))
            for ce, se in zip(entries, scratch[key])
        ]
    return out


def _slab_prefix(lists: Dict[str, list], attend_len: int) -> Dict[str, list]:
    """The pool a decode chunk works on: rows ``[:attend_len]`` of every K/V
    slab (and int8 scale), conv states whole. The host picks a length that
    covers every row a live slot reads or writes in the chunk
    (``ContinuousBatcher._grow_valid``), so the rows left out are rows the
    mask rejects anyway — and XLA, which cannot skip a masked row, reads a
    slab whole at every step it is handed whole."""
    return {
        key: entries if key == "conv" else [e[:, :, :attend_len] for e in entries]
        for key, entries in lists.items()
    }


def _slab_restore(lists: Dict[str, list], heads: Dict[str, list]) -> Dict[str, list]:
    """Write a chunk's prefixes (:func:`_slab_prefix`) back over the head of
    the donated slabs, in place; a full-length entry replaces its slab."""
    return {
        key: [jax.lax.dynamic_update_slice(e, h, (0,) * e.ndim) for e, h in zip(entries, heads[key])]
        for key, entries in lists.items()
    }


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def _admit_jit(params, cfg: LlamaConfig, cache, last, prompt, slot, kv_valid, pos_offset):
    """Prefill ``prompt`` [1, P] into batch slot ``slot`` of ``cache``.

    The single-sequence prefill runs with its own [1, ...] scratch cache
    (so its attention sees only this prompt and a conv layer starts from
    "before the sequence"), then its K/V rows and conv states scatter into
    the batch cache at ``slot``: the conv state is the real prompt's last
    rows, the bucket's pad positions masked out of it (``decode_step``).
    `last` [B, V] gets the slot's next-token logits.
    """
    scratch = init_cache(cfg, batch=1, max_len=kv_valid.shape[1])
    logits, scratch = decode_step(
        params, cfg, prompt, scratch,
        kv_valid=kv_valid[slot][None],
        pos_offset=pos_offset[slot][None],
        last_only=True,
    )
    out = _scatter_slot(cache, scratch, slot)
    nl = mask_pad_vocab(logits[:, -1, :], cfg)
    last = jax.lax.dynamic_update_slice(last, nl, (slot, 0))
    # cache["pos"] is managed per-slot on host (slot positions differ);
    # the batch cache carries pos=0 and step passes explicit positions.
    return out, last


def _forward_wide(params, cfg: LlamaConfig, cache, tokens, slot_pos, kv_valid, pos_offset):
    """THE serving-chunk forward, S-wide with PER-SLOT positions: token i of
    slot b writes cache row ``slot_pos[b]+i`` and attends rows
    ``col <= slot_pos[b]+i`` (within kv_valid, and the sliding-window band
    when the layer has one). Shared by the plain decode chunk (S=1 inside
    a scan) and the speculative verify chunk (S=k+1). The block itself is
    ``llama.transformer_block`` — the one body every path runs, where every
    model-family flag is read; what is this path's own is below: where a
    slot's rows land, which mask attention gets, which slots are live.
    Attention goes through ``gqa_cache_attention``: S=1 masks are
    expressible as [B, L] kv_valid (keeping the flash / int8-streaming
    dispatch), S>1 passes the full [B, S, L] mask (XLA path; S <= k+1 keeps
    its scratch tiny).

    ``cache`` is the pool's per-layer lists (:func:`_cache_lists`): an
    attention layer scatters into its K/V slab, a conv layer advances its
    slot's state. A slot with no valid row is idle: its token stays out of
    the experts' dispatch.

    Returns (logits [B, S, V] vocab-masked f32, the new lists, expert counts
    int32 [expert layers, E]: the (token, choice) pairs each expert got —
    [0, 1] for a stack without expert layers).
    """
    from kakveda_tpu.models.attention import gqa_cache_attention
    from kakveda_tpu.models.llama import _rope_freqs, embed_tokens, lm_logits, run_layers

    b, s = tokens.shape
    max_len = kv_valid.shape[1]

    positions = slot_pos[:, None] + jnp.arange(s)[None, :] - pos_offset[:, None]
    cos, sin = _rope_freqs(cfg, positions)
    x = embed_tokens(params, cfg, tokens)

    col = jnp.arange(max_len)[None, None, :]  # [1, 1, L]
    qpos = (slot_pos[:, None] + jnp.arange(s)[None, :])[:, :, None]  # [B, S, 1]
    masks = {0: kv_valid[:, None, :] & (col <= qpos)}  # [B, S, L], by window
    # only a stack with expert layers asks which slots are live
    live = jnp.broadcast_to(jnp.any(kv_valid, axis=1)[:, None], (b, s)) if cfg.n_experts else None

    rows = jnp.arange(b)[:, None]  # [B, 1]
    wcols = slot_pos[:, None] + jnp.arange(s)[None, :]  # [B, S] write indices

    def attend(q, kv, entry, window, softcap):
        # Per-slot scatter: row i of slot b lands at cache[b, :, slot_pos[b]+i]
        # — a real scatter (in-place row writes), not a whole-cache rewrite;
        # mode="drop" clamps overshoot past the window (discarded host-side).
        new = {
            key: entry[key].at[rows, :, wcols].set(jnp.swapaxes(r, 1, 2), mode="drop")
            for key, r in kv.items()
        }
        if window not in masks:
            masks[window] = masks[0] & (col > qpos - window)
        if s == 1:
            # [B, L] mask keeps the flash/int8-streaming dispatch;
            # pos0=max_len makes the kernel's scalar causal mask a no-op.
            valid, full = masks[window][:, 0, :], None
        else:
            valid, full = None, masks[window]
        attn = gqa_cache_attention(
            q, new["k"], new["v"], jnp.asarray(max_len), valid,
            softcap=softcap, k_scale=new.get("ks"), v_scale=new.get("vs"), full_mask=full,
        )
        return attn, new

    x, lists, stats = run_layers(params, cfg, x, cos, sin, attend, cache, token_mask=live, return_aux=True)
    logits = mask_pad_vocab(lm_logits(params, cfg, x), cfg)
    counts = [pairs for _, pairs in stats if pairs is not None]
    return logits, lists, jnp.stack(counts) if counts else jnp.zeros((0, 1), jnp.int32)


@partial(jax.jit, static_argnames=("cfg", "n_steps", "attend_len"), donate_argnums=(2,))
def _step_chunk_jit(
    params, cfg: LlamaConfig, cache, last, slot_pos, kv_valid, pos_offset, temps, rng, n_steps: int, attend_len: int
):
    """Advance every slot by ``n_steps`` tokens in one program, over the
    first ``attend_len`` rows of the pool's slabs (:func:`_slab_prefix`; an
    idle slot's row past them is dropped like any overshoot).

    ``slot_pos`` [B] — per-slot NEXT cache index (prompt length + tokens
    decoded so far). decode_step's scalar `pos` can't express per-slot
    positions, so the chunk scans :func:`_forward_wide` at S=1 with a
    per-slot write index: token t of slot b lands at cache[b, :, slot_pos[b]+t].
    ``temps`` [B] — per-slot sampling temperature; a slot with temp <= 0
    decodes greedily, others sample categorically (one rng split per step,
    shared across slots — rows are independent draws of the same key).
    The scan carries the prefix's per-layer lists, whatever the stack holds:
    K/V slabs, int8 scales, conv states; they are written back at the end.

    Returns (cache, last, slot_pos, rng, the chunk's fetch). The fetch is ONE
    int32 array [B + expert layers, max(n_steps, E + n_steps)]: rows [:B] hold
    the tokens [B, n_steps]; row B + l holds expert layer l's pair counts over
    the chunk [E], then its distinct experts touched at each step [n_steps] —
    so the expert counters ride the fetch the loop already makes
    (:func:`split_fetch`). Without expert layers it is the tokens alone.
    """

    pool = _cache_lists(cache)
    valid = kv_valid[:, :attend_len]

    def one_step(carry, _):
        lists, last, slot_pos, rng = carry
        rng, sub = jax.random.split(rng)
        sampled = jax.random.categorical(
            sub, last / jnp.maximum(temps, 1e-6)[:, None], axis=-1
        )
        nxt = jnp.where(temps > 0.0, sampled, jnp.argmax(last, axis=-1))  # [B]
        logits, lists, counts = _forward_wide(
            params, cfg, lists, nxt[:, None].astype(jnp.int32), slot_pos, valid, pos_offset,
        )
        return (lists, logits[:, -1, :], slot_pos + 1, rng), (nxt, counts)

    (heads, last, slot_pos, rng), (toks, counts) = jax.lax.scan(
        one_step, (_slab_prefix(pool, attend_len), last, slot_pos, rng), None, length=n_steps
    )
    fetch = toks.T.astype(jnp.int32)  # [B, n_steps]
    if counts.shape[1]:  # [n_steps, expert layers, E]
        stats = jnp.concatenate(
            [jnp.sum(counts, axis=0), jnp.sum(counts > 0, axis=2).T.astype(jnp.int32)], axis=1
        )  # [expert layers, E + n_steps]
        fetch = jnp.concatenate(
            [jnp.pad(fetch, ((0, 0), (0, stats.shape[1] - n_steps))), stats], axis=0
        )
    return {"pos": cache["pos"], **_slab_restore(pool, heads)}, last, slot_pos, rng, fetch


def split_fetch(fetch: np.ndarray, n_slots: int, n_steps: int, n_experts: int):
    """A chunk's fetch (``_step_chunk_jit``) on the host: (tokens [B, n_steps],
    pair counts [expert layers, E], experts touched [expert layers, n_steps]);
    the last two None for a stack without expert layers."""
    if fetch.shape[0] == n_slots:
        return fetch, None, None
    stats = fetch[n_slots:]
    return fetch[:n_slots, :n_steps], stats[:, :n_experts], stats[:, n_experts:n_experts + n_steps]


@partial(jax.jit, static_argnames=("cfg", "k", "attend_len"), donate_argnums=(2,))
def _spec_chunk_jit(params, cfg: LlamaConfig, cache, last, slot_pos, kv_valid, pos_offset, drafts, k: int, attend_len: int):
    """Speculative verify chunk: each slot advances 1..k+1 GREEDY tokens in
    ONE :func:`_forward_wide` pass over k+1 positions, over the first
    ``attend_len`` rows of the pool's slabs as the plain chunk is.

    ``drafts`` [B, k] are host-side prompt-lookup guesses for the tokens
    AFTER the committed next token t0 (= argmax(last), computed in-program
    so every chunk emits >= 1 token). The k+1-wide forward writes all rows
    and produces logits at every position; the accepted prefix is the run
    of drafts matching their own greedy verdicts. Rows written past the
    accepted point hold K/V of rejected tokens — never read (validity is
    bounded by each query's own position) and overwritten as real decoding
    reaches them, the same clamp-and-discard contract as pipelined
    overshoot. Decode is weight-bandwidth-bound, so the k+1-wide forward
    rides the SAME weight stream as a 1-wide step — accepted tokens are
    nearly free (models/speculative.py measures 1.3-1.7 tokens/round on
    judge-shaped traffic).

    Returns (cache, new_last [B,V], new_slot_pos [B], toks [B, k+1],
    counts [B]) — the host emits ``toks[b, :counts[b]]``.
    """
    t0 = jnp.argmax(last, axis=-1).astype(jnp.int32)  # [B]
    tokens = jnp.concatenate([t0[:, None], drafts.astype(jnp.int32)], axis=1)  # [B, k+1]
    pool = _cache_lists(cache)
    logits, heads, _ = _forward_wide(
        params, cfg, _slab_prefix(pool, attend_len), tokens, slot_pos, kv_valid[:, :attend_len], pos_offset,
    )
    new_cache = {"pos": cache["pos"], **_slab_restore(pool, heads)}

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]; [b, i] follows tokens[b, :i+1]
    match = (drafts.astype(jnp.int32) == greedy[:, :-1]).astype(jnp.int32)  # [B, k]
    m_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B] accepted drafts
    counts = m_acc + 1  # emitted = t0 + accepted drafts
    # Next chunk's `last` = logits after the final emitted token.
    new_last = jnp.take_along_axis(logits, m_acc[:, None, None], axis=1)[:, 0, :]
    return new_cache, new_last, slot_pos + counts, tokens, counts


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def _admit_prefix_jit(
    params, cfg: LlamaConfig, cache, last, pfx, suffix, slot, kv_valid, pos_offset, write_pos
):
    """Prefill only ``suffix`` [1, S'] into batch slot ``slot``, reusing the
    precomputed K/V rows of a shared prompt prefix (``pfx``: per-layer
    [1, KV, plen, D] slabs from :meth:`ContinuousBatcher.register_prefix`).

    Prefix K/V rows are position-INDEPENDENT of the slot layout: RoPE
    rotates by logical position (cache index − pos_offset), and a prefix
    token's logical position is its own index regardless of how much left
    pad the admission bucket adds — so one registered slab serves every
    bucket. The slab lands at [off, off+plen); the suffix chunk recomputes
    rows from ``write_pos`` (= off + split point), overwriting the slab's
    tail where the power-of-two suffix chunk overlaps it with identical
    values. Attention over not-yet-written rows is causally masked exactly
    as in chunked prefill.
    """
    off = pos_offset[slot]
    scratch = init_cache(cfg, batch=1, max_len=kv_valid.shape[1])
    scratch["pos"] = write_pos
    for key, slabs in pfx.items():
        starts = (0, 0, off, 0) if slabs[0].ndim == 4 else (0, 0, off)
        scratch[key] = [
            jax.lax.dynamic_update_slice(sk, pk, starts)
            for sk, pk in zip(scratch[key], slabs)
        ]
    logits, scratch = decode_step(
        params, cfg, suffix, scratch,
        kv_valid=kv_valid[slot][None],
        pos_offset=pos_offset[slot][None],
        last_only=True,
    )
    out = _scatter_slot(cache, scratch, slot)
    nl = mask_pad_vocab(logits[:, -1, :], cfg)
    last = jax.lax.dynamic_update_slice(last, nl, (slot, 0))
    return out, last


@partial(jax.jit, static_argnames=("cfg",))
def _prefix_prefill_jit(params, cfg: LlamaConfig, ids):
    """One compiled prefill for prefix registration ([1, plen] exact-length
    cache). Eager decode_step here would pay a per-op dispatch —
    thousands of them — for what is one program."""
    scratch = init_cache(cfg, batch=1, max_len=ids.shape[1])
    _, scratch = decode_step(params, cfg, ids, scratch, last_only=True)
    return scratch


@dataclass
class _Prefix:
    """One registered shared prompt prefix: token ids + per-layer K/V slabs
    ([1, KV, plen, D], int8 + scales when the cache is quantized), plus an
    n-gram index over the ids so speculative drafting can copy template
    continuations even before a slot's own history contains them."""

    ids: Tuple[int, ...]
    kv: Dict[str, List[jax.Array]]
    index: Optional[NgramIndex] = None


@dataclass
class _Slot:
    req_id: int
    prompt_len: int
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False
    # Streaming: called from process_chunk with (new_tokens, done) after
    # each chunk. MUST be fast/non-blocking (queue put) — it runs on the
    # engine loop thread between device dispatches.
    on_tokens: Optional[object] = None
    # Prompt ids retained for host-side speculative drafting (prompt +
    # out = the lookup corpus).
    prompt_ids: List[int] = field(default_factory=list)
    # Speculative state (spec pools only): incremental suffix index over
    # prompt+emitted history; per-slot adaptive draft length in
    # [1, spec_k]; acceptance EMA driving it; and the pipelined copy
    # cursor — (corpus, next idx, period, frozen len), the head of the
    # predicted-continuation chain. The chain survives only while every
    # processed chunk fully matches its own prediction (which travels in
    # the HANDLE, not here — by processing time a newer dispatch has
    # already moved this cursor); any mismatch clears it and the next
    # dispatch re-anchors.
    index: Optional[NgramIndex] = None
    k: int = 0
    accept_ema: float = 0.0
    spec_cursor: Optional[Tuple] = None


class ContinuousBatcher:
    """Admit-as-you-go generation over a fixed slot pool. Greedy by
    default; per-request ``temperature`` samples that slot only."""

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        *,
        batch_slots: int = 8,
        max_len: int = 512,
        chunk_steps: int = 8,
        eos_id: Optional[int] = None,
        rng: Optional[jax.Array] = None,
        spec_k: int = 0,
        name: str = "default",
        recorder: Optional[_metrics.FlightRecorder] = None,
    ):
        if cfg.has_conv and spec_k:
            # A verify chunk's rejected drafts would stay in a conv state:
            # it has no rows to leave unread, and no rollback yet.
            raise UnsupportedLayerError(
                "speculative decoding cannot run a config with conv layers "
                "(no rollback of conv state to the accepted position): serve it with spec_k=0"
            )
        self.params, self.cfg = params, cfg
        self.B, self.max_len = batch_slots, max_len
        self.chunk_steps = chunk_steps
        self.spec_k = spec_k
        self.name = name
        self.recorder = recorder
        # Observability + the acceptance auto-gate's decision state, one
        # dict so serving_stats/bench surface everything at once.
        # gate_state: disabled (spec_k=0) | warmup (measuring) | on | off.
        # The loop thread mutates this concurrently with readers — every
        # mutation holds ``stats_lock`` (RLock: the gate helper nests
        # inside locked sections) and readers go through
        # :meth:`stats_snapshot` / ``ServingEngine.stats()``.
        self.stats_lock = sanitize.named_lock("ContinuousBatcher.stats_lock", kind="rlock")
        self.spec_stats = {
            "chunks": 0, "emitted": 0, "slot_chunks": 0,
            "drafted": 0, "accepted": 0,
            "gate_state": "warmup" if spec_k else "disabled",
            "tokens_per_verify": 0.0,
            "break_even": 0.0,
            "k_trace": [],  # pool verify width per chunk, last 64
        }
        # Metrics-plane children, resolved ONCE here: a per-chunk update is
        # a lock + an add, nothing label-shaped on the hot path.
        reg = _metrics.get_registry()
        self._gate_gauge = reg.gauge(
            "kakveda_serving_spec_gate_state",
            "1 for the pool's current speculation gate state "
            "(disabled|warmup|on|off)", ("engine", "state"),
        )
        self._gate_transitions = reg.counter(
            "kakveda_serving_gate_transitions_total",
            "Speculation auto-gate state transitions", ("engine", "from", "to"),
        )
        for gs in _GATE_STATES:
            self._gate_gauge.labels(engine=name, state=gs).set(
                1.0 if gs == self.spec_stats["gate_state"] else 0.0
            )
        chunk_hist = reg.histogram(
            "kakveda_serving_chunk_seconds",
            "Effective decode-chunk wall (dispatch to process, overlapped "
            "under pipelining)", ("engine", "flavor"),
        )
        prefix_ctr = reg.counter(
            "kakveda_serving_prefix_requests_total",
            "Admissions by prefix-cache result", ("engine", "result"),
        )
        self._mx = {
            "chunk_plain": chunk_hist.labels(engine=name, flavor="plain"),
            "chunk_spec": chunk_hist.labels(engine=name, flavor="spec"),
            "tokens": reg.counter(
                "kakveda_serving_tokens_total",
                "Decode tokens emitted to callers", ("engine",),
            ).labels(engine=name),
            "drafted": reg.counter(
                "kakveda_serving_spec_drafted_total",
                "Speculative draft tokens sent to verify chunks", ("engine",),
            ).labels(engine=name),
            "accepted": reg.counter(
                "kakveda_serving_spec_accepted_total",
                "Speculative draft tokens accepted by verify chunks",
                ("engine",),
            ).labels(engine=name),
            "prefix_hit": prefix_ctr.labels(engine=name, result="hit"),
            "prefix_miss": prefix_ctr.labels(engine=name, result="miss"),
            "active": reg.gauge(
                "kakveda_serving_active_slots",
                "Occupied slots in the continuous-batching pool", ("engine",),
            ).labels(engine=name),
            "spec_k": reg.gauge(
                "kakveda_serving_spec_k",
                "Pool verify width of the most recent speculative chunk",
                ("engine",),
            ).labels(engine=name),
            "attend_rows": reg.histogram(
                "kakveda_serving_attend_rows",
                "Rows of each K/V slab a decode chunk's attention reads (the "
                "slot window = the pool holds a long sequence); one observation "
                "a dispatched chunk", ("engine",), buckets=_metrics.ATTEND_ROWS_BUCKETS,
            ).labels(engine=name),
        }
        reg.gauge(
            "kakveda_serving_slots",
            "Total slots in the continuous-batching pool", ("engine",),
        ).labels(engine=name).set(batch_slots)
        if cfg.n_experts:
            # Expert-layer load, from the counts each chunk's fetch carries
            # (:func:`split_fetch`); a stack without expert layers has no
            # such children.
            self._mx["moe_touched"] = reg.histogram(
                "kakveda_moe_experts_touched",
                "Distinct experts that got at least one token, per expert "
                "layer per decode step", ("engine",), buckets=_metrics.MOE_TOUCHED_BUCKETS,
            ).labels(engine=name)
            self._mx["moe_skew"] = reg.histogram(
                "kakveda_moe_load_max_over_mean",
                "Fullest expert's load over the mean load, worst expert layer, "
                "over the recent chunks' decoded tokens; one observation a chunk",
                ("engine",), buckets=_metrics.MOE_SKEW_BUCKETS,
            ).labels(engine=name)
        # kakveda: owned-by[serving-loop] — decayed (token, choice) pairs per expert
        self._moe_load: Optional[np.ndarray] = None
        self._last_k_rec = 0
        # Gate inputs: recent per-chunk wall times for each arm (median —
        # robust to one-off compile spikes), recent per-slot emitted
        # counts, and the knobs. Walls are recorded where the chunk's
        # effective cost is visible: handles carry their dispatch
        # timestamp and process_*_chunk computes dispatch→process, which
        # under pipelining is the overlapped (real) per-chunk cost.
        self._spec_walls: deque = deque(maxlen=16)
        self._plain_walls: deque = deque(maxlen=16)
        # kakveda: owned-by[serving-loop] — gate decision state, loop thread only
        self._tpv_recent: deque = deque(maxlen=32)
        self._gate_warmup = int(os.environ.get("KAKVEDA_SERVE_SPEC_WARMUP", "8"))
        self._gate_calib = int(os.environ.get("KAKVEDA_SERVE_SPEC_CALIB", "2"))
        self._gate_reprobe = int(os.environ.get("KAKVEDA_SERVE_SPEC_REPROBE", "256"))
        self._gate_prior = float(os.environ.get("KAKVEDA_SERVE_SPEC_BREAKEVEN", "1.35"))
        # kakveda: owned-by[serving-loop] — spec chunks since (re)entering warmup
        self._gate_spec_chunks = 0
        self._gate_plain_since_off = 0
        self._gate_reprobes = 0
        # Pipelined speculation: the device slot_pos returned by the last
        # verify chunk (threaded into the next dispatch WITHOUT a host
        # sync) and the un-processed in-flight chunk count/width (the
        # read-validity growth budget). Valid only while no admission or
        # plain chunk interleaves — both reset/guard it.
        self._spec_pos_dev = None
        self._spec_pending = 0
        self._spec_pending_width = 0
        # First dispatch of each program shape pays its compile; those
        # walls would poison the gate's medians (a 1000× break-even from
        # one trace), so the first sample per shape is dropped.
        self._spec_widths_warm: set = set()
        self._plain_warm = False
        # Chaos-harness sites, resolved once (core/faults.py): a bare
        # attribute check per chunk when unarmed. Dispatch fires before
        # the device program is launched, fetch before a handle's results
        # are consumed — both escape to the engine loop, whose supervisor
        # rebuilds this batcher wholesale (mid-flight state is discarded,
        # so a fault can never leave it half-mutated in service).
        self._fault_dispatch = _faults.site("engine.dispatch")
        self._fault_fetch = _faults.site("engine.fetch")
        self.eos_id = eos_id
        self.cache = init_cache(cfg, batch=batch_slots, max_len=max_len)
        cache_gauge = reg.gauge(
            "kakveda_serving_cache_bytes",
            "Bytes of the slot pool by kind: kv (the attention layers' K/V "
            "slabs and their scales), conv (the conv layers' states)",
            ("engine", "kind"),
        )
        for kind, nbytes in self.cache_bytes().items():
            cache_gauge.labels(engine=name, kind=kind).set(nbytes)
        reg.gauge(
            "kakveda_serving_fused_qkv_layers",
            "Attention layers the pool serves with one q|k|v projection "
            "weight (llama.fuse_qkv)", ("engine",),
        ).labels(engine=name).set(sum("wqkv" in layer for layer in params["layers"]))
        self.last = jnp.full((batch_slots, cfg.vocab_size), -1e30, jnp.float32)
        # Host-side mirrors of the per-slot bookkeeping: step() would
        # otherwise pay per-slot device syncs (int(dev_arr[slot])) and
        # per-slot scatter dispatches between chunks — on remote-attached
        # chips that host bookkeeping can exceed the chunk's compute. The
        # device copies are rebuilt from the mirrors once per call.
        self._kv_np = np.zeros((batch_slots, max_len), bool)
        self._off_np = np.zeros((batch_slots,), np.int32)
        self._pos_np = np.zeros((batch_slots,), np.int32)
        self._temp_np = np.zeros((batch_slots,), np.float32)  # ≤0 = greedy
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.slots: Dict[int, _Slot] = {}
        self.free = list(range(batch_slots))
        self.results: Dict[int, List[int]] = {}
        self._next_id = 0
        self._prefixes: Dict[Tuple[int, ...], _Prefix] = {}
        self.prefix_stats = {"registered": 0, "hits": 0, "hit_tokens_saved": 0}

    def cache_bytes(self) -> Dict[str, int]:
        """The slot pool's bytes by kind (kv | conv)."""
        out = {"kv": 0, "conv": 0}
        for key, entries in _cache_lists(self.cache).items():
            out["conv" if key == "conv" else "kv"] += sum(int(e.nbytes) for e in entries)
        return out

    @staticmethod
    def bucket_for(prompt_len: int, max_len: int) -> int:
        """Admission pad width: power-of-two ≥ prompt (min 8), capped at
        the slot window. THE definition shared by admit() and
        ServingEngine.fits() — the engine's fallback contract (never admit
        what would truncate) depends on the two staying identical. Thin
        wrapper over the ONE blessed bucket seam (``ops/knn.pow2_bucket``)
        with the admission floor/clamp semantics."""
        from kakveda_tpu.ops.knn import pow2_bucket

        return pow2_bucket(prompt_len, floor=8, cap=max_len - 1)

    def register_prefix(self, prefix_ids: List[int]) -> bool:
        """Precompute and retain the K/V rows of a shared prompt prefix so
        later admissions prefill only their suffix (``_admit_prefix_jit``).

        The natural users are the fixed instruction templates in front of
        every LLM-judge call and the playground/eval system preamble — the
        reference pays the full prompt on every Ollama hop
        (services/dashboard/app.py:1182-1258); here the shared head of the
        prompt costs its FLOPs once per process instead of once per request.

        Returns False (no-op) when the prefix is too short to matter, too
        long for the slot window, the model's RoPE regime depends on the
        final sequence length (Phi-3 longrope: a prefix computed at length
        plen would rotate in a different regime than the full prompt —
        reuse would be silently wrong, so it is refused), or the config has
        conv layers (reuse would need a snapshot of their state at the
        prefix's end, which the pool does not keep: refused with a warning).
        """
        ids = tuple(int(t) for t in prefix_ids)
        if self.cfg.has_conv:
            log.warning("register_prefix refused: the config has conv layers "
                        "(no snapshot of conv state at a prefix's end); every prompt prefills whole")
            return False
        if len(ids) < 8 or len(ids) + 9 >= self.max_len:
            return False
        if getattr(self.cfg, "rope_dim_factors_long", None):
            return False
        if ids in self._prefixes:
            return True
        scratch = _prefix_prefill_jit(
            self.params, self.cfg, jnp.asarray([list(ids)], jnp.int32)
        )
        keys = ("k", "v") + (("ks", "vs") if self.cfg.kv_quant == "int8" else ())
        # Bounded store: auto-registration (generate_batch common heads)
        # must not accumulate slabs without limit — each is
        # plen·KV·D·layers·2 resident HBM bytes. Dict order is recency
        # (moved-to-end on hit); evict the least recently used.
        maxp = int(os.environ.get("KAKVEDA_SERVE_PREFIX_MAX", "4"))
        while len(self._prefixes) >= max(1, maxp):
            self._prefixes.pop(next(iter(self._prefixes)))
        self._prefixes[ids] = _Prefix(
            ids=ids, kv={k: scratch[k] for k in keys},
            index=NgramIndex(ids) if self.spec_k else None,
        )
        with self.stats_lock:
            self.prefix_stats["registered"] += 1
        return True

    def stats_snapshot(self) -> dict:
        """Deep-copied spec/prefix stats under the stats lock — THE read
        API. The loop thread mutates the live dicts between chunks
        (``k_trace`` append vs list copy is the observable race), so
        readers never touch them directly."""
        with self.stats_lock:
            return {
                "spec": copy.deepcopy(self.spec_stats),
                "prefix": dict(self.prefix_stats),
            }

    def _set_gate_state(self, new: str) -> None:
        """ONE definition of a gate transition: spec_stats, the state
        gauge vector, the transition counter and the flight recorder move
        together. Takes ``stats_lock`` itself (RLock — callers already
        inside a locked section just re-enter), so the transition is
        atomic even from a caller that forgot the lock."""
        with self.stats_lock:
            old = self.spec_stats["gate_state"]
            if new == old:
                return
            self.spec_stats["gate_state"] = new
            self._gate_gauge.labels(engine=self.name, state=old).set(0.0)
            self._gate_gauge.labels(engine=self.name, state=new).set(1.0)
            self._gate_transitions.labels(
                **{"engine": self.name, "from": old, "to": new}
            ).inc()
            if self.recorder is not None:
                self.recorder.record(
                    "gate", **{
                        "from": old, "to": new,
                        "tokens_per_verify": self.spec_stats["tokens_per_verify"],
                        "break_even": self.spec_stats["break_even"],
                    }
                )

    def _match_prefix(self, prompt_ids: List[int]):
        """Longest registered prefix of ``prompt_ids`` plus the suffix-chunk
        split: returns (entry, split, suffix_width) or None. The suffix
        chunk is the power-of-two-wide tail the admission recomputes —
        ``split = len(prompt) − suffix_width`` tokens come from the slab,
        and the chunk re-derives the overlap [split, plen) with identical
        values (keeping compile count logarithmic instead of per-length)."""
        if not self._prefixes:
            return None
        best = None
        for pe in self._prefixes.values():
            pl_ = len(pe.ids)
            if best is not None and pl_ <= len(best.ids):
                continue
            if len(prompt_ids) >= pl_ and tuple(prompt_ids[:pl_]) == pe.ids:
                best = pe
        if best is None:
            return None
        # Recency for the LRU bound: a hit keeps its prefix resident.
        self._prefixes[best.ids] = self._prefixes.pop(best.ids)
        p = len(prompt_ids)
        sw = 8
        while sw < p - len(best.ids):
            sw <<= 1
        split = p - sw
        if split <= 0:
            return None  # suffix chunk covers the whole prompt: no reuse win
        return best, split, sw

    @property
    def has_capacity(self) -> bool:
        return bool(self.free)

    @property
    def active(self) -> int:
        return len(self.slots)

    def admit(
        self,
        prompt_ids: List[int],
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        on_tokens=None,
    ) -> int:
        """Prefill into a free slot; returns a request id.

        Prompts are LEFT-padded to a power-of-two bucket so admission hits
        a handful of compiled prefill programs under mixed-length traffic
        instead of retracing per distinct length; pad slots are masked by
        kv_valid and pos_offset exactly as in generate_tokens_batch."""
        if not self.free:
            raise RuntimeError("no free slot; call step() until one retires")
        if self._spec_pending:
            # Admission rewrites a slot's host mirrors, but an in-flight
            # verify chunk's successor would still read the THREADED
            # device slot_pos for that slot — process the pending chunk
            # first so host state is authoritative again.
            raise RuntimeError(
                "admit() with a speculative chunk in flight; process_spec_chunk first"
            )
        self._spec_pos_dev = None
        p = len(prompt_ids)
        if p + 1 >= self.max_len:
            raise ValueError("prompt too long for the slot window")
        bucket = self.bucket_for(p, self.max_len)
        off = bucket - p
        slot = self.free.pop()
        rid = self._next_id
        self._next_id += 1
        # Slot validity: the real prompt rows [off, bucket), growing per step.
        ar = np.arange(self.max_len)
        self._kv_np[slot] = (ar >= off) & (ar < bucket)
        self._off_np[slot] = off
        self._pos_np[slot] = bucket
        self._temp_np[slot] = temperature
        # .copy(): on the CPU backend jnp.asarray can alias the numpy
        # buffer ZERO-COPY, and these mirrors keep mutating while the
        # async program reads them — observed as flaky garbage logits.
        m = (
            self._match_prefix(list(prompt_ids))
            if os.environ.get("KAKVEDA_SERVE_PREFIX", "1") != "0"
            else None
        )
        if m is not None:
            pe, split, sw = m
            with self.stats_lock:
                self.prefix_stats["hits"] += 1
                self.prefix_stats["hit_tokens_saved"] += split
            self._mx["prefix_hit"].inc()
            self.cache, self.last = _admit_prefix_jit(
                self.params, self.cfg, self.cache, self.last,
                pe.kv, jnp.asarray([list(prompt_ids[split:])], jnp.int32),
                jnp.asarray(slot),
                jnp.asarray(self._kv_np.copy()), jnp.asarray(self._off_np.copy()),
                jnp.asarray(off + split, jnp.int32),
            )
        else:
            self._mx["prefix_miss"].inc()
            padded = [0] * off + list(prompt_ids)
            self.cache, self.last = _admit_jit(
                self.params, self.cfg, self.cache, self.last,
                jnp.asarray([padded], jnp.int32), jnp.asarray(slot),
                jnp.asarray(self._kv_np.copy()), jnp.asarray(self._off_np.copy()),
            )
        # st.index stays None until the first draft actually needs it
        # (_anchor builds it lazily): a pool whose gate is OFF — or that
        # never goes speculative — pays zero index maintenance.
        self.slots[slot] = _Slot(
            req_id=rid, prompt_len=bucket, max_new=max_new_tokens, on_tokens=on_tokens,
            prompt_ids=list(prompt_ids),
            k=self.spec_k,
        )
        self._mx["active"].set(len(self.slots))
        return rid

    def step_async(self):
        """Dispatch one decode chunk WITHOUT fetching its tokens; returns a
        handle for :meth:`process_chunk` (or None when no slot is active).

        This is the pipelining half of ``step()``: the per-chunk token
        fetch and the host work between chunks cost time the device would
        otherwise sit idle for, so an engine that dispatches chunk i+1
        before processing chunk i's tokens overlaps them with device
        work. Retirement (EOS / max_new) is then detected one
        chunk late; the overshoot chunk wastes compute but cannot corrupt
        state — cache writes clamp at the window (``mode="drop"``), each
        slot attends only within its own cache row, and the overshoot
        tokens are discarded host-side — so outputs are token-identical
        to the unpipelined path."""
        if not self.slots:
            return None
        if self._spec_pending:
            raise RuntimeError(
                "step_async() with a speculative chunk in flight; process_spec_chunk first"
            )
        self._fault_dispatch.fire()
        # A plain chunk moves the frontier through the host mirrors; any
        # previously threaded device slot_pos is stale from here on.
        self._spec_pos_dev = None
        t_dispatch = time.perf_counter()
        attend_len = self._grow_valid(self.chunk_steps)

        _ledger.note_transfer(
            "h2d",
            self._pos_np.nbytes + self._kv_np.nbytes + self._off_np.nbytes
            + self._temp_np.nbytes,
        )
        self.cache, self.last, _, self.rng, toks = _step_chunk_jit(
            self.params, self.cfg, self.cache, self.last, jnp.asarray(self._pos_np.copy()),
            jnp.asarray(self._kv_np.copy()), jnp.asarray(self._off_np.copy()),
            jnp.asarray(self._temp_np.copy()), self.rng, self.chunk_steps, attend_len,
        )
        self._pos_np += self.chunk_steps  # every slot advances in lockstep
        try:
            toks.copy_to_host_async()
        except Exception:  # noqa: BLE001 — backends without async copy
            pass
        # Slot refs (shared, not copied): a slot retired by an EARLIER
        # handle's processing — or by cancel_request between chunks —
        # shows st.done here and its overshoot tokens are skipped. A
        # freed slot re-admitted before this handle is processed gets a
        # NEW _Slot object (the snapshot still holds the done one), and
        # the admit scatter is ordered after the in-flight chunk by the
        # functional cache threading — so a snapshot can never alias or
        # corrupt a newer request.
        return toks, dict(self.slots), t_dispatch

    def process_chunk(self, handle) -> List[int]:
        """Fetch a dispatched chunk's tokens and retire finished slots;
        returns req_ids completed by that chunk."""
        if handle is None:
            return []
        self._fault_fetch.fire()
        toks, snapshot, t_dispatch = handle
        fetch = np.asarray(toks)
        _ledger.note_transfer("d2h", fetch.nbytes)
        toks_h, pairs, touched = split_fetch(fetch, self.B, self.chunk_steps, self.cfg.n_experts)
        if pairs is not None:
            self._note_expert_load(pairs, touched)
        # Gate denominator: dispatch→process is the chunk's EFFECTIVE
        # wall — under pipelining the fetch overlapped the next chunk's
        # device work, so this interval is the overlapped cost the spec
        # arm has to beat, not the synchronous one.
        wall = time.perf_counter() - t_dispatch
        self._mx["chunk_plain"].observe(wall)
        if self.spec_k and any(not st.done for st in snapshot.values()):
            self.note_plain_wall(wall)
        finished = []
        for slot, st in snapshot.items():
            if st.done:
                continue  # retired by an earlier chunk; these are overshoot tokens
            self._emit(slot, st, toks_h[slot], finished)
        return finished

    def _note_expert_load(self, pairs: np.ndarray, touched: np.ndarray) -> None:
        """A chunk's expert counts onto the metrics plane. ``touched``
        [expert layers, steps]: one observation per layer per step. ``pairs``
        [expert layers, E] goes into a decayed sum (about the last 50 chunks'
        tokens) whose fullest expert over the mean, in the worst layer, is
        observed once a chunk: one step's few dozen pairs over E experts read
        4-6 under perfectly even routing, which says how few they are and
        nothing of the router. A chunk no live slot took part in observes
        nothing."""
        if not pairs.any():
            return
        for v in touched.ravel().tolist():
            self._mx["moe_touched"].observe(v)
        load = pairs if self._moe_load is None else self._moe_load * 0.98 + pairs
        self._moe_load = load
        self._mx["moe_skew"].observe(float((load.max(axis=1) / load.mean(axis=1)).max()))

    def _emit(self, slot: int, st: _Slot, tok_row, finished: List[int]) -> None:
        """Accept a chunk's tokens into a slot (EOS / budget / window stops),
        fire the streaming callback, retire when done. Shared by the plain
        chunk path and the speculative path."""
        n_before = len(st.out)
        for t in tok_row:
            t = int(t)
            if self.eos_id is not None and t == self.eos_id:
                st.done = True
                break
            st.out.append(t)
            if st.index is not None:
                st.index.append(t)  # keep the draft corpus current
            if len(st.out) >= st.max_new or st.prompt_len + len(st.out) + 1 >= self.max_len:
                st.done = True
                break
        if len(st.out) > n_before:
            self._mx["tokens"].inc(len(st.out) - n_before)
        if st.on_tokens is not None:
            # Streaming: surface this chunk's accepted tokens as they
            # land. Exceptions must not kill the engine loop — a gone
            # stream consumer just stops receiving.
            try:
                st.on_tokens(st.out[n_before:], st.done)
            except Exception:  # noqa: BLE001
                st.on_tokens = None
        if st.done:
            self.results[st.req_id] = st.out
            finished.append(st.req_id)
            del self.slots[slot]
            self.free.append(slot)
            self._kv_np[slot] = False
            self._mx["active"].set(len(self.slots))

    def _grow_valid(self, steps: int) -> int:
        """Grow read-validity on the host mirror (vectorized over slots):
        each active slot may read its next ``steps`` rows as it writes
        them (reads stay bounded per-step by ``col <= slot_pos`` inside
        the chunk program). The left-pad region [0, pos_offset) stays
        invalid. One [B, L] upload per chunk replaces per-slot device
        scatters. ONE definition for both chunk flavors — the invariant
        must not fork.

        Returns the chunk's ``attend_len``, the prefix of each K/V slab the
        chunk program works on (:func:`_slab_prefix`): the power of two, from
        512 and capped at the window, that covers the highest ACTIVE slot's
        ``pos + steps`` — so no valid row of a live slot lies at or past it.
        An idle slot's position drifts in lockstep and does not count. At a
        2,048 window that is three chunk programs at most, each compiled on
        first use as an admit bucket is; a pool that holds a long sequence
        works on the whole slab."""
        from kakveda_tpu.ops.knn import pow2_bucket

        ar = np.arange(self.max_len)[None, :]
        active = np.zeros((self.B,), bool)
        active[list(self.slots)] = True
        limit = self._pos_np + steps
        self._kv_np |= active[:, None] & (ar >= self._off_np[:, None]) & (ar < limit[:, None])
        attend_len = pow2_bucket(int(limit[active].max()), floor=512, cap=self.max_len)
        self._mx["attend_rows"].observe(attend_len)
        return attend_len

    @staticmethod
    def _draft(hist: List[int], k: int) -> List[int]:
        """Prompt-lookup draft (host side), THE reference semantics the
        per-slot incremental index implements: most recent earlier
        occurrence of the LONGEST matching history suffix (3→2→1 tokens —
        longer context anchors the copy in the right template region),
        copy what followed it SHIFTED by one — the verify chunk's first
        position is the committed token t0 (known only on device), so
        drafts guess t0's continuation. A copy region that runs off the
        end of history extrapolates PERIODICALLY (period = distance from
        anchor to tail), so constant and short-period loops — exactly the
        most repetitive traffic — draft their own continuation instead of
        degenerating to PAD. PAD (0) fills only when history gives no
        anchor at all; wrong drafts cost nothing extra (the verify
        forward runs k+1 wide either way)."""
        idx = NgramIndex(hist)
        j, _ = idx.anchor
        if j < 0:
            return [0] * k
        n = len(hist)
        d, _ = copy_run(hist, j + 2, k, n - 1 - j, n=n)
        return d + [0] * (k - len(d))

    def _anchor(self, st: _Slot):
        """Anchor selection for one slot: its live suffix index first,
        the registered-prefix corpora as a fallback source — template
        traffic (LLM-judge calls, system preambles) reproduces spans of
        the registered head whose continuation the slot's own short
        history may not contain yet, so a weak self-anchor (< 3-gram)
        defers to a deeper match inside a registered prefix. Returns
        ``(corpus, j, period)`` — period 0 for cross-corpus hits (the
        hit may be the corpus tail itself, and periodicity of someone
        else's text means nothing: copy literally, no wrap)."""
        if st.index is None:
            st.index = NgramIndex(st.prompt_ids + st.out)
        j, m = st.index.anchor
        corpus, period = st.index.toks, (len(st.index.toks) - 1 - j if j >= 0 else 0)
        if m < 3 and self._prefixes:
            tail = st.index.toks[-3:]
            for pe in self._prefixes.values():
                if pe.index is None:
                    continue
                pj, pm = pe.index.lookup(tail)
                if pm > m and pj + 2 < len(pe.index.toks):
                    j, m, corpus, period = pj, pm, pe.index.toks, 0
        return corpus, j, period

    def _draft_slot(self, st: _Slot, k: int):
        """Drafts for one slot with host-authoritative history. Returns
        ``(drafts[k], cursor, predicted_emission)`` — cursor/prediction
        feed the pipelined continuation (:meth:`step_spec_async`)."""
        corpus, j, period = self._anchor(st)
        if j < 0:
            return [0] * k, None, None
        n = len(corpus)
        seq, nxt = copy_run(corpus, j + 1, k + 1, period, n=n)
        drafts = seq[1:] + [0] * (k + 1 - len(seq))
        cursor = (corpus, nxt, period, n) if len(seq) == k + 1 else None
        return drafts, cursor, seq

    @staticmethod
    def _draft_cursor(st: _Slot, k: int):
        """Drafts for a slot whose previous verify chunk is still in
        flight AND whose prediction chain is alive: continue the SAME
        copy run past the predicted emission. The host hasn't seen the
        in-flight chunk's tokens, so anchoring on the stale suffix would
        guess a continuation of the WRONG tail; continuing the cursor
        instead bets the in-flight chunk fully accepts — exactly the
        traffic where speculation pays — and process_spec_chunk drops
        the cursor the moment a chunk doesn't."""
        corpus, idx, period, n = st.spec_cursor
        seq, nxt = copy_run(corpus, idx, k + 1, period, n=n)
        drafts = seq[1:] + [0] * (k + 1 - len(seq))
        cursor = (corpus, nxt, period, n) if len(seq) == k + 1 else None
        return drafts, cursor, seq

    def _draft_slot_stale(self, st: _Slot, k: int):
        """Drafts for a slot whose chain broke while a chunk is in
        flight: re-anchor on the HOST-known (stale) history. The broken
        chain means the in-flight chunk carries PAD/garbage drafts, so it
        will (almost always) commit exactly ONE unseen token — the
        continuation of the stale tail, i.e. the anchor's own first
        prediction. Predict k+2 ahead and skip BOTH that token (p0) and
        this chunk's own t0 (p1): drafts are p2.. — the pipeline
        re-enters the accepting regime one chunk after a miss instead of
        never. If the in-flight chunk surprises with >1 tokens the
        prediction just misses and the next dispatch re-anchors again
        (acceptance heuristics never touch parity)."""
        corpus, j, period = self._anchor(st)
        if j < 0:
            return [0] * k, None, None
        n = len(corpus)
        seq, nxt = copy_run(corpus, j + 1, k + 2, period, n=n)
        drafts = seq[2:] + [0] * (k + 2 - len(seq))
        ok = len(seq) == k + 2
        cursor = (corpus, nxt, period, n) if ok else None
        return drafts, cursor, seq[1:] if ok else None

    def _pool_k(self) -> int:
        """Verify width for the next chunk: the max of the active slots'
        adaptive k, rounded up to a power of two so the compile count
        stays logarithmic in spec_k, capped at the configured ceiling."""
        top = max(st.k for st in self.slots.values())
        k = 1
        while k < top:
            k <<= 1
        return max(1, min(k, self.spec_k))

    def step_spec_async(self):
        """Dispatch one speculative verify chunk WITHOUT fetching its
        acceptance; returns a handle for :meth:`process_spec_chunk`.

        This is what makes engine speculation compatible with the chunk
        pipelining win: the verify program RETURNS the post-acceptance
        slot_pos, which threads into the next dispatch as a device array
        — no host sync between verify chunks. The host drafts chunk i+1
        from each slot's copy CURSOR (the predicted continuation of the
        in-flight chunk), read-validity grows by the whole in-flight
        width from the last host-known position, and overshoot obeys the
        same clamp-and-discard contract as plain pipelining (writes clamp
        via mode="drop" in the slot's own cache row; stale snapshots skip
        done slots; rejected-draft rows are overwritten before any query
        can attend that far). Admissions require host-authoritative state:
        callers drain in-flight handles before admitting (admit raises
        otherwise)."""
        if not self.slots:
            return None
        self._fault_dispatch.fire()
        t_dispatch = time.perf_counter()  # drafting is part of the chunk's cost
        k = self._pool_k()
        pipelined = self._spec_pending > 0
        drafts = np.zeros((self.B, k), np.int32)
        kmap: Dict[int, int] = {}
        pmap: Dict[int, Optional[List[int]]] = {}
        for slot, st in self.slots.items():
            kd = min(max(st.k, 1), k)
            kmap[slot] = kd
            if not pipelined:
                row, cursor, pred = self._draft_slot(st, kd)
            elif st.spec_cursor is not None:
                row, cursor, pred = self._draft_cursor(st, kd)
            else:
                row, cursor, pred = self._draft_slot_stale(st, kd)
            drafts[slot, : len(row)] = row  # columns past kd stay PAD
            st.spec_cursor = cursor
            pmap[slot] = pred
        # Validity must cover every in-flight chunk's reads from the last
        # host-known position; rows past the true frontier are garbage-
        # but-valid and excluded by each query's own causal bound
        # (col <= qpos), the same argument that makes rejected-draft rows
        # safe.
        attend_len = self._grow_valid(self._spec_pending_width + k + 1)
        slot_pos = (
            self._spec_pos_dev
            if self._spec_pos_dev is not None
            else jnp.asarray(self._pos_np.copy())
        )
        _ledger.note_transfer(
            "h2d",
            self._kv_np.nbytes + self._off_np.nbytes
            + getattr(drafts, "nbytes", 0),
        )
        self.cache, self.last, self._spec_pos_dev, toks, counts = _spec_chunk_jit(
            self.params, self.cfg, self.cache, self.last, slot_pos,
            jnp.asarray(self._kv_np.copy()), jnp.asarray(self._off_np.copy()),
            jnp.asarray(drafts), k, attend_len,
        )
        self._spec_pending += 1
        self._spec_pending_width += k + 1
        for arr in (toks, counts):
            try:
                arr.copy_to_host_async()
            except Exception:  # noqa: BLE001 — backends without async copy
                pass
        return toks, counts, dict(self.slots), k, kmap, pmap, t_dispatch

    def process_spec_chunk(self, handle) -> List[int]:
        """Fetch a dispatched verify chunk's tokens/acceptance, emit the
        accepted prefixes, adapt each slot's draft length, and feed the
        auto-gate; returns req_ids completed by that chunk."""
        if handle is None:
            return []
        self._fault_fetch.fire()
        toks, counts, snapshot, k, kmap, pmap, t_dispatch = handle
        toks_h = np.asarray(toks)
        counts_h = np.asarray(counts).astype(np.int32)
        _ledger.note_transfer("d2h", toks_h.nbytes + counts_h.nbytes)
        self._spec_pending -= 1
        self._spec_pending_width -= k + 1
        wall = time.perf_counter() - t_dispatch
        self._mx["chunk_spec"].observe(wall)
        if k in self._spec_widths_warm:
            self._spec_walls.append(wall)
        else:
            self._spec_widths_warm.add(k)  # compile run — not a cost sample
        # Every slot's mirror advances by ITS emitted count (inactive slots
        # drift harmlessly — admission resets their position, exactly as
        # with the lockstep += chunk_steps of the plain path).
        self._pos_np += counts_h
        finished: List[int] = []
        self._gate_spec_chunks += 1
        # Per-chunk stats accumulate locally and land in spec_stats under
        # ONE lock acquire — the lock must not be held across _emit (its
        # streaming callbacks are caller code).
        em = sc = dr = ac = 0
        for slot, st in snapshot.items():
            if st.done:
                st.spec_cursor = None
                continue  # retired earlier; overshoot tokens, skip
            n = int(counts_h[slot])
            kd = kmap.get(slot, k)
            a = max(0, min(n - 1, kd))  # accepted drafts (t0 is free)
            em += n
            sc += 1
            dr += kd
            ac += a
            self._tpv_recent.append(n)
            # Per-slot adaptive k: a fully-accepted chunk DOUBLES the
            # draft width (rejected drafts ride the same weight stream,
            # so recovering fast when traffic turns repetitive is nearly
            # free); a fully-rejected one halves toward 1, so a slot
            # whose traffic stopped repeating stops paying host drafting
            # and verify width for nothing. Partial accepts hold.
            frac = a / kd if kd else 0.0
            st.accept_ema = 0.7 * st.accept_ema + 0.3 * frac
            if a >= kd:
                st.k = min(self.spec_k, max(st.k, kd) * 2)
            elif a == 0:
                st.k = max(1, st.k // 2)
            # The prediction chain survives ONLY a fully-accepted chunk
            # whose tokens match ITS OWN prediction (from the handle — a
            # newer dispatch has already moved the slot's cursor past
            # this chunk, and that continuation is garbage if this chunk
            # deviated).
            pred = pmap.get(slot)
            emitted = [int(t) for t in toks_h[slot][:n]]
            if pred is None or n != kd + 1 or emitted != pred[:n]:
                st.spec_cursor = None
            self._emit(slot, st, toks_h[slot][:n], finished)
        with self.stats_lock:
            s = self.spec_stats
            s["chunks"] += 1
            s["emitted"] += em
            s["slot_chunks"] += sc
            s["drafted"] += dr
            s["accepted"] += ac
            kt = s["k_trace"]
            kt.append(k)
            if len(kt) > 64:
                del kt[0]
        self._mx["drafted"].inc(dr)
        self._mx["accepted"].inc(ac)
        self._mx["spec_k"].set(k)
        if self.recorder is not None and k != self._last_k_rec:
            self.recorder.record("pool_k", k=k)
            self._last_k_rec = k
        self._gate_eval()
        return finished

    def step_spec(self) -> List[int]:
        """One synchronous speculative verify chunk for every active slot
        (greedy pools only — the engine falls back to plain chunks when
        any active slot samples). The engine loop pipelines instead
        (step_spec_async / process_spec_chunk one chunk apart) whenever
        :meth:`spec_pipeline_ready` says the overlap is acceptance-safe."""
        return self.process_spec_chunk(self.step_spec_async())

    def spec_pipeline_ready(self) -> bool:
        """True when dispatching the NEXT verify chunk before fetching the
        in-flight one is acceptance-safe: every active slot sits on a
        live prediction chain AND has been accepting (EMA ≥ 0.5). A
        cursor continuation bets on FULL acceptance of the un-fetched
        chunk — on traffic that accepts halfway, that bet loses most
        chunks and would trade real acceptance for overlap; the sync
        order (fetch, re-anchor, dispatch) keeps acceptance there, and
        the gate decides whether sync verify chunks pay at all."""
        return all(
            st.spec_cursor is not None and st.accept_ema >= 0.5
            for st in self.slots.values()
        )

    def note_plain_wall(self, wall: float) -> None:
        """Record one plain chunk's effective wall (chunk_steps tokens per
        slot) — the cost the auto-gate compares verify chunks against.
        process_chunk self-reports; while the gate is OFF each plain
        chunk also counts toward the re-probe window that sends the gate
        back to warmup (traffic may turn repetitive again)."""
        if self._plain_warm:
            self._plain_walls.append(wall)
        else:
            self._plain_warm = True  # compile run — not a cost sample
        with self.stats_lock:
            if self.spec_stats["gate_state"] == "off":
                self._gate_plain_since_off += 1
                if self._gate_reprobe and self._gate_plain_since_off >= self._gate_reprobe:
                    self._set_gate_state("warmup")
                    self._gate_spec_chunks = 0
                    self._gate_plain_since_off = 0
                    self._gate_reprobes += 1
                    self._tpv_recent.clear()

    def _gate_eval(self) -> None:
        """The acceptance auto-gate: speculation pays iff observed
        tokens/verify clears the measured break-even — the verify chunk's
        effective wall divided by the plain path's effective per-token
        wall (both medians of recent chunks, so one compile spike can't
        flip the gate). Below it, the pool turns speculation OFF and
        decodes plain — spec can never again be a configured slowdown; a
        re-probe window (KAKVEDA_SERVE_SPEC_REPROBE plain chunks) sends
        it back to warmup with a hysteresis margin so a borderline pool
        doesn't flap."""
        if not self.spec_k:
            return
        tpv = float(np.mean(self._tpv_recent)) if self._tpv_recent else 0.0
        if self._spec_walls and self._plain_walls:
            spec_w = float(np.median(self._spec_walls))
            plain_w = float(np.median(self._plain_walls)) / max(self.chunk_steps, 1)
            be = spec_w / max(plain_w, 1e-9)
        else:
            be = self._gate_prior  # no plain measurement yet: conservative prior
        with self.stats_lock:
            g = self.spec_stats
            g["tokens_per_verify"] = round(tpv, 3)
            g["break_even"] = round(be, 3)
            if g["gate_state"] in ("warmup", "on") and self._gate_spec_chunks >= self._gate_warmup:
                need = be * (1.1 if self._gate_reprobes else 1.0)
                if tpv < need:
                    self._set_gate_state("off")
                    self._gate_plain_since_off = 0
                else:
                    self._set_gate_state("on")

    def cancel_request(self, rid: int) -> Optional[List[int]]:
        """Retire a mid-decode request NOW (between chunks): returns its
        partial tokens, frees the slot, and marks the _Slot done so a
        stale pipelined snapshot skips it as overshoot. THE retirement
        bookkeeping for cancellation — one definition, shared with the
        normal retire tail in _emit. Returns None when the rid is not
        active (already finished or never admitted)."""
        for slot, st in list(self.slots.items()):
            if st.req_id == rid:
                st.done = True
                del self.slots[slot]
                self.free.append(slot)
                self._kv_np[slot] = False
                self._mx["active"].set(len(self.slots))
                return st.out
        return None

    def spec_ready(self) -> bool:
        """True when the next chunk should be a speculative verify chunk:
        spec enabled, the auto-gate not OFF, the gate's plain-cost
        calibration done (the first KAKVEDA_SERVE_SPEC_CALIB chunks of a
        pool run plain so break-even is measured, not assumed), and every
        active slot greedy. THE predicate for both step() and the engine
        loop (which needs it separately to drain its pipelined handle
        before switching chunk flavors). The brownout ladder's FIRST step
        (core/admission.py) vetoes speculation here — under pressure the
        verify-width FLOPs go back to plain decode; the gate's own state
        machine is untouched, so stepping back down resumes where the
        gate left off."""
        return bool(
            self.spec_k
            and self.slots
            and self.spec_stats["gate_state"] != "off"
            and len(self._plain_walls) >= self._gate_calib
            and all(self._temp_np[s] <= 0.0 for s in self.slots)
            and _admission.get_admission().brownout.spec_allowed()
        )

    def step(self) -> List[int]:
        """One decode chunk for every active slot; returns req_ids finished
        in this chunk (their token lists land in ``results``). With
        ``spec_k`` set, an all-greedy pool and the auto-gate open this IS
        a speculative verify chunk — ONE dispatch rule for
        step()/run_all/engine callers."""
        if self.spec_ready():
            return self.step_spec()
        return self.process_chunk(self.step_async())

    def run_all(self, prompts: List[List[int]], max_new_tokens: int = 64) -> List[List[int]]:
        """Drain a whole request list through the slot pool (admitting as
        slots free up); returns outputs in request order."""
        pending = list(enumerate(prompts))
        order: Dict[int, int] = {}
        while pending or self.slots:
            while pending and self.free:
                idx, p = pending.pop(0)
                order[self.admit(p, max_new_tokens)] = idx
            self.step()
        # Consume only THIS call's request ids: results from an earlier
        # run_all/admit on the same batcher must neither leak in nor crash
        # the index lookup (run_all is reusable for warmup+measure passes).
        outs: List[List[int]] = [[] for _ in prompts]
        for rid, idx in order.items():
            outs[idx] = self.results.pop(rid, [])
        return outs


class ServingEngine:
    """The ONLINE serving path: one shared ContinuousBatcher behind a
    thread-safe submit API, so every concurrent caller — playground chat,
    eval runner, LLM-judge tier — joins ONE decode batch instead of each
    running its own per-request decode stream (the reference's model: one
    sequential Ollama HTTP hop per request, services/dashboard/app.py:
    1226-1258).

    A single daemon loop thread owns the batcher (admission and decode
    chunks never race); callers block on a Future. Requests are admitted
    mid-decode as slots free up, each with its own max_tokens/temperature.
    Greedy outputs are slot-for-slot identical to a solo
    ``generate_tokens`` call (the batcher's parity invariant), so routing
    online traffic here is a throughput decision, not an accuracy one.

    ``fits()`` mirrors the batcher's admission bucketing: a request whose
    padded prompt + budget would overrun the slot window is the CALLER's
    cue to fall back to a solo decode (LlamaRuntime does exactly that) —
    inside the pool it would truncate where the solo path keeps going.
    """

    def __init__(
        self,
        params: Params,
        cfg: LlamaConfig,
        *,
        batch_slots: int = 8,
        max_len: int = 512,
        chunk_steps: int = 8,
        eos_id: Optional[int] = None,
        rng: Optional[jax.Array] = None,
        spec_k: Optional[int] = None,
        name: Optional[str] = None,
    ):
        if spec_k is None:
            spec_k = int(os.environ.get("KAKVEDA_SERVE_SPEC", "0"))
        self.name = name or "default"
        # The flight recorder: request timelines + gate/k transitions,
        # dumped via GET /flightrecorder and automatically on loop death.
        self.recorder = _metrics.FlightRecorder(f"serving/{self.name}")
        # Everything the supervisor needs to rebuild the batcher after a
        # loop death — the rebuild constructs a FRESH ContinuousBatcher
        # (cache slabs re-zeroed by init_cache) from these.
        self._params, self._cfg = params, cfg
        self._cb_kw = dict(
            batch_slots=batch_slots, max_len=max_len, chunk_steps=chunk_steps,
            eos_id=eos_id, rng=rng, spec_k=spec_k,
        )
        self.cb = ContinuousBatcher(
            params, cfg, name=self.name, recorder=self.recorder, **self._cb_kw
        )
        # Supervisor state: restart budget (read once — the supervisor must
        # not change behavior mid-life because the env moved), restarts
        # consumed, and the terminal-death latch (submit fails fast on it).
        self._restart_budget = int(os.environ.get("KAKVEDA_SERVE_RESTARTS", "2"))
        self._restarts = 0  # kakveda: owned-by[serving-loop] (supervisor writes)
        self._dead = threading.Event()
        # Prefixes successfully registered on the live batcher, in order —
        # the supervisor re-registers them on the rebuilt batcher so a
        # restart doesn't silently lose the prefix-cache hit rate.
        self._prefix_ids: List[Tuple[int, ...]] = []
        reg = _metrics.get_registry()
        el = {"engine": self.name}
        self._m_requests = reg.counter(
            "kakveda_serving_requests_total",
            "Serving requests by outcome", ("engine", "outcome"),
        )
        self._mx = {
            "queue_wait": reg.histogram(
                "kakveda_serving_queue_wait_seconds",
                "Submit-to-admission wait in the serving engine queue",
                ("engine",),
            ).labels(**el),
            "prefill": reg.histogram(
                "kakveda_serving_prefill_seconds",
                "Admission prefill dispatch wall per request", ("engine",),
            ).labels(**el),
            "first_chunk": reg.histogram(
                "kakveda_serving_first_chunk_seconds",
                "End of a request's admission prefill to its first delivered "
                "tokens (chunks queued ahead, its first decode chunk, the "
                "fetch)", ("engine",),
            ).labels(**el),
            "ttft": reg.histogram(
                "kakveda_serving_ttft_seconds",
                "Submit-to-first-token latency per request", ("engine",),
            ).labels(**el),
            "request": reg.histogram(
                "kakveda_serving_request_seconds",
                "Submit-to-completion wall per request", ("engine",),
            ).labels(**el),
            "rate": reg.histogram(
                "kakveda_serving_tokens_per_second",
                "Per-request decode rate (tokens / request wall)",
                ("engine",), buckets=_metrics.RATE_BUCKETS,
            ).labels(**el),
            "errors": reg.counter(
                "kakveda_serving_engine_errors_total",
                "Serving-engine loop deaths (flight recorder dumped on "
                "each)", ("engine",),
            ).labels(**el),
            "restarts": reg.counter(
                "kakveda_serving_engine_restarts_total",
                "Supervisor restarts of a serving-engine loop after a "
                "crash (bounded by KAKVEDA_SERVE_RESTARTS)", ("engine",),
            ).labels(**el),
        }
        # Overload protection (core/admission.py): the submit-side backlog
        # bound. Past it, submit() SHEDS with a typed OverloadError instead
        # of growing a queue nobody will drain before callers time out —
        # the HTTP tier surfaces it as 429 + Retry-After.
        self._admit_queue = int(os.environ.get("KAKVEDA_ADMIT_QUEUE", "64"))
        # Per-tenant weighted-fair slot admission (docs/robustness.md
        # § multi-tenancy): when enabled and submits carry a tenant, a
        # freed slot goes to the waiting head of the LEAST-served tenant
        # (deficit pick, per-tenant FIFO), with a starvation bound — any
        # item passed over KAKVEDA_TENANT_PROMOTE_ROUNDS times is admitted
        # next regardless of deficit (max-wait promotion). All tenant-blind
        # or KAKVEDA_TENANT_FAIR=0 traffic degenerates to exact FIFO.
        self._tenant_fair = _admission.tenant_fair_enabled()
        self._promote_rounds = max(
            1, int(os.environ.get("KAKVEDA_TENANT_PROMOTE_ROUNDS", "8")))
        self._fair_table_max = max(
            2, int(os.environ.get("KAKVEDA_TENANT_TABLE", "512")))
        # Loop-owned under _submit_lock (picks happen inside the lock):
        # recent slot admissions per tenant — the deficit input. Bounded +
        # halved periodically so share means RECENT share.
        self._fair_served: Dict[str, int] = {}
        self._fair_picks = 0
        self._fair_promotions = 0
        # Generation items: (ids, max_new, temp, on_tokens, t_submit,
        # deadline_abs_or_None, fut); control items: ("cancel"|"prefix", …, fut).
        self._q: "queue.Queue[tuple]" = queue.Queue()
        self._closed = threading.Event()
        self._submit_lock = sanitize.named_lock("ServingEngine._submit_lock")  # closes the submit/close race
        # submit inserts pre-handoff under _submit_lock (the close race);
        # kakveda: owned-by[serving-loop] — the loop owns every later mutation.
        self._pend: Dict[int, Future] = {}  # loop-owned; close() fails leftovers
        self._waiting: List = []  # loop-owned: admitted-when-a-slot-frees queue
        # kakveda: owned-by[serving-loop] — per-request timeline state
        self._track: Dict[int, dict] = {}
        self._stats = {"submitted": 0, "completed": 0, "max_active": 0, "chunks": 0}
        self._thread = threading.Thread(target=self._loop, daemon=True, name="serving-engine")
        self._thread.start()

    def stats(self) -> dict:
        """Lock-guarded deep-copy snapshot of the engine counters plus the
        batcher's spec/prefix stats. The loop thread mutates all of these
        concurrently with readers (``k_trace`` append vs list copy), so
        THE read API is this snapshot — never the live dicts."""
        with self.cb.stats_lock:
            snap = dict(self._stats)
            snap["spec"] = copy.deepcopy(self.cb.spec_stats)
            snap["prefix"] = dict(self.cb.prefix_stats)
        snap["restarts"] = self._restarts
        snap["dead"] = self._dead.is_set()
        with self._submit_lock:
            snap["tenant_fair"] = {
                "enabled": self._tenant_fair,
                "served": dict(self._fair_served),
                "promotions": self._fair_promotions,
            }
        return snap

    def _bump(self, key: str, v: int = 1) -> None:
        with self.cb.stats_lock:
            self._stats[key] += v

    def _note_active(self) -> None:
        with self.cb.stats_lock:
            self._stats["max_active"] = max(self._stats["max_active"], self.cb.active)

    def _finish_telemetry(self, rid: int, n_tokens: int) -> Optional[dict]:
        """Close a request's timeline: observe the lifecycle histograms,
        record the flight-recorder event, and return the timeline dict
        (attached to the caller's Future so generate() can surface it in
        meta / as OTel span events)."""
        tr = self._track.pop(rid, None)
        if tr is None:
            return None
        wall = time.perf_counter() - tr["submit"]
        rate = n_tokens / wall if wall > 0 else 0.0
        tp = _trace.parse_traceparent(tr.get("traceparent") or "")
        self._mx["request"].observe(wall, exemplar=tp[0] if tp else None)
        if n_tokens:
            self._mx["rate"].observe(rate)
        self._m_requests.labels(engine=self.name, outcome="completed").inc()
        tl = {
            "request_id": rid,
            "queue_wait_ms": round((tr["admit"] - tr["submit"]) * 1000, 3),
            "prefill_ms": round(tr.get("prefill_s", 0.0) * 1000, 3),
            "first_chunk_ms": round(tr.get("first_chunk_s", 0.0) * 1000, 3),
            "ttft_ms": (
                round((tr["first"] - tr["submit"]) * 1000, 3)
                if tr["first"] is not None else None
            ),
            "wall_ms": round(wall * 1000, 3),
            "tokens": n_tokens,
            "tokens_per_s": round(rate, 2),
        }
        if self.recorder is not None:
            self.recorder.record("request", **tl)
        # Timeline -> span: recorded after the fact (the loop thread has
        # no ambient context), parented on the submitter's traceparent so
        # a /warn or /generate trace shows queue-wait/prefill/ttft inline.
        rec = _trace.get_tracer().record_completed(
            "serving.request",
            traceparent=tr.get("traceparent") or None,
            ts=time.time() - wall, dur_ms=tl["wall_ms"], outcome="ok",
            engine=self.name, queue_wait_ms=tl["queue_wait_ms"],
            prefill_ms=tl["prefill_ms"], first_chunk_ms=tl["first_chunk_ms"],
            ttft_ms=tl["ttft_ms"] or 0.0,
            tokens=n_tokens,
        )
        if rec:
            tl["trace_id"] = rec["trace_id"]
        return tl

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """True when the request can run in the pool WITHOUT truncating
        where a solo decode wouldn't: the admission bucket (power-of-two
        left-pad) plus the full token budget must fit the slot window."""
        ml = self.cb.max_len
        if prompt_len + 1 >= ml:
            return False
        bucket = ContinuousBatcher.bucket_for(prompt_len, ml)
        return bucket + max_new_tokens + 1 <= ml

    def submit(
        self,
        prompt_ids: List[int],
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        on_tokens=None,
        deadline_s: Optional[float] = None,
        klass: str = "interactive",
        tenant: str = "",
    ) -> Future:
        """Enqueue a request; the Future resolves to the generated id list.

        ``on_tokens(new_ids, done)`` (optional) streams each decode chunk's
        accepted tokens as they land — called on the engine loop thread, so
        it must be non-blocking (push to a queue and return).

        ``deadline_s`` (optional) bounds submit-to-completion wall time:
        past it, the request retires at the next chunk boundary through
        the cancel_request done-flag path (safe under pipelining) and its
        Future fails with :class:`DeadlineExceededError` carrying the
        partial tokens.

        ``klass`` is the admission class (``interactive`` default,
        ``background`` for batch/eval work). Overload protection runs
        BEFORE anything enqueues: a degraded backend fails fast with
        :class:`DeviceUnavailableError`; the brownout ladder may shed the
        class outright or clamp ``max_new_tokens``; a backlog past
        ``KAKVEDA_ADMIT_QUEUE`` sheds with :class:`OverloadError`; and a
        ``deadline_s`` the live queue-wait history says cannot be met is
        rejected NOW instead of burning a slot and expiring anyway.
        ``tenant`` (optional, the app key) enters the request into the
        weighted-fair slot scheduler and stamps shed provenance; empty
        keeps the request tenant-blind (exact seed behavior).

        Neither error is a RuntimeError — shed work must surface as 429,
        never silently take the solo-decode fallback path."""
        _admission.get_device_health().check()
        adm = _admission.get_admission()
        if adm.enabled:
            if adm.brownout.class_shed(klass):
                self._m_requests.labels(engine=self.name, outcome="shed").inc()
                adm.shed(klass, "brownout", tenant=tenant)
            with self._submit_lock:
                backlog = self._q.qsize() + len(self._waiting)
            if backlog >= self._admit_queue:
                self._m_requests.labels(engine=self.name, outcome="shed").inc()
                adm.shed(
                    klass, "queue_full",
                    detail=f"engine backlog {backlog} >= {self._admit_queue}",
                    tenant=tenant,
                )
            if deadline_s is not None and backlog > 0:
                # Deadline-aware shed: only with a LIVE backlog — an empty
                # queue means the wait history describes some past storm,
                # not this request's fate.
                predicted = adm.predicted_wait(klass)
                if predicted > deadline_s:
                    self._m_requests.labels(engine=self.name, outcome="shed").inc()
                    adm.shed(
                        klass, "deadline",
                        detail=f"predicted queue wait {predicted:.2f}s exceeds "
                               f"deadline {deadline_s:.2f}s",
                        tenant=tenant,
                    )
            cap = adm.brownout.token_cap()
            if cap is not None:
                max_new_tokens = min(max_new_tokens, cap)
        with self._submit_lock:
            # Atomic with close()'s drain: without the lock a put landing
            # between close()'s _closed.set() and its queue drain would
            # enqueue into a dead loop and hang its caller forever.
            if self._dead.is_set():
                raise EngineDeadError(
                    f"ServingEngine {self.name!r} is dead (restart budget "
                    f"exhausted after {self._restarts} restart(s))"
                )
            if self._closed.is_set():
                raise RuntimeError("ServingEngine is closed")
            t0 = time.perf_counter()
            deadline = t0 + deadline_s if deadline_s is not None else None
            fut: Future = Future()
            # Trace context is captured HERE (the caller's contextvar) and
            # rides the Future — the loop thread has no ambient context, so
            # the serialized traceparent is the only bridge to the
            # serving.request span recorded at _finish_telemetry.
            fut.traceparent = _trace.current_traceparent()
            # Tenant identity + fairness counters ride the Future too (the
            # traceparent precedent): the 7-field waiting-item layout and
            # every item[5]/item[-1] access stay untouched.
            fut.tenant = tenant
            fut.fair_rounds = 0
            self._q.put(
                (list(prompt_ids), max_new_tokens, temperature, on_tokens,
                 t0, deadline, fut)
            )
            self._bump("submitted")
            return fut

    def generate_ids(
        self, prompt_ids: List[int], max_new_tokens: int = 64, temperature: float = 0.0
    ) -> List[int]:
        """Blocking submit — what runtime.generate calls from its executor
        thread while the loop thread decodes for everyone at once."""
        return self.submit(prompt_ids, max_new_tokens, temperature).result()

    def cancel(self, fut: Future) -> None:
        """Best-effort cancel of a submitted request: if still queued, the
        Future cancels; if mid-decode, the loop retires its slot at the
        next chunk boundary (the slot frees for other traffic instead of
        decoding a result nobody will read — the disconnect case). The
        Future resolves with the tokens generated so far."""
        if fut.cancel():
            return  # never admitted; set_running_or_notify_cancel skips it
        with self._submit_lock:
            if self._closed.is_set():
                return
            self._q.put(("cancel", fut, fut))

    def register_prefix(self, prefix_ids: List[int], timeout: float = 120.0) -> bool:
        """Precompute a shared prompt prefix's K/V once; later submits whose
        prompts start with it prefill only their suffix. Runs on the loop
        thread (the batcher is loop-owned; a registration prefill must not
        race a decode chunk's donated cache). Blocking; returns whether the
        prefix was accepted (see ContinuousBatcher.register_prefix)."""
        with self._submit_lock:
            if self._dead.is_set():
                raise EngineDeadError(
                    f"ServingEngine {self.name!r} is dead (restart budget "
                    f"exhausted after {self._restarts} restart(s))"
                )
            if self._closed.is_set():
                raise RuntimeError("ServingEngine is closed")
            fut: Future = Future()
            self._q.put(("prefix", list(prefix_ids), fut))
        return bool(fut.result(timeout=timeout))

    @staticmethod
    def _fail(fut: Future, err: BaseException) -> None:
        """set_exception tolerant of losing the race against the loop's
        set_result (close() can outlive its 5 s join while a chunk compile
        finishes): whichever side lands second is a no-op, never an
        InvalidStateError escaping into restore()/eviction."""
        try:
            if not fut.done():
                fut.set_exception(err)
        except Exception:  # noqa: BLE001 — InvalidStateError: already resolved
            pass

    def _fail_all(self, err: BaseException) -> None:
        """Fail everything queued, waiting-for-a-slot, or mid-decode —
        shared by close() and the loop's own exit/death paths. The submit
        lock guards the _waiting handoff (the loop mutates it under the
        same lock), so close() racing a loop thread that outlives its
        join can't corrupt the list or strand an item both sides miss:
        whichever side runs LAST sees the leftovers, and _fail tolerates
        double resolution."""
        with self._submit_lock:
            while True:
                try:
                    *_rest, fut = self._q.get_nowait()
                except queue.Empty:
                    break
                self._fail(fut, err)
            for item in self._waiting:
                self._fail(item[-1], err)
            self._waiting.clear()
            for fut in list(self._pend.values()):
                self._fail(fut, err)
            self._pend.clear()
            self._track.clear()

    def _fail_inflight(self, err: BaseException) -> None:
        """Fail ONLY requests already admitted into the (now dead) batcher —
        their slot state is unrecoverable. Queued/waiting items are left in
        place: the supervisor's rebuilt loop re-admits them."""
        with self._submit_lock:
            for fut in list(self._pend.values()):
                self._fail(fut, err)
            self._pend.clear()
            self._track.clear()

    def _pick_waiting_locked(self):
        """Pop the next waiting generation item for a freed slot. Caller
        holds ``_submit_lock`` and guarantees ``_waiting`` is non-empty.

        Tenant-fair path (KAKVEDA_TENANT_FAIR=1, docs/robustness.md
        § multi-tenancy):

        1. Max-wait promotion — the earliest-queued item passed over
           ``_promote_rounds`` times is taken regardless of deficit. This
           is the starvation BOUND: every pick increments the skip count
           of every item left behind, so any waiting item is admitted
           within K scheduling rounds of reaching the front of its
           tenant's subqueue, flood or no flood.
        2. Deficit pick — among each tenant's FIFO head, take the tenant
           with the fewest recent slot admissions. A light tenant beats a
           flooder for every freed slot; per-tenant order stays FIFO.

        Tenant-blind traffic (all tenants ``""``) reduces to index 0 both
        ways — exact FIFO — and ``KAKVEDA_TENANT_FAIR=0`` short-circuits
        to ``pop(0)`` before any of this runs (bit-for-bit seed)."""
        if not self._tenant_fair or len(self._waiting) <= 1:
            return self._waiting.pop(0)
        pick = None
        for i, item in enumerate(self._waiting):
            if getattr(item[-1], "fair_rounds", 0) >= self._promote_rounds:
                pick = i
                self._fair_promotions += 1
                _admission.note_tenant_promotion("serving")
                break
        if pick is None:
            seen = set()
            best = None
            pick = 0
            for i, item in enumerate(self._waiting):
                t = getattr(item[-1], "tenant", "")
                if t in seen:
                    continue  # only each tenant's FIFO head competes
                seen.add(t)
                s = self._fair_served.get(t, 0)
                if best is None or s < best:
                    best, pick = s, i
        item = self._waiting.pop(pick)
        t = getattr(item[-1], "tenant", "")
        if t not in self._fair_served and len(self._fair_served) >= self._fair_table_max:
            # Bounded table: drop the heaviest-served key — it re-enters
            # at zero (brief priority boost, the safe failure direction).
            del self._fair_served[max(self._fair_served,
                                      key=self._fair_served.get)]
        self._fair_served[t] = self._fair_served.get(t, 0) + 1
        self._fair_picks += 1
        if self._fair_picks % 1024 == 0:
            # Decay: fair share means RECENT share, and zeros drop.
            self._fair_served = {
                k: v // 2 for k, v in self._fair_served.items() if v // 2 > 0
            }
        for other in self._waiting:
            fut = other[-1]
            fut.fair_rounds = getattr(fut, "fair_rounds", 0) + 1
        return item

    def _rebuild(self) -> None:
        """Rebuild the batcher after a loop death: a FRESH ContinuousBatcher
        (cache slabs re-zeroed by init_cache; gate/k/pipeline/adaptive state
        back to construction defaults — the constructor publishes the full
        gate-gauge vector, the same single-definition family
        ``_set_gate_state`` moves), then re-register every previously
        accepted prefix so a restart doesn't silently lose the prefix-cache
        hit rate. Supervisor-thread only."""
        self.cb = ContinuousBatcher(
            self._params, self._cfg, name=self.name, recorder=self.recorder,
            **self._cb_kw,
        )
        # Fairness state is RE-DERIVED from the surviving queue, never
        # trusted from the crashed loop: served deficits reset and every
        # waiting item's skip count restarts, so the rebuilt scheduler
        # starts from what is actually still queued (ISSUE contract — a
        # crash must not let stale counters starve or favor anyone).
        with self._submit_lock:
            self._fair_served.clear()
            self._fair_picks = 0
            for item in self._waiting:
                item[-1].fair_rounds = 0
        for ids in list(self._prefix_ids):
            try:
                self.cb.register_prefix(list(ids))
            # Prefix reuse is an optimization: a rebuild must come up even
            # if a registration prefill fails (compile error on the fresh
            # batcher, OOM, …). The batcher's register_prefix raises no
            # typed admission errors, so nothing shed-shaped is swallowed.
            except Exception as e:  # noqa: BLE001  # kakveda: allow[typed-errors]
                log.warning(
                    "prefix re-registration failed after engine restart: %s", e
                )

    def _finish_rids(self, rids: List[int]) -> None:
        """Resolve completed requests' Futures (telemetry rides along) —
        THE completion path, shared by the serve loop and deadline sweep."""
        for rid in rids:
            self._bump("completed")
            fut = self._pend.pop(rid, None)
            toks = self.cb.results.pop(rid, [])
            tl = self._finish_telemetry(rid, len(toks))
            if fut is not None:
                if tl is not None:
                    fut.timeline = tl  # read back by LlamaRuntime.generate
                if not fut.done():
                    try:
                        fut.set_result(toks)
                    except Exception:  # noqa: BLE001 — close() won the race
                        pass

    def _expire_item(self, fut: Future, tokens: List[int], where: str) -> None:
        """Fail one request's Future with the typed deadline error (outcome
        counter + flight-recorder event ride along). Loop-thread only."""
        self._m_requests.labels(engine=self.name, outcome="deadline").inc()
        if self.recorder is not None:
            self.recorder.record("deadline", tokens=len(tokens), where=where)
        self._fail(
            fut,
            DeadlineExceededError(
                f"deadline exceeded {where} ({len(tokens)} tokens decoded)",
                tokens,
            ),
        )

    def _expire_deadlines(self) -> None:
        """Retire every request whose deadline passed. Admitted requests go
        through ``ContinuousBatcher.cancel_request`` — the done-flag-first
        retirement path, so a stale pipelined (plain OR verify) snapshot
        skips the freed slot as overshoot; requests still waiting for a
        slot fail without occupying one. Loop-thread only."""
        now = time.perf_counter()
        for rid, tr in list(self._track.items()):
            dl = tr.get("deadline")
            if dl is None or now < dl:
                continue
            toks = self.cb.cancel_request(rid)
            if toks is None:
                if rid in self.cb.results:
                    # Finished between chunks before the sweep saw it:
                    # deliver the completed result, not a deadline error.
                    self._finish_rids([rid])
                continue
            fut = self._pend.pop(rid, None)
            self._track.pop(rid, None)
            if fut is not None:
                self._expire_item(fut, toks, "mid-decode")
        with self._submit_lock:
            still = []
            for item in self._waiting:
                dl = item[5]
                if dl is not None and now >= dl:
                    self._expire_item(item[-1], [], "while queued")
                else:
                    still.append(item)
            self._waiting[:] = still

    def close(self) -> None:
        with self._submit_lock:
            self._closed.set()
        self._thread.join(timeout=5.0)
        # Callers must not hang on a dead loop. Idempotent with the
        # loop's own exit cleanup — this call covers a loop thread stuck
        # past the join inside a long chunk compile; the loop's finally
        # covers items it moved after this drain.
        self._fail_all(RuntimeError("ServingEngine closed"))

    def _admit_one(self, item) -> None:
        if item[0] == "cancel":
            _, fut, _ = item
            rid = next((r for r, f in self._pend.items() if f is fut), None)
            if rid is None:
                return  # already finished (or was never admitted)
            toks = self.cb.cancel_request(rid)
            self._pend.pop(rid, None)
            self._track.pop(rid, None)
            self._m_requests.labels(engine=self.name, outcome="cancelled").inc()
            if self.recorder is not None:
                self.recorder.record("cancel", request_id=rid, tokens=len(toks or []))
            if toks is None:
                toks = self.cb.results.pop(rid, [])  # finished between chunks
            if not fut.done():
                try:
                    fut.set_result(toks)
                except Exception:  # noqa: BLE001 — lost the race with completion
                    pass
            return
        if item[0] == "prefix":
            _, ids, fut = item
            if not fut.set_running_or_notify_cancel():
                return
            try:
                ok = self.cb.register_prefix(ids)
                if ok:
                    # Remember accepted prefixes so a supervisor rebuild
                    # re-registers them on the fresh batcher.
                    key = tuple(int(t) for t in ids)
                    if key not in self._prefix_ids:
                        self._prefix_ids.append(key)
                fut.set_result(ok)
            except Exception as e:  # noqa: BLE001 — registration errors belong to the caller
                self._fail(fut, e)
            return
        ids, max_new, temp, on_tokens, t_submit, deadline, fut = item
        if deadline is not None and time.perf_counter() >= deadline:
            self._expire_item(fut, [], "expired before admission")
            return
        if not fut.set_running_or_notify_cancel():
            return
        t_admit = time.perf_counter()
        self._mx["queue_wait"].observe(t_admit - t_submit)
        # Feed the admission controller's live queue-wait history — the
        # input deadline-aware shedding reads (submit rejects a deadline
        # the observed waits say cannot be met).
        _admission.get_admission().note_wait("interactive", t_admit - t_submit)
        # Lifecycle tracking rides the slot's own streaming callback: the
        # wrapper sees each chunk's accepted tokens on the loop thread
        # (TTFT + token counts with no extra bookkeeping in the batcher),
        # then forwards to the caller's callback if any.
        track = {
            "submit": t_submit, "admit": t_admit, "first": None, "tokens": 0,
            "deadline": deadline,
            "traceparent": getattr(fut, "traceparent", None),
        }
        mx_ttft, mx_first = self._mx["ttft"], self._mx["first_chunk"]

        def _on_tokens(new, done, _orig=on_tokens, _tr=track):
            if _tr["first"] is None and new:
                _tr["first"] = first = time.perf_counter()
                mx_ttft.observe(first - _tr["submit"])
                # ttft = queue_wait + prefill + first_chunk. "prefill_s" is
                # absent while cb.admit itself is still running: tokens it
                # delivered waited for no chunk.
                prefill_s = _tr.get("prefill_s")
                _tr["first_chunk_s"] = (
                    0.0 if prefill_s is None
                    else max(0.0, first - _tr["admit"] - prefill_s)
                )
                mx_first.observe(_tr["first_chunk_s"])
            _tr["tokens"] += len(new)
            if _orig is not None:
                _orig(new, done)

        try:
            with annotate("serve.admit"):
                rid = self.cb.admit(
                    ids, max_new_tokens=max_new, temperature=temp, on_tokens=_on_tokens
                )
        except Exception as e:  # noqa: BLE001 — admission errors belong to the caller
            self._m_requests.labels(engine=self.name, outcome="rejected").inc()
            self._fail(fut, e)
            return
        track["prefill_s"] = time.perf_counter() - t_admit
        self._mx["prefill"].observe(track["prefill_s"])
        self._track[rid] = track
        self._pend[rid] = fut

    def _loop(self) -> None:
        """Supervise the serve loop: on a crash, fail the in-flight futures
        with a typed RETRYABLE error, rebuild the batcher (cache slabs
        re-zeroed, prefixes re-registered, gate/k state reset), and restart
        under a bounded exponential-backoff budget (KAKVEDA_SERVE_RESTARTS).
        Past the budget the engine is terminally dead: everything pending
        fails with EngineDeadError and submit() fails fast from then on.
        Queued / waiting-for-a-slot requests survive a restart — the rebuilt
        loop re-admits them."""
        backoff = 0.1
        while True:
            try:
                # Ledger attribution: compiles/uploads from the loop thread
                # land on the serve entry / decode phase (module-level jits
                # self-label with their fn names when created post-install).
                with _ledger.entry("serve.loop"), _ledger.phase("decode"):
                    self._serve()
                break  # clean close() exit
            except BaseException as e:  # noqa: BLE001 — a dead loop must not strand callers
                # A device/runtime error escaping a chunk would otherwise
                # kill this thread silently: every pending Future would
                # hang forever. The flight recorder dumps here — the "why"
                # of a stochastic 500 is one log line / one /flightrecorder
                # fetch, not log archaeology.
                self._mx["errors"].inc()
                # Real backend-error detection: a loop death whose cause
                # looks like the chip going away (vs a software bug or an
                # injected engine.* fault) latches device-loss DEGRADED
                # mode — generation fails fast from then on and the probe
                # owns recovery (core/admission.py).
                _admission.get_device_health().note_failure(e, where="engine.loop")
                if self.recorder is not None:
                    self.recorder.record(
                        "engine_error", error=f"{type(e).__name__}: {e}"
                    )
                    try:
                        log.error(
                            "serving engine %s loop died (%s: %s); flight recorder dump: %s",
                            self.name, type(e).__name__, e, self.recorder.dump_json(),
                        )
                    except Exception:  # noqa: BLE001 — telemetry must not mask the death
                        pass
                if self._closed.is_set():
                    # Crash racing close(): plain shutdown semantics.
                    self._fail_all(RuntimeError(
                        f"ServingEngine closed (loop died during shutdown: {e})"
                    ))
                    return
                if self._restarts >= self._restart_budget:
                    self._die(e)
                    return
                self._restarts += 1
                self._mx["restarts"].inc()
                self._fail_inflight(EngineRetryableError(
                    f"ServingEngine loop died mid-decode "
                    f"({type(e).__name__}: {e}); restarting — safe to resubmit"
                ))
                try:
                    self._rebuild()
                except BaseException as rebuild_err:  # noqa: BLE001
                    log.error(
                        "serving engine %s rebuild failed: %s", self.name, rebuild_err
                    )
                    self._die(rebuild_err)
                    return
                if self.recorder is not None:
                    self.recorder.record(
                        "engine_restart", attempt=self._restarts,
                        budget=self._restart_budget, backoff_s=round(backoff, 3),
                    )
                log.warning(
                    "serving engine %s restarted (%d/%d) after %s: %s; "
                    "re-admitting queued requests",
                    self.name, self._restarts, self._restart_budget,
                    type(e).__name__, e,
                )
                if self._closed.wait(backoff):
                    break  # closed during backoff: fall through to the drain
                backoff = min(backoff * 2.0, 5.0)
        # Normal shutdown: anything still queued/waiting/mid-decode at this
        # point — including items this thread moved AFTER close()'s own
        # drain — must fail rather than hang its caller.
        self._fail_all(RuntimeError("ServingEngine closed"))

    def _die(self, cause: BaseException) -> None:
        """Terminal death: latch ``_dead`` (submit/register_prefix fail
        fast with EngineDeadError) and fail everything pending."""
        with self._submit_lock:
            self._dead.set()
            self._closed.set()
        self._fail_all(EngineDeadError(
            f"ServingEngine loop died terminally after {self._restarts} "
            f"restart(s): {type(cause).__name__}: {cause}"
        ))

    def _serve(self) -> None:
        # Chunk pipelining (KAKVEDA_SERVE_PIPELINE=0 opts out): dispatch
        # chunk i+1 BEFORE fetching chunk i's tokens, so each token fetch
        # and the host work around it overlap the next chunk's device
        # work — per-chunk cost drops from compute+host to max(compute,
        # host). Outputs are token-identical (see step_async); the cost is
        # retirement lag: a finished slot frees one chunk later, and one
        # overshoot chunk runs at the end of each busy period.
        #
        # Speculative verify chunks pipeline the SAME way since the chunk
        # program threads its post-acceptance slot_pos on device
        # (step_spec_async): chunk i's host draft/accept work overlaps
        # chunk i+1's device time, drafting from each slot's copy cursor.
        # The one ordering rule is that admission needs host-authoritative
        # slot state, so the in-flight verify handle drains before the
        # pump may admit.
        pipelined = os.environ.get("KAKVEDA_SERVE_PIPELINE", "1") != "0"
        pending_handle = None  # plain chunk in flight
        pending_spec = None  # speculative verify chunk in flight

        def pump_queue(block: bool) -> None:
            # Control items (cancel, prefix registration) act immediately —
            # a cancel matters MOST when the pool is full, so they must
            # not wait behind the capacity gate. Generation requests wait
            # in _waiting until a slot frees. _waiting handoff happens
            # under the submit lock (close() drains the same list from
            # its thread); admission itself runs unlocked — it can hide a
            # prefill compile and must not block submitters that long.
            nonlocal pending_spec
            # An empty pool blocks here for the next arrival: that wait is
            # "serve.wait", which never counts as a stall; the non-blocking
            # drain of the queue is "serve.pump". Admissions below are
            # phases of their own ("serve.admit").
            with annotate("serve.wait" if block else "serve.pump"):
                try:
                    while True:
                        item = self._q.get(timeout=0.1) if block else self._q.get_nowait()
                        block = False
                        if item[0] in ("cancel", "prefix"):
                            self._admit_one(item)
                        else:
                            with self._submit_lock:
                                self._waiting.append(item)
                except queue.Empty:
                    pass
            while self.cb.has_capacity:
                with self._submit_lock:
                    if not self._waiting:
                        break
                    item = self._pick_waiting_locked()
                if pending_spec is not None:
                    drain_spec()
                self._admit_one(item)

        def dispatch(step):
            with annotate("serve.chunk.dispatch"):
                return step()

        def process(handle, process_chunk) -> None:
            # The wait for the device is a phase of its own: a chunk lasts
            # chunk_steps x the model's step whatever the host does, so it
            # never counts as a host stall. What follows is host work:
            # the copy, accept, callbacks, the finished requests' futures.
            if handle is not None:
                with annotate("serve.chunk.fetch"):
                    handle[0].block_until_ready()
                with annotate("serve.chunk.process"):
                    self._finish_rids(process_chunk(handle))

        def process_plain(handle) -> None:
            process(handle, self.cb.process_chunk)

        def drain_spec() -> None:
            nonlocal pending_spec
            process(pending_spec, self.cb.process_spec_chunk)
            pending_spec = None

        t_cycle = None
        while not self._closed.is_set():
            now = time.perf_counter()
            if t_cycle is not None:
                observe_phase("serve.cycle", now - t_cycle)
            t_cycle = now
            # Idle: block briefly for the next arrival (bounded so
            # close() is prompt) instead of spinning on an empty pool.
            pump_queue(
                block=not self.cb.slots
                and pending_handle is None
                and pending_spec is None
                and not self._waiting
            )
            # Deadline sweep between chunks: expired requests retire via
            # the cancel_request done-flag path (safe while a pipelined
            # plain or verify handle is still in flight).
            with annotate("serve.expire"):
                self._expire_deadlines()
            if self.cb.spec_ready():
                # Flavor switch plain→spec: drain the plain handle so
                # the verify dispatch sees authoritative positions.
                process_plain(pending_handle)
                pending_handle = None
                if self.cb.slots:
                    self._note_active()
                    if (
                        pipelined
                        and pending_spec is not None
                        and self.cb.spec_pipeline_ready()
                    ):
                        # Full-accept regime: dispatch verify chunk
                        # i+1 (cursor drafts), THEN fetch chunk i —
                        # the draft/accept host work and the fetch
                        # RTT ride under the device's verify time.
                        nxt = dispatch(self.cb.step_spec_async)
                        drain_spec()
                        pending_spec = nxt
                        self._bump("chunks")
                    else:
                        # Acceptance-preserving sync order: fetch and
                        # re-anchor on real history before drafting.
                        if pending_spec is not None:
                            drain_spec()
                        if self.cb.slots and self.cb.spec_ready():
                            pending_spec = dispatch(self.cb.step_spec_async)
                            if not pipelined:
                                drain_spec()
                            self._bump("chunks")
                elif pending_spec is not None:
                    drain_spec()
            elif self.cb.slots:
                # Flavor switch spec→plain (gate closed, or a sampled
                # request joined): drain the verify handle first.
                if pending_spec is not None:
                    drain_spec()
                if not self.cb.slots:
                    continue  # the drain retired the whole pool
                self._note_active()
                handle = dispatch(self.cb.step_async)
                self._bump("chunks")
                if not pipelined:
                    process_plain(handle)
                else:
                    process_plain(pending_handle)
                    pending_handle = handle
            else:
                process_plain(pending_handle)
                pending_handle = None
                if pending_spec is not None:
                    drain_spec()
