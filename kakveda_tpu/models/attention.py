"""Fused attention kernels for the Llama runtime.

The reference never runs a model forward itself (it HTTP-calls Ollama;
reference: services/dashboard/app.py:1182-1258) — this module is the
TPU-native replacement's hot path. Two tiers over one contract:

``gqa_cache_attention(q, k, v, pos0, kv_valid)``
    q            [B, S, H, D]    queries (prefill chunk or decode step)
    k, v         [B, KV, L, D]   KV cache, head-major so each head's rows
                                 are contiguous for DMA streaming
    pos0         scalar int32    cache slot of q[:, 0] (cache["pos"])
    kv_valid     [B, L] bool     optional per-slot validity (left-pad batching)
    -> [B, S, H, D]

* **XLA path** (`_gqa_xla`): grouped einsum that keeps the GQA group axis
  explicit — K/V are *never* repeated to H heads, so the cache is read once
  per step instead of ``n_rep`` (=8 for Llama-3/TinyLlama) times. At 1B
  scale, repeat-materialization was ~1.5 GB of HBM traffic per decode step
  — more than the weights.
* **Pallas flash path** (`flash_gqa_cache`): blockwise online-softmax
  attention (flash attention) — scores live only in VMEM tiles, never a
  ``[B, H, S, L]`` f32 HBM tensor. GQA-native: the group's ``R`` query
  heads are folded into the q-row axis so each (batch, kv-head) program is
  one ``[S·R, D] @ [D, L_blk]`` MXU matmul per cache tile. Dispatched for
  long-context inference shapes (see `_flash_wins`) where the XLA path's
  transient score scratch gets into the gigabytes; at short serving shapes
  the batched einsum is faster because the Pallas grid serializes over
  B·KV small programs. Training always uses the XLA path (it
  differentiates).

Both paths produce identical logits (tested to ~1e-5 in f32; see
tests/test_attention.py). One documented don't-care divergence: a query row
with NO visible slot (a left-pad position earlier than every valid cache
slot) softmaxes to a uniform average in the XLA paths but emits zeros from
the flash kernel; such rows are pad positions whose activations can't reach
any real token's logits (their K/V slots are themselves masked).
"""

from __future__ import annotations

import functools
import logging
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

log = logging.getLogger("kakveda.attention")

# Which path each traced attention call took, keyed by path + shapes. The
# choice below is made in Python while jit traces, so this fills once per
# compiled program (one count per layer), not per step; /readyz serves it
# so an operator can see that a prefill really went through the kernel.
_TRACED_PATHS: dict = {}
_TRACED_LOCK = threading.Lock()


def _note_path(path: str, q, k) -> None:
    key = f"{path} q{list(q.shape)} cache{list(k.shape)}:{k.dtype.name}"
    with _TRACED_LOCK:
        first = key not in _TRACED_PATHS
        _TRACED_PATHS[key] = _TRACED_PATHS.get(key, 0) + 1
    if first:
        log.info("attention path: %s", key)


def traced_paths() -> dict:
    """{"<path> q[B,S,H,D] cache[B,KV,L,D]:<dtype>": layers traced}."""
    with _TRACED_LOCK:
        return dict(_TRACED_PATHS)


# ---------------------------------------------------------------------------
# XLA grouped path (differentiable; CPU + fallback)
# ---------------------------------------------------------------------------


def _gqa_xla(q, k, v, pos0, kv_valid, window: int = 0, softcap: float = 0.0, full_mask=None):
    b, s, h, d = q.shape
    _, kv, l, _ = k.shape
    r = h // kv
    scale = d**-0.5
    # [B,S,H,D] -> [B,KV,S,R,D]; group axis stays explicit so XLA batches
    # the matmul over KV instead of materializing repeated K/V.
    q5 = q.reshape(b, s, kv, r, d).transpose(0, 2, 1, 3, 4)
    scores = jnp.einsum("bgsrd,bgld->bgsrl", q5, k).astype(jnp.float32) * scale
    if softcap:
        # Gemma-2 attention-logit softcapping: cap·tanh(s/cap), pre-mask.
        scores = softcap * jnp.tanh(scores / softcap)
    if full_mask is not None:
        # Caller-computed [B, S, L] mask (per-slot query positions — the
        # speculative serving chunk); replaces causal/window/kv_valid.
        scores = jnp.where(full_mask[:, None, :, None, :], scores, _NEG_INF)
    else:
        q_pos = pos0 + jnp.arange(s)
        l_pos = jnp.arange(l)
        mask = q_pos[:, None] >= l_pos[None, :]  # [S, L]
        if window:
            # Sliding-window attention (Mistral): keep iff q_pos − l_pos < window.
            mask &= (q_pos[:, None] - l_pos[None, :]) < window
        if kv_valid is not None:
            full = mask[None, :, :] & kv_valid[:, None, :]  # [B, S, L]
            scores = jnp.where(full[:, None, :, None, :], scores, _NEG_INF)
        else:
            scores = jnp.where(mask[None, None, :, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgsrl,bgld->bgsrd", probs, v)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernel
# ---------------------------------------------------------------------------


def _flash_body(
    pos0_ref,
    q,  # [q_blk, D]
    k,  # [l_blk, D] compute dtype (int8 tiles: cast, scales passed apart)
    v,  # [l_blk, D]
    valid_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    r: int,
    q_blk: int,
    l_blk: int,
    n_l: int,
    scale: float,
    window: int,
    k_scale=None,  # [1, l_blk] f32 per-row scales of an int8 K tile
    v_scale=None,  # [1, l_blk] f32 per-row scales of an int8 V tile
):
    lb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(lb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # [q_blk, l_blk] scores on the MXU, f32 accumulation.
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if k_scale is not None:
        # q·(k8·ks) == (q·k8)·ks: the per-row K scale lands on the score
        # COLUMNS, so it broadcasts along lanes as stored.
        s = s * k_scale

    # Causal + validity mask. Query rows fold (seq, group-head): row i is
    # sequence position (qb*q_blk + i) // r.
    rows = jax.lax.broadcasted_iota(jnp.int32, (q_blk, l_blk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q_blk, l_blk), 1)
    q_pos = pos0_ref[0, 0] + (qb * q_blk + rows) // r
    l_pos = lb * l_blk + cols
    keep = (q_pos >= l_pos) & (valid_ref[0, 0][None, :] > 0.5)
    if window:
        keep &= (q_pos - l_pos) < window
    s = jnp.where(keep, s, _NEG_INF)

    m_prev = m_scr[:, :1]  # [q_blk, 1] (all lanes equal; col 0 is truth)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # Re-mask after exp: on an all-masked tile, s - m_new == 0 would exp to 1.
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)  # [q_blk, 1]
    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    # p·(v8·vs) == (p·vs)·v8: same move for V, on the probability columns
    # (the softmax denominator above keeps the unscaled p).
    pw = p if v_scale is None else p * v_scale
    pv = jax.lax.dot_general(
        pw.astype(v.dtype),
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[:] = acc_scr[:] * corr + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(lb == n_l - 1)
    def _emit():
        denom = jnp.maximum(l_scr[:, :1], 1e-20)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def _flash_kernel(
    pos0_ref,  # SMEM [1, 1]
    q_ref,  # VMEM [1, q_blk, D]
    k_ref,  # VMEM [1, l_blk, D]
    v_ref,  # VMEM [1, l_blk, D]
    valid_ref,  # VMEM [1, 1, l_blk] f32
    o_ref,  # VMEM [1, q_blk, D]
    m_scr,  # VMEM [q_blk, 128] f32
    l_scr,  # VMEM [q_blk, 128] f32
    acc_scr,  # VMEM [q_blk, D] f32
    **kw,
):
    _flash_body(
        pos0_ref, q_ref[0], k_ref[0], v_ref[0], valid_ref, o_ref,
        m_scr, l_scr, acc_scr, **kw,
    )


def _flash_kernel_kv8(
    pos0_ref,  # SMEM [1, 1]
    q_ref,  # VMEM [1, q_blk, D]
    k_ref,  # VMEM [1, l_blk, D] int8
    ks_ref,  # VMEM [1, 1, l_blk] f32 per-row scales
    v_ref,  # VMEM [1, l_blk, D] int8
    vs_ref,  # VMEM [1, 1, l_blk] f32
    valid_ref,  # VMEM [1, 1, l_blk] f32
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    **kw,
):
    """int8-KV variant: the cache tiles DMA from HBM as int8 (+1 f32
    scale per head_dim row) — ~½ the bandwidth of bf16 tiles on the
    stream that binds long-context decode. The tiles are cast to the
    compute dtype (exact: |int8| ≤ 127) and the per-row scales are applied
    to the score / probability COLUMNS inside `_flash_body` instead of to
    the K/V rows: the scale blocks arrive lane-major ``[1, l_blk]``, and
    Mosaic has no lane→sublane move to turn them into the ``[l_blk, 1]``
    column a row-wise dequant needs (nor does v5e's VPU multiply bf16).
    Same math as `_kv_dequant` up to rounding — the scale multiplies in
    f32 here, in the compute dtype there — so flash and the XLA path
    agree to compute-dtype tolerance, not bitwise."""
    dt = q_ref.dtype
    _flash_body(
        pos0_ref, q_ref[0], k_ref[0].astype(dt), v_ref[0].astype(dt), valid_ref,
        o_ref, m_scr, l_scr, acc_scr,
        k_scale=ks_ref[0], v_scale=vs_ref[0], **kw,
    )


@functools.partial(jax.jit, static_argnames=("q_blk", "l_blk", "window", "interpret"))
def flash_gqa_cache(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, KV, L, D] (cfg.dtype, or int8 with k_scale)
    v: jax.Array,  # [B, KV, L, D]
    pos0: jax.Array,
    kv_valid: jax.Array | None,
    *,
    k_scale: jax.Array | None = None,  # [B, KV, L] f32 — int8-cache rows
    v_scale: jax.Array | None = None,
    q_blk: int = 512,
    l_blk: int = 512,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    _, kv, l, _ = k.shape
    r = h // kv
    sr = s * r
    # Pad the folded q-row axis to the f32 sublane multiple: decode shapes
    # (s=1, r<8) otherwise can't tile at all. Padded rows compute
    # throwaway attention (their q_pos lands past the real rows; denom is
    # floor-guarded) and are sliced off the output.
    sr_pad = -(-sr // 8) * 8
    q_blk = min(q_blk, sr_pad)
    l_blk = min(l_blk, l)
    if sr_pad % q_blk or l % l_blk:
        raise ValueError(f"flash layout: SR={sr_pad} q_blk={q_blk} L={l} l_blk={l_blk}")
    kv8 = k_scale is not None

    # Fold (seq, group-head) into the q-row axis: [B*KV, S*R, D]. With an
    # int8 cache the q tiles keep their own dtype (casting q to int8 would
    # destroy it); the kernel dequantizes K/V tiles in VMEM.
    qf = (
        q.reshape(b, s, kv, r, d)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b * kv, sr, d)
    )
    if not kv8:
        qf = qf.astype(k.dtype)
    if sr_pad != sr:
        qf = jnp.pad(qf, ((0, 0), (0, sr_pad - sr), (0, 0)))
    kf = k.reshape(b * kv, l, d)
    vf = v.reshape(b * kv, l, d)
    valid = (
        jnp.ones((b, 1, l), jnp.float32)
        if kv_valid is None
        else kv_valid.astype(jnp.float32).reshape(b, 1, l)
    )
    pos = jnp.asarray(pos0, jnp.int32).reshape(1, 1)
    n_q = sr_pad // q_blk
    n_l = l // l_blk

    smem_spec = pl.BlockSpec((1, 1), lambda bg, qb, lb: (0, 0), memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, q_blk, d), lambda bg, qb, lb: (bg, qb, 0), memory_space=pltpu.VMEM)
    l_spec = pl.BlockSpec((1, l_blk, d), lambda bg, qb, lb: (bg, lb, 0), memory_space=pltpu.VMEM)
    sc_spec = pl.BlockSpec((1, 1, l_blk), lambda bg, qb, lb: (bg, 0, lb), memory_space=pltpu.VMEM)
    valid_spec = pl.BlockSpec(
        (1, 1, l_blk), lambda bg, qb, lb, _kv=kv: (bg // _kv, 0, lb), memory_space=pltpu.VMEM
    )
    kw = dict(r=r, q_blk=q_blk, l_blk=l_blk, n_l=n_l, scale=d**-0.5, window=window)
    if kv8:
        kernel = functools.partial(_flash_kernel_kv8, **kw)
        in_specs = [smem_spec, q_spec, l_spec, sc_spec, l_spec, sc_spec, valid_spec]
        operands = (
            pos, qf, kf, k_scale.reshape(b * kv, 1, l),
            vf, v_scale.reshape(b * kv, 1, l), valid,
        )
        kv_bytes = 2 * l * (d + 4)  # int8 values + f32 scales
    else:
        kernel = functools.partial(_flash_kernel, **kw)
        in_specs = [smem_spec, q_spec, l_spec, l_spec, valid_spec]
        operands = (pos, qf, kf, vf, valid)
        kv_bytes = 2 * l * d * k.dtype.itemsize

    out = pl.pallas_call(
        kernel,
        grid=(b * kv, n_q, n_l),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, q_blk, d), lambda bg, qb, lb: (bg, qb, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((b * kv, sr_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 128), jnp.float32),
            pltpu.VMEM((q_blk, 128), jnp.float32),
            pltpu.VMEM((q_blk, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * kv * sr * l * d,
            bytes_accessed=b * kv * (sr * d * q.dtype.itemsize + kv_bytes),
            transcendentals=b * kv * sr * l,
        ),
        interpret=interpret,
        # A stable name for the trace (a multi-row call is an admission's
        # prefill; one row is a decode step over an int8 cache).
        name="flash_prefill" if s > 1 else "flash_decode",
    )(*operands)

    # [B*KV, S*R(+pad), D] -> [B, S, H, D]
    if sr_pad != sr:
        out = out[:, :sr]
    return (
        out.reshape(b, kv, s, r, d).transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)
    ).astype(q.dtype)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def _flash_ok(s: int, h: int, kv: int, l: int, d: int) -> bool:
    """Layout gate: the cache length must tile by the l-block and lanes
    want d % 128 == 0 or d == 64 (Mosaic pads 64-lane tiles acceptably).
    The folded q-row axis (S·R) pads itself to the sublane multiple
    inside flash_gqa_cache, so short decode shapes qualify."""
    return h % kv == 0 and l % 128 == 0 and (d % 128 == 0 or d == 64)


def _flash_wins(s: int, h: int, kv: int, l: int) -> bool:
    """Profitability gate, measured on v5e (see docs/performance.md): the
    Pallas grid serializes over B·KV programs, so at short S·R / short cache
    the batched XLA einsum is faster (its [B,KV,S,R,L] f32 scratch is small
    and transient). Flash wins where that scratch gets big — long-context
    prefill and long caches — and is mandatory where XLA's scratch would
    not fit HBM at all (S and L in the thousands)."""
    r = h // kv
    return (s * r) * l >= 1024 * 2048


def _pick_block(n: int, cap: int, step: int) -> int:
    """Largest divisor of ``n`` that is ≤ cap and a multiple of ``step``."""
    best = step
    c = step
    while c <= min(n, cap):
        if n % c == 0:
            best = c
        c += step
    return best


def gqa_cache_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos0: jax.Array,
    kv_valid: jax.Array | None = None,
    *,
    window: int = 0,
    softcap: float = 0.0,
    k_scale: jax.Array | None = None,  # int8 cache: [B, KV, L] per-row scales
    v_scale: jax.Array | None = None,
    use_flash: bool | None = None,
    full_mask: jax.Array | None = None,  # [B, S, L] per-query mask (spec chunks)
) -> jax.Array:
    """Cached GQA attention — dispatches to the Pallas flash kernel on TPU
    (inference shapes that fit its tiling), XLA grouped einsum otherwise.
    ``window`` > 0 applies sliding-window attention (Mistral) in both paths;
    ``softcap`` > 0 (Gemma-2 logit capping) always takes the XLA path.
    With ``k_scale``/``v_scale`` the cache is int8 (cfg.kv_quant): the
    flash path streams the int8 tiles from HBM and dequantizes in VMEM —
    the bandwidth win, on top of the capacity win — while the XLA path
    dequantizes up front (same math, materialized). ``KAKVEDA_FLASH=0``
    forces the XLA path."""
    b, s, h, d = q.shape
    _, kv, l, _ = k.shape

    def _dequant():
        from kakveda_tpu.models.llama import _kv_dequant

        return _kv_dequant(k, k_scale, q.dtype), _kv_dequant(v, v_scale, q.dtype)

    if full_mask is not None or softcap:
        # full_mask: per-slot query positions (the speculative serving
        # chunk) — inexpressible in the flash kernel's scalar-pos0 causal
        # mask, so these shapes take the XLA path. S ≤ k+1 keeps its
        # scratch tiny. softcap likewise always takes the XLA path.
        _note_path("xla", q, k)
        if k_scale is not None:
            kd, vd = _dequant()
            return _gqa_xla(
                q, kd, vd, pos0, kv_valid, window=window, softcap=softcap, full_mask=full_mask
            )
        return _gqa_xla(
            q, k, v, pos0, kv_valid, window=window, softcap=softcap, full_mask=full_mask
        )
    if use_flash is None:
        from kakveda_tpu.ops.device import is_tpu_backend

        env = os.environ.get("KAKVEDA_FLASH", "auto")
        use_flash = (
            env != "0"
            and is_tpu_backend()
            and _flash_ok(s, h, kv, l, d)
            # int8 caches prefer the kernel wherever the shape tiles: the
            # XLA path must materialize a full bf16 dequant copy of the
            # cache (write + re-read through HBM — MORE traffic than a
            # plain bf16 cache), while the kernel streams int8 and
            # expands in VMEM. For bf16 caches the measured profitability
            # gate applies.
            and (env == "1" or k_scale is not None or _flash_wins(s, h, kv, l))
        )
    if use_flash:
        r = h // kv
        sr = s * r
        _note_path("flash_kv8" if k_scale is not None else "flash", q, k)
        return flash_gqa_cache(
            q, k, v, pos0, kv_valid,
            k_scale=k_scale, v_scale=v_scale,
            q_blk=_pick_block(-(-sr // 8) * 8, 512, 8),
            l_blk=_pick_block(l, 512, 128),
            window=window,
        )
    _note_path("xla", q, k)
    if k_scale is not None:
        kd, vd = _dequant()
        return _gqa_xla(q, kd, vd, pos0, kv_valid, window=window)
    return _gqa_xla(q, k, v, pos0, kv_valid, window=window)
