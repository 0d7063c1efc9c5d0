"""Fused-attention parity: the Pallas flash kernel (interpret mode on CPU)
and the grouped XLA path must both reproduce the plain O(S²) oracle
(`causal_attention`) bit-for-bit up to f32 tolerance, across GQA group
sizes, cache offsets (decode), and left-pad validity masks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kakveda_tpu.models.attention import _gqa_xla, flash_gqa_cache, gqa_cache_attention
from kakveda_tpu.models.llama import _repeat_kv, causal_attention


def _mk(b, s, h, kv, l, d, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv, l, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, l, d)), jnp.float32)
    return q, k, v


def _oracle(q, k, v, pos0, kv_valid):
    """causal_attention over the repeated, seq-major cache + explicit
    validity masking (mirrors the pre-fusion decode_step math)."""
    b, s, h, d = q.shape
    kv = k.shape[1]
    ks = k.transpose(0, 2, 1, 3)  # [B, L, KV, D]
    vs = v.transpose(0, 2, 1, 3)
    kr = _repeat_kv(ks, h // kv)
    vr = _repeat_kv(vs, h // kv)
    l = kr.shape[1]
    scale = d**-0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    q_pos = pos0 + jnp.arange(s)
    mask = q_pos[:, None] >= jnp.arange(l)[None, :]
    if kv_valid is not None:
        full = mask[None, :, :] & kv_valid[:, None, :]
        scores = jnp.where(full[:, None], scores, -1e30)
    else:
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vr)


CASES = [
    # (B, S, H, KV, L, D, pos0, with_valid)   — prefill, decode, MQA, MHA
    (2, 8, 4, 2, 32, 16, 0, False),
    (2, 1, 4, 2, 32, 16, 7, False),     # single-token decode mid-cache
    (1, 4, 8, 1, 16, 8, 3, False),      # MQA (kv=1)
    (2, 8, 4, 4, 32, 16, 0, False),     # MHA (no grouping)
    (2, 8, 4, 2, 32, 16, 0, True),      # left-pad validity mask
    (3, 1, 8, 2, 64, 32, 20, True),     # batched decode with pads
]


@pytest.mark.parametrize("b,s,h,kv,l,d,pos0,with_valid", CASES)
def test_grouped_xla_matches_oracle(b, s, h, kv, l, d, pos0, with_valid):
    q, k, v = _mk(b, s, h, kv, l, d, seed=b + s)
    valid = None
    if with_valid:
        rng = np.random.default_rng(99)
        off = rng.integers(0, 4, size=(b,))
        valid = jnp.asarray(np.arange(l)[None, :] >= off[:, None])
    want = np.asarray(_oracle(q, k, v, pos0, valid))
    got = np.asarray(_gqa_xla(q, k, v, jnp.asarray(pos0), valid))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,s,h,kv,l,d,pos0,with_valid", CASES)
def test_flash_kernel_matches_oracle(b, s, h, kv, l, d, pos0, with_valid):
    q, k, v = _mk(b, s, h, kv, l, d, seed=b * 7 + s)
    valid = None
    if with_valid:
        rng = np.random.default_rng(7)
        off = rng.integers(0, 4, size=(b,))
        valid = jnp.asarray(np.arange(l)[None, :] >= off[:, None])
    want = np.asarray(_oracle(q, k, v, pos0, valid))
    got = np.asarray(
        flash_gqa_cache(
            q, k, v, jnp.asarray(pos0), valid, q_blk=8, l_blk=16, interpret=True
        )
    )
    # Fully-masked query rows (pad positions before any valid slot) are
    # don't-care: softmax gives a uniform average, flash gives zeros.
    if valid is not None:
        q_pos = pos0 + np.arange(s)
        visible = (q_pos[None, :, None] >= np.arange(l)[None, None, :]) & np.asarray(
            valid
        )[:, None, :]
        live = visible.any(-1)  # [B, S]
        got = got[live]
        want = want[live]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,s,h,kv,l,d,pos0,with_valid", CASES)
def test_flash_kernel_int8_cache_matches_dequant_path(b, s, h, kv, l, d, pos0, with_valid):
    """int8-KV flash: streaming int8 tiles and applying the per-row scales
    to the score / probability columns IN the kernel must equal
    dequantize-then-attend (the XLA fallback's math) to f32 rounding."""
    from kakveda_tpu.models.llama import _kv_dequant, _kv_quant_rows

    q, k, v = _mk(b, s, h, kv, l, d, seed=b * 11 + s)
    k_i8, k_sc = _kv_quant_rows(k)
    v_i8, v_sc = _kv_quant_rows(v)
    valid = None
    if with_valid:
        rng = np.random.default_rng(7)
        off = rng.integers(0, 4, size=(b,))
        valid = jnp.asarray(np.arange(l)[None, :] >= off[:, None])
    want = np.asarray(
        _gqa_xla(
            q, _kv_dequant(k_i8, k_sc, q.dtype), _kv_dequant(v_i8, v_sc, q.dtype),
            jnp.asarray(pos0), valid,
        )
    )
    got = np.asarray(
        flash_gqa_cache(
            q, k_i8, v_i8, jnp.asarray(pos0), valid,
            k_scale=k_sc, v_scale=v_sc, q_blk=8, l_blk=16, interpret=True,
        )
    )
    if valid is not None:
        q_pos = pos0 + np.arange(s)
        visible = (q_pos[None, :, None] >= np.arange(l)[None, None, :]) & np.asarray(
            valid
        )[:, None, :]
        live = visible.any(-1)
        got, want = got[live], want[live]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_flash_int8_bf16_matches_dequant_path():
    """Under bf16 compute the kernel scales in f32 on the score columns
    where `_kv_dequant` scales the rows in bf16 (the row form needs a
    lane→sublane move Mosaic refuses): the two agree to bf16 tolerance."""
    from kakveda_tpu.models.llama import _kv_dequant, _kv_quant_rows

    rng = np.random.default_rng(3)
    b, s, h, kv, l, d = 1, 8, 4, 2, 32, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, kv, l, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, kv, l, d)), jnp.bfloat16)
    k_i8, k_sc = _kv_quant_rows(k)
    v_i8, v_sc = _kv_quant_rows(v)
    want = _gqa_xla(
        q, _kv_dequant(k_i8, k_sc, jnp.bfloat16), _kv_dequant(v_i8, v_sc, jnp.bfloat16),
        jnp.asarray(0), None,
    )
    got = flash_gqa_cache(
        q, k_i8, v_i8, jnp.asarray(0), None,
        k_scale=k_sc, v_scale=v_sc, q_blk=8, l_blk=16, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=1e-2, rtol=1e-2
    )


def test_flash_decode_shape_pads_q_rows():
    """Single-token decode with a small GQA ratio folds to s*r < 8 query
    rows; the kernel pads them to the sublane multiple and slices the
    output — parity with the XLA path on the same int8 cache."""
    from kakveda_tpu.models.llama import _kv_dequant, _kv_quant_rows

    rng = np.random.default_rng(4)
    b, s, h, kv, l, d = 3, 1, 8, 2, 128, 64  # sr = 4 -> pads to 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv, l, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, l, d)), jnp.float32)
    k_i8, k_sc = _kv_quant_rows(k)
    v_i8, v_sc = _kv_quant_rows(v)
    pos0 = 40
    want = np.asarray(
        _gqa_xla(
            q, _kv_dequant(k_i8, k_sc, q.dtype), _kv_dequant(v_i8, v_sc, q.dtype),
            jnp.asarray(pos0), None,
        )
    )
    got = np.asarray(
        flash_gqa_cache(
            q, k_i8, v_i8, jnp.asarray(pos0), None,
            k_scale=k_sc, v_scale=v_sc, q_blk=8, l_blk=128, interpret=True,
        )
    )
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_dispatch_int8_cache_xla_fallback_matches_oracle():
    """gqa_cache_attention with k_scale/v_scale on CPU (XLA path) equals
    the oracle over the dequantized cache."""
    from kakveda_tpu.models.llama import _kv_dequant, _kv_quant_rows

    q, k, v = _mk(2, 4, 4, 2, 32, 16, seed=5)
    k_i8, k_sc = _kv_quant_rows(k)
    v_i8, v_sc = _kv_quant_rows(v)
    want = np.asarray(
        _oracle(q, _kv_dequant(k_i8, k_sc, q.dtype), _kv_dequant(v_i8, v_sc, q.dtype), 3, None)
    )
    got = np.asarray(
        gqa_cache_attention(q, k_i8, v_i8, jnp.asarray(3), None, k_scale=k_sc, v_scale=v_sc)
    )
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_flash_kernel_multiblock_streaming():
    """Cache longer than one l-block: online-softmax accumulation across
    tiles must agree with the oracle, including a fully-masked leading tile
    (pos0 far into the cache) and an empty trailing tile."""
    b, s, h, kv, l, d = 2, 4, 4, 2, 64, 16
    q, k, v = _mk(b, s, h, kv, l, d, seed=5)
    for pos0 in (0, 17, 59):
        want = np.asarray(_oracle(q, k, v, pos0, None))
        got = np.asarray(
            flash_gqa_cache(q, k, v, jnp.asarray(pos0), None, q_blk=8, l_blk=16, interpret=True)
        )
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5, err_msg=f"pos0={pos0}")


def _windowed_oracle(q, k, v, pos0, window):
    """Banded oracle = llama.causal_attention(window=) over the repeated,
    seq-major cache (one oracle for the semantics, shared with llama.py)."""
    h, kv = q.shape[2], k.shape[1]
    kr = _repeat_kv(k.transpose(0, 2, 1, 3), h // kv)
    vr = _repeat_kv(v.transpose(0, 2, 1, 3), h // kv)
    return causal_attention(q, kr, vr, q_off=pos0, window=window)


@pytest.mark.parametrize("pos0,window", [(0, 4), (20, 8), (31, 5)])
def test_sliding_window_xla_and_flash_match_oracle(pos0, window):
    """Mistral-style sliding window in both fused paths vs the banded oracle
    — including a decode position deep enough that the window excludes
    early cache slots."""
    b, s, h, kv, l, d = 2, 8 if pos0 == 0 else 1, 4, 2, 32, 16
    q, k, v = _mk(b, s, h, kv, l, d, seed=pos0 + window)
    want = np.asarray(_windowed_oracle(q, k, v, pos0, window))
    got_xla = np.asarray(_gqa_xla(q, k, v, jnp.asarray(pos0), None, window=window))
    np.testing.assert_allclose(got_xla, want, atol=1e-5, rtol=1e-5)
    got_flash = np.asarray(
        flash_gqa_cache(
            q, k, v, jnp.asarray(pos0), None, q_blk=8, l_blk=16, window=window, interpret=True
        )
    )
    np.testing.assert_allclose(got_flash, want, atol=1e-5, rtol=1e-5)
    # The band must actually bite: full-causal on the same inputs differs.
    full = np.asarray(_gqa_xla(q, k, v, jnp.asarray(pos0), None))
    assert np.abs(full - want).max() > 1e-4


def test_dispatch_uses_xla_on_cpu():
    """On a CPU backend the dispatcher must take the XLA path (flash is
    TPU-only outside interpret mode) and still match the oracle."""
    q, k, v = _mk(2, 4, 4, 2, 32, 16, seed=11)
    got = np.asarray(gqa_cache_attention(q, k, v, jnp.asarray(2), None))
    want = np.asarray(_oracle(q, k, v, 2, None))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_flash_bf16_close_to_f32_oracle():
    """bf16 inputs (the production dtype): flash kernel accumulates in f32,
    so it should sit within bf16 rounding of the f32 oracle."""
    b, s, h, kv, l, d = 2, 8, 8, 2, 32, 64
    q, k, v = _mk(b, s, h, kv, l, d, seed=3)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    want = np.asarray(_oracle(q, k, v, 0, None))
    got = np.asarray(
        flash_gqa_cache(qb, kb, vb, jnp.asarray(0), None, q_blk=16, l_blk=16, interpret=True)
    ).astype(np.float32)
    np.testing.assert_allclose(got, want, atol=0.04, rtol=0.04)
