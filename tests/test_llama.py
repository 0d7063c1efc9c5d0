"""Llama model tests: shapes, ring-attention equivalence, cached decode
consistency, training convergence, sharded train step on dp×cp×tp mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kakveda_tpu.models.llama import (
    LlamaConfig,
    _repeat_kv,
    causal_attention,
    decode_step,
    forward,
    init_cache,
    init_params,
    param_specs,
)
from kakveda_tpu.models.tokenizer import ByteTokenizer
from kakveda_tpu.parallel.mesh import create_mesh

CFG = LlamaConfig(
    vocab_size=264,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq_len=128,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def test_forward_shapes(params):
    tokens = jnp.ones((2, 16), jnp.int32)
    logits = forward(params, CFG, tokens)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_causality(params):
    """Changing a future token must not change past logits."""
    rng = np.random.default_rng(0)
    t1 = rng.integers(3, 259, size=(1, 16)).astype(np.int32)
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] - 3 + 1) % 256 + 3
    l1 = forward(params, CFG, jnp.asarray(t1))
    l2 = forward(params, CFG, jnp.asarray(t2))
    np.testing.assert_allclose(np.asarray(l1[0, :-1]), np.asarray(l2[0, :-1]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


def test_ring_attention_matches_dense(params):
    """Ring attention over a cp>1 mesh must reproduce single-device attention."""
    mesh = create_mesh("dp:1,cp:4,tp:2")
    tokens = jnp.asarray(np.random.default_rng(1).integers(3, 259, size=(2, 32)), jnp.int32)
    dense = forward(params, CFG, tokens)
    ring = forward(params, CFG, tokens, mesh=mesh, cp_axis="cp")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-3, rtol=1e-3)


def test_ring_attention_softcap_and_alt_window(params):
    """Gemma-2-style attn softcapping + alternating per-layer windows must
    survive the ring (cp) path identically to the dense path — the softcap
    is applied inside every ring sub-block before masking."""
    import dataclasses

    cfg2 = dataclasses.replace(CFG, attn_softcap=5.0, sliding_window=8, alt_window=True)
    mesh = create_mesh("dp:1,cp:4,tp:2")
    tokens = jnp.asarray(np.random.default_rng(3).integers(3, 259, size=(2, 32)), jnp.int32)
    dense = forward(params, cfg2, tokens)
    # the deltas must actually change the logits vs the plain config
    assert np.abs(np.asarray(dense) - np.asarray(forward(params, CFG, tokens))).max() > 1e-3
    ring = forward(params, cfg2, tokens, mesh=mesh, cp_axis="cp")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-3, rtol=1e-3)


def test_decode_matches_forward(params):
    """Prefill+incremental decode logits must match the full forward pass."""
    rng = np.random.default_rng(2)
    ids = rng.integers(3, 259, size=(1, 12)).astype(np.int32)
    full = np.asarray(forward(params, CFG, jnp.asarray(ids)))

    cache = init_cache(CFG, batch=1, max_len=32)
    # prefill first 8, then 4 single-token steps
    l1, cache = decode_step(params, CFG, jnp.asarray(ids[:, :8]), cache)
    got = [np.asarray(l1)]
    for i in range(8, 12):
        li, cache = decode_step(params, CFG, jnp.asarray(ids[:, i : i + 1]), cache)
        got.append(np.asarray(li))
    got = np.concatenate(got, axis=1)
    np.testing.assert_allclose(got, full, atol=1e-3, rtol=1e-3)


def test_generate_deterministic():
    from kakveda_tpu.models.generate import LlamaRuntime

    rt = LlamaRuntime(cfg=CFG, seed=0)
    r1 = rt.generate("hello", max_tokens=8)
    r2 = rt.generate("hello", max_tokens=8)
    assert r1.text == r2.text
    assert r1.meta["provider"] == "tpu"
    assert r1.meta["tokens_generated"] <= 8


def test_train_step_reduces_loss():
    from kakveda_tpu.models.train import make_train_step

    cfg = CFG
    params = init_params(jax.random.PRNGKey(1), cfg)
    step, opt = make_train_step(cfg)
    opt_state = opt.init(params)
    tokens = jnp.asarray(
        np.tile(np.arange(3, 19, dtype=np.int32), (4, 1))  # a memorizable sequence
    )
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


def test_sharded_train_step_dp_cp_tp():
    """Full training step jitted over a 2×2×2 mesh: tp-sharded params,
    dp×cp-sharded batch, ring attention across cp."""
    from kakveda_tpu.models.train import make_sharded_train_step

    mesh = create_mesh("dp:2,cp:2,tp:2")
    step, init_state = make_sharded_train_step(CFG, mesh)
    params, opt_state = init_state(jax.random.PRNGKey(0))

    # param sharding actually applied
    wq = params["layers"][0]["wq"]
    assert wq.sharding.spec == param_specs(CFG)["layers"][0]["wq"]

    tokens = jnp.asarray(np.random.default_rng(3).integers(3, 259, size=(4, 32)), jnp.int32)
    params, opt_state, loss = step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
    params, opt_state, loss2 = step(params, opt_state, tokens)
    assert float(loss2) < float(loss)


def test_sharded_loss_matches_unsharded():
    """The dp×cp×tp-sharded loss must equal the single-device loss."""
    from kakveda_tpu.models.train import lm_loss, make_sharded_train_step

    mesh = create_mesh("dp:2,cp:2,tp:2")
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jnp.asarray(np.random.default_rng(4).integers(3, 259, size=(4, 32)), jnp.int32)
    base = float(lm_loss(params, CFG, tokens))

    from kakveda_tpu.models.train import shard_params

    sp = shard_params(params, CFG, mesh)
    sharded = float(lm_loss(sp, CFG, tokens, mesh, "cp"))
    assert abs(base - sharded) / abs(base) < 1e-3


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    s = "Héllo, wörld! 失敗 🙂"
    ids = tok.encode(s, bos=True, eos=True)
    assert ids[0] == tok.BOS and ids[-1] == tok.EOS
    assert tok.decode(ids) == s
    assert max(ids) < tok.vocab_size


def test_generate_top_p_sampling():
    import jax

    from kakveda_tpu.models.generate import generate_tokens
    from kakveda_tpu.models.llama import init_params

    params = init_params(jax.random.PRNGKey(0), CFG)
    ids = generate_tokens(
        params, CFG, [5, 6, 7], max_new_tokens=8, temperature=0.8, top_p=0.9,
        rng=jax.random.PRNGKey(1),
    )
    assert 0 < len(ids) <= 8
    assert all(0 <= t < CFG.vocab_size for t in ids)
    # top_p=tiny keeps only the argmax nucleus → matches greedy
    greedy = generate_tokens(params, CFG, [5, 6, 7], max_new_tokens=8, temperature=0.0)
    nucleus = generate_tokens(
        params, CFG, [5, 6, 7], max_new_tokens=8, temperature=0.5, top_p=1e-6,
        rng=jax.random.PRNGKey(2),
    )
    assert nucleus == greedy


def test_fit_and_checkpoint_roundtrip(tmp_path):
    from kakveda_tpu.models.generate import LlamaRuntime
    from kakveda_tpu.models.train import fit

    ckpt = str(tmp_path / "ckpt")
    params, losses = fit(
        CFG, "the platform remembers failures. " * 40,
        steps=12, batch=2, seq_len=64, checkpoint_path=ckpt, log_every=0,
        log_fn=lambda s: None,
    )
    assert losses[-1] < losses[0]

    rt = LlamaRuntime(cfg=CFG, params=params)
    expected = rt.generate("the platform", max_tokens=8).text
    fresh = LlamaRuntime(cfg=CFG, seed=999)  # different init...
    fresh.load_checkpoint(ckpt)              # ...restored from disk
    assert fresh.generate("the platform", max_tokens=8).text == expected


def test_batched_generation_matches_single():
    """Left-padded batching with position offsets + KV masks is exact: each
    sequence's greedy output equals its solo generate_tokens output."""
    import jax

    from kakveda_tpu.models.generate import generate_tokens, generate_tokens_batch
    from kakveda_tpu.models.llama import init_params

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13, 14, 15, 16], [42]]
    solo = [
        generate_tokens(params, CFG, p, max_new_tokens=8, max_len=128) for p in prompts
    ]
    batched = generate_tokens_batch(params, CFG, prompts, max_new_tokens=8)
    assert batched == solo


def test_fused_generation_matches_step_loop():
    """The one-compiled-program decode (lax.scan over decode_step) must emit
    exactly what the per-step loop emits under greedy sampling."""
    import jax

    from kakveda_tpu.models.generate import generate_tokens_batch, generate_tokens_fused
    from kakveda_tpu.models.llama import init_params

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13, 14, 15, 16], [42]]
    stepped = generate_tokens_batch(params, CFG, prompts, max_new_tokens=8)
    fused = generate_tokens_fused(params, CFG, prompts, max_new_tokens=8)
    assert fused == stepped

    # EOS truncation: force an eos_id that the greedy path emits and check
    # the fused output stops there like the step loop does.
    eos = stepped[0][2] if len(stepped[0]) > 2 else None
    if eos is not None:
        f = generate_tokens_fused(params, CFG, prompts, max_new_tokens=8, eos_id=eos)
        s = generate_tokens_batch(params, CFG, prompts, max_new_tokens=8, eos_id=eos)
        assert f == s


def test_runtime_generate_batch():
    from kakveda_tpu.models.generate import LlamaRuntime

    rt = LlamaRuntime(cfg=CFG, seed=0)
    solo = [rt.generate(p, max_tokens=6).text for p in ("hello", "a longer prompt here")]
    batch = rt.generate_batch(["hello", "a longer prompt here"], max_tokens=6)
    assert [r.text for r in batch] == solo
    assert batch[0].meta["batched"] == 2


def test_decode_session_chunked_parity():
    """Chunked decode (DecodeSession) must emit exactly the fused whole-
    generation tokens — greedy, across uneven chunk boundaries — and honor
    the cache window."""
    import numpy as np

    from kakveda_tpu.models.generate import DecodeSession, generate_tokens_fused
    from kakveda_tpu.models.llama import init_params

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13, 14, 15, 16], [42]]
    fused = generate_tokens_fused(params, CFG, prompts, max_new_tokens=12)

    sess = DecodeSession(params, CFG, prompts, chunk_steps=5, max_len=64)
    chunks = []
    while (c := sess.step_chunk()) is not None and sum(x.shape[1] for x in chunks) < 12:
        chunks.append(c)
    toks = np.concatenate(chunks, axis=1)[:, :12]
    for i in range(len(prompts)):
        assert toks[i].tolist() == fused[i][:12]

    # Window exhaustion: session stops at max_len-1 total positions.
    small = DecodeSession(params, CFG, [[5, 6, 7]], chunk_steps=64, max_len=16)
    out = small.step_chunk()
    assert out is not None and out.shape[1] == 16 - 1 - 3
    assert small.step_chunk() is None


def test_tp_sharded_generation_matches_single():
    """Fused generation with Megatron-TP-sharded params on a tp:2 mesh must
    emit exactly the single-device greedy tokens (XLA inserts the tp
    collectives from the param shardings; batch stays replicated)."""
    from kakveda_tpu.models.generate import generate_tokens_fused
    from kakveda_tpu.models.hf_convert import shard_params
    from kakveda_tpu.models.llama import init_params

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[5, 6, 7], [10, 11, 12, 13]]
    single = generate_tokens_fused(params, CFG, prompts, max_new_tokens=8)

    mesh = create_mesh("dp:1,tp:2")
    sharded = shard_params(params, CFG, mesh)
    wq = sharded["layers"][0]["wq"]
    assert wq.sharding.spec == param_specs(CFG)["layers"][0]["wq"]
    tp_out = generate_tokens_fused(sharded, CFG, prompts, max_new_tokens=8)
    assert tp_out == single


def test_ring_attention_key_blocking_matches_dense():
    """Sub-blocked ring hops (key_block < S_local) must still reproduce
    dense attention — the second-level online-softmax accumulation is
    exact, not approximate."""
    from functools import partial

    from kakveda_tpu.models.llama import ring_attention_local

    mesh = create_mesh("dp:1,cp:4,tp:1")
    rng = np.random.default_rng(7)
    b, s, h, kvh, d = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)

    from jax.sharding import PartitionSpec as P

    def run(key_block):
        spec = P("dp", "cp", None, None)
        return jax.shard_map(
            partial(ring_attention_local, axis_name="cp", n_chunks=4, key_block=key_block),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )(q, k, v)

    dense = np.asarray(causal_attention(q, _repeat_kv(k, 2), _repeat_kv(v, 2)))
    blocked = np.asarray(run(key_block=4))  # S_local=8 → 2 sub-blocks/hop
    unblocked = np.asarray(run(key_block=2048))
    np.testing.assert_allclose(blocked, dense, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(blocked, unblocked, atol=1e-6)
