"""LFM2-MoE on the served path (ISSUE 29): gated short-convolution layers with
their state in the slot pool, a cache per layer type, a sigmoid router with a
selection bias, ragged no-drop dispatch — each against the plain reference
(``benchmarks/families/lfm2_moe_model.py``, which imports nothing of the
program) or a ten-line numpy one, at tiny widths on seeded weights.

Tolerances. The program in float32 computes the reference's mathematics in
another order (fused projections, a cache instead of a full pass): logits
agree to a few 1e-6 here, and ``F32_TOL`` = 1e-4 leaves room for other CPUs'
summation order. In bfloat16 the same comparison reads 0.5-1 at its worst
token (a top-k choice made differently) and 6e-3 in the mean, so computing in
a lower precision than the test states fails the tolerance by a wide margin
(``test_a_lower_precision_fails_the_tolerance``)."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import correct, manifest  # noqa: E402

from kakveda_tpu.models.hf_convert import hf_config_to_llama, load_hf_checkpoint  # noqa: E402
from kakveda_tpu.models.llama import (  # noqa: E402
    LlamaConfig, UnsupportedLayerError, decode_step, forward, init_cache, init_params,
)
from kakveda_tpu.models.moe import moe_mlp, router_topk  # noqa: E402
from kakveda_tpu.models.serving import ContinuousBatcher, ServingEngine, _admit_jit  # noqa: E402

FILE = json.loads((BENCH / "configs" / "judge-lfm2-24b-a2b.json").read_text())
TINY = {**FILE, **FILE["rehearsal"]["model"]}
SEED = 2_500_000_011
LIVE = 259
F32_TOL = 1e-4


@pytest.fixture(scope="module")
def model():
    return manifest.load_module("families", "lfm2_moe_model")


@pytest.fixture(scope="module")
def served(model):
    """(LlamaConfig in float32, the family's seeded weights with calibrated biases)."""
    return hf_config_to_llama(TINY, dtype=jnp.float32), model.make_params(SEED, TINY)


def _prompts(n, lo=9, hi=40, seed=0):
    rng = np.random.default_rng(seed)
    return [[1] + rng.integers(35, 130, int(rng.integers(lo, hi))).tolist() for _ in range(n)]


def _gaps(model, prompts, outs):
    """Every served token's reference-logit gap (harness/correct.served_gaps)."""
    width = max(len(p) + len(o) for p, o in zip(prompts, outs))
    toks = np.zeros((len(prompts), width), np.int32)
    for r, (p, o) in enumerate(zip(prompts, outs)):
        toks[r, :len(p) + len(o)] = p + o
    ref = model.logits(SEED, TINY, toks, LIVE)
    return np.asarray(correct.served_gaps(ref, [len(p) for p in prompts], outs))


# --- the config -----------------------------------------------------------------------


def test_the_catalog_rows_config_maps_as_it_stands():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("the catalog of public architectures is not on this machine")
    row = next(json.loads(ln) for ln in catalog.read_text().splitlines() if '"LFM2-24B-A2B"' in ln)
    cfg = hf_config_to_llama(row["config"])
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.d_ff_dense, cfg.d_ff, cfg.n_experts, cfg.n_experts_per_tok, cfg.n_dense_layers) == (11776, 1536, 64, 4, 2)
    assert (cfg.conv_l_cache, cfg.vocab_size, cfg.rope_theta, cfg.n_layers) == (3, 65536, 1e6, 40)
    assert cfg.router_score == "sigmoid" and cfg.router_bias and cfg.norm_topk_prob and cfg.qk_norm
    assert len(cfg.layers_of("conv")) == 30 and len(cfg.layers_of("full_attention")) == 10
    hash(cfg)  # a static jit argument
    # every published width and count of the row is in the benchmark's file, only the depth cut
    for key, val in row["config"].items():
        if key not in ("num_hidden_layers", "layer_types"):
            assert FILE[key] == val, key
    assert FILE["layer_types"] == row["config"]["layer_types"][:8] and FILE["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("change,match", [
    ({"layer_types": ["conv", "full_attention"]}, "layer_types names 2"),
    ({"layer_types": ["conv", "full_attention", "mamba"]}, "unknown layer type"),
    ({"conv_bias": True}, "conv_bias"),
])
def test_a_config_it_cannot_run_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        hf_config_to_llama({**TINY, **change})


def test_a_checkpoint_of_the_family_is_refused_until_its_tensor_names_are_mapped(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps(TINY))
    with pytest.raises(ValueError, match="tensor names"):
        load_hf_checkpoint(str(tmp_path))


def test_a_cache_per_layer_type(served):
    cfg, _ = served
    cache = init_cache(cfg, batch=3, max_len=32)
    assert len(cache["k"]) == len(cache["v"]) == 1 and len(cache["conv"]) == 2  # conv, full_attention, conv
    assert cache["k"][0].shape == (3, cfg.n_kv_heads, 32, cfg.head_dim)
    assert cache["conv"][0].shape == (3, cfg.conv_l_cache - 1, cfg.d_model)
    q8 = init_cache(LlamaConfig(**{**cfg.__dict__, "kv_quant": "int8"}), batch=1, max_len=8)
    assert q8["k"][0].dtype == jnp.int8 and q8["conv"][0].dtype == cfg.dtype  # int8 KV stays attention's alone
    assert "conv" not in init_cache(LlamaConfig.tiny(), batch=1, max_len=8)  # every layer attention: today's cache


# --- against the plain reference ---------------------------------------------------------


def test_full_forward_against_the_reference(model, served):
    cfg, params = served
    toks = np.random.default_rng(0).integers(3, LIVE, (2, 48)).astype(np.int32)
    ref = np.asarray(model.logits(SEED, TINY, toks, LIVE))
    got = np.asarray(forward(params, cfg, jnp.asarray(toks)))[..., :LIVE]
    assert np.abs(ref - got).max() < F32_TOL


def test_a_lower_precision_fails_the_tolerance(model, served):
    _, params = served
    toks = np.random.default_rng(0).integers(3, LIVE, (2, 48)).astype(np.int32)
    ref = np.asarray(model.logits(SEED, TINY, toks, LIVE))
    bf16 = np.asarray(forward(params, hf_config_to_llama(TINY, dtype=jnp.bfloat16), jnp.asarray(toks)))[..., :LIVE]
    assert np.abs(ref - bf16).max() > 100 * F32_TOL
    ctl = np.asarray(model.logits(SEED, TINY, toks, LIVE, int8=True))  # the benchmark's control is lower still
    assert np.abs(ref - ctl).mean() > np.abs(ref - bf16).mean()


def test_prefill_then_decode_equals_the_full_pass(served):
    cfg, params = served
    toks = jnp.asarray(np.random.default_rng(1).integers(3, LIVE, (2, 21)), jnp.int32)
    full = forward(params, cfg, toks)
    cache = init_cache(cfg, batch=2, max_len=32)
    a, cache = decode_step(params, cfg, toks[:, :13], cache)
    rest = []
    for t in range(13, 21):  # one token at a time through both kinds of state
        lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache)
        rest.append(lg)
    got = jnp.concatenate([a] + rest, axis=1)
    assert float(jnp.abs(full - got).max()) < F32_TOL


def test_served_through_the_slot_pool_with_slots_reused(model, served):
    """Prefill in buckets, then chunked decode through ``ServingEngine`` with
    three times as many requests as slots: every served token is the
    reference's best at its position (its logit gap is float32 rounding)."""
    cfg, params = served
    eng = ServingEngine(params, cfg, batch_slots=2, max_len=128, chunk_steps=4, spec_k=0, name="lfm2-test")
    try:
        prompts = _prompts(6)
        futs = [eng.submit(p, max_new_tokens=11) for p in prompts]
        outs = [[int(t) for t in f.result(timeout=300)] for f in futs]
    finally:
        eng.close()
    assert all(len(o) == 11 for o in outs)
    assert _gaps(model, prompts, outs).max() < F32_TOL
    from kakveda_tpu.core.metrics import get_registry

    text = get_registry().render()
    for line in ('kakveda_moe_experts_touched_count{engine="lfm2-test"}', 'kakveda_moe_load_max_over_mean_count{engine="lfm2-test"}'):
        assert float(next(ln for ln in text.splitlines() if ln.startswith(line)).split()[-1]) > 0
    conv = next(ln for ln in text.splitlines() if ln.startswith('kakveda_serving_cache_bytes{engine="lfm2-test",kind="conv"}'))
    assert float(conv.split()[-1]) == 2 * 2 * (cfg.conv_l_cache - 1) * cfg.d_model * 4  # slots x conv layers x rows x D x f32


def test_a_conv_state_zeroed_mid_sequence_fails_the_comparison(model, served):
    """The test of the test: the same comparison, with the pool's conv states
    wiped between two chunks, must not pass."""
    cfg, params = served
    cb = ContinuousBatcher(params, cfg, batch_slots=2, max_len=128, chunk_steps=4)
    prompts = _prompts(2, seed=3)
    rids = [cb.admit(p, max_new_tokens=12) for p in prompts]
    cb.step()
    cb.cache["conv"] = [jnp.zeros_like(c) for c in cb.cache["conv"]]
    while cb.slots:
        cb.step()
    outs = [cb.results[r] for r in rids]
    assert _gaps(model, prompts, outs).max() > 100 * F32_TOL


def test_a_padded_admit_equals_the_unpadded_one(served):
    """Admits are left-padded to a bucket: the pad positions are masked out of
    ``u``, so the slot's conv state, its next-token logits and what it decodes
    next are those of the same prompt admitted with no padding at all."""
    cfg, params = served
    prompt = _prompts(1, lo=19, hi=20, seed=5)[0]  # 20 tokens: bucket 32, 12 pad positions
    p, max_len, slots = len(prompt), 64, 2

    def admit(width):
        off = width - p
        kv = np.zeros((slots, max_len), bool)
        kv[1, off:width] = True
        offs = np.asarray([0, off], np.int32)
        cache = init_cache(cfg, batch=slots, max_len=max_len)
        last = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
        cache, last = _admit_jit(params, cfg, cache, last, jnp.asarray([[0] * off + prompt], jnp.int32),
                                 jnp.asarray([1]), jnp.asarray(kv), jnp.asarray(offs))
        return cache, last

    (c_pad, l_pad), (c_raw, l_raw) = admit(32), admit(p)
    for a, b in zip(c_pad["conv"], c_raw["conv"]):
        assert float(jnp.abs(a[1] - b[1]).max()) < 1e-6 and float(jnp.abs(b[1]).max()) > 0
        assert float(jnp.abs(a[0]).max()) == 0  # the other slot untouched
    assert float(jnp.abs(l_pad[1] - l_raw[1]).max()) < F32_TOL
    cb = ContinuousBatcher(params, cfg, batch_slots=2, max_len=64, chunk_steps=4)  # bucket 32 through admit()
    rid = cb.admit(prompt, max_new_tokens=8)
    while cb.slots:
        cb.step()
    solo = init_cache(cfg, batch=1, max_len=64)
    lg, solo = decode_step(params, cfg, jnp.asarray([prompt], jnp.int32), solo, last_only=True)
    want = []
    for _ in range(8):
        want.append(int(jnp.argmax(lg[0, -1, :LIVE])))
        lg, solo = decode_step(params, cfg, jnp.asarray([[want[-1]]], jnp.int32), solo)
    assert cb.results[rid] == want


def test_a_reused_slot_starts_from_the_new_prompts_state(served):
    """Serve A, then B in A's slot: B is what B served first gives (tokens and
    the slot's logits after them), so nothing of A's conv state is left."""
    cfg, params = served
    a, b = _prompts(2, seed=9)

    def serve(order):
        cb = ContinuousBatcher(params, cfg, batch_slots=1, max_len=128, chunk_steps=4)
        out = {}
        for name, prompt in order:
            rid = cb.admit(prompt, max_new_tokens=9)
            while cb.slots:
                cb.step()
            out[name] = (cb.results[rid], np.asarray(cb.last[0, :LIVE]))
        return out

    after_a, first = serve([("a", a), ("b", b)])["b"], serve([("b", b)])["b"]
    assert after_a[0] == first[0]
    assert np.abs(after_a[1] - first[1]).max() < 1e-6


# --- the router and the dispatch, alone ------------------------------------------------------


def _numpy_router(logits, bias, k, norm=True, scale=1.0):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    idx = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    g = np.take_along_axis(s, idx, axis=-1)
    if norm:
        g = g / (g.sum(-1, keepdims=True) + 1e-6)
    return g * scale, idx


@pytest.mark.parametrize("norm,scale", [(True, 1.0), (False, 2.5)])
def test_the_sigmoid_router_against_ten_lines_of_numpy(norm, scale):
    cfg = LlamaConfig.tiny(n_experts=8, n_experts_per_tok=2, router_score="sigmoid", router_bias=True,
                           norm_topk_prob=norm, routed_scaling_factor=scale)
    rng = np.random.default_rng(2)
    logits = rng.permutation(np.linspace(-3, 3, 5 * 8)).reshape(5, 8).astype(np.float32)  # ties-free
    bias = np.zeros(8, np.float32)
    w0, i0, _ = router_topk(jnp.asarray(logits), cfg, jnp.asarray(bias))
    g0, n0 = _numpy_router(logits, bias, 2, norm, scale)
    assert (np.asarray(i0) == n0).all() and np.abs(np.asarray(w0) - g0).max() < 1e-6
    # a bias flips a selection and changes no weight of what stays selected: row 0's runner-up
    # gives way to its third expert, whose weight is its own unbiased score's
    third = int(np.argsort(-logits[0])[2])
    bias[third] = 1.0
    w1, i1, _ = router_topk(jnp.asarray(logits), cfg, jnp.asarray(bias))
    g1, n1 = _numpy_router(logits, bias, 2, norm, scale)
    assert (np.asarray(i1) == n1).all() and np.abs(np.asarray(w1) - g1).max() < 1e-6
    assert third in np.asarray(i1)[0] and third not in np.asarray(i0)[0]
    if not norm:  # unnormalised, the expert that stays keeps its weight to the bit
        keep = int(np.asarray(i0)[0, 0])
        assert float(w1[0][list(np.asarray(i1)[0]).index(keep)]) == float(w0[0, 0])


def _loop_over_experts(x, layer, w, idx):
    xf = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    out = np.zeros_like(xf)
    for e in range(layer["we_gate"].shape[0]):
        for t, c in zip(*np.nonzero(np.asarray(idx) == e)):
            g = xf[t] @ np.asarray(layer["we_gate"][e], np.float64)
            y = (g / (1 + np.exp(-g)) * (xf[t] @ np.asarray(layer["we_up"][e], np.float64))) @ np.asarray(layer["we_down"][e], np.float64)
            out[t] += float(w[t, c]) * y
    return out.reshape(x.shape)


@pytest.mark.parametrize("rows", [7, 100])  # 28 pairs: one slice of the grouped matmul; 400: four, groups cut at the seams
@pytest.mark.parametrize("crowd", [False, True])
def test_ragged_dispatch_against_a_loop_over_experts(crowd, rows):
    """No token dropped at any imbalance: with a bias that sends every token's
    first choice to expert 3 (others get none of the firsts, some get nothing at
    all), the grouped matmuls give what a Python loop over experts gives."""
    cfg = LlamaConfig.tiny(d_model=32, d_ff=48, n_experts=8, n_experts_per_tok=2, n_layers=1, dtype=jnp.float32,
                           router_score="sigmoid", router_bias=True)
    layer = init_params(jax.random.PRNGKey(4), cfg)["layers"][0]
    if crowd:
        layer = dict(layer, expert_bias=jnp.zeros((8,)).at[3].set(10.0).at[5].set(5.0))  # all to 3, then 5
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, rows, 32)), jnp.float32)
    out, _, counts = moe_mlp(x, layer, cfg, return_aux=True)
    logits = x.reshape(-1, 32) @ layer["router"]
    w, idx, _ = router_topk(logits, cfg, layer["expert_bias"])
    if crowd:
        assert np.asarray(counts).tolist() == [0, 0, 0, 2 * rows, 0, 2 * rows, 0, 0]
    assert int(counts.sum()) == 2 * rows * 2
    assert np.abs(np.asarray(out) - _loop_over_experts(np.asarray(x), layer, np.asarray(w), idx)).max() < 1e-4
    # a masked token stays out of the dispatch: zeros for it, no expert touched on its account
    mask = jnp.ones((2, rows), bool).at[1, 2:].set(False)
    m_out, _, m_counts = moe_mlp(x, layer, cfg, token_mask=mask, return_aux=True)
    assert int(m_counts.sum()) == (rows + 2) * 2 and float(jnp.abs(m_out[1, 2:]).max()) == 0
    assert float(jnp.abs(m_out[0] - out[0]).max()) < 1e-6


# --- what does not take a conv layer yet says so ------------------------------------------------


def test_speculation_prefix_reuse_pipeline_and_training_refuse_a_conv_config(served, caplog):
    cfg, params = served
    with pytest.raises(UnsupportedLayerError, match="speculative"):
        ContinuousBatcher(params, cfg, batch_slots=2, max_len=64, spec_k=2)
    cb = ContinuousBatcher(params, cfg, batch_slots=2, max_len=64)
    with caplog.at_level("WARNING", logger="kakveda.serving"):
        assert cb.register_prefix(list(range(3, 40))) is False
    assert "conv layers" in caplog.text and not cb._prefixes
    from kakveda_tpu.models.pipeline import pp_forward, split_stages
    from kakveda_tpu.models.train import lm_loss

    with pytest.raises(UnsupportedLayerError, match="conv"):
        split_stages(params, cfg, 1)
    with pytest.raises(UnsupportedLayerError, match="conv"):
        pp_forward(params, cfg, jnp.zeros((4, 8), jnp.int32), mesh=None)
    with pytest.raises(UnsupportedLayerError, match="training"):
        lm_loss(params, cfg, jnp.zeros((1, 8), jnp.int32))
    dense_then_experts = LlamaConfig.tiny(n_layers=2, n_experts=4, n_dense_layers=1, d_ff_dense=64, d_ff=32)
    with pytest.raises(UnsupportedLayerError, match="differ in kind"):
        split_stages(init_params(jax.random.PRNGKey(0), dense_then_experts), dense_then_experts, 1)
