"""Sparse-MoE block (models/moe.py): routing/dispatch correctness vs a
per-token dense oracle, HF Mixtral logit parity, cached-decode parity,
expert-parallel sharding parity, capacity-drop semantics, and trainability
(gradients reach the router)."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kakveda_tpu.models.llama import (
    LlamaConfig,
    forward,
    init_params,
    param_specs,
    specs_for_mesh,
)
from kakveda_tpu.models.moe import expert_capacity, load_balancing_loss, moe_mlp, router_topk


def _moe_cfg(**kw) -> LlamaConfig:
    base = dict(
        vocab_size=64,
        d_model=32,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=48,
        max_seq_len=64,
        dtype=jnp.float32,
        n_experts=4,
        n_experts_per_tok=2,
    )
    base.update(kw)
    return LlamaConfig(**base)


def _oracle_moe(x: np.ndarray, layer, cfg: LlamaConfig) -> np.ndarray:
    """Per-token dense reference: every token runs its top-k experts
    directly, no dispatch buffers."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    router = np.asarray(layer["router"], np.float32)
    logits = xf.astype(np.float32) @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xf, np.float32)
    k = cfg.n_experts_per_tok
    for t in range(xf.shape[0]):
        top = np.argsort(-probs[t])[:k]
        w = probs[t][top]
        w = w / w.sum()
        for wi, ei in zip(w, top):
            wg = np.asarray(layer["we_gate"][ei], np.float32)
            wu = np.asarray(layer["we_up"][ei], np.float32)
            wd = np.asarray(layer["we_down"][ei], np.float32)
            h = xf[t].astype(np.float32)
            gate = h @ wg
            gate = gate / (1.0 + np.exp(-gate))  # silu
            y = (gate * (h @ wu)) @ wd
            out[t] += wi * y
    return out.reshape(b, s, d)


def test_moe_mlp_matches_per_token_oracle():
    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    layer = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, cfg.d_model)), jnp.float32)
    got = np.asarray(moe_mlp(x, layer, cfg))
    want = _oracle_moe(np.asarray(x), layer, cfg)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_router_topk_renormalizes():
    logits = jnp.asarray(np.random.default_rng(1).standard_normal((7, 8)), jnp.float32)
    w, idx, probs = router_topk(logits, _moe_cfg(n_experts=8))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    assert np.asarray(probs).shape == (7, 8)
    # top-k indices really are the argmax-ordered experts
    assert (np.asarray(idx[:, 0]) == np.asarray(probs).argmax(-1)).all()


def test_expert_capacity_factor():
    # factor <= 0 is the ragged no-drop dispatch: it has no capacity at all
    cfg = _moe_cfg(expert_capacity_factor=1.0)
    # T·k/E = 100·2/4 = 50
    assert expert_capacity(100, cfg) == 50
    assert expert_capacity(3, _moe_cfg(expert_capacity_factor=0.01)) == 1


def test_capacity_drop_changes_output_but_stays_finite():
    cfg_exact = _moe_cfg()
    cfg_tight = _moe_cfg(expert_capacity_factor=0.3)
    params = init_params(jax.random.PRNGKey(2), cfg_exact)
    layer = params["layers"][0]
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 32, cfg_exact.d_model)), jnp.float32)
    exact = np.asarray(moe_mlp(x, layer, cfg_exact))
    dropped = np.asarray(moe_mlp(x, layer, cfg_tight))
    assert np.isfinite(dropped).all()
    assert np.abs(exact - dropped).max() > 1e-6  # the cap actually bit


def test_moe_forward_and_decode_parity(decode_parity):
    """Full forward on an MoE config, and the cached decode path must
    reproduce its greedy continuation exactly (dispatch inside decode
    operates on T = B tokens)."""
    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    decode_parity(params, cfg, list(range(5, 17)), n=6)


def test_moe_ep_sharded_forward_parity():
    """Experts sharded over an ep×tp submesh produce the same logits as the
    unsharded forward — XLA inserts the dispatch/combine collectives."""
    from jax.sharding import NamedSharding

    from kakveda_tpu.parallel.mesh import create_mesh

    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 64, size=(2, 9)))
    want = np.asarray(forward(params, cfg, ids))

    mesh = create_mesh("dp:2,ep:2,tp:2")
    specs = specs_for_mesh(param_specs(cfg), mesh)
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    we = sharded["layers"][0]["we_gate"]
    assert we.sharding.spec == specs["layers"][0]["we_gate"]
    got = np.asarray(forward(sharded, cfg, ids))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_specs_for_mesh_drops_absent_axes():
    from jax.sharding import PartitionSpec as P

    from kakveda_tpu.parallel.mesh import create_mesh

    cfg = _moe_cfg()
    mesh = create_mesh("dp:2,tp:2")  # no ep axis
    specs = specs_for_mesh(param_specs(cfg), mesh)
    assert specs["layers"][0]["we_gate"] == P(None, None, "tp")
    assert specs["layers"][0]["we_down"] == P(None, "tp", None)


def test_load_balancing_loss_uniform_is_top_k():
    # HF load_balancing_loss_func convention: counts normalize by T (each
    # token contributes top_k assignments), so the uniform minimum is
    # top_k and the one-expert collapse approaches E·top_k.
    t, e, k = 64, 4, 2
    probs = jnp.full((t, e), 1.0 / e)
    # perfectly balanced assignments
    idx = jnp.asarray(np.stack([np.arange(t) % e, (np.arange(t) + 1) % e], -1))
    loss = float(load_balancing_loss(probs, idx, e, k))
    assert abs(loss - k) < 1e-5
    # collapse onto one expert: loss rises toward E·k
    probs_bad = jnp.zeros((t, e)).at[:, 0].set(1.0)
    idx_bad = jnp.zeros((t, k), jnp.int32)
    assert float(load_balancing_loss(probs_bad, idx_bad, e, k)) > 2 * 3.9


def test_aux_loss_wired_into_training_objective():
    """router_aux_coef > 0 adds the summed per-layer load-balancing loss
    to lm_loss; the aux term sits in [top_k, E·top_k] per layer (HF
    normalization)."""
    from kakveda_tpu.models.train import lm_loss

    cfg0 = _moe_cfg()
    cfg1 = _moe_cfg(router_aux_coef=0.5)
    params = init_params(jax.random.PRNGKey(6), cfg0)
    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 64, size=(2, 16)))
    base = float(lm_loss(params, cfg0, tokens))
    with_aux = float(lm_loss(params, cfg1, tokens))
    per_layer_aux = (with_aux - base) / (0.5 * cfg0.n_layers)
    k = cfg0.n_experts_per_tok
    assert k - 1e-3 <= per_layer_aux <= cfg0.n_experts * k + 1e-3, per_layer_aux
    # aux still differentiates
    g = jax.grad(lm_loss)(params, cfg1, tokens)
    assert np.isfinite(float(jnp.abs(g["layers"][0]["router"]).max()))


def test_moe_gradients_reach_router_and_experts():
    from kakveda_tpu.models.train import lm_loss

    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 64, size=(2, 16)))
    loss, grads = jax.value_and_grad(lm_loss)(params, cfg, tokens)
    assert np.isfinite(float(loss))
    g = grads["layers"][0]
    for key in ("router", "we_gate", "we_up", "we_down"):
        gn = float(jnp.abs(g[key]).max())
        assert np.isfinite(gn) and gn > 0.0, key
