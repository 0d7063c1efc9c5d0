"""One q|k|v projection weight a layer (``llama.fuse_qkv``).

``LlamaRuntime`` holds each attention layer's ``wq``, ``wk``, ``wv`` as one
``wqkv`` and ``qkv_proj`` reads it with one dot. Each output column is the
same contraction as before, so a fused tree must serve what the three
weights served: the same logits through ``forward``, ``decode_step`` and the
pool's chunk, and the same greedy tokens, for every served stack. A tree
sharded over a mesh keeps its three weights, and the pool's gauge says how
many layers it serves fused.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kakveda_tpu.core import metrics as _metrics
from kakveda_tpu.models.hf_convert import hf_config_to_llama
from kakveda_tpu.models.llama import (
    LlamaConfig,
    decode_step,
    forward,
    fuse_qkv,
    init_cache,
    init_params,
    unfuse_qkv,
)
from kakveda_tpu.models.quant import quantize_params_int8
from kakveda_tpu.models.serving import ContinuousBatcher

CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
KINDS = ("tiny", "mistral-window", "qwen2-bias", "lfm2", "int8-weights", "int8-kv")


def _rehearsal(name: str, **over) -> LlamaConfig:
    """A benchmark configuration at its rehearsal widths, in float32."""
    f = json.loads((CONFIGS / f"{name}.json").read_text())
    return hf_config_to_llama({**f, **f["rehearsal"]["model"], **over}, dtype=jnp.float32)


def _case(kind: str):
    """(config, the unfused tree) of one served stack."""
    if kind == "mistral-window":
        cfg = _rehearsal("judge-mistral-7b", sliding_window=6)  # binds inside the prompts below
    elif kind == "lfm2":
        cfg = _rehearsal("judge-lfm2-24b-a2b")  # conv layers, q_norm / k_norm, experts
    elif kind == "int8-kv":
        cfg = LlamaConfig.tiny(dtype=jnp.float32, kv_quant="int8")
    else:
        cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_bias=kind == "qwen2-bias")
    params = init_params(jax.random.PRNGKey(3), cfg)
    if kind == "qwen2-bias":  # zero biases would hide a part added to the wrong columns
        keys = iter(jax.random.split(jax.random.PRNGKey(4), 3 * cfg.n_layers))
        for layer in params["layers"]:
            for b in ("bq", "bk", "bv"):
                layer[b] = 0.5 * jax.random.normal(next(keys), layer[b].shape, jnp.float32)
    if kind == "int8-weights":
        params = quantize_params_int8(params)
    return cfg, params


def _attention_layers(cfg: LlamaConfig) -> int:
    return len(cfg.layers_of("full_attention"))


PROMPTS = [[5, 6, 7, 8, 9, 10, 11, 12, 13], [40, 41, 42]]


@pytest.mark.parametrize("kind", KINDS)
def test_fused_tree_serves_the_unfused_logits(kind):
    cfg, params = _case(kind)
    fused = fuse_qkv(params)
    layers = fused["layers"]
    assert sum("wqkv" in layer for layer in layers) == _attention_layers(cfg)
    assert not any(k in layer for layer in layers for k in ("wq", "wk", "wv"))

    def close(a, b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)

    tokens = jnp.asarray([PROMPTS[0]])
    close(forward(fused, cfg, tokens), forward(params, cfg, tokens))

    def prefill_then_step(p):
        logits, cache = decode_step(p, cfg, tokens, init_cache(cfg, batch=1, max_len=32))
        step, _ = decode_step(p, cfg, jnp.argmax(logits[:, -1:], axis=-1), cache)
        return logits, step

    for a, b in zip(prefill_then_step(fused), prefill_then_step(params)):
        close(a, b)

    pools = [ContinuousBatcher(p, cfg, batch_slots=2, max_len=64, chunk_steps=4, name=f"qkv-{kind}-{i}")
             for i, p in enumerate((params, fused))]
    served = [pool.run_all(PROMPTS, max_new_tokens=12) for pool in pools]
    assert served[1] == served[0] and all(len(o) == 12 for o in served[0])
    close(pools[1].last, pools[0].last)  # the last chunk's logits, slot by slot


@pytest.mark.parametrize("kind", ["tiny", "qwen2-bias", "int8-weights"])
def test_unfuse_gives_back_the_three_weights(kind):
    cfg, params = _case(kind)
    back = unfuse_qkv(fuse_qkv(params), cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_pairs_fuse_as_their_quantization():
    """Scales are per output column: quantizing the fused weight is fusing
    the quantized three."""
    cfg, params = _case("tiny")
    a = quantize_params_int8(fuse_qkv(params))["layers"][0]["wqkv"]
    b = fuse_qkv(quantize_params_int8(params))["layers"][0]["wqkv"]
    for key in ("q", "s"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


def test_tree_sharded_over_a_mesh_stays_unfused():
    from kakveda_tpu.models.hf_convert import shard_params
    from kakveda_tpu.parallel.mesh import create_mesh

    cfg, params = _case("tiny")
    sharded = shard_params(params, cfg, create_mesh("dp:1,tp:2"))
    kept = fuse_qkv(sharded)
    assert all("wqkv" not in layer and "wq" in layer for layer in kept["layers"])
    assert kept["layers"][0]["wq"] is sharded["layers"][0]["wq"]


def _gauge(name: str) -> float:
    text = _metrics.get_registry().render()
    line = next(ln for ln in text.splitlines() if ln.startswith(f'kakveda_serving_fused_qkv_layers{{engine="{name}"}}'))
    return float(line.split()[-1])


@pytest.mark.parametrize("kind", ["tiny", "lfm2", "sharded"])
def test_gauge_reads_the_layers_served_fused(kind):
    from kakveda_tpu.models.generate import LlamaRuntime

    cfg, params = _case("tiny" if kind == "sharded" else kind)
    if kind == "sharded":
        from kakveda_tpu.models.hf_convert import shard_params
        from kakveda_tpu.parallel.mesh import create_mesh

        params = shard_params(params, cfg, create_mesh("dp:1,tp:2"))
    rt = LlamaRuntime(cfg=cfg, params=params)
    name = f"qkv-gauge-{kind}"
    ContinuousBatcher(rt.params, rt.cfg, batch_slots=2, max_len=64, chunk_steps=4, name=name)
    want = {"tiny": cfg.n_layers, "lfm2": _attention_layers(cfg), "sharded": 0}[kind]
    if kind == "lfm2":
        assert 0 < want < cfg.n_layers  # conv layers beside the attention ones
    assert _gauge(name) == want
