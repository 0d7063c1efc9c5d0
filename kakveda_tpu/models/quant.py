"""Weight-only int8 quantization for the serving path.

Decode throughput on a single chip is weight-bandwidth-bound: every step
streams every dense matrix out of HBM (2.2 GB at 1.1B bf16). Symmetric
per-output-channel int8 halves that stream — the dequantize (one multiply
by a [out] scale row) fuses into the consuming matmul's weight-operand
read, so HBM traffic is int8-sized while the MXU still accumulates in
f32/bf16.

Representation: a quantized dense weight is the pytree leaf pair
``{"q": int8 [in, out], "s": f32 [out]}`` — ``llama.wmat`` materializes
either form, so forward/decode code is quantization-agnostic. RMSNorm
gains and the embedding table stay unquantized (tiny, and the embedding
is a gather, not a matmul).

Scope: inference only (the quantized tree is not a training target).
Enable with ``LlamaRuntime(..., quant="int8")`` or ``KAKVEDA_QUANT=int8``
for the env-built runtime. The reference has no comparable surface — its
"model runtime" is an HTTP client to an external Ollama daemon
(reference: services/dashboard/app.py:1182-1258).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

_DENSE_KEYS = ("wq", "wk", "wv", "wqkv", "wo", "w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down")


def quantize_tensor_int8(w: jax.Array) -> Dict[str, jax.Array]:
    """Symmetric per-output-channel (last axis) int8: q = round(w / s),
    s = absmax / 127 per output column. Works for 2-D dense weights
    ([in, out] → s [out]) and stacked MoE expert weights
    ([E, in, out] → s [E, out]) alike — the reduction is over the
    contraction (in) axis."""
    w32 = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w32), axis=-2) / 127.0
    s = jnp.where(s == 0.0, 1.0, s)
    q = jnp.clip(jnp.round(w32 / s[..., None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def quantize_params_int8(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize every dense projection (incl. stacked MoE experts — on
    Mixtral the expert FFNs are ~95% of weight bytes) + lm_head; keep
    norms, biases, the MoE router and the embedding."""
    out: Dict[str, Any] = {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "lm_head": quantize_tensor_int8(params["lm_head"]),
        "layers": [],
    }
    for layer in params["layers"]:
        ql = {}
        for k, v in layer.items():
            ql[k] = quantize_tensor_int8(v) if k in _DENSE_KEYS else v
        out["layers"].append(ql)
    return out


def quantization_error(params: Dict[str, Any], qparams: Dict[str, Any]) -> float:
    """Max relative per-tensor reconstruction error across dense weights
    (test/diagnostic helper)."""
    worst = 0.0
    for orig, quant in zip(params["layers"], qparams["layers"]):
        for k in _DENSE_KEYS:
            if k not in orig:
                continue
            w = orig[k].astype(jnp.float32)
            wq = quant[k]["q"].astype(jnp.float32) * quant[k]["s"][..., None, :]
            num = jnp.max(jnp.abs(w - wq))
            den = jnp.max(jnp.abs(w))
            worst = max(worst, float(num / den))
    return worst
