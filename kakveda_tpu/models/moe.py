"""Sparse Mixture-of-Experts MLP for the Llama runtime (Mixtral, LFM2-MoE).

The reference's model tier is an HTTP client to an Ollama daemon
(reference: services/dashboard/app.py:1182-1258), which is how it "supports"
MoE checkpoints like Mixtral. Here the MoE block is a first-class layer on
the same runtime/mesh as everything else, designed TPU-first:

  * **Routing** (:func:`router_topk`), in float32 from the layer's
    activations, by the config's published switches. ``router_score ==
    "softmax"`` is HF Mixtral: softmax over all expert logits, top-k,
    renormalise the kept weights (parity-tested in tests/test_hf_convert.py).
    ``"sigmoid"`` is HF LFM2-MoE: sigmoid scores, the top-k SELECTED by score
    + the layer's ``expert_bias`` (``use_expert_bias``: the bias evens out the
    experts' load and never enters a weight), weighted by the unbiased
    scores over (their sum + 1e-6) when ``norm_topk_prob``, times
    ``routed_scaling_factor``.
  * **Dispatch** is ragged and no-drop: the [T·k] (token, choice) pairs are
    sorted by expert, the tokens' rows gathered in that order, and each
    projection is a grouped matmul over the groups' rows
    (``jax.lax.ragged_dot``: on a TPU XLA lowers it to its grouped-matmul
    kernel, which reads the weights of the experts that got rows and of no
    other; on the CPU to plain XLA), 128 rows at a time
    (:func:`_grouped_matmul`). No ``[E·T, d]`` buffer, no capacity, no
    dropped token at any imbalance: work and weight traffic follow the
    routing that happened. ``token_mask`` takes tokens out of the dispatch
    altogether (the serving pool's idle slots): they touch no expert and
    get zeros.
  * ``expert_capacity_factor > 0`` (training's drop discipline) keeps its own
    static-shaped path, :func:`_capacity_dispatch`: each expert takes at
    most ``ceil(T·k/E · factor)`` tokens by position priority (GShard), the
    rest are dropped; one batched einsum per projection over ``[E, cap, d]``.
  * **Expert parallelism**: the stacked-E leading axis is the ``ep`` mesh
    axis (llama.param_specs), composing with tensor parallelism over the
    ffn width (``we_gate [E, D, F]`` shards P("ep", None, "tp")). XLA
    partitions the matmuls over both axes and inserts the dispatch/combine
    collectives from the shardings.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from kakveda_tpu.models.llama import LlamaConfig, Params, wmat


def router_topk(logits: jax.Array, cfg: LlamaConfig, bias=None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Route by the config's switches (module docstring). ``logits`` [T, E];
    ``bias`` [E] or None. Returns (weights [T,k] f32, expert_idx [T,k],
    full scores [T,E] — the latter feeds the load-balancing loss)."""
    k = cfg.n_experts_per_tok
    logits = logits.astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        eps = 1e-6  # HF Lfm2MoeSparseMoeBlock's
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        eps = 0.0
    if bias is None:
        w, idx = jax.lax.top_k(scores, k)
    else:  # the bias selects; the weights are the unbiased scores
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True) + eps, 1e-20)
    if cfg.routed_scaling_factor != 1.0:
        w = w * cfg.routed_scaling_factor
    return w, idx, scores


def expert_capacity(n_tokens: int, cfg: LlamaConfig) -> int:
    """Static per-expert token capacity of the drop discipline
    (``expert_capacity_factor > 0``) for a T-token dispatch."""
    k, e = cfg.n_experts_per_tok, cfg.n_experts
    return min(n_tokens, max(1, math.ceil(n_tokens * k / e * cfg.expert_capacity_factor)))


# Rows of the sorted pairs given to one grouped matmul. XLA's TPU kernel for
# ``ragged_dot`` tiles the rows by min(m, 512) and runs a whole masked tile
# for every (tile, group) that overlap: a 256-token admit's 1,024 pairs over
# 64 experts (16 rows a group) paid 65 tiles of 512 rows where 1,024 rows were
# needed, and was compute-bound on that waste (PERF.md section 6, PR 29).
# Slices of 128 rows pay a quarter of it for one more read of the weights of
# the few groups a slice boundary cuts; a decode step's rows are one slice.
_SLICE_ROWS = 128


def _grouped_matmul(xs: jax.Array, w: jax.Array, counts: jax.Array) -> jax.Array:
    """``xs`` [M, d] grouped by expert (``counts`` [E] rows each, in order;
    rows behind the last group belong to nobody) times ``w`` [E, d, f]."""
    m = xs.shape[0]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    return jnp.concatenate([
        jax.lax.ragged_dot(  # the part of each group that lies in rows [a, a + rows)
            xs[a:a + _SLICE_ROWS], w,
            jnp.clip(ends, a, a + _SLICE_ROWS) - jnp.clip(starts, a, a + _SLICE_ROWS),
        )
        for a in range(0, m, _SLICE_ROWS)
    ])


def _ragged_dispatch(xf, w, idx, layer: Params, e: int, token_mask=None):
    """No-drop grouped dispatch: xf [T, d], w / idx [T, k] ->
    (out [T, d], counts [E] int32: the rows each expert got)."""
    t, d = xf.shape
    k = idx.shape[1]
    dt = xf.dtype
    e_flat = idx.reshape(t * k)
    if token_mask is not None:
        # expert id E sorts last, behind every group: those rows are computed
        # by nobody and zeroed below
        e_flat = jnp.where(jnp.repeat(token_mask.reshape(t), k), e_flat, e)
    order = jnp.argsort(e_flat, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[e_flat].add(1)[:e]
    xs = xf[order // k]  # [T·k, d], grouped by expert
    gate = jax.nn.silu(_grouped_matmul(xs, wmat(layer["we_gate"], dt), counts))
    up = _grouped_matmul(xs, wmat(layer["we_up"], dt), counts)
    ys = _grouped_matmul(gate * up, wmat(layer["we_down"], dt), counts)
    # Combine: each pair's row back at (token, choice), weighted, summed over
    # the k choices in float32. Rows behind the last group hold nothing.
    inv = jnp.zeros((t * k,), jnp.int32).at[order].set(jnp.arange(t * k, dtype=jnp.int32))
    kept = (e_flat < e)[:, None]
    y_pairs = jnp.where(kept, ys[inv].astype(jnp.float32) * w.reshape(t * k, 1), 0.0)
    return jnp.sum(y_pairs.reshape(t, k, d), axis=1).astype(dt), counts


def _capacity_dispatch(xf, w, idx, layer: Params, e: int, cap: int):
    """The drop discipline: sort-based dispatch into a static [E, cap, d]
    buffer; pairs beyond an expert's capacity are dropped (position
    priority: the stable sort keeps token order within an expert)."""
    t, d = xf.shape
    k = idx.shape[1]
    dt = xf.dtype
    e_flat = idx.reshape(t * k)
    w_flat = w.reshape(t * k)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    tok_sorted = order.astype(jnp.int32) // k
    counts = jnp.zeros((e,), jnp.int32).at[e_sorted].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[e_sorted]
    keep = pos < cap
    slot = jnp.where(keep, e_sorted * cap + pos, e * cap)  # out-of-range => .at[].set drop
    xe = jnp.zeros((e * cap, d), dt).at[slot, :].set(xf[tok_sorted], mode="drop").reshape(e, cap, d)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wmat(layer["we_gate"], dt)))
    up = jnp.einsum("ecd,edf->ecf", xe, wmat(layer["we_up"], dt))
    ye = jnp.einsum("ecf,efd->ecd", gate * up, wmat(layer["we_down"], dt))
    y_rows = ye.reshape(e * cap, d)[jnp.minimum(slot, e * cap - 1)]
    contrib = y_rows * (w_flat[order] * keep.astype(jnp.float32))[:, None].astype(dt)
    return jnp.zeros((t, d), dt).at[tok_sorted, :].add(contrib)


def moe_mlp(x: jax.Array, layer: Params, cfg: LlamaConfig, return_aux: bool = False, token_mask=None):
    """Sparse-MoE SwiGLU MLP: x [B, S, D] -> [B, S, D]. With ``return_aux``
    ``(out, aux, counts)`` — aux is this layer's load-balancing loss, which
    the training objective adds at ``cfg.router_aux_coef``; counts [E] int32
    are the (token, choice) pairs each expert got (what the serving chunk's
    expert counters are made of; None on the drop discipline's path). A
    caller takes what it needs: under ``jit`` the other is never computed.
    ``token_mask`` [B, S] bool: False takes a token out of the dispatch
    (module docstring).

    Layer params: ``router`` [D, E], stacked ``we_gate``/``we_up``
    [E, D, F], ``we_down`` [E, F, D], and ``expert_bias`` [E] where the
    router selects with one (llama.init_params / Mixtral conversion in
    models/hf_convert.py).
    """
    b, s, d = x.shape
    e = cfg.n_experts
    xf = x.reshape(b * s, d)
    logits = xf.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
    w, idx, scores = router_topk(logits, cfg, layer.get("expert_bias"))  # [T, k]
    if cfg.expert_capacity_factor > 0.0:
        out = _capacity_dispatch(xf, w, idx, layer, e, expert_capacity(b * s, cfg))
        counts = None
    else:
        out, counts = _ragged_dispatch(xf, w, idx, layer, e, token_mask)
    out = out.reshape(b, s, d)
    if return_aux:
        return out, load_balancing_loss(scores, idx, e, cfg.n_experts_per_tok), counts
    return out


def load_balancing_loss(
    router_probs: jax.Array, expert_idx: jax.Array, n_experts: int, top_k: int = 1
) -> jax.Array:
    """Switch/Mixtral auxiliary load-balancing loss: E · Σ_e f_e · P_e,
    where f_e is the per-TOKEN fraction routed to expert e (assignment
    counts / T — each token contributes ``top_k`` counts, matching HF
    ``load_balancing_loss_func``'s sum of one-hot means over the top-k
    slots; normalizing by T·k instead would shrink the term by 1/k and
    silently under-weight HF-sourced ``router_aux_loss_coef`` values) and
    P_e the mean router probability of e. Minimized (=top_k) by uniform
    routing; add ``coef · loss`` to the LM loss when fine-tuning a MoE
    config (HF ``router_aux_loss_coef``)."""
    probs = router_probs.reshape(-1, n_experts)
    idx = expert_idx.reshape(-1)
    t = jnp.maximum(idx.size // max(top_k, 1), 1)
    f = jnp.zeros((n_experts,), jnp.float32).at[idx].add(1.0) / t
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)
