"""Training step for the in-tree Llama, sharded over a dp×cp×tp mesh.

The reference never trains anything — its "model" is an HTTP call. Here the
framework owns the model, so it also owns the fine-tuning loop (the LLM
failure-classifier is a fine-tune target): causal-LM loss, AdamW, and a
``make_sharded_train_step`` that jits the whole update over a
``jax.sharding.Mesh`` with

  * params/opt-state sharded per ``param_specs`` (TP over ``tp``,
    replicated over ``dp``/``cp``),
  * batch sharded P('dp', 'cp') — data parallel over batch, context
    parallel over sequence (ring attention inside the forward),
  * donated params/opt-state so the update is in-place in HBM.

XLA inserts the gradient all-reduces from the shardings; there is no
hand-written NCCL/MPI anywhere — the collectives ride ICI.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kakveda_tpu.models.llama import (
    LlamaConfig,
    Params,
    UnsupportedLayerError,
    forward,
    init_params,
    param_specs,
    specs_for_mesh,
)


def lm_loss_from_logits(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Causal-LM loss given logits [B, S, V]: next-token targets are the
    tokens shifted left, the wrapped last position masked out. The ONE
    definition of the training objective — shared by the dense step here
    and the pipeline-parallel step (models/pipeline.py)."""
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss(
    params: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S] — next-token targets are tokens shifted left
    mesh: Optional[Mesh] = None,
    cp_axis: Optional[str] = None,
) -> jax.Array:
    """CE loss; MoE configs with ``router_aux_coef > 0`` add the summed
    load-balancing aux loss (models/moe.py, HF router_aux_loss_coef). A
    config with conv layers is refused: no training run has been held against
    a reference's gradients for that operator, and its weights have no
    sharding rule beyond replication."""
    if cfg.has_conv:
        raise UnsupportedLayerError("training does not take a config with conv layers yet")
    if cfg.n_experts and cfg.router_aux_coef > 0.0:
        logits, aux = forward(params, cfg, tokens, mesh=mesh, cp_axis=cp_axis, with_aux=True)
        return lm_loss_from_logits(logits, tokens) + cfg.router_aux_coef * aux
    logits = forward(params, cfg, tokens, mesh=mesh, cp_axis=cp_axis)
    return lm_loss_from_logits(logits, tokens)


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.01) -> optax.GradientTransformation:
    return optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=weight_decay)


def make_train_step(cfg: LlamaConfig, opt: Optional[optax.GradientTransformation] = None):
    """Single-device (or pure-DP) jitted train step."""
    opt = opt or make_optimizer()

    @jax.jit
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, cfg, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step, opt


def shard_params(params: Params, cfg: LlamaConfig, mesh: Mesh) -> Params:
    from kakveda_tpu.parallel.distributed import put_global

    specs = specs_for_mesh(param_specs(cfg), mesh)
    return jax.tree.map(
        lambda x, s: put_global(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, jax.Array) or hasattr(x, "shape"),
    )


def make_sharded_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    opt: Optional[optax.GradientTransformation] = None,
    cp_axis: Optional[str] = "cp",
):
    """Jitted full training step over the mesh; returns (step, init_state).

    ``init_state(rng)`` materializes sharded params + opt state directly on
    the mesh (init is itself jitted with output shardings, so the f32 master
    weights never exist unsharded on one device).
    """
    opt = opt or make_optimizer()
    specs = specs_for_mesh(param_specs(cfg), mesh)
    param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                   is_leaf=lambda x: isinstance(x, P))
    batch_sharding = NamedSharding(mesh, P("dp", cp_axis if cp_axis in mesh.axis_names else None))
    repl = NamedSharding(mesh, P())

    use_cp = cp_axis if (cp_axis and cp_axis in mesh.axis_names and mesh.shape[cp_axis] > 1) else None

    def _init(rng):
        params = init_params(rng, cfg)
        opt_state = opt.init(params)
        return params, opt_state

    # Opt-state sharding mirrors the param tree inside adamw's mu/nu. Match
    # by pytree-path suffix, not leaf shape: wq [d, d] and wo [d, d] share a
    # shape but carry transposed PartitionSpecs, so a shape-keyed map would
    # silently reshard one of them every step.
    from jax.tree_util import keystr, tree_flatten_with_path, tree_map_with_path

    params_shape = jax.eval_shape(lambda r: init_params(r, cfg), jax.random.PRNGKey(0))
    param_paths = [keystr(path) for path, _ in tree_flatten_with_path(params_shape)[0]]
    path_to_sharding = dict(zip(param_paths, jax.tree.leaves(param_shardings)))

    def _sharding_for(path, leaf):
        if leaf.ndim == 0:
            return repl
        ps = keystr(path)
        for param_path, sharding in path_to_sharding.items():
            if ps.endswith(param_path):
                return sharding
        return repl

    opt_state_shape = jax.eval_shape(lambda r: opt.init(init_params(r, cfg)), jax.random.PRNGKey(0))
    opt_shardings = tree_map_with_path(_sharding_for, opt_state_shape)

    init_state = jax.jit(_init, out_shardings=(param_shardings, opt_shardings))

    def _step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, cfg, tokens, mesh, use_cp)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    step = jax.jit(
        _step,
        in_shardings=(param_shardings, opt_shardings, batch_sharding),
        out_shardings=(param_shardings, opt_shardings, repl),
        donate_argnums=(0, 1),
    )
    return step, init_state


# ---------------------------------------------------------------------------
# training loop + checkpointing (train → save → serve on the platform)
# ---------------------------------------------------------------------------


def save_checkpoint(params: Params, path: str) -> None:
    """Orbax checkpoint of the param pytree; LlamaRuntime.load_checkpoint
    (and KAKVEDA_LLAMA_CKPT) restore it for serving."""
    import os

    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(str(path)), params)
    ckptr.wait_until_finished()


def corpus_to_batches(text: str, batch: int, seq_len: int):
    """Tokenize a text corpus into as many [batch, seq_len] blocks as it
    yields (wrapping), for the demo fine-tune loop."""
    import numpy as np

    from kakveda_tpu.models.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    ids = tok.encode(text)
    need = batch * seq_len
    n_blocks = max(1, len(ids) // need)
    flat = np.resize(np.asarray(ids, np.int32), n_blocks * need)
    return [
        jnp.asarray(flat[i * need : (i + 1) * need].reshape(batch, seq_len))
        for i in range(n_blocks)
    ]


def fit(
    cfg: LlamaConfig,
    corpus: str,
    *,
    steps: int = 50,
    batch: int = 4,
    seq_len: int = 128,
    lr: float = 3e-4,
    seed: int = 0,
    checkpoint_path: Optional[str] = None,
    log_every: int = 10,
    log_fn=print,
) -> tuple[Params, list[float]]:
    """Small-scale causal-LM fit over a text corpus; returns (params,
    per-step losses) and optionally saves an orbax checkpoint that
    ``runtime=tpu`` serves directly."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    step, opt = make_train_step(cfg, make_optimizer(lr))
    opt_state = opt.init(params)
    batches = corpus_to_batches(corpus, batch, seq_len)
    losses: list[float] = []
    for i in range(steps):
        tokens = batches[i % len(batches)]
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
        if log_every and (i + 1) % log_every == 0:
            log_fn(f"step {i + 1}/{steps} loss {losses[-1]:.4f}")
    if checkpoint_path:
        save_checkpoint(params, checkpoint_path)
        log_fn(f"checkpoint saved to {checkpoint_path}")
    return params, losses
