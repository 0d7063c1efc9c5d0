"""Pipeline parallelism (GPipe) for the transformer runtime.

The reference has no model parallelism of any kind (SURVEY §2.9 — its model
tier is an HTTP call). This module completes the framework's parallelism
set — dp (batch), cp (ring attention over sequence), tp (Megatron), ep
(MoE experts) — with **pp**: layers split into contiguous stages placed on
a ``pp`` mesh axis, microbatches streamed through the stages, activations
hopping stage→stage over ICI (``ppermute``).

Design (TPU-first, shard_map-manual):

  * **Stage-stacked params**: the per-layer dicts are re-packed into one
    pytree whose layer arrays carry a leading ``[n_stages, layers_per_stage,
    …]`` axis sharded ``P("pp")`` — each device materializes ONLY its own
    stage's weights (1/S of the model), which is the point of pp: models
    that don't fit one chip.
  * **GPipe schedule**: ``n_micro + n_stages − 1`` ticks. At tick t, stage
    s runs microbatch ``t − s`` (when in range): stage 0 feeds from the
    input queue, later stages from the activation received over the ring
    at the end of the previous tick. The loop is a ``lax.scan`` with static
    length — fully compiled, no host round-trips per tick.
  * **Within a stage**: ``lax.scan`` over the stacked layer axis of
    ``llama.transformer_block`` — the one block body the dense forward,
    the cached decode and the serving chunk run (MoE layers included) —
    so pp has no model code of its own.
  * Embedding / final norm / lm head run replicated outside the shard_map
    region (tiny next to the layer stack).

Composition and trade-offs: pp as implemented composes with the data axes
(microbatching IS batch splitting); it is the *inter-op* alternative to
the *intra-op* tp/ep sharding — shard_map is manual-mode, so stage weights
inside the region don't also auto-shard over tp. Pick pp when the model
doesn't fit (weights 1/S per chip), tp when latency matters. Bubble
fraction is the GPipe ``(S−1)/(M+S−1)``; raise ``n_micro`` to amortize.

Parity: ``pp_forward`` reproduces ``llama.forward`` logits exactly
(tests/test_pipeline_parallel.py), and ``make_pp_train_step`` trains
the same loss as the dense step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kakveda_tpu.models.llama import (
    LlamaConfig,
    Params,
    UnsupportedLayerError,
    _rope_freqs,
    _sequence_attention,
    embed_tokens,
    lm_logits,
    param_specs,
    transformer_block,
)


def split_stages(params: Params, cfg: LlamaConfig, n_stages: int) -> Params:
    """Re-pack the flat layer list into stage-stacked arrays
    ``[n_stages, layers_per_stage, …]`` (leading axis shards over ``pp``)."""
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers do not split into {n_stages} stages")
    _refuse_mixed_layers(cfg)
    per = cfg.n_layers // n_stages
    layers = params["layers"]
    if any(jax.tree.structure(layer) != jax.tree.structure(layers[0]) for layer in layers):
        # dense layers before the expert layers: two trees, nothing to stack
        raise UnsupportedLayerError("pipeline parallelism cannot stack layers whose weights differ in kind")
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves).reshape((n_stages, per) + leaves[0].shape),
        *layers,
    )
    return {
        "embed": params["embed"],
        "stages": stacked,
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
    }


def _refuse_mixed_layers(cfg: LlamaConfig) -> None:
    """The stages stack their layers into one array per weight and scan over
    them: every layer has to be the same kind with the same tree. A config
    with conv layers is refused (served on the tp/ep paths instead) — never
    run on a wrong path."""
    if any(cfg.layer_kind(i) != "full_attention" for i in range(cfg.n_layers)):
        raise UnsupportedLayerError("pipeline parallelism cannot stack a config with conv layers")


def pp_param_specs(cfg: LlamaConfig) -> Params:
    """Spec tree for the stage-stacked FLOAT layout (the training path):
    stage arrays P("pp", …), embed/norm/head replicated (they run outside
    the pipelined region). For serving trees that may carry int8 pairs,
    ``place_stacked`` derives specs from the actual structure instead."""
    layer = param_specs(cfg)["layers"][0]
    stacked = jax.tree.map(lambda s: P("pp"), layer, is_leaf=lambda x: isinstance(x, P))
    return {
        "embed": P(),
        "stages": stacked,
        "final_norm": P(),
        "lm_head": P(),
    }


def _stage_apply(x: jax.Array, stage_layers: Params, cfg: LlamaConfig, cos, sin) -> jax.Array:
    """Run one stage's stacked layers over activations x [mb, S, D]: a scan
    of the one block body over the stacked axis. Every layer is attention
    with layer 0's window — what ``_refuse_mixed_layers`` and ``pp_forward``'s
    refusal of ``alt_window`` guarantee."""
    attend = partial(_sequence_attention, cfg=cfg)

    def layer_step(h, layer):
        return transformer_block(h, layer, cfg, 0, cos, sin, attend)[0], None

    x, _ = jax.lax.scan(layer_step, x, stage_layers)
    return x


def pp_forward(
    stacked: Params,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, S]
    mesh: Mesh,
    n_micro: int = 4,
    pp_axis: str = "pp",
) -> jax.Array:
    """Pipelined full-sequence forward: tokens [B, S] -> logits [B, S, V].

    ``B`` must divide into ``n_micro`` microbatches; bubble fraction is
    (S−1)/(n_micro+S−1)."""
    _refuse_mixed_layers(cfg)
    n_stages = mesh.shape[pp_axis]
    b, s = tokens.shape
    if b % n_micro:
        raise ValueError(f"batch {b} does not split into {n_micro} microbatches")
    if cfg.alt_window:
        # The stage body scans layers with ONE static attention mask;
        # Gemma-2's per-layer alternating window would need per-iteration
        # masks. Serve those models on the tp/ep paths instead.
        raise ValueError("pipeline parallelism does not support alternating windows")
    mb = b // n_micro

    positions = jnp.broadcast_to(jnp.arange(s), (mb, s))
    cos, sin = _rope_freqs(cfg, positions)

    x = embed_tokens(stacked, cfg, tokens)
    x_mb = x.reshape(n_micro, mb, s, -1)

    n_ticks = n_micro + n_stages - 1

    def pp_body(stages_local, x_all, cos_, sin_):
        # stages_local: stage arrays with local leading dim 1 — this
        # device's stage. x_all: every microbatch (replicated over pp).
        me = jax.lax.axis_index(pp_axis)
        layers_here = jax.tree.map(lambda a: a[0], stages_local)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            recv, outs = carry
            # Stage 0 consumes microbatch t (clamped; out-of-range ticks
            # produce garbage that never reaches outs). Other stages
            # consume what arrived over the ring last tick.
            src = x_all[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(me == 0, src, recv)
            y = _stage_apply(inp, layers_here, cfg, cos_, sin_)
            # Last stage banks microbatch t − (S−1) when in range.
            oi = t - (n_stages - 1)
            oc = jnp.clip(oi, 0, n_micro - 1)
            bank = (me == n_stages - 1) & (oi >= 0)
            prev_row = jax.lax.dynamic_index_in_dim(outs, oc, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(bank, y, prev_row), oc, 0
            )
            recv = jax.lax.ppermute(y, pp_axis, perm)
            return (recv, outs), None

        outs0 = jnp.zeros_like(x_all)
        (_, outs), _ = jax.lax.scan(
            tick, (jnp.zeros_like(x_all[0]), outs0), jnp.arange(n_ticks)
        )
        # Only the last stage's banked outputs are real; psum with the
        # others zeroed replicates them to every pp member.
        outs = jnp.where(me == n_stages - 1, outs, 0.0)
        return jax.lax.psum(outs, pp_axis)

    stage_spec = jax.tree.map(lambda a: P(pp_axis), stacked["stages"])
    y_mb = jax.shard_map(
        pp_body,
        mesh=mesh,
        in_specs=(stage_spec, P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(stacked["stages"], x_mb, cos, sin)

    return lm_logits(stacked, cfg, y_mb.reshape(b, s, -1))


def place_stacked(stacked: Params, cfg: LlamaConfig, mesh: Mesh) -> Params:
    """Place a stage-stacked tree on the mesh (stages over ``pp``). Specs
    derive from the actual tree structure, so int8 weight-only pairs
    ``{"q","s"}`` (models/quant.py) place too — both members carry the
    stage axis."""
    specs = {
        "embed": jax.tree.map(lambda a: P(), stacked["embed"]),
        "stages": jax.tree.map(lambda a: P("pp"), stacked["stages"]),
        "final_norm": P(),
        "lm_head": jax.tree.map(lambda a: P(), stacked["lm_head"]),
    }
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), stacked, specs
    )


def make_pp_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    n_micro: int = 4,
    lr: float = 3e-4,
):
    """Jitted pipelined training step; returns (step, init_state).

    Same causal-LM loss as models/train.py, gradients flow back through the
    pipeline ticks (ppermute transposes to the reverse rotation)."""
    import optax

    n_stages = mesh.shape["pp"]
    opt = optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=0.01)
    specs = pp_param_specs(cfg)
    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs, is_leaf=lambda x: isinstance(x, P)
    )
    repl = NamedSharding(mesh, P())

    def loss_fn(stacked, tokens):
        from kakveda_tpu.models.train import lm_loss_from_logits

        logits = pp_forward(stacked, cfg, tokens, mesh, n_micro=n_micro)
        return lm_loss_from_logits(logits, tokens)

    def _init(rng):
        from kakveda_tpu.models.llama import init_params

        stacked = split_stages(init_params(rng, cfg), cfg, n_stages)
        return stacked, opt.init(stacked)

    # Param shardings are pinned; the AdamW state (mu/nu mirror the param
    # tree) is left unspecified — GSPMD derives it from the init
    # computation, which keeps each stage's moments on its stage's devices.
    init_state = jax.jit(_init, out_shardings=(shardings, None))

    def _step(stacked, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(stacked, tokens)
        updates, opt_state = opt.update(grads, opt_state, stacked)
        stacked = optax.apply_updates(stacked, updates)
        return stacked, opt_state, loss

    step = jax.jit(
        _step,
        in_shardings=(shardings, None, repl),
        out_shardings=(shardings, None, repl),
        donate_argnums=(0, 1),
    )
    return step, init_state
