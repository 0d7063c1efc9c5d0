"""A decode chunk works on a static prefix of each K/V slab.

``ContinuousBatcher._grow_valid`` picks ``attend_len`` per dispatched chunk
(512, 1,024, ... capped at the slot window) so that it covers every row a
live slot reads or writes; the chunk programs run ``_forward_wide`` on
``slab[:, :, :attend_len]`` (``_slab_prefix``) and write it back. The rows
left out are rows the mask rejects anyway, so every served token must equal
the one the same pool serves when it works on the whole slab — for every
served stack, for both chunk flavours, across a change of length mid-life —
and the chosen length must never cut a live slot's valid row off.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kakveda_tpu.core import metrics as _metrics
from kakveda_tpu.models.hf_convert import hf_config_to_llama
from kakveda_tpu.models.llama import LlamaConfig, init_params
from kakveda_tpu.models.serving import ContinuousBatcher

CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
WINDOW = 1024  # two lengths of the rule: 512 and the window


def _rehearsal(name: str, **over) -> LlamaConfig:
    """A benchmark configuration at its rehearsal widths, in float32."""
    f = json.loads((CONFIGS / f"{name}.json").read_text())
    return hf_config_to_llama({**f, **f["rehearsal"]["model"], **over}, dtype=jnp.float32)


def _cfg(kind: str) -> LlamaConfig:
    if kind == "tiny":
        return LlamaConfig.tiny(dtype=jnp.float32)
    if kind == "int8-kv":
        return LlamaConfig.tiny(dtype=jnp.float32, kv_quant="int8")
    if kind == "mistral-window-binds":
        return _rehearsal("judge-mistral-7b", sliding_window=48)
    return _rehearsal("judge-lfm2-24b-a2b")  # conv + attention + experts


def _pool(params, cfg, name, *, spec_k=0, slots=2):
    return ContinuousBatcher(
        params, cfg, batch_slots=slots, max_len=WINDOW, chunk_steps=8, spec_k=spec_k, name=name
    )


def _record_lengths(cb, whole_slab=False):
    """Wrap the pool's one length rule: record (steps asked, positions and
    active slots before, length chosen, the active slots' validity as the
    chunk is dispatched) per dispatch; ``whole_slab`` forces the window,
    which is the program before the rule."""
    seen, grow = [], cb._grow_valid

    def wrapped(steps):
        pos, active = cb._pos_np.copy(), sorted(cb.slots)
        chosen = grow(steps)
        seen.append((steps, pos, active, chosen, cb._kv_np[active].copy()))
        return cb.max_len if whole_slab else chosen

    cb._grow_valid = wrapped
    return seen


def _prompts():
    rng = np.random.default_rng(0)
    # bucket 512 -> the window's length; bucket 8 and 128 -> 512. Two slots:
    # the long and the short share chunks, then the third takes the long's place.
    return [rng.integers(3, 200, n).tolist() for n in (500, 5, 100)]


@pytest.mark.parametrize(
    "kind,spec_k",
    [
        ("tiny", 0), ("mistral-window-binds", 0), ("lfm2", 0), ("int8-kv", 0),
        ("tiny", 4), ("mistral-window-binds", 4), ("int8-kv", 4),  # a conv stack refuses speculation
    ],
)
def test_prefix_attention_serves_the_whole_slab_tokens(kind, spec_k):
    cfg = _cfg(kind)
    params = init_params(jax.random.PRNGKey(1), cfg)
    ruled = _pool(params, cfg, f"attend-{kind}-{spec_k}", spec_k=spec_k)
    whole = _pool(params, cfg, f"attend-{kind}-{spec_k}-whole", spec_k=spec_k)
    seen = _record_lengths(ruled)
    _record_lengths(whole, whole_slab=True)

    got = ruled.run_all(_prompts(), max_new_tokens=40)
    assert got == whole.run_all(_prompts(), max_new_tokens=40)
    assert all(len(o) == 40 for o in got)
    # the pool changed length while the short request was alive, both ways round
    lengths = [rec[3] for rec in seen]
    assert set(lengths) == {512, WINDOW} and lengths[0] == WINDOW and lengths[-1] == 512
    if spec_k:
        assert ruled.spec_stats["chunks"] > 0  # verify chunks ran among the plain ones


def _check(cb, seen, child, before):
    """One dispatch's invariant, from what the wrapper saw and what the
    histogram got."""
    steps, pos, active, chosen, valid = seen[-1]
    assert (child.count - before[0], child.sum - before[1]) == (1, float(chosen))
    need = int(pos[active].max()) + steps
    assert min(need, cb.max_len) <= chosen <= cb.max_len
    assert chosen in (512, cb.max_len) and (chosen == 512) == (need <= 512)
    assert valid.any(axis=1).all() and not valid[:, chosen:].any()


@pytest.mark.parametrize("seed,spec_k", [(0, 0), (1, 0), (2, 0), (3, 4), (4, 4)])
def test_attend_len_covers_every_live_row(seed, spec_k):
    """Random pools: admits of every bucket, retirements, the overshoot chunk
    a pipelined loop dispatches after a retirement it has not seen yet, a
    freed place admitted into again, idle slots whose position runs far
    ahead of every live one."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, n_layers=1, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    cb = _pool(params, cfg, f"attend-inv-{seed}", spec_k=spec_k, slots=4)
    seen = _record_lengths(cb)
    child = cb._mx["attend_rows"]
    rng = np.random.default_rng(seed)
    lens = (3, 17, 120, 250, 500, 700)
    in_flight, idle_planted, admits, overshot = None, 0, 0, 0
    for it in range(48):
        if cb.free and rng.random() < (0.9 if it < 3 else 0.35):
            cb.admit(rng.integers(3, 200, int(rng.choice(lens))).tolist(), int(rng.integers(4, 28)))
            admits += 1
        for slot in cb.free:  # an idle slot's mirror drifts; here, far past every live one
            if rng.random() < 0.5:
                cb._pos_np[slot] = WINDOW - 16
                idle_planted += 1
        if not cb.slots:
            continue
        before = (child.count, child.sum)
        if spec_k:  # a verify chunk in flight refuses admits: this flavour runs unpipelined
            cb.step()
        else:  # as the engine loop: the next chunk goes out before this one's tokens are read,
            handle = cb.step_async()  # so a request that just finished still rides it (overshoot)
            overshot += len(cb.process_chunk(in_flight))
            in_flight = handle
        _check(cb, seen, child, before)
    assert idle_planted and admits > cb.B and {rec[3] for rec in seen} == {512, WINDOW}
    assert cb.spec_stats["chunks"] > 0 if spec_k else overshot
    text = _metrics.get_registry().render()
    assert f'kakveda_serving_attend_rows_count{{engine="attend-inv-{seed}"}} {len(seen)}' in text


# --- compiled for the chip, without the chip: the loop holds no whole slab ---------------


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (the TPU's compiler is installed; nothing runs)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_chunk_program_for_the_chip_keeps_whole_slabs_out_of_its_loop(one_chip, no_compile_cache):
    """`chat-short`'s widths and pool (two layers of them), compiled for the
    v5e at ``attend_len`` 512: XLA keeps a slab in another layout inside the
    ``while`` than at the program's boundary, and with the prefix cut inside
    the loop it materialised a slice of every slab at every step. Cut once,
    outside the scan, the body names no 2,048-row array at all, and the
    program's temporaries shrink."""
    import re

    from kakveda_tpu.models.llama import init_cache
    from kakveda_tpu.models.serving import _step_chunk_jit

    cfg = hf_config_to_llama(
        {**json.loads((CONFIGS / "judge-mistral-7b.json").read_text()), "num_hidden_layers": 2}, dtype=jnp.bfloat16
    )
    slots, window = 16, 2048

    def shaped(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)), jnp.bfloat16)
    cache = shaped(jax.eval_shape(lambda: init_cache(cfg, batch=slots, max_len=window)))
    args = (
        params, cfg, cache, arg((slots, cfg.vocab_size), jnp.float32), arg((slots,), jnp.int32),
        arg((slots, window), jnp.bool_), arg((slots,), jnp.int32), arg((slots,), jnp.float32), arg((2,), jnp.uint32), 8,
    )
    compiled = {n: _step_chunk_jit.lower(*args, n).compile() for n in (512, window)}
    bodies = {}
    for n, c in compiled.items():
        text = c.as_text()
        body = re.search(r"body=%([\w.\-]+)", text).group(1)
        bodies[n] = re.search(r"\n%" + re.escape(body) + r" \(.*?\n\}", text, re.S).group(0)
    assert f"[{slots},8,{window},128]" in bodies[window]  # the pattern finds a slab where there is one
    assert f"[{slots},8,{window},128]" not in bodies[512] and f"[{slots},8,512,128]" in bodies[512]
    assert not re.search(rf"\[{slots},8,512,128\]\S* slice\(", bodies[512])  # no materialised cut of a slab a step
    temp = {n: c.memory_analysis().temp_size_in_bytes for n, c in compiled.items()}
    assert temp[512] < temp[window]


def test_one_qkv_weight_leaves_no_weight_or_activation_copy_in_the_programs_for_the_chip(one_chip, no_compile_cache):
    """The same widths and pool, the chunk at 512 rows and the admit at
    bucket 128, compiled for the v5e from three q/k/v weights a layer and
    from the one ``llama.fuse_qkv`` makes. Three weights arrive in the
    default layout and the dots want the contraction axis minor, so each
    program re-lays every one out at every call, and inside the loop the
    separate ``wq`` dot waits for a copy of its layer's normed activation.
    One weight needs neither."""
    import re

    from kakveda_tpu.models.llama import fuse_qkv, init_cache
    from kakveda_tpu.models.serving import _admit_jit, _step_chunk_jit

    cfg = hf_config_to_llama(
        {**json.loads((CONFIGS / "judge-mistral-7b.json").read_text()), "num_hidden_layers": 2}, dtype=jnp.bfloat16
    )
    slots, window, d = 16, 2048, cfg.d_model

    def shaped(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    three = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = shaped(jax.eval_shape(lambda: init_cache(cfg, batch=slots, max_len=window)))
    last, valid, per_slot = arg((slots, cfg.vocab_size), jnp.float32), arg((slots, window), jnp.bool_), arg((slots,), jnp.int32)
    widths = {cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim}
    weight = rf"bf16\[{d},(?:{'|'.join(map(str, widths))})\]"  # wq, wk / wv, wqkv
    found = {}
    for name, tree in (("three", three), ("one", jax.eval_shape(fuse_qkv, three))):
        params = shaped(tree, jnp.bfloat16)
        chunk = _step_chunk_jit.lower(
            params, cfg, cache, last, per_slot, valid, per_slot, arg((slots,), jnp.float32), arg((2,), jnp.uint32), 8, 512
        ).compile()
        admit = _admit_jit.lower(params, cfg, cache, last, arg((1, 128), jnp.int32), arg((1,), jnp.int32), valid, per_slot).compile()
        text = chunk.as_text()
        body = re.search(r"body=%([\w.\-]+)", text).group(1)
        found[name] = {
            "entry": re.findall(rf"= {weight}\S* copy\(", re.search(r"\nENTRY .*?\n\}", text, re.S).group(0)),
            "body": re.findall(
                rf"= \(bf16\[{slots},{d}\]\S*, .*? copy-start\(",
                re.search(r"\n%" + re.escape(body) + r" \(.*?\n\}", text, re.S).group(0),
            ),
            "admit": re.findall(rf"= bf16\[(?:{d},{d}|1024,{d})\]\S* copy\(", admit.as_text()),
            "temp": chunk.memory_analysis().temp_size_in_bytes,
        }
    three, one = found["three"], found["one"]
    assert three["entry"] and three["body"] and three["admit"]  # the patterns find what is there
    assert not one["entry"] and not one["body"] and not one["admit"]
    assert one["temp"] < three["temp"]
