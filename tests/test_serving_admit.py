"""Admission two prompts to a program.

``ContinuousBatcher.admit_many`` prefills up to ``ADMIT_WIDTH`` (two) prompts
in one ``_admit_jit`` run over a [W, ...] scratch cache; the engine's pump
takes the waiting items two at a time in the order its pick gives. A pair
must serve what the same prompts admitted one by one serve, for every served
stack; the set of admit programs is widths 1 and 2 for each bucket the
traffic used, all built by that bucket's first admit; and the queue keeps its
rules: order, tenant promotion, cancellation, expiry, one queue-wait and one
TTFT observation a request.
"""

import json
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kakveda_tpu.core import metrics
from kakveda_tpu.models.generate import generate_tokens
from kakveda_tpu.models.hf_convert import hf_config_to_llama
from kakveda_tpu.models.llama import LlamaConfig, init_params
from kakveda_tpu.models.serving import (
    ContinuousBatcher,
    DeadlineExceededError,
    ServingEngine,
    _admit_jit,
)

CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
WINDOW = 512
TOL = 1e-4  # float32 stacks: the pair and the singles differ by summation order at most


def _cfg(kind: str) -> LlamaConfig:
    if kind == "int8-kv":
        return LlamaConfig.tiny(dtype=jnp.float32, kv_quant="int8")
    if kind in ("mistral-window-binds", "lfm2"):
        name, over = (
            ("judge-mistral-7b", {"sliding_window": 48}) if kind != "lfm2" else ("judge-lfm2-24b-a2b", {})
        )
        f = json.loads((CONFIGS / f"{name}.json").read_text())
        return hf_config_to_llama({**f, **f["rehearsal"]["model"], **over}, dtype=jnp.float32)
    return LlamaConfig.tiny(dtype=jnp.float32)


# (stack, the two prompts' lengths): one bucket, or 128 + 256 served at 256
CASES = [
    ("tiny", (40, 57)),
    ("tiny", (100, 200)),
    ("lfm2", (70, 90)),  # conv state at each row's own end, the experts' dispatch
    ("lfm2", (100, 200)),
    ("int8-kv", (100, 200)),
    ("mistral-window-binds", (100, 61)),  # a 48-row window inside both prompts
]


@pytest.mark.parametrize("kind,lens", CASES)
def test_a_pair_serves_what_the_same_prompts_admitted_alone_serve(kind, lens):
    cfg = _cfg(kind)
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(sum(lens))
    prompts = [rng.integers(3, 200, n).tolist() for n in lens]

    def pool(name):
        return ContinuousBatcher(params, cfg, batch_slots=4, max_len=WINDOW, chunk_steps=8, name=name)

    pair, alone = pool(f"pair-{kind}"), pool(f"alone-{kind}")
    width = metrics.get_registry().histogram("kakveda_serving_admit_width", "", ("engine",))
    w0 = width.labels(engine=f"pair-{kind}").count
    rp = pair.admit_many([dict(prompt_ids=p, max_new_tokens=24) for p in prompts])
    assert width.labels(engine=f"pair-{kind}").count - w0 == 1  # one program for both
    ra = [alone.admit(p, max_new_tokens=24) for p in prompts]
    slot_p = {st.req_id: s for s, st in pair.slots.items()}
    slot_a = {st.req_id: s for s, st in alone.slots.items()}
    # both share the larger bucket in the pair; alone each has its own
    assert {pair.slots[slot_p[r]].prompt_len for r in rp} == {max(ContinuousBatcher.bucket_for(n, WINDOW) for n in lens)}
    for p_id, a_id in zip(rp, ra):
        got, want = np.asarray(pair.last[slot_p[p_id]]), np.asarray(alone.last[slot_a[a_id]])
        assert np.abs(got - want).max() < TOL * max(1.0, np.abs(want).max())
    while pair.slots:
        pair.step()
    while alone.slots:
        alone.step()
    assert [pair.results[r] for r in rp] == [alone.results[r] for r in ra]
    assert all(len(pair.results[r]) == 24 for r in rp)


def test_a_pair_a_larger_bucket_would_cut_short_is_not_shared():
    """A shorter prompt moved into the larger bucket starts later in the window:
    where its budget would then overrun the window, the two are admitted one by
    one, and a prompt the window cannot hold fails alone."""
    cb = ContinuousBatcher(
        init_params(jax.random.PRNGKey(0), LlamaConfig.tiny(dtype=jnp.float32)),
        LlamaConfig.tiny(dtype=jnp.float32), batch_slots=2, max_len=WINDOW, chunk_steps=4, name="cut-short",
    )
    assert cb.shared_bucket([100, 200], [200, 200]) == 256
    assert cb.shared_bucket([100, 200], [300, 200]) is None  # 128 + 300 fits, 256 + 300 does not
    assert cb.shared_bucket([100, WINDOW], [8, 8]) is None
    with pytest.raises(ValueError):
        cb.admit_many([dict(prompt_ids=[5] * 100, max_new_tokens=300), dict(prompt_ids=[6] * 200, max_new_tokens=8)])
    assert len(cb.free) == 2 and not cb.slots  # nothing admitted
    with pytest.raises(ValueError):
        cb.admit_many([dict(prompt_ids=[5] * 3)] * 3)  # three is over the width


# --- the program set -----------------------------------------------------------------------------


@pytest.fixture()
def no_compile_cache():
    """A program found in the persistent cache is loaded, not compiled, and
    would count as nothing: keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


class _Compiles:
    """Backend compiles, as the benchmark's launcher counts them."""

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def during(self, fn):
        self.n, self.on = 0, True
        try:
            fn()
        finally:
            self.on = False
        return self.n


def test_the_admit_programs_are_widths_one_and_two_of_each_bucket_used(no_compile_cache):
    # widths of their own, so no program of another test is in the jit's cache
    cfg = LlamaConfig.tiny(dtype=jnp.float32, n_layers=1, d_model=40, n_heads=2, n_kv_heads=1, d_ff=72)
    params = init_params(jax.random.PRNGKey(0), cfg)
    compiles = _Compiles()
    before = _admit_jit._cache_size()
    eng = ServingEngine(params, cfg, batch_slots=4, max_len=WINDOW, chunk_steps=4, name="program-set")
    eng.close()
    assert _admit_jit._cache_size() == before  # nothing built at start
    cb = ContinuousBatcher(params, cfg, batch_slots=4, max_len=WINDOW, chunk_steps=4, name="program-set")
    cb.admit_many([dict(prompt_ids=[5] * 100), dict(prompt_ids=[6] * 90)])  # width 2 into 128
    assert _admit_jit._cache_size() == before + 2  # and width 1 with it
    assert compiles.during(lambda: cb.admit([7] * 70)) == 0
    while cb.slots:
        cb.step()
    cb.admit([8] * 200)  # width 1 into 256
    assert _admit_jit._cache_size() == before + 4
    assert compiles.during(lambda: cb.admit_many([dict(prompt_ids=[9] * 150), dict(prompt_ids=[4] * 20)])) == 0
    assert _admit_jit._cache_size() == before + 4
    # a second pool of the same shapes builds nothing either
    again = ContinuousBatcher(params, cfg, batch_slots=4, max_len=WINDOW, chunk_steps=4, name="program-set")
    assert compiles.during(lambda: again.admit_many([dict(prompt_ids=[5] * 100), dict(prompt_ids=[6] * 200)])) == 0
    assert _admit_jit._cache_size() == before + 4


# --- the queue -----------------------------------------------------------------------------------


CFG = LlamaConfig(
    vocab_size=264, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype=jnp.float32,
)


def _series(family: str, engine: str) -> int:
    return metrics.get_registry().snapshot().get(family, {}).get("series", {}).get(f"engine={engine}", {"count": 0})["count"]


def test_waiting_prompts_go_in_pairs_in_order_and_dropped_ones_leave_their_partner(monkeypatch):
    """Hold the loop inside its first admit while eight more requests queue,
    one of them cancelled and one expiring; then every live one is admitted
    in the cycle that finds them, two to a program in submit order, the odd
    one alone, and each is served what it would be served alone."""
    from kakveda_tpu.core import admission

    # the deadline-aware shed reads waits other tests left: the deadline here
    # is to pass while the request waits, not to be refused at submit
    monkeypatch.setattr(admission.get_admission(), "enabled", False)
    params = init_params(jax.random.PRNGKey(0), CFG)
    name = "pairs-in-order"
    eng = ServingEngine(params, CFG, batch_slots=12, max_len=64, chunk_steps=4, name=name)
    log, entered, release = [], threading.Event(), threading.Event()
    admit_many, step_async = eng.cb.admit_many, eng.cb.step_async

    def held_admit(requests):
        log.append([r["prompt_ids"][0] for r in requests])
        if not entered.is_set():
            entered.set()
            release.wait(30)
        return admit_many(requests)

    def marked_step():
        log.append("chunk")
        return step_async()

    eng.cb.admit_many, eng.cb.step_async = held_admit, marked_step
    prompts = {i: [i, i + 1, i + 2] for i in range(10, 19)}
    waits0, ttft0 = _series("kakveda_serving_queue_wait_seconds", name), _series("kakveda_serving_ttft_seconds", name)
    try:
        futs = {10: eng.submit(prompts[10], max_new_tokens=6)}
        assert entered.wait(60)
        for i in range(11, 19):
            futs[i] = eng.submit(prompts[i], max_new_tokens=6, deadline_s=0.05 if i == 14 else None)
        eng.cancel(futs[12])
        time.sleep(0.1)  # 14's deadline passes while it waits
        release.set()
        outs = {i: f.result(timeout=120) for i, f in futs.items() if i not in (12, 14)}
    finally:
        eng.close()
    assert futs[12].cancelled()
    with pytest.raises(DeadlineExceededError):
        futs[14].result(timeout=5)
    # 11+12 and 13+14 lost one each; 15+16, 17+18 are pairs; all in one cycle
    first = log.index("chunk")
    cycle = log[first + 1:log.index("chunk", first + 1)]
    assert log[:first] == [[10]] and cycle == [[11], [13], [15, 16], [17, 18]]
    for i, out in outs.items():
        assert out == generate_tokens(params, CFG, prompts[i], max_new_tokens=6, max_len=64)
    assert _series("kakveda_serving_queue_wait_seconds", name) - waits0 == len(outs)
    assert _series("kakveda_serving_ttft_seconds", name) - ttft0 == len(outs)


def _fair_engine(promote: int, free: int = 8):
    # _pick_group_locked touches only this state (test_tenants.py's way)
    eng = SimpleNamespace(
        _tenant_fair=True, _promote_rounds=promote, _fair_served={}, _fair_table_max=512,
        _fair_picks=0, _fair_promotions=0, _waiting=[], cb=SimpleNamespace(free=list(range(free))),
    )
    eng._pick_waiting_locked = lambda: ServingEngine._pick_waiting_locked(eng)
    return eng


def _item(tenant):
    return ("req", SimpleNamespace(tenant=tenant, fair_rounds=0))


def test_groups_keep_the_tenant_pick_and_its_promotion_bound():
    promote = 3
    eng = _fair_engine(promote)
    # the deficit pick: the light tenant leads the first pair
    eng._fair_served = {"f": 5}
    eng._waiting = [_item("f"), _item("f"), _item("v")]
    group = ServingEngine._pick_group_locked(eng)
    assert [it[-1].tenant for it in group] == ["v", "f"] and len(eng._waiting) == 1
    # a victim that looks heavy is still picked within `promote` + 1 picks,
    # as when slots were handed one by one (test_tenants.py)
    eng = _fair_engine(promote)
    eng._fair_served = {"v": 1000}
    victim = _item("v")
    eng._waiting = [_item("f") for _ in range(3)] + [victim]
    picks = 0
    while True:
        group = ServingEngine._pick_group_locked(eng)
        assert len(group) == 2
        eng._waiting += [_item("f") for _ in group]  # the flooder refills
        if victim in group:
            picks += group.index(victim) + 1
            break
        picks += len(group)
    assert picks <= promote + 1 and eng._fair_promotions == 1
    # no more than the free slots
    eng = _fair_engine(promote, free=1)
    eng._waiting = [_item(""), _item("")]
    assert len(ServingEngine._pick_group_locked(eng)) == 1
