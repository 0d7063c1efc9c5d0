"""The model family seam (``families/<family>.py``): nothing reads differently
after the move, and a second family arrives as new files alone."""

import filecmp
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from harness import manifest

BENCH = Path(__file__).resolve().parents[1]
MISTRAL = json.loads((BENCH / "configs" / "judge-mistral-7b.json").read_text())
TINY = {**MISTRAL, **MISTRAL["rehearsal"]["model"]}


def _tree_hash(params) -> str:
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


# Taken on the parent tree (PR 25, ``harness/weights.make_params``) at the
# rehearsal sizes, before anything moved: the same seed gives the same arrays.
@pytest.mark.parametrize("seed,digest", [
    (7, "e613bc3da1db480d9937dc212360ea26a9859f456539e85ef954b247a86877f4"),
    (2_500_000_011, "0b3ddf302aa74ed9debba38606c60ecd5996e40a649ef8b43b9015c14d566076")])
def test_the_move_left_every_weight_bit_for_bit(seed, digest):
    model = manifest.load_module("families", "mistral_model")
    params = model.make_params(seed, TINY)
    assert _tree_hash(params) == digest
    # what the reference takes layer by layer is what the program was given
    assert np.array_equal(np.asarray(model.layer_weights(seed, TINY, 1)["w_down"]), np.asarray(params["layers"][1]["w_down"]))
    assert np.array_equal(np.asarray(model.head_weights(seed, TINY)["lm_head"]), np.asarray(params["lm_head"]))


def test_flash_prefill_work_by_hand():
    fam = manifest.load_module("families", "mistral")
    # causal attention among 256 tokens, 32 heads x 128: 4 x 256^2/2 x 4096 operations;
    # q and out 256 x 4096, k and v 256 x 1024, two bytes each
    assert fam.work(MISTRAL, "flash_prefill", rows=256) == {
        "flops": 4 * (256 * 256 / 2) * 32 * 128, "bytes": 2 * (2 * 256 * 4096 + 2 * 256 * 1024)}
    with pytest.raises(KeyError):
        fam.work(MISTRAL, "scan")


def test_the_same_trace_reads_the_same_shares_as_before_the_move():
    """A synthetic trace and window; the four numbers were read by the PARENT
    tree's readers (PR 25: ``peaks.lm_*`` and ``op_share``'s own arithmetic)
    from the same input, before anything moved."""
    import random

    from harness import prom

    rng = random.Random(5)
    recs = {f"p{i}": {"ids": list(range(rng.randint(65, 256))), "out": list(range(64))} for i in range(480)}
    trace = {"programs": {"jit__admit_jit(1)": [0.3658, 36], "jit__step_chunk_jit(2)": [2.6523, 25]},
             "ops": {'%x = custom-call(...), custom_call_target="tpu_custom_call"': [0.0123, 228, "jit__admit_jit(1)"]}}
    cell = manifest.load_cell("chat-short")
    ctx = {"trace": trace, "device": {"kind": "TPU v5 lite"}, "sizes": cell.config["sizes"], "cell": cell, "chat_records": recs,
           "prom_before": prom.parse('kakveda_serving_chunk_seconds_count{engine="e"} 1000\n'),
           "prom_after": prom.parse('kakveda_serving_chunk_seconds_count{engine="e"} 4840\n')}
    parent = {"prefill_mfu": 42.602637374277585, "decode_mfu": 0.2118590554735465,
              "decode_roofline": 50.608140293560496, "flash_roofline": 11.866311682897047}
    for name, value in parent.items():
        desc = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
        assert manifest.load_module("readers", desc["reader"]).read(ctx, desc["params"]) == value, name


def test_a_configuration_its_family_cannot_build_is_refused(tmp_path):
    root = _copy(tmp_path)
    cfg = root / "benchmarks" / "configs" / "judge-mistral-7b.json"
    config = json.loads(cfg.read_text())
    cfg.write_text(json.dumps({k: v for k, v in config.items() if k != "num_key_value_heads"}))
    with pytest.raises(manifest.ManifestError, match="num_key_value_heads"):
        manifest.load_cell("chat-short", root=root)
    cfg.write_text(json.dumps({**config, "family": "nope"}))
    with pytest.raises(manifest.ManifestError, match="families"):
        manifest.load_cell("chat-short", root=root)


# --- a second family, from added files alone --------------------------------------------


def _copy(tmp_path: Path) -> Path:
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns(".*", "__pycache__"))
    return tmp_path


def _add_the_second_family(root: Path) -> set:
    """What a ``model_config`` PR adds: a family module, a configuration, a
    cell on chat-short's traffic, its limits, and their entries. Returns the
    files added."""
    bench = root / "benchmarks"
    shutil.copy(BENCH / "tests" / "second_family" / "mixtral_tiny.py", bench / "families" / "mixtral_tiny.py")
    config = {**MISTRAL, "name": "judge-mixtral-tiny", "family": "mixtral_tiny", "model_type": "mixtral",
              "num_local_experts": 4, "num_experts_per_tok": 2}
    (bench / "configs" / "judge-mixtral-tiny.json").write_text(json.dumps(config))
    # a limit of its own, from CPU readings at the rehearsal sizes on six seeds: sound 0.00023-0.0064 (a
    # top-2 choice that bf16 and float32 make differently now and then reads a gap of 0.2-1.2 on that
    # token), ``chat_token`` 0.0137-0.110. A proof of the seam, not a calibrated cell.
    (bench / "limits" / "chat-moe.json").write_text(json.dumps(
        {"cell": "chat-moe", "limits": {"logit_gap_mean": 0.009, "unserved": 0}}))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "judge-mixtral-tiny", "source": "test", "file": "benchmarks/configs/judge-mixtral-tiny.json",
                          "reduced": [], "why": "the seam's proof"})
    bm["workloads"].append({"name": "chat-moe", "config": "judge-mixtral-tiny", "traffic": "chat-short", "chips": 1,
                            "why": "the seam's proof"})
    for m in bm["end_to_end"]:
        if "chat-short" in m.get("workloads", []):
            m["workloads"].append("chat-moe")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return {Path("families/mixtral_tiny.py"), Path("configs/judge-mixtral-tiny.json"), Path("limits/chat-moe.json")}


def _rehearse(root: Path, cell: str, seed: int, fault: str) -> dict:
    cmd = [sys.executable, str(root / "benchmarks" / "run.py"), "--workload", cell, "--seed", str(seed), "--seconds", "3",
           "--rehearse-on-cpu"] + (["--fault", fault] if fault else [])
    # the copy compiles what the tree has compiled before: the same CPU programs, the tree's cache
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": os.environ.get("JAX_COMPILATION_CACHE_DIR", str(BENCH / ".jax_cache"))}
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip() == "", "a rehearsal prints no result"
    line = [ln for ln in p.stderr.splitlines() if "would have printed: " in ln][-1]
    return json.loads(line.split("would have printed: ", 1)[1])


@pytest.fixture(scope="module")
def second_family_root(tmp_path_factory):
    root = _copy(tmp_path_factory.mktemp("second_family"))
    for name in ("kakveda_tpu", "config"):  # the program, as the checkout has it beside benchmarks/
        os.symlink(BENCH.parent / name, root / name)
    added = _add_the_second_family(root)
    # every file the benchmark had is still there, byte for byte; only the added ones are new
    ours = {p.relative_to(BENCH) for p in BENCH.rglob("*") if p.is_file()
            and not any(part.startswith(".") or part == "__pycache__" for part in p.relative_to(BENCH).parts)}
    theirs = {p.relative_to(root / "benchmarks") for p in (root / "benchmarks").rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    assert theirs - ours == added and not ours - theirs
    for rel in ours:
        assert filecmp.cmp(BENCH / rel, root / "benchmarks" / rel, shallow=False), rel
    return root


def test_the_second_family_resolves_and_counts_its_own_work(second_family_root):
    cell = manifest.load_cell("chat-moe", root=second_family_root)
    assert cell.config["family"] == "mixtral_tiny" and {m["name"] for m in cell.end_to_end} >= {"ttft_p50_ms", "tpot_p95_ms", "setup_s"}
    fam = manifest.load_module("families", "mixtral_tiny", second_family_root / "benchmarks")
    dense = manifest.load_module("families", "mistral")
    d, ff, layers = 4096, 14336, 12
    one = fam.work(cell.config, "prefill", tokens=1, attended=1, head_rows=1)
    base = dense.work(MISTRAL, "prefill", tokens=1, attended=1, head_rows=1)
    # two experts a token, not four: one more expert's 3 d ff than the dense block, and the router
    assert one["flops"] - base["flops"] == 2 * layers * (3 * d * ff + d * 4)
    assert one["bytes"] - base["bytes"] == 2 * layers * (3 * d * ff + d * 4)  # one token touches two experts
    many = fam.work(cell.config, "decode", tokens=16, attended=16, head_rows=16, steps=8)
    assert many["bytes"] == 8 * (dense.weight_bytes(MISTRAL) + 2 * layers * (3 * 3 * d * ff + d * 4))  # all four read
    with pytest.raises(manifest.ManifestError, match="num_local_experts"):
        bad = second_family_root / "benchmarks" / "configs" / "judge-mixtral-tiny.json"
        good = bad.read_text()
        try:
            bad.write_text(json.dumps({k: v for k, v in json.loads(good).items() if k != "num_local_experts"}))
            manifest.load_cell("chat-moe", root=second_family_root)
        finally:
            bad.write_text(good)


def test_the_second_family_is_correct_on_the_sound_path(second_family_root):
    out = _rehearse(second_family_root, "chat-moe", 2_500_000_211, "")
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0 and out["numbers"]["served_tokens"] > 0


def test_the_second_family_with_a_token_altered_is_not_correct(second_family_root):
    out = _rehearse(second_family_root, "chat-moe", 2_500_000_211, "chat_token")
    assert out["correct"] is False, out["compared"]
