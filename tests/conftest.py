"""Test bootstrap: run everything on a simulated 8-device CPU mesh.

``JAX_PLATFORMS=cpu`` in the environment is all it takes to keep JAX off an
accelerator; it is defaulted here, before jax is imported, for a bare
``pytest`` run. The host-device-count flag must likewise be set before jax
initializes its backends. Compiled programs go to the persistent cache the
entry points use (``ops/device.setup_compile_cache``): the suite builds the
same small programs in hundreds of fresh jit wrappers and server
subprocesses, and finds them there instead of compiling them again.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

from kakveda_tpu.ops.device import setup_compile_cache  # noqa: E402

setup_compile_cache()

import pytest  # noqa: E402


@pytest.fixture()
def tmp_data_dir(tmp_path):
    return tmp_path / "data"


@pytest.fixture
def decode_parity():
    """Cached greedy decode must reproduce the full forward's argmax chain,
    and the slot pool must reproduce the cached decode — the serving-path
    invariant every model family asserts, over the three paths that hand
    ``llama.transformer_block`` a state of their own (none, one scalar write
    position, per-slot positions). A fixture (not a conftest import) so it
    works under any pytest import mode."""
    import jax.numpy as jnp

    from kakveda_tpu.models.generate import generate_tokens
    from kakveda_tpu.models.llama import forward, mask_pad_vocab
    from kakveda_tpu.models.serving import ContinuousBatcher

    def check(params, cfg, prompt, n=8):
        greedy_cached = generate_tokens(params, cfg, prompt, max_new_tokens=n)
        toks = list(prompt)
        for _ in range(n):
            logits = forward(params, cfg, jnp.asarray([toks]))
            # Same padded-vocab masking as the decode path — without it a
            # checkpoint with effective_vocab set could argmax a pad column
            # here and spuriously fail (or hide a masking bug).
            toks.append(int(jnp.argmax(mask_pad_vocab(logits[0, -1], cfg))))
        assert greedy_cached == toks[len(prompt) :]
        pool = ContinuousBatcher(params, cfg, batch_slots=2, max_len=64, chunk_steps=4)
        assert pool.run_all([list(prompt)], max_new_tokens=n) == [greedy_cached]

    return check
