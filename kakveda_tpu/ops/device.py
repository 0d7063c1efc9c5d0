"""Backend identity, the persistent compile cache, and the device report.

Everything that asks "what are we running on?" asks here, so the answer is
one thing: the platform JAX initialised. A backend that cannot initialise
raises — it is never read as "not a TPU", which would silently select the
f32 index store and the XLA paths on a machine that was meant to use the
chip.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax


def is_tpu_backend() -> bool:
    """True when the default backend is TPU hardware. A backend-init
    failure propagates."""
    return jax.default_backend() == "tpu"


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Called once by every entry point before the first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no code
    sets another directory. Otherwise the cache lives at
    ``<checkout>/.jax_cache``, resolved from this package's own location:
    the directory is part of what a cached program is found by, so it must
    not depend on the working directory, a pid or a clock.

    The size/time floors below which JAX skips writing an entry are
    lowered to zero: a machine that is thrown away after each run pays
    every compile again, the small ones included.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def device_report() -> dict:
    """Platform, device kind and count as JAX reports them."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }
