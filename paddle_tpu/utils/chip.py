"""What every entry point that runs on the chip does first: place JAX's
persistent compilation cache, and name the device in what it prints.

Called by ``chip_smoke.py``, each ``bench.py`` row,
``tools/perf_probe.py`` and ``serving/replica.py``'s main — never at package import, so the CPU
test suite keeps JAX's default (no persistent cache).
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Turn the persistent compilation cache on and return where it
    lives. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own handling of
    the variable places the cache and no directory is set here. Unset,
    the cache goes to ``<checkout>/.jax_cache`` — one fixed, git-ignored
    path (the path is part of the cache key: a directory that moves
    never hits). Either way every program is kept, however quick its
    compile, so that a second process compiles nothing the first did."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_record() -> dict:
    """The device as JAX reports it, for every result a benchmark or
    probe prints — never the ``JAX_PLATFORMS`` request."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}
