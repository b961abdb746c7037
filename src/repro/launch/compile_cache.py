"""JAX's persistent compilation cache for the program's entry points.

Called by ``repro.launch.train`` and ``chip_smoke.py``, never on import, so
the tests stay off the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already uses that directory and no other is set here; otherwise the cache
lives at the fixed path ``<repo>/.jax_cache`` (gitignored).  The path is part
of the cache key, so it is never a temporary, per-process or timestamped
directory.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    The cache key includes the HLO metadata (op names, source lines), which
    JAX leaves out by default.  Without it, an executable loaded from the
    cache carries the op names of whichever version of the program compiled
    it first; with it, the executable that runs always carries the named
    scopes of the program that asked for it, which is what the benchmark
    charges device time to blocks by (``benchmarks/chip/scopes.py``)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.normpath(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
