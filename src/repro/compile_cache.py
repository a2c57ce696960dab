"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (`chip_smoke.py`, `repro.launch.serve`, `benchmarks/run`,
the `repro.tune` CLI) calls :func:`enable_compile_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and no directory is set in code.  Otherwise the cache
lives at the fixed ``<checkout>/.jax_cache`` (gitignored) — never a
temporary, per-process or time-based path: the directory is part of what
a later run must find again.  Either way the size and compile-time
thresholds drop to zero, so the Pallas kernels, which compile in well
under JAX's default one-second bar, are cached too.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
