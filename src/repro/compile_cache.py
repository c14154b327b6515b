"""JAX's persistent compilation cache, as every entry point turns it on.

A ResNet-50 device program takes seconds to compile (its weights enter the
program as constants), and each serving replica compiles every batch
bucket.  The directory is part of the cache's identity, so it is fixed:
``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the variable itself and
nothing is set here), otherwise ``.jax_cache/`` at the repository root.
How much of a compile a later run finds there is measured, not assumed:
``benchmarks/chip_probes.py compile_cache``, results in PERF.md.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
