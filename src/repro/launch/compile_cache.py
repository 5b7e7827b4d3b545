"""JAX's persistent compilation cache, set up the same way by every entry
point that runs on the chip (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``).

The directory is part of what makes a cache hit, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
set here; otherwise the cache lives at ``<repo>/.jax_cache`` (listed in
``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed fallback location: the repository root's ``.jax_cache``
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
