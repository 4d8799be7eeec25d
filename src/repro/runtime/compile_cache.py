"""Where compiled programs persist between processes.

``use_compile_cache()`` is called by the entry points (``repro.cli``,
``repro.launch.train``, ``chip_smoke.py``) before they compile anything,
never at import.  jax reads ``JAX_COMPILATION_CACHE_DIR`` itself, so
where that is set the cache stays there and no other directory is set.
Otherwise the cache is ``.jax_cache/`` at the repository root: a fixed
path, because the path is part of the cache key and a directory that
moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
