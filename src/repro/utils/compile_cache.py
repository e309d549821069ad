"""One place that decides where JAX keeps its persistent compile cache.

The entry points (``launch/train.py``, ``launch/serve_lda.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call ``enable_compile_cache``
before their first compilation. A cache entry's key includes the cache
path, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set, and no other
directory then, otherwise ``.jax_cache/`` at the root of the checkout
(listed in ``.gitignore``). JAX opens the cache at its first compilation,
so the call comes before that.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
