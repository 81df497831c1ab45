"""JAX's persistent compilation cache, for entry points only.

Scripts (``chip_smoke.py``, ``python -m repro.serving.loadgen``,
``examples/*``, ``benchmarks/*``) call :func:`enable_compile_cache` once at
start-up, so processes that compile the same programs share them. Library
code never calls it: importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout root (src/repro/launch/cache.py, three levels down)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
#: where the cache lives unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise;
#: a fixed path, so a later process finds what an earlier one stored
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
