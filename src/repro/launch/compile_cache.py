"""JAX's persistent compilation cache, placed from outside.

Entry points (``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up; library
code and tests never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing here overrides it. Otherwise the cache lives at a fixed
path in the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is part of
what makes an entry findable again, so it never depends on a temp directory,
a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<repo>/.jax_cache`` — this file is ``<repo>/src/repro/launch/...``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
