"""JAX's persistent compilation cache, for the entry points that drive the
chip (`chip_smoke.py`, `benchmarks/run.py`, `benchmarks/qos_serving.py`).

A fresh machine starts with no compiled code, and a full-width step
compiles for seconds. The cache keeps those programs across the processes
of one run, and across runs where the directory survives. Its path is part
of every entry's key, so it is fixed: `$JAX_COMPILATION_CACHE_DIR` when
set (JAX reads that variable itself, and nothing here overrides it),
otherwise `.jax_cache/` at the repository root. Importing the package sets
no cache, so tests never write one.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DIR_NAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn the persistent cache on for this process and return its
    directory. Call before the first compile: JAX opens the cache once."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(root), DIR_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
