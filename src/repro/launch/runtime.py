"""Process-level JAX setup shared by the entry points' ``main()``.

Called from ``main()``, never at import: importing a module must not
change how another program's JAX behaves.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's compile-cache directory (gitignored).  The path is part
#: of what a cache entry is found under, so it stays fixed across runs.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_jax() -> str:
    """Turn on JAX's persistent compilation cache and keep libtpu's logs
    out of the shared temporary directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here overrides it; otherwise the cache lives in
    :data:`CACHE_DIR`.  Returns the directory in use.
    """
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
