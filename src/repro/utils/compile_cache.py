"""Where JAX keeps its persistent compilation cache.

A cache entry is found again only under the same directory, so the
directory must not move between runs: no temp, pid- or time-derived
path.  ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured by JAX
itself and nothing is configured here; otherwise the cache lives in
``.jax_cache`` inside the checkout (listed in ``.gitignore``).
Call before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def use_compile_cache(checkout: str | os.PathLike) -> str:
    """Enable the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` if set (left to JAX), else
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = Path(checkout).resolve() / CACHE_DIRNAME
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
