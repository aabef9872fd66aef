"""Where the program keeps JAX's persistent compilation cache.

A cache entry is found again only under the same directory, so the path is
fixed: never a temp directory, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def configure_compile_cache(checkout: str | Path) -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and nothing
    else is chosen here; otherwise the cache lives in ``.jax_cache`` under
    ``checkout``, the root of the checkout the entry point runs from
    (listed in its .gitignore).  Call before the first compile.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(checkout).resolve() / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
