"""Where entry points that serve on a chip keep JAX's persistent compile
cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set nothing is
changed here.  Otherwise the cache goes to a fixed ``<checkout>/.jax_cache``
(listed in .gitignore): the path is part of the cache key, so a temp-,
pid- or time-derived directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache(checkout: Path = CHECKOUT) -> str:
    """Point JAX's persistent compile cache at its directory (call before
    the first compile).  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
