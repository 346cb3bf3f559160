"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` from ``main()``; importing this
module configures nothing.
"""
from __future__ import annotations

import os

# the checkout root: src/repro/launch/ -> three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Put JAX's persistent compilation cache in one fixed place and return
    the directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set here.  Otherwise the cache goes to ``.jax_cache/`` at the
    checkout root (git-ignored).  The path is fixed — never a temp name, a
    pid or a time — because it is part of the cache's key.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
