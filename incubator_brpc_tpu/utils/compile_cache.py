"""JAX persistent compilation cache for the repo's programs.

``chip_smoke.py`` and ``bench.py`` call :func:`enable_compile_cache`
before their first compile; importing the package never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at the one fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of the cache key, so a path built from a temporary name, a PID or the
time would never hit.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
