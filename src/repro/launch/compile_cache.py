"""Where the program keeps JAX's persistent compilation cache.

A cold start on the chip compiles every step program, which can take as
long as the work itself; the persistent cache lets the next process skip
that. The cache key includes the directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
itself, and nothing here overrides it), otherwise ``.jax_cache`` at the
root of the checkout (git-ignored). Never a temporary name, a process id
or the time.

Entry points call :func:`enable_compile_cache` from their ``main``;
nothing calls it at import, so library users and the tests keep JAX's own
settings.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    # JAX opens the cache at the first compile that finds a directory set,
    # so a compile before this call does not keep it closed (a test holds
    # this)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
