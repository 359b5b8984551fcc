"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here sets another directory.  Otherwise the cache lives at a
fixed path inside the checkout (``artifacts/jax_cache``, git-ignored):
the directory is part of what a later process must find again, so it is
never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os


def repo_root() -> str:
    """The checkout this package runs from (the directory holding
    ``pyproject.toml``), else the working directory."""
    here = os.path.abspath(os.path.dirname(__file__))
    cand = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    if os.path.exists(os.path.join(cand, "pyproject.toml")):
        return cand
    return os.getcwd()


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first compile;
    returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(repo_root(), "artifacts", "jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
