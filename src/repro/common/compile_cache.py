"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, decides: JAX reads it itself and
this module sets no other path. Otherwise the cache lives at one fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored). The
path is part of a cache entry's key, so it is never derived from a temp
directory, a pid or a time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it: the variable's value when set, else ``DEFAULT_DIR``."""
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    # with the variable set this is the value JAX already read from it
    # (re-applied in case jax was imported before the variable was set)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
