"""Persistent XLA compilation cache for the repository's entry points.

A cold CTMC sweep spends many seconds compiling; JAX's persistent cache
lets a second process with the same program skip that.  The cache key
includes the directory, so the directory must not move between runs:
it is ``JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the
fixed ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``).  Scripts call :func:`enable` once at start-up; importing
the library never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout cache directory used when ``ENV_VAR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise
    :data:`DEFAULT_DIR`, the same path on every call.
    """
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
