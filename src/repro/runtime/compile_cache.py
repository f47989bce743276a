"""JAX's persistent compilation cache, kept at one fixed path.

A cold process on a TPU host compiles every program again; the persistent
cache lets a second run in the same checkout load them instead.  A cached
entry is only found again from the same directory, so the directory never
moves: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself
and nothing here overrides it), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
CHECKOUT_CACHE = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str | None:
    """Turn the persistent compilation cache on before the first compile.

    Returns the directory in use, or None when neither the environment
    names one nor this module runs from a source checkout (an installed
    package has no checkout to keep the cache in)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (CHECKOUT / "pyproject.toml").is_file():
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
