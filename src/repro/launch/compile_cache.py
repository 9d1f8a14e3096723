"""JAX's persistent compilation cache for the entry points.

A cache is found again only at the path it was written to, so the path
is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads the variable itself, and nothing here overrides it), else
``.jax_cache`` at the root of this checkout. Launchers call
``enable_compile_cache`` from their ``main``; importing this module sets
nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
