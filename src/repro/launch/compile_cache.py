"""JAX's persistent compilation cache, at a place callers can steer.

:func:`enable` honours ``JAX_COMPILATION_CACHE_DIR`` when it is set --
JAX reads that variable itself, so nothing else is set.  Otherwise the
cache goes to ``.jax_cache/`` at the root of the checkout: a fixed path
(never a temp, pid or time path), so every later process of this
checkout finds what an earlier one compiled.  The directory is listed
in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable", "DEFAULT_DIR", "ENV"]

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
