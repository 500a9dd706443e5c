"""Where the program runs: Pallas interpret mode and the compile cache.

Pallas kernels compile for the chip when JAX's default backend is a TPU
and run in the Pallas interpreter everywhere else (the CPU test suite).
:func:`resolve_interpret` is the one place that decision is made; every
``interpret=None`` default in the library resolves through it.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_interpret() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; ``None`` follows the platform."""
    return default_interpret() if interpret is None else bool(interpret)


def use_compilation_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is configured here.  Otherwise the cache goes to the fixed
    directory ``<root>/.jax_cache`` (the path is part of the cache key, so
    it must not move between runs).  Returns the directory in use.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
