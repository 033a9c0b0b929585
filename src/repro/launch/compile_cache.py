"""JAX's persistent compilation cache for the repo's entry points.

A cache is found again only at the path it was written to, so the path is
fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (the
cache then lives there and nowhere else), otherwise ``.jax_cache`` at the
root of this checkout (listed in ``.gitignore``).  Entry points call
:func:`enable_compile_cache` before their first compilation; the test
suite does not, so tests never write a cache.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """Where the cache lives: the environment's directory, else the
    checkout's ``.jax_cache``."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(CHECKOUT_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that path."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
