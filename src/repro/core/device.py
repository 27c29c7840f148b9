"""Backend facts the program derives from the platform it runs on.

* ``interpret_mode()``: Pallas kernels run in interpret mode exactly when
  no TPU backs jax, and never when one does.
* ``enable_compile_cache()``: the persistent XLA compilation cache for
  entry points (``chip_smoke.py``, ``benchmarks/run.py``).  It is never
  called on library import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache", "interpret_mode"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``: the path is part of each entry's key, so it must
    not move between runs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
