"""JAX's persistent compilation cache, set in one place for entry points.

Call ``enable_compile_cache()`` from a program's entry point only (the
training launcher, ``chip_smoke.py``, the examples, the benchmark
runner) — never at import and never from tests.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set; otherwise the cache lives at a fixed, git-ignored path in
the checkout, so successive runs of one checkout find each other's
programs (the path is part of the cache key).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
