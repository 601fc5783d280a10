"""Where JAX's persistent compilation cache lives.

A cold batch program at deployment size takes minutes to compile, so
every entry point that drives one (``chip_smoke.py``,
``examples/stream_maintenance.py``, ``benchmarks/run.py``) calls
``enable_compile_cache()`` from its ``main()``. Nothing here runs at
import: a library user keeps full control of JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# fixed, inside the checkout (src/repro/ -> repo root): the directory is
# part of every cache key, so a path made from a temporary name, a pid
# or the time would never hit across processes
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is changed; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
