"""Where JAX keeps its persistent compilation cache.

Entry points that compile for a device (``chip_smoke.py``,
``examples/fleet_sim.py``, ``bench/run.py``, ``bench/calibrate.py``) call
:func:`enable_compile_cache` first thing in ``main``.  Importing
:mod:`repro` never does: tests and library callers keep JAX's own
defaults.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing here
  overrides it.
* Otherwise: one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (listed in ``.gitignore``).  The path is part of the cache key, so it is
  never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
