"""JAX's persistent compilation cache for the repo's entry points.

``enable()`` is called once, before the first compile, by ``chip_smoke.py``
and ``repro.launch.train``:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  else is configured;
* otherwise the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``).  The path is fixed — never built from a
  temporary name, a process id or a time — because it is part of what the
  cache is keyed on, so a moving directory would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
