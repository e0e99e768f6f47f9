"""Persistent XLA compile cache, placed from outside the program.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and the program
  sets no cache path in code.
* Unset: the cache lives at a fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The path is part of
  what a later run must find again, so it is never a temp, pid or
  time-derived directory.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once, before their first compile.  Tests
never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Tuple

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ: Mapping[str, str] = os.environ) -> Tuple[str, bool]:
    """``(directory, set_in_code)``: the cache directory in effect, and
    whether the program has to set it (False when the environment does)."""
    if environ.get(ENV):
        return environ[ENV], False
    return str(REPO_CACHE), True


def enable_compile_cache(environ: Mapping[str, str] = os.environ) -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path, set_in_code = cache_dir(environ)
    if set_in_code:
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: Pallas kernels compile in well under the 1 s
    # default floor, and a cold chip call recompiles whatever is missing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
