"""Where JAX keeps its persistent compilation cache.

Entry points that compile for a device (``chip_smoke.py``, the
``repro.launch.*`` CLIs, ``benchmarks/run.py``) call
:func:`use_compile_cache` before their first compile:

  * if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here sets any other directory;
  * otherwise the cache goes to ``.jax_cache`` at the root of the
    checkout (listed in ``.gitignore``).  The path is fixed — never made
    from a temporary name, a pid or the time — because the directory is
    part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: Root of the checkout this package was imported from (``src/repro`` is
#: two levels below it).
CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
