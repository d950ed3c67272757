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

Either way the cache key includes the program's metadata (the named
scopes and source locations that ride on each compiled op): keyed
without it, two builds that differ only in their scopes share an entry,
and the one that compiled second would run, and show in a device trace,
the other's op names.  Source paths enter the metadata relative to the
checkout, so the same commit checked out elsewhere finds its entries.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: Root of the checkout this package was imported from (``src/repro`` is
#: two levels below it).
CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT}{os.sep}"))
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
