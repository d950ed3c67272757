"""The persistent compilation cache goes where `repro.compile_cache` says:
the directory in ``JAX_COMPILATION_CACHE_DIR`` when it is set, else the
checkout's own ``.jax_cache`` — and nowhere else."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_SCRIPT = """
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
path = use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _run(env):
    env = dict(env, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_env_var_directory_is_used_and_nothing_else(tmp_path):
    target = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(target))
    helper, config = _run(env)
    assert helper == config == str(target)
    assert target.is_dir() and any(target.iterdir())


def test_default_directory_is_fixed_inside_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    first = _run(env)
    second = _run(env)
    expected = str(REPO / ".jax_cache")
    assert first == second == [expected, expected]
    assert any(Path(expected).iterdir())
    ignored = (REPO / ".gitignore").read_text().split()
    if ".jax_cache/" not in ignored:
        pytest.fail(".jax_cache/ must be listed in .gitignore")
