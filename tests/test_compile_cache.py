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


_SCOPES = """
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def plain():
    def run(x):
        return jnp.sin(x) * 3 + 1
    return run


def scoped():
    def run(x):
        with jax.named_scope("walk.record"):
            return jnp.sin(x) * 3 + 1
    return run


x = jnp.arange(7.0)
for make in (plain, scoped):
    text = jax.jit(make()).lower(x).compile().as_text()
    print(make.__name__, "walk.record" in text)
"""


def test_a_build_with_named_scopes_gets_its_own_cache_entry(tmp_path):
    """Two builds of one program that differ only in a named scope do not
    share a cache entry: the scoped build, compiled second, keeps its own
    op names (a device trace reads them) rather than loading the other's."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _SCOPES], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["plain", "False", "scoped", "True"]
    assert len(list(tmp_path.iterdir())) >= 2


_PROBE = """
import jax, jax.numpy as jnp


def run(x):
    with jax.named_scope("walk.record"):
        return jnp.sin(x) * 3 + 1
"""

_FROM_CHECKOUT = """
import os, jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.probe import run
jax.jit(run)(jnp.arange(7.0)).block_until_ready()
print(len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
"""


def test_the_same_code_checked_out_elsewhere_finds_its_cache_entries(
        tmp_path):
    """The source paths in the metadata the cache key holds are relative to
    the checkout: a second checkout of the same code at another path loads
    the first one's entries and writes none of its own."""
    cache = tmp_path / "cache"
    counts = []
    for name in ("one", "two"):
        pkg = tmp_path / name / "src" / "repro"
        pkg.mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (pkg / "compile_cache.py").write_text(
            (REPO / "src" / "repro" / "compile_cache.py").read_text())
        (pkg / "probe.py").write_text(_PROBE)
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
                   PYTHONPATH=str(pkg.parent), JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", _FROM_CHECKOUT],
                           capture_output=True, text=True, env=env,
                           timeout=120)
        assert r.returncode == 0, r.stderr
        counts.append(int(r.stdout.split()[-1]))
    assert counts[0] >= 1 and counts[1] == counts[0]
