"""The harness finds every cell's configuration, traffic mix and metric
readers by name, refuses unknown names clearly, and refuses to run
without a TPU or outside a checkout."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import generator, registry
from bench.registry import BenchError

ROOT = registry.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = registry.benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_what_it_names(cell):
    w = registry.cell(cell, BENCH)
    config = registry.config(w["config"])
    assert config["name"] == w["config"]
    generator.validate(registry.traffic(w["traffic"]))
    e2e = registry.metrics_of(cell, BENCH, "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    layer = registry.metrics_of(cell, BENCH, "per_layer")
    assert layer, f"{cell} reports no per-layer metric"
    for m in layer:
        assert callable(registry.metric(m["name"]).read)


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            reported = registry.metrics_of(cell, BENCH, "end_to_end")
            assert m["moves"] in [r["name"] for r in reported]
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("find, name", [
    (lambda n: registry.cell(n, BENCH), "no-such-cell"),
    (registry.config, "no-such-config"),
    (registry.traffic, "no-such-mix"),
    (registry.metric, "no_such_metric"),
])
def test_unknown_names_fail_clearly(find, name):
    with pytest.raises(BenchError, match=name):
        find(name)


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-ppr.closed",
         "--seed", "3000000123", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _prints_no_result(proc):
    return not any(line.startswith("{")
                   for line in proc.stdout.splitlines())


def test_run_fails_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _prints_no_result(proc)


def test_run_fails_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".graph_cache", ".traces",
                                                  "__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "src/repro" in proc.stderr
    assert _prints_no_result(proc)
