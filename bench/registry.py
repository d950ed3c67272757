"""Finds what a cell names, by name: its entry in ``BENCHMARK.json``, its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``) and its per-layer metric readers
(``bench/metrics/<metric>.py``).  A later change adds any of these by
adding files and entries; nothing here lists them."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A cell, configuration, traffic mix or metric that cannot be found
    or read; the run stops with this message and prints no result."""


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise BenchError(f"no {what} file {path.relative_to(ROOT)}")
    try:
        return json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise BenchError(f"{what} file {path.relative_to(ROOT)}: {e}") from e


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json", "benchmark")


def cell(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has: "
                     f"{known}")


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json", f"configuration {name!r}")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json", f"traffic mix {name!r}")


def metric(name: str):
    """The reader module of per-layer metric ``name``: it has
    ``read(run) -> float | None``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {name!r}: "
                         f"{path.relative_to(ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise BenchError(f"{path.relative_to(ROOT)} has no read(run)")
    return mod


def metrics_of(cell_name: str, bench: dict, section: str) -> list[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    this cell reports: those that list it, and the end-to-end metrics that
    list no cells (``setup_s``), which every cell reports.  A per-layer
    metric lists its cells."""
    if section == "per_layer":
        for m in bench[section]:
            if "workloads" not in m:
                raise BenchError(f"per-layer metric {m['name']!r} lists no "
                                 "workloads")
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]
