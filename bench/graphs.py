"""The configuration's graph: a Graph500 Kronecker graph drawn from the
configuration's own seed (``bench/graph500.py``), loaded by the program's
own CSR and alias builders, and beside it the reference's copy of the same
edges.  Both are kept in ``bench/.graph_cache`` (ignored by git), keyed by
the configuration's graph and the sources that build it, so that every run
after a checkout's first loads them instead of building them again."""
from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

from bench import graph500
from bench.registry import BENCH, ROOT, BenchError

CACHE = BENCH / ".graph_cache"
KEEP = 4  # cached graphs kept; the oldest go first
_SOURCES = (BENCH / "graph500.py", ROOT / "src/repro/graph/csr.py",
            ROOT / "src/repro/graph/alias.py")
_FIELDS = ("row_ptr", "col", "weights", "alias_prob", "alias_idx")
_REF = ("ref_row_ptr", "ref_col", "ref_weights")


def _key(config: dict) -> str:
    h = hashlib.sha256(json.dumps(config["graph"], sort_keys=True).encode())
    for path in _SOURCES:
        h.update(path.read_bytes())
    return f"{config['name']}-{h.hexdigest()[:16]}"


def _build(config: dict) -> dict:
    from repro.graph.alias import build_alias_tables
    from repro.graph.csr import build_csr
    gc = config["graph"]
    if gc.get("generator") != "graph500":
        raise BenchError(f"configuration {config['name']}: unknown graph "
                         f"generator {gc.get('generator')!r}")
    n, seed = 1 << int(gc["scale"]), int(gc["seed"])
    ij = graph500.kronecker(int(gc["scale"]), int(gc["edgefactor"]), seed)
    edges, weights = graph500.undirected(ij, n, bool(gc["weighted"]), seed)
    del ij
    g = build_csr(edges, n, weights=weights)
    if gc["alias_tables"]:
        g = build_alias_tables(g)
    arrays = {f: np.asarray(getattr(g, f)) for f in _FIELDS
              if getattr(g, f) is not None}
    arrays["meta"] = np.array([g.num_vertices, g.num_edges, g.max_degree],
                              np.int64)
    ref = graph500.csr(edges, n, weights)
    arrays.update({k: v for k, v in zip(_REF, ref) if v is not None})
    return arrays


def host_graph(config: dict, cache: Path = CACHE) -> tuple[dict, str]:
    """The graph's arrays on the host, and ``"built"`` or ``"loaded"``."""
    cache.mkdir(parents=True, exist_ok=True)
    path = cache / f"{_key(config)}.npz"
    if path.is_file():
        with np.load(path) as z:
            return {k: z[k] for k in z.files}, "loaded"
    arrays = _build(config)
    tmp = path.with_suffix(f".{os.getpid()}.tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)
    old = sorted(cache.glob("*.npz"), key=lambda p: p.stat().st_mtime)
    for p in old[:-KEEP]:
        p.unlink(missing_ok=True)
    return arrays, "built"


def device_graph(arrays: dict):
    """A ``CSRGraph`` on the default device, waited for."""
    import jax
    import jax.numpy as jnp

    from repro.graph.csr import CSRGraph
    nv, ne, maxd = (int(x) for x in arrays["meta"])
    fields = {f: jnp.asarray(arrays[f]) for f in _FIELDS if f in arrays}
    g = CSRGraph(num_vertices=nv, num_edges=ne, max_degree=maxd, **fields)
    jax.block_until_ready(g)
    return g


def reference_graph(arrays: dict):
    """The reference's copy of the graph (``bench.reference.Graph``)."""
    from bench import reference
    return reference.Graph(arrays["ref_row_ptr"], arrays["ref_col"],
                           arrays.get("ref_weights"))


def load(config: dict, cache: Path = CACHE) -> tuple:
    """``(device graph, host arrays, timings)`` for the run's set-up."""
    t0 = time.perf_counter()
    arrays, how = host_graph(config, cache)
    t1 = time.perf_counter()
    g = device_graph(arrays)
    t2 = time.perf_counter()
    return g, arrays, {f"graph_{how}_s": t1 - t0, "device_put_s": t2 - t1}
