"""The Graph500 Kronecker graph, as the Graph500 specification's reference
generator (``kronecker_generator``, specification section 3) makes it:
``N = 2**scale`` vertices, ``M = edgefactor * N`` edge tuples, each of the
``scale`` address bits drawn with the initiator ``A, B, C = 0.57, 0.19,
0.19``, then the vertex labels randomly permuted.  The graph is undirected:
as the specification's kernel 1 may, self-loops and duplicate tuples are
removed, and each edge is kept in both directions.  Edge weights, where the
configuration asks for them, are one per undirected edge, uniform as the
specification's SSSP kernel draws them, taken in (0, 1] (``1 - U[0, 1)``)
so that no edge weighs 0, as RidgeWalker section VIII-A4 takes ThunderRW's.

Everything is drawn from the configuration's own graph seed: a deployment's
graph is one fixed data set, and a run's ``--seed`` draws only the traffic
and the walks on it.
"""
from __future__ import annotations

import numpy as np

INITIATOR = (0.57, 0.19, 0.19)


def kronecker(scale: int, edgefactor: int, seed: int) -> np.ndarray:
    """``(2, M)`` int64 edge tuples, vertex labels permuted."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    n, m = 1 << scale, edgefactor << scale
    a, b, c = INITIATOR
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] |= ii.astype(np.int64) << bit
        ij[1] |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[ij]


def undirected(ij: np.ndarray, num_vertices: int, weighted: bool,
               seed: int) -> tuple[np.ndarray, np.ndarray | None]:
    """Kernel 1's edge list: no self-loops, no duplicates, each edge once
    as ``(min, max)`` with its weight; then both directions, ``(E, 2)``."""
    u, v = ij
    keep = u != v
    lo, hi = np.minimum(u[keep], v[keep]), np.maximum(u[keep], v[keep])
    keys = np.unique(lo * num_vertices + hi)
    lo, hi = keys // num_vertices, keys % num_vertices
    w = None
    if weighted:
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        w1 = (1.0 - rng.random(keys.size)).astype(np.float32)
        w = np.concatenate([w1, w1])
    edges = np.stack([np.concatenate([lo, hi]), np.concatenate([hi, lo])],
                     axis=1)
    return edges, w


def csr(edges: np.ndarray, num_vertices: int, weights=None):
    """``(row_ptr, col, weights)`` of the edge list, each neighbour list
    in increasing order: the reference's own copy of the graph."""
    key = edges[:, 0] * num_vertices + edges[:, 1]
    order = np.argsort(key, kind="stable")
    key = key[order]
    row_ptr = np.searchsorted(key // num_vertices,
                              np.arange(num_vertices + 1), side="left")
    col = (key % num_vertices).astype(np.int32)
    return (row_ptr.astype(np.int64), col,
            None if weights is None else weights[order])
