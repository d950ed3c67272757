"""Uniform neighbour choice (URW, PPR): one column draw names the
neighbour ``min(floor(u * deg), deg - 1)`` of the vertex's sorted list."""
from __future__ import annotations

from bench.reference import pick_index

DRAWS = 1


def pick(graph, v, addr, deg, u, precision):
    """Offset of the chosen neighbour in each vertex's neighbour list."""
    return pick_index(deg, u[:, 0], precision)
