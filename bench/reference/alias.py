"""Walker's alias method (DeepWalk on a weighted graph).

The table of a neighbour list is built by Vose's method as the system
states it builds its tables: weights scaled to mean 1 in float64,
``small`` and ``large`` work lists filled in index order and used as
stacks (the last index first), each probability stored in float32, and
what is left on either list at the end set to probability 1.  A draw
takes two uniforms: ``u0`` names the column ``k`` as the uniform sampler
does, and the walk keeps ``k`` if ``u1 < prob[k]`` and else takes
``alias[k]``.  Tables are built here from the graph's weights, only for
the vertices the checked positions stand on, all at once (``vose_many``).
"""
from __future__ import annotations

import numpy as np

from bench.reference import lowered, pick_index

DRAWS = 2


def vose(w: np.ndarray):
    """(prob, alias) of one neighbour list with weights ``w`` (float64)."""
    d = w.size
    prob = np.ones(d, np.float32)
    alias = np.arange(d, dtype=np.int64)
    scaled = (w * d) / w.sum()
    small = [i for i in range(d) if scaled[i] < 1.0]
    large = [i for i in range(d) if scaled[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s] = scaled[s]
        alias[s] = g
        scaled[g] = scaled[g] + scaled[s] - 1.0
        (small if scaled[g] < 1.0 else large).append(g)
    return prob, alias


def vose_many(row_ptr: np.ndarray, weights: np.ndarray, verts: np.ndarray):
    """The tables ``vose`` builds for each vertex of ``verts``, all at once:
    ``(pos, prob, alias)``, with ``pos`` each entry's place in the graph's
    edge arrays.  The vertices run in lockstep, one pair of pops a round,
    and each keeps ``vose``'s own order of operations: the large stack's
    top ``g`` stays on top while it stays large, and when it turns small it
    is the next small popped."""
    deg = row_ptr[verts + 1] - row_ptr[verts]
    base = np.concatenate([[0], np.cumsum(deg)[:-1]])
    seg = np.repeat(np.arange(verts.size), deg)
    local = np.arange(int(deg.sum())) - base[seg]
    pos = row_ptr[verts][seg] + local
    w = weights[pos].astype(np.float64)
    sums = np.array([w[b:b + d].sum() for b, d in zip(base, deg)])
    scaled = (w * deg[seg]) / sums[seg]
    prob = np.ones(local.size, np.float32)
    alias = local.copy()
    small, large = np.flatnonzero(scaled < 1.0), np.flatnonzero(scaled >= 1.0)
    n_s = np.bincount(seg[small], minlength=verts.size)
    n_l = np.bincount(seg[large], minlength=verts.size)
    s_lo = np.concatenate([[0], np.cumsum(n_s)[:-1]])
    l_lo = np.concatenate([[0], np.cumsum(n_l)[:-1]])
    s_top, l_top = s_lo + n_s - 1, l_lo + n_l - 1
    pending = np.full(verts.size, -1)
    act = np.flatnonzero((n_s > 0) & (n_l > 0))
    while act.size:
        pend = pending[act]
        fresh = pend < 0
        s = np.where(fresh, small[np.maximum(s_top[act], 0)], pend)
        s_top[act[fresh]] -= 1
        pending[act] = -1
        g = large[l_top[act]]
        prob[s] = scaled[s]
        alias[s] = local[g]
        scaled[g] = scaled[g] + scaled[s] - 1.0
        turned = scaled[g] < 1.0
        l_top[act[turned]] -= 1
        pending[act[turned]] = g[turned]
        act = act[((pending[act] >= 0) | (s_top[act] >= s_lo[act]))
                  & (l_top[act] >= l_lo[act])]
    return pos, prob, alias


class Tables:
    """Alias tables of the vertices asked for, built once each."""

    def __init__(self, graph):
        if graph.weights is None:
            raise ValueError("alias sampling needs a weighted graph")
        self.graph = graph
        self.built = np.zeros(graph.num_vertices, bool)
        self.prob = np.ones(graph.col.size, np.float32)
        self.alias = np.zeros(graph.col.size, np.int64)

    def lookup(self, v: np.ndarray, k: np.ndarray):
        """``prob[k]`` and ``alias[k]`` in the table of each vertex ``v``."""
        new = np.unique(v[~self.built[v]])
        if new.size:
            pos, prob, alias = vose_many(self.graph.row_ptr,
                                         self.graph.weights, new)
            self.prob[pos], self.alias[pos] = prob, alias
            self.built[new] = True
        at = self.graph.row_ptr[v] + k
        return self.prob[at], self.alias[at]


def pick(graph, v, addr, deg, u, precision):
    """Offset of the chosen neighbour in each vertex's neighbour list."""
    tables = graph.memo.get("alias")
    if tables is None:
        tables = graph.memo["alias"] = Tables(graph)
    k = pick_index(deg, u[:, 0], precision)
    prob, alias = tables.lookup(v, k)
    accept = u[:, 1] < lowered(prob, precision)
    idx = np.where(accept, k, alias)
    return np.clip(idx, 0, np.maximum(deg - 1, 0))
