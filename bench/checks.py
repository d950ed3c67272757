"""The comparison that decides a run's `correct`.

What the timed path returned (a sample of its recorded walks, each with
the identity it was sampled under) is held against the plain reference in
``bench/reference``: every position of every sampled walk is checked
(``wrong_hops``), every row's shape and start (``bad_rows``), and every
hop is looked up among the graph's edges (``non_edges``, the structural
check ``chip_smoke.py`` makes).  The harness adds what only it can count:
walks or requests due in the window that never came back (``missing``)
and task identities handed out twice (``dup_ids``).  Every limit is 0;
``walks_checked`` must be at least 1.  How each limit was set is in
PERF.md.
"""
from __future__ import annotations

import numpy as np

from bench import reference

LIMITS = {"wrong_hops": 0, "bad_rows": 0, "non_edges": 0, "missing": 0,
          "dup_ids": 0}


class EdgeSet:
    """Host-side CSR membership: sorted ``u * |V| + v`` keys (copied from
    ``chip_smoke.py``)."""

    def __init__(self, graph: reference.Graph):
        self.nv = graph.num_vertices
        src = np.repeat(np.arange(self.nv, dtype=np.int64),
                        np.diff(graph.row_ptr))
        self.keys = np.sort(src * self.nv + graph.col)

    def contains(self, u, v):
        k = u.astype(np.int64) * self.nv + v.astype(np.int64)
        i = np.minimum(np.searchsorted(self.keys, k), self.keys.size - 1)
        return self.keys[i] == k


def row_faults(paths, lengths, starts, max_hops, num_vertices):
    """Per row: True where the row is not its start, then ``length - 1``
    vertices, then -1 padding, with a length in ``[1, max_hops + 1]``
    (the shape checks of ``chip_smoke.py``'s ``check_paths``)."""
    paths = np.asarray(paths)
    lengths = np.asarray(lengths)
    if paths.ndim != 2 or paths.shape[1] != max_hops + 1:
        return np.ones(lengths.shape[0], bool)
    pos = np.arange(max_hops + 1)[None, :]
    inside = pos < lengths[:, None]
    ok_len = (lengths >= 1) & (lengths <= max_hops + 1)
    ok_start = paths[:, 0] == starts
    ok_in = np.all(~inside | ((paths >= 0) & (paths < num_vertices)), axis=1)
    ok_pad = np.all(inside | (paths == -1), axis=1)
    return ~(ok_len & ok_start & ok_in & ok_pad)


def positions(graph: reference.Graph, walk: dict, sample: dict):
    """Every recorded position of the sample's well-formed rows: the task
    at it, its hop and vertex, and what the recorded walk did next (-1: it
    ended); and how many rows were malformed."""
    max_hops = int(walk["max_hops"])
    bad = row_faults(sample["paths"], sample["lengths"], sample["starts"],
                     max_hops, graph.num_vertices)
    paths = np.asarray(sample["paths"])[~bad]
    lengths = np.asarray(sample["lengths"])[~bad]
    inside = np.arange(max_hops + 1)[None, :] < lengths[:, None]
    row, hop = np.nonzero(inside)
    v = paths[row, hop]
    nxt = np.where(hop < lengths[row] - 1,
                   paths[row, np.minimum(hop + 1, max_hops)], -1)
    task = tuple(np.asarray(sample[k])[~bad][row]
                 for k in ("seed", "epoch", "qid"))
    return task, hop, v, nxt, int(bad.sum())


def compare(graph: reference.Graph, walk: dict, sample: dict) -> dict:
    """Counts of what the sample gets wrong against the reference."""
    task, hop, v, nxt, bad = positions(graph, walk, sample)
    cont, ref_nxt = reference.expected(graph, walk, *task, hop, v)
    went = nxt >= 0
    wrong = (cont != went) | (went & (ref_nxt != nxt))
    non_edges = int((~EdgeSet(graph).contains(v[went], nxt[went])).sum())
    return {"wrong_hops": int(wrong.sum()), "bad_rows": bad,
            "non_edges": non_edges,
            "walks_checked": int(np.asarray(sample["lengths"]).size - bad),
            "hops_checked": int(went.sum())}


def control_reading(graph: reference.Graph, walk: dict, sample: dict) -> int:
    """Positions of the sample at which the reference computed in bfloat16
    (the control, put in the program's place) disagrees with the float32
    reference: the count the control would read as ``wrong_hops``."""
    task, hop, v, _, _ = positions(graph, walk, sample)
    cont, nxt = reference.expected(graph, walk, *task, hop, v)
    lo_cont, lo_nxt = reference.expected(graph, walk, *task, hop, v,
                                         precision="bfloat16")
    return int(((cont != lo_cont) | (cont & (nxt != lo_nxt))).sum())


def control_verdict(graph: reference.Graph, walk: dict, sample: dict,
                    counts: dict) -> tuple[bool, dict]:
    """The harness's verdict with the control in the program's place: the
    run's own counts, with ``wrong_hops`` as the control reads it."""
    return verdict({**counts,
                    "wrong_hops": control_reading(graph, walk, sample)})


def verdict(counts: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit."""
    table = {name: {"value": int(counts.get(name, 0)), "limit": limit}
             for name, limit in LIMITS.items()}
    table["walks_checked"] = {"value": int(counts.get("walks_checked", 0)),
                              "limit": 1, "at_least": True}
    ok = all(row["value"] >= row["limit"] if row.get("at_least")
             else row["value"] <= row["limit"] for row in table.values())
    return ok, table
