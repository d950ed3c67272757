"""Plain reference of the walk semantics, in numpy, for deciding `correct`.

It imports nothing of the system under test.  What it knows of the system
is the published contract a walk is sampled under:

* Randomness is counter-based Threefry-2x32 (Salmon et al., SC'11, 20
  rounds), keyed as ``jax.random`` keys are: the base key of seed ``s`` is
  ``(0, s)``; ``fold_in(key, d)`` encrypts the counter ``(0, d)``.  The
  draws of one task are keyed by folding, in order, the occupant epoch
  (only when it is above 0), the query id, the hop and the salt of the
  draw stream into the base key; ``n`` words are then drawn as
  ``jax.random.bits`` lays them out without partitionable Threefry
  (counters ``0..n-1`` split into two halves, outputs concatenated).
* A word becomes a uniform in [0, 1) from its top 23 bits: ``(b >> 9) *
  2**-23`` in float32.
* At hop ``h`` a walk at vertex ``v`` first draws its stop test when the
  configuration has a stop probability ``a`` (salt 2): it ends if
  ``u < a``.  Otherwise it ends if ``v`` has no out-edge, and else takes
  the neighbour its sampler picks from the column draws (salt 0).  A walk
  that has taken ``max_hops`` hops ends.
* The samplers themselves live in the module of their kind beside this
  file (``uniform.py``, ``alias.py``), found by the configuration's
  sampler kind.

The comparison is teacher-forced: at every position of a recorded path
the reference says whether the walk goes on and where, and each position
where the recorded path disagrees is one wrong hop.  ``precision`` is
``"float32"`` for the reference and ``"bfloat16"`` for the control (the
same rules with every uniform and product rounded to bfloat16).
"""
from __future__ import annotations

import importlib

import numpy as np

SALT_COLUMN = 0
SALT_STOP = 2
PRECISIONS = ("float32", "bfloat16")

_PARITY = np.uint32(0x1BD11BDA)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32, 20 rounds, on uint32 arrays that broadcast."""
    k0, k1, x0, x1 = (np.asarray(a, np.uint32) for a in (k0, k1, x0, x1))
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    with np.errstate(over="ignore"):
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for i in range(5):
            for r in _ROTATIONS[i % 2]:
                x0 = x0 + x1
                x1 = _rotl(x1, r) ^ x0
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def fold_in(k0, k1, data):
    data = np.asarray(data, np.uint32)
    return threefry2x32(k0, k1, np.zeros_like(data), data)


def task_bits(seed, epoch, qid, hop, salt: int, num: int) -> np.ndarray:
    """(N, num) uint32 words of the draw stream ``salt`` of each task."""
    seed = np.asarray(seed, np.uint32)
    epoch = np.asarray(epoch, np.int64)
    k0 = np.zeros(np.broadcast(seed, epoch, qid, hop).shape, np.uint32)
    k1 = np.broadcast_to(seed, k0.shape).astype(np.uint32)
    e0, e1 = fold_in(k0, k1, np.maximum(epoch, 0).astype(np.uint32))
    salted = np.broadcast_to(epoch > 0, k0.shape)
    k0, k1 = np.where(salted, e0, k0), np.where(salted, e1, k1)
    k0, k1 = fold_in(k0, k1, np.asarray(qid).astype(np.uint32))
    k0, k1 = fold_in(k0, k1, np.asarray(hop).astype(np.uint32))
    k0, k1 = fold_in(k0, k1, np.full(k0.shape, salt, np.uint32))
    half = (num + 1) // 2
    c0 = np.arange(half, dtype=np.uint32)
    c1 = np.where(c0 + half < num, c0 + half, 0).astype(np.uint32)
    y0, y1 = threefry2x32(k0[:, None], k1[:, None], c0[None, :], c1[None, :])
    return np.concatenate([y0, y1], axis=1)[:, :num]


def uniforms(bits: np.ndarray, precision: str) -> np.ndarray:
    u = (bits >> np.uint32(9)).astype(np.float32) * np.float32(2.0 ** -23)
    return lowered(u, precision)


def lowered(x, precision: str):
    """``x`` as the precision computes it (bfloat16 only for the control)."""
    if precision == "float32":
        return np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
    raise ValueError(f"unknown precision {precision!r} (one of {PRECISIONS})")


def pick_index(deg: np.ndarray, u: np.ndarray, precision: str) -> np.ndarray:
    """``min(floor(u * deg), deg - 1)``, the column a uniform draw names."""
    prod = np.asarray(u * lowered(deg, precision), np.float32)
    idx = np.floor(prod).astype(np.int64)
    return np.clip(idx, 0, np.maximum(deg - 1, 0))


class Graph:
    """The host copy of the input graph the reference walks on."""

    def __init__(self, row_ptr, col, weights=None):
        self.row_ptr = np.asarray(row_ptr).astype(np.int64)
        self.col = np.asarray(col).astype(np.int64)
        self.weights = None if weights is None else np.asarray(weights)
        self.num_vertices = self.row_ptr.size - 1
        self.memo = {}  # per-graph state of the samplers (alias tables)

    def degree(self, v):
        v = np.clip(v, 0, self.num_vertices - 1)
        return self.row_ptr[v + 1] - self.row_ptr[v]


def sampler(kind: str):
    """The sampler module of ``kind`` (``bench/reference/<kind>.py``)."""
    try:
        return importlib.import_module(f"{__name__}.{kind}")
    except ModuleNotFoundError as e:
        raise LookupError(
            f"no reference sampler for kind {kind!r}: add "
            f"bench/reference/{kind}.py") from e


def expected(graph: Graph, walk: dict, seed, epoch, qid, hop, v,
             precision: str = "float32"):
    """Whether each task ``(seed, epoch, qid, hop)`` at vertex ``v`` takes
    a hop, and the vertex it moves to (-1 where it ends)."""
    n = np.asarray(v).shape[0]
    v = np.asarray(v, np.int64)
    hop = np.asarray(hop, np.int64)
    cont = (hop < int(walk["max_hops"])) & (v >= 0) & (v < graph.num_vertices)
    stop_prob = float(walk.get("stop_prob", 0.0))
    if stop_prob > 0.0:
        u = uniforms(task_bits(seed, epoch, qid, hop, SALT_STOP, 1),
                     precision)[:, 0]
        cont &= ~(u < lowered(np.float32(stop_prob), precision))
    deg = graph.degree(v)
    cont &= deg > 0
    nxt = np.full(n, -1, np.int64)
    idx = np.flatnonzero(cont)
    if idx.size:
        mod = sampler(walk["sampler"])
        u = uniforms(task_bits(np.broadcast_to(seed, (n,))[idx],
                               np.broadcast_to(epoch, (n,))[idx],
                               np.broadcast_to(qid, (n,))[idx], hop[idx],
                               SALT_COLUMN, mod.DRAWS), precision)
        addr = graph.row_ptr[v[idx]]
        off = mod.pick(graph, v[idx], addr, deg[idx], u, precision)
        nxt[idx] = graph.col[addr + off]
    return cont, nxt
