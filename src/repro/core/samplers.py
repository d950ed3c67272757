"""Sampler definitions and their shared arithmetic (paper §VII, Table I).

:class:`SamplerSpec` is the host-programmable configuration of the
paper's pluggable AXI-Stream sampling module (p, q, α, mode bits).  It no
longer carries per-sampler execution code: a spec *lowers* into a
declarative phase program (`repro.core.phase_program`) — a short
sequence of typed gather/score/draw/commit phases with explicit operand
residency — and every backend (vectorized jnp superstep, fused Pallas
kernel, sharded engine) interprets that one program.

| GRW            | weighted | sampler            |
|----------------|----------|--------------------|
| URW, PPR       | no       | uniform            |
| DeepWalk       | yes      | alias (Walker)     |
| Node2Vec       | no       | rejection          |
| Node2Vec       | yes      | reservoir (E-S)    |
| MetaPath       | either   | typed uniform      |

What remains here is the arithmetic every lowering shares — index
picking, adjacency bisection, the Node2Vec (p, q) bias, the
Efraimidis–Spirakis chunk fold — written once so the backends cannot
drift apart numerically (bit-identity across backends is pinned by
tests).  The helpers are residency-aware: they accept the full
`CSRGraph` *or* a sharded `LocalView` (``num_shards`` maps global vertex
ids to local rows), so the single-device and distributed engines run the
same expressions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax.numpy as jnp

# Salt channels re-exported from the registry in `core/rng.py` (the single
# source of truth — uniqueness is asserted there at import, and the
# `repro.analysis` RNG-collision pass reads the registry as ground truth).
from repro.core.rng import (SALT_ACCEPT, SALT_CHUNK0,  # noqa: F401
                            SALT_COLUMN, SALT_STOP)

# Sampler kinds with a phase-program lowering (`phase_program.lower`).
KINDS = ("uniform", "alias", "rejection_n2v", "reservoir_n2v", "metapath")


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Static configuration of the sampling module (host-programmable
    AXI4-Lite registers in the paper: p, q, α, mode bits).

    Validation happens at construction — a malformed spec (unknown kind,
    empty MetaPath schedule, non-positive Node2Vec parameters) fails
    here with an actionable message instead of deep inside tracing."""

    kind: str = "uniform"   # uniform|alias|rejection_n2v|reservoir_n2v|metapath
    p: float = 1.0          # Node2Vec return parameter
    q: float = 1.0          # Node2Vec in-out parameter
    stop_prob: float = 0.0  # PPR teleport/termination probability α
    rejection_rounds: int = 12
    reservoir_chunk: int = 64
    metapath: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown sampler kind: {self.kind!r} (one of {KINDS})")
        if not isinstance(self.metapath, tuple):
            # Specs must stay hashable (phase-program lowering is cached
            # on the frozen spec) — coerce list-like schedules to tuples.
            object.__setattr__(self, "metapath",
                               tuple(int(t) for t in self.metapath))
        if self.kind == "metapath":
            if not self.metapath:
                raise ValueError(
                    "metapath samplers need a non-empty edge-type schedule "
                    "(pass metapath=(t0, t1, ...) / "
                    "WalkProgram.metapath(schedule=[...]))")
            if any(int(t) < 0 for t in self.metapath):
                raise ValueError(
                    f"metapath schedule entries are edge-type ids and must "
                    f"be non-negative, got {self.metapath}")
        if not 0.0 <= self.stop_prob <= 1.0:
            raise ValueError(
                f"stop_prob must be a probability in [0, 1], got "
                f"{self.stop_prob}")
        if self.second_order and (self.p <= 0 or self.q <= 0):
            raise ValueError(
                f"Node2Vec parameters must be positive, got p={self.p} "
                f"q={self.q}")
        if self.rejection_rounds <= 0:
            raise ValueError(
                f"rejection_rounds must be positive, got "
                f"{self.rejection_rounds}")
        if self.reservoir_chunk <= 0:
            raise ValueError(
                f"reservoir_chunk must be positive, got "
                f"{self.reservoir_chunk}")

    @property
    def second_order(self) -> bool:
        return self.kind in ("rejection_n2v", "reservoir_n2v")

    @property
    def capability(self) -> str | None:
        """Distributed-execution capability this sampler declares — read
        off the lowered phase program's residency schedule (all-local →
        ``first_order``; a score at owner(v_prev) → ``two_phase``; the
        chunked reservoir loop → ``chunked_reservoir``).  The sharded
        engine dispatches on this to allocate the task word and routing
        schedule."""
        from repro.core.phase_program import lower
        return lower(self).capability


# --------------------------------------------------------------------------
# Shared arithmetic: written once, interpreted by every lowering.
# --------------------------------------------------------------------------


def _col_at(g, e):
    return g.col[jnp.clip(e, 0, g.col.shape[-1] - 1)]


def _uniform_index(deg: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """index = min(floor(u * deg), deg-1); safe for deg == 0."""
    idx = jnp.floor(u * deg.astype(u.dtype)).astype(jnp.int32)
    return jnp.clip(idx, 0, jnp.maximum(deg - 1, 0))


def vertex_row(g, v: jnp.ndarray) -> jnp.ndarray:
    """Map a (global) vertex id to its row in ``g``'s per-vertex arrays
    (``row_ptr`` / ``type_offsets``).  Identity for the full CSRGraph;
    ``v // num_shards`` for a sharded LocalView (vertex v is owned by
    device ``v % N`` and stored at local row ``v // N``).  Negative /
    out-of-range ids clamp to a valid row — callers mask validity."""
    shards = getattr(g, "num_shards", 1)
    rows = g.row_ptr.shape[-1] - 1
    local = v // shards if shards > 1 else v
    return jnp.clip(jnp.where(v >= 0, local, 0), 0, rows - 1)


def edge_exists(g, src: jnp.ndarray, dst: jnp.ndarray) -> jnp.ndarray:
    """Vectorized adjacency test: is dst in src's (sorted) neighbor list?

    Lower-bound bisection with a static iteration count (log2 of max
    segment length).  ``src`` broadcasts against ``dst``'s leading dims.
    Works on the full CSRGraph and on a sharded LocalView (the bisection
    runs over the local copy of src's segment — same values, same
    result), which is what lets the sharded verify/score phases reuse
    the exact single-device bias expression."""
    while src.ndim < dst.ndim:
        src = src[..., None]
    row = vertex_row(g, src)
    lo = jnp.broadcast_to(g.row_ptr[row], dst.shape).astype(jnp.int32)
    hi0 = jnp.broadcast_to(g.row_ptr[row + 1], dst.shape).astype(jnp.int32)
    hi = hi0
    iters = max(1, int(math.ceil(math.log2(max(int(g.max_degree), 2) + 1))))
    for _ in range(iters):
        active = lo < hi
        mid = (lo + hi) // 2
        v = _col_at(g, mid)
        go_right = v < dst
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    found = (lo < hi0) & (_col_at(g, lo) == dst)
    valid_src = jnp.broadcast_to(src >= 0, dst.shape)
    return found & valid_src


def n2v_bias(spec, g, v_prev, y):
    """Node2Vec bias: 1/p if returning, 1 if y ∈ N(v_prev), 1/q otherwise.
    Hop 0 (v_prev < 0) → unbiased (weight 1)."""
    inv_p = 1.0 / spec.p
    inv_q = 1.0 / spec.q
    vp = v_prev if y.ndim == v_prev.ndim else v_prev[..., None]
    is_ret = y == vp
    common = edge_exists(g, v_prev, y)
    w = jnp.where(is_ret, inv_p, jnp.where(common, 1.0, inv_q))
    no_hist = jnp.broadcast_to(vp < 0, y.shape)
    return jnp.where(no_hist, 1.0, w)


def rejection_choose(spec, u_acc: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Bounded-round rejection reduction: accept round j iff
    ``u_acc[j] · w_max <= w[j]``; the last round is forced (bounded
    fallback) and the first accepted round wins.  Returns the winning
    round index per lane — shared by the jnp lowering and the sharded
    propose/verify phases so accepts cannot drift."""
    w_max = max(1.0 / spec.p, 1.0, 1.0 / spec.q)
    accept = (u_acc * w_max <= w).at[:, -1].set(True)
    return jnp.argmax(accept, axis=1)


def es_chunk_score(u, valid, w):
    """Efraimidis–Spirakis chunk scoring: key = u^(1/w), monotone in
    log(u)/w (stabler) — returns the within-chunk (argmax, max).

    Shared verbatim by the local reservoir scan and the sharded
    engine's chunk-score phase so the two are bit-identical: both feed the
    same (u, valid, w) and the same float ops produce the same key.
    """
    key = jnp.where(valid & (w > 0), jnp.log(u + 1e-20) / w, -jnp.inf)
    c_best = jnp.argmax(key, axis=1)
    c_key = jnp.take_along_axis(key, c_best[:, None], 1)[:, 0]
    return c_best, c_key


def es_merge(best_key, best_idx, chunk_index, chunk_size, c_best, c_key):
    """Fold one chunk's (argmax, max) into the running reservoir maximum.
    Strict > keeps the earliest chunk on ties — shared by both engines."""
    take = c_key > best_key
    best_idx = jnp.where(take,
                         chunk_index * chunk_size + c_best.astype(jnp.int32),
                         best_idx)
    best_key = jnp.maximum(best_key, c_key)
    return best_key, best_idx


def es_num_chunks(max_degree: int, chunk: int) -> int:
    return max(1, -(-int(max_degree) // chunk))
