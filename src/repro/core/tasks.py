"""Stateless task / slot-pool structures (paper §V-A).

The paper decomposes each GRW query into minimal stateless tasks
``Q_s^y = <v_last, ID_y, x, ...>`` that fit in a single pipeline word.  The
TPU-native layout is a structure-of-arrays *slot pool*: ``W`` lanes, each
holding one task word.  A lane is either live (carrying a task) or free;
the zero-bubble scheduler's job is to keep every lane live whenever work
exists (paper §VI).

``v_prev`` carries the one extra vertex of history needed by second-order
walks (Node2Vec) — exactly the paper's "or two vertices for higher-order
walks" extension of the task tuple.

``epoch`` extends the task identity for the open system's ring-buffer slot
economy: query ids are *reused* once a query completes and is harvested,
and the occupant's epoch salts its RNG derivation
(``rng.task_fold(..., epoch=...)``) so successive occupants of one slot
sample independent walks.  Closed-batch runs carry epoch 0 everywhere,
which derives bit-identically to the classic ``(seed, query_id, hop)``
tuple.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np


class WalkerSlots(NamedTuple):
    """Slot pool of stateless walk tasks (SoA; all arrays shape (W,))."""

    v_curr: jnp.ndarray   # int32 — the task's v_last (current vertex)
    v_prev: jnp.ndarray   # int32 — previous vertex (2nd-order walks); -1 if none
    query_id: jnp.ndarray  # int32 — unique query id (result tracking); -1 = free
    hop: jnp.ndarray      # int32 — hop count x
    active: jnp.ndarray   # bool  — lane holds a live task
    epoch: Optional[jnp.ndarray] = None  # int32 — slot-reuse epoch (RNG salt)

    @property
    def width(self) -> int:
        return self.v_curr.shape[-1]


def empty_slots(width: int) -> WalkerSlots:
    return WalkerSlots(
        v_curr=jnp.full((width,), -1, jnp.int32),
        v_prev=jnp.full((width,), -1, jnp.int32),
        query_id=jnp.full((width,), -1, jnp.int32),
        hop=jnp.zeros((width,), jnp.int32),
        active=jnp.zeros((width,), bool),
        epoch=jnp.zeros((width,), jnp.int32),
    )


class N2VSlots(NamedTuple):
    """Two-phase second-order task word (SoA) for distributed Node2Vec
    rejection sampling: phase A draws K proposals at owner(v_curr), phase B
    verifies them against N(v_prev) — the paper's "two vertices for
    higher-order walks" extension of the task tuple, plus the K·32-bit
    candidate payload carried between phases."""

    v_curr: jnp.ndarray    # (S,) int32
    v_prev: jnp.ndarray    # (S,) int32
    query_id: jnp.ndarray  # (S,) int32 (-1 = free)
    hop: jnp.ndarray       # (S,) int32
    active: jnp.ndarray    # (S,) bool
    phase: jnp.ndarray     # (S,) int32: 0 = propose (A), 1 = verify (B)
    cand: jnp.ndarray      # (S, K) int32 — proposals carried A -> B
    epoch: Optional[jnp.ndarray] = None  # (S,) int32 — slot-reuse epoch


def empty_n2v_slots(width: int, k: int) -> N2VSlots:
    return N2VSlots(
        v_curr=jnp.full((width,), -1, jnp.int32),
        v_prev=jnp.full((width,), -1, jnp.int32),
        query_id=jnp.full((width,), -1, jnp.int32),
        hop=jnp.zeros((width,), jnp.int32),
        active=jnp.zeros((width,), bool),
        phase=jnp.zeros((width,), jnp.int32),
        cand=jnp.full((width, k), -1, jnp.int32),
        epoch=jnp.zeros((width,), jnp.int32),
    )


class ReservoirSlots(NamedTuple):
    """Chunked-scan second-order task word for distributed *weighted*
    Node2Vec (Efraimidis–Spirakis reservoir).  The scan over N(v_curr)
    ping-pongs between owner(v_curr) (gather a chunk of candidates and
    their edge weights) and owner(v_prev) (score the chunk against the
    local adjacency bias), carrying the running reservoir maximum."""

    v_curr: jnp.ndarray    # (S,) int32
    v_prev: jnp.ndarray    # (S,) int32
    query_id: jnp.ndarray  # (S,) int32 (-1 = free)
    hop: jnp.ndarray       # (S,) int32
    active: jnp.ndarray    # (S,) bool
    phase: jnp.ndarray     # (S,) int32: 2c = gather chunk c @owner(v_curr),
                           #             2c+1 = score chunk c @owner(v_prev),
                           #             2·n_chunks = finalize @owner(v_curr)
    cand: jnp.ndarray      # (S, CH) int32 — chunk candidates (-1 = padding)
    cand_w: jnp.ndarray    # (S, CH) float32 — candidate edge weights
    best_key: jnp.ndarray  # (S,) float32 — running E-S reservoir key
    best_idx: jnp.ndarray  # (S,) int32 — running argmax neighbor offset
    last_chunk: Optional[jnp.ndarray] = None  # (S,) bool — scored chunk was
                           # the final one deg(v_curr) needs (early finalize)
    epoch: Optional[jnp.ndarray] = None       # (S,) int32 — slot-reuse epoch


def empty_reservoir_slots(width: int, chunk: int) -> ReservoirSlots:
    return ReservoirSlots(
        v_curr=jnp.full((width,), -1, jnp.int32),
        v_prev=jnp.full((width,), -1, jnp.int32),
        query_id=jnp.full((width,), -1, jnp.int32),
        hop=jnp.zeros((width,), jnp.int32),
        active=jnp.zeros((width,), bool),
        phase=jnp.zeros((width,), jnp.int32),
        cand=jnp.full((width, chunk), -1, jnp.int32),
        cand_w=jnp.zeros((width, chunk), jnp.float32),
        best_key=jnp.full((width,), -jnp.inf, jnp.float32),
        best_idx=jnp.zeros((width,), jnp.int32),
        last_chunk=jnp.zeros((width,), bool),
        epoch=jnp.zeros((width,), jnp.int32),
    )


class QueryQueue(NamedTuple):
    """Device-resident pending-query ring (the Theorem VI.1 queue).

    ``head`` is the next arrival to issue; ``staged`` is the injection
    watermark — arrivals with sequence >= staged have not yet "arrived" from
    the host (models the C-cycle observation/injection delay of §VI-A).  The
    feedback controller advances ``staged``; refill may only consume
    ``head < staged``.

    ``head``/``staged``/``tail`` are *monotone arrival counters* (they never
    wrap); the buffers they index are rings of ``capacity`` slots addressed
    mod capacity.  ``order[i % capacity]`` is the query id assigned to the
    i-th arrival — in the closed system it is the identity permutation (query
    i occupies slot i), while the open system's ring-buffer slot economy
    re-issues reclaimed slots to later arrivals, so arrival order and slot
    id decouple.  ``start_vertex[qid]`` / ``epoch[qid]`` are indexed by slot
    id and describe the slot's *current occupant*; ``epoch`` salts the
    occupant's RNG derivation so successive occupants sample independently.

    Invariants: ``head <= staged <= tail`` and ``tail - head <= capacity``
    (an arrival only exists while its slot is live, and at most ``capacity``
    slots are live).
    """

    start_vertex: jnp.ndarray  # (Q,) int32 — start vertex by slot id
    head: jnp.ndarray          # scalar int32 — monotone issue counter
    staged: jnp.ndarray        # scalar int32 — monotone staging watermark
    tail: jnp.ndarray          # scalar int32 — monotone arrival counter
    order: jnp.ndarray         # (Q,) int32 — slot id by arrival seq (mod Q)
    epoch: jnp.ndarray         # (Q,) int32 — occupant epoch by slot id

    @property
    def capacity(self) -> int:
        return self.start_vertex.shape[-1]


def make_queue(start_vertices, staged: int | None = None,
               tail: int | None = None) -> QueryQueue:
    sv = jnp.asarray(start_vertices, jnp.int32)
    q = sv.shape[-1]
    tail = q if tail is None else tail
    staged = tail if staged is None else staged
    if tail > q:
        raise ValueError(
            f"tail={tail} exceeds the queue buffer capacity {q}; only "
            f"queries that fit in the buffer can have arrived")
    if staged > tail:
        raise ValueError(
            f"staged={staged} exceeds tail={tail}: the injection watermark "
            f"cannot run ahead of the queries that actually arrived "
            f"(invariant head <= staged <= tail <= capacity)")
    return QueryQueue(
        start_vertex=sv,
        head=jnp.zeros((), jnp.int32),
        staged=jnp.asarray(staged, jnp.int32),
        tail=jnp.asarray(tail, jnp.int32),
        order=jnp.arange(q, dtype=jnp.int32),
        epoch=jnp.zeros((q,), jnp.int32),
    )


def empty_queue(capacity: int) -> QueryQueue:
    """Open-system ring: room for ``capacity`` live queries, none arrived
    yet; slot ids are handed out by the host's free ring at injection."""
    return QueryQueue(
        start_vertex=jnp.zeros((capacity,), jnp.int32),
        head=jnp.zeros((), jnp.int32),
        staged=jnp.zeros((), jnp.int32),
        tail=jnp.zeros((), jnp.int32),
        order=jnp.arange(capacity, dtype=jnp.int32),
        epoch=jnp.zeros((capacity,), jnp.int32),
    )


class WalkStats(NamedTuple):
    """Cycle-accurate-style utilization counters (paper Fig. 3 / Fig. 11)."""

    steps: jnp.ndarray        # total hops executed (visited vertices)
    slot_steps: jnp.ndarray   # total lane-supersteps elapsed
    bubbles: jnp.ndarray      # lane-supersteps with no live task (idle lanes)
    starved: jnp.ndarray      # idle lane-supersteps WHILE upstream work existed
                              # (the quantity Theorem VI.1 drives to zero;
                              # bubbles - starved = unavoidable tail drain)
    terminations: jnp.ndarray  # completed queries
    supersteps: jnp.ndarray   # wall supersteps executed
    route_waits: jnp.ndarray  # tasks that waited a superstep for routing capacity
    drops: jnp.ndarray        # tasks lost to capacity overflow (must be 0)
    launches: jnp.ndarray     # kernel/superstep dispatches: the per-hop jnp
                              # and pallas impls pay one launch per superstep
                              # (launches == supersteps); the fused
                              # device-resident kernel amortizes many
                              # supersteps per launch, so
                              # supersteps / launches is the fusion factor
    cache_hits: jnp.ndarray   # lane-gathers served from the SMEM hot-vertex
                              # cache (fused kernel with cache_budget > 0;
                              # 0 everywhere else)
    cache_misses: jnp.ndarray  # lane-gathers that fell through to the HBM
                              # DMA loops despite the cache being on
    cache_coalesced: jnp.ndarray  # lane-gathers that shared another lane's
                              # issue because their v_curr coincided within
                              # the superstep (same-vertex coalescing)

    def bubble_ratio(self):
        return self.bubbles / jnp.maximum(self.slot_steps, 1)

    def occupancy(self):
        return 1.0 - self.bubble_ratio()

    def supersteps_per_launch(self):
        return self.supersteps / jnp.maximum(self.launches, 1)

    def cache_hit_rate(self):
        """Fraction of cache probes (leader gathers) served from SMEM."""
        return self.cache_hits / jnp.maximum(
            self.cache_hits + self.cache_misses, 1)


def zero_stats() -> WalkStats:
    return WalkStats(*(jnp.zeros((), jnp.int32)
                       for _ in range(len(WalkStats._fields))))


class WalkResult(NamedTuple):
    """Collected walk paths: paths[q, t] = t-th vertex of query q, -1 padded."""

    paths: jnp.ndarray    # (Q, max_len) int32
    lengths: jnp.ndarray  # (Q,) int32 — number of vertices recorded
    stats: WalkStats

    def as_numpy(self):
        return np.asarray(self.paths), np.asarray(self.lengths)
