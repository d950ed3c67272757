"""Library-grade analytical cost model for walk-engine runs.

This is ``benchmarks/roofline.py``'s walk-engine half lifted into the
library: a closed-batch drain is priced as

    cost = S·a  +  S·W·b  +  S·W·B·c  +  launches·d

where ``S`` is the superstep count the drain needs, ``W`` the lane-pool
width, ``B`` the per-lane **bytes gathered per hop** — counted off the
sampler kind's declarative DMA schedule
(`repro.kernels.fused_superstep.dma_schedule`), not guessed — and
``launches`` the host dispatch count (``ceil(S / hops_per_launch)``
under the fused superstep, 1 for the fully jitted drains).  The four
coefficients ``(a, b, c, d)`` form a :class:`CostCoeffs`; they can be
*fit* from measured samples per sampler kind (:func:`fit`) and are used
to rank and prune the candidate grid before any timing
(:func:`prune`) — the roofline-model pruning of the tuner.

The reservoir chunk-trip count comes from the graph signature too: a
walking lane sits on a vertex with probability proportional to its
degree, so the typical lane scans ``ceil(d/chunk)`` chunks at the
degree-weighted median degree ``d`` — both the jnp scan (which scores
the live lanes' degree sum) and the fused kernel (``ceil(deg/chunk)``
trips per lane) follow each lane's own degree.

No wall-clock here: everything is arithmetic over the
:class:`~repro.tune.cache.GraphSignature` and the phase program's
static schedule (`repro.tune.measure` is the only module allowed to
time anything).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.samplers import es_num_chunks
from repro.tune.cache import PLAIN_QS, WEIGHTED_QS, GraphSignature
from repro.tune.space import Candidate

# Bytes the sampler needs per `start` op of the declarative DMA
# schedule, by buffer (per lane for one hop).  Scalar probes are 4-byte
# words; RP_entry / (lo, hi) pair probes are two words; the reservoir
# chunk stages a whole CH-wide chunk of columns or weights per start; the
# superstep record is six words per lane and the arrival-ring window
# three.  These are the words the walk consumes, not what the DMA engine
# moves: on the chip each gather copies whole 128-word rows
# (`kernels.common.as_rows`), which this model does not price.
_BUF_WORD_BYTES = {
    "rpbuf": 8,     # RP_entry: (row_ptr[v], row_ptr[v+1])
    "pairbuf": 8,   # v_prev RP_entry / typed sub-segment bounds
    "colbuf": 4,    # one column probe
    "probbuf": 4,   # alias probability probe
    "aliasbuf": 4,  # alias index probe
    "wbuf": 24,     # superstep record (query, hop, vertex, done, refill)
    "qbuf": 12,     # arrival-ring entry (slot id, start, epoch)
}


@dataclasses.dataclass(frozen=True)
class CostCoeffs:
    """Fitted roofline coefficients, all in microseconds per unit."""

    superstep_us: float = 30.0   # fixed dispatch/bookkeeping per superstep
    lane_us: float = 0.02        # per lane-hop of compute
    byte_us: float = 0.002       # per lane-byte gathered
    launch_us: float = 150.0     # per host->device kernel dispatch

    def as_array(self) -> np.ndarray:
        """(4,) coefficient vector matching :func:`features` columns."""
        return np.array([self.superstep_us, self.lane_us, self.byte_us,
                         self.launch_us], dtype=np.float64)


DEFAULT_COEFFS = CostCoeffs()


def expected_walk_len(program) -> float:
    """E[L] under the program's stop rule (geometric, capped)."""
    stop = float(getattr(program.spec, "stop_prob", 0.0))
    max_hops = float(program.max_hops)
    if stop <= 0.0:
        return max_hops
    return min(max_hops, 1.0 / stop)


@functools.lru_cache(maxsize=256)
def _schedule_bytes(kind: str, rounds: int, bisect_iters: int, chunks: int,
                    reservoir_chunk: int, cached: bool = False) -> float:
    """Per-lane bytes of one hop, summed over the kind's DMA schedule.

    ``cached=True`` prices the fully-hit representative superstep of the
    gather hierarchy: only the HBM copies the cache cannot absorb remain
    (v_prev-keyed probes, record flush) — on-chip cache reads move no HBM
    bytes and are skipped with the rest of the non-``start`` ops.
    """
    from repro.kernels.fused_superstep.fused_superstep import dma_schedule
    ops = dma_schedule(kind, lanes=1, rounds=rounds,
                       bisect_iters=bisect_iters, chunks=chunks,
                       records=1, cached=cached)
    total = 0.0
    for op in ops:
        if op.kind != "start":
            continue
        if op.buffer in ("ckcol", "ckwgt"):
            total += 4.0 * reservoir_chunk   # a whole staged chunk
        else:
            total += _BUF_WORD_BYTES.get(op.buffer, 4)
    return total


def bytes_per_hop(spec, sig: GraphSignature,
                  chunk_trips: Optional[int] = None,
                  cached: bool = False) -> float:
    """Per-lane bytes gathered per hop for ``spec`` on a ``sig`` graph.

    ``chunk_trips`` overrides the reservoir chunk-loop trip count (a
    lane scans ``ceil(deg/CH)`` chunks, fewer than the static
    ``es_num_chunks(max_degree, CH)`` bound).  ``cached=True`` prices a
    cache-hit hop (residual HBM traffic only); blend the two with
    :func:`predicted_hit_rate` for the effective per-hop bytes.
    """
    bisect = max(1, int(math.ceil(
        math.log2(max(int(sig.max_degree), 2) + 1))))
    trips = 1
    if spec.kind == "reservoir_n2v":
        trips = (int(chunk_trips) if chunk_trips is not None
                 else es_num_chunks(sig.max_degree, spec.reservoir_chunk))
    return _schedule_bytes(spec.kind, int(spec.rejection_rounds), bisect,
                           max(1, trips), int(spec.reservoir_chunk),
                           bool(cached))


@functools.lru_cache(maxsize=64)
def _spec_payloads(spec) -> Tuple[str, ...]:
    from repro.core.phase_program import lower
    return lower(spec).cache_payloads


def predicted_hit_rate(sig: GraphSignature, budget_bytes: int,
                       payloads: Sequence[str]) -> float:
    """Modeled hit rate of a hot-vertex cache sized to ``budget_bytes``.

    The builder admits vertices in descending-degree order, and a
    walking lane occupies a vertex with probability proportional to its
    degree (stationary distribution), so the hit rate of a cache that
    covers every vertex of degree > d is the *edge-mass* fraction above
    d — read off the signature's degree-weighted quantile ladder, while
    the plain ladder prices the directory overhead (vertex count above
    d).  We scan the candidate thresholds both ladders store and keep
    the largest mass fraction whose modeled footprint fits the budget.
    Arithmetic over the signature only — no adjacency access, no clock.
    """
    budget = int(budget_bytes)
    if budget <= 0:
        return 0.0
    from repro.graph.hot_cache import (edge_payload_bytes,
                                       vertex_overhead_bytes)
    payloads = tuple(payloads)
    per_edge = max(edge_payload_bytes(payloads), 4)
    # The signature does not store the edge-type count; 2 is the floor
    # for a typed graph and only perturbs the per-vertex directory term.
    per_vert = vertex_overhead_bytes(
        payloads, 2 if "type_offsets" in payloads else 0)
    # Anchor both ladders at degree 0 (zero mass / zero vertices below).
    dq = np.concatenate(([0.0], np.asarray(sig.deg_q, np.float64)))
    pq = np.concatenate(([0.0], np.asarray(PLAIN_QS, np.float64)))
    dwq = np.concatenate(([0.0], np.asarray(sig.deg_wq, np.float64)))
    wq = np.concatenate(([0.0], np.asarray(WEIGHTED_QS, np.float64)))
    thresholds = np.unique(np.concatenate((dq, dwq)))
    best = 0.0
    for d in thresholds:
        vert_frac = 1.0 - float(np.interp(d, dq, pq))
        mass_frac = 1.0 - float(np.interp(d, dwq, wq))
        need = (vert_frac * sig.num_vertices * per_vert
                + mass_frac * sig.num_edges * per_edge)
        if need <= budget:
            best = max(best, mass_frac)
    return float(min(max(best, 0.0), 1.0))


# ----------------------------------------------------------- prediction


def lane_degree(sig: GraphSignature) -> int:
    """Degree of a typical walking lane's vertex: the degree-weighted
    median (a lane occupies a vertex with probability proportional to
    its degree), interpolated over the signature's weighted ladder."""
    qs = np.asarray(WEIGHTED_QS)
    vals = np.asarray(sig.deg_wq, dtype=np.float64)
    return int(round(float(np.interp(0.5, qs, vals))))


def _reservoir_trips(spec, sig: GraphSignature) -> Optional[int]:
    if spec.kind != "reservoir_n2v":
        return None
    return max(1, -(-lane_degree(sig) // max(int(spec.reservoir_chunk), 1)))


def features(program, execution, sig: GraphSignature,
             num_queries: int) -> np.ndarray:
    """(4,) feature vector [S, S·W, S·W·B, launches] of a closed run."""
    ex = execution.resolved()
    spec = program.spec
    w = int(ex.num_slots)
    length = expected_walk_len(program)
    q = max(int(num_queries), 1)
    supersteps = max(length, math.ceil(q * length / max(w, 1)))
    trips = _reservoir_trips(spec, sig)
    b = bytes_per_hop(spec, sig, chunk_trips=trips)
    cb = getattr(ex, "cache_budget", 0)
    if ex.step_impl == "fused" and isinstance(cb, int) and cb > 0:
        # Gather hierarchy: a hit hop moves only the residual HBM bytes
        # the cache cannot absorb, so the effective per-hop traffic is
        # the hit-rate blend of the two schedules.
        h = predicted_hit_rate(sig, cb, _spec_payloads(spec))
        b_hit = bytes_per_hop(spec, sig, chunk_trips=trips, cached=True)
        b = (1.0 - h) * b + h * b_hit
    if ex.step_impl == "fused":
        launches = math.ceil(supersteps / max(int(ex.hops_per_launch), 1))
    else:
        launches = 1.0   # fully jitted drain: one dispatch
    return np.array([supersteps, supersteps * w, supersteps * w * b,
                     launches], dtype=np.float64)


def predict_us(program, execution, sig: GraphSignature, num_queries: int,
               coeffs: CostCoeffs = DEFAULT_COEFFS) -> float:
    """Modeled wall-time (microseconds) of one closed-batch run."""
    return float(features(program, execution, sig, num_queries)
                 @ coeffs.as_array())


def fit(feature_rows: Sequence[np.ndarray],
        measured_us: Sequence[float],
        base: CostCoeffs = DEFAULT_COEFFS) -> CostCoeffs:
    """Fit :class:`CostCoeffs` from measured samples (least squares,
    clipped non-negative).  With fewer samples than coefficients the
    system is underdetermined — fall back to uniformly rescaling
    ``base`` so total predicted time matches total measured time (the
    ranking the pruner needs survives a global rescale)."""
    X = np.asarray(list(feature_rows), dtype=np.float64)
    y = np.asarray(list(measured_us), dtype=np.float64)
    if X.ndim != 2 or X.shape[0] == 0 or X.shape[0] != y.shape[0]:
        raise ValueError(
            f"fit needs matching non-empty samples, got X{X.shape} "
            f"y{y.shape}")
    if X.shape[0] >= X.shape[1]:
        sol, *_ = np.linalg.lstsq(X, y, rcond=None)
        sol = np.clip(sol, 0.0, None)
        if sol.any():
            return CostCoeffs(*sol.tolist())
    pred = X @ base.as_array()
    scale = float(y.sum() / pred.sum()) if pred.sum() > 0 else 1.0
    c = base.as_array() * max(scale, 1e-9)
    return CostCoeffs(*c.tolist())


def prune(program, execution, sig: GraphSignature, num_queries: int,
          candidates: Sequence[Candidate], keep: int = 6,
          coeffs: CostCoeffs = DEFAULT_COEFFS,
          always_keep: Sequence[Candidate] = ()) -> Tuple[Candidate, ...]:
    """Model-ranked top-``keep`` candidates (plus ``always_keep``).

    Ranking is by :func:`predict_us` of the candidate applied to
    ``(program, execution)``; ties break toward the earlier candidate so
    pruning is deterministic.  ``always_keep`` (typically the default
    candidate) survives regardless of rank — the guarantee that tuning
    can never select something worse than what it was allowed to keep.
    """
    scored = []
    for i, cand in enumerate(candidates):
        prog_c, ex_c = cand.apply(program, execution)
        scored.append((predict_us(prog_c, ex_c, sig, num_queries, coeffs),
                       i, cand))
    scored.sort(key=lambda t: (t[0], t[1]))
    kept = [c for _, _, c in scored[:max(int(keep), 1)]]
    for cand in always_keep:
        if cand not in kept:
            kept.append(cand)
    return tuple(kept)


def predictions(program, execution, sig: GraphSignature, num_queries: int,
                candidates: Sequence[Candidate],
                coeffs: CostCoeffs = DEFAULT_COEFFS) -> Dict[Candidate, float]:
    """Modeled cost of every candidate (the ``--no-measure`` ranking)."""
    out = {}
    for cand in candidates:
        prog_c, ex_c = cand.apply(program, execution)
        out[cand] = predict_us(prog_c, ex_c, sig, num_queries, coeffs)
    return out
