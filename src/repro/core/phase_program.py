"""Sampler phase-program IR: one declarative sampler definition, every
backend a lowering of it.

RidgeWalker's Markov decomposition (paper §IV–§VI) makes each hop of a
walk a stateless task that factors into fine-grained *phases* any
substrate can execute out of order — the paper's Row Access / Sampling /
Column Access pipeline stages, generalized (LightRW makes the same
observation for second-order dynamic walks: every sampler reduces to a
small set of gather/score/commit primitives).  This module is that
factorization made explicit:

  * a :class:`SamplerSpec` **lowers once** (:func:`lower`) into a
    :class:`PhaseProgram` — a short sequence of typed :class:`Phase`
    records (``draw`` / ``gather`` / ``score`` / ``commit``) with
    explicit *operand residency* (owner-of-``v_curr`` vs
    owner-of-``v_prev``), and
  * every backend is a generic interpreter/lowerer of that IR:

      - the single-device jnp superstep executes the phases vectorized
        in one pass (:func:`make_sampler` — the replacement for the old
        per-sampler ``sample_*`` dispatch table);
      - the sharded engine (`core/distributed.py`) reads the residency
        schedule to build the task word and per-superstep routing plan
        (replacing the hand-written ``_FirstOrderCap`` /
        ``_TwoPhaseN2VCap`` / ``_ChunkedReservoirCap`` trio);
      - the fused device-resident Pallas kernel
        (`kernels/fused_superstep`) stages the same phases' operands
        through its double-buffered DMA machinery for every program:
        loop-free phase lists run as one launch-resident pass, and the
        chunked reservoir scan runs as an in-kernel degree-adaptive
        chunk loop with its carry held in SMEM (``fused`` is True for
        all programs — there is no jnp fallback).

Because each phase's arithmetic lives in exactly one executor here and
each backend drives the *same* executors (or, for the kernel, a pinned
scalar transliteration of them), all lowerings sample bit-identical
walks — the property `tests/test_fused_step.py` / `test_walker_api.py`
pin across impls and backends.

Phase vocabulary
----------------
``draw(width, salt)``
    Consume ``width`` U[0,1) draws from the task's stateless stream
    (`rng.task_uniforms` at the given salt channel).
``gather(segment, width)``
    Materialize candidate operands from the graph at the phase's
    residency: ``csr`` (``width`` proposal columns from N(v_curr)),
    ``typed`` (the MetaPath sub-segment bounds from ``type_offsets``),
    ``alias`` (Walker alias-table probes), ``chunk`` (one reservoir
    chunk of (candidate, edge weight)).
``score(reduction)``
    Reduce candidates to a decision: ``pick_uniform``, ``alias_accept``,
    ``first_accept`` (bounded-round rejection), ``es_reservoir``
    (Efraimidis–Spirakis weighted reservoir fold).
``commit``
    Column access on the chosen offset + hop advance (engine-owned).

Residency is what the sharded engine routes on: a program whose phases
all live at ``v_curr`` is a one-superstep hop at owner(v_curr)
(``first_order``); a ``score`` at ``v_prev`` splits the hop into a
propose/verify superstep pair (``two_phase``); a looping chunk program
ping-pongs gather@owner(v_curr) / score@owner(v_prev) until the scan
covers deg(v_curr) (``chunked_reservoir``).

Run ``python -m repro.core.phase_program`` to regenerate the
sampler × step_impl × backend support matrix embedded in
``docs/api.md`` and ``python -m repro.core.phase_program --schedule``
for the phase-program → schedule → backend table in
``docs/architecture.md`` — both docs tables are generated from these
declarations, not hand-maintained (pinned by tests;
``python -m repro.core.phase_program --check`` fails on drift and is
run by CI).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import rng as task_rng
from repro.core.rng import SALT_CHUNK0, SALT_COLUMN, SALT_STOP
from repro.core.samplers import (KINDS, SamplerSpec, _uniform_index,
                                 es_chunk_score, es_merge,
                                 n2v_bias, rejection_choose, vertex_row)

__all__ = ["KINDS", "Phase", "PhaseProgram", "DrawStream", "lower",
           "make_sampler", "reservoir_scan", "chunk_gather", "chunk_score",
           "fused_kinds", "support_rows", "render_support_matrix",
           "render_schedule_table"]


@dataclasses.dataclass(frozen=True)
class Phase:
    """One typed phase of a hop.

    ``op``        — draw | gather | score | commit.
    ``variant``   — gather segment (csr/typed/alias/chunk) or score
                    reduction (pick_uniform/alias_accept/first_accept/
                    es_reservoir); "" for draw/commit.
    ``residency`` — which vertex's owner holds this phase's operands:
                    "v_curr" or "v_prev".
    ``width``     — per-lane operand fan-out (rng draws for ``draw``,
                    candidates for ``gather``).
    ``salt``      — rng salt channel for ``draw``.
    """

    op: str
    variant: str = ""
    residency: str = "v_curr"
    width: int = 1
    salt: int = SALT_COLUMN

    @property
    def cacheable(self) -> bool:
        """May this phase's graph operands be served from the hot-vertex
        cache?  True exactly for ``v_curr``-resident ``gather``/``commit``
        phases: their operands are slices of the current vertex's
        adjacency payload, which is what `graph.hot_cache` packs into
        SMEM.  ``v_prev``-resident phases (the rejection verify and the
        reservoir bias/membership probes) address N(v_prev) and always
        take the HBM DMA path."""
        return self.op in ("gather", "commit") and self.residency == "v_curr"


@dataclasses.dataclass(frozen=True)
class PhaseProgram:
    """A lowered sampler: the phase list plus the derived facts every
    backend dispatches on.

    ``loop``    — the gather/score pair repeats per reservoir chunk
                  (trip count ceil(deg/chunk)).
    ``carry``   — cross-residency task-word payload the phases thread
                  between owners: "none" (single-word WalkerSlots),
                  "candidates" (N2VSlots: K proposal columns + a phase
                  bit), "reservoir" (ReservoirSlots: chunk buffer +
                  running E-S maximum + phase counter).
    ``requires``— graph payloads the program samples from
                  ("alias" | "typed" | "weights"), used for validation
                  and the docs matrix.
    """

    kind: str
    phases: Tuple[Phase, ...]
    loop: bool = False
    carry: str = "none"
    requires: Tuple[str, ...] = ()

    # ------------------------------------------------------------ derived

    @property
    def schedule(self) -> str:
        """Sharded execution schedule implied by the residencies:
        ``single_phase`` (whole hop at owner(v_curr)), ``two_phase``
        (propose at owner(v_curr), verify at owner(v_prev)), or
        ``chunked_loop`` (per-chunk gather/score ping-pong)."""
        if self.loop:
            return "chunked_loop"
        if any(p.residency == "v_prev" for p in self.phases):
            return "two_phase"
        return "single_phase"

    @property
    def capability(self) -> Optional[str]:
        """Distributed capability the program declares — the dispatch
        key `core.distributed` allocates the task word and routing
        schedule from.  ``None`` would mean "not distributable"; every
        current program declares one (MetaPath's typed sub-segments are
        partitioned alongside the CSR shards)."""
        return {"single_phase": "first_order",
                "two_phase": "two_phase",
                "chunked_loop": "chunked_reservoir"}[self.schedule]

    @property
    def fused(self) -> bool:
        """Lowerable to the device-resident fused superstep kernel.

        True for every program: loop-free phase lists run as one
        launch-resident pass, and the looping chunk program
        (``chunked_loop``) runs as an in-kernel degree-adaptive chunk
        loop whose reservoir carry stays SMEM-resident — so the fused
        kernel covers the whole sampler matrix and the engine never
        falls back to jnp.
        """
        return True

    @property
    def pallas(self) -> bool:
        """Covered by the one-hop `kernels/walk_step` Pallas kernel
        (single-residency programs over the plain/alias CSR segments)."""
        return all(p.residency == "v_curr" for p in self.phases) and not (
            self.loop or "typed" in self.requires)

    @property
    def cache_payloads(self) -> Tuple[str, ...]:
        """Adjacency payload arrays the hot-vertex cache must pack for
        this program — read off the cacheable (``v_curr``-resident)
        gather/commit phases, so `graph.hot_cache.build_hot_cache` sizes
        the SMEM block from the program, not a hand-kept list.

        Every program needs ``col`` (the commit column access); the
        alias probe adds ``alias_prob``/``alias_idx``, the typed gather
        adds ``type_offsets``, and the reservoir chunk gather adds
        ``weights``.  ``v_prev``-resident phases contribute nothing —
        their operands stay on the HBM DMA path.
        """
        payloads = ["col"]
        for ph in self.phases:
            if not ph.cacheable or ph.op != "gather":
                continue
            payloads += {"alias": ["alias_prob", "alias_idx"],
                         "typed": ["type_offsets"],
                         "chunk": ["weights"],
                         "csr": []}[ph.variant]
        return tuple(payloads)

    # ------------------------------------------- static-analysis exports

    def draw_streams(self) -> Tuple["DrawStream", ...]:
        """Declarative RNG draw streams this program consumes per task —
        the schedule-export hook the `repro.analysis` RNG-collision pass
        reads.

        Each ``draw`` phase contributes one stream at its salt channel;
        in a looping program the draw repeats per chunk at
        ``salt + chunk``, an open-ended *family* (chunk counts are
        degree-dependent and statically unbounded).  Engine-issued draws
        (the PPR stop draw) are declared separately
        (`repro.core.walk_engine.ENGINE_DRAW_STREAMS`) — they share the
        same (seed, epoch, qid, hop) tuple, so the analyzer checks them
        against these streams too.
        """
        streams = []
        for n, ph in enumerate(self.phases):
            if ph.op != "draw":
                continue
            streams.append(DrawStream(
                site=f"{self.kind}.phases[{n}].draw",
                salt=ph.salt, width=ph.width, family=self.loop))
        return tuple(streams)


class DrawStream(NamedTuple):
    """One per-task RNG draw stream: ``width`` uniforms at salt channel
    ``salt`` (or, for a chunk *family*, at every salt in ``[salt, ∞)`` —
    one chunk per salt, degree-dependent count).  Two streams with
    distinct salts are disjoint by the Threefry key fold; two streams
    sharing any salt value both consume counters ``[0, width)`` there and
    therefore collide — the RNG-collision pass's whole check."""

    site: str
    salt: int
    width: int
    family: bool = False

    def salt_span(self) -> Tuple[int, Optional[int]]:
        """Half-open salt interval this stream draws from (``None`` hi =
        unbounded chunk family)."""
        return (self.salt, None if self.family else self.salt + 1)


@functools.lru_cache(maxsize=None)
def lower(spec: SamplerSpec) -> PhaseProgram:
    """Lower a sampler definition to its phase program (cached — specs
    are frozen/hashable, and backends re-lower freely)."""
    k = spec.kind
    if k == "uniform":
        return PhaseProgram(k, (
            Phase("draw", width=1),
            Phase("score", "pick_uniform"),
            Phase("commit"),
        ))
    if k == "alias":
        return PhaseProgram(k, (
            Phase("draw", width=2),
            Phase("gather", "alias"),
            Phase("score", "alias_accept"),
            Phase("commit"),
        ), requires=("alias",))
    if k == "metapath":
        return PhaseProgram(k, (
            Phase("draw", width=1),
            Phase("gather", "typed"),
            Phase("score", "pick_uniform"),
            Phase("commit"),
        ), requires=("typed",))
    if k == "rejection_n2v":
        K = spec.rejection_rounds
        return PhaseProgram(k, (
            Phase("draw", width=2 * K),
            Phase("gather", "csr", width=K),
            Phase("score", "first_accept", residency="v_prev", width=K),
            Phase("commit"),
        ), carry="candidates")
    if k == "reservoir_n2v":
        CH = spec.reservoir_chunk
        return PhaseProgram(k, (
            Phase("draw", width=CH, salt=SALT_CHUNK0),
            Phase("gather", "chunk", width=CH),
            Phase("score", "es_reservoir", residency="v_prev", width=CH),
            Phase("commit"),
        ), loop=True, carry="reservoir", requires=("weights",))
    raise ValueError(f"unknown sampler kind: {k!r}")


# ==========================================================================
# jnp lowering: execute the phase list vectorized, one pass per hop.
# Each (op, variant) pair has exactly one executor; the sharded engine's
# propose/verify/chunk supersteps call the same executors on its local
# graph views (they are residency-aware via `samplers.vertex_row` /
# `edge_exists`), which is what keeps every backend bit-identical.
# ==========================================================================


class _Ctx:
    """Mutable interpretation state threaded through one hop's phases."""

    __slots__ = ("spec", "g", "addr", "deg", "slots", "base_key", "u",
                 "cand_idx", "cand", "seg_base", "seg_cnt", "index", "ok")

    def __init__(self, spec, g, addr, deg, slots, base_key):
        self.spec, self.g = spec, g
        self.addr, self.deg = addr, deg
        self.slots, self.base_key = slots, base_key
        self.u = None
        self.cand_idx = None     # (W, K) neighbor offsets
        self.cand = None         # (W, K) candidate vertices
        self.seg_base = None     # typed sub-segment base offset
        self.seg_cnt = None      # typed sub-segment length
        self.index = None        # chosen neighbor offset
        self.ok = None           # lane has a valid continuation


def _exec_draw(ph: Phase, ctx: _Ctx):
    s = ctx.slots
    ctx.u = task_rng.task_uniforms(ctx.base_key, s.query_id, s.hop, ph.width,
                                   ph.salt, epoch=s.epoch)


def _exec_gather_alias(ph: Phase, ctx: _Ctx):
    # The alias tables live beside the CSR segment at owner(v_curr); the
    # jnp pass probes them directly in the score phase (the fused kernel
    # lowers this phase to its two one-element DMA probes).
    pass


def _exec_gather_typed(ph: Phase, ctx: _Ctx):
    """MetaPath sub-segment bounds for hop t's scheduled edge type."""
    g, s, spec = ctx.g, ctx.slots, ctx.spec
    sched = jnp.asarray(spec.metapath, jnp.int32)
    t = sched[s.hop % len(spec.metapath)]
    row = vertex_row(g, s.v_curr)
    base = g.type_offsets[row, t]
    ctx.seg_base = base
    ctx.seg_cnt = g.type_offsets[row, t + 1] - base


def _exec_gather_csr(ph: Phase, ctx: _Ctx):
    """K proposal columns from N(v_curr) (rejection sampling phase A)."""
    K = ph.width
    u_col = ctx.u[:, :K]
    ctx.cand_idx = _uniform_index(ctx.deg[:, None], u_col)
    e = jnp.clip(ctx.addr[:, None] + ctx.cand_idx, 0,
                 ctx.g.col.shape[-1] - 1)
    ctx.cand = ctx.g.col[e]


def _exec_score_pick_uniform(ph: Phase, ctx: _Ctx):
    """index = min(floor(u·n), n-1) over the CSR segment or, when a typed
    gather ran, over the scheduled sub-segment (no match → dead lane)."""
    if ctx.seg_base is not None:
        ctx.index = ctx.seg_base + _uniform_index(ctx.seg_cnt, ctx.u[:, 0])
        ctx.ok = (ctx.seg_cnt > 0) & (ctx.deg > 0)
    else:
        ctx.index = _uniform_index(ctx.deg, ctx.u[:, 0])
        ctx.ok = ctx.deg > 0


def _exec_score_alias_accept(ph: Phase, ctx: _Ctx):
    """Walker alias method: accept the column draw with prob[e], else
    take the alias index — O(1) per draw, two uniforms, two probes."""
    g = ctx.g
    k = _uniform_index(ctx.deg, ctx.u[:, 0])
    e = jnp.clip(ctx.addr + k, 0, g.col.shape[-1] - 1)
    accept = ctx.u[:, 1] < g.alias_prob[e]
    idx = jnp.where(accept, k, g.alias_idx[e])
    ctx.index = jnp.clip(idx, 0, jnp.maximum(ctx.deg - 1, 0))
    ctx.ok = ctx.deg > 0


def _exec_score_first_accept(ph: Phase, ctx: _Ctx):
    """Bounded-round rejection (gSampler/KnightKing style): first
    proposal whose (p, q) bias survives the accept test wins; the last
    round is forced (geometric tail bias < (1-a_min)^K, measured in
    tests)."""
    K = ph.width
    w = n2v_bias(ctx.spec, ctx.g, ctx.slots.v_prev, ctx.cand)
    first = rejection_choose(ctx.spec, ctx.u[:, K:], w)
    ctx.index = jnp.take_along_axis(ctx.cand_idx, first[:, None], 1)[:, 0]
    ctx.ok = ctx.deg > 0


def _exec_commit(ph: Phase, ctx: _Ctx):
    pass  # column access + hop advance are engine-owned


_JNP_EXEC = {
    ("draw", ""): _exec_draw,
    ("gather", "alias"): _exec_gather_alias,
    ("gather", "typed"): _exec_gather_typed,
    ("gather", "csr"): _exec_gather_csr,
    ("score", "pick_uniform"): _exec_score_pick_uniform,
    ("score", "alias_accept"): _exec_score_alias_accept,
    ("score", "first_accept"): _exec_score_first_accept,
    ("commit", ""): _exec_commit,
}


def reservoir_scan(spec: SamplerSpec, g, addr, deg, slots, base_key):
    """Chunked-loop lowering executed locally: the whole E-S reservoir
    scan of N(v_curr) (weighted Node2Vec, LightRW's method) — key =
    u^(1/w'), keep the max; O(deg) work per hop.

    This is the jnp lowering of the looping (draw, gather-chunk,
    score-chunk) program; the sharded engine lowers the *same* program
    to a per-chunk gather@owner(v_curr) / score@owner(v_prev) superstep
    ping-pong (`distributed.ProgramCapability`), staging
    :func:`chunk_gather`'s output through the task word and folding with
    the shared `es_chunk_score`/`es_merge` — same uniforms, same float
    ops, bit-identical scanned argmax.

    The scan scores exactly the live lanes' positions.  Position ``p`` of
    lane ``i`` is flat item ``start[i] + p`` of the lane-major packing of
    ``deg`` positions per active lane, scored in blocks of ``W * chunk``:
    each item derives its uniform from the same Threefry counters as
    ``task_uniforms(..., chunk, SALT_CHUNK0 + p // chunk)`` (draw
    ``p % chunk`` of that chunk's block layout), gathers its column and
    weight, and keys with the same float ops as `es_chunk_score`.  Per
    lane the earliest position holding the maximal key wins — exactly
    the chunked fold's first-argmax within a chunk and strict ``>``
    across chunks.  A lane with no finite key keeps position 0, as the
    chunked fold does.  Inactive lanes score nothing; their sample is
    never consumed.  The work per superstep is the live lanes' degree
    sum, not ``W * max_degree``.

    Items are sorted by lane, so a block finds each item's lane with one
    W-sized scatter and a prefix sum, and folds each lane's items with a
    segmented prefix scan read at the lane's last item (on a TPU v5e at
    W = 4096 this block, bias aside, runs 2.75x faster than one built on
    a per-item binary search and B-sized segment scatters)."""
    CH = spec.reservoir_chunk
    W = addr.shape[0]
    B = W * CH
    pairs = (CH + 1) // 2
    base = jnp.asarray(base_key, jnp.uint32)
    span = jnp.where(slots.active, jnp.maximum(deg, 0), 0).astype(jnp.int32)
    ends = jnp.cumsum(span)
    starts = ends - span
    total = ends[-1]
    t = jnp.arange(B, dtype=jnp.int32)

    def fold(a, b):
        """Segmented (max key, first position) fold of lane-sorted items:
        ``b`` restarts the fold where its lane differs from ``a``'s."""
        a_key, a_pos, a_lane = a
        b_key, b_pos, b_lane = b
        keep_a = (a_lane == b_lane) & ~(b_key > a_key)
        return (jnp.where(keep_a, a_key, b_key),
                jnp.where(keep_a, a_pos, b_pos), b_lane)

    def block(b, carry):
        best_key, best_pos = carry
        lo = b * B
        f = lo + t
        # Lane of item f = (number of lanes starting at or before f) - 1.
        rel = starts - lo
        first = jnp.zeros((B,), jnp.int32).at[
            jnp.where(rel >= 1, rel, B)].add(1, mode="drop")
        lane = (jnp.sum(rel <= 0, dtype=jnp.int32) - 1
                + jnp.cumsum(first))
        lane = jnp.minimum(lane, W - 1)
        p = f - starts[lane]
        live = f < total
        c, j = p // CH, p % CH
        k0, k1 = task_rng.task_key_pair(
            base[0], base[1], slots.query_id[lane], slots.hop[lane],
            SALT_CHUNK0 + c,
            None if slots.epoch is None else slots.epoch[lane])
        low = j < pairs
        blk = jnp.where(low, j, j - pairs)
        x1 = jnp.where(blk + pairs < CH, blk + pairs, 0)
        y0, y1 = task_rng.threefry2x32(k0, k1, blk, x1)
        u = task_rng.bits_to_uniform(jnp.where(low, y0, y1))
        e = jnp.clip(addr[lane] + p, 0, g.col.shape[-1] - 1)
        y = g.col[e]
        w_edge = g.weights[e] if g.weights is not None else 1.0
        w = w_edge * n2v_bias(spec, g, slots.v_prev[lane], y)
        key = jnp.where(live & (w > 0), jnp.log(u + 1e-20) / w, -jnp.inf)
        seg = jnp.where(live, lane, W)   # W = the block's dead tail
        s_key, s_pos, _ = jax.lax.associative_scan(fold, (key, p, seg))
        # Each lane's fold is read at its last item inside this block.
        last = jnp.minimum(ends, lo + B) - 1 - lo
        here = (span > 0) & (ends > lo) & (starts < lo + B)
        at = jnp.clip(last, 0, B - 1)
        b_key = jnp.where(here, s_key[at], -jnp.inf)
        take = b_key > best_key          # blocks ascend in position
        return (jnp.where(take, b_key, best_key),
                jnp.where(take, s_pos[at], best_pos))

    init = (jnp.full((W,), -jnp.inf), jnp.zeros((W,), jnp.int32))
    _, best = jax.lax.fori_loop(0, (total + B - 1) // B, block, init)
    return jnp.clip(best, 0, jnp.maximum(deg - 1, 0)), deg > 0


def chunk_gather(g, addr, deg, chunk, width):
    """Stage chunk ``chunk`` of (candidate vertex, edge weight) from the
    CSR segment at ``addr`` — the gather phase of the chunked-loop
    program, shared by the sharded lowering.  Padding lanes carry
    ``(-1, 0.0)`` so the score phase's validity mask and E-S keys match
    the local scan exactly."""
    pos = chunk[:, None] * width + jnp.arange(width, dtype=jnp.int32)[None, :]
    valid = pos < deg[:, None]
    e = jnp.clip(addr[:, None] + pos, 0, g.col.shape[-1] - 1)
    y = jnp.where(valid, g.col[e], -1)
    if g.weights is not None:
        w_edge = jnp.where(valid, g.weights[e], 0.0)
    else:
        w_edge = jnp.where(valid, 1.0, 0.0)
    return y, w_edge


def chunk_score(spec: SamplerSpec, g, slots, chunk, width, base_key):
    """Score one staged chunk at owner(v_prev): E-S keys under the local
    adjacency bias, folded into the carried reservoir maximum — the
    score phase of the chunked-loop program (sharded lowering)."""
    u = task_rng.task_uniforms(base_key, slots.query_id, slots.hop, width,
                               SALT_CHUNK0 + chunk, epoch=slots.epoch)
    svalid = slots.cand >= 0
    w = slots.cand_w * n2v_bias(spec, g, slots.v_prev, slots.cand)
    c_best, c_key = es_chunk_score(u, svalid, w)
    return es_merge(slots.best_key, slots.best_idx, chunk, width, c_best,
                    c_key)


def make_sampler(spec: SamplerSpec):
    """Lower ``spec`` for the vectorized single-superstep backend:
    returns ``sample(g, addr, deg, slots, base_key) -> (index, ok)``.

    ``g`` may be the full `CSRGraph` or a sharded `LocalView` — the
    executors are residency-aware (`samplers.vertex_row` maps vertex ids
    to local rows), so the same lowering serves the single-device engine
    and the sharded engine's single-phase hops."""
    prog = lower(spec)
    if prog.loop:
        return functools.partial(reservoir_scan, spec)
    execs = [( _JNP_EXEC[(p.op, p.variant)], p) for p in prog.phases]

    def sample(g, addr, deg, slots, base_key):
        """Execute the lowered phases over one superstep's lane pool."""
        ctx = _Ctx(spec, g, addr, deg, slots, base_key)
        for fn, ph in execs:
            fn(ph, ctx)
        return ctx.index, ctx.ok

    return sample


# ==========================================================================
# Support matrix: the docs table is generated from the programs, not
# hand-maintained (docs/api.md embeds render_support_matrix()'s output;
# a test pins the embedding).
# ==========================================================================

_KIND_LABEL = {
    "uniform": "uniform (urw/ppr)",
    "alias": "alias (deepwalk)",
    "rejection_n2v": "rejection_n2v (node2vec)",
    "reservoir_n2v": "reservoir_n2v (weighted node2vec)",
    "metapath": "metapath",
}


def _default_spec(kind: str) -> SamplerSpec:
    return SamplerSpec(kind=kind,
                       metapath=(0,) if kind == "metapath" else ())


def support_rows():
    """One row per sampler kind: which step_impl lowers it natively,
    which sharded capability it declares, and the schedule / carry /
    residency facts the architecture table documents — all read off the
    phase programs."""
    rows = []
    for kind in KINDS:
        prog = lower(_default_spec(kind))
        residency = ("v_curr + v_prev"
                     if any(p.residency == "v_prev" for p in prog.phases)
                     else "v_curr")
        rows.append({
            "kind": kind,
            "label": _KIND_LABEL[kind],
            "jnp": True,
            "pallas": prog.pallas,
            "fused": prog.fused,
            "capability": prog.capability,
            "schedule": prog.schedule,
            "carry": prog.carry,
            "residency": residency,
            "requires": prog.requires,
            "phases": prog.phases,
            "cache_payloads": prog.cache_payloads,
        })
    return rows


def render_support_matrix() -> str:
    """Markdown sampler × step_impl × backend matrix (embedded verbatim
    in docs/api.md — regenerate with
    ``python -m repro.core.phase_program``)."""
    lines = [
        "| sampler | `jnp` | `pallas` (one-hop kernel) "
        "| `fused` (k-superstep kernel) | `sharded` capability |",
        "|---|---|---|---|---|",
    ]
    for r in support_rows():
        pallas = "✓" if r["pallas"] else "falls back to jnp"
        fused = "✓" if r["fused"] else "falls back to jnp"
        lines.append(f"| {r['label']} | ✓ | {pallas} | {fused} "
                     f"| `{r['capability']}` |")
    return "\n".join(lines)


def _phase_sig(ph: Phase) -> str:
    """Compact one-token rendering of a phase for the schedule table."""
    tag = ph.op if not ph.variant else f"{ph.op}:{ph.variant}"
    if ph.op in ("draw", "gather") and ph.width > 1:
        tag += f"×{ph.width}"
    if ph.residency == "v_prev":
        tag += "@v_prev"
    return tag


def render_schedule_table() -> str:
    """Markdown phase-program → schedule → backend table (embedded
    verbatim in docs/architecture.md — regenerate with
    ``python -m repro.core.phase_program --schedule``).

    Widths are those of the default spec (K = rejection_rounds = 12,
    CH = reservoir_chunk = 64); they scale with the spec fields but the
    schedule / carry / residency columns are spec-invariant.
    """
    lines = [
        "| sampler | phases | schedule | carry | residency "
        "| graph payloads | hot-cache payloads |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in support_rows():
        phases = " → ".join(_phase_sig(p) for p in r["phases"])
        loop = " (looped per chunk)" if r["schedule"] == "chunked_loop" \
            else ""
        req = ", ".join(f"`{x}`" for x in r["requires"]) or "—"
        hot = ", ".join(f"`{x}`" for x in r["cache_payloads"])
        lines.append(f"| {r['label']} | `{phases}`{loop} "
                     f"| `{r['schedule']}` | `{r['carry']}` "
                     f"| {r['residency']} | {req} | {hot} |")
    return "\n".join(lines)


def fused_kinds() -> Tuple[str, ...]:
    """Sampler kinds the fused device-resident kernel covers (derived
    from the phase programs, not a hand-kept list — all of them since
    the chunked reservoir scan moved in-kernel)."""
    return tuple(r["kind"] for r in support_rows() if r["fused"])


def _check_docs_embeddings() -> int:
    """Verify the committed docs embed the generated tables verbatim.

    Returns a process exit code: 0 when every generated line appears in
    its doc, 1 (with a diff-style report) on drift — the CI docs-drift
    job runs ``python -m repro.core.phase_program --check``.
    """
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[3]
    targets = [
        (root / "docs" / "api.md", render_support_matrix(),
         "support matrix"),
        (root / "docs" / "architecture.md", render_support_matrix(),
         "support matrix"),
        (root / "docs" / "architecture.md", render_schedule_table(),
         "schedule table"),
    ]
    failures = []
    for path, table, name in targets:
        text = path.read_text() if path.exists() else ""
        missing = [ln for ln in table.splitlines() if ln not in text]
        if missing:
            failures.append((path, name, missing))
    for path, name, missing in failures:
        print(f"DRIFT: {path} is missing {len(missing)} generated "
              f"{name} line(s):")
        for ln in missing:
            print(f"  {ln}")
    if failures:
        print("regenerate with `python -m repro.core.phase_program` / "
              "`--schedule` and paste the output into the docs")
        return 1
    print("docs embeddings up to date")
    return 0


def _main(argv=None) -> int:
    """CLI: print the generated docs tables or check them for drift."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.core.phase_program",
        description="Generate (or drift-check) the docs tables derived "
                    "from the sampler phase programs.")
    ap.add_argument("--schedule", action="store_true",
                    help="print the phase-program → schedule → backend "
                         "table (docs/architecture.md) instead of the "
                         "support matrix (docs/api.md)")
    ap.add_argument("--check", action="store_true",
                    help="verify docs/*.md embed the generated tables "
                         "verbatim; exit 1 on drift")
    args = ap.parse_args(argv)
    if args.check:
        return _check_docs_embeddings()
    print(render_schedule_table() if args.schedule
          else render_support_matrix())
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
