"""Distributed walk engine: N asynchronous "pipelines" = N devices
(paper §IV: 16 pipelines over 32 HBM channels → here, the device mesh).

The full run loop lives inside a single ``shard_map`` over the ``ch``
(channel) axis: per superstep each device (a) executes one *phase* of work
for every live task homed on it, (b) terminates finished walks and refills
freed lanes from its local query shard (zero-bubble scheduling), (c)
routes every live task to the device that owns the data its next phase
reads, with one ``all_to_all`` (the butterfly, `router.py`).

One generic superstep serves every sampler through the **phase-program
IR** (`repro.core.phase_program`): a sampler lowers once into typed
gather/score/draw/commit phases with explicit operand residency, and
:class:`ProgramCapability` interprets the lowered program's residency
schedule — all-local programs (uniform/alias/metapath over partitioned
``type_offsets``) execute a whole hop at owner(v_curr); a score phase
resident at owner(v_prev) splits the hop into a propose/verify superstep
pair (rejection Node2Vec); the looping chunk program ping-pongs reservoir
chunks between the two owners (weighted Node2Vec).  The engine allocates
the task word the program's ``carry`` declares (`WalkerSlots` /
`N2VSlots` / `ReservoirSlots`) and drives the same routing path for all.

Because tasks are stateless and their randomness derives from
(seed, query_id, hop), the distributed engine produces *bit-identical
walks* to the single-device engine — the strongest possible correctness
check of the paper's claim that out-of-order, cross-pipeline execution
does not alter the sampled distribution (§V-A).  Tests assert this for
first- AND second-order walks.

Losslessness.  Refill is flow-controlled: a device admits new queries
only up to its fair share of the *global* live-task headroom
(``psum``-coordinated), which bounds live tasks system-wide by N·W_loc;
the router retention region is provisioned to that bound
(`DistConfig.retention_cap`), so bucket overflow can always be retained
and ``drops == 0`` is a guarantee, not a hope.  (The previous
heuristically-sized retention dropped tasks under hub skew — the root
cause of the 8-device bit-identity failure; see ROADMAP.)

Path write-back uses the paper's streaming-window scheme (§IV-B): each
device appends (query_id, hop, vertex) records to a device-resident
emission log, flushed to host memory after the run and scattered into
per-query paths.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rng as task_rng, router
from repro.core.phase_program import (PhaseProgram, chunk_gather,
                                      chunk_score, lower, make_sampler,
                                      reservoir_scan)
from repro.core.rng import SALT_COLUMN, SALT_STOP
from repro.core.samplers import (SamplerSpec, _uniform_index, es_num_chunks,
                                 n2v_bias, rejection_choose)
from repro.core.scheduler import routing_capacity
from repro.core.tasks import (WalkerSlots, empty_n2v_slots,
                              empty_reservoir_slots, empty_slots, zero_stats)
from repro.graph.partition import PartitionedGraph, owner_of


@dataclasses.dataclass(frozen=True)
class DistConfig:
    slots_per_device: int = 256    # W_loc — target live tasks per device
    max_hops: int = 80
    capacity_margin: float = 2.0   # Theorem VI.1 margin on bucket capacity
    retention_factor: float = 1.0  # × N·W_loc (global live bound); >= 1.0
                                   # guarantees drops == 0 (see module doc)
    log_capacity: int = 1 << 16    # emission-log entries per device
    record_paths: bool = True
    max_supersteps: int = 1 << 16
    axis_name: str = "ch"

    def __post_init__(self):
        if self.slots_per_device <= 0:
            raise ValueError(
                f"slots_per_device must be a positive lane count, got "
                f"{self.slots_per_device}")
        if self.max_hops <= 0:
            raise ValueError(f"max_hops must be positive, got "
                             f"{self.max_hops}")
        if self.capacity_margin <= 0:
            raise ValueError(f"capacity_margin must be positive, got "
                             f"{self.capacity_margin}")
        if self.retention_factor <= 0:
            raise ValueError(f"retention_factor must be positive, got "
                             f"{self.retention_factor}")
        if self.log_capacity <= 0 or self.max_supersteps <= 0:
            raise ValueError(
                f"log_capacity / max_supersteps must be positive, got "
                f"{self.log_capacity} / {self.max_supersteps}")

    def bucket_cap(self, num_devices: int) -> int:
        return routing_capacity(self.slots_per_device, num_devices,
                                self.capacity_margin)

    def retention_cap(self, num_devices: int) -> int:
        """Retention region sized to the global live-task bound N·W_loc:
        every live task in the system could, worst case, pile onto one
        device (hub skew) and must be retainable there."""
        return int(math.ceil(self.retention_factor
                             * num_devices * self.slots_per_device))

    def pool_size(self, num_devices: int) -> int:
        return (num_devices * self.bucket_cap(num_devices)
                + self.retention_cap(num_devices))


class LocalView(NamedTuple):
    """Per-device graph shard presented with the sampler interface.

    ``num_shards`` is what makes the shared sampler arithmetic
    residency-aware: `samplers.vertex_row` maps a global vertex id to
    ``v // num_shards``, its row in this shard's per-vertex arrays
    (`edge_exists` and the typed-segment gather run unchanged on either
    the full graph or a view)."""
    row_ptr: jnp.ndarray
    col: jnp.ndarray
    weights: Optional[jnp.ndarray]
    alias_prob: Optional[jnp.ndarray]
    alias_idx: Optional[jnp.ndarray]
    max_degree: int
    type_offsets: Optional[jnp.ndarray] = None
    num_shards: int = 1


class DistLogs(NamedTuple):
    qid: jnp.ndarray     # (N, cap) int32
    hop: jnp.ndarray     # (N, cap) int32
    vertex: jnp.ndarray  # (N, cap) int32
    cursor: jnp.ndarray  # (N,) int32


class StepOut(NamedTuple):
    """What a capability's per-phase step hands back to the generic
    superstep: the updated pool plus the hop-advance/termination masks the
    emission log and refill need.  ``query_id``/``active`` must be left
    untouched by the step — the generic code owns their lifecycle."""
    slots: Any
    adv: jnp.ndarray         # lanes that advanced one hop this superstep
    terminated: jnp.ndarray  # lanes whose walk ended this superstep
    v_next: jnp.ndarray      # vertex to record for advanced lanes
    new_hop: jnp.ndarray     # hop index of that record


def _local_row_access(view: LocalView, v: jnp.ndarray, num_devices: int,
                      v_per_dev: int):
    lid = jnp.clip(jnp.where(v >= 0, v // num_devices, 0), 0, v_per_dev - 1)
    addr = view.row_ptr[lid]
    deg = view.row_ptr[lid + 1] - addr
    return addr, deg


# --------------------------------------------------------------------------
# Generic capability: ONE engine adapter interpreting the lowered phase
# program — residency schedule → routing plan, `carry` → task word, phase
# bodies → the shared executors in `phase_program` / `samplers`.
# --------------------------------------------------------------------------


class ProgramCapability:
    """Sharded lowering of a :class:`~repro.core.phase_program.PhaseProgram`.

    The program's residency schedule picks one of three execution plans
    (this is derived structure, not per-sampler code):

    ``single_phase`` — every phase resident at owner(v_curr): the whole
    hop (Row Access → phase list → Column Access) executes in one
    superstep on the owner, via the same vectorized phase interpreter
    the single-device engine uses (`phase_program.make_sampler`, which
    is residency-aware through `LocalView.num_shards`).  Covers uniform,
    alias, and — with ``type_offsets`` partitioned alongside the CSR
    shards — metapath.

    ``two_phase`` — a score phase resident at owner(v_prev): phase A
    executes the program's csr-gather at owner(v_curr) and stages the
    candidate fan-out in the task word (`N2VSlots`); phase B executes
    the first-accept score at owner(v_prev) with the same
    (seed, qid, hop)-derived uniforms and the shared
    `rejection_choose`/`n2v_bias` arithmetic ⇒ bit-identical walks.
    Hop 0 has no v_prev (bias ≡ 1) and scores locally in phase A, which
    also avoids an owner(-1) thundering-herd hotspot on device 0.

    ``chunked_loop`` — the looping gather/score chunk pair: the O(deg)
    E-S reservoir scan ping-pongs `phase_program.chunk_gather` output
    (staged in `ReservoirSlots`) between owner(v_curr) and
    owner(v_prev)'s `phase_program.chunk_score` fold; phase 2·n_chunks
    finalizes at owner(v_curr) with a column access on the winning
    offset.  Per-lane early finalize: the gather phase flags the chunk
    covering deg(v_curr), and its score phase jumps straight to finalize
    instead of stepping through empty chunks (skipped chunks contribute
    only -inf reservoir keys, so the scanned argmax — and bit-identity
    with the local scan, which folds those same -inf chunks — is
    unchanged).

    Hop-0 prescan (``hop0_inline=False``, closed engine, chunked loop
    only): hop 0 is the one hop whose whole scan is local (bias ≡ 1
    without v_prev), so the closed engine batches it *once* before the
    superstep loop (:meth:`prescan_hop0`) instead of tracing the full
    chunked scan into every superstep — refilled tasks enter the pool
    already at hop 1.  Draws still derive from ``(seed, qid, hop=0,
    chunk)``, so paths are bit-identical.  The streaming engine keeps
    the inline hop-0 path (arrivals land mid-run)."""

    def __init__(self, prog: PhaseProgram, spec: SamplerSpec,
                 cfg: DistConfig, num_devices: int, v_per_dev: int,
                 max_degree: int, hop0_inline: bool = True):
        self.prog, self.spec, self.cfg = prog, spec, cfg
        self.N, self.v_per_dev = num_devices, v_per_dev
        self.schedule = prog.schedule
        if self.schedule == "chunked_loop":
            self.CH = spec.reservoir_chunk
            self.n_chunks = es_num_chunks(max_degree, self.CH)
            self.hop0_inline = hop0_inline
            self.prescan = not hop0_inline
        else:
            self.hop0_inline = True
            self.prescan = False
        if self.schedule == "single_phase":
            self._sampler = make_sampler(spec)

    # ------------------------------------------------- task word / routing

    def empty_pool(self, size: int):
        carry = self.prog.carry
        if carry == "candidates":
            return empty_n2v_slots(size, self.spec.rejection_rounds)
        if carry == "reservoir":
            return empty_reservoir_slots(size, self.CH)
        return empty_slots(size)

    def home(self, slots) -> jnp.ndarray:
        if self.schedule == "single_phase":
            return owner_of(slots.v_curr, self.N)
        if self.schedule == "two_phase":
            return owner_of(jnp.where(slots.phase == 0, slots.v_curr,
                                      jnp.maximum(slots.v_prev, 0)), self.N)
        # chunked loop: even phases (gather / finalize) live at
        # owner(v_curr); odd (score) at owner(v_prev).
        return owner_of(jnp.where(slots.phase % 2 == 0, slots.v_curr,
                                  jnp.maximum(slots.v_prev, 0)), self.N)

    def route_dest(self, slots) -> jnp.ndarray:
        if self.schedule == "two_phase":
            return owner_of(jnp.where(slots.phase == 1,
                                      jnp.maximum(slots.v_prev, 0),
                                      slots.v_curr), self.N)
        return self.home(slots)

    def reset_extras(self, slots, take):
        carry = self.prog.carry
        if carry == "candidates":
            return slots._replace(phase=jnp.where(take, 0, slots.phase))
        if carry == "reservoir":
            return slots._replace(
                phase=jnp.where(take, 0, slots.phase),
                best_key=jnp.where(take, -jnp.inf, slots.best_key),
                best_idx=jnp.where(take, 0, slots.best_idx),
                last_chunk=jnp.where(take, False, slots.last_chunk),
            )
        return slots

    # ------------------------------------------------------------- stepping

    def step(self, view: LocalView, slots, mine, base_key) -> StepOut:
        return {"single_phase": self._step_single,
                "two_phase": self._step_two_phase,
                "chunked_loop": self._step_chunked}[self.schedule](
                    view, slots, mine, base_key)

    def _step_single(self, view: LocalView, slots, mine,
                     base_key) -> StepOut:
        """Whole hop at owner(v_curr): Row Access → phase interpreter →
        Column Access (the sharded twin of `walk_engine._process`)."""
        spec, cfg = self.spec, self.cfg
        if spec.stop_prob > 0.0:
            u_stop = task_rng.task_uniforms(base_key, slots.query_id,
                                            slots.hop, 1, SALT_STOP,
                                            epoch=slots.epoch)[:, 0]
            stop = mine & (u_stop < spec.stop_prob)
        else:
            stop = jnp.zeros_like(mine)

        addr, deg = _local_row_access(view, slots.v_curr, self.N,
                                      self.v_per_dev)
        idx, ok = self._sampler(view, addr, deg, slots, base_key)
        e = jnp.clip(addr + idx, 0, view.col.shape[-1] - 1)
        v_next = view.col[e]

        adv = mine & ~stop & ok
        dead = mine & ~stop & ~ok
        new_hop = jnp.where(adv, slots.hop + 1, slots.hop)
        reached_max = adv & (new_hop >= cfg.max_hops)
        terminated = stop | dead | reached_max

        slots = slots._replace(
            v_curr=jnp.where(adv, v_next, slots.v_curr),
            v_prev=jnp.where(adv, slots.v_curr, slots.v_prev),
            hop=new_hop,
        )
        return StepOut(slots, adv, terminated, v_next, new_hop)

    def _step_two_phase(self, view: LocalView, slots, mine,
                        base_key) -> StepOut:
        """Propose @ owner(v_curr) (csr-gather phase), verify @
        owner(v_prev) (first-accept score phase)."""
        spec, cfg = self.spec, self.cfg
        K = spec.rejection_rounds

        do_a = mine & (slots.phase == 0)
        if spec.stop_prob > 0.0:   # termination draw at the top of a hop
            u_stop = task_rng.task_uniforms(base_key, slots.query_id,
                                            slots.hop, 1, SALT_STOP,
                                            epoch=slots.epoch)[:, 0]
            stop = do_a & (u_stop < spec.stop_prob)
        else:
            stop = jnp.zeros_like(do_a)

        # ---- phase A: the gather(csr, K) phase at owner(v_curr) ---------
        addr, deg = _local_row_access(view, slots.v_curr, self.N,
                                      self.v_per_dev)
        u = task_rng.task_uniforms(base_key, slots.query_id, slots.hop,
                                   2 * K, SALT_COLUMN, epoch=slots.epoch)
        u_col, u_acc = u[:, :K], u[:, K:]
        idx = _uniform_index(deg[:, None], u_col)
        e = jnp.clip(addr[:, None] + idx, 0, view.col.shape[-1] - 1)
        proposals = view.col[e]                                   # (S, K)
        dead = do_a & ~stop & (deg == 0)
        hop0 = do_a & ~stop & (slots.v_prev < 0) & (deg > 0)
        # Hop 0 scores locally: no v_prev ⇒ bias ≡ 1.
        first0 = rejection_choose(spec, u_acc, jnp.ones_like(u_acc))
        v0 = jnp.take_along_axis(proposals, first0[:, None], 1)[:, 0]
        go_b = do_a & ~stop & ~dead & ~hop0

        # ---- phase B: the score(first_accept) phase at owner(v_prev) ----
        do_b = mine & (slots.phase == 1)
        w = n2v_bias(spec, view, slots.v_prev, slots.cand)
        first = rejection_choose(spec, u_acc, w)
        vb = jnp.take_along_axis(slots.cand, first[:, None], 1)[:, 0]

        adv = do_b | hop0
        v_next = jnp.where(hop0, v0, vb)
        new_hop = jnp.where(adv, slots.hop + 1, slots.hop)
        reached_max = adv & (new_hop >= cfg.max_hops)
        terminated = stop | dead | reached_max

        slots = slots._replace(
            v_curr=jnp.where(adv, v_next, slots.v_curr),
            v_prev=jnp.where(adv, slots.v_curr, slots.v_prev),
            hop=new_hop,
            phase=jnp.where(go_b, 1, jnp.where(adv, 0, slots.phase)),
            cand=jnp.where(go_b[:, None], proposals, slots.cand),
        )
        return StepOut(slots, adv, terminated, v_next, new_hop)

    def prescan_hop0(self, view: LocalView, starts, qids, own, base_key):
        """Batched hop-0 scan for the queries this device owns data for
        (chunked loop, closed engine).

        One vectorized E-S reservoir scan over all owned start vertices
        (bias ≡ 1: no v_prev yet), with the exact (seed, qid, hop=0,
        chunk) uniforms and the hop-0 stop draw the inline path uses —
        bit-identical outcomes, evaluated once instead of inside every
        superstep.  Returns ``(v1, adv0, term0, enter)``: the sampled
        hop-1 vertex, whether the query advanced (a path record exists),
        whether it terminated at the prescan, and whether it should enter
        the slot pool (advanced and hop budget left).
        """
        spec, cfg = self.spec, self.cfg
        zeros = jnp.zeros_like(qids)
        addr, deg = _local_row_access(view, starts, self.N, self.v_per_dev)
        if spec.stop_prob > 0.0:
            u = task_rng.task_uniforms(base_key, qids, zeros, 1, SALT_STOP,
                                       epoch=zeros)[:, 0]
            stop = own & (u < spec.stop_prob)
        else:
            stop = jnp.zeros_like(own)
        dead = own & ~stop & (deg == 0)
        adv0 = own & ~stop & ~dead
        scan_slots = WalkerSlots(
            v_curr=starts, v_prev=jnp.full_like(starts, -1), query_id=qids,
            hop=zeros, active=adv0, epoch=zeros)
        idx0, _ = reservoir_scan(spec, view, addr, deg, scan_slots, base_key)
        v1 = view.col[jnp.clip(addr + idx0, 0, view.col.shape[-1] - 1)]
        reached = adv0 & (1 >= cfg.max_hops)
        term0 = stop | dead | reached
        return v1, adv0, term0, adv0 & ~reached

    def _step_chunked(self, view: LocalView, slots, mine,
                      base_key) -> StepOut:
        """One chunk phase of the looping gather/score program."""
        spec, cfg = self.spec, self.cfg
        CH, NC = self.CH, self.n_chunks
        phase = slots.phase
        chunk = phase // 2

        is_gather = mine & (phase % 2 == 0) & (phase < 2 * NC)
        is_score = mine & (phase % 2 == 1)
        is_final = mine & (phase == 2 * NC)
        at_hop_start = is_gather & (chunk == 0)

        if spec.stop_prob > 0.0:
            u_stop = task_rng.task_uniforms(base_key, slots.query_id,
                                            slots.hop, 1, SALT_STOP,
                                            epoch=slots.epoch)[:, 0]
            stop = at_hop_start & (u_stop < spec.stop_prob)
        else:
            stop = jnp.zeros_like(mine)

        addr, deg = _local_row_access(view, slots.v_curr, self.N,
                                      self.v_per_dev)
        dead = at_hop_start & ~stop & (deg == 0)

        # ---- hop 0: all-local scan (bias ≡ 1 without v_prev) ------------
        if self.hop0_inline:
            hop0 = at_hop_start & ~stop & (slots.v_prev < 0) & (deg > 0)
            idx0, _ = reservoir_scan(spec, view, addr, deg, slots, base_key)
            v0 = view.col[jnp.clip(addr + idx0, 0, view.col.shape[-1] - 1)]
        else:  # closed engine: hop 0 was batched by prescan_hop0
            hop0 = jnp.zeros_like(mine)
            v0 = slots.v_curr

        # ---- gather phase: stage chunk c of (candidate, edge weight) ----
        do_gather = is_gather & ~stop & ~dead & ~hop0
        y, w_edge = chunk_gather(view, addr, deg, chunk, CH)
        cand = jnp.where(do_gather[:, None], y, slots.cand)
        cand_w = jnp.where(do_gather[:, None], w_edge, slots.cand_w)

        # ---- score phase: E-S fold under the local N(v_prev) bias -------
        m_key, m_idx = chunk_score(spec, view, slots, chunk, CH, base_key)

        # ---- finalize: column access on the scanned argmax --------------
        idx_f = jnp.clip(slots.best_idx, 0, jnp.maximum(deg - 1, 0))
        v_f = view.col[jnp.clip(addr + idx_f, 0, view.col.shape[-1] - 1)]

        adv = is_final | hop0
        v_next = jnp.where(hop0, v0, v_f)
        new_hop = jnp.where(adv, slots.hop + 1, slots.hop)
        reached_max = adv & (new_hop >= cfg.max_hops)
        terminated = stop | dead | reached_max

        # Early finalize: the gather phase sees deg(v_curr) and flags the
        # chunk covering the last neighbor; its score phase then jumps to
        # the finalize phase rather than stepping through empty chunks.
        covers_deg = (chunk + 1) * CH >= deg
        next_phase = jnp.where(is_score & slots.last_chunk,
                               2 * NC, phase + 1)
        slots = slots._replace(
            v_curr=jnp.where(adv, v_next, slots.v_curr),
            v_prev=jnp.where(adv, slots.v_curr, slots.v_prev),
            hop=new_hop,
            phase=jnp.where(do_gather | is_score, next_phase,
                            jnp.where(adv, 0, phase)),
            cand=cand,
            cand_w=cand_w,
            best_key=jnp.where(is_score, m_key,
                               jnp.where(adv, -jnp.inf, slots.best_key)),
            best_idx=jnp.where(is_score, m_idx,
                               jnp.where(adv, 0, slots.best_idx)),
            last_chunk=jnp.where(do_gather, covers_deg,
                                 jnp.where(adv, False, slots.last_chunk)),
        )
        return StepOut(slots, adv, terminated, v_next, new_hop)


def get_capability(spec: SamplerSpec, cfg: DistConfig, num_devices: int,
                   v_per_dev: int, max_degree: int,
                   hop0_inline: bool = True) -> ProgramCapability:
    """Lower the sampler's phase program to the generic engine adapter.

    ``hop0_inline=False`` (closed engine) lets the chunked-loop schedule
    batch its hop-0 work into a one-time prescan instead of the
    per-superstep critical path.
    """
    prog = lower(spec)
    if prog.capability is None:  # no current program declares None
        raise NotImplementedError(
            f"sampler kind {spec.kind!r} declares no distributed "
            "capability; run it on the single-device backend")
    return ProgramCapability(prog, spec, cfg, num_devices, v_per_dev,
                             max_degree, hop0_inline=hop0_inline)


# --------------------------------------------------------------------------
# Generic superstep: phase-step → emission log → terminate → flow-controlled
# refill → butterfly route.  Identical for every capability.
# --------------------------------------------------------------------------


def _superstep_dist(cap, cfg: DistConfig, N: int, base_key, view,
                    starts_loc, qcount, rank, seeds, carry):
    (slots, head, log_q, log_h, log_v, cursor, stats, done, t) = carry
    W_loc = cfg.slots_per_device
    K = cfg.bucket_cap(N)
    R = cfg.retention_cap(N)
    S = cfg.pool_size(N)

    # ---- process: one phase for locally-homed live tasks ----------------
    mine = slots.active & (cap.home(slots) == rank)
    out = cap.step(view, slots, mine, base_key)
    slots, adv, terminated = out.slots, out.adv, out.terminated

    # ---- emission log (streaming write-back, paper §IV-B) ---------------
    # Runs before the terminated lanes' query_id is cleared (the final hop
    # of a walk is still a recorded visit).
    log_drop = jnp.zeros((), jnp.int32)
    if cfg.record_paths:
        cap_log = cfg.log_capacity
        pos = cursor + jnp.cumsum(adv.astype(jnp.int32)) - 1
        keep = adv & (pos < cap_log)
        p_safe = jnp.where(keep, pos, cap_log)
        log_q = log_q.at[p_safe].set(jnp.where(adv, slots.query_id, -1),
                                     mode="drop")
        log_h = log_h.at[p_safe].set(out.new_hop, mode="drop")
        log_v = log_v.at[p_safe].set(out.v_next, mode="drop")
        log_drop = jnp.sum((adv & ~keep).astype(jnp.int32))
        cursor = jnp.minimum(cursor + jnp.sum(adv.astype(jnp.int32)), cap_log)

    slots = slots._replace(
        query_id=jnp.where(terminated, -1, slots.query_id),
        active=slots.active & ~terminated,
    )

    # ---- zero-bubble refill, flow-controlled to the global live bound ---
    # Each device admits at most its fair share of the global headroom
    # N·W_loc - live, so system-wide live tasks never exceed N·W_loc — the
    # bound the retention region is provisioned for (drops == 0 by
    # construction, not by margin).
    n_active = jnp.sum(slots.active.astype(jnp.int32))
    global_live = jax.lax.psum(n_active, cfg.axis_name)
    slack = jnp.maximum(N * W_loc - global_live, 0)
    free = ~slots.active
    budget = jnp.minimum(jnp.maximum(W_loc - n_active, 0), slack // N)
    avail = jnp.minimum(jnp.maximum(qcount - head, 0), budget)
    rank_free = jnp.cumsum(free.astype(jnp.int32)) - 1
    take = free & (rank_free < avail)
    k_local = head + rank_free
    k_safe = jnp.clip(k_local, 0, starts_loc.shape[0] - 1)
    # Refill seeds: the plain engine admits hop-0 tasks at the start
    # vertex; a hop-0 prescan capability seeds hop-1 tasks (v_prev = the
    # start) and skips queries the prescan already terminated (`enter`).
    seed_vc, seed_vp, seed_hop, seed_enter = seeds
    adm = take & seed_enter[k_safe]  # admitted to the pool
    slots = slots._replace(
        v_curr=jnp.where(adm, seed_vc[k_safe], slots.v_curr),
        v_prev=jnp.where(adm, seed_vp[k_safe], slots.v_prev),
        query_id=jnp.where(adm, k_local * N + rank, slots.query_id),
        hop=jnp.where(adm, seed_hop[k_safe], slots.hop),
        active=slots.active | adm,
        epoch=jnp.where(adm, 0, slots.epoch),  # closed batch == epoch 0
    )
    slots = cap.reset_extras(slots, adm)
    head = head + jnp.sum(take.astype(jnp.int32))

    # ---- route: butterfly all_to_all to each task's next home -----------
    dest = cap.route_dest(slots)
    lane = jnp.arange(S, dtype=jnp.int32)
    priority = jnp.where(lane >= N * K, 0, 1)  # retained tasks go first
    rr = router.pack_buckets(slots, dest, priority, N, K, R)
    incoming = router.exchange(rr.send, cfg.axis_name)
    slots = type(slots)(*(jnp.concatenate([a, b])
                          for a, b in zip(incoming, rr.retention)))

    # ---- stats + global termination -------------------------------------
    busy = jnp.sum(mine.astype(jnp.int32))
    upstream = (head < qcount).astype(jnp.int32)
    stats = stats._replace(
        steps=stats.steps + jnp.sum(adv.astype(jnp.int32)),
        slot_steps=stats.slot_steps + W_loc,
        bubbles=stats.bubbles + jnp.maximum(W_loc - busy, 0),
        starved=stats.starved + jnp.maximum(W_loc - busy, 0) * upstream,
        terminations=stats.terminations
        + jnp.sum(terminated.astype(jnp.int32)),
        supersteps=stats.supersteps + 1,
        route_waits=stats.route_waits + rr.waits,
        drops=stats.drops + rr.drops + log_drop,
        launches=stats.launches + 1,
    )
    n_live = jnp.sum(slots.active.astype(jnp.int32))
    remaining = jnp.maximum(qcount - head, 0)
    done = jax.lax.psum(n_live + remaining, cfg.axis_name) == 0
    return (slots, head, log_q, log_h, log_v, cursor, stats, done, t + 1)


def _run_hop0_prescan(cap, cfg: DistConfig, N: int, rank, view: LocalView,
                      starts_l, qcount_l, base_key, log_q, log_h, log_v):
    """One-time batched hop-0 pass for prescan capabilities (closed engine).

    All devices gather the global query list once; each device runs the
    capability's vectorized hop-0 scan for the start vertices *it* owns,
    logs the resulting hop-1 records locally, and a psum distributes the
    hop-1 refill seeds back to the device staging each query.  Runs before
    the superstep loop — O(Q) work once instead of a full reservoir scan
    traced into every superstep.
    """
    q_loc = starts_l.shape[0]
    starts_all = jax.lax.all_gather(starts_l, cfg.axis_name)   # (N, q_loc)
    qcount_all = jax.lax.all_gather(qcount_l, cfg.axis_name)   # (N,)
    ks = jnp.arange(q_loc, dtype=jnp.int32)
    ranks = jnp.arange(N, dtype=jnp.int32)
    qid_all = (ks[None, :] * N + ranks[:, None]).reshape(-1)
    valid = (ks[None, :] < qcount_all[:, None]).reshape(-1)
    sflat = starts_all.reshape(-1)
    own = valid & (owner_of(sflat, N) == rank)
    v1, adv0, term0, enter = cap.prescan_hop0(view, sflat, qid_all, own,
                                              base_key)

    # The owner that computed each hop-1 vertex logs its (qid, 1, v1)
    # record — same emission-log discipline as the superstep.
    log_drop = jnp.zeros((), jnp.int32)
    cursor = jnp.zeros((), jnp.int32)
    if cfg.record_paths:
        cap_log = log_q.shape[0]
        pos = jnp.cumsum(adv0.astype(jnp.int32)) - 1
        keep = adv0 & (pos < cap_log)
        p_safe = jnp.where(keep, pos, cap_log)
        log_q = log_q.at[p_safe].set(jnp.where(adv0, qid_all, -1),
                                     mode="drop")
        log_h = log_h.at[p_safe].set(1, mode="drop")
        log_v = log_v.at[p_safe].set(v1, mode="drop")
        log_drop = jnp.sum((adv0 & ~keep).astype(jnp.int32))
        cursor = jnp.minimum(jnp.sum(adv0.astype(jnp.int32)), cap_log)

    stats0 = zero_stats()._replace(
        steps=jnp.sum(adv0.astype(jnp.int32)),
        terminations=jnp.sum(term0.astype(jnp.int32)),
        drops=log_drop,
    )

    # Hand every device the hop-1 seeds for the queries IT stages: each
    # query has exactly one owner, so a psum of owner-masked values is a
    # broadcast of that owner's result.
    v1_all = jax.lax.psum(jnp.where(enter, v1, 0), cfg.axis_name)
    enter_all = jax.lax.psum(enter.astype(jnp.int32), cfg.axis_name) > 0
    seeds = (v1_all.reshape(N, q_loc)[rank], starts_l,
             jnp.ones_like(starts_l), enter_all.reshape(N, q_loc)[rank])
    return seeds, log_q, log_h, log_v, cursor, stats0


def make_distributed_engine(pg: PartitionedGraph, spec: SamplerSpec,
                            cfg: DistConfig, mesh: jax.sharding.Mesh):
    """Build a jitted distributed runner over the given 1-D mesh.

    Works for every sampler kind that declares a capability — first- and
    second-order walks share this one routing path.
    """
    N = pg.num_devices
    assert mesh.devices.size == N, (mesh.devices.size, N)
    v_per_dev = pg.vertices_per_device
    prog = lower(spec)
    if "typed" in prog.requires and pg.type_offsets is None:
        raise ValueError(
            "metapath programs need type_offsets partitioned with the "
            "graph — build the CSRGraph with num_edge_types > 0 before "
            "partition_graph")
    cap = get_capability(spec, cfg, N, v_per_dev, pg.max_degree,
                         hop0_inline=False)
    P = jax.sharding.PartitionSpec

    has_w = pg.weights is not None
    has_alias = pg.alias_prob is not None
    has_to = pg.type_offsets is not None

    def body(rowp, colp, wp, app, aip, top, starts_loc, qcount, base_key):
        rank = jax.lax.axis_index(cfg.axis_name)
        view = LocalView(
            row_ptr=rowp[0], col=colp[0],
            weights=wp[0] if has_w else None,
            alias_prob=app[0] if has_alias else None,
            alias_idx=aip[0] if has_alias else None,
            max_degree=pg.max_degree,
            type_offsets=top[0] if has_to else None,
            num_shards=N,
        )
        starts_l = starts_loc[0]
        qcount_l = qcount[0, 0]
        S = cfg.pool_size(N)
        cap_log = cfg.log_capacity if cfg.record_paths else 1
        log_q = jnp.full((cap_log,), -1, jnp.int32)
        log_h = jnp.full((cap_log,), -1, jnp.int32)
        log_v = jnp.full((cap_log,), -1, jnp.int32)
        cursor = jnp.zeros((), jnp.int32)
        stats0 = zero_stats()
        # Default refill seeds: hop-0 tasks at the start vertex.
        seeds = (starts_l, jnp.full_like(starts_l, -1),
                 jnp.zeros_like(starts_l),
                 jnp.ones(starts_l.shape, bool))
        if getattr(cap, "prescan", False):
            # ---- one-time batched hop-0 local scan (out of the
            # per-superstep critical path; see ProgramCapability) ------
            seeds, log_q, log_h, log_v, cursor, stats0 = _run_hop0_prescan(
                cap, cfg, N, rank, view, starts_l, qcount_l, base_key,
                log_q, log_h, log_v)
        carry = (
            cap.empty_pool(S),
            jnp.zeros((), jnp.int32),
            log_q, log_h, log_v, cursor,
            stats0,
            jnp.asarray(False),
            jnp.zeros((), jnp.int32),
        )

        def cond(c):
            return (~c[7]) & (c[8] < cfg.max_supersteps)

        step = partial(_superstep_dist, cap, cfg, N, base_key, view,
                       starts_l, qcount_l, rank, seeds)
        carry = jax.lax.while_loop(cond, step, carry)
        _, head, log_q, log_h, log_v, cursor, stats, _, _ = carry
        return (log_q[None], log_h[None], log_v[None], cursor[None],
                jax.tree.map(lambda x: x[None], stats))

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(cfg.axis_name), P(cfg.axis_name), P(cfg.axis_name),
                  P(cfg.axis_name), P(cfg.axis_name), P(cfg.axis_name),
                  P(cfg.axis_name), P(cfg.axis_name), P()),
        out_specs=(P(cfg.axis_name),) * 4 + (P(cfg.axis_name),),
        check_vma=False,
    )

    @jax.jit
    def run(graph: PartitionedGraph, starts_sharded, qcount, base_key):
        dummy = jnp.zeros((N, 1), jnp.float32)
        dummy_i = jnp.zeros((N, 1), jnp.int32)
        dummy_to = jnp.zeros((N, 1, 2), jnp.int32)
        return smapped(graph.row_ptr, graph.col,
                       graph.weights if has_w else dummy,
                       graph.alias_prob if has_alias else dummy,
                       graph.alias_idx if has_alias else dummy_i,
                       graph.type_offsets if has_to else dummy_to,
                       starts_sharded, qcount, base_key)

    return run


# --------------------------------------------------------------------------
# Open-system (streaming) distributed engine: persistent sharded state,
# chunked supersteps, host injection between chunks — the multi-device
# realization of the ring-buffer slot economy (core/walk_engine.py).  The
# same capability dispatch, flow-controlled refill, and butterfly routing
# as the closed engine; only arrival/injection and harvest differ.
# --------------------------------------------------------------------------


class DistStreamState(NamedTuple):
    """Persistent sharded stream state; every leaf's leading axis is the
    device (channel) axis.

    Arrivals are staged by the host into per-device *arrival rings* —
    (start, qid, epoch) triplets appended at monotone ``tail`` counters on
    whichever device the host round-robins them to.  Refill turns a staged
    arrival into a hop-0 task on the staging device, and the very next
    routing phase (the same butterfly ``all_to_all`` every live task rides)
    carries it to owner(start_vertex) — injected queries reuse the existing
    distributed routing rather than a second injection network.

    ``paths``/``lengths``/``done`` are streaming write-back windows indexed
    by global slot id: each device scatters only the hops *it* executed and
    the host folds the shards with an elementwise max at harvest.  Every
    (qid, hop) cell is written by exactly one device (the one that advanced
    that hop) while all others keep the -1/0 fill, so the fold is exact and
    — unlike the closed engine's bounded emission log — structurally
    lossless: streaming harvests can never drop path records.

    Rings are provisioned to the full stream ``capacity`` per device, so
    even if every live query is staged on one device the ring cannot
    overflow (live queries are bounded by ``capacity`` host-side).
    """

    slots: Any               # capability task word, leaves (N, S, ...)
    ring_start: jnp.ndarray  # (N, cap) int32 — start vertex by arrival seq
    ring_qid: jnp.ndarray    # (N, cap) int32 — slot id by arrival seq
    ring_epoch: jnp.ndarray  # (N, cap) int32 — occupant epoch by arrival seq
    head: jnp.ndarray        # (N,) int32 — monotone per-device issue counter
    tail: jnp.ndarray        # (N,) int32 — monotone per-device arrival counter
    paths: jnp.ndarray       # (N, cap, max_hops+1) int32 — per-device hops
    lengths: jnp.ndarray     # (N, cap) int32
    done: jnp.ndarray        # (N, cap) bool — terminated, by slot id
    stats: WalkStats         # leaves (N,)


def init_dist_stream_state(pg: PartitionedGraph, spec: SamplerSpec,
                           cfg: DistConfig, capacity: int) -> DistStreamState:
    """Empty sharded open-system state with room for ``capacity`` live
    queries (global slot ids 0..capacity-1, shared across devices)."""
    N = pg.num_devices
    cap_ = get_capability(spec, cfg, N, pg.vertices_per_device,
                          pg.max_degree)
    pool = cap_.empty_pool(cfg.pool_size(N))

    def rep(x):
        return jnp.broadcast_to(x[None], (N,) + x.shape)

    return DistStreamState(
        slots=jax.tree.map(rep, pool),
        ring_start=jnp.zeros((N, capacity), jnp.int32),
        ring_qid=jnp.zeros((N, capacity), jnp.int32),
        ring_epoch=jnp.zeros((N, capacity), jnp.int32),
        head=jnp.zeros((N,), jnp.int32),
        tail=jnp.zeros((N,), jnp.int32),
        paths=jnp.full((N, capacity, cfg.max_hops + 1), -1, jnp.int32),
        lengths=jnp.zeros((N, capacity), jnp.int32),
        done=jnp.zeros((N, capacity), bool),
        stats=jax.tree.map(rep, zero_stats()),
    )


@jax.jit
def inject_stream_queries(state: DistStreamState, starts_blk, qid_blk,
                          epoch_blk, counts) -> DistStreamState:
    """Stage arrival blocks into the per-device rings (host→device).

    ``starts_blk``/``qid_blk``/``epoch_blk`` are (N, B) blocks (padded to a
    fixed B so injection compiles O(log capacity) shapes); row r's first
    ``counts[r]`` entries are real arrivals for device r.  Recycled slots'
    ``done`` bits and path rows are cleared on *every* device shard — an
    old occupant's hops may have been recorded anywhere.
    """
    N, cap = state.ring_qid.shape
    B = starts_blk.shape[1]
    idx = jnp.arange(B, dtype=jnp.int32)[None, :]
    counts = jnp.asarray(counts, jnp.int32)
    valid = idx < counts[:, None]
    row = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, B))
    pos = jnp.where(valid, (state.tail[:, None] + idx) % cap, cap)
    ring_start = state.ring_start.at[row, pos].set(
        jnp.asarray(starts_blk, jnp.int32), mode="drop")
    ring_qid = state.ring_qid.at[row, pos].set(
        jnp.asarray(qid_blk, jnp.int32), mode="drop")
    ring_epoch = state.ring_epoch.at[row, pos].set(
        jnp.asarray(epoch_blk, jnp.int32), mode="drop")

    cols = jnp.where(valid, jnp.asarray(qid_blk, jnp.int32), cap).reshape(-1)
    done = state.done.at[:, cols].set(False, mode="drop")
    paths = state.paths.at[:, cols, :].set(-1, mode="drop")
    lengths = state.lengths.at[:, cols].set(0, mode="drop")
    return state._replace(
        ring_start=ring_start, ring_qid=ring_qid, ring_epoch=ring_epoch,
        tail=state.tail + counts, done=done, paths=paths, lengths=lengths)


def _superstep_dist_stream(cap, cfg: DistConfig, N: int, capacity: int,
                           base_key, view, rank, carry):
    """One streaming superstep: phase-step → path/done scatter → terminate
    → flow-controlled ring refill → butterfly route (mirrors
    `_superstep_dist`, with the arrival ring in place of the start shard
    and scatter windows in place of the emission log)."""
    i, _, st = carry
    slots = st.slots
    W_loc = cfg.slots_per_device
    K = cfg.bucket_cap(N)
    R = cfg.retention_cap(N)
    S = cfg.pool_size(N)

    # ---- process: one phase for locally-homed live tasks ----------------
    mine = slots.active & (cap.home(slots) == rank)
    out = cap.step(view, slots, mine, base_key)
    slots, adv, terminated = out.slots, out.adv, out.terminated

    # ---- streaming write-back: scatter executed hops locally ------------
    scatter_q = jnp.where(adv, slots.query_id, capacity)   # capacity = drop
    paths = st.paths.at[scatter_q, out.new_hop].set(out.v_next, mode="drop")
    lengths = st.lengths.at[scatter_q].set(out.new_hop + 1, mode="drop")
    done = st.done.at[jnp.where(terminated, slots.query_id, capacity)].set(
        True, mode="drop")

    slots = slots._replace(
        query_id=jnp.where(terminated, -1, slots.query_id),
        active=slots.active & ~terminated,
    )

    # ---- zero-bubble refill from the local arrival ring, flow-controlled
    # to the global live bound N·W_loc (identical psum coordination to the
    # closed engine, so losslessness carries over to the open system) ----
    n_active = jnp.sum(slots.active.astype(jnp.int32))
    global_live = jax.lax.psum(n_active, cfg.axis_name)
    slack = jnp.maximum(N * W_loc - global_live, 0)
    free = ~slots.active
    budget = jnp.minimum(jnp.maximum(W_loc - n_active, 0), slack // N)
    avail = jnp.minimum(jnp.maximum(st.tail - st.head, 0), budget)
    rank_free = jnp.cumsum(free.astype(jnp.int32)) - 1
    take = free & (rank_free < avail)
    pos = (st.head + jnp.maximum(rank_free, 0)) % capacity
    qid = st.ring_qid[pos]
    start = st.ring_start[pos]
    ep = st.ring_epoch[pos]
    slots = slots._replace(
        v_curr=jnp.where(take, start, slots.v_curr),
        v_prev=jnp.where(take, -1, slots.v_prev),
        query_id=jnp.where(take, qid, slots.query_id),
        hop=jnp.where(take, 0, slots.hop),
        active=slots.active | take,
        epoch=jnp.where(take, ep, slots.epoch),
    )
    slots = cap.reset_extras(slots, take)
    head = st.head + jnp.sum(take.astype(jnp.int32))
    # Record hop 0 on the staging device; the route below hands the task
    # to owner(start_vertex) for its first hop.
    sq = jnp.where(take, qid, capacity)
    paths = paths.at[sq, 0].set(start, mode="drop")
    lengths = lengths.at[sq].set(1, mode="drop")

    # ---- route: butterfly all_to_all to each task's next home -----------
    dest = cap.route_dest(slots)
    lane = jnp.arange(S, dtype=jnp.int32)
    priority = jnp.where(lane >= N * K, 0, 1)  # retained tasks go first
    rr = router.pack_buckets(slots, dest, priority, N, K, R)
    incoming = router.exchange(rr.send, cfg.axis_name)
    slots = type(slots)(*(jnp.concatenate([a, b])
                          for a, b in zip(incoming, rr.retention)))

    # ---- stats + global work flag ---------------------------------------
    busy = jnp.sum(mine.astype(jnp.int32))
    upstream = (head < st.tail).astype(jnp.int32)
    stats = st.stats._replace(
        steps=st.stats.steps + jnp.sum(adv.astype(jnp.int32)),
        slot_steps=st.stats.slot_steps + W_loc,
        bubbles=st.stats.bubbles + jnp.maximum(W_loc - busy, 0),
        starved=st.stats.starved + jnp.maximum(W_loc - busy, 0) * upstream,
        terminations=st.stats.terminations
        + jnp.sum(terminated.astype(jnp.int32)),
        supersteps=st.stats.supersteps + 1,
        route_waits=st.stats.route_waits + rr.waits,
        drops=st.stats.drops + rr.drops,
        launches=st.stats.launches + 1,
    )
    n_live = jnp.sum(slots.active.astype(jnp.int32))
    pending = jnp.maximum(st.tail - head, 0)
    work = jax.lax.psum(n_live + pending, cfg.axis_name) > 0
    st = DistStreamState(
        slots=slots, ring_start=st.ring_start, ring_qid=st.ring_qid,
        ring_epoch=st.ring_epoch, head=head, tail=st.tail, paths=paths,
        lengths=lengths, done=done, stats=stats)
    return (i + 1, work, st)


def make_sharded_stream_engine(pg: PartitionedGraph, spec: SamplerSpec,
                               cfg: DistConfig, mesh: jax.sharding.Mesh,
                               capacity: int):
    """Build a jitted ``run(graph, state, base_key, k) -> DistStreamState``
    advancing the sharded stream by at most ``k`` supersteps (stopping
    early when no work remains anywhere).  ``k`` is traced; the host
    injects with :func:`inject_stream_queries` between chunks and harvests
    by max-folding the per-device path windows.
    """
    N = pg.num_devices
    assert mesh.devices.size == N, (mesh.devices.size, N)
    v_per_dev = pg.vertices_per_device
    prog = lower(spec)
    if "typed" in prog.requires and pg.type_offsets is None:
        raise ValueError(
            "metapath programs need type_offsets partitioned with the "
            "graph — build the CSRGraph with num_edge_types > 0 before "
            "partition_graph")
    cap_ = get_capability(spec, cfg, N, v_per_dev, pg.max_degree)
    P = jax.sharding.PartitionSpec

    has_w = pg.weights is not None
    has_alias = pg.alias_prob is not None
    has_to = pg.type_offsets is not None

    def body(rowp, colp, wp, app, aip, top, state, base_key, k):
        rank = jax.lax.axis_index(cfg.axis_name)
        view = LocalView(
            row_ptr=rowp[0], col=colp[0],
            weights=wp[0] if has_w else None,
            alias_prob=app[0] if has_alias else None,
            alias_idx=aip[0] if has_alias else None,
            max_degree=pg.max_degree,
            type_offsets=top[0] if has_to else None,
            num_shards=N,
        )
        st = jax.tree.map(lambda x: x[0], state)
        live0 = jnp.sum(st.slots.active.astype(jnp.int32))
        pending0 = jnp.maximum(st.tail - st.head, 0)
        work0 = jax.lax.psum(live0 + pending0, cfg.axis_name) > 0

        step = partial(_superstep_dist_stream, cap_, cfg, N, capacity,
                       base_key, view, rank)

        def cond(c):
            return c[1] & (c[0] < k)

        _, _, st = jax.lax.while_loop(
            cond, step, (jnp.zeros((), jnp.int32), work0, st))
        return jax.tree.map(lambda x: x[None], st)

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(cfg.axis_name), P(cfg.axis_name), P(cfg.axis_name),
                  P(cfg.axis_name), P(cfg.axis_name), P(cfg.axis_name),
                  P(cfg.axis_name), P(), P()),
        out_specs=P(cfg.axis_name),
        check_vma=False,
    )

    @jax.jit
    def run(graph: PartitionedGraph, state: DistStreamState, base_key,
            k) -> DistStreamState:
        dummy = jnp.zeros((N, 1), jnp.float32)
        dummy_i = jnp.zeros((N, 1), jnp.int32)
        dummy_to = jnp.zeros((N, 1, 2), jnp.int32)
        return smapped(graph.row_ptr, graph.col,
                       graph.weights if has_w else dummy,
                       graph.alias_prob if has_alias else dummy,
                       graph.alias_idx if has_alias else dummy_i,
                       graph.type_offsets if has_to else dummy_to,
                       state, base_key, jnp.asarray(k, jnp.int32))

    return run


def shard_starts(starts, num_devices: int):
    """Round-robin shard start vertices across devices; returns the
    (N, q_loc) padded shard matrix and the (N, 1) per-device counts.
    Query ``k`` of device ``r`` is global query id ``k·N + r``."""
    starts = np.asarray(starts, dtype=np.int32)
    N = num_devices
    q_loc = max((starts.shape[0] + N - 1) // N, 1)
    starts_sh = np.zeros((N, q_loc), dtype=np.int32)
    qcount = np.zeros((N, 1), dtype=np.int32)
    for r in range(N):
        part = starts[r::N]
        starts_sh[r, : part.size] = part
        qcount[r, 0] = part.size
    return starts_sh, qcount


def mesh_devices(num_partitions: int) -> np.ndarray:
    """The first ``num_partitions`` devices, one per graph partition.

    Raises when fewer devices exist: a mesh over fewer devices than
    partitions would silently run a different deployment.
    """
    devs = jax.devices()
    if num_partitions > len(devs):
        raise ValueError(
            f"the graph has {num_partitions} partitions but only "
            f"{len(devs)} {devs[0].platform} device(s) are present; "
            f"partition for at most {len(devs)} devices")
    return np.array(devs[:num_partitions])


def _run_distributed(pg: PartitionedGraph, starts, spec: SamplerSpec,
                     cfg: Optional[DistConfig] = None,
                     mesh: Optional[jax.sharding.Mesh] = None, seed: int = 0):
    """One-shot distributed run. Returns (DistLogs, WalkStats-per-device)."""
    cfg = cfg or DistConfig()
    N = pg.num_devices
    if mesh is None:
        mesh = jax.sharding.Mesh(mesh_devices(N), (cfg.axis_name,))
    starts_sh, qcount = shard_starts(starts, N)
    run = make_distributed_engine(pg, spec, cfg, mesh)
    base_key = task_rng.stream_key(seed)
    log_q, log_h, log_v, cursor, stats = run(
        pg, jnp.asarray(starts_sh), jnp.asarray(qcount), base_key)
    logs = DistLogs(qid=log_q, hop=log_h, vertex=log_v, cursor=cursor)
    return logs, stats


def run_distributed(pg: PartitionedGraph, starts, spec: SamplerSpec,
                    cfg: Optional[DistConfig] = None,
                    mesh: Optional[jax.sharding.Mesh] = None, seed: int = 0):
    """Deprecated one-shot entry — use
    ``repro.walker.compile(program, backend="sharded").run(...)``."""
    warnings.warn(
        "run_distributed is deprecated; use repro.walker.compile(program, "
        "backend='sharded').run(graph, starts) instead",
        DeprecationWarning, stacklevel=2)
    return _run_distributed(pg, starts, spec, cfg, mesh, seed)


def assemble_paths(logs: DistLogs, starts, max_hops: int):
    """Host-side scatter of the emission logs into per-query paths."""
    starts = np.asarray(starts)
    Q = starts.shape[0]
    paths = np.full((Q, max_hops + 1), -1, dtype=np.int32)
    lengths = np.ones((Q,), dtype=np.int32)
    paths[:, 0] = starts
    q = np.asarray(logs.qid).reshape(-1)
    h = np.asarray(logs.hop).reshape(-1)
    v = np.asarray(logs.vertex).reshape(-1)
    valid = q >= 0
    q, h, v = q[valid], h[valid], v[valid]
    paths[q, h] = v
    np.maximum.at(lengths, q, h + 1)
    return paths, lengths
