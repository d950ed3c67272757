"""Single-device walk engine: out-of-order slot-pool execution with
zero-bubble refill (paper §V + §VI, adapted to a SIMD superstep machine).

The per-hop superstep names its phases with ``jax.named_scope``
(``walk.sample``, ``walk.commit``, ``walk.refill``, ``walk.record``); the
names ride on the compiled ops' metadata, so a device trace can split the
superstep's time by phase at no run-time cost.

One *superstep* advances every live lane by one hop through the paper's
three stages — Row Access → Sampling → Column Access — then terminates
finished walks and immediately refills freed lanes from the pending-query
queue (the zero-bubble scheduler).  Because each task is stateless
(`tasks.py`) and its randomness derives from (seed, query_id, hop)
(`rng.py`), lanes are interchangeable: a query may be served by different
lanes on different hops without changing its sampled path — the Markov
decomposition of §V-A.

Two scheduling modes reproduce the paper's Fig. 11 ablation axis:
  * ``zero_bubble`` — per-superstep compaction + refill (RidgeWalker).
  * ``static``      — bulk-synchronous batches: a batch of W queries is
    bound to lanes and the engine waits for the *slowest* walk before
    loading the next batch (FastRW/LightRW-style static scheduling).
    Early-terminating walks leave idle lanes that are counted as bubbles.

The host→device injection latency is modeled by the queue's ``staged``
watermark, advanced by a feedback controller with C-superstep-delayed
observations of ``head`` (paper §VI-A "Back-pressure and Observation
Delay"); `scheduler.py` provisions the stage-ahead depth per Theorem VI.1.

Closed vs. open system
----------------------
The engine exposes two execution styles over one superstep function:

  * ``build_engine`` — the closed system of the paper's evaluation: a fixed
    query batch is drained to completion inside a single
    ``jax.lax.while_loop``.
  * ``make_superstep_runner`` — the open system of the queuing-theoretic
    setting Theorem VI.1 actually models: a jitted
    ``run_supersteps(graph, state, seed, k)`` advances *at most* ``k``
    supersteps and returns the persistent :class:`StreamState`, so the host
    can append newly arrived queries (``inject_queries``) between chunks
    without recompiling.  ``k`` and the arrival count are traced scalars;
    only the buffer shapes are static.

Ring-buffer slot economy (continuous operation)
-----------------------------------------------
The open system never drains: query-id slots are a *ring*.  When a query
completes and its paths are harvested, the host returns its slot to a free
ring and re-issues it to the next arrival with ``epoch + 1``; the RNG
derivation is salted with ``(epoch, qid, hop)`` (`rng.task_fold`), so
successive occupants of one slot sample independent walks and an unbounded
request stream is served with a bounded device buffer — no generation
rotation, no drain barrier.  ``inject_queries`` scatters arrivals into
host-assigned slots and appends them to the arrival-order ring
(``QueryQueue.order``) that refill consumes; epoch 0 derives bit-identically
to the classic ``(seed, query_id, hop)`` tuple, so a closed batch is simply
epoch 0 of a stream.

`repro.walker` is the front-end over both (``compile(program).run()`` /
``.stream()`` / ``.serve()``); the deprecated ``make_engine`` /
``run_walks`` names survive as warning shims.

Because path content depends only on ``(seed, epoch, query_id, hop)``,
chunked execution is bit-identical to one-shot execution for the same seed,
and epoch ``e`` of a stream is bit-identical to a closed batch run under
``rng.stream_key(seed, e)`` — the properties `tests/test_streaming.py`
pins down.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import rng as task_rng, scheduler as sched
from repro.core.phase_program import lower as lower_program, make_sampler
from repro.core.rng import SALT_COLUMN, SALT_STOP
from repro.core.samplers import SamplerSpec
from repro.core.tasks import (QueryQueue, WalkerSlots, WalkResult, WalkStats,
                              empty_queue, empty_slots, make_queue, zero_stats)
from repro.graph.csr import CSRGraph, column_access, row_access


# Allowed scheduling modes / step implementations — shared with
# ExecutionConfig so the two validation layers cannot drift.
MODES = ("zero_bubble", "static")
STEP_IMPLS = ("jnp", "pallas", "fused")

# Schedule-export hook for the static analyzer (`repro.analysis`): draw
# streams the engine itself issues per task, outside any sampler phase
# program.  The PPR stop draw shares the task's (seed, epoch, qid, hop)
# tuple with the sampler's draws, so its salt channel must stay disjoint
# from every phase-program stream — the RNG-collision pass checks these
# against `PhaseProgram.draw_streams()`.  (All three backends — this jnp
# superstep, the sharded engine, and the fused kernel — issue the same
# logical stop draw at SALT_STOP.)
ENGINE_DRAW_STREAMS = (("engine.stop_draw", SALT_STOP, 1),)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 1024          # W — lane count (outstanding tasks/core)
    max_hops: int = 80             # paper §VIII-A4: query length 80
    record_paths: bool = True
    mode: str = "zero_bubble"      # zero_bubble | static
    injection_delay: int = 0       # C supersteps of host->device latency
    queue_depth_factor: float = 1.0  # × Theorem VI.1 depth D
    max_supersteps: int = 1 << 20  # safety bound for the while loop
    step_impl: str = "jnp"         # jnp | pallas (one-hop kernel) | fused
                                   # (device-resident multi-hop kernel)
    hops_per_launch: int = 16      # fused only: supersteps per kernel launch
                                   # (the k of the O(k·state) -> O(state)
                                   # host-traffic reduction)
    cache_budget: int = 0          # fused only: byte budget of the SMEM
                                   # hot-vertex adjacency cache (0 = off);
                                   # gathers on cached hubs skip the HBM
                                   # DMA loops, bit-identically

    def __post_init__(self):
        if self.num_slots <= 0:
            raise ValueError(
                f"num_slots must be a positive lane count (W), got "
                f"{self.num_slots}; a zero-width slot pool can do no work")
        if self.max_hops <= 0:
            raise ValueError(
                f"max_hops must be positive, got {self.max_hops}; a walk "
                "needs at least one hop of budget")
        if self.mode not in MODES:
            raise ValueError(
                f"mode must be one of {MODES}, got {self.mode!r}")
        if self.step_impl not in STEP_IMPLS:
            raise ValueError(
                f"step_impl must be one of {STEP_IMPLS}, got "
                f"{self.step_impl!r}")
        if self.injection_delay < 0:
            raise ValueError(
                f"injection_delay is a latency in supersteps and cannot be "
                f"negative, got {self.injection_delay}")
        if self.queue_depth_factor <= 0:
            raise ValueError(
                f"queue_depth_factor must be positive (it scales the "
                f"Theorem VI.1 stage-ahead depth), got "
                f"{self.queue_depth_factor}")
        if self.max_supersteps <= 0:
            raise ValueError(
                f"max_supersteps must be positive, got {self.max_supersteps}")
        if self.hops_per_launch <= 0:
            raise ValueError(
                f"hops_per_launch must be a positive superstep count per "
                f"fused-kernel launch, got {self.hops_per_launch}")
        if self.cache_budget < 0:
            raise ValueError(
                f"cache_budget is a byte budget (0 disables the hot-vertex "
                f"cache) and cannot be negative, got {self.cache_budget}")


class StreamState(NamedTuple):
    """Persistent engine state threaded through chunked superstep runs.

    All leaves are device arrays with static shapes, so the same jitted
    ``run_supersteps`` serves every chunk of a stream.  ``done[q]`` flips to
    True when query ``q`` terminates — the harvesting signal for the service
    layer (a lane-independent property: it does not matter which lane served
    the final hop).
    """

    slots: WalkerSlots
    queue: QueryQueue
    paths: jnp.ndarray      # (Q, max_hops+1) int32; (1, 1) when not recording
    lengths: jnp.ndarray    # (Q,) int32; (1,) when not recording
    done: jnp.ndarray       # (Q,) bool — query fully terminated
    stats: WalkStats
    head_hist: jnp.ndarray  # (C+1,) int32 — delayed head observations


def _stage_depth(cfg: EngineConfig) -> int:
    d = sched.min_queue_depth(cfg.num_slots, mu=1.0, delay=cfg.injection_delay)
    return max(1, int(round(cfg.queue_depth_factor * d)))


def maybe_build_cache(spec: SamplerSpec, cfg: EngineConfig, graph: CSRGraph):
    """Hot-vertex cache for this (spec, cfg, graph), or ``None``.

    The cache only exists for the fused kernel with a positive byte
    budget; its payload set comes from the phase program's declared
    ``cache_payloads`` (columns always, plus weights / alias tables /
    typed offsets as the sampler's gather phases require).  Building is
    host-side numpy work — callers that rebind graphs should memoize on
    graph identity (`repro.walker.compile` does).
    """
    if cfg.step_impl != "fused" or cfg.cache_budget <= 0:
        return None
    from repro.graph.hot_cache import build_hot_cache
    payloads = lower_program(spec).cache_payloads
    return build_hot_cache(graph, payloads, cfg.cache_budget)


def _fresh_buffers(cfg: EngineConfig, num_queries: int):
    if cfg.record_paths:
        paths = jnp.full((num_queries, cfg.max_hops + 1), -1, jnp.int32)
        lengths = jnp.zeros((num_queries,), jnp.int32)
    else:
        paths = jnp.full((1, 1), -1, jnp.int32)
        lengths = jnp.zeros((1,), jnp.int32)
    return paths, lengths


def init_stream_state(cfg: EngineConfig, capacity: int) -> StreamState:
    """Empty open-system state: a buffer with room for ``capacity`` queries,
    none of which have arrived yet (``tail == 0``)."""
    paths, lengths = _fresh_buffers(cfg, capacity)
    return StreamState(
        slots=empty_slots(cfg.num_slots),
        queue=empty_queue(capacity),
        paths=paths,
        lengths=lengths,
        done=jnp.zeros((capacity,), bool),
        stats=zero_stats(),
        head_hist=jnp.zeros((cfg.injection_delay + 1,), jnp.int32),
    )


def inject_queries(state: StreamState, qids, new_starts=None, epochs=None,
                   n_valid=None) -> StreamState:
    """Admit arrivals into ring slots (host→device injection).

    ``qids`` are the slot ids the host popped from its free ring (a slot is
    free initially or once its previous occupant was harvested and
    released); ``epochs`` are the occupant epochs salting each slot's RNG
    stream.  All three arrays may be padded to a fixed block size to bound
    the number of compiled shapes; only the first ``n_valid`` entries
    become real queries.  The arrival sequence ``tail`` advances by
    ``n_valid`` and the new occupants are appended to the arrival-order
    ring that refill consumes.  Recycled slots' ``done`` bits and recorded
    path rows are cleared here, so stale epochs can never leak into a
    harvest.  The host must only hand out free slots — `WalkStream` /
    `serve.WalkService` own that free-ring bookkeeping.

    The pre-ring form ``inject_queries(state, new_starts, n_valid)``
    (append fresh queries at sequential slots from the tail) survives as a
    deprecated shim.
    """
    if epochs is None:  # legacy 3-arg form: (state, new_starts, n_valid)
        warnings.warn(
            "inject_queries(state, starts, n_valid) is deprecated; the ring "
            "engine takes (state, qids, starts, epochs, n_valid) — or use "
            "repro.walker.compile(program).stream(graph), which owns the "
            "slot-ring bookkeeping", DeprecationWarning, stacklevel=2)
        starts = jnp.asarray(qids, jnp.int32)
        n_valid = jnp.asarray(new_starts, jnp.int32)
        # Sequential fresh slots at the tail, epoch 0 — exactly the old
        # append semantics (pad entries beyond n_valid stay inert).
        qids = state.queue.tail + jnp.arange(starts.shape[0], dtype=jnp.int32)
        new_starts = starts
        epochs = jnp.zeros((starts.shape[0],), jnp.int32)
    return _inject_queries(state, qids, new_starts, epochs, n_valid)


@jax.jit
def _inject_queries(state: StreamState, qids: jnp.ndarray,
                    new_starts: jnp.ndarray, epochs: jnp.ndarray,
                    n_valid) -> StreamState:
    q = state.queue
    cap = q.capacity
    n = jnp.asarray(n_valid, jnp.int32)
    qids = jnp.asarray(qids, jnp.int32)
    idx = jnp.arange(qids.shape[0], dtype=jnp.int32)
    valid = idx < n
    slot = jnp.where(valid, qids, cap)                       # cap = OOB drop
    sv = q.start_vertex.at[slot].set(
        jnp.asarray(new_starts, jnp.int32), mode="drop")
    ep = q.epoch.at[slot].set(jnp.asarray(epochs, jnp.int32), mode="drop")
    pos = jnp.where(valid, (q.tail + idx) % cap, cap)
    order = q.order.at[pos].set(qids, mode="drop")
    done = state.done.at[slot].set(False, mode="drop")
    paths, lengths = state.paths, state.lengths
    if paths.shape[0] == state.done.shape[0]:  # recording paths
        paths = paths.at[slot].set(-1, mode="drop")
        lengths = lengths.at[slot].set(0, mode="drop")
    return state._replace(
        queue=q._replace(start_vertex=sv, epoch=ep, order=order,
                         tail=q.tail + n),
        done=done, paths=paths, lengths=lengths)


def _refill(slots: WalkerSlots, queue: QueryQueue, paths, lengths,
            cfg: EngineConfig, terminated: jnp.ndarray):
    """Zero-bubble compaction + refill: freed lanes pull the next staged
    arrivals via a prefix-sum ranking (the butterfly balancer's O(1)-per-task
    dispatch, §VI-C, realized as a vectorized scan).  Arrivals are consumed
    from the order ring — the slot id, start vertex, and epoch of occupant
    ``head + rank`` all come from the ring, so reclaimed slots are re-issued
    transparently."""
    with jax.named_scope("walk.refill"):
        free = (~slots.active) | terminated
        if cfg.mode == "static":
            # Bulk-synchronous: only reload when the whole batch drained.
            all_free = jnp.all(free)
            free = free & all_free
        avail = jnp.maximum(queue.staged - queue.head, 0)
        rank = jnp.cumsum(free.astype(jnp.int32)) - 1   # rank among free lanes
        take = free & (rank < avail)
        nq = queue.capacity
        pos = (queue.head + jnp.maximum(rank, 0)) % nq  # arrival seq -> ring
        qid = queue.order[pos]
        start = queue.start_vertex[qid]
        ep = queue.epoch[qid]

        new_slots = WalkerSlots(
            v_curr=jnp.where(take, start, slots.v_curr),
            v_prev=jnp.where(take, -1, slots.v_prev),
            query_id=jnp.where(take, qid,
                               jnp.where(terminated, -1, slots.query_id)),
            hop=jnp.where(take, 0, slots.hop),
            active=jnp.where(take, True, slots.active & ~terminated),
            epoch=jnp.where(take, ep, slots.epoch),
        )
        n_taken = jnp.sum(take.astype(jnp.int32))
        new_queue = queue._replace(head=queue.head + n_taken)
    if cfg.record_paths:
        with jax.named_scope("walk.record"):
            scatter_q = jnp.where(take, qid, nq)  # nq = OOB -> dropped
            paths = paths.at[scatter_q, 0].set(start, mode="drop")
            lengths = lengths.at[scatter_q].set(1, mode="drop")
    return new_slots, new_queue, paths, lengths


def _advance_controller(queue: QueryQueue, head_hist: jnp.ndarray,
                        cfg: EngineConfig, depth: int):
    """Feedback-driven staging: observe head with C-superstep delay, keep
    the staged watermark >= delayed_head + D (Theorem VI.1), clipped to the
    queries that have actually *arrived* (``tail``) — in the open system the
    controller reacts to live arrivals, not a fixed batch size.

    ``head_hist`` holds the last C+1 head observations; pushing the current
    head first and reading index 0 yields the head from exactly C
    supersteps ago (the freshest observation available under the delay)."""
    with jax.named_scope("walk.refill"):
        head_hist = jnp.concatenate([head_hist[1:], queue.head[None]])
        delayed_head = head_hist[0]
        target = jnp.minimum(delayed_head + depth, queue.tail)
        staged = jnp.maximum(queue.staged, target)
        return queue._replace(staged=staged), head_hist


def _process(graph: CSRGraph, rows, spec: SamplerSpec, cfg: EngineConfig,
             base_key, slots: WalkerSlots, paths, lengths, done):
    """One hop for every live lane: Row Access → Sampling → Column Access →
    terminate (paper Alg. II.1 lines 5-9, vectorized over lanes).  ``rows``
    is the graph's row layout (:func:`_kernel_rows`) for the Pallas
    walk-step kernel."""
    A = slots.active

    with jax.named_scope("walk.sample"):
        # PPR teleport/termination draw (before the hop; geometric length).
        if spec.stop_prob > 0.0:
            u_stop = task_rng.task_uniforms(
                base_key, slots.query_id, slots.hop, 1, SALT_STOP,
                epoch=slots.epoch)[:, 0]
            stop = A & (u_stop < spec.stop_prob)
        else:
            stop = jnp.zeros_like(A)

        if cfg.step_impl == "pallas" and lower_program(spec).pallas:
            # Fused Pallas walk-step kernel (async DMA pipeline,
            # kernels/walk_step).
            from repro.kernels.walk_step import ops as walk_ops
            if spec.kind == "uniform":
                u = task_rng.task_uniforms(base_key, slots.query_id,
                                           slots.hop, 1, SALT_COLUMN,
                                           epoch=slots.epoch)
                v_next, deg = walk_ops.walk_step_uniform(
                    slots.v_curr, u[:, 0], rows)
            else:
                u = task_rng.task_uniforms(base_key, slots.query_id,
                                           slots.hop, 2, SALT_COLUMN,
                                           epoch=slots.epoch)
                v_next, deg = walk_ops.walk_step_alias(
                    slots.v_curr, u[:, 0], u[:, 1], rows)
            ok = deg > 0
        else:
            addr, deg = row_access(graph, slots.v_curr)           # stage 1
            sampler = make_sampler(spec)                          # phase program
            idx, ok = sampler(graph, addr, deg, slots, base_key)  # stage 2
            v_next = column_access(graph, addr, idx)              # stage 3

    with jax.named_scope("walk.commit"):
        adv = A & ~stop & ok
        dead = A & ~stop & ~ok
        new_hop = jnp.where(adv, slots.hop + 1, slots.hop)
        reached_max = adv & (new_hop >= cfg.max_hops)
        terminated = stop | dead | reached_max

        new_slots = WalkerSlots(
            v_curr=jnp.where(adv, v_next, slots.v_curr),
            v_prev=jnp.where(adv, slots.v_curr, slots.v_prev),
            query_id=slots.query_id,
            hop=new_hop,
            active=slots.active,
            epoch=slots.epoch,
        )
    if cfg.record_paths:
        with jax.named_scope("walk.record"):
            nq = paths.shape[0]
            scatter_q = jnp.where(adv, slots.query_id, nq)
            paths = paths.at[scatter_q, new_hop].set(v_next, mode="drop")
            lengths = lengths.at[scatter_q].set(new_hop + 1, mode="drop")
    with jax.named_scope("walk.commit"):
        nd = done.shape[0]
        scatter_d = jnp.where(terminated & A, slots.query_id, nd)
        done = done.at[scatter_d].set(True, mode="drop")
    return new_slots, terminated, adv, paths, lengths, done


def _superstep(graph, rows, spec, cfg, base_key, depth,
               state: StreamState) -> StreamState:
    slots, queue, paths, lengths, done, stats, head_hist = state
    W = cfg.num_slots

    slots, terminated, adv, paths, lengths, done = _process(
        graph, rows, spec, cfg, base_key, slots, paths, lengths, done)

    with jax.named_scope("walk.commit"):
        n_active = jnp.sum(slots.active.astype(jnp.int32))
        idle = W - n_active
        # Idle lanes while unserved queries exist upstream = scheduler
        # starvation (what Theorem VI.1 eliminates); idle lanes after the
        # last arrived query was issued = unavoidable tail drain.
        upstream = (queue.head < queue.tail).astype(jnp.int32)
        stats = stats._replace(
            steps=stats.steps + jnp.sum(adv.astype(jnp.int32)),
            slot_steps=stats.slot_steps + W,
            bubbles=stats.bubbles + idle,
            starved=stats.starved + idle * upstream,
            terminations=stats.terminations
            + jnp.sum((terminated & slots.active).astype(jnp.int32)),
            supersteps=stats.supersteps + 1,
            # The per-hop impls dispatch one device program per superstep;
            # the fused kernel instead counts one launch per k supersteps.
            launches=stats.launches + 1,
        )

    queue, head_hist = _advance_controller(queue, head_hist, cfg, depth)
    slots, queue, paths, lengths = _refill(slots, queue, paths, lengths, cfg,
                                           terminated)
    return StreamState(slots, queue, paths, lengths, done, stats, head_hist)


def _kernel_rows(graph: CSRGraph, spec: SamplerSpec, cfg: EngineConfig):
    """The graph's row layout for the Pallas kernels, built once per run
    before the superstep loop (None for the jnp superstep)."""
    if cfg.step_impl == "jnp":
        return None
    from repro.kernels.common import graph_rows
    return graph_rows(graph, spec.reservoir_chunk)


def _work_left(state: StreamState):
    return (state.queue.head < state.queue.tail) | jnp.any(state.slots.active)


def make_superstep_runner(spec: SamplerSpec, cfg: EngineConfig, cache=None):
    """Build a jitted ``run_supersteps(graph, state, seed, k) -> StreamState``.

    Advances the stream by at most ``k`` supersteps, stopping early when no
    work remains (no staged queries and no live lanes).  ``k`` is a traced
    scalar, so chunk sizes can vary call-to-call without recompilation; the
    host injects arrivals between chunks with :func:`inject_queries`.

    With ``cfg.step_impl == "fused"`` the chunk is executed as
    ``ceil(k / hops_per_launch)`` launches of the device-resident fused
    kernel instead of ``k`` superstep bounces — same state protocol, same
    bit-exact paths, O(state) host traffic per launch instead of per hop.
    ``cache`` is the graph-specific :class:`~repro.graph.HotVertexCache`
    from :func:`maybe_build_cache` (fused + ``cache_budget > 0`` only).
    """
    depth = _stage_depth(cfg)
    # Every phase program lowers to the fused kernel (the chunked
    # reservoir runs as an in-kernel chunk loop) — cfg.step_impl is
    # taken at face value, no fallback resolution.
    assert lower_program(spec).fused, spec.kind

    if cfg.step_impl == "fused":
        from repro.kernels.fused_superstep import build_fused_launch
        launch = build_fused_launch(spec, cfg, depth, cache=cache)

        @jax.jit
        def run_supersteps(graph: CSRGraph, state: StreamState, seed,
                           k) -> StreamState:
            base_key = task_rng.stream_key(seed)
            k = jnp.asarray(k, jnp.int32)
            rows = _kernel_rows(graph, spec, cfg)

            def cond(carry):
                i, st = carry
                return (i < k) & _work_left(st)

            def body(carry):
                i, st = carry
                kc = jnp.minimum(cfg.hops_per_launch, k - i)
                return i + kc, launch(graph, rows, st, base_key, kc)

            _, state = jax.lax.while_loop(
                cond, body, (jnp.zeros((), jnp.int32), state))
            return state

        return run_supersteps

    @jax.jit
    def run_supersteps(graph: CSRGraph, state: StreamState, seed,
                       k) -> StreamState:
        base_key = task_rng.stream_key(seed)
        step = partial(_superstep, graph, _kernel_rows(graph, spec, cfg),
                       spec, cfg, base_key, depth)

        def cond(carry):
            i, st = carry
            return (i < k) & _work_left(st)

        def body(carry):
            i, st = carry
            return i + 1, step(st)

        _, state = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), state))
        return state

    return run_supersteps


def build_engine(spec: SamplerSpec, cfg: EngineConfig, cache=None):
    """Build a jitted ``run(graph, start_vertices, seed) -> WalkResult``
    (the closed system: drain a fixed query batch to completion).

    Engine-layer builder used by `repro.walker.compile`; prefer the
    `Walker` front-end unless you are extending the engine itself.

    ``step_impl="fused"`` drains the batch as a ``while_loop`` over
    device-resident fused-kernel launches (``hops_per_launch`` supersteps
    each) instead of per-hop superstep bounces — bit-identical paths,
    O(state) host traffic per launch.  ``cache`` is the graph-specific
    hot-vertex cache from :func:`maybe_build_cache`, closure-captured by
    the fused launch (ignored by the per-hop impls).
    """
    assert lower_program(spec).fused, spec.kind
    fused_launch = None
    if cfg.step_impl == "fused":
        from repro.kernels.fused_superstep import build_fused_launch
        fused_launch = build_fused_launch(spec, cfg, _stage_depth(cfg),
                                          cache=cache)

    @partial(jax.jit, static_argnames=("num_queries",))
    def run(graph: CSRGraph, start_vertices: jnp.ndarray, seed,
            num_queries: int) -> WalkResult:
        base_key = task_rng.stream_key(seed)
        depth = _stage_depth(cfg)
        queue = make_queue(start_vertices, staged=min(depth, num_queries))
        paths, lengths = _fresh_buffers(cfg, num_queries)
        state = StreamState(
            slots=empty_slots(cfg.num_slots),
            queue=queue,
            paths=paths,
            lengths=lengths,
            done=jnp.zeros((num_queries,), bool),
            stats=zero_stats(),
            head_hist=jnp.zeros((cfg.injection_delay + 1,), jnp.int32),
        )
        # Initial injection so lanes processed in superstep 1 are live.
        queue, head_hist = _advance_controller(state.queue, state.head_hist,
                                               cfg, depth)
        slots, queue, paths, lengths = _refill(
            state.slots, queue, state.paths, state.lengths, cfg,
            jnp.zeros((cfg.num_slots,), bool))
        state = state._replace(slots=slots, queue=queue, paths=paths,
                               lengths=lengths, head_hist=head_hist)
        rows = _kernel_rows(graph, spec, cfg)

        def cond(st):
            return _work_left(st) & (st.stats.supersteps < cfg.max_supersteps)

        if cfg.step_impl == "fused":
            def body(st):
                kc = jnp.minimum(cfg.hops_per_launch,
                                 cfg.max_supersteps - st.stats.supersteps)
                return fused_launch(graph, rows, st, base_key, kc)

            state = jax.lax.while_loop(cond, body, state)
        else:
            step = partial(_superstep, graph, rows, spec, cfg, base_key,
                           depth)
            state = jax.lax.while_loop(cond, step, state)
        return WalkResult(paths=state.paths, lengths=state.lengths,
                          stats=state.stats)

    return run


def make_engine(spec: SamplerSpec, cfg: EngineConfig):
    """Deprecated alias for :func:`build_engine` — prefer
    ``repro.walker.compile(program).run(...)``."""
    warnings.warn(
        "make_engine is deprecated; use repro.walker.compile(program)"
        ".run(graph, starts) (or build_engine when extending the engine)",
        DeprecationWarning, stacklevel=2)
    return build_engine(spec, cfg)


def _run_walks(graph: CSRGraph, start_vertices, spec: SamplerSpec,
               cfg: Optional[EngineConfig] = None, seed: int = 0) -> WalkResult:
    """One-shot closed-system run (engine-internal reference path)."""
    cfg = cfg or EngineConfig()
    sv = jnp.asarray(start_vertices, jnp.int32)
    run = build_engine(spec, cfg, cache=maybe_build_cache(spec, cfg, graph))
    return run(graph, sv, seed, num_queries=int(sv.shape[0]))


def run_walks(graph: CSRGraph, start_vertices, spec: SamplerSpec,
              cfg: Optional[EngineConfig] = None, seed: int = 0) -> WalkResult:
    """Deprecated convenience one-shot API — prefer
    ``repro.walker.compile(program).run(graph, starts)``."""
    warnings.warn(
        "run_walks is deprecated; use repro.walker.compile(program)"
        ".run(graph, starts)",
        DeprecationWarning, stacklevel=2)
    return _run_walks(graph, start_vertices, spec, cfg, seed)
