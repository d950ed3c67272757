"""compile(program, backend=...) — one entry point for every regime.

The Markov property makes every hop a stateless task (paper §V-A), so a
single superstep definition serves the closed batch system, the open
streaming system, the multi-tenant service, and the ``shard_map``-
partitioned multi-device system.  :func:`compile` binds a
:class:`~repro.walker.WalkProgram` to a backend and returns a
:class:`Walker` exposing all three execution styles on either backend:

    walker = compile(WalkProgram.node2vec(p=2.0, q=0.5), backend="sharded")
    result = walker.run(graph, starts, seed=0)        # closed batch
    stream = walker.stream(graph, capacity=4096)      # open system
    service = walker.serve(graph)                     # multi-tenant

Streams are *continuous*: query-id slots form a ring (a host-side free
ring hands slots to arrivals; ``release`` reclaims them after harvest with
``epoch + 1``), so an unbounded arrival stream runs in a bounded device
buffer with no drain barrier.  Paths are bit-identical across backends
for the same (seed, epoch, query_id, hop) — epoch ``e`` of any stream
equals ``Walker.run`` under ``rng.stream_key(seed, e)`` — pinned by
``tests/test_walker_api.py`` and ``tests/test_streaming.py``.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import Counter, deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import corpus_ring
from repro.core import rng as task_rng
from repro.core.distributed import (DistLogs, assemble_paths,
                                    init_dist_stream_state,
                                    inject_stream_queries,
                                    make_distributed_engine,
                                    make_sharded_stream_engine,
                                    mesh_devices, shard_starts)
from repro.core.tasks import WalkResult, WalkStats
from repro.core.walk_engine import (StreamState, build_engine,
                                    init_stream_state, inject_queries,
                                    make_superstep_runner, maybe_build_cache)
from repro.graph.partition import PartitionedGraph, partition_graph
from repro.walker.execution import ExecutionConfig
from repro.walker.program import WalkProgram

BACKENDS = ("single", "sharded")


def _pad_block(n: int, floor: int = 16) -> int:
    """Next power of two >= n (>= floor): bounds distinct injection shapes
    to O(log capacity) jit specializations."""
    b = floor
    while b < n:
        b <<= 1
    return b


def _harvest_blocks(capacity: int) -> list:
    """Every block size a harvest of 1..capacity rows pads to."""
    blocks, b = [], _pad_block(1)
    while b < capacity:
        blocks.append(b)
        b <<= 1
    return blocks + [capacity]


@jax.jit
def _gather_rows(paths, lengths, idx):
    """Rows ``idx`` of a single-device stream's path and length buffers."""
    return paths[idx], lengths[idx]


@jax.jit
def _gather_rows_sharded(paths, lengths, idx):
    """Rows ``idx`` of a sharded stream's buffers, max-folded over the
    device axis (each hop is recorded by exactly the device that ran it)."""
    return (jnp.max(paths[:, idx, :], axis=0),
            jnp.max(lengths[:, idx], axis=0))


def compile(program: WalkProgram, backend: str = "single",
            execution: Optional[ExecutionConfig] = None,
            mesh: Optional[jax.sharding.Mesh] = None) -> "Walker":
    """Bind ``program`` to an execution backend.

    backend:
      ``single``  — one device: slot-pool engine with zero-bubble refill.
      ``sharded`` — ``shard_map`` over a 1-D device mesh: vertex-
                    partitioned graph, per-phase butterfly routing,
                    flow-controlled lossless refill.
    """
    if not isinstance(program, WalkProgram):
        raise TypeError(
            f"compile expects a WalkProgram, got {type(program).__name__}; "
            "build one with WalkProgram.urw()/ppr()/deepwalk()/node2vec()/"
            "metapath() or WalkProgram(spec=...)")
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got "
                         f"{backend!r}")
    return Walker(program, backend, execution or ExecutionConfig(), mesh)


class Walker:
    """A compiled walk program: one algorithm, three execution styles."""

    def __init__(self, program: WalkProgram, backend: str,
                 execution: ExecutionConfig,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.program = program
        self.backend = backend
        self.execution = execution
        self._mesh = mesh
        self._engines = {}          # closed-system runners keyed by config
        self._resolved = {}         # (sig, workload) -> (program, execution)
        self._dist_cache = {}       # sharded runners keyed by graph shape
        self._emb_cache = {}        # train_embeddings jitted pieces
        #: Per-device WalkStats of the last sharded ``run`` (one entry per
        #: device in every field); ``run`` itself reports their totals.
        self.last_device_stats: Optional[WalkStats] = None

    # ----------------------------------------------------------- internals

    def _bind(self, graph, num_queries: Optional[int] = None):
        """Concrete ``(program, execution)`` for this graph + workload.

        Resolves any ``"auto"`` knob sentinels through the tuning cache /
        analytical model (`repro.tune.resolve`) — memoized per (graph
        signature, workload bucket), so repeat runs on a same-shaped
        graph reuse both the resolution and the compiled engine.  With
        no sentinels present this is the identity.
        """
        from repro import tune
        if not tune.needs_resolution(self.program, self.execution):
            return self.program, self.execution
        sig = tune.graph_signature(graph)
        key = (sig.token(), tune.workload_bucket(num_queries))
        if key not in self._resolved:
            self._resolved[key] = tune.resolve(
                self.program, self.execution, graph, backend=self.backend,
                num_queries=num_queries)
        return self._resolved[key]

    def _engine_cfg(self):
        return self.execution.engine_config(self.program)

    def _single_engine(self, program=None, execution=None, graph=None):
        program = program or self.program
        execution = execution or self.execution
        cfg = execution.engine_config(program)
        # The hot-vertex cache is a function of the graph, so graph
        # identity must key the memo whenever a cache would be built; the
        # memo pins the graph object, keeping its id() stable for the
        # cache entry's lifetime.
        wants_cache = (graph is not None and cfg.step_impl == "fused"
                       and cfg.cache_budget > 0)
        key = (program.spec, cfg, id(graph) if wants_cache else None)
        if key not in self._engines:
            cache = (maybe_build_cache(program.spec, cfg, graph)
                     if wants_cache else None)
            self._engines[key] = (build_engine(program.spec, cfg,
                                               cache=cache), graph)
        return self._engines[key][0]

    def _partition(self, graph) -> PartitionedGraph:
        if isinstance(graph, PartitionedGraph):
            return graph
        n = self.execution.num_devices or len(jax.devices())
        return partition_graph(graph, n)

    def _dist_engine(self, pg: PartitionedGraph, program=None,
                     execution=None):
        program = program or self.program
        execution = execution or self.execution
        # max_degree is baked into the compiled engine (bisect iteration
        # count, reservoir chunk count), so it must key the cache — as
        # must the resolved (spec, execution) when knobs were auto-tuned.
        key = (pg.num_devices, pg.vertices_per_device, pg.col.shape,
               pg.max_degree,
               pg.weights is not None, pg.alias_prob is not None,
               program.spec, execution)
        if key not in self._dist_cache:
            cfg = execution.dist_config(program, pg.num_devices)
            mesh = self._mesh
            if mesh is None:
                mesh = jax.sharding.Mesh(mesh_devices(pg.num_devices),
                                         (cfg.axis_name,))
            self._dist_cache[key] = (
                make_distributed_engine(pg, program.spec, cfg, mesh), cfg)
        return self._dist_cache[key]

    # ---------------------------------------------------------- closed run

    def run(self, graph, starts, seed: int = 0) -> WalkResult:
        """Closed system: drain the batch of ``starts`` to completion.

        ``seed`` may be an int or a PRNG key (e.g. ``rng.stream_key(s, e)``
        to reproduce epoch ``e`` of a stream as a closed batch).  On the
        sharded backend ``graph`` may be a ``CSRGraph`` (partitioned on the
        fly over the configured device count) or a pre-built
        ``PartitionedGraph``; the emission logs are assembled into the same
        ``WalkResult`` layout as the single-device engine, with per-device
        stats summed.

        Each call records ``walker.bind`` (program checks, knob
        resolution), ``walker.engine`` (start upload, engine dispatch) and,
        on the sharded backend, ``walker.assemble`` (path assembly from the
        emission logs) as ``jax.profiler`` spans; they cost nothing
        measurable when no trace is running.
        """
        if self.backend == "single":
            with TraceAnnotation("walker.bind"):
                self.program.requires(graph)
                program, execution = self._bind(graph,
                                                int(np.shape(starts)[0]))
            with TraceAnnotation("walker.engine"):
                sv = jnp.asarray(starts, jnp.int32)
                return self._single_engine(program, execution, graph)(
                    graph, sv, seed, num_queries=int(sv.shape[0]))

        with TraceAnnotation("walker.bind"):
            if not isinstance(graph, PartitionedGraph):
                self.program.requires(graph)
            elif (self.program.spec.kind == "alias"
                  and graph.alias_prob is None):
                raise ValueError(
                    "alias (DeepWalk) programs need alias tables on the "
                    "partitioned graph — build the CSRGraph with alias "
                    "tables before partition_graph")
            pg = self._partition(graph)
            program, execution = self._bind(pg, np.asarray(starts).size)
        with TraceAnnotation("walker.engine"):
            run, cfg = self._dist_engine(pg, program, execution)
            starts_np = np.asarray(starts, dtype=np.int32)
            starts_sh, qcount = shard_starts(starts_np, pg.num_devices)
            base_key = task_rng.stream_key(seed)
            log_q, log_h, log_v, cursor, stats = run(
                pg, jnp.asarray(starts_sh), jnp.asarray(qcount), base_key)
            self.last_device_stats = WalkStats(*stats)
            # Devices run the lockstep superstep loop the same number of
            # times: supersteps/launches are global clocks (max), the rest
            # is additive.
            total = WalkStats(*(
                jnp.max(v) if name in ("supersteps", "launches")
                else jnp.sum(v)
                for name, v in zip(WalkStats._fields, stats)))
            if int(total.supersteps) >= cfg.max_supersteps:
                warnings.warn(
                    f"sharded run hit max_supersteps={cfg.max_supersteps} "
                    "before draining — walks may be truncated; raise "
                    "ExecutionConfig.max_supersteps", RuntimeWarning,
                    stacklevel=2)
            if int(total.drops) > 0:
                # Routing drops are structurally impossible (flow-controlled
                # refill), so any drop is an emission-log overflow: recorded
                # paths have holes.
                warnings.warn(
                    f"{int(total.drops)} path records dropped (emission log "
                    "overflow) — assembled paths are incomplete; raise "
                    "ExecutionConfig.log_capacity", RuntimeWarning,
                    stacklevel=2)
        if cfg.record_paths:
            with TraceAnnotation("walker.assemble"):
                logs = DistLogs(qid=log_q, hop=log_h, vertex=log_v,
                                cursor=cursor)
                paths, lengths = assemble_paths(logs, starts_np,
                                                self.program.max_hops)
                return WalkResult(paths=jnp.asarray(paths),
                                  lengths=jnp.asarray(lengths), stats=total)
        dummy = jnp.full((1, 1), -1, jnp.int32)
        return WalkResult(paths=dummy, lengths=jnp.zeros((1,), jnp.int32),
                          stats=total)

    # --------------------------------------------------------- open stream

    def stream(self, graph, capacity: int = 4096, seed: int = 0):
        """Open system: a persistent stream accepting injections between
        superstep chunks, with ring-buffer slot reclamation (``release``)
        for continuous operation.

        On ``backend="single"`` returns a :class:`WalkStream`; on
        ``backend="sharded"`` a :class:`ShardedWalkStream` over the
        capability-dispatched distributed superstep.  Both expose the same
        inject / advance / harvest_ids / release surface, so
        `serve.WalkService` runs unchanged over either.
        """
        if self.backend == "single":
            self.program.requires(graph)
            program, execution = self._bind(graph, capacity)
            return WalkStream(program, execution, graph, capacity, seed)
        if not isinstance(graph, PartitionedGraph):
            self.program.requires(graph)
        pg = self._partition(graph)
        program, execution = self._bind(pg, capacity)
        cfg = execution.dist_config(program, pg.num_devices)
        mesh = self._mesh
        if mesh is None:
            mesh = jax.sharding.Mesh(mesh_devices(pg.num_devices),
                                     (cfg.axis_name,))
        return ShardedWalkStream(program, cfg, pg, mesh, capacity, seed)

    # ------------------------------------------------------------- service

    def serve(self, graph, capacity: int = 4096, chunk: int = 16,
              seed: int = 0, adapt: bool = False, controller=None):
        """Multi-tenant request service over the streaming engine (either
        backend — the service only speaks the stream interface).

        ``adapt=True`` attaches the Theorem VI.1 chunk controller
        (`repro.serve.scheduler.HopsController`, overridable via
        ``controller``): the service adapts its supersteps-per-launch
        online from the engine's occupancy stats, trace exposed on
        ``ServiceAnalysis.adaptation``.
        """
        from repro.serve.service import WalkService
        return WalkService(stream=self.stream(graph, capacity=capacity,
                                              seed=seed),
                           chunk=chunk, adapt=adapt, controller=controller)

    # ------------------------------------------------- walks → embeddings

    def train_embeddings(self, graph, *, seed: int = 0,
                         rounds: int = 4, walks_per_round: int = 64,
                         steps_per_round: int = 32, batch_size: int = 256,
                         dim: int = 32, window: int = 5,
                         num_negatives: int = 5,
                         ring_capacity: Optional[int] = None,
                         opt_cfg=None, overlap: bool = True,
                         use_kernel: bool = True,
                         ckpt_dir: Optional[str] = None,
                         ckpt_every: int = 0, log_every: int = 0,
                         batch_hook=None) -> dict:
        """Device-resident walks→embeddings pipeline (DeepWalk/node2vec).

        Runs ``rounds`` walk-production rounds of ``walks_per_round``
        walks each; completed paths land directly in an HBM corpus ring
        (`repro.core.corpus_ring`) and ``steps_per_round`` SGNS grad
        steps per round consume (center, context, negatives) windows
        sampled straight from the ring — the paths never visit the host.
        With ``overlap=True`` round ``r+1``'s walk launch is dispatched
        before round ``r``'s grad steps, so walking and training share
        the device queue; ``overlap=False`` is the serial baseline
        (host round-trip + blocking), bit-identical in result.

        Round ``r``'s corpus is the closed batch of starts
        ``(r·walks_per_round + i) % |V|`` under ``rng.stream_key(seed,
        r)`` — a pure function of ``(seed, r)`` on either backend, so a
        run checkpointed via ``ckpt_dir`` resumes bit-identically
        (pending rounds are re-produced, ingested rounds are not).

        Returns ``{"params", "opt_state", "ring", "step", "history",
        "config"}`` — ``params`` are the trained (device-resident)
        embedding tables.
        """
        from repro.models import embeddings as emb
        from repro.optim import adamw
        from repro.runtime import train_loop

        if walks_per_round <= 0 or rounds <= 0:
            raise ValueError(
                f"rounds ({rounds}) and walks_per_round ({walks_per_round}) "
                "must be positive")
        path_width = self.program.max_hops + 1

        # ------------------------------------------------------- producer
        if self.backend == "single":
            self.program.requires(graph)
            nv = int(graph.num_vertices)
            if "engine" not in self._emb_cache:
                program, execution = self._bind(graph, walks_per_round)
                cfg = dataclasses.replace(
                    execution.engine_config(program), record_paths=True)
                self._emb_cache["engine"] = build_engine(
                    program.spec, cfg,
                    cache=maybe_build_cache(program.spec, cfg, graph))
            engine = self._emb_cache["engine"]
            stream = None

            def produce(r: int):
                sv = jnp.asarray(
                    (r * walks_per_round + np.arange(walks_per_round)) % nv,
                    jnp.int32)
                res = engine(graph, sv, task_rng.stream_key(seed, r),
                             num_queries=walks_per_round)
                return res.paths, res.lengths
        else:
            stream = self.stream(graph, capacity=walks_per_round, seed=seed)
            nv = int(stream.graph.num_vertices)

            def produce(r: int):
                starts = (r * walks_per_round
                          + np.arange(walks_per_round)) % nv
                qids, epochs = stream.inject(starts)
                if int(epochs[0]) != r:
                    raise RuntimeError(
                        f"producer stream is at epoch {int(epochs[0])} but "
                        f"round {r} was requested (rounds must be produced "
                        "in order; use seek_epochs after a resume)")
                stream.drain()
                paths, lengths = stream.harvest_device(qids)
                stream.release(qids)
                return paths, lengths

        # ------------------------------------------------------- consumer
        sg_cfg = emb.SkipGramConfig(num_vertices=nv, dim=dim,
                                    num_negatives=num_negatives,
                                    window=window)
        opt_cfg = opt_cfg or adamw.AdamWConfig(
            lr=1e-2, warmup_steps=max(1, rounds * steps_per_round // 10),
            total_steps=rounds * steps_per_round)
        params0 = emb.init_params(task_rng.stream_key(seed), sg_cfg)
        state0 = (params0, adamw.init_state(params0))
        # Reuse jitted pieces across calls (repeat training runs on one
        # Walker hit the jit cache instead of recompiling).
        skey = ("sampler", nv, batch_size, window, num_negatives)
        if skey not in self._emb_cache:
            self._emb_cache[skey] = corpus_ring.make_batch_sampler(
                nv, batch_size, window, num_negatives)
        sampler = self._emb_cache[skey]
        base_key = task_rng.stream_key(seed)

        def sample(ring, step):
            return sampler(ring, base_key, step)

        gkey = ("sgns", dataclasses.astuple(sg_cfg),
                dataclasses.astuple(opt_cfg), use_kernel)
        if gkey not in self._emb_cache:
            self._emb_cache[gkey] = emb.make_sgns_step(
                sg_cfg, opt_cfg, use_kernel=use_kernel)
        sgns = self._emb_cache[gkey]

        def step_fn(state, batch):
            params, opt = state
            if not overlap:
                # Serial baseline: the naive wiring stages every batch
                # through the host (the per-step transfer the corpus
                # ring exists to delete) and blocks on every grad step.
                corpus_ring.record_host_copy("train_embeddings.serial_batch")
                batch = tuple(jnp.asarray(np.asarray(x)) for x in batch)
            params, opt, aux = sgns(params, opt, batch)
            if not overlap:
                jax.block_until_ready(params["in_embed"])
            return (params, opt), aux

        # ----------------------------------------------------------- ring
        cap = ring_capacity or max(2 * walks_per_round, walks_per_round)
        if self.backend == "sharded":
            ndev = stream.graph.num_devices
            cap = -(-cap // ndev) * ndev  # row-shardable across the mesh
        ring0 = corpus_ring.init_ring(cap, path_width)

        state, ring, start_step = train_loop.resume_pipeline(
            ckpt_dir, state0, ring0)
        rounds_done = int(ring.tail) // walks_per_round
        if stream is not None:
            stream.seek_epochs(rounds_done)
            mesh, ax = stream._mesh, stream.cfg.axis_name
            P = jax.sharding.PartitionSpec
            ring = jax.device_put(ring, corpus_ring.CorpusRing(
                paths=jax.sharding.NamedSharding(mesh, P(ax, None)),
                lengths=jax.sharding.NamedSharding(mesh, P(ax)),
                tail=jax.sharding.NamedSharding(mesh, P())))
            if nv % ndev == 0:
                # Vocab-sharded tables: each device owns |V|/N rows of
                # both tables (and their optimizer moments).
                vocab = jax.sharding.NamedSharding(mesh, P(ax, None))
                state = jax.tree.map(
                    lambda x: jax.device_put(x, vocab)
                    if getattr(x, "ndim", 0) == 2 and x.shape[0] == nv
                    else x, state)

        if overlap:
            def append(ring, walks):
                return corpus_ring.append(ring, *walks)
        else:
            def append(ring, walks):
                # The naive hand-off this module exists to delete: pull
                # every path to the host, re-upload, and fence.
                corpus_ring.record_host_copy("train_embeddings.serial")
                paths = np.asarray(walks[0])
                lengths = np.asarray(walks[1])
                ring = corpus_ring.append(ring, jnp.asarray(paths),
                                          jnp.asarray(lengths))
                jax.block_until_ready(ring.paths)
                return ring

        pcfg = train_loop.PipelineConfig(
            rounds=rounds, steps_per_round=steps_per_round, overlap=overlap,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, log_every=log_every)
        state, ring, step, history, _ = train_loop.run_pipelined(
            produce, append, sample, step_fn, state, ring, pcfg,
            start_step=start_step, rounds_done=rounds_done,
            batch_hook=batch_hook)
        params, opt_state = state
        return {"params": params, "opt_state": opt_state, "ring": ring,
                "step": step, "history": history, "config": sg_cfg}


class _StreamBase:
    """Host-side ring economy shared by both stream backends.

    The host owns the free ring: slot ids 0..capacity-1 start free, an
    injection pops slots FIFO and assigns each arrival ``(epoch, qid)``,
    and :meth:`release` returns harvested slots with ``epoch + 1`` so the
    next occupant samples an independent walk (`rng.task_fold` salts the
    derivation with the epoch).  The stream therefore never drains as a
    whole — slots individually complete, are harvested, and go around
    again.

    Every blocking device→host read the stream makes is counted by site
    (:attr:`syncs`), and :meth:`advance` records ``stream.sync`` /
    ``stream.dispatch`` profiler spans, so a trace can split the blocking
    reads from the engine dispatch.
    """

    capacity: int
    #: The backend's jitted row gather, ``(paths, lengths, idx)`` → rows.
    _rows = None
    _rows_warm = False

    @property
    def state(self):
        """The device state.  Replacing it (a dispatch, an injection, a
        reset) voids the last :meth:`done_mask` read."""
        return self._state

    @state.setter
    def state(self, state) -> None:
        self._state = state
        self._done_read = None

    def _init_ring(self) -> None:
        self._free = deque(range(self.capacity))
        self._epochs = np.zeros((self.capacity,), np.int32)
        self._live = np.zeros((self.capacity,), bool)
        self._injected = 0

    # -- subclass hooks ----------------------------------------------------

    def _device_inject(self, qids: np.ndarray, starts: np.ndarray,
                       epochs: np.ndarray) -> None:
        raise NotImplementedError

    def _dispatch(self, k: int) -> None:
        """Enqueue at most ``k`` supersteps on the persistent device state."""
        raise NotImplementedError

    def _supersteps(self) -> int:
        """The engine's superstep clock (one blocking read)."""
        raise NotImplementedError

    def _done_flags(self) -> np.ndarray:
        """Per-slot completion flags (one blocking read)."""
        raise NotImplementedError

    def _gather(self, qids):
        """``(paths, lengths)`` rows of ``qids`` on device, padded to a
        power-of-two block by one call of the backend's jitted gather, and
        the number of real rows.  The first call compiles every block size
        a harvest can present, so none compiles later."""
        idx = np.asarray(qids, np.int32).reshape(-1)
        n = idx.size
        if not self._rows_warm:
            for b in _harvest_blocks(self.capacity):
                self._rows(self.state.paths, self.state.lengths,
                           np.zeros((b,), np.int32))
            self._rows_warm = True
        # Pad rows gather slot 0 and are dropped.
        block = np.zeros((min(_pad_block(n), self.capacity),), np.int32)
        block[:n] = idx
        return self._rows(self.state.paths, self.state.lengths, block), n

    def harvest_device(self, qids):
        """Fetch ``(paths, lengths)`` for the given live query-id slots as
        *device-resident* arrays (no host copy) — the corpus-ring feed."""
        (paths, lengths), n = self._gather(qids)
        if n == paths.shape[0]:
            return paths, lengths
        return paths[:n], lengths[:n]

    # -- device reads --------------------------------------------------------

    @property
    def syncs(self) -> Counter:
        """Blocking device→host reads since construction, by site (a copy):
        ``advance`` (the superstep clock before and after a chunk),
        ``done_mask``, ``harvest`` (one read of the paths and lengths of
        :meth:`harvest_ids`), ``release`` (its ``done`` check, where no
        :meth:`done_mask` read of the current state can serve) and
        ``walk_stats`` (one per counter)."""
        return Counter(self._syncs)

    @property
    def num_syncs(self) -> int:
        """All blocking device→host reads since construction."""
        return self._num_syncs

    def _init_syncs(self) -> None:
        self._syncs = Counter()
        self._num_syncs = 0

    def _count_sync(self, site: str, n: int = 1) -> None:
        self._syncs[site] += n
        self._num_syncs += n

    def advance(self, k: int = 16) -> int:
        """Run at most ``k`` supersteps; returns how many executed."""
        with TraceAnnotation("stream.sync"):
            self._count_sync("advance")
            before = self._supersteps()
        with TraceAnnotation("stream.dispatch"):
            self._dispatch(k)
        with TraceAnnotation("stream.sync"):
            self._count_sync("advance")
            return self._supersteps() - before

    def done_mask(self) -> np.ndarray:
        """(capacity,) bool — True where that slot's query terminated
        (free slots included)."""
        self._count_sync("done_mask")
        self._done_read = self._done_flags()
        return self._done_read

    # -- ring economy ------------------------------------------------------

    @property
    def num_free(self) -> int:
        """Slots available for injection right now."""
        return len(self._free)

    @property
    def num_live(self) -> int:
        """Slots occupied by injected-but-not-released queries."""
        return self.capacity - len(self._free)

    @property
    def num_injected(self) -> int:
        """Total arrivals ever injected (monotone; exceeds capacity once
        slots recycle)."""
        return self._injected

    def epoch_of(self, qids) -> np.ndarray:
        """Current occupant epoch of each slot id."""
        return self._epochs[np.asarray(qids, np.int64)]

    def inject(self, starts, n_valid: Optional[int] = None):
        """Admit arrivals into free ring slots.

        Returns ``(qids, epochs)`` — the slot id and epoch assigned to each
        arrival, the identity under which its walk is sampled and
        harvested.  Raises if fewer than ``n_valid`` slots are free
        (``release`` harvested queries to make room).
        """
        sv = np.asarray(starts, np.int32).reshape(-1)
        n = int(sv.size if n_valid is None else n_valid)
        if not 0 < n <= sv.size:
            raise ValueError(
                f"n_valid={n} must be within [1, {sv.size}] (the injected "
                "block)")
        if n > len(self._free):
            raise ValueError(
                f"injecting {n} queries overflows the slot ring "
                f"({self.num_live}/{self.capacity} live, {len(self._free)} "
                "free); release harvested queries or raise capacity "
                "(WalkService does this bookkeeping for you)")
        qids = np.asarray([self._free.popleft() for _ in range(n)], np.int32)
        epochs = self._epochs[qids]
        self._live[qids] = True
        self._injected += n
        self._device_inject(qids, sv[:n], epochs)
        return qids, epochs

    def release(self, qids) -> None:
        """Return harvested slots to the free ring with ``epoch + 1``.

        Every slot must read ``done``: in the last :meth:`done_mask` read
        where nothing has replaced the device state since, else in a new
        read."""
        qids = np.asarray(qids, np.int64).reshape(-1)
        if np.unique(qids).size != qids.size:
            # A duplicate would enter the free ring twice and hand the same
            # (epoch, qid) identity to two future arrivals.
            raise ValueError("release with duplicate slot ids")
        if not self._live[qids].all():
            raise ValueError("release of a slot that is not live")
        done = self._done_read
        if done is None:    # the state changed since the last done_mask
            self._count_sync("release")
            done = self._done_read = self._done_flags()
        if not done[qids].all():
            raise ValueError(
                "release of an unfinished query: harvest only completed "
                "slots (done_mask) before recycling them")
        self._live[qids] = False
        self._epochs[qids] += 1
        self._free.extend(int(q) for q in qids)

    def seek_epochs(self, epoch: int) -> None:
        """Fast-forward every free slot's epoch (resume support).

        A resumed pipelined training run re-creates the stream with all
        epochs at 0 but needs production to continue at walk round
        ``rounds_done``; seeking makes the next occupant of every slot
        sample round ``epoch`` — bit-identical to a fresh run that walked
        through the earlier rounds, because epoch ``e`` of a slot is a
        pure function of ``(seed, e, qid)``.
        """
        if self._live.any():
            raise RuntimeError("seek_epochs with live queries outstanding")
        if epoch < int(self._epochs.max(initial=0)):
            raise ValueError(
                f"seek_epochs({epoch}) would rewind a slot already past it "
                f"(max epoch {int(self._epochs.max(initial=0))}) and replay "
                "a used (epoch, qid) identity")
        self._epochs[:] = epoch

    def harvest_ids(self, qids):
        """Fetch ``(paths, lengths)`` for the given live query-id slots as
        numpy: one jitted gather and one recorded host round-trip of both."""
        (paths, lengths), n = self._gather(qids)
        corpus_ring.record_host_copy("harvest_ids")
        self._count_sync("harvest")
        paths, lengths = jax.device_get((paths, lengths))
        return paths[:n], lengths[:n]

    def done_live_mask(self) -> np.ndarray:
        """(capacity,) bool — live slots whose query has terminated (the
        harvestable set; released slots read False)."""
        return self.done_mask() & self._live

    def harvest(self, lo: int = 0, hi: Optional[int] = None):
        """Recorded (paths, lengths) for the contiguous slot range
        [lo, hi) as numpy.  Before any slot recycles, slots are handed out
        FIFO, so this matches injection order; under reuse prefer
        :meth:`harvest_ids` with the ids :meth:`inject` returned."""
        hi = min(self._injected, self.capacity) if hi is None else hi
        return self.harvest_ids(np.arange(lo, hi))

    def drain(self, chunk: int = 64, max_chunks: int = 100_000) -> None:
        """Advance until every live (injected, unreleased) query is done."""
        for _ in range(max_chunks):
            live = self._live
            if not live.any() or bool(self.done_mask()[live].all()):
                return
            self.advance(chunk)
        raise RuntimeError("stream did not drain (engine stalled?)")


class WalkStream(_StreamBase):
    """Persistent single-device open-system stream: inject → advance →
    harvest → release.

    Thin stateful handle over the jitted superstep runner; all device
    state lives in a :class:`~repro.core.StreamState` whose shapes are
    static, so any injection/advance cadence reuses one compilation.
    """

    _rows = staticmethod(_gather_rows)

    def __init__(self, program: WalkProgram, execution: ExecutionConfig,
                 graph, capacity: int, seed: int):
        if capacity <= 0:
            raise ValueError(f"stream capacity must be positive, got "
                             f"{capacity}")
        self.program = program
        self.graph = graph
        self.seed = seed
        self.capacity = int(capacity)
        # Harvesting slices recorded paths; recording is mandatory here
        # (same guard as WalkService).
        self._cfg = dataclasses.replace(
            execution.engine_config(program), record_paths=True)
        self._runner = make_superstep_runner(
            program.spec, self._cfg,
            cache=maybe_build_cache(program.spec, self._cfg, graph))
        self.state: StreamState = init_stream_state(self._cfg, self.capacity)
        self._init_syncs()
        self._init_ring()

    @property
    def num_slots(self) -> int:
        """W — walker lanes of the underlying engine."""
        return self._cfg.num_slots

    @property
    def max_hops(self) -> int:
        """The program's hop budget (path buffers are ``max_hops + 1``)."""
        return self.program.max_hops

    @property
    def cfg(self):
        """The lowered engine-layer config (:class:`EngineConfig`)."""
        return self._cfg

    def _device_inject(self, qids, starts, epochs) -> None:
        n = qids.shape[0]
        b = min(_pad_block(n), self.capacity)
        qb = np.full((b,), self.capacity, np.int32)  # capacity = inert pad
        sb = np.zeros((b,), np.int32)
        eb = np.zeros((b,), np.int32)
        qb[:n], sb[:n], eb[:n] = qids, starts, epochs
        self.state = inject_queries(self.state, jnp.asarray(qb),
                                    jnp.asarray(sb), jnp.asarray(eb), n)

    def _dispatch(self, k: int) -> None:
        self.state = self._runner(self.graph, self.state, self.seed, k)

    def _supersteps(self) -> int:
        return int(self.state.stats.supersteps)

    def _done_flags(self) -> np.ndarray:
        return np.asarray(self.state.done)

    def walk_stats(self) -> WalkStats:
        """Engine counters since construction/reset (host ints)."""
        self._count_sync("walk_stats", len(WalkStats._fields))
        return WalkStats(*(int(getattr(self.state.stats, f))
                           for f in WalkStats._fields))

    def reset(self, seed: Optional[int] = None) -> None:
        """Fresh state and ring (keeps the compiled runner warm); pass a
        new ``seed`` to decorrelate from previous runs."""
        if self._live.any():
            raise RuntimeError("reset with live queries outstanding")
        if seed is not None:
            self.seed = seed
        self.state = init_stream_state(self._cfg, self.capacity)
        self._init_ring()


class ShardedWalkStream(_StreamBase):
    """Persistent sharded open-system stream (``backend="sharded"``).

    Same interface and same ring economy as :class:`WalkStream`, running
    over the capability-dispatched distributed superstep: arrivals are
    staged round-robin onto per-device arrival rings and the butterfly
    router carries each new task to owner(start_vertex); the psum
    flow-control admits injections only while global live tasks stay
    ≤ N·W_loc, so the closed engine's losslessness (drops == 0) carries
    over to the open system.  Harvest max-folds the per-device path
    windows (each hop is recorded by exactly the device that executed it).

    Bit-identity: the ``(epoch, qid)`` occupant samples exactly the walk
    ``Walker.run`` samples for query ``qid`` under
    ``rng.stream_key(seed, epoch)`` — identical across backends.
    """

    _rows = staticmethod(_gather_rows_sharded)

    def __init__(self, program: WalkProgram, cfg, pg: PartitionedGraph,
                 mesh, capacity: int, seed: int):
        if capacity <= 0:
            raise ValueError(f"stream capacity must be positive, got "
                             f"{capacity}")
        self.program = program
        self.graph = pg
        self.seed = seed
        self.capacity = int(capacity)
        self._cfg = cfg
        self._mesh = mesh
        self._runner = make_sharded_stream_engine(pg, program.spec, cfg,
                                                  mesh, self.capacity)
        self.state = init_dist_stream_state(pg, program.spec, cfg,
                                            self.capacity)
        self._base_key = task_rng.stream_key(seed)
        self._next_dev = 0  # round-robin staging cursor
        self._init_syncs()
        self._init_ring()

    @property
    def num_slots(self) -> int:
        """W — total lanes across the mesh (devices × W_loc)."""
        return self.graph.num_devices * self._cfg.slots_per_device

    @property
    def max_hops(self) -> int:
        """The program's hop budget (path buffers are ``max_hops + 1``)."""
        return self.program.max_hops

    @property
    def cfg(self):
        """The lowered engine-layer config (:class:`DistConfig`)."""
        return self._cfg

    def _device_inject(self, qids, starts, epochs) -> None:
        n = qids.shape[0]
        N = self.graph.num_devices
        per_dev = -(-n // N)
        b = min(_pad_block(per_dev), self.capacity)
        qb = np.zeros((N, b), np.int32)
        sb = np.zeros((N, b), np.int32)
        eb = np.zeros((N, b), np.int32)
        cnt = np.zeros((N,), np.int32)
        for i in range(n):
            r = (self._next_dev + i) % N
            qb[r, cnt[r]] = qids[i]
            sb[r, cnt[r]] = starts[i]
            eb[r, cnt[r]] = epochs[i]
            cnt[r] += 1
        self._next_dev = (self._next_dev + n) % N
        self.state = inject_stream_queries(
            self.state, jnp.asarray(sb), jnp.asarray(qb), jnp.asarray(eb),
            jnp.asarray(cnt))

    def _dispatch(self, k: int) -> None:
        self.state = self._runner(self.graph, self.state, self._base_key, k)

    def _supersteps(self) -> int:
        return int(jnp.max(self.state.stats.supersteps))

    def _done_flags(self) -> np.ndarray:
        # A slot is done once any device terminated its occupant's walk.
        return np.asarray(jnp.any(self.state.done, axis=0))

    def walk_stats(self) -> WalkStats:
        """Engine counters summed across devices (supersteps/launches are
        the global lockstep clock: max)."""
        self._count_sync("walk_stats", len(WalkStats._fields))
        return WalkStats(*(
            int(jnp.max(v)) if name in ("supersteps", "launches")
            else int(jnp.sum(v))
            for name, v in zip(WalkStats._fields, self.state.stats)))

    def reset(self, seed: Optional[int] = None) -> None:
        """Fresh state and ring (keeps the compiled runner warm)."""
        if self._live.any():
            raise RuntimeError("reset with live queries outstanding")
        if seed is not None:
            self.seed = seed
            self._base_key = task_rng.stream_key(seed)
        self.state = init_dist_stream_state(self.graph, self.program.spec,
                                            self._cfg, self.capacity)
        self._next_dev = 0
        self._init_ring()
