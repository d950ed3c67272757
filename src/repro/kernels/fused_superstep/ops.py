"""Jitted public wrapper for the fused device-resident superstep kernel.

``build_fused_launch(spec, cfg, depth)`` returns a jitted
``launch(graph, rows, state, base_key, k) -> StreamState`` that advances the
open-system :class:`~repro.core.walk_engine.StreamState` by at most ``k``
supersteps inside ONE Pallas launch (``k`` is traced — the host picks the
``hops_per_launch`` cadence without recompiling).  The engine-level
runners (`core/walk_engine.py`) drain a closed batch or chunk a stream by
looping launches; everything between launches is exactly the jnp engine's
host protocol (``inject_queries``, harvesting), so the two impls are
interchangeable mid-stream.

Memory placement.  The lane pool (every ``(W,)`` array), the queue
counters, stats and the hot-vertex cache block sit in SMEM, which holds
1 MiB on a TPU v5e core; :func:`smem_bytes` adds up the kernel's SMEM
and a ``cache_budget`` that does not fit beside the rest is refused.
Nothing ``Q``-sized enters the kernel: refill reads the arrival ring
through a per-superstep window DMA, and the kernel streams one record
row block per superstep to HBM, which this wrapper scatters into
``done`` / ``paths`` / ``lengths`` after the launch.  Graph arrays come
in as 128-lane rows (``rows``, `kernels.common.graph_rows`), laid out
once per run by the engine rather than once per launch.

``interpret`` defaults to interpreting the kernel body off-TPU (CPU CI)
and compiling on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.phase_program import fused_kinds, lower
from repro.core.tasks import WalkStats
from repro.kernels.common import LANES, default_interpret, span_rows
from repro.kernels.fused_superstep import fused_superstep as _k

# Sampler kinds the fused kernel covers — read off the phase programs.
# Every program lowers here (loop-free programs as one launch-resident
# pass, the chunked reservoir as the in-kernel chunk loop), so this is
# all of `samplers.KINDS`; there is no jnp fallback.
FUSED_KINDS = fused_kinds()

#: SMEM of one TPU v5e TensorCore (Mosaic refuses a kernel that asks for
#: more: "Allocation ... would exceed memory (size=1048576)").
SMEM_BYTES = 1 << 20


def to_width(graph) -> int:
    """Row width ``T + 1`` of the graph's typed sub-segment offsets."""
    to = graph.type_offsets
    return int(to.shape[1]) if to is not None else 2


def smem_bytes(shapes) -> int:
    """Bytes of a list of 4-byte-word SMEM arrays given by shape."""
    return int(sum(4 * int(np.prod(s)) for s in shapes))


def build_fused_launch(spec, cfg, depth: int, interpret: bool | None = None,
                       cache=None):
    """Build the jitted single-launch runner for ``spec`` × ``cfg``.

    ``cache`` is the graph-specific
    :class:`~repro.graph.HotVertexCache` from
    `core.walk_engine.maybe_build_cache` (or ``None``): its packed
    payload block rides into the kernel as SMEM-resident operands and
    v_curr-keyed gathers on cached vertices skip their HBM DMAs —
    bit-identically, since the block packs verbatim CSR slices.

    Raises ``ValueError`` when ``cfg.cache_budget`` bytes cannot sit in
    SMEM beside the kernel's lane pool and staging buffers.
    """
    assert lower(spec).fused, spec.kind
    kind = spec.kind
    rejection = kind == "rejection_n2v"
    reservoir = kind == "reservoir_n2v"
    second = rejection or reservoir
    interpret = default_interpret(interpret)
    W = cfg.num_slots
    H = cfg.max_hops
    C = cfg.injection_delay
    record_paths = cfg.record_paths
    stop_prob = float(spec.stop_prob)
    static_mode = cfg.mode == "static"
    mp_sched = tuple(int(t) for t in spec.metapath)
    rej_rounds = int(spec.rejection_rounds) if rejection else 0
    CH = int(spec.reservoir_chunk) if reservoir else 1
    # Rows a CH-word chunk window spans (the reservoir's chunk DMAs).
    CR = span_rows(CH) if reservoir else 1
    inv_p = 1.0 / float(spec.p)
    inv_q = 1.0 / float(spec.q)
    KMAX = int(cfg.hops_per_launch)
    WR = -(-W // LANES)                    # 128-lane rows of one lane array
    NF = len(_k.REC_FIELDS)
    RB = -(-NF * WR // 8) * 8              # record rows per superstep
    WIN_ROWS = KMAX * WR + 1               # arrival-ring window rows
    if cache is not None:
        # A kind-required payload the graph could not provide (e.g. no
        # alias tables) disables the cache rather than half-serving it.
        needed = {"alias": ("alias_prob", "alias_idx"),
                  "metapath": ("type_offsets",)}.get(kind, ())
        if any(getattr(cache, p) is None for p in needed):
            cache = None
    use_cache = cache is not None
    num_hot = cache.num_hot if use_cache else 1
    cache_trips = cache.probe_trips if use_cache else 1
    cache_len = cache.num_entries if use_cache else 1

    # Second-order samplers (rejection / reservoir) bisect N(v_prev)
    # breadth-wise: rejection over the W lanes, the reservoir over the CH
    # positions of the staged chunk.
    BW = W if rejection else (CH if reservoir else 1)
    Wc = W if use_cache else 1
    lane_smem = [
        ((W,), jnp.int32),      # stop flags
        ((W,), jnp.float32),    # u0 (column draw)
        ((W,), jnp.float32),    # u1 (alias accept)
        ((W,), jnp.int32),      # addr
        ((W,), jnp.int32),      # deg
        ((W,), jnp.int32),      # edge index
        ((W,), jnp.int32),      # v_next
        ((W,), jnp.int32),      # terminated
    ]
    staging_smem = [
        ((2, 2, LANES), jnp.int32),     # row-access DMA buf
        ((2, 1, LANES), jnp.int32),     # column DMA buf
        ((2, 1, LANES), jnp.float32),   # alias-prob DMA buf
        ((2, 1, LANES), jnp.int32),     # alias-idx DMA buf
        ((RB, LANES), jnp.int32),       # superstep record staging
        ((2,), jnp.int32),              # flush in flight, its superstep
        ((len(_k.WIN_FIELDS), WR + 1, LANES), jnp.int32),  # ring window
        ((2, 2, LANES), jnp.int32),     # pair-gather DMA buf
    ]
    # Second-order scratch (inert (1,) when unused): v_prev segment bounds
    # per lane, bisection lo/hi per breadth-wise probe, rejection's folded
    # key pair / candidate / first-accept flag, the reservoir's SMEM carry
    # (running E-S key + winning offset rides cand), per-chunk uniforms
    # and membership flags, and the ping-pong chunk column/weight DMA
    # buffers.  Then the gather-hierarchy scratch (inert (1,) when the
    # cache is off): per-lane probe result (cache slot or -1), coalescing
    # leader, and the direct-mapped tag table (vertex, lane).
    second_smem = [
        ((W if second else 1,), jnp.int32),        # plo
        ((W if second else 1,), jnp.int32),        # phi
        ((BW,), jnp.int32),                        # bisect lo
        ((BW,), jnp.int32),                        # bisect hi
        ((W if rejection else 1,), jnp.uint32),    # kq0
        ((W if rejection else 1,), jnp.uint32),    # kq1
        ((W if second else 1,), jnp.int32),        # cand/best
        ((W if rejection else 1,), jnp.int32),     # got
        ((W if reservoir else 1,), jnp.float32),   # E-S key
        ((CH,), jnp.float32),                      # per-chunk uniforms
        ((CH,), jnp.int32),                        # common-neighbor flags
        ((2, CR, LANES), jnp.int32),               # chunk column DMA buf
        ((2, CR, LANES), jnp.float32),             # chunk weight DMA buf
    ]
    cache_smem = [((Wc,), jnp.int32)] * 4          # cslot, leader, tag v/l

    if use_cache:
        # The packed hot-vertex block: launch-resident SMEM operands (the
        # on-chip tier of the gather hierarchy).
        cache_np = [
            np.asarray(cache.hot_ids, np.int32),
            np.asarray(cache.hot_deg, np.int32),
            np.asarray(cache.hot_off, np.int32),
            np.asarray(cache.col, np.int32),
            np.asarray(cache.weights if cache.weights is not None
                       else np.zeros((1,)), np.float32),
            np.asarray(cache.alias_prob if cache.alias_prob is not None
                       else np.zeros((1,)), np.float32),
            np.asarray(cache.alias_idx if cache.alias_idx is not None
                       else np.zeros((1,)), np.int32),
            np.asarray(cache.type_offsets if cache.type_offsets is not None
                       else np.zeros((1, 2)), np.int32),
        ]
    else:  # inert placeholders — the kernel never touches them
        cache_np = [np.full((1,), -1, np.int32), np.zeros((1,), np.int32),
                    np.zeros((2,), np.int32), np.zeros((1,), np.int32),
                    np.zeros((1,), np.float32), np.zeros((1,), np.float32),
                    np.zeros((1,), np.int32), np.zeros((1, 2), np.int32)]

    # SMEM operands: key, launch control, 6 lane arrays in and out, queue
    # counters, head history and stats in and out.
    io_shapes = [(2,), (1,)] + [(W,)] * 12 + [(3,), (C + 1,),
                                              (_k.NUM_STATS,)] * 2
    base = smem_bytes(io_shapes + [shape for shape, _ in lane_smem
                                   + staging_smem + second_smem
                                   + cache_smem])
    if cfg.cache_budget > 0 and base + cfg.cache_budget > SMEM_BYTES:
        raise ValueError(
            f"cache_budget={cfg.cache_budget} bytes does not fit in SMEM: "
            f"the {kind} kernel at num_slots={W} already takes {base} of "
            f"its {SMEM_BYTES} bytes, leaving {max(SMEM_BYTES - base, 0)} "
            f"for the hot-vertex cache")

    smem_ref = pltpu.SMEM
    dma2 = pltpu.SemaphoreType.DMA((2,))
    dma1 = pltpu.SemaphoreType.DMA((1,))
    st = [smem_ref(*a) for a in staging_smem]
    scratch = (
        [smem_ref(*a) for a in lane_smem]
        + [st[0], dma2, st[1], dma2, st[2], dma2, st[3], dma2,
           st[4], dma1, st[5], st[6],
           pltpu.SemaphoreType.DMA((len(_k.WIN_FIELDS),)), st[7], dma2]
        + [smem_ref(*a) for a in second_smem]
        + [pltpu.SemaphoreType.DMA((2, 2))]
        + [smem_ref(*a) for a in cache_smem])

    @jax.jit
    def launch(graph, rows, state, base_key, k):
        Q = state.done.shape[0]
        nv = graph.row_ptr.shape[0] - 1
        ne = graph.col.shape[0]
        has_weights = reservoir and graph.weights is not None
        kernel = functools.partial(
            _k.fused_superstep_kernel, nv, ne, W, H, depth, C,
            stop_prob, kind, mp_sched, to_width(graph), rej_rounds, inv_p,
            inv_q, int(graph.max_degree), CH, CR, has_weights, static_mode,
            use_cache, num_hot, cache_trips, cache_len, RB)
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        s = state.slots
        q = state.queue
        stats_vec = jnp.stack(
            [jnp.asarray(v, jnp.int32) for v in state.stats])
        qctr = jnp.stack([q.head, q.staged, q.tail]).astype(jnp.int32)
        # The arrival ring in consumption order from this launch's head
        # (slot id, start vertex, epoch): refill reads it as a window of
        # at most W arrivals per superstep, so no Q-sized array enters the
        # kernel.
        pos = jax.lax.rem(q.head + jnp.arange(WIN_ROWS * LANES,
                                              dtype=jnp.int32), Q)
        wq = q.order[pos]
        qwin = jnp.stack([wq, q.start_vertex[wq], q.epoch[wq]]).astype(
            jnp.int32).reshape(len(_k.WIN_FIELDS) * WIN_ROWS, LANES)
        # Graph arrays as 128-lane rows, laid out once per run by the
        # caller (the DMA engine moves whole rows).
        assert rows.col.shape[0] >= -(-ne // LANES) + max(2, CR), (
            "graph rows are padded for a narrower chunk window")
        inputs = [
            jnp.asarray(base_key, jnp.uint32),
            jnp.asarray(k, jnp.int32).reshape(1),
            s.v_curr, s.v_prev, s.query_id, s.hop,
            s.active.astype(jnp.int32), s.epoch,
            qctr, state.head_hist.astype(jnp.int32), stats_vec, qwin,
            rows.row_ptr, rows.col, rows.weights, rows.alias_prob,
            rows.alias_idx, rows.type_offsets,
            *[jnp.asarray(a) for a in cache_np],
            jnp.full((KMAX * RB, LANES), -1, jnp.int32),
        ]
        outs = pl.pallas_call(
            kernel,
            in_specs=[smem] * 11 + [hbm] * 7 + [smem] * 8 + [hbm],
            out_specs=[smem] * 9 + [hbm],
            out_shape=[jax.ShapeDtypeStruct((W,), jnp.int32)] * 6 + [
                jax.ShapeDtypeStruct((3,), jnp.int32),
                jax.ShapeDtypeStruct((C + 1,), jnp.int32),
                jax.ShapeDtypeStruct((_k.NUM_STATS,), jnp.int32),
                jax.ShapeDtypeStruct((KMAX * RB, LANES), jnp.int32),
            ],
            scratch_shapes=scratch,
            input_output_aliases={len(inputs) - 1: 9},
            interpret=interpret,
        )(*inputs)
        (vcur, vprev, qid, hop, act, ep, qctr_o, hist_o, stats_o,
         log) = outs
        done, paths, lengths = _apply_records(
            log, state.done, state.paths, state.lengths)
        return state._replace(
            slots=s._replace(v_curr=vcur, v_prev=vprev, query_id=qid,
                             hop=hop, active=act != 0, epoch=ep),
            queue=q._replace(head=qctr_o[0], staged=qctr_o[1],
                             tail=qctr_o[2]),
            paths=paths, lengths=lengths, done=done,
            stats=WalkStats(*(stats_o[i] for i in range(_k.NUM_STATS))),
            head_hist=hist_o)

    def _apply_records(log, done, paths, lengths):
        """Scatter the launch's superstep records into the Q-sized
        buffers — the same writes the jnp superstep makes per hop (a
        query's slot holds one occupant for the whole launch, so each
        (query, hop) cell is written at most once and lengths only
        grow)."""
        rec = log.reshape(KMAX, RB, LANES)[:, :NF * WR].reshape(
            KMAX, NF, WR * LANES)[:, :, :W]
        f = {name: rec[:, i].reshape(-1)
             for i, name in enumerate(_k.REC_FIELDS)}
        Q = done.shape[0]
        done = done.at[jnp.where(f["done"] == 1, f["query"], Q)].set(
            True, mode="drop")
        if record_paths:
            aq = jnp.where(f["hop"] >= 0, f["query"], Q)
            paths = paths.at[aq, jnp.maximum(f["hop"], 0)].set(
                f["vertex"], mode="drop")
            lengths = lengths.at[aq].max(f["hop"] + 1, mode="drop")
            nq = jnp.where(f["new_query"] >= 0, f["new_query"], Q)
            paths = paths.at[nq, 0].set(f["new_start"], mode="drop")
            lengths = lengths.at[nq].max(1, mode="drop")
        return done, paths, lengths

    return launch
