"""Device-resident fused superstep — the paper's *perfectly pipelined*
walker as ONE Pallas kernel (§V–VI), ``step_impl="fused"``.

The per-hop impls (``jnp`` / ``pallas``) fuse at most one pipeline pass
(Row Access → Sampling → Column Access) and bounce the entire lane pool,
RNG key folds, stop draws, termination, path scatter, and refill through
an XLA superstep on every hop — the launch-and-drain pattern
statically-scheduled designs (FastRW/LightRW) suffer from.  This kernel
instead keeps the whole machine resident on the device across ``k``
supersteps per launch:

  * **WalkerSlots + queue counters + stats stay in SMEM** for the entire
    launch (the paper's single-pipeline-word task tuples, §V-A).  The
    arrival ring stays in HBM: each superstep prefetches the window of at
    most W arrivals refill can consume (slot id / start / epoch in ring
    order, from the current head), so zero-bubble refill is pure scalar
    work and no Q-sized array occupies SMEM.
  * **In-kernel ThundeRiNG analogue**: per-task uniforms are derived on
    SMEM scalars via the shared :func:`repro.core.rng.threefry2x32` —
    the same fold chain as the jnp path, so draws are bit-identical and
    no random bits ever touch HBM (§VII).
  * **Graph gathers stay asynchronous**: row access / column access /
    alias-table probes issue the same double-buffered row DMAs as
    `kernels/walk_step` (the graph arrays are 128-lane rows; a gather
    copies the row holding its word, or the two rows holding a pair),
    overlapping lane *i+1*'s fetch with lane *i*'s sampling arithmetic
    (§V-B).
  * **Async write-back**: each superstep stages one record per lane
    (query, new hop, vertex, done flag, refilled query and its start) and
    streams the block to an HBM log while the next superstep gathers (the
    paper's §IV-B streaming-window write-back).  The wrapper scatters the
    log into ``done`` / ``paths`` / ``lengths`` after the launch — the
    chip's DMA engine cannot store single words into those arrays.
  * **In-kernel termination + zero-bubble refill**: the PPR stop draw,
    hop budget, dead-end detection, prefix-sum lane compaction, and the
    Theorem VI.1 staging controller all run between hops without leaving
    the kernel.

Host↔device traffic per launch therefore drops from O(k · state) (per-hop
superstep bouncing) to one state round-trip, and ``stats.launches`` counts
1 per ``k`` supersteps instead of 1 per superstep — the fusion factor
``supersteps / launches`` that `WalkStats.supersteps_per_launch` reports.

The kernel is a lowering of the sampler **phase-program IR**
(`repro.core.phase_program`): every loop-free program (``prog.fused``)
stages its gather/score phases through the DMA machinery here —

  * ``uniform`` / ``alias`` (and PPR via the stop draw): the original
    double-buffered row/column/alias-probe pipeline;
  * ``metapath``: the typed-segment gather is one extra double-buffered
    2-element DMA loop over the lane pool (``type_offsets[v, t:t+2]``
    packs the sub-segment bounds, like the RP_entry pair, with lane
    i+1's pair in flight while lane i picks), then the same uniform
    pick;
  * ``rejection_n2v``: the csr-gather(K) / first-accept score pair runs
    breadth-wise across the lane pool with in-kernel per-round uniforms
    (same Threefry counters as ``rng.task_uniforms(..., 2K, ...)``) and
    an O(log d) adjacency bisection over N(v_prev) whose proposal /
    probe column fetches are the same double-buffered one-element DMA
    loops as the uniform pipeline — the verify phase's operands never
    leave SMEM;
  * ``reservoir_n2v`` (weighted Node2Vec): the ``chunked_loop`` schedule
    runs in-kernel — a degree-adaptive chunk loop (trip count
    ``ceil(deg/chunk)`` per lane: like the jnp scan, each lane's work
    follows its own degree) streams each lane's CSR segment
    through ping-pong (2, chunk) column/weight DMA buffers (chunk c+1's
    fetch in flight while chunk c is scored), and the Efraimidis–
    Spirakis reservoir carry (running E-S key + winning offset per
    lane) lives in SMEM alongside the lane pool, folded with the same
    float ops as `samplers.es_chunk_score`/`es_merge`.

Every sampler kind therefore runs device-resident with overlapped
memory traffic — there is no jnp fallback path left in the engine.

Semantics are pinned bit-identical to the jnp superstep
(`core/walk_engine.py`) for every sampler, including PPR stop draws,
both scheduling modes, and the open-system ring economy —
``tests/test_fused_step.py``.  Layout note: slot state is (W,) in SMEM
(1 MiB on a v5e core, shared with the staging buffers and the hot-vertex
cache — `ops.smem_bytes` accounts for it); everything Q- or graph-sized
(CSR, edge weights, alias tables, type_offsets, the arrival ring, the
record log) stays in HBM.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng
from repro.core.rng import SALT_CHUNK0, SALT_COLUMN, SALT_STOP
from repro.core.samplers import _uniform_index
from repro.core.tasks import WalkStats
from repro.kernels.common import LANES, ScheduleBuilder
from repro.kernels.walk_step.walk_step import (_rows_copy, _word,
                                               cached_gather1_loop,
                                               cached_gather2_loop,
                                               gather1_loop, gather2_loop,
                                               row_access_loop)

# WalkStats slot indices inside the SMEM stats vector.
STAT = {f: i for i, f in enumerate(WalkStats._fields)}
NUM_STATS = len(WalkStats._fields)


def dma_schedule(kind: str = "uniform", lanes: int = 3, rounds: int = 2,
                 bisect_iters: int = 2, chunks: int = 3, records: int = 3,
                 cached: bool = False, probe_trips: int = 2):
    """Declarative DMA schedule of one fused-superstep launch, for the
    static hazard analyzer (`repro.analysis.dma_hazards`).

    Mirrors `fused_superstep_kernel`'s per-kind pipeline op-for-op with
    small unroll counts (double-buffered loops are period-2 in the slot
    cycle, so ``lanes``/``chunks`` ≥ 3 covers prologue, both steady-state
    parities, and drain):

      * every kind: `row_access_loop` on ``rpbuf``;
      * ``uniform``: column gather on ``colbuf``;
      * ``alias``: prob/alias probe loops then the column gather;
      * ``metapath``: the typed sub-segment bounds ride the
        `gather2_loop` on ``pairbuf``, then the column gather;
      * ``rejection_n2v``: v_prev RP_entry pairs on ``pairbuf``, then per
        round a proposal gather, ``bisect_iters`` probe gathers, and the
        membership gather — all on ``colbuf``;
      * ``reservoir_n2v``: v_prev pairs on ``pairbuf``, then per lane the
        ping-pong (``ckcol``, ``ckwgt``) chunk loop with chunk c+1 in
        flight while chunk c's bisection probes (``colbuf``) and E-S fold
        consume the staged chunk, then the final column access;
      * the arrival-ring window (``qbuf``), started at the top of the
        superstep and waited by refill;
      * the per-superstep record flush (``wbuf``, ``records``
        supersteps): one staging slot, waited before it is rewritten and
        drained at the end of the launch.

    ``cached=True`` emits the *fully-hit* representative superstep of the
    gather hierarchy (``cache_budget > 0`` and every lane's v_curr hot):
    every v_curr-keyed gather becomes smem-tier ``cache.*`` reads — the
    directory probe (``probe_trips`` binary-search reads plus the
    (addr, deg) payload on ``cache.idx``) replaces the RP_entry loop, and
    the column / alias / typed / chunk payloads read ``cache.col`` /
    ``cache.prob`` / ``cache.alias`` / ``cache.toff`` / ``cache.wgt`` —
    while the v_prev-keyed loops (the second-order samplers' pair fetch,
    bisection probes, and membership checks) and the path write-back keep
    their HBM copies.  The DMA pass proves hit paths issue **no** copies
    (a ``start`` on an smem buffer is a phantom copy) and the surviving
    miss-side loops stay wait-dominated.  Partially-hit supersteps
    interleave this schedule with the uncached one per lane, so the two
    declarations jointly cover every execution.

    Keep in sync with the kernel — the analyzer checks this declaration,
    and the declaration is only as good as its fidelity to the loops
    above.
    """
    b = ScheduleBuilder()
    window = b.start("qbuf", 0)                     # arrival-ring window

    def probe():
        # Directory probe: the binary search over the sorted hot-id list,
        # then the (addr, deg) directory payload — all launch-resident.
        for _ in range(probe_trips):
            b.cache_read("cache.idx")
        b.cache_read("cache.idx")

    def col_gather():
        if cached:
            for _ in range(lanes):
                b.cache_read("cache.col")
        else:
            b.gather_loop("colbuf", lanes)

    if cached:
        for _ in range(lanes):                      # row access via probe
            probe()
    else:
        b.gather_loop("rpbuf", lanes)               # row access (RP_entry)
    if kind == "alias":
        if cached:
            for _ in range(lanes):
                b.cache_read("cache.prob")
            for _ in range(lanes):
                b.cache_read("cache.alias")
        else:
            b.gather_loop("probbuf", lanes)
            b.gather_loop("aliasbuf", lanes)
        col_gather()
    elif kind == "metapath":
        if cached:
            for _ in range(lanes):                  # typed bounds
                b.cache_read("cache.toff")
        else:
            b.gather_loop("pairbuf", lanes)         # type_offsets[v, t:t+2]
        col_gather()
    elif kind == "rejection_n2v":
        b.gather_loop("pairbuf", lanes)             # RP_entry of v_prev
        for _ in range(rounds):
            col_gather()                            # proposal columns
            for _ in range(bisect_iters):
                b.gather_loop("colbuf", lanes)      # bisection probes
            b.gather_loop("colbuf", lanes)          # membership check
    elif kind == "reservoir_n2v":
        b.gather_loop("pairbuf", lanes)             # RP_entry of v_prev
        for _lane in range(lanes):
            if cached:
                # Hit lane: the chunk loop scores the cached row
                # elementwise — no ping-pong copies; only the
                # N(v_prev)-side bisection/membership DMAs remain.
                for _c in range(chunks):
                    b.cache_read("cache.col")       # candidate columns
                    for _ in range(bisect_iters):
                        b.gather_loop("colbuf", 2)  # probes over CH posns
                    b.gather_loop("colbuf", 2)      # membership check
                    b.cache_read("cache.col")       # E-S fold operands
                    b.cache_read("cache.wgt")
                continue
            # Per-lane degree-adaptive chunk loop: ping-pong (ckcol,
            # ckwgt) with chunk c+1 in flight while chunk c is scored.
            pend = {0: [(buf, b.start(buf, 0))
                        for buf in ("ckcol", "ckwgt")]}
            for c in range(chunks):
                if c + 1 < chunks:
                    pend[c + 1] = [(buf, b.start(buf, (c + 1) % 2))
                                   for buf in ("ckcol", "ckwgt")]
                for buf, cid in pend.pop(c):
                    b.wait(buf, c % 2, cid)
                # Candidate reads feed the breadth-wise bisection...
                b.read("ckcol", c % 2)
                for _ in range(bisect_iters):
                    b.gather_loop("colbuf", 2)      # probes over CH posns
                b.gather_loop("colbuf", 2)          # membership check
                # ...and the E-S fold consumes columns and weights.
                b.read("ckcol", c % 2)
                b.read("ckwgt", c % 2)
        col_gather()                                # final column access
    else:  # uniform / ppr
        col_gather()
    b.wait("qbuf", 0, window)                       # refill consumes it
    b.read("qbuf", 0)
    b.flush_loop("wbuf", records)                   # superstep records
    return b.ops


def _bisect_iters(max_degree: int) -> int:
    """Static adjacency-bisection trip count — MUST match
    `samplers.edge_exists` so the fused verify phase takes the same
    number of halvings as the jnp score executor."""
    return max(1, int(math.ceil(math.log2(max(int(max_degree), 2) + 1))))


class _CacheCtx:
    """Hot-vertex cache refs + static geometry, threaded through the
    sampling helpers (``None`` everywhere when ``cache_budget == 0`` —
    the cached code paths are then never traced, so the cache-off kernel
    is the exact pre-cache kernel).

    ``cslot`` is the per-lane probe result scratch: the lane's v_curr
    cache slot, or -1 on a miss — the single hit predicate every
    downstream gather keys on.  After the row-access phase, a hit lane's
    ``addr_scr`` holds the *cache-space* base ``hot_off[slot]`` instead
    of the HBM ``row_ptr[v]``, so ``addr + offset`` arithmetic is
    uniform across tiers and only the indexed array changes.
    """

    def __init__(self, num_hot, probe_trips, length,
                 chot_ref, cdeg_ref, coff_ref, ccol_ref, cwgt_ref,
                 cprob_ref, cali_ref, ctoff_ref, cslot_scr):
        self.num_hot = num_hot          # H (static)
        self.probe_trips = probe_trips  # binary-search trips (static)
        self.length = length            # packed payload length P (static)
        self.chot = chot_ref            # (H,) sorted hot vertex ids
        self.cdeg = cdeg_ref            # (H,) degrees
        self.coff = coff_ref            # (H+1,) exclusive prefix offsets
        self.col = ccol_ref             # (P,) packed columns
        self.wgt = cwgt_ref             # (P,) weights or None
        self.prob = cprob_ref           # (P,) alias accept probs or None
        self.alias = cali_ref           # (P,) alias indices or None
        self.toff = ctoff_ref           # (H, T+1) typed bounds or None
        self.cslot = cslot_scr          # (W,) per-lane probe result

    def hit_fn(self):
        return lambda i: self.cslot[i] >= 0


def _g1(n, e_fn, src_ref, buf, sem, num_entries, on_result, cache,
        cache_ref):
    """Column-style gather that serves hit lanes from ``cache_ref`` (the
    e_fn index is tier-uniform: cache-space for hits, HBM-space for
    misses, because row access already swapped the hit lanes' base
    address)."""
    cached_gather1_loop(
        n, e_fn, src_ref, buf, sem, num_entries, on_result,
        cache_ref=cache_ref,
        cache_len=cache.length if cache is not None else 1,
        hit_fn=cache.hit_fn() if cache is not None else None)


def _cache_probe(vv, cache):
    """Binary search (lower bound) for ``vv`` in the sorted hot-id
    directory — ``probe_trips`` statically-unrolled halvings of scalar
    SMEM reads; returns the cache slot or -1 on a miss."""
    lo = jnp.int32(0)
    hi = jnp.int32(cache.num_hot)
    for _ in range(cache.probe_trips):
        active = lo < hi
        mid = (lo + hi) // 2
        go = cache.chot[jnp.clip(mid, 0, cache.num_hot - 1)] < vv
        lo = jnp.where(active & go, mid + 1, lo)
        hi = jnp.where(active & jnp.logical_not(go), mid, hi)
    found = (lo < cache.num_hot) & (
        cache.chot[jnp.clip(lo, 0, cache.num_hot - 1)] == vv)
    return jnp.where(found, lo, jnp.int32(-1))


def _cached_row_access(W, num_vertices, cache, rp_ref, rpbuf, rpsem,
                       vcur, act, addr_scr, deg_scr, lead_scr,
                       tagv_scr, tagl_scr, stats):
    """Row access through the gather hierarchy: same-vertex coalescing →
    SMEM directory probe → HBM RP_entry DMAs for miss leaders only.

    Pass 1 fills a direct-mapped tag table (vertex → writing lane) in
    *reverse* lane order, so the surviving writer of each tag slot is
    the smallest lane index — every follower's leader precedes it and an
    ascending pass can forward the leader's result.  Staleness is
    impossible: every lane writes its own tag slot each superstep, so a
    surviving tag always belongs to a current v_curr.  Pass 2 resolves
    each lane's leader (a tag match is a full vertex-id match — distinct
    vertices sharing a tag slot fall back to self-leadership) and probes
    the directory (followers share their leader's vertex and therefore
    its probe result).  Pass 3 serves hit leaders from the directory —
    cache-space base + degree, no DMA — and runs the usual
    double-buffered RP_entry loop with start *and* wait predicated on
    "miss leader".  Pass 4 forwards leader results to followers, applies
    the same per-lane validity guard as the uncached ``on_row``, and
    accumulates the live-lane hit/miss/coalesced counters.

    Bit-identity: ``cdeg[slot]`` equals ``rp[v+1] - rp[v]`` by
    construction and followers share the leader's vertex, so every
    lane's (effective address, degree) resolves to the same bytes as the
    uncached loop.
    """
    def vv_of(i):
        return jnp.clip(vcur[i], 0, num_vertices - 1)

    def tag_fill(t, _):
        i = W - 1 - t
        vv = vv_of(i)
        s = jax.lax.rem(vv, W)
        tagv_scr[s] = vv
        tagl_scr[s] = i
        return 0

    jax.lax.fori_loop(0, W, tag_fill, 0)

    def lead_probe(i, _):
        vv = vv_of(i)
        s = jax.lax.rem(vv, W)
        lead_scr[i] = jnp.where(tagv_scr[s] == vv, tagl_scr[s], i)
        cache.cslot[i] = _cache_probe(vv, cache)
        return 0

    jax.lax.fori_loop(0, W, lead_probe, 0)

    def need(i):
        ii = jnp.minimum(i, W - 1)  # lookahead may probe index W
        return (lead_scr[ii] == ii) & (cache.cslot[ii] < 0)

    def copy(i, slot):
        return _rows_copy(rp_ref, vv_of(i), 2, rpbuf, rpsem, slot)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when((i + 1 < W) & need(i + 1))
        def _():
            copy(i + 1, jax.lax.rem(i + 1, 2)).start()

        @pl.when(need(i))
        def _():
            copy(i, slot).wait()
            off = jax.lax.rem(vv_of(i), LANES)
            lo = _word(rpbuf, slot, off)
            addr_scr[i] = lo
            deg_scr[i] = _word(rpbuf, slot, off + 1) - lo

        @pl.when((lead_scr[i] == i) & (cache.cslot[i] >= 0))
        def _():
            s = jnp.clip(cache.cslot[i], 0, cache.num_hot - 1)
            addr_scr[i] = cache.coff[s]
            deg_scr[i] = cache.cdeg[s]

        return 0

    @pl.when(need(0))
    def _():
        copy(0, 0).start()

    jax.lax.fori_loop(0, W, body, 0, unroll=False)

    def fin(i, acc):
        hits, misses, coal = acc
        led = lead_scr[i]
        follower = led != i

        @pl.when(follower)
        def _():
            addr_scr[i] = addr_scr[led]
            deg_scr[i] = deg_scr[led]

        v = vcur[i]
        deg_scr[i] = jnp.where((v >= 0) & (v < num_vertices),
                               deg_scr[i], 0)
        live = act[i] == 1
        hit = cache.cslot[i] >= 0
        return (hits + (live & ~follower & hit).astype(jnp.int32),
                misses + (live & ~follower & ~hit).astype(jnp.int32),
                coal + (live & follower).astype(jnp.int32))

    hits, misses, coal = jax.lax.fori_loop(
        0, W, fin, (jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    stats[STAT["cache_hits"]] = stats[STAT["cache_hits"]] + hits
    stats[STAT["cache_misses"]] = stats[STAT["cache_misses"]] + misses
    stats[STAT["cache_coalesced"]] = stats[STAT["cache_coalesced"]] + coal


def _rejection_sample(W, num_vertices, num_edges, K, inv_p, inv_q,
                      max_degree, k0, k1, rp_ref, col_ref,
                      colbuf, colsem, pairbuf, pairsem,
                      vcur, vprev, qid_o, hop_o, ep_o,
                      addr_scr, deg_scr, idx_scr, vnext_scr, u1_scr,
                      plo_scr, phi_scr, blo_scr, bhi_scr,
                      kq0_scr, kq1_scr, cand_scr, got_scr, cache=None):
    """In-kernel lowering of the rejection program's gather(csr, K) +
    score(first_accept) phases, breadth-wise across the lane pool: per
    round, derive (u_col, u_acc) from the same Threefry counters as
    ``rng.task_uniforms(..., 2K, SALT_COLUMN)`` (draw j and draw K+j
    share one block), propose a column, bisect the candidate in
    N(v_prev) (identical trip count and compares to
    `samplers.edge_exists`), apply the (p, q) bias, and keep the first
    accepted proposal — the last round is forced, like the jnp executor.
    Every column fetch (proposal, bisection probe, membership check)
    runs through the double-buffered one-element DMA loop, so lane i+1's
    fetch is in flight while lane i's arithmetic runs.  With ``cache``,
    the v_curr-keyed *proposal* fetch serves hit lanes from the packed
    cache columns; the v_prev-keyed bisection/membership fetches always
    go to HBM (the cache is keyed on the current vertex only).
    """
    iters = _bisect_iters(max_degree)
    w_max = max(inv_p, 1.0, inv_q)

    # RP_entry pair of v_prev per lane: the verify phase's bisection
    # bounds, plus the lane's folded key pair and accept state.
    def vp_word(i):
        return jnp.clip(vprev[i], 0, num_vertices - 1)

    def on_vp(i, lo, hi):
        plo_scr[i] = lo
        phi_scr[i] = hi
        c0, c1 = rng.task_key_pair(k0, k1, qid_o[i], hop_o[i], SALT_COLUMN,
                                   ep_o[i])
        kq0_scr[i] = c0
        kq1_scr[i] = c1
        got_scr[i] = 0
        vnext_scr[i] = 0

    gather2_loop(W, vp_word, rp_ref, pairbuf, pairsem, on_vp)

    def round_body(j, _):
        ju = j.astype(jnp.uint32)

        def lane_draw(i, _i):
            y0, y1 = rng.threefry2x32(kq0_scr[i], kq1_scr[i], ju,
                                      ju + jnp.uint32(K))
            prop = _uniform_index(deg_scr[i], rng.bits_to_uniform(y0))
            u1_scr[i] = rng.bits_to_uniform(y1)
            idx_scr[i] = addr_scr[i] + prop
            blo_scr[i] = plo_scr[i]
            bhi_scr[i] = phi_scr[i]
            return 0

        jax.lax.fori_loop(0, W, lane_draw, 0)

        def on_cand(i, v):
            cand_scr[i] = v

        _g1(W, lambda i: idx_scr[i], col_ref, colbuf, colsem,
            num_edges, on_cand, cache,
            cache.col if cache is not None else None)

        for _ in range(iters):
            def on_probe(i, cv):
                lo = blo_scr[i]
                hi = bhi_scr[i]
                active = lo < hi
                mid = (lo + hi) // 2
                go_right = cv < cand_scr[i]
                blo_scr[i] = jnp.where(active & go_right, mid + 1, lo)
                bhi_scr[i] = jnp.where(active & ~go_right, mid, hi)

            gather1_loop(W, lambda i: (blo_scr[i] + bhi_scr[i]) // 2,
                         col_ref, colbuf, colsem, num_edges, on_probe)

        def on_member(i, cv):
            y = cand_scr[i]
            vp = vprev[i]
            common = (blo_scr[i] < phi_scr[i]) & (cv == y) & (vp >= 0)
            w = jnp.where(vp < 0, 1.0,
                          jnp.where(y == vp, inv_p,
                                    jnp.where(common, 1.0, inv_q)))
            accept = (u1_scr[i] * w_max <= w) | (j == K - 1)
            got = got_scr[i] == 1
            take = accept & ~got
            vnext_scr[i] = jnp.where(take, y, vnext_scr[i])
            got_scr[i] = (got | accept).astype(jnp.int32)

        gather1_loop(W, lambda i: blo_scr[i], col_ref, colbuf, colsem,
                     num_edges, on_member)
        return 0

    jax.lax.fori_loop(0, K, round_body, 0)


def _metapath_sample(W, num_vertices, num_edges, mp_sched, to_width, to_ref,
                     col_ref,
                     colbuf, colsem, pairbuf, pairsem,
                     vcur, hop_o, u0_scr, addr_scr, deg_scr, idx_scr,
                     vnext_scr, cache=None):
    """In-kernel lowering of the metapath program's gather(typed) +
    score(pick_uniform) phases: the scheduled type's packed sub-segment
    bounds (``type_offsets[v, t:t+2]``) ride the double-buffered pair
    DMA loop (lane i+1's bounds in flight while lane i picks),
    the staged uniform picks within the sub-segment, and a no-match
    sub-segment zeroes the lane's effective degree (early termination,
    same as the jnp executor).  With ``cache``, hit lanes take their
    bounds from the packed ``toff`` rows (type offsets are row-relative,
    so the cached row reads identically to the HBM row) and their column
    from the packed cache columns."""
    L = len(mp_sched)

    def seg_t(i):
        r = jax.lax.rem(hop_o[i], L)
        t = jnp.int32(mp_sched[0])
        for s in range(1, L):
            t = jnp.where(r == s, jnp.int32(mp_sched[s]), t)
        return t

    def seg_word(i):
        # Flat word of type_offsets[v, t] in the row-tiled (N*(T+1),)
        # layout; its pair partner t+1 is the next word.
        v_safe = jnp.clip(vcur[i], 0, num_vertices - 1)
        return v_safe * to_width + seg_t(i)

    def on_seg(i, base, end):
        cnt = end - base
        pick = base + _uniform_index(cnt, u0_scr[i])
        idx_scr[i] = addr_scr[i] + pick
        deg_scr[i] = jnp.where(cnt > 0, deg_scr[i], 0)

    if cache is not None and cache.toff is not None:
        def hit_pair(i):
            s = jnp.clip(cache.cslot[i], 0, cache.num_hot - 1)
            t = seg_t(i)
            return cache.toff[s, t], cache.toff[s, t + 1]

        cached_gather2_loop(W, seg_word, to_ref, pairbuf, pairsem, on_seg,
                            hit_fn=cache.hit_fn(), hit_pair_fn=hit_pair)
    else:
        gather2_loop(W, seg_word, to_ref, pairbuf, pairsem, on_seg)

    def on_col(i, v):
        vnext_scr[i] = v

    _g1(W, lambda i: idx_scr[i], col_ref, colbuf, colsem,
        num_edges, on_col, cache,
        cache.col if cache is not None else None)


def _reservoir_sample(W, num_vertices, num_edges, CH, CR, inv_p, inv_q,
                      max_degree, has_weights, k0, k1,
                      rp_ref, col_ref, wgt_ref,
                      colbuf, colsem, pairbuf, pairsem,
                      ckcol, ckwgt, cksem,
                      act, stop_scr, vcur, vprev, qid_o, hop_o, ep_o,
                      addr_scr, deg_scr, idx_scr, vnext_scr,
                      plo_scr, phi_scr, blo_scr, bhi_scr,
                      cand_scr, bkey_scr, ures_scr, fnd_scr, cache=None):
    """In-kernel ``chunked_loop`` schedule — the Efraimidis–Spirakis
    weighted reservoir scan (weighted Node2Vec) as a degree-adaptive
    chunk loop per lane.

    Per lane, the trip count is ``ceil(deg/CH)`` (chunks past a lane's
    own degree contribute only -inf reservoir keys, so stopping the
    loop there cannot change the scanned argmax).  Chunk c of
    (column, edge weight) streams through ping-pong ``(2, CR, 128)`` DMA
    buffers (the CR rows that can hold a CH-word window) with chunk c+1's fetch in flight while chunk c is scored;
    the per-chunk uniforms reproduce ``rng.task_uniforms(..., CH,
    SALT_CHUNK0 + c)``'s counter layout exactly; the (p, q) bias
    bisects all CH candidates in N(v_prev) breadth-wise (identical trip
    count and compares to `samplers.edge_exists`, probes double-
    buffered); and the running (E-S key, winning offset) carry is held
    in SMEM alongside the lane pool, folded with strict ``>`` so the
    earliest maximal key wins — the same tie-break as
    `samplers.es_chunk_score` (first within-chunk argmax) +
    `samplers.es_merge` (strict cross-chunk merge), making the fold
    bit-identical to `phase_program.reservoir_scan`.

    With ``cache``, a hit lane's whole chunk loop goes DMA-free: the
    candidate columns and fold weights read the packed cache row
    elementwise (same bytes as the staged chunk — verbatim CSR slices),
    both ping-pong copies are predicated off, and only the
    N(v_prev)-keyed bisection/membership fetches still touch HBM.  Miss
    lanes run the unchanged ping-pong pipeline.
    """
    iters = _bisect_iters(max_degree)
    pairs = (CH + 1) // 2

    # v_prev RP_entry pair per lane (bias bisection bounds), plus the
    # reservoir carry init.
    def vp_word(i):
        return jnp.clip(vprev[i], 0, num_vertices - 1)

    def on_vp(i, lo, hi):
        plo_scr[i] = lo
        phi_scr[i] = hi
        bkey_scr[i] = -jnp.inf
        cand_scr[i] = 0

    gather2_loop(W, vp_word, rp_ref, pairbuf, pairsem, on_vp)

    def lane_scan(i, _):
        deg = deg_scr[i]
        # Lanes whose sample is consumed this superstep: active, not
        # PPR-stopped, with a non-empty segment.  The jnp path computes
        # (masked, unused) results for the rest; skipping them here
        # changes nothing observable.
        run = (act[i] == 1) & (stop_scr[i] == 0) & (deg > 0)

        @pl.when(run)
        def _():
            addr = addr_scr[i]
            vp = vprev[i]
            plo = plo_scr[i]
            phi = phi_scr[i]
            n_tr = (deg + CH - 1) // CH
            hit = (cache.cslot[i] >= 0) if cache is not None else None

            def when_miss(fn):
                # Hit lanes read the cached row elementwise — every
                # chunk copy is predicated off for them.
                if cache is not None:
                    pl.when(jnp.logical_not(hit))(fn)
                else:
                    fn()

            def ck_copies(c, slot):
                # A chunk is the CR rows that hold words [addr + c*CH,
                # addr + c*CH + CH) of the row-tiled `col` (and weights);
                # position j sits at word `off + j` of the staged rows.
                # The row arrays carry CR rows of padding, so the copy
                # never leaves them; positions past the lane's degree
                # are masked out of the fold.
                row = (addr + c * CH) // LANES
                cps = [pltpu.make_async_copy(
                    col_ref.at[pl.ds(row, CR)], ckcol.at[slot],
                    cksem.at[slot, 0])]
                if has_weights:
                    cps.append(pltpu.make_async_copy(
                        wgt_ref.at[pl.ds(row, CR)], ckwgt.at[slot],
                        cksem.at[slot, 1]))
                return cps

            def _start0():
                for cp in ck_copies(0, 0):
                    cp.start()

            when_miss(_start0)

            def chunk_body(c, _c):
                slot = jax.lax.rem(c, 2)

                def _prefetch():
                    @pl.when(c + 1 < n_tr)
                    def _():
                        for cp in ck_copies(c + 1, jax.lax.rem(c + 1, 2)):
                            cp.start()

                when_miss(_prefetch)

                def _drain():
                    for cp in ck_copies(c, slot):
                        cp.wait()

                when_miss(_drain)

                off = jax.lax.rem(addr + c * CH, LANES)

                def cache_e(j):
                    # Cache-space index of chunk position j (addr is the
                    # packed-row base for hit lanes).
                    return jnp.clip(addr + c * CH + j, 0,
                                    cache.length - 1)

                def cand(j):
                    # chunk_gather's staging: invalid positions -> -1.
                    val = _word(ckcol, slot, off + j)
                    if cache is not None:
                        val = jnp.where(hit, cache.col[cache_e(j)], val)
                    return jnp.where(c * CH + j < deg, val, -1)

                # Per-chunk uniforms: same counter split as
                # rng.key_bits(CH) (draw j and draw pairs+j share a
                # Threefry block; odd widths pad one zero counter).
                d0, d1 = rng.task_key_pair(
                    k0, k1, qid_o[i], hop_o[i], SALT_CHUNK0 + c, ep_o[i])

                def draw_block(b, _b):
                    bu = b.astype(jnp.uint32)
                    x1 = jnp.where(b + pairs < CH, bu + jnp.uint32(pairs),
                                   jnp.uint32(0))
                    y0, y1 = rng.threefry2x32(d0, d1, bu, x1)
                    ures_scr[b] = rng.bits_to_uniform(y0)

                    @pl.when(b + pairs < CH)
                    def _():
                        ures_scr[b + pairs] = rng.bits_to_uniform(y1)

                    return 0

                jax.lax.fori_loop(0, pairs, draw_block, 0)

                # Bias verify: bisect all CH candidates in N(v_prev)
                # breadth-wise, probe DMAs double-buffered.
                def binit(j, _j):
                    blo_scr[j] = plo
                    bhi_scr[j] = phi
                    return 0

                jax.lax.fori_loop(0, CH, binit, 0)

                for _ in range(iters):
                    def on_probe(j, cv):
                        lo = blo_scr[j]
                        hi = bhi_scr[j]
                        active = lo < hi
                        mid = (lo + hi) // 2
                        go_right = cv < cand(j)
                        blo_scr[j] = jnp.where(active & go_right, mid + 1,
                                               lo)
                        bhi_scr[j] = jnp.where(active & ~go_right, mid, hi)

                    gather1_loop(CH,
                                 lambda j: (blo_scr[j] + bhi_scr[j]) // 2,
                                 col_ref, colbuf, colsem, num_edges,
                                 on_probe)

                def on_member(j, cv):
                    fnd_scr[j] = ((blo_scr[j] < phi)
                                  & (cv == cand(j))).astype(jnp.int32)

                gather1_loop(CH, lambda j: blo_scr[j], col_ref, colbuf,
                             colsem, num_edges, on_member)

                # E-S fold into the SMEM reservoir carry: strict > is
                # exactly es_chunk_score's first-argmax + es_merge's
                # earliest-chunk tie-break, flattened.
                def fold(j, _f):
                    valid = c * CH + j < deg
                    y = cand(j)
                    if has_weights:
                        wv = _word(ckwgt, slot, off + j)
                        if cache is not None and cache.wgt is not None:
                            wv = jnp.where(hit, cache.wgt[cache_e(j)], wv)
                        w_edge = jnp.where(valid, wv, 0.0)
                    else:
                        w_edge = jnp.where(valid, 1.0, 0.0)
                    common = (fnd_scr[j] == 1) & (vp >= 0)
                    bias = jnp.where(vp < 0, 1.0,
                                     jnp.where(y == vp, inv_p,
                                               jnp.where(common, 1.0,
                                                         inv_q)))
                    w = w_edge * bias
                    key = jnp.where(valid & (w > 0),
                                    jnp.log(ures_scr[j] + 1e-20) / w,
                                    -jnp.inf)
                    take = key > bkey_scr[i]
                    bkey_scr[i] = jnp.where(take, key, bkey_scr[i])
                    cand_scr[i] = jnp.where(take, c * CH + j, cand_scr[i])
                    return 0

                jax.lax.fori_loop(0, CH, fold, 0)
                return 0

            jax.lax.fori_loop(0, n_tr, chunk_body, 0)
            idx_scr[i] = addr + jnp.clip(cand_scr[i], 0,
                                         jnp.maximum(deg - 1, 0))

        @pl.when(~run)
        def _():
            idx_scr[i] = addr_scr[i]

        return 0

    jax.lax.fori_loop(0, W, lane_scan, 0)

    def on_col(i, v):
        vnext_scr[i] = v

    _g1(W, lambda i: idx_scr[i], col_ref, colbuf, colsem,
        num_edges, on_col, cache,
        cache.col if cache is not None else None)


# Fields of the per-superstep record the kernel streams to HBM; the
# wrapper (`ops.build_fused_launch`) scatters them into done / paths /
# lengths after the launch.
REC_FIELDS = ("query", "hop", "vertex", "done", "new_query", "new_start")
REC = {f: i for i, f in enumerate(REC_FIELDS)}
# Fields of the arrival-ring window (ring order, from the launch's head),
# stacked as row blocks of one (R, 128) HBM array.  Each field is copied
# by its own 2-D row DMA: a strided copy across the field axis of a 3-D
# array crashed the chip at unaligned row offsets.
WIN_FIELDS = ("query", "start", "epoch")


def fused_superstep_kernel(
        # ---- static configuration (bound via functools.partial) ----
        num_vertices, num_edges, W, max_hops, depth, delay,
        stop_prob, kind, mp_sched, to_width, rej_rounds, inv_p, inv_q,
        max_degree, res_chunk, res_rows, has_weights, static_mode,
        use_cache, num_hot, cache_probe_trips, cache_len, rec_rows,
        # ---- inputs ----
        key_ref, ctl_ref,
        vcur_in, vprev_in, qid_in, hop_in, act_in, ep_in,
        qctr_in, hist_in, stats_in, qwin_ref,
        rp_ref, col_ref, wgt_ref, prob_ref, alias_ref, to_ref,
        chot_ref, cdeg_ref, coff_ref, ccol_ref, cwgt_ref, cprob_ref,
        cali_ref, ctoff_ref, log_in,
        # ---- outputs ----
        vcur, vprev, qid_o, hop_o, act, ep_o,
        qctr, hist, stats, log,
        # ---- scratch ----
        stop_scr, u0_scr, u1_scr, addr_scr, deg_scr, idx_scr, vnext_scr,
        term_scr,
        rpbuf, rpsem, colbuf, colsem, probbuf, probsem, aliasbuf, aliassem,
        recbuf, recsem, wcnt, qbuf, qsem, pairbuf, pairsem,
        plo_scr, phi_scr, blo_scr, bhi_scr, kq0_scr, kq1_scr, cand_scr,
        got_scr, bkey_scr, ures_scr, fnd_scr, ckcol, ckwgt, cksem,
        cslot_scr, lead_scr, tagv_scr, tagl_scr):
    del log_in  # aliased with `log` (input_output_aliases)
    alias = kind == "alias"
    k0 = key_ref[0]
    k1 = key_ref[1]
    lane_rows = (W + LANES - 1) // LANES
    win_rows = qbuf.shape[1]
    field_rows = qwin_ref.shape[0] // len(WIN_FIELDS)
    head0 = qctr_in[0]
    wcnt[0] = 0
    # The gather-hierarchy context: None when cache_budget == 0, so the
    # cache-off kernel traces exactly the pre-cache pipeline.
    cache = None
    if use_cache:
        cache = _CacheCtx(
            num_hot, cache_probe_trips, cache_len,
            chot_ref, cdeg_ref, coff_ref, ccol_ref,
            cwgt_ref if has_weights else None,
            cprob_ref if alias else None,
            cali_ref if alias else None,
            ctoff_ref if kind == "metapath" else None,
            cslot_scr)

    def rec(field, i, value):
        recbuf[REC[field] * lane_rows + i // LANES,
               jax.lax.rem(i, LANES)] = value

    def flush_copy(step):
        """The record flush of superstep ``step`` (local to the launch):
        the staged records go to their own row block of the HBM log."""
        return pltpu.make_async_copy(
            recbuf, log.at[pl.ds(step * rec_rows, rec_rows)], recsem.at[0])

    def flush_wait():
        """Drain the one record flush in flight, if any — before the
        staging rows are overwritten, and at the end of the launch."""
        @pl.when(wcnt[0] == 1)
        def _():
            flush_copy(wcnt[1]).wait()
            wcnt[0] = 0

    # ---- bring the launch-resident state into the output refs ----------
    def cp_w(i, _):
        vcur[i] = vcur_in[i]
        vprev[i] = vprev_in[i]
        qid_o[i] = qid_in[i]
        hop_o[i] = hop_in[i]
        act[i] = act_in[i]
        ep_o[i] = ep_in[i]
        return 0

    jax.lax.fori_loop(0, W, cp_w, 0)
    for i in range(3):
        qctr[i] = qctr_in[i]
    for i in range(delay + 1):
        hist[i] = hist_in[i]
    for i in range(NUM_STATS):
        stats[i] = stats_in[i]
    stats[STAT["launches"]] = stats[STAT["launches"]] + 1

    # ---- one superstep (bit-identical to walk_engine._superstep) -------
    def superstep(step, carry):
        head = qctr[0]
        tail = qctr[2]
        n_active = jax.lax.fori_loop(0, W, lambda i, a: a + act[i],
                                     jnp.int32(0))
        work = (head < tail) | (n_active > 0)

        @pl.when(work)
        def _():
            # -- prefetch the arrival-ring window refill may consume: at
            # most W arrivals from `head`, in flight across the gathers.
            qoff = head - head0
            qcopies = [pltpu.make_async_copy(
                qwin_ref.at[pl.ds(f * field_rows + qoff // LANES,
                                  win_rows)], qbuf.at[f], qsem.at[f])
                for f in range(len(WIN_FIELDS))]
            for cp in qcopies:
                cp.start()

            # -- per-lane stop draw + sampling uniforms (in-kernel RNG) --
            # The draw phase of the program: uniform/metapath consume one
            # uniform, alias two (counter layout exactly matches
            # rng.task_uniforms); rejection derives its 2K per-round
            # uniforms and the reservoir its CH per-chunk uniforms inside
            # the sampling loops below.
            def lane_rng(i, _):
                q = qid_o[i]
                h = hop_o[i]
                e = ep_o[i]
                if stop_prob > 0.0:
                    s0, s1 = rng.task_key_pair(k0, k1, q, h, SALT_STOP, e)
                    b0, _b1 = rng.threefry2x32(s0, s1, jnp.uint32(0),
                                               jnp.uint32(0))
                    u = rng.bits_to_uniform(b0)
                    stop_scr[i] = ((act[i] == 1)
                                   & (u < stop_prob)).astype(jnp.int32)
                else:
                    stop_scr[i] = 0
                if kind not in ("rejection_n2v", "reservoir_n2v"):
                    c0, c1 = rng.task_key_pair(k0, k1, q, h, SALT_COLUMN, e)
                    if alias:
                        y0, y1 = rng.threefry2x32(c0, c1, jnp.uint32(0),
                                                  jnp.uint32(1))
                        u0_scr[i] = rng.bits_to_uniform(y0)
                        u1_scr[i] = rng.bits_to_uniform(y1)
                    else:
                        y0, _y1 = rng.threefry2x32(c0, c1, jnp.uint32(0),
                                                   jnp.uint32(0))
                        u0_scr[i] = rng.bits_to_uniform(y0)
                return 0

            jax.lax.fori_loop(0, W, lane_rng, 0)

            # -- Row Access: packed (addr, deg) DMA per lane, or the
            # gather hierarchy (coalesce -> SMEM probe -> miss DMA) -----
            if use_cache:
                _cached_row_access(W, num_vertices, cache, rp_ref,
                                   rpbuf, rpsem, vcur, act,
                                   addr_scr, deg_scr, lead_scr,
                                   tagv_scr, tagl_scr, stats)
            else:
                def on_row(i, addr, deg):
                    v = vcur[i]
                    addr_scr[i] = addr
                    deg_scr[i] = jnp.where((v >= 0) & (v < num_vertices),
                                           deg, 0)

                row_access_loop(W, lambda i: vcur[i], rp_ref, rpbuf, rpsem,
                                num_vertices, on_row)

            # -- Sampling + Column Access (per phase program) ------------
            if kind == "rejection_n2v":
                _rejection_sample(
                    W, num_vertices, num_edges, rej_rounds, inv_p, inv_q,
                    max_degree, k0, k1, rp_ref, col_ref,
                    colbuf, colsem, pairbuf, pairsem,
                    vcur, vprev, qid_o, hop_o, ep_o,
                    addr_scr, deg_scr, idx_scr, vnext_scr, u1_scr,
                    plo_scr, phi_scr, blo_scr, bhi_scr,
                    kq0_scr, kq1_scr, cand_scr, got_scr, cache=cache)
            elif kind == "reservoir_n2v":
                _reservoir_sample(
                    W, num_vertices, num_edges, res_chunk, res_rows,
                    inv_p, inv_q, max_degree, has_weights, k0, k1,
                    rp_ref, col_ref, wgt_ref,
                    colbuf, colsem, pairbuf, pairsem,
                    ckcol, ckwgt, cksem,
                    act, stop_scr, vcur, vprev, qid_o, hop_o, ep_o,
                    addr_scr, deg_scr, idx_scr, vnext_scr,
                    plo_scr, phi_scr, blo_scr, bhi_scr,
                    cand_scr, bkey_scr, ures_scr, fnd_scr, cache=cache)
            elif kind == "metapath":
                _metapath_sample(
                    W, num_vertices, num_edges, mp_sched, to_width, to_ref,
                    col_ref, colbuf, colsem, pairbuf, pairsem,
                    vcur, hop_o, u0_scr, addr_scr, deg_scr, idx_scr,
                    vnext_scr, cache=cache)
            else:
                def pick(i):
                    return jnp.clip(
                        addr_scr[i] + _uniform_index(deg_scr[i], u0_scr[i]),
                        0, num_edges - 1)

                if alias:
                    def on_prob(i, p):
                        # accept -> keep draw; reject -> alias probe below
                        idx_scr[i] = jnp.where(u1_scr[i] < p, 0, -1)

                    _g1(W, pick, prob_ref, probbuf, probsem,
                        num_edges, on_prob, cache,
                        cache.prob if cache is not None else None)

                    def on_alias(i, a):
                        deg = deg_scr[i]
                        kdraw = _uniform_index(deg, u0_scr[i])
                        j = jnp.where(idx_scr[i] < 0, a, kdraw)
                        j = jnp.clip(j, 0, jnp.maximum(deg - 1, 0))
                        idx_scr[i] = jnp.clip(addr_scr[i] + j, 0,
                                              num_edges - 1)

                    _g1(W, pick, alias_ref, aliasbuf, aliassem,
                        num_edges, on_alias, cache,
                        cache.alias if cache is not None else None)
                else:
                    def set_idx(i, _):
                        idx_scr[i] = pick(i)
                        return 0

                    jax.lax.fori_loop(0, W, set_idx, 0)

                def on_col(i, v):
                    vnext_scr[i] = v

                _g1(W, lambda i: idx_scr[i], col_ref, colbuf,
                    colsem, num_edges, on_col, cache,
                    cache.col if cache is not None else None)

            # -- terminate + advance; stage this superstep's records -----
            # (the previous superstep's flush has overlapped the gathers
            # above; drain it before its staging rows are rewritten).
            flush_wait()

            def lane_update(i, acc):
                steps_acc, term_acc = acc
                A = act[i] == 1
                stop = stop_scr[i] == 1
                ok = deg_scr[i] > 0
                adv = A & (~stop) & ok
                dead = A & (~stop) & (~ok)
                nh = jnp.where(adv, hop_o[i] + 1, hop_o[i])
                term = stop | dead | (adv & (nh >= max_hops))
                term_scr[i] = term.astype(jnp.int32)
                rec("query", i, jnp.where(A, qid_o[i], -1))
                rec("hop", i, jnp.where(adv, nh, -1))
                rec("vertex", i, vnext_scr[i])
                rec("done", i, (term & A).astype(jnp.int32))
                vprev[i] = jnp.where(adv, vcur[i], vprev[i])
                vcur[i] = jnp.where(adv, vnext_scr[i], vcur[i])
                hop_o[i] = nh
                return (steps_acc + adv.astype(jnp.int32),
                        term_acc + (term & A).astype(jnp.int32))

            n_steps, n_term = jax.lax.fori_loop(
                0, W, lane_update, (jnp.int32(0), jnp.int32(0)))

            # -- stats (same accounting as the jnp superstep) ------------
            idle = W - n_active
            upstream = (head < tail).astype(jnp.int32)
            stats[STAT["steps"]] = stats[STAT["steps"]] + n_steps
            stats[STAT["slot_steps"]] = stats[STAT["slot_steps"]] + W
            stats[STAT["bubbles"]] = stats[STAT["bubbles"]] + idle
            stats[STAT["starved"]] = stats[STAT["starved"]] + idle * upstream
            stats[STAT["terminations"]] = (stats[STAT["terminations"]]
                                           + n_term)
            stats[STAT["supersteps"]] = stats[STAT["supersteps"]] + 1

            # -- staging controller (Theorem VI.1, delayed observation) --
            for j in range(delay):
                hist[j] = hist[j + 1]
            hist[delay] = head
            staged = jnp.maximum(qctr[1],
                                 jnp.minimum(hist[0] + depth, tail))
            qctr[1] = staged

            # -- zero-bubble prefix-sum refill from the ring window ------
            for cp in qcopies:
                cp.wait()
            if static_mode:
                all_free = jax.lax.fori_loop(
                    0, W,
                    lambda i, a: a & ((act[i] == 0) | (term_scr[i] == 1)),
                    True)
            avail = jnp.maximum(staged - head, 0)
            shift = jax.lax.rem(qoff, LANES)

            def lane_refill(i, acc):
                rank, taken = acc
                free = (act[i] == 0) | (term_scr[i] == 1)
                if static_mode:
                    free = free & all_free
                take = free & (rank < avail)
                rec("new_query", i, -1)
                rec("new_start", i, 0)

                @pl.when(take)
                def _():
                    w = shift + rank
                    r = w // LANES
                    c = jax.lax.rem(w, LANES)
                    nq = qbuf[0, r, c]
                    start = qbuf[1, r, c]
                    vcur[i] = start
                    vprev[i] = -1
                    qid_o[i] = nq
                    hop_o[i] = 0
                    act[i] = 1
                    ep_o[i] = qbuf[2, r, c]
                    rec("new_query", i, nq)
                    rec("new_start", i, start)

                @pl.when((~take) & (term_scr[i] == 1))
                def _():
                    qid_o[i] = -1
                    act[i] = 0

                return (rank + free.astype(jnp.int32),
                        taken + take.astype(jnp.int32))

            _, n_taken = jax.lax.fori_loop(
                0, W, lane_refill, (jnp.int32(0), jnp.int32(0)))
            qctr[0] = head + n_taken

            # -- stream this superstep's records out (async) -------------
            flush_copy(step).start()
            wcnt[0] = 1
            wcnt[1] = step

        return carry

    jax.lax.fori_loop(0, ctl_ref[0], superstep, 0)
    flush_wait()
