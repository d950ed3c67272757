"""Per-algorithm walks/sec across ``step_impl`` ∈ {jnp, pallas, fused}.

The three implementations sample bit-identical walks (pinned by
``tests/test_fused_step.py``); this suite tracks what each one *costs*:

  * ``jnp``    — vectorized XLA superstep, one dispatch per hop.
  * ``pallas`` — one-hop fused walk-step kernel inside the jnp superstep.
  * ``fused``  — device-resident multi-hop superstep kernel
                 (``hops_per_launch`` supersteps per launch).

Off-TPU the Pallas kernels run in interpret mode, so the pallas/fused
rows measure the interpreter, not the hardware — the suite pins the
harness and the BENCH.json schema either way, and becomes the fused-
pipeline headline number on a real TPU.  ``walks_per_s`` is completed
queries per wall-second of the closed-batch drain.
"""
import time

import numpy as np

from benchmarks.common import bench_walk, emit
from repro.graph import build_csr, make_dataset
from repro.graph.generators import GRAPH500, rmat_edges
from repro.walker import (ExecutionConfig, WalkProgram,
                          compile as compile_walker)

IMPLS = ("jnp", "pallas", "fused")

# Sampler kinds the cached-vs-uncached rows track: the pure-column
# gather, the typed metapath gather (type_offsets payload), and the
# chunked E-S reservoir (weights payload) — one row per cache payload
# shape.
CACHED_ALGOS = ("urw", "metapath", "reservoir_n2v")


def _algos(hops):
    return {
        "urw": WalkProgram.urw(hops),
        "ppr": WalkProgram.ppr(0.15, hops),
        "deepwalk": WalkProgram.deepwalk(hops),
        # PR-5 fused coverage: the rejection verify phase and the typed
        # metapath gather now run inside the device-resident kernel.
        "rejection_n2v": WalkProgram.node2vec(2.0, 0.5, hops,
                                              rejection_rounds=8),
        "metapath": WalkProgram.metapath([0, 1, 2], hops),
        # PR-6 fused coverage: weighted Node2Vec's chunked E-S reservoir
        # runs the in-kernel chunk loop — the last matrix row.
        "reservoir_n2v": WalkProgram.node2vec(2.0, 0.5, hops,
                                              weighted=True),
    }


def run(quick: bool = False):
    scale = 9 if quick else 11
    queries = 192 if quick else 1024
    hops = 12 if quick else 40
    slots = 64 if quick else 256
    g = make_dataset("WG", scale_override=scale, weighted=True,
                     with_alias=True, num_edge_types=3)
    starts = np.random.default_rng(1).integers(0, g.num_vertices, queries)
    out = {}
    for algo, program in _algos(hops).items():
        for impl in IMPLS:
            ex = ExecutionConfig(num_slots=slots, record_paths=False,
                                 step_impl=impl, hops_per_launch=8)
            dt, a = bench_walk(g, starts, program, ex, repeats=2)
            wps = queries / dt
            emit(f"impl_{algo}_{impl}", dt * 1e6,
                 f"walks_per_s={wps:.1f};msteps={a.msteps_per_s:.3f};"
                 f"supersteps_per_launch={a.supersteps_per_launch:.1f}")
            out.setdefault(algo, {})[impl] = wps
    # Fused kernel with hops_per_launch="auto": the compile-time resolver
    # (cache -> cost model, no wall clock) picks the launch granularity.
    ex = ExecutionConfig(num_slots=slots, record_paths=False,
                         step_impl="fused", hops_per_launch="auto")
    dt, a = bench_walk(g, starts, _algos(hops)["urw"], ex, repeats=2)
    wps = queries / dt
    emit("impl_urw_fused_auto", dt * 1e6,
         f"walks_per_s={wps:.1f};msteps={a.msteps_per_s:.3f};"
         f"supersteps_per_launch={a.supersteps_per_launch:.1f}")
    out.setdefault("urw", {})["fused_auto"] = wps
    _cached_rows(out, quick)
    return out


def _cached_rows(out, quick: bool):
    """Cached vs uncached fused superstep on a Graph500-skewed RMAT.

    The hot-vertex cache targets exactly this degree distribution: a few
    hubs carry most of the stationary gather traffic, so a small SMEM
    budget absorbs a large hit fraction.  Both variants are timed
    interleaved (min-of-k, drift-fair) and the *shipped* row is whichever
    is faster — fallback-to-default, so the reported speedup is >= 1.0 by
    construction and turning the cache on can never regress a
    deployment.  Hit rate and both raw timings ride in ``derived``.
    """
    import jax

    scale = 8 if quick else 10
    queries = 128 if quick else 512
    hops = 10 if quick else 24
    slots = 64 if quick else 256
    budget = (1 << 14) if quick else (1 << 17)
    repeats = 3
    edges, n = rmat_edges(scale, 8, GRAPH500, seed=2)
    r = np.random.default_rng(5)
    g = build_csr(edges, n,
                  weights=r.random(edges.shape[0]).astype(np.float32) + 1e-3,
                  edge_types=r.integers(0, 3, edges.shape[0]).astype(
                      np.int32),
                  num_edge_types=3)
    starts = np.random.default_rng(11).integers(0, n, queries).astype(
        np.int32)
    algos = {
        "urw": WalkProgram.urw(hops),
        "metapath": WalkProgram.metapath([0, 1, 2], hops),
        "reservoir_n2v": WalkProgram.node2vec(2.0, 0.5, hops, weighted=True),
    }
    for algo in CACHED_ALGOS:
        program = algos[algo]

        def runner(cb):
            ex = ExecutionConfig(num_slots=slots, record_paths=False,
                                 step_impl="fused", hops_per_launch=8,
                                 cache_budget=cb)
            w = compile_walker(program, execution=ex)

            def run():
                res = w.run(g, starts, seed=0)
                jax.block_until_ready(res.stats.steps)
                return res

            return run

        run_off, run_on = runner(0), runner(budget)
        run_off()                      # compile + warm
        hit = float(run_on().stats.cache_hit_rate())
        t_off = t_on = float("inf")
        for _ in range(repeats):       # interleaved min-of-k
            t0 = time.perf_counter()
            run_off()
            t_off = min(t_off, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run_on()
            t_on = min(t_on, time.perf_counter() - t0)
        ship_cached = t_on < t_off
        dt = t_on if ship_cached else t_off
        speedup = max(t_off / t_on, 1.0)
        wps = queries / dt
        emit(f"impl_{algo}_fused_cached", dt * 1e6,
             f"walks_per_s={wps:.1f};uncached_us={t_off * 1e6:.1f};"
             f"cached_us={t_on * 1e6:.1f};speedup={speedup:.2f};"
             f"hit_rate={hit:.3f};"
             f"ship={'cached' if ship_cached else 'default'}")
        out.setdefault(algo, {})["fused_cached"] = wps


if __name__ == "__main__":
    run()
