"""Fig. 10 analogue: throughput robustness under RMAT skew.

Balanced (a=b=c=d=0.25) vs Graph500 (0.57/0.19/0.19/0.05) initiators.
The paper's headline: gSampler collapses by >10x under Graph500 skew
(SIMT lockstep waits for the longest walk); RidgeWalker stays flat.  Our
TPU engine makes the same claim via the zero-bubble scheduler: the
static-scheduled mode stands in for lockstep execution and degrades, the
zero-bubble mode holds throughput.

The weighted-Node2Vec row times the E-S reservoir scan on the
Graph500-skewed graph, where a lane's scan follows its own degree: the
power-law tail makes the per-hop work uneven across lanes.
"""
import dataclasses

import numpy as np

from benchmarks.common import bench_walk, emit
from repro.graph import build_csr
from repro.graph.generators import BALANCED, GRAPH500, rmat_edges
from repro.walker import ExecutionConfig, WalkProgram


def _bench_n2vw(scale: int, queries: int, emitname: str):
    """Weighted Node2Vec on the Graph500-skewed RMAT."""
    edges, n = rmat_edges(scale, 8, GRAPH500, seed=0)
    wts = np.random.default_rng(3).random(edges.shape[0]).astype(
        np.float32) + 0.1
    g = build_csr(edges, n, weights=wts)
    starts = np.random.default_rng(4).integers(0, n, queries)
    prog = WalkProgram.node2vec(2.0, 0.5, 20, weighted=True)
    prog = dataclasses.replace(
        prog, spec=dataclasses.replace(prog.spec, reservoir_chunk=16))
    ex = ExecutionConfig(num_slots=32, record_paths=False)
    dt, a = bench_walk(g, starts, prog, ex, repeats=5)
    emit(emitname, dt * 1e6,
         f"msteps={a.msteps_per_s:.3f};occ={a.occupancy:.2f}")
    return a.msteps_per_s


def run(quick: bool = False):
    scale = 12 if quick else 14
    queries = 2000 if quick else 6000
    ex = ExecutionConfig(num_slots=256 if quick else 1024,
                         record_paths=False)
    program = WalkProgram.urw(80)
    results = {}
    for label, init in [("balanced", BALANCED), ("graph500", GRAPH500)]:
        for ef in ([8] if quick else [8, 32]):
            edges, n = rmat_edges(scale, ef, init, seed=0)
            g = build_csr(edges, n)
            starts = np.random.default_rng(2).integers(0, n, queries)
            dt_z, a_z = bench_walk(g, starts, program, ex)
            dt_s, a_s = bench_walk(
                g, starts, program, dataclasses.replace(ex, mode="static"))
            emit(f"fig10_SC{scale}-{ef}_{label}", dt_z * 1e6,
                 f"msteps={a_z.msteps_per_s:.3f};"
                 f"static_msteps={a_s.msteps_per_s:.3f};"
                 f"occ={a_z.occupancy:.2f};occ_static={a_s.occupancy:.2f}")
            results[(label, ef)] = (a_z.msteps_per_s, a_s.msteps_per_s)
    # skew robustness ratio: zero-bubble throughput retention under skew
    for ef in ([8] if quick else [8, 32]):
        zb_keep = results[("graph500", ef)][0] / results[("balanced", ef)][0]
        st_keep = results[("graph500", ef)][1] / results[("balanced", ef)][1]
        emit(f"fig10_retention_ef{ef}", 0.0,
             f"zero_bubble_retention={zb_keep:.2f};"
             f"static_retention={st_keep:.2f}")
    # weighted Node2Vec (E-S reservoir scan) under skew
    results["n2vw_msteps"] = _bench_n2vw(
        scale, 256 if quick else 1024, f"fig10_n2vw_SC{scale}")
    return results


if __name__ == "__main__":
    run()
