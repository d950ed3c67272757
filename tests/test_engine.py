"""Walk-engine behaviour: path validity, zero-bubble theorem, scheduling
modes, Pallas/jnp step equivalence, determinism."""
import dataclasses

import numpy as np
import pytest

from repro.core import EngineConfig
from repro.core.samplers import SamplerSpec
from repro.core.scheduler import analyze_run, min_queue_depth
from repro.core.walk_engine import _run_walks


CFG = EngineConfig(num_slots=128, max_hops=16)


def _walk(g, starts, spec, hops, cfg=None, seed=0):
    cfg = dataclasses.replace(cfg or CFG, max_hops=hops)
    return _run_walks(g, starts, spec, cfg, seed=seed)


def urw(g, starts, hops, cfg=None, seed=0):
    return _walk(g, starts, SamplerSpec(kind="uniform"), hops, cfg, seed)


def ppr(g, starts, alpha, hops, cfg=None, seed=0):
    return _walk(g, starts, SamplerSpec(kind="uniform", stop_prob=alpha),
                 hops, cfg, seed)


def deepwalk(g, starts, hops, cfg=None, seed=0):
    return _walk(g, starts, SamplerSpec(kind="alias"), hops, cfg, seed)


def node2vec(g, starts, p, q, hops, cfg=None, seed=0):
    return _walk(g, starts, SamplerSpec(kind="rejection_n2v", p=p, q=q),
                 hops, cfg, seed)


def metapath(g, starts, schedule, hops, cfg=None, seed=0):
    return _walk(g, starts,
                 SamplerSpec(kind="metapath", metapath=tuple(schedule)),
                 hops, cfg, seed)


def _valid_paths(g, paths, lengths):
    rp, col = np.asarray(g.row_ptr), np.asarray(g.col)
    for q in range(paths.shape[0]):
        for t in range(lengths[q] - 1):
            u, v = paths[q, t], paths[q, t + 1]
            seg = col[rp[u]:rp[u + 1]]
            if v not in seg:
                return False, (q, t, u, v)
    return True, None


@pytest.mark.parametrize("algo", ["urw", "ppr", "deepwalk", "node2vec"])
def test_paths_are_real_walks(algo, small_graph, weighted_graph, rng):
    g = weighted_graph if algo in ("deepwalk",) else small_graph
    starts = rng.integers(0, g.num_vertices, 200)
    runners = {
        "urw": lambda: urw(g, starts, 16, cfg=CFG),
        "ppr": lambda: ppr(g, starts, 0.15, 16, cfg=CFG),
        "deepwalk": lambda: deepwalk(g, starts, 16, cfg=CFG),
        "node2vec": lambda: node2vec(g, starts, 2.0, 0.5, 16, cfg=CFG),
    }
    res = runners[algo]()
    p, l = res.as_numpy()
    ok, info = _valid_paths(g, p, l)
    assert ok, f"invalid transition {info}"
    assert (p[np.arange(len(starts)), 0] == starts).all()
    assert int(res.stats.terminations) == len(starts)
    assert (l <= 17).all() and (l >= 1).all()


def test_every_query_completes(small_graph, rng):
    starts = rng.integers(0, small_graph.num_vertices, 500)
    res = urw(small_graph, starts, 8, cfg=CFG)
    _, l = res.as_numpy()
    assert (l >= 1).all()


def test_zero_bubble_theorem(small_graph, rng):
    """Theorem VI.1: with queue depth D = N + μCN the scheduler never
    starves a lane while work exists; under-provisioning starves."""
    starts = rng.integers(0, small_graph.num_vertices, 600)
    for C in (0, 2, 5):
        cfg = dataclasses.replace(CFG, injection_delay=C)
        a = analyze_run(urw(small_graph, starts, 12, cfg=cfg).stats)
        assert a.starved == 0, f"C={C}: starved={a.starved}"
        assert a.zero_bubble
    cfg = dataclasses.replace(CFG, injection_delay=5, queue_depth_factor=0.05)
    a = analyze_run(urw(small_graph, starts, 12, cfg=cfg).stats)
    assert a.starved > 0


def test_min_queue_depth_formula():
    assert min_queue_depth(16, 1.0, 0) == 16
    assert min_queue_depth(16, 1.0, 4) == 16 + 64
    assert min_queue_depth(128, 0.5, 2) == 128 + 128


def test_static_mode_has_more_bubbles(small_graph, rng):
    """Fig. 11 qualitative: static (bulk-synchronous) scheduling wastes
    lanes on early-terminating walks; zero-bubble does not."""
    starts = rng.integers(0, small_graph.num_vertices, 600)
    a_zb = analyze_run(urw(small_graph, starts, 16, cfg=CFG).stats)
    cfg_s = dataclasses.replace(CFG, mode="static")
    a_st = analyze_run(urw(small_graph, starts, 16, cfg=cfg_s).stats)
    assert a_st.bubble_ratio > a_zb.bubble_ratio + 0.1
    assert a_st.supersteps > a_zb.supersteps


def test_deterministic_across_slot_counts(small_graph, rng):
    """Stateless decomposition: paths depend only on (seed, qid) — NOT on
    lane count, scheduling order, or batch boundaries (paper §V-A)."""
    starts = rng.integers(0, small_graph.num_vertices, 150)
    res_a = urw(small_graph, starts, 12,
                      cfg=dataclasses.replace(CFG, num_slots=32))
    res_b = urw(small_graph, starts, 12,
                      cfg=dataclasses.replace(CFG, num_slots=256))
    res_c = urw(small_graph, starts, 12,
                      cfg=dataclasses.replace(CFG, mode="static"))
    pa, la = res_a.as_numpy()
    pb, lb = res_b.as_numpy()
    pc, lc = res_c.as_numpy()
    assert np.array_equal(pa, pb) and np.array_equal(la, lb)
    assert np.array_equal(pa, pc) and np.array_equal(la, lc)


def test_pallas_step_equivalence(small_graph, weighted_graph, rng):
    starts = rng.integers(0, small_graph.num_vertices, 100)
    cfgp = dataclasses.replace(CFG, step_impl="pallas")
    for g, algo in ((small_graph, urw), (weighted_graph, deepwalk)):
        r1, r2 = algo(g, starts, 8, cfg=CFG), algo(g, starts, 8, cfg=cfgp)
        assert np.array_equal(*(r.as_numpy()[0] for r in (r1, r2)))


def test_ppr_geometric_lengths(small_graph, rng):
    starts = rng.integers(0, small_graph.num_vertices, 800)
    res = ppr(small_graph, starts, 0.3, 64, cfg=CFG)
    _, l = res.as_numpy()
    # hops ~ Geometric(0.3) truncated by dead ends: mean well below 1/0.3+1
    assert 1.0 < l.mean() < 1 + 1 / 0.3 + 1


def test_metapath_early_termination(rng):
    from repro.graph import make_dataset
    g = make_dataset("WG", scale_override=9, num_edge_types=4)
    starts = rng.integers(0, g.num_vertices, 300)
    res = metapath(g, starts, [0, 1, 2, 3], 16, cfg=CFG)
    p, l = res.as_numpy()
    # with 4 types, most walks terminate early -> stressing the scheduler
    assert l.mean() < 16
    a = analyze_run(res.stats)
    assert a.starved == 0


@pytest.mark.parametrize("algo", ["ppr", "deepwalk"])
def test_superstep_phases_are_named_in_the_compiled_program(
        algo, weighted_graph):
    """The jnp superstep's ``jax.named_scope`` phases survive into the
    compiled program's op metadata, where a device trace reads them; every
    scatter into the 32 x 9 paths buffer is ``walk.record``."""
    import re

    import jax.numpy as jnp

    from repro.configs.ridgewalker import ALGORITHMS
    from repro.core.walk_engine import build_engine
    run = build_engine(ALGORITHMS[algo], EngineConfig(num_slots=16,
                                                      max_hops=8))
    hlo = run.lower(weighted_graph, jnp.zeros((32,), jnp.int32), 0,
                    num_queries=32).compile().as_text().splitlines()
    for scope in ("walk.sample", "walk.commit", "walk.refill",
                  "walk.record"):
        assert any(f"/{scope}/" in line for line in hlo), scope
    paths = [line for line in hlo
             if re.search(r"= s32\[32,9\]\S* scatter\(", line)]
    assert paths and all("/walk.record/" in line for line in paths)
