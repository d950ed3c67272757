"""Sampler correctness: distributions, adjacency tests, 2nd-order bias."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.phase_program import make_sampler
from repro.core.samplers import SamplerSpec, edge_exists
from repro.core.tasks import WalkerSlots
from repro.graph import build_alias_tables, build_csr


def _slots(v_curr, v_prev=None, n=None):
    n = n or len(v_curr)
    return WalkerSlots(
        v_curr=jnp.asarray(v_curr, jnp.int32),
        v_prev=jnp.asarray(v_prev if v_prev is not None else [-1] * n,
                           jnp.int32),
        query_id=jnp.arange(n, dtype=jnp.int32),
        hop=jnp.zeros((n,), jnp.int32),
        active=jnp.ones((n,), bool))


def _star_graph(weights=None):
    """Vertex 0 with 4 neighbors 1..4."""
    edges = np.array([[0, 1], [0, 2], [0, 3], [0, 4]])
    return build_csr(edges, 5, weights=weights)


def _empirical(g, spec, n=20000, v_prev=None):
    slots = _slots([0] * n, v_prev=[v_prev] * n if v_prev is not None
                   else None)
    # vary query ids -> independent streams
    from repro.graph.csr import row_access
    addr, deg = row_access(g, slots.v_curr)
    sampler = make_sampler(spec)
    idx, ok = sampler(g, addr, deg, slots, jax.random.PRNGKey(0))
    e = np.asarray(jnp.clip(addr + idx, 0, g.num_edges - 1))
    chosen = np.asarray(g.col)[e]
    return np.bincount(chosen, minlength=5)[1:5] / n


def test_uniform_distribution():
    g = _star_graph()
    freq = _empirical(g, SamplerSpec(kind="uniform"))
    np.testing.assert_allclose(freq, 0.25, atol=0.02)


def test_alias_weighted_distribution():
    w = np.array([0.1, 0.2, 0.3, 0.4], np.float32)
    g = build_alias_tables(_star_graph(weights=w))
    freq = _empirical(g, SamplerSpec(kind="alias"))
    np.testing.assert_allclose(freq, w / w.sum(), atol=0.02)


def test_edge_exists():
    edges = np.array([[0, 1], [0, 3], [1, 2], [2, 0], [2, 3]])
    g = build_csr(edges, 4)
    src = jnp.asarray([0, 0, 0, 1, 2, 2, 3])
    dst = jnp.asarray([1, 2, 3, 2, 0, 1, 0])
    got = np.asarray(edge_exists(g, src, dst))
    assert list(got) == [True, False, True, True, True, False, False]
    # batched candidate matrix
    got2 = np.asarray(edge_exists(g, jnp.asarray([0, 2]),
                                  jnp.asarray([[1, 2, 3], [0, 3, 1]])))
    assert got2.tolist() == [[True, False, True], [True, True, False]]


def _n2v_exact(g, v_prev, v_curr, p, q, weights=None):
    """Exact Node2Vec transition distribution."""
    rp, col = np.asarray(g.row_ptr), np.asarray(g.col)
    nbrs = col[rp[v_curr]:rp[v_curr + 1]]
    w = np.ones(len(nbrs)) if weights is None else \
        np.asarray(weights)[rp[v_curr]:rp[v_curr + 1]]
    prev_nbrs = set(col[rp[v_prev]:rp[v_prev + 1]])
    bias = np.array([1 / p if y == v_prev else
                     (1.0 if y in prev_nbrs else 1 / q) for y in nbrs])
    probs = w * bias
    return nbrs, probs / probs.sum()


@pytest.mark.parametrize("weighted", [False, True])
def test_node2vec_distribution(weighted, rng):
    # ring + chords graph, walk from 2 with prev=1
    edges = [(i, (i + 1) % 8) for i in range(8)]
    edges += [((i + 1) % 8, i) for i in range(8)]
    edges += [(2, 5), (2, 6), (1, 3)]
    edges = np.array(sorted(set(edges)))
    w = (rng.random(len(edges)).astype(np.float32) + 0.1) if weighted else None
    g = build_csr(edges, 8, weights=w)
    p_, q_ = 2.0, 0.5
    kind = "reservoir_n2v" if weighted else "rejection_n2v"
    spec = SamplerSpec(kind=kind, p=p_, q=q_, rejection_rounds=16)
    n = 30000
    slots = _slots([2] * n, v_prev=[1] * n)
    from repro.graph.csr import row_access
    addr, deg = row_access(g, slots.v_curr)
    idx, ok = make_sampler(spec)(g, addr, deg, slots, jax.random.PRNGKey(1))
    e = np.asarray(jnp.clip(addr + idx, 0, g.num_edges - 1))
    chosen = np.asarray(g.col)[e]
    nbrs, probs = _n2v_exact(g, 1, 2, p_, q_,
                             None if not weighted else g.weights)
    emp = np.bincount(chosen, minlength=8)[nbrs] / n
    np.testing.assert_allclose(emp, probs, atol=0.025)


def test_metapath_respects_types(rng):
    from repro.graph import make_dataset
    g = make_dataset("WG", scale_override=9, num_edge_types=3)
    spec = SamplerSpec(kind="metapath", metapath=(1,))
    n = 500
    starts = rng.integers(0, g.num_vertices, n)
    slots = _slots(starts)
    from repro.graph.csr import row_access
    addr, deg = row_access(g, slots.v_curr)
    idx, ok = make_sampler(spec)(g, addr, deg, slots, jax.random.PRNGKey(2))
    e = np.asarray(jnp.clip(addr + idx, 0, g.num_edges - 1))
    et = np.asarray(g.edge_type)
    ok = np.asarray(ok)
    assert ok.sum() > 0
    assert (et[e[ok]] == 1).all()


def test_stateless_rng_reproducible():
    """The draw is a pure function of (seed, qid, hop) — the stateless-task
    invariant that makes out-of-order execution sound (paper §V-A)."""
    from repro.core import rng as task_rng
    k = jax.random.PRNGKey(0)
    qid = jnp.asarray([5, 5, 9], jnp.uint32)
    hop = jnp.asarray([1, 1, 2], jnp.uint32)
    u1 = task_rng.task_uniforms(k, qid, hop, 3)
    u2 = task_rng.task_uniforms(k, qid[::-1], hop[::-1], 3)[::-1]
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    assert np.array_equal(np.asarray(u1[0]), np.asarray(u1[1]))
    assert not np.array_equal(np.asarray(u1[0]), np.asarray(u1[2]))


def _chunked_reservoir_reference(spec, g, v_curr, v_prev, qid, hop, key):
    """The E-S reservoir fold as the chunked-loop program defines it:
    every lane scans all ``ceil(max_degree/chunk)`` chunks of its row
    (the sharded lowering's gather/score primitives), keeping the
    earliest maximal key.  Returns the chosen offset per lane."""
    from repro.core import rng as task_rng
    from repro.core.phase_program import chunk_gather
    from repro.core.rng import SALT_CHUNK0
    from repro.core.samplers import (es_chunk_score, es_merge,
                                     es_num_chunks, n2v_bias)
    CH = spec.reservoir_chunk
    v_curr = jnp.asarray(v_curr, jnp.int32)
    v_prev = jnp.asarray(v_prev, jnp.int32)
    qid = jnp.asarray(qid, jnp.int32)
    hop = jnp.asarray(hop, jnp.int32)
    epoch = jnp.zeros_like(qid)
    addr = g.row_ptr[v_curr]
    deg = g.row_ptr[v_curr + 1] - addr

    def body(c, carry):
        u = task_rng.task_uniforms(key, qid, hop, CH, SALT_CHUNK0 + c,
                                   epoch=epoch)
        y, w_edge = chunk_gather(g, addr, deg, jnp.full_like(qid, c), CH)
        w = w_edge * n2v_bias(spec, g, v_prev, y)
        c_best, c_key = es_chunk_score(u, y >= 0, w)
        return es_merge(*carry, c, CH, c_best, c_key)

    init = (jnp.full(qid.shape, -jnp.inf), jnp.zeros(qid.shape, jnp.int32))
    _, best = jax.lax.fori_loop(0, es_num_chunks(g.max_degree, CH), body,
                                init)
    return np.asarray(jnp.clip(best, 0, jnp.maximum(deg - 1, 0)))


def test_reservoir_adaptive_chunks_bit_identical(rng):
    """The engine's reservoir scan scores only the live lanes' positions;
    every hop it samples must be the hop the full chunked fold (all
    ``ceil(max_degree/chunk)`` chunks per lane) picks — chunks past a
    lane's degree only ever hold -inf reservoir keys."""
    from repro.core import EngineConfig
    from repro.core import rng as task_rng
    from repro.core.walk_engine import _run_walks
    from repro.graph import make_dataset

    g = make_dataset("WG", scale_override=9, weighted=True)
    starts = rng.integers(0, g.num_vertices, 150).astype(np.int32)
    spec = SamplerSpec(kind="reservoir_n2v", p=2.0, q=0.5,
                       reservoir_chunk=16)
    cfg = EngineConfig(num_slots=32, max_hops=8)
    paths, lengths = _run_walks(g, starts, spec, cfg, seed=3).as_numpy()
    q, h = np.nonzero(np.arange(cfg.max_hops)[None, :] < lengths[:, None] - 1)
    v_prev = np.where(h > 0, paths[q, np.maximum(h - 1, 0)], -1)
    idx = _chunked_reservoir_reference(spec, g, paths[q, h], v_prev, q, h,
                                       task_rng.stream_key(3))
    rp = np.asarray(g.row_ptr)
    want = np.asarray(g.col)[rp[paths[q, h]] + idx]
    assert q.size > 500
    np.testing.assert_array_equal(paths[q, h + 1], want)


@pytest.mark.parametrize("num_lanes,chunk,active_frac", [
    (8, 4, 1.0),      # 32-item blocks: hub lanes span many blocks
    (16, 16, 0.5),    # half the lanes idle, holes in the packing
    (5, 3, 0.8),      # odd chunk: uniforms from both Threefry words
    (64, 64, 1.0),    # one block holds most lanes whole
])
def test_reservoir_scan_matches_chunked_fold(num_lanes, chunk, active_frac):
    """`reservoir_scan` on arbitrary lane states (hubs, degree-0 and
    idle lanes, lanes split across blocks) picks the chunked fold's
    offset on every live lane with a neighbor."""
    from repro.core import rng as task_rng
    from repro.core.phase_program import reservoir_scan

    r = np.random.default_rng(num_lanes * 100 + chunk)
    n = 40
    edges = [(0, v) for v in range(1, n)] + [(v, 0) for v in range(1, n)]
    edges += [(v, v % (n - 1) + 1) for v in range(1, n)]
    edges += [(v % (n - 1) + 1, v) for v in range(1, n)]
    edges += [(int(a), int(b)) for a, b in r.integers(1, n, (60, 2))
              if a != b]
    edges = np.unique(np.asarray(edges, np.int64), axis=0)
    g = build_csr(edges, n + 1,   # vertex n has no out-edges
                  weights=r.random(len(edges)).astype(np.float32) + 1e-3)
    spec = SamplerSpec(kind="reservoir_n2v", p=2.0, q=0.5,
                       reservoir_chunk=chunk)
    v_curr = r.integers(0, n + 1, num_lanes).astype(np.int32)
    v_curr[0] = 0                                    # a hub lane
    v_prev = r.integers(-1, n, num_lanes).astype(np.int32)
    active = r.random(num_lanes) < active_frac
    active[0] = True
    key = task_rng.stream_key(11)
    slots = WalkerSlots(
        v_curr=jnp.asarray(v_curr), v_prev=jnp.asarray(v_prev),
        query_id=jnp.arange(num_lanes, dtype=jnp.int32) * 7,
        hop=jnp.asarray(r.integers(0, 9, num_lanes).astype(np.int32)),
        active=jnp.asarray(active),
        epoch=jnp.zeros((num_lanes,), jnp.int32))
    addr = g.row_ptr[slots.v_curr]
    deg = g.row_ptr[slots.v_curr + 1] - addr
    idx, ok = reservoir_scan(spec, g, addr, deg, slots, key)
    want = _chunked_reservoir_reference(spec, g, v_curr, v_prev,
                                        slots.query_id, slots.hop, key)
    use = active & np.asarray(ok)
    assert use.sum() >= 2
    np.testing.assert_array_equal(np.asarray(idx)[use], want[use])


