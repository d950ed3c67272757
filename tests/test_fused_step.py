"""Fused device-resident superstep (`step_impl="fused"`) + shared Threefry
RNG: bit-equality of the rng refactor against the jax.random derivation,
and bit-identity of the fused kernel against the jnp superstep over
{uniform, ppr, alias, rejection_n2v, metapath, reservoir_n2v} ×
{zero_bubble, static} × {closed batch, chunked stream} — every phase
program lowers to the kernel (the chunked E-S reservoir runs the in-kernel
chunk loop; there is no jnp fallback path).
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, rng as task_rng
from repro.core.samplers import SamplerSpec
from repro.core.walk_engine import (_run_walks, init_stream_state,
                                    inject_queries, make_superstep_runner)

CFG = EngineConfig(num_slots=32, max_hops=10)
SPECS = {
    "uniform": SamplerSpec(kind="uniform"),
    "ppr": SamplerSpec(kind="uniform", stop_prob=0.15),
    "alias": SamplerSpec(kind="alias"),
    "rejection_n2v": SamplerSpec(kind="rejection_n2v", p=2.0, q=0.5,
                                 rejection_rounds=6),
    "metapath": SamplerSpec(kind="metapath", metapath=(0, 1, 2)),
    # Small chunk so the in-kernel loop runs multiple (and partial) chunks
    # on the scale-9 fixture's degree range.
    "reservoir_n2v": SamplerSpec(kind="reservoir_n2v", p=2.0, q=0.5,
                                 reservoir_chunk=8),
}


@pytest.fixture(scope="module")
def fused_graph():
    """One graph with every payload the fused matrix samples from
    (weights + alias tables + edge types)."""
    from repro.graph import make_dataset
    return make_dataset("WG", scale_override=9, weighted=True,
                        with_alias=True, num_edge_types=3)


def _fused(cfg, hops_per_launch=4, **kw):
    return dataclasses.replace(cfg, step_impl="fused",
                               hops_per_launch=hops_per_launch, **kw)


def _assert_same_run(r1, r2):
    p1, l1 = r1.as_numpy()
    p2, l2 = r2.as_numpy()
    assert np.array_equal(p1, p2)
    assert np.array_equal(l1, l2)
    # launches is the one knob that differs by design (fusion factor).
    for f in r1.stats._fields:
        if f == "launches":
            continue
        assert int(getattr(r1.stats, f)) == int(getattr(r2.stats, f)), f


# ------------------------------------------------------------ shared RNG


def _jaxrandom_task_uniforms(base_key, qid, hop, num, salt=0, epoch=None):
    """The historical jax.random-based derivation, kept here verbatim as
    the reference the refactored `rng` module must match bit-for-bit."""
    salt_b = jnp.broadcast_to(jnp.asarray(salt, jnp.uint32),
                              qid.shape).astype(jnp.uint32)
    if epoch is None:
        def one(q, h, s):
            k = jax.random.fold_in(base_key, q)
            k = jax.random.fold_in(k, h)
            return jax.random.fold_in(k, s)

        keys = jax.vmap(one)(qid.astype(jnp.uint32), hop.astype(jnp.uint32),
                             salt_b)
    else:
        ep = jnp.broadcast_to(jnp.asarray(epoch, jnp.int32), qid.shape)

        def one(q, h, s, e):
            salted = jax.random.fold_in(base_key, e.astype(jnp.uint32))
            kb = jnp.where(e > 0, salted, base_key)
            k = jax.random.fold_in(kb, q)
            k = jax.random.fold_in(k, h)
            return jax.random.fold_in(k, s)

        keys = jax.vmap(one)(qid.astype(jnp.uint32), hop.astype(jnp.uint32),
                             salt_b, ep)
    return jax.vmap(lambda k: jax.random.uniform(k, (num,)))(keys)


@pytest.mark.parametrize("epoch_kind", ["none", "zero", "mixed"])
@pytest.mark.parametrize("salt", [0, 2, 8, 17])
def test_rng_bit_equal_to_jax_random(epoch_kind, salt, rng):
    """`rng.threefry2x32`-based task_uniforms == the jax.random fold chain,
    across epochs, salts, and odd/even draw counts.  `core.rng` implements
    the non-partitionable Threefry layout (two draws per cipher block), so
    the reference runs under ``jax.threefry_partitionable(False)``; JAX's
    default partitionable layout draws each word from its own block."""
    key = jax.random.PRNGKey(123)
    qid = jnp.asarray(rng.integers(0, 5000, 64), jnp.int32)
    hop = jnp.asarray(rng.integers(0, 80, 64), jnp.int32)
    epoch = {"none": None, "zero": 0,
             "mixed": jnp.asarray(rng.integers(0, 9, 64), jnp.int32)}[
        epoch_kind]
    for num in (1, 2, 5, 24):
        with jax.threefry_partitionable(False):
            ref = _jaxrandom_task_uniforms(key, qid, hop, num, salt, epoch)
        got = task_rng.task_uniforms(key, qid, hop, num, salt, epoch)
        assert np.array_equal(np.asarray(ref), np.asarray(got)), num


def test_threefry_primitive_bit_equal():
    """The shared block cipher itself matches jax.random.bits under the
    non-partitionable Threefry layout (``fold_in`` is layout-free)."""
    key = jax.random.PRNGKey(7)
    folded = jax.random.fold_in(key, 42)
    y0, y1 = task_rng.threefry2x32(key[0], key[1], jnp.uint32(0),
                                   jnp.uint32(42))
    assert np.array_equal(np.asarray(folded), np.asarray([y0, y1]))
    for num in (1, 2, 3, 8, 9):
        with jax.threefry_partitionable(False):
            ref = jax.random.bits(folded, (num,), jnp.uint32)
        got = task_rng.key_bits(folded[0], folded[1], num)
        assert np.array_equal(np.asarray(ref), np.asarray(got).reshape(-1))


def test_epoch_zero_matches_legacy_tuple(rng):
    """Epoch 0 must keep deriving exactly like the 3-tuple (the contract
    that makes a closed batch epoch 0 of a stream)."""
    key = jax.random.PRNGKey(5)
    qid = jnp.asarray(rng.integers(0, 999, 32), jnp.int32)
    hop = jnp.asarray(rng.integers(0, 30, 32), jnp.int32)
    a = task_rng.task_uniforms(key, qid, hop, 3, 1, epoch=None)
    b = task_rng.task_uniforms(key, qid, hop, 3, 1,
                               epoch=jnp.zeros((32,), jnp.int32))
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- fused vs jnp, closed


@pytest.mark.parametrize("algo", sorted(SPECS))
@pytest.mark.parametrize("mode", ["zero_bubble", "static"])
def test_fused_closed_batch_bit_identical(algo, mode, fused_graph, rng):
    """Closed batch: fused kernel == jnp superstep — paths, lengths, and
    every stat except the launch count — for every covered sampler
    (rejection Node2Vec's in-kernel verify and MetaPath's typed gather
    included)."""
    spec = SPECS[algo]
    cfg = dataclasses.replace(CFG, mode=mode)
    starts = rng.integers(0, fused_graph.num_vertices, 80).astype(np.int32)
    r_jnp = _run_walks(fused_graph, starts, spec, cfg, seed=9)
    r_fused = _run_walks(fused_graph, starts, spec, _fused(cfg), seed=9)
    _assert_same_run(r_jnp, r_fused)
    assert int(r_fused.stats.launches) < int(r_fused.stats.supersteps)
    assert int(r_jnp.stats.launches) == int(r_jnp.stats.supersteps)


def test_fused_hops_per_launch_invariance(small_graph, rng):
    """The launch cadence is a pure machine knob: any hops_per_launch
    samples identical paths, and the launch count shrinks as k grows."""
    starts = rng.integers(0, small_graph.num_vertices, 60).astype(np.int32)
    spec = SPECS["ppr"]
    ref = _run_walks(small_graph, starts, spec, CFG, seed=4)
    launches = []
    for k in (1, 3, 16):
        r = _run_walks(small_graph, starts, spec, _fused(CFG, k), seed=4)
        _assert_same_run(ref, r)
        launches.append(int(r.stats.launches))
    assert launches[0] > launches[1] > launches[2] >= 1
    # supersteps-per-launch is surfaced in the stats
    assert float(ref.stats.supersteps_per_launch()) == pytest.approx(1.0)
    assert float(r.stats.supersteps_per_launch()) > 1.0


def test_fused_injection_delay_and_depth(small_graph, rng):
    """The Theorem VI.1 staging controller runs in-kernel: delayed head
    observations behave identically to the jnp superstep."""
    starts = rng.integers(0, small_graph.num_vertices, 100).astype(np.int32)
    for C in (1, 3):
        cfg = dataclasses.replace(CFG, injection_delay=C)
        r1 = _run_walks(small_graph, starts, SPECS["uniform"], cfg, seed=2)
        r2 = _run_walks(small_graph, starts, SPECS["uniform"], _fused(cfg),
                        seed=2)
        _assert_same_run(r1, r2)


def test_fused_no_record_paths(small_graph, rng):
    """record_paths=False (throughput mode): stats still match."""
    starts = rng.integers(0, small_graph.num_vertices, 64).astype(np.int32)
    cfg = dataclasses.replace(CFG, record_paths=False)
    r1 = _run_walks(small_graph, starts, SPECS["ppr"], cfg, seed=6)
    r2 = _run_walks(small_graph, starts, SPECS["ppr"], _fused(cfg), seed=6)
    for f in r1.stats._fields:
        if f != "launches":
            assert int(getattr(r1.stats, f)) == int(getattr(r2.stats, f)), f


def test_fused_reservoir_in_kernel_no_fallback(weighted_graph, rng):
    """Weighted Node2Vec (the chunked E-S reservoir) runs in-kernel: the
    full Walker path emits no fallback warning and matches the jnp
    superstep bit-for-bit — the matrix row the kernel closed last."""
    from repro import walker

    program = walker.WalkProgram.node2vec(2.0, 0.5, 6, weighted=True)
    assert program.spec.kind == "reservoir_n2v"
    starts = rng.integers(0, weighted_graph.num_vertices, 24).astype(np.int32)
    ref = walker.compile(program, execution=walker.ExecutionConfig(
        num_slots=16)).run(weighted_graph, starts, seed=0)
    ex = walker.ExecutionConfig(num_slots=16, step_impl="fused",
                                hops_per_launch=4)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = walker.compile(program, execution=ex).run(weighted_graph,
                                                        starts, seed=0)
    assert not [c for c in caught if issubclass(c.category, RuntimeWarning)
                and "falling back" in str(c.message)]
    _assert_same_run(ref, got)


def test_fused_reservoir_adaptive_vs_fixed_chunks(weighted_graph, rng):
    """Both engines scan only each lane's own degree — the jnp scan packs
    the live lanes' positions, the fused kernel runs ``ceil(deg/chunk)``
    trips per lane — and sample identical paths for any chunk width
    (chunks past a lane's degree contribute only -inf reservoir keys)."""
    starts = rng.integers(0, weighted_graph.num_vertices, 40).astype(np.int32)
    for chunk in (8, 3):
        spec = SamplerSpec(kind="reservoir_n2v", p=2.0, q=0.5,
                           reservoir_chunk=chunk)
        ref = _run_walks(weighted_graph, starts, spec, CFG, seed=3)
        _assert_same_run(ref, _run_walks(weighted_graph, starts, spec,
                                         _fused(CFG), seed=3))


def test_fused_reservoir_partial_final_chunks(rng):
    """Skewed degrees exercise the chunk loop's ragged tail: a hub whose
    degree is not a multiple of reservoir_chunk (partial final chunk,
    clamped fixed-length DMA) next to degree-2 ring vertices (single
    partial chunk), with p/q biases live via the ring back-edges."""
    from repro.graph import build_csr

    n = 48
    edges = []
    for v in range(1, n):          # star: hub 0 <-> every spoke
        edges += [(0, v), (v, 0)]
    for v in range(1, n):          # ring over the spokes
        w = v % (n - 1) + 1
        edges += [(v, w), (w, v)]
    g = build_csr(np.asarray(edges, np.int64), n,
                  weights=rng.random(len(edges)).astype(np.float32) + 1e-3)
    spec = SamplerSpec(kind="reservoir_n2v", p=4.0, q=0.25,
                       reservoir_chunk=16)
    deg0 = int(g.row_ptr[1] - g.row_ptr[0])
    assert deg0 % spec.reservoir_chunk != 0 and deg0 > spec.reservoir_chunk
    starts = rng.integers(0, n, 40).astype(np.int32)
    cfg = dataclasses.replace(CFG, num_slots=16, max_hops=6)
    ref = _run_walks(g, starts, spec, cfg, seed=12)
    got = _run_walks(g, starts, spec, _fused(cfg), seed=12)
    _assert_same_run(ref, got)


# ------------------------------------------------- fused vs jnp, stream


def _stream_drain(runner, graph, state, seed, chunk):
    for _ in range(10_000):
        if bool(np.asarray(state.done).all()):
            return state
        state = runner(graph, state, seed, chunk)
    raise AssertionError("stream did not drain")


@pytest.mark.parametrize("algo", sorted(SPECS))
def test_fused_chunked_stream_bit_identical(algo, fused_graph, rng):
    """Open system: mid-stream injection + odd chunk sizes, fused vs jnp —
    identical paths/lengths/done and identical stream stats, for every
    covered sampler."""
    spec = SPECS[algo]
    starts = rng.integers(0, fused_graph.num_vertices, 90).astype(np.int32)
    cfg = dataclasses.replace(CFG, num_slots=16)

    def run(c):
        runner = make_superstep_runner(spec, c)
        st = init_stream_state(c, capacity=90)
        st = inject_queries(st, jnp.arange(50, dtype=jnp.int32),
                            jnp.asarray(starts[:50]),
                            jnp.zeros((50,), jnp.int32), 50)
        st = runner(fused_graph, st, 8, 5)   # mid-flight...
        st = inject_queries(st, jnp.arange(50, 90, dtype=jnp.int32),
                            jnp.asarray(starts[50:]),
                            jnp.zeros((40,), jnp.int32), 40)
        return _stream_drain(runner, fused_graph, st, 8, 7)

    s1 = run(cfg)
    s2 = run(_fused(cfg, hops_per_launch=3))
    assert np.array_equal(np.asarray(s1.paths), np.asarray(s2.paths))
    assert np.array_equal(np.asarray(s1.lengths), np.asarray(s2.lengths))
    assert np.array_equal(np.asarray(s1.done), np.asarray(s2.done))
    for f in s1.stats._fields:
        if f != "launches":
            assert int(getattr(s1.stats, f)) == int(getattr(s2.stats, f)), f


def test_fused_ring_reclamation_stream(small_graph, rng):
    """The ring economy (epoch-salted slot reuse) runs unchanged over the
    fused runner: Walker.stream with step_impl='fused' harvests the same
    walks as the jnp stream under identical inject/release schedules."""
    from repro import walker

    program = walker.WalkProgram(spec=SPECS["ppr"], max_hops=8)
    arrivals = rng.integers(0, small_graph.num_vertices, 60).astype(np.int32)

    def soak(execution):
        w = walker.compile(program, execution=execution)
        stream = w.stream(small_graph, capacity=24, seed=11)
        pending = arrivals.tolist()
        out = {}
        live = {}
        while pending or live:
            n = min(8, stream.num_free, len(pending))
            if n:
                wave = np.asarray(pending[:n], np.int32)
                del pending[:n]
                qids, epochs = stream.inject(wave)
                for q, e in zip(qids, epochs):
                    live[int(q)] = int(e)
            stream.advance(5)
            done = stream.done_live_mask()
            ready = [q for q in live if done[q]]
            if ready:
                paths, lengths = stream.harvest_ids(ready)
                for i, q in enumerate(ready):
                    out[(live.pop(q), q)] = (paths[i].copy(), int(lengths[i]))
                stream.release(ready)
        return out

    ex = walker.ExecutionConfig(num_slots=8)
    a = soak(ex)
    b = soak(dataclasses.replace(ex, step_impl="fused", hops_per_launch=4))
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(a[k][0], b[k][0]), k
        assert a[k][1] == b[k][1], k


# ------------------------------------------- gather hierarchy (hot cache)

# Stats that legitimately differ cache-on vs cache-off: the launch
# cadence (as everywhere) and the cache's own counters.  Everything the
# engine counted before the hierarchy existed must stay bit-identical.
_CACHE_ONLY = ("launches", "cache_hits", "cache_misses", "cache_coalesced")
_CACHE_BUDGET = 1 << 13


def _assert_same_walks_mod_cache(r_off, r_on):
    p1, l1 = r_off.as_numpy()
    p2, l2 = r_on.as_numpy()
    assert np.array_equal(p1, p2)
    assert np.array_equal(l1, l2)
    for f in r_off.stats._fields:
        if f in _CACHE_ONLY:
            continue
        assert int(getattr(r_off.stats, f)) == int(getattr(r_on.stats, f)), f


@pytest.mark.parametrize("algo", sorted(SPECS))
@pytest.mark.parametrize("mode", ["zero_bubble", "static"])
def test_cached_fused_closed_bit_identical(algo, mode, fused_graph, rng):
    """Closed batch: the SMEM hot-vertex cache is invisible in every
    sampled walk and every pre-existing stat — hits read the same bytes
    from a different tier — while the new counters show it actually
    served traffic (nonzero hits on the skewed fixture, all-zero when
    the cache is off)."""
    spec = SPECS[algo]
    cfg = dataclasses.replace(CFG, mode=mode)
    starts = rng.integers(0, fused_graph.num_vertices, 80).astype(np.int32)
    r_off = _run_walks(fused_graph, starts, spec, _fused(cfg), seed=9)
    r_on = _run_walks(fused_graph, starts, spec,
                      _fused(cfg, cache_budget=_CACHE_BUDGET), seed=9)
    _assert_same_walks_mod_cache(r_off, r_on)
    assert int(r_on.stats.cache_hits) > 0
    assert 0.0 < float(r_on.stats.cache_hit_rate()) <= 1.0
    for f in ("cache_hits", "cache_misses", "cache_coalesced"):
        assert int(getattr(r_off.stats, f)) == 0, f


@pytest.mark.parametrize("algo", sorted(SPECS))
def test_cached_fused_stream_bit_identical(algo, fused_graph, rng):
    """Open system: mid-stream injection over the cached runner drains to
    the same paths/lengths/done as the uncached one."""
    from repro.core.walk_engine import maybe_build_cache

    spec = SPECS[algo]
    starts = rng.integers(0, fused_graph.num_vertices, 90).astype(np.int32)
    cfg = _fused(dataclasses.replace(CFG, num_slots=16), hops_per_launch=3)

    def run(budget):
        c = dataclasses.replace(cfg, cache_budget=budget)
        runner = make_superstep_runner(
            spec, c, cache=maybe_build_cache(spec, c, fused_graph))
        st = init_stream_state(c, capacity=90)
        st = inject_queries(st, jnp.arange(50, dtype=jnp.int32),
                            jnp.asarray(starts[:50]),
                            jnp.zeros((50,), jnp.int32), 50)
        st = runner(fused_graph, st, 8, 5)   # mid-flight...
        st = inject_queries(st, jnp.arange(50, 90, dtype=jnp.int32),
                            jnp.asarray(starts[50:]),
                            jnp.zeros((40,), jnp.int32), 40)
        return _stream_drain(runner, fused_graph, st, 8, 7)

    s_off = run(0)
    s_on = run(_CACHE_BUDGET)
    assert np.array_equal(np.asarray(s_off.paths), np.asarray(s_on.paths))
    assert np.array_equal(np.asarray(s_off.lengths),
                          np.asarray(s_on.lengths))
    assert np.array_equal(np.asarray(s_off.done), np.asarray(s_on.done))
    for f in s_off.stats._fields:
        if f not in _CACHE_ONLY:
            assert int(getattr(s_off.stats, f)) == int(
                getattr(s_on.stats, f)), f
    assert int(s_on.stats.cache_hits) > 0


def test_cached_fused_static_stream_spot_check(fused_graph, rng):
    """The stream × static-mode corner of the matrix (one kind)."""
    spec = SPECS["uniform"]
    from repro.core.walk_engine import maybe_build_cache

    cfg = _fused(dataclasses.replace(CFG, num_slots=16, mode="static"))
    starts = rng.integers(0, fused_graph.num_vertices, 48).astype(np.int32)

    def run(budget):
        c = dataclasses.replace(cfg, cache_budget=budget)
        runner = make_superstep_runner(
            spec, c, cache=maybe_build_cache(spec, c, fused_graph))
        st = init_stream_state(c, capacity=48)
        st = inject_queries(st, jnp.arange(48, dtype=jnp.int32),
                            jnp.asarray(starts), jnp.zeros((48,), jnp.int32),
                            48)
        return _stream_drain(runner, fused_graph, st, 8, 5)

    s_off, s_on = run(0), run(_CACHE_BUDGET)
    assert np.array_equal(np.asarray(s_off.paths), np.asarray(s_on.paths))
    assert int(s_on.stats.cache_hits) > 0


def test_cache_budget_knob_threads_through_walker(fused_graph, rng):
    """The public Walker path builds and memoizes the cache: same walks
    as cache-off, nonzero hit rate in the returned stats."""
    from repro import walker

    program = walker.WalkProgram(spec=SPECS["uniform"], max_hops=10)
    starts = rng.integers(0, fused_graph.num_vertices, 64).astype(np.int32)
    ref = walker.compile(program, execution=walker.ExecutionConfig(
        num_slots=32, step_impl="fused", hops_per_launch=4)).run(
            fused_graph, starts, seed=5)
    w = walker.compile(program, execution=walker.ExecutionConfig(
        num_slots=32, step_impl="fused", hops_per_launch=4,
        cache_budget=_CACHE_BUDGET))
    got = w.run(fused_graph, starts, seed=5)
    _assert_same_walks_mod_cache(ref, got)
    assert float(got.stats.cache_hit_rate()) > 0.0
    # Same graph object: the engine (and its cache) is memoized.
    assert len(w._engines) == 1
    w.run(fused_graph, starts, seed=5)
    assert len(w._engines) == 1
