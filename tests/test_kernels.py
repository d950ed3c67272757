"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests
against the pure-jnp oracles (interpret=True executes kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stubs
from repro.graph import make_dataset
from repro.kernels.common import graph_rows
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.segment_sum import segment_sum
from repro.kernels.segment_sum.ref import segment_sum_ref
from repro.kernels.walk_step import ops as ws_ops, ref as ws_ref

given, settings, st = hypothesis_or_stubs()


@pytest.fixture(scope="module")
def graph():
    return make_dataset("WG", scale_override=9, weighted=True,
                        with_alias=True)


@pytest.mark.parametrize("W,tile", [(5, 64), (64, 16), (200, 64), (256, 256)])
def test_walk_step_uniform_sweep(graph, W, tile, rng):
    v = jnp.asarray(rng.integers(0, graph.num_vertices, W), jnp.int32)
    u = jnp.asarray(rng.random(W), jnp.float32)
    vn, dg = ws_ops.walk_step_uniform(v, u, graph_rows(graph), tile=tile)
    vr, dr = ws_ref.walk_step_uniform_ref(v, u, graph.row_ptr, graph.col)
    np.testing.assert_array_equal(np.asarray(vn), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(dg), np.asarray(dr))


@pytest.mark.parametrize("W,tile", [(7, 32), (128, 64)])
def test_walk_step_alias_sweep(graph, W, tile, rng):
    v = jnp.asarray(rng.integers(0, graph.num_vertices, W), jnp.int32)
    u1 = jnp.asarray(rng.random(W), jnp.float32)
    u2 = jnp.asarray(rng.random(W), jnp.float32)
    vn, dg = ws_ops.walk_step_alias(v, u1, u2, graph_rows(graph), tile=tile)
    vr, dr = ws_ref.walk_step_alias_ref(v, u1, u2, graph.row_ptr, graph.col,
                                        graph.alias_prob, graph.alias_idx)
    np.testing.assert_array_equal(np.asarray(vn), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(dg), np.asarray(dr))


def test_walk_step_dangling_vertices(graph):
    """deg==0 lanes must report v_next = -1 (termination sentinel)."""
    deg = np.diff(np.asarray(graph.row_ptr))
    dang = np.where(deg == 0)[0]
    assert dang.size > 0
    v = jnp.asarray(dang[:32], jnp.int32)
    u = jnp.zeros((v.shape[0],), jnp.float32)
    vn, dg = ws_ops.walk_step_uniform(v, u, graph_rows(graph), tile=32)
    assert (np.asarray(vn) == -1).all()
    assert (np.asarray(dg) == 0).all()


@pytest.mark.parametrize("E,V,D,te,rb,dtype", [
    (64, 16, 8, 16, 8, jnp.float32),
    (1000, 177, 16, 128, 64, jnp.float32),
    (333, 64, 4, 32, 16, jnp.float32),
    (256, 32, 8, 64, 32, jnp.bfloat16),
])
def test_segment_sum_sweep(E, V, D, te, rb, dtype, rng):
    seg = np.sort(rng.integers(0, V, E)).astype(np.int32)
    dat = jnp.asarray(rng.random((E, D)), dtype)
    out = segment_sum(dat, seg, V, tile_e=te, row_block=rb)
    ref = segment_sum_ref(dat, jnp.asarray(seg), V)
    atol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_segment_sum_empty_segments(rng):
    """Rows with no incident edges must be exactly zero."""
    seg = np.sort(rng.choice(np.arange(0, 50, 5), 40)).astype(np.int32)
    dat = jnp.asarray(rng.random((40, 4)), jnp.float32)
    out = np.asarray(segment_sum(dat, seg, 50, tile_e=16, row_block=8))
    empty = np.setdiff1d(np.arange(50), seg)
    assert (out[empty] == 0).all()


@settings(max_examples=25, deadline=None)
@given(E=st.integers(1, 300), V=st.integers(1, 80), D=st.integers(1, 9),
       seed=st.integers(0, 2**31 - 1))
def test_segment_sum_property(E, V, D, seed):
    r = np.random.default_rng(seed)
    seg = np.sort(r.integers(0, V, E)).astype(np.int32)
    dat = jnp.asarray(r.standard_normal((E, D)), jnp.float32)
    out = segment_sum(dat, seg, V, tile_e=32, row_block=16)
    ref = segment_sum_ref(dat, jnp.asarray(seg), V)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.parametrize("B,H,R,D,tb", [
    (8, 3, 40, 8, 8), (100, 1, 500, 16, 32), (33, 6, 64, 4, 16),
])
def test_embedding_bag_sweep(B, H, R, D, tb, rng):
    idx = jnp.asarray(rng.integers(-1, R, (B, H)), jnp.int32)
    w = jnp.asarray(rng.random((B, H)), jnp.float32)
    tbl = jnp.asarray(rng.random((R, D)), jnp.float32)
    out = embedding_bag(idx, tbl, w, tile_b=tb)
    ref = embedding_bag_ref(idx, w, tbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(B=st.integers(1, 60), H=st.integers(1, 8), R=st.integers(2, 100),
       D=st.integers(1, 16), seed=st.integers(0, 2**31 - 1))
def test_embedding_bag_property(B, H, R, D, seed):
    r = np.random.default_rng(seed)
    idx = jnp.asarray(r.integers(-1, R, (B, H)), jnp.int32)
    w = jnp.asarray(r.random((B, H)), jnp.float32)
    tbl = jnp.asarray(r.standard_normal((R, D)), jnp.float32)
    out = embedding_bag(idx, tbl, w, tile_b=16)
    ref = embedding_bag_ref(idx, w, tbl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_embedding_bag_all_padding():
    idx = jnp.full((4, 3), -1, jnp.int32)
    tbl = jnp.ones((10, 8), jnp.float32)
    out = embedding_bag(idx, tbl, tile_b=4)
    assert (np.asarray(out) == 0).all()


def _primitives(jaxpr, in_loop=False, seen=None):
    """Primitive names of ``jaxpr``, split by whether they sit inside a
    ``while`` body (the engine's superstep loop) or outside it."""
    seen = seen if seen is not None else {"outside": set(), "loop": set()}
    for eqn in jaxpr.eqns:
        seen["loop" if in_loop else "outside"].add(eqn.primitive.name)
        inner = in_loop or eqn.primitive.name == "while"
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, inner, seen)
    return seen


@pytest.mark.parametrize("impl,runner", [
    ("pallas", "closed"), ("fused", "closed"),
    ("pallas", "stream"), ("fused", "stream")])
def test_kernel_rows_laid_out_once_per_run(graph, impl, runner):
    """The graph's row layout (`kernels.common.graph_rows`) is built
    before the superstep loop, never re-padded per hop or per launch."""
    import jax

    from repro.core import EngineConfig
    from repro.core.samplers import SamplerSpec
    from repro.core.walk_engine import (build_engine, init_stream_state,
                                        make_superstep_runner)
    spec = SamplerSpec(kind="alias")
    cfg = EngineConfig(num_slots=32, max_hops=4, step_impl=impl)
    if runner == "closed":
        run = build_engine(spec, cfg)
        jaxpr = jax.make_jaxpr(lambda g, s: run(g, s, 0, num_queries=64))(
            graph, jnp.zeros((64,), jnp.int32))
    else:
        run = make_superstep_runner(spec, cfg)
        jaxpr = jax.make_jaxpr(run)(graph, init_stream_state(cfg, 64), 0, 8)
    seen = _primitives(jaxpr.jaxpr)
    assert "pad" in seen["outside"]
    assert "pad" not in seen["loop"]
