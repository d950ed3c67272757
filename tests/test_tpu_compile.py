"""Ahead-of-time compiles for a described TPU v5e chip at deployment size.

Nothing here runs on a chip: each test lowers a kernel or a jitted step
for one device of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what the chip would refuse (DMA shapes
that do not fit the memory tiling, SMEM or VMEM over capacity, scalar ops
Mosaic cannot lower).  Interpret-mode tests cannot see any of that.

Sizes are the chip smoke's (`chip_smoke.py`): the web-Google stand-in at
2^20 vertices (``make_dataset("WG", scale_override=20)``), W = 4096 lanes,
a closed batch of Q = 131072 queries, 80-hop walks.  Only shapes are
needed, so no graph is generated at that size here.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import EngineConfig
from repro.core.samplers import SamplerSpec
from repro.core.walk_engine import (_stage_depth, init_stream_state,
                                    make_superstep_runner)
from repro.graph.csr import CSRGraph

NV = 1 << 20          # WG stand-in vertices at scale 20
NE = 6_162_283        # its edge count (seed 0)
MAX_DEGREE = 18_507   # its max out-degree (seed 0)
W = 4096              # configs.ridgewalker ENGINE.num_slots
Q = 131_072           # the smoke's closed batch
HOPS = 80             # configs.ridgewalker query length
CACHE_BUDGET = 1 << 18  # chip_smoke.CACHE_BUDGET

SPECS = {
    "uniform": SamplerSpec(kind="uniform"),
    "alias": SamplerSpec(kind="alias"),
    "rejection_n2v": SamplerSpec(kind="rejection_n2v", p=2.0, q=0.5),
    "reservoir_n2v": SamplerSpec(kind="reservoir_n2v", p=2.0, q=0.5),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache
    off: an entry compiled for a described chip cannot be read back
    without one, and would warn on the next compile."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _graph(sharding, weighted=False, alias=False):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return CSRGraph(
        row_ptr=s((NV + 1,), jnp.int32), col=s((NE,), jnp.int32),
        weights=s((NE,), jnp.float32) if weighted else None,
        alias_prob=s((NE,), jnp.float32) if alias else None,
        alias_idx=s((NE,), jnp.int32) if alias else None,
        num_vertices=NV, num_edges=NE, max_degree=MAX_DEGREE)


def _rows(graph, spec, sharding):
    from repro.kernels.common import graph_rows
    return _shapes(jax.eval_shape(
        lambda g: graph_rows(g, spec.reservoir_chunk), graph), sharding)


def _cfg(**kw):
    return EngineConfig(num_slots=W, max_hops=HOPS, **kw)


def _state(cfg, sharding):
    return _shapes(jax.eval_shape(lambda: init_stream_state(cfg, Q)),
                   sharding)


def _scalar(sharding, dtype=jnp.int32, shape=()):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kind", ["uniform", "node2vec_w"])
def test_jnp_superstep_runner_compiles(one_chip, kind):
    spec = (SPECS["uniform"] if kind == "uniform"
            else SPECS["reservoir_n2v"])
    cfg = _cfg()
    run = make_superstep_runner(spec, cfg)
    graph = _graph(one_chip, weighted=kind == "node2vec_w")
    run.lower(graph, _state(cfg, one_chip), _scalar(one_chip),
              _scalar(one_chip)).compile()


def _full_cache(payloads):
    """A hot-vertex cache block that fills the smoke's budget; the kernel
    only needs its shapes, so it is packed from a smaller graph whose
    hubs already exceed the budget."""
    from repro.graph import build_hot_cache, make_dataset
    g = make_dataset("WG", scale_override=15)
    cache = build_hot_cache(g, payloads, CACHE_BUDGET)
    assert cache.nbytes() > CACHE_BUDGET * 0.9
    return cache


@pytest.mark.parametrize("kind,cached", [
    ("uniform", False), ("alias", False), ("rejection_n2v", False),
    ("reservoir_n2v", False), ("uniform", True)])
def test_fused_launch_compiles(one_chip, kind, cached):
    from repro.core.phase_program import lower
    from repro.kernels.fused_superstep import build_fused_launch
    spec = SPECS[kind]
    cfg = _cfg(step_impl="fused", cache_budget=CACHE_BUDGET if cached else 0)
    cache = _full_cache(lower(spec).cache_payloads) if cached else None
    launch = build_fused_launch(spec, cfg, _stage_depth(cfg),
                                interpret=False, cache=cache)
    graph = _graph(one_chip, weighted=kind == "reservoir_n2v",
                   alias=kind == "alias")
    compiled = launch.lower(graph, _rows(graph, spec, one_chip),
                            _state(cfg, one_chip),
                            _scalar(one_chip, jnp.uint32, (2,)),
                            _scalar(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["uniform", "alias"])
def test_walk_step_compiles(one_chip, kind):
    from repro.kernels.walk_step import ops
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    v, u = s((W,), jnp.int32), s((W,), jnp.float32)
    rows = _rows(_graph(one_chip, alias=kind == "alias"), SPECS[kind],
                 one_chip)
    if kind == "uniform":
        fn = lambda *a: ops.walk_step_uniform(*a, interpret=False)
        args = (v, u, rows)
    else:
        fn = lambda *a: ops.walk_step_alias(*a, interpret=False)
        args = (v, u, u, rows)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_embedding_bag_compiles(one_chip):
    from repro.kernels.embedding_bag import ops
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    B, Hots, R, D = 1024, 8, 1 << 20, 128
    fn = lambda i, t, w: ops.embedding_bag(i, t, w, interpret=False)
    compiled = jax.jit(fn).lower(s((B, Hots), jnp.int32),
                                 s((R, D), jnp.float32),
                                 s((B, Hots), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cache_budget_over_smem_is_refused():
    """A cache budget that cannot sit in SMEM beside the lane pool is an
    error, never a silently smaller cache."""
    from repro.kernels.fused_superstep import build_fused_launch
    from repro.kernels.fused_superstep.ops import SMEM_BYTES
    cfg = _cfg(step_impl="fused", cache_budget=SMEM_BYTES)
    with pytest.raises(ValueError, match="does not fit in SMEM"):
        build_fused_launch(SPECS["uniform"], cfg, _stage_depth(cfg),
                           interpret=False)
    ok = dataclasses.replace(cfg, cache_budget=CACHE_BUDGET)
    build_fused_launch(SPECS["uniform"], ok, _stage_depth(ok),
                       interpret=False)
