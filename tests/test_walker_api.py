"""Unified walker API (`repro.walker`): program/config validation, the
algorithm × backend parity matrix (batch / streaming / sharded all
bit-identical to the seed `run_walks` reference), the public-API
snapshot, and the deprecation shims."""
import dataclasses
import warnings

import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import walker
from repro.core import EngineConfig
from repro.core.tasks import make_queue
from repro.core.walk_engine import _run_walks

H = 10  # hop budget for the parity matrix


def _programs():
    return {
        "urw": walker.WalkProgram.urw(H),
        "ppr": walker.WalkProgram.ppr(0.15, H),
        "deepwalk": walker.WalkProgram.deepwalk(H),
        "node2vec": walker.WalkProgram.node2vec(2.0, 0.5, H),
        "node2vec_w": walker.WalkProgram.node2vec(2.0, 0.5, H, weighted=True),
        "metapath": walker.WalkProgram.metapath([0, 1, 2], H),
    }


@pytest.fixture(scope="module")
def rich_graph():
    """One graph carrying every payload (weights, alias tables, edge
    types) so a single fixture serves the whole algorithm matrix."""
    from repro.graph import make_dataset
    return make_dataset("WG", scale_override=9, weighted=True,
                        with_alias=True, num_edge_types=3)


def _reference(g, program, starts, seed):
    cfg = EngineConfig(num_slots=64, max_hops=program.max_hops)
    return _run_walks(g, starts, program.spec, cfg, seed=seed)


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("algo", sorted(_programs()))
def test_batch_parity(algo, rich_graph, rng):
    """compile(program).run == the seed run_walks reference, bit-identical."""
    program = _programs()[algo]
    starts = rng.integers(0, rich_graph.num_vertices, 150).astype(np.int32)
    rp, rl = _reference(rich_graph, program, starts, seed=4).as_numpy()
    res = walker.compile(
        program, execution=walker.ExecutionConfig(num_slots=64)).run(
            rich_graph, starts, seed=4)
    bp, bl = res.as_numpy()
    assert np.array_equal(rp, bp) and np.array_equal(rl, bl)
    assert int(res.stats.terminations) == len(starts)


@pytest.mark.parametrize("algo", sorted(_programs()))
def test_stream_parity(algo, rich_graph, rng):
    """Walker.stream (open system, chunked) == the closed batch."""
    program = _programs()[algo]
    starts = rng.integers(0, rich_graph.num_vertices, 150).astype(np.int32)
    rp, rl = _reference(rich_graph, program, starts, seed=4).as_numpy()
    stream = walker.compile(
        program, execution=walker.ExecutionConfig(num_slots=64)).stream(
            rich_graph, capacity=150, seed=4)
    stream.inject(starts[:70])
    stream.advance(3)                  # arrivals land mid-flight
    stream.inject(starts[70:])
    stream.drain(chunk=7)
    sp, sl = stream.harvest()
    assert np.array_equal(rp, sp) and np.array_equal(rl, sl)


SHARDED_PARITY = r"""
import numpy as np
from repro import walker
from repro.graph import make_dataset, partition_graph
from repro.core import EngineConfig
from repro.core.walk_engine import _run_walks

H = 10
cases = [
    ("urw", walker.WalkProgram.urw(H), {}),
    ("ppr", walker.WalkProgram.ppr(0.15, H), {}),
    ("deepwalk", walker.WalkProgram.deepwalk(H),
     dict(weighted=True, with_alias=True)),
    ("node2vec", walker.WalkProgram.node2vec(2.0, 0.5, H), {}),
    ("node2vec_w", walker.WalkProgram.node2vec(2.0, 0.5, H, weighted=True),
     dict(weighted=True)),
    ("metapath", walker.WalkProgram.metapath([0, 1, 2], H),
     dict(num_edge_types=3)),
]
for name, program, kwargs in cases:
    g = make_dataset("WG", scale_override=9, **kwargs)
    pg = partition_graph(g, 2)
    starts = np.random.default_rng(0).integers(
        0, g.num_vertices, 160).astype(np.int32)
    ref = _run_walks(g, starts, program.spec,
                     EngineConfig(num_slots=64, max_hops=H), seed=4)
    rp, rl = ref.as_numpy()
    sharded = walker.compile(
        program, backend="sharded",
        execution=walker.ExecutionConfig(slots_per_device=16,
                                         log_capacity=1 << 14))
    res = sharded.run(pg, starts, seed=4)
    dp, dl = res.as_numpy()
    assert (dp == rp).all() and (dl == rl).all(), name
    assert int(np.asarray(res.stats.drops)) == 0, name

    # sharded *streaming* (ring substrate): same walks, mid-flight inject
    stream = sharded.stream(pg, capacity=160, seed=4)
    stream.inject(starts[:70])
    stream.advance(3)
    stream.inject(starts[70:])
    stream.drain(chunk=7)
    sp, sl = stream.harvest()
    assert (sp == rp).all() and (sl == rl).all(), name
    assert int(stream.walk_stats().drops) == 0, name
print("SHARDED_PARITY_OK")
"""


@pytest.mark.slow
def test_sharded_parity_two_devices():
    """Every algorithm — metapath included, now that type_offsets shard
    with the CSR — on the 2-device sharded backend == single-device
    reference, through compile(program, backend='sharded'): closed batch
    AND open stream over the same ring substrate."""
    out = run_in_subprocess(SHARDED_PARITY, devices=2)
    assert "SHARDED_PARITY_OK" in out


def test_sharded_metapath_needs_typed_partition(small_graph, rng):
    """A metapath program on an *untyped* partitioned graph fails with an
    actionable error (type_offsets were never built)."""
    from repro.graph import partition_graph
    pg = partition_graph(small_graph, 1)
    starts = rng.integers(0, small_graph.num_vertices, 16).astype(np.int32)
    w = walker.compile(_programs()["metapath"], backend="sharded",
                       execution=walker.ExecutionConfig(num_devices=1))
    with pytest.raises(ValueError, match="type_offsets"):
        w.run(pg, starts)


def test_sharded_run_refuses_more_partitions_than_devices(small_graph,
                                                          rng):
    """A mesh is never built over fewer devices than graph partitions."""
    import jax

    from repro.graph import partition_graph
    n = len(jax.devices()) + 1
    pg = partition_graph(small_graph, n)
    starts = rng.integers(0, small_graph.num_vertices, 16).astype(np.int32)
    w = walker.compile(_programs()["urw"], backend="sharded",
                       execution=walker.ExecutionConfig(num_devices=n))
    with pytest.raises(ValueError, match="partitions but only"):
        w.run(pg, starts)


# ------------------------------------------------------------ validation


def test_program_validation():
    with pytest.raises(ValueError, match="max_hops"):
        walker.WalkProgram.urw(0)
    with pytest.raises(ValueError, match="stop_prob"):
        walker.WalkProgram.ppr(alpha=1.5)
    with pytest.raises(ValueError, match="schedule"):
        walker.WalkProgram.metapath([])
    with pytest.raises(ValueError, match="positive"):
        walker.WalkProgram.node2vec(p=0.0)
    with pytest.raises(TypeError, match="WalkProgram"):
        walker.compile("urw")
    with pytest.raises(ValueError, match="backend"):
        walker.compile(walker.WalkProgram.urw(), backend="tpu_pod")


def test_sampler_spec_validation():
    """Malformed specs fail at construction (not deep inside tracing):
    the kind registry, the MetaPath schedule, and the Node2Vec params are
    all checked by SamplerSpec.__post_init__ itself."""
    from repro.core.samplers import SamplerSpec
    with pytest.raises(ValueError, match="schedule"):
        SamplerSpec(kind="metapath", metapath=())
    with pytest.raises(ValueError, match="schedule"):
        SamplerSpec(kind="metapath")
    with pytest.raises(ValueError, match="non-negative"):
        SamplerSpec(kind="metapath", metapath=(0, -1))
    with pytest.raises(ValueError, match="unknown sampler kind"):
        SamplerSpec(kind="levy_flight")
    with pytest.raises(ValueError, match="positive"):
        SamplerSpec(kind="rejection_n2v", q=-1.0)
    with pytest.raises(ValueError, match="rejection_rounds"):
        SamplerSpec(kind="rejection_n2v", rejection_rounds=0)
    with pytest.raises(ValueError, match="reservoir_chunk"):
        SamplerSpec(kind="reservoir_n2v", reservoir_chunk=0)


def test_support_matrix_generated_from_programs():
    """The docs support matrix is generated from the phase-program
    declarations — docs/api.md must embed render_support_matrix()'s
    output verbatim (regenerate with
    ``python -m repro.core.phase_program``)."""
    import os

    from repro.core.phase_program import (fused_kinds, lower,
                                          render_support_matrix,
                                          support_rows)
    rows = {r["kind"]: r for r in support_rows()}
    # the acceptance surface: fused covers every sampler kind (the
    # chunked reservoir loop runs in-kernel), and every kind (metapath
    # included) is sharded
    from repro.core.samplers import KINDS
    assert fused_kinds() == KINDS
    assert all(r["capability"] is not None for r in rows.values())
    assert rows["metapath"]["capability"] == "first_order"
    assert lower(walker.WalkProgram.node2vec(
        2.0, 0.5, weighted=True).spec).schedule == "chunked_loop"
    docs = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "api.md")).read()
    for line in render_support_matrix().splitlines():
        assert line in docs, f"docs/api.md out of date, missing: {line}"


def test_schedule_table_generated_from_programs():
    """docs/architecture.md must embed both generated tables verbatim
    (regenerate with ``python -m repro.core.phase_program`` /
    ``--schedule``; CI runs ``--check``)."""
    import os

    from repro.core.phase_program import (render_schedule_table,
                                          render_support_matrix)
    arch = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "architecture.md")).read()
    for table in (render_schedule_table(), render_support_matrix()):
        for line in table.splitlines():
            assert line in arch, \
                f"docs/architecture.md out of date, missing: {line}"


def test_execution_config_validation():
    with pytest.raises(ValueError, match="num_slots"):
        walker.ExecutionConfig(num_slots=0)
    with pytest.raises(ValueError, match="mode"):
        walker.ExecutionConfig(mode="eager")
    with pytest.raises(ValueError, match="injection_delay"):
        walker.ExecutionConfig(injection_delay=-1)
    with pytest.raises(ValueError, match="queue_depth_factor"):
        walker.ExecutionConfig(queue_depth_factor=0.0)
    with pytest.raises(ValueError, match="num_devices"):
        walker.ExecutionConfig(num_devices=0)


def test_engine_config_validation():
    with pytest.raises(ValueError, match="num_slots"):
        EngineConfig(num_slots=0)
    with pytest.raises(ValueError, match="max_hops"):
        EngineConfig(max_hops=-1)
    with pytest.raises(ValueError, match="step_impl"):
        EngineConfig(step_impl="cuda")
    # valid configs still replace cleanly
    cfg = dataclasses.replace(EngineConfig(), num_slots=4)
    assert cfg.num_slots == 4


def test_dist_config_validation():
    from repro.core.distributed import DistConfig
    with pytest.raises(ValueError, match="slots_per_device"):
        DistConfig(slots_per_device=0)
    with pytest.raises(ValueError, match="max_hops"):
        DistConfig(max_hops=0)


def test_make_queue_watermark_validation():
    with pytest.raises(ValueError, match="staged"):
        make_queue(np.zeros(8, np.int32), staged=9)
    with pytest.raises(ValueError, match="capacity"):
        make_queue(np.zeros(8, np.int32), tail=12)
    q = make_queue(np.zeros(8, np.int32), staged=4)
    assert int(q.staged) == 4 and int(q.tail) == 8


def test_stream_admission_overflow(rich_graph, rng):
    stream = walker.compile(walker.WalkProgram.urw(4)).stream(
        rich_graph, capacity=8)
    stream.inject(rng.integers(0, rich_graph.num_vertices, 8))
    with pytest.raises(ValueError, match="overflows"):
        stream.inject(rng.integers(0, rich_graph.num_vertices, 1))


def test_stream_release_recycles_slots(rich_graph, rng):
    """Ring economy: released slots are re-issued FIFO with epoch + 1;
    releasing an unfinished or non-live slot is rejected."""
    stream = walker.compile(walker.WalkProgram.urw(4)).stream(
        rich_graph, capacity=8)
    starts = rng.integers(0, rich_graph.num_vertices, 8).astype(np.int32)
    qids, epochs = stream.inject(starts)
    assert np.array_equal(qids, np.arange(8)) and (epochs == 0).all()
    assert stream.num_free == 0
    with pytest.raises(ValueError, match="unfinished"):
        stream.release(qids[:2])
    stream.drain(chunk=4)
    with pytest.raises(ValueError, match="duplicate"):
        stream.release([qids[0], qids[0]])
    stream.release(qids[:3])
    assert stream.num_free == 3
    with pytest.raises(ValueError, match="not live"):
        stream.release(qids[:1])           # double release
    q2, e2 = stream.inject(starts[:3])
    assert np.array_equal(q2, qids[:3]) and (e2 == 1).all()
    assert stream.num_injected == 11


# ---------------------------------------------------- API snapshot + shims


def test_public_api_snapshot():
    """The public surface of repro.walker is intentional: additions and
    removals must update this snapshot (and docs/api.md)."""
    assert list(walker.__all__) == [
        "WalkProgram",
        "ExecutionConfig",
        "compile",
        "Walker",
        "WalkStream",
        "ShardedWalkStream",
        "BACKENDS",
    ]
    assert walker.BACKENDS == ("single", "sharded")
    for name in walker.__all__:
        assert getattr(walker, name) is not None
    # the two stream backends expose one interface (WalkService contract)
    for method in ("inject", "advance", "done_mask", "harvest_ids",
                   "release", "walk_stats", "reset", "drain"):
        assert callable(getattr(walker.WalkStream, method))
        assert callable(getattr(walker.ShardedWalkStream, method))


def test_deprecated_names_importable():
    """Surviving legacy entry points remain importable shims; the
    ``core.walks`` / ``core.distributed_n2v`` modules (two PRs past
    deprecation) are gone for good."""
    from repro.core.distributed import run_distributed        # noqa: F401
    from repro.core.walk_engine import (make_engine,          # noqa: F401
                                        make_superstep_runner, run_walks)
    with pytest.raises(ImportError):
        from repro.core import walks                          # noqa: F401
    with pytest.raises(ImportError):
        from repro.core import distributed_n2v                # noqa: F401


def test_legacy_run_walks_shim_warns(rich_graph, rng):
    from repro.core.samplers import SamplerSpec
    from repro.core.walk_engine import run_walks
    starts = rng.integers(0, rich_graph.num_vertices, 32).astype(np.int32)
    with pytest.deprecated_call():
        res = run_walks(rich_graph, starts, SamplerSpec(kind="uniform"),
                        EngineConfig(num_slots=32, max_hops=4), seed=1)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        new = walker.compile(
            walker.WalkProgram.urw(4),
            execution=walker.ExecutionConfig(num_slots=32)).run(
                rich_graph, starts, seed=1)
    # the new surface must NOT route through a deprecated shim
    assert not [w for w in caught
                if issubclass(w.category, DeprecationWarning)
                and "repro.walker" in str(w.message)]
    assert np.array_equal(*(r.as_numpy()[0] for r in (res, new)))
