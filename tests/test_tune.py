"""Autotuning subsystem: signatures, cache, model, tuner, serve adaptation.

Everything here is deterministic — measurement runs use the injected
measurer (`tune.measure.InjectedMeasurer`), never a clock — so the full
tuning pipeline (enumerate -> anchor -> fit -> prune -> pick -> cache)
is exercised as a pure function of its inputs.
"""
import dataclasses
import json

import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import tune, walker
from repro.serve import HopsController
from repro.tune.cache import WEIGHTED_QS, GraphSignature
from repro.walker import ExecutionConfig, WalkProgram


# ------------------------------------------------------------- signatures


def test_signature_stable_and_distinguishes_skew(small_graph):
    sig1 = tune.graph_signature(small_graph)
    sig2 = tune.graph_signature(small_graph)
    assert sig1 == sig2
    assert sig1.token() == sig2.token()
    assert sig1.num_vertices == small_graph.num_vertices
    assert sig1.num_edges == small_graph.num_edges
    # the ladders are sorted ascending and end at max_degree
    assert list(sig1.deg_q) == sorted(sig1.deg_q)
    assert sig1.deg_q[-1] == sig1.max_degree
    assert sig1.deg_wq[-1] == sig1.max_degree


def test_signature_weighted_flag(small_graph, weighted_graph):
    assert not tune.graph_signature(small_graph).weighted
    assert tune.graph_signature(weighted_graph).weighted
    assert (tune.graph_signature(small_graph).token()
            != tune.graph_signature(weighted_graph).token())


def test_workload_bucket():
    assert tune.workload_bucket(None) == 0
    assert tune.workload_bucket(0) == 0
    assert tune.workload_bucket(1) == 64
    assert tune.workload_bucket(64) == 64
    assert tune.workload_bucket(65) == 128
    assert tune.workload_bucket(1000) == 1024


# ------------------------------------------------------------------ cache


def test_cache_round_trip(tmp_path, small_graph):
    path = str(tmp_path / "cache.json")
    sig = tune.graph_signature(small_graph)
    key = tune.cache_key(sig, "uniform", "single", "jnp", "cpu", True, 256)
    cache = tune.TuningCache(path)
    cache.put(key, {"num_slots": 128}, meta={"source": "measured"})
    assert cache.save() == path

    reloaded = tune.TuningCache(path)
    rec = reloaded.get(key)
    assert rec["knobs"] == {"num_slots": 128}
    assert rec["meta"]["source"] == "measured"
    # key stability: recomputing from the same graph hits the same entry
    key2 = tune.cache_key(tune.graph_signature(small_graph), "uniform",
                          "single", "jnp", "cpu", True, 256)
    assert key2 == key
    # workload bucketing: 200 and 256 queries share a bucket, 257 does not
    assert tune.cache_key(sig, "uniform", "single", "jnp", "cpu", True,
                          200) == key
    assert tune.cache_key(sig, "uniform", "single", "jnp", "cpu", True,
                          257) != key


def test_cache_tolerates_corrupt_file(tmp_path):
    path = tmp_path / "broken.json"
    path.write_text("{not json")
    cache = tune.TuningCache(str(path))
    assert len(cache) == 0
    path.write_text(json.dumps({"version": 999, "entries": {"k": {}}}))
    assert len(tune.TuningCache(str(path))) == 0


# ------------------------------------------------------------------ space


def test_candidate_apply_and_validity():
    prog = WalkProgram.urw(8)
    ex = ExecutionConfig(record_paths=False)
    cand = tune.Candidate.of(num_slots=64, queue_depth_factor=2.0)
    prog2, ex2 = cand.apply(prog, ex)
    assert ex2.num_slots == 64 and ex2.queue_depth_factor == 2.0
    assert prog2 is prog
    with pytest.raises(ValueError):
        tune.Candidate.of(num_slots=-1).apply(prog, ex)
    with pytest.raises(ValueError):
        tune.Candidate.of(bogus_knob=1).apply(prog, ex)


def test_enumeration_excludes_resampling_knobs_by_default():
    prog = WalkProgram.node2vec(2.0, 0.5, 8, weighted=True)
    ex = ExecutionConfig(record_paths=False)
    cands = tune.enumerate_candidates(prog, ex)
    chunks = {c.get("reservoir_chunk") for c in cands}
    assert chunks == {prog.spec.reservoir_chunk}  # pinned, never enumerated
    assert all("adaptive_chunks" not in c.to_dict() for c in cands)
    with_rs = tune.enumerate_candidates(prog, ex, include_resampling=True)
    assert len({c.get("reservoir_chunk") for c in with_rs}) > 1


def test_hops_per_launch_only_on_fused():
    prog = WalkProgram.urw(8)
    jnp_knobs = {k.name for k in tune.knobs_for(
        prog, ExecutionConfig(record_paths=False))}
    fused_knobs = {k.name for k in tune.knobs_for(
        prog, ExecutionConfig(record_paths=False, step_impl="fused"))}
    assert "hops_per_launch" not in jnp_knobs
    assert "hops_per_launch" in fused_knobs


# ------------------------------------------------------------------ model


def test_reservoir_trips_follow_lane_degree():
    """The model prices a reservoir hop at the chunk trips of a typical
    walking lane (degree-weighted median degree), not the graph's
    max-degree bound: a power-law tail leaves the price unchanged."""
    assert len(WEIGHTED_QS) == 8
    mk = lambda dmax: GraphSignature(  # noqa: E731
        num_vertices=4096, num_edges=32768, max_degree=dmax,
        weighted=True, typed=False,
        deg_q=(1, 2, 4, 8, 16, 300, dmax),
        deg_wq=(20, 40, 80, 120, 160, 400, 600, dmax))
    spec = WalkProgram.node2vec(2.0, 0.5, 8, weighted=True).spec
    d = tune.lane_degree(mk(600))
    assert d == 20      # the weighted ladder's median rung
    assert tune.lane_degree(mk(60000)) == d
    assert (tune.bytes_per_hop(spec, mk(600))
            < tune.bytes_per_hop(spec, mk(600), chunk_trips=600))


def test_bytes_per_hop_orders_sampler_kinds(weighted_graph):
    sig = tune.graph_signature(weighted_graph)
    uni = tune.bytes_per_hop(WalkProgram.urw(8).spec, sig)
    rej = tune.bytes_per_hop(WalkProgram.node2vec(2.0, 0.5, 8).spec, sig)
    res = tune.bytes_per_hop(
        WalkProgram.node2vec(2.0, 0.5, 8, weighted=True).spec, sig)
    assert 0 < uni < rej < res


def test_fit_recovers_scale():
    rows = [np.array([10.0, 100.0, 1000.0, 1.0]),
            np.array([20.0, 400.0, 2000.0, 1.0]),
            np.array([5.0, 50.0, 5000.0, 2.0]),
            np.array([40.0, 200.0, 1500.0, 4.0]),
            np.array([15.0, 300.0, 2500.0, 1.0])]
    true = tune.CostCoeffs(10.0, 0.5, 0.01, 100.0)
    ys = [float(r @ true.as_array()) for r in rows]
    fitted = tune.fit(rows, ys)
    for r, y in zip(rows, ys):
        assert float(r @ fitted.as_array()) == pytest.approx(y, rel=1e-6)


def test_fit_underdetermined_rescales():
    rows = [np.array([10.0, 100.0, 1000.0, 1.0])]
    ys = [float(rows[0] @ tune.DEFAULT_COEFFS.as_array()) * 3.0]
    fitted = tune.fit(rows, ys)
    assert (float(rows[0] @ fitted.as_array())
            == pytest.approx(ys[0], rel=1e-6))


def test_prune_keeps_model_best_and_default(small_graph):
    prog = WalkProgram.urw(8)
    ex = ExecutionConfig(record_paths=False)
    sig = tune.graph_signature(small_graph)
    cands = tune.enumerate_candidates(prog, ex)
    preds = {c: tune.predict_us(*c.apply(prog, ex), sig, 256)
             for c in cands}
    best = min(preds, key=preds.get)
    knobs = tune.knobs_for(prog, ex)
    default = tune.default_candidate(prog, ex, knobs)
    kept = tune.prune(prog, ex, sig, 256, cands, keep=3,
                      always_keep=(default,))
    assert best in kept
    assert default in kept
    assert len(kept) <= 3 + 1


# ------------------------------------------------------------------ tuner


def test_autotune_injected_measurer_is_deterministic(small_graph):
    prog = WalkProgram.urw(8)
    ex = ExecutionConfig(record_paths=False)

    def cost(c):  # prefer small lane pools, mildly penalize deep queues
        return float(c.get("num_slots")) + 10.0 * float(
            c.get("queue_depth_factor"))

    results = []
    for _ in range(2):
        meas = tune.InjectedMeasurer(cost)
        res = tune.autotune(small_graph, prog, ex, num_queries=128,
                            measurer=meas, cache=tune.TuningCache(None),
                            keep=4)
        assert res.source == "measured"
        assert meas.calls >= 1            # runners were never timed
        results.append(res.candidate)
    assert results[0] == results[1]
    # the injected cost is minimized at the smallest grid point
    assert results[0].get("num_slots") == 32
    assert results[0].get("queue_depth_factor") == 0.5


def test_autotune_min_gain_keeps_default(small_graph):
    """A sub-threshold win must not displace the default (hysteresis)."""
    prog = WalkProgram.urw(8)
    ex = ExecutionConfig(record_paths=False)
    knobs = tune.knobs_for(prog, ex)
    default = tune.default_candidate(prog, ex, knobs)

    def cost(c):  # everyone ties except a 1% win somewhere else
        return 0.99 if c != default else 1.0

    res = tune.autotune(small_graph, prog, ex, num_queries=128,
                        measurer=tune.InjectedMeasurer(cost),
                        cache=tune.TuningCache(None), min_gain=0.02)
    assert res.candidate == default


def test_autotune_writes_and_reuses_cache(small_graph):
    prog = WalkProgram.urw(8)
    ex = ExecutionConfig(record_paths=False)
    cache = tune.TuningCache(None)
    res = tune.autotune(small_graph, prog, ex, num_queries=128,
                        measurer=tune.InjectedMeasurer(
                            lambda c: float(c.get("num_slots"))),
                        cache=cache, keep=3)
    assert len(cache) == 1
    again = tune.autotune(small_graph, prog, ex, num_queries=128,
                          measurer=tune.InjectedMeasurer(lambda c: 0.0),
                          cache=cache, keep=3)
    assert again.source == "cache"
    assert again.candidate == res.candidate


def test_model_only_autotune_no_measure(small_graph):
    res = tune.autotune(small_graph, WalkProgram.urw(8),
                        ExecutionConfig(record_paths=False),
                        num_queries=128, measurer=None,
                        cache=tune.TuningCache(None))
    assert res.source == "model"
    assert not res.measured
    assert not res.execution.has_auto


# --------------------------------------------------------- auto sentinels


def test_execution_config_auto_validation():
    ex = ExecutionConfig(num_slots="auto", hops_per_launch="auto")
    assert ex.has_auto
    assert ex.auto_knobs == ("num_slots", "hops_per_launch")
    with pytest.raises(ValueError):
        ExecutionConfig(num_slots="turbo")
    with pytest.raises(ValueError):
        ex.engine_config(WalkProgram.urw(8))
    r = ex.resolved(num_slots=64)
    assert r.num_slots == 64
    assert r.hops_per_launch == 16   # sentinel fell back to field default
    with pytest.raises(ValueError):
        ex.resolved(record_paths=False)   # not a tunable knob


def test_sampler_spec_adaptive_auto_validation():
    """Reservoir specs carry no scan-shape option; a malformed chunk
    width still fails at construction."""
    spec = WalkProgram.node2vec(2.0, 0.5, 8, weighted=True).spec
    assert not hasattr(spec, "adaptive_chunks")
    with pytest.raises(ValueError):
        dataclasses.replace(spec, reservoir_chunk=0)


def test_auto_resolution_preserves_paths(small_graph):
    prog = WalkProgram.urw(8)
    starts = np.arange(64, dtype=np.int32) % small_graph.num_vertices
    out_auto = walker.compile(
        prog, execution=ExecutionConfig(num_slots="auto")).run(
        small_graph, starts, seed=3)
    out_def = walker.compile(
        prog, execution=ExecutionConfig()).run(small_graph, starts, seed=3)
    assert (np.asarray(out_auto.paths) == np.asarray(out_def.paths)).all()
    assert (np.asarray(out_auto.lengths)
            == np.asarray(out_def.lengths)).all()


def test_auto_resolution_uses_cached_entry(small_graph, tmp_path):
    path = str(tmp_path / "cache.json")
    prog = WalkProgram.urw(8)
    ex = ExecutionConfig(num_slots="auto", tune_cache=path)
    sig = tune.graph_signature(small_graph)
    from repro.tune.tuner import _device_kind, _interpret_mode
    key = tune.cache_key(sig, "uniform", "single", "jnp", _device_kind(),
                         _interpret_mode(), 64)
    cache = tune.TuningCache(path)
    cache.put(key, {"num_slots": 96}, meta={"source": "test"})
    cache.save()
    prog2, ex2 = tune.resolve(prog, ex, small_graph, num_queries=64)
    assert ex2.num_slots == 96


def test_reservoir_auto_gate_resolution(weighted_graph):
    """A reservoir program with concrete execution knobs has nothing to
    resolve: `resolve` hands it back unchanged."""
    prog = WalkProgram.node2vec(2.0, 0.5, 8, weighted=True)
    ex = ExecutionConfig(num_slots=32, record_paths=False)
    assert not tune.needs_resolution(prog, ex)
    prog2, ex2 = tune.resolve(prog, ex, weighted_graph,
                              cache=tune.TuningCache(None))
    assert prog2 is prog and ex2 is ex
    prog3, _ = tune.resolve(prog, ExecutionConfig(num_slots="auto"),
                            weighted_graph, cache=tune.TuningCache(None))
    assert prog3 is prog


SHARDED_AUTO = r"""
import numpy as np
from repro import walker
from repro.graph import make_dataset, partition_graph
from repro.walker import ExecutionConfig, WalkProgram

g = make_dataset("WG", scale_override=9)
pg = partition_graph(g, 2)
prog = WalkProgram.urw(6)
starts = np.arange(32, dtype=np.int32) % g.num_vertices
out_auto = walker.compile(prog, backend="sharded",
                          execution=ExecutionConfig(num_slots="auto")).run(
    pg, starts, seed=1)
out_def = walker.compile(prog, backend="sharded",
                         execution=ExecutionConfig()).run(pg, starts, seed=1)
assert (np.asarray(out_auto.paths) == np.asarray(out_def.paths)).all()
print("SHARDED_AUTO_OK")
"""


def test_auto_resolution_sharded_backend():
    out = run_in_subprocess(SHARDED_AUTO, devices=2)
    assert "SHARDED_AUTO_OK" in out


# -------------------------------------------------------- serve adaptation


def test_controller_bounds_and_validation():
    c = HopsController(min_chunk=2, max_chunk=32)
    assert c.clamp(1) == 2 and c.clamp(1000) == 32 and c.clamp(8) == 8
    with pytest.raises(ValueError):
        HopsController(min_chunk=0)
    with pytest.raises(ValueError):
        HopsController(low_water=0.5, high_water=0.1)
    with pytest.raises(ValueError):
        HopsController(patience=0)


def test_controller_shrinks_on_starvation():
    c = HopsController(min_chunk=1, max_chunk=64, high_water=0.15)
    chunk, ev = c.propose(32, starved_ratio=0.5, bubble_ratio=0.6)
    assert chunk == 16 and ev.reason == "shrink"
    # at the floor the event degrades to "hold", never below min_chunk
    chunk, ev = c.propose(1, starved_ratio=0.9, bubble_ratio=0.9)
    assert chunk == 1 and ev.reason == "hold"


def test_controller_grows_only_after_patience():
    c = HopsController(min_chunk=1, max_chunk=64, patience=3)
    for _ in range(2):
        chunk, ev = c.propose(8, starved_ratio=0.0, bubble_ratio=0.1)
        assert chunk == 8 and ev is None
    chunk, ev = c.propose(8, starved_ratio=0.0, bubble_ratio=0.1)
    assert chunk == 16 and ev.reason == "grow"
    # a bad window resets the streak
    c.propose(16, starved_ratio=0.5, bubble_ratio=0.5)
    chunk, ev = c.propose(8, starved_ratio=0.0, bubble_ratio=0.0)
    assert chunk == 8 and ev is None


def test_controller_holds_between_watermarks():
    c = HopsController(low_water=0.02, high_water=0.15, patience=1)
    chunk, ev = c.propose(8, starved_ratio=0.08, bubble_ratio=0.3)
    assert chunk == 8 and ev is None


def test_controller_converges_under_synthetic_load():
    """Feedback loop against a synthetic plant: starvation grows with the
    chunk (big launches strand arrivals).  The controller must settle
    inside its bounds without oscillating forever."""
    c = HopsController(min_chunk=1, max_chunk=256, patience=2)
    chunk = 256
    history = []
    for _ in range(64):
        starved = min(0.9, chunk / 64.0 * 0.2)   # plant: starved ~ chunk
        chunk, _ = c.propose(chunk, starved, bubble_ratio=starved)
        history.append(chunk)
    tail = history[-16:]
    assert all(1 <= h <= 256 for h in history)
    assert max(tail) - min(tail) <= max(tail) // 2 + 1  # bounded cycle
    assert max(tail) <= 64    # settled well below the starved regime


def test_service_adaptation_trace(small_graph):
    """Overloaded service grows its chunk; the trace lands in analyze()."""
    w = walker.compile(WalkProgram.urw(12),
                       execution=ExecutionConfig(num_slots=64))
    svc = w.serve(small_graph, seed=0, chunk=2, adapt=True,
                  controller=HopsController(min_chunk=1, max_chunk=32,
                                            patience=2))
    rng = np.random.default_rng(0)
    for _ in range(30):
        svc.submit(rng.integers(0, small_graph.num_vertices,
                                size=64).astype(np.int32))
        svc.step()
    svc.drain()
    events = svc.analyze().adaptation
    assert events, "overload produced no adaptation events"
    assert any(e.reason == "grow" for e in events)
    assert all(1 <= e.chunk_after <= 32 for e in events)
    assert svc.chunk <= 32
    # the trace survives into ServiceAnalysis verbatim
    assert events == svc.adaptation


def test_service_fixed_without_adapt(small_graph):
    w = walker.compile(WalkProgram.urw(8),
                       execution=ExecutionConfig(num_slots=64))
    svc = w.serve(small_graph, seed=0, chunk=4)
    svc.submit(np.arange(16, dtype=np.int32))
    svc.drain()
    assert svc.chunk == 4
    assert svc.analyze().adaptation == ()
