"""The trace reduction, on hand-made planes and on a small trace of a
closed-batch run recorded on a TPU v5e (``bench/testdata``); and the
work the roofline counts, the same whatever implements the step."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import registry
from bench.trace import Trace, coverage, short_op, union

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes(ops, modules, spans):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev(n, s, d) for n, s, d in ops]),
        NS(name="XLA Modules", events=[_ev(n, s, d) for n, s, d in modules])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[_ev(n, s, d) for n, s, d in spans])])
    return [device, host, NS(name="/device:CUSTOM:x", lines=[])]


def test_union_and_coverage():
    u = union(np.array([[5, 7], [1, 3], [2, 4], [6, 9], [10, 11]], float))
    np.testing.assert_array_equal(u, [[1, 4], [5, 9], [10, 11]])
    np.testing.assert_array_equal(coverage(u, np.array([0, 2, 4.5, 8, 20])),
                                  [0, 1, 3, 6, 8])


def test_reduction_of_hand_made_planes():
    ops = [("%a = s32[4] fusion(x)", 100, 200), ("%b = s32[4] fusion(x)", 250, 100),
           ("%a = s32[4] fusion(x)", 600, 300), ("%c = f32[2] add(x)", 0, 50)]
    modules = [("jit_run(123)", 100, 250), ("jit_run(123)", 600, 300),
               ("jit_other(9)", 0, 50)]
    spans = [("window", 90, 1000), ("batch", 95, 900), ("walker.run", 95, 10),
             ("fetch", 105, 600), ("wait", 990, 100)]
    t = Trace(_planes(ops, modules, spans))
    assert t.window_s == pytest.approx(1000e-9)
    # Busy in [90, 1090]: [100, 350] and [600, 900]; op %c lies outside.
    assert t.busy_s == pytest.approx(550e-9)
    assert t.idle_share() == pytest.approx(0.45)
    assert t.program_s("jit_run") == pytest.approx(550e-9)
    assert t.program_s("jit_nothing") is None
    idle = dict(t.idle_by_span())
    # Gaps: [90, 100] in walker.run (and batch), [350, 600] half in fetch
    # ([350, 600] ∩ [105, 705] is all of it), [900, 1090]: batch covers
    # [900, 995], wait covers [990, 1090] -> wait covers more than half.
    assert idle == pytest.approx({"walker.run": 10e-9, "fetch": 250e-9,
                                  "wait": 190e-9})
    assert t.top_ops(2)[0] == ["%a fusion s32[4]", pytest.approx(500e-9)]


def test_short_op_names():
    assert short_op("%fusion.166 = s32[10616832]{0:T(1024)S(1)} fusion("
                    "s32[1] %x), kind=kCustom") == "%fusion.166 fusion s32[10616832]"
    assert short_op("odd") == "odd"


def test_recorded_trace_of_a_closed_run(tmp_path):
    path = tmp_path / "closed_tiny.xplane.pb"
    path.write_bytes(gzip.decompress(
        (TESTDATA / "closed_tiny.xplane.pb.gz").read_bytes()))
    t = Trace.from_file(path)
    assert len(t.devices) == 1
    assert t.span("batch").shape == (2, 2)
    assert t.window_s == pytest.approx(0.02318286, rel=1e-6)
    assert t.busy_s == pytest.approx(0.01265476, rel=1e-6)
    assert t.program_s("jit_run") == pytest.approx(0.012668438, rel=1e-6)
    idle = dict(t.idle_by_span())
    assert set(idle) == {"fetch", "walker.run"}
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s)
    ops = t.top_ops(10)
    assert len(ops) == 10 and ops[0][0].startswith("%while")
    # The readers of the closed cells on this trace.
    view = {"trace": t, "walk": {"sampler": "alias"}, "device_kind": "TPU v5 lite",
            "counters": {"steps": 20000, "slot_steps": 25000, "supersteps": 100}}
    read = {m: registry.metric(m).read(view) for m in (
        "superstep_ms", "lane_occupancy", "superstep_roofline",
        "walker_host_ms_per_batch", "device_idle_share.throughput")}
    assert read["superstep_ms"] == pytest.approx(0.12668438, rel=1e-6)
    assert read["lane_occupancy"] == pytest.approx(80.0)
    assert read["superstep_roofline"] == pytest.approx(
        100 * 20000 * 24 / 0.012668438 / 819e9, rel=1e-6)
    assert 0 < read["walker_host_ms_per_batch"] < 1e3 * t.window_s / 2
    assert read["device_idle_share.throughput"] == pytest.approx(
        100 * (1 - 0.01265476 / 0.02318286), rel=1e-5)


def test_peaks_and_bytes_tables():
    bench = Path(__file__).resolve().parents[1]
    peaks = json.loads((bench / "peaks.json").read_text())
    assert peaks["source"] and peaks["devices"]["TPU v5 lite"][
        "hbm_bytes_per_s"] == 819e9
    kinds = json.loads((bench / "bytes_per_step.json").read_text())["kinds"]
    for cell in registry.benchmark()["workloads"]:
        kind = registry.config(cell["config"])["walk"]["sampler"]
        assert kind in kinds


@pytest.mark.parametrize("name, key", [
    ("request_tail_p95_ms", "latency_s"),
    ("admission_wait_p95_ms", "admission_wait_s"),
    ("generator_late_p95_ms", "generator_late_s"),
])
def test_request_tails_by_nearest_rank(name, key):
    reader = registry.metric(name)
    assert reader.read({"requests": None}) is None
    values = np.arange(1, 101) / 1e3
    assert reader.read({"requests": {key: values}}) == pytest.approx(95.0)
    values[-10:] = np.inf        # unanswered requests count as the slowest
    assert reader.read({"requests": {key: values}}) == np.inf


def test_roofline_refuses_an_unknown_device():
    view = {"trace": NS(program_s=lambda name: 1.0), "walk": {"sampler": "alias"},
            "device_kind": "TPU v9 imaginary", "counters": {"steps": 1}}
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        registry.metric("superstep_roofline").read(view)


@pytest.mark.parametrize("algo", ["ppr", "deepwalk"])
def test_jnp_and_fused_steps_count_the_same_work(algo):
    """The roofline counts ``WalkStats.steps``: the jnp superstep and the
    fused kernel (interpreted here) must report the same work."""
    from repro import walker
    from repro.configs.ridgewalker import ALGORITHMS
    from repro.graph import make_dataset
    g = make_dataset("WG", scale_override=7, weighted=algo == "deepwalk",
                     with_alias=algo == "deepwalk", seed=3)
    starts = np.random.default_rng(0).integers(0, g.num_vertices, 48)
    counts = {}
    for impl in ("jnp", "fused"):
        w = walker.compile(
            walker.WalkProgram(spec=ALGORITHMS[algo], max_hops=8, name=algo),
            execution=walker.ExecutionConfig(num_slots=16, step_impl=impl,
                                             hops_per_launch=4))
        res = w.run(g, starts.astype(np.int32), seed=11)
        counts[impl] = (int(res.stats.steps), int(res.stats.slot_steps),
                        int(res.stats.supersteps), np.asarray(res.paths))
    assert counts["jnp"][:3] == counts["fused"][:3]
    np.testing.assert_array_equal(counts["jnp"][3], counts["fused"][3])
