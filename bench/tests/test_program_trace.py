"""What the program records in a trace: the ``tf_op`` paths read from the
raw XSpace, leaf ops, and the per-layer readers of the program's spans,
counters and scopes, on hand-made data and on traces recorded on a TPU
v5e (``bench/testdata``, made by ``bench/record_traces.py``)."""
import gzip
from pathlib import Path

import numpy as np
import pytest

from bench import registry
from bench.program_trace import (PROGRAM_SPANS, ProgramTrace, components,
                                 field, leaves, op_paths)
from bench.trace import Trace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
NEW = ("serve_syncs_per_step", "serve_syncs_per_step.served",
       "serve_harvest_ms_per_request", "serve_host_ms_per_step.served")
# What the readers give on the recorded serving toy trace.
EXPECTED = {"serve_syncs_per_step": 5.0, "serve_syncs_per_step.served": 5.0,
            "serve_harvest_ms_per_request": 6.2115015,
            "serve_host_ms_per_step.served": 10.252204666666668}


def _unpack(tmp_path, name) -> Path:
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(gzip.decompress(
        (TESTDATA / f"{name}.xplane.pb.gz").read_bytes()))
    return path


def _view(path) -> dict:
    return {"trace": Trace.from_file(path), "xplane": path,
            "walk": {"sampler": "alias"}, "device_kind": "TPU v5 lite",
            "requests": None}


# ----------------------------------------------------- wire format


def _entry(key: int, value: bytes) -> bytes:
    return field(1, key) + field(2, value)


def test_op_paths_of_a_hand_made_xspace():
    """A ``tf_op`` held as a string, and one held as a reference to a stat
    metadata's name, both keyed by (program id, op name); planes that are
    not TPU devices are left out."""
    stat_md = [(1, "tf_op"), (2, "program_id"),
               (3, "jit(run)/while/body/walk.sample/add:")]
    events = [
        (5, "%a = s32[4] scatter(x)", [field(1, 1) + field(
            5, "jit(run)/while/body/walk.record/scatter:"),
            field(1, 2) + field(3, 7)]),
        (6, "%b = s32[4] add(x)", [field(1, 1) + field(7, 3),
                                   field(1, 2) + field(3, 7)]),
        (8, "%c = s32[4] copy(x)", [field(1, 2) + field(3, 7)]),
    ]
    plane = field(2, "/device:TPU:0") + field(3, field(2, "XLA Ops"))
    for k, name in stat_md:
        plane += field(5, _entry(k, field(1, k) + field(2, name)))
    for k, name, stats in events:
        md = field(1, k) + field(2, name) + b"".join(
            field(5, s) for s in stats)
        plane += field(4, _entry(k, md))
    host = field(2, "/host:CPU") + field(4, _entry(1, field(2, "x")))
    xspace = field(1, host) + field(1, plane) + field(4, "hostname")
    assert op_paths(xspace) == [{
        (7, "%a = s32[4] scatter(x)"):
            "jit(run)/while/body/walk.record/scatter:",
        (7, "%b = s32[4] add(x)"): "jit(run)/while/body/walk.sample/add:"}]


def test_op_paths_of_the_recorded_closed_run():
    xspace = gzip.decompress(
        (TESTDATA / "closed_tiny.xplane.pb.gz").read_bytes())
    (paths,) = op_paths(xspace)
    found = {v for (pid, name), v in paths.items()
             if name.startswith("%maximum_and_fusion ")}
    assert found == {"jit(run)/jit(remainder)/rem:"}
    assert {pid for pid, _ in paths} >= {16306078227998777748}


def test_components_and_leaves():
    assert components("jit(run)/while/body/walk.record/scatter:") == {
        "jit(run)", "while", "body", "walk.record", "scatter"}
    assert "walk.record" not in components("jit(run)/walk.recorder/x:")
    ops = np.array([[0, 100], [10, 20], [30, 40], [100, 110], [120, 130],
                    [125, 126], [20, 30]], float)
    np.testing.assert_array_equal(
        leaves(ops), [False, True, True, True, False, True, True])


# ------------------------------------------------- recorded traces


def test_new_readers_are_silent_without_the_program_spans(tmp_path):
    """On a trace of a program without the spans and scopes (the parent
    of this change recorded it), every new reader returns None."""
    view = _view(_unpack(tmp_path, "closed_tiny"))
    view["counters"] = {"steps": 20000, "slot_steps": 25000,
                        "supersteps": 100}
    for name in NEW:
        assert registry.metric(name).read(view) is None, name
    split = ProgramTrace.of(view).scope_split("jit_run")
    assert all(split[s] == 0 for s in ("walk.sample", "walk.commit",
                                       "walk.refill", "walk.record"))
    assert split["unscoped"] == split["total"] > 0


def test_new_readers_on_traces_with_the_spans_and_scopes(tmp_path):
    """The four readers, and the scope split that ``python3 -m
    bench.program_trace`` prints, on toy traces recorded on a TPU v5e by
    ``bench/record_traces.py``: DeepWalk batches of 512 walks of 16 hops,
    and six PPR requests of 32 walks in a 0.1 s window.  At this size the
    TPU compiler keeps the paths scatter as written, so it carries
    ``walk.record``; at the cells' size it rewrites that scatter into ops
    with no name, which the split counts as unscoped."""
    closed = _view(_unpack(tmp_path, "closed_scoped"))
    serve = _view(_unpack(tmp_path, "serve_tiny"))
    read = {name: registry.metric(name).read(serve) for name in NEW}
    assert read == pytest.approx(EXPECTED, rel=1e-6)
    split = ProgramTrace.of(closed).scope_split("jit_run")
    assert split == pytest.approx(
        {"walk.sample": 5.72107e-4, "walk.commit": 5.0894e-5,
         "walk.refill": 4.10011e-4, "walk.record": 2.206179e-3,
         "unscoped": 2.00459e-4, "total": 3.43965e-3}, rel=1e-6)
    # The syncs of a step are 3 + 3h for h requests harvested in it.
    pt = ProgramTrace.of(serve)
    _, steps = pt.spans("serve.step")
    harvests, meta = pt.spans("serve.harvest")
    ran = [m for m in steps if m["chunks"]]
    assert sum(m["syncs"] for m in ran) == 3 * len(ran) + 3 * len(harvests)
    assert len({m["request_id"] for m in meta}) == len(meta) > 0
    assert {n for n in PROGRAM_SPANS if pt.spans(n)[0].shape[0]} == set(
        PROGRAM_SPANS) - {"walker.bind", "walker.engine", "walker.assemble"}
