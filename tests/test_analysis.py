"""Static-verifier tests: clean runs over the real declarations, CLI
exit-code semantics, property tests mutating valid declarations into
each hazard class, and the BENCH schema validation."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO, SRC, hypothesis_or_stubs
from repro.analysis import dma_hazards, residency, rng_collisions, run_all
from repro.analysis.fixtures import FIXTURES, run_fixture
from repro.core.phase_program import DrawStream, _default_spec, lower
from repro.core.rng import SALTS, SaltRegistry
from repro.core.samplers import KINDS
from repro.kernels.common import DmaOp, schedule_buffers

given, settings, st = hypothesis_or_stubs()


# ------------------------------------------------------------- clean runs


def test_repo_is_clean():
    assert run_all() == []


@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_streams_disjoint(kind):
    streams = rng_collisions.spec_streams(_default_spec(kind))
    assert len(streams) >= 2  # sampler draw + engine stop draw
    assert rng_collisions.check_streams(streams) == []


@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_residency_legal(kind):
    assert residency.check_program(lower(_default_spec(kind))) == []


def test_every_kernel_schedule_hazard_free():
    schedules = dma_hazards.kernel_schedules()
    # every kernel in the tree is declared
    assert {"walk_step.uniform", "walk_step.alias", "embedding_bag",
            "segment_sum"} <= set(schedules)
    assert {f"fused_superstep.{k}" for k in KINDS} <= set(schedules)
    for name, ops in schedules.items():
        assert dma_hazards.check_schedule(ops, name) == []
        assert len(schedule_buffers(ops)) >= 1


def test_builder_patterns_hazard_free():
    """The ScheduleBuilder emitters are safe by construction at any
    unroll count ≥ 1 (they mirror the kernels' loop shapes)."""
    from repro.kernels.common import ScheduleBuilder
    for n in (1, 2, 3, 5):
        b = ScheduleBuilder()
        b.gather_loop("g", n)
        b.pingpong_loop(["c", "w"], n, reads_per_chunk=2)
        b.flush_loop("wb", n)
        assert dma_hazards.check_schedule(b.ops, f"patterns[{n}]") == []


def test_fixtures_all_trip():
    for name in FIXTURES:
        findings = run_fixture(name)
        assert findings, f"fixture {name} produced no findings"
        for f in findings:
            assert f.site and f.message  # diagnostics are actionable


# ---------------------------------------------------------- salt registry


def test_registry_rejects_duplicate_scalar():
    reg = SaltRegistry()
    reg.register("A", 0)
    with pytest.raises(ValueError):
        reg.register("B", 0)


def test_registry_rejects_scalar_inside_family():
    reg = SaltRegistry()
    reg.register("FAM", 8, family=True)
    with pytest.raises(ValueError):
        reg.register("S", 12)
    reg.register("OK", 3)  # below the family base is fine


def test_registry_rejects_second_family():
    reg = SaltRegistry()
    reg.register("FAM", 8, family=True)
    with pytest.raises(ValueError):
        reg.register("FAM2", 100, family=True)


def test_global_registry_channels():
    names = SALTS.names()
    assert {"SALT_COLUMN", "SALT_ACCEPT", "SALT_STOP",
            "SALT_CHUNK0"} <= set(names)
    assert SALTS["SALT_CHUNK0"].family


# ----------------------------------------------- property tests: mutation


@given(salt=st.integers(min_value=0, max_value=7),
       w1=st.integers(min_value=1, max_value=64),
       w2=st.integers(min_value=1, max_value=64))
@settings(max_examples=30, deadline=None)
def test_any_duplicate_salt_collides(salt, w1, w2):
    streams = (DrawStream("a", salt, w1), DrawStream("b", salt, w2))
    findings = rng_collisions.check_streams(streams)
    assert findings and findings[0].pass_name == "rng"
    assert f"[0, {min(w1, w2)})" in findings[0].message


@given(offset=st.integers(min_value=0, max_value=100))
@settings(max_examples=30, deadline=None)
def test_any_scalar_inside_chunk_family_collides(offset):
    fam = DrawStream("fam", 8, 64, family=True)
    scalar = DrawStream("scalar", 8 + offset, 1)
    assert rng_collisions.check_streams((fam, scalar))


@given(drop=st.integers(min_value=0, max_value=5))
@settings(max_examples=30, deadline=None)
def test_dropping_any_wait_is_caught(drop):
    from repro.kernels.walk_step.walk_step import dma_schedule
    ops = dma_schedule("uniform")
    waits = [i for i, op in enumerate(ops) if op.kind == "wait"]
    i = waits[drop % len(waits)]
    mutated = ops[:i] + ops[i + 1:]
    findings = dma_hazards.check_schedule(mutated, "mutated")
    assert findings
    assert any("read-before-arrival" in f.message
               or "never waited" in f.message for f in findings)


@given(kind=st.sampled_from(["uniform", "alias", "metapath",
                             "rejection_n2v", "reservoir_n2v"]),
       seed=st.integers(min_value=0, max_value=10**6))
@settings(max_examples=25, deadline=None)
def test_pinning_pingpong_to_one_slot_is_caught(kind, seed):
    from repro.kernels.fused_superstep.fused_superstep import dma_schedule
    ops = dma_schedule(kind)
    # Only ping-pong buffers (those using both slots) can be pinned.
    bufs = [b for b in schedule_buffers(ops)
            if any(op.slot == 1 for op in ops if op.buffer == b)]
    buf = bufs[seed % len(bufs)]
    mutated = [op._replace(slot=0) if op.buffer == buf else op
               for op in ops]
    findings = dma_hazards.check_schedule(mutated, "mutated")
    assert any("overwrite-while-in-flight" in f.message
               or "not in flight" in f.message for f in findings)


@given(kind=st.sampled_from(["uniform", "alias", "metapath"]))
@settings(max_examples=10, deadline=None)
def test_moving_phase_to_vprev_is_caught(kind):
    prog = lower(_default_spec(kind))
    idx = next(i for i, p in enumerate(prog.phases)
               if p.op in ("draw", "gather"))
    phases = list(prog.phases)
    phases[idx] = dataclasses.replace(phases[idx], residency="v_prev")
    mutated = dataclasses.replace(prog, phases=tuple(phases))
    findings = residency.check_program(mutated)
    assert any("v_prev" in f.message for f in findings)


def test_single_phase_with_carry_is_caught():
    prog = dataclasses.replace(lower(_default_spec("uniform")),
                               carry="candidates")
    assert residency.check_program(prog)


def test_dead_accumulate_without_init_is_caught():
    ops = [DmaOp("visit", "out", 0, first=False, live=True)]
    findings = dma_hazards.check_schedule(ops, "x")
    assert any("uninitialized" in f.message for f in findings)


# -------------------------------------------------------------------- CLI


def _run_cli(*args):
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)


def test_cli_check_passes_on_repo():
    r = _run_cli("--check")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all invariants hold" in r.stdout


def test_cli_fixture_exits_nonzero_with_diagnostics():
    for name in ("rng-duplicate-salt", "dma-missing-wait",
                 "residency-vprev-draw", "determinism-jax-random"):
        r = _run_cli("--fixture", name)
        assert r.returncode == 1, (name, r.stdout)
        assert "finding" in r.stdout  # per-finding diagnostics printed


def test_cli_table_embedded_in_docs():
    r = _run_cli("--table")
    assert r.returncode == 0
    doc = open(os.path.join(REPO, "docs", "architecture.md")).read()
    for line in r.stdout.splitlines():
        if line.strip():
            assert line in doc, f"docs drift: {line!r}"


# ----------------------------------------------------------- BENCH schema


def test_bench_schema_accepts_valid():
    sys.path.insert(0, REPO)
    try:
        from benchmarks.run import validate_payload
    finally:
        sys.path.pop(0)
    payload = {"fig8": {"urw": {"us_per_call": 1.5, "derived": "x"}},
               "walks_per_sec": {"urw": {"jnp": 1e6, "fused": 2e6}}}
    assert validate_payload(payload) == []
    assert json.dumps(payload)  # serializable


@pytest.mark.parametrize("mutate,expect", [
    (lambda p: p["fig8"]["urw"].update(us_per_per_call=1.0), "unknown"),
    (lambda p: p["fig8"]["urw"].pop("derived"), "missing"),
    (lambda p: p["fig8"]["urw"].update(us_per_call="fast"), "number"),
    (lambda p: p.update(fig9=[1, 2]), "expected dict"),
    (lambda p: p["walks_per_sec"]["urw"].update(jnp="NaN?"), "number"),
])
def test_bench_schema_rejects_malformed(mutate, expect):
    sys.path.insert(0, REPO)
    try:
        from benchmarks.run import validate_payload
    finally:
        sys.path.pop(0)
    payload = {"fig8": {"urw": {"us_per_call": 1.5, "derived": "x"}},
               "walks_per_sec": {"urw": {"jnp": 1e6}}}
    mutate(payload)
    problems = validate_payload(payload)
    assert problems and any(expect in p for p in problems)
