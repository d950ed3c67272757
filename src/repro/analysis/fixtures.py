"""Deliberately broken inputs proving each pass actually catches its
hazard class.

Each fixture builds a *mutated* copy of a real declaration (a valid
phase program with one phase moved, a valid schedule with one wait
dropped, …) and runs the single pass that owns the invariant.  The CLI
(``python -m repro.analysis --fixture NAME``) exits non-zero when
findings are produced — CI asserts every fixture trips, so a checker
regression that silently stops detecting a hazard class fails the
build, not a code review.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

from repro.analysis import determinism, dma_hazards, residency, \
    rng_collisions
from repro.analysis.report import Finding
from repro.core.phase_program import DrawStream, _default_spec, lower
from repro.core.rng import SALT_CHUNK0, SALT_COLUMN
from repro.kernels.common import DmaOp
from repro.kernels.walk_step.walk_step import dma_schedule as ws_schedule


def _replace_phase(prog, i, **changes):
    phases = list(prog.phases)
    phases[i] = dataclasses.replace(phases[i], **changes)
    return dataclasses.replace(prog, phases=tuple(phases))


# ----------------------------------------------------------- rng fixtures


def rng_duplicate_salt() -> List[Finding]:
    """Two scalar streams of one task on the same salt channel — e.g. a
    second draw phase added without registering a new salt."""
    streams = (DrawStream("fixture.draw_a", SALT_COLUMN, 2),
               DrawStream("fixture.draw_b", SALT_COLUMN, 1))
    return rng_collisions.check_streams(streams, context="fixture")


def rng_chunk_overlap() -> List[Finding]:
    """A scalar stream salted inside the open-ended chunk family — the
    chunk-c draw with c = salt - SALT_CHUNK0 collides with it."""
    streams = (DrawStream("fixture.reservoir", SALT_CHUNK0, 64,
                          family=True),
               DrawStream("fixture.extra", SALT_CHUNK0 + 3, 4))
    return rng_collisions.check_streams(streams, context="fixture")


def rng_corpus_salt_reuse() -> List[Finding]:
    """The corpus-ring negatives draw put back on a walk channel — the
    defect the SALT_NEGATIVE registration exists to prevent.  Consumer
    batches fold (qid=batch element, hop=grad step) under the round-0
    stream key, the very tuples walk tasks fold, so a consumer stream on
    SALT_COLUMN collides with the uniform sampler's column draw."""
    streams = rng_collisions.spec_streams(_default_spec("uniform"))
    streams += (DrawStream("fixture.corpus_negatives", SALT_COLUMN, 5),)
    return rng_collisions.check_streams(streams, context="fixture")


def rng_literal_salt() -> List[Finding]:
    """A call site passing a raw integer salt the registry never saw."""
    src = ("from repro.core import rng as task_rng\n"
           "def f(base_key, qid, hop):\n"
           "    return task_rng.task_uniforms(base_key, qid, hop, 2, 5)\n")
    return rng_collisions.check_source(src, "fixture/literal_salt.py")


# ----------------------------------------------------------- dma fixtures


def dma_missing_wait() -> List[Finding]:
    """A gather loop with one copy-wait dropped: the read consumes the
    slot while its copy is still in flight (read-before-arrival), and
    the copy is never drained."""
    ops = [op for op in ws_schedule("uniform")
           if not (op.kind == "wait" and op.buffer == "rpbuf"
                   and op.copy == 1)]
    return dma_hazards.check_schedule(ops, "fixture.missing_wait")


def dma_overwrite_in_flight() -> List[Finding]:
    """Ping-pong slots swapped to a single slot: copy i+1 re-issues the
    slot copy i still occupies (overwrite-while-in-flight)."""
    ops = [op._replace(slot=0) if op.buffer == "colbuf" else op
           for op in ws_schedule("uniform")]
    return dma_hazards.check_schedule(ops, "fixture.overwrite")


def dma_undrained() -> List[Finding]:
    """A trailing prefetch with no drain before the kernel returns."""
    ops = list(ws_schedule("uniform"))
    ops.append(DmaOp("start", "colbuf", 0, copy=999))
    return dma_hazards.check_schedule(ops, "fixture.undrained")


def dma_cached_phantom_copy() -> List[Finding]:
    """A cached gather op that still issues an HBM copy on the hit path:
    the cache probe resolved the vertex on-chip, yet the emitter started
    a DMA into the cache-tier column buffer anyway.  Bit-identical in
    result (the same bytes arrive) but the latency win is gone — exactly
    the silent regression the phantom-copy rule exists to trip."""
    from repro.kernels.fused_superstep.fused_superstep import \
        dma_schedule as fused_schedule
    ops = list(fused_schedule("uniform", cached=True))
    hit = next(i for i, op in enumerate(ops)
               if op.kind == "read" and op.tier == "smem"
               and op.buffer == "cache.col")
    ops.insert(hit, DmaOp("start", "cache.col", 0, copy=990))
    return dma_hazards.check_schedule(ops, "fixture.cached_phantom")


def visit_nonconsecutive() -> List[Finding]:
    """segment-sum visiting a block, leaving it, then returning — the
    revisit contract an unsorted segment vector would break."""
    ops = [DmaOp("visit", "out", 0, first=True),
           DmaOp("visit", "out", 1, first=True),
           DmaOp("visit", "out", 0, first=False)]
    return dma_hazards.check_schedule(ops, "fixture.nonconsecutive")


def visit_bad_first() -> List[Finding]:
    """first_visit set on a revisit — would zero a partial accumulation."""
    ops = [DmaOp("visit", "out", 0, first=True),
           DmaOp("visit", "out", 0, first=True)]
    return dma_hazards.check_schedule(ops, "fixture.bad_first")


# ----------------------------------------------------- residency fixtures


def residency_vprev_draw() -> List[Finding]:
    """A single_phase program with its draw moved to owner(v_prev) —
    the interpreter has no superstep to run it in."""
    prog = _replace_phase(lower(_default_spec("uniform")), 0,
                          residency="v_prev")
    return residency.check_program(prog)


def residency_missing_carry() -> List[Finding]:
    """A two_phase program whose carry was dropped: the verify superstep
    at owner(v_prev) would receive no candidate payload."""
    prog = dataclasses.replace(lower(_default_spec("rejection_n2v")),
                               carry="none")
    return residency.check_program(prog)


# --------------------------------------------------- determinism fixtures


def determinism_jax_random() -> List[Finding]:
    """An ambient jax.random draw inside the deterministic tree."""
    src = ("import jax\n"
           "def sample(key, n):\n"
           "    return jax.random.uniform(key, (n,))\n")
    return determinism.check_source(src, "fixture/ambient_random.py")


def determinism_no_interpret() -> List[Finding]:
    """A pallas_call wrapper with no interpret plumbing."""
    src = ("from jax.experimental import pallas as pl\n"
           "def launch(x):\n"
           "    return pl.pallas_call(lambda r, o: None)(x)\n")
    return determinism.check_source(src, "fixture/no_interpret.py")


def determinism_tune_clock() -> List[Finding]:
    """A wall-clock read leaking out of tune/measure.py into the rest of
    the autotuner — e.g. the candidate space or cost model timing itself.
    Only measure.py may touch the clock; everything the compile path
    imports (space, model, cache, tuner) must stay replayable."""
    src = ("import time\n"
           "def knob_grid():\n"
           "    t0 = time.perf_counter()\n"
           "    return [2 ** k for k in range(5)], t0\n")
    return determinism.check_source(src, "fixture/tune/space.py")


FIXTURES: Dict[str, Callable[[], List[Finding]]] = {
    "rng-duplicate-salt": rng_duplicate_salt,
    "rng-chunk-overlap": rng_chunk_overlap,
    "rng-corpus-salt-reuse": rng_corpus_salt_reuse,
    "rng-literal-salt": rng_literal_salt,
    "dma-missing-wait": dma_missing_wait,
    "dma-overwrite-in-flight": dma_overwrite_in_flight,
    "dma-undrained": dma_undrained,
    "dma-cached-phantom-copy": dma_cached_phantom_copy,
    "visit-nonconsecutive": visit_nonconsecutive,
    "visit-bad-first": visit_bad_first,
    "residency-vprev-draw": residency_vprev_draw,
    "residency-missing-carry": residency_missing_carry,
    "determinism-jax-random": determinism_jax_random,
    "determinism-no-interpret": determinism_no_interpret,
    "determinism-tune-clock": determinism_tune_clock,
}


def run_fixture(name: str) -> List[Finding]:
    return FIXTURES[name]()
