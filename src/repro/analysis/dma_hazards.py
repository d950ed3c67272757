"""DMA hazard pass: prove every declared kernel schedule pipeline-safe.

Input is the ``dma_schedule()`` declaration each Pallas kernel exports
(`kernels/common.DmaOp` sequences in program order — the double-buffered
gather loops, the fused kernel's ping-pong chunk loop, the delayed-wait
path write-back, and segment-sum's output-block visit sequence).  The
checker is a single forward scan holding per-``(buffer, slot)`` state:

  * **read-before-arrival** — a ``read`` is legal only when the latest
    copy issued on its slot has been waited (and some copy ever filled
    the slot);
  * **overwrite-while-in-flight** — a ``start`` or ``write`` on a slot
    with an un-waited copy clobbers data the DMA engine is still moving
    (inbound: partially-arrived gather; outbound: a store still being
    streamed home);
  * **malformed wait** — a ``wait`` must name the copy currently in
    flight on its slot (waiting a never-started / already-waited /
    wrong-slot copy means the semaphore accounting is off by one);
  * **un-drained copy** — every copy started must be waited before the
    kernel returns (Pallas semaphores must balance per launch);
  * **phantom copy** — a ``start`` targeting an SMEM-resident buffer (one
    the schedule reads with ``tier="smem"``, or tagged so itself).  The
    cached gather hierarchy's whole point is that hit paths issue *no*
    DMA — a copy into cache-tier storage means a hit path still went to
    HBM, silently erasing the latency win while staying bit-identical.

``read`` ops with ``tier="smem"`` are cache-hit probes/payload reads:
they touch on-chip memory only, so no dominating wait is required and
they participate in no slot state.

For the grid-scheduled `segment_sum` (no explicit DMAs) the same scan
checks the Pallas TPU output-revisit contract over ``visit`` ops:
revisits of an output block must be **consecutive** (the data-dependent
``index_map`` may not return to a block it left), and the declared
``first_visit`` flag must be set on exactly the first visit of each
block (it selects zero-init vs accumulate).

Because every loop in the kernels is slot-periodic with period 2, the
small unrolls the emitters use (n ≥ 3) exhaust the reachable state
space — the scan is a proof, not a sampling.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from repro.analysis.report import Finding
from repro.kernels.common import DmaOp

Slot = Tuple[str, int]


def check_schedule(ops: Sequence[DmaOp], name: str = "kernel"
                   ) -> List[Finding]:
    """Forward-scan hazard check of one declared DMA schedule."""
    findings = []
    in_flight: Dict[Slot, int] = {}   # slot -> un-waited copy id
    copy_slot: Dict[int, Slot] = {}   # copy id -> slot it was issued on
    filled: Dict[Slot, bool] = {}     # slot has waited-arrived contents
    visits: List[DmaOp] = []

    def flag(i, op, msg):
        findings.append(Finding("dma", f"{name}[{i}]", f"{op.kind} "
                                f"{op.buffer}/slot{op.slot}: {msg}"))

    # Buffers the schedule declares SMEM-resident (cache-tier): any read
    # at tier="smem" marks its buffer as on-chip for the whole schedule.
    smem_bufs = {op.buffer for op in ops
                 if getattr(op, "tier", "hbm") == "smem"}

    for i, op in enumerate(ops):
        slot = (op.buffer, op.slot)
        if op.kind == "read" and getattr(op, "tier", "hbm") == "smem":
            continue  # on-chip read: no DMA, no slot state
        if op.kind == "start":
            if op.tier == "smem" or op.buffer in smem_bufs:
                flag(i, op, "DMA start into an SMEM-resident cache buffer "
                            "(phantom copy) — cached hit paths must serve "
                            "from on-chip memory without issuing copies")
                continue
            if slot in in_flight:
                flag(i, op, f"re-issued while copy {in_flight[slot]} is "
                            f"still un-waited (overwrite-while-in-flight)"
                            f" — wait the prior copy before reusing the "
                            f"slot")
            in_flight[slot] = op.copy
            copy_slot[op.copy] = slot
            filled[slot] = False
        elif op.kind == "wait":
            if op.copy not in copy_slot:
                flag(i, op, f"waits copy {op.copy} that was never "
                            f"started")
            elif copy_slot[op.copy] != slot:
                b, s = copy_slot[op.copy]
                flag(i, op, f"waits copy {op.copy} on the wrong slot "
                            f"(started on {b}/slot{s})")
            elif in_flight.get(slot) != op.copy:
                flag(i, op, f"waits copy {op.copy} which is not in "
                            f"flight there (already waited, or a newer "
                            f"copy {in_flight.get(slot)} superseded it)")
            else:
                del in_flight[slot]
                filled[slot] = True
        elif op.kind == "read":
            if slot in in_flight:
                flag(i, op, f"read while copy {in_flight[slot]} is "
                            f"un-waited (read-before-arrival) — insert "
                            f"the copy-wait before consuming the slot")
            elif not filled.get(slot, False):
                flag(i, op, "read of a slot no waited copy ever filled "
                            "(read-before-arrival)")
        elif op.kind == "write":
            if slot in in_flight:
                flag(i, op, f"overwritten while copy {in_flight[slot]} "
                            f"is un-waited (overwrite-while-in-flight) — "
                            f"reclaim the staging slot with its delayed "
                            f"wait first")
            filled[slot] = True
        elif op.kind == "visit":
            visits.append(op)
        else:
            flag(i, op, f"unknown op kind {op.kind!r}")

    for slot, cid in sorted(in_flight.items()):
        findings.append(Finding(
            "dma", f"{name}[end]",
            f"copy {cid} on {slot[0]}/slot{slot[1]} never waited — "
            f"drain all outstanding copies before the kernel returns"))
    findings += _check_visits(visits, name)
    return findings


def _check_visits(visits: Sequence[DmaOp], name: str) -> List[Finding]:
    """Output-revisit contract over ``visit`` ops (grid-order block
    sequence with declared first/live flags)."""
    findings = []
    closed = set()    # blocks already left
    initialized = set()
    current = None
    for i, op in enumerate(visits):
        block = op.slot
        site = f"{name}.visit[{i}]"
        if block != current:
            if current is not None:
                closed.add(current)
            if block in closed:
                findings.append(Finding(
                    "dma", site,
                    f"output block {block} revisited non-consecutively "
                    f"(left after an earlier visit) — Pallas revisits "
                    f"must be consecutive; sort segments / fix the "
                    f"index_map clamp"))
            current = block
        if op.first:
            if block in initialized:
                findings.append(Finding(
                    "dma", site,
                    f"first_visit set on a revisit of block {block} — "
                    f"would zero a partially accumulated output block"))
            initialized.add(block)
        elif op.live and block not in initialized:
            findings.append(Finding(
                "dma", site,
                f"live accumulation into block {block} before any "
                f"first_visit zero-init — reads uninitialized output"))
    return findings


def kernel_schedules():
    """Name → declared-op-list for every kernel in the tree (imported
    lazily so the pass stays usable without the full kernel deps)."""
    from repro.kernels.embedding_bag.embedding_bag import \
        dma_schedule as eb_schedule
    from repro.kernels.fused_superstep.fused_superstep import \
        dma_schedule as fused_schedule
    from repro.kernels.segment_sum.segment_sum import \
        dma_schedule as ss_schedule
    from repro.kernels.walk_step.walk_step import \
        dma_schedule as ws_schedule

    schedules = {}
    for kind in ("uniform", "alias"):
        schedules[f"walk_step.{kind}"] = ws_schedule(kind)
    for kind in ("uniform", "alias", "metapath", "rejection_n2v",
                 "reservoir_n2v"):
        schedules[f"fused_superstep.{kind}"] = fused_schedule(kind)
        # Cached variant: the fully-hit representative superstep — cache
        # probes and payload reads at tier="smem", HBM loops only where
        # the hierarchy cannot serve (v_prev-keyed state, write-back).
        schedules[f"fused_superstep.{kind}.cached"] = fused_schedule(
            kind, cached=True)
    schedules["embedding_bag"] = eb_schedule()
    schedules["segment_sum"] = ss_schedule()
    return schedules


def check_repo() -> List[Finding]:
    findings = []
    for name, ops in kernel_schedules().items():
        findings += check_schedule(ops, name)
    return findings
