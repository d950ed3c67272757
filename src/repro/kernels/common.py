"""Shared kernel-wrapper plumbing + the declarative DMA-schedule IR.

Every Pallas kernel in this tree that issues asynchronous copies also
*emits* its DMA schedule as data (a ``dma_schedule()`` function next to
the kernel): a flat sequence of :class:`DmaOp` records — copy start,
copy wait, buffer-slot read/write — in the kernel's program order.  The
static analyzer (`repro.analysis.dma_hazards`) builds the dependence
relation over that sequence and proves the two async-pipeline safety
properties RidgeWalker's "perfect pipelining" rests on:

  * every **read** of a staging slot is dominated by the **wait** of the
    copy that filled it (no read-before-arrival), and
  * no slot is **re-issued or overwritten** while a prior copy on it is
    still un-waited (no overwrite-while-in-flight), and every copy is
    drained before the kernel returns.

Double-buffered loops are periodic with period 2 (the slot cycle), so a
schedule unrolled for n ≥ 3 iterations covers every steady-state slot
interaction plus the prologue and drain — the emitters below default to
small unroll counts on that argument.

The `ScheduleBuilder` emitters mirror the generic loop shapes
(`walk_step.row_access_loop`/`gather1_loop`/`gather2_loop`, the fused
kernel's ping-pong chunk loop and per-superstep record flush); each kernel
composes them into its full schedule.  Keep an emitter and its loop in
the same module so a pipeline change and its declared schedule travel in
one diff.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Sequence, Tuple

import jax


class DmaOp(NamedTuple):
    """One event of a kernel's declared DMA schedule, in program order.

    ``kind``:
      * ``start`` — an async copy (id ``copy``) begins on ``(buffer,
        slot)``; the slot is busy until the matching ``wait``.
      * ``wait``  — the copy ``copy`` on ``(buffer, slot)`` completes.
      * ``read``  — kernel arithmetic consumes ``(buffer, slot)``; legal
        only if the latest inbound copy on the slot has been waited.
      * ``write`` — kernel arithmetic overwrites ``(buffer, slot)`` (the
        write-back staging pattern); legal only with no copy in flight
        on the slot.
      * ``visit`` — an output-block visit (grid-scheduled kernels like
        `segment_sum`, which revisit output blocks instead of issuing
        explicit DMAs); ``slot`` is the block id, ``first`` flags the
        declared init-vs-accumulate bit, ``live`` whether the visit
        actually accumulates.

    ``tier`` names the memory tier the op touches: ``"hbm"`` (the
    default — every async-copy staging buffer) or ``"smem"`` for the
    hot-vertex cache block, which is launch-resident in SMEM and therefore
    never the target of a copy.  A ``read`` with ``tier="smem"`` needs no
    dominating wait (the data is always resident); a ``start`` on an smem
    buffer is by definition a *phantom copy* — a hit path issuing HBM
    traffic it was built to avoid — and the DMA pass flags it.
    """

    kind: str
    buffer: str
    slot: int
    copy: int = -1
    first: bool = False
    live: bool = True
    tier: str = "hbm"


class ScheduleBuilder:
    """Accumulates a kernel's :class:`DmaOp` sequence with globally
    unique copy ids (buffers are reused across loop instances — ids must
    not be)."""

    def __init__(self):
        self.ops: list[DmaOp] = []
        self._next_copy = 0

    # ---------------------------------------------------------- primitives

    def start(self, buffer: str, slot: int) -> int:
        cid = self._next_copy
        self._next_copy += 1
        self.ops.append(DmaOp("start", buffer, slot, cid))
        return cid

    def wait(self, buffer: str, slot: int, copy: int) -> None:
        self.ops.append(DmaOp("wait", buffer, slot, copy))

    def read(self, buffer: str, slot: int, tier: str = "hbm") -> None:
        self.ops.append(DmaOp("read", buffer, slot, tier=tier))

    def cache_read(self, buffer: str) -> None:
        """A hit-path read of the on-chip hot-vertex cache: no
        copy, no wait — the declarative record of "this gather issued no
        HBM traffic" that the DMA pass verifies cached schedules by."""
        self.read(buffer, 0, tier="smem")

    def write(self, buffer: str, slot: int) -> None:
        self.ops.append(DmaOp("write", buffer, slot))

    def visit(self, buffer: str, block: int, first: bool,
              live: bool = True) -> None:
        self.ops.append(DmaOp("visit", buffer, block, first=first,
                              live=live))

    # ------------------------------------------------------------ patterns

    def gather_loop(self, buffer: str, n: int = 3) -> None:
        """The double-buffered gather shape shared by `row_access_loop` /
        `gather1_loop` / `gather2_loop`: ``start(0)``; per item *i*,
        prefetch *i+1* into the other slot, then wait and consume *i*."""
        if n <= 0:
            return
        pend = {0: self.start(buffer, 0)}
        for i in range(n):
            if i + 1 < n:
                pend[i + 1] = self.start(buffer, (i + 1) % 2)
            self.wait(buffer, i % 2, pend.pop(i))
            self.read(buffer, i % 2)

    def pingpong_loop(self, buffers: Sequence[str], n: int = 3,
                      reads_per_chunk: int = 1) -> None:
        """The fused kernel's chunk-loop shape: several buffers (column +
        weight) advance through the same slot cycle together, chunk c+1's
        copies in flight while chunk c is consumed ``reads_per_chunk``
        times (the E-S fold reads the staged chunk once per position
        group)."""
        if n <= 0:
            return
        pend = {0: [(b, self.start(b, 0)) for b in buffers]}
        for c in range(n):
            if c + 1 < n:
                pend[c + 1] = [(b, self.start(b, (c + 1) % 2))
                               for b in buffers]
            for b, cid in pend.pop(c):
                self.wait(b, c % 2, cid)
            for _ in range(reads_per_chunk):
                for b in buffers:
                    self.read(b, c % 2)

    def flush_loop(self, buffer: str, n: int = 3) -> None:
        """The fused kernel's per-superstep record flush: one staging
        slot, rewritten once per superstep after the previous flush out
        of it has been waited (the flush overlaps the next superstep's
        gathers), and drained at the end of the launch."""
        pend = None
        for _ in range(n):
            if pend is not None:
                self.wait(buffer, 0, pend)
            self.write(buffer, 0)
            pend = self.start(buffer, 0)
        if pend is not None:
            self.wait(buffer, 0, pend)


def schedule_buffers(ops: Sequence[DmaOp]) -> Tuple[str, ...]:
    """Distinct buffer names referenced by a schedule, in first-use
    order (the docs table and diagnostics name buffers with this)."""
    seen: dict[str, None] = {}
    for op in ops:
        seen.setdefault(op.buffer)
    return tuple(seen)


def default_interpret(interpret: bool | None) -> bool:
    """Resolve a wrapper's per-call ``interpret`` override.

    ``None`` (the default everywhere) means: compile the Pallas kernel on
    a TPU backend, interpret its body elsewhere — so the same call sites
    exercise the real kernels on hardware while CPU CI keeps validating
    them in interpret mode.  Pass an explicit bool to force either.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# --------------------------------------------------------------------------
# Row-tiled HBM layout for scalar gathers.
#
# Mosaic refuses DMAs of one or two words out of a 1-D HBM array (a 1-D
# slice must be a multiple of the 1024-word tiling), and out of an (N, 1)
# column.  It accepts whole 128-lane rows of an (R, 128) array at any
# dynamic row.  So every array the walk kernels gather single words from
# is laid out as (R, 128) rows: a gather copies the row(s) holding the
# word(s) into an SMEM staging slot and picks the word out — flat word
# ``e`` lives at ``[e // LANES, e % LANES]``, the same address arithmetic
# as the 1-D array, so every gathered value is unchanged.
# --------------------------------------------------------------------------

LANES = 128


def span_rows(width: int) -> int:
    """Rows a ``width``-word window spans at the worst alignment (its
    first word at lane 127)."""
    return (int(width) + LANES - 2) // LANES + 1


def as_rows(x, extra_rows: int = 2):
    """Flatten ``x`` into ``(R, LANES)`` rows with at least ``extra_rows``
    rows of zero padding past the last word, so a fixed-size window DMA
    that starts at any valid word stays inside the array."""
    import jax.numpy as jnp
    flat = jnp.ravel(x)
    n = flat.shape[0]
    rows = -(-n // LANES) + int(extra_rows)
    return jnp.pad(flat, (0, rows * LANES - n)).reshape(rows, LANES)


@partial(jax.tree_util.register_dataclass,
         data_fields=["row_ptr", "col", "weights", "alias_prob", "alias_idx",
                      "type_offsets"],
         meta_fields=["num_vertices", "num_edges"])
@dataclasses.dataclass(frozen=True)
class GraphRows:
    """A graph's gather operands in the row layout, built once per run by
    :func:`graph_rows` — outside the engine's superstep loop — and shared
    by the walk kernels (`walk_step`, `fused_superstep`).  An array the
    graph lacks is a one-row zero placeholder, so the kernels' operand
    lists keep one shape."""

    row_ptr: Any
    col: Any
    weights: Any
    alias_prob: Any
    alias_idx: Any
    type_offsets: Any
    num_vertices: int
    num_edges: int


def graph_rows(graph, window: int = 1) -> GraphRows:
    """Lay out ``graph``'s CSR arrays as rows.  ``window`` is the widest
    run of consecutive words one DMA copies out of ``col``/``weights``
    (the reservoir chunk); they get the padding rows such a window can
    overrun."""
    import jax.numpy as jnp
    pad = max(2, span_rows(window))

    def rows(x, dtype, extra=2):
        return as_rows(jnp.zeros((1,), dtype) if x is None else x, extra)

    return GraphRows(
        row_ptr=rows(graph.row_ptr, jnp.int32),
        col=rows(graph.col, jnp.int32, pad),
        weights=rows(graph.weights, jnp.float32, pad),
        alias_prob=rows(graph.alias_prob, jnp.float32),
        alias_idx=rows(graph.alias_idx, jnp.int32),
        type_offsets=rows(graph.type_offsets, jnp.int32),
        num_vertices=int(graph.row_ptr.shape[0]) - 1,
        num_edges=int(graph.col.shape[0]))


def flat_words(rows, n: int):
    """The first ``n`` words of a row-tiled array, as a 1-D array."""
    return rows.reshape(-1)[:n]
