"""What the program itself records in a profiler trace, beside what
``bench/trace.py`` reduces.

* The program's spans (``walker.*`` in ``Walker.run``, ``serve.*`` in
  ``WalkService.step``, ``stream.*`` in the stream's ``advance``) with
  their metadata: ``request_id`` on ``serve.harvest``, and on
  ``serve.step`` the step's share of the service's counters (``chunks``,
  ``syncs``: blocking device-to-host reads).
* The named scope of every device operation: XLA writes each op's
  ``tf_op`` path (``jit(run)/while/body/walk.record/scatter:``) into the
  op's metadata in the trace.  ``jax.profiler.ProfileData`` does not expose
  the stats of event metadata, so they are read from the raw ``XSpace``
  by a small walk over the protobuf wire format (no ``tensorflow``).

A traced run of ``bench/run.py`` leaves its trace in ``bench/.traces``; a
reader finds it there, or at the view's ``xplane`` where a test names one.
The readers of one run share what is parsed: it is kept in the view.

    python3 -m bench.program_trace [trace.xplane.pb | dir]

prints the device idle time by span (the program's spans nested in the
benchmark's) and each engine program's leaf-op seconds by scope.
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np

from bench.registry import BENCH
from bench.trace import (DEVICE_PLANE, SPANS, Trace, coverage, find,
                         union)

TRACE_DIR = BENCH / ".traces"   # where bench/run.py writes a traced run
# Outer spans before the spans nested in them; all nest inside the
# benchmark's own spans (``SPANS``).
PROGRAM_SPANS = ("walker.bind", "walker.engine", "walker.assemble",
                 "serve.step", "serve.admit", "serve.advance",
                 "stream.dispatch", "stream.sync", "serve.done_mask",
                 "serve.harvest", "serve.release")
SCOPES = ("walk.sample", "walk.commit", "walk.refill", "walk.record")

# ------------------------------------------------ protobuf wire format
# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_MD_NAME, _MD_STATS = 2, 5                      # XEventMetadata
_STAT_MD_ID, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7
_STAT_NAME = 2                                  # XStatMetadata


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def encode_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    """One field in the wire format: a varint for an int, else a
    length-delimited field of the bytes (or the UTF-8 of the str)."""
    if isinstance(value, int):
        return encode_varint(num << 3) + encode_varint(value)
    value = value.encode() if isinstance(value, str) else bytes(value)
    return encode_varint(num << 3 | 2) + encode_varint(len(value)) + value


def parts(buf):
    """``(field number, value, raw bytes)`` of each field of one message:
    the value an int for a varint, a memoryview for a length-delimited
    field, bytes for a fixed one; the raw bytes the whole field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        start = i
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, val, buf[start:i]


def fields(buf):
    """``(field number, value)`` of one message, as :func:`parts`."""
    for num, val, _ in parts(buf):
        yield num, val


def _map_entry(buf):
    key = value = None
    for num, val in fields(buf):
        if num == _MAP_KEY:
            key = val
        elif num == _MAP_VALUE:
            value = val
    return key, value


def op_paths(xspace: bytes) -> list[dict]:
    """For each TPU device plane, in the order of the trace (that of
    ``Trace.devices``): ``{(program id, op name): tf_op path}``, the op
    name as ``ProfileData`` gives it (the HLO text) and the program id as
    the ``XLA Modules`` line gives it (``jit_run(<id>)``)."""
    out = []
    for num, plane in fields(xspace):
        if num != _SPACE_PLANES:
            continue
        name, event_md, stat_md = "", [], {}
        for pnum, val in fields(plane):
            if pnum == _PLANE_NAME:
                name = bytes(val).decode()
            elif pnum == _PLANE_EVENT_MD:
                event_md.append(val)
            elif pnum == _PLANE_STAT_MD:
                key, md = _map_entry(val)
                stat_md[key] = next((bytes(v).decode() for n, v in fields(md)
                                     if n == _STAT_NAME), "")
        if not DEVICE_PLANE.match(name):
            continue
        want = {k: v for k, v in stat_md.items()
                if v in ("tf_op", "program_id")}
        paths = {}
        for entry in event_md:
            _, md = _map_entry(entry)
            op, stats = "", {}
            for mnum, val in fields(md):
                if mnum == _MD_NAME:
                    op = bytes(val).decode()
                elif mnum == _MD_STATS:
                    stat = dict(fields(val))
                    key = want.get(stat.get(_STAT_MD_ID))
                    if key == "program_id":
                        stats[key] = stat.get(_STAT_UINT, stat.get(_STAT_INT))
                    elif key == "tf_op":
                        if _STAT_STR in stat:
                            stats[key] = bytes(stat[_STAT_STR]).decode()
                        elif _STAT_REF in stat:
                            stats[key] = stat_md.get(stat[_STAT_REF], "")
            if "tf_op" in stats:
                paths[(stats.get("program_id"), op)] = stats["tf_op"]
        out.append(paths)
    return out


def components(path: str) -> set:
    """The scope components of a ``tf_op`` path (``name:type``)."""
    return set(path.rsplit(":", 1)[0].split("/"))


def leaves(ops: np.ndarray) -> np.ndarray:
    """Which ``(start, end)`` rows of one line contain no other row: ops
    on a line nest (a ``while`` op spans its body), so a row is a parent
    exactly when the next row by start (the longer first) lies in it."""
    order = np.lexsort((-ops[:, 1], ops[:, 0]))
    s = ops[order]
    parent = np.zeros(len(s), bool)
    parent[:-1] = (s[1:, 0] < s[:-1, 1]) & (s[1:, 1] <= s[:-1, 1])
    leaf = np.empty(len(s), bool)
    leaf[order] = ~parent
    return leaf


# ------------------------------------------------------------ the view


_PROGRAM_ID = re.compile(r"\((\d+)\)$")


class ProgramTrace:
    """The program's spans and the scopes of the device ops of one traced
    window (``trace``, the benchmark's reduction of the same file)."""

    def __init__(self, xspace: bytes, trace: Trace):
        from jax.profiler import ProfileData
        self.trace = trace
        self.paths = op_paths(xspace)
        if len(self.paths) != len(trace.devices):
            raise ValueError("the trace's device planes do not match")
        self._spans = defaultdict(list)
        self._leaves = {}
        for plane in ProfileData.from_serialized_xspace(xspace).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#", 1)[0]
                    if name in PROGRAM_SPANS:
                        self._spans[name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             dict(ev.stats)))

    @classmethod
    def from_file(cls, path, trace: Trace | None = None) -> "ProgramTrace":
        return cls(Path(path).read_bytes(), trace or Trace.from_file(path))

    @classmethod
    def of(cls, run) -> "ProgramTrace":
        """The view's program trace, parsed once for all its readers."""
        if "program_trace" not in run:
            path = run.get("xplane") or find(TRACE_DIR)
            run["program_trace"] = cls.from_file(path, run["trace"])
        return run["program_trace"]

    # ------------------------------------------------------------ spans

    def spans(self, name: str) -> tuple[np.ndarray, list]:
        """``(start, end)`` rows (ns) and metadata of the program's span
        ``name`` that lie inside the window."""
        lo, hi = self.trace.lo, self.trace.hi
        rows = [r for r in self._spans.get(name, ()) if lo <= r[0]
                and r[1] <= hi]
        iv = np.asarray([r[:2] for r in rows], np.float64).reshape(-1, 2)
        return iv, [r[2] for r in rows]

    def host_s(self, name: str) -> np.ndarray:
        """Seconds of each span ``name`` in the window less the device
        busy time inside it."""
        iv, _ = self.spans(name)
        return np.asarray([(e - s) * 1e-9 - self.trace.busy_in(s, e)
                           for s, e in iv])

    def syncs_per_step(self) -> float | None:
        """Blocking device-to-host reads per service step that ran a chunk,
        from the ``chunks`` and ``syncs`` metadata of the window's
        ``serve.step`` spans; None where the service puts none there."""
        meta = [m for m in self.spans("serve.step")[1]
                if "syncs" in m and "chunks" in m]
        chunks = sum(int(m["chunks"]) for m in meta)
        if chunks == 0:
            return None
        return sum(int(m["syncs"]) for m in meta) / chunks

    def idle_by_span(self, k: int = 20) -> list:
        """``Trace.idle_by_span`` over the benchmark's spans and the
        program's nested in them: each idle gap goes to the innermost span
        that covers at least half of it, else to the span that covers most
        of it, else to ``other``."""
        tr = self.trace
        gaps = tr.gaps()
        length = gaps[:, 1] - gaps[:, 0]
        named = [(n, tr.span(n)) for n in SPANS]
        named += [(n, self.spans(n)[0]) for n in PROGRAM_SPANS]
        named = [(n, iv) for n, iv in named if iv.shape[0]]
        inner = np.full(gaps.shape[0], -1)
        most = np.full(gaps.shape[0], -1)
        most_cover = np.zeros(gaps.shape[0])
        for i, (_, iv) in enumerate(named):
            u = union(iv)
            c = coverage(u, gaps[:, 1]) - coverage(u, gaps[:, 0])
            inner = np.where(c >= 0.5 * length, i, inner)
            take = c > most_cover
            most = np.where(take, i, most)
            most_cover = np.where(take, c, most_cover)
        best = np.where(inner >= 0, inner, most)
        tot = defaultdict(float)
        for i, g in zip(best.tolist(), length):
            tot[named[i][0] if i >= 0 else "other"] += g * 1e-9
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]

    # ----------------------------------------------------------- scopes

    def _leaf_ops(self, device: int, program: str) -> list:
        """Leaf ops of ``program`` on one device, clipped to the window:
        ``(seconds, tf_op path)`` each.  An op is a leaf when it contains
        no other op of the ``XLA Ops`` line (a ``while`` op spans its
        body); an op belongs to the program whose module it starts in."""
        if (device, program) not in self._leaves:
            self._leaves[device, program] = self._find_leaves(device, program)
        return self._leaves[device, program]

    def _find_leaves(self, device: int, program: str) -> list:
        d, tr = self.trace.devices[device], self.trace
        ops = d["ops"]
        pat = re.compile(rf"^{re.escape(program)}(\(|\.|$)")
        mods = sorted((s, e, n) for n, s, e in d["modules"] if pat.match(n))
        if not mods or ops.shape[0] == 0:
            return []
        leaf = leaves(ops)
        m_start = np.asarray([m[0] for m in mods])
        m_end = np.asarray([m[1] for m in mods])
        j = np.searchsorted(m_start, ops[:, 0], side="right") - 1
        inside = (j >= 0) & (ops[:, 0] < m_end[np.maximum(j, 0)])
        lo, hi = tr.lo, tr.hi
        sec = (np.clip(ops[:, 1], lo, hi) - np.clip(ops[:, 0], lo, hi)) * 1e-9
        paths = self.paths[device]
        pids = [int(m.group(1)) if (m := _PROGRAM_ID.search(n[2])) else None
                for n in mods]
        out = []
        for i in np.flatnonzero(leaf & inside & (sec > 0)):
            name = d["op_names"][i]
            out.append((float(sec[i]),
                        paths.get((pids[j[i]], name), "")))
        return out

    def scope_split(self, program: str) -> dict:
        """Seconds of ``program``'s leaf ops by superstep scope, with the
        ops of no scope as ``unscoped``, and in all as ``total``."""
        out = dict.fromkeys((*SCOPES, "unscoped", "total"), 0.0)
        n = len(self.trace.devices)
        for dev in range(n):
            for sec, path in self._leaf_ops(dev, program):
                hit = [s for s in SCOPES if s in components(path)]
                out[hit[0] if hit else "unscoped"] += sec / n
                out["total"] += sec / n
        return out


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    target = Path(args[0]) if args else TRACE_DIR
    path = find(target) if target.is_dir() else target
    pt = ProgramTrace.from_file(path)
    programs = sorted({re.sub(r"\(.*", "", n) for d in pt.trace.devices
                       for n, _, _ in d["modules"]})
    print(json.dumps({
        "window_s": pt.trace.window_s, "busy_s": pt.trace.busy_s,
        "idle_by_span": pt.idle_by_span(),
        "scopes": {p: pt.scope_split(p) for p in programs},
        "spans": {n: len(pt.spans(n)[0]) for n in PROGRAM_SPANS}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
