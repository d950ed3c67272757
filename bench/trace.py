"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* Device busy time is the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
  inside the benchmark's ``window`` span, averaged over the devices.
* Program time is the device time of one jitted program, keyed by its
  stable name: ``jit_<function>`` on the ``XLA Modules`` line.
* Idle gaps, the stretches of the window with no device operation, are
  attributed to the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  (``batch``, ``walker.run``, ``fetch``, ``svc.step``, ``svc.submit``,
  ``wait``): each gap goes to the innermost span that covers at least
  half of it, else to the span that covers most of it, else to ``other``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
# Outer spans before the spans nested in them.
SPANS = ("batch", "walker.run", "fetch", "svc.step", "svc.submit", "wait")


def find(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``(start, end)`` rows into disjoint sorted intervals."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    if iv.shape[0] == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [iv.shape[0] - 1]])
    return np.stack([iv[new, 0], reach[last]], axis=1)


def coverage(disjoint: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of ``(-inf, t]`` that sorted disjoint intervals cover."""
    t = np.asarray(t, np.float64)
    if disjoint.shape[0] == 0:
        return np.zeros_like(t)
    length = disjoint[:, 1] - disjoint[:, 0]
    before = np.concatenate([[0.0], np.cumsum(length)])
    i = np.searchsorted(disjoint[:, 0], t, side="right") - 1
    j = np.maximum(i, 0)
    part = np.clip(t - disjoint[j, 0], 0.0, length[j])
    return np.where(i >= 0, before[j] + part, 0.0)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def covered(intervals: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that disjoint ``intervals`` cover."""
    iv = clip(intervals, lo, hi)
    return float((iv[:, 1] - iv[:, 0]).sum())


_HLO = re.compile(r"^(%[\w.\-]+) = (\S+?)(\{\S*\})? ([a-z][\w\-]*)\(")


def short_op(text: str) -> str:
    """``%name op type`` of an HLO instruction's text (its first 80
    characters where it does not parse)."""
    m = _HLO.match(text)
    return f"{m.group(1)} {m.group(4)} {m.group(2)}" if m else text[:80]


class Trace:
    """One traced window: device intervals and the benchmark's spans, in
    nanoseconds on the trace's clock."""

    def __init__(self, planes):
        self.devices = []      # per device: (ops (N, 2), names, modules)
        self.spans = defaultdict(list)
        for plane in planes:
            if DEVICE_PLANE.match(plane.name):
                self.devices.append(self._device(plane))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == WINDOW or ev.name in SPANS:
                            self.spans[ev.name].append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns))
        if not self.devices:
            raise ValueError("the trace holds no TPU device plane")
        if len(self.spans.get(WINDOW, ())) != 1:
            raise ValueError("the trace must hold exactly one 'window' span")
        self.lo, self.hi = self.spans[WINDOW][0]
        self.spans = {k: np.asarray(v, np.float64)
                      for k, v in self.spans.items()}
        self.busy = [union(clip(d["ops"], self.lo, self.hi))
                     for d in self.devices]

    @classmethod
    def from_file(cls, path) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(str(path)).planes)

    @staticmethod
    def _device(plane) -> dict:
        out = {"ops": [], "op_names": [], "modules": []}
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    out["ops"].append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
                    out["op_names"].append(ev.name)
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    out["modules"].append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
        out["ops"] = np.asarray(out["ops"], np.float64).reshape(-1, 2)
        return out

    # ---------------------------------------------------------- numbers

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over devices."""
        return float(np.mean([covered(b, self.lo, self.hi)
                              for b in self.busy])) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def busy_in(self, lo: float, hi: float) -> float:
        """Device busy seconds inside ``[lo, hi]`` (ns), over devices."""
        return float(np.mean([covered(b, lo, hi) for b in self.busy])) * 1e-9

    def program_s(self, name: str) -> float | None:
        """Device seconds of jitted program ``name`` (``jit_run``) in the
        window, averaged over devices; None if it never ran there."""
        pat = re.compile(rf"^{re.escape(name)}(\(|\.|$)")
        per_dev = []
        for d in self.devices:
            iv = np.asarray([(s, e) for n, s, e in d["modules"]
                             if pat.match(n)], np.float64).reshape(-1, 2)
            per_dev.append(covered(union(iv), self.lo, self.hi))
        total = float(np.mean(per_dev)) * 1e-9
        return total if total > 0 else None

    def span(self, name: str) -> np.ndarray:
        """``(start, end)`` rows of the benchmark's span ``name``."""
        return self.spans.get(name, np.zeros((0, 2)))

    # -------------------------------------------------------- breakdown

    def top_ops(self, k: int = 10) -> list:
        """The device operations that took most time (first device), by
        short name: ``%fusion.12 fusion s32[4096]``."""
        d = self.devices[0]
        tot = defaultdict(float)
        iv = d["ops"]
        for name, (s, e) in zip(d["op_names"], iv):
            s, e = max(s, self.lo), min(e, self.hi)
            if e > s:
                tot[short_op(name)] += (e - s) * 1e-9
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]

    def gaps(self) -> np.ndarray:
        """Idle stretches of the window on the first device."""
        b = self.busy[0]
        edges = np.concatenate([[self.lo], b.ravel(), [self.hi]])
        gaps = edges.reshape(-1, 2)
        return gaps[gaps[:, 1] > gaps[:, 0]]

    def idle_by_span(self, k: int = 10) -> list:
        """Idle seconds by the span the host was in, largest first: each
        gap goes to the innermost span that covers at least half of it,
        else to the span that covers most of it, else to ``other``."""
        gaps = self.gaps()
        length = gaps[:, 1] - gaps[:, 0]
        names = [n for n in SPANS if n in self.spans]
        inner = np.full(gaps.shape[0], -1)
        most = np.full(gaps.shape[0], -1)
        most_cover = np.zeros(gaps.shape[0])
        for i, n in enumerate(names):
            u = union(self.spans[n])
            c = coverage(u, gaps[:, 1]) - coverage(u, gaps[:, 0])
            inner = np.where(c >= 0.5 * length, i, inner)
            take = c > most_cover
            most = np.where(take, i, most)
            most_cover = np.where(take, c, most_cover)
        best = np.where(inner >= 0, inner, most)
        tot = defaultdict(float)
        for i, g in zip(best.tolist(), length):
            tot[names[i] if i >= 0 else "other"] += g * 1e-9
        return sorted(([n, t] for n, t in tot.items()),
                      key=lambda x: -x[1])[:k]
