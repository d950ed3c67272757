#!/usr/bin/env python3
"""Record the small traces that the trace tests read, on a TPU.

    python3 bench/record_traces.py --out DIR [--seed N]

Runs two cells through ``bench/run.py``'s ``measure`` with tracing on, at
a toy size that keeps each trace small: closed DeepWalk batches of 512
walks of 16 hops (``g500-deepwalk.closed``), and an open window of 0.1 s
with six PPR requests of 32 walks (``g500-ppr.serve``), both on the
Graph500 graph at scale 10 with 256 lanes.  The trace runs on through the
drain of the requests still out at the window's close, so few requests
keep it small.  Writes each window's trace gzipped, with the events that
lie wholly outside the window (and 1 ms about it) left out
(``closed_scoped.xplane.pb.gz``, ``serve_tiny.xplane.pb.gz``), and the
run's result line beside it (``.json``).  Copy the traces to
``bench/testdata`` by hand.
"""
from __future__ import annotations

import argparse
import copy
import gzip
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.program_trace import field, parts  # noqa: E402
from bench.trace import Trace, find  # noqa: E402

TOY = {"scale": 10, "num_slots": 256, "max_hops": 16}
CELLS = {
    "closed_scoped": ("g500-deepwalk.closed", 0.01,
                      {"walks_per_batch": 512, "walks_per_source": 1}),
    "serve_tiny": ("g500-ppr.serve", 0.1,
                   {"walks_per_source": 32, "rate_per_s": 60.0}),
}


# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES, _PLANE_LINES, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 1, 3, 3, 4
_EVENT_OFFSET_PS, _EVENT_DURATION_PS = 2, 3
MARGIN_NS = 1e6


def _trim_line(line, lo: float, hi: float) -> bytes:
    start = next((v for n, v, _ in parts(line) if n == _LINE_TIMESTAMP_NS),
                 0)
    out = bytearray()
    for num, val, raw in parts(line):
        if num == _LINE_EVENTS:
            ev = {n: v for n, v, _ in parts(val) if isinstance(v, int)}
            s = start + ev.get(_EVENT_OFFSET_PS, 0) * 1e-3
            if s + ev.get(_EVENT_DURATION_PS, 0) * 1e-3 < lo or s > hi:
                continue
        out += raw
    return bytes(out)


def trim(xspace: bytes, lo: float, hi: float) -> bytes:
    """The XSpace without the events that end before ``lo`` or start after
    ``hi`` (ns, on the trace's clock); all else is copied as it is."""
    out = bytearray()
    for num, plane, raw in parts(xspace):
        if num != _SPACE_PLANES:
            out += raw
            continue
        body = bytearray()
        for pnum, val, praw in parts(plane):
            body += (field(pnum, _trim_line(val, lo, hi))
                     if pnum == _PLANE_LINES else praw)
        out += field(num, bytes(body))
    return bytes(out)


def toy(cell: dict, mix_changes: dict) -> tuple[dict, dict]:
    config = copy.deepcopy(registry.config(cell["config"]))
    config["graph"]["scale"] = TOY["scale"]
    config["engine"]["num_slots"] = TOY["num_slots"]
    config["walk"]["max_hops"] = TOY["max_hops"]
    if "service" in config:
        config["service"] = {"capacity": 128, "chunk": 4}
    mix = {**registry.traffic(cell["traffic"]), **mix_changes}
    return config, mix


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=Path, required=True)
    ap.add_argument("--seed", type=int, default=14)
    args = ap.parse_args(argv)
    args.out.mkdir(parents=True, exist_ok=True)
    bench = registry.benchmark()
    with tempfile.TemporaryDirectory() as graphs:
        for name, (cell_name, seconds, mix_changes) in CELLS.items():
            config, mix = toy(registry.cell(cell_name, bench), mix_changes)
            result, _ = bench_run.measure(
                cell_name, args.seed, seconds, True, bench=bench,
                config=config, mix=mix, cache_dir=Path(graphs))
            path = find(bench_run.TRACE_DIR)
            window = Trace.from_file(path)
            raw = trim(path.read_bytes(), window.lo - MARGIN_NS,
                       window.hi + MARGIN_NS)
            (args.out / f"{name}.xplane.pb.gz").write_bytes(
                gzip.compress(raw, 9))
            (args.out / f"{name}.json").write_text(json.dumps(result))
            print(name, json.dumps(result["metrics"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
