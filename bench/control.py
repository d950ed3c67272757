#!/usr/bin/env python3
"""Readings that set the limits of `correct` (PERF.md), one process:

    python bench/control.py --workload <cell> --seeds 11 12 13 --seconds 3

For each seed, one run of the cell at its own size and load with a short
window, and on the same sample of walks two readings: the program's
(against the float32 reference) and the control's (the reference computed
in bfloat16 put in the program's place), each with the harness's own
verdict.  The benchmark's own runs never run the control.  Prints one JSON
line per seed, then a summary line."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)

from bench import checks  # noqa: E402
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in args.seeds:
        result, got = bench_run.measure(args.workload, seed, args.seconds,
                                        False)
        counts = {k: v["value"] for k, v in result["checks"].items()}
        ctrl_ok, ctrl = checks.control_verdict(got["graph"], got["walk"],
                                               got["sample"], counts)
        row = {"seed": seed, "correct": result["correct"], **counts,
               "control_correct": ctrl_ok,
               "control_wrong_hops": ctrl["wrong_hops"]["value"]}
        rows.append(row)
        print("reading:", json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_wrong_hops_max": max(r["wrong_hops"] for r in rows),
        "control_wrong_hops_min": min(r["control_wrong_hops"] for r in rows),
        "all_correct": all(r["correct"] for r in rows),
        "control_never_correct": not any(r["control_correct"]
                                         for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
