"""Share of the HBM roofline the closed-batch engine program reaches.

The bytes a hop needs, by sampler kind (``bench/bytes_per_step.json``),
times the hops taken (``WalkStats.steps``), over the device time of the
engine program (``jit_run``), over the device's HBM bandwidth
(``bench/peaks.json``).  The walk is bound by bandwidth and latency, never
by arithmetic, so bytes set its roofline.  It counts the bytes the walk
needs whatever implements the step, not the rows a kernel's DMAs move,
and so reads far below 1 %."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ENGINE = "jit_run"


def read(run):
    engine_s = run["trace"].program_s(ENGINE)
    if engine_s is None:
        return None
    per_step = json.loads((BENCH / "bytes_per_step.json").read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = run["walk"]["sampler"]
    if kind not in per_step["kinds"]:
        raise KeyError(f"no bytes per step for sampler kind {kind!r}")
    if run["device_kind"] not in peaks:
        raise KeyError(f"no peaks for device {run['device_kind']!r} in "
                       "bench/peaks.json")
    nbytes = per_step["kinds"][kind]["bytes"] * run["counters"]["steps"]
    bw = peaks[run["device_kind"]]["hbm_bytes_per_s"]
    return 100.0 * nbytes / engine_s / bw
