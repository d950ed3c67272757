#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep on the chip:

    python bench/sweep.py --workload g500-ppr.serve --seed 7 --seconds 10 \\
        --rates 40 60 80 100

Runs the cell's traffic mix at each arrival rate in turn, for ``--seconds``
each, on one warmed-up service, and prints per rate what was completed in
the window, the latency tails, and the backlog left when it closed.  The
knee is the highest rate whose backlog does not grow over the window; the
cells' rates are set from it by hand.  The benchmark's runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import generator, graphs, loops, registry  # noqa: E402
from bench.run import build_walker  # noqa: E402
from bench.stats import nearest_rank  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    from repro.compile_cache import use_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py: no TPU", file=sys.stderr)
        return 3
    use_compile_cache()
    cell = registry.cell(args.workload, registry.benchmark())
    config = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    if mix["loop"] != "open":
        print("bench/sweep.py: not an open-loop cell", file=sys.stderr)
        return 2
    rng = np.random.default_rng(np.random.SeedSequence(args.seed))
    graph, arrays, _ = graphs.load(config)
    sources = generator.Sources(np.diff(arrays["ref_row_ptr"]), rng)
    loop = loops.Open(build_walker(config), graph, mix, sources, rng,
                      config["service"], loops.walk_seed(rng))
    loop.warm_up()
    for rate in args.rates:
        loop.mix = dict(mix, rate_per_s=rate)
        out = loop.window(args.seconds)
        req = out["requests"]
        print(json.dumps({
            "rate_per_s": rate, "offered": out["attempted"],
            "completed_per_s": out["notes"]["completed_in_window"]
            / out["window_s"],
            "backlog_at_close": out["attempted"]
            - out["notes"]["completed_in_window"],
            "p50_ms": out["e2e"]["request_p50_ms"],
            "p95_ms": out["e2e"]["request_p95_ms"],
            "p99_ms": 1e3 * nearest_rank(req["latency_s"], 0.99),
            "late_p95_ms": 1e3 * nearest_rank(req["generator_late_s"], 0.95),
            "steps_per_s": out["e2e"]["served_steps_per_s"],
            "occupancy": out["counters"]["steps"]
            / max(out["counters"]["slot_steps"], 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
