"""GRW service driver — the paper's workload as a runnable CLI, on the
unified walker API (`repro.walker.compile`).

  PYTHONPATH=src python -m repro.launch.walk --algo deepwalk --dataset WG \
      --queries 2000 --slots 1024
  PYTHONPATH=src python -m repro.launch.walk --algo node2vec --backend sharded \
      ...   (one partition per device present; on the CPU set
             XLA_FLAGS=--xla_force_host_platform_device_count=N first)
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import walker
from repro.compile_cache import use_compile_cache
from repro.configs.ridgewalker import ALGORITHMS, QUERY_LENGTH
from repro.core.scheduler import analyze_run
from repro.graph import make_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="urw", choices=sorted(ALGORITHMS))
    ap.add_argument("--dataset", default="WG")
    ap.add_argument("--scale", type=int, default=None,
                    help="RMAT scale override (CPU-sized default)")
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--slots", type=int, default=1024)
    ap.add_argument("--max-hops", type=int, default=QUERY_LENGTH)
    ap.add_argument("--mode", default="zero_bubble",
                    choices=["zero_bubble", "static"])
    ap.add_argument("--step-impl", default="jnp",
                    choices=["jnp", "pallas", "fused"])
    ap.add_argument("--hops-per-launch", type=int, default=16,
                    help="fused only: supersteps per kernel launch")
    ap.add_argument("--backend", default="single",
                    choices=list(walker.BACKENDS))
    ap.add_argument("--distributed", action="store_true",
                    help="alias for --backend sharded")
    ap.add_argument("--devices", type=int, default=None,
                    help="sharded only: partitions, one per device "
                         "(default: every device present)")
    ap.add_argument("--record-paths", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    spec = ALGORITHMS[args.algo]
    program = walker.WalkProgram(spec=spec, max_hops=args.max_hops,
                                 name=args.algo)
    weighted = spec.kind in ("alias", "reservoir_n2v")
    g = make_dataset(args.dataset, weighted=weighted,
                     with_alias=spec.kind == "alias",
                     scale_override=args.scale, seed=args.seed)
    print(f"{args.dataset}: |V|={g.num_vertices} |E|={g.num_edges} "
          f"max_deg={g.max_degree}")
    rng = np.random.default_rng(args.seed)
    starts = rng.integers(0, g.num_vertices, args.queries).astype(np.int32)

    backend = "sharded" if args.distributed else args.backend
    if backend == "sharded":
        if args.mode != "zero_bubble" or args.step_impl != "jnp":
            ap.error("--mode/--step-impl only apply to --backend single "
                     "(the sharded superstep is always zero-bubble jnp)")
        execution = walker.ExecutionConfig(
            num_slots=args.slots, record_paths=args.record_paths,
            num_devices=args.devices)
    else:
        execution = walker.ExecutionConfig(
            num_slots=args.slots, record_paths=args.record_paths,
            mode=args.mode, step_impl=args.step_impl,
            hops_per_launch=args.hops_per_launch)
    w = walker.compile(program, backend=backend, execution=execution)
    t0 = time.time()
    res = w.run(g, starts, seed=args.seed)
    res.stats.steps.block_until_ready()
    dt = time.time() - t0
    a = analyze_run(res.stats, dt)
    print(f"steps={a.steps} supersteps={a.supersteps} "
          f"throughput={a.msteps_per_s:.3f} MStep/s "
          f"occupancy={a.occupancy:.3f} starved={a.starved} drops={a.drops}")


if __name__ == "__main__":
    main()
