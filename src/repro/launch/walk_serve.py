"""Open-system GRW serving driver — continuous Poisson arrivals against the
streaming walk engine (the queuing setting Theorem VI.1 models).

  PYTHONPATH=src python -m repro.launch.walk_serve --algo urw --dataset WG \
      --rho 0.8 --requests 64 --request-size 16 --slots 512 --chunk 8

Sharded serving runs the same service over the distributed superstep
(requires >1 visible device; on CPU force them with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``):

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      python -m repro.launch.walk_serve --backend sharded --slots 64

Compare with `repro.launch.walk`, which drains a fixed (closed) batch.
"""
from __future__ import annotations

import argparse

from repro import walker
from repro.configs.ridgewalker import ALGORITHMS, QUERY_LENGTH
from repro.graph import make_dataset
from repro.serve import OpenLoad, run_open_load


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="urw", choices=sorted(ALGORITHMS))
    ap.add_argument("--backend", default="single",
                    choices=sorted(walker.BACKENDS),
                    help="single device or sharded across the device mesh")
    ap.add_argument("--dataset", default="WG")
    ap.add_argument("--scale", type=int, default=None,
                    help="RMAT scale override (CPU-sized default)")
    ap.add_argument("--rho", type=float, default=0.8,
                    help="offered utilization λ·E[L]/W (>=1 overloads)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--request-size", type=int, default=16,
                    help="walks per request")
    ap.add_argument("--slots", type=int, default=512)
    ap.add_argument("--max-hops", type=int, default=QUERY_LENGTH)
    ap.add_argument("--chunk", type=int, default=8,
                    help="supersteps per host-injection chunk")
    ap.add_argument("--capacity", type=int, default=8192,
                    help="live-query slot-ring capacity (slots recycle "
                    "continuously; this bounds concurrency, not volume)")
    ap.add_argument("--injection-delay", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    spec = ALGORITHMS[args.algo]
    weighted = spec.kind in ("alias", "reservoir_n2v")
    g = make_dataset(args.dataset, weighted=weighted,
                     with_alias=spec.kind == "alias",
                     scale_override=args.scale, seed=args.seed)
    print(f"{args.dataset}: |V|={g.num_vertices} |E|={g.num_edges} "
          f"max_deg={g.max_degree}")

    program = walker.WalkProgram(spec=spec, max_hops=args.max_hops,
                                 name=args.algo)
    execution = walker.ExecutionConfig(num_slots=args.slots,
                                       injection_delay=args.injection_delay)
    svc = walker.compile(program, backend=args.backend,
                         execution=execution).serve(
        g, capacity=args.capacity, chunk=args.chunk, seed=args.seed)
    load = OpenLoad(num_requests=args.requests,
                    request_size=args.request_size,
                    utilization=args.rho)
    a = run_open_load(svc, load, seed=args.seed)
    stats = svc.walk_stats()
    print(f"backend={args.backend} offered_load={a.offered_load:.2f} "
          f"walks/superstep (rho={a.utilization:.2f})")
    print(f"requests={a.requests} walks={a.walks} supersteps={a.supersteps} "
          f"drops={int(stats.drops)}")
    print(f"sojourn supersteps: p50={a.p50_sojourn:.1f} "
          f"p99={a.p99_sojourn:.1f} mean={a.mean_sojourn:.1f} "
          f"(admission wait p50={a.p50_admission_wait:.1f} "
          f"p99={a.p99_admission_wait:.1f})")
    print(f"throughput={a.throughput:.1f} hops/superstep "
          f"({a.msteps_per_s:.3f} MStep/s) bubble_ratio={a.bubble_ratio:.3f} "
          f"starved_ratio={a.starved_ratio:.3f}")


if __name__ == "__main__":
    main()
