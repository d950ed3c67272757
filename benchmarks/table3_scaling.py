"""Table III analogue: throughput scaling with memory channels.

The paper scales across FPGAs with 4/32 memory channels (U250 ->
U55C); the TPU analogue scales the distributed engine across devices
(each device = one channel's row-pointer + neighbor shard).

Every point runs in this process on the devices JAX already sees: a chip
belongs to one process, and the harness (`benchmarks.run`) has touched
JAX before this suite starts.  The quick suite asks for 1, 2 and 4
devices, the full suite also for 8; fewer devices present is an error,
never a shorter table (on the CPU, start the harness with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get N).  A
failed point raises; it never becomes a result row."""
import time

import jax
import numpy as np

from benchmarks.common import emit


def _point(n: int, scale: int, queries: int) -> dict:
    from repro import walker
    from repro.graph import make_dataset, partition_graph
    g = make_dataset("WG", scale_override=scale)
    pg = partition_graph(g, n)
    starts = np.random.default_rng(0).integers(
        0, g.num_vertices, queries).astype(np.int32)
    w = walker.compile(
        walker.WalkProgram.urw(80), backend="sharded",
        execution=walker.ExecutionConfig(
            num_devices=n, slots_per_device=max(2048 // n, 64),
            record_paths=False))
    res = w.run(pg, starts)   # compile+warm
    jax.block_until_ready(res.stats.steps)
    t0 = time.time()
    res = w.run(pg, starts)
    jax.block_until_ready(res.stats.steps)
    dt = time.time() - t0
    steps = int(np.asarray(res.stats.steps))
    drops = int(np.asarray(res.stats.drops))
    if drops:
        raise RuntimeError(f"table3 at {n} devices dropped {drops} tasks")
    return dict(N=n, dt=dt, steps=steps, msteps=steps / dt / 1e6,
                waits=int(np.asarray(res.stats.route_waits)), drops=drops)


def run(quick: bool = False):
    counts = (1, 2, 4) if quick else (1, 2, 4, 8)
    present = len(jax.devices())
    if present < max(counts):
        raise RuntimeError(
            f"table3 asks for {max(counts)} devices and {present} are "
            f"present; on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={max(counts)}")
    results = {}
    for n in counts:
        d = _point(n, scale=11 if quick else 12,
                   queries=1500 if quick else 4000)
        results[n] = d
        emit(f"table3_ch{n}", d["dt"] * 1e6,
             f"msteps={d['msteps']:.3f};waits={d['waits']};"
             f"drops={d['drops']}")
    top = max(counts)
    eff = (results[top]["msteps"] / results[1]["msteps"]) / top
    emit("table3_scaling_eff", 0.0,
         f"devices={top};parallel_efficiency={eff:.2f}")
    return results


if __name__ == "__main__":
    run()
