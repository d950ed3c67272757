#!/usr/bin/env python3
"""Bring-up smoke of the RidgeWalker walk engine on one TPU v5e chip.

    python3 chip_smoke.py                 # one chip, every phase below
    python3 chip_smoke.py --four-chips    # sharded backend on 4 chips only

Drives the engine through `repro.walker.compile` at the size of a real
deployment — the paper's web-Google graph (Table II) as the seeded RMAT
stand-in at 2^20 vertices (~6.2M edges), 80-hop queries, W = 4096 lanes
(`configs/ridgewalker.py`), a closed batch of 131072 start vertices — and
checks every result on the host with numpy:

  * jnp: closed batch of urw, ppr, deepwalk (alias tables), node2vec
    (rejection) and node2vec_w (weighted reservoir), paths recorded;
  * cpu: the first 1024 queries of each algorithm rerun on the host's CPU
    backend in this process must be bit-identical (the RNG and index
    arithmetic are integer and IEEE-exact);
  * kernels: ``step_impl="fused"`` (with and without the hot-vertex
    cache) and ``step_impl="pallas"`` compiled for the chip — never
    interpreted — must reproduce the jnp paths bit for bit;
  * serve: ``Walker.serve`` answers waves of requests for every algorithm.

Every path must walk edges of the CSR, no walk may exceed 80 hops, PPR
walks must be shorter on average than URW walks, and no task may be
dropped.  ``--four-chips`` runs only the sharded backend over a 4-chip
mesh for urw and node2vec against the single-chip jnp run.

Exits non-zero, printing no result, when JAX finds no TPU (the CPU is
never a fallback), when the repo's ``src/`` is not beside this file, or
when any phase fails.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process; the compile cache follows
`repro.compile_cache`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ALGOS = ("urw", "ppr", "deepwalk", "node2vec", "node2vec_w")
CPU_CHECK = 1024       # queries cross-checked on the host CPU backend
CACHE_BUDGET = 1 << 18  # hot-vertex cache bytes (fits SMEM beside W=4096)


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


class Clock:
    """Wall and compile seconds of one phase (compile time is the XLA
    backend compile JAX reports through its monitoring events; tracing
    and lowering count as wall time only)."""

    compile_s = 0.0
    _EVENTS = ("/jax/core/compile/backend_compile_duration",)

    @classmethod
    def install(cls):
        import jax

        def on_event(event, duration, **_):
            if event in cls._EVENTS:
                cls.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = Clock.compile_s
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            say(f"[{self.name}] wall={time.perf_counter() - self.t0:.3f}s "
                f"compile={Clock.compile_s - self.c0:.3f}s")


# ------------------------------------------------------------------ inputs


def make_inputs(scale, queries, seed):
    import numpy as np

    from repro.graph import make_dataset
    g = make_dataset("WG", scale_override=scale, weighted=True,
                     with_alias=True, seed=seed)
    starts = np.random.default_rng(seed).integers(
        0, g.num_vertices, queries).astype(np.int32)
    return g, starts


class EdgeSet:
    """Host-side CSR membership: sorted ``u * |V| + v`` keys."""

    def __init__(self, g):
        import numpy as np
        rp = np.asarray(g.row_ptr).astype(np.int64)
        col = np.asarray(g.col).astype(np.int64)
        self.nv = int(g.num_vertices)
        src = np.repeat(np.arange(self.nv, dtype=np.int64), np.diff(rp))
        self.keys = np.sort(src * self.nv + col)

    def contains(self, u, v):
        import numpy as np
        k = u.astype(np.int64) * self.nv + v.astype(np.int64)
        i = np.minimum(np.searchsorted(self.keys, k), self.keys.size - 1)
        return self.keys[i] == k


def check_paths(edges, paths, lengths, starts, max_hops, label):
    """Structure and edge validity of recorded walks; returns mean hops."""
    import numpy as np
    paths = np.asarray(paths)
    lengths = np.asarray(lengths)
    q = starts.shape[0]
    check(paths.shape == (q, max_hops + 1),
          f"{label}: paths shape {paths.shape}")
    check(np.array_equal(paths[:, 0], starts),
          f"{label}: paths do not begin at their start vertices")
    check(lengths.min() >= 1 and lengths.max() <= max_hops + 1,
          f"{label}: lengths outside [1, {max_hops + 1}] "
          f"(walks of more than {max_hops} hops)")
    pos = np.arange(max_hops + 1)[None, :]
    inside = pos < lengths[:, None]
    check(np.all(paths[inside] >= 0) and np.all(paths[~inside] == -1),
          f"{label}: path rows are not their length of vertices then -1")
    hop = inside[:, 1:]
    u, v = paths[:, :-1][hop], paths[:, 1:][hop]
    bad = int((~edges.contains(u, v)).sum())
    check(bad == 0, f"{label}: {bad} of {u.size} hops are not CSR edges")
    return float(lengths.mean() - 1), int(u.size)


def report(label, stats, wall):
    steps = int(stats.steps)
    slot_steps = max(int(stats.slot_steps), 1)
    say(f"{label}: steps={steps} supersteps={int(stats.supersteps)} "
        f"launches={int(stats.launches)} "
        f"occupancy={steps / slot_steps:.4f} drops={int(stats.drops)} "
        f"wall={wall:.3f}s")
    check(int(stats.drops) == 0, f"{label}: {int(stats.drops)} drops")


# ------------------------------------------------------------------ phases


def program(algo, max_hops):
    from repro import walker
    from repro.configs.ridgewalker import ALGORITHMS
    return walker.WalkProgram(spec=ALGORITHMS[algo], max_hops=max_hops,
                              name=algo)


def execution(**kw):
    from repro import walker
    from repro.configs.ridgewalker import ENGINE
    return walker.ExecutionConfig(num_slots=ENGINE.num_slots,
                                  record_paths=True, **kw)


def closed_batch(g, starts, algo, max_hops, seed, **kw):
    import jax
    import numpy as np

    from repro import walker
    w = walker.compile(program(algo, max_hops), execution=execution(**kw))
    t0 = time.perf_counter()
    res = w.run(g, starts, seed=seed)
    jax.block_until_ready(res.paths)
    wall = time.perf_counter() - t0
    return np.asarray(res.paths), np.asarray(res.lengths), res.stats, wall


def phase_jnp(ctx):
    """Closed batch of every algorithm on the default jnp superstep."""
    out = {}
    starts = ctx.starts
    for algo in ALGOS:
        with Clock(f"jnp {algo}"):
            paths, lengths, stats, wall = closed_batch(
                ctx.g, starts, algo, ctx.max_hops, ctx.seed)
            report(f"jnp {algo}", stats, wall)
            mean, hops = check_paths(ctx.edges, paths, lengths, starts,
                                     ctx.max_hops, f"jnp {algo}")
            say(f"jnp {algo}: queries={starts.size} hops_checked={hops} "
                f"mean_hops={mean:.3f}")
        out[algo] = (paths, lengths, mean)
    check(out["ppr"][2] < out["urw"][2],
          f"ppr walks ({out['ppr'][2]:.3f} hops) are not shorter on "
          f"average than urw walks ({out['urw'][2]:.3f})")
    return out


def phase_cpu(ctx, ref):
    """The first CPU_CHECK queries of every algorithm on the CPU backend."""
    import jax
    import numpy as np
    cpu = jax.devices("cpu")[0]
    g_cpu = jax.device_put(ctx.g, cpu)
    n = min(CPU_CHECK, ctx.starts.size)
    for algo in ALGOS:
        with Clock(f"cpu {algo}"), jax.default_device(cpu):
            paths, lengths, _stats, _wall = closed_batch(
                g_cpu, ctx.starts[:n], algo, ctx.max_hops, ctx.seed)
        same = (np.array_equal(paths, ref[algo][0][:n])
                and np.array_equal(lengths, ref[algo][1][:n]))
        check(same, f"cpu {algo}: first {n} queries differ from the chip")
        say(f"cpu {algo}: first {n} queries bit-identical to the chip")


def phase_kernels(ctx, ref):
    """Pallas kernels compiled for the chip reproduce the jnp paths."""
    import numpy as np

    from repro.kernels.common import default_interpret
    check(default_interpret(None) is False,
          "kernels would run in interpret mode on this backend")
    runs = [("pallas", "urw", {}), ("pallas", "deepwalk", {}),
            ("fused", "urw", {}),
            ("fused", "urw", {"cache_budget": CACHE_BUDGET}),
            ("fused", "deepwalk", {})]
    for impl, algo, kw in runs:
        label = f"{impl}{'+cache' if kw else ''} {algo}"
        with Clock(label):
            paths, lengths, stats, wall = closed_batch(
                ctx.g, ctx.starts, algo, ctx.max_hops, ctx.seed,
                step_impl=impl, **kw)
            report(label, stats, wall)
        check(np.array_equal(paths, ref[algo][0])
              and np.array_equal(lengths, ref[algo][1]),
              f"{label}: paths differ from the jnp run")
        if kw:
            hits, misses = int(stats.cache_hits), int(stats.cache_misses)
            say(f"{label}: cache_hits={hits} cache_misses={misses} "
                f"coalesced={int(stats.cache_coalesced)}")
            check(hits > 0, f"{label}: the hot-vertex cache never hit")
        say(f"{label}: paths bit-identical to jnp")


def phase_serve(ctx, waves=2, requests=8, size=256):
    """`Walker.serve` answers waves of requests, as launch/walk_serve.py."""
    import numpy as np

    from repro import walker
    rng = np.random.default_rng(ctx.seed + 1)
    for algo in ALGOS:
        with Clock(f"serve {algo}"):
            svc = walker.compile(program(algo, ctx.max_hops),
                                 execution=execution()).serve(
                ctx.g, capacity=4 * requests * size, chunk=16,
                seed=ctx.seed)
            t0 = time.perf_counter()
            walks = 0
            for _ in range(waves):
                sent = {}
                for _ in range(requests):
                    sv = rng.integers(0, ctx.g.num_vertices,
                                      size).astype(np.int32)
                    sent[svc.submit(sv)] = sv
                done = {r.request_id: r for r in svc.drain()}
                for rid, sv in sent.items():
                    r = done[rid]
                    check_paths(ctx.edges, r.paths, r.lengths, sv,
                                ctx.max_hops, f"serve {algo} request {rid}")
                    walks += sv.size
            wall = time.perf_counter() - t0
            report(f"serve {algo}", svc.walk_stats(), wall)
            say(f"serve {algo}: requests={waves * requests} walks={walks}")


def phase_four_chips(ctx):
    """Sharded backend over a 4-chip mesh vs the single-chip jnp run."""
    import jax
    import numpy as np

    from repro import walker
    for algo in ("urw", "node2vec"):
        with Clock(f"single {algo}"):
            ref_p, ref_l, stats, wall = closed_batch(
                ctx.g, ctx.starts, algo, ctx.max_hops, ctx.seed)
            report(f"single {algo}", stats, wall)
        with Clock(f"sharded {algo}"):
            # Emission-log room for every record of the batch on any one
            # device, so the comparison can never see a truncated path.
            w = walker.compile(program(algo, ctx.max_hops),
                               backend="sharded",
                               execution=execution(
                                   num_devices=4,
                                   log_capacity=ctx.starts.size
                                   * (ctx.max_hops + 1)))
            t0 = time.perf_counter()
            res = w.run(ctx.g, ctx.starts, seed=ctx.seed)
            jax.block_until_ready(res.paths)
            report(f"sharded {algo}", res.stats,
                   time.perf_counter() - t0)
        per_dev = [int(s) for s in np.asarray(w.last_device_stats.steps)]
        # What the owner map alone predicts: a hop runs where its source
        # vertex lives (owner = v % 4), read off the single-chip paths.
        hop = np.arange(ctx.max_hops)[None, :] < ref_l[:, None] - 1
        by_owner = np.bincount(ref_p[:, :-1][hop] % 4, minlength=4)
        say(f"sharded {algo}: steps per device={per_dev} "
            f"max/mean={max(per_dev) * 4 / max(sum(per_dev), 1):.4f} "
            f"hops by source owner (v % 4)={by_owner.tolist()}")
        check(len(per_dev) == 4 and min(per_dev) > 0,
              f"sharded {algo}: not every chip did work: {per_dev}")
        check(np.array_equal(np.asarray(res.paths), ref_p)
              and np.array_equal(np.asarray(res.lengths), ref_l),
              f"sharded {algo}: paths differ from the single-chip run")
        check_paths(ctx.edges, res.paths, res.lengths, ctx.starts,
                    ctx.max_hops, f"sharded {algo}")
        say(f"sharded {algo}: paths bit-identical to the single-chip run")


class Context:
    def __init__(self, scale, queries, seed, max_hops):
        with Clock("graph"):
            self.g, self.starts = make_inputs(scale, queries, seed)
            self.edges = EdgeSet(self.g)
        self.seed = seed
        self.max_hops = max_hops
        say(f"graph WG stand-in scale={scale}: |V|={self.g.num_vertices} "
            f"|E|={self.g.num_edges} max_degree={self.g.max_degree} "
            f"queries={queries} max_hops={max_hops} seed={seed}")


def run(args):
    os.environ.pop("RIDGEWALKER_TUNE_CACHE", None)  # committed knobs only
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        # The CPU cross-check is a second backend of this same process.
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeFailure(f"no repro package under {ROOT / 'src'}: run "
                           "chip_smoke.py from a checkout of the repo")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX sees {dev.platform} devices only")
    need = 4 if args.four_chips else 1
    check(len(devices) >= need,
          f"{need} chips needed, {len(devices)} present")
    Clock.install()
    say(f"device: {dev.platform} {dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache_dir}")
    from repro.configs.ridgewalker import QUERY_LENGTH
    ctx = Context(args.scale, args.queries, args.seed, QUERY_LENGTH)
    if args.four_chips:
        phase_four_chips(ctx)
    else:
        ref = phase_jnp(ctx)
        phase_cpu(ctx, ref)
        phase_serve(ctx)
        phase_kernels(ctx, ref)
    say(f"total compile={Clock.compile_s:.3f}s")
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(devices)}}


def watchdog(seconds):
    """End the process with a failure once ``seconds`` have passed: a
    call that never returns from the device must not outlive the smoke's
    time limit (a thread can end the process while the main thread is
    blocked inside the runtime)."""
    import threading

    def fire():
        print(f"chip_smoke FAILED: still running after {seconds:.0f}s",
              file=sys.stderr, flush=True)
        os._exit(1)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded backend over 4 chips and "
                         "the single-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, the start vertices and walks")
    ap.add_argument("--scale", type=int, default=20,
                    help="RMAT scale of the WG stand-in (2^scale vertices)")
    ap.add_argument("--queries", type=int, default=131072,
                    help="start vertices of the closed batch")
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="seconds after which the smoke fails")
    args = ap.parse_args()
    watchdog(args.deadline)
    t0 = time.perf_counter()
    try:
        result = run(args)
    except Exception as e:  # every phase's failure ends the smoke
        import traceback
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(1)
    say(f"total wall={time.perf_counter() - t0:.3f}s")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
