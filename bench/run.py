#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``).  The run loads the configuration's
graph (built once from the configuration's own seed and kept in
``bench/.graph_cache``), puts it on the device, draws the traffic and the
walk seeds from ``--seed``, compiles and warms up the cell's own shapes,
then measures for ``--seconds``: a closed loop of ``Walker.run`` batches
or an open loop of requests to ``Walker.serve``.  It then holds a seeded
sample of what the timed path returned against the plain reference
(``bench/reference``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window by ``bench/metrics/<metric>.py``), ``device``, with ``--trace
1`` a ``breakdown``, and last ``checks``: each number compared beside its
limit, which are also the last lines of standard error.  Earlier lines
give the set-up split and what the window did.

Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, when ``src/repro`` is not beside ``bench/``, or
when the cell or anything it names cannot be found.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# The package is imported as ``bench``; the script's own directory must not
# shadow standard modules (``trace``).
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import checks, generator, graphs, loops  # noqa: E402
from bench import registry  # noqa: E402
from bench.registry import BENCH, BenchError  # noqa: E402

TRACE_DIR = BENCH / ".traces"


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _say(tag: str, obj) -> None:
    print(f"{tag}: {json.dumps(obj)}", flush=True)


def build_walker(config: dict):
    """The configuration's walk program, compiled for its backend."""
    from repro import walker
    from repro.core.samplers import SamplerSpec
    walk, eng = config["walk"], config["engine"]
    spec = SamplerSpec(kind=walk["sampler"],
                       stop_prob=float(walk.get("stop_prob", 0.0)))
    program = walker.WalkProgram(spec=spec, max_hops=int(walk["max_hops"]),
                                 name=walk["algorithm"])
    execution = walker.ExecutionConfig(num_slots=int(eng["num_slots"]),
                                       record_paths=bool(eng["record_paths"]))
    return walker.compile(program, backend=eng["backend"],
                          execution=execution)


def _compile_clock():
    """Seconds of XLA compilation so far, from JAX's monitoring events."""
    import jax
    total = [0.0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return total


def measure(cell_name: str, seed: int, seconds: float, trace: bool, *,
            bench: dict | None = None, config: dict | None = None,
            mix: dict | None = None, require_tpu: bool = True,
            cache_dir: Path | None = None) -> tuple:
    """One run of one cell: the result object, and what the comparison
    read (``sample``, ``graph``, ``walk``) for the control's readings."""
    bench = bench or registry.benchmark()
    cell = registry.cell(cell_name, bench)
    config = config or registry.config(cell["config"])
    mix = mix or registry.traffic(cell["traffic"])
    generator.validate(mix)
    e2e = registry.metrics_of(cell_name, bench, "end_to_end")
    layer = registry.metrics_of(cell_name, bench, "per_layer")
    readers = {m["name"]: registry.metric(m["name"]) for m in layer}
    if not (registry.ROOT / "src" / "repro").is_dir():
        raise BenchError("no src/repro beside bench/: run from a checkout "
                         "of the repository")
    if str(registry.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(registry.ROOT / "src"))

    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX sees {devices[0].platform} only")
        if len(devices) < int(cell["chips"]):
            raise NoChip(f"the cell needs {cell['chips']} chips, JAX sees "
                         f"{len(devices)}")
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiled = _compile_clock()
    setup = {"init_s": time.perf_counter() - T_START}

    rng = np.random.default_rng(np.random.SeedSequence(seed))
    graph, arrays, graph_times = graphs.load(config,
                                             cache_dir or graphs.CACHE)
    setup.update(graph_times)
    sources = generator.Sources(np.diff(arrays["ref_row_ptr"]), rng)
    t = time.perf_counter()
    w = build_walker(config)
    if mix["loop"] == "closed":
        loop = loops.Closed(w, graph, mix, sources, rng)
    else:
        loop = loops.Open(w, graph, mix, sources, rng, config["service"],
                          loops.walk_seed(rng))
    loop.warm_up()
    setup["warm_up_s"] = time.perf_counter() - t
    setup["compile_s"] = compiled[0]
    setup_s = time.perf_counter() - T_START
    setup["setup_s"] = setup_s
    _say("setup", {**setup, "compile_cache": cache,
                   "graph": {"vertices": int(arrays["meta"][0]),
                             "edges": int(arrays["meta"][1]),
                             "max_degree": int(arrays["meta"][2])}})

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    compiled_before = compiled[0]
    try:
        out = loop.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    used = devices[:int(cell["chips"])]
    peaks = [d.memory_stats() or {} for d in used]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(int(p.get("peak_bytes_in_use", 0))
                                       for p in peaks)}
    _say("window", {"window_s": out["window_s"], **out["notes"],
                    "counters": out["counters"], "e2e": out["e2e"],
                    "compile_s_in_window": compiled[0] - compiled_before})

    graph_ref = graphs.reference_graph(arrays)
    t = time.perf_counter()
    counts = ({} if out["sample"] is None else
              checks.compare(graph_ref, config["walk"], out["sample"]))
    counts.update(missing=out["missing"], dup_ids=out["dup_ids"])
    correct, table = checks.verdict(counts)
    _say("check", {**counts, "check_s": time.perf_counter() - t})

    metrics = {}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if not trace:
        values = {"setup_s": setup_s, **out["e2e"]}
        for m in e2e:
            if m["name"] not in values:
                raise BenchError(f"cell {cell_name} lists {m['name']!r}, "
                                 "which its loop does not measure")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from bench.trace import Trace, find
        tr = Trace.from_file(find(TRACE_DIR))
        view = {"trace": tr, "counters": out["counters"],
                "requests": out.get("requests"), "walk": config["walk"],
                "device_kind": device["kind"]}
        for m in layer:
            v = readers[m["name"]].read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_by_span(10)}
    result["checks"] = table
    return result, {"sample": out["sample"], "graph": graph_ref,
                    "walk": config["walk"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        result, _ = measure(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    except (BenchError, NoChip) as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 2 if isinstance(e, BenchError) else 3
    for name, row in result["checks"].items():
        op = ">=" if row.get("at_least") else "<="
        print(f"check {name} = {row['value']} (limit {op} {row['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
