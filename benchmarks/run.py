# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness (deliverable d): one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run                     # quick suite
  PYTHONPATH=src python -m benchmarks.run --full              # full sizes
  PYTHONPATH=src python -m benchmarks.run --json BENCH.json   # + machine-
      readable {suite: {name: {us_per_call, derived}}} with a per-
      algorithm walks/sec summary across step_impl ∈ {jnp, pallas, fused}

Fig. 8  — vs statically-scheduled FPGA-baseline analogue
Fig. 9  — per-algorithm throughput across datasets
Fig. 10 — RMAT balanced vs Graph500 skew robustness (+ degree-adaptive
          reservoir scan for weighted Node2Vec)
Fig. 11 — scheduler/async ablation breakdown
Table III — channel (device) scaling of the distributed engine
Table IV  — per-kernel on-chip budgets (TPU analogue of LUT/BRAM)
Roofline  — dry-run derived compute/memory/collective terms (§Roofline)
step_impl — walks/sec across the jnp / pallas / fused superstep impls
"""
import argparse
import json
import numbers
import sys
import time


#: Keys every ``environment`` block must carry — numbers vary by host,
#: but the *shape* is part of the BENCH schema so dashboards can always
#: tell CPU-interpret runs from real-TPU runs before comparing timings.
ENVIRONMENT_KEYS = ("jax_version", "backend", "device_kind",
                    "device_count", "interpret")


def environment_metadata() -> dict:
    """Execution-environment block recorded in every BENCH payload.

    Timings from an interpret-mode CPU run and a compiled TPU run are
    not comparable; stamping the backend/device/interpret flags into the
    payload makes every BENCH_*.json self-describing.
    """
    import jax

    from repro.kernels.common import default_interpret
    devs = jax.devices()
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "unknown",
        "device_count": len(devs),
        "interpret": bool(default_interpret(None)),
    }


def validate_payload(payload) -> list:
    """Validate the BENCH JSON schema before it is written.

    Shape: ``{suite: {row: {"us_per_call": number, "derived": str}}}``
    plus the optional ``walks_per_sec`` summary
    (``{algo: {impl: number}}``) and the ``environment`` block
    (``{jax_version, backend, device_kind, device_count, interpret}``).
    Returns a list of problem strings — a malformed suite result (a
    typo'd key, a non-numeric timing, a stray nesting level) must fail
    the run instead of silently producing a BENCH.json downstream
    dashboards mis-parse.
    """
    problems = []
    if not isinstance(payload, dict):
        return [f"payload is {type(payload).__name__}, expected dict"]
    for suite, rows in payload.items():
        if suite == "environment":
            if not isinstance(rows, dict):
                problems.append(f"environment: expected dict, got "
                                f"{type(rows).__name__}")
                continue
            missing = set(ENVIRONMENT_KEYS) - set(rows)
            extra = set(rows) - set(ENVIRONMENT_KEYS)
            if missing:
                problems.append(f"environment: missing key(s) "
                                f"{sorted(missing)}")
            if extra:
                problems.append(f"environment: unknown key(s) "
                                f"{sorted(extra)}")
            if "device_count" in rows and not isinstance(
                    rows["device_count"], numbers.Real):
                problems.append("environment: device_count is "
                                f"{type(rows['device_count']).__name__}, "
                                f"expected number")
            if "interpret" in rows and not isinstance(
                    rows["interpret"], bool):
                problems.append("environment: interpret is "
                                f"{type(rows['interpret']).__name__}, "
                                f"expected bool")
            for k in ("jax_version", "backend", "device_kind"):
                if k in rows and not isinstance(rows[k], str):
                    problems.append(f"environment: {k} is "
                                    f"{type(rows[k]).__name__}, "
                                    f"expected str")
            continue
        if suite == "walks_per_sec":
            if not isinstance(rows, dict):
                problems.append(f"walks_per_sec: expected dict, got "
                                f"{type(rows).__name__}")
                continue
            for algo, impls in rows.items():
                if not isinstance(impls, dict):
                    problems.append(f"walks_per_sec[{algo!r}]: expected "
                                    f"dict of impl→rate")
                    continue
                for impl, rate in impls.items():
                    if not isinstance(rate, numbers.Real):
                        problems.append(
                            f"walks_per_sec[{algo!r}][{impl!r}]: rate is "
                            f"{type(rate).__name__}, expected number")
            continue
        if not isinstance(rows, dict):
            problems.append(f"suite {suite!r}: expected dict of rows, "
                            f"got {type(rows).__name__}")
            continue
        for row, rec in rows.items():
            if not isinstance(rec, dict):
                problems.append(f"{suite}.{row}: expected record dict, "
                                f"got {type(rec).__name__}")
                continue
            extra = set(rec) - {"us_per_call", "derived"}
            missing = {"us_per_call", "derived"} - set(rec)
            if extra:
                problems.append(f"{suite}.{row}: unknown key(s) "
                                f"{sorted(extra)}")
            if missing:
                problems.append(f"{suite}.{row}: missing key(s) "
                                f"{sorted(missing)}")
            us = rec.get("us_per_call")
            if "us_per_call" in rec and not isinstance(us, numbers.Real):
                problems.append(f"{suite}.{row}: us_per_call is "
                                f"{type(us).__name__}, expected number")
            der = rec.get("derived")
            if "derived" in rec and not isinstance(der, str):
                problems.append(f"{suite}.{row}: derived is "
                                f"{type(der).__name__}, expected str")
    return problems


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results as JSON to PATH")
    args, _ = ap.parse_known_args()
    quick = not args.full
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (common, e2e_embeddings, fig8_fpga_baselines,
                            fig9_throughput, fig10_rmat_skew, fig11_ablation,
                            roofline, serve_walks, step_impl_matrix,
                            table3_scaling, table4_kernels, tuned_vs_default)
    suites = {
        "fig8": fig8_fpga_baselines.run,
        "fig9": fig9_throughput.run,
        "fig10": fig10_rmat_skew.run,
        "fig11": fig11_ablation.run,
        "table3": table3_scaling.run,
        "table4": table4_kernels.run,
        "roofline": roofline.run,
        "serve": serve_walks.run,
        "step_impl": step_impl_matrix.run,
        "e2e_embeddings": e2e_embeddings.run,
        "tuned_vs_default": tuned_vs_default.run,
    }
    print("name,us_per_call,derived")
    payload = {"environment": environment_metadata()}
    failed = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        start = len(common.RECORDS)
        try:
            ret = fn(quick=quick)
        except Exception as e:  # a failing suite must not hide the others
            ret = None
            failed.append(name)
            common.emit(f"{name}_SUITE_ERROR", 0.0,
                        f"{type(e).__name__}:{e}")
        payload[name] = {
            row_name: {"us_per_call": us, "derived": derived}
            for row_name, us, derived in common.RECORDS[start:]
        }
        if name == "step_impl" and isinstance(ret, dict):
            # per-algorithm walks/sec summary across the three impls
            payload["walks_per_sec"] = ret
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if args.json:
        problems = validate_payload(payload)
        if problems:
            # never write a malformed BENCH.json — fail loudly instead
            print(f"# BENCH schema invalid ({len(problems)} problem(s)); "
                  f"not writing {args.json}:", file=sys.stderr)
            for p in problems:
                print(f"#   {p}", file=sys.stderr)
            sys.exit(1)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    if failed:
        # every suite ran (errors never hide the others), but the harness
        # itself must fail CI when any suite crashed
        print(f"# FAILED suites: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
