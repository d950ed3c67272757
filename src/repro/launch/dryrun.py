import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape × mesh) cell:
  jax.jit(step_fn).lower(*ShapeDtypeStructs).compile()
and record memory_analysis / cost_analysis / collective bytes parsed from
the post-SPMD HLO — the inputs to the §Roofline analysis.  No arrays are
ever allocated (ShapeDtypeStruct stand-ins only).

Results land incrementally in experiments/dryrun/<mesh>/<arch>__<shape>.json
so the sweep is resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi35_moe --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCHS, get_arch
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the SPMD module."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s*"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", ls)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if "-start" in ls.split("=")[1][:64] and op + "-start" not in ls:
            pass
        out[op] += _shape_bytes(shape_str)
        counts[op] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    out["counts"] = counts
    return out


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   chips: int) -> dict:
    """Terms in seconds. The compiled SPMD module is the PER-DEVICE
    program, so cost_analysis flops/bytes and the parsed collective shard
    bytes are already per-chip: divide by per-chip peaks only.  (The spec
    formula `total / (chips × peak)` is identical — our inputs are
    `total / chips` already.)"""
    ct = flops / PEAK_FLOPS_BF16
    mt = bytes_accessed / HBM_BW
    lt = coll_bytes / ICI_BW
    terms = {"compute_s": ct, "memory_s": mt, "collective_s": lt}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    terms["bound_s"] = max(ct, mt, lt)
    return terms


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             force: bool = False, overrides: dict | None = None,
             tag: str = "") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(d, f"{arch}__{shape}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    from repro.launch.specs import build_cell, scan_layer_count
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape, "mesh": list(mesh.shape.values()),
           "chips": chips, "status": "error", "overrides": overrides or {},
           "tag": tag}
    t0 = time.time()
    try:
        fn, args, donate, meta = build_cell(arch, shape, mesh, multi_pod,
                                            overrides=overrides)
        rec["meta"] = meta
        with mesh:
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
        rec["lower_compile_s"] = time.time() - t0

        def _cost(c):
            ca = c.cost_analysis() or {}
            cb = collective_bytes(c.as_text())
            return {"flops": float(ca.get("flops", 0.0)),
                    "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                    "collective_bytes": cb["total"],
                    "collectives": cb}

        cost = _cost(compiled)
        rec["cost_analysis_raw"] = dict(cost)

        # XLA's cost model counts a lax.scan body ONCE regardless of trip
        # count.  For scanned-layer models, lower L=1 and L=2 variants and
        # extrapolate: cost(L) = cost(1) + (L-1)·(cost(2)-cost(1)).
        field, L = scan_layer_count(arch)
        if field is not None and L and L > 1:
            with mesh:
                f1, a1, _, _ = build_cell(arch, shape, mesh, multi_pod,
                                          layers_override=1,
                                          overrides=overrides)
                c1 = _cost(jax.jit(f1).lower(*a1).compile())
                f2, a2, _, _ = build_cell(arch, shape, mesh, multi_pod,
                                          layers_override=2,
                                          overrides=overrides)
                c2 = _cost(jax.jit(f2).lower(*a2).compile())
            for k in ("flops", "bytes_accessed", "collective_bytes"):
                per_layer = max(c2[k] - c1[k], 0.0)
                cost[k] = c1[k] + (L - 1) * per_layer
            rec["cost_extrapolation"] = {
                "layers": L, "L1": {k: c1[k] for k in
                                    ("flops", "bytes_accessed",
                                     "collective_bytes")},
                "L2": {k: c2[k] for k in ("flops", "bytes_accessed",
                                          "collective_bytes")}}
        rec["cost_analysis"] = {"flops": cost["flops"],
                                "bytes_accessed": cost["bytes_accessed"]}
        rec["collectives"] = cost["collectives"]
        rec["collectives"]["total"] = cost["collective_bytes"]
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
        except Exception as e:  # CPU backend may not expose this
            rec["memory_analysis"] = {"error": str(e)}
        rec["roofline"] = roofline_terms(cost["flops"],
                                         cost["bytes_accessed"],
                                         cost["collective_bytes"], chips)
        rec["status"] = "ok"
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
        rec["lower_compile_s"] = time.time() - t0
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    for a in archs:
        mod = get_arch(a)
        shapes = list(mod.SHAPES) if args.shape is None else [args.shape]
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    n_ok = 0
    for a, s, mp in cells:
        rec = run_cell(a, s, mp, args.out, force=args.force)
        tag = "multi " if mp else "single"
        if rec["status"] == "ok":
            n_ok += 1
            r = rec["roofline"]
            print(f"[{tag}] {a:14s} {s:14s} OK   "
                  f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                  f"coll={r['collective_s']:.3e}s dom={r['dominant']}",
                  flush=True)
        else:
            print(f"[{tag}] {a:14s} {s:14s} FAIL {rec['error'][:120]}",
                  flush=True)
    print(f"{n_ok}/{len(cells)} cells OK")


if __name__ == "__main__":
    main()
