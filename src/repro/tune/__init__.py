"""Measurement-driven autotuner with roofline-model search-space pruning.

The engine's realized throughput hangs on machine knobs —
``num_slots``, ``hops_per_launch``, ``queue_depth_factor``, the E-S
reservoir chunking — whose right values are a function of
*(graph, sampler, machine, workload)*, not constants.  This package
closes that loop:

* `repro.tune.space` — the tunable knob grid + validity constraints
  (delegated to the config dataclasses' own validation);
* `repro.tune.model` — the analytical cost model (bytes/hop counted
  off the phase program's DMA schedule) used to prune the grid and to
  answer ``"auto"`` sentinels without timing;
* `repro.tune.measure` — the **only** module allowed to read a clock
  (interleaved min-of-k timing; tests inject deterministic costs);
* `repro.tune.cache` — the persistent JSON cache keyed by graph
  signature x sampler x machine x workload;
* `repro.tune.tuner` — orchestration: `autotune` (measured) and
  `resolve` (cache/model-only; what ``Walker`` compilation calls).

CLI: ``python -m repro.tune [--no-measure] --cache tune_cache.json``.
"""
from repro.tune.cache import (GraphSignature, TuningCache, cache_key,
                              default_cache_path, graph_signature,
                              workload_bucket)
from repro.tune.measure import InjectedMeasurer, Measurer, WalkMeasurer
from repro.tune.model import (DEFAULT_COEFFS, CostCoeffs, bytes_per_hop,
                              expected_walk_len, fit, lane_degree,
                              predict_us, prune)
from repro.tune.space import (Candidate, Knob, default_candidate,
                              enumerate_candidates, knobs_for)
from repro.tune.tuner import TuneResult, autotune, needs_resolution, resolve

__all__ = [
    "GraphSignature", "TuningCache", "cache_key", "default_cache_path",
    "graph_signature", "workload_bucket",
    "Measurer", "InjectedMeasurer", "WalkMeasurer",
    "CostCoeffs", "DEFAULT_COEFFS", "bytes_per_hop", "expected_walk_len",
    "fit", "lane_degree", "predict_us", "prune",
    "Candidate", "Knob", "default_candidate", "enumerate_candidates",
    "knobs_for",
    "TuneResult", "autotune", "needs_resolution", "resolve",
]
