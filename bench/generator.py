"""The one traffic generator: turns a traffic mix (``bench/traffic/<name>.json``)
and a seed into the work a run offers.

A mix is data only.  Its keys:

* ``loop``: ``closed`` (batches back to back, each sent when the last came
  back) or ``open`` (requests sent at their due times, whatever is still
  running).
* ``sources``: which vertices start walks, drawn uniformly from the run's
  seed: ``"out_degree_ge_1"``, the vertices with an edge.
* ``walks_per_source``: walks started at each drawn source (a PPR query
  is that many walks from one source; a DeepWalk start is one walk).
* closed: ``walks_per_batch``, a multiple of ``walks_per_source``.
* open: ``rate_per_s`` requests per second, one source per request, and
  ``arrivals``: ``{"process": "poisson", "seed": n}``.  Arrival times are
  a Poisson process conditioned on its count: exactly ``round(rate_per_s
  * seconds)`` requests, drawn from the mix's own seed, so that every run
  offers the same arrivals and the run's seed draws the sources, and with
  them the walks.
"""
from __future__ import annotations

import numpy as np

LOOPS = ("closed", "open")
POOLS = ("out_degree_ge_1",)
PROCESSES = ("poisson",)


class Sources:
    """Seeded uniform draws of source vertices from the mix's pool."""

    def __init__(self, degree: np.ndarray, rng: np.random.Generator):
        self.pool = np.flatnonzero(degree >= 1).astype(np.int32)
        self.rng = rng

    def draw(self, n: int) -> np.ndarray:
        return self.pool[self.rng.integers(0, self.pool.size, n)]


def batch_starts(mix: dict, sources: Sources) -> np.ndarray:
    """One closed batch: each drawn source repeated ``walks_per_source``
    times in a row, ``walks_per_batch`` walks in all."""
    per = int(mix["walks_per_source"])
    total = int(mix["walks_per_batch"])
    return np.repeat(sources.draw(total // per), per).astype(np.int32)


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """Sorted due times, in seconds from the window's start."""
    n = int(round(float(mix["rate_per_s"]) * seconds))
    rng = np.random.default_rng(int(mix["arrivals"]["seed"]))
    return np.sort(rng.uniform(0.0, seconds, n))


def requests(mix: dict, sources: Sources, seconds: float):
    """The open loop's requests: (due times, one source per request)."""
    due = arrival_times(mix, seconds)
    return due, sources.draw(due.size).astype(np.int32)


def validate(mix: dict) -> None:
    """Refuse a mix this generator cannot read."""
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}, got "
                         f"{mix.get('loop')!r}")
    need = {"closed": ("sources", "walks_per_source", "walks_per_batch"),
            "open": ("sources", "walks_per_source", "rate_per_s",
                     "arrivals")}
    missing = [k for k in need[mix["loop"]] if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    if mix["sources"] not in POOLS:
        raise ValueError(f"sources must be one of {POOLS}, got "
                         f"{mix['sources']!r}")
    if mix["loop"] == "closed" and \
            int(mix["walks_per_batch"]) % int(mix["walks_per_source"]):
        raise ValueError("walks_per_batch must be a multiple of "
                         "walks_per_source")
    if mix["loop"] == "open" and (
            mix["arrivals"].get("process") not in PROCESSES
            or "seed" not in mix["arrivals"]):
        raise ValueError(f"arrivals must name a process of {PROCESSES} "
                         "and a seed")
