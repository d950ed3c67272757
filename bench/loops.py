"""The timed paths: a closed loop of ``Walker.run`` batches and an open loop
of requests to ``Walker.serve``.  Each warms up the shapes its window
uses, then measures, and hands back what it measured, the program's
counters over the window, and a seeded sample of the walks it returned
(with the identity each was sampled under) for the comparison with the
reference.

Every call into the program sits in a ``jax.profiler.TraceAnnotation``
span (``window``, ``batch``, ``walker.run``, ``fetch``, ``svc.submit``,
``svc.step``, ``wait``), so that a traced run can say what the host was
doing while the device sat idle.  The spans cost about a microsecond each
when no trace is being taken, and are there in every run alike.
"""
from __future__ import annotations

import time

import numpy as np

from bench import generator

SAMPLE_PER_BATCH = 256    # walks checked per closed batch, besides its longest
SAMPLE_REQUESTS = 64      # requests checked per open window, besides the longest
GRACE_S = 60.0            # how long the open loop waits for late answers
COUNTERS = ("steps", "slot_steps", "bubbles", "starved", "supersteps",
            "launches", "drops")


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def walk_seed(rng: np.random.Generator) -> int:
    """A walk seed the program takes as a 32-bit signed int."""
    return int(rng.integers(0, 2 ** 31 - 1))


def _counters(stats) -> dict:
    return {k: int(getattr(stats, k)) for k in COUNTERS}


class Closed:
    """Batches of ``walks_per_batch`` walks, one ``Walker.run`` each, back
    to back; each batch's paths and lengths are fetched to the host before
    the next is sent, as a corpus or PPR job that consumes them would."""

    def __init__(self, walker, graph, mix, sources, rng):
        self.walker, self.graph, self.mix = walker, graph, mix
        self.sources, self.rng = sources, rng

    def _batch(self, starts, seed):
        import jax
        with _span("batch"):
            with _span("walker.run"):
                res = self.walker.run(self.graph, starts, seed=seed)
            with _span("fetch"):
                paths = np.asarray(res.paths)
                lengths = np.asarray(res.lengths)
                stats = jax.device_get(res.stats)
        return paths, lengths, stats

    def warm_up(self) -> None:
        self._batch(generator.batch_starts(self.mix, self.sources),
                    walk_seed(self.rng))

    def window(self, seconds: float) -> dict:
        parts, counters, walks, batch_s = [], dict.fromkeys(COUNTERS, 0), 0, []
        t0 = time.perf_counter()
        with _span("window"):
            while True:
                starts = generator.batch_starts(self.mix, self.sources)
                seed = walk_seed(self.rng)
                tb = time.perf_counter()
                paths, lengths, stats = self._batch(starts, seed)
                te = time.perf_counter()
                batch_s.append(te - tb)
                for k, v in _counters(stats).items():
                    counters[k] += v
                walks += starts.size
                pick = self.rng.choice(starts.size,
                                       min(SAMPLE_PER_BATCH, starts.size),
                                       replace=False)
                pick = np.union1d(pick, [int(np.argmax(lengths))])
                parts.append({"starts": starts[pick], "paths": paths[pick],
                              "lengths": lengths[pick],
                              "seed": np.full(pick.size, seed, np.uint32),
                              "epoch": np.zeros(pick.size, np.int64),
                              "qid": pick.astype(np.int64)})
                if te - t0 >= seconds:
                    break
        window_s = te - t0
        sample = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        return {"window_s": window_s, "counters": counters,
                "attempted": walks, "failed": 0, "missing": 0, "dup_ids": 0,
                "sample": sample, "batches": len(batch_s),
                "e2e": {"steps_per_s": counters["steps"] / window_s},
                "notes": {"batches": len(batch_s),
                          "batch_s_median": float(np.median(batch_s))}}


class Open:
    """Requests of ``walks_per_source`` walks from one source, sent at due
    times drawn by the generator, whatever is still running; the service
    steps (admit, one chunk of supersteps, harvest) while any request is
    pending or in flight, and the loop sleeps until the next due time when
    none is.  A request's latency runs from its due time to its harvest.
    Requests due in the window that are still out when it closes are
    waited for, up to ``GRACE_S``; one that never comes back is failed."""

    def __init__(self, walker, graph, mix, sources, rng, service_cfg,
                 seed: int):
        self.mix, self.sources, self.rng = mix, sources, rng
        self.seed = seed
        self.per = int(mix["walks_per_source"])
        self.svc = walker.serve(graph, capacity=int(service_cfg["capacity"]),
                                chunk=int(service_cfg["chunk"]), seed=seed)

    def _request(self, source) -> np.ndarray:
        return np.full(self.per, source, np.int32)

    def warm_up(self) -> None:
        for s in self.sources.pool[:2]:
            self.svc.submit(self._request(s))
        self.svc.drain()

    def window(self, seconds: float) -> dict:
        svc = self.svc
        due, src = generator.requests(self.mix, self.sources, seconds)
        n = due.size
        rids = np.full(n, -1, np.int64)
        sent = np.zeros(n)
        before = svc.walk_stats()
        i = 0
        t0 = time.perf_counter()
        with _span("window"):
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                while i < n and due[i] <= now:
                    sent[i] = time.perf_counter() - t0
                    with _span("svc.submit"):
                        rids[i] = svc.submit(self._request(src[i]))
                    i += 1
                if svc.num_pending or svc.num_inflight:
                    with _span("svc.step"):
                        svc.step()
                else:
                    nxt = due[i] if i < n else seconds
                    with _span("wait"):
                        time.sleep(max(0.0, min(nxt, seconds)
                                       - (time.perf_counter() - t0)))
            t_end = time.perf_counter()
        after = svc.walk_stats()
        counters = {k: getattr(after, k) - getattr(before, k)
                    for k in COUNTERS}
        for j in range(i, n):      # due in the window, sent late
            sent[j] = time.perf_counter() - t0
            rids[j] = svc.submit(self._request(src[j]))
        deadline = t_end + GRACE_S
        while (svc.num_pending or svc.num_inflight) and \
                time.perf_counter() < deadline:
            svc.step()
        reqs = [svc.poll(int(r)) for r in rids]
        done = np.array([r is not None for r in reqs])
        completed = np.array([r.wall_completed if r is not None else np.inf
                              for r in reqs])
        latency = completed - (t0 + due)
        hops = np.array([int(r.lengths.sum()) - r.num_walks
                         if r is not None else 0 for r in reqs])
        in_window = completed <= t_end
        window_s = t_end - t0
        admission = np.array([r.wall_admitted - r.wall_submitted
                              for r in reqs if r is not None])
        sample, dup = self._sample([r for r in reqs if r is not None],
                                   src[done])
        from bench.stats import nearest_rank
        return {"window_s": window_s, "counters": counters,
                "attempted": int(n), "failed": int((~done).sum()),
                "missing": int((~done).sum()), "dup_ids": dup,
                "sample": sample,
                "requests": {"latency_s": latency,
                             "admission_wait_s": admission,
                             "generator_late_s": sent - due},
                "e2e": {"served_steps_per_s": float(hops[in_window].sum())
                        / window_s,
                        "request_p95_ms": 1e3 * nearest_rank(latency, 0.95),
                        "request_p50_ms": 1e3 * nearest_rank(latency, 0.50)},
                "notes": {"requests": int(n),
                          "completed_in_window": int(in_window.sum()),
                          "sent_late": int(n - i)}}

    def _sample(self, reqs, sources):
        """Seeded sample of the answered requests, with the one holding
        the longest walk; and how many walk identities were handed out
        twice among all of them."""
        if not reqs:
            return None, 0
        ids = np.concatenate([r.epochs.astype(np.int64) * self.svc.capacity
                              + r.qids for r in reqs])
        dup = int(ids.size - np.unique(ids).size)
        longest = int(np.argmax([r.lengths.max() for r in reqs]))
        k = min(SAMPLE_REQUESTS, len(reqs))
        pick = np.union1d(self.rng.choice(len(reqs), k, replace=False),
                          [longest])
        chosen = [reqs[j] for j in pick]
        sample = {
            "starts": np.concatenate([self._request(sources[j])
                                      for j in pick]),
            "paths": np.concatenate([r.paths for r in chosen]),
            "lengths": np.concatenate([r.lengths for r in chosen]),
            "seed": np.full(self.per * len(chosen), self.seed, np.uint32),
            "epoch": np.concatenate([r.epochs for r in chosen]),
            "qid": np.concatenate([r.qids for r in chosen])}
        return sample, dup
