"""Streaming (open-system) engine + WalkService: chunked/one-shot parity,
mid-stream injection, multi-tenant harvesting, and the ring-buffer slot
economy (continuous reclamation, epoch-salted RNG, no drain barrier)."""
import dataclasses
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_subprocess
from repro import walker
from repro.core import EngineConfig, rng as task_rng
from repro.core.samplers import SamplerSpec
from repro.core.walk_engine import (init_stream_state, inject_queries,
                                    make_superstep_runner, run_walks)
from repro.graph.csr import build_csr
from repro.serve import OpenLoad, WalkService, run_open_load

CFG = EngineConfig(num_slots=64, max_hops=12)
SPECS = {
    "uniform": SamplerSpec(kind="uniform"),
    "node2vec": SamplerSpec(kind="rejection_n2v", p=2.0, q=0.5),
}


def _drain_stream(runner, graph, state, seed, chunk):
    for _ in range(10_000):
        if bool(np.asarray(state.done).all()):
            return state
        state = runner(graph, state, seed, chunk)
    raise AssertionError("stream did not drain")


def _inject_fresh(state, starts, qid0=0):
    """Engine-level injection of fresh (epoch 0) queries into sequential
    slots — the closed-batch special case of the ring economy."""
    n = len(starts)
    qids = jnp.arange(qid0, qid0 + n, dtype=jnp.int32)
    return inject_queries(state, qids, jnp.asarray(starts, jnp.int32),
                          jnp.zeros((n,), jnp.int32), n)


@pytest.mark.parametrize("algo", sorted(SPECS))
def test_chunked_matches_oneshot(algo, small_graph, rng):
    """Parity: chunked run_supersteps == one-shot engine, bit-identical."""
    spec = SPECS[algo]
    starts = rng.integers(0, small_graph.num_vertices, 300).astype(np.int32)
    one = run_walks(small_graph, starts, spec, CFG, seed=3)
    p1, l1 = one.as_numpy()

    runner = make_superstep_runner(spec, CFG)
    state = init_stream_state(CFG, capacity=300)
    state = _inject_fresh(state, starts)
    state = _drain_stream(runner, small_graph, state, seed=3, chunk=7)
    assert np.array_equal(p1, np.asarray(state.paths))
    assert np.array_equal(l1, np.asarray(state.lengths))
    assert int(state.stats.terminations) == 300


def test_midstream_injection_preserves_paths(small_graph, rng):
    """Queries injected while the engine is mid-flight sample the same
    paths as a single up-front batch (stateless tasks, §V-A)."""
    spec = SPECS["uniform"]
    starts = rng.integers(0, small_graph.num_vertices, 200).astype(np.int32)
    p1, l1 = run_walks(small_graph, starts, spec, CFG, seed=5).as_numpy()

    runner = make_superstep_runner(spec, CFG)
    state = init_stream_state(CFG, capacity=200)
    state = _inject_fresh(state, starts[:80])
    state = runner(small_graph, state, 5, 4)
    assert not bool(np.asarray(state.done).all())
    state = _inject_fresh(state, starts[80:], qid0=80)
    state = _drain_stream(runner, small_graph, state, seed=5, chunk=6)
    assert np.array_equal(p1, np.asarray(state.paths))
    assert np.array_equal(l1, np.asarray(state.lengths))


def test_inject_padding_is_inert(small_graph, rng):
    """Padded injection (fixed block shapes) must not create phantom
    queries: tail advances by n_valid only, pad entries are dropped."""
    spec = SPECS["uniform"]
    starts = rng.integers(0, small_graph.num_vertices, 48).astype(np.int32)
    p1, l1 = run_walks(small_graph, starts, spec, CFG, seed=2).as_numpy()

    runner = make_superstep_runner(spec, CFG)
    state = init_stream_state(CFG, capacity=48)
    pad_q = np.full((32,), 48, np.int32)       # 48 = capacity = inert pad
    pad_s = np.zeros((32,), np.int32)
    pad_q[:20] = np.arange(20)
    pad_s[:20] = starts[:20]
    state = inject_queries(state, jnp.asarray(pad_q), jnp.asarray(pad_s),
                           jnp.zeros((32,), jnp.int32), 20)
    assert int(state.queue.tail) == 20
    state = _inject_fresh(state, starts[20:], qid0=20)
    assert int(state.queue.tail) == 48
    state = _drain_stream(runner, small_graph, state, seed=2, chunk=5)
    assert np.array_equal(p1, np.asarray(state.paths))
    assert np.array_equal(l1, np.asarray(state.lengths))


def test_legacy_inject_shim_warns_and_matches(small_graph, rng):
    """The pre-ring inject_queries(state, starts, n_valid) form survives
    as a deprecated shim with identical append-at-tail semantics."""
    spec = SPECS["uniform"]
    starts = rng.integers(0, small_graph.num_vertices, 40).astype(np.int32)
    p1, l1 = run_walks(small_graph, starts, spec, CFG, seed=6).as_numpy()
    runner = make_superstep_runner(spec, CFG)
    state = init_stream_state(CFG, capacity=40)
    with pytest.deprecated_call():
        state = inject_queries(state, jnp.asarray(starts), 40)
    assert int(state.queue.tail) == 40
    state = _drain_stream(runner, small_graph, state, seed=6, chunk=5)
    assert np.array_equal(p1, np.asarray(state.paths))
    assert np.array_equal(l1, np.asarray(state.lengths))


def test_staged_watermark_tracks_arrivals(small_graph):
    """Open system: the controller may stage only queries that actually
    arrived (staged <= tail), not the whole buffer capacity."""
    spec = SPECS["uniform"]
    runner = make_superstep_runner(spec, CFG)
    state = init_stream_state(CFG, capacity=512)
    state = _inject_fresh(state, np.zeros((16,), np.int32))
    state = runner(small_graph, state, 0, 3)
    assert int(state.queue.staged) <= int(state.queue.tail) == 16
    assert int(state.queue.head) <= int(state.queue.staged)


def test_service_two_waves(small_graph, rng):
    """Two request waves; every walk completes and each tenant harvests
    exactly its own queries."""
    cfg = dataclasses.replace(CFG, max_hops=8)
    svc = WalkService(small_graph, SPECS["uniform"], cfg,
                      capacity=512, chunk=4, seed=1)
    waves = []
    rids = []
    for _ in range(3):
        waves.append(rng.integers(0, small_graph.num_vertices, 16)
                     .astype(np.int32))
        rids.append(svc.submit(waves[-1]))
    svc.step()
    assert svc.num_inflight == 3
    for _ in range(2):
        waves.append(rng.integers(0, small_graph.num_vertices, 24)
                     .astype(np.int32))
        rids.append(svc.submit(waves[-1]))
    done = svc.drain()
    assert len(done) == 5 and svc.num_pending == svc.num_inflight == 0

    seen = set()
    for rid, starts in zip(rids, waves):
        r = svc.poll(rid)
        assert r is not None and r.done
        assert r.paths.shape == (len(starts), cfg.max_hops + 1)
        assert np.array_equal(r.paths[:, 0], starts)
        assert (r.lengths >= 1).all() and (r.lengths <= cfg.max_hops + 1).all()
        assert r.sojourn >= 1
        assert r.admission_wait >= 0
        assert r.sojourn >= r.admission_wait
        # (epoch, qid) identities are disjoint across tenants
        ids = {(int(e), int(q)) for e, q in zip(r.epochs, r.qids)}
        assert len(ids) == r.num_walks
        assert not (ids & seen)
        seen |= ids

    # harvested paths are real walks on the graph
    rp, col = np.asarray(small_graph.row_ptr), np.asarray(small_graph.col)
    r = svc.poll(rids[0])
    for q in range(r.num_walks):
        for t in range(r.lengths[q] - 1):
            u, v = r.paths[q, t], r.paths[q, t + 1]
            assert v in col[rp[u]:rp[u + 1]]


def test_service_ring_reclamation_bounded_buffer(small_graph, rng):
    """An unbounded request stream is served with a bounded device buffer
    via ring-buffer slot reclamation (no rotation, no drain barrier): all
    requests complete and recycled slots carry bumped epochs."""
    svc = WalkService(small_graph, SPECS["uniform"],
                      dataclasses.replace(CFG, max_hops=6),
                      capacity=64, chunk=4, seed=2)
    rids = [svc.submit(rng.integers(0, small_graph.num_vertices, 32))
            for _ in range(6)]
    done = svc.drain()
    assert len(done) == 6
    assert all(svc.poll(rid).done for rid in rids)
    assert int(svc.walk_stats().terminations) == 6 * 32
    # 6 x 32 = 192 walks through 64 slots: slots recycled at least twice
    assert max(int(r.epochs.max()) for r in done) >= 2
    # a recycled slot's occupants have strictly increasing epochs
    by_slot = {}
    for r in done:
        for e, q in zip(r.epochs, r.qids):
            by_slot.setdefault(int(q), []).append(int(e))
    assert any(len(v) > 1 for v in by_slot.values())
    for q, epochs in by_slot.items():
        assert len(set(epochs)) == len(epochs), f"slot {q} epoch reused"


def test_open_load_below_saturation_completes(small_graph):
    """Poisson arrivals at moderate utilization: everything completes and
    sojourn percentiles are finite."""
    svc = WalkService(small_graph, SPECS["uniform"],
                      dataclasses.replace(CFG, max_hops=8),
                      capacity=1024, chunk=4, seed=3)
    a = run_open_load(svc, OpenLoad(num_requests=20, request_size=8,
                                    utilization=0.5), seed=0)
    assert a.requests == 20
    assert a.walks == 20 * 8
    assert a.p50_sojourn <= a.p99_sojourn < float("inf")
    assert a.p50_admission_wait <= a.p99_admission_wait < float("inf")
    assert 0.0 <= a.bubble_ratio <= 1.0


# ------------------------------------------------------- streaming soak


def _soak_stream(stream, make_reference, graph, capacity, total, rng,
                 inject_wave=8, chunk=5):
    """Push ``total`` (> capacity) queries through a small slot ring,
    asserting the ring-economy invariants:

      * every (epoch, qid) identity is harvested exactly once,
      * each slot's occupant epochs strictly increase,
      * per epoch, harvested paths are bit-identical to a closed-batch
        ``Walker.run`` under ``rng.stream_key(seed, epoch)``.
    """
    pending = [rng.integers(0, graph.num_vertices, 1).astype(np.int32)[0]
               for _ in range(total)]
    harvested = {}          # (epoch, qid) -> (start, path, length)
    live = {}               # qid -> (epoch, start)
    max_epoch_seen = np.full((capacity,), -1, np.int64)
    while pending or live:
        n = min(inject_wave, stream.num_free, len(pending))
        if n:
            wave = np.asarray(pending[:n], np.int32)
            del pending[:n]
            qids, epochs = stream.inject(wave)
            for q, e, s in zip(qids, epochs, wave):
                assert int(e) > max_epoch_seen[q], "epoch must increase"
                max_epoch_seen[q] = int(e)
                live[int(q)] = (int(e), int(s))
        stream.advance(chunk)
        done = stream.done_live_mask()
        ready = [q for q in live if done[q]]
        if ready:
            paths, lengths = stream.harvest_ids(ready)
            for i, q in enumerate(ready):
                e, s = live.pop(q)
                key = (e, q)
                assert key not in harvested, f"{key} harvested twice"
                harvested[key] = (s, paths[i].copy(), int(lengths[i]))
            stream.release(ready)

    assert len(harvested) == total
    assert int(stream.walk_stats().drops) == 0

    # per-epoch closed-batch reference under the epoch-salted key
    by_epoch = {}
    for (e, q), rec in harvested.items():
        by_epoch.setdefault(e, {})[q] = rec
    assert len(by_epoch) >= 2, "soak must actually recycle slots"
    for e, rows in by_epoch.items():
        starts_e = np.zeros((capacity,), np.int32)
        for q, (s, _, _) in rows.items():
            starts_e[q] = s
        ref = make_reference(starts_e, task_rng.stream_key(stream.seed, e))
        ep, el = ref.as_numpy()
        for q, (_, path, length) in rows.items():
            assert np.array_equal(ep[q], path), (e, q)
            assert el[q] == length, (e, q)


@pytest.mark.parametrize("algo", sorted(SPECS))
def test_soak_single_device_ring(algo, small_graph, rng):
    """≥3× capacity queries through a 32-slot single-device ring."""
    program = walker.WalkProgram(spec=SPECS[algo], max_hops=8)
    w = walker.compile(program,
                       execution=walker.ExecutionConfig(num_slots=16))
    stream = w.stream(small_graph, capacity=32, seed=11)
    _soak_stream(stream, lambda s, k: w.run(small_graph, s, seed=k),
                 small_graph, capacity=32, total=100, rng=rng)


SHARDED_SOAK = r"""
import numpy as np
from repro import walker
from repro.graph import make_dataset, partition_graph
from tests_soak import soak

g = make_dataset("WG", scale_override=9)
pg = partition_graph(g, 2)
for algo in ("urw", "node2vec"):
    if algo == "urw":
        program = walker.WalkProgram.urw(8)
    else:
        program = walker.WalkProgram.node2vec(2.0, 0.5, 8)
    sharded = walker.compile(
        program, backend="sharded",
        execution=walker.ExecutionConfig(slots_per_device=8))
    single = walker.compile(
        program, execution=walker.ExecutionConfig(num_slots=16))
    stream = sharded.stream(pg, capacity=32, seed=11)
    soak(stream, lambda s, k: single.run(g, s, seed=k), g,
         capacity=32, total=100)
print("SHARDED_SOAK_OK")
"""


@pytest.mark.slow
def test_soak_sharded_ring_two_devices(tmp_path):
    """≥3× capacity queries through a 2-device sharded ring; per-epoch
    paths bit-identical to the single-device closed batch."""
    import inspect
    import os
    import textwrap

    # Ship the soak harness to the subprocess as a module so both soak
    # tests share one implementation.
    src = (
        "import numpy as np\n"
        "from repro.core import rng as task_rng\n"
        + textwrap.dedent(inspect.getsource(_soak_stream)).replace(
            "_soak_stream", "_soak_impl")
        + "\ndef soak(stream, ref, graph, capacity, total):\n"
        "    _soak_impl(stream, ref, graph, capacity, total,\n"
        "               np.random.default_rng(0))\n")
    (tmp_path / "tests_soak.py").write_text(src)
    out = run_in_subprocess(SHARDED_SOAK, devices=2,
                            extra_path=os.fspath(tmp_path))
    assert "SHARDED_SOAK_OK" in out


# ------------------------------------------------- spans and sync counters


def _cycle_graph(n: int = 16):
    """A ring with edges both ways: no walk dead-ends, so a walk of ``h``
    hops ends in exactly its ``h + 1``-th superstep."""
    edges = [(i, (i + 1) % n) for i in range(n)]
    return build_csr(np.array(edges + [(b, a) for a, b in edges]), n)


@pytest.mark.parametrize("backend", ["single", "sharded"])
def test_service_step_sync_counts(backend):
    """Blocking device→host reads of one ``WalkService.step``, by site: two
    reads of the superstep clock around the chunk and one ``done_mask``,
    then, in a step that finishes requests, one read of all their paths
    and lengths; the release check reuses the step's ``done_mask`` read.
    Pinned: a change that removes a sync changes these on purpose."""
    g = _cycle_graph()
    w = walker.compile(walker.WalkProgram.urw(4), backend=backend,
                       execution=walker.ExecutionConfig(num_slots=8))
    svc = w.serve(g, capacity=16, chunk=64, seed=1)

    def one_step(requests, k=None):
        before = svc.counters
        for n in requests:
            svc.submit(np.arange(n, dtype=np.int32))
        svc.step(k)
        return svc.counters - before

    chunk = Counter({"chunks": 1, "sync.advance": 2, "sync.done_mask": 1})
    assert one_step([], None) == Counter()          # nothing to run
    assert one_step([4], 2) == chunk                # 4 hops, 2 supersteps
    assert svc.num_inflight == 1
    assert one_step([]) == chunk + Counter({"sync.harvest": 1,
                                            "harvests": 1, "harvested": 1})
    assert one_step([3, 3]) == chunk + Counter({"sync.harvest": 1,
                                                "harvests": 1,
                                                "harvested": 2})
    assert svc.num_inflight == svc.num_pending == 0
    # Reading the engine counters is a blocking read of each of them.
    before = svc.counters
    svc.walk_stats()
    assert svc.counters - before == Counter(
        {"sync.walk_stats": len(svc.walk_stats()._fields)})
    assert svc.stream.num_syncs == sum(svc.stream.syncs.values())


def _program_spans(log_dir) -> list:
    """``(name, metadata)`` of the program's spans in a profiler trace."""
    path = next(Path(log_dir).glob("**/*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#", 1)[0]
                    if name.startswith(("serve.", "stream.", "walker.")):
                        out.append((name, dict(ev.stats)))
    return out


def test_service_and_walker_spans_in_a_profiler_trace(tmp_path):
    """The service's and ``Walker.run``'s spans land in a ``jax.profiler``
    trace with their metadata: one ``serve.harvest`` per harvesting step
    with its request count and ids, and the step's chunks and syncs on
    ``serve.step``."""
    g = _cycle_graph()
    ex = walker.ExecutionConfig(num_slots=8)
    single = walker.compile(walker.WalkProgram.urw(4), execution=ex)
    sharded = walker.compile(walker.WalkProgram.urw(4), backend="sharded",
                             execution=ex)
    svc = single.serve(g, capacity=16, chunk=2, seed=1)
    starts = np.arange(4, dtype=np.int32)
    for w in (single, sharded):             # compile outside the trace
        w.run(g, starts)
    svc.submit(starts)
    svc.drain()
    before = svc.counters

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rids = [svc.submit(starts[:3]) for _ in range(2)]
        svc.drain()
        for w in (single, sharded):
            w.run(g, starts, seed=2)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    names = Counter(n for n, _ in spans)
    assert set(names) == {
        "serve.step", "serve.admit", "serve.advance", "stream.dispatch",
        "stream.sync", "serve.done_mask", "serve.harvest", "serve.release",
        "walker.bind", "walker.engine", "walker.assemble"}
    assert names["walker.bind"] == names["walker.engine"] == 2
    assert names["walker.assemble"] == 1    # sharded only
    assert names["stream.sync"] == 2 * names["stream.dispatch"]
    # Both requests finish in one step: one harvest takes them together.
    assert names["serve.release"] == names["serve.harvest"] == 1
    harvests = [m for n, m in spans if n == "serve.harvest"]
    assert [m["requests"] for m in harvests] == [2]
    assert sorted(int(r) for m in harvests
                  for r in str(m["request_ids"]).split()) == rids
    steps = [m for n, m in spans if n == "serve.step"]
    delta = svc.counters - before
    assert delta["harvests"] == 1 and delta["harvested"] == 2
    assert sum(m["chunks"] for m in steps) == delta["chunks"] > 0
    assert sum(m["syncs"] for m in steps) == sum(
        v for k, v in delta.items() if k.startswith("sync."))
    assert sum(m["admitted"] for n, m in spans if n == "serve.admit") == 2


# ------------------------------------------------------- batched harvest


def _urw_walker(backend):
    return walker.compile(walker.WalkProgram.urw(4), backend=backend,
                          execution=walker.ExecutionConfig(num_slots=8))


@pytest.mark.parametrize("backend", ["single", "sharded"])
@pytest.mark.parametrize("sizes", [[7], [5, 17], [3, 20, 40]],
                         ids=["one", "two", "three"])
def test_requests_harvested_together_match_each_alone(backend, sizes,
                                                      small_graph, rng):
    """Requests that finish in the same step, harvested by one gather and
    one read, get exactly the paths and lengths that harvesting each
    request alone gives, and leave the same epochs and free ring; over two
    rounds, so the second runs on recycled slots."""
    w = _urw_walker(backend)
    cap = sum(sizes) + 1            # the second round wraps the ring
    svc = w.serve(small_graph, capacity=cap, chunk=64, seed=4)
    alone = w.stream(small_graph, capacity=cap, seed=4)
    for round_ in range(2):
        starts = [rng.integers(0, small_graph.num_vertices, n)
                  for n in sizes]
        before = svc.counters
        rids = [svc.submit(s) for s in starts]
        svc.step()          # every walk ends within 5 supersteps
        assert svc.counters - before >= Counter(
            {"harvests": 1, "harvested": len(sizes)})
        for rid, s in zip(rids, starts):
            req = svc.poll(rid)
            qids, epochs = alone.inject(s)
            np.testing.assert_array_equal(qids, req.qids)
            np.testing.assert_array_equal(epochs, req.epochs)
            alone.drain()
            paths, lengths = alone.harvest_ids(qids)
            alone.release(qids)
            np.testing.assert_array_equal(req.paths, paths)
            np.testing.assert_array_equal(req.lengths, lengths)
        every = np.arange(cap)
        np.testing.assert_array_equal(svc.stream.epoch_of(every),
                                      alone.epoch_of(every))
        assert list(svc.stream._free) == list(alone._free)
        assert max(int(svc.poll(r).epochs.max()) for r in rids) == round_


@pytest.mark.parametrize("backend", ["single", "sharded"])
def test_release_reuses_the_done_mask_read(backend):
    """``release`` right after ``done_mask`` makes no blocking read; once
    a dispatch has replaced the state it reads ``done`` again, and still
    refuses a slot whose walk has not ended."""
    stream = _urw_walker(backend).stream(_cycle_graph(), capacity=16, seed=1)
    qids, _ = stream.inject(np.arange(4))
    stream.drain()                  # ends on a done_mask read
    before = stream.syncs
    stream.release(qids)
    assert stream.syncs == before
    qids, _ = stream.inject(np.arange(4))
    assert not stream.done_mask()[qids].any()
    with pytest.raises(ValueError, match="unfinished"):
        stream.release(qids)        # refused on the done_mask read
    assert stream.syncs - before == Counter({"done_mask": 1})
    stream.advance(1)               # 4 hops take 5 supersteps
    with pytest.raises(ValueError, match="unfinished"):
        stream.release(qids)
    assert stream.syncs["release"] == before["release"] + 1


@pytest.mark.parametrize("backend", ["single", "sharded"])
def test_harvest_compiles_no_shape_after_warm_up(backend):
    """Once a service has harvested its first request, requests finishing
    in any grouping, up to the whole ring, make the jitted gather compile
    no further shape."""
    svc = _urw_walker(backend).serve(_cycle_graph(), capacity=64, chunk=64,
                                     seed=2)
    svc.submit(np.arange(2, dtype=np.int32))
    svc.drain()
    gather = type(svc.stream)._rows
    compiled = gather._cache_size()
    before = svc.counters
    groupings = [[1], [2, 3], [10, 10], [16, 17], [30, 30, 4], [64],
                 [9] * 7]
    for sizes in groupings:
        for n in sizes:
            svc.submit(np.arange(n, dtype=np.int32) % 16)
        svc.step()
        assert svc.num_inflight == 0
    assert gather._cache_size() == compiled
    delta = svc.counters - before
    assert delta["harvests"] == len(groupings)
    assert delta["harvested"] == sum(len(g) for g in groupings)
