"""The comparison that decides `correct`, at a size a test run holds.

* The plain reference agrees with the program on every position of
  closed batches and served requests (uniform with restart, alias).
* The control, the reference computed in bfloat16 put in the program's
  place, comes out not correct.
* A run driven through the harness, with its look for a chip skipped and
  the timed path broken underneath, comes out not correct for each fault
  a single-chip cell can have: a step that leaves its walks where they
  were, half of each batch or request left out, and an answer altered
  where it is produced.  (No cell spans chips, so there is no exchange to
  leave out.)
"""
import copy
import importlib

import jax
import numpy as np
import pytest

from bench import checks, graphs, loops, reference, registry
from bench import run as bench_run

SEED = 3_000_000_017  # above 2**31, as the driver's seeds are


def test_base_key_is_zero_and_seed():
    for s in (0, 7, 2 ** 31 - 2):
        assert jax.random.key_data(jax.random.PRNGKey(s)).tolist() == [0, s]


def test_alias_tables_built_at_once_match_one_by_one():
    from bench.reference import alias
    rng = np.random.default_rng(7)
    deg = np.array([1, 2, 5, 40, 3, 700, 1, 64])
    row_ptr = np.concatenate([[0], np.cumsum(deg)])
    w = (1.0 - rng.random(row_ptr[-1])).astype(np.float32)
    w[row_ptr[3]:row_ptr[4]] = 0.5          # ties: every entry at 1.0
    w[row_ptr[7]:row_ptr[7] + 8] = 1e-6     # a run of tiny weights
    verts = np.array([5, 0, 3, 2, 7, 1, 4, 6])
    pos, prob, al = alias.vose_many(row_ptr, w, verts)
    for x in verts:
        p1, a1 = alias.vose(w[row_ptr[x]:row_ptr[x + 1]].astype(np.float64))
        at = (pos >= row_ptr[x]) & (pos < row_ptr[x + 1])
        np.testing.assert_array_equal(prob[at], p1)
        np.testing.assert_array_equal(al[at], a1)


def _walker(kind, stop, slots=32):
    from repro import walker
    from repro.core.samplers import SamplerSpec
    return walker.compile(
        walker.WalkProgram(spec=SamplerSpec(kind=kind, stop_prob=stop),
                           max_hops=24),
        execution=walker.ExecutionConfig(num_slots=slots, record_paths=True))


@pytest.mark.parametrize("kind, stop", [("alias", 0.0), ("uniform", 0.15)])
def test_reference_agrees_and_control_does_not(kind, stop):
    config = copy.deepcopy(registry.config(
        "g500-deepwalk" if kind == "alias" else "g500-ppr"))
    config["graph"]["scale"] = 9
    arrays = graphs._build(config)
    g = graphs.device_graph(arrays)
    ref = graphs.reference_graph(arrays)
    walk = {"sampler": kind, "stop_prob": stop, "max_hops": 24}
    w = _walker(kind, stop)
    starts = np.random.default_rng(1).choice(
        np.flatnonzero(np.diff(ref.row_ptr) > 0), 600)
    res = w.run(g, starts.astype(np.int32), seed=2 ** 31 - 2)
    closed = {"starts": starts, "paths": np.asarray(res.paths),
              "lengths": np.asarray(res.lengths),
              "seed": np.full(600, 2 ** 31 - 2, np.uint32),
              "epoch": np.zeros(600, np.int64), "qid": np.arange(600)}
    svc = w.serve(g, capacity=256, chunk=3, seed=5)
    for s in starts[:30]:
        svc.submit(np.full(40, s, np.int32))
    reqs = svc.drain()
    served = {"starts": np.repeat(starts[:30], 40),
              "paths": np.concatenate([r.paths for r in reqs]),
              "lengths": np.concatenate([r.lengths for r in reqs]),
              "seed": np.full(1200, 5, np.uint32),
              "epoch": np.concatenate([r.epochs for r in reqs]),
              "qid": np.concatenate([r.qids for r in reqs])}
    assert served["epoch"].max() > 0   # slots went around the ring
    for sample in (closed, served):
        counts = checks.compare(ref, walk, sample)
        assert counts["wrong_hops"] == counts["bad_rows"] == 0
        assert counts["non_edges"] == 0 and counts["hops_checked"] > 500
        assert checks.verdict(counts)[0]
        ok, table = checks.control_verdict(ref, walk, sample, counts)
        assert not ok and table["wrong_hops"]["value"] > 0


def _tiny(cell):
    bench = registry.benchmark()
    w = registry.cell(cell, bench)
    config = copy.deepcopy(registry.config(w["config"]))
    config["graph"]["scale"] = 9
    config["engine"]["num_slots"] = 64
    mix = copy.deepcopy(registry.traffic(w["traffic"]))
    if mix["loop"] == "closed":
        mix["walks_per_batch"] = 512
        mix["walks_per_source"] = min(mix["walks_per_source"], 64)
    else:
        config["service"]["capacity"] = 512
        mix["walks_per_source"] = 32
        mix["rate_per_s"] = 30.0
    return config, mix


def _measure(cell, tmp_path, monkeypatch):
    monkeypatch.setattr(loops, "GRACE_S", 2.0)
    config, mix = _tiny(cell)
    result, _ = bench_run.measure(cell, SEED, 0.5, False, config=config,
                                  mix=mix, require_tpu=False,
                                  cache_dir=tmp_path)
    return result


def _no_progress(monkeypatch):
    from repro.core import walk_engine
    real = walk_engine._process

    def stuck(graph, rows, spec, cfg, base_key, slots, paths, lengths, done):
        _, terminated, adv, _, _, _ = real(graph, rows, spec, cfg, base_key,
                                           slots, paths, lengths, done)
        # Lanes end where they stand: no hop is taken or recorded.
        ended = slots.active
        nd = done.shape[0]
        done = done.at[jax.numpy.where(ended, slots.query_id, nd)].set(
            True, mode="drop")
        return slots, ended, adv & False, paths, lengths, done

    monkeypatch.setattr(walk_engine, "_process", stuck)


def _half_left_out(monkeypatch):
    wc = importlib.import_module("repro.walker.compile")
    real_run, real_harvest = wc.Walker.run, wc._StreamBase.harvest_ids

    def run(self, graph, starts, seed=0):
        starts = np.asarray(starts)
        half = starts.shape[0] // 2
        res = real_run(self, graph, starts[:half], seed=seed)
        pad = np.full((starts.shape[0] - half,) + res.paths.shape[1:], -1,
                      np.int32)
        return res._replace(
            paths=np.concatenate([np.asarray(res.paths), pad]),
            lengths=np.concatenate([np.asarray(res.lengths),
                                    np.zeros(pad.shape[0], np.int32)]))

    def harvest_ids(self, qids):
        paths, lengths = (a.copy() for a in real_harvest(self, qids))
        half = paths.shape[0] // 2
        paths[half:], lengths[half:] = -1, 0
        return paths, lengths

    monkeypatch.setattr(wc.Walker, "run", run)
    monkeypatch.setattr(wc._StreamBase, "harvest_ids", harvest_ids)


def _answer_altered(monkeypatch):
    from repro.core import walk_engine
    real = walk_engine.column_access

    def altered(g, addr, index):
        v = real(g, addr, index)
        # One lane in eight takes the next vertex id instead.
        lane = jax.numpy.arange(v.shape[0]) % 8 == 3
        return jax.numpy.where(lane, (v + 1) % g.num_vertices, v)

    monkeypatch.setattr(walk_engine, "column_access", altered)


CELLS = ["g500-deepwalk.closed", "g500-ppr.serve"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path, monkeypatch):
    result = _measure(cell, tmp_path, monkeypatch)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", [_no_progress, _half_left_out,
                                   _answer_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, tmp_path,
                                          monkeypatch):
    fault(monkeypatch)
    result = _measure(cell, tmp_path, monkeypatch)
    assert not result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
