"""The one traffic generator: same seed, same traffic; every seed offers
the same amount of work; a mix's arrival seed fixes its arrival times."""
import numpy as np
import pytest

from bench import generator, registry

DEGREE = np.array([0, 3, 1, 0, 5, 2, 0, 1] * 64)


def _mix(**kw):
    base = {"loop": "open", "sources": "out_degree_ge_1",
            "walks_per_source": 4, "rate_per_s": 50.0,
            "arrivals": {"process": "poisson", "seed": 99}}
    base.update(kw)
    generator.validate(base)
    return base


def _draw(mix, seed, seconds=4.0):
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    return generator.requests(mix, generator.Sources(DEGREE, rng), seconds)


def test_open_traffic():
    mix = _mix()
    due, src = _draw(mix, 2 ** 33 + 5)
    again = _draw(mix, 2 ** 33 + 5)
    other = _draw(mix, 2 ** 33 + 6)
    assert due.size == other[0].size == 200
    assert np.all(np.diff(due) >= 0) and due.min() >= 0 and due.max() < 4.0
    np.testing.assert_array_equal(due, again[0])
    np.testing.assert_array_equal(src, again[1])
    assert not np.array_equal(src, other[1])
    assert np.all(DEGREE[src] >= 1)


def test_arrival_seed_fixes_the_times_not_the_sources():
    mix = _mix()
    a, b = _draw(mix, 1), _draw(mix, 2)
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[1], b[1])
    c = _draw(_mix(arrivals={"process": "poisson", "seed": 100}), 1)
    assert not np.array_equal(a[0], c[0])


def test_closed_batches_repeat_each_source():
    mix = {"loop": "closed", "sources": "out_degree_ge_1",
           "walks_per_source": 8, "walks_per_batch": 64}
    generator.validate(mix)
    rng = np.random.default_rng(3)
    starts = generator.batch_starts(mix, generator.Sources(DEGREE, rng))
    assert starts.shape == (64,) and starts.dtype == np.int32
    assert np.all(starts.reshape(8, 8) == starts.reshape(8, 8)[:, :1])
    assert np.all(DEGREE[starts] >= 1)


@pytest.mark.parametrize("mix, match", [
    ({"loop": "sideways"}, "loop"),
    ({"loop": "open", "sources": "out_degree_ge_1", "walks_per_source": 1},
     "rate_per_s"),
    ({"loop": "closed", "sources": "all", "walks_per_source": 1,
      "walks_per_batch": 8}, "sources"),
    ({"loop": "closed", "sources": "out_degree_ge_1", "walks_per_source": 3,
      "walks_per_batch": 8}, "multiple"),
    ({"loop": "open", "sources": "out_degree_ge_1", "walks_per_source": 1,
      "rate_per_s": 1.0, "arrivals": {"process": "poisson"}}, "seed"),
])
def test_bad_mixes_are_refused(mix, match):
    with pytest.raises(ValueError, match=match):
        generator.validate(mix)


def test_committed_mixes_are_valid():
    for cell in registry.benchmark()["workloads"]:
        generator.validate(registry.traffic(cell["traffic"]))
