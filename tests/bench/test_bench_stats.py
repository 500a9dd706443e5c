"""Serving latencies: exact percentiles over every request scheduled in
the window, a failed request entering at the wait limit."""
import numpy as np
import pytest

from bench import serve


def _win(lat):
    return {"lat_ms": lat}


def test_percentiles_are_exact_over_the_whole_list():
    # 20 latencies: the 95th lies 0.05 of the way from the 19th to the 20th
    lat = [float(i) for i in range(1, 21)]
    got = serve.e2e(_win(lat))
    assert got["p50_ms"] == 10.5
    assert got["p95_ms"] == pytest.approx(19.05)


@pytest.mark.parametrize("q", ["p50_ms", "p95_ms"])
def test_percentiles_match_numpy_on_a_long_tail(q):
    lat = list(np.random.default_rng(3).exponential(10.0, size=257))
    want = {"p50_ms": np.median(lat), "p95_ms": np.percentile(lat, 95)}[q]
    assert serve.e2e(_win(lat))[q] == pytest.approx(want)


def test_a_failed_request_counts_at_the_wait_limit():
    # 19 answered in 1 ms, one never answered (the wait limit, 60 s past
    # the last due time): more than 5% failed moves the tail to the limit
    lat = [1.0] * 18 + [60_000.0] * 2
    assert serve.e2e(_win(lat))["p95_ms"] == pytest.approx(60_000.0)


def test_schedule_offers_every_seed_the_same_gaps_and_mix():
    tr = {"arrivals": "poisson", "rate_hz": 200.0, "weights": [2, 1, 1],
          "pool_per_shape": 16}
    a, b = serve.schedule(tr, 5.0, 1), serve.schedule(tr, 5.0, 2 ** 31 + 7)
    assert len(a) == len(b) == 1000
    gaps = [np.sort(np.diff([0.0] + [t for t, _, _ in s])) for s in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1])
    assert [t for t, _, _ in a] != [t for t, _, _ in b]
    for s in (a, b):
        shapes = np.bincount([si for _, si, _ in s])
        assert list(shapes) == [500, 250, 250]
        assert max(t for t, _, _ in s) < 5.0
