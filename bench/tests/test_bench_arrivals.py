"""The one generator of arrivals reads every mix's arrival process from data."""

import itertools

import numpy as np
import pytest

from benchlib import arrivals, spec


def _take(traffic, seed, n):
    return list(itertools.islice(arrivals.due_times(traffic, seed), n))


def test_closed_loop_has_no_due_times():
    assert _take({"arrival": "closed"}, 1, 5) == [None] * 5


def test_periodic_is_due_every_period():
    got = _take({"arrival": "periodic", "rate_per_s": 200}, 2 ** 31 + 5, 6)
    assert got == pytest.approx([i / 200 for i in range(6)])


def test_poisson_gives_every_seed_the_same_gaps_in_its_own_order():
    mix = {"arrival": "poisson", "rate_per_s": 50}
    a, b = arrivals.gaps(mix, 1), arrivals.gaps(mix, 3_000_000_000)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, arrivals.gaps(mix, 1))
    assert a.mean() == pytest.approx(1 / 50, rel=0.05)
    due = _take(mix, 1, 4)
    assert np.diff(due) == pytest.approx(a[:3])


def test_bursts_bring_several_requests_at_once():
    mix = {"arrival": "periodic", "rate_per_s": 10, "burst": {"size": 3, "every": 2}}
    got = _take(mix, 7, 8)
    assert got == pytest.approx([0.0, 0.0, 0.0, 0.1, 0.2, 0.2, 0.2, 0.3])


def test_unknown_process_is_refused():
    with pytest.raises(ValueError):
        _take({"arrival": "sometimes", "rate_per_s": 1}, 1, 1)


def test_every_committed_mix_runs_through_the_generator():
    for w in spec.benchmark()["workloads"]:
        mix = spec.load_traffic(w["traffic"])
        due = _take(mix, 11, 3)
        assert len(due) == 3
