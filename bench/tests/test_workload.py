"""The copied request generators: seeded, and cut off at the close."""
import numpy as np

import workload


def _seeds(alpha, seed=3):
    rank = workload.zipf_rank(np.arange(100)[::-1])  # vertex 0 most referenced
    return workload.ZipfSeeds(rank, alpha, np.random.default_rng(seed))


def test_zipf_seeds_are_seeded_and_skewed():
    a, b = _seeds(1.1).draw(5000), _seeds(1.1).draw(5000)
    assert np.array_equal(a, b)
    counts = np.bincount(a, minlength=100)
    assert counts[0] > counts[1] > counts[10] > 0
    assert np.bincount(_seeds(0.0).draw(5000), minlength=100).min() > 0


def test_closed_loop_issues_nothing_at_or_after_the_close():
    wl = workload.ClosedLoop(_seeds(1.1), clients=4, think_s=0.0,
                             close_at=5.0)
    first = wl.take_due(0.0)
    assert [r.client for r in first] == [0, 1, 2, 3]
    assert all(r.deadline == float("inf") for r in first)
    for r in first[:2]:
        wl.on_complete(r, 4.0)
    for r in first[2:]:
        wl.on_complete(r, 5.0)
    assert wl.next_time() == 4.0
    again = wl.take_due(6.0)
    assert [r.t_arrival for r in again] == [4.0, 4.0]
    assert wl.next_time() == float("inf") and wl.take_due(99.0) == []
