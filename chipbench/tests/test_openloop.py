"""The Poisson schedule and the exact percentiles, from the seed."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.kinds import openloop


def test_schedule_is_reproducible_from_the_seed():
    a = openloop.schedule(2 ** 33 + 1, 4000, 10, 64_696)
    b = openloop.schedule(2 ** 33 + 1, 4000, 10, 64_696)
    c = openloop.schedule(2 ** 33 + 2, 4000, 10, 64_696)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0][:100], c[0][:100])


def test_schedule_is_poisson_at_the_rate():
    due, rows = openloop.schedule(9, 4000, 10, 64_696)
    assert len(due) == pytest.approx(40_000, rel=0.02)
    assert (np.diff(due) > 0).all() and due[-1] < 10
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / 4000, rel=0.02)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)   # exponential
    assert rows.min() >= 0 and rows.max() < 64_696


def test_percentiles_are_exact_and_reproducible():
    lat = np.random.default_rng(3).exponential(2.0, 10_001)
    assert harness.percentile(lat, 50) == np.sort(lat)[5000]
    assert harness.percentile(lat, 99) == np.sort(lat)[9900]
    assert harness.percentile(lat, 99) == harness.percentile(lat.copy(), 99)
    assert harness.percentile([1.0, 2.0], 50) == 1.5
