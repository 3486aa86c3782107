"""The vectorised generator reproduces Table 3's shapes, from the seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import data

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 7])
def test_w8a_matches_table_3(seed):
    cfg = _config("w8a-lr")
    rows = data.make(cfg, seed)
    assert (rows.n, rows.d) == (64_696, 300)
    assert rows.values.shape == rows.indices.shape == (64_696, 114)
    assert rows.nnz.mean() == pytest.approx(11.65, rel=0.01)
    assert rows.nnz.min() >= 1 and rows.nnz.max() <= 114
    assert (np.count_nonzero(rows.values, axis=1) == rows.nnz).all()
    assert rows.indices.max() < 300
    for r in range(0, rows.n, 997):           # distinct features in a row
        idx = rows.indices[r, :rows.nnz[r]]
        assert len(np.unique(idx)) == len(idx)
    assert set(np.unique(rows.y)) == {-1.0, 1.0}


def test_covtype_matches_table_3():
    rows = data.make(_config("covtype-lr"), 3)
    assert rows.X.shape == (581_008, 54) and rows.X.dtype == np.float32
    assert rows.n == 581_008


def test_same_seed_same_rows_other_seed_other_rows():
    cfg = dict(_config("w8a-lr"), n=2048)
    a, b, c = (data.make(cfg, s) for s in (5, 5, 6))
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.indices, b.indices) and np.array_equal(a.y, b.y)
    assert not np.array_equal(a.values, c.values)


def test_popularity_is_zipf():
    rows = data.make(dict(_config("w8a-lr"), n=20_000), 1)
    counts = np.bincount(rows.indices[rows.values != 0], minlength=300)
    assert counts[0] > counts[9] > counts[99] > counts[299]


def test_to_dense_puts_each_nonzero_in_its_column():
    rows = data.make(dict(_config("w8a-lr"), n=64), 4)
    X = rows.to_dense()
    for r in range(64):
        k = rows.nnz[r]
        assert np.array_equal(X[r, rows.indices[r, :k]], rows.values[r, :k])
        assert np.count_nonzero(X[r]) == k
