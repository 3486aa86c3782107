"""The ``train_wide`` kind off the chip: the CSR generator, the CSR
reference against the dense one, and the faults and control of this
kind coming out not correct through ``harness.run``."""
import numpy as np
import pytest

from chipbench import data_wide, harness, reference, reference_csr
from chipbench.kinds import train_wide
from conftest import drive

#: rows of the tiny cell: a few micro-batches of news20's row widths
TINY_ROWS = 32


def _config():
    return harness.load_cell("news.train").config


def test_widths_keep_the_sources_mean_and_widest_row():
    """At full n the rows hold news20's 454.99 mean and 16,423 widest
    nonzeros to within 1%, over four seeds."""
    cfg, gen = _config(), _config()["generator"]
    counts = np.concatenate([
        data_wide.widths(np.random.default_rng(s), cfg["n"], cfg["max_nnz"],
                         gen["nnz_mu"], gen["nnz_sigma"]) for s in range(4)])
    assert counts.mean() == pytest.approx(cfg["mean_nnz"], rel=0.01)
    assert counts.max() == pytest.approx(cfg["max_nnz"], rel=0.01)
    assert counts.min() >= 1


def _small(seed):
    gen = _config()["generator"]
    return data_wide.csr_rows(seed, 200, 50_000, 2_000, gen["nnz_mu"],
                              gen["nnz_sigma"], gen["noise"])


def test_rows_are_unit_norm_distinct_and_pure_in_the_seed():
    seed = 2 ** 33 + 7
    rows = _small(seed)
    row = np.repeat(np.arange(rows.n), rows.nnz)
    norms = np.sqrt(np.bincount(row, weights=rows.values.astype(np.float64)
                                ** 2, minlength=rows.n))
    np.testing.assert_allclose(norms, 1.0, rtol=1e-6)
    # features increase within a row, so none repeats
    key = row.astype(np.int64) * rows.d + rows.indices
    assert (np.diff(key) > 0).all()
    assert rows.indices.min() >= 0 and rows.indices.max() < rows.d
    assert set(np.unique(rows.y)) == {-1.0, 1.0}
    again = _small(seed)
    for a, b in [(rows.offsets, again.offsets), (rows.indices, again.indices),
                 (rows.values, again.values), (rows.y, again.y)]:
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(_small(seed + 1).indices[:50], rows.indices[:50])


def test_zipf_popularity_falls_with_rank():
    rows = _small(5)
    counts = np.bincount(rows.indices, minlength=rows.d)
    assert counts[:10].sum() > 10 * counts[1000:1010].sum() > 0


@pytest.mark.parametrize("mode", ["f64", "f32", "bf16"])
def test_csr_reference_equals_the_dense_reference(mode):
    gen = _config()["generator"]
    rows = data_wide.csr_rows(11, 64, 3_000, 400, 4.0, gen["nnz_sigma"],
                              gen["noise"])
    X = np.zeros((rows.n, rows.d))
    X[np.repeat(np.arange(rows.n), rows.nnz), rows.indices] = rows.values
    kw = dict(step=8.0, batch=8, epochs=3, mode=mode)
    got_w, got_l = reference_csr.sgd(rows.offsets, rows.indices, rows.values,
                                     rows.y, rows.d, **kw)
    with reference.float64():
        want_w, want_l = reference.sgd(X, rows.y, **kw)
    tol = 1e-12 if mode == "f64" else 1e-5
    for a, b in zip(got_w, want_w):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)
    np.testing.assert_allclose(got_l, want_l, rtol=tol)


def _tiny():
    cell = harness.load_cell("news.train")
    cell.config["n"] = TINY_ROWS
    return cell


def test_program_is_correct_at_a_tiny_size():
    r = drive(_tiny())
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("what", ["unchanged", "half_batch", "control"])
def test_faults_and_control_are_not_correct(what):
    cell = _tiny()
    drv = (train_wide.control() if what == "control"
           else train_wide.driver(what))
    r = drive(cell, driver=drv)
    assert r["correct"] is False
    failing = [k for k, c in r["checks"].items() if not c["value"] <= c["limit"]]
    assert failing, r["checks"]
