"""Data of the benchmark's configurations, made from the seed in bulk.

A vectorised copy of the repository's Table-3 generators
(``repro.data.synthetic``), with two changes: no per-row Python loop, and
sparse rows keep the source's widest row as their clip (w8a: 114).

* Dense rows are standard normal; labels come from a planted hyperplane
  with a share ``noise`` of them flipped.
* Sparse rows draw their nonzero count from a log-normal, floored and
  clipped to ``[1, max_nnz]``, with ``mu`` set so that the expected count
  is the source's mean.  Their features are drawn without replacement
  with Zipf popularity (rank ``r`` has weight ``1 / r``), by the Gumbel
  top-k trick, and their values are standard normal.

Everything is a pure function of the seed, which may be any
non-negative integer (numpy's ``default_rng`` takes big ones).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Rows:
    """Rows of one data set: padded ELL for sparse data, dense otherwise."""

    y: np.ndarray                    # [n] float32 in {-1, +1}
    d: int
    X: np.ndarray | None = None      # [n, d] float32 (dense data)
    values: np.ndarray | None = None   # [n, K] float32, zero padded
    indices: np.ndarray | None = None  # [n, K] int32, padding at 0
    nnz: np.ndarray | None = None      # [n] nonzeros of each row

    @property
    def n(self) -> int:
        return len(self.y)

    @property
    def dense(self) -> bool:
        return self.X is not None

    def cut(self, n: int) -> "Rows":
        """The first ``n`` rows."""
        def head(a):
            return None if a is None else a[:n]
        return Rows(self.y[:n], self.d, head(self.X), head(self.values),
                    head(self.indices), head(self.nnz))

    def to_dense(self, rows: np.ndarray | None = None,
                 dtype=np.float64) -> np.ndarray:
        """``[len(rows), d]`` dense copy of the chosen rows (all by default)."""
        if self.dense:
            X = self.X if rows is None else self.X[rows]
            return X.astype(dtype)
        vals = self.values if rows is None else self.values[rows]
        idx = self.indices if rows is None else self.indices[rows]
        nnz = self.nnz if rows is None else self.nnz[rows]
        out = np.zeros((len(vals), self.d), dtype)
        keep = np.arange(vals.shape[1])[None, :] < nnz[:, None]
        r = np.nonzero(keep)[0]
        out[r, idx[keep]] = vals[keep]
        return out


def _labels(rng, margins: np.ndarray, noise: float) -> np.ndarray:
    y = np.where(margins >= 0, 1.0, -1.0)
    y[rng.random(len(y)) < noise] *= -1.0
    return y.astype(np.float32)


def dense_rows(seed: int, n: int, d: int, noise: float) -> Rows:
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d), dtype=np.float32)
    w_star = rng.standard_normal(d, dtype=np.float32)
    return Rows(_labels(rng, X @ w_star, noise), d, X=X)


def sparse_rows(seed: int, n: int, d: int, max_nnz: int, nnz_mu: float,
                nnz_sigma: float, noise: float) -> Rows:
    rng = np.random.default_rng(seed)
    nnz = np.clip(rng.lognormal(nnz_mu, nnz_sigma, n), 1, max_nnz) \
        .astype(np.int32)
    ranks = np.arange(1, d + 1, dtype=np.float64)
    w_star = (rng.standard_normal(d) / np.sqrt(ranks)).astype(np.float32)
    # Gumbel top-k: the k largest of log p + Gumbel noise are a draw of k
    # features without replacement with probabilities p
    u = rng.random((n, d), dtype=np.float32)
    keys = (-np.log(ranks)).astype(np.float32) \
        - np.log(-np.log(np.maximum(u, np.float32(1e-30))))
    part = np.argpartition(-keys, max_nnz - 1, axis=1)[:, :max_nnz]
    order = np.argsort(-np.take_along_axis(keys, part, 1), axis=1)
    top = np.take_along_axis(part, order, 1).astype(np.int32)
    keep = np.arange(max_nnz)[None, :] < nnz[:, None]
    vals = rng.standard_normal((n, max_nnz), dtype=np.float32)
    values = np.where(keep, vals, np.float32(0)).astype(np.float32)
    indices = np.where(keep, top, 0).astype(np.int32)
    margins = np.sum(values * w_star[indices], axis=1)
    return Rows(_labels(rng, margins, noise), d, values=values,
                indices=indices, nnz=nnz)


def make(config: dict, seed: int) -> Rows:
    """All rows of a configuration, as its file states them."""
    gen = config["generator"]
    if gen["kind"] == "dense":
        return dense_rows(seed, config["n"], config["d"], gen["noise"])
    return sparse_rows(seed, config["n"], config["d"], config["max_nnz"],
                       gen["nnz_mu"], gen["nnz_sigma"], gen["noise"])


def model(seed: int, d: int) -> np.ndarray:
    """A served model drawn from the seed: ``[d]`` float32, N(0, 1/r)."""
    rng = np.random.default_rng([seed, 1])
    ranks = np.arange(1, d + 1, dtype=np.float64)
    return (rng.standard_normal(d) / np.sqrt(ranks)).astype(np.float32)
