"""Data of a configuration too wide for an ``[n, d]`` draw, made from the
seed in bulk: CSR rows, never a dense or padded array.

``data.sparse_rows`` draws an ``[n, d]`` Gumbel matrix, 108 GB at
news20's 19,992 x 1,355,191; this generator does work that follows the
nonzeros.

* A row's nonzero count is log-normal, floored and clipped to
  ``[1, max_nnz]`` (the configuration's ``nnz_mu`` and ``nnz_sigma``).
* Its features are drawn without replacement with Zipf popularity (rank
  ``r`` has weight ``1 / r``): i.i.d. inverse-CDF draws, each row
  drawing again as many as it lacks until it holds its count of distinct
  features.  The first ``k`` distinct features of an i.i.d. sequence are
  a draw without replacement, and drawing again only what is missing
  never overshoots.  A row's features are kept in increasing order.
* Values are standard normal, each row scaled to unit L2 norm.
* Labels come from a planted hyperplane, ``N(0, 1/r)`` at rank ``r``,
  with a share ``noise`` of them flipped.

Everything is a pure function of the seed, which may be any
non-negative integer.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Rows:
    """CSR rows of one data set, on the host."""

    y: np.ndarray          # [n] float32 in {-1, +1}
    d: int
    offsets: np.ndarray    # [n+1] int64
    indices: np.ndarray    # [nnz] int32, increasing within a row
    values: np.ndarray     # [nnz] float32
    dense = False

    @property
    def n(self) -> int:
        return len(self.y)

    @property
    def nnz(self) -> np.ndarray:
        """Nonzeros of each row."""
        return np.diff(self.offsets)


def widths(rng, n: int, max_nnz: int, mu: float, sigma: float) -> np.ndarray:
    """Nonzeros of each of ``n`` rows."""
    return np.clip(rng.lognormal(mu, sigma, n), 1, max_nnz).astype(np.int64)


def zipf_rows(rng, counts: np.ndarray, d: int) -> tuple[np.ndarray, np.ndarray]:
    """``(offsets, indices)`` of rows of ``counts`` distinct Zipf features."""
    n = len(counts)
    cdf = np.cumsum(1.0 / np.arange(1, d + 1, dtype=np.float64))
    cdf /= cdf[-1]

    def draw(rows):
        feat = np.minimum(np.searchsorted(cdf, rng.random(len(rows)),
                                          side="right"), d - 1)
        return rows * d + feat

    keys = np.unique(draw(np.repeat(np.arange(n, dtype=np.int64), counts)))
    have = np.bincount(keys // d, minlength=n)
    while True:
        need = counts - have
        if not need.any():
            break
        new = np.unique(draw(np.repeat(np.arange(n, dtype=np.int64), need)))
        pos = np.searchsorted(keys, new)
        old = (pos < len(keys)) & (keys[np.minimum(pos, len(keys) - 1)] == new)
        new, pos = new[~old], pos[~old]
        keys = np.insert(keys, pos, new)
        have += np.bincount(new // d, minlength=n)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    return offsets, (keys % d).astype(np.int32)


def csr_rows(seed: int, n: int, d: int, max_nnz: int, nnz_mu: float,
             nnz_sigma: float, noise: float) -> Rows:
    rng = np.random.default_rng(seed)
    counts = widths(rng, n, max_nnz, nnz_mu, nnz_sigma)
    offsets, indices = zipf_rows(rng, counts, d)
    values = rng.standard_normal(len(indices), dtype=np.float32)
    row = np.repeat(np.arange(n), counts)
    norms = np.sqrt(np.bincount(row, weights=np.square(values, dtype=np.float64),
                                minlength=n))
    values = (values / norms[row]).astype(np.float32)
    ranks = np.arange(1, d + 1, dtype=np.float64)
    w_star = rng.standard_normal(d) / np.sqrt(ranks)
    margins = np.bincount(row, weights=values * w_star[indices], minlength=n)
    y = np.where(margins >= 0, 1.0, -1.0)
    y[rng.random(n) < noise] *= -1.0
    return Rows(y.astype(np.float32), d, offsets, indices, values)


def make(config: dict, seed: int) -> Rows:
    """All rows of a configuration, as its file states them."""
    gen = config["generator"]
    return csr_rows(seed, config["n"], config["d"], config["max_nnz"],
                    gen["nnz_mu"], gen["nnz_sigma"], gen["noise"])
