"""Sparse training data in ELL (padded) format — the TPU-native CSR analogue.

The paper converts CSR to a zero-padded dense-width format for the col-major
GPU access path (Section 5.2.1: "we map sparse data into a dense padded
format that stores all the examples at the same width — equal to the maximum
number of non-zero features").  On TPU the same trade is forced globally:
variable-length rows are hostile to fixed-shape tiles, so we adopt ELL:

    values  : [N, K]  float   (zero padded)
    indices : [N, K]  int32   (index 0 padded; padded values are 0 so the
                               contribution vanishes)

with K = max nnz/row (optionally a high percentile with overflow rows split).
The GLM margin is a gather-dot; the gradient is a scatter-add, both expressed
with jnp.take / segment_sum so they lower to XLA gather/scatter on TPU.

Rows too wide for that padding (news20: 455 nonzeros a row on average,
16,423 at most) go to the device as ``CSRRows`` instead: row offsets and
flat indices and values, padded only to the CSR kernels' DMA chunk.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics, trace

Array = jax.Array


class ELLMatrix(NamedTuple):
    """Padded sparse matrix (ELLPACK layout)."""

    values: Array   # [N, K] float
    indices: Array  # [N, K] int32
    d: int          # number of features (model dimension)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.values.shape[0], self.d)

    @property
    def max_nnz(self) -> int:
        return self.values.shape[1]


class CSRMatrix(NamedTuple):
    """Host-side CSR triple — the ingestion-facing sparse layout.

    This is the representation parsers produce (variable-length rows,
    no padding); ``to_ell`` converts to the TPU-native padded layout.
    All arrays are numpy: CSR never reaches a kernel directly.
    """

    indptr: np.ndarray   # [N+1] int64 row offsets
    indices: np.ndarray  # [nnz] int32 column ids
    values: np.ndarray   # [nnz] float32
    d: int               # number of features (model dimension)

    @property
    def shape(self) -> tuple[int, int]:
        return (len(self.indptr) - 1, self.d)

    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr)

    @property
    def avg_nnz(self) -> float:
        return float(self.nnz / max(self.n, 1))

    def row(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        lo, hi = int(self.indptr[i]), int(self.indptr[i + 1])
        return self.indices[lo:hi], self.values[lo:hi]

    def select(self, rows: np.ndarray) -> "CSRMatrix":
        """Row subset (host-side, vectorized — used by train/test splits)."""
        rows = np.asarray(rows, dtype=np.int64)
        counts = self.row_nnz[rows]
        indptr = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        # absolute source index = row start + offset within the row
        within = np.arange(int(indptr[-1]), dtype=np.int64) \
            - np.repeat(indptr[:-1], counts)
        take = np.repeat(self.indptr[rows], counts) + within
        return CSRMatrix(indptr, self.indices[take], self.values[take], self.d)

    def to_ell(self, pad_to: int | None = None) -> ELLMatrix:
        """Zero-padded ELL conversion, vectorized (the paper's padded-width
        format, §5.2.1: every row stored at the same width).  ``pad_to``
        defaults to the maximum row width so no entry is dropped; an
        explicit narrower ``pad_to`` truncates overflow rows."""
        N = self.n
        K = int(self.row_nnz.max()) if (pad_to is None and N) else (pad_to or 1)
        K = max(K, 1)
        values = np.zeros((N, K), dtype=np.float32)
        indices = np.zeros((N, K), dtype=np.int32)
        if self.nnz:
            row_of = np.repeat(np.arange(N, dtype=np.int64), self.row_nnz)
            pos = np.arange(self.nnz, dtype=np.int64) \
                - np.repeat(self.indptr[:-1], self.row_nnz)
            keep = pos < K
            values[row_of[keep], pos[keep]] = self.values[keep]
            indices[row_of[keep], pos[keep]] = self.indices[keep]
        return ELLMatrix(jnp.asarray(values), jnp.asarray(indices), self.d)

    def to_rows(self) -> "CSRRows":
        """The rows on the device as the CSR kernels take them (in order,
        not padded to the widest row): the slots padded only to a whole
        number of the kernels' DMA chunks, padded slots index 0 and value
        0.  Counts the nonzeros held (``sparse.csr.nnz``) and the slots
        (``sparse.csr.slots``)."""
        from repro.kernels.glm_sgd_csr.kernel import CHUNK

        nnz = self.nnz
        if nnz >= 2 ** 31:
            raise ValueError(f"{nnz} nonzeros overflow int32 row offsets")
        with trace.span("sparse.csr.pack", n=self.n, nnz=nnz):
            slots = max(CHUNK, nnz + (-nnz) % CHUNK)
            indices = np.zeros(slots, np.int32)
            values = np.zeros(slots, np.float32)
            indices[:nnz] = self.indices
            values[:nnz] = self.values
            rows = CSRRows(jnp.asarray(self.indptr.astype(np.int32)),
                           jnp.asarray(indices), jnp.asarray(values), self.d,
                           int(self.row_nnz.max(initial=0)))
        metrics.counter("sparse.csr.nnz").inc(nnz)
        metrics.counter("sparse.csr.slots").inc(slots)
        return rows

    def to_dense(self) -> np.ndarray:
        """Densify host-side (dense datasets and tests — O(N*d))."""
        out = np.zeros((self.n, self.d), dtype=np.float32)
        rows = np.repeat(np.arange(self.n), self.row_nnz)
        np.add.at(out, (rows, self.indices), self.values)
        return out


class CSRRows(NamedTuple):
    """CSR rows on the device: the layout of the CSR kernels
    (``glm_sgd_csr``, ``glm_csr_margins``).

    Row ``i`` is the slots ``offsets[i]:offsets[i+1]``.  Slots past
    ``offsets[-1]`` are padding (index 0, value 0).  Built by
    :meth:`CSRMatrix.to_rows`.
    """

    offsets: Array   # [N+1] int32 row extents
    indices: Array   # [S] int32 feature ids, S >= nnz
    values: Array    # [S] float32
    d: int           # number of features (model dimension)
    width: int       # nonzeros of the widest row

    @property
    def shape(self) -> tuple[int, int]:
        return (self.offsets.shape[0] - 1, self.d)

    def to_ell(self) -> ELLMatrix:
        """The same rows padded to the widest (a gather on the device)."""
        start = self.offsets[:-1, None]
        pos = start + jnp.arange(max(self.width, 1), dtype=jnp.int32)[None, :]
        keep = pos < self.offsets[1:, None]
        pos = jnp.where(keep, pos, 0)
        return ELLMatrix(jnp.where(keep, self.values[pos], 0.0),
                         jnp.where(keep, self.indices[pos], 0), self.d)


def from_csr_parts(
    rows_idx: list[np.ndarray], rows_val: list[np.ndarray], d: int
) -> CSRMatrix:
    """Assemble a ``CSRMatrix`` from per-row (indices, values) pairs."""
    indptr = np.zeros(len(rows_idx) + 1, dtype=np.int64)
    np.cumsum([len(r) for r in rows_idx], out=indptr[1:])
    indices = (np.concatenate(rows_idx).astype(np.int32)
               if rows_idx else np.zeros(0, dtype=np.int32))
    values = (np.concatenate(rows_val).astype(np.float32)
              if rows_val else np.zeros(0, dtype=np.float32))
    return CSRMatrix(indptr, indices, values, d)


def from_dense(X: np.ndarray, pad_to: int | None = None) -> ELLMatrix:
    """Build an ELLMatrix from a dense [N, d] array (host-side, numpy)."""
    N, d = X.shape
    nnz_per_row = (X != 0).sum(axis=1)
    K = int(nnz_per_row.max()) if pad_to is None else pad_to
    K = max(K, 1)
    values = np.zeros((N, K), dtype=X.dtype)
    indices = np.zeros((N, K), dtype=np.int32)
    for i in range(N):
        (nz,) = np.nonzero(X[i])
        nz = nz[:K]
        values[i, : len(nz)] = X[i, nz]
        indices[i, : len(nz)] = nz
    return ELLMatrix(jnp.asarray(values), jnp.asarray(indices), d)


def from_rows(
    rows_idx: list[np.ndarray], rows_val: list[np.ndarray], d: int,
    pad_to: int | None = None,
) -> ELLMatrix:
    """Build from per-row (indices, values) pairs — CSR-style input."""
    N = len(rows_idx)
    K = pad_to if pad_to is not None else max((len(r) for r in rows_idx), default=1)
    K = max(K, 1)
    values = np.zeros((N, K), dtype=np.float32)
    indices = np.zeros((N, K), dtype=np.int32)
    for i, (idx, val) in enumerate(zip(rows_idx, rows_val)):
        k = min(len(idx), K)
        values[i, :k] = val[:k]
        indices[i, :k] = idx[:k]
    return ELLMatrix(jnp.asarray(values), jnp.asarray(indices), d)


def to_dense(m: ELLMatrix) -> Array:
    """Densify (testing only — O(N*d))."""
    N, K = m.values.shape
    out = jnp.zeros((N, m.d), dtype=m.values.dtype)
    rows = jnp.repeat(jnp.arange(N), K)
    return out.at[rows, m.indices.reshape(-1)].add(m.values.reshape(-1))


# ---------------------------------------------------------------------------
# Sparse GLM margin / gradient
# ---------------------------------------------------------------------------


def margins(m: ELLMatrix, w: Array) -> Array:
    """x_i . w for every row — gather model features then row-sum.

    The gather is the TPU analogue of the paper's coalesced model access: the
    [N, K] index block is a single gather op, contiguous in the example axis.
    """
    wg = jnp.take(w, m.indices, axis=0)          # [N, K]
    return jnp.sum(m.values * wg, axis=1)        # [N]


def grad(task: str, m: ELLMatrix, y: Array, w: Array) -> Array:
    """Sum GLM gradient: scatter-add of pull_i * values_i into w-space."""
    from repro.core import glm

    mar = y * margins(m, w)
    pull = glm.PULLS[task](mar, y)               # [N]
    contrib = m.values * pull[:, None]           # [N, K]
    flat_idx = m.indices.reshape(-1)
    flat_val = contrib.reshape(-1)
    return jax.ops.segment_sum(flat_val, flat_idx, num_segments=m.d)


def loss(task: str, m: ELLMatrix, y: Array, w: Array) -> Array:
    return margin_loss(task, y * margins(m, w))


def margin_loss(task: str, mar: Array) -> Array:
    """Sum loss of the signed margins ``y_i * (x_i . w)``."""
    if task == "lr":
        return jnp.sum(jnp.maximum(-mar, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(mar))))
    return jnp.sum(jnp.maximum(0.0, 1.0 - mar))


def incremental_epoch(task: str, w: Array, m: ELLMatrix, y: Array, step: float) -> Array:
    """Per-example sparse SGD epoch (sequential oracle), scanned.

    Each step touches only the K nonzero features of the example — the
    sparse-update property that makes Hogwild converge (Niu et al. 2011).
    """
    from repro.core import glm

    pull_fn = glm.PULLS[task]

    def body(w, xy):
        vals, idx, y_i = xy
        wg = jnp.take(w, idx, axis=0)
        margin = y_i * jnp.dot(vals, wg)
        pull = pull_fn(margin, y_i)
        return w.at[idx].add(-step * pull * vals), None

    w_out, _ = jax.lax.scan(body, w, (m.values, m.indices, y))
    return w_out


def minibatch_epoch(
    task: str, w: Array, m: ELLMatrix, y: Array, step: float, batch: int
) -> Array:
    """Mini-batch sparse SGD epoch (per-replica rule of the async engine)."""
    n = m.values.shape[0]
    assert n % batch == 0, (n, batch)
    K = m.values.shape[1]
    vb = m.values.reshape(n // batch, batch, K)
    ib = m.indices.reshape(n // batch, batch, K)
    yb = y.reshape(n // batch, batch)

    def body(w, xiy):
        vals, idx, yk = xiy
        g = grad(task, ELLMatrix(vals, idx, m.d), yk, w)
        return w - (step / batch) * g, None

    w_out, _ = jax.lax.scan(body, w, (vb, ib, yb))
    return w_out
