"""Plain reference of training on CSR rows: logistic regression by
mini-batch SGD, over the rows' nonzeros, never densified.

Written from the definitions, independent of the program under test (it
imports nothing of it), in numpy:

    loss(w)  = sum_i log(1 + exp(-y_i x_i . w))
    step     w <- w - (alpha / B) * sum_{i in batch} x_i * (-y_i sigma(-y_i x_i . w))

The ``B`` rows of a micro-batch are consecutive, so their nonzeros are
one run of slots: a step reads that run, sums each row's products, and
adds each product of the update into ``w`` one by one (a feature that
two rows share takes both).  Products are taken in one of three
precisions (as in :mod:`chipbench.reference`):

* ``"f64"``  float64, the reference proper;
* ``"f32"``  float32;
* ``"bf16"`` float32 whose products are of bfloat16-rounded factors (to
  nearest, ties to even, on the bits), a TPU matmul's default precision:
  the control, one step below the configuration's float32.
"""
from __future__ import annotations

import numpy as np

MODES = ("f64", "f32", "bf16")


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 ``x`` rounded to bfloat16, kept in float32."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def _mul(a, b, mode):
    if mode == "bf16":
        return _bf16(a) * _bf16(b)
    return a * b


def _dtype(mode: str):
    if mode not in MODES:
        raise ValueError(f"mode {mode!r}, not one of {MODES}")
    return np.float64 if mode == "f64" else np.float32


def _row_sums(products, counts, dt):
    """Sum of each row's run of ``products``; a row with none sums to 0."""
    out = np.zeros(len(counts), dt)
    held = counts > 0
    if held.any():
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        out[held] = np.add.reduceat(products, starts[held])
    return out


def _sigmoid(m):
    return 1.0 / (1.0 + np.exp(-m))


class Rows:
    """CSR rows in one precision."""

    def __init__(self, offsets, indices, values, y, d, mode):
        self.dt = _dtype(mode)
        self.mode = mode
        self.offsets = np.asarray(offsets, np.int64)
        self.indices = np.asarray(indices, np.int64)
        self.values = np.asarray(values, self.dt)
        self.y = np.asarray(y, self.dt)
        self.d = d
        self.counts = np.diff(self.offsets)

    @property
    def n(self) -> int:
        return len(self.y)

    def margins(self, w, lo=0, hi=None) -> np.ndarray:
        """``x_i . w`` of rows ``[lo, hi)``."""
        hi = self.n if hi is None else hi
        a, b = self.offsets[lo], self.offsets[hi]
        prod = _mul(self.values[a:b], w[self.indices[a:b]], self.mode)
        return _row_sums(prod, self.counts[lo:hi], self.dt)

    def loss(self, w) -> float:
        m = self.y * self.margins(w)
        return float(np.sum(np.maximum(-m, 0.0) + np.log1p(np.exp(-np.abs(m)))))

    def epoch(self, w, step: float, batch: int) -> np.ndarray:
        """One pass of mini-batch SGD over the rows in order; a new ``w``."""
        w = np.array(w, self.dt)
        scale = self.dt(step / batch)
        for lo in range(0, self.n - self.n % batch, batch):
            hi = lo + batch
            yb = self.y[lo:hi]
            pull = -yb * _sigmoid(-yb * self.margins(w, lo, hi))
            a, b = self.offsets[lo], self.offsets[hi]
            contrib = _mul(self.values[a:b],
                           np.repeat(pull, self.counts[lo:hi]), self.mode)
            np.add.at(w, self.indices[a:b], -scale * contrib)
        return w


def sgd(offsets, indices, values, y, d: int, *, step: float, batch: int,
        epochs: int, mode: str) -> tuple[list[np.ndarray], list[float]]:
    """``epochs`` epochs of mini-batch SGD from ``w = 0`` over all rows in
    order.  Returns the model and the loss after each epoch, in float64."""
    rows = Rows(offsets, indices, values, y, d, mode)
    w = np.zeros(d, rows.dt)
    ws, losses = [], []
    for _ in range(epochs):
        w = rows.epoch(w, step, batch)
        ws.append(w.astype(np.float64))
        losses.append(rows.loss(w))
    return ws, losses
