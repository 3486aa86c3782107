"""Useful work of the GLM path: operations and bytes, from the data's shape.

A corrected copy of ``repro.roofline.kernels.kernel_cost`` for the
families the benchmark drives.  Sparse work is priced at the nonzeros the
rows hold, never at the padded ELL width, so a layout with less padding
cannot read over its roofline.  A nonzero costs 4 bytes of value and 4 of
index; a row costs its 4-byte label; the model is read and written once.

Logistic regression, per nonzero ``x_ij``:

* the SGD epoch does 4 operations: one multiply-add for the margin
  ``x_i . w`` and one for the update ``w_j -= c * x_ij``;
* the loss does 2: the multiply-add of the margin.

The per-row link (sigmoid, log1p) is left out: a handful of operations a
row, against tens of nonzeros.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
I32 = 4


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip; an unknown chip is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; known: {sorted(table['devices'])}") \
            from None


def data_bytes(n: int, d: int, nnz: int | None) -> float:
    """One read of the data: values (and indices, when sparse) and labels."""
    if nnz is None:                      # dense: every entry is a value
        return float(F32 * n * d + F32 * n)
    return float((F32 + I32) * nnz + F32 * n)


def _nonzeros(n: int, d: int, nnz: int | None) -> int:
    return n * d if nnz is None else nnz


def sgd_epoch(n: int, d: int, nnz: int | None = None) -> dict:
    """One mini-batch SGD epoch: every row read once, the model kept."""
    return {"flops": 4.0 * _nonzeros(n, d, nnz),
            "bytes": data_bytes(n, d, nnz) + 2.0 * F32 * d}


def loss(n: int, d: int, nnz: int | None = None) -> dict:
    """The loss over all rows."""
    return {"flops": 2.0 * _nonzeros(n, d, nnz),
            "bytes": data_bytes(n, d, nnz) + F32 * d}


def epoch_with_loss(n: int, d: int, nnz: int | None = None) -> dict:
    """An epoch and its loss check, as a fused implementation would do
    them: the data read once, all the operations of both."""
    return {"flops": sgd_epoch(n, d, nnz)["flops"] + loss(n, d, nnz)["flops"],
            "bytes": data_bytes(n, d, nnz) + 2.0 * F32 * d}


def least_seconds(work: dict, peak: dict) -> float:
    """The roofline: the larger of bytes over bandwidth and operations over
    the FLOP rate."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["flops"] / peak["flops_per_s"])
