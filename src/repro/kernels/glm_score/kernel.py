"""Fused sparse (ELL) GLM scoring Pallas kernel — inference sibling of
``glm_sgd_sparse``.

One launch scores a whole padded micro-batch: the model is pinned in
VMEM across the row-tile grid (BlockSpec index map is constant, so the
pipeline never re-streams it), and — like every sparse family here —
the per-row gather ``w[idx]`` is a dense one-hot product over the full
padded feature axis (:func:`repro.kernels.common.ell_onehot`):

    grid step i:  load ELL tile vals_i/idx_i [TB, K]  (HBM->VMEM stream)
                  onehot  = (idx_i == iota_d)                  [TB, d, K]
                  margins = rowsum(vals_i * sum_d(onehot * w)) (VPU)
                  out_i   = link(margins)                      (VPU)

The link (LR sigmoid / SVM identity) is fused into the launch, so a
scoring batch is exactly one kernel — the serving-path analogue of the
paper's coalesced sparse model access (§5.2.1).  Padded ELL entries
(value 0 at index 0) contribute 0 to the margin, and padded *rows*
(admission-queue filler) are entirely zero, so their margin is exactly
0 and the engine just drops their scores; no masking is needed.  The
one-hot spans the full padded feature axis, so ops.py budgets its VMEM
(:func:`repro.kernels.common.onehot_fits`) and routes over-budget
problems to the reference oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _link(task, margins):
    if task == "lr":
        return jax.nn.sigmoid(margins)
    return margins


def _kernel(task, vals_ref, idx_ref, w_ref, out_ref):
    vals = vals_ref[...]              # [TB, K]
    idx = idx_ref[...]                # [TB, K] int32 (global feature ids)
    onehot = common.ell_onehot(idx, w_ref.shape[0])       # [TB, d_pad, K]
    wg = common.onehot_gather(onehot, w_ref[...])         # [TB, K] = w[idx]
    margins = jnp.sum(vals * wg, axis=1, keepdims=True)
    out_ref[...] = _link(task, margins)


def glm_score_pallas(
    task: str,
    w: jax.Array,        # [d_pad, 1]
    values: jax.Array,   # [N_pad, K]
    indices: jax.Array,  # [N_pad, K] int32
    *,
    block_rows: int,
    interpret: bool,
) -> jax.Array:
    n_pad, kk = values.shape
    d_pad = w.shape[0]
    assert n_pad % block_rows == 0, (n_pad, block_rows)
    grid = (n_pad // block_rows,)
    body = functools.partial(_kernel, task)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, kk), lambda i: (i, 0)),  # values
            pl.BlockSpec((block_rows, kk), lambda i: (i, 0)),  # indices
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),        # w (pinned)
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),  # rows are independent
        ),
        interpret=interpret,
        name="glm_score",
    )(values, indices, w)
