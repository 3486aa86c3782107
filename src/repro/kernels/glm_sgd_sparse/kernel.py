"""Fused sparse (ELL) incremental-SGD epoch Pallas kernel.

The dense ``glm_sgd`` kernel fuses gradient + update into one launch with
the model pinned in VMEM scratch (Section 5's Hogwild-kernel analogue).
This is its sparse sibling: the per-step example tile is a padded-ELL
``(values, indices)`` pair, and — like ``glm_sparse`` — the gather and
scatter against the VMEM-resident model become dense one-hot products
(:func:`repro.kernels.common.ell_onehot`):

    grid step k:  load ELL tile vals_k/idx_k [MB, K] (HBM->VMEM stream)
                  onehot  = (idx_k == iota_d)                  [MB, d, K]
                  margins = y_k * rowsum(vals_k * sum_d(onehot * w))
                  w_vmem -= (alpha/MB) * sum_{MB,K}(onehot * vals*pull)

One launch = one epoch = N/MB model updates with zero HBM traffic for
the model.  The one-hot spans the *full* padded feature axis (no
d-blocking): the model must stay live across steps, so ops.py budgets
the one-hot against VMEM and routes over-budget problems to the
reference oracle.  Padded ELL entries (value 0) contribute 0 to both the
margin and the scatter, so no masking is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _pull(task, margins, y):
    if task == "lr":
        return -y * jax.nn.sigmoid(-margins)
    return -y * (margins < 1.0).astype(margins.dtype)


def _kernel(task, scale, vals_ref, idx_ref, y_ref, w0_ref, out_ref, w_s):
    @pl.when(pl.program_id(0) == 0)
    def _():
        w_s[...] = w0_ref[...]

    vals = vals_ref[...]              # [MB, K]
    idx = idx_ref[...]                # [MB, K] int32 (global feature ids)
    y = y_ref[...]                    # [MB, 1]
    onehot = common.ell_onehot(idx, w_s.shape[0])         # [MB, d_pad, K]

    w = w_s[...]                      # [d_pad, 1]
    wg = common.onehot_gather(onehot, w)                  # [MB, K] = w[idx]
    margins = y * jnp.sum(vals * wg, axis=1, keepdims=True)
    pull = _pull(task, margins, y)    # [MB, 1]
    g = common.onehot_scatter(onehot, vals * pull)        # [d_pad, 1]
    w_s[...] = w - scale * g

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        out_ref[...] = w_s[...]


def ell_sgd_pallas(
    task: str,
    w0: jax.Array,       # [d_pad, 1]
    values: jax.Array,   # [N, K]
    indices: jax.Array,  # [N, K] int32
    y: jax.Array,        # [N, 1]
    *,
    step: float,
    micro_batch: int,
    interpret: bool,
) -> jax.Array:
    n, kk = values.shape
    d_pad = w0.shape[0]
    assert n % micro_batch == 0, (n, micro_batch)
    grid = (n // micro_batch,)
    body = functools.partial(_kernel, task, step / micro_batch)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((micro_batch, kk), lambda i: (i, 0)),
            pl.BlockSpec((micro_batch, kk), lambda i: (i, 0)),
            pl.BlockSpec((micro_batch, 1), lambda i: (i, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d_pad, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: state carried
        ),
        interpret=interpret,
    )(values, indices, y, w0)
