"""ELL sparse GLM gradient Pallas kernels — gather/scatter as one-hot ops.

The paper pads CSR to a fixed width so the GPU col-major path gets coalesced
access (Section 5.2.1).  The TPU analogue goes further: there is no efficient
per-lane random gather into VMEM, but gather/scatter over a *bounded feature
block* become dense products with a one-hot tile
(:func:`repro.kernels.common.ell_onehot`):

    gather :  w[idx]        ==  sum_d(onehot(idx, Db) * w_block)
    scatter:  g[idx] += c   ==  g_block += sum_{rows,K}(onehot(idx, Db) * c)

The gradient takes two launches:

    margins:  grid (row tile, d-block) — m_i = x_i . w, accumulated across
              d-blocks into the row tile's [TB, 1] output block;
    gradient: grid (d-block, row tile) — pull_i = f'(y_i m_i), and the
              scatter of vals * pull accumulates into the d-block's output
              block (row tiles are contiguous per d-block, so it flushes
              exactly once).

The margins travel through HBM between the launches, one ``[TB, 1]`` block
per row tile, so VMEM holds no buffer that grows with N.  Everything is
fixed-shape; the only data-dependent values are the indices, which never
leave the integer compare feeding the one-hot.  Padded entries (value 0)
contribute 0 to both launches, so no explicit masking is needed beyond
clamping out-of-block indices to 0 with value 0.  This trades O(N*K*d)
vector work for zero irregular memory traffic — profitable exactly when d is
moderate (w8a / real-sim scale).  For very wide models (news) the XLA
gather/segment-sum path (ref.py) is the production path; ops.py picks
automatically based on a one-hot VMEM and width budget.
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


def _block_onehot(d_block, j, vals, idx):
    """One-hot of the entries that fall in d-block ``j``, and their values."""
    local = idx - j * d_block     # [TB, K]
    in_block = (local >= 0) & (local < d_block)
    local = jnp.where(in_block, local, 0)
    sel = jnp.where(in_block, vals, 0.0)       # masked values (0 => no-op)
    return common.ell_onehot(local, d_block), sel


def _margin_kernel(d_block, vals_ref, idx_ref, w_ref, m_ref):
    j = pl.program_id(1)          # d block (fast axis: output stays resident)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.zeros_like(m_ref)

    onehot, sel = _block_onehot(d_block, j, vals_ref[...], idx_ref[...])
    wg = common.onehot_gather(onehot, w_ref[...])          # [TB, K]
    m_ref[...] += jnp.sum(sel * wg, axis=1, keepdims=True)


def _grad_kernel(task, d_block, vals_ref, idx_ref, y_ref, m_ref, g_ref):
    j = pl.program_id(0)          # d block   (output block: slow axis)
    i = pl.program_id(1)          # row tile  (contiguous revisits per block)

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    onehot, sel = _block_onehot(d_block, j, vals_ref[...], idx_ref[...])
    y = y_ref[...]                             # [TB, 1]
    pull = _pull(task, y * m_ref[...], y)      # [TB, 1]
    g_ref[...] += common.onehot_scatter(onehot, sel * pull)


def ell_glm_grad_pallas(
    task: str,
    w: jax.Array,        # [d_pad, 1]
    values: jax.Array,   # [N_pad, K]
    indices: jax.Array,  # [N_pad, K] int32
    y: jax.Array,        # [N_pad, 1]
    *,
    block_rows: int,
    d_block: int,
    interpret: bool,
) -> jax.Array:
    n_pad, kk = values.shape
    d_pad = w.shape[0]
    assert n_pad % block_rows == 0 and d_pad % d_block == 0
    rows, blocks = n_pad // block_rows, d_pad // d_block
    tile = pl.BlockSpec((block_rows, kk), lambda i, j: (i, 0))
    margins = pl.pallas_call(
        functools.partial(_margin_kernel, d_block),
        grid=(rows, blocks),
        in_specs=[
            tile,                                                # values
            tile,                                                # indices
            pl.BlockSpec((d_block, 1), lambda i, j: (j, 0)),     # w block
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(values, indices, w)

    tile = pl.BlockSpec((block_rows, kk), lambda j, i: (i, 0))
    column = pl.BlockSpec((block_rows, 1), lambda j, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_grad_kernel, task, d_block),
        grid=(blocks, rows),
        in_specs=[tile, tile, column, column],   # values, indices, y, margins
        out_specs=pl.BlockSpec((d_block, 1), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(values, indices, y, margins)
