"""Fused GLM gradient Pallas kernel — the paper's data-access-path axis on TPU.

One kernel fuses the whole gradient pipeline (margin matvec -> pull -> X^T
accumulate), replacing the paper's chain of blocking ViennaCL primitives.
The model ``w`` is resident in VMEM for the entire grid; example tiles
stream HBM->VMEM once.  Two physical layouts realize the paper's row- vs
col-major access paths:

* ``row``:  X stored ``[N, d]``; a tile ``[TB, d]`` puts the *feature* axis on
  the 128-lane minor dimension — the margin matvec contracts along lanes
  (MXU-friendly) but the X^T-pull accumulation needs a transposed operand.
* ``col``:  X stored ``[d, N]`` (transposed up front, like the paper's
  materialized transpose); a tile ``[d, TB]`` puts the *example* axis on
  lanes — the gradient accumulation ``Xc @ pull`` is lane-aligned
  ("coalesced") while the margin matvec is the transposed one.

The roofline consequences of this choice are measured in
benchmarks/fig8_access_path.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pull(task: str, margins: jax.Array, y: jax.Array) -> jax.Array:
    if task == "lr":
        return -y * jax.nn.sigmoid(-margins)
    return -y * (margins < 1.0).astype(margins.dtype)


def _kernel_row(task, x_ref, y_ref, w_ref, g_ref):
    X = x_ref[...]            # [TB, d]
    w = w_ref[...]            # [d, 1]
    y = y_ref[...]            # [TB, 1]
    margins = y * jnp.dot(X, w, preferred_element_type=jnp.float32)
    pull = _pull(task, margins, y)

    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    g_ref[...] += jax.lax.dot_general(
        X, pull, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # X^T @ pull : contract example axis


def _kernel_col(task, xc_ref, y_ref, w_ref, g_ref):
    Xc = xc_ref[...]          # [d, TB]  (example axis on lanes)
    w = w_ref[...]            # [d, 1]
    y = y_ref[...]            # [TB, 1]
    # margins = (Xc^T w): contract the feature axis (sublanes)
    margins = y * jax.lax.dot_general(
        Xc, w, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    pull = _pull(task, margins, y)

    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    g_ref[...] += jnp.dot(Xc, pull, preferred_element_type=jnp.float32)


def glm_grad_pallas(
    task: str,
    w: jax.Array,     # [d_pad, 1]
    X: jax.Array,     # [N_pad, d_pad] (row) or [d_pad, N_pad] (col)
    y: jax.Array,     # [N_pad, 1]
    *,
    layout: str,
    block_rows: int,
    interpret: bool,
) -> jax.Array:
    if layout == "row":
        n_pad, d_pad = X.shape
        x_spec = pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0))
        body = functools.partial(_kernel_row, task)
    else:
        d_pad, n_pad = X.shape
        x_spec = pl.BlockSpec((d_pad, block_rows), lambda i: (0, i))
        body = functools.partial(_kernel_col, task)
    grid = (n_pad // block_rows,)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            x_spec,
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),   # y
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),        # w (resident)
        ],
        out_specs=pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),  # g accumulator
        out_shape=jax.ShapeDtypeStruct((d_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # revisited output block
        ),
        interpret=interpret,
        name="glm_grad",
    )(X, y, w)
