"""Public wrapper for the fused GLM gradient kernel — registry-dispatched.

Three registered flavors (paper: "every primitive in two flavors"):
``pallas-tpu`` / ``pallas-interpret`` run kernel.py; ``reference`` runs
the ref.py oracle.  All flavors cast inputs to fp32 (the kernels
accumulate in fp32), so bf16 inputs agree across backends to fp32
round-off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.glm_grad import kernel as K
from repro.kernels.glm_grad import ref as R


@functools.partial(
    jax.jit, static_argnames=("task", "layout", "block_rows", "interpret")
)
def _pallas(task, w, X, y, *, layout, block_rows, interpret):
    """Pad to TPU tiles and run the Pallas kernel.  Returns [d] fp32.

    Pads d to the 128-lane tile and N to the row-block size (zero example
    rows contribute zero gradient, so padding is exact).  ``layout='col'``
    materializes the transpose up front — the paper's col-major access path.
    """
    n, d = X.shape
    d_pad = common.padded(d, common.LANE)
    if block_rows is None:
        block_rows = max(common.SUBLANE, min(512, common.padded(n, common.SUBLANE)))
    n_pad = common.padded(n, block_rows)

    Xp = common.pad_to(common.pad_to(X.astype(jnp.float32), 1, d_pad), 0, n_pad)
    yp = common.pad_to(y.astype(jnp.float32).reshape(n, 1), 0, n_pad, value=1.0)
    wp = common.pad_to(w.astype(jnp.float32).reshape(d, 1), 0, d_pad)

    if layout == "col":
        Xp = Xp.T  # materialized transpose (paper: col-major path)

    g = K.glm_grad_pallas(
        task, wp, Xp, yp, layout=layout, block_rows=block_rows, interpret=interpret
    )
    return g[:d, 0]


@common.register_kernel("glm_grad", common.PALLAS_TPU)
def _glm_grad_tpu(task, w, X, y, *, layout="row", block_rows=None):
    return _pallas(task, w, X, y, layout=layout, block_rows=block_rows,
                   interpret=False)


@common.register_kernel("glm_grad", common.PALLAS_INTERPRET)
def _glm_grad_interpret(task, w, X, y, *, layout="row", block_rows=None):
    return _pallas(task, w, X, y, layout=layout, block_rows=block_rows,
                   interpret=True)


@common.register_kernel("glm_grad", common.REFERENCE, caps=common.Caps(dtypes=None))
@functools.partial(jax.jit, static_argnames=("task", "layout", "block_rows"))
def _glm_grad_reference(task, w, X, y, *, layout="row", block_rows=None):
    del layout, block_rows  # access path is a kernel-layout concept
    return R.glm_grad_ref(task, w.astype(jnp.float32), X.astype(jnp.float32),
                          y.astype(jnp.float32))


def glm_grad(
    task: str,
    w: jax.Array,   # [d]
    X: jax.Array,   # [N, d]
    y: jax.Array,   # [N]
    *,
    layout: str = "row",
    block_rows: int | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Sum GLM gradient via the best available backend.  Returns [d] fp32.

    ``block_rows=None`` takes the kernel's built-in rule: N padded to
    sublanes, at most 512 rows a block.
    """
    info = {"dtype": jnp.result_type(X).name, "n": X.shape[0], "d": X.shape[1]}
    return common.dispatch(
        "glm_grad", task, w, X, y, layout=layout, block_rows=block_rows,
        backend=backend, info=info,
    )
