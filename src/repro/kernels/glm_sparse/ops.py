"""Public wrappers for the ELL sparse GLM gradient and margins —
registry-dispatched.

The Pallas one-hot flavors carry a capability budget (one-hot work grows
with d; the ``[block_rows, d_block, K]`` one-hot must fit its VMEM share),
so very wide problems, or rows too wide for the one-hot, auto-route to the
``reference`` XLA gather/segment-sum flavor — the sparse analogue of the
paper's per-dataset optimal-configuration finding (Table 6).

``ell_margins`` (family ``glm_sparse_margins``) is the gradient's margins
launch on its own, with the whole model in one d-block padded to the
128-lane tile: the margins ``x_i . w`` a loss over ELL rows needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.glm_sparse import kernel as K
from repro.kernels.glm_sparse import ref as R

#: one-hot work grows with d
_MAX_D_PALLAS = 32_768
DEFAULT_BLOCK_ROWS = 8
DEFAULT_D_BLOCK = 512
#: row tile of the margins-only launch: on a TPU v5e at w8a's shape
#: (64,696 x 300, K = 114) 8 rows take 4.0 ms a call, 16 rows 4.9 ms;
#: 32 rows (5.9 ms) overflow the one-hot budget
MARGINS_BLOCK_ROWS = 8


def pallas_path_ok(d: int, k: int) -> bool:
    """Width budget, and the default tile's one-hot fits VMEM."""
    return (d <= _MAX_D_PALLAS
            and common.onehot_fits(DEFAULT_BLOCK_ROWS, DEFAULT_D_BLOCK, k))


_PALLAS_CAPS = common.Caps(
    sparse=True,
    check=lambda info: pallas_path_ok(info.get("d", 0), info.get("k", 1)),
)


@functools.partial(
    jax.jit, static_argnames=("task", "block_rows", "d_block", "interpret")
)
def _pallas(task, w, values, indices, y, *, block_rows, d_block, interpret):
    n, kk = values.shape
    d = w.shape[0]
    d_pad = common.padded(d, d_block)
    n_pad = common.padded(n, block_rows)
    vp = common.pad_to(values.astype(jnp.float32), 0, n_pad)
    ip = common.pad_to(indices.astype(jnp.int32), 0, n_pad)
    yp = common.pad_to(y.astype(jnp.float32).reshape(n, 1), 0, n_pad, value=1.0)
    wp = common.pad_to(w.astype(jnp.float32).reshape(d, 1), 0, d_pad)
    g = K.ell_glm_grad_pallas(
        task, wp, vp, ip, yp,
        block_rows=block_rows, d_block=d_block, interpret=interpret,
    )
    return g[:d, 0]


@common.register_kernel("glm_sparse", common.PALLAS_TPU, caps=_PALLAS_CAPS)
def _glm_sparse_tpu(task, w, values, indices, y, *,
                    block_rows=DEFAULT_BLOCK_ROWS, d_block=DEFAULT_D_BLOCK):
    return _pallas(task, w, values, indices, y, block_rows=block_rows,
                   d_block=d_block, interpret=False)


@common.register_kernel("glm_sparse", common.PALLAS_INTERPRET, caps=_PALLAS_CAPS)
def _glm_sparse_interpret(task, w, values, indices, y, *,
                          block_rows=DEFAULT_BLOCK_ROWS,
                          d_block=DEFAULT_D_BLOCK):
    return _pallas(task, w, values, indices, y, block_rows=block_rows,
                   d_block=d_block, interpret=True)


@common.register_kernel(
    "glm_sparse", common.REFERENCE, caps=common.Caps(dtypes=None, sparse=True)
)
@functools.partial(jax.jit, static_argnames=("task", "block_rows", "d_block"))
def _glm_sparse_reference(task, w, values, indices, y, *,
                          block_rows=DEFAULT_BLOCK_ROWS,
                          d_block=DEFAULT_D_BLOCK):
    del block_rows, d_block
    return R.ell_glm_grad_ref(
        task, w.astype(jnp.float32), values.astype(jnp.float32), indices,
        y.astype(jnp.float32),
    )


def ell_glm_grad(
    task: str,
    w: jax.Array,        # [d]
    values: jax.Array,   # [N, K]
    indices: jax.Array,  # [N, K] int32
    y: jax.Array,        # [N]
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    d_block: int = DEFAULT_D_BLOCK,
    backend: str | None = None,
) -> jax.Array:
    """ELL sparse GLM gradient via the best available backend."""
    n, kk = values.shape
    info = {"dtype": jnp.result_type(values).name, "sparse": True,
            "n": n, "d": w.shape[0], "k": kk}
    return common.dispatch(
        "glm_sparse", task, w, values, indices, y, block_rows=block_rows,
        d_block=d_block, backend=backend, info=info,
    )


def margins_fit(d: int, k: int) -> bool:
    """Does the margins launch's one-hot, over the whole model padded to
    lanes, fit VMEM?"""
    return common.onehot_fits(MARGINS_BLOCK_ROWS,
                              common.padded(d, common.LANE), k)


_MARGINS_CAPS = common.Caps(
    sparse=True,
    check=lambda info: margins_fit(info.get("d", 0), info.get("k", 1)),
)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _margins_pallas(w, values, indices, *, interpret):
    """One margins launch over row tiles, the model pinned as one d-block.

    N is zero-padded up to the row tile and the filler rows sliced off
    (a zero row's margin is 0, which is not a zero loss).  A one-hot
    over VMEM's budget raises ``ValueError`` (:func:`margins_fit`).
    """
    n, _ = values.shape
    d = w.shape[0]
    n_pad = common.padded(n, MARGINS_BLOCK_ROWS)
    d_pad = common.padded(d, common.LANE)
    common.check_onehot("glm_sparse_margins", MARGINS_BLOCK_ROWS, d_pad,
                        values.shape[1])
    vp = common.pad_to(values.astype(jnp.float32), 0, n_pad)
    ip = common.pad_to(indices.astype(jnp.int32), 0, n_pad)
    wp = common.pad_to(w.astype(jnp.float32).reshape(d, 1), 0, d_pad)
    m = K.margins_pallas(wp, vp, ip, block_rows=MARGINS_BLOCK_ROWS,
                         d_block=d_pad, interpret=interpret)
    return m[:n, 0]


@common.register_kernel("glm_sparse_margins", common.PALLAS_TPU,
                        caps=_MARGINS_CAPS)
def _margins_tpu(w, values, indices):
    return _margins_pallas(w, values, indices, interpret=False)


@common.register_kernel("glm_sparse_margins", common.PALLAS_INTERPRET,
                        caps=_MARGINS_CAPS)
def _margins_interpret(w, values, indices):
    return _margins_pallas(w, values, indices, interpret=True)


@common.register_kernel(
    "glm_sparse_margins", common.REFERENCE,
    caps=common.Caps(dtypes=None, sparse=True),
)
@jax.jit
def _margins_reference(w, values, indices):
    return R.ell_margins_ref(w.astype(jnp.float32),
                             values.astype(jnp.float32), indices)


def ell_margins(
    w: jax.Array,        # [d]
    values: jax.Array,   # [N, K]  zero-padded ELL
    indices: jax.Array,  # [N, K]  int32
    *,
    backend: str | None = None,
) -> jax.Array:
    """``[N]`` float32 margins ``x_i . w`` via the best available backend.

    The one-hot gather is exact (one non-zero term a sum), so the Pallas
    flavors differ from the XLA gather only in the order of each row's
    sum over its K entries.
    """
    n, kk = values.shape
    info = {"dtype": jnp.result_type(values).name, "sparse": True,
            "n": n, "d": w.shape[0], "k": kk}
    return common.dispatch("glm_sparse_margins", w, values, indices,
                           backend=backend, info=info)
