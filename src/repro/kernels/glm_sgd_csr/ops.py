"""Public wrappers for the CSR sparse SGD epoch (``glm_sgd_csr``) and
margins (``glm_csr_margins``) — registry-dispatched.

The rows are :class:`repro.core.sparse.CSRRows` arrays: ``offsets
[N+1]``, and ``indices`` / ``values`` ``[S]`` with ``S`` at least the
nonzeros held.  The Pallas flavors hold the model in VMEM as ``[d_pad /
1024, 8, 128]`` (kernel.py), so they take any ``d`` whose model fits
VMEM, and any row widths; they need ``S`` to be a whole number of
``kernel.CHUNK`` slots, which :meth:`CSRMatrix.to_rows` gives (any
other ``S`` is padded here, a copy a call).  The epoch's one capability
gate is the micro-batch tiling rule
(:func:`repro.kernels.common.micro_batch_ok`); forcing a Pallas flavor
past it raises ``ValueError``.

The ``reference`` flavors are the segment-sum oracles of ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.glm_sgd_csr import kernel as K
from repro.kernels.glm_sgd_csr import ref as R

#: micro-batch when the caller pins none
DEFAULT_MICRO_BATCH = 8


def _tiles(w: jax.Array) -> jax.Array:
    """``[d]`` to the kernels' ``[d_pad / 1024, 8, 128]`` fp32 layout."""
    d_pad = common.padded(w.shape[0], K.TILE)
    return common.pad_to(w.astype(jnp.float32), 0, d_pad).reshape(
        d_pad // K.TILE, common.SUBLANE, common.LANE)


def _slots(indices, values):
    return (common.pad_to(indices.astype(jnp.int32), 0, K.CHUNK),
            common.pad_to(values.astype(jnp.float32), 0, K.CHUNK))


def _info(w, offsets, values, **extra):
    return {"dtype": jnp.result_type(values).name, "sparse": True,
            "n": offsets.shape[0] - 1, "d": w.shape[0], **extra}


_EPOCH_CAPS = common.Caps(sparse=True, check=lambda info: (
    info.get("n") is None or info.get("micro_batch") is None
    or common.micro_batch_ok(info["n"], info["micro_batch"])))


@functools.partial(
    jax.jit, static_argnames=("task", "step", "micro_batch", "interpret"))
def _pallas(task, w, offsets, indices, values, y, *, step, micro_batch,
            interpret):
    n, d = offsets.shape[0] - 1, w.shape[0]
    common.check_micro_batch("glm_sgd_csr", n, micro_batch)
    w_out = K.csr_sgd_pallas(
        task, _tiles(w), offsets.astype(jnp.int32),
        *_slots(indices, values), y.astype(jnp.float32).reshape(n, 1),
        step=step, micro_batch=micro_batch, interpret=interpret)
    return w_out.reshape(-1)[:d]


@common.register_kernel("glm_sgd_csr", common.PALLAS_TPU, caps=_EPOCH_CAPS)
def _csr_sgd_tpu(task, w, offsets, indices, values, y, *, step,
                 micro_batch=DEFAULT_MICRO_BATCH):
    return _pallas(task, w, offsets, indices, values, y, step=step,
                   micro_batch=micro_batch, interpret=False)


@common.register_kernel("glm_sgd_csr", common.PALLAS_INTERPRET,
                        caps=_EPOCH_CAPS)
def _csr_sgd_interpret(task, w, offsets, indices, values, y, *, step,
                       micro_batch=DEFAULT_MICRO_BATCH):
    return _pallas(task, w, offsets, indices, values, y, step=step,
                   micro_batch=micro_batch, interpret=True)


@common.register_kernel("glm_sgd_csr", common.REFERENCE,
                        caps=common.Caps(dtypes=None, sparse=True))
@functools.partial(jax.jit, static_argnames=("task", "step", "micro_batch"))
def _csr_sgd_reference(task, w, offsets, indices, values, y, *, step,
                       micro_batch=DEFAULT_MICRO_BATCH):
    return R.csr_sgd_epoch_ref(
        task, w.astype(jnp.float32), offsets, indices,
        values.astype(jnp.float32), y.astype(jnp.float32), step, micro_batch)


def csr_sgd_epoch(
    task: str,
    w: jax.Array,        # [d]
    offsets: jax.Array,  # [N + 1] int32
    indices: jax.Array,  # [S] int32
    values: jax.Array,   # [S]
    y: jax.Array,        # [N]
    *,
    step: float,
    micro_batch: int = DEFAULT_MICRO_BATCH,
    backend: str | None = None,
) -> jax.Array:
    """One mini-batch SGD epoch over CSR rows via the best available
    backend; ``micro_batch`` must divide N."""
    info = _info(w, offsets, values, micro_batch=micro_batch)
    return common.dispatch("glm_sgd_csr", task, w, offsets, indices, values,
                           y, step=step, micro_batch=micro_batch,
                           backend=backend, info=info)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _margins_pallas(w, offsets, indices, values, *, interpret):
    """Rows padded to a whole block with empty rows (their ends repeat
    the last end), whose margins are sliced off."""
    n = offsets.shape[0] - 1
    n_pad = common.padded(n, K.MARGIN_ROWS)
    off = jnp.pad(offsets.astype(jnp.int32), (0, n_pad - n), mode="edge")
    m = K.csr_margins_pallas(_tiles(w), off, *_slots(indices, values),
                             interpret=interpret)
    return m[:n, 0]


_MARGINS_CAPS = common.Caps(sparse=True)


@common.register_kernel("glm_csr_margins", common.PALLAS_TPU,
                        caps=_MARGINS_CAPS)
def _margins_tpu(w, offsets, indices, values):
    return _margins_pallas(w, offsets, indices, values, interpret=False)


@common.register_kernel("glm_csr_margins", common.PALLAS_INTERPRET,
                        caps=_MARGINS_CAPS)
def _margins_interpret(w, offsets, indices, values):
    return _margins_pallas(w, offsets, indices, values, interpret=True)


@common.register_kernel("glm_csr_margins", common.REFERENCE,
                        caps=common.Caps(dtypes=None, sparse=True))
@jax.jit
def _margins_reference(w, offsets, indices, values):
    return R.csr_margins_ref(w.astype(jnp.float32), offsets, indices,
                             values.astype(jnp.float32))


def csr_margins(
    w: jax.Array,        # [d]
    offsets: jax.Array,  # [N + 1] int32
    indices: jax.Array,  # [S] int32
    values: jax.Array,   # [S]
    *,
    backend: str | None = None,
) -> jax.Array:
    """``[N]`` float32 margins ``x_i . w`` via the best available backend."""
    return common.dispatch("glm_csr_margins", w, offsets, indices, values,
                           backend=backend, info=_info(w, offsets, values))
