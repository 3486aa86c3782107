"""Public wrapper for the fused incremental-SGD epoch — registry-dispatched.

The ``reference`` flavor is the sequential lax.scan oracle (ref.py); the
Pallas flavors run one kernel launch per epoch with the model pinned in
VMEM.  All flavors update in fp32.

The Pallas flavors need ``micro_batch`` to tile ``n`` (the epoch is a
fixed-shape grid of ``[micro_batch, d]`` tiles, so it must divide ``n``
and be a multiple of 8 or ``n`` itself —
:func:`repro.kernels.common.micro_batch_ok`): the dispatch caps see both
in the call info, so auto-selection falls through to ``reference``
(which handles any micro-batch and the ragged tail) instead of dying
inside the kernel — forcing a Pallas flavor onto such a call raises
``ValueError``.  A caller that pins no ``micro_batch`` gets
``DEFAULT_MICRO_BATCH``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.glm_sgd import kernel as K
from repro.kernels.glm_sgd import ref as R

#: micro-batch when the caller pins none
DEFAULT_MICRO_BATCH = 8


_PALLAS_CAPS = common.Caps(check=lambda info: (
    info.get("n") is None or info.get("micro_batch") is None
    or common.micro_batch_ok(info["n"], info["micro_batch"])))


@functools.partial(
    jax.jit, static_argnames=("task", "step", "micro_batch", "interpret")
)
def _pallas(task, w, X, y, *, step, micro_batch, interpret):
    """One fused SGD epoch over (X, y); model stays in VMEM throughout.

    ``micro_batch`` must obey the tiling rule (checked, ValueError); d is
    padded to the 128-lane tile internally.
    """
    n, d = X.shape
    common.check_micro_batch("glm_sgd", n, micro_batch)
    d_pad = common.padded(d, common.LANE)
    Xp = common.pad_to(X.astype(jnp.float32), 1, d_pad)
    yp = y.astype(jnp.float32).reshape(n, 1)
    wp = common.pad_to(w.astype(jnp.float32).reshape(d, 1), 0, d_pad)
    w_out = K.glm_sgd_pallas(
        task, wp, Xp, yp, step=step, micro_batch=micro_batch, interpret=interpret
    )
    return w_out[:d, 0]


@common.register_kernel("glm_sgd", common.PALLAS_TPU, caps=_PALLAS_CAPS)
def _glm_sgd_tpu(task, w, X, y, *, step, micro_batch=DEFAULT_MICRO_BATCH):
    return _pallas(task, w, X, y, step=step, micro_batch=micro_batch,
                   interpret=False)


@common.register_kernel("glm_sgd", common.PALLAS_INTERPRET, caps=_PALLAS_CAPS)
def _glm_sgd_interpret(task, w, X, y, *, step, micro_batch=DEFAULT_MICRO_BATCH):
    return _pallas(task, w, X, y, step=step, micro_batch=micro_batch,
                   interpret=True)


@common.register_kernel("glm_sgd", common.REFERENCE, caps=common.Caps(dtypes=None))
@functools.partial(jax.jit, static_argnames=("task", "step", "micro_batch"))
def _glm_sgd_reference(task, w, X, y, *, step, micro_batch=DEFAULT_MICRO_BATCH):
    return R.glm_sgd_epoch_ref(
        task, w.astype(jnp.float32), X.astype(jnp.float32),
        y.astype(jnp.float32), step, micro_batch,
    )


def glm_sgd_epoch(
    task: str,
    w: jax.Array,   # [d]
    X: jax.Array,   # [N, d]
    y: jax.Array,   # [N]
    *,
    step: float,
    micro_batch: int = DEFAULT_MICRO_BATCH,
    backend: str | None = None,
) -> jax.Array:
    """One mini-batch SGD epoch via the best available backend."""
    n, d = X.shape
    info = {"dtype": jnp.result_type(X).name, "n": n, "d": d,
            "micro_batch": micro_batch}
    return common.dispatch(
        "glm_sgd", task, w, X, y, step=step, micro_batch=micro_batch,
        backend=backend, info=info,
    )
