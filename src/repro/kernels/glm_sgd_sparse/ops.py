"""Public wrapper for the fused sparse-SGD epoch — registry-dispatched.

The ``reference`` flavor is the gather/segment-sum lax.scan oracle; the
Pallas flavors run one launch per epoch with the model pinned in VMEM and
gather/scatter lowered to one-hot products (kernel.py).

Two capability gates route problems the kernel cannot shape to the
oracle: the micro-batch tiling rule (:func:`repro.kernels.common.
micro_batch_ok`: a divisor of n that is a multiple of 8 or n itself) and
the one-hot VMEM budget (:func:`repro.kernels.common.onehot_fits`; the
one-hot spans the full padded feature axis because the model never
leaves VMEM).  Forcing a Pallas flavor past either raises
``ValueError``; rows too wide for the one-hot train on the CSR path
(``glm_sgd_csr``).  A caller that pins no ``micro_batch`` gets
``DEFAULT_MICRO_BATCH``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.glm_sgd_sparse import kernel as K
from repro.kernels.glm_sgd_sparse import ref as R

#: micro-batch when the caller pins none
DEFAULT_MICRO_BATCH = 8


def _caps_check(info: dict) -> bool:
    n, mb = info.get("n"), info.get("micro_batch")
    if n is not None and mb is not None and not common.micro_batch_ok(n, mb):
        return False
    d, k = info.get("d"), info.get("k")
    if d is not None and k is not None and mb is not None:
        return common.onehot_fits(mb, common.padded(d, common.LANE), k)
    return True


_PALLAS_CAPS = common.Caps(sparse=True, check=_caps_check)


@functools.partial(
    jax.jit, static_argnames=("task", "step", "micro_batch", "interpret")
)
def _pallas(task, w, values, indices, y, *, step, micro_batch, interpret):
    """One fused sparse SGD epoch; model stays in VMEM throughout.

    ``micro_batch`` must obey the tiling rule and the one-hot must fit
    its VMEM budget (both checked, ValueError); d is padded to the
    128-lane tile internally.
    """
    n, _ = values.shape
    d = w.shape[0]
    common.check_micro_batch("glm_sgd_sparse", n, micro_batch)
    d_pad = common.padded(d, common.LANE)
    common.check_onehot("glm_sgd_sparse", micro_batch, d_pad, values.shape[1])
    vp = values.astype(jnp.float32)
    ip = indices.astype(jnp.int32)
    yp = y.astype(jnp.float32).reshape(n, 1)
    wp = common.pad_to(w.astype(jnp.float32).reshape(d, 1), 0, d_pad)
    w_out = K.ell_sgd_pallas(
        task, wp, vp, ip, yp, step=step, micro_batch=micro_batch,
        interpret=interpret,
    )
    return w_out[:d, 0]


@common.register_kernel("glm_sgd_sparse", common.PALLAS_TPU, caps=_PALLAS_CAPS)
def _ell_sgd_tpu(task, w, values, indices, y, *, step,
                 micro_batch=DEFAULT_MICRO_BATCH):
    return _pallas(task, w, values, indices, y, step=step,
                   micro_batch=micro_batch, interpret=False)


@common.register_kernel("glm_sgd_sparse", common.PALLAS_INTERPRET,
                        caps=_PALLAS_CAPS)
def _ell_sgd_interpret(task, w, values, indices, y, *, step,
                       micro_batch=DEFAULT_MICRO_BATCH):
    return _pallas(task, w, values, indices, y, step=step,
                   micro_batch=micro_batch, interpret=True)


@common.register_kernel("glm_sgd_sparse", common.REFERENCE,
                        caps=common.Caps(dtypes=None, sparse=True))
@functools.partial(jax.jit, static_argnames=("task", "step", "micro_batch"))
def _ell_sgd_reference(task, w, values, indices, y, *, step,
                       micro_batch=DEFAULT_MICRO_BATCH):
    return R.ell_sgd_epoch_ref(
        task, w.astype(jnp.float32), values.astype(jnp.float32),
        indices.astype(jnp.int32), y.astype(jnp.float32), step, micro_batch,
    )


def ell_sgd_epoch(
    task: str,
    w: jax.Array,        # [d]
    values: jax.Array,   # [N, K]  zero-padded ELL
    indices: jax.Array,  # [N, K]  int32
    y: jax.Array,        # [N]
    *,
    step: float,
    micro_batch: int = DEFAULT_MICRO_BATCH,
    backend: str | None = None,
) -> jax.Array:
    """One mini-batch SGD epoch on ELL data via the best available backend."""
    n, kk = values.shape
    info = {"dtype": jnp.result_type(values).name, "sparse": True,
            "n": n, "d": w.shape[0], "k": kk, "micro_batch": micro_batch}
    return common.dispatch(
        "glm_sgd_sparse", task, w, values, indices, y, step=step,
        micro_batch=micro_batch, backend=backend, info=info,
    )
