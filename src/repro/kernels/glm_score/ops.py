"""Public wrapper for the fused ELL scoring kernel — registry-dispatched.

The ``reference`` flavor is the ``lax.scan`` gather/link oracle; the
Pallas flavors score one padded micro-batch per launch with the model
pinned in VMEM and the gather lowered to a one-hot product (kernel.py).
Rows are independent, so — unlike the fused SGD epoch — there is no
divisibility cap: N is zero-padded up to ``block_rows`` and the filler
scores are sliced off.  One capability gate routes problems the one-hot
cannot shape to the oracle: its VMEM budget
(:func:`repro.kernels.common.onehot_fits`; the one-hot spans the full
padded feature axis because the model never leaves VMEM).  When the
caller does not pin ``block_rows``, the per-device autotuner cache
(:mod:`repro.kernels.tune`) is consulted before the built-in default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common, tune
from repro.kernels.glm_score import kernel as K
from repro.kernels.glm_score import ref as R

#: built-in row tile when neither the caller nor the tuner pins one
DEFAULT_BLOCK_ROWS = 8


def _caps_check(info: dict) -> bool:
    d, k = info.get("d"), info.get("k")
    if d is not None and k is not None:
        return common.onehot_fits(
            info.get("block_rows", DEFAULT_BLOCK_ROWS),
            common.padded(d, common.LANE), k)
    return True


_PALLAS_CAPS = common.Caps(sparse=True, check=_caps_check)


def _pallas(task, w, values, indices, *, block_rows, interpret):
    """One fused scoring launch; model pinned in VMEM throughout.

    N is padded up to ``block_rows`` (filler rows are all-zero, so their
    margin is exactly 0); d is padded to the 128-lane tile internally.
    """
    n, _ = values.shape
    d = w.shape[0]
    n_pad = common.padded(n, block_rows)
    d_pad = common.padded(d, common.LANE)
    vp = common.pad_to(values.astype(jnp.float32), 0, n_pad)
    ip = common.pad_to(indices.astype(jnp.int32), 0, n_pad)
    wp = common.pad_to(w.astype(jnp.float32).reshape(d, 1), 0, d_pad)
    scores = K.glm_score_pallas(
        task, wp, vp, ip, block_rows=block_rows, interpret=interpret,
    )
    return scores[:n, 0]


# jit names a program after its function: device traces show jit_glm_score
_pallas.__name__ = "glm_score"
_pallas = jax.jit(_pallas, static_argnames=("task", "block_rows",
                                            "interpret"))


@common.register_kernel("glm_score", common.PALLAS_TPU, caps=_PALLAS_CAPS)
def _glm_score_tpu(task, w, values, indices, *,
                   block_rows=DEFAULT_BLOCK_ROWS):
    return _pallas(task, w, values, indices, block_rows=block_rows,
                   interpret=False)


@common.register_kernel("glm_score", common.PALLAS_INTERPRET,
                        caps=_PALLAS_CAPS)
def _glm_score_interpret(task, w, values, indices, *,
                         block_rows=DEFAULT_BLOCK_ROWS):
    return _pallas(task, w, values, indices, block_rows=block_rows,
                   interpret=True)


@common.register_kernel(
    "glm_score", common.REFERENCE, caps=common.Caps(dtypes=None, sparse=True)
)
@functools.partial(jax.jit, static_argnames=("task", "block_rows"))
def _glm_score_reference(task, w, values, indices, *,
                         block_rows=DEFAULT_BLOCK_ROWS):
    del block_rows
    return R.glm_score_ref(
        task, w.astype(jnp.float32), values.astype(jnp.float32),
        indices.astype(jnp.int32),
    )


def glm_score(
    task: str,
    w: jax.Array,        # [d]
    values: jax.Array,   # [N, K]  zero-padded ELL
    indices: jax.Array,  # [N, K]  int32
    *,
    block_rows: int | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Served scores for a padded-ELL batch via the best available backend.

    Returns ``[N]`` float32 — LR rows are sigmoid probabilities, SVM rows
    raw decision margins (:data:`repro.core.glm.LINKS`).
    ``block_rows=None`` consults the autotuner cache for this
    (backend, device, shape-class) before falling back to
    ``DEFAULT_BLOCK_ROWS``.
    """
    n, kk = values.shape
    d = w.shape[0]
    info = {"dtype": jnp.result_type(values).name, "sparse": True,
            "n": n, "d": d, "k": kk}
    if block_rows is None:
        b0 = common.resolve_backend("glm_score", backend=backend,
                                    interpret=interpret, info=info)
        run = None
        if tune.timeable(w, values, indices):
            run = lambda **cfg: common.dispatch(  # noqa: E731
                "glm_score", task, w, values, indices, backend=b0, **cfg)
        block_rows = tune.consult("glm_score", b0, info, run) \
            .get("block_rows", DEFAULT_BLOCK_ROWS)
    info["block_rows"] = block_rows
    return common.dispatch(
        "glm_score", task, w, values, indices, block_rows=block_rows,
        backend=backend, interpret=interpret, info=info,
    )
