"""Pure-jnp oracles for the CSR sparse SGD epoch and margins.

Straightforward segment gather and scatter over the CSR arrays, fp32:
each micro-step computes every row's margin from the current model and
keeps the pulls of the micro-batch's rows only, so a step costs all the
slots; correct and plain, not fast.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _pull(task, margins, y):
    if task == "lr":
        return -y * jax.nn.sigmoid(-margins)
    return -y * (margins < 1.0).astype(margins.dtype)


def slot_rows(offsets: jax.Array, slots: int) -> jax.Array:
    """``[slots]`` int32: the row each slot belongs to; a slot past the
    last row's end gets ``n``, which every segment sum drops."""
    return (jnp.searchsorted(offsets, jnp.arange(slots, dtype=offsets.dtype),
                             side="right") - 1).astype(jnp.int32)


def csr_margins_ref(
    w: jax.Array,        # [d]
    offsets: jax.Array,  # [N + 1] int32
    indices: jax.Array,  # [S] int32
    values: jax.Array,   # [S]
) -> jax.Array:
    """``[N]`` margins ``x_i . w``."""
    n = offsets.shape[0] - 1
    rows = slot_rows(offsets, indices.shape[0])
    return jax.ops.segment_sum(values * w[indices], rows, num_segments=n)


def csr_sgd_epoch_ref(
    task: str,
    w: jax.Array,        # [d]
    offsets: jax.Array,  # [N + 1] int32
    indices: jax.Array,  # [S] int32
    values: jax.Array,   # [S]
    y: jax.Array,        # [N]
    step: float,
    batch: int,
) -> jax.Array:
    """Mini-batch SGD over the rows in order, ``batch`` dividing ``N``:
    ``w -= (step / batch) * sum_{i in batch} pull_i x_i``."""
    n, d = offsets.shape[0] - 1, w.shape[0]
    assert n % batch == 0, (n, batch)
    rows = slot_rows(offsets, indices.shape[0])
    batch_of = jnp.arange(n) // batch

    def update(w, k):
        margins = y * csr_margins_ref(w, offsets, indices, values)
        pull = jnp.where(batch_of == k, _pull(task, margins, y), 0.0)
        coef = jnp.take(pull, rows, mode="fill", fill_value=0.0)
        g = jax.ops.segment_sum(values * coef, indices, num_segments=d)
        return w - (step / batch) * g, None

    return jax.lax.scan(update, w, jnp.arange(n // batch))[0]
