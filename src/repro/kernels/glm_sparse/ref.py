"""Pure-jnp oracles for the ELL sparse GLM margins and gradient kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _pull(task, margins, y):
    if task == "lr":
        return -y * jax.nn.sigmoid(-margins)
    return -y * (margins < 1.0).astype(margins.dtype)


def ell_margins_ref(
    w: jax.Array,        # [d]
    values: jax.Array,   # [N, K]  zero-padded
    indices: jax.Array,  # [N, K]  int32 (0-padded; padded values are 0)
) -> jax.Array:
    """Margins ``x_i . w`` on ELL data via the XLA gather."""
    return jnp.sum(values * jnp.take(w, indices, axis=0), axis=1)


def ell_glm_grad_ref(
    task: str,
    w: jax.Array,        # [d]
    values: jax.Array,   # [N, K]  zero-padded
    indices: jax.Array,  # [N, K]  int32 (0-padded; padded values are 0)
    y: jax.Array,        # [N]
) -> jax.Array:
    """Sum GLM gradient on ELL data via gather + segment-sum (XLA path)."""
    d = w.shape[0]
    margins = y * ell_margins_ref(w, values, indices)   # [N]
    pull = _pull(task, margins, y)
    contrib = values * pull[:, None]             # [N, K]
    return jax.ops.segment_sum(
        contrib.reshape(-1), indices.reshape(-1), num_segments=d
    )
