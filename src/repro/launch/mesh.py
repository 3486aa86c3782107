"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is
    the slow DCN boundary — the async-local replica axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Tiny mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    model = model or 1
    return auto_mesh((n // model, model), ("data", "model"))


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    JAX 0.9 makes mesh axes ``Explicit`` by default, which turns the
    trainer's ``with_sharding_constraint`` annotations into type errors;
    this code base shards with GSPMD propagation.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
