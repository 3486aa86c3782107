"""Fused incremental-SGD epoch Pallas kernel — the Hogwild-kernel analogue.

The paper's asynchronous GPU kernel fuses gradient computation and model
update into one function that runs per example (Section 5).  On TPU the
grid steps of a core execute *sequentially*, so the same fusion gives a
deterministic incremental/mini-batch SGD pass with the model held in VMEM
scratch across the entire epoch shard:

    grid step k:  load example tile X_k [MB, d] (HBM->VMEM stream)
                  margins = y_k * (X_k @ w_vmem)          (MXU)
                  w_vmem -= (alpha/MB) * X_k^T pull        (MXU + VPU)

One kernel launch = one epoch over the shard = N/MB model updates, zero HBM
traffic for the model (it never leaves VMEM until the final write-out).
This is the TPU-native answer to "model access must be coalesced": the model
is pinned on-chip, so every update is a VMEM-bandwidth operation.  There are
no intra-core write conflicts to stagger (the GPU warp-shuffle trick is
unnecessary by construction — see DESIGN.md §2); cross-core asynchrony is
provided by the replica-merge engine on top.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pull(task, margins, y):
    if task == "lr":
        return -y * jax.nn.sigmoid(-margins)
    return -y * (margins < 1.0).astype(margins.dtype)


def _kernel(task, scale, x_ref, y_ref, w0_ref, out_ref, w_s):
    @pl.when(pl.program_id(0) == 0)
    def _():
        w_s[...] = w0_ref[...]

    X = x_ref[...]                    # [MB, d]
    y = y_ref[...]                    # [MB, 1]
    w = w_s[...]                      # [d, 1]
    margins = y * jnp.dot(X, w, preferred_element_type=jnp.float32)
    pull = _pull(task, margins, y)
    g = jax.lax.dot_general(          # X^T @ pull
        X, pull, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    w_s[...] = w - scale * g

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        out_ref[...] = w_s[...]


def glm_sgd_pallas(
    task: str,
    w0: jax.Array,    # [d_pad, 1]
    X: jax.Array,     # [N, d_pad]
    y: jax.Array,     # [N, 1]
    *,
    step: float,
    micro_batch: int,
    interpret: bool,
) -> jax.Array:
    n, d_pad = X.shape
    assert n % micro_batch == 0, (n, micro_batch)
    grid = (n // micro_batch,)
    body = functools.partial(_kernel, task, step / micro_batch)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((micro_batch, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((micro_batch, 1), lambda i: (i, 0)),
            pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d_pad, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d_pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d_pad, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: state carried
        ),
        interpret=interpret,
    )(X, y, w0)
