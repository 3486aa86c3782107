"""Training jobs: epochs of mini-batch SGD, each followed by its loss check.

The program is built once in set-up by ``repro.core.sgd.make_epoch_fn``
(the engine's own entry) and driven as ``repro.core.sgd.run`` drives it:
an epoch, ``block_until_ready``, then the loss read back to the host.  A
job is ``epochs_per_job`` such epochs from ``w = 0``.  Set-up runs the
first ``checked_epochs`` epochs of the first job through that same call;
the window goes on with the job and starts new ones until ``seconds``
have passed.

Correct: the models and losses of the checked epochs against the float64
reference of the same SGD on the same rows (:mod:`chipbench.reference`).
"""
from __future__ import annotations

import math
import time

import numpy as np

from chipbench import data, reference


def program(problem, strategy, sparse_data: bool):
    """``(init, epoch_fn, loss_fn)`` of the engine, as the window drives it."""
    from repro.core import sgd

    init, epoch_fn, loss_fn, _ = sgd.make_epoch_fn(
        problem, strategy, sparse_data=sparse_data)
    return init, epoch_fn, loss_fn


class Job:
    """The compiled step with its state: the window's own call."""

    def __init__(self, init, epoch_fn, loss_fn, epochs_per_job, spans):
        self.init, self.epoch_fn, self.loss_fn = init, epoch_fn, loss_fn
        self.epochs_per_job = epochs_per_job
        self.spans = spans
        self.w = init
        self.epoch = 0

    def step(self) -> float:
        """One epoch and its loss check; returns the loss."""
        import jax

        if self.epoch == self.epochs_per_job:
            self.w, self.epoch = self.init, 0
        with self.spans.span("bench.epoch"):
            self.w = jax.block_until_ready(self.epoch_fn(self.w))
        with self.spans.span("bench.loss"):
            loss = float(self.loss_fn(self.w))
        self.epoch += 1
        return loss


def make_problem(config: dict, rows: data.Rows):
    import jax.numpy as jnp

    from repro.core import sparse
    from repro.core.glm import GLMProblem

    y = jnp.asarray(rows.y)
    if rows.dense:
        return GLMProblem(config["task"], jnp.asarray(rows.X), y,
                          config["step_size"]), False
    ell = sparse.ELLMatrix(jnp.asarray(rows.values),
                           jnp.asarray(rows.indices), rows.d)
    return (config["task"], ell, y, config["step_size"]), True


def setup(ctx, build=program) -> dict:
    from repro.core import sgd

    cfg, tr = ctx.config, ctx.traffic
    with ctx.spans.span("bench.data"):
        rows = data.make(cfg, ctx.seed)
        problem, sparse_data = make_problem(cfg, rows)
    strategy = sgd.SyncSGD(batch=tr["micro_batch"], kernel_backend=ctx.kernel)
    job = Job(*build(problem, strategy, sparse_data), tr["epochs_per_job"],
              ctx.spans)
    checked = []
    for _ in range(tr["checked_epochs"]):
        loss = job.step()
        checked.append((np.asarray(job.w, np.float64), loss))
    return {"rows": rows, "job": job, "checked": checked}


def window(ctx, state) -> dict:
    job = state["job"]
    n = state["rows"].n
    epochs = failed = 0
    t0 = time.perf_counter()
    while True:
        loss = job.step()
        epochs += 1
        failed += not math.isfinite(loss)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    rows = state["rows"]
    return {"attempted": epochs, "failed": failed, "epochs": epochs,
            "window_s": elapsed, "n": n, "d": rows.d,
            "nnz": None if rows.dense else int(rows.nnz.sum()),
            "metrics": {"train_rows_per_s": epochs * n / elapsed}}


def numbers(checked, ref_ws, ref_losses) -> dict:
    """The compared numbers of the checked epochs.

    A step is the window's call, one epoch: 8,087 (w8a) or 72,626
    (covtype) micro-steps that the program runs in one launch and whose
    states in between it never exposes.

    * ``loss_gap``: the largest relative gap of an epoch's loss;
    * ``grad_gap``: the gap between the norms of the first step's update
      as the optimizer gets it, ``(w0 - w1) / step`` with ``w0 = 0``,
      relative to the reference's norm;
    * ``change_gap``: the same for the change of the model over the
      checked epochs, ``w_last - w0``;
    * ``model_gap``: the norm of the difference of the two models after
      the checked epochs, relative to the reference's norm; unlike the
      gaps of norms it sees a model that points the wrong way.

    The model is one leaf, so the worst leaf is that leaf.
    """
    loss_gap = max(abs(l - r) / abs(r)
                   for (_, l), r in zip(checked, ref_losses))

    def norm_gap(w, w_ref):
        ref = float(np.linalg.norm(w_ref))
        return abs(float(np.linalg.norm(w)) - ref) / ref

    w_last, ref_last = checked[-1][0], ref_ws[-1]
    return {"loss_gap": loss_gap,
            "grad_gap": norm_gap(checked[0][0], ref_ws[0]),
            "change_gap": norm_gap(w_last, ref_last),
            "model_gap": float(np.linalg.norm(w_last - ref_last)
                               / np.linalg.norm(ref_last))}


def reference_run(ctx, rows, mode: str):
    return reference.sgd(rows.to_dense(), rows.y,
                         step=ctx.config["step_size"],
                         batch=ctx.traffic["micro_batch"],
                         epochs=ctx.traffic["checked_epochs"], mode=mode)


def check(ctx, state, window_out) -> dict:
    rows, checked = state["rows"], state["checked"]
    state.clear()                    # free the program's state first
    with reference.float64():
        ref_ws, ref_losses = reference_run(ctx, rows, "f64")
    return numbers(checked, ref_ws, ref_losses)
