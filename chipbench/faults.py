"""Faults planted under the timed path, to show that ``correct`` sees them.

Each returns a driver (``setup``, ``window``, ``check``) for
``harness.run``, equal to the traffic kind's own but with the program
broken underneath:

* ``unchanged``   every training step returns its state unchanged;
* ``half_batch``  every micro-batch leaves out half of its rows and takes
  the mean over the rest;
* ``altered``     the scoring kernel's answer for the first row of every
  batch is moved by 0.01 where it is produced.

:func:`control` plants the control instead: the plain reference one
precision step below the configuration's (see :mod:`chipbench.reference`)
in the program's place: at ``"high"`` in the scoring kernel's place, at
``"bf16"`` in training's epoch and loss (``"high"`` reads as the program
there; PERF.md, "Correct").

The chip's readings of these faults, with the program's and the
control's, set each limit (see ``calibrate.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import types

import numpy as np

from chipbench import reference
from chipbench.kinds import live, score, train

FAULTS = ("unchanged", "half_batch", "altered")


def _halved(rows_or_values, batch: int):
    """Zero the second half of every micro-batch of rows."""
    keep = (np.arange(len(rows_or_values)) % batch) < batch // 2
    return rows_or_values * keep.reshape((-1,) + (1,) * (rows_or_values.ndim - 1))


def _train_build(fault: str):
    def build(problem, strategy, sparse_data):
        init, epoch_fn, loss_fn = train.program(problem, strategy, sparse_data)
        if fault == "unchanged":
            return init, (lambda w: w), loss_fn
        b = strategy.batch
        if sparse_data:
            task, ell, y, step = problem
            broken = (task, ell._replace(values=_halved(ell.values, b)), y,
                      2 * step)
        else:
            broken = problem._replace(X=_halved(problem.X, b),
                                      step=2 * problem.step)
        _, epoch_fn, _ = train.program(broken, strategy, sparse_data)
        return init, epoch_fn, loss_fn

    return build


@contextlib.contextmanager
def _scoring(replace):
    """The engine's scoring kernel replaced by ``replace(real)``."""
    from repro.serve import glm as serve_glm

    real = serve_glm.glm_score
    serve_glm.glm_score = replace(real)
    try:
        yield
    finally:
        serve_glm.glm_score = real


def _altered(real):
    """Move the score of each batch's first row by 0.01 in the engine."""
    def broken(*args, **kwargs):
        return real(*args, **kwargs).at[0].add(0.01)
    return broken


def _control(real):
    """The reference's ``"high"`` scores in the kernel's place."""
    import jax.numpy as jnp

    def scores(task, w, values, indices, **kwargs):
        got = reference.scores(np.asarray(values), np.asarray(indices),
                               np.asarray(w)[None, :],
                               np.zeros(len(values), np.int32), mode="high")
        return jnp.asarray(got, jnp.float32)
    return scores


def _with_scoring(mod, replace):
    def window(ctx, state):
        with _scoring(replace):
            return mod.window(ctx, state)
    return types.SimpleNamespace(setup=mod.setup, window=window,
                                 check=mod.check)


#: the control's precision in training: ``"high"`` separates no number
#: there, its rounding being below the float32 state's own
TRAIN_CONTROL = "bf16"


def _train_control(mode: str):
    """The reference's epoch and loss at ``mode`` in the engine's place,
    on the same rows (sparse rows densified, which changes no sum)."""
    def build(problem, strategy, sparse_data):
        import jax.numpy as jnp

        if sparse_data:
            _, ell, y, step = problem
            n = ell.values.shape[0]
            X = jnp.zeros((n, ell.d), jnp.float32).at[
                jnp.arange(n)[:, None], ell.indices].add(ell.values)
        else:
            X, y, step = problem.X, problem.y, problem.step
        step, b = jnp.asarray(step, jnp.float32), strategy.batch
        return (jnp.zeros(X.shape[1], jnp.float32),
                lambda w: reference._epoch(w, X, y, step, b, mode),
                lambda w: reference._loss(X, y, w, mode))

    return build


def control(kind: str, mode: str | None = None):
    """The ``kind`` driver with the control in the program's place;
    ``mode`` picks another precision for training's (calibration)."""
    if kind == "train":
        build = _train_control(mode or TRAIN_CONTROL)
        return types.SimpleNamespace(
            setup=lambda ctx: train.setup(ctx, build=build),
            window=train.window, check=train.check)
    return _with_scoring({"score": score, "live": live}[kind], _control)


def driver(kind: str, fault: str):
    """The ``kind`` driver with ``fault`` planted under it."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}, not one of {FAULTS}")
    if kind == "train":
        if fault == "altered":
            raise ValueError("training has no answer to alter")
        return types.SimpleNamespace(
            setup=lambda ctx: train.setup(ctx, build=_train_build(fault)),
            window=train.window, check=train.check)
    mod = {"score": score, "live": live}[kind]
    if fault == "altered":
        return _with_scoring(mod, _altered)
    if kind == "score":
        raise ValueError(f"scoring has no training state to break ({fault})")
    return types.SimpleNamespace(setup=_live_setup(fault), window=live.window,
                                 check=live.check)


def _live_setup(fault: str):
    """Set up as usual, then break the learner for the window's steps."""
    def setup(ctx):
        state = live.setup(ctx)
        learner = state["learner"]
        if fault == "unchanged":
            learner._epoch = lambda W, *args: W
        else:
            lc = learner.config
            rows = state["rows"]
            halved = dataclasses.replace(
                rows, values=_halved(rows.values, lc.local_batch))
            learner._iter = iter(live.Backlog(halved, learner.stream.n_batch))
            learner.config = dataclasses.replace(lc, step_size=2 * lc.step_size)
            learner._epoch = learner._build_epoch()
        return state

    return setup
