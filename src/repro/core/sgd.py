"""Parallel SGD engine — the paper's exploratory axes as first-class config.

Exploratory axes (paper Fig. 1) and how they appear here:

* **Model-update strategy** — ``SyncSGD`` (Algorithm 2: one transactional
  update per pass; statistical efficiency identical to sequential) vs
  ``AsyncLocalSGD`` (Hogwild-family: R model replicas doing independent
  incremental/mini-batch updates over their partitions, merged periodically —
  the DimmWitted per-NUMA-node scheme of paper §5.1, which is the faithful
  TPU-expressible analogue of lock-free Hogwild; see DESIGN.md §2).

* **Model replication** (paper Table 2: kernel / block / thread) — the replica
  count R.  R=1 ≙ ``kernel`` (one shared model), R=#devices ≙ ``block``,
  R≫#devices ≙ ``thread``.  More replicas ⇒ better hardware efficiency
  (fewer/cheaper merges) and worse statistical efficiency — the paper's
  central trade-off, reproduced measurably.

* **Data access path** (row-rr / row-ch) — the example→replica assignment:
  ``round_robin`` interleaves examples, ``chunk`` gives contiguous ranges.
  (col-major is a *layout* choice inside the compute kernel — see
  kernels/glm_grad — not a partitioning choice.)

* **Data replication** (no-rep / rep-k) — each replica receives its partition
  plus ``rep_k`` halo examples from the neighbouring partition (paper
  §5.2.3), trading one extra pass-fraction of hardware efficiency for
  statistical efficiency.

The single-host study engine emulates R replicas with ``vmap`` (replica axis
is a real array axis), so statistical efficiency measurements are exact and
reproducible; the distributed trainer (train/trainer.py) runs the same
schedule over mesh axes with one replica per device/pod.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import glm, sparse
from repro.obs import trace

Array = jax.Array

# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

AccessPath = Literal["round_robin", "chunk"]
MergeScheme = Literal["mean", "weighted"]


@dataclasses.dataclass(frozen=True)
class SyncSGD:
    """Synchronous (transactional) updates — paper's synchronous axis.

    ``batch`` = B in Algorithm 1.  B=N gives batch gradient descent (the
    TF/BIDMach/ViennaCL configuration of the paper's experiments); smaller B
    gives mini-batch synchronous SGD with an update barrier per batch.

    ``kernel_backend`` routes the gradient/epoch computation through the
    kernel dispatch registry (``pallas-tpu`` / ``pallas-interpret`` /
    ``reference`` — see DESIGN.md §3) instead of the inline XLA
    expressions; None keeps the pure-XLA path.  Dense data supports any
    batch size (full-batch → glm_grad, mini-batch → glm_sgd); sparse
    data: full-batch → glm_sparse, mini-batch → glm_sgd_sparse, or
    glm_sgd_csr on CSR rows too wide for the one-hot (make_epoch_fn).
    """

    batch: int | None = None  # None -> full batch (B = N)
    kernel_backend: str | None = None

    @property
    def name(self) -> str:
        base = "sync" if self.batch is None else f"sync-b{self.batch}"
        if self.kernel_backend:
            base += f"[{self.kernel_backend}]"
        return base


@dataclasses.dataclass(frozen=True)
class AsyncLocalSGD:
    """Asynchronous replica-merge updates — paper's asynchronous axis.

    replicas      R model replicas (model-replication granularity).
    local_batch   per-replica update granularity (1 = incremental Hogwild).
    merge_every   merge period in *epochs*; <1 merges multiple times per
                  epoch (e.g. 0.25 ⇒ 4 merges/epoch).  Staleness knob.
    access        example→replica assignment (row-rr vs row-ch).
    rep_k         halo data replication (paper §5.2.3).

    ``kernel_backend`` mirrors ``SyncSGD.kernel_backend``: replica epochs
    route through the kernel dispatch registry (dense → glm_sgd's fused
    epoch vmapped over the replica axis; sparse → glm_sparse, which is a
    sum-gradient kernel and therefore needs full-partition local updates,
    ``local_batch`` == partition size).  None keeps the pure-XLA path.
    """

    replicas: int = 8
    local_batch: int = 1
    merge_every: float = 1.0
    access: AccessPath = "chunk"
    rep_k: int = 0
    merge: MergeScheme = "mean"
    kernel_backend: str | None = None

    @property
    def name(self) -> str:
        base = (
            f"async-r{self.replicas}-b{self.local_batch}"
            f"-m{self.merge_every}-{self.access[:5]}-rep{self.rep_k}"
        )
        if self.kernel_backend:
            base += f"[{self.kernel_backend}]"
        return base


# ---------------------------------------------------------------------------
# Data partitioning (access path + rep-k halos)
# ---------------------------------------------------------------------------


def partition_indices(
    n: int, replicas: int, access: AccessPath = "chunk", rep_k: int = 0
) -> np.ndarray:
    """Example→replica assignment matrix ``[replicas, per + rep_k]``.

    ``chunk``       replica r gets the contiguous range [r*per, (r+1)*per).
    ``round_robin`` replica r gets examples r, r+R, r+2R, ...
    ``rep_k``       each replica additionally gets the first ``rep_k``
                    examples of the *next* replica's partition (cyclic halo),
                    preserving sequential access — paper §5.2.3.
    """
    per = n // replicas
    n_eff = per * replicas
    base = np.arange(n_eff)
    if access == "chunk":
        parts = base.reshape(replicas, per)
    elif access == "round_robin":
        parts = base.reshape(per, replicas).T
    else:
        raise ValueError(access)
    if rep_k > 0:
        # halo = the first rep_k examples of the *following* partitions in
        # cyclic order (wraps across several partitions when rep_k > per)
        halos = []
        for r in range(replicas):
            stream = np.concatenate(
                [parts[(r + s) % replicas] for s in range(1, replicas + 1)])
            halos.append(stream[:rep_k])
        parts = np.concatenate([parts, np.stack(halos, axis=0)], axis=1)
    return parts.astype(np.int32)


# ---------------------------------------------------------------------------
# Epoch execution
# ---------------------------------------------------------------------------


def _dense_replica_epoch(task, W, Xp, yp, step, local_batch):
    """One local epoch on every replica (vmap over the replica axis)."""

    def one(w, X, y):
        if local_batch == 1:
            return glm.incremental_epoch(task, w, X, y, step)
        return glm.minibatch_epoch(task, w, X, y, step, local_batch)

    return jax.vmap(one)(W, Xp, yp)


def _sparse_replica_epoch(task, W, vals, idx, d, yp, step, local_batch):
    def one(w, v, i, y):
        m = sparse.ELLMatrix(v, i, d)
        if local_batch == 1:
            return sparse.incremental_epoch(task, w, m, y, step)
        return sparse.minibatch_epoch(task, w, m, y, step, local_batch)

    return jax.vmap(one)(W, vals, idx, yp)


def merge_replicas(W: Array, scheme: MergeScheme = "mean") -> Array:
    """Replica merge: average and redistribute (paper §5.1 merge thread)."""
    if scheme == "mean":
        mean = jnp.mean(W, axis=0)
        return jnp.broadcast_to(mean, W.shape)
    raise ValueError(scheme)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunResult:
    """History of one SGD run (the three performance axes derive from it)."""

    losses: np.ndarray          # [epochs+1] loss after each epoch (incl. init)
    epoch_times: np.ndarray     # [epochs]   wall seconds per epoch
    strategy: str
    task: str

    def epochs_to(self, target: float) -> int | None:
        """Statistical efficiency: #epochs to reach loss <= target."""
        hit = np.nonzero(self.losses <= target)[0]
        return int(hit[0]) if len(hit) else None

    def time_to(self, target: float) -> float | None:
        """Time to convergence: sum of epoch times until target reached."""
        e = self.epochs_to(target)
        if e is None:
            return None
        return float(np.sum(self.epoch_times[:e]))

    @property
    def time_per_epoch(self) -> float:
        """Hardware efficiency: mean seconds per epoch."""
        return float(np.mean(self.epoch_times))


def _bind(fn, data):
    """``fn(data, *args)`` jitted, with ``data`` bound as an argument.

    A jitted function that closes over arrays compiles them into the
    program as constants: at covtype's full size that is a 350 MB
    executable per program, seconds of TPU compile each, and too large
    for the persistent compile cache.  The result is a pytree
    (``jax.tree_util.Partial``), so a caller that jits over it, like the
    study runner's stacked vmap, receives ``data`` as an argument too.
    """
    return jax.tree_util.Partial(jax.jit(fn), data)


def _onehot_fits(micro_batch: int | None, d: int, width: int) -> bool:
    """Do the one-hot kernels' tiles over ``d`` features and rows of
    ``width`` nonzeros fit VMEM: the loss's margins launch's, and the
    fused epoch's at ``micro_batch`` rows (None: no fused epoch)?"""
    from repro.kernels import common
    from repro.kernels.glm_sparse.ops import margins_fit

    return margins_fit(d, width) and (
        micro_batch is None
        or common.onehot_fits(micro_batch, common.padded(d, common.LANE),
                              width))


def _sparse_rows(m, strategy):
    """The layout the sparse epoch and loss run on: ELL rows as given; CSR
    rows as given where the CSR kernels take them, padded to ELL where
    the one-hot fits or no kernel backend is asked for."""
    if not isinstance(m, sparse.CSRRows):
        return m
    n = m.shape[0]
    mini = isinstance(strategy, SyncSGD) and (strategy.batch or n) < n
    if (strategy.kernel_backend is None
            or _onehot_fits(strategy.batch if mini else None, m.d, m.width)):
        return m.to_ell()
    if not mini:
        raise ValueError(
            f"CSR rows too wide for the one-hot kernels (d={m.d}, widest "
            f"row {m.width}) train only by mini-batch SyncSGD on the CSR "
            f"kernel glm_sgd_csr; {strategy.name} would need a CSR "
            f"sum-gradient or replica kernel, which the registry lacks")
    return m


def make_epoch_fn(
    problem: glm.GLMProblem | tuple,
    strategy,
    *,
    sparse_data: bool = False,
    step_param: bool = False,
):
    """Build a jitted ``(w_state) -> w_state`` epoch function + initial state.

    Returns ``(init_state, epoch_fn, loss_fn, merges_per_epoch)``.  For
    SyncSGD the state is ``w [d]``; for AsyncLocalSGD it is ``W [R, d]``.
    The data are arguments of the compiled programs, never constants
    (:func:`_bind`).

    With ``step_param=True`` the epoch function takes ``(state, step)``
    with the step size as a traced scalar instead of baking the problem's
    step in — the study runner vmaps it over a stacked step axis to run a
    whole §6.1 step-size grid in one program.  Kernel-backend epochs bake
    the step statically (it is a kernel compile-time constant) and refuse
    ``step_param``.

    Sparse problems come as ``(task, rows, y, step)`` with ``rows`` an
    :class:`sparse.ELLMatrix` or :class:`sparse.CSRRows`.  ELL rows take
    the one-hot kernels (``glm_sgd_sparse``, ``glm_sparse``) with a
    ``kernel_backend`` and the loss takes its margins from
    ``glm_sparse_margins`` where that one-hot fits the VMEM budget and
    from the XLA gather of :func:`sparse.loss` where it does not.  CSR
    rows are routed by :func:`_onehot_fits`: where the one-hot fits they
    are padded to ELL and take that same path; where it does not, a
    mini-batch ``SyncSGD`` with a ``kernel_backend`` trains on the CSR
    kernels, the epoch on ``glm_sgd_csr`` and the loss's margins on
    ``glm_csr_margins`` (full-batch and ``AsyncLocalSGD`` have no CSR
    kernel and raise ``ValueError``).  Without a ``kernel_backend`` CSR
    rows are padded to ELL for the XLA path.
    """
    if sparse_data:
        task, m, y, step0 = problem
        m = _sparse_rows(m, strategy)
        n, d = m.shape
        csr = isinstance(m, sparse.CSRRows)
        data = ((m.offsets, m.indices, m.values, y) if csr
                else (m.values, m.indices, y))
    else:
        csr = False
        task, X, y, step0 = problem.task, problem.X, problem.y, problem.step
        n, d = X.shape
        data = (X, y)

    def _finalize(epoch_of_step, data):
        """Bind the step statically, or expose it as a traced argument."""
        if step_param:
            return _bind(epoch_of_step, data)
        return _bind(lambda data, state: epoch_of_step(data, state, step0),
                     data)

    def _ell(v, i):
        return sparse.ELLMatrix(v, i, d)

    backend = strategy.kernel_backend
    kernel_loss = False
    if csr:
        from repro.kernels.glm_sgd_csr import csr_margins, csr_sgd_epoch
    elif sparse_data and backend is not None:
        from repro.kernels.glm_sparse.ops import ell_margins, margins_fit

        kernel_loss = margins_fit(d, m.max_nnz)

    if csr:
        def loss(data, w):
            o, i, v, y = data
            return sparse.margin_loss(
                task, y * csr_margins(w, o, i, v, backend=backend))
    elif kernel_loss:
        # the margins through the registry; the link and sum stay in XLA
        def loss(data, w):
            v, i, y = data
            return sparse.margin_loss(
                task, y * ell_margins(w, v, i, backend=backend))
    elif sparse_data:
        def loss(data, w):
            v, i, y = data
            return sparse.loss(task, _ell(v, i), y, w)
    else:
        def loss(data, w):
            X, y = data
            return glm.LOSSES[task](w, X, y)

    if isinstance(strategy, SyncSGD):
        batch = strategy.batch or n
        if backend is not None and step_param:
            raise ValueError(
                "step_param needs kernel_backend=None (kernel epochs bake "
                "the step size as a compile-time constant)")

        if csr:
            def epoch(data, w):
                o, i, v, y = data
                return csr_sgd_epoch(task, w, o, i, v, y, step=step0,
                                     micro_batch=batch, backend=backend)

            epoch = _bind(epoch, data)

        elif sparse_data:
            if backend is not None:
                # full-batch -> glm_sparse (sum gradient); mini-batch ->
                # glm_sgd_sparse (fused epoch, model resident in VMEM)
                from repro.kernels.glm_sgd_sparse import (
                    ell_sgd_epoch as _kepoch_sp,
                )
                from repro.kernels.glm_sparse import ell_glm_grad as _kgrad_sp

                def epoch(data, w):
                    v, i, y = data
                    if batch >= n:
                        g = _kgrad_sp(task, w, v, i, y, backend=backend)
                        return w - step0 * g
                    return _kepoch_sp(task, w, v, i, y, step=step0,
                                      micro_batch=batch, backend=backend)

                epoch = _bind(epoch, data)

            else:

                def epoch_s(data, w, step):
                    v, i, y = data
                    if batch >= n:
                        g = sparse.grad(task, _ell(v, i), y, w)
                        return w - (step / n) * g * n  # alpha on sum grad
                    return sparse.minibatch_epoch(task, w, _ell(v, i), y,
                                                  step, batch)

                epoch = _finalize(epoch_s, data)

        else:
            if backend is not None:
                # route through the kernel dispatch registry: full-batch ->
                # glm_grad (fused sum gradient), mini-batch -> glm_sgd
                # (fused epoch, model resident in VMEM on TPU)
                from repro.kernels.glm_grad import glm_grad as _kgrad
                from repro.kernels.glm_sgd import glm_sgd_epoch as _kepoch

                def epoch(data, w):
                    X, y = data
                    if batch >= n:
                        g = _kgrad(task, w, X, y, backend=backend)
                        return w - step0 * g
                    return _kepoch(task, w, X, y, step=step0,
                                   micro_batch=batch, backend=backend)

                epoch = _bind(epoch, data)

            else:

                def epoch_s(data, w, step):
                    X, y = data
                    if batch >= n:
                        g = glm.grad_fused(task, w, X, y)
                        return w - step * g
                    return glm.minibatch_epoch(task, w, X, y, step, batch)

                epoch = _finalize(epoch_s, data)

        init = jnp.zeros((d,), dtype=jnp.float32)
        return init, epoch, _bind(loss, data), 0

    assert isinstance(strategy, AsyncLocalSGD)
    R = strategy.replicas
    if backend is not None and step_param:
        raise ValueError(
            "step_param needs kernel_backend=None (kernel epochs bake "
            "the step size as a compile-time constant)")
    parts = partition_indices(n, R, strategy.access, strategy.rep_k)
    per = parts.shape[1]
    merges = max(1, int(round(1.0 / strategy.merge_every))) if strategy.merge_every <= 1 else 1
    # merge_every > 1 handled by the driver (merge every int(merge_every) epochs)
    # per-replica partitions: [R, per, ...] for every array of the data
    parted = tuple(jnp.take(a, parts, axis=0) for a in data)

    if sparse_data:
        if backend is not None:
            if strategy.local_batch == per:
                # full-partition update: glm_sparse sum gradient
                from repro.kernels.glm_sparse import ell_glm_grad as _kgrad_sp

                def _replica_epoch(parted, W, step):
                    def one(w, v, i, yr):
                        g = _kgrad_sp(task, w, v, i, yr, backend=backend)
                        return w - (step / per) * g

                    return jax.vmap(one)(W, *parted)

            elif per % strategy.local_batch == 0:
                # mini-batch local updates: fused sparse-SGD epoch kernel
                from repro.kernels.glm_sgd_sparse import (
                    ell_sgd_epoch as _kepoch_sp,
                )

                def _replica_epoch(parted, W, step):
                    def one(w, v, i, yr):
                        return _kepoch_sp(task, w, v, i, yr, step=step,
                                          micro_batch=strategy.local_batch,
                                          backend=backend)

                    return jax.vmap(one)(W, *parted)

            else:
                raise ValueError(
                    f"kernel_backend epochs need local_batch to divide the "
                    f"partition size {per} (= n//replicas + rep_k), got "
                    f"{strategy.local_batch}")

        else:

            def _replica_epoch(parted, W, step):
                vals_p, idx_p, y_p = parted
                return _sparse_replica_epoch(
                    task, W, vals_p, idx_p, d, y_p, step, strategy.local_batch)

    else:
        if backend is not None:
            if per % strategy.local_batch != 0:
                raise ValueError(
                    f"kernel_backend epochs need local_batch to divide the "
                    f"partition size {per} (= n//replicas + rep_k), got "
                    f"{strategy.local_batch}")
            from repro.kernels.glm_sgd import glm_sgd_epoch as _kepoch

            def _replica_epoch(parted, W, step):
                def one(w, Xr, yr):
                    return _kepoch(task, w, Xr, yr, step=step,
                                   micro_batch=strategy.local_batch,
                                   backend=backend)

                return jax.vmap(one)(W, *parted)

        else:

            def _replica_epoch(parted, W, step):
                Xp, y_p = parted
                return _dense_replica_epoch(
                    task, W, Xp, y_p, step, strategy.local_batch)

    def epoch_s(parted, W, step):
        for _ in range(merges):
            W = _replica_epoch(parted, W, step)
            W = merge_replicas(W, strategy.merge)
        return W

    init = jnp.zeros((R, d), dtype=jnp.float32)
    return (init, _finalize(epoch_s, parted),
            _bind(lambda data, W: loss(data, W[0]), data), merges)


def run(
    problem,
    strategy,
    epochs: int,
    *,
    sparse_data: bool = False,
    record_time: bool = True,
) -> RunResult:
    """Run SGD for ``epochs`` passes, recording loss + wall time per pass."""
    import time

    init, epoch_fn, loss_fn, merges = make_epoch_fn(
        problem, strategy, sparse_data=sparse_data)
    task = problem[0] if sparse_data else problem.task

    state = init
    losses = [float(loss_fn(state))]
    times = []
    # warmup compile outside the timed region
    with trace.span("engine.compile", strategy=strategy.name, task=task):
        state_c = epoch_fn(state)
        jax.block_until_ready(state_c)
    state = state_c
    losses.append(float(loss_fn(state)))
    times.append(float("nan"))  # epoch 1 time includes compile; exclude
    for e in range(epochs - 1):
        # host-level epoch span: for async strategies the epoch body fuses
        # `merges` replica-merge rounds (merge_replicas runs inside jit)
        with trace.span("engine.epoch", epoch=e + 1, strategy=strategy.name,
                        merges=merges):
            t0 = time.perf_counter()
            state = epoch_fn(state)
            jax.block_until_ready(state)
            times.append(time.perf_counter() - t0)
        losses.append(float(loss_fn(state)))
    # replace the compile-epoch time with the median of the rest
    if len(times) > 1:
        times[0] = float(np.nanmedian(times[1:]))
    else:
        times[0] = 0.0
    return RunResult(
        losses=np.asarray(losses),
        epoch_times=np.asarray(times),
        strategy=strategy.name,
        task=task,
    )
