"""The live async-local SGD learner: replica-merge over a stream.

This is the paper's §5.1 replica-merge scheme (the offline
``AsyncLocalSGD`` engine in :mod:`repro.core.sgd`) lifted to the
continual setting: instead of closed epochs over a frozen dataset, the
learner consumes an unbounded :mod:`repro.live.stream` minibatch
sequence, runs one *local* pass per replica per stream step, and merges
the replicas every ``merge_every`` steps.  Three pieces the offline
engine does not have, all previously dead code, are wired in:

* **bounded-staleness fault masking** — the merge averages only the
  replicas :class:`repro.train.fault.MergeGate` reports alive; a dead
  replica's model is frozen (it computes nothing) and dropped from the
  mean, and on revival it is re-seeded from the latest merged model —
  the paper's straggler insight applied to failures: a dead pod degrades
  the merge, never halts the stream;
* **error-feedback compressed merges** — ``compress=True`` exchanges
  int8-quantized per-replica *deltas* from the last merged anchor
  (:mod:`repro.optim.compress`, the Keuper & Pfreundt / Buckwild
  low-precision idea at the expensive interconnect boundary), with a
  persistent per-replica error-feedback buffer so the merged model stays
  unbiased over time;
* **kernel dispatch** — replica passes route through ``glm_sgd`` /
  ``glm_sgd_sparse`` / ``glm_sparse`` exactly like the offline engine
  (``kernel_backend=None`` keeps the pure-XLA path), vmapped over the
  replica axis, jitted once: stream batches hold one shape by contract.

Every step emits a ``live.step`` span over fetch, pass, merge and
publish (children ``live.fetch``, ``live.put``, ``live.pass``,
``live.merge``, ``live.publish``) and ``live.*`` counters, so a traced
run renders the learner next to the serving engine's ``serve.*`` spans
on one timeline (docs/LIVE.md).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import glm, sparse
from repro.core.sgd import partition_indices
from repro.live.stream import StreamBatch
from repro.obs import metrics, trace
from repro.optim import compress as C
from repro.train.fault import Heartbeat, MergeGate

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LiveConfig:
    """Knobs of the live loop (the offline ``AsyncLocalSGD`` axes plus
    the staleness/compression knobs the continual setting adds).

    replicas        R model replicas (paper's model-replication axis).
    step_size       SGD step alpha (constant; streams are unbounded).
    local_batch     per-replica update granularity (1 = incremental).
    merge_every     merge period in *stream steps* (staleness knob #1).
    access/rep_k    example->replica assignment within a chunk
                    (row-rr / row-ch + halos), as in the offline engine.
    compress        int8 error-feedback delta exchange at merges.
    kernel_backend  kernel dispatch registry backend (None = pure XLA).
    timeout_s       heartbeat staleness bound for the merge gate.
    """

    task: str = "lr"
    replicas: int = 4
    step_size: float = 0.05
    local_batch: int = 1
    merge_every: int = 4
    access: Literal["round_robin", "chunk"] = "chunk"
    rep_k: int = 0
    compress: bool = False
    kernel_backend: str | None = None
    timeout_s: float = 60.0

    def __post_init__(self):
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1: {self.replicas}")
        if self.merge_every < 1:
            raise ValueError(f"merge_every must be >= 1: {self.merge_every}")
        if self.local_batch < 1:
            raise ValueError(f"local_batch must be >= 1: {self.local_batch}")


class LiveLearner:
    """Replica-merge SGD over a live stream — see the module docstring.

    The learner is single-threaded by design (call :meth:`step` from one
    thread); concurrency with the serving path happens through the
    publisher's atomic ``swap_model``, never through shared mutable
    state.  ``clock`` feeds the heartbeat (injectable for deterministic
    staleness tests); :meth:`kill` / :meth:`revive` simulate replica
    death from the driving thread.
    """

    def __init__(self, config: LiveConfig, stream, *,
                 clock: Callable[[], float] = time.monotonic):
        self.config = config
        self.stream = stream
        self.d = stream.d
        R = config.replicas
        self._parts = partition_indices(
            stream.n_batch, R, config.access, config.rep_k)
        self.per = self._parts.shape[1]
        if self.per < 1:
            raise ValueError(
                f"chunk of {stream.n_batch} rows cannot feed "
                f"{R} replicas")
        if self.per % config.local_batch:
            raise ValueError(
                f"local_batch must divide the per-replica partition "
                f"{self.per} (= n_batch//replicas + rep_k), got "
                f"{config.local_batch}")
        self.heartbeat = Heartbeat(R, config.timeout_s, clock=clock)
        self.gate = MergeGate(config.merge_every, self.heartbeat)
        self.W: Array = jnp.zeros((R, self.d), jnp.float32)
        self.anchor: Array = jnp.zeros((self.d,), jnp.float32)
        self._ef: Array | None = (
            jnp.zeros((R, self.d), jnp.float32) if config.compress else None)
        self.steps = 0
        self.merges = 0
        self.merges_skipped = 0
        #: optional HealthMonitor (set by monitor.watch_live): each step
        #: reports published-snapshot staleness; one None check otherwise
        self.monitor = None
        self._merge_hooks: list[Callable[["LiveLearner"], None]] = []
        self._iter = iter(stream)
        self._epoch = self._build_epoch()
        trace.count_compiles()   # a compile mid-stream is a stall

    # -- construction --------------------------------------------------------

    def _build_epoch(self):
        """The jitted ``(W, data..., alive) -> W`` replica pass.

        Dead replicas compute nothing: their rows are returned frozen
        (``where(alive)`` on the output).  Dispatch mirrors
        ``core.sgd.make_epoch_fn``'s async branches.
        """
        cfg = self.config
        task, step, lb = cfg.task, cfg.step_size, cfg.local_batch
        per, d, backend = self.per, self.d, cfg.kernel_backend
        dense = getattr(self.stream, "dense", False)

        if dense:
            if backend is not None:
                from repro.kernels.glm_sgd import glm_sgd_epoch as _kepoch

                def one(w, Xr, yr):
                    return _kepoch(task, w, Xr, yr, step=step,
                                   micro_batch=lb, backend=backend)
            else:

                def one(w, Xr, yr):
                    if lb == 1:
                        return glm.incremental_epoch(task, w, Xr, yr, step)
                    return glm.minibatch_epoch(task, w, Xr, yr, step, lb)

            @jax.jit
            def epoch(W, Xp, yp, alive):
                W_new = jax.vmap(one)(W, Xp, yp)
                return jnp.where(alive[:, None], W_new, W)

            return epoch

        if backend is not None:
            if lb == per:
                # full-partition update: glm_sparse sum gradient
                from repro.kernels.glm_sparse import ell_glm_grad as _kgrad

                def one(w, v, i, yr):
                    g = _kgrad(task, w, v, i, yr, backend=backend)
                    return w - (step / per) * g
            else:
                # mini-batch local updates: fused sparse-SGD epoch kernel
                from repro.kernels.glm_sgd_sparse import (
                    ell_sgd_epoch as _kepoch_sp,
                )

                def one(w, v, i, yr):
                    return _kepoch_sp(task, w, v, i, yr, step=step,
                                      micro_batch=lb, backend=backend)
        else:

            def one(w, v, i, yr):
                m = sparse.ELLMatrix(v, i, d)
                if lb == 1:
                    return sparse.incremental_epoch(task, w, m, yr, step)
                return sparse.minibatch_epoch(task, w, m, yr, step, lb)

        @jax.jit
        def epoch(W, vals_p, idx_p, yp, alive):
            W_new = jax.vmap(one)(W, vals_p, idx_p, yp)
            return jnp.where(alive[:, None], W_new, W)

        return epoch

    # -- liveness ------------------------------------------------------------

    def alive(self) -> np.ndarray:
        return self.gate.alive_mask()

    def kill(self, replica: int) -> None:
        """Simulate replica death: its heartbeat goes permanently stale
        (until :meth:`revive`), so it stops training and is dropped from
        merges."""
        self.heartbeat.last_seen[replica] = -np.inf
        metrics.counter("live.kills").inc()

    def revive(self, replica: int) -> None:
        """Revive a dead replica: fresh heartbeat + model re-seeded from
        the latest merged anchor (it rejoins the consensus, not its own
        stale past)."""
        self.heartbeat.beat(replica)
        self.W = self.W.at[replica].set(self.anchor)
        if self._ef is not None:
            self._ef = self._ef.at[replica].set(0.0)
        metrics.counter("live.revivals").inc()

    # -- the loop ------------------------------------------------------------

    @property
    def merged_model(self) -> Array:
        """The latest merged model ``[d]`` (zeros before the first
        merge) — what the publisher ships to the scoring engine."""
        return self.anchor

    def add_merge_hook(self, fn: Callable[["LiveLearner"], None]) -> None:
        """``fn(learner)`` runs after every completed merge (the
        publisher attaches here)."""
        self._merge_hooks.append(fn)

    def step(self) -> StreamBatch:
        """One stream step: fetch the next chunk, run one local pass on
        every *alive* replica, merge when the gate says so.  Returns the
        consumed batch.

        Traced as ``live.step`` around the whole step, with children
        ``live.fetch``, ``live.put`` (the per-replica index and the
        copies to the device), ``live.pass`` (the epoch call until it
        returns) and, on a merge, ``live.merge`` and ``live.publish``."""
        with trace.span("live.step") as st:
            alive = self.gate.alive_mask()
            with trace.span("live.fetch"):
                batch = next(self._iter)
            if trace.enabled():
                st.set(step=self.steps, seq=batch.seq, alive=int(alive.sum()))
            with trace.span("live.put"):
                parts = self._parts
                if getattr(self.stream, "dense", False):
                    xs = (jnp.asarray(batch.X[parts]),)
                else:
                    xs = (jnp.asarray(batch.values[parts]),
                          jnp.asarray(batch.indices[parts]))
                yp = jnp.asarray(batch.y[parts])
                alive_j = jnp.asarray(alive)
            with trace.span("live.pass"):
                self.W = self._epoch(self.W, *xs, yp, alive_j)
            # alive replicas made progress this step; dead ones stay silent
            for r in np.nonzero(alive)[0]:
                self.heartbeat.beat(int(r))
            self.steps += 1
            metrics.counter("live.steps").inc()
            if self.gate.should_merge(self.steps):
                self.merge()
            if self.monitor is not None:
                self.monitor.on_learner_step(self)
        return batch

    def merge(self) -> Array | None:
        """Average the alive replicas (optionally through the int8
        error-feedback channel), redistribute, and advance the anchor.
        Returns the merged model, or None when every replica is dead
        (the merge is skipped — the stream keeps flowing)."""
        alive = self.gate.alive_mask()
        n_alive = int(alive.sum())
        if n_alive == 0:
            self.merges_skipped += 1
            metrics.counter("live.merges_skipped").inc()
            if trace.enabled():
                trace.instant("live.merge_skipped", step=self.steps)
            return None
        with trace.span("live.merge", step=self.steps, merge=self.merges,
                        alive=n_alive,
                        compressed=bool(self.config.compress)):
            alive_j = jnp.asarray(alive)
            if self.config.compress:
                merged, self._ef = _compressed_merge(
                    self.W, self.anchor, self._ef, alive_j)
            else:
                merged = _masked_mean(self.W, alive_j)
            self.W = jnp.where(alive_j[:, None],
                               jnp.broadcast_to(merged, self.W.shape),
                               self.W)
            self.anchor = merged
        self.merges += 1
        metrics.counter("live.merges").inc()
        for hook in self._merge_hooks:
            hook(self)
        return merged

    def run(self, n_steps: int) -> "LiveLearner":
        for _ in range(n_steps):
            self.step()
        return self

    def loss(self, eval_ell: sparse.ELLMatrix, y) -> float:
        """Holdout loss of the merged model (the served quantity)."""
        return float(sparse.loss(self.config.task, eval_ell,
                                 jnp.asarray(y), self.anchor))


# ---------------------------------------------------------------------------
# merge math (jitted)
# ---------------------------------------------------------------------------


@jax.jit
def _masked_mean(W: Array, alive: Array) -> Array:
    """Mean over alive rows only (dead replicas dropped from the
    consensus, paper §5.1 merge thread + MergeGate masking)."""
    mask = alive.astype(W.dtype)
    return (mask @ W) / jnp.maximum(mask.sum(), 1.0)


@jax.jit
def _compressed_merge(W: Array, anchor: Array, ef: Array,
                      alive: Array) -> tuple[Array, Array]:
    """int8 error-feedback delta exchange: each alive replica quantizes
    ``w_r - anchor`` (plus its carried residual), the dequantized deltas
    average into the new anchor, residuals persist per replica.  Dead
    replicas exchange nothing and their feedback is frozen."""

    def one(w_r, ef_r):
        delta = (w_r - anchor) + ef_r
        q, s = C.quantize_leaf(delta)
        deq = C.dequantize_leaf(q, s, delta)
        return deq, delta - deq

    deq, ef_new = jax.vmap(one)(W, ef)
    mask = alive.astype(W.dtype)
    mean_delta = (mask @ deq) / jnp.maximum(mask.sum(), 1.0)
    ef = jnp.where(alive[:, None], ef_new, ef)
    return anchor + mean_delta, ef
