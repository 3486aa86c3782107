"""GLM scoring service: batched admission over the fused scoring kernel.

The training half of the repo turns the paper's §4/§5 access-path
findings into SGD kernels; this is the inference half the north star
calls "millions of users": a scoring engine for trained GLMs (LR
probabilities, SVM margins) built from three pieces —

* **batched admission** — a bounded FIFO queue; requests accumulate
  until either ``max_batch`` are waiting or the oldest has waited
  ``flush_deadline_s``, then one micro-batch is scored.  Batches are
  always *padded to exactly* ``max_batch`` rows (all-zero filler), so
  every launch has one stable shape and jit never re-traces on traffic
  wobble (the serving analogue of the study runner's vmap-stacked
  grids);
* **the fused scoring kernel** — ``kernels/glm_score``: one launch per
  batch, model pinned in VMEM, ELL gather as a one-hot product, the
  task link (LR sigmoid / SVM identity) fused in.  Dispatch goes
  through the standard three-backend registry, so the engine runs
  anywhere the conformance suite does;
* **atomic snapshot hot-swap** — the model is an immutable
  :class:`ModelSnapshot`; ``swap_model`` publishes a new snapshot in a
  single reference assignment, and a flush reads the reference exactly
  once for its whole batch.  Readers therefore never observe a torn
  update: every response is stamped with the one ``model_version`` that
  scored it (the snapshot-read discipline async training needs — see
  ROADMAP "train while serving").

Thread model: any number of producer threads may ``try_admit``/
``submit``; any number of consumer threads may ``flush`` (dequeue is
under the lock, scoring is outside it).  Every flush is counted
(``serve.*`` counters, ``serve.queue_wait_us`` among them) and, with
tracing on, spanned: ``serve.flush`` with children ``serve.pack``,
``serve.put``, ``serve.dispatch``, ``serve.wait`` and ``serve.respond``
(docs/SERVING.md, docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.glm import LINKS
from repro.kernels.glm_score import glm_score
from repro.obs import metrics, trace


@dataclasses.dataclass(frozen=True)
class ModelSnapshot:
    """One immutable published model.  ``w`` is the [d] weight vector;
    ``version`` increases by 1 per ``swap_model``.  ``step`` is the
    producer's progress stamp — a live learner publishes the learner
    step that produced this model (``repro.live.publish``), so staleness
    is measurable per snapshot; None for models with no live producer."""

    task: str
    w: jax.Array
    version: int
    step: int | None = None

    def __post_init__(self):
        if self.task not in LINKS:
            raise ValueError(f"unknown task {self.task!r}; "
                             f"one of {tuple(LINKS)}")


@dataclasses.dataclass(frozen=True)
class ScoreRequest:
    """One request row in padded-ELL form (values zero-padded to the
    engine's ``ell_width``; padded entries carry index 0, value 0)."""

    rid: int
    values: np.ndarray   # [<=K] float
    indices: np.ndarray  # [<=K] int


@dataclasses.dataclass(frozen=True)
class ScoreResponse:
    rid: int
    score: float           # LR: sigmoid probability; SVM: raw margin
    model_version: int     # the ONE snapshot that scored this request
    latency_s: float       # admission -> response wall time


class GLMScoreEngine:
    """Batched scoring over a trained GLM — see the module docstring.

    Parameters
    ----------
    task, w:
        The served model (``swap_model`` replaces it atomically).
    ell_width:
        Fixed ELL row width K.  Shorter request rows are zero-padded up;
        longer rows are rejected at admission (``ValueError``).
    max_batch:
        Rows per scoring launch; also the padded batch shape.
    queue_depth:
        Bound of the admission FIFO; a full queue rejects (``try_admit``
        returns False) instead of buffering unboundedly.
    flush_deadline_s:
        A non-full batch is flushed once its *oldest* request has waited
        this long (``maybe_flush``); ``flush`` ignores the deadline.
    backend / block_rows:
        Forwarded to the ``glm_score`` dispatch (None = auto backend,
        autotuner-consulted row tile).
    clock:
        Injectable monotonic clock (tests pin deadlines without
        sleeping).
    fault_stall_s:
        Chaos/CI hook: every flush sleeps this long before scoring —
        the deadline-stall fault the monitor-smoke job uses to force a
        latency-SLO breach.  0 (the default) is a plain no-op.

    A :class:`repro.obs.monitor.HealthMonitor` attaches via its
    ``attach_engine(engine)`` (sets ``self.monitor``); each flush then
    reports rows, queue depth, fill, and per-request latencies.  With
    no monitor attached the only cost is one ``None`` check per flush.
    """

    def __init__(self, task: str, w, *, ell_width: int,
                 max_batch: int = 32, queue_depth: int = 256,
                 flush_deadline_s: float = 0.005,
                 backend: str | None = None, block_rows: int | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 fault_stall_s: float = 0.0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1: {max_batch}")
        if queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1: {queue_depth}")
        if ell_width < 1:
            raise ValueError(f"ell_width must be >= 1: {ell_width}")
        self.ell_width = ell_width
        self.max_batch = max_batch
        self.queue_depth = queue_depth
        self.flush_deadline_s = flush_deadline_s
        if fault_stall_s < 0:
            raise ValueError(f"fault_stall_s must be >= 0: {fault_stall_s}")
        self.backend = backend
        self.block_rows = block_rows
        self.fault_stall_s = fault_stall_s
        self.monitor = None
        trace.count_compiles()   # a compile while serving is a stall
        self._clock = clock
        self._lock = threading.Lock()
        #: FIFO of (request, padded values row, padded indices row, t_admit)
        self._queue: deque = deque()
        self._model = ModelSnapshot(
            task, jnp.asarray(w, jnp.float32).reshape(-1), version=0)

    # -- model hot-swap ------------------------------------------------------

    @property
    def model(self) -> ModelSnapshot:
        """The currently published snapshot (atomic reference read)."""
        return self._model

    def swap_model(self, w, *, task: str | None = None,
                   step: int | None = None) -> ModelSnapshot:
        """Atomically publish a new model; returns the new snapshot.

        In-flight batches keep scoring against the snapshot they read at
        dequeue time — a flush is consistent with exactly one version,
        never a mix.  ``step`` stamps the producer's progress (the live
        learner step that trained this model) onto the snapshot.
        """
        with self._lock:
            old = self._model
            w = jnp.asarray(w, jnp.float32).reshape(-1)
            if w.shape != old.w.shape:
                raise ValueError(
                    f"swap_model shape mismatch: serving d={old.w.shape[0]}, "
                    f"got d={w.shape[0]}")
            snap = ModelSnapshot(task if task is not None else old.task,
                                 w, version=old.version + 1, step=step)
            self._model = snap
        metrics.counter("serve.model_swaps").inc()
        if trace.enabled():
            trace.instant("serve.swap", version=snap.version)
        return snap

    # -- admission -----------------------------------------------------------

    def _pad_row(self, req: ScoreRequest) -> tuple[np.ndarray, np.ndarray]:
        vals = np.asarray(req.values, np.float32).reshape(-1)
        idx = np.asarray(req.indices, np.int32).reshape(-1)
        if vals.shape != idx.shape:
            raise ValueError(
                f"request {req.rid}: values/indices length mismatch "
                f"({vals.shape[0]} vs {idx.shape[0]})")
        if vals.shape[0] > self.ell_width:
            raise ValueError(
                f"request {req.rid}: {vals.shape[0]} nonzeros exceed the "
                f"engine ell_width={self.ell_width}")
        pad = self.ell_width - vals.shape[0]
        if pad:
            vals = np.pad(vals, (0, pad))
            idx = np.pad(idx, (0, pad))
        return vals, idx

    def try_admit(self, req: ScoreRequest) -> bool:
        """Enqueue one request; False when the bounded FIFO is full.

        Malformed rows (width over ``ell_width``, ragged values/indices)
        raise — they could never score — while backpressure is a clean
        False so producers can retry/shed.
        """
        row = self._pad_row(req)
        with self._lock:
            full = len(self._queue) >= self.queue_depth
            if not full:
                self._queue.append((req, *row, self._clock()))
        if full:
            metrics.counter("serve.rejected").inc()
            if self.monitor is not None:
                self.monitor.on_reject()
            return False
        metrics.counter("serve.admitted").inc()
        return True

    def submit(self, req: ScoreRequest, *, spin_s: float = 1e-4) -> None:
        """Blocking admit: spins (releasing the lock) until space frees.

        Only sensible when some other thread drains via ``flush``.
        """
        while not self.try_admit(req):
            time.sleep(spin_s)

    def __len__(self) -> int:
        return len(self._queue)

    # -- scoring -------------------------------------------------------------

    def _dequeue(self, limit: int) -> tuple[list, float]:
        """Up to ``limit`` queued entries, FIFO, and the clock at dequeue."""
        with self._lock:
            n = min(limit, len(self._queue))
            return [self._queue.popleft() for _ in range(n)], self._clock()

    def flush(self) -> list[ScoreResponse]:
        """Score up to ``max_batch`` queued requests (FIFO) now.

        The batch is padded to exactly ``max_batch`` all-zero rows so the
        jitted launch sees one stable shape; filler scores are dropped.
        Returns one response per dequeued request, in admission order,
        all stamped with the single snapshot that scored them.  Counter
        ``serve.queue_wait_us`` grows by the batch's summed wait from
        admission to dequeue, on the engine's clock.
        """
        if not self._queue:
            return []
        with trace.span("serve.flush") as fl:
            with trace.span("serve.pack"):
                entries, t_deq = self._dequeue(self.max_batch)
                if not entries:
                    return []                   # another consumer took them
                snap = self._model   # ONE atomic snapshot read per batch
                n = len(entries)
                vals = np.zeros((self.max_batch, self.ell_width), np.float32)
                idx = np.zeros((self.max_batch, self.ell_width), np.int32)
                for i, (_, v, ix, _) in enumerate(entries):
                    vals[i] = v
                    idx[i] = ix
            metrics.counter("serve.queue_wait_us").inc(
                round(sum(t_deq - e[3] for e in entries) * 1e6))
            if trace.enabled():
                fl.set(rows=n, first_rid=entries[0][0].rid,
                       last_rid=entries[-1][0].rid, version=snap.version)
            if self.fault_stall_s:
                time.sleep(self.fault_stall_s)      # injected deadline stall
            with trace.span("serve.put"):
                vals, idx = jnp.asarray(vals), jnp.asarray(idx)
            with trace.span("serve.dispatch"):
                scores = glm_score(snap.task, snap.w, vals, idx,
                                   block_rows=self.block_rows,
                                   backend=self.backend)
            with trace.span("serve.wait"):
                scores = np.asarray(jax.block_until_ready(scores), np.float32)
            with trace.span("serve.respond"):
                t1 = self._clock()
                metrics.counter("serve.scored").inc(n)
                metrics.counter("serve.batches").inc()
                responses = [
                    ScoreResponse(req.rid, float(scores[i]), snap.version,
                                  max(0.0, t1 - t_admit))
                    for i, (req, _, _, t_admit) in enumerate(entries)
                ]
                if self.monitor is not None:
                    self.monitor.on_flush(
                        n=n, padded=self.max_batch,
                        queue_depth=len(self._queue),
                        latencies=[r.latency_s for r in responses])
        return responses

    def maybe_flush(self) -> list[ScoreResponse]:
        """Flush only when a batch is *due*: ``max_batch`` rows waiting,
        or the oldest request older than ``flush_deadline_s``."""
        with self._lock:
            if not self._queue:
                return []
            full = len(self._queue) >= self.max_batch
            overdue = (self._clock() - self._queue[0][3]
                       >= self.flush_deadline_s)
        if not (full or overdue):
            return []
        return self.flush()

    def drain(self, *, max_flushes: int = 10_000) -> list[ScoreResponse]:
        """Flush until the queue is empty; responses in admission order."""
        out: list[ScoreResponse] = []
        for _ in range(max_flushes):
            batch = self.flush()
            if not batch:
                break
            out.extend(batch)
        return out
