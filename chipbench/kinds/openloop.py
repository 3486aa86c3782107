"""Open-loop scoring traffic, shared by the ``score`` and ``live`` kinds.

Arrivals are Poisson at a fixed rate: the gaps are exponential, drawn
from the seed, and every request is a row of the configuration's data
drawn from the seed, at its own width (admission pads it).  Requests are
built in set-up.  In the window one producer thread admits each request
at its due time (``try_admit``; a full queue rejects it) and one flusher
thread loops ``maybe_flush``.  A request's latency runs from when it was
due to when the flush that scored it returned, so a stall counts against
every request that waited behind it.  After the window the flusher
drains what was admitted, for at most ``drain_s``.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import data


def schedule(seed: int, rate: float, seconds: float, n_rows: int):
    """Due times (s from the window's start) and the row of each request."""
    rng = np.random.default_rng([seed, 2])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
    due = np.cumsum(gaps)
    if due[-1] < seconds:
        raise ValueError("the arrival draw ends before the window does")
    due = due[due < seconds]
    return due, rng.integers(0, n_rows, len(due))


def requests(rows: data.Rows, pick: np.ndarray, first_rid: int = 0) -> list:
    from repro.serve.glm import ScoreRequest

    return [ScoreRequest(first_rid + i, rows.values[r, :rows.nnz[r]],
                         rows.indices[r, :rows.nnz[r]])
            for i, r in enumerate(pick)]


def warm(engine, rows: data.Rows) -> None:
    """Score a few full batches: compiles the one batch shape there is."""
    reqs = requests(rows, np.arange(engine.max_batch), -engine.max_batch)
    for _ in range(3):
        for r in reqs:
            engine.try_admit(r)
        engine.drain()


class OpenLoop:
    """One window of open-loop traffic against one engine."""

    def __init__(self, engine, reqs: list, due: np.ndarray, spans, *,
                 poll_s: float = 1e-4, drain_s: float = 60.0):
        n = len(reqs)
        self.engine, self.reqs, self.due, self.spans = engine, reqs, due, spans
        self.poll_s, self.drain_s = poll_s, drain_s
        self.latency = np.full(n, np.nan)
        self.score = np.full(n, np.nan)
        self.version = np.full(n, -1, np.int64)
        self.answers = np.zeros(n, np.int64)
        self.rejected = np.zeros(n, bool)
        self.late = np.zeros(n)
        self._produced = threading.Event()

    def _produce(self, t0: int) -> None:
        try:
            for i, req in enumerate(self.reqs):
                target = t0 + int(self.due[i] * 1e9)
                wait = target - time.perf_counter_ns()
                if wait > 0:
                    time.sleep(wait * 1e-9)
                self.late[i] = (time.perf_counter_ns() - target) * 1e-9
                self.rejected[i] = not self.engine.try_admit(req)
        finally:
            self._produced.set()

    def _flush(self, t0: int) -> None:
        stop = None
        while True:
            ta = time.perf_counter_ns()
            out = self.engine.maybe_flush()
            if out:
                tb = time.perf_counter_ns()
                self.spans.add("bench.flush", ta, tb)
                since = (tb - t0) * 1e-9
                for r in out:
                    self.answers[r.rid] += 1
                    self.latency[r.rid] = since - self.due[r.rid]
                    self.score[r.rid] = r.score
                    self.version[r.rid] = r.model_version
                continue
            if self._produced.is_set():
                if not len(self.engine):
                    return
                stop = stop or ta + int(self.drain_s * 1e9)
                if ta > stop:
                    return
            time.sleep(self.poll_s)

    def start(self, t0: int) -> list[threading.Thread]:
        threads = [threading.Thread(target=self._produce, args=(t0,)),
                   threading.Thread(target=self._flush, args=(t0,))]
        for t in threads:
            t.start()
        return threads

    def summary(self) -> dict:
        answered = self.answers > 0
        lat_ms = self.latency[answered] * 1e3
        failed = int(np.sum(self.rejected | ~answered))
        return {"requests": len(self.reqs), "answered": int(answered.sum()),
                "rejected": int(self.rejected.sum()), "failed": failed,
                "latency_ms": lat_ms, "late_s": self.late}

    def unanswered(self) -> int:
        """Admitted requests never answered, plus answers given twice."""
        admitted = ~self.rejected
        return int(np.sum(admitted & (self.answers == 0))
                   + np.sum(np.maximum(self.answers - 1, 0)))
