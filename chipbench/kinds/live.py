"""Train while serving: a live learner publishes into the scoring engine.

One process, three threads.  The learner thread steps a ``LiveLearner``
over a backlog stream: the configuration's rows cut into chunks of
``chunk_rows``, made in set-up and taken in order, round and round.  A
``SnapshotPublisher`` swaps every merged model into the
``GLMScoreEngine``, which :mod:`chipbench.kinds.openloop` loads with
Poisson requests at the mix's fixed rate.  Set-up takes the learner
through its first merge and publish, which compiles every program the
window runs.

Correct: the merged model after the window against a float64 replay of
the same chunks; published versions that rise by one per merge; and
every answer's score against the float64 score of the replay's model of
the version the answer names.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import data, harness, reference
from chipbench.kinds import openloop


class Backlog:
    """A stream over chunks made in advance (the learner's ``stream``)."""

    dense = False

    def __init__(self, rows: data.Rows, chunk_rows: int):
        from repro.live.stream import StreamBatch

        self.n_batch, self.d = chunk_rows, rows.d
        self.chunks = [
            StreamBatch(i, rows.values[s:s + chunk_rows],
                        rows.indices[s:s + chunk_rows], rows.y[s:s + chunk_rows])
            for i, s in enumerate(range(0, rows.n - chunk_rows + 1,
                                        chunk_rows))]

    def __iter__(self):
        i = 0
        while True:
            yield self.chunks[i % len(self.chunks)]
            i += 1


def _engine(cfg, tr):
    from repro.serve.glm import GLMScoreEngine

    return GLMScoreEngine(cfg["task"], np.zeros(cfg["d"], np.float32),
                          ell_width=cfg["max_nnz"], **tr["engine"])


def setup(ctx) -> dict:
    from repro.live import LiveConfig, LiveLearner, SnapshotPublisher

    cfg, tr = ctx.config, ctx.traffic
    with ctx.spans.span("bench.data"):
        rows = data.make(cfg, ctx.seed)
        stream = Backlog(rows, tr["chunk_rows"])
        due, pick = openloop.schedule(ctx.seed, tr["rate_per_s"],
                                      ctx.seconds, rows.n)
        reqs = openloop.requests(rows, pick)
    engine = _engine(cfg, tr)
    openloop.warm(engine, rows)
    lc = LiveConfig(task=cfg["task"], kernel_backend=ctx.kernel,
                    **tr["learner"])
    learner = LiveLearner(lc, stream)
    publisher = SnapshotPublisher(engine, every_merges=tr["publish_every"]) \
        .attach(learner)
    for _ in range(lc.merge_every):
        learner.step()
    loop = openloop.OpenLoop(engine, reqs, due, ctx.spans,
                             drain_s=tr["drain_s"])
    return {"rows": rows, "stream": stream, "pick": pick, "loop": loop,
            "config": lc, "learner": learner, "publisher": publisher}


def window(ctx, state) -> dict:
    learner, loop = state["learner"], state["loop"]
    steps0 = learner.steps
    end = {}

    def learn(t0):
        while time.perf_counter_ns() - t0 < ctx.seconds * 1e9:
            ta = time.perf_counter_ns()
            learner.step()
            end["t"] = time.perf_counter_ns()
            ctx.spans.add("bench.learner_step", ta, end["t"])

    t0 = time.perf_counter_ns()
    threads = loop.start(t0) + [threading.Thread(target=learn, args=(t0,))]
    threads[-1].start()
    for t in threads:
        t.join()
    steps = learner.steps - steps0
    elapsed = (end["t"] - t0) * 1e-9
    s = loop.summary()
    lat = s["latency_ms"]
    return {"attempted": s["requests"], "failed": s["failed"],
            "window_s": elapsed, "steps": steps, "openloop": s,
            "metrics": {
                "score_p50_ms": harness.percentile(lat, 50),
                "score_p90_ms": harness.percentile(lat, 90),
                "live_rows_per_s": steps * ctx.traffic["chunk_rows"] / elapsed}}


def version_faults(history: list[dict], merges: int) -> int:
    """Publishes whose version is not their merge count, plus merges that
    published nothing."""
    bad = sum(h["version"] != h["merge"] for h in history)
    return bad + abs(merges - len(history))


def replay(lc, steps: int, stream: Backlog, rows: data.Rows, mode: str):
    """The merged models of the reference over the chunks the learner took
    in its ``steps`` steps."""
    s, c = len(stream.chunks), stream.n_batch
    X = rows.cut(s * c).to_dense().reshape(s, c, rows.d)
    y = rows.y[:s * c].reshape(s, c)
    return reference.live(X, y, np.arange(steps) % s,
                          replicas=lc.replicas, local_batch=lc.local_batch,
                          merge_every=lc.merge_every, step=lc.step_size,
                          mode=mode)


def check(ctx, state, window_out) -> dict:
    learner, loop = state["learner"], state["loop"]
    merged = np.asarray(learner.merged_model, np.float64)
    faults = version_faults(state["publisher"].history, learner.merges)
    unanswered = loop.unanswered()
    ok = loop.answers > 0
    pick = state["pick"][ok]
    rows = state["rows"]
    values, indices = rows.values[pick], rows.indices[pick]
    versions, got = loop.version[ok], loop.score[ok]
    stream, lc = state["stream"], state["config"]
    state.clear()
    with reference.float64():
        anchors = replay(lc, learner.steps, stream, rows, "f64")
        valid = (versions >= 0) & (versions < len(anchors))
        want = reference.scores(values[valid], indices[valid], anchors,
                                versions[valid], mode="f64")
    ref = anchors[-1]
    return {"model_gap": float(np.linalg.norm(merged - ref)
                               / np.linalg.norm(ref)),
            "score_gap": float(np.max(np.abs(got[valid] - want), initial=0.0)),
            "version_faults": float(faults + np.sum(~valid)
                                    + abs(len(anchors) - 1 - learner.merges)),
            "unanswered": float(unanswered)}
