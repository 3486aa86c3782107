"""Open-loop scoring against a fixed model drawn from the seed.

``GLMScoreEngine`` at the engine parameters the mix states, fed by
:mod:`chipbench.kinds.openloop` at the mix's fixed rate.  The training
engine is not touched.

Correct: every answer's score against the float64
``sigmoid(sum(values * w[indices]))`` of the model it names, and every
admitted request answered exactly once.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import data, harness, reference
from chipbench.kinds import openloop


def setup(ctx) -> dict:
    from repro.serve.glm import GLMScoreEngine

    cfg, tr = ctx.config, ctx.traffic
    with ctx.spans.span("bench.data"):
        rows = data.make(cfg, ctx.seed)
        w = data.model(ctx.seed, cfg["d"])
        due, pick = openloop.schedule(ctx.seed, tr["rate_per_s"],
                                      ctx.seconds, rows.n)
        reqs = openloop.requests(rows, pick)
    engine = GLMScoreEngine(cfg["task"], w, ell_width=cfg["max_nnz"],
                            **tr["engine"])
    openloop.warm(engine, rows)
    loop = openloop.OpenLoop(engine, reqs, due, ctx.spans,
                             drain_s=tr["drain_s"])
    return {"rows": rows, "w": w, "pick": pick, "loop": loop}


def window(ctx, state) -> dict:
    loop = state["loop"]
    t0 = time.perf_counter_ns()
    for t in loop.start(t0):
        t.join()
    s = loop.summary()
    lat = s["latency_ms"]
    return {"attempted": s["requests"], "failed": s["failed"],
            "window_s": ctx.seconds, "openloop": s,
            "metrics": {"score_p50_ms": harness.percentile(lat, 50),
                        "score_p90_ms": harness.percentile(lat, 90)}}


def served(state):
    """Rows, versions and scores of the answered requests."""
    loop, rows = state["loop"], state["rows"]
    ok = loop.answers > 0
    pick = state["pick"][ok]
    return (rows.values[pick], rows.indices[pick], loop.version[ok],
            loop.score[ok])


def check(ctx, state, window_out) -> dict:
    values, indices, versions, got = served(state)
    unanswered = state["loop"].unanswered()
    w = state["w"]
    state.clear()
    with reference.float64():
        want = reference.scores(values, indices, w[None, :].astype(np.float64),
                                versions, mode="f64")
    return {"score_gap": float(np.max(np.abs(got - want), initial=0.0)),
            "unanswered": float(unanswered)}
