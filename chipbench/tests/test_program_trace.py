"""The program's spans against the device trace, and the readers of the
program's counters, on planted inputs and on the recorded trace."""
from pathlib import Path

import pytest

from chipbench import harness
from chipbench import program_trace as P
from chipbench import trace_reduce as T
from conftest import TINY_SECONDS, tiny_cell
from repro.obs.trace import Record

TRACE = Path(__file__).parent / "data" / "glm_path.xplane.pb"
SHIFT = 5000          # the benchmark's clock against the trace's


def rec(name, a, b, tid, depth, id_, parent=0):
    return Record(name, a + SHIFT, b + SHIFT, None, None, tid, depth, id_,
                  parent, {})


def planted():
    """A 1000 ns window, the device busy in [100, 200] and [600, 700]."""
    ops = [T.Op("jit_glm_score/glm_score.1", "jit_glm_score", s, e, True)
           for s, e in [(100, 200), (600, 700)]]
    tr = T.Trace({"/device:TPU:0": ops}, [("bench.window", 0, 1000)])
    bench = [("bench.window", SHIFT, SHIFT + 1000),
             ("bench.flush", SHIFT, SHIFT + 1000)]
    records = [
        rec("serve.wait", 300, 500, tid=1, depth=1, id_=2, parent=1),
        rec("serve.flush", 200, 500, tid=1, depth=0, id_=1),
        rec("live.pass", 400, 450, tid=2, depth=1, id_=4, parent=3),
        rec("live.step", 400, 800, tid=2, depth=0, id_=3),
    ]
    return tr, bench, records


def test_idle_time_goes_to_innermost_spans_split_between_threads():
    tr, bench, records = planted()
    got = P.idle_by_span(tr, bench, records)
    assert {k: round(v * 1e9, 6) for k, v in got["idle_gaps"]} == {
        "bench.flush x2": 300.0,      # [0, 100] and [800, 1000]: no span open
        "live.step x2": 225.0,        # alone in [500, 600] and [700, 800]
        "serve.wait x1": 150.0,       # alone in [300, 400], shared after
        "serve.flush x1": 100.0,      # [200, 300]: its child not yet open
        "live.pass x1": 25.0,         # shared with serve.wait in [400, 450]
    }
    assert got["program_share"] == pytest.approx(500 / 800)


def test_innermost_pieces_follow_nesting_per_thread():
    _, _, records = planted()
    assert P.innermost(records) == [
        (5200, 5300, ("serve.flush",)),
        (5300, 5400, ("serve.wait",)),
        (5400, 5450, ("live.pass", "serve.wait")),
        (5450, 5500, ("live.step", "serve.wait")),
        (5500, 5800, ("live.step",)),
    ]


def test_without_program_spans_idle_keeps_the_reduce_labels():
    tr = T.load(str(TRACE))
    got = P.idle_by_span(tr, tr.spans, [])
    assert got["idle_gaps"] == T.reduce(tr)["idle_gaps"]
    assert got["program_share"] == 0.0


def test_span_metrics_on_planted_records():
    _, _, records = planted()
    records = [r._replace(cpu_start_ns=0, cpu_end_ns=100_000)
               if r.name == "live.step" else r for r in records]
    m = P.span_metrics(records)
    assert m["score.flush_ms"] == pytest.approx(300e-6)
    assert m["score.host_ms"] == pytest.approx(100e-6)     # flush - its wait
    assert m["score.device_wait_ms"] == pytest.approx(200e-6)
    assert m["live.step_offcpu_ms"] == pytest.approx((400 - 100_000) / 1e6)
    assert m["live.put_ms"] is None
    assert P.span_metrics([])["score.host_ms"] is None


def _run(counters):
    cell = tiny_cell("w8a.score")
    return harness.Run(cell, harness.Spans(), counters, {}, None, "cpu", None)


def test_counter_readers_on_planted_runs():
    wait = harness._reader("score.queue_wait_ms")
    assert wait(_run({"serve.scored": 64, "serve.queue_wait_us": 128_000})) \
        == pytest.approx(2.0)
    assert wait(_run({"serve.scored": 64})) is None      # no such counter
    assert wait(_run({})) is None

    from repro.obs import trace

    compiles = harness._reader("jit.compiles")
    trace.count_compiles()                  # the program turns it on at 0
    assert compiles(_run({})) == 0.0
    assert compiles(_run({"jax.compiles": 2})) == 2.0


def test_tool_runs_the_serving_window_off_the_chip():
    import jax

    cell = tiny_cell("w8a.score")
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    out = P.run(cell, 2 ** 33 + 5, TINY_SECONDS, profile=False,
                kernel="pallas-interpret")
    assert out["failed"] == 0 and out["records"] > 0
    m = out["program"]
    for name in ("score.queue_wait_ms", "score.flush_ms", "score.host_ms",
                 "score.device_wait_ms"):
        assert m[name] > 0, name
    assert m["score.host_ms"] < m["score.flush_ms"]
    assert m["live.step_ms"] is None and m["jit.compiles"] == 0
