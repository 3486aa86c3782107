"""trace_reduce on a profiler trace recorded on a TPU v5e.

``data/glm_path.xplane.pb`` holds, inside one ``bench.window`` span,
three dense ``glm_sgd`` epochs (65,536 x 54) each with its loss, two
sparse ``glm_sgd_sparse`` epochs (8,192 x 114) each with its XLA gather
loss, and one 32-row ``glm_score`` flush; each under a ``bench.*`` span.
"""
from pathlib import Path

import pytest

from chipbench import trace_reduce as T

TRACE = Path(__file__).parent / "data" / "glm_path.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return T.load(str(TRACE))


def _raw_ops():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(TRACE))
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    return [(e.start_ns, e.end_ns, e.name) for e in line.events]
    raise AssertionError("no XLA Ops line")


def test_window_is_the_bench_window_span(trace):
    lo, hi = T.window(trace)
    assert hi - lo == pytest.approx(48_108_595)
    assert T.reduce(trace)["window_s"] == pytest.approx(0.048108595)


def test_busy_is_the_union_of_ops(trace):
    lo, hi = T.window(trace)
    ops = sorted((max(s, lo), min(e, hi)) for s, e, _ in _raw_ops() if e > lo and s < hi)
    busy, end = 0.0, lo
    for s, e in ops:                      # sweep, independent of _union
        if e > end:
            busy += e - max(s, end)
            end = e
    r = T.reduce(trace)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - busy * 1e-9 / r["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]


def test_kernels_are_told_apart_by_program(trace):
    kernels = T.reduce(trace)["kernels"]
    assert set(kernels) == {"jit_epoch/_pallas.1", "jit__pallas/_pallas.1"}
    launches, seconds = kernels["jit_epoch/_pallas.1"]
    assert launches == 5                  # three dense and two sparse epochs
    lo, hi = T.window(trace)
    raw = sum(min(e, hi) - max(s, lo) for s, e, name in _raw_ops()
              if T.KERNEL_TARGET in name and e - s > 100_000)
    assert seconds == pytest.approx(raw * 1e-9)   # clipped to the window
    assert kernels["jit__pallas/_pallas.1"][0] == 1


def test_idle_gaps_are_labelled_by_bench_spans(trace):
    r = T.reduce(trace)
    labels = {name.split(" x")[0] for name, _ in r["idle_gaps"]}
    assert labels <= {"bench.epoch", "bench.loss", "bench.sparse_epoch",
                      "bench.sparse_loss", "bench.flush", "none"}
    total = sum(s for _, s in r["idle_gaps"])
    assert total == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    top = r["device_ops"][0]
    assert top[0] == "jit_loss/fusion"    # the sparse gather loss


def test_spans_on_another_clock_are_aligned_by_the_window(trace):
    lo, hi = T.window(trace)
    shift = 10 ** 12
    spans = [("bench.window", lo - shift, hi - shift),
             ("bench.flush", lo - shift + 5, lo - shift + 7)]
    got = T.aligned(trace, spans)
    assert got[0] == ("bench.window", lo, hi)
    assert got[1] == ("bench.flush", lo + 5, lo + 7)
    r = T.reduce(trace, spans=[("bench.window", lo - shift, hi - shift)])
    assert [name for name, _ in r["idle_gaps"]] == [
        r["idle_gaps"][0][0]] and r["idle_gaps"][0][0].startswith("none")
