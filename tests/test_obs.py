"""Observability: span tracer, metrics registry, export, report CLI.

The acceptance contract of the subsystem (ISSUE 7):
* disabled (the default) the tracer is a shared no-op — no files, no
  jit-lowering drift, bounded overhead on a tight loop;
* enabled, spans nest, round-trip through the JSONL file, and export to
  valid Chrome trace-event JSON the report CLI validates;
* metrics snapshots are deterministic and never enter ``BENCH_*.json``;
* store JSONL events are schema-stamped and validated on read;
* a traced 2-worker sweep leaves per-shard trace files that stitch into
  one timeline with the driver.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sgd
from repro.obs import export, metrics, report, trace
from repro.study import spec, store
from repro.study.runner import Runner
from repro.sweep import LocalProcessExecutor
from repro.utils.timing import median_time, time_stats


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Enable tracing into a temp dir; restore the disabled state after."""
    monkeypatch.setenv(trace.ENV_TRACE, "1")
    monkeypatch.setenv(trace.ENV_TRACE_DIR, str(tmp_path))
    monkeypatch.delenv(trace.ENV_TRACE_TAG, raising=False)
    trace.refresh()
    metrics.reset()
    yield tmp_path
    monkeypatch.delenv(trace.ENV_TRACE, raising=False)
    trace.refresh()
    metrics.reset()


# ---------------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------------


def test_disabled_is_shared_noop_and_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv(trace.ENV_TRACE, raising=False)
    monkeypatch.setenv(trace.ENV_TRACE_DIR, str(tmp_path))
    trace.refresh()
    assert not trace.enabled()
    assert trace.current_path() is None
    assert trace.span("a.b", x=1) is trace.span("c.d")      # the singleton
    with trace.span("runner.trial", key="k"):
        trace.instant("kernel.caps_fallback", chosen="reference")
    assert list(tmp_path.iterdir()) == []                   # no I/O at all


def test_disabled_decorator_returns_function_unchanged():
    def f(x):
        return x + 1

    assert trace.span("study.tune")(f) is f


def test_disabled_overhead_is_bounded():
    t0 = time.perf_counter()
    for _ in range(10_000):
        with trace.span("engine.epoch", epoch=1):
            pass
    assert time.perf_counter() - t0 < 0.5       # generous absolute bound


def test_spans_do_not_change_jit_lowering(traced):
    """Spans are host-side: a jitted body lowers identically whether the
    call sites are instrumented or not, traced or not."""

    def plain(x):
        return jnp.tanh(x) * 2.0

    def instrumented(x):
        with trace.span("serve.dispatch", kernel="t"):
            return jnp.tanh(x) * 2.0

    # the module name embeds fn.__name__; align it so the only possible
    # diff is real lowering drift
    instrumented.__name__ = "plain"

    x = jnp.ones((8, 8))
    lowered_plain = jax.jit(plain).lower(x).as_text()
    assert jax.jit(instrumented).lower(x).as_text() == lowered_plain
    trace.flush()
    ft_before = export.read_trace(trace.current_path())
    # making x compiled a broadcast: tracing on records it as jax.compile
    assert [s["name"] for s in ft_before.spans
            if s["name"] != trace.COMPILE_SPAN] == ["serve.dispatch"]


# ---------------------------------------------------------------------------
# enabled: round-trip, nesting, export
# ---------------------------------------------------------------------------


def test_span_roundtrip_nesting_and_chrome_export(traced):
    with trace.span("runner.trial", key="k1", label="t"):
        with trace.span("engine.epoch", epoch=1):
            time.sleep(0.002)
        with trace.span("engine.epoch", epoch=2):
            pass
    trace.instant("kernel.caps_fallback", chosen="reference")

    @trace.span("study.tune", bases=1)
    def tuned():
        return 41 + 1

    assert tuned() == 42

    trace.flush()
    ft = export.read_trace(trace.current_path())
    assert ft.tag == trace.DEFAULT_TAG
    names = [s["name"] for s in ft.spans]
    # spans are written at *exit*: children precede their parent
    assert names == ["engine.epoch", "engine.epoch", "runner.trial",
                     "study.tune"]
    by_name = {s["name"]: s for s in ft.spans}
    assert by_name["runner.trial"]["depth"] == 0
    assert by_name["engine.epoch"]["depth"] == 1
    assert by_name["runner.trial"]["args"]["key"] == "k1"
    assert [i["name"] for i in ft.instants] == ["kernel.caps_fallback"]

    doc = export.to_chrome([ft])
    assert export.validate_chrome(doc) == []
    assert export.layers([ft]) == ("engine", "runner", "study")
    agg = export.breakdown([ft])
    assert agg["runner.trial"]["count"] == 1
    assert agg["engine.epoch"]["count"] == 2
    # the parent's self time excludes its children
    assert agg["runner.trial"]["self_s"] <= agg["runner.trial"]["total_s"]
    assert agg["runner.trial"]["total_s"] >= agg["engine.epoch"]["total_s"]


def test_span_records_error_and_schema_gate(traced):
    with pytest.raises(RuntimeError):
        with trace.span("sweep.execute"):
            raise RuntimeError("boom")
    trace.flush()
    ft = export.read_trace(trace.current_path())
    assert ft.spans[0]["args"]["error"] == "RuntimeError"

    # a trace stamped newer than the reader refuses to parse
    newer = traced / "trace-future-1.jsonl"
    newer.write_text(json.dumps({
        "kind": "meta", "schema": trace.TRACE_SCHEMA + 1, "pid": 1,
        "tag": "future", "t0_unix_ns": 0, "t0_perf_ns": 0}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        export.read_trace(newer)


def test_report_cli_check_and_perfetto(traced, capsys):
    with trace.span("runner.trial", key="k"):
        pass
    trace.flush()
    out_json = traced / "merged.json"
    assert report.main([str(traced), "--check"]) == 0
    assert report.main([str(traced), "--perfetto", str(out_json)]) == 0
    doc = json.loads(out_json.read_text())
    assert export.validate_chrome(doc) == []
    assert any(ev.get("ph") == "X" for ev in doc["traceEvents"])
    capsys.readouterr()
    assert report.main([str(traced / "empty-subdir")]) == 1    # nothing there


def test_report_json_is_machine_readable(traced, capsys):
    """``--json`` (satellite): the tables as data — what CI smoke jobs
    parse and assert on instead of grepping human output."""
    with trace.span("runner.trial", key="k"):
        with trace.span("engine.epoch", epoch=1):
            pass
        with trace.span("engine.epoch", epoch=2):
            pass
    trace.instant("kernel.caps_fallback", chosen="reference")
    metrics.counter("serve.scored").inc(7)
    trace.flush()
    metrics.write_sidecar()
    capsys.readouterr()
    assert report.main([str(traced), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert {"engine", "runner"} <= set(doc["layers"])
    assert doc["spans"]["engine.epoch"]["count"] == 2
    assert doc["spans"]["runner.trial"]["total_s"] >= \
        doc["spans"]["runner.trial"]["self_s"]
    assert doc["instants"] == {"kernel.caps_fallback": 1}
    assert doc["counters"]["serve.scored"] == 7
    [f] = doc["files"]
    assert f["tag"] == trace.DEFAULT_TAG and f["spans"] == 3
    assert len(doc["metrics_files"]) == 1


def test_chrome_events_stitch_skewed_per_file_clock_anchors(tmp_path):
    """Anchor stitching (satellite): two processes whose perf_counter
    epochs are wildly skewed must still land at their *wall-clock*
    relative offsets in the merged timeline — ``unix_ns`` re-anchors
    each file through its own ``(t0_unix_ns, t0_perf_ns)`` pair."""
    def write(name, tag, t0_unix, t0_perf, ts):
        p = tmp_path / name
        p.write_text("\n".join(json.dumps(r, sort_keys=True) for r in (
            {"kind": "meta", "schema": trace.TRACE_SCHEMA, "pid": 1,
             "tag": tag, "t0_unix_ns": t0_unix, "t0_perf_ns": t0_perf},
            {"kind": "span", "name": f"{tag}.work", "ts": ts,
             "dur": 1_000_000, "tid": 0, "depth": 0},
        )) + "\n")
        return p

    # A: perf epoch 0; its span starts 0.5s after its unix anchor (1.0s)
    write("trace-a-1.jsonl", "a", 1_000_000_000, 0, 500_000_000)
    # B: perf epoch 7s ahead; span 0.1s after its unix anchor (2.0s)
    write("trace-b-1.jsonl", "b", 2_000_000_000, 7_000_000_000,
          7_100_000_000)
    traces = export.collect([tmp_path])
    a, b = sorted(traces, key=lambda t: t.tag)
    assert a.unix_ns(500_000_000) == 1_500_000_000
    assert b.unix_ns(7_100_000_000) == 2_100_000_000

    evs = {ev["name"]: ev for ev in export.chrome_events(traces)
           if ev.get("ph") == "X"}
    # merged timeline is zero-based at the earliest event; the 0.6s
    # wall-clock gap survives the 7s perf-anchor skew (ts is in us)
    assert evs["a.work"]["ts"] == pytest.approx(0.0)
    assert evs["b.work"]["ts"] == pytest.approx(600_000.0)
    assert export.validate_chrome(export.to_chrome(traces)) == []


# ---------------------------------------------------------------------------
# in-memory recording, thread CPU time, profiler mirror, export, compiles
# ---------------------------------------------------------------------------


@pytest.fixture
def in_memory(tmp_path, monkeypatch):
    """Tracing off by env, with the trace dir pointed at an empty dir."""
    monkeypatch.delenv(trace.ENV_TRACE, raising=False)
    monkeypatch.setenv(trace.ENV_TRACE_DIR, str(tmp_path))
    trace.refresh()
    yield tmp_path
    trace.disable()
    trace.refresh()


def test_enable_disable_records_in_memory_and_writes_no_file(in_memory):
    assert not trace.enabled()
    trace.enable()
    assert trace.enabled() and trace.current_path() is None
    with trace.span("serve.flush", rows=3) as fl:
        with trace.span("serve.pack"):
            pass
        fl.set(version=7)
        trace.instant("serve.swap", version=8)
    trace.disable()
    assert not trace.enabled()
    assert trace.span("serve.flush") is trace.NOOP

    recs = trace.records()                  # still readable after disable
    assert [(r.name, r.kind, r.depth) for r in recs] == [
        ("serve.pack", "span", 1), ("serve.swap", "instant", 1),
        ("serve.flush", "span", 0)]
    pack, swap, flush = recs
    assert pack.parent == swap.parent == flush.id and flush.parent == 0
    assert flush.attrs == {"rows": 3, "version": 7}
    assert flush.start_ns <= pack.start_ns <= pack.end_ns <= flush.end_ns
    assert len({pack.id, swap.id, flush.id}) == 3
    assert metrics.write_sidecar() is None  # in-memory tracing persists nothing
    assert list(in_memory.iterdir()) == []

    trace.enable()                          # a fresh buffer each time
    assert trace.records() == []


def test_records_carry_thread_cpu_time(in_memory):
    """Outermost spans carry the thread's CPU time; nested ones do not
    (the clock is a system call)."""
    trace.enable()
    with trace.span("live.pass"):
        time.sleep(0.05)                    # off the CPU
    with trace.span("live.step"):
        with trace.span("live.put"):
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.03:   # on it
                pass
    trace.disable()
    slept, nested, busy = trace.records()
    assert nested.cpu_start_ns is None and nested.cpu_end_ns is None
    wall = slept.end_ns - slept.start_ns
    assert wall >= 50_000_000
    assert 0 <= slept.cpu_end_ns - slept.cpu_start_ns < wall / 2
    cpu = busy.cpu_end_ns - busy.cpu_start_ns
    assert 30_000_000 <= cpu <= busy.end_ns - busy.start_ns + 1_000_000


def test_trace_annotation_mirror_keeps_lowering_and_reaches_the_profiler(
        in_memory):
    """Enabled spans enter ``jax.profiler.TraceAnnotation``: a jitted body
    still lowers identically, and a profiler capture holds the spans on
    its host plane."""
    from jax.profiler import ProfileData, TraceAnnotation

    def plain(x):
        return jnp.tanh(x) * 2.0

    def instrumented(x):
        with trace.span("serve.dispatch"):
            return jnp.tanh(x) * 2.0

    instrumented.__name__ = "plain"
    x = jnp.ones((8, 8))
    lowered_plain = jax.jit(plain).lower(x).as_text()
    trace.enable()
    assert trace._TRACER.annotation is TraceAnnotation
    assert jax.jit(instrumented).lower(x).as_text() == lowered_plain

    jax.profiler.start_trace(str(in_memory / "xprof"))
    try:
        with trace.span("serve.flush"):
            with trace.span("serve.wait"):
                pass
    finally:
        jax.profiler.stop_trace()
    trace.disable()
    [path] = (in_memory / "xprof").rglob("*.xplane.pb")
    host = [e.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events]
    assert host.count("serve.flush") == 1 and host.count("serve.wait") == 1


def test_export_at_flush_and_at_exit_in_the_file_format(traced):
    with trace.span("runner.trial", key="k"):
        with trace.span("engine.epoch", epoch=1):
            pass
    assert not trace.current_path().exists()    # buffered until a flush
    assert trace.flush() == trace.current_path()
    ft = export.read_trace(trace.current_path())
    assert [(s["name"], s["depth"]) for s in ft.spans] == [
        ("engine.epoch", 1), ("runner.trial", 0)]
    assert set(ft.spans[1]) == {"kind", "name", "ts", "dur", "pid", "tid",
                                "depth", "args"}
    assert trace.records() == []                # exported records leave
    assert trace.flush() == trace.current_path()
    assert len(export.read_trace(trace.current_path()).spans) == 2

    # a process that never flushes writes its spans at exit
    code = ("from repro.obs import trace\n"
            "with trace.span('sweep.shard', worker=0):\n"
            "    trace.instant('sweep.mark')\n")
    env = {**os.environ, "PYTHONPATH": "src", trace.ENV_TRACE_TAG: "child"}
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   cwd=Path(__file__).resolve().parents[1], timeout=120)
    [child] = traced.glob("trace-child-*.jsonl")
    ft = export.read_trace(child)
    assert ft.tag == "child"
    assert [s["name"] for s in ft.spans] == ["sweep.shard"]
    assert [i["name"] for i in ft.instants] == ["sweep.mark"]
    assert ft.spans[0]["args"] == {"worker": 0}
    assert report.main([str(traced), "--check"]) == 0


def test_jax_compiles_counts_new_shapes_not_repeats(in_memory):
    trace.count_compiles()
    count = metrics.counter(trace.COMPILE_COUNTER)
    triple = jax.jit(lambda x: x * 3.0 + 1.0)
    c0 = count.value
    triple(np.ones(3, np.float32))
    assert count.value == c0 + 1                # a new shape compiles
    triple(np.ones(3, np.float32))
    assert count.value == c0 + 1                # a repeat does not
    trace.enable()
    with trace.span("serve.dispatch"):
        triple(np.ones(5, np.float32))
    trace.disable()
    assert count.value == c0 + 2
    compile_, dispatch = trace.records()
    assert compile_.name == trace.COMPILE_SPAN
    assert "lambda" in compile_.attrs["fun_name"]
    assert compile_.parent == dispatch.id and compile_.depth == 1
    assert dispatch.start_ns <= compile_.end_ns <= dispatch.end_ns


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_snapshot_is_deterministic_and_typed():
    metrics.reset()
    metrics.counter("b.hits").inc()
    metrics.counter("a.miss").inc(2)
    metrics.gauge("q.depth").set(3)
    h = metrics.histogram("lat")
    h.observe(5e-6)
    h.observe(2.0)
    snap = metrics.snapshot()
    assert snap["schema"] == metrics.METRICS_SCHEMA
    assert list(snap["counters"]) == ["a.miss", "b.hits"]    # sorted
    assert snap["counters"]["a.miss"] == 2
    assert snap["gauges"]["q.depth"] == 3.0
    hist = snap["histograms"]["lat"]
    assert hist["count"] == 2 and hist["min"] == 5e-6 and hist["max"] == 2.0
    assert len(hist["counts"]) == len(hist["edges"]) + 1
    assert snap == metrics.snapshot()                        # stable

    with pytest.raises(TypeError, match="already registered"):
        metrics.gauge("a.miss")
    with pytest.raises(ValueError, match="edges"):
        metrics.histogram("lat", edges=(1.0, 2.0))
    metrics.reset()


def test_metrics_sidecar_piggybacks_on_tracing(tmp_path, monkeypatch):
    monkeypatch.delenv(trace.ENV_TRACE, raising=False)
    trace.refresh()
    metrics.reset()
    metrics.counter("x").inc()
    assert metrics.write_sidecar() is None      # disabled: no default path

    monkeypatch.setenv(trace.ENV_TRACE, "1")
    monkeypatch.setenv(trace.ENV_TRACE_DIR, str(tmp_path))
    trace.refresh()
    p = metrics.write_sidecar()
    assert p is not None and p.parent == tmp_path
    assert json.loads(p.read_text())["counters"]["x"] == 1
    monkeypatch.delenv(trace.ENV_TRACE, raising=False)
    trace.refresh()
    metrics.reset()


# ---------------------------------------------------------------------------
# store event schema (satellite)
# ---------------------------------------------------------------------------


def test_store_events_are_schema_stamped_and_validated(tmp_path):
    st = store.StudyStore(tmp_path / "out.json",
                          jsonl_path=tmp_path / "runs.jsonl")
    st.record_event("sweep_shard", worker=0, returncode=0)
    st.write()
    events = store.load_events(tmp_path / "runs.jsonl")
    assert [e["event"] for e in events] == ["sweep_shard"]
    assert events[0]["schema"] == store.EVENT_SCHEMA
    assert store.load_events(tmp_path / "runs.jsonl",
                             kinds=("sweep_merge",)) == []

    # legacy (pre-stamp) lines load; newer-than-reader lines refuse
    with open(tmp_path / "runs.jsonl", "a") as f:
        f.write(json.dumps({"event": "legacy_kind"}) + "\n")
    assert [e["event"] for e in store.load_events(tmp_path / "runs.jsonl")] \
        == ["sweep_shard", "legacy_kind"]
    with open(tmp_path / "runs.jsonl", "a") as f:
        f.write(json.dumps({"event": "future",
                            "schema": store.EVENT_SCHEMA + 1}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        store.load_events(tmp_path / "runs.jsonl")


def test_kernel_bench_store_records_events(tmp_path):
    st = store.KernelBenchStore(tmp_path / "k.json",
                                jsonl_path=tmp_path / "k.jsonl")
    st.record_event("timing_stats", label="x", median=1e-3, std=1e-5)
    st.record_entry("x", {"wall_s": 1e-3})
    st.write()
    # dispersion lands in the sidecar, never the deterministic snapshot
    assert "timing_stats" not in (tmp_path / "k.json").read_text()
    [ev] = store.load_events(tmp_path / "k.jsonl")
    assert ev["event"] == "timing_stats" and ev["std"] == 1e-5


# ---------------------------------------------------------------------------
# timing dispersion (satellite)
# ---------------------------------------------------------------------------


def test_time_stats_shape_and_median_consistency():
    stats = time_stats(lambda: sum(range(50)), warmup=1, iters=5)
    assert set(stats) == {"median", "min", "mean", "std", "iters"}
    assert stats["iters"] == 5
    assert stats["min"] <= stats["median"] <= stats["min"] + stats["std"] * 5 \
        or stats["median"] >= stats["min"]
    assert stats["min"] <= stats["mean"]
    assert median_time(lambda: 1, warmup=0, iters=3) >= 0.0


# ---------------------------------------------------------------------------
# traced 2-worker sweep stitches into one timeline
# ---------------------------------------------------------------------------


def test_traced_two_worker_sweep_produces_stitchable_timeline(traced,
                                                              tmp_path):
    trials = list(spec.grid(
        [spec.DatasetSpec(d, max_n=96) for d in ("covtype", "w8a")],
        ["lr"], [sgd.SyncSGD()], steps=(1e-2, 1e-1), epochs=2))
    ex = LocalProcessExecutor(workers=2, work_dir=tmp_path / "work")
    st = store.StudyStore(tmp_path / "out.json",
                          jsonl_path=tmp_path / "runs.jsonl")
    Runner(cache_dir=tmp_path / "cache", store=st, executor=ex).run(trials)
    st.write()

    trace.flush()
    traces = export.collect([traced])
    tags = {t.tag for t in traces}
    assert trace.DEFAULT_TAG in tags                   # the driver
    assert {"shard0a0", "shard1a0"} <= tags            # one file per worker
    # the merged view spans driver + worker layers
    layer_set = set(export.layers(traces))
    assert {"sweep", "runner", "engine"} <= layer_set
    doc = export.to_chrome(traces)
    assert export.validate_chrome(doc) == []
    assert report.main([str(traced), "--check"]) == 0

    # provenance events carry each attempt's trace file path
    shard_events = store.load_events(tmp_path / "runs.jsonl",
                                     kinds=("sweep_shard",))
    assert {e["worker"] for e in shard_events} == {0, 1}
    for e in shard_events:
        assert e["trace_file"] and "shard" in e["trace_file"]
