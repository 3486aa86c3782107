"""The benchmark's driver: one cell, one seed, one measured window.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name:

* ``BENCHMARK.json``            the cells and metrics;
* ``configs/<config>.json``     a configuration's sizes (its ``file``);
* ``traffic/<mix>.json``        a traffic mix: its ``kind`` names the
  driver in ``kinds/`` and the rest are that driver's parameters;
* ``limits/<cell>.json``        the limit of each number ``correct``
  compares, with the readings it was set from;
* ``metrics/<metric>.py``       a per-layer metric's reader:
  ``read(run) -> float | None``.

A run: set-up (data from the seed, the program built and warmed, and for
training its first steps), the window of ``seconds``, the device's peak
memory, then the comparison with the plain reference, which runs after
the program's state is freed and is not counted in ``setup_s``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration's file
    traffic: dict          # the traffic mix's file
    limits: dict           # number -> {"limit": ..., ...}
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path | None = None) -> Cell:
    bench = json.loads((bench_path or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((PKG / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((PKG / "limits" / f"{name}.json").read_text())
    return Cell(name, w["chips"], config, traffic, limits["numbers"],
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


class Spans:
    """The benchmark's own spans around calls into each layer.

    Kept in memory on the host clock (``perf_counter_ns``).  The window
    span is also written into the profiler's trace, which ties the two
    clocks together.  Appending to a list is atomic, so any thread may
    record.
    """

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.records: list[tuple[str, int, int]] = []

    def add(self, name: str, t0: int, t1: int) -> None:
        self.records.append((name, t0, t1))

    def span(self, name: str):
        return _Span(self, name)

    def of(self, name: str) -> list[tuple[int, int]]:
        return [(a, b) for n, a, b in self.records if n == name]

    def in_window(self, name: str) -> list[tuple[int, int]]:
        """The spans ``name`` that started inside the window span."""
        (w0, w1), = self.of(WINDOW_SPAN)
        return [(a, b) for a, b in self.of(name) if w0 <= a <= w1]

    def mean_ms(self, name: str) -> float | None:
        """Mean length of the window's spans ``name``, in ms."""
        got = self.in_window(name)
        if not got:
            return None
        return sum(b - a for a, b in got) / len(got) / 1e6


class _Span:
    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name
        self.annotation = None

    def __enter__(self):
        if self.spans.traced and self.name == WINDOW_SPAN:
            import jax

            self.annotation = jax.profiler.TraceAnnotation(self.name)
            self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.spans.add(self.name, self.t0, time.perf_counter_ns())
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False


@dataclasses.dataclass
class Context:
    """What a traffic driver is given."""

    cell: Cell
    seed: int
    seconds: float
    kernel: str            # the kernel backend the cell asks for
    spans: Spans

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


class StallMonitor:
    """A thread that sleeps 1 ms at a time and keeps every wake-up that
    came more than ``floor_ms`` late: ``(start_ns, overshoot_ns)``.  Runs
    in traced runs only, beside the window."""

    def __init__(self, floor_ms: float = 20.0):
        self.floor_ns = int(floor_ms * 1e6)
        self.stalls: list[tuple[int, int]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self) -> None:
        while not self._stop.is_set():
            t0 = time.perf_counter_ns()
            time.sleep(0.001)
            over = time.perf_counter_ns() - t0 - 1_000_000
            if over > self.floor_ns:
                self.stalls.append((t0, over))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        return False


def settle() -> None:
    """End set-up: the requests, rows and chunks it made stay alive all
    window, so keep the garbage collector from walking them again and
    again (a full pass over them stalls the host for about 100 ms, which
    a server's own heap would not)."""
    gc.collect()
    gc.freeze()


def percentile(values, q: float) -> float:
    """Exact ``q``-th percentile of every sample, linearly interpolated."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def counters() -> dict:
    from repro.obs import metrics

    return dict(metrics.snapshot()["counters"])


def kernel_guard(before: dict, after: dict, kernel: str) -> list[str]:
    """Counters that show a kernel family left the backend asked for.

    Every backend resolution moves ``kernel.backend.<family>.<backend>``,
    every routing around a better backend ``kernel.fallback.*``.  Off the
    chip, ``pallas-interpret`` is asked for and the routing around the
    absent ``pallas-tpu`` (reason ``host``) is expected.
    """
    bad = []
    for k, v in after.items():
        if v == before.get(k, 0):
            continue
        if k.startswith("kernel.fallback."):
            if not (kernel == "pallas-interpret" and k.endswith(".host")):
                bad.append(k)
        elif k.startswith("kernel.backend.") and not k.endswith("." + kernel):
            bad.append(k)
    return sorted(bad)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number against its limit; a number that is not finite fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]["limit"]
        passed = math.isfinite(value) and value <= limit
        ok &= passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def _reader(metric: str):
    path = PKG / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a per-layer reader is given."""

    cell: Cell
    spans: Spans
    counters: dict          # counters the window moved
    window: dict            # what the driver measured in the window
    trace: dict | None      # trace_reduce.reduce() of the traced window
    device_kind: str
    stalls: list | None     # StallMonitor.stalls over the traced window


def _start_trace():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(tdir, profiler_options=opts)
    return tdir


def _reduce_trace(tdir: str, spans: Spans) -> dict:
    from chipbench import trace_reduce

    try:
        path = next(Path(tdir).rglob("*.xplane.pb"))
        tr = trace_reduce.load(str(path))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return trace_reduce.reduce(tr, spans=spans.records)


def run(cell: Cell, seed: int, seconds: float, *, traced: bool,
        kernel: str, t_start: float, driver=None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``driver`` replaces the traffic kind's module (tests plant faults
    there); ``t_start`` is the host clock at process start.
    """
    driver = driver or importlib.import_module(f"chipbench.kinds.{cell.kind}")
    spans = Spans(traced)
    ctx = Context(cell, seed, seconds, kernel, spans)
    before = counters()
    state = driver.setup(ctx)
    settle()
    setup_s = time.perf_counter() - t_start
    at_window = counters()
    tdir = _start_trace() if traced else None
    monitor = StallMonitor() if traced else contextlib.nullcontext()
    try:
        with monitor, spans.span(WINDOW_SPAN):
            window = driver.window(ctx, state)
    finally:
        if tdir is not None:
            import jax

            jax.profiler.stop_trace()
    after = counters()
    moved = {k: v - at_window.get(k, 0) for k, v in after.items()
             if v != at_window.get(k, 0)}
    bad = kernel_guard(before, after, kernel)
    device = device_info(cell.chips)
    numbers = driver.check(ctx, state, window)
    ok, checks = judge(numbers, cell.limits)
    if bad:
        ok = False
        checks["kernel_fallbacks"] = {"value": len(bad), "limit": 0,
                                      "counters": bad}
    result = {"correct": ok, "attempted": window["attempted"],
              "failed": window["failed"]}
    if traced:
        reduced = _reduce_trace(tdir, spans)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        run_ = Run(cell, spans, moved, window, reduced, device["kind"],
                   monitor.stalls)
        metrics = {}
        for m in cell.per_layer:
            value = _reader(m["name"])(run_)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    return result


def compile_cache(root: Path = ROOT) -> Path:
    """Point JAX's persistent compile cache at a fixed directory in the
    checkout that only the benchmark writes, made if missing.  Call
    before JAX is imported.

    Eviction stays off: with a size limit JAX keeps an access-time file
    beside each entry, and one entry without it (a run killed between
    the two writes, or entries copied in from elsewhere) makes every
    later write fail, so that every run compiles again."""
    import os

    path = root / ".chipbench_cache" / "jax"
    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return path


def print_result(result: dict) -> None:
    """Each compared number beside its limit, last on standard error; the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
