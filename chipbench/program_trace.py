"""A cell's window with the program's own spans on the device trace's clock.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> --seconds <s> [--profile 0|1]

Runs the cell's set-up and window as ``run.py`` does, with the program's
tracer (``repro.obs.trace.enable()``) recording in memory for the
window, and prints one JSON line:

* ``end_to_end``: the window's end-to-end numbers with the tracer on, to
  set against a ``run.py --trace 0`` run of the same seed (the cost of
  tracing);
* ``program``: the benchmark's readers of the program's counters, the
  program-span metrics below, and the counters the window moved;
* with ``--profile 1``, under a ``jax.profiler`` capture of the window:
  ``breakdown`` — the harness's ``device_ops``, the device's idle time
  split among the program spans open in it (:func:`idle_by_span`), the
  share of idle time a program span labels, and how many of each
  program span the profiler's host plane holds (the
  ``TraceAnnotation`` mirror).

No correctness check runs: ``run.py`` is the benchmark.  Like it, this
needs the chip and exits 2 without one.
"""
from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: the prefixes of the program's spans that label idle time
PROGRAM_PREFIXES = ("serve.", "live.", "jax.")


# ---------------------------------------------------------------------------
# metrics of the program's spans (records of ``repro.obs.trace``)
# ---------------------------------------------------------------------------


def spans(records, name: str) -> list:
    return [r for r in records if r.kind == "span" and r.name == name]


def mean_ms(records, name: str) -> float | None:
    """Mean length of the spans ``name``, in ms."""
    got = spans(records, name)
    if not got:
        return None
    return sum(r.end_ns - r.start_ns for r in got) / len(got) / 1e6


def host_ms(records) -> float | None:
    """Mean over ``serve.flush`` of its length minus its ``serve.wait``
    child: the flush's time on the host."""
    flushes = spans(records, "serve.flush")
    if not flushes:
        return None
    wait = {r.parent: r.end_ns - r.start_ns for r in spans(records, "serve.wait")}
    return sum(r.end_ns - r.start_ns - wait.get(r.id, 0)
               for r in flushes) / len(flushes) / 1e6


def offcpu_ms(records, name: str = "live.step") -> float | None:
    """Mean over the spans ``name`` of wall time minus the thread's CPU
    time: waiting for the interpreter lock, a copy, or a frozen host.
    Only a thread's outermost spans carry CPU time."""
    got = [r for r in spans(records, name) if r.cpu_start_ns is not None]
    if not got:
        return None
    return sum((r.end_ns - r.start_ns) - (r.cpu_end_ns - r.cpu_start_ns)
               for r in got) / len(got) / 1e6


def span_metrics(records) -> dict:
    """What a reader of each span metric would read, None where absent."""
    return {
        "score.flush_ms": mean_ms(records, "serve.flush"),
        "score.host_ms": host_ms(records),
        "score.device_wait_ms": mean_ms(records, "serve.wait"),
        "live.step_ms": mean_ms(records, "live.step"),
        "live.put_ms": mean_ms(records, "live.put"),
        "live.step_offcpu_ms": offcpu_ms(records),
    }


# ---------------------------------------------------------------------------
# idle time by program span
# ---------------------------------------------------------------------------


def _thread_pieces(recs) -> list[tuple[int, int, str]]:
    """One thread's timeline cut where a span opens or closes, each piece
    with the innermost span open in it (deepest, then latest)."""
    events = sorted([(r.start_ns, 1, r) for r in recs]
                    + [(r.end_ns, 0, r) for r in recs],
                    key=lambda e: (e[0], e[1]))
    open_: dict[int, object] = {}
    out, prev = [], None
    for t, opens, r in events:
        if open_ and t > prev:
            top = max(open_.values(), key=lambda o: (o.depth, o.start_ns))
            out.append((prev, t, top.name))
        if opens:
            open_[r.id] = r
        else:
            open_.pop(r.id, None)
        prev = t
    return out


def innermost(records) -> list[tuple[int, int, tuple[str, ...]]]:
    """The host timeline cut into pieces ``(start, end, names)``: ``names``
    holds, for each thread with a program span open, its innermost one."""
    by_tid = defaultdict(list)
    for r in records:
        if (r.kind == "span" and r.end_ns > r.start_ns
                and r.name.startswith(PROGRAM_PREFIXES)):
            by_tid[r.tid].append(r)
    events = []
    for tid, recs in by_tid.items():
        for a, b, name in _thread_pieces(recs):
            events += [(a, 1, tid, name), (b, 0, tid, name)]
    events.sort(key=lambda e: (e[0], e[1]))
    active: dict[int, str] = {}
    out, prev = [], None
    for t, opens, tid, name in events:
        if active and t > prev:
            out.append((prev, t, tuple(sorted(active.values()))))
        if opens:
            active[tid] = name
        elif active.get(tid) == name:
            del active[tid]
        prev = t
    return out


def idle_by_span(tr, bench_spans, records, top: int = 10) -> dict:
    """Each idle gap of the device in the window, split by instant among
    the innermost program spans open then (evenly between the threads
    that have one); time with no program span open keeps the label
    ``trace_reduce.reduce`` gives the gap.  ``bench_spans`` and
    ``records`` are on one host clock (``perf_counter_ns``), aligned to
    the trace by the window span both the trace and ``bench_spans`` hold.
    """
    from chipbench import trace_reduce as T

    lo, hi = T.window(tr)
    bench = T.aligned(tr, bench_spans)
    (w0,) = [a for n, a, _ in bench_spans if n == T.WINDOW_SPAN]
    shift = lo - w0
    label = T._Labeller([s for s in bench if s[2] > lo and s[1] < hi])
    pieces = [(a + shift, b + shift, names)
              for a, b, names in innermost(records)]
    starts = [p[0] for p in pieces]
    seconds: dict[str, float] = defaultdict(float)
    count: dict[str, int] = defaultdict(int)
    by_program = idle = 0.0
    for plane_ops in tr.ops.values():
        inside = [o for o in plane_ops if o.end > lo and o.start < hi]
        merged = T._union(((o.start, o.end) for o in inside), lo, hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            got: dict[str, float] = defaultdict(float)
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(pieces) and pieces[i][0] < g1:
                a, b, names = pieces[i]
                part = min(b, g1) - max(a, g0)
                if part > 0:
                    for n in names:
                        got[n] += part / len(names)
                i += 1
            covered = sum(got.values())
            if g1 - g0 - covered > 0:
                got[label(g0, g1)] += g1 - g0 - covered
            for n, ns in got.items():
                seconds[n] += ns * 1e-9
                count[n] += 1
            by_program += covered * 1e-9
            idle += (g1 - g0) * 1e-9
    n_planes = len(tr.ops)
    return {
        "idle_gaps": sorted(([f"{k} x{count[k]}", v / n_planes]
                             for k, v in seconds.items()),
                            key=lambda kv: -kv[1])[:top],
        "program_share": by_program / idle if idle else None,
    }


def annotations(path: str) -> dict[str, int]:
    """How many events of each program span the profiler's host plane holds."""
    from jax.profiler import ProfileData

    got: dict[str, int] = defaultdict(int)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        got[e.name] += 1
    return dict(sorted(got.items()))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(cell, seed: int, seconds: float, *, profile: bool,
        kernel: str) -> dict:
    import importlib
    import shutil

    from chipbench import harness, trace_reduce
    from repro.obs import trace

    traffic = importlib.import_module(f"chipbench.kinds.{cell.kind}")
    spans = harness.Spans(profile)
    ctx = harness.Context(cell, seed, seconds, kernel, spans)
    state = traffic.setup(ctx)
    harness.settle()
    at_window = harness.counters()
    tdir = harness._start_trace() if profile else None
    try:
        with spans.span(harness.WINDOW_SPAN):
            trace.enable()
            try:
                window = traffic.window(ctx, state)
            finally:
                trace.disable()
    finally:
        if tdir is not None:
            import jax

            jax.profiler.stop_trace()
    records = trace.records()
    moved = {k: v - at_window.get(k, 0) for k, v in harness.counters().items()
             if v != at_window.get(k, 0)}
    latency = window.get("openloop", {}).get("latency_ms")
    counted = harness.Run(cell, spans, moved, window, None, "", None)
    program = {name: harness._reader(name)(counted)
               for name in ("score.queue_wait_ms", "jit.compiles")}
    out = {"workload": cell.name, "seed": seed,
           "end_to_end": window["metrics"],
           "score_mean_ms": (float(latency.mean()) if latency is not None
                             and len(latency) else None),
           "failed": window["failed"],
           "attempted": window["attempted"],
           "program": {**program, **span_metrics(records)},
           "records": len(records),
           "counters": {k: v for k, v in moved.items()
                        if k.startswith(("serve.", "live.", "jax."))}}
    if tdir is not None:
        try:
            path = str(next(Path(tdir).rglob("*.xplane.pb")))
            tr = trace_reduce.load(path)
            reduced = trace_reduce.reduce(tr, spans=spans.records)
            out["breakdown"] = {
                "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
                "device_ops": reduced["device_ops"],
                "bench_idle_gaps": reduced["idle_gaps"],
                **idle_by_span(tr, spans.records, records),
                "annotations": annotations(path)}
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = p.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    harness.compile_cache()
    import jax

    cell = harness.load_cell(args.workload)
    if jax.default_backend() != "tpu" or len(jax.devices()) < cell.chips:
        print(f"program_trace: {args.workload} needs {cell.chips} TPU "
              f"chip(s)", file=sys.stderr)
        return 2
    from repro.utils import compile_cache

    compile_cache.enable()
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    out = run(cell, args.seed, args.seconds, profile=bool(args.profile),
              kernel="pallas-tpu")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
