"""Reduce a ``jax.profiler`` trace of one run to the benchmark's numbers.

What a TPU trace holds (read by hand from a trace of the GLM path):

* one plane per chip, ``/device:TPU:<i>``, with a line ``XLA Modules``
  (one event per program launch, named ``jit_<fn>(<hash>)``) and a line
  ``XLA Ops`` (one event per HLO instruction that ran, named by its HLO
  text, ``%<instr> = <shape> <opcode>(...)``).  A Pallas kernel is an op
  whose text holds ``custom_call_target="tpu_custom_call"``; the kernels
  carry no name of their own, so a kernel is told apart by the program
  it runs in;
* the plane ``/host:CPU``, whose lines are host threads.  The
  benchmark's own spans (``jax.profiler.TraceAnnotation``, names starting
  ``bench.``) are events there, on the same clock as the device events
  to within about a millisecond.

All times in the trace are nanoseconds; everything returned is seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Op:
    name: str        # "<program>/<instruction>", e.g. "jit_epoch/_pallas.1"
    program: str     # "jit_epoch"
    start: float     # ns
    end: float       # ns
    kernel: bool     # a Pallas (Mosaic) kernel


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Op]]                  # device plane -> ops
    spans: list[tuple[str, float, float]]     # host spans (name, start, end)


def _program(name: str) -> str:
    return name.split("(", 1)[0]


def _instruction(text: str) -> str:
    m = re.match(r"%?([^\s=]+)", text)
    return m.group(1) if m else text[:40]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[str, list[Op]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((e.start_ns, e.end_ns, _program(e.name))
                             for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in modules]
            out = []
            for e in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                prog = modules[i][2] if i >= 0 and e.start_ns <= modules[i][1] \
                    else "?"
                out.append(Op(f"{prog}/{_instruction(e.name)}", prog,
                              e.start_ns, e.end_ns, KERNEL_TARGET in e.name))
            ops[plane.name] = sorted(out, key=lambda o: o.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    if not ops:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    return Trace(ops, sorted(spans, key=lambda s: s[1]))


def window(trace: Trace) -> tuple[float, float]:
    """The measured window: the first ``bench.window`` span."""
    for name, t0, t1 in trace.spans:
        if name == WINDOW_SPAN:
            return t0, t1
    raise ValueError("the trace holds no bench.window span")


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _Labeller:
    """Finds the benchmark span that overlaps an idle gap most."""

    def __init__(self, spans):
        self.spans = [s for s in spans if s[0] != WINDOW_SPAN]
        self.starts = [s[1] for s in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0.0)

    def __call__(self, g0: float, g1: float) -> str:
        best, best_overlap = "none", 0.0
        i = bisect.bisect_left(self.starts, g1) - 1
        while i >= 0 and self.starts[i] >= g0 - self.longest:
            name, s, e = self.spans[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
            i -= 1
        return best


def aligned(trace: Trace, spans) -> list[tuple[str, float, float]]:
    """Spans kept on another host clock, moved onto the trace's clock by
    the window span that both hold."""
    start = [t0 for name, t0, _ in spans if name == WINDOW_SPAN]
    if not start:
        raise ValueError("the spans hold no bench.window span")
    shift = window(trace)[0] - start[0]
    return sorted(((n, a + shift, b + shift) for n, a, b in spans),
                  key=lambda s: s[1])


def reduce(trace: Trace, spans=None, top: int = 10) -> dict:
    """Busy and idle time over the window, device time per op and per
    kernel, and idle time by what the host was doing.

    ``busy_s`` is the union of the intervals in which an op ran, averaged
    over the device planes; ``idle_share`` is ``1 - busy_s / window_s``.
    ``kernels`` maps a kernel's ``<program>/<instruction>`` name to
    ``[launches, seconds]``.  Idle time is labelled by ``spans``
    (``(name, start_ns, end_ns)`` on any host clock that also holds the
    window span), or else by the ``bench.`` spans in the trace itself.
    """
    lo, hi = window(trace)
    if spans is not None:
        trace = Trace(trace.ops, aligned(trace, spans))
    window_s = (hi - lo) * 1e-9
    busy = []
    per_op: dict[str, float] = defaultdict(float)
    kernels: dict[str, list] = {}
    gaps: dict[str, float] = defaultdict(float)
    gap_count: dict[str, int] = defaultdict(int)
    label = _Labeller([s for s in trace.spans if s[2] > lo and s[1] < hi])
    for plane_ops in trace.ops.values():
        inside = [o for o in plane_ops if o.end > lo and o.start < hi]
        merged = _union(((o.start, o.end) for o in inside), lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for o in inside:
            dur = (min(o.end, hi) - max(o.start, lo)) * 1e-9
            per_op[o.name] += dur
            if o.kernel:
                k = kernels.setdefault(o.name, [0, 0.0])
                k[0] += 1
                k[1] += dur
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                what = label(g0, g1)
                gaps[what] += (g1 - g0) * 1e-9
                gap_count[what] += 1
    busy_s = sum(busy) / len(busy)
    n_planes = len(trace.ops)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernels": kernels,
        "device_ops": sorted(([k, v / n_planes] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([f"{k} x{gap_count[k]}", v / n_planes]
                             for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
