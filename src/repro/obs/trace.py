"""Structured span tracer — one ``None`` check per span unless enabled.

Usage::

    from repro.obs import trace

    with trace.span("runner.trial", key=t.key):
        ...

    @trace.span("study.tune")
    def tune(...): ...

Disabled (the default), ``trace.span`` returns a shared no-op context
manager after a single ``None`` check — no allocation beyond the kwargs
dict at the call site, no I/O, no interaction with jit tracing (spans
are pure host-side bookkeeping, so a jitted function lowers identically
with tracing on or off; tests assert this).

Enabled, each closed span appends one :class:`Record` to an in-memory
process buffer: its name, start and end on ``perf_counter_ns``, for a
thread's outermost span the thread's CPU time (``thread_time_ns``) at
entry and exit, thread id, nesting depth, its own id and its parent's,
and its attrs.  Each span
also enters ``jax.profiler.TraceAnnotation(name)``, so any profiler
capture — a benchmark's traced window, ``REPRO_TRACE_XPROF``, an
operator's TensorBoard session — shows the program's spans on the
device timeline.  Two switches turn it on:

* ``trace.enable()`` / ``trace.disable()`` — in-process: the spans stay
  in memory (``trace.records()``) and no file is written;
* ``REPRO_TRACE=1`` — the operator's switch: the buffer is also
  exported to ``$REPRO_TRACE_DIR/trace-<tag>-<pid>.jsonl`` (dir default
  ``trace/``) at :func:`flush` and at exit.  ``tag`` comes from
  ``$REPRO_TRACE_TAG`` ("main" when unset); the sweep executor sets it
  per worker subprocess (``shard<W>a<A>``) so a multi-worker run yields
  one file per shard attempt and the report CLI stitches them into one
  timeline.  Call sites that mark durability points (the sweep worker
  after every stack group, the health monitor on a breach) call
  :func:`flush`.

While tracing is on, every backend compile JAX reports moves counter
``jax.compiles`` and records a ``jax.compile`` span (attr ``fun_name``:
what compiled) that ends at the report and lasts as long as the compile.
:func:`count_compiles` turns the counter on without the spans.

File format (``TRACE_SCHEMA``):

* line 1 — meta: ``{"kind": "meta", "schema": 1, "pid", "tag",
  "t0_unix_ns", "t0_perf_ns", "argv"}``.  The two anchors let the
  exporter align per-process monotonic clocks onto one wall-clock
  timeline (``unix_ns = t0_unix_ns + (ts - t0_perf_ns)``).
* span lines — ``{"kind": "span", "name", "ts", "dur" (both
  perf_counter_ns), "pid", "tid", "depth", "args"}``.  ``depth`` is the
  thread-local nesting level at entry; spans are recorded at *exit*.
* instant lines — ``{"kind": "instant", ...}`` with ``dur`` 0.

``REPRO_TRACE_XPROF=<pattern>`` additionally wraps the first trial whose
label matches the pattern (``1`` matches any) in a ``jax.profiler``
capture under ``$REPRO_TRACE_DIR/xprof`` — see :func:`xprof`.
"""
from __future__ import annotations

import atexit
import functools
import itertools
import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import NamedTuple

#: bump when the trace line format changes incompatibly
TRACE_SCHEMA = 1

ENV_TRACE = "REPRO_TRACE"
ENV_TRACE_DIR = "REPRO_TRACE_DIR"
ENV_TRACE_TAG = "REPRO_TRACE_TAG"
ENV_XPROF = "REPRO_TRACE_XPROF"

DEFAULT_TRACE_DIR = "trace"
DEFAULT_TAG = "main"

#: the ``jax.monitoring`` duration event of one backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_COUNTER = "jax.compiles"
COMPILE_SPAN = "jax.compile"


def trace_path(root: str | Path, tag: str, pid: int) -> Path:
    """The trace file a process with this (root, tag, pid) writes."""
    return Path(root) / f"trace-{tag}-{pid}.jsonl"


class Record(NamedTuple):
    """One closed span (or instant) as the buffer holds it."""

    name: str
    start_ns: int          # perf_counter_ns at entry
    end_ns: int            # perf_counter_ns at exit
    cpu_start_ns: int | None   # thread_time_ns at entry, outermost spans only
    cpu_end_ns: int | None     # thread_time_ns at exit (else both None)
    tid: int
    depth: int             # open spans on this thread at entry
    id: int
    parent: int            # id of the enclosing span on this thread, 0 at top
    attrs: dict
    kind: str = "span"     # or "instant"


class _NoopSpan:
    """Disabled-path singleton: no-op context manager AND decorator."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __call__(self, fn):
        return fn

    def set(self, **attrs) -> None:
        pass


NOOP = _NoopSpan()


class _Tracer:
    """One per process while tracing is on: the buffer, the clock
    anchors, per-thread nesting, and (``root`` given) the export file."""

    def __init__(self, root: str | Path | None, tag: str = DEFAULT_TAG):
        self.root = Path(root) if root is not None else None
        self.tag = tag
        self.pid = os.getpid()
        self.t0_unix_ns = time.time_ns()
        self.t0_perf_ns = time.perf_counter_ns()
        self.buffer: list[Record] = []
        self.ids = itertools.count(1)
        self.annotation = _profiler_annotation()
        self._fh = None
        self._lock = threading.Lock()
        self._tls = threading.local()
        if self.root is not None:
            atexit.register(self.close)

    @property
    def path(self) -> Path:
        return trace_path(self.root, self.tag, self.pid)

    def stack(self) -> list[int]:
        """Ids of the spans open on the calling thread, innermost last."""
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    # -- export -------------------------------------------------------------

    def _file(self):
        if self._fh is None:
            self.root.mkdir(parents=True, exist_ok=True)
            fh = open(self.path, "a")
            fh.write(json.dumps({
                "kind": "meta", "schema": TRACE_SCHEMA, "pid": self.pid,
                "tag": self.tag, "t0_unix_ns": self.t0_unix_ns,
                "t0_perf_ns": self.t0_perf_ns,
                "argv": sys.argv[:4],
            }, sort_keys=True) + "\n")
            self._fh = fh
        return self._fh

    def _line(self, r: Record) -> str:
        rec = {"kind": r.kind, "name": r.name, "ts": r.start_ns,
               "dur": r.end_ns - r.start_ns, "pid": self.pid, "tid": r.tid,
               "depth": r.depth}
        if r.attrs:
            rec["args"] = r.attrs
        return json.dumps(rec, sort_keys=True, default=str) + "\n"

    def export(self) -> Path | None:
        """Write the buffered records to the file and drop them from the
        buffer.  No-op without a ``root``, and in a forked child (the
        buffer it inherited belongs to its parent's file)."""
        if self.root is None or os.getpid() != self.pid:
            return None
        with self._lock:
            n = len(self.buffer)
            if n:
                fh = self._file()
                fh.writelines(self._line(r) for r in self.buffer[:n])
                fh.flush()
                del self.buffer[:n]
        return self.path

    def close(self) -> None:
        try:
            self.export()
        except OSError:
            pass  # never let telemetry turn a clean exit into a traceback
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


class _Span:
    """Enabled-path span: times the ``with`` body, records at exit."""

    __slots__ = ("_t", "name", "attrs", "_stack", "_id", "_parent", "_depth",
                 "_t0", "_c0", "_ann")

    def __init__(self, tracer: _Tracer, name: str, attrs: dict):
        self._t = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Add attrs known only once the span is open."""
        self.attrs.update(attrs)

    def __enter__(self):
        t = self._t
        self._stack = stack = t.stack()
        self._depth = len(stack)
        self._parent = stack[-1] if stack else 0
        self._id = next(t.ids)
        stack.append(self._id)
        self._ann = None
        if t.annotation is not None:
            self._ann = t.annotation(self.name)
            self._ann.__enter__()
        # the thread CPU clock is a system call (about 6 us on a TPU VM's
        # host): read it for a thread's outermost span only
        self._c0 = time.thread_time_ns() if not self._depth else None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, *exc):
        t1 = time.perf_counter_ns()
        c1 = time.thread_time_ns() if self._c0 is not None else None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._stack.pop()
        attrs = self.attrs
        if exc_type is not None:
            attrs = {**attrs, "error": exc_type.__name__}
        self._t.buffer.append(Record(
            self.name, self._t0, t1, self._c0, c1, threading.get_ident(),
            self._depth, self._id, self._parent, attrs))
        return False

    def __call__(self, fn):
        name, attrs = self.name, self.attrs

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with span(name, **attrs):
                return fn(*a, **k)

        return wrapped


_TRACER: _Tracer | None = None
#: the tracer :func:`disable` turned off, so :func:`records` still reads it
_LAST: _Tracer | None = None


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation``, with the compile counter turned
    on; imported lazily so ``repro.obs`` imports without JAX (None
    there)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    count_compiles()
    return TraceAnnotation


def _install(tracer: _Tracer | None) -> None:
    global _TRACER, _LAST
    cur = _TRACER
    if cur is not None and cur is not tracer:
        cur.close()
        _LAST = cur
    _TRACER = tracer


def refresh() -> None:
    """(Re-)read the ``REPRO_TRACE*`` env vars and swap the tracer.

    Processes pick the config up at import; tests (and anything that
    mutates the env mid-process) call this to apply a change.
    """
    if os.environ.get(ENV_TRACE) == "1":
        root = os.environ.get(ENV_TRACE_DIR) or DEFAULT_TRACE_DIR
        tag = os.environ.get(ENV_TRACE_TAG) or DEFAULT_TAG
        cur = _TRACER
        if (cur is None or cur.root is None or cur.pid != os.getpid()
                or (str(cur.root), cur.tag) != (str(Path(root)), tag)):
            _install(_Tracer(root, tag))
    else:
        _install(None)


def enable() -> None:
    """Record spans in memory from now on, into a fresh buffer, and write
    no file.  Already on (``REPRO_TRACE=1`` or an earlier ``enable``),
    this changes nothing."""
    if _TRACER is None:
        _install(_Tracer(None))


def disable() -> None:
    """Stop recording; an exporting tracer writes what it holds first.
    :func:`records` still returns what was recorded."""
    _install(None)


def records() -> list[Record]:
    """The records of the current tracer, or of the last one turned off:
    every span closed since :func:`enable`, or since the last export under
    ``REPRO_TRACE=1``.  Children come before their parents."""
    t = _TRACER or _LAST
    return list(t.buffer) if t is not None else []


def flush() -> Path | None:
    """Durability point: under ``REPRO_TRACE=1`` write the buffered spans
    to this process's trace file (returned); otherwise do nothing.  Never
    raises."""
    t = _TRACER
    if t is None:
        return None
    try:
        return t.export()
    except OSError:
        return None


def enabled() -> bool:
    return _TRACER is not None


def current_path() -> Path | None:
    """This process's trace file (None unless exporting)."""
    t = _TRACER
    return t.path if t is not None and t.root is not None else None


def current_dir() -> Path | None:
    """The export directory (None unless exporting)."""
    return _TRACER.root if _TRACER is not None else None


def span(name: str, **attrs):
    """A span named ``name`` — context manager or decorator.

    Disabled, returns the shared no-op immediately (the fast path the
    overhead test gates).  ``attrs`` must be JSON-friendly scalars;
    anything else is stringified on export.  ``.set(**attrs)`` on the
    entered span adds attrs known only inside it.
    """
    t = _TRACER
    if t is None:
        return NOOP
    return _Span(t, name, attrs)


def instant(name: str, **attrs) -> None:
    """A zero-duration marker event (rendered as an arrow in Perfetto)."""
    t = _TRACER
    if t is None:
        return
    stack = t.stack()
    now = time.perf_counter_ns()
    t.buffer.append(Record(name, now, now, None, None, threading.get_ident(),
                           len(stack), next(t.ids),
                           stack[-1] if stack else 0, attrs, "instant"))


# ---------------------------------------------------------------------------
# Compiles (jax.monitoring)
# ---------------------------------------------------------------------------

_compiles_watched = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    from repro.obs import metrics

    metrics.counter(COMPILE_COUNTER).inc()
    t = _TRACER
    if t is None:
        return
    end = time.perf_counter_ns()
    stack = t.stack()
    t.buffer.append(Record(
        COMPILE_SPAN, end - int(duration_secs * 1e9), end, None, None,
        threading.get_ident(), len(stack), next(t.ids),
        stack[-1] if stack else 0,
        {"fun_name": str(kwargs.get("fun_name", "?"))}))


def count_compiles() -> None:
    """Count every backend compile JAX reports in ``jax.compiles`` (made
    at 0 here, so a reader can tell no compiles from no counter); while
    tracing is on each also records a ``jax.compile`` span.  Registers
    one ``jax.monitoring`` duration listener per process; the listener's
    time source is the compile's duration, which this module places on
    ``perf_counter_ns``."""
    global _compiles_watched
    if _compiles_watched:
        return
    import jax.monitoring

    from repro.obs import metrics

    metrics.counter(COMPILE_COUNTER)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _compiles_watched = True


# ---------------------------------------------------------------------------
# Optional jax.profiler capture (REPRO_TRACE_XPROF)
# ---------------------------------------------------------------------------

_xprof_captured = False


@contextmanager
def xprof(label: str):
    """Capture a ``jax.profiler`` trace around the first matching trial.

    Active only when ``REPRO_TRACE_XPROF`` is set: the value ``1``
    matches any label, anything else matches as a substring.  At most
    one capture per process (profiler sessions do not nest), written to
    ``<trace dir>/xprof``.  Profiler failures degrade to a plain pass-
    through — telemetry must never take a trial down.
    """
    global _xprof_captured
    pattern = os.environ.get(ENV_XPROF)
    if (not pattern or _xprof_captured
            or (pattern != "1" and pattern not in label)):
        yield
        return
    _xprof_captured = True
    root = current_dir() or Path(os.environ.get(ENV_TRACE_DIR)
                                 or DEFAULT_TRACE_DIR)
    sess = None
    try:
        import jax
        sess = jax.profiler.trace(str(root / "xprof"))
        sess.__enter__()
    except Exception:
        sess = None
    try:
        with span("obs.xprof", label=label):
            yield
    finally:
        if sess is not None:
            try:
                sess.__exit__(None, None, None)
            except Exception:
                pass


refresh()   # pick up REPRO_TRACE at import
